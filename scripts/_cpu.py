"""Import this FIRST in ad-hoc scripts to force CPU jax with 8 virtual
devices (the repo's conftest armor, shared). Tests and rehearsals run
here; the chip is reached only through chip_smoke.py / bench.py, which
never import this."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# the persistent-cache AOT loader logs a benign ERROR about the
# prefer-no-scatter/gather tuning pseudo-features on every load; keep
# the test tier readable (override via TF_CPP_MIN_LOG_LEVEL)
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (os.environ["XLA_FLAGS"]
                               + " --xla_force_host_platform_device_count=8").strip()
import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache (works on the CPU backend too): the
# suite's cost on a 1-core runner is almost entirely compiles, so warm
# reruns of the verifier/TPC-DS tiers drop from minutes to seconds.
# Placed by the engine's own function, where every entry point places it.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from presto_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache()
