#!/usr/bin/env python
"""kernaudit: presto-tpu's jaxpr-level IR gate. Run before sending a PR
(tpulint checks the AST; this checks the IR XLA actually compiles).

Thin launcher over ``presto_tpu.audit.cli`` -- see that module for the
exit-code contract and DESIGN.md ("Kernel IR auditing") for the pass
catalog (K001-K005), suppression syntax (``# kernaudit: disable=K001``
on the source line an eqn traces to), and baseline policy
(``kernaudit_baseline.json``, committed empty -- fix, don't baseline).

    python scripts/kernaudit.py                  # TPC-H q1-q22 gate
    python scripts/kernaudit.py --json           # stable machine output
    python scripts/kernaudit.py --queries 1,6 --tier local
    python scripts/kernaudit.py --select K001 tests/fixtures/kernaudit/k001_bad.py
    python scripts/kernaudit.py --list-passes
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _cpu  # noqa: E402,F401  (the gate only traces: the shared CPU armor)

from presto_tpu.audit.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
