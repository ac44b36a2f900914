"""The resident tier: a memory table's staged columns kept in HBM by
table version.

A memory table's published arrays are replaced, never written, and
every write moves the table to a new version (connectors/memory.py). So
a column read, range-proved and put for version v is that column for
as long as the table stays at v. A whole-table scan of such a table
(`exec/runner._stage_resident`) takes from here the columns it finds
and stages only the others, which are kept for the next statement: the
worker's cache of hot columns (Velox's AsyncDataCache keeps them in the
worker's memory; here the worker's memory is the chip's).

* An entry is one staged column, keyed by its place (connector, table,
  version, capacity, sharding) and its (column, physical dtype asked
  for). Per place the tier keeps the `active` mask and the row count,
  so that counting a resident scan reads nothing back (`rows_of`).
* Its room is the caller's budget (`budget`: the statement's
  `hbm_budget_bytes` capped at the device's `bytes_limit`) less the
  largest program the process has planned (`note_program`, called
  before each dispatch), on the fullest chip. Each call that can grow
  the tier or the largest program trims to its own caller's room:
  whole (connector, table, version) groups go, least recently used
  first. Where no budget is known (the CPU backend reports none) the
  tier is not used at all.
* A table's move to a new version drops its older groups at once (the
  store's `on_publish`, told once the store's lock is released). A
  statement that still holds a dropped column keeps it until it lets
  its batches go.
* Where the statement has a `MemoryPool`, the tier's bytes are
  registered there as revocable, a registration for each group's
  columns kept under that pool: a query's reservation evicts them
  before it fails. Bytes are counted once: a statement leaves out of
  its own reservation what the tier has registered of its scans
  (`pooled_bytes`), and the columns it stages for the tier move from
  its reservation to the tier's registration (`keep`).

Lock order: the tier's lock is taken under no other of this package's
locks but the store's registration (`watch`), and a pool's lock is
taken only after the tier's is released.

Resident columns are scan leaves, which are never donated
(exec/donation.py): no program consumes one.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional

import numpy as np

__all__ = ["tier", "budget", "shard_key", "ResidentTier"]


def budget(hbm_budget_bytes=None) -> Optional[int]:
    """A caller's budget a chip: the statement's `hbm_budget_bytes`
    (a session value may come as text) capped at the device's own
    `bytes_limit`, else that limit; None where neither is known."""
    limit = _device_limit()
    if hbm_budget_bytes and int(hbm_budget_bytes) > 0:
        return min(int(hbm_budget_bytes), limit) if limit \
            else int(hbm_budget_bytes)
    return limit


@functools.lru_cache(maxsize=1)
def _device_limit() -> Optional[int]:
    """The first device's `bytes_limit`, read once: a constant of the
    process's chip (None where the backend reports no memory)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def shard_key(sharding) -> Optional[tuple]:
    """What a place keys of a scan's `sharding`: the devices in the
    mesh's order and the partition spec; None on one device."""
    if sharding is None:
        return None
    return (tuple(d.id for d in sharding.mesh.devices.flat),
            str(sharding.spec))


def _bytes_by_device(tree) -> Dict[int, int]:
    """Bytes each device holds of the arrays of `tree`."""
    import jax
    held: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shard = int(np.prod(leaf.sharding.shard_shape(leaf.shape))) \
            * leaf.dtype.itemsize
        for d in leaf.sharding.device_set:
            held[d.id] = held.get(d.id, 0) + shard
    return held


def _nbytes(tree) -> int:
    """Bytes the arrays of `tree` hold over all their devices."""
    return sum(_bytes_by_device(tree).values())


class _Group:
    """What the tier holds of one (connector, table, version)."""

    __slots__ = ("columns", "actives", "held", "used", "pooled")

    def __init__(self):
        self.columns: Dict[tuple, object] = {}  # (cap, shard, col, dt)
        self.actives: Dict[tuple, tuple] = {}   # (cap, shard) -> (a, rows)
        self.held: Dict[int, int] = {}          # device id -> bytes
        self.used = 0
        # (pool, registration, entry keys it covers); an `active`
        # mask's key is (cap, shard)
        self.pooled: List[tuple] = []


class ResidentTier:
    """The process's resident columns: one a process (`tier()`), as the
    chip's memory is the process's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._groups: Dict[tuple, _Group] = {}  # (conn, table, version)
        self._newest: Dict[tuple, int] = {}     # (conn, table) -> version
        self._rows: Dict[int, tuple] = {}       # id(active) -> (a, rows)
        self._watched: set = set()
        self._tick = 0
        self._largest_program = 0

    # -- a scan's side ----------------------------------------------------

    def take(self, place: tuple, wanted: List[tuple]):
        """The columns of `wanted` ((column, dtype) pairs) the tier holds
        at `place` (connector, table, version, capacity, shard), by pair,
        and that place's (active, rows), or None where it holds none."""
        conn, table, version, cap, shard = place
        with self._lock:
            group = self._groups.get((conn, table, version))
            if group is None:
                return {}, None
            self._tick += 1
            group.used = self._tick
            found = {w: group.columns[(cap, shard) + w] for w in wanted
                     if (cap, shard) + w in group.columns}
            return found, group.actives.get((cap, shard))

    def pooled_bytes(self, place: tuple, wanted: List[tuple], pool) -> int:
        """Bytes of a scan at `place` of the columns `wanted` (and its
        `active` mask) that the tier holds registered in `pool`: what
        the statement's own reservation leaves out."""
        conn, table, version, cap, shard = place
        with self._lock:
            group = self._groups.get((conn, table, version))
            if group is None:
                return 0
            keys = set()
            for p, _, covered in group.pooled:
                if p is pool:
                    keys |= covered
            arrays = [group.columns[(cap, shard) + w] for w in wanted
                      if (cap, shard) + w in keys]
            if (cap, shard) in keys:
                arrays.append(group.actives[(cap, shard)][0])
        return _nbytes(arrays)

    def keep(self, place: tuple, columns: Dict[tuple, object], active,
             rows: int, budget_bytes: int, pool=None,
             query_id: Optional[str] = None) -> None:
        """Keep what a scan staged at `place`: `columns` by (column,
        dtype), its `active` mask and row count; then evict, least
        recently used first, until the fullest chip holds no more than
        the room `budget_bytes` leaves. Nothing is kept of a version
        the store has moved past. With `pool`, what was kept is
        registered there, its bytes moved from `query_id`'s reservation
        (the statement that staged them reserved them)."""
        conn, table, version, cap, shard = place
        gkey = (conn, table, version)
        with self._lock:
            if self._newest.get((conn, table), version) > version:
                return
            group = self._groups.setdefault(gkey, _Group())
            self._tick += 1
            group.used = self._tick
            added = {}
            if (cap, shard) not in group.actives:
                group.actives[(cap, shard)] = (active, rows)
                self._rows[id(active)] = (active, rows)
                added[(cap, shard)] = active
            for w, block in columns.items():
                if (cap, shard) + w not in group.columns:
                    group.columns[(cap, shard) + w] = block
                    added[(cap, shard) + w] = block
            grown = _bytes_by_device(list(added.values()))
            for dev, n in grown.items():
                group.held[dev] = group.held.get(dev, 0) + n
            gone = self._trim_locked(budget_bytes)
        self._unpool(gone)
        if pool is not None and grown and gkey not in {g for g, _ in gone}:
            self._pool(pool, gkey, sum(grown.values()), set(added),
                       query_id)

    def rows_of(self, batch) -> Optional[int]:
        """A resident scan's row count, from its `active` mask's entry;
        None for a batch whose mask the tier does not hold."""
        hit = self._rows.get(id(batch.active))
        return hit[1] if hit is not None and hit[0] is batch.active \
            else None

    def held_bytes(self) -> int:
        """What the tier holds on its fullest chip."""
        with self._lock:
            return self._fullest_locked()

    # -- room -------------------------------------------------------------

    def note_program(self, nbytes: int, budget_bytes: Optional[int]) -> None:
        """A program about to be dispatched plans `nbytes` a chip: where
        it is the largest yet the room shrinks, and the tier is trimmed
        to the caller's `budget_bytes` before the program runs."""
        with self._lock:
            self._largest_program = max(self._largest_program, nbytes)
            gone = self._trim_locked(budget_bytes)
        self._unpool(gone)

    def _fullest_locked(self) -> int:
        held: Dict[int, int] = {}
        for group in self._groups.values():
            for dev, n in group.held.items():
                held[dev] = held.get(dev, 0) + n
        return max(held.values(), default=0)

    def _trim_locked(self, budget_bytes: Optional[int]) -> List[tuple]:
        if budget_bytes is None:  # no room known: nothing to trim to
            return []
        room = budget_bytes - self._largest_program
        gone = []
        while self._groups and self._fullest_locked() > room:
            gkey = min(self._groups, key=lambda k: self._groups[k].used)
            gone.append((gkey, self._drop_locked(gkey)))
        return gone

    # -- eviction ---------------------------------------------------------

    def _drop_locked(self, gkey: tuple) -> _Group:
        group = self._groups.pop(gkey)
        for active, _ in group.actives.values():
            self._rows.pop(id(active), None)
        return group

    def _unpool(self, gone: List[tuple]) -> None:
        for _, group in gone:
            for pool, rid, _ in group.pooled:
                pool.unregister_revocable(rid)

    def drop_older(self, conn: str, table: str, version: int) -> None:
        """The store moved `table` to `version`: its older groups go."""
        with self._lock:
            newest = max(self._newest.get((conn, table), 0), version)
            self._newest[(conn, table)] = newest
            gone = [(g, self._drop_locked(g)) for g in list(self._groups)
                    if g[:2] == (conn, table) and g[2] < newest]
        self._unpool(gone)

    def _revoked(self, gkey: tuple) -> int:
        """A pool's revocation: the group goes, with its other
        registrations (the revoked one the pool has forgotten)."""
        with self._lock:
            if gkey not in self._groups:
                return 0
            group = self._drop_locked(gkey)
        self._unpool([(gkey, group)])
        return sum(group.held.values())

    def _pool(self, pool, gkey: tuple, nbytes: int, keys: set,
              query_id: Optional[str]) -> None:
        from .memory import MemoryReservationError
        conn, table, version = gkey
        if query_id is not None:  # the statement's reservation had them
            pool.free(query_id, nbytes)
        try:
            rid = pool.register_revocable(
                f"resident:{conn}.{table}@{version}", nbytes,
                lambda: self._revoked(gkey))
        except MemoryReservationError:
            if query_id is not None:  # the statement holds them still
                pool.note_usage(query_id, nbytes)
            with self._lock:  # no room in the pool: the group is not kept
                gone = [(gkey, self._drop_locked(gkey))] \
                    if gkey in self._groups else []
            self._unpool(gone)
            return
        with self._lock:
            group = self._groups.get(gkey)
            if group is not None:
                group.pooled.append((pool, rid, keys))
                return
        pool.unregister_revocable(rid)  # dropped meanwhile

    def watch(self, conn: str, conn_module) -> None:
        """Follow the store's versions of catalog `conn` (`on_publish`),
        once: called before a scan's snapshot, so that no move past the
        version it read goes unseen. The store tells its listeners
        after it lets its lock go, so it never waits on the tier's."""
        with self._lock:
            if conn in self._watched:
                return
            conn_module.on_publish(
                lambda table, version: self.drop_older(conn, table, version))
            self._watched.add(conn)

    def clear(self) -> None:
        """Drop everything (a test's fresh start)."""
        with self._lock:
            gone = [(g, self._drop_locked(g)) for g in list(self._groups)]
            self._largest_program = 0
        self._unpool(gone)


_TIER = ResidentTier()


def tier() -> ResidentTier:
    return _TIER
