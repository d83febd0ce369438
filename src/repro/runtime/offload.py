"""Host page offload: swap a request's pages to host memory and back.

Preemption support for the serving engine (``runtime/engine.py``).  A
preempted request's device pages — raw K/V *and* the per-page Stem
selection summaries (``kg``/``vm``) and, implicitly, its cursor state held
by the engine — are gathered into a snapshot, copied to host numpy
buffers, and the device pages are returned to the ``PageAllocator``
free list (``allocator.evict``).  Re-admission allocates a fresh set of
physical pages (``allocator.restore``) and scatters the snapshot back
bit-identically; because a page carries its own OAM/SAM summaries, the
restored request resumes decode (or mid-prefill chunking) with **zero
recompute** — no prefill replay, no summary rebuild, no extra traces.

Both ``gather_pages`` and ``scatter_pages`` operate on the engine's
per-layer pool tree (``PagePool`` or ``LatentPool`` leaves stacked
``(n_layers, hk, P, ...)``) with a fixed-width ``(max_pages_per_slot,)`` page-id row padded with the
trash page, so the engine jits each exactly once
(``launch/steps.make_page_extract`` / ``make_page_restore``).  Padding
slots gather/scatter the trash page, which holds garbage by design.

Prefix caching changes WHAT is snapshotted, not how: a preempted request's
prefix-SHARED pages are never gathered or scattered — their contents stay
on the device (co-tenants may be reading them) and the engine keeps one
pinned allocator reference per shared page for the duration of the
offload.  Only the privately-held suffix/decode pages round-trip through
this store; ``put(..., pinned=...)`` records the pinned ids per request so
the residency accounting stays honest.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def gather_pages(pools, page_row):
    """Extract the pages named by ``page_row`` from every layer's pool.

    pools: the engine pool tree, PagePool or LatentPool leaves stacked
    (n, hk, P, ...): every leaf holds its pages on axis 2.
    page_row: (max_pages_per_slot,) int32 global page ids, trash-padded.
    Returns the same tree shape with the page axis narrowed to the row
    width — the device-side snapshot (copy to host with ``to_host``).
    """
    return jax.tree.map(lambda leaf: leaf[:, :, page_row], pools)


def scatter_pages(pools, page_row, snapshot):
    """Write a snapshot back into the pages named by ``page_row``.

    Exact inverse of ``gather_pages`` modulo page renaming: the snapshot's
    i-th page lands in ``page_row[i]``, which need not be the page it was
    gathered from — the engine's page-table row carries the new mapping.
    Trash-padding slots rewrite page 0 (garbage by design, harmless).
    """
    return jax.tree.map(lambda leaf, snap: leaf.at[:, :, page_row].set(snap),
                        pools, snapshot)


def snapshot_nbytes(snapshot) -> int:
    total = 0
    for leaf in jax.tree.leaves(snapshot):
        if isinstance(leaf, HostShards):
            total += leaf.nbytes
        else:
            total += int(np.asarray(leaf).nbytes)
    return total


# ---------------------------------------------------------------------------
# Mesh-sharded snapshots (sharding/serving.py engines)
# ---------------------------------------------------------------------------

class HostShards:
    """One sharded device array as per-shard host buffers keyed by mesh
    coordinate ``(dp, tp)``.  Under tp>1 a snapshot leaf's KV-head axis is
    split across devices; copying each shard's bytes verbatim and putting
    them back on the device at the *same* mesh coordinate makes the
    preempt/restore round-trip bit-identical with no gather/reshard on
    either side.  Only the preempted slot group's shards are stored —
    other groups' rows in the fixed-width extract are trash-page garbage.
    """

    __slots__ = ("shards", "shape", "dtype")

    def __init__(self, shards: dict, shape, dtype):
        self.shards = shards            # (dp, tp) coord -> np.ndarray
        self.shape = tuple(shape)       # global (all-groups) shape
        self.dtype = dtype

    @property
    def nbytes(self) -> int:
        return sum(int(s.nbytes) for s in self.shards.values())


def _mesh_coords(mesh) -> dict:
    """Device id -> (dp, tp) mesh coordinate."""
    return {dev.id: tuple(int(i) for i in idx)
            for idx, dev in np.ndenumerate(mesh.devices)}


def shard_snapshot_to_host(snapshot, smesh, group: int):
    """Copy a mesh-sharded snapshot to host, keeping only slot group
    ``group``'s shards.  Snapshot leaves are ``(dp, n, hk, W, ...)`` placed
    with the pool sharding (dp over groups, tp over KV heads); each leaf
    becomes a :class:`HostShards` holding the dp==group blocks per tp
    coordinate."""
    coords = _mesh_coords(smesh.mesh)

    def one(x):
        shards = {}
        for sh in x.addressable_shards:
            c = coords[sh.device.id]
            if c[0] != group:
                continue
            shards[c] = np.asarray(sh.data)
        return HostShards(shards, x.shape, x.dtype)

    return jax.tree.map(one, snapshot)


def assemble_sharded_snapshot(host, smesh, group: int):
    """Inverse of ``shard_snapshot_to_host``: rebuild device-sharded
    snapshot leaves with group ``group``'s bytes at their original tp
    coordinates and zeros elsewhere (other groups' rows scatter into their
    trash page — garbage by design)."""
    from repro.sharding import serving as serving_lib

    mesh = smesh.mesh
    sharding = jax.sharding.NamedSharding(mesh, serving_lib.POOL_SPEC)

    def one(hs: HostShards):
        sample = next(iter(hs.shards.values()))
        arrs = []
        for idx, dev in np.ndenumerate(mesh.devices):
            c = tuple(int(i) for i in idx)
            buf = hs.shards.get(c)
            if buf is None:
                buf = np.zeros(sample.shape, hs.dtype)
            arrs.append(jax.device_put(buf, dev))
        return jax.make_array_from_single_device_arrays(
            hs.shape, sharding, arrs)

    return jax.tree.map(
        one, host, is_leaf=lambda x: isinstance(x, HostShards))


class HostPageStore:
    """Host-side store of offloaded page snapshots, keyed by request uid.

    ``put`` forces the device snapshot onto the host (numpy) so the device
    pages can be reused immediately; ``pop`` hands the numpy tree back for
    the jitted scatter (shapes/dtypes are fixed, so restore never retraces).
    Tracks resident and peak bytes for the engine's metrics.
    """

    def __init__(self):
        self._store: dict = {}
        self._pinned: dict = {}      # uid -> device pages pinned, not copied
        self.nbytes = 0
        self.peak_nbytes = 0
        self.total_offloads = 0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, uid) -> bool:
        return uid in self._store

    def put(self, uid, snapshot, pinned=()) -> None:
        """Store a request's private-page snapshot.  ``pinned`` lists the
        prefix-shared device pages that stay resident on the device (the
        engine holds one allocator reference each) — recorded for
        observability, no bytes copied."""
        if uid in self._store:
            raise ValueError(f"request {uid} already offloaded")

        def to_host(x):
            if isinstance(x, HostShards):    # already host-resident shards
                return x
            return np.asarray(jax.device_get(x))

        host = jax.tree.map(to_host, snapshot,
                            is_leaf=lambda x: isinstance(x, HostShards))
        self._store[uid] = host
        self._pinned[uid] = list(pinned)
        self.nbytes += snapshot_nbytes(host)
        self.peak_nbytes = max(self.peak_nbytes, self.nbytes)
        self.total_offloads += 1

    def get(self, uid):
        return self._store[uid]

    def pinned(self, uid) -> list:
        """Device pages this offloaded request keeps pinned (shared prefix)."""
        return list(self._pinned.get(uid, ()))

    def pop(self, uid):
        snap = self._store.pop(uid)
        self._pinned.pop(uid, None)
        self.nbytes -= snapshot_nbytes(snap)
        return snap

    def drop(self, uid) -> None:
        """Discard a snapshot without restoring (aborted request)."""
        if uid in self._store:
            self.pop(uid)
