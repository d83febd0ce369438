"""Mesh-sharded serving: tensor-parallel page pools + data-parallel slot groups.

The serving mesh is ``(dp, tp)``:

* **tp** shards every ``PagePool`` leaf over the KV-head axis.  Stem
  selection is already per KV head (the GQA dedup fetches one K/V page
  set per KV head), so scoring and attention run shard-local on
  ``hk // tp`` heads with no cross-device math.  The only collective in
  the whole step is one ``all_gather`` of the per-head attention outputs
  right before the output projection — psum-free, so the sharded step is
  **bitwise identical** to the single-device step.
* **dp** adds a leading *slot-group* axis to the pools and to every
  host-side batch array.  One engine instance drives ``dp`` independent
  slot groups (each with its own ``PageAllocator`` and page table)
  through the same two compiled traces; the host scheduler partitions
  its token budget per group.

Page tables, selections, and live counts stay replicated host-side: they
are tiny int32 arrays, and keeping them replicated means the scheduler
needs no device round-trips to make decisions (no per-step host syncs
beyond the two logits fetches the single-device engine already does).

The TP head slicing is threaded into ``models/attention.py`` via a
threadlocal *head-sharding context* (:func:`head_sharding`) that the
shard-mapped unified step activates during tracing.  Outside the
context, :func:`local_heads` / :func:`gather_heads` are identity — the
single-device path is untouched.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
TP_AXIS = "tp"

# PagePool leaves are stacked ``(n_layers, hk, num_pages, ...)`` and gain a
# leading slot-group axis under the mesh: ``(dp, n_layers, hk, ...)``.  A
# PartitionSpec is a *prefix* spec, so one spec covers every leaf rank
# (k/v are rank 6, kg rank 6, vm rank 4).
POOL_SPEC = P(DP_AXIS, None, TP_AXIS)
# Host-side batch arrays carry the slot-group axis first: (dp, ...).
GROUP_SPEC = P(DP_AXIS)
# Parameters are replicated — full projections run on every shard so the
# head slicing commutes bitwise with the single-device computation.
REPLICATED = P()


@dataclass(frozen=True)
class ServingMesh:
    """A ``(dp, tp)`` serving mesh plus its JAX mesh object."""
    dp: int
    tp: int
    mesh: Mesh

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp


def make_serving_mesh(dp: int, tp: int, devices=None) -> ServingMesh:
    """Build a ``(dp, tp)`` mesh from the first ``dp*tp`` devices.

    ``jax.make_mesh`` grabs *all* devices; serving meshes are often a
    subset (e.g. dp=2,tp=1 on an 8-device host), so build the Mesh
    explicitly."""
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp}, tp={tp}")
    devices = list(devices if devices is not None else jax.devices())
    need = dp * tp
    if len(devices) < need:
        raise ValueError(
            f"mesh ({dp},{tp}) needs {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(dp, tp)
    return ServingMesh(dp=dp, tp=tp, mesh=Mesh(grid, (DP_AXIS, TP_AXIS)))


def validate_serving(cfg, executor: Optional[str], smesh: ServingMesh) -> None:
    """Check the model + executor against the mesh's sharding contract."""
    if smesh.tp > 1 and cfg.num_kv_heads % smesh.tp != 0:
        raise ValueError(
            f"tp={smesh.tp} must divide num_kv_heads={cfg.num_kv_heads}")
    if smesh.tp > 1 and cfg.num_heads % smesh.tp != 0:
        raise ValueError(
            f"tp={smesh.tp} must divide num_heads={cfg.num_heads}")
    if smesh.tp > 1 and executor is not None:
        from repro.core import policy as policy_lib
        spec = policy_lib.get_paged_executor(executor)
        if spec.sharding != "kv-head":
            raise ValueError(
                f"executor {executor!r} declares sharding="
                f"{spec.sharding!r}; tp>1 requires 'kv-head'")


def pool_sharding(smesh: ServingMesh) -> NamedSharding:
    return NamedSharding(smesh.mesh, POOL_SPEC)


def group_sharding(smesh: ServingMesh) -> NamedSharding:
    """Sharding for per-slot-group host arrays — leading ``(dp,)`` axis
    split over data-parallel groups, everything else replicated.  The
    async engine's device-resident fed-back-token buffer lives here:
    each group's decode lanes read their own sampled ids locally, so the
    per-step logits all-gather is replaced by a ``(dp, S) int32`` fetch."""
    return NamedSharding(smesh.mesh, GROUP_SPEC)


def shard_pools(pools, smesh: ServingMesh):
    """Broadcast freshly-initialised pools to ``(dp,)+shape`` and place
    them: dp slot groups each get a full pool copy, KV-head axis sharded
    over tp.  All groups start from the same pristine pool, so group 0 of
    a dp>1 engine is bit-identical to a single-device pool."""
    from repro.runtime import paged as paged_lib

    latent = any(isinstance(p, paged_lib.LatentPool)
                 for p in jax.tree.leaves(pools, is_leaf=paged_lib.is_pool))
    if latent and smesh.tp > 1:
        raise NotImplementedError(
            f"latent (MLA) page pools hold one latent head per token and "
            f"cannot be split over tp={smesh.tp}; serve MLA with tp=1 "
            "(dp slot groups only)")
    sh = pool_sharding(smesh)

    def place(leaf):
        grouped = jnp.broadcast_to(leaf, (smesh.dp,) + leaf.shape)
        return jax.device_put(grouped, sh)

    return jax.tree.map(place, pools)


# ---------------------------------------------------------------------------
# Head-sharding context (consumed by models/attention.py)
# ---------------------------------------------------------------------------

_TP_CTX = threading.local()


@contextlib.contextmanager
def head_sharding(tp: int):
    """Activate TP head slicing for code traced inside this context.

    The shard-mapped unified step wraps its trace in this context so
    ``apply_decode_paged`` / ``apply_chunk_paged`` slice their local
    heads and all-gather the attention output.  tp<=1 keeps the helpers
    as identity."""
    prev = getattr(_TP_CTX, "tp", None)
    _TP_CTX.tp = tp if tp and tp > 1 else None
    try:
        yield
    finally:
        _TP_CTX.tp = prev


def active_tp() -> Optional[int]:
    return getattr(_TP_CTX, "tp", None)


def local_heads(x: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
    """Slice this shard's contiguous head block out of a full-head tensor.

    Inside the head-sharding context the full projections are computed
    replicated (bitwise equal on every shard); each shard then keeps
    heads ``[rank*h_loc, (rank+1)*h_loc)``.  Slicing whole KV-head groups
    keeps the GQA group_reduce intact.  No-op outside the context."""
    tp = active_tp()
    if tp is None:
        return x
    h = x.shape[axis]
    h_loc = h // tp
    start = jax.lax.axis_index(TP_AXIS) * h_loc
    return jax.lax.dynamic_slice_in_dim(x, start, h_loc, axis)


def gather_heads(x: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
    """Reassemble per-shard head blocks into the full head axis.

    ``tiled=True`` concatenates along ``axis`` in rank order — the exact
    inverse of :func:`local_heads` — so the output projection sees the
    same operand it would single-device.  No-op outside the context."""
    tp = active_tp()
    if tp is None:
        return x
    return jax.lax.all_gather(x, TP_AXIS, axis=axis, tiled=True)
