"""PartitionSpec builders for the hybrid MP/DP layout (paper §III-A).

One convention everywhere:

* embedding tables / adagrad accs / FCounters — row-sharded over the *whole*
  mesh (every chip is a model-parallel shard);
* the HybridHash hot tier — replicated (a hit is a local gather);
* dense params + optimizer moments — replicated (DP side of the hybrid);
* batches — leading dim sharded over the whole mesh (every chip also holds a
  data shard: that is PICASSO's "hybrid" placement).

These spec pytrees mirror the state pytrees exactly (same dict keys, same
NamedTuple containers), so they serve as ``shard_map`` in/out specs and — via
``to_named`` — as ``jit`` in/out shardings.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.packed_embedding import CacheState, ProjState
from repro.core.packing import PicassoPlan
from repro.embedding.state import EmbeddingState

Axes = Union[str, Tuple[str, ...]]


def _is_spec(x: Any) -> bool:
    return isinstance(x, P)


def replicated(tree: Any) -> Any:
    """Fully-replicated specs matching ``tree``'s structure (rank-aware)."""
    return jax.tree.map(lambda x: P(*((None,) * len(x.shape))), tree)


def to_named(mesh, specs: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec)


def batch_specs(batch: Any, axes: Axes) -> Any:
    """Shard every batch leaf's leading dim over the full mesh (hybrid DP)."""
    return jax.tree.map(
        lambda x: P(axes, *((None,) * (len(x.shape) - 1))), batch)


def emb_state_specs(axes: Axes, with_l2: bool = False,
                    with_proj: bool = False,
                    with_index: bool = False) -> EmbeddingState:
    """Specs for one packed group's EmbeddingState (table MP, tiers DP).

    ``with_l2`` mirrors whether the group's state carries an L2 host tier
    (``plan.l2_rows[gid] > 0``); like the hot tier it is replicated across
    the mesh — on TPU its leaves additionally live in pinned host memory,
    which PartitionSpecs cannot express: use ``emb_shardings(pin_l2=True)``
    for the memory-kind-aware NamedShardings. ``with_proj`` mirrors a narrow
    master
    (``plan.narrow_width(gid) < dim``): the learned ``[d, D]`` up-projection
    is replicated — its gradient is psum'd so replicas stay bit-identical.
    ``with_index`` mirrors a hot tier with rows (``plan.cache_rows[gid] >
    0``), which carries the rank index of its keys, replicated like them.
    """
    return EmbeddingState(
        w=P(axes, None),
        acc=P(axes, None),
        counts=P(axes),
        cache=(CacheState(keys=P(), rows=P(), acc=P(), bits=P(), base=P())
               if with_index else CacheState(keys=P(), rows=P(), acc=P())),
        l2=CacheState(keys=P(), rows=P(), acc=P()) if with_l2 else None,
        proj=ProjState(kernel=P(None, None), acc=P(None, None))
        if with_proj else None,
    )


def emb_specs(plan: PicassoPlan, axes: Axes) -> Dict[str, EmbeddingState]:
    """Specs for the full per-group embedding dict (the ``"emb"`` subtree)."""
    return {str(g.gid): emb_state_specs(
        axes, with_l2=plan.l2_rows.get(g.gid, 0) > 0,
        with_proj=plan.narrow_width(g.gid) < g.dim,
        with_index=plan.cache_rows.get(g.gid, 0) > 0)
            for g in plan.groups}


def host_memory_kind() -> Optional[str]:
    """The backend's pinned-host memory kind, or ``None`` where there is no
    addressable host memory space (the CPU rig) — the capability check every
    memory-kind-aware builder gates on."""
    try:
        return jax.local_devices()[0].memory("pinned_host").kind
    except Exception:
        return None


def emb_shardings(plan: PicassoPlan, mesh, axes: Axes, *,
                  pin_l2: bool = False) -> Dict[str, EmbeddingState]:
    """``emb_specs`` as NamedShardings — optionally memory-kind-aware.

    PartitionSpecs cannot express a memory space, so ``--pin-l2`` placement
    used to be undone by the first jitted step (its in/out shardings re-staged
    the L2 tier into device memory). With ``pin_l2=True`` — and only where
    the backend actually exposes a ``pinned_host`` memory kind
    (``host_memory_kind``) — the cold-side leaves get host-memory
    NamedShardings instead: every L2 tier leaf, and the narrow master
    (``w``/``acc``, still row-sharded over ``axes``) of groups whose planned
    width is narrowed — exactly the state the cost model prices as
    host-resident. Everything else keeps its device placement, and on
    backends without host memory kinds the result is bit-identical to
    ``to_named(mesh, emb_specs(...))``.
    """
    named = to_named(mesh, emb_specs(plan, axes))
    kind = host_memory_kind() if pin_l2 else None
    if kind is None:
        return named

    def pin(s: NamedSharding) -> NamedSharding:
        return NamedSharding(mesh, s.spec, memory_kind=kind)

    out: Dict[str, EmbeddingState] = {}
    for g in plan.groups:
        st = named[str(g.gid)]
        if st.l2 is not None:
            st = st._replace(l2=jax.tree.map(pin, st.l2))
        if plan.narrow_width(g.gid) < g.dim:
            st = st._replace(w=pin(st.w), acc=pin(st.acc))
        out[str(g.gid)] = st
    return out


def state_shardings(plan: PicassoPlan, mesh, axes: Axes, dense: Any,
                    opt: Optional[Any] = None, *,
                    pin_l2: bool = False) -> Dict[str, Any]:
    """``state_specs`` as NamedShardings, with ``emb_shardings``' optional
    host-memory placement for the cold tiers (jit in/out shardings for the
    train/serve steps — this is what keeps a pinned L2 tier pinned *across*
    steps instead of being silently re-staged onto device)."""
    named = to_named(mesh, state_specs(plan, axes, dense, opt))
    if pin_l2:
        named["emb"] = emb_shardings(plan, mesh, axes, pin_l2=True)
    return named


def state_specs(plan: PicassoPlan, axes: Axes, dense: Any,
                opt: Optional[Any] = None) -> Dict[str, Any]:
    """Specs for the full train/serve state pytree.

    ``opt=None`` builds the serve-time subset (no optimizer, no step counter);
    callers then index ``["emb"]`` / ``["dense"]`` as needed.
    """
    specs: Dict[str, Any] = {
        "emb": emb_specs(plan, axes),
        "dense": replicated(dense),
    }
    if opt is not None:
        specs["opt"] = replicated(opt)
        specs["step"] = P()
    return specs
