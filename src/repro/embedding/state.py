"""Per-group embedding state (table shard + adagrad acc + FCounter + caches).

``l2`` is the optional host-memory cache tier behind the replicated hot tier
(``cache``): same ``CacheState`` container, more rows, filled by the flush
with the frequency ranks just below the L1 set. It is ``None`` whenever the
plan budgets no L2 rows for the group — ``None`` is an empty pytree node, so
plans without an L2 budget keep the exact pre-L2 state structure (sharding
specs, checkpoints, and donation all line up with older runs).

On a real TPU deployment the L2 leaves are *intended* to live in pinned host
memory (``memory_kind='pinned_host'``): ``pin_l2_to_host`` is the placement
hook, wired into both launchers behind ``--pin-l2``. The jitted step
shardings do not carry memory kinds yet, so the repro keeps the tier as
ordinary replicated arrays — the math is identical, only the placement
differs (see its docstring and ROADMAP for that remaining limitation).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packed_embedding import (CacheState, ProjState, init_cache,
                                         proj_pinv, rank_words,
                                         with_rank_index)
from repro.core.packing import PackedGroup, PicassoPlan


class EmbeddingState(NamedTuple):
    w: jnp.ndarray       # [rows, D]   (sharded over the whole mesh; D is the
    #                      group's NARROW width for picasso_narrow groups)
    acc: jnp.ndarray     # [rows, 1]   adagrad accumulator
    counts: jnp.ndarray  # [rows]      FCounter (warm-up + running stats)
    cache: CacheState    # replicated hot tier (L1) — always model width;
    #                      carries the rank index of its keys when it has rows
    l2: Optional[CacheState] = None  # host-memory tier (L2), None = no tier
    proj: Optional[ProjState] = None  # learned [d, D] up-projection; set
    #   exactly when the master is narrow (None keeps the pre-narrow pytree
    #   structure for every other group, like the l2 leaf does)


def _np_proj_kernel(gid: int, nd: int, d: int) -> np.ndarray:
    """Deterministic projection init, shared by the jit init path and the
    host-side migration (a re-widened group must get bit-identical fresh
    projections in both): orthonormal ROWS (QR of a seeded normal), so at
    init ``P @ P^T = I`` — widening is an isometry and the pseudo-inverse is
    exactly ``P^T``. Seeded per (gid, d, D) so groups decorrelate."""
    rng = np.random.default_rng(
        np.random.SeedSequence(entropy=0x91CA550, spawn_key=(gid, nd, d)))
    a = rng.standard_normal((d, nd))
    q, _ = np.linalg.qr(a)            # [D, nd], orthonormal columns
    return np.ascontiguousarray(q.T.astype(np.float32))  # [nd, D]


def init_proj(gid: int, nd: int, d: int, dtype=jnp.float32) -> ProjState:
    return ProjState(kernel=jnp.asarray(_np_proj_kernel(gid, nd, d), dtype),
                     acc=jnp.zeros((nd, 1), dtype))


def init_group_state(key: jax.Array, group: PackedGroup, hot_rows: int,
                     dtype=jnp.float32, l2_rows: int = 0,
                     narrow_dim: Optional[int] = None) -> EmbeddingState:
    """``narrow_dim`` < the group dim makes the MASTER table narrow (cold ids
    live at width ``d`` and are projected up at lookup); the cache tiers stay
    at the full model width — hot ids are always wide on device."""
    nd = group.dim if narrow_dim is None else int(narrow_dim)
    narrow = 0 < nd < group.dim
    width = nd if narrow else group.dim
    scale = 1.0 / jnp.sqrt(jnp.asarray(max(width, 1), jnp.float32))
    w = jax.random.normal(key, (group.rows, width), dtype) * scale
    return EmbeddingState(
        w=w,
        acc=jnp.zeros((group.rows, 1), dtype),
        counts=jnp.zeros((group.rows,), jnp.int32),
        cache=with_rank_index(init_cache(hot_rows, group.dim, group.rows,
                                         dtype), group.rows),
        l2=(init_cache(l2_rows, group.dim, group.rows, dtype)
            if l2_rows > 0 else None),
        proj=(init_proj(group.gid, width, group.dim, dtype)
              if narrow else None),
    )


def init_embedding_state(key: jax.Array, plan: PicassoPlan,
                         dtype=jnp.float32) -> Dict[int, EmbeddingState]:
    keys = jax.random.split(key, len(plan.groups))
    return {
        g.gid: init_group_state(keys[i], g, plan.cache_rows.get(g.gid, 0),
                                dtype, l2_rows=plan.l2_rows.get(g.gid, 0),
                                narrow_dim=plan.narrow_width(g.gid))
        for i, g in enumerate(plan.groups)
    }


def abstract_embedding_state(plan: PicassoPlan, dtype=jnp.float32) -> Dict[int, EmbeddingState]:
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    out = {}
    for g in plan.groups:
        h = plan.cache_rows.get(g.gid, 0)
        h2 = plan.l2_rows.get(g.gid, 0)
        nd = plan.narrow_width(g.gid)
        words = rank_words(g.rows)
        out[g.gid] = EmbeddingState(
            w=jax.ShapeDtypeStruct((g.rows, nd), dtype),
            acc=jax.ShapeDtypeStruct((g.rows, 1), dtype),
            counts=jax.ShapeDtypeStruct((g.rows,), jnp.int32),
            cache=CacheState(
                keys=jax.ShapeDtypeStruct((h,), jnp.int32),
                rows=jax.ShapeDtypeStruct((h, g.dim), dtype),
                acc=jax.ShapeDtypeStruct((h, 1), dtype),
                **({"bits": jax.ShapeDtypeStruct((words,), jnp.uint32),
                    "base": jax.ShapeDtypeStruct((words,), jnp.int32)}
                   if h > 0 else {}),
            ),
            l2=(CacheState(
                keys=jax.ShapeDtypeStruct((h2,), jnp.int32),
                rows=jax.ShapeDtypeStruct((h2, g.dim), dtype),
                acc=jax.ShapeDtypeStruct((h2, 1), dtype),
            ) if h2 > 0 else None),
            proj=(ProjState(
                kernel=jax.ShapeDtypeStruct((nd, g.dim), dtype),
                acc=jax.ShapeDtypeStruct((nd, 1), dtype),
            ) if nd < g.dim else None),
        )
    return out


def pin_l2_to_host(state: Any, mesh=None) -> Any:
    """Best effort: move every L2 tier leaf to pinned host memory.

    Wired into both launchers behind ``--pin-l2`` (the trainer re-applies it
    after every replan migration). On backends that expose
    ``memory_kind='pinned_host'`` the L2
    leaves are re-placed replicated-over-``mesh`` in host memory (so the
    mesh-wide replication the sharding specs declare is preserved — this
    requires ``mesh``; without one, or on backends without host memory kinds
    such as the CPU test rig, the state is returned unchanged). The jitted
    train step keeps the placement across steps via memory-kind-aware
    ``out_shardings`` (``repro.dist.sharding.emb_shardings(pin_l2=True)``),
    so this initial ``device_put`` is the only bulk host copy.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        return state
    try:
        dev = jax.local_devices()[0]
        kind = dev.memory("pinned_host").kind  # raises if unsupported
        host = NamedSharding(mesh, PartitionSpec(), memory_kind=kind)
    except Exception:
        return state

    def move(st):
        if not isinstance(st, EmbeddingState) or st.l2 is None:
            return st
        return st._replace(
            l2=jax.tree.map(lambda x: jax.device_put(x, host), st.l2))

    if isinstance(state, dict) and "emb" in state:
        return {**state, "emb": {k: move(v) for k, v in state["emb"].items()}}
    if isinstance(state, dict):
        return {k: move(v) for k, v in state.items()}
    return move(state)


def l2_pinning_supported() -> bool:
    """True when this backend exposes a ``pinned_host`` memory space (the
    precondition for ``pin_l2_to_host`` to do anything)."""
    try:
        jax.local_devices()[0].memory("pinned_host")
        return True
    except Exception:
        return False


_PIN_L2_WARNED = False


def warn_pin_l2_limits() -> None:
    """One-time ``--pin-l2`` caveat, printed by both launchers.

    On backends that expose ``pinned_host`` the placement is now real across
    steps (memory-kind-aware jit shardings,
    ``repro.dist.sharding.emb_shardings(pin_l2=True)``); on backends without
    such a memory space the flag is a no-op outright — the user asked for
    host residency they are not getting, so say so once."""
    global _PIN_L2_WARNED
    if _PIN_L2_WARNED:
        return
    _PIN_L2_WARNED = True
    if not l2_pinning_supported():
        print("[pin-l2] warning: this backend exposes no 'pinned_host' "
              "memory kind — --pin-l2 is a no-op here (see the --pin-l2 "
              "row in README.md for the flag's documented limits)")


# ---------------------------------------------------------------------------
# plan-revision state migration (repro.runtime replanning loop)
# ---------------------------------------------------------------------------


def tier_gates(plan: PicassoPlan, gid: int, *, use_cache: bool = True,
               use_l2: bool = True) -> Tuple[bool, bool]:
    """(cache_on, l2_on) for one group — the exact gating rule the engine
    applies (strategy class attrs x plan budgets x engine flags), recomputed
    from the plan's recorded assignment. Groups without a recorded strategy
    default to 'picasso', mirroring ``make_flush_fn``'s broadcast default.
    """
    # lazy import: engine.strategies imports this module (EmbeddingState)
    from repro.engine.strategies import get_strategy

    cls = get_strategy(plan.strategy.get(gid, "picasso"))
    cache_on = bool(use_cache and cls.uses_cache
                    and plan.cache_rows.get(gid, 0) > 0)
    l2_on = bool(use_l2 and cache_on and cls.uses_l2
                 and plan.l2_rows.get(gid, 0) > 0)
    return cache_on, l2_on


def _np_tier(st) -> CacheState:
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), st)


def _np_write_back(w: np.ndarray, acc: np.ndarray, tier: CacheState) -> None:
    """Owner write-back of a replicated tier into the (host-copy) master
    arrays: authoritative tier rows + optimizer slots land on their row ids.
    Sentinel keys (>= rows_padded, i.e. empty slots) are skipped."""
    keys = np.asarray(tier.keys)
    mine = keys < w.shape[0]
    w[keys[mine]] = np.asarray(tier.rows)[mine].astype(w.dtype)
    acc[keys[mine]] = np.asarray(tier.acc)[mine].astype(acc.dtype)


def _np_empty_tier(h: int, d: int, rows_padded: int, dtype) -> CacheState:
    return CacheState(keys=np.full((h,), rows_padded, np.int32),
                      rows=np.zeros((h, d), dtype),
                      acc=np.zeros((h, 1), dtype))


def _np_load_tier(w: np.ndarray, acc: np.ndarray, keys: np.ndarray,
                  rows_padded: int, dtype) -> CacheState:
    tier = _np_empty_tier(keys.shape[0], w.shape[1], rows_padded, dtype)
    tier.keys[:] = keys
    mine = keys < rows_padded
    tier.rows[mine] = w[keys[mine]].astype(dtype)
    tier.acc[mine] = acc[keys[mine]].astype(dtype)
    return tier


def _rank_tier_keys(counts: np.ndarray, h1: int, h2: int, rows_padded: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-(h1+h2) row ids by measured frequency, split hottest-h1 / next-h2
    (the host-side analogue of the two-tier flush ranking). Rows with zero
    counts never enter a tier (sentinel instead), matching ``flush_cache``'s
    ``tvals > 0`` guard."""
    h = h1 + h2
    c = np.asarray(counts).astype(np.int64, copy=False).reshape(-1)
    order = np.argsort(-c, kind="stable")[:h]
    ranked = np.where(c[order] > 0, order, rows_padded)
    if ranked.shape[0] < h:  # tier larger than the table (degenerate)
        ranked = np.concatenate(
            [ranked, np.full((h - ranked.shape[0],), rows_padded, np.int64)])
    keys1 = np.sort(ranked[:h1]).astype(np.int32)
    keys2 = np.sort(ranked[h1:]).astype(np.int32)
    return keys1, keys2


def _np_proj_pinv(kernel: np.ndarray, ridge: float = 1e-6) -> np.ndarray:
    """Host mirror of ``packed_embedding.proj_pinv`` (regularized right
    pseudo-inverse ``P^T (P P^T + lam I)^{-1}``), used when migration must
    narrow wide rows."""
    k = np.asarray(kernel, np.float64)
    gram = k @ k.T
    eye = np.eye(gram.shape[0])
    return (k.T @ np.linalg.solve(gram + ridge * eye, eye)).astype(np.float32)


def _migrate_group(group: PackedGroup, st: EmbeddingState,
                   gates_old: Tuple[bool, bool], gates_new: Tuple[bool, bool],
                   h1_new: int, h2_new: int, cache_update: str,
                   nd_old: int, nd_new: int) -> EmbeddingState:
    """Move one group's live state onto new tier budgets/gating (host numpy).

    1. In 'psum' mode, active tiers are authoritative for their rows between
       flushes: write both back into the master shard first, so no update is
       lost when the tier shrinks or disappears. ('stale' mode: the master
       is already exact; tiers are read-only snapshots — no write-back.)
       Narrow masters (``nd_old < dim``) take the write-back through the
       projection's pseudo-inverse.
    2. Re-rank tier residency from the measured FCounter: the hottest
       ``h1_new`` rows seed the new L1 and the next ``h2_new`` the new L2
       (disjoint, like the two-tier flush), loaded from the just-synced
       master so rows and adagrad slots migrate together. New tiers load
       from a full-width view: ids resident in the old tiers carry their
       EXACT wide rows across (psum mode); everything else is widened
       through the projection.
    3. Width transitions re-master the table: ``nd`` widening re-projects
       every row up (``w @ P``, exact for tier-carried ids), narrowing goes
       through a fresh deterministic projection's pseudo-inverse. An
       unchanged narrow width keeps the learned projection AND the narrow
       master bitwise (no lossy widen/narrow round trip).
    4. Optimizer slots and FCounter mass are preserved exactly for ids that
       don't change tier (``acc`` is per-row and width-independent).
    """
    cache_on_old, l2_on_old = gates_old
    cache_on_new, l2_on_new = gates_new
    dim = group.dim
    w = np.array(jax.device_get(st.w))      # mutable host copies
    acc = np.array(jax.device_get(st.acc))
    counts = np.asarray(jax.device_get(st.counts))
    dtype = w.dtype
    rows_padded = group.rows

    narrow_old = st.proj is not None and w.shape[1] < dim
    proj_old = (np.asarray(jax.device_get(st.proj.kernel), np.float32)
                if narrow_old else None)
    pinv_old = _np_proj_pinv(proj_old) if narrow_old else None

    old_tiers = []
    if cache_on_old:
        old_tiers.append(_np_tier(st.cache))
    if l2_on_old and st.l2 is not None:
        old_tiers.append(_np_tier(st.l2))

    if cache_update == "psum":
        for tier in old_tiers:
            if narrow_old:  # wide tier rows -> narrow master via pinv
                keys = np.asarray(tier.keys)
                mine = keys < rows_padded
                w[keys[mine]] = (np.asarray(tier.rows)[mine].astype(np.float32)
                                 @ pinv_old).astype(dtype)
                acc[keys[mine]] = np.asarray(tier.acc)[mine].astype(acc.dtype)
            else:
                _np_write_back(w, acc, tier)

    # Full-width view used for tier loads and width transitions. For narrow
    # masters the widened rows are approximations — except for ids the old
    # tiers held, whose exact wide rows override (psum mode: the tier was
    # authoritative; stale mode: tiers are snapshots, master wins).
    if narrow_old:
        w_wide = (w.astype(np.float32) @ proj_old).astype(dtype)
        if cache_update == "psum":
            for tier in old_tiers:
                keys = np.asarray(tier.keys)
                mine = keys < rows_padded
                w_wide[keys[mine]] = np.asarray(tier.rows)[mine].astype(dtype)
    else:
        w_wide = w

    proj: Optional[ProjState] = None
    if 0 < nd_new < dim:
        if narrow_old and nd_new == nd_old:
            w_new = w  # exact narrow pass-through; learned projection survives
            proj = ProjState(
                kernel=np.asarray(jax.device_get(st.proj.kernel)),
                acc=np.asarray(jax.device_get(st.proj.acc)))
        else:  # widening round trip or first narrowing: fresh projection
            kern = _np_proj_kernel(group.gid, nd_new, dim)
            w_new = (w_wide.astype(np.float32)
                     @ _np_proj_pinv(kern)).astype(dtype)
            proj = ProjState(kernel=kern.astype(dtype),
                             acc=np.zeros((nd_new, 1), dtype))
    else:
        w_new = w_wide  # re-widened (or was never narrow)

    keys1, keys2 = _rank_tier_keys(counts,
                                   h1_new if cache_on_new else 0,
                                   h2_new if l2_on_new else 0, rows_padded)
    if cache_on_new:
        cache = _np_load_tier(w_wide, acc, keys1, rows_padded, dtype)
    else:  # allocated (plan budgets rows) but inert under the new strategy
        cache = _np_empty_tier(h1_new, group.dim, rows_padded, dtype)
    cache = with_rank_index(cache, rows_padded)
    l2: Optional[CacheState] = None
    if h2_new > 0:
        l2 = (_np_load_tier(w_wide, acc, keys2, rows_padded, dtype)
              if l2_on_new
              else _np_empty_tier(h2_new, group.dim, rows_padded, dtype))
    return EmbeddingState(w=w_new, acc=acc, counts=counts, cache=cache,
                          l2=l2, proj=proj)


def _reshard_group_state(group: PackedGroup, st: EmbeddingState
                         ) -> EmbeddingState:
    """Re-cut one group's state for a new padded row count (host numpy).

    A world-size change re-pads the packed table (``rows = _pad_to(logical,
    world)``) without touching the logical rows, so the migration is a pure
    permutation plus padding surgery:

    - master ``w``/``acc``/FCounter ``counts`` are zero-extended (scale-down
      in world can mean MORE padding) or truncated — only ever padding rows,
      which are never looked up; a nonzero FCounter in the truncated tail
      would mean a real row is about to be dropped, so that raises;
    - tier sentinel keys are remapped: an empty slot holds ``keys ==
      rows_padded``, and every key >= ``min(r_old, r_new)`` is by
      construction a sentinel (valid residents are logical rows, which fit
      under both paddings), so they all move to the NEW sentinel value.
      Resident keys, rows, and adagrad slots carry bitwise.
    - the learned projection (narrow masters) is row-count-independent and
      carries bitwise.
    """
    w = np.array(jax.device_get(st.w))
    acc = np.array(jax.device_get(st.acc))
    counts = np.array(jax.device_get(st.counts))
    r_old, r_new = int(w.shape[0]), int(group.rows)
    dtype = w.dtype
    if r_new > r_old:
        pad = r_new - r_old
        w = np.concatenate([w, np.zeros((pad, w.shape[1]), dtype)])
        acc = np.concatenate([acc, np.zeros((pad, 1), acc.dtype)])
        counts = np.concatenate([counts, np.zeros((pad,), counts.dtype)])
    elif r_new < r_old:
        if np.asarray(counts[r_new:]).any():
            raise ValueError(
                f"g{group.gid}: resharding {r_old} -> {r_new} rows would "
                "drop rows with nonzero FCounter mass — the truncated tail "
                "must be pure padding")
        w, acc, counts = w[:r_new], acc[:r_new], counts[:r_new]
    cut = min(r_old, r_new)

    def remap(tier: Optional[CacheState]) -> Optional[CacheState]:
        if tier is None:
            return None
        keys = np.asarray(jax.device_get(tier.keys))
        keys = np.where(keys >= cut, r_new, keys).astype(np.int32)
        out = CacheState(keys=keys,
                         rows=np.asarray(jax.device_get(tier.rows)),
                         acc=np.asarray(jax.device_get(tier.acc)))
        # the index is over the row space, so it is rebuilt for the new one
        return out if tier.bits is None else with_rank_index(out, r_new)

    proj = None
    if st.proj is not None:
        proj = ProjState(kernel=np.asarray(jax.device_get(st.proj.kernel)),
                         acc=np.asarray(jax.device_get(st.proj.acc)))
    return EmbeddingState(w=w, acc=acc, counts=counts,
                          cache=remap(st.cache), l2=remap(st.l2), proj=proj)


def reshard_state(new_plan: PicassoPlan, state: Any) -> Any:
    """Re-cut live embedding state onto ``new_plan``'s padded row counts.

    The state-side half of ``core.packing.reshard_plan``: per group, pad or
    truncate the padding rows and remap tier sentinel keys
    (``_reshard_group_state``); groups whose rows already match pass through
    untouched. Accepts the full train/serve state dict (``{"emb": ...}``) or
    the bare per-group emb dict. Returns host (numpy) arrays for resharded
    groups — callers re-place the state under the new mesh's shardings
    (``runtime.elastic.place_state``) before stepping.
    """
    if isinstance(state, dict) and "emb" in state:
        return {**state, "emb": reshard_state(new_plan, state["emb"])}
    out = {}
    for g in new_plan.groups:
        key = str(g.gid) if str(g.gid) in state else g.gid
        st = state[key]
        if int(np.shape(st.w)[0]) == g.rows:
            out[key] = st
        else:
            out[key] = _reshard_group_state(g, st)
    return out


def migrate_state(old_plan: PicassoPlan, new_plan: PicassoPlan, state: Any, *,
                  use_cache: bool = True, use_l2: bool = True,
                  cache_update: str = "psum") -> Any:
    """Carry live embedding state from ``old_plan`` to ``new_plan``.

    The two plans must be revisions of one structural plan (same gids, same
    packed dims — ``revise_plan`` and ``reshard_plan`` guarantee this); what
    may differ is ``cache_rows``/``l2_rows``, the per-group strategy
    assignment, and — across a world-size change (``reshard_plan``) — the
    padded row counts, which are re-cut first via ``_reshard_group_state``
    (a pure padding/sentinel permutation, exact for every logical row).

    Per group:

    - **no-change pass-through** — identical tier shapes *and* identical
      gating return the group's arrays untouched (bitwise: a replan that
      recompiles to the same plan is a no-op);
    - otherwise the group is migrated on host (``_migrate_group``): 'psum'
      tiers are written back so every master row and adagrad slot survives
      exactly, then the new tiers are re-seeded with the measured top-(H1+H2)
      rows split hottest-H1 -> L1 / next-H2 -> L2. Narrow-width changes
      (``plan.narrow_width``) re-master the table across the projection:
      ids heating into a tier re-widen, cooling ids narrow through the
      pseudo-inverse, and ids staying tier-resident carry exact wide rows.

    ``use_cache``/``use_l2``/``cache_update`` MUST mirror the engine flags
    the state was trained under (same contract as ``make_flush_fn``).
    Accepts the full train/serve state dict (``{"emb": ...}``) or the bare
    per-group emb dict; returns the same shape of structure. Migrated groups
    come back as host (numpy) arrays — callers re-place them on the mesh
    (``repro.runtime.Replanner`` does) before stepping.
    """
    if isinstance(state, dict) and "emb" in state:
        return {**state, "emb": migrate_state(
            old_plan, new_plan, state["emb"], use_cache=use_cache,
            use_l2=use_l2, cache_update=cache_update)}

    old_gids = sorted(g.gid for g in old_plan.groups)
    new_gids = sorted(g.gid for g in new_plan.groups)
    if old_gids != new_gids:
        raise ValueError(
            f"migrate_state needs revisions of one structural plan; group "
            f"sets differ: {old_gids} vs {new_gids}")
    out: Dict[str, EmbeddingState] = {}
    for g in new_plan.groups:
        og = old_plan.group(g.gid)
        if og.dim != g.dim:
            raise ValueError(
                f"g{g.gid}: packed dim changed across revisions "
                f"({og.rows}x{og.dim} -> {g.rows}x{g.dim}); only tier "
                "budgets, strategy, and world padding may change")
        h_old = (old_plan.cache_rows.get(g.gid, 0),
                 old_plan.l2_rows.get(g.gid, 0))
        h_new = (new_plan.cache_rows.get(g.gid, 0),
                 new_plan.l2_rows.get(g.gid, 0))
        gates_old = tier_gates(old_plan, g.gid, use_cache=use_cache,
                               use_l2=use_l2)
        gates_new = tier_gates(new_plan, g.gid, use_cache=use_cache,
                               use_l2=use_l2)
        nd_old = old_plan.narrow_width(g.gid)
        nd_new = new_plan.narrow_width(g.gid)
        st = state[str(g.gid)]
        if og.rows != g.rows:  # world resize: recut padding/sentinels first
            st = _reshard_group_state(g, st)
        if h_old == h_new and gates_old == gates_new and nd_old == nd_new:
            out[str(g.gid)] = st  # bitwise pass-through
        else:
            out[str(g.gid)] = _migrate_group(g, st, gates_old, gates_new,
                                             h_new[0], h_new[1], cache_update,
                                             nd_old, nd_new)
    return out
