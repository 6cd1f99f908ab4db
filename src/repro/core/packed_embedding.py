"""PICASSO packed-embedding primitives (paper §III-B, §III-D).

This is the kernel layer beneath ``repro.engine.EmbeddingEngine``: stateless,
fixed-shape collective building blocks. Workloads never call these directly —
they go through the engine's ``LookupStrategy`` classes, which compose them.

Executes one *packed* lookup per D-packed group, model-parallel over the whole
mesh, inside ``shard_map``:

    ids -> [K-Packed Unique&Partition] -> all_to_all (Shuffle) -> local Gather
        -> all_to_all back -> Stitch -> (hot-cache merge) -> unique rows

and the exact transposed path for sparse gradients. All shapes are static
(TPU collectives require it): ``unique`` is sort-based with a fixed output
size, the Shuffle uses fixed-capacity per-peer buckets sized by the planner
(Eq. 1 statistics), and the HybridHash hot tier absorbs the skew head that
would otherwise overflow the buckets.

HybridHash on TPU (see DESIGN.md §2): hot rows are replicated per chip; a hit
is a local gather with zero ICI traffic. Hit gradients are psum'd (replicas
stay bit-identical) and applied to the replicated hot tier; the hot tier is
the authoritative storage for its rows between flushes, so training stays
*exact* synchronous SGD — flush writes rows+optimizer state back to the owner
shard and reloads the new top-k set.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.kernels import ops
from repro.optim import grad_compression as gcomp

Axes = Union[str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# fixed-shape building blocks (K-Packing: Unique&Partition fused)
# ---------------------------------------------------------------------------


class UniqueResult(NamedTuple):
    uniq: jnp.ndarray      # [n] ascending; slots >= n_uniq hold ``sentinel``
    inv: jnp.ndarray       # [n] original position -> unique slot
    n_uniq: jnp.ndarray    # scalar
    uvalid: jnp.ndarray    # [n] bool, slot validity


def fixed_unique(ids: jnp.ndarray, sentinel: int) -> UniqueResult:
    """Sort-based unique with static output size == input size."""
    n = ids.shape[0]
    order = jnp.argsort(ids)
    s = ids[order]
    is_first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    slot_sorted = (jnp.cumsum(is_first) - 1).astype(jnp.int32)
    inv = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
    uniq = jnp.full((n,), sentinel, ids.dtype).at[slot_sorted].set(s)
    n_uniq = jnp.sum(is_first).astype(jnp.int32)
    uvalid = jnp.arange(n, dtype=jnp.int32) < n_uniq
    return UniqueResult(uniq, inv, n_uniq, uvalid)


class Routing(NamedTuple):
    """Unique&Partition output: where each unique slot goes in the Shuffle."""

    owner: jnp.ndarray    # [n] destination shard (== world for drop)
    pos: jnp.ndarray      # [n] position within the per-peer bucket
    send_slot: jnp.ndarray  # [n] flattened owner*cap + pos (world*cap = drop)
    kept: jnp.ndarray     # [n] routed (miss & under capacity)
    overflow: jnp.ndarray  # scalar count of dropped uniques


def partition(uniq: jnp.ndarray, miss: jnp.ndarray, rows_per_shard: int, world: int,
              capacity: int) -> Routing:
    """Partition sorted unique ids into fixed-capacity per-owner buckets.

    ``uniq`` ascending => block owner ids are monotone, so the rank of a miss
    within its owner's bucket is a cumsum difference (no extra sort).
    """
    n = uniq.shape[0]
    owner = jnp.minimum(uniq // rows_per_shard, world).astype(jnp.int32)
    prefix = jnp.cumsum(miss.astype(jnp.int32)) - miss.astype(jnp.int32)  # exclusive
    start = jnp.searchsorted(owner, owner, side="left").astype(jnp.int32)
    pos = prefix - prefix[start]
    kept = miss & (pos < capacity) & (owner < world)
    send_slot = jnp.where(kept, owner * capacity + pos, world * capacity).astype(jnp.int32)
    overflow = jnp.sum(miss & (pos >= capacity))
    return Routing(owner, pos, send_slot, kept, overflow)


def _a2a(x: jnp.ndarray, axes: Axes) -> jnp.ndarray:
    """all_to_all over (possibly multiple) mesh axes; [world, ...] layout."""
    with obs.scope(obs.SHUFFLE):
        return lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)


def _compressed_a2a_rows(send_g: jnp.ndarray, axes: Axes, world: int,
                         cap: int, compress: str = "none",
                         fused: bool = False) -> jnp.ndarray:
    """all_to_all ``[world*cap, D]`` gradient rows, compressed on the wire.

    ``compress='none'`` is the exact legacy hop (bitwise-identical bytes and
    math). Otherwise the rows are compressed *before* the collective (so only
    the narrow payload crosses ICI), every payload leaf rides its own
    all_to_all (leaves keep the leading row dim, so the [world, cap, ...]
    reshape is payload-shape agnostic), and owners decompress after. Zero
    rows — padded bucket slots — survive every mode bitwise, which the
    dedup+adagrad scatter's validity masking relies on.
    """
    d = send_g.shape[-1]
    if compress == "none":
        return _a2a(send_g.reshape(world, cap, d), axes).reshape(world * cap, d)
    payload = gcomp.compress_rows(send_g, compress, fused=fused)
    payload = jax.tree.map(
        lambda x: _a2a(x.reshape(world, cap, *x.shape[1:]), axes)
        .reshape(world * cap, *x.shape[1:]),
        payload)
    return gcomp.decompress_rows(payload, d, compress, fused=fused)


# ---------------------------------------------------------------------------
# forward: Shuffle & Stitch (+ HybridHash read path)
# ---------------------------------------------------------------------------


class LookupCtx(NamedTuple):
    """Everything the backward/statistics passes need (all static shapes).

    ``l2_hit``/``l2_slot`` are ``None`` unless the lookup probed an L2 host
    tier (``mp_lookup(..., l2_keys=, l2_rows=)``); ``None`` collapses to an
    empty pytree node, so plain-picasso contexts keep their PR-2 structure.
    """

    uniq: jnp.ndarray
    inv: jnp.ndarray
    uvalid: jnp.ndarray
    hit: jnp.ndarray        # [n] served by hot tier
    cache_slot: jnp.ndarray  # [n] clamped position in hot_keys
    routing: Routing
    recv_ids: jnp.ndarray   # [world, cap] ids this shard served (owner side)
    recv_local: jnp.ndarray  # [world, cap] local row idx (clamped)
    recv_valid: jnp.ndarray  # [world, cap]
    l2_hit: Optional[jnp.ndarray] = None   # [n] served by L2 host tier
    l2_slot: Optional[jnp.ndarray] = None  # [n] clamped position in l2_keys
    narrow_rows: Optional[jnp.ndarray] = None  # [n, d] routed narrow rows
    #   (picasso_narrow only: the gather_project residual — zero at tier-hit
    #   and padded positions — from which the projection gradient is one
    #   ``narrow^T @ g_u`` matmul in the backward)
    n_uniq: Optional[jnp.ndarray] = None  # scalar: distinct ids looked up
    #   (``UniqueResult.n_uniq``; None where the lookup does not dedup)


def cache_probe(uniq: jnp.ndarray, uvalid: jnp.ndarray,
                hot_keys: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if hot_keys is None or hot_keys.shape[0] == 0:
        z = jnp.zeros(uniq.shape, bool)
        return z, jnp.zeros(uniq.shape, jnp.int32)
    p = jnp.searchsorted(hot_keys, uniq).astype(jnp.int32)
    p_c = jnp.clip(p, 0, hot_keys.shape[0] - 1)
    hit = (hot_keys[p_c] == uniq) & uvalid
    return hit, p_c


def _probe_tiers(u: UniqueResult, hot_keys, hot_rows, l2_keys, l2_rows,
                 fused: bool):
    """The tiered probe, under the ``tier_probe`` scope: L1 (``hot_keys``)
    first, then the L2 tier for the L1 misses only. Returns ``(hit,
    cache_slot, l1_probe_rows, l2_hit, l2_slot, l2_probe_rows, miss)``,
    ``l2_*`` None without an L2 tier.

    The probe is the search: which ids a tier holds, and at which slot. The
    hit rows are fetched by the Stitch (``_stitch_tiers``), except where the
    ``tier_probe`` kernel runs (``ops.runs_kernel``): it fetches each hit row
    in its search pass, and returns it zero-masked as ``*_probe_rows`` (else
    None)."""
    kernel = ops.runs_kernel("tier_probe", fused)
    with obs.scope(obs.TIER_PROBE):
        if (kernel and hot_keys is not None and hot_keys.shape[0] > 0
                and hot_rows is not None):
            hit, slot, l1_rows = ops.tier_probe(u.uniq, u.uvalid, hot_keys,
                                                hot_rows, fused=True)
        else:
            hit, slot = cache_probe(u.uniq, u.uvalid, hot_keys)
            l1_rows = None
        if l2_keys is None or l2_keys.shape[0] == 0:
            return hit, slot, l1_rows, None, None, None, u.uvalid & ~hit
        if kernel:
            l2_hit, l2_slot, l2_rows_p = ops.tier_probe(
                u.uniq, u.uvalid & ~hit, l2_keys, l2_rows, fused=True)
        else:
            l2_hit, l2_slot = cache_probe(u.uniq, u.uvalid & ~hit, l2_keys)
            l2_rows_p = None
        return (hit, slot, l1_rows, l2_hit, l2_slot, l2_rows_p,
                u.uvalid & ~hit & ~l2_hit)


def _stitch_tiers(miss_rows, hit, cache_slot, hot_rows, l1_probe_rows,
                  l2_hit, l2_slot, l2_rows, l2_probe_rows):
    """Stitch the tier hits over the routed-back rows: L2 hits first, then
    L1 hits. Where the probe kernel returned its rows (``*_probe_rows``)
    they are taken as they are, else the tier's rows are gathered at the
    probed slot."""
    if l2_hit is not None:
        l2 = (l2_probe_rows if l2_probe_rows is not None
              else jnp.take(l2_rows, l2_slot, axis=0))
        miss_rows = jnp.where(l2_hit[:, None], l2.astype(miss_rows.dtype),
                              miss_rows)
    if l1_probe_rows is not None:
        return jnp.where(hit[:, None], l1_probe_rows.astype(miss_rows.dtype),
                         miss_rows)
    if hot_rows is not None and hot_rows.shape[0] > 0:
        hot = jnp.take(hot_rows, cache_slot, axis=0)
        return jnp.where(hit[:, None], hot.astype(miss_rows.dtype), miss_rows)
    return miss_rows


def mp_lookup(
    table_shard: jnp.ndarray,      # [rows_per_shard, D]
    ids: jnp.ndarray,              # [n] packed global row ids
    *,
    axes: Axes,
    world: int,
    capacity: int,
    hot_keys: Optional[jnp.ndarray] = None,   # [H] replicated, sorted
    hot_rows: Optional[jnp.ndarray] = None,   # [H, D] replicated
    l2_keys: Optional[jnp.ndarray] = None,    # [H2] L2 host tier, sorted
    l2_rows: Optional[jnp.ndarray] = None,    # [H2, D] L2 host tier
    fused: bool = False,                      # fused tier-probe kernels
) -> Tuple[jnp.ndarray, LookupCtx]:
    """Forward packed lookup. Returns unique rows [n, D] + routing context.

    Probe order is strictly tiered: L1 (``hot_keys``, device-resident hot
    tier) first, then — only for L1 misses — the L2 host tier (``l2_keys``),
    and only the remaining misses ride the all_to_all Shuffle. The two tiers
    are disjoint by flush construction (top-H1 / next-H2 by frequency), and
    the L2 probe additionally masks out L1 hits so an overlapping user-built
    tier can never serve one id twice. With ``l2_keys=None`` (no L2 tier)
    the math — including every intermediate — is bitwise-identical to the
    PR-2 path, and ``ctx.l2_hit`` stays ``None``.

    ``fused=True`` replaces each tier's searchsorted/take/where chain with
    one ``ops.tier_probe`` kernel pass (binary search + hit-masked row
    gather) where that kernel runs (``_probe_tiers``); the probed rows come
    back zero-masked, so the Stitch below is a single ``where`` per tier and
    hit values are identical either way.
    """
    rps, d = table_shard.shape
    rows_padded = rps * world
    n = ids.shape[0]

    with obs.scope(obs.UNIQUE):
        u = fixed_unique(ids, sentinel=rows_padded)
    (hit, cache_slot, l1_probe_rows, l2_hit, l2_slot, l2_probe_rows,
     miss) = _probe_tiers(u, hot_keys, hot_rows, l2_keys, l2_rows, fused)
    with obs.scope(obs.PARTITION):
        r = partition(u.uniq, miss, rps, world, capacity)
        send_ids = jnp.full((world * capacity,), -1, jnp.int32)
        send_ids = send_ids.at[r.send_slot].set(u.uniq.astype(jnp.int32),
                                                mode="drop")

    # ---- Shuffle: route miss ids to owners --------------------------------
    recv_ids = _a2a(send_ids.reshape(world, capacity), axes)  # [world, cap]

    # ---- local Gather ------------------------------------------------------
    with obs.scope(obs.GATHER):
        my = lax.axis_index(axes)
        base = my.astype(jnp.int32) * rps
        recv_valid = recv_ids >= 0
        recv_local = jnp.clip(recv_ids - base, 0, rps - 1)
        served = jnp.take(table_shard, recv_local.reshape(-1), axis=0)
        served = served * recv_valid.reshape(-1, 1).astype(served.dtype)

    # ---- Shuffle back + Stitch ---------------------------------------------
    back = _a2a(served.reshape(world, capacity, d), axes).reshape(world * capacity, d)
    with obs.scope(obs.STITCH):
        take_idx = jnp.minimum(r.send_slot, world * capacity - 1)
        miss_rows = (jnp.take(back, take_idx, axis=0)
                     * r.kept[:, None].astype(served.dtype))
        rows_u = _stitch_tiers(miss_rows, hit, cache_slot, hot_rows,
                               l1_probe_rows, l2_hit, l2_slot, l2_rows,
                               l2_probe_rows)

    ctx = LookupCtx(
        uniq=u.uniq, inv=u.inv, uvalid=u.uvalid, hit=hit, cache_slot=cache_slot,
        routing=r, recv_ids=recv_ids, recv_local=recv_local, recv_valid=recv_valid,
        l2_hit=l2_hit, l2_slot=l2_slot, n_uniq=u.n_uniq,
    )
    return rows_u, ctx


def mp_lookup_narrow(
    table_shard: jnp.ndarray,      # [rows_per_shard, d] NARROW master shard
    ids: jnp.ndarray,              # [n] packed global row ids
    *,
    proj: jnp.ndarray,             # [d, D] learned up-projection (replicated)
    axes: Axes,
    world: int,
    capacity: int,
    hot_keys: Optional[jnp.ndarray] = None,   # [H1] sorted; tier rows are WIDE
    hot_rows: Optional[jnp.ndarray] = None,   # [H1, D]
    l2_keys: Optional[jnp.ndarray] = None,    # [H2] sorted
    l2_rows: Optional[jnp.ndarray] = None,    # [H2, D]
    fused: bool = False,
) -> Tuple[jnp.ndarray, LookupCtx]:
    """``mp_lookup`` with hot/cold heterogeneous widths: tier-resident (hot)
    ids are served full-width ``D`` rows exactly as in the L2 path, while the
    misses ride the Shuffle at the narrow width ``d`` — the owner gathers
    ``[d]`` rows from the narrow master shard, the return hop carries
    ``world*cap*d`` elements, and the Stitch is one fused
    ``ops.gather_project`` pass that projects the routed-back narrow rows up
    through ``proj`` (no ``[n, d]``-then-``[n, D]`` op chain). The narrow
    rows land in ``ctx.narrow_rows`` (zeros at tier-hit/padded positions) as
    the residual for the projection's gradient.

    Probe order, overflow accounting, and the returned routing context are
    identical to ``mp_lookup``; only the wire width and the Stitch differ.
    """
    rps, nd = table_shard.shape
    rows_padded = rps * world

    with obs.scope(obs.UNIQUE):
        u = fixed_unique(ids, sentinel=rows_padded)
    (hit, cache_slot, l1_probe_rows, l2_hit, l2_slot, l2_probe_rows,
     miss) = _probe_tiers(u, hot_keys, hot_rows, l2_keys, l2_rows, fused)
    with obs.scope(obs.PARTITION):
        r = partition(u.uniq, miss, rps, world, capacity)
        send_ids = jnp.full((world * capacity,), -1, jnp.int32)
        send_ids = send_ids.at[r.send_slot].set(u.uniq.astype(jnp.int32),
                                                mode="drop")

    # ---- Shuffle: route miss ids to owners --------------------------------
    recv_ids = _a2a(send_ids.reshape(world, capacity), axes)

    # ---- local Gather (narrow width on the wire) ---------------------------
    with obs.scope(obs.GATHER):
        my = lax.axis_index(axes)
        base = my.astype(jnp.int32) * rps
        recv_valid = recv_ids >= 0
        recv_local = jnp.clip(recv_ids - base, 0, rps - 1)
        served = jnp.take(table_shard, recv_local.reshape(-1), axis=0)
        served = served * recv_valid.reshape(-1, 1).astype(served.dtype)

    # ---- Shuffle back + fused gather+project Stitch ------------------------
    back = _a2a(served.reshape(world, capacity, nd), axes).reshape(
        world * capacity, nd)
    with obs.scope(obs.STITCH):
        take_idx = jnp.minimum(r.send_slot, world * capacity - 1)
        miss_rows, narrow = ops.gather_project(back, take_idx, r.kept, proj,
                                               fused=fused)
        rows_u = _stitch_tiers(miss_rows, hit, cache_slot, hot_rows,
                               l1_probe_rows, l2_hit, l2_slot, l2_rows,
                               l2_probe_rows)

    ctx = LookupCtx(
        uniq=u.uniq, inv=u.inv, uvalid=u.uvalid, hit=hit, cache_slot=cache_slot,
        routing=r, recv_ids=recv_ids, recv_local=recv_local, recv_valid=recv_valid,
        l2_hit=l2_hit, l2_slot=l2_slot, narrow_rows=narrow, n_uniq=u.n_uniq,
    )
    return rows_u, ctx


def pool(
    rows_u: jnp.ndarray,    # [n, D] unique rows (differentiation leaf)
    ctx_inv: jnp.ndarray,   # [n]
    weights: jnp.ndarray,   # [n] (0 for padding; 1/len for mean pooling)
    seg: jnp.ndarray,       # [n] bag index (sorted; packed layout covers all)
    n_bags: int,
    fused: bool = False,
) -> jnp.ndarray:
    """SegmentReduction: ids -> bags. Differentiable wrt rows_u.

    Routed through ``ops.gather_pool`` (a ``jax.custom_vjp`` whose backward
    is the fused transpose); with ``fused=True`` neither direction
    materializes the ``[n, D]`` per-id intermediate."""
    return ops.gather_pool(rows_u, ctx_inv, weights, seg, n_bags, fused=fused)


# ---------------------------------------------------------------------------
# backward: transposed Shuffle + row-wise adagrad (sparse-exact)
# ---------------------------------------------------------------------------


def _dedup_apply(w_shard: jnp.ndarray, acc_shard: jnp.ndarray,
                 idx: jnp.ndarray, g: jnp.ndarray, valid: jnp.ndarray,
                 lr: float, eps: float, fused: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sum duplicate row grads, then row-wise adagrad on touched rows only.

    ``fused=True`` runs the one-pass Pallas kernel (sorted-run detection +
    adagrad + in-place scatter; reference accumulation order, ~1 ULP);
    ``False`` the argsort/segment_sum/scatter chain — both via
    ``ops.dedup_adagrad``."""
    with obs.scope(obs.MASTER_UPDATE):
        return ops.dedup_adagrad(w_shard, acc_shard, idx, g, valid, lr, eps,
                                 fused=fused)


class CacheState(NamedTuple):
    keys: jnp.ndarray   # [H] sorted global row ids (sentinel = rows_padded)
    rows: jnp.ndarray   # [H, D]
    acc: jnp.ndarray    # [H, 1] adagrad accumulator


class ProjState(NamedTuple):
    """Learned per-group up-projection for hot/cold heterogeneous placement
    (``picasso_narrow``): cold ids live as ``[d]``-narrow master rows and are
    projected to the model width ``D`` at lookup. Replicated (like the tiers);
    its gradient is psum'd, so replicas stay bit-identical."""

    kernel: jnp.ndarray  # [d, D]
    acc: jnp.ndarray     # [d, 1] row-wise adagrad accumulator


def init_cache(h: int, d: int, rows_padded: int, dtype=jnp.float32) -> CacheState:
    return CacheState(
        keys=jnp.full((h,), rows_padded, jnp.int32),
        rows=jnp.zeros((h, d), dtype),
        acc=jnp.zeros((h, 1), dtype),
    )


def apply_sparse_grads(
    w_shard: jnp.ndarray,
    acc_shard: jnp.ndarray,
    cache: Optional[CacheState],
    ctx: LookupCtx,
    g_u: jnp.ndarray,    # [n, D] grad wrt unique rows
    *,
    axes: Axes,
    world: int,
    lr: float,
    eps: float = 1e-8,
    cache_update: str = "psum",   # 'psum' (replica-consistent exact) | 'stale'
    fused: bool = False,          # fused dedup+adagrad scatter kernels
    compress: str = "none",       # routed-grad wire compression (grad_compression)
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[CacheState]]:
    """Transposed path: miss grads -> owners; hit grads -> hot tier or owners.

    'psum'  — hit grads are psum'd into the replicated hot tier; the hot tier
              is authoritative between flushes (exact training, but the
              all-reduce is O(H*D) per step — expensive for large H).
    'stale' — hit grads are routed to the *owner* shards through a second
              small all_to_all (O(hits*D)); the hot tier is read-only between
              flushes (paper Algorithm 1 semantics: bounded read staleness of
              flush_iters, master always exact).

    ``compress`` shrinks the routed all_to_all payloads ('none'|'fp16'|'topk',
    see ``repro.optim.grad_compression``). It covers the per-step routed hops
    only — tier-maintenance traffic (hot-tier psums, flush reloads) stays
    exact, since its cost is amortized and its consumers assume bitwise
    replica consistency.
    """
    # ---- miss gradients: transposed Shuffle --------------------------------
    w_shard, acc_shard = _apply_miss_grads(w_shard, acc_shard, ctx, g_u,
                                           axes, world, lr, eps, fused,
                                           compress)

    if cache is None or cache.keys.shape[0] == 0:
        return w_shard, acc_shard, cache

    if cache_update == "stale":
        # ---- hit gradients: route to owners (cache stays read-only) --------
        w_shard, acc_shard = _route_hit_grads(w_shard, acc_shard, ctx, ctx.hit,
                                              g_u, axes, world, lr, eps, fused,
                                              compress)
        return w_shard, acc_shard, cache

    # ---- 'psum': hit grads into the replicated hot tier --------------------
    cache = _psum_into_tier(cache, ctx.hit, ctx.cache_slot, g_u, axes, lr, eps,
                            fused)
    return w_shard, acc_shard, cache


def _apply_miss_grads(w_shard, acc_shard, ctx: LookupCtx, g_u, axes: Axes,
                      world: int, lr: float, eps: float, fused: bool = False,
                      compress: str = "none"):
    """Transposed Shuffle: route miss grads to owner shards and apply."""
    d = w_shard.shape[1]
    cap = ctx.recv_ids.shape[1]  # static block shape
    send_g = jnp.zeros((world * cap, d), g_u.dtype)
    send_g = send_g.at[ctx.routing.send_slot].set(
        g_u * ctx.routing.kept[:, None].astype(g_u.dtype), mode="drop")
    recv_g = _compressed_a2a_rows(send_g, axes, world, cap, compress, fused)
    return _dedup_apply(
        w_shard, acc_shard,
        ctx.recv_local.reshape(-1), recv_g, ctx.recv_valid.reshape(-1), lr, eps,
        fused)


def _route_hit_grads(w_shard, acc_shard, ctx: LookupCtx, hit_mask, g_u,
                     axes: Axes, world: int, lr: float, eps: float,
                     fused: bool = False, compress: str = "none"):
    """'stale' mode: grads of tier-served ids ride a second small all_to_all
    to the owner shards; the tier itself stays read-only between flushes."""
    rps, d = w_shard.shape
    cap = ctx.recv_ids.shape[1]
    r = partition(ctx.uniq, hit_mask, rps, world, cap)
    send_ids = jnp.full((world * cap,), -1, jnp.int32)
    send_ids = send_ids.at[r.send_slot].set(ctx.uniq.astype(jnp.int32), mode="drop")
    send_hg = jnp.zeros((world * cap, d), g_u.dtype)
    send_hg = send_hg.at[r.send_slot].set(
        g_u * r.kept[:, None].astype(g_u.dtype), mode="drop")
    recv_ids = _a2a(send_ids.reshape(world, cap), axes).reshape(-1)
    recv_hg = _compressed_a2a_rows(send_hg, axes, world, cap, compress, fused)
    my = lax.axis_index(axes).astype(jnp.int32)
    local = jnp.clip(recv_ids - my * rps, 0, rps - 1)
    return _dedup_apply(
        w_shard, acc_shard, local, recv_hg, recv_ids >= 0, lr, eps, fused)


def _tier_adagrad(tier: CacheState, g_hot: jnp.ndarray, lr: float,
                  eps: float) -> CacheState:
    """Row-wise adagrad on a replicated tier from a replica-consistent
    per-slot gradient (rows without gradient stay bit-identical)."""
    gsq = jnp.mean(jnp.square(g_hot), axis=-1, keepdims=True)
    touched = (jnp.abs(g_hot).max(axis=-1, keepdims=True) > 0).astype(gsq.dtype)
    acc_new = tier.acc + gsq * touched
    upd = lr * g_hot / jnp.sqrt(acc_new + eps)
    return CacheState(tier.keys, tier.rows - upd.astype(tier.rows.dtype),
                      acc_new.astype(tier.acc.dtype))


def _psum_into_tier(tier: CacheState, hit_mask, slot, g_u, axes: Axes,
                    lr: float, eps: float, fused: bool = False) -> CacheState:
    """'psum' mode: all-reduce tier-hit grads and adagrad the replicated tier
    in place (replicas stay bit-identical; the tier is authoritative for its
    rows between flushes). Comm is O(H*D) per step — right for the small
    device-resident hot tier.

    Deliberately NOT routed through the dedup+adagrad kernel even when
    ``fused``: the psum forces the dense ``[H, D]`` buffer into existence
    anyway, after which the dense row-wise adagrad is a single fused
    elementwise pass — a per-row scatter kernel over the identity index
    would only serialize it. Fusion pays where it removes the dense buffer
    (``_allgather_into_tier``) or the scatter chain (``_dedup_apply``)."""
    del fused
    h = tier.keys.shape[0]
    d = g_u.shape[1]
    with obs.scope(obs.TIER_UPDATE):
        g_hit = g_u * hit_mask[:, None].astype(g_u.dtype)
        g_hot = jnp.zeros((h, d), g_u.dtype).at[slot].add(g_hit)
        g_hot = lax.psum(g_hot, axes)
        return _tier_adagrad(tier, g_hot, lr, eps)


def _allgather_into_tier(tier: CacheState, hit_mask, slot, g_u, axes: Axes,
                         lr: float, eps: float, fused: bool = False
                         ) -> CacheState:
    """Exact replicated-tier update with comm independent of the tier size:
    all_gather every shard's (masked) hit grads + slots, scatter-add them
    locally on each replica. The gathered order is identical everywhere, so
    replicas stay consistent like the psum path, but the wire cost is
    O(world * n * D) instead of O(H * D) — the right trade for the L2 host
    tier, whose H2 is 10-100x the hot tier while n stays batch-sized.

    When fused, the gathered grads feed the dedup+adagrad kernel directly —
    the dense ``[H2, D]`` scatter buffer is never materialized (within-row
    accumulation happens in sorted-slot order, replica-identical)."""
    h = tier.keys.shape[0]
    d = g_u.shape[1]
    with obs.scope(obs.TIER_UPDATE):
        g_hit = g_u * hit_mask[:, None].astype(g_u.dtype)
        slots = jnp.where(hit_mask, slot, h).astype(jnp.int32)  # h = drop
        all_slots = lax.all_gather(slots, axes, tiled=True)      # [world*n]
        all_g = lax.all_gather(g_hit, axes, tiled=True)          # [world*n, D]
        if fused:
            rows2, acc2 = ops.dedup_adagrad(
                tier.rows, tier.acc, all_slots, all_g, all_slots < h, lr, eps,
                fused=True)
            return CacheState(tier.keys, rows2, acc2)
        g_hot = jnp.zeros((h, d), g_u.dtype).at[all_slots].add(all_g,
                                                               mode="drop")
        return _tier_adagrad(tier, g_hot, lr, eps)


def apply_sparse_grads_l2(
    w_shard: jnp.ndarray,
    acc_shard: jnp.ndarray,
    cache: Optional[CacheState],
    l2: CacheState,
    ctx: LookupCtx,
    g_u: jnp.ndarray,
    *,
    axes: Axes,
    world: int,
    lr: float,
    eps: float = 1e-8,
    cache_update: str = "psum",
    fused: bool = False,
    compress: str = "none",
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[CacheState], CacheState]:
    """Two-tier transposed path (L1 hot tier + L2 host tier).

    Misses (neither tier) ride the transposed Shuffle exactly as in
    ``apply_sparse_grads``. Tier-hit grads follow ``cache_update``:

    'psum'  — both tiers stay authoritative between flushes (exact). L1 hit
              grads are psum'd as usual (O(H1*D), small tier). For L2 the
              update picks the cheaper of two exact, replica-consistent
              reductions by *static* shapes: the dense O(H2*D) psum, or an
              all_gather of the batch's hit grads + slots applied locally
              (O(world*n*D)) — for a host tier 10-100x the hot tier, the
              gather is what keeps per-step comm proportional to the batch
              rather than the tier.
    'stale' — the union of L1 and L2 hits rides one second all_to_all to the
              owner shards; both tiers are read-only between flushes
              (Algorithm 1 bounded-staleness, master always exact).

    ``ctx`` must come from an L2-probing ``mp_lookup`` (``ctx.l2_hit`` set).
    """
    w_shard, acc_shard = _apply_miss_grads(w_shard, acc_shard, ctx, g_u,
                                           axes, world, lr, eps, fused,
                                           compress)
    if cache_update == "stale":
        both = ctx.hit | ctx.l2_hit
        w_shard, acc_shard = _route_hit_grads(w_shard, acc_shard, ctx, both,
                                              g_u, axes, world, lr, eps, fused,
                                              compress)
        return w_shard, acc_shard, cache, l2
    if cache is not None and cache.keys.shape[0] > 0:
        cache = _psum_into_tier(cache, ctx.hit, ctx.cache_slot, g_u, axes,
                                lr, eps, fused)
    h2 = l2.keys.shape[0]
    if h2 > 0:
        n, d = g_u.shape
        gather_elems = (world - 1) * n * (d + 1)   # hit grads + slots
        if gather_elems < h2 * d:
            l2 = _allgather_into_tier(l2, ctx.l2_hit, ctx.l2_slot, g_u,
                                      axes, lr, eps, fused)
        else:
            l2 = _psum_into_tier(l2, ctx.l2_hit, ctx.l2_slot, g_u, axes,
                                 lr, eps, fused)
    return w_shard, acc_shard, cache, l2


def _proj_adagrad(proj: ProjState, g_proj: jnp.ndarray, lr: float,
                  eps: float) -> ProjState:
    """Row-wise adagrad on the replicated projection from a psum'd (replica-
    consistent) gradient — the same update rule the tiers use, so the
    projection trains in lockstep with the rows it serves."""
    gsq = jnp.mean(jnp.square(g_proj), axis=-1, keepdims=True)
    acc_new = proj.acc + gsq
    upd = lr * g_proj / jnp.sqrt(acc_new + eps)
    return ProjState(proj.kernel - upd.astype(proj.kernel.dtype),
                     acc_new.astype(proj.acc.dtype))


def apply_sparse_grads_narrow(
    w_shard: jnp.ndarray,       # [rps, d] narrow master shard
    acc_shard: jnp.ndarray,
    cache: Optional[CacheState],  # L1 (wide rows)
    l2: Optional[CacheState],     # L2 (wide rows); None = narrow w/o L2 tier
    proj: ProjState,
    ctx: LookupCtx,               # from mp_lookup_narrow (narrow_rows set)
    g_u: jnp.ndarray,             # [n, D] grad wrt the (wide) unique rows
    *,
    axes: Axes,
    world: int,
    lr: float,
    eps: float = 1e-8,
    cache_update: str = "psum",
    fused: bool = False,
    compress: str = "none",
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[CacheState],
           Optional[CacheState], ProjState]:
    """Two-tier transposed path at heterogeneous widths.

    The wide cotangent is folded through ``proj^T`` ONCE (``g_n = g_u @
    proj.kernel.T``, one MXU pass); routed hops then carry the narrow
    gradient — the same ``world*cap*d`` wire the forward used — through the
    unchanged (compressible) ``_apply_miss_grads`` / ``_route_hit_grads``
    machinery, and the owner-side dedup+adagrad updates the narrow master.
    Tier-hit grads update the WIDE tiers exactly as in
    ``apply_sparse_grads_l2`` (the tiers are authoritative full-width rows in
    'psum' mode). The projection's own gradient is one ``narrow^T @ g_u``
    matmul off the lookup's residual (only routed positions contribute — the
    chain rule: tier hits never passed through ``proj``), psum'd so replicas
    stay bit-identical, then adagrad'd.
    """
    g_n = (g_u @ proj.kernel.T).astype(g_u.dtype)   # [n, d]
    w_shard, acc_shard = _apply_miss_grads(w_shard, acc_shard, ctx, g_n,
                                           axes, world, lr, eps, fused,
                                           compress)
    if cache_update == "stale":
        both = ctx.hit if ctx.l2_hit is None else (ctx.hit | ctx.l2_hit)
        w_shard, acc_shard = _route_hit_grads(w_shard, acc_shard, ctx, both,
                                              g_n, axes, world, lr, eps, fused,
                                              compress)
    else:
        if cache is not None and cache.keys.shape[0] > 0:
            cache = _psum_into_tier(cache, ctx.hit, ctx.cache_slot, g_u, axes,
                                    lr, eps, fused)
        h2 = 0 if l2 is None else l2.keys.shape[0]
        if h2 > 0 and ctx.l2_hit is not None:
            n, d = g_u.shape
            gather_elems = (world - 1) * n * (d + 1)
            if gather_elems < h2 * d:
                l2 = _allgather_into_tier(l2, ctx.l2_hit, ctx.l2_slot, g_u,
                                          axes, lr, eps, fused)
            else:
                l2 = _psum_into_tier(l2, ctx.l2_hit, ctx.l2_slot, g_u, axes,
                                     lr, eps, fused)
    g_proj = lax.psum(ctx.narrow_rows.T @ g_u, axes)   # [d, D]
    proj = _proj_adagrad(proj, g_proj, lr, eps)
    return w_shard, acc_shard, cache, l2, proj


# ---------------------------------------------------------------------------
# frequency statistics + HybridHash flush (Algorithm 1)
# ---------------------------------------------------------------------------


def count_frequencies(counts_shard: jnp.ndarray, ctx: LookupCtx) -> jnp.ndarray:
    """Owner-side FCounter update from the ids received this step.

    Counts *routed* queries; for the single-tier path, cache hits are counted
    via their last routed appearance before entering the hot set (good enough
    for top-k drift on a small L1, and the decay in ``flush_cache`` re-ranks
    over time). Two-tier strategies must additionally count tier hits
    (``count_hit_frequencies``): with an L2 covering a large table fraction,
    the uncounted resident mass would otherwise decay below the routed tail
    and the flush would churn-evict genuinely hot rows.
    """
    with obs.scope(obs.COUNT_FREQUENCIES):
        return counts_shard.at[ctx.recv_local.reshape(-1)].add(
            ctx.recv_valid.reshape(-1).astype(counts_shard.dtype))


def count_hit_frequencies(counts_shard: jnp.ndarray, ctx: LookupCtx,
                          hit_mask: jnp.ndarray, *, axes: Axes,
                          world: int) -> jnp.ndarray:
    """FCounter update for tier-served lookups, with zero communication.

    Tier hits never ride the Shuffle, so the owner shard does not observe
    them. Instead of psum'ing per-slot hit counts (O(H) ints per step — the
    very cost the tier avoids), each shard scatters the hits *it* issued into
    its own slice of the FCounter, weighted by ``world``: a shard owns a
    scrambled row with probability 1/world, so the weighted local sample is
    an unbiased (Horvitz-Thompson) estimate of the global hit count — exact
    at world=1, ranking-preserving in expectation at scale.
    """
    rps = counts_shard.shape[0]
    with obs.scope(obs.COUNT_FREQUENCIES):
        my = lax.axis_index(axes).astype(jnp.int32)
        local = ctx.uniq.astype(jnp.int32) - my * rps
        ok = hit_mask & (local >= 0) & (local < rps)
        safe = jnp.where(ok, jnp.clip(local, 0, rps - 1), rps)
        inc = (jnp.asarray(world, counts_shard.dtype)
               * ok.astype(counts_shard.dtype))
        return counts_shard.at[safe].add(inc, mode="drop")


def cache_hit_count(ctx: LookupCtx) -> jnp.ndarray:
    return jnp.sum(ctx.hit)


def l2_hit_count(ctx: LookupCtx) -> jnp.ndarray:
    if ctx.l2_hit is None:
        return jnp.zeros((), jnp.int32)
    return jnp.sum(ctx.l2_hit)


def flush_cache(
    w_shard: jnp.ndarray,
    acc_shard: jnp.ndarray,
    counts_shard: jnp.ndarray,
    cache: CacheState,
    *,
    axes: Axes,
    world: int,
    decay: float = 0.5,
    write_back: bool = True,   # False for cache_update='stale' (master is exact)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, CacheState]:
    """Periodic HybridHash flush (Algorithm 1 L23-26), replica-consistent.

    1. write back hot rows + optimizer state to owner shards (no comm: the
       hot tier is replicated, owners take their slice) — 'psum' mode only;
    2. select the new global top-H by frequency (all_gather of local top-H);
    3. load the new hot set (psum of owner contributions).
    """
    rps, d = w_shard.shape
    h = cache.keys.shape[0]
    rows_padded = rps * world
    my = lax.axis_index(axes).astype(jnp.int32)
    base = my * rps

    # ---- 1. write back ------------------------------------------------------
    if write_back:
        w_shard, acc_shard = _write_back_tier(w_shard, acc_shard, cache,
                                              base, rps, rows_padded)

    # ---- 2. global top-H ----------------------------------------------------
    # scrambled ids spread the hot set ~uniformly over shards, so the global
    # top-H is inside the union of per-shard top-(4H/world) w.h.p. — keeps the
    # all_gather at 4H instead of world*H.
    k_local = min(rps, max(32, (4 * h + world - 1) // world))
    lvals, lidx = lax.top_k(counts_shard, k_local)
    gids = base + lidx.astype(jnp.int32)
    all_vals = lax.all_gather(lvals, axes, tiled=True)   # [world*k_local]
    all_ids = lax.all_gather(gids, axes, tiled=True)
    tvals, tidx = lax.top_k(all_vals, h)
    new_keys = jnp.sort(jnp.where(tvals > 0, all_ids[tidx], rows_padded))

    # ---- 3. load new hot set ------------------------------------------------
    new_cache = _load_tier(w_shard, acc_shard, new_keys, base, rps,
                           rows_padded, axes)

    counts_shard = (counts_shard.astype(jnp.float32) * decay).astype(counts_shard.dtype)
    return w_shard, acc_shard, counts_shard, new_cache


def _write_back_tier(w_shard, acc_shard, tier: CacheState, base, rps: int,
                     rows_padded: int):
    """Owner shards take their slice of a replicated tier (no comm)."""
    local = tier.keys - base
    mine = (local >= 0) & (local < rps) & (tier.keys < rows_padded)
    safe_idx = jnp.where(mine, jnp.clip(local, 0, rps - 1), rps)
    w_shard = w_shard.at[safe_idx].set(tier.rows.astype(w_shard.dtype), mode="drop")
    acc_shard = acc_shard.at[safe_idx].set(tier.acc.astype(acc_shard.dtype), mode="drop")
    return w_shard, acc_shard


def _load_tier(w_shard, acc_shard, keys, base, rps: int, rows_padded: int,
               axes: Axes) -> CacheState:
    """psum of owner contributions: master rows -> a fresh replicated tier."""
    nlocal = keys - base
    nmine = (nlocal >= 0) & (nlocal < rps) & (keys < rows_padded)
    nclip = jnp.clip(nlocal, 0, rps - 1)
    contrib_w = jnp.take(w_shard, nclip, axis=0) * nmine[:, None].astype(w_shard.dtype)
    contrib_a = jnp.take(acc_shard, nclip, axis=0) * nmine[:, None].astype(acc_shard.dtype)
    return CacheState(keys, lax.psum(contrib_w, axes), lax.psum(contrib_a, axes))


def flush_cache_l2(
    w_shard: jnp.ndarray,
    acc_shard: jnp.ndarray,
    counts_shard: jnp.ndarray,
    cache: CacheState,
    l2: CacheState,
    *,
    axes: Axes,
    world: int,
    decay: float = 0.5,
    write_back: bool = True,   # False for cache_update='stale'
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, CacheState, CacheState]:
    """Two-tier HybridHash flush: one global frequency ranking fills both tiers.

    1. write back L1 and L2 rows + optimizer state to owner shards ('psum'
       mode only — in 'stale' mode the master is already exact);
    2. select the global top-(H1+H2) rows by FCounter frequency; the hottest
       H1 become the new L1 hot tier, the next H2 the new L2 host tier — so
       the tiers are disjoint by construction and L2 holds exactly the skew
       tail that overflows the device-resident budget;
    3. reload both tiers from the (just-synced) master shards.

    Degenerate tiers (0 rows) are handled: an empty L1 makes this equivalent
    to a single-tier flush of L2 and vice versa.
    """
    rps, d = w_shard.shape
    h1, h2 = cache.keys.shape[0], l2.keys.shape[0]
    h = h1 + h2
    rows_padded = rps * world
    my = lax.axis_index(axes).astype(jnp.int32)
    base = my * rps

    # ---- 1. write back ------------------------------------------------------
    if write_back:
        w_shard, acc_shard = _write_back_tier(w_shard, acc_shard, cache,
                                              base, rps, rows_padded)
        w_shard, acc_shard = _write_back_tier(w_shard, acc_shard, l2,
                                              base, rps, rows_padded)

    # ---- 2. one global top-(H1+H2), split by rank ---------------------------
    k_local = min(rps, max(32, (4 * h + world - 1) // world))
    lvals, lidx = lax.top_k(counts_shard, k_local)
    gids = base + lidx.astype(jnp.int32)
    all_vals = lax.all_gather(lvals, axes, tiled=True)
    all_ids = lax.all_gather(gids, axes, tiled=True)
    tvals, tidx = lax.top_k(all_vals, h)
    keys_ranked = jnp.where(tvals > 0, all_ids[tidx], rows_padded)
    keys1 = jnp.sort(keys_ranked[:h1])   # hottest H1 -> device tier
    keys2 = jnp.sort(keys_ranked[h1:])   # next H2    -> host tier

    # ---- 3. reload both tiers from master -----------------------------------
    new_l1 = _load_tier(w_shard, acc_shard, keys1, base, rps, rows_padded, axes)
    new_l2 = _load_tier(w_shard, acc_shard, keys2, base, rps, rows_padded, axes)

    counts_shard = (counts_shard.astype(jnp.float32) * decay).astype(counts_shard.dtype)
    return w_shard, acc_shard, counts_shard, new_l1, new_l2


def proj_pinv(proj_kernel: jnp.ndarray, ridge: float = 1e-6) -> jnp.ndarray:
    """Regularized right pseudo-inverse of the ``[d, D]`` up-projection:
    ``pinv = P^T (P P^T + ridge*I)^{-1}``, a ``[D, d]`` map with
    ``narrow @ P @ pinv ~= narrow``. At init the projection's rows are
    orthonormal, so ``pinv ~= P^T`` exactly; the ridge keeps the ``[d, d]``
    solve well-posed as the kernel trains away from orthonormality. Used to
    *narrow* wide rows (tier write-back, wide->narrow migration)."""
    nd = proj_kernel.shape[0]
    gram = proj_kernel @ proj_kernel.T
    eye = jnp.eye(nd, dtype=proj_kernel.dtype)
    return proj_kernel.T @ jnp.linalg.solve(gram + ridge * eye, eye)


def _write_back_tier_narrow(w_shard, acc_shard, tier: CacheState, pinv,
                            base, rps: int, rows_padded: int):
    """Owner shards take their slice of a replicated WIDE tier, narrowed
    through the projection's pseudo-inverse into the narrow master."""
    local = tier.keys - base
    mine = (local >= 0) & (local < rps) & (tier.keys < rows_padded)
    safe_idx = jnp.where(mine, jnp.clip(local, 0, rps - 1), rps)
    nrows = tier.rows @ pinv                                  # [H, d]
    w_shard = w_shard.at[safe_idx].set(nrows.astype(w_shard.dtype), mode="drop")
    acc_shard = acc_shard.at[safe_idx].set(tier.acc.astype(acc_shard.dtype),
                                           mode="drop")
    return w_shard, acc_shard


def _load_tier_widened(w_shard, acc_shard, keys, proj_kernel, base, rps: int,
                       rows_padded: int, axes: Axes) -> CacheState:
    """psum of owner contributions at the narrow width, then ONE widening
    matmul on the assembled tier — narrow master rows -> a fresh replicated
    wide tier (never a per-id widen)."""
    nlocal = keys - base
    nmine = (nlocal >= 0) & (nlocal < rps) & (keys < rows_padded)
    nclip = jnp.clip(nlocal, 0, rps - 1)
    contrib_n = jnp.take(w_shard, nclip, axis=0) * nmine[:, None].astype(w_shard.dtype)
    contrib_a = jnp.take(acc_shard, nclip, axis=0) * nmine[:, None].astype(acc_shard.dtype)
    narrow = lax.psum(contrib_n, axes)
    return CacheState(keys, (narrow @ proj_kernel).astype(w_shard.dtype),
                      lax.psum(contrib_a, axes))


def _carry_exact_rows(tier: CacheState, old1: CacheState, old2: CacheState,
                      rows_padded: int) -> CacheState:
    """Keep ids that stayed tier-resident at their EXACT wide rows: a hot id
    that survives the re-rank must not round-trip through the rank-``d``
    projection (which would crush the component of its row orthogonal to the
    projection's span every flush). Freshly promoted ids keep their widened
    (``narrow @ P``) reload."""
    rows, acc = tier.rows, tier.acc
    for old in (old1, old2):
        if old.keys.shape[0] == 0:
            continue
        p = jnp.searchsorted(old.keys, tier.keys).astype(jnp.int32)
        pc = jnp.clip(p, 0, old.keys.shape[0] - 1)
        found = (old.keys[pc] == tier.keys) & (tier.keys < rows_padded)
        rows = jnp.where(found[:, None], jnp.take(old.rows, pc, axis=0), rows)
        acc = jnp.where(found[:, None], jnp.take(old.acc, pc, axis=0), acc)
    return CacheState(tier.keys, rows, acc)


def flush_cache_narrow(
    w_shard: jnp.ndarray,       # [rps, d] narrow master shard
    acc_shard: jnp.ndarray,
    counts_shard: jnp.ndarray,
    cache: CacheState,          # L1 (wide)
    l2: CacheState,             # L2 (wide)
    proj_kernel: jnp.ndarray,   # [d, D]
    *,
    axes: Axes,
    world: int,
    decay: float = 0.5,
    write_back: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, CacheState, CacheState]:
    """Two-tier flush at heterogeneous widths — the re-widening lifecycle:

    1. write back both WIDE tiers into the narrow master through the
       projection's pseudo-inverse ('psum' mode; adagrad scalars pass through
       exactly);
    2. one global top-(H1+H2) frequency ranking, split hottest-H1 / next-H2
       (identical to ``flush_cache_l2``);
    3. reload both tiers *widened* (``narrow @ P``, one matmul per tier) —
       but ids that stayed tier-resident keep their exact pre-flush wide rows
       (``_carry_exact_rows``): only ids crossing the hot/cold boundary pass
       through the projection, so a persistently hot id trains at the full
       width indefinitely while a cooled id is narrowed to its best
       rank-``d`` approximation.

    In 'stale' mode (``write_back=False``) the narrow master is already
    exact and the tiers are read-only widened copies — no write-back, and no
    exact-carry either (the master is the single source of truth).
    """
    rps, nd = w_shard.shape
    h1, h2 = cache.keys.shape[0], l2.keys.shape[0]
    h = h1 + h2
    rows_padded = rps * world
    my = lax.axis_index(axes).astype(jnp.int32)
    base = my * rps

    if write_back:
        pinv = proj_pinv(proj_kernel)
        w_shard, acc_shard = _write_back_tier_narrow(w_shard, acc_shard, cache,
                                                     pinv, base, rps, rows_padded)
        w_shard, acc_shard = _write_back_tier_narrow(w_shard, acc_shard, l2,
                                                     pinv, base, rps, rows_padded)

    k_local = min(rps, max(32, (4 * h + world - 1) // world))
    lvals, lidx = lax.top_k(counts_shard, k_local)
    gids = base + lidx.astype(jnp.int32)
    all_vals = lax.all_gather(lvals, axes, tiled=True)
    all_ids = lax.all_gather(gids, axes, tiled=True)
    tvals, tidx = lax.top_k(all_vals, h)
    keys_ranked = jnp.where(tvals > 0, all_ids[tidx], rows_padded)
    keys1 = jnp.sort(keys_ranked[:h1])
    keys2 = jnp.sort(keys_ranked[h1:])

    new_l1 = _load_tier_widened(w_shard, acc_shard, keys1, proj_kernel,
                                base, rps, rows_padded, axes)
    new_l2 = _load_tier_widened(w_shard, acc_shard, keys2, proj_kernel,
                                base, rps, rows_padded, axes)
    if write_back:
        new_l1 = _carry_exact_rows(new_l1, cache, l2, rows_padded)
        new_l2 = _carry_exact_rows(new_l2, cache, l2, rows_padded)

    counts_shard = (counts_shard.astype(jnp.float32) * decay).astype(counts_shard.dtype)
    return w_shard, acc_shard, counts_shard, new_l1, new_l2


# ---------------------------------------------------------------------------
# baseline strategies (paper §II-C) for comparison benchmarks
# ---------------------------------------------------------------------------


def ps_lookup(table_shard: jnp.ndarray, ids: jnp.ndarray, *, axes: Axes, world: int
              ) -> jnp.ndarray:
    """PS/DP-style lookup: all_gather ids, psum partial rows (no routing, no
    dedup, no cache). Communication O(world * n * D) vs O(n * D) for the
    PICASSO path — this is the fragmentary baseline the paper beats."""
    rps, d = table_shard.shape
    my = lax.axis_index(axes).astype(jnp.int32)
    base = my * rps
    all_ids = lax.all_gather(ids, axes, tiled=True)         # [world*n]
    local = all_ids - base
    ok = (local >= 0) & (local < rps)
    part = jnp.take(table_shard, jnp.clip(local, 0, rps - 1), axis=0)
    part = part * ok[:, None].astype(part.dtype)
    full = lax.psum(part, axes)                              # [world*n, D]
    n = ids.shape[0]
    return lax.dynamic_slice_in_dim(full, my * n, n, axis=0)


def mp_lookup_nodedup(
    table_shard: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    axes: Axes,
    world: int,
    capacity: int,
) -> Tuple[jnp.ndarray, LookupCtx]:
    """Model-parallel Shuffle *without* K-Packed dedup (paper §II-C baseline).

    Every raw id rides the all_to_all — duplicates each consume their own
    bucket slot, so the wire payload is O(n) rows instead of O(uniq). This is
    the 'fragmentary op sequence' PICASSO's Unique&Partition fusion beats; it
    exists so ``bench_throughput`` can price the dedup itself.

    Returns the same ``(rows, LookupCtx)`` contract as ``mp_lookup`` (ids are
    sorted, not uniqued — ``inv`` maps original positions to sorted slots, so
    pooling and the transposed gradient path compose unchanged; the owner-side
    dedup+adagrad scatter sums the duplicate rows' grads, keeping training
    math identical to the deduped path whenever nothing overflows). Needs
    ``capacity >= n`` per owner in the worst case — plan with
    ``exact_capacity=True`` for lossless parity runs.
    """
    rps, d = table_shard.shape
    n = ids.shape[0]
    order = jnp.argsort(ids)
    s = ids[order]                                  # sorted, duplicates kept
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    every = jnp.ones((n,), bool)
    r = partition(s, every, rps, world, capacity)

    send_ids = jnp.full((world * capacity,), -1, jnp.int32)
    send_ids = send_ids.at[r.send_slot].set(s.astype(jnp.int32), mode="drop")
    recv_ids = _a2a(send_ids.reshape(world, capacity), axes)

    my = lax.axis_index(axes)
    base = my.astype(jnp.int32) * rps
    recv_valid = recv_ids >= 0
    recv_local = jnp.clip(recv_ids - base, 0, rps - 1)

    served = jnp.take(table_shard, recv_local.reshape(-1), axis=0)
    served = served * recv_valid.reshape(-1, 1).astype(served.dtype)
    back = _a2a(served.reshape(world, capacity, d), axes).reshape(
        world * capacity, d)
    take_idx = jnp.minimum(r.send_slot, world * capacity - 1)
    rows = jnp.take(back, take_idx, axis=0) * r.kept[:, None].astype(served.dtype)

    ctx = LookupCtx(
        uniq=s, inv=inv, uvalid=every,
        hit=jnp.zeros((n,), bool), cache_slot=jnp.zeros((n,), jnp.int32),
        routing=r, recv_ids=recv_ids, recv_local=recv_local,
        recv_valid=recv_valid,
    )
    return rows, ctx
