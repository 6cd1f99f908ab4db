"""Pluggable lookup strategies for the EmbeddingEngine (paper §II-C, §IV).

A ``LookupStrategy`` owns the per-group sparse hot path: how packed IDs turn
into rows (forward) and how row gradients turn into table updates (backward).
The engine is strategy-agnostic; everything below the ``lookup`` /
``apply_grads`` boundary — collectives, dedup, caching — is a strategy detail.

Strategies bind to *groups*, not to the whole engine: the plan carries a
``gid -> name`` assignment (``PicassoPlan.strategy``, compiled by the
``repro.core.assign`` cost model or spelled out by the user) and the engine
dispatches each packed group to its own instance. A plan can therefore
PS-replicate its tiny tables while routing + caching the big skewed ones in
the same step — every strategy here must stay exact under that mixing (the
parity suite trains mixed and pure engines against each other).

Concrete strategies (selected by name through the registry):

``picasso``
    The full system: K-Packed Unique&Partition, fixed-capacity all_to_all
    Shuffle, HybridHash hot tier on the read and update paths.
``hybrid``
    MP all_to_all routing per group, but no HybridHash tier: same Shuffle,
    every unique goes to its owner shard every step. Packing is a *plan*
    choice, not a strategy choice — the paper's full intermediate baseline
    ("MP without packing or cache", §II-C) is this strategy on a plan built
    with ``enable_packing=False`` (one fragmentary op per table).
``ps``
    PS-style all_gather + psum lookups (the fragmentary baseline): no routing,
    no dedup, no cache; communication O(world * n * D).
``picasso_l2``
    The picasso path with a second, host-memory cache tier (HugeCTR-style
    hierarchical parameter cache) behind the hot tier: unique ids probe L1
    (device-resident top-H1 rows), then L2 (host-resident next-H2 rows), and
    only the remainder rides the all_to_all Shuffle. Write-back and re-rank
    happen at flush time for both tiers at once. Cold or absent L2 is
    bitwise-identical to ``picasso``.
``picasso_narrow``
    The picasso_l2 path with frequency-adaptive widths: tier-resident (hot)
    ids are served full-width ``D`` rows on device while the cold master
    stores/routes narrow ``d = plan.narrow_dim`` rows, projected up at
    lookup by a learned per-group ``[d, D]`` map. With no narrow budget the
    strategy is bitwise-identical to ``picasso_l2``.
``mp_nodedup``
    The Shuffle *without* K-Packed dedup: every raw id (duplicates included)
    rides the all_to_all. Prices the Unique&Partition fusion itself; exact
    vs ``picasso`` under ``exact_capacity`` plans.
``allgather_rows``
    Dedup'd replication baseline: unique ids are served by ``ps_lookup`` and
    row grads ride one (optionally compressed) all_gather back. Sits between
    ``ps`` (no dedup) and the routed strategies in wire cost.

Every MP strategy's routed gradient hop honours ``grad_compress``
('none' | 'fp16' | 'topk', see ``repro.optim.grad_compression``): the
all_to_all / all_gather payload is compressed on the wire and expanded on
the owner side; 'none' keeps training bitwise-identical.

New workloads (multi-task serving, frequency-adaptive dims, other baselines)
land as one ``@register_strategy`` class instead of a new copy of the loop.
A strategy advertises its cache behaviour through class attributes the
engine gates on per group: ``uses_cache`` (L1 participates where the plan
budgets ``cache_rows``), ``uses_l2`` (L2 participates where the plan budgets
``l2_rows`` *and* L1 is active), and ``extra_metric_keys`` (extra static
metric names ``tier_metrics`` reports, e.g. per-tier hit counters).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import packed_embedding as pe
from repro.embedding.state import EmbeddingState
from repro.optim import grad_compression as gcomp

Axes = Union[str, Tuple[str, ...]]

_REGISTRY: Dict[str, Type["LookupStrategy"]] = {}


def register_strategy(name: str):
    """Class decorator: make a LookupStrategy selectable by name."""

    def deco(cls: Type["LookupStrategy"]) -> Type["LookupStrategy"]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_strategy(name: str) -> Type["LookupStrategy"]:
    """Resolve a strategy class by name; unknown names raise with the menu."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown lookup strategy {name!r}; available: "
            f"{', '.join(available_strategies())}") from None


class LookupStrategy:
    """Base class: per-group sparse forward/backward, parameterized once.

    Subclasses implement ``lookup`` and ``apply_grads``; both receive the
    group's EmbeddingState and a group id (to index the static plan data) and
    must keep all shapes static — they run inside ``shard_map``.
    """

    name = "base"
    uses_cache = False        # whether the HybridHash hot tier participates
    uses_l2 = False           # whether the L2 host tier participates
    uses_routing_ctx = True   # ctx carries Shuffle routing (MP strategies)
    extra_metric_keys: Tuple[str, ...] = ()  # keys tier_metrics reports

    def __init__(self, *, axes: Axes, world: int, capacity: Dict[int, int],
                 lr: float = 0.05, eps: float = 1e-8,
                 cache_update: str = "psum", use_fused: bool = False,
                 grad_compress: str = "none"):
        self.axes = axes
        self.world = world
        self.capacity = capacity
        self.lr = lr
        self.eps = eps
        self.cache_update = cache_update
        # static (resolved) switch: True routes every hot-path op this
        # strategy issues — tier probes, the dedup+adagrad scatter — through
        # the fused Pallas kernels (see repro.kernels.ops.resolve_fused)
        self.use_fused = use_fused
        # wire compression of the routed sparse-gradient payload
        self.grad_compress = gcomp.validate_routed_mode(grad_compress)

    # ----------------------------------------------------------------- fwd
    def lookup(self, st: EmbeddingState, gid: int, ids: jnp.ndarray,
               *, cache_on: bool = False, l2_on: bool = False
               ) -> Tuple[jnp.ndarray, Any]:
        """ids [n] -> (rows [n, D], ctx). ``ctx.inv`` maps positions to rows."""
        raise NotImplementedError

    # ----------------------------------------------------------------- bwd
    def apply_grads(self, st: EmbeddingState, gid: int, ctx: Any,
                    g_rows: jnp.ndarray, *, cache_on: bool = False,
                    l2_on: bool = False
                    ) -> Tuple[EmbeddingState, jnp.ndarray, jnp.ndarray]:
        """Row grads -> updated state. Returns (state, overflow, cache_hits).

        ``cache_hits`` counts ids served by *any* cache tier (L1 + L2 for
        two-tier strategies); ``tier_metrics`` breaks it down.
        """
        raise NotImplementedError

    # ------------------------------------------------------------- metrics
    def distinct_ids(self, ctx: Any) -> jnp.ndarray:
        """The distinct ids this lookup worked on after its dedup (an int32
        scalar); zero for a strategy that does not dedup."""
        return jnp.zeros((), jnp.int32)

    def tier_metrics(self, ctx: Any) -> Dict[str, jnp.ndarray]:
        """Per-tier counters for this lookup, keyed by ``extra_metric_keys``.

        Must return exactly ``extra_metric_keys`` (int32 scalars) for every
        ctx this strategy produced — the keys are static metric pytree
        entries, so they cannot depend on whether a tier was warm.
        """
        return {}


@register_strategy("picasso")
class PicassoStrategy(LookupStrategy):
    """Full packed/interleaved/cached path (paper §III-B/D).

    Forward: fixed-shape unique -> cache probe -> partition -> all_to_all
    Shuffle -> local gather -> Shuffle back -> Stitch (+ hot-tier merge).
    Backward: transposed Shuffle for miss grads; hit grads psum'd into the
    replicated hot tier ('psum') or routed to owners ('stale'); FCounter
    update on the owner side.
    """

    uses_cache = True

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        return pe.mp_lookup(
            st.w, ids, axes=self.axes, world=self.world,
            capacity=self.capacity[gid],
            hot_keys=st.cache.keys if cache_on else None,
            hot_rows=st.cache.rows if cache_on else None,
            fused=self.use_fused)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        w2, acc2, cache2 = pe.apply_sparse_grads(
            st.w, st.acc, st.cache if cache_on else None, ctx, g_rows,
            axes=self.axes, world=self.world, lr=self.lr, eps=self.eps,
            cache_update=self.cache_update, fused=self.use_fused,
            compress=self.grad_compress)
        counts2 = pe.count_frequencies(st.counts, ctx)
        st2 = EmbeddingState(w=w2, acc=acc2, counts=counts2,
                             cache=cache2 if cache2 is not None else st.cache,
                             l2=st.l2)  # preserve an (unused) L2 tier as-is
        return (st2, ctx.routing.overflow.astype(jnp.int32),
                pe.cache_hit_count(ctx).astype(jnp.int32))

    def distinct_ids(self, ctx):
        return ctx.n_uniq


@register_strategy("hybrid")
class HybridStrategy(PicassoStrategy):
    """MP all_to_all routing without the HybridHash tier (paper §II-C).

    Same Shuffle/Stitch machinery as PICASSO, but the hot tier never
    participates: every unique id is routed to its owner shard every step.
    Isolates the cache's contribution in ablations; pair with a plan built
    with ``enable_packing=False`` to reproduce the paper's full "MP without
    packing or cache" intermediate baseline.
    """

    uses_cache = False

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        return super().lookup(st, gid, ids, cache_on=False)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        return super().apply_grads(st, gid, ctx, g_rows, cache_on=False)


@register_strategy("picasso_l2")
class PicassoL2Strategy(PicassoStrategy):
    """PICASSO with a hierarchical parameter cache: L1 hot tier + L2 host tier.

    HugeCTR-style multi-level caching behind the replicated hot tier: the
    fixed-shape unique set probes the device-resident L1 first, L1 misses
    probe the (much larger) host-memory L2, and only ids absent from both
    tiers ride the all_to_all Shuffle. On TPU the L2 leaves live in pinned
    host memory (``pin_l2_to_host``) — a hit costs one host DMA instead of
    an ICI round trip; the repro keeps the arrays replicated so the math is
    identical either way.

    Backward follows ``cache_update`` exactly like the L1 tier: 'psum' keeps
    both replicated tiers authoritative between flushes (tier-hit grads are
    all-reduced into their own tier); 'stale' routes the union of tier hits
    to the owner shards and leaves both tiers read-only. The two-tier flush
    (``pe.flush_cache_l2``) writes both tiers back (psum mode), re-ranks one
    global frequency top-(H1+H2), and splits it: hottest H1 rows -> L1,
    next H2 -> L2 — the tiers stay disjoint by construction.

    With ``l2_on=False`` (no plan budget / ``use_l2=False`` / L1 disabled)
    every path is bitwise-identical to ``picasso``. With the tier on but
    cold, lookups, pooled outputs, and sparse updates are still bitwise
    identical — but the FCounter is intentionally NOT: this strategy also
    counts tier-served hits (``count_hit_frequencies``, the anti-churn
    correction), so once L1 warms, flush rankings — and through them later
    numerics — may diverge from plain picasso by design.
    """

    uses_l2 = True
    extra_metric_keys = ("cache_hits/l1", "cache_hits/l2")

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        if not l2_on or st.l2 is None:
            return super().lookup(st, gid, ids, cache_on=cache_on)
        return pe.mp_lookup(
            st.w, ids, axes=self.axes, world=self.world,
            capacity=self.capacity[gid],
            hot_keys=st.cache.keys if cache_on else None,
            hot_rows=st.cache.rows if cache_on else None,
            l2_keys=st.l2.keys, l2_rows=st.l2.rows,
            fused=self.use_fused)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        if not l2_on or st.l2 is None or ctx.l2_hit is None:
            return super().apply_grads(st, gid, ctx, g_rows, cache_on=cache_on)
        w2, acc2, cache2, l22 = pe.apply_sparse_grads_l2(
            st.w, st.acc, st.cache if cache_on else None, st.l2, ctx, g_rows,
            axes=self.axes, world=self.world, lr=self.lr, eps=self.eps,
            cache_update=self.cache_update, fused=self.use_fused,
            compress=self.grad_compress)
        counts2 = pe.count_frequencies(st.counts, ctx)
        # tier-served ids never route, so they must be counted explicitly or
        # the flush ranking churn-evicts the resident (hottest) rows
        counts2 = pe.count_hit_frequencies(counts2, ctx, ctx.hit | ctx.l2_hit,
                                           axes=self.axes, world=self.world)
        st2 = EmbeddingState(w=w2, acc=acc2, counts=counts2,
                             cache=cache2 if cache2 is not None else st.cache,
                             l2=l22)
        hits = pe.cache_hit_count(ctx) + pe.l2_hit_count(ctx)
        return (st2, ctx.routing.overflow.astype(jnp.int32),
                hits.astype(jnp.int32))

    def tier_metrics(self, ctx):
        return {"cache_hits/l1": pe.cache_hit_count(ctx).astype(jnp.int32),
                "cache_hits/l2": pe.l2_hit_count(ctx).astype(jnp.int32)}


@register_strategy("picasso_narrow")
class PicassoNarrowStrategy(PicassoL2Strategy):
    """Frequency-adaptive embedding widths: hot ids wide, cold ids narrow.

    The two-tier picasso_l2 machinery with a heterogeneous-width master: ids
    resident in either cache tier are served full-width ``D`` rows exactly as
    in ``picasso_l2``, while the cold remainder rides the Shuffle at the
    planned narrow width ``d = plan.narrow_dim`` — the master shard stores
    ``[rows, d]``, both routed hops carry ``d``-wide payloads, and one fused
    ``ops.gather_project`` pass stitches the routed-back narrow rows up
    through a learned per-group ``[d, D]`` projection (``st.proj``).

    Backward mirrors the forward wire: the wide cotangent folds through
    ``proj^T`` once, routed grads travel narrow, tier-hit grads update the
    wide tiers, and the projection trains from the lookup's narrow residual
    (psum'd, adagrad'd) — see ``pe.apply_sparse_grads_narrow``. The flush
    (``pe.flush_cache_narrow``) implements the re-widening lifecycle: ids
    heating into a tier are widened ``narrow @ P``, ids staying resident
    keep their exact wide rows, cooling ids narrow through the projection's
    pseudo-inverse.

    Degenerate case: a plan that doesn't actually narrow this group
    (``narrow_dim >= D``, or the assignment routed it elsewhere) initializes
    no projection (``st.proj is None``) and every path below delegates to
    ``PicassoL2Strategy`` — bitwise-identical to ``picasso_l2``.
    """

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        if st.proj is None:  # not narrowed on this plan: exact L2 path
            return super().lookup(st, gid, ids, cache_on=cache_on, l2_on=l2_on)
        with_l2 = l2_on and st.l2 is not None
        return pe.mp_lookup_narrow(
            st.w, ids, proj=st.proj.kernel, axes=self.axes, world=self.world,
            capacity=self.capacity[gid],
            hot_keys=st.cache.keys if cache_on else None,
            hot_rows=st.cache.rows if cache_on else None,
            l2_keys=st.l2.keys if with_l2 else None,
            l2_rows=st.l2.rows if with_l2 else None,
            fused=self.use_fused)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        if st.proj is None:
            return super().apply_grads(st, gid, ctx, g_rows,
                                       cache_on=cache_on, l2_on=l2_on)
        with_l2 = l2_on and st.l2 is not None and ctx.l2_hit is not None
        w2, acc2, cache2, l22, proj2 = pe.apply_sparse_grads_narrow(
            st.w, st.acc, st.cache if cache_on else None,
            st.l2 if with_l2 else None, st.proj, ctx, g_rows,
            axes=self.axes, world=self.world, lr=self.lr, eps=self.eps,
            cache_update=self.cache_update, fused=self.use_fused,
            compress=self.grad_compress)
        counts2 = pe.count_frequencies(st.counts, ctx)
        if cache_on or with_l2:
            both = (ctx.hit if ctx.l2_hit is None
                    else ctx.hit | ctx.l2_hit)
            counts2 = pe.count_hit_frequencies(counts2, ctx, both,
                                               axes=self.axes,
                                               world=self.world)
        st2 = EmbeddingState(w=w2, acc=acc2, counts=counts2,
                             cache=cache2 if cache2 is not None else st.cache,
                             l2=l22 if with_l2 else st.l2,
                             proj=proj2)
        hits = pe.cache_hit_count(ctx) + pe.l2_hit_count(ctx)
        return (st2, ctx.routing.overflow.astype(jnp.int32),
                hits.astype(jnp.int32))


class PSCtx(NamedTuple):
    """Context of a PS lookup: rows are per-id, so ``inv`` is the identity."""

    inv: jnp.ndarray   # [n] == arange(n)
    ids: jnp.ndarray   # [n] original packed ids (backward needs them)


@register_strategy("ps")
class PSStrategy(LookupStrategy):
    """PS/DP-style baseline (paper §II-C): all_gather ids, psum partial rows.

    No routing, no dedup, no cache — the fragmentary pattern PICASSO beats.
    Backward all_gathers per-id grads and scatters into the local shard.
    """

    uses_cache = False
    uses_routing_ctx = False

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        rows = pe.ps_lookup(st.w, ids, axes=self.axes, world=self.world)
        n = ids.shape[0]
        return rows, PSCtx(inv=jnp.arange(n, dtype=jnp.int32), ids=ids)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        rps = st.w.shape[0]
        my = lax.axis_index(self.axes).astype(jnp.int32)
        base = my * rps
        all_ids = lax.all_gather(ctx.ids, self.axes, tiled=True)
        all_g = gcomp.compressed_all_gather(g_rows, self.axes,
                                            mode=self.grad_compress,
                                            fused=self.use_fused)
        local = all_ids - base
        ok = (local >= 0) & (local < rps)
        w2, acc2 = pe._dedup_apply(st.w, st.acc, jnp.clip(local, 0, rps - 1),
                                   all_g, ok, self.lr, self.eps,
                                   fused=self.use_fused)
        zero = jnp.zeros((), jnp.int32)
        return st._replace(w=w2, acc=acc2), zero, zero


@register_strategy("mp_nodedup")
class MPNoDedupStrategy(LookupStrategy):
    """Model-parallel Shuffle without K-Packed dedup (paper §II-C baseline).

    Every raw id — duplicates included — consumes a Shuffle bucket slot, so
    the wire payload scales with the batch's id count rather than its unique
    count. Exists to price the Unique&Partition fusion in benchmarks; exact
    vs ``picasso`` when nothing overflows (plan with ``exact_capacity=True``
    for parity runs: duplicate grads are summed by the owner-side
    dedup+adagrad scatter, recovering the deduped math).
    """

    uses_cache = False

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        return pe.mp_lookup_nodedup(
            st.w, ids, axes=self.axes, world=self.world,
            capacity=self.capacity[gid])

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        w2, acc2 = pe._apply_miss_grads(
            st.w, st.acc, ctx, g_rows, self.axes, self.world, self.lr,
            self.eps, self.use_fused, self.grad_compress)
        counts2 = pe.count_frequencies(st.counts, ctx)
        st2 = st._replace(w=w2, acc=acc2, counts=counts2)
        return (st2, ctx.routing.overflow.astype(jnp.int32),
                jnp.zeros((), jnp.int32))


class AllGatherCtx(NamedTuple):
    """Context of an allgather_rows lookup: rows are per-unique-slot."""

    inv: jnp.ndarray    # [n] position -> unique slot
    uniq: jnp.ndarray   # [n] sorted unique ids (sentinel-padded)
    n_uniq: jnp.ndarray  # scalar


@register_strategy("allgather_rows")
class AllGatherRowsStrategy(LookupStrategy):
    """Dedup'd replication baseline: unique rows via all_gather+psum.

    Forward dedups the batch (fixed-shape unique), then serves the unique set
    with the PS machinery — sentinel slots gather exact zero rows. Backward
    all_gathers every shard's unique ids plus their row grads (the grads hop
    honours ``grad_compress``) and applies them locally on the owner shard.
    Wire cost sits between ``ps`` (no dedup at all) and the routed paths
    (O(world * uniq * D) vs O(uniq * D)); no routing ctx, no cache tiers.
    """

    uses_cache = False
    uses_routing_ctx = False

    def lookup(self, st, gid, ids, *, cache_on=False, l2_on=False):
        rps = st.w.shape[0]
        u = pe.fixed_unique(ids, sentinel=rps * self.world)
        rows = pe.ps_lookup(st.w, u.uniq, axes=self.axes, world=self.world)
        return rows, AllGatherCtx(inv=u.inv, uniq=u.uniq, n_uniq=u.n_uniq)

    def apply_grads(self, st, gid, ctx, g_rows, *, cache_on=False, l2_on=False):
        rps = st.w.shape[0]
        my = lax.axis_index(self.axes).astype(jnp.int32)
        base = my * rps
        all_ids = lax.all_gather(ctx.uniq, self.axes, tiled=True)
        all_g = gcomp.compressed_all_gather(g_rows, self.axes,
                                            mode=self.grad_compress,
                                            fused=self.use_fused)
        local = all_ids.astype(jnp.int32) - base
        ok = (local >= 0) & (local < rps)
        w2, acc2 = pe._dedup_apply(st.w, st.acc, jnp.clip(local, 0, rps - 1),
                                   all_g, ok, self.lr, self.eps,
                                   fused=self.use_fused)
        zero = jnp.zeros((), jnp.int32)
        return st._replace(w=w2, acc=acc2), zero, zero

    def distinct_ids(self, ctx):
        return ctx.n_uniq
