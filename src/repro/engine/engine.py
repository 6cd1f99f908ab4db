"""EmbeddingEngine: the one owner of PICASSO's packed sparse path.

Architecture (engine layer)
---------------------------

Every workload — hybrid MP/DP training, online/bulk serving, two-tower
retrieval, and the dry-run cells — consumes the *same* engine instead of
re-implementing the ``pack_group -> lookup -> pool`` loop:

    EmbeddingEngine(plan, axes, world, strategy=<spec>)
        .forward(emb, packed)          -> (pooled, ctx)     # K-interleaved
        .backward(emb, ctx, g_pooled)  -> (emb', metrics)   # transposed path
        .flush(emb)                    -> emb'              # HybridHash flush
        .lookup_rows(emb, gid, ids)    -> rows              # raw per-id rows

``forward`` runs the planner's K-Interleaving waves (lookups of wave k+1 are
pinned behind a barrier with wave k's outputs, Fig. 8c) and pools each packed
group into ``pooled[gid]: [B, n_bags, D]``. ``backward`` takes the loss
gradient w.r.t. those pooled tensors, applies the (linear) SegmentReduction
transpose to recover per-row gradients, and hands them to each group's
strategy update path; it also folds cache hit / bucket overflow counters into
metrics. ``ctx`` is a pytree, so engine calls compose with
``jax.value_and_grad``, ``lax.cond`` and the D-Interleaving micro-batch
pipeline in the train step.

Strategy is a **per-packed-group property of the plan**, not an engine-wide
flag: the engine owns a ``Dict[gid, LookupStrategy]`` and dispatches per
group in every entry point. The ``strategy=`` argument accepts

- a registry name (``'picasso' | 'hybrid' | 'ps' | 'picasso_l2' |
  'picasso_narrow'``) — broadcast to every group (the original
  single-strategy constructor, kept as sugar);
- ``'mixed'`` / ``'auto'`` — use ``plan.strategy`` when the planner recorded
  an assignment, else compile one with the ``repro.core.assign`` cost model
  (tiny tables PS-replicated, big skewed tables routed + cached);
- an explicit ``{gid: name}`` dict or a ``StrategyAssignment``.

Invariants the engine maintains (and the tests pin down):

* **Per-group cache gating.** The HybridHash hot tier (L1) participates only
  where the assigned strategy has ``uses_cache`` AND the plan budgets
  ``cache_rows`` for that gid. The L2 host tier sits strictly *behind* L1:
  it participates only where the strategy has ``uses_l2``, the plan budgets
  ``l2_rows``, the engine's ``use_l2`` flag is on, AND L1 itself is active
  for the group (``--no-cache`` therefore disables both tiers).
* **Flush skips uncached groups.** ``flush`` touches exactly the groups
  whose tiers participate: L1+L2 groups get the two-tier flush (one global
  frequency ranking split top-H1 / next-H2), L1-only groups the single-tier
  flush, and every other group — including PS-assigned groups whose budgeted
  tier the training path never populated — passes through untouched.
* **Assignment resolution order** (``repro.core.assign.resolve_assignment``):
  an explicit ``StrategyAssignment``/dict is taken as-is (validated for
  exact gid coverage); ``'mixed'``/``'auto'`` uses ``plan.strategy`` when
  the plan carries one, else compiles a fresh assignment and records it on
  the plan; any other registry name broadcasts to every group.

Metrics are the totals ``overflow``, ``cache_hits`` and ``distinct_ids``,
per-strategy-class sums (``overflow/<name>``,
``cache_hits/<name>``) when a plan mixes classes, plus any strategy-declared
per-tier keys (``cache_hits/l1`` / ``cache_hits/l2`` for ``picasso_l2``) —
``metric_keys`` is static so callers can build shard_map out_specs from it.

All shapes are static: the engine runs inside ``shard_map`` on TPU meshes.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packed_embedding as pe
from repro.core.assign import StrategySpec, resolve_assignment
from repro.kernels import ops
from repro.core.features import PackedBatch
from repro.core.interleaving import wave_barrier
from repro.core.packing import PicassoPlan
from repro.embedding.state import EmbeddingState
from repro.engine.strategies import LookupStrategy, get_strategy
from repro.optim import grad_compression as gcomp

Axes = Union[str, Tuple[str, ...]]


def export_stats(plan: PicassoPlan, emb: Dict[str, EmbeddingState]
                 ) -> Dict[int, np.ndarray]:
    """Harvest the live FCounter off-device: ``gid -> counts`` (full logical
    array, host numpy).

    This is the measurement half of the replanning loop (repro.runtime):
    the counts feed ``compile_assignment(plan, stats=...)`` and the
    stats-driven ``plan_cache``/``plan_l2`` re-budget. Reading a sharded
    array through ``device_get`` materializes the logical (mesh-wide) value,
    so the result is shard-layout independent — exactly what the planners
    expect. Call between steps (off the jitted hot path).
    """
    return {g.gid: np.asarray(jax.device_get(emb[str(g.gid)].counts))
            for g in plan.groups}


class EngineContext(NamedTuple):
    """Everything ``backward`` needs from a ``forward`` call (a pytree)."""

    ctxs: Dict[int, Any]            # gid -> strategy lookup ctx
    packed: Dict[int, PackedBatch]  # gid -> the packed batch it served


class EmbeddingEngine:
    """Owns the full sparse path for one PicassoPlan on one mesh.

    Parameters
    ----------
    plan: the planner output (groups, capacities, waves, cache budget, and
        optionally a per-group strategy assignment).
    axes/world: mesh axes the engine's collectives run over, and their size.
    strategy: a registry name (broadcast), ``'mixed'``/``'auto'`` (use or
        compile a per-group assignment), a ``{gid: name}`` dict, or a
        ``StrategyAssignment`` — see ``repro.core.assign``.
    use_cache: enable the HybridHash hot tier (honoured per group: only
        where the assigned strategy has ``uses_cache=True`` and the plan
        budgets a non-zero cache for that gid).
    use_l2: enable the L2 host-memory tier behind the hot tier (honoured
        per group: strategy has ``uses_l2=True``, the plan budgets
        ``l2_rows``, and the group's L1 tier is itself active).
    use_interleave: issue lookups in the planner's K-Interleaving waves;
        ``False`` collapses to a single wave.
    lr_emb/eps: row-wise adagrad hyperparameters for the sparse update.
    cache_update: ``'psum'`` (exact, replica-consistent hot tier) or
        ``'stale'`` (Algorithm 1 bounded-staleness semantics).
    use_fused_kernels: ``'auto'`` (fused Pallas sparse kernels on TPU or
        under ``REPRO_FORCE_PALLAS_INTERPRET``, jnp reference on CPU),
        ``'on'``/``True`` (force the kernels; interpreted off-TPU) or
        ``'off'``/``False`` (force the reference chains). Resolved ONCE here
        (``repro.kernels.ops.resolve_fused``) to a static bool every
        strategy and the pool/transpose below carry through their traces.
    grad_compress: wire compression of the routed sparse-gradient payload
        (``'none' | 'fp16' | 'topk'``, see ``repro.optim.grad_compression``)
        — applied by every strategy's backward collective; ``'none'`` keeps
        training bitwise-identical. Tier-maintenance traffic stays exact.
    capacity: optional per-gid override of the all_to_all bucket capacity
        (e.g. retrieval candidate towers that look up far more ids per shard
        than the training batch the plan was sized for).
    """

    def __init__(self, plan: PicassoPlan, axes: Axes, world: int, *,
                 strategy: StrategySpec = "picasso", use_cache: bool = True,
                 use_l2: bool = True, use_interleave: bool = True,
                 lr_emb: float = 0.05, eps: float = 1e-8,
                 cache_update: str = "psum",
                 use_fused_kernels: Any = "auto",
                 grad_compress: str = "none",
                 capacity: Optional[Dict[int, int]] = None):
        if int(plan.world) != int(world):
            # a stale engine after an elastic reshard: the plan's padded row
            # counts and capacities derive from plan.world, so collectives
            # built for `world` shards would mis-route rows silently
            raise ValueError(
                f"plan was compiled for world={plan.world} but the engine is "
                f"built for world={world} — after a reshard, rebuild the "
                "engine/step from the resharded plan (core.packing."
                "reshard_plan), not the stale one")
        self.plan = plan
        self.axes = axes
        self.world = world
        self.cache_update = cache_update
        self.use_fused = ops.resolve_fused(use_fused_kernels)
        self.grad_compress = gcomp.validate_routed_mode(grad_compress)
        # gid -> registry name; raises on unknown names / partial coverage
        # (an auto-compiled assignment is recorded on the plan, so the
        # host-flush engine and later call sites gate caches identically)
        self.assignment: Dict[int, str] = resolve_assignment(
            plan, strategy, world=world, use_cache=use_cache)
        # narrow masters are only readable through picasso_narrow: a plan
        # that narrows a group (recorded assignment + narrow budget) cannot
        # be driven by an engine assigning that group elsewhere — the master
        # shard is [rows, d], every other strategy expects [rows, D]
        for g in plan.groups:
            if (plan.narrow_width(g.gid) < g.dim
                    and self.assignment.get(g.gid) != "picasso_narrow"):
                raise ValueError(
                    f"g{g.gid}: the plan narrows this group's master to "
                    f"width {plan.narrow_width(g.gid)} (< dim {g.dim}), but "
                    f"this engine assigns {self.assignment.get(g.gid)!r}; "
                    "narrow state is only readable through 'picasso_narrow' "
                    "— keep the recorded assignment or re-plan without "
                    "narrow_dim")
        names = tuple(sorted(set(self.assignment.values())))
        self.strategy_names = names
        self.strategy_name = names[0] if len(names) == 1 else "mixed"
        cap = dict(capacity if capacity is not None else plan.capacity)
        # one instance per distinct name (they are stateless per-call), one
        # dispatch-map entry per group
        insts: Dict[str, LookupStrategy] = {
            name: get_strategy(name)(
                axes=axes, world=world, capacity=cap, lr=lr_emb, eps=eps,
                cache_update=cache_update, use_fused=self.use_fused,
                grad_compress=self.grad_compress)
            for name in names}
        self.strategies: Dict[int, LookupStrategy] = {
            gid: insts[name] for gid, name in self.assignment.items()}
        # per-group cache gating: strategy must use the tier AND the plan
        # must budget rows for this gid
        self.cache_on: Dict[int, bool] = {
            g.gid: bool(use_cache
                        and self.strategies[g.gid].uses_cache
                        and plan.cache_rows.get(g.gid, 0) > 0)
            for g in plan.groups}
        # L2 sits strictly behind L1: an inactive hot tier turns it off too
        self.l2_on: Dict[int, bool] = {
            g.gid: bool(use_l2
                        and self.cache_on[g.gid]
                        and self.strategies[g.gid].uses_l2
                        and plan.l2_rows.get(g.gid, 0) > 0)
            for g in plan.groups}
        self.any_cache = any(self.cache_on.values())
        self._extra_keys = tuple(sorted(
            {k for n in names for k in get_strategy(n).extra_metric_keys}))
        self.waves = (plan.interleave if use_interleave
                      else [[g.gid for g in plan.groups]])

    def export_stats(self, emb: Dict[str, EmbeddingState]
                     ) -> Dict[int, np.ndarray]:
        """Module-level ``export_stats`` bound to this engine's plan."""
        return export_stats(self.plan, emb)

    @property
    def metric_keys(self) -> Tuple[str, ...]:
        """Static metric pytree keys ``backward`` emits (callers build
        shard_map out_specs from this)."""
        keys = ["overflow", "cache_hits", "distinct_ids"]
        if len(self.strategy_names) > 1:
            keys += [f"overflow/{n}" for n in self.strategy_names]
            keys += [f"cache_hits/{n}" for n in self.strategy_names]
        keys += list(self._extra_keys)
        return tuple(keys)

    # ------------------------------------------------------------- forward
    def _wave_lookups(self, emb: Dict[str, EmbeddingState],
                      packed: Dict[int, PackedBatch]
                      ) -> Tuple[Dict[int, jnp.ndarray], Dict[int, Any]]:
        """Per-group lookups in K-Interleaving waves (Fig. 8c), each group
        through its own assigned strategy."""
        rows: Dict[int, jnp.ndarray] = {}
        ctxs: Dict[int, Any] = {}
        ids_in = {g.gid: packed[g.gid].ids for g in self.plan.groups}
        for wi, wave in enumerate(self.waves):
            if wi > 0:
                # wave wi's inputs pass through one barrier with wave wi-1's
                # outputs -> a real control boundary between the all_to_alls.
                prev = self.waves[wi - 1]
                flat = wave_barrier([rows[g] for g in prev]
                                    + [ids_in[g] for g in wave])
                for g, v in zip(prev, flat[: len(prev)]):
                    rows[g] = v
                for j, g in enumerate(wave):
                    ids_in[g] = flat[len(prev) + j]
            for gid in wave:
                rows[gid], ctxs[gid] = self.strategies[gid].lookup(
                    emb[str(gid)], gid, ids_in[gid],
                    cache_on=self.cache_on[gid], l2_on=self.l2_on[gid])
        return rows, ctxs

    def forward(self, emb: Dict[str, EmbeddingState],
                packed: Dict[int, PackedBatch]
                ) -> Tuple[Dict[int, jnp.ndarray], EngineContext]:
        """Packed batch -> pooled group outputs ``[B, n_bags, D]`` + ctx."""
        with obs.scope(obs.SPARSE_LOOKUP):
            rows, ctxs = self._wave_lookups(emb, packed)
            pooled = {}
            for gid, pb in packed.items():
                g = self.plan.group(gid)
                b = pb.ids.shape[0] // g.ids_per_sample
                with obs.scope(obs.POOL):
                    p = pe.pool(rows[gid], ctxs[gid].inv, pb.weights, pb.seg,
                                b * g.n_bags, fused=self.use_fused)
                pooled[gid] = p.reshape(b, g.n_bags, g.dim)
        return pooled, EngineContext(ctxs=ctxs, packed=dict(packed))

    def lookup_rows(self, emb: Dict[str, EmbeddingState], gid: int,
                    ids: jnp.ndarray) -> jnp.ndarray:
        """Raw per-id rows ``[n, D]`` for one group (retrieval towers)."""
        with obs.scope(obs.SPARSE_LOOKUP):
            rows_u, ctx = self.strategies[gid].lookup(
                emb[str(gid)], gid, ids, cache_on=self.cache_on[gid],
                l2_on=self.l2_on[gid])
            return jnp.take(rows_u, ctx.inv, axis=0)

    # ------------------------------------------------------------ backward
    def backward(self, emb: Dict[str, EmbeddingState], ctx: EngineContext,
                 g_pooled: Dict[int, jnp.ndarray]
                 ) -> Tuple[Dict[str, EmbeddingState], Dict[str, jnp.ndarray]]:
        """Pooled grads -> sparse updates. Returns (emb', local metrics).

        The SegmentReduction of ``forward`` is linear in the looked-up rows,
        so its transpose is explicit: ``g_rows[u] = sum_{i: inv[i]=u} w[i] *
        g_pooled[seg[i]]``. Metrics are per-shard sums; callers psum them.
        With a mixed assignment, ``overflow/<name>`` and ``cache_hits/<name>``
        break the totals down per strategy class (see ``metric_keys``).
        ``distinct_ids`` counts the distinct ids the groups' lookups worked
        on (``LookupStrategy.distinct_ids``).
        """
        with obs.scope(obs.SPARSE_UPDATE):
            return self._backward(emb, ctx, g_pooled)

    def _backward(self, emb, ctx, g_pooled):
        emb = dict(emb)
        zero = jnp.zeros((), jnp.int32)
        ovf = {n: zero for n in self.strategy_names}
        hits = {n: zero for n in self.strategy_names}
        extra = {k: zero for k in self._extra_keys}
        distinct = zero
        for gid, g_p in g_pooled.items():
            pb = ctx.packed[gid]
            gctx = ctx.ctxs[gid]
            name = self.assignment[gid]
            g_flat = g_p.reshape(-1, g_p.shape[-1])
            # transpose of the pool: one fused segment-grad pass produces the
            # [n_unique, D] row grads directly (no [n, D] per-id intermediate
            # when fused — see ops.segment_grad)
            with obs.scope(obs.SEGMENT_GRAD):
                g_rows = ops.segment_grad(g_flat, pb.seg, pb.weights,
                                          gctx.inv, pb.ids.shape[0],
                                          fused=self.use_fused)
            st2, o, h = self.strategies[gid].apply_grads(
                emb[str(gid)], gid, gctx, g_rows, cache_on=self.cache_on[gid],
                l2_on=self.l2_on[gid])
            emb[str(gid)] = st2
            ovf[name] = ovf[name] + o
            hits[name] = hits[name] + h
            distinct = distinct + self.strategies[gid].distinct_ids(gctx)
            for k, v in self.strategies[gid].tier_metrics(gctx).items():
                extra[k] = extra[k] + v
        metrics = {"overflow": sum(ovf.values(), zero),
                   "cache_hits": sum(hits.values(), zero),
                   "distinct_ids": distinct}
        if len(self.strategy_names) > 1:
            for n in self.strategy_names:
                metrics[f"overflow/{n}"] = ovf[n]
                metrics[f"cache_hits/{n}"] = hits[n]
        metrics.update(extra)
        return emb, metrics

    # --------------------------------------------------------------- flush
    def flush(self, emb: Dict[str, EmbeddingState]) -> Dict[str, EmbeddingState]:
        """HybridHash flush (Algorithm 1 L23-26) for every *cached* group —
        groups whose assigned strategy never reads a tier are skipped even
        when the plan budgets rows for them. Groups with an active L2 host
        tier get the two-tier flush: both tiers written back (psum mode),
        then one global frequency ranking refills L1 (top-H1) and L2
        (next-H2) disjointly."""
        with obs.scope(obs.FLUSH):
            return self._flush(emb)

    def _flush(self, emb):
        out = dict(emb)
        for g in self.plan.groups:
            if not self.cache_on.get(g.gid, False):
                continue
            st = out[str(g.gid)]
            wb = self.cache_update == "psum"
            if st.proj is not None:
                # narrow master: heterogeneous-width flush (write-back via
                # the projection pseudo-inverse, widened reload, exact carry
                # for ids staying tier-resident). A missing L2 tier flushes
                # as an empty wide tier and stays absent.
                l2t = st.l2
                if l2t is None:
                    l2t = pe.CacheState(
                        keys=jnp.full((0,), g.rows, jnp.int32),
                        rows=jnp.zeros((0, g.dim), st.cache.rows.dtype),
                        acc=jnp.zeros((0, 1), st.cache.acc.dtype))
                w2, acc2, counts2, cache2, l22 = pe.flush_cache_narrow(
                    st.w, st.acc, st.counts, st.cache, l2t,
                    st.proj.kernel, axes=self.axes, world=self.world,
                    write_back=wb)
                out[str(g.gid)] = EmbeddingState(
                    w2, acc2, counts2, cache2,
                    l22 if st.l2 is not None else None, st.proj)
            elif self.l2_on.get(g.gid, False) and st.l2 is not None:
                w2, acc2, counts2, cache2, l22 = pe.flush_cache_l2(
                    st.w, st.acc, st.counts, st.cache, st.l2, axes=self.axes,
                    world=self.world, write_back=wb)
                out[str(g.gid)] = EmbeddingState(w2, acc2, counts2, cache2, l22)
            else:
                w2, acc2, counts2, cache2 = pe.flush_cache(
                    st.w, st.acc, st.counts, st.cache, axes=self.axes,
                    world=self.world, write_back=wb)
                out[str(g.gid)] = EmbeddingState(w2, acc2, counts2, cache2,
                                                 st.l2)
        return out
