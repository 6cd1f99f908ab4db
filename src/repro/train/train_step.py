"""Hybrid MP/DP train step for WDL models (paper §III-A + Fig. 6).

One SPMD program under ``shard_map`` over the full mesh:

  pack (D-Packing) -> EmbeddingEngine.forward (K-Packing + K-Interleaving)
  -> micro-batch pipeline (D-Interleaving): dense fwd/bwd of chunk i overlaps
     the Shuffle of chunk i+1
  -> dense grads psum (DP) + Adam ; EmbeddingEngine.backward routes sparse
     grads (MP) + row-wise Adagrad ; HybridHash hit grads psum'd into the
     replicated hot tier
  -> FCounter update ; periodic HybridHash flush (EmbeddingEngine.flush).

The D-Interleaving pipeline has two strengths, both static knobs:

``pipeline_micro`` (legacy order) issues chunk i+1's Shuffle before chunk
i's dense compute and trusts XLA's latency-hiding scheduler to interleave
them. ``overlap`` ('off' | 'on' | 'auto', the *software-pipelined* step)
additionally double-buffers the prefetch: the lookup of chunk i+1 and the
consumed outputs of chunk i pass through one ``optimization_barrier``
(``pipeline_handoff``), which pins the two-slot schedule — the compiler can
neither sink the in-flight Shuffle below the dense stage nor collapse the
two buffers. Barriers are value-identity, so 'on' and 'off' compute
bit-identical numbers; 'off' is byte-for-byte the legacy step (a regression
test pins its jaxpr), and 'auto' turns overlap on exactly when the step has
more than one micro-batch to pipeline.

The whole sparse path lives in ``repro.engine.EmbeddingEngine``; this module
only owns the micro-batch pipeline, the dense optimizer, and metric psums.
Strategies (paper §II-C / §IV baselines) are selected per packed group via
``TrainConfig.strategy``:
  'picasso' — the full system (packed + interleaved + HybridHash);
  'picasso_l2' — picasso plus an L2 host-memory cache tier behind the hot
      tier (requires a plan built with ``l2_bytes > 0``; emits per-tier
      ``cache_hits/l1`` / ``cache_hits/l2`` counters);
  'picasso_narrow' — picasso_l2 with frequency-adaptive widths: hot ids
      full-width in the tiers, the cold master narrow (requires a plan
      built with ``narrow_dim``; cold rows are projected up at lookup);
  'hybrid'  — MP all_to_all per group but no HybridHash tier;
  'ps'      — PS-style all_gather+psum lookups (the fragmentary baseline);
  'mixed'/'auto' — per-group assignment from the plan (or compiled by the
      ``repro.core.assign`` cost model), also spellable as a {gid: name}
      dict / ``StrategyAssignment``. Mixed runs emit per-strategy-class
      ``overflow/<name>`` / ``cache_hits/<name>`` metric breakdowns.
Unknown names raise at trace-construction time with the registry's menu.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.features import PackedBatch, pack_group
from repro.core.interleaving import pipeline_handoff, resolve_overlap
from repro.core.packing import PicassoPlan
from repro.dist.sharding import batch_specs, emb_specs, state_specs, to_named
from repro.embedding.state import EmbeddingState
from repro.engine import EmbeddingEngine
from repro.models.wdl import WDLModel
from repro.optim.optimizers import adam_init, adam_update, lamb_update


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr_emb: float = 0.05
    lr_dense: float = 1e-3
    optimizer: str = "adam"        # 'adam' | 'lamb'
    # registry name ('picasso' | 'hybrid' | 'ps'), 'mixed'/'auto' (per-group
    # assignment from the plan / cost model), {gid: name}, or a
    # StrategyAssignment — anything repro.core.assign.resolve_assignment takes
    strategy: Any = "picasso"
    pipeline_micro: bool = True    # D-Interleaving pipeline order
    # software-pipelined step: 'off' = the legacy (jaxpr-pinned) loop,
    # 'on' = double-buffered prefetch behind a pipeline_handoff barrier,
    # 'auto' = on exactly when n_micro > 1 (bools accepted too)
    overlap: Any = "auto"
    use_cache: bool = True
    use_l2: bool = True            # L2 host tier (only where the plan
                                   # budgets l2_rows AND L1 is active)
    use_interleave: bool = True    # K-Interleaving waves (False: one wave)
    # fused Pallas sparse kernels (gather+pool VJP, dedup+adagrad scatter,
    # tier probes): 'auto' = on where Pallas runs (TPU / interpret soak),
    # True/'on' force, False/'off' force the jnp reference chains
    use_fused_kernels: Any = "auto"
    cache_update: str = "psum"     # 'psum' (exact) | 'stale' (Algorithm 1)
    flush_in_step: bool = True     # False: host calls make_flush_fn() instead
    grad_compression: str = "none"  # 'none' | 'bf16' | 'f8' (dense DP psum)
    # wire compression of the ROUTED sparse-gradient payload ('none' |
    # 'fp16' | 'topk'; repro.optim.grad_compression.ROUTED_MODES) — applied
    # inside every strategy's backward collective
    grad_compress: str = "none"
    # mirror of the launcher's --pin-l2: the jitted step's out_shardings pin
    # the L2 tier (and narrow masters) to pinned_host memory so the initial
    # pin_l2_to_host placement survives across steps. Inert on backends
    # without a host memory kind (the CPU rig) — the step is byte-identical.
    pin_l2: bool = False
    eps: float = 1e-8


def _mesh_world(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def _slice_micro(x, i, micro):
    return lax.dynamic_slice_in_dim(x, i * micro, micro, axis=0)


def make_train_step(model: WDLModel, plan: PicassoPlan, mesh, axes: Tuple[str, ...],
                    global_batch: int, tcfg: TrainConfig = TrainConfig(),
                    donate: bool = True):
    """Returns (jitted_step, state_specs_pytree). step(state, batch) -> (state, metrics).

    ``donate=False`` keeps the input state buffers alive across the call —
    required by the anomaly guard, which must be able to *reject* a step by
    returning the prior state (donation would have freed it). Donation only
    affects buffer aliasing, never the computed values, so a non-donating
    step is bitwise identical to the donating one at higher peak memory.
    """
    world = _mesh_world(mesh, axes)
    assert global_batch % world == 0, (global_batch, world)
    b_local = global_batch // world
    micro = plan.microbatch if plan.microbatch <= b_local else b_local
    n_micro = max(1, b_local // micro)

    # The engine owns lookups, pooling, sparse updates, and the flush;
    # the strategy name is validated against the registry right here.
    engine = EmbeddingEngine(
        plan, axes, world, strategy=tcfg.strategy, use_cache=tcfg.use_cache,
        use_l2=tcfg.use_l2, use_interleave=tcfg.use_interleave,
        lr_emb=tcfg.lr_emb, eps=tcfg.eps, cache_update=tcfg.cache_update,
        use_fused_kernels=tcfg.use_fused_kernels,
        grad_compress=tcfg.grad_compress)
    # static resolution: the traced loop below has no overlap branches left
    use_overlap = resolve_overlap(tcfg.overlap, n_micro)

    # -------------------------------------------------------- loss closure
    def micro_loss(dense, pooled, mb):
        loss_sum, logits = model.loss(dense, pooled, mb)
        return loss_sum / global_batch, logits

    # --------------------------------------------------------------- step
    def local_step(state, batch):
        emb: Dict[str, EmbeddingState] = dict(state["emb"])
        dense, opt, step = state["dense"], state["opt"], state["step"]

        with obs.scope(obs.STEP_MISC):
            packed_full = {g.gid: pack_group(g, batch["fields"])
                           for g in plan.groups}

        def packed_micro(i):
            out = {}
            for gid, pb in packed_full.items():
                g = plan.group(gid)
                ips = g.ids_per_sample
                ids = _slice_micro(pb.ids.reshape(b_local, ips), i, micro).reshape(-1)
                wts = _slice_micro(pb.weights.reshape(b_local, ips), i, micro).reshape(-1)
                seg = pb.seg[: micro * ips]  # per-sample pattern repeats
                out[gid] = PackedBatch(ids=ids, weights=wts, seg=seg, n_bags=g.n_bags)
            return out

        def batch_micro(i):
            mb = {"fields": {n: {k: _slice_micro(v, i, micro) for k, v in f.items()}
                             for n, f in batch["fields"].items()},
                  "labels": _slice_micro(batch["labels"], i, micro)}
            if "dense" in batch:
                mb["dense"] = _slice_micro(batch["dense"], i, micro)
            return mb

        grad_fn = jax.value_and_grad(micro_loss, argnums=(0, 1), has_aux=True)

        loss_acc = jnp.zeros(())
        g_dense_acc = jax.tree.map(jnp.zeros_like, dense)
        em_acc = {k: jnp.zeros((), jnp.int32) for k in engine.metric_keys}

        pending = (engine.forward(emb, packed_micro(0)), batch_micro(0))
        for i in range(n_micro):
            (pooled, ectx), mb = pending
            if use_overlap and i + 1 < n_micro:
                # software pipeline: the prefetch of chunk i+1 and the
                # consumed outputs of chunk i cross one handoff barrier, so
                # the in-flight Shuffle is pinned *beside* (not after) the
                # dense stage and the two buffer slots stay distinct
                nxt = engine.forward(emb, packed_micro(i + 1))
                (pooled, ectx), nxt = pipeline_handoff((pooled, ectx), nxt)
                pending = (nxt, batch_micro(i + 1))
            elif tcfg.pipeline_micro and i + 1 < n_micro:
                # D-Interleaving: issue Shuffle of chunk i+1 before dense of i
                pending = (engine.forward(emb, packed_micro(i + 1)),
                           batch_micro(i + 1))
            with obs.scope(obs.DENSE):
                (loss, _logits), (g_dense, g_pooled) = grad_fn(dense, pooled, mb)
                loss_acc = loss_acc + loss
                g_dense_acc = jax.tree.map(jnp.add, g_dense_acc, g_dense)
            emb, em = engine.backward(emb, ectx, g_pooled)
            with obs.scope(obs.STEP_MISC):
                em_acc = {k: em_acc[k] + em[k] for k in em_acc}
            if not use_overlap and not (tcfg.pipeline_micro) and i + 1 < n_micro:
                pending = (engine.forward(emb, packed_micro(i + 1)),
                           batch_micro(i + 1))

        # ---- dense DP: psum grads over the whole mesh ----------------------
        with obs.scope(obs.DENSE):
            if tcfg.grad_compression != "none":
                from repro.optim.grad_compression import compressed_psum
                g_dense_acc, _ = compressed_psum(g_dense_acc, axes,
                                                 mode=tcfg.grad_compression)
            else:
                g_dense_acc = lax.psum(g_dense_acc, axes)
            upd = adam_update if tcfg.optimizer == "adam" else lamb_update
            dense2, opt2 = upd(dense, g_dense_acc, opt, tcfg.lr_dense)

        # ---- HybridHash flush (Algorithm 1 L23-26) -------------------------
        step2 = step + 1
        if engine.any_cache and tcfg.flush_in_step:
            # the cond itself is named too, so that its time on the device
            # is the flush's on every step
            with obs.scope(obs.FLUSH):
                do_flush = ((step2 >= plan.warmup_iters)
                            & (step2 % plan.flush_iters == 0))
                emb = lax.cond(do_flush, engine.flush, lambda e: e, emb)

        new_state = {"emb": emb, "dense": dense2, "opt": opt2, "step": step2}
        with obs.scope(obs.STEP_MISC):
            loss_glob = lax.psum(loss_acc, axes)
            # global dense-gradient norm (g_dense_acc is already psum'd): the
            # numeric health signal runtime.guard thresholds for spike
            # rejection
            grad_norm = jnp.sqrt(sum(jnp.vdot(g, g)
                                     for g in jax.tree.leaves(g_dense_acc)))
            metrics = {"loss": loss_glob, "step": step2, "grad_norm": grad_norm,
                       **{k: lax.psum(em_acc[k], axes)
                          for k in engine.metric_keys}}
        return new_state, metrics

    # ---------------------------------------------------------------- wrap
    dense0 = jax.eval_shape(lambda k: model.init_dense(k), jax.random.PRNGKey(0))
    opt0 = jax.eval_shape(adam_init, dense0)
    sspecs = state_specs(plan, axes, dense0, opt0)
    mspecs = {"loss": P(), "step": P(), "grad_norm": P(),
              **{k: P() for k in engine.metric_keys}}

    def wrapped(state, batch):
        bspecs = batch_specs(batch, axes)
        f = jax.shard_map(local_step, mesh=mesh,
                          in_specs=(sspecs, bspecs),
                          out_specs=(sspecs, mspecs),
                          check_vma=False)
        return f(state, batch)

    jit_kw = {}
    if tcfg.pin_l2:
        from repro.dist.sharding import host_memory_kind, state_shardings
        if host_memory_kind() is not None:
            # memory-kind-aware out shardings: without these the first step
            # would return the L2 tier / narrow masters in device memory and
            # the --pin-l2 placement would silently evaporate
            jit_kw["out_shardings"] = (
                state_shardings(plan, mesh, axes, dense0, opt0, pin_l2=True),
                to_named(mesh, mspecs))
    if donate:
        jit_kw["donate_argnums"] = (0,)
    step_fn = jax.jit(wrapped, **jit_kw)
    return step_fn, sspecs


def make_flush_fn(plan: PicassoPlan, mesh, axes: Tuple[str, ...],
                  cache_update: str = "psum", strategy: Any = None,
                  use_cache: bool = True, use_l2: bool = True):
    """Host-scheduled HybridHash flush: jitted state -> state (called every
    ``plan.flush_iters`` steps by the trainer when flush_in_step=False).
    Keeps the flush collectives OUT of the hot train step.

    ``strategy=None`` follows the plan: a recorded per-group assignment
    (``plan.strategy``) gates the flush exactly like the train engine —
    groups with a budgeted-but-unused cache (e.g. PS-assigned) are skipped,
    not clobbered with stale hot rows — and unassigned plans keep the
    original broadcast-'picasso' gating. Pass the training spec explicitly
    only when it was never recorded on the plan.

    ``use_cache``/``use_l2`` MUST mirror the TrainConfig flags the train
    engine ran with: a flush engine gating a tier ON that training gated OFF
    would write a never-updated (stale) tier snapshot back over master rows
    the training path has been updating directly."""
    world = _mesh_world(mesh, axes)
    if strategy is None:
        strategy = "mixed" if plan.strategy else "picasso"
    engine = EmbeddingEngine(plan, axes, world, cache_update=cache_update,
                             strategy=strategy, use_cache=use_cache,
                             use_l2=use_l2)
    especs = emb_specs(plan, axes)

    def wrapped(state):
        f = jax.shard_map(engine.flush, mesh=mesh, in_specs=(especs,),
                          out_specs=especs, check_vma=False)
        return {**state, "emb": f(state["emb"])}

    return jax.jit(wrapped, donate_argnums=(0,))


def init_state(model: WDLModel, plan: PicassoPlan, key, mesh=None, axes=None):
    """Initialize a TrainState; with mesh given, tables come out pre-sharded."""
    from repro.embedding.state import init_embedding_state

    def build(k):
        k1, k2 = jax.random.split(k)
        emb = init_embedding_state(k1, plan)
        dense = model.init_dense(k2)
        return {"emb": {str(g): s for g, s in emb.items()},
                "dense": dense, "opt": adam_init(dense),
                "step": jnp.zeros((), jnp.int32)}

    if mesh is None:
        return build(key)
    dense0 = jax.eval_shape(lambda k: model.init_dense(k), key)
    sspecs = state_specs(plan, axes, dense0, jax.eval_shape(adam_init, dense0))
    shardings = to_named(mesh, sspecs)
    return jax.jit(build, out_shardings=shardings)(key)
