"""Inference steps for WDL models: online (p99) / bulk scoring / retrieval.

Same shard_map program shape as training minus the backward: the shared
``repro.engine.EmbeddingEngine`` executes the packed lookups (including the
HybridHash read path and K-Interleaving waves) -> interactions -> sigmoid
scores. Retrieval scores one query against 1M candidates: two-tower archs
(sasrec / mind) embed the user once and dot against mesh-sharded candidate
item rows served by the same engine with a widened bucket capacity, with a
distributed top-k; pure-CTR archs (deepfm / dcn-v2) run a bulk forward over
the candidate batch (batched-dot, never a loop).

All sharding specs are built once at trace-construction time — nothing is
recomputed per call. The lookup strategy is selectable per packed group via
``ServeConfig.strategy``: a registry name (``'picasso' | 'hybrid' | 'ps' |
'picasso_l2' | 'mp_nodedup' | 'allgather_rows'``) broadcasts,
``'mixed'``/``'auto'`` or a ``{gid: name}`` dict serves each
group through its own assigned path (see ``repro.core.assign``), so serving
benchmarks can A/B pure against mixed layouts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.features import field_index, pack_group
from repro.core.packing import PicassoPlan
from repro.dist.sharding import batch_specs, emb_specs, replicated
from repro.engine import EmbeddingEngine
from repro.models.wdl import WDLModel


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-side engine knobs (mirrors TrainConfig for the sparse path)."""

    # registry name, 'mixed'/'auto', {gid: name}, or a StrategyAssignment
    strategy: Any = "picasso"
    use_cache: bool = True
    use_l2: bool = True   # L2 host tier (plan-budgeted, behind L1)
    # fused Pallas sparse kernels: 'auto' (backend default) | 'on' | 'off'
    use_fused_kernels: Any = "auto"


def _mesh_world(mesh, axes):
    return int(np.prod([mesh.shape[a] for a in axes]))


def make_serve_step(model: WDLModel, plan: PicassoPlan, mesh, axes, global_batch: int,
                    use_cache: bool = True, strategy: Any = "picasso",
                    scfg: Optional[ServeConfig] = None):
    """Forward-only scoring: batch -> sigmoid probabilities [B, n_tasks].

    ``scfg`` bundles the engine knobs; the bare ``use_cache``/``strategy``
    kwargs are kept as sugar and ignored when ``scfg`` is given.
    """
    scfg = scfg or ServeConfig(strategy=strategy, use_cache=use_cache)
    world = _mesh_world(mesh, axes)
    engine = EmbeddingEngine(plan, axes, world, strategy=scfg.strategy,
                             use_cache=scfg.use_cache, use_l2=scfg.use_l2,
                             use_fused_kernels=scfg.use_fused_kernels)

    # specs are static per (model, plan): build them once, not per trace call
    especs = emb_specs(plan, axes)
    rep = replicated(jax.eval_shape(lambda k: model.init_dense(k),
                                    jax.random.PRNGKey(0)))

    def local_fn(emb, dense, batch):
        packed = {g.gid: pack_group(g, batch["fields"]) for g in plan.groups}
        pooled, _ctx = engine.forward(emb, packed)
        logits = model.apply(dense, pooled, batch)
        return jax.nn.sigmoid(logits)

    def wrapped(state, batch):
        f = jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(especs, rep, batch_specs(batch, axes)),
                          out_specs=P(axes, None), check_vma=False)
        return f(state["emb"], state["dense"], batch)

    return jax.jit(wrapped)


def make_retrieval_step(model: WDLModel, plan: PicassoPlan, mesh, axes,
                        n_candidates: int, top_k: int = 100,
                        strategy: Any = "picasso",
                        scfg: Optional[ServeConfig] = None,
                        score_chunk: Optional[int] = None):
    """Two-tower retrieval: one user -> top-k of 1M+ candidates.

    The user representation is computed from the behaviour sequence
    (self_attn_seq / capsule interaction); candidate ids are mesh-sharded,
    their rows come from the *local* slice of the MP item table via the same
    packed-lookup engine, scores are a batched dot, and top-k is
    local-top-k -> all_gather -> global-top-k.

    ``score_chunk`` bounds per-shard memory: the local candidate slice is
    scored in fixed-size chunks (``lax.scan`` over ``lax.top_k``-merged
    running bests — a streaming top-k), so the engine's bucket capacity and
    every intermediate scale with the *chunk*, not with ``n_candidates``.
    ``None``/0 scores the whole local slice in one chunk (the old bound).
    The merge keeps the single-chunk tie-break order, so chunked and
    unchunked retrieval return identical results.

    Retrieval always runs uncached: only ``scfg.strategy`` is honoured here;
    ``scfg.use_cache`` is ignored (the candidate chunk has no skew head for
    the hot tier to absorb, and retrieval plans are built cache-free).
    """
    scfg = scfg or ServeConfig(strategy=strategy, use_cache=False)
    world = _mesh_world(mesh, axes)
    cand_local = n_candidates // world
    chunk = int(score_chunk) if score_chunk else cand_local
    chunk = max(1, min(chunk, cand_local))
    n_chunks = -(-cand_local // chunk)
    pad = n_chunks * chunk - cand_local
    fidx = field_index(model.plan)
    item_field = next(f.name for f in model.cfg.fields
                      if f.pooling == "none" and f.max_len > 1)
    gid = fidx[item_field].gid

    engine = EmbeddingEngine(plan, axes, world, strategy=scfg.strategy,
                             use_cache=False,
                             use_fused_kernels=scfg.use_fused_kernels)
    # candidate tower: same assignment, but buckets sized for one score
    # chunk — per-shard memory no longer grows with n_candidates
    cand_engine = EmbeddingEngine(
        plan, axes, world, strategy=scfg.strategy, use_cache=False,
        use_fused_kernels=scfg.use_fused_kernels,
        capacity={**plan.capacity, gid: max(plan.capacity[gid], chunk)})

    especs = emb_specs(plan, axes)
    rep = replicated(jax.eval_shape(lambda k: model.init_dense(k),
                                    jax.random.PRNGKey(0)))

    def local_fn(emb, dense, batch, cand_ids):
        # --- user tower (batch=1, replicated compute) -----------------------
        packed = {g.gid: pack_group(g, batch["fields"]) for g in plan.groups}
        pooled, _ctx = engine.forward(emb, packed)
        user = model.user_repr(dense, pooled, batch)          # [K, D]

        # --- candidate tower: chunked scoring + streaming top-k -------------
        ids_flat = cand_ids.reshape(-1)
        if pad:
            ids_flat = jnp.concatenate(
                [ids_flat, jnp.broadcast_to(ids_flat[:1], (pad,))])
        valid = jnp.arange(n_chunks * chunk, dtype=jnp.int32) < cand_local
        k = min(top_k, cand_local)

        def score_one(carry, x):
            best_v, best_i = carry
            cids, cval = x
            rows = cand_engine.lookup_rows(emb, gid, cids)
            sc = jnp.max(rows @ user.T, axis=-1).astype(jnp.float32)
            sc = jnp.where(cval, sc, -jnp.inf)      # mask the pad tail
            av = jnp.concatenate([best_v, sc])
            ai = jnp.concatenate([best_i, cids])
            nv, nix = lax.top_k(av, k)
            return (nv, jnp.take(ai, nix)), None

        init = (jnp.full((k,), -jnp.inf, jnp.float32),
                jnp.zeros((k,), cand_ids.dtype))
        (sv, s_ids), _ = lax.scan(
            score_one, init, (ids_flat.reshape(n_chunks, chunk),
                              valid.reshape(n_chunks, chunk)))
        gv = lax.all_gather(sv, axes, tiled=True)              # [world*k]
        gi = lax.all_gather(s_ids, axes, tiled=True)
        fv, fi = lax.top_k(gv, top_k)
        return fv, gi[fi]

    def wrapped(state, batch, cand_ids):
        bspec = jax.tree.map(lambda x: P(*((None,) * len(x.shape))), batch)
        f = jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(especs, rep, bspec, P(axes)),
                          out_specs=(P(), P()), check_vma=False)
        return f(state["emb"], state["dense"], batch, cand_ids)

    return jax.jit(wrapped)
