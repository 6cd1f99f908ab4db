"""Dry-run cell builders: (arch x input-shape x mesh) -> lowerable step.

Every cell returns a ``Cell``: a python callable suitable for
``jax.jit(fn, in_shardings=...).lower(*abstract_args)`` plus the abstract
args (ShapeDtypeStruct — no allocation) and metadata for the roofline
(MODEL_FLOPS, dtype, parallelism notes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig, SchNetConfig, ShapeSpec, WDLConfig, get_config, get_shapes
from repro.core.packing import PicassoPlan, make_plan
from repro.data.synthetic import batch_spec
from repro.dist.sharding import batch_specs, state_specs, to_named
from repro.embedding.state import abstract_embedding_state
from repro.layers.transformer import (abstract_kv_cache, abstract_lm_params, lm_decode_step,
                                      lm_loss, lm_param_specs, lm_prefill)
from repro.models.schnet import init_schnet, schnet_loss
from repro.models.wdl import WDLModel
from repro.optim.optimizers import adam_init, adam_update
from repro.serve.serve_step import make_retrieval_step, make_serve_step
from repro.train.train_step import TrainConfig, make_train_step


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    fn: Callable          # already jit-wrapped (or plain fn + shardings)
    args: Tuple[Any, ...]  # abstract args
    model_flops: float     # 6*N*D (or per-kind analytic estimate), fwd+bwd
    note: str = ""


def _abstract(tree, mesh, specs):
    """Attach NamedShardings to ShapeDtypeStructs (no allocation)."""
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct,)))


def _rep_specs(tree):
    return jax.tree.map(lambda x: P(*((None,) * len(x.shape))), tree)


# ---------------------------------------------------------------------------
# recsys cells
# ---------------------------------------------------------------------------


def _wdl_plan(cfg: WDLConfig, world: int, per_dev_batch: int, **kw) -> PicassoPlan:
    return make_plan(cfg, world=world, per_device_batch=max(per_dev_batch, 1),
                     hot_bytes=kw.pop("hot_bytes", 1 << 30), **kw)


def _wdl_flops(cfg: WDLConfig, plan: PicassoPlan, batch: int, train: bool) -> float:
    """Analytic useful-FLOPs: embedding ~0; interactions + MLP dominate."""
    mults = 0.0
    base = sum(f.dim for f in cfg.fields if f.pooling != "none")
    dense_dim = cfg.dense_arch[-1] if cfg.dense_arch else cfg.n_dense
    base += dense_dim
    d = base
    for it in cfg.interactions:
        if it.kind == "cross":
            mults += it.kwargs.get("n_layers", 3) * base * base
        elif it.kind == "self_attn_seq":
            f0 = cfg.field_by_name(it.fields[0])
            L, D = f0.max_len, f0.dim
            mults += it.kwargs.get("n_blocks", 2) * (4 * L * D * D + 2 * L * L * D + 2 * L * D * D)
        elif it.kind == "capsule":
            f0 = cfg.field_by_name(it.fields[0])
            mults += f0.max_len * f0.dim * f0.dim * (1 + it.kwargs.get("routing_iters", 3))
    prev = None
    for h in (cfg.dense_arch or ()):
        mults += (prev or cfg.n_dense) * h
        prev = h
    prev = None
    for h in cfg.mlp_dims:
        mults += (prev or d) * h
        prev = h
    fwd = 2.0 * batch * mults
    return fwd * (3.0 if train else 1.0)


def build_wdl_cell(arch: str, shape: ShapeSpec, mesh, smoke: bool = False,
                   tcfg: Optional[TrainConfig] = None, plan_kw: Optional[dict] = None,
                   strategy: Any = "picasso") -> Cell:
    """``strategy`` selects the EmbeddingEngine lookup path for the
    serve/retrieval cells — a registry name (broadcast), ``'mixed'``/
    ``'auto'`` (per-group cost-model assignment), or a ``{gid: name}`` dict;
    train cells take the same spec from ``tcfg.strategy``."""
    cfg = get_config(arch, smoke=smoke)
    axes = tuple(mesh.axis_names)
    world = int(mesh.devices.size)
    plan_kw = dict(plan_kw or {})

    if shape.kind == "retrieval":
        nc = shape["n_candidates"]
        has_seq = any(f.pooling == "none" and f.max_len > 1 for f in cfg.fields)
        if has_seq:
            # two-tower: encode user once, dot against mesh-sharded candidates
            nc_pad = ((nc + world - 1) // world) * world
            plan = _wdl_plan(cfg, world, 1, **plan_kw)
            model = WDLModel(cfg, plan)
            step = make_retrieval_step(model, plan, mesh, axes, nc_pad,
                                       strategy=strategy)
            state = _abstract_state(model, plan, mesh, axes)
            batch = _abstract(batch_spec(cfg, 1), mesh, _rep_specs(batch_spec(cfg, 1)))
            cand = jax.ShapeDtypeStruct((nc_pad,), jnp.int32,
                                        sharding=NamedSharding(mesh, P(axes)))
            flops = 2.0 * nc_pad * plan.group(next(iter(plan.capacity))).dim
            return Cell(arch, shape.name, step, (state, batch, cand), flops,
                        "two-tower retrieval, distributed top-k")
        # pure-CTR arch: retrieval == bulk forward over the candidate batch
        nc_pad = ((nc + world - 1) // world) * world
        plan = _wdl_plan(cfg, world, max(1, nc_pad // world), **plan_kw)
        model = WDLModel(cfg, plan)
        step = make_serve_step(model, plan, mesh, axes, nc_pad, strategy=strategy)
        state = _abstract_state(model, plan, mesh, axes)
        bsp = batch_spec(cfg, nc_pad)
        batch = _abstract(bsp, mesh, batch_specs(bsp, axes))
        return Cell(arch, shape.name, step, (state, batch),
                    _wdl_flops(cfg, plan, nc_pad, False),
                    "CTR bulk candidate scoring (batched, no loop)")

    gb = shape["batch"]
    per_dev = max(1, gb // world)
    plan = _wdl_plan(cfg, world, per_dev, **plan_kw)
    model = WDLModel(cfg, plan)
    state = _abstract_state(model, plan, mesh, axes)
    bsp = batch_spec(cfg, gb)
    batch = _abstract(bsp, mesh, batch_specs(bsp, axes))

    if shape.kind == "train":
        step, _ = make_train_step(model, plan, mesh, axes, gb, tcfg or TrainConfig())
        return Cell(arch, shape.name, step, (state, batch),
                    _wdl_flops(cfg, plan, gb, True), "hybrid MP/DP train")
    step = make_serve_step(model, plan, mesh, axes, gb, strategy=strategy)
    return Cell(arch, shape.name, step, (state, batch),
                _wdl_flops(cfg, plan, gb, False), "forward scoring")


def _abstract_state(model: WDLModel, plan: PicassoPlan, mesh, axes) -> Dict:
    emb = abstract_embedding_state(plan)
    dense = jax.eval_shape(model.init_dense, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adam_init, dense)
    state = {"emb": {str(g): s for g, s in emb.items()}, "dense": dense,
             "opt": opt, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = state_specs(plan, axes, dense, opt)
    return _abstract(state, mesh, specs)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_mesh_info(mesh):
    axes = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in axes if a != "model")
    shape = {a: mesh.shape[a] for a in axes}
    return axes, dp_axes, shape


def _moe_exec(cfg, mesh, dp_axes, moe_shard: bool):
    """Token-group MoE dispatch: groups == data shards, buffers pinned
    group-sharded so the dispatch sort/scatter stays shard-local."""
    if cfg.moe is None or not moe_shard:
        return None
    dpn = int(np.prod([mesh.shape[a] for a in dp_axes]))
    if dpn <= 1:
        return None
    sh = NamedSharding(mesh, P(dp_axes, None, None, None))  # [G, E, C, D]
    return (dpn, sh)


def make_lm_train_step(cfg: LMConfig, mesh, attn_chunk=512, loss_chunk=512,
                       remat=True, lr=1e-4, shard_mode: str = "fsdp",
                       unroll: bool = False, moe_shard: bool = False):
    """shard_mode: 'fsdp' (params+moments dp-sharded; per-layer gathers) |
    'zero1' (params dp-replicated, moments dp-sharded: one reduce-scatter +
    all-gather per step instead of 3x per-layer gathers)."""
    axes, dp_axes, mshape = _lm_mesh_info(mesh)
    pspecs = lm_param_specs(cfg, mshape, dp_axes, fsdp=shard_mode == "fsdp")
    mspecs = lm_param_specs(cfg, mshape, dp_axes, fsdp=True)  # moments always sharded
    mexec = _moe_exec(cfg, mesh, dp_axes, moe_shard)

    def step(params, opt, tokens):
        loss, g = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, tokens, attn_chunk=attn_chunk, remat=remat,
                              loss_chunk=loss_chunk, unroll=unroll,
                              moe_exec=mexec))(params)
        params2, opt2 = adam_update(params, g, opt, lr)
        return params2, opt2, loss

    params = abstract_lm_params(cfg)
    opt = jax.eval_shape(adam_init, params)
    ospecs = {"m": mspecs, "v": mspecs, "t": P()}
    in_sh = (to_named(mesh, pspecs), to_named(mesh, ospecs),
             NamedSharding(mesh, P(dp_axes, None)))
    out_sh = (to_named(mesh, pspecs), to_named(mesh, ospecs), NamedSharding(mesh, P()))
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0, 1))
    return fn, params, opt, pspecs


def _cache_specs(cfg: LMConfig, batch: int, dp: Tuple[str, ...], mshape) -> P:
    dpn = int(np.prod([mshape[a] for a in dp]))
    b_ax = dp if batch % dpn == 0 and batch >= dpn else None
    return P(None, b_ax, "model", None, None)  # seq-sharded KV (flash-decode)


def build_lm_cell(arch: str, shape: ShapeSpec, mesh, smoke: bool = False,
                  n_layers_override: Optional[int] = None,
                  lm_kw: Optional[dict] = None) -> Cell:
    cfg = get_config(arch, smoke=smoke)
    if n_layers_override is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers_override)
    lm_kw = dict(lm_kw or {})
    axes, dp_axes, mshape = _lm_mesh_info(mesh)
    seq, gb = shape["seq_len"], shape["global_batch"]
    n, na = cfg.param_count(), cfg.active_param_count()

    if shape.kind == "train":
        shard_mode = lm_kw.get("shard_mode", "fsdp")
        fn, params, opt, pspecs = make_lm_train_step(cfg, mesh, **lm_kw)
        mspecs = lm_param_specs(cfg, mshape, dp_axes, fsdp=True)
        toks = jax.ShapeDtypeStruct((gb, seq), jnp.int32,
                                    sharding=NamedSharding(mesh, P(dp_axes, None)))
        args = (_abstract(params, mesh, pspecs),
                _abstract(opt, mesh, {"m": mspecs, "v": mspecs, "t": P()}),
                toks)
        return Cell(arch, shape.name, fn, args, 6.0 * na * gb * seq,
                    f"TP+{shard_mode} train")

    if shape.kind == "prefill":
        pspecs = lm_param_specs(cfg, mshape, dp_axes)
        csp = _cache_specs(cfg, gb, dp_axes, mshape)
        unroll = lm_kw.get("unroll", False)
        mexec = _moe_exec(cfg, mesh, dp_axes, lm_kw.get("moe_shard", False))

        def step(params, tokens):
            return lm_prefill(cfg, params, tokens, attn_chunk=512, unroll=unroll,
                              moe_exec=mexec)

        fn = jax.jit(step,
                     in_shardings=(to_named(mesh, pspecs),
                                   NamedSharding(mesh, P(dp_axes, None))),
                     out_shardings=(NamedSharding(mesh, P(dp_axes, "model")),
                                    jax.tree.map(lambda _: NamedSharding(mesh, csp),
                                                 abstract_kv_cache(cfg, gb, seq))))
        toks = jax.ShapeDtypeStruct((gb, seq), jnp.int32,
                                    sharding=NamedSharding(mesh, P(dp_axes, None)))
        args = (_abstract(abstract_lm_params(cfg), mesh, pspecs), toks)
        return Cell(arch, shape.name, fn, args, 2.0 * na * gb * seq, "prefill")

    # decode: one new token against a KV cache of seq_len (ring-buffer for SWA)
    cache_len = min(seq, cfg.swa_window) if cfg.swa_window else seq
    pspecs = lm_param_specs(cfg, mshape, dp_axes)
    csp = _cache_specs(cfg, gb, dp_axes, mshape)
    cache = abstract_kv_cache(cfg, gb, cache_len)

    unroll = lm_kw.get("unroll", False)

    def step(params, cache, tokens, length):
        slot = length % cache_len
        return lm_decode_step(cfg, params, cache, tokens, slot, unroll=unroll)

    fn = jax.jit(step,
                 in_shardings=(to_named(mesh, pspecs),
                               jax.tree.map(lambda _: NamedSharding(mesh, csp), cache),
                               NamedSharding(mesh, P(None, None)),
                               NamedSharding(mesh, P())),
                 out_shardings=(NamedSharding(mesh, P(None, "model")),
                                jax.tree.map(lambda _: NamedSharding(mesh, csp), cache)),
                 donate_argnums=(1,))
    toks = jax.ShapeDtypeStruct((gb, 1), jnp.int32,
                                sharding=NamedSharding(mesh, P(None, None)))
    ln = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    args = (_abstract(abstract_lm_params(cfg), mesh, pspecs),
            jax.tree.map(lambda c: jax.ShapeDtypeStruct(
                c.shape, c.dtype, sharding=NamedSharding(mesh, csp)), cache),
            toks, ln)
    return Cell(arch, shape.name, fn, args, 2.0 * na * gb,
                f"decode, kv={cache_len}" + (" (SWA ring)" if cfg.swa_window else ""))


# ---------------------------------------------------------------------------
# SchNet cells
# ---------------------------------------------------------------------------


def make_schnet_step(cfg: SchNetConfig, mesh, d_feat: int, batched: bool, lr=1e-3):
    axes = tuple(mesh.axis_names)

    def local_step(params, opt, batch):
        loss, g = jax.value_and_grad(
            lambda p: schnet_loss(cfg, p, batch, axes=axes))(params)
        g = lax.pmean(g, axes)
        loss = lax.pmean(loss, axes)
        params2, opt2 = adam_update(params, g, opt, lr)
        return params2, opt2, loss

    params = jax.eval_shape(functools.partial(init_schnet, cfg, d_feat=d_feat),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(adam_init, params)
    rep = _rep_specs(params)
    orep = _rep_specs(opt)

    def batch_spec_fn(batch):
        sh = {}
        for k, v in batch.items():
            if k in ("src", "dst", "dist", "edge_w"):
                sh[k] = P(axes, *((None,) * (len(v.shape) - 1)))
            elif k == "nodes" and not batched:
                sh[k] = P(*((None,) * len(v.shape)))
            else:
                sh[k] = P(*((None,) * len(v.shape)))
        return sh

    def wrapped(params, opt, batch):
        f = jax.shard_map(local_step, mesh=mesh,
                          in_specs=(rep, orep, batch_spec_fn(batch)),
                          out_specs=(rep, orep, P()), check_vma=False)
        return f(params, opt, batch)

    return jax.jit(wrapped, donate_argnums=(0, 1)), params, opt, rep, orep, batch_spec_fn


def _pad(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def build_gnn_cell(arch: str, shape: ShapeSpec, mesh, smoke: bool = False) -> Cell:
    cfg = get_config(arch, smoke=smoke)
    axes = tuple(mesh.axis_names)
    world = int(mesh.devices.size)

    if shape.kind == "graph_minibatch":
        f0, f1, bn = shape["fanout0"], shape["fanout1"], shape["batch_nodes"]
        n_nodes = _pad(bn * (1 + f0 + f0 * f1) + 64, world)
        n_edges = _pad(bn * f0 + bn * f0 * f1, world)
        d_feat = 0
        note = f"sampled subgraph {n_nodes}n/{n_edges}e (fanout {f0}-{f1})"
    elif shape.kind == "graph_batched":
        b, nn, ne = shape["batch"], shape["n_nodes"], shape["n_edges"]
        n_nodes, n_edges, d_feat = _pad(b * nn, world), _pad(b * ne, world), 0
        note = f"{b} molecules batched"
    else:
        n_nodes = shape["n_nodes"]
        n_edges = _pad(shape["n_edges"], world)
        d_feat = shape["d_feat"]
        note = "full-graph"

    batched = shape.kind == "graph_batched"
    fn, params, opt, rep, orep, bspec_fn = make_schnet_step(cfg, mesh, d_feat, batched)

    batch = {
        "nodes": jax.ShapeDtypeStruct((n_nodes, d_feat) if d_feat else (n_nodes,),
                                      jnp.float32 if d_feat else jnp.int32),
        "src": jax.ShapeDtypeStruct((n_edges,), jnp.int32),
        "dst": jax.ShapeDtypeStruct((n_edges,), jnp.int32),
        "dist": jax.ShapeDtypeStruct((n_edges,), jnp.float32),
        "edge_w": jax.ShapeDtypeStruct((n_edges,), jnp.float32),
    }
    if batched:
        ng = shape["batch"]
        batch["graph_ids"] = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
        batch["target"] = jax.ShapeDtypeStruct((ng,), jnp.float32)
    else:
        batch["target"] = jax.ShapeDtypeStruct((n_nodes,), jnp.float32)
        batch["node_w"] = jax.ShapeDtypeStruct((n_nodes,), jnp.float32)

    args = (_abstract(params, mesh, rep), _abstract(opt, mesh, orep),
            _abstract(batch, mesh, bspec_fn(batch)))
    d = cfg.d_hidden
    flops = 3.0 * 2.0 * (n_edges * (cfg.n_rbf * d + d * d) * cfg.n_interactions
                         + n_nodes * 4 * d * d * cfg.n_interactions)
    return Cell(arch, shape.name, fn, args, flops, note)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def build_cell(arch: str, shape: ShapeSpec, mesh, smoke: bool = False, **kw) -> Cell:
    cfg = get_config(arch, smoke=True)  # cheap kind probe
    kind = cfg.kind
    if kind == "wdl":
        kw.pop("n_layers_override", None)
        return build_wdl_cell(arch, shape, mesh, smoke=smoke, **kw)
    if kind == "lm":
        return build_lm_cell(arch, shape, mesh, smoke=smoke,
                             n_layers_override=kw.get("n_layers_override"),
                             lm_kw=kw.get("lm_kw"))
    return build_gnn_cell(arch, shape, mesh, smoke=smoke)


def arch_kind(arch: str) -> str:
    return get_config(arch, smoke=True).kind
