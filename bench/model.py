"""The system under test, wired as ``repro.launch.train`` wires it.

``build`` makes the plan with the launcher's arguments (``hot_bytes``,
``flush_iters``, ``warmup_iters`` and the mesh from the configuration file,
``capacity_slack`` at its default), resolves the strategy with
``maybe_compile``, and builds ``WDLModel`` and the donating jitted train step
with ``--fused-kernels auto`` and ``--overlap auto``; where the configuration
states a ``matmul_precision``, the step is traced at it. ``init_state`` fills
the program's state in one jitted call with the benchmark's own weights
(``bench/weights.py``), so that the reference can rebuild them from the seed.
``make_readers`` reads what the correctness check compares out of the
program's state, by the program's table layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights


@dataclasses.dataclass
class SUT:
    cfg_json: Dict
    ref: Any            # the configuration's reference module
    wdl: Any            # repro WDLConfig
    plan: Any
    mesh: Any
    axes: tuple
    step: Any           # jitted (state, batch) -> (state, metrics), donating
    sspecs: Any
    model: Any
    global_batch: int

    @property
    def fields(self):
        """The reference's ``Field``s, as ``generator`` takes them."""
        return self.ref.fields(self.cfg_json)

    def stream_of(self, name: str) -> int:
        return self.ref.EMB_STREAM0 + [f.name for f in self.fields].index(name)


def wdl_config(cfg: Dict, ref):
    from repro.configs.base import FeatureField, InteractionSpec, WDLConfig
    fields = tuple(FeatureField(name=f.name, vocab=f.vocab, dim=f.dim, max_len=f.hot,
                                pooling="sum") for f in ref.fields(cfg))
    inter = tuple(InteractionSpec(it["kind"], kwargs={k: v for k, v in it.items()
                                                      if k != "kind"})
                  for it in cfg["interactions"])
    return WDLConfig(name=cfg["name"], fields=fields, n_dense=cfg.get("n_dense", 0),
                     interactions=inter, mlp_dims=tuple(cfg["mlp_dims"]),
                     dense_arch=tuple(cfg.get("bottom_mlp", ())))


PRECISIONS = ("default", "high", "highest")


class AtPrecision:
    """A jitted step called, and lowered, under ``jax.default_matmul_precision``,
    so that it is traced at that precision: the precision is part of the
    key under which JAX keeps the traced step."""

    def __init__(self, step, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"matmul_precision {precision!r}: one of {PRECISIONS}")
        self.step, self.precision = step, precision

    def __call__(self, state, batch):
        with jax.default_matmul_precision(self.precision):
            return self.step(state, batch)

    def lower(self, *args):
        with jax.default_matmul_precision(self.precision):
            return self.step.lower(*args)


def build(cfg: Dict, ref, step_wrapper=None) -> SUT:
    from repro.core.packing import make_plan
    from repro.engine import maybe_compile
    from repro.launch.mesh import make_mesh
    from repro.models.wdl import WDLModel
    from repro.train.train_step import TrainConfig, make_train_step

    shape = tuple(cfg["mesh"])
    axes = ("data", "model")[: len(shape)]
    mesh = make_mesh(shape, axes)
    world = int(np.prod(shape))
    gb = int(cfg["global_batch"])
    wdl = wdl_config(cfg, ref)
    plan = make_plan(wdl, world=world, per_device_batch=gb // world,
                     hot_bytes=int(cfg["hot_bytes"]),
                     flush_iters=int(cfg["flush_iters"]),
                     warmup_iters=int(cfg["warmup_iters"]), mesh_shape=shape)
    strategy = maybe_compile(plan, cfg["strategy"], use_cache=True)
    model = WDLModel(wdl, plan)
    tcfg = TrainConfig(strategy=strategy, use_cache=True, use_interleave=True,
                       use_fused_kernels="auto", overlap="auto",
                       grad_compress="none", pin_l2=False,
                       lr_emb=float(cfg["lr_emb"]), lr_dense=float(cfg["lr_dense"]))
    step, sspecs = make_train_step(model, plan, mesh, axes, gb, tcfg, donate=True)
    if step_wrapper is not None:
        step = step_wrapper(step)
    if "matmul_precision" in cfg:
        step = AtPrecision(step, cfg["matmul_precision"])
    return SUT(cfg, ref, wdl, plan, mesh, axes, step, sspecs, model, gb)


def _set(tree: Dict, path, value):
    node = tree
    for p in path[:-1]:
        node = node.get(p) if isinstance(node, dict) else None
    if not isinstance(node, dict) or path[-1] not in node:
        raise ValueError(f"the program has no dense leaf {'/'.join(path)}")
    if node[path[-1]].shape != value.shape:
        raise ValueError(f"dense leaf {'/'.join(path)}: program shape "
                         f"{node[path[-1]].shape}, reference {value.shape}")
    node[path[-1]] = value


def _layout(sut: SUT, g):
    """A packed group's tables in row order: their first rows, vocabularies
    and weight streams."""
    tables = sorted(g.tables, key=lambda t: g.table_offsets[t.name])
    starts = np.array([g.table_offsets[t.name] for t in tables], np.int64)
    vocab = np.array([t.vocab for t in tables], np.int64)
    streams = np.array([sut.stream_of(t.name) for t in tables], np.int64)
    return [t.name for t in tables], starts, vocab, streams


def _table_of(rows, starts):
    """Index (in row order) of the table each global row belongs to: an
    elementwise count of the tables that start at or before it, so a
    row-sharded array is computed shard by shard."""
    k = jnp.zeros(rows.shape, jnp.int32)
    for s in starts[1:]:
        k = k + (rows >= int(s)).astype(jnp.int32)
    return k


def _initial_rows(sut: SUT, g, seed, rows):
    """The benchmark's initial value of each global row in ``rows`` of group
    ``g``: ``[len(rows), dim]``, zero on padding rows."""
    _, starts, vocab, streams = _layout(sut, g)
    k = _table_of(rows, starts)
    local = rows - jnp.asarray(starts, jnp.int32)[k]
    stream = jnp.asarray(streams, jnp.uint32)[k]
    w = weights.table_rows(seed, stream, jnp.where(local < jnp.asarray(vocab, jnp.int32)[k],
                                                   local, 0), g.dim, sut.ref.emb_std(g.dim))
    real = rows < int(starts[-1] + vocab[-1])
    return jnp.where(real[:, None], w, 0.0)


BLOCK = 1 << 20  # rows a reader works on at a time


def _local_rows(sut: SUT, g):
    """Rows per chip of a row-sharded table, and this chip's first row
    (inside ``shard_map``)."""
    from jax import lax
    rps = g.rows // math.prod(sut.cfg_json["mesh"])
    return rps, lax.axis_index(sut.axes).astype(jnp.int32) * rps


def _shard_map(sut: SUT, f, in_specs, out_specs):
    return jax.shard_map(f, mesh=sut.mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _blocked_table_sums(sut: SUT, g, row_values):
    """Per table of ``g``: the sum over this chip's rows of
    ``row_values(rows, start)``, a ``[n]`` vector for the ``n`` rows from
    local row ``start``, taken in blocks of ``BLOCK`` rows, so no temporary
    grows with the table. Returns a ``[tables]`` vector (this chip's part)."""
    from jax import lax
    names, starts, _, _ = _layout(sut, g)
    rps, base = _local_rows(sut, g)
    blk = min(BLOCK, rps)
    n_blocks = -(-rps // blk)

    def body(i, acc):
        start = jnp.minimum(i * blk, rps - blk)    # the last block may overlap
        rows = base + start + jnp.arange(blk, dtype=jnp.int32)
        fresh = start + jnp.arange(blk, dtype=jnp.int32) >= i * blk
        v = jnp.where(fresh, row_values(rows, start), 0.0)
        k = _table_of(rows, starts)
        return acc + jnp.stack([jnp.sum(jnp.where(k == j, v, 0.0))
                                for j in range(len(names))])

    return lax.fori_loop(0, n_blocks, body, jnp.zeros((len(names),), jnp.float32))


def init_state(sut: SUT, seed: int):
    """The program's train state, every table row and dense leaf from the
    seed, made on the device in one jitted call. The step counter starts at
    ``flush_iters - 1``, so the first step ends in a tier flush and the next
    ones read and update rows in the hot tier."""
    return state_fn(sut)(weights.seed_words(seed))


def state_fn(sut: SUT):
    """The jitted function of the seed's words that ``init_state`` calls.
    Each chip makes its own rows of every table, a block at a time."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import to_named
    from repro.optim.optimizers import adam_init
    from repro.train.train_step import init_state as program_init

    ref, plan, cfg = sut.ref, sut.plan, sut.cfg_json
    spec = ref.dense_spec(cfg)

    def build(seed):
        st = program_init(sut.model, plan, jax.random.PRNGKey(0))
        dense = jax.tree.map(lambda x: x, st["dense"])
        for k, (path, shape, std) in enumerate(spec):
            _set(dense, path, weights.leaf(seed, ref.DENSE_STREAM0 + k, shape, std))
        if len(jax.tree.leaves(dense)) != len(spec):
            raise ValueError(f"program has {len(jax.tree.leaves(dense))} dense "
                             f"leaves, the reference {len(spec)}")
        emb = {}
        for g in plan.groups:
            def local(seed, g=g):
                rps, base = _local_rows(sut, g)
                blk = min(BLOCK, rps)

                def body(i, w):
                    start = jnp.minimum(i * blk, rps - blk)
                    rows = base + start + jnp.arange(blk, dtype=jnp.int32)
                    return lax.dynamic_update_slice_in_dim(
                        w, _initial_rows(sut, g, seed, rows), start, 0)
                return lax.fori_loop(0, -(-rps // blk), body,
                                     jnp.zeros((rps, g.dim), jnp.float32))
            w = _shard_map(sut, local, (P(),), P(sut.axes, None))(seed)
            emb[str(g.gid)] = st["emb"][str(g.gid)]._replace(w=w)
        return {"emb": emb, "dense": dense, "opt": adam_init(dense),
                "step": jnp.asarray(plan.flush_iters - 1, jnp.int32)}

    return jax.jit(build, out_shardings=to_named(sut.mesh, sut.sspecs))


def _leaf_norms(tree, scale=1.0):
    return {"/".join(str(getattr(p, "key", p)) for p in path):
            jnp.sqrt(jnp.sum(jnp.square(x))) * scale
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}


def make_readers(sut: SUT):
    """Three jitted reads of the program's state, none donating:
    ``grad_norms(state)`` after step 1 (each leaf's first gradient, as the
    optimizer holds it: Adam's ``m / (1 - b1)``, and for a table field
    ``sqrt(d * sum(acc))`` over its rows, the accumulator being
    ``mean_d(g^2)`` from zero); ``change_norms(state, dense0, seed)``
    (``seed`` as ``weights.seed_words``) after the last compared step (each
    leaf's distance from its initial value, a table row read from the hot
    tier where the tier holds it); and ``table_norms(state, seed)`` after a
    tier flush (each table's distance from its initial value in the master
    alone). Each chip reads its own rows in blocks, so no read raises the
    peak memory of the step it checks."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    ref, plan = sut.ref, sut.plan
    b1 = ref.ADAM_B1
    rows_spec = P(sut.axes, None)

    def grad_norms(state):
        out = {f"dense/{k}": v for k, v in
               _leaf_norms(state["opt"]["m"], 1.0 / (1.0 - b1)).items()}
        for g in plan.groups:
            def local(acc, g=g):
                part = _blocked_table_sums(
                    sut, g, lambda rows, start: lax.dynamic_slice_in_dim(
                        acc, start, min(BLOCK, acc.shape[0]))[:, 0])
                return lax.psum(part, sut.axes)
            sums = _shard_map(sut, local, (rows_spec,), P())(
                state["emb"][str(g.gid)].acc)
            for j, n in enumerate(_layout(sut, g)[0]):
                out[f"emb/{n}"] = jnp.sqrt(g.dim * sums[j])
        return out

    def master_sq(g, w, keys, seed):
        """Per table of ``g``: the squared distance of its master rows from
        their initial values, leaving out the rows whose key is in ``keys``."""
        def local(w, keys, seed):
            rps, base = _local_rows(sut, g)
            mine = (keys >= base) & (keys < base + rps)
            left_out = jnp.zeros((rps,), bool).at[
                jnp.where(mine, keys - base, rps)].set(True, mode="drop")

            def d2(rows, start):
                blk = min(BLOCK, rps)
                wb = lax.dynamic_slice_in_dim(w, start, blk)
                lb = lax.dynamic_slice_in_dim(left_out, start, blk)
                sq = jnp.sum(jnp.square(wb - _initial_rows(sut, g, seed, rows)), -1)
                return jnp.where(lb, 0.0, sq)
            return lax.psum(_blocked_table_sums(sut, g, d2), sut.axes)
        return _shard_map(sut, local, (rows_spec, P(), P()), P())(w, keys, seed)

    def change_norms(state, dense0, seed):
        out = {f"dense/{k}": v for k, v in
               _leaf_norms(jax.tree.map(jnp.subtract, state["dense"], dense0)).items()}
        for g in plan.groups:
            st = state["emb"][str(g.gid)]
            names, starts, _, _ = _layout(sut, g)
            master = master_sq(g, st.w, st.cache.keys, seed)
            valid = st.cache.keys < g.rows
            kk = jnp.where(valid, st.cache.keys, 0)
            tier_sq = jnp.where(valid, jnp.sum(jnp.square(
                st.cache.rows - _initial_rows(sut, g, seed, kk)), -1), 0.0)
            tier = jax.ops.segment_sum(tier_sq, _table_of(kk, starts),
                                       num_segments=len(names))
            for j, n in enumerate(names):
                out[f"emb/{n}"] = jnp.sqrt(master[j] + tier[j])
        return out

    def table_norms(state, seed):
        out = {}
        for g in plan.groups:
            sq = master_sq(g, state["emb"][str(g.gid)].w, jnp.full((1,), g.rows, jnp.int32),
                           seed)
            for j, n in enumerate(_layout(sut, g)[0]):
                out[f"emb/{n}"] = jnp.sqrt(sq[j])
        return out

    return jax.jit(grad_norms), jax.jit(change_norms), jax.jit(table_norms)
