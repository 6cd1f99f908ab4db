"""8-device sanity sweep of the EmbeddingEngine strategy layer.

Exercises PicassoStrategy lookups (with/without the hot cache, with/without
overflow) and the sparse gradient path against dense numpy references.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import packed_embedding as pe
from repro.embedding.state import EmbeddingState
from repro.engine import PicassoStrategy
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
AXES = ("data", "model")
WORLD = 8
RPS = 16            # rows per shard
ROWS = RPS * WORLD  # 128
D = 5
N = 24              # ids per device

rng = np.random.default_rng(0)
table = jnp.asarray(rng.normal(size=(ROWS, D)).astype(np.float32))
ids = jnp.asarray(rng.integers(0, ROWS, size=(WORLD, N)).astype(np.int32))

# hot cache: rows 3, 7, 11 cached
hot_keys = jnp.asarray(np.array([3, 7, 11] + [ROWS] * 5, np.int32))
hot_rows = jnp.where((hot_keys < ROWS)[:, None], table[jnp.clip(hot_keys, 0, ROWS - 1)], 0.0)


def _state(tsh, acc=None, use_cache=False):
    cache = (pe.CacheState(hot_keys, hot_rows, jnp.zeros((hot_keys.shape[0], 1)))
             if use_cache else pe.init_cache(0, D, ROWS))
    return EmbeddingState(
        w=tsh, acc=acc if acc is not None else jnp.zeros((tsh.shape[0], 1)),
        counts=jnp.zeros((tsh.shape[0],), jnp.int32), cache=cache)


def run(table, ids, cap, use_cache):
    strat = PicassoStrategy(axes=AXES, world=WORLD, capacity={0: cap})

    def f(tsh, ids_l):
        st = _state(tsh, use_cache=use_cache)
        rows_u, ctx = strat.lookup(st, 0, ids_l.reshape(-1), cache_on=use_cache)
        per_id = jnp.take(rows_u, ctx.inv, axis=0)
        return per_id.reshape(1, N, D), ctx.routing.overflow.reshape(1)

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXES, None), P(AXES, None)),
        out_specs=(P(AXES, None, None), P(AXES)), check_vma=False))(table, ids)


expected = np.asarray(table)[np.asarray(ids)]

for cap, cache in [(N, False), (N, True), (8, False), (8, True)]:
    got, ovf = run(table, ids, cap, cache)
    ok = np.allclose(np.asarray(got), expected, atol=1e-6)
    print(f"cap={cap:3d} cache={cache}: match={ok} overflow={np.asarray(ovf).sum()}")

# gradient path: g_u routed back == dense scatter reference
strat = PicassoStrategy(axes=AXES, world=WORLD, capacity={0: N}, lr=0.1, eps=1e-8)


def step(tsh, acc, ids_l, g_per_id):
    st = _state(tsh, acc)
    rows_u, ctx = strat.lookup(st, 0, ids_l.reshape(-1))
    # pretend dL/d(per_id) = g_per_id -> accumulate onto unique slots
    g_u = jax.ops.segment_sum(g_per_id.reshape(-1, D), ctx.inv, num_segments=N)
    st2, _, _ = strat.apply_grads(st, 0, ctx, g_u)
    return st2.w, st2.acc


acc0 = jnp.zeros((ROWS, 1), jnp.float32)
g = jnp.asarray(rng.normal(size=(WORLD, N, D)).astype(np.float32))
w2, acc2 = jax.jit(shard_map(
    step, mesh=mesh,
    in_specs=(P(AXES, None), P(AXES, None), P(AXES, None), P(AXES, None, None)),
    out_specs=(P(AXES, None), P(AXES, None)), check_vma=False))(table, acc0, ids, g)

# reference: dense scatter-add + rowwise adagrad
gref = np.zeros((ROWS, D), np.float32)
np.add.at(gref, np.asarray(ids).ravel(), np.asarray(g).reshape(-1, D))
accref = (gref ** 2).mean(-1, keepdims=True)
wref = np.asarray(table) - 0.1 * gref / np.sqrt(accref + 1e-8)
touched = np.abs(gref).max(-1) > 0
print("grad path w match:", np.allclose(np.asarray(w2), wref, atol=1e-5))
print("acc match:", np.allclose(np.asarray(acc2)[touched], accref[touched], atol=1e-6))
