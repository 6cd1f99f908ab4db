"""Engine strategy layer on a 1x1 mesh: the full shard_map path (unique,
partition, Shuffle/Stitch, pooling, sparse adagrad, HybridHash) vs the dense
EmbeddingBag oracle, exercised through ``repro.engine`` strategies.
Multi-device equivalence is in test_distributed.py."""
import functools

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:
    from hypothesis_fallback import given, settings, st

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import packed_embedding as pe
from repro.core.hashing import scramble, scramble_np
from jax import shard_map
from repro.embedding.bag import embedding_bag
from repro.embedding.state import EmbeddingState
from repro.engine import PicassoStrategy

AXES = ("data", "model")


def _group_state(table, hot_keys=None, hot_rows=None) -> EmbeddingState:
    """Single-group EmbeddingState around a dense table (tests only)."""
    v, d = table.shape
    if hot_keys is not None:
        cache = pe.CacheState(keys=hot_keys, rows=hot_rows,
                              acc=jnp.zeros((hot_keys.shape[0], 1), jnp.float32))
    else:
        cache = pe.init_cache(0, d, v)
    return EmbeddingState(w=table, acc=jnp.zeros((v, 1), jnp.float32),
                          counts=jnp.zeros((v,), jnp.int32), cache=cache)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=1, max_size=64))
def test_fixed_unique_property(ids):
    ids = jnp.asarray(np.array(ids, np.int32))
    u = pe.fixed_unique(ids, sentinel=1 << 20)
    ref = np.unique(np.asarray(ids))
    n_u = int(u.n_uniq)
    assert n_u == len(ref)
    np.testing.assert_array_equal(np.asarray(u.uniq)[:n_u], ref)
    # inverse mapping reconstructs the input
    np.testing.assert_array_equal(np.asarray(u.uniq)[np.asarray(u.inv)], np.asarray(ids))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 10_000))
def test_scramble_bijective(vocab):
    ids = np.arange(min(vocab, 2048), dtype=np.int32)
    s = scramble_np(ids, vocab)
    assert len(np.unique(s)) == len(ids)
    assert s.min() >= 0 and s.max() < vocab


def _lookup1(mesh, table, ids, cap, hot_keys=None, hot_rows=None):
    strat = PicassoStrategy(axes=AXES, world=1, capacity={0: cap})

    def f(tsh, ids_l):
        gst = _group_state(tsh, hot_keys, hot_rows)
        rows_u, ctx = strat.lookup(gst, 0, ids_l, cache_on=hot_keys is not None)
        return jnp.take(rows_u, ctx.inv, axis=0)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(AXES, None), P()),
                             out_specs=P(), check_vma=False))(table, ids)


def test_lookup_matches_gather(mesh1):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 7)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, 40).astype(np.int32))
    got = _lookup1(mesh1, table, ids, cap=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(table)[np.asarray(ids)],
                               atol=1e-6)


def test_lookup_with_cache(mesh1):
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    hot_keys = jnp.asarray(np.array([2, 5, 9, 32, 32, 32, 32, 32], np.int32))
    hot_rows = jnp.where((hot_keys < 32)[:, None],
                         table[jnp.clip(hot_keys, 0, 31)], 0.0)
    ids = jnp.asarray(rng.integers(0, 32, 24).astype(np.int32))
    got = _lookup1(mesh1, table, ids, cap=24, hot_keys=hot_keys, hot_rows=hot_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(table)[np.asarray(ids)],
                               atol=1e-6)


def test_pool_matches_embedding_bag(mesh1):
    rng = np.random.default_rng(2)
    v, d, n, nb = 50, 6, 30, 8
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, nb, n)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=n).astype(np.float32))
    strat = PicassoStrategy(axes=AXES, world=1, capacity={0: n})

    def f(tsh, ids_l, w_l, seg_l):
        rows_u, ctx = strat.lookup(_group_state(tsh), 0, ids_l)
        return pe.pool(rows_u, ctx.inv, w_l, seg_l, nb)

    got = jax.jit(shard_map(f, mesh=mesh1,
                            in_specs=(P(AXES, None), P(), P(), P()),
                            out_specs=P(), check_vma=False))(table, ids, w, seg)
    exp = embedding_bag(table, ids, seg, nb, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5)


def test_sparse_adagrad_matches_dense(mesh1):
    rng = np.random.default_rng(3)
    v, d, n = 40, 5, 25
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    g_per_id = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    strat = PicassoStrategy(axes=AXES, world=1, capacity={0: n}, lr=0.1)

    def f(tsh, ids_l, g):
        gst = _group_state(tsh)
        rows_u, ctx = strat.lookup(gst, 0, ids_l)
        g_u = jax.ops.segment_sum(g, ctx.inv, num_segments=n)
        st2, _, _ = strat.apply_grads(gst, 0, ctx, g_u)
        return st2.w, st2.acc

    w2, a2 = jax.jit(shard_map(
        f, mesh=mesh1, in_specs=(P(AXES, None), P(), P()),
        out_specs=(P(AXES, None), P(AXES, None)), check_vma=False))(table, ids, g_per_id)

    gref = np.zeros((v, d), np.float32)
    np.add.at(gref, np.asarray(ids), np.asarray(g_per_id))
    accref = (gref ** 2).mean(-1, keepdims=True)
    wref = np.asarray(table) - 0.1 * gref / np.sqrt(accref + 1e-8)
    touched = np.abs(gref).max(-1) > 0
    np.testing.assert_allclose(np.asarray(w2)[touched], wref[touched], atol=1e-5)


def test_overflow_counted(mesh1):
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    ids = jnp.asarray(np.arange(32, dtype=np.int32))  # 32 distinct ids
    strat = PicassoStrategy(axes=AXES, world=1, capacity={0: 8})

    def f(tsh, ids_l):
        _, ctx = strat.lookup(_group_state(tsh), 0, ids_l)
        return ctx.routing.overflow.reshape(())

    ovf = jax.jit(shard_map(f, mesh=mesh1, in_specs=(P(AXES, None), P()),
                            out_specs=P(), check_vma=False))(table, ids)
    assert int(ovf) == 32 - 8  # uniques beyond capacity dropped & counted


def test_flush_cache_roundtrip(mesh1):
    """Flush writes hot rows back and reloads the top-k set consistently."""
    rng = np.random.default_rng(5)
    v, d, h = 32, 4, 8
    w = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    acc = jnp.zeros((v, 1), jnp.float32)
    counts = jnp.asarray(np.arange(v, dtype=np.int32))  # row 31 hottest
    cache = pe.init_cache(h, d, v)

    def f(w, acc, counts, ck, cr, ca):
        return pe.flush_cache(w, acc, counts, pe.CacheState(ck, cr, ca),
                              axes=AXES, world=1)

    w2, acc2, counts2, cache2 = jax.jit(shard_map(
        f, mesh=mesh1,
        in_specs=(P(AXES, None), P(AXES, None), P(AXES), P(), P(), P()),
        out_specs=(P(AXES, None), P(AXES, None), P(AXES),
                   pe.CacheState(P(), P(), P())),
        check_vma=False))(
        w, acc, counts, *cache)
    cache2 = pe.CacheState(*cache2)
    keys = np.asarray(cache2.keys)
    assert set(keys[keys < v]) == set(range(v - h, v))  # top-8 hottest rows
    rows = np.asarray(cache2.rows)
    for i, k in enumerate(keys):
        if k < v:
            np.testing.assert_allclose(rows[i], np.asarray(w)[k], atol=1e-6)
