"""The hot tier's rank index (``packed_embedding.rank_index``).

The L1 tier carries a bitmap of its keys over the global row space and the
count of keys below each 32-row word, built whenever its keys are set. The
probe then reads a query's hit and slot from its word instead of searching
the sorted keys. This file pins:

1. the probe: ``(hit, slot)`` from the index equal the search's bit for bit
   on every row and on the unique's sentinel padding, and the host twin
   ``rank_index_np`` equals ``rank_index``;
2. the producers: every place that sets an L1 tier's keys (state init and
   its dry-run shapes, the three flushes, host migration, reshard, and
   checkpoint restore, with or without a stored index) leaves the tier
   carrying ``rank_index`` of its keys, and the L2 tier none;
3. the training path: two flush periods with the index give the same
   losses and state bit for bit as without it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import packed_embedding as pe
from repro.core.assign import apply_assignment, resolve_assignment
from repro.core.packing import make_plan, reshard_plan, revise_plan
from repro.data.synthetic import batch_stream
from repro.dist.sharding import batch_specs, to_named
from repro.embedding.state import (abstract_embedding_state,
                                   init_embedding_state, migrate_state,
                                   reshard_state)
from repro.models.wdl import WDLModel
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.train_step import TrainConfig, init_state, make_train_step

# ------------------------------------------------------------------ probe


def _tier(rows, valid, h=None):
    """Sorted distinct ``valid`` keys padded with sentinels to ``h`` slots."""
    valid = np.sort(np.asarray(valid, np.int32))
    h = len(valid) if h is None else h
    return np.concatenate([valid, np.full(h - len(valid), rows, np.int32)])


_RNG = np.random.default_rng(14)
PROBE_CASES = {
    "padded_tier": (1000, _tier(1000, _RNG.choice(1000, 37, replace=False),
                                h=64)),
    "word_edges_and_last_row": (640, _tier(640, [0, 31, 32, 63, 639],
                                           h=8)),
    "one_key": (300, _tier(300, [137])),
    "all_sentinel": (96, _tier(96, [], h=5)),
    "full_tier": (160, _tier(160, np.arange(160))),
    "rows_not_multiple_of_32": (77, _tier(77, [0, 31, 32, 63, 64, 76],
                                          h=10)),
}


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_rank_probe_equals_search(case):
    """Every row id as a query, then the sentinel padding a fixed-size
    unique leaves (``uvalid`` false): the index's ``(hit, slot)`` are the
    search's, and the host twin builds the same index."""
    rows, keys = PROBE_CASES[case]
    uniq = np.concatenate([np.arange(rows), np.full(7, rows)]).astype(np.int32)
    uvalid = uniq < rows
    index = pe.rank_index(jnp.asarray(keys), rows)
    host = pe.rank_index_np(keys, rows)
    for dev, h in zip(index, host):
        assert np.asarray(dev).dtype == h.dtype
        assert np.asarray(dev).shape == (pe.rank_words(rows),)
        np.testing.assert_array_equal(np.asarray(dev), h)
    hit_s, slot_s = pe.cache_probe(jnp.asarray(uniq), jnp.asarray(uvalid),
                                   jnp.asarray(keys))
    hit_r, slot_r = jax.jit(pe.cache_probe)(
        jnp.asarray(uniq), jnp.asarray(uvalid), jnp.asarray(keys), index)
    np.testing.assert_array_equal(np.asarray(hit_r), np.asarray(hit_s))
    np.testing.assert_array_equal(np.asarray(slot_r), np.asarray(slot_s))
    assert np.asarray(hit_r).sum() == (keys < rows).sum()


# -------------------------------------------------------------- producers


def _assert_indexed(tier, rows):
    bits, base = pe.rank_index_np(np.asarray(tier.keys), rows)
    np.testing.assert_array_equal(np.asarray(tier.bits), bits)
    np.testing.assert_array_equal(np.asarray(tier.base), base)


GB = 64
PLAN_KW = dict(hot_bytes=1 << 14, l2_bytes=1 << 16, flush_iters=5,
               warmup_iters=2)


def test_state_init_and_dry_run_shapes_carry_the_index():
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, **PLAN_KW)
    apply_assignment(plan, resolve_assignment(plan, "picasso_l2"))
    emb = init_embedding_state(jax.random.PRNGKey(0), plan)
    abstract = abstract_embedding_state(plan)
    for g in plan.groups:
        st = emb[g.gid]
        assert plan.cache_rows[g.gid] > 0
        _assert_indexed(st.cache, g.rows)
        assert st.l2 is None or st.l2.bits is None
        assert (jax.tree.structure(abstract[g.gid])
                == jax.tree.structure(st))
        for a, x in zip(jax.tree.leaves(abstract[g.gid]),
                        jax.tree.leaves(st)):
            assert (a.shape, a.dtype) == (x.shape, x.dtype)


def _put(mesh, axes, batch):
    return jax.device_put(batch, to_named(mesh, batch_specs(batch, axes)))


def _strip(state):
    """The same state with every hot tier's index taken off."""
    return {**state, "emb": {
        k: st._replace(cache=st.cache._replace(bits=None, base=None))
        for k, st in state["emb"].items()}}


RUNS = {  # case -> (strategy, cache_update, narrow_dim)
    "picasso": ("picasso", "psum", None),
    "picasso_stale": ("picasso", "stale", None),
    "picasso_l2": ("picasso_l2", "psum", None),
    "picasso_narrow": ("picasso_narrow", "psum", 4),
}


@pytest.fixture(scope="module")
def runs(mesh1, axes):
    """Per case: losses and final state of 12 steps (two flush periods
    after warm-up) from one initial state, once with the hot tiers' index
    and once without, plus the plan."""
    out = {}

    def get(case):
        if case in out:
            return out[case]
        strategy, update, nd = RUNS[case]
        cfg = get_config("deepfm", smoke=True)
        plan = make_plan(cfg, world=1, per_device_batch=GB, narrow_dim=nd,
                         **PLAN_KW)
        apply_assignment(plan, resolve_assignment(plan, strategy))
        model = WDLModel(cfg, plan)
        tcfg = TrainConfig(strategy=strategy, cache_update=update)
        res = {"plan": plan}
        for name, prep in (("indexed", lambda s: s), ("searched", _strip)):
            state = prep(init_state(model, plan, jax.random.PRNGKey(0),
                                    mesh=mesh1, axes=axes))
            step, _ = make_train_step(model, plan, mesh1, axes, GB, tcfg)
            stream = batch_stream(cfg, GB, seed=3)
            losses, hits = [], []
            for _ in range(12):
                state, m = step(state, _put(mesh1, axes, next(stream)))
                losses.append(np.asarray(m["loss"]))
                hits.append(int(m["cache_hits"]))
            res[name] = (losses, hits, jax.device_get(state))
        out[case] = res
        return res

    return get


@pytest.mark.parametrize("case", sorted(RUNS))
def test_flush_carries_rank_index(runs, case):
    """``flush_cache`` / ``flush_cache_l2`` / ``flush_cache_narrow`` index
    the L1 tier they load, and leave the L2 tier unindexed."""
    r = runs(case)
    _, hits, state = r["indexed"]
    assert sum(hits) > 0
    for g in r["plan"].groups:
        st = state["emb"][str(g.gid)]
        assert (np.asarray(st.cache.keys) < g.rows).any()
        _assert_indexed(st.cache, g.rows)
        assert st.l2 is None or st.l2.bits is None
        assert st.proj is not None or RUNS[case][2] is None
    for st in r["searched"][2]["emb"].values():
        assert st.cache.bits is None


@pytest.mark.parametrize("case", sorted(RUNS))
def test_indexed_lookup_is_bitwise_the_search(runs, case):
    """``mp_lookup`` / ``mp_lookup_narrow`` over two flush periods: the same
    losses, tier hits and state whether the tier carries its index or not."""
    r = runs(case)
    (la, ha, sa), (lb, hb, sb) = r["indexed"], r["searched"]
    np.testing.assert_array_equal(np.stack(la), np.stack(lb))
    assert ha == hb
    sa = _strip(sa)
    assert jax.tree.structure(sa) == jax.tree.structure(sb)
    for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _host_plan(world):
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=world, per_device_batch=8, hot_bytes=1 << 12,
                     l2_bytes=1 << 13, flush_iters=5, warmup_iters=2)
    apply_assignment(plan, {g.gid: "picasso_l2" for g in plan.groups})
    return cfg, plan


def _host_state_with_tiers():
    """A host state whose tiers hold rows: seeded counts, then a migration
    to a smaller budget re-ranks them (``_np_load_tier``)."""
    cfg, plan = _host_plan(4)
    state = init_state(WDLModel(cfg, plan), plan, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    emb = {}
    for g in plan.groups:
        counts = np.zeros(g.rows, np.int32)   # logical rows only, not padding
        n = max(g.table_offsets[t.name] + t.vocab for t in g.tables)
        counts[:n] = rng.integers(0, 50, size=n)
        emb[str(g.gid)] = state["emb"][str(g.gid)]._replace(counts=counts)
    bud = revise_plan(plan, hot_bytes=1 << 11, l2_bytes=1 << 12)
    apply_assignment(bud, {g.gid: "picasso_l2" for g in bud.groups})
    return bud, migrate_state(plan, bud, {**state, "emb": emb})


def test_migration_and_reshard_carry_the_index():
    plan, state = _host_state_with_tiers()
    for g in plan.groups:
        st = state["emb"][str(g.gid)]
        assert (np.asarray(st.cache.keys) < g.rows).all()
        _assert_indexed(st.cache, g.rows)
        assert st.l2.bits is None
    plan3 = reshard_plan(plan, 3, 8)
    s3 = reshard_state(plan3, state)
    for g in plan3.groups:
        st = s3["emb"][str(g.gid)]
        assert pe.rank_words(g.rows) == np.asarray(st.cache.bits).shape[0]
        _assert_indexed(st.cache, g.rows)
        assert st.l2.bits is None


@pytest.mark.parametrize("stored", ["with_index", "without_index"])
def test_checkpoint_restore_carries_the_index(tmp_path, stored):
    """A checkpoint restores the index it holds, and one written without an
    index (before tiers carried one) restores with it rebuilt from keys."""
    plan, state = _host_state_with_tiers()
    save_checkpoint(str(tmp_path), 3,
                    state if stored == "with_index" else _strip(state))
    template = {**state, "emb": abstract_embedding_state(plan)}
    template["emb"] = {str(k): v for k, v in template["emb"].items()}
    restored, step = restore_checkpoint(str(tmp_path), template)
    assert step == 3
    for g in plan.groups:
        a, b = state["emb"][str(g.gid)], restored["emb"][str(g.gid)]
        _assert_indexed(b.cache, g.rows)
        assert b.l2.bits is None
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
