"""L2 host-memory cache tier (picasso_l2): planning, probe order, hit/miss/
write-back correctness, bitwise parity with plain picasso when the tier is
disabled or cold, two-tier flush (psum + stale), the cost-model routing that
sends L1-overflowing groups to the tier, and end-to-end train/serve with the
per-tier metric breakdown."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.engine as E
from repro.configs import get_config
from repro.configs.base import FeatureField, InteractionSpec, WDLConfig
from repro.core import packed_embedding as pe
from repro.core.features import pack_group
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from jax import shard_map
from repro.dist.sharding import batch_specs, emb_specs, replicated, to_named
from repro.embedding.state import EmbeddingState, init_embedding_state
from repro.engine import (EmbeddingEngine, PicassoL2Strategy, PicassoStrategy,
                          PSStrategy, available_strategies,
                          compile_assignment, estimate_l2_gain, get_strategy)

AXES = ("data", "model")
GB = 16


def _cfg64():
    """One 64-row dim-4 table: hot tier 8 rows, L2 sized by l2_bytes."""
    return WDLConfig(name="l2", fields=(FeatureField("a", 64, 4),), n_dense=0,
                     interactions=(InteractionSpec("fm"),), mlp_dims=(8,))


def _mixed_cfg():
    """Tiny (ps) + big (cacheable) groups, as in test_strategies."""
    fields = (FeatureField("tiny", 64, 8, max_len=1, pooling="sum"),
              FeatureField("big", 50_000, 16, max_len=1, pooling="sum"))
    return WDLConfig(name="mixl2", fields=fields, n_dense=0,
                     interactions=(InteractionSpec("fm"),), mlp_dims=(8,))


# ----------------------------------------------------------- registry/plan
def test_registry_and_package_exports():
    assert "picasso_l2" in available_strategies()
    assert get_strategy("picasso_l2") is PicassoL2Strategy
    assert PicassoL2Strategy.uses_cache and PicassoL2Strategy.uses_l2
    assert PicassoL2Strategy.extra_metric_keys == ("cache_hits/l1",
                                                   "cache_hits/l2")
    assert not PicassoStrategy.uses_l2
    # repro.engine re-exports the full launcher surface from one place
    for name in ("AUTO_NAMES", "available_strategies", "maybe_compile",
                 "compile_assignment", "PicassoL2Strategy", "EmbeddingEngine"):
        assert name in E.__all__ and hasattr(E, name)


def test_plan_l2_budget_sits_behind_hot_tier():
    plan = make_plan(_cfg64(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=320)
    (gid,) = [g.gid for g in plan.groups]
    assert plan.cache_rows[gid] == 8
    assert plan.l2_rows[gid] == 16          # 320 B / ((4+1)*4 B/row)
    # no budget -> no tier (and the state keeps the legacy pytree structure)
    assert make_plan(_cfg64(), 1, GB, hot_bytes=1 << 14).l2_rows[gid] == 0
    # L2 is strictly behind L1: no hot tier, no L2 either
    flat = make_plan(_cfg64(), 1, GB, enable_cache=False, l2_bytes=1 << 20)
    assert flat.l2_rows[gid] == 0
    # an over-generous budget cannot overlap the L1 rows
    big = make_plan(_cfg64(), 1, GB, hot_bytes=1 << 14, l2_bytes=1 << 20)
    assert big.cache_rows[gid] + big.l2_rows[gid] <= 64


def test_state_structure_with_and_without_l2():
    plan_l2 = make_plan(_cfg64(), 1, GB, hot_bytes=1 << 14, l2_bytes=320)
    plan_no = make_plan(_cfg64(), 1, GB, hot_bytes=1 << 14)
    (gid,) = [g.gid for g in plan_l2.groups]
    st = init_embedding_state(jax.random.PRNGKey(0), plan_l2)[gid]
    assert st.l2 is not None and st.l2.keys.shape == (16,)
    assert st.l2.rows.shape == (16, 4)
    st0 = init_embedding_state(jax.random.PRNGKey(0), plan_no)[gid]
    assert st0.l2 is None
    # None collapses: unbudgeted states keep the pre-L2 leaf count
    assert len(jax.tree.leaves(st0)) == 6
    assert len(jax.tree.leaves(st)) == 9
    # specs mirror the state structure leaf-for-leaf (shard_map requires it)
    from jax.sharding import PartitionSpec as P
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    assert len(jax.tree.leaves(emb_specs(plan_l2, AXES)[str(gid)],
                               is_leaf=is_spec)) == 9
    assert len(jax.tree.leaves(emb_specs(plan_no, AXES)[str(gid)],
                               is_leaf=is_spec)) == 6


# ------------------------------------------------------------- probe order
def test_l2_lookup_tier_provenance(mesh1):
    """L1 hits come from the hot tier, L1-misses that hit L2 come from the
    host tier, the rest from the sharded table — with disjoint masks."""
    rng = np.random.default_rng(7)
    v, d = 32, 4
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    s = 32  # sentinel = rows_padded
    l1_keys = jnp.asarray(np.array([2, 5, 9, s, s, s, s, s], np.int32))
    l1_rows = jnp.where((l1_keys < v)[:, None],
                        jnp.full((8, d), 100.0), 0.0).astype(jnp.float32)
    l2_keys = jnp.asarray(np.array([0, 1, 3, 4, 12, 13, s, s], np.int32))
    l2_rows = jnp.where((l2_keys < v)[:, None],
                        jnp.full((8, d), 200.0), 0.0).astype(jnp.float32)
    ids = jnp.asarray(np.array([2, 0, 12, 20, 5, 21, 3, 2], np.int32))
    strat = PicassoL2Strategy(axes=AXES, world=1, capacity={0: ids.shape[0]})

    def f(tsh, ids_l):
        st = EmbeddingState(
            w=tsh, acc=jnp.zeros((v, 1)), counts=jnp.zeros((v,), jnp.int32),
            cache=pe.CacheState(l1_keys, l1_rows, jnp.zeros((8, 1))),
            l2=pe.CacheState(l2_keys, l2_rows, jnp.zeros((8, 1))))
        rows_u, ctx = strat.lookup(st, 0, ids_l, cache_on=True, l2_on=True)
        per_id = jnp.take(rows_u, ctx.inv, axis=0)
        n_l1 = jnp.sum(ctx.hit)
        n_l2 = jnp.sum(ctx.l2_hit)
        overlap = jnp.sum(ctx.hit & ctx.l2_hit)
        return per_id, n_l1, n_l2, overlap

    from jax.sharding import PartitionSpec as P
    per_id, n_l1, n_l2, overlap = jax.jit(shard_map(
        f, mesh=mesh1, in_specs=(P(AXES, None), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False))(table, ids)
    per_id = np.asarray(per_id)
    exp = {2: 100.0, 5: 100.0, 0: 200.0, 3: 200.0, 12: 200.0}
    for i, idv in enumerate(np.asarray(ids)):
        if int(idv) in exp:
            np.testing.assert_allclose(per_id[i], exp[int(idv)])
        else:  # 20, 21: miss both tiers -> real table row via the Shuffle
            np.testing.assert_allclose(per_id[i], np.asarray(table)[int(idv)],
                                       atol=1e-6)
    assert int(n_l1) == 2       # uniques {2, 5}
    assert int(n_l2) == 3       # uniques {0, 3, 12}
    assert int(overlap) == 0    # tiers never serve the same id


# ----------------------------------------------------------------- parity
def _roundtrip(mesh, strategy, *, l2_bytes=0, use_l2=True, use_cache=True):
    """forward + backward of one synthetic batch through the bare engine."""
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14,
                     l2_bytes=l2_bytes, exact_capacity=True)
    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    batch = make_batch(cfg, GB, np.random.default_rng(3))
    fields = jax.tree.map(jnp.asarray, batch["fields"])
    engine = EmbeddingEngine(plan, AXES, 1, strategy=strategy,
                             use_cache=use_cache, use_l2=use_l2, lr_emb=0.1)
    especs = emb_specs(plan, AXES)

    def f(emb, fields):
        packed = {g.gid: pack_group(g, fields) for g in plan.groups}
        pooled, ctx = engine.forward(emb, packed)
        emb2, _m = engine.backward(emb, ctx, pooled)
        return pooled, emb2

    pooled_specs = {g.gid: jax.sharding.PartitionSpec(AXES, None, None)
                    for g in plan.groups}
    g = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(especs, replicated(fields)),
        out_specs=(pooled_specs, especs), check_vma=False))
    pooled, emb2 = g(emb0, fields)
    tables = {k: np.asarray(v.w) for k, v in emb2.items()}
    return jax.tree.map(np.asarray, pooled), tables


def test_l2_cold_or_disabled_is_bitwise_picasso(mesh1):
    """Acceptance: picasso_l2 with a cold L2 tier — and with the tier
    disabled (use_l2=False / no budget) — produces pooled outputs and
    post-update tables bitwise identical to plain picasso."""
    ref_pooled, ref_tables = _roundtrip(mesh1, "picasso")
    for kw in (dict(l2_bytes=1 << 16),               # budgeted, cold tier
               dict(l2_bytes=1 << 16, use_l2=False),  # tier switched off
               dict(l2_bytes=0)):                     # no budget at all
        pooled, tables = _roundtrip(mesh1, "picasso_l2", **kw)
        for gid in ref_pooled:
            np.testing.assert_array_equal(pooled[gid], ref_pooled[gid],
                                          err_msg=f"pooled/{gid}/{kw}")
        for k in ref_tables:
            np.testing.assert_array_equal(tables[k], ref_tables[k],
                                          err_msg=f"table/{k}/{kw}")


# ------------------------------------------------------- backward / tiers
def test_l2_psum_hit_grads_update_tier_not_master(mesh1):
    """'psum' mode: grads of L2-served ids are adagrad-applied to the L2
    tier (authoritative between flushes); the master rows stay untouched."""
    rng = np.random.default_rng(11)
    v, d, n = 32, 4, 8
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    s = 32
    l2_keys = jnp.asarray(np.array([4, 7, s, s, s, s, s, s], np.int32))
    l2_rows0 = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))
    ids = jnp.asarray(np.array([4, 7, 4, 20, 21, 22, 23, 19], np.int32))
    g_per_id = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    strat = PicassoL2Strategy(axes=AXES, world=1, capacity={0: n}, lr=0.1)

    def f(tsh, ids_l, g):
        st = EmbeddingState(
            w=tsh, acc=jnp.zeros((v, 1)), counts=jnp.zeros((v,), jnp.int32),
            cache=pe.init_cache(4, d, v),
            l2=pe.CacheState(l2_keys, l2_rows0, jnp.zeros((8, 1))))
        rows_u, ctx = strat.lookup(st, 0, ids_l, cache_on=True, l2_on=True)
        g_u = jax.ops.segment_sum(g, ctx.inv, num_segments=n)
        st2, _, hits = strat.apply_grads(st, 0, ctx, g_u, cache_on=True,
                                         l2_on=True)
        return st2.w, st2.l2.rows, st2.l2.acc, hits, st2.counts

    from jax.sharding import PartitionSpec as P
    w2, l2r, l2a, hits, counts = jax.jit(shard_map(
        f, mesh=mesh1, in_specs=(P(AXES, None), P(), P()),
        out_specs=(P(AXES, None), P(), P(), P(), P(AXES)), check_vma=False))(
            table, ids, g_per_id)
    assert int(hits) == 2  # uniques {4, 7} served by L2
    # tier-served ids feed the FCounter too (anti-churn): one count each at
    # world=1, alongside the routed-miss counts
    counts = np.asarray(counts)
    assert counts[4] == 1 and counts[7] == 1
    assert counts[20] == 1  # routed miss counted on the owner as before
    w2, l2r, l2a = np.asarray(w2), np.asarray(l2r), np.asarray(l2a)
    # master rows 4 and 7 untouched (the tier owns them between flushes)
    np.testing.assert_array_equal(w2[4], np.asarray(table)[4])
    np.testing.assert_array_equal(w2[7], np.asarray(table)[7])
    # tier slots 0 (id 4) and 1 (id 7) moved by row-wise adagrad
    gnp = np.asarray(g_per_id)
    idnp = np.asarray(ids)
    for slot, idv in ((0, 4), (1, 7)):
        gsum = gnp[idnp == idv].sum(0)
        acc = (gsum ** 2).mean(keepdims=True)
        exp = np.asarray(l2_rows0)[slot] - 0.1 * gsum / np.sqrt(acc + 1e-8)
        np.testing.assert_allclose(l2r[slot], exp, atol=1e-5)
        np.testing.assert_allclose(l2a[slot], acc, atol=1e-6)
    # untouched tier slots stay put
    np.testing.assert_array_equal(l2r[2:], np.asarray(l2_rows0)[2:])
    # miss ids updated the master as usual
    assert not np.allclose(w2[20], np.asarray(table)[20])


# ------------------------------------------------------------------ flush
def _two_tier_state(plan, gid):
    """Markers: L1 = rows 0..7 @777, L2 = rows 8..23 @888, counts make
    rows 40..63 the hottest (63 hottest)."""
    st = init_embedding_state(jax.random.PRNGKey(1), plan)[gid]
    h1, h2 = plan.cache_rows[gid], plan.l2_rows[gid]
    assert (h1, h2) == (8, 16)
    return EmbeddingState(
        w=st.w, acc=st.acc,
        counts=jnp.arange(64, dtype=jnp.int32),
        cache=pe.CacheState(keys=jnp.arange(h1, dtype=jnp.int32),
                            rows=jnp.full((h1, 4), 777.0),
                            acc=jnp.ones((h1, 1))),
        l2=pe.CacheState(keys=jnp.arange(h1, h1 + h2, dtype=jnp.int32),
                         rows=jnp.full((h2, 4), 888.0),
                         acc=jnp.full((h2, 1), 2.0)))


def _flush(mesh1, cache_update):
    plan = make_plan(_cfg64(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=320)
    (gid,) = [g.gid for g in plan.groups]
    st = _two_tier_state(plan, gid)
    eng = EmbeddingEngine(plan, AXES, 1, strategy="picasso_l2",
                          cache_update=cache_update)
    assert eng.l2_on[gid]
    especs = emb_specs(plan, AXES)
    out = jax.jit(shard_map(eng.flush, mesh=mesh1, in_specs=(especs,),
                            out_specs=especs, check_vma=False))(
        {str(gid): st})
    return np.asarray(st.w), out[str(gid)]


def test_two_tier_flush_psum_write_back_and_split(mesh1):
    """psum flush: both tiers written back to master, then one global
    frequency ranking refills L1 (top-8) and L2 (next-16) disjointly."""
    w0, st2 = _flush(mesh1, "psum")
    w2 = np.asarray(st2.w)
    np.testing.assert_allclose(w2[:8], 777.0)    # L1 write-back
    np.testing.assert_allclose(w2[8:24], 888.0)  # L2 write-back
    np.testing.assert_allclose(w2[24:], w0[24:], atol=1e-6)
    k1 = np.asarray(st2.cache.keys)
    k2 = np.asarray(st2.l2.keys)
    np.testing.assert_array_equal(np.sort(k1), np.arange(56, 64))  # top-8
    np.testing.assert_array_equal(np.sort(k2), np.arange(40, 56))  # next-16
    assert not set(k1) & set(k2)
    for i, k in enumerate(k1):
        np.testing.assert_allclose(np.asarray(st2.cache.rows)[i], w2[k],
                                   atol=1e-6)
    for i, k in enumerate(k2):
        np.testing.assert_allclose(np.asarray(st2.l2.rows)[i], w2[k],
                                   atol=1e-6)


def test_two_tier_flush_stale_master_stays_exact(mesh1):
    """'stale' mode: neither (read-only) tier is written back — the master
    is authoritative; both tiers are re-ranked and reloaded from it."""
    w0, st2 = _flush(mesh1, "stale")
    w2 = np.asarray(st2.w)
    np.testing.assert_allclose(w2, w0, atol=1e-6)  # no write-back at all
    np.testing.assert_array_equal(np.sort(np.asarray(st2.cache.keys)),
                                  np.arange(56, 64))
    np.testing.assert_array_equal(np.sort(np.asarray(st2.l2.keys)),
                                  np.arange(40, 56))
    for i, k in enumerate(np.asarray(st2.l2.keys)):
        np.testing.assert_allclose(np.asarray(st2.l2.rows)[i], w0[k],
                                   atol=1e-6)


def test_stale_flush_with_mixed_l1_l2_assignment(mesh1):
    """A mixed plan (ps tiny group + picasso_l2 big group), stale mode:
    flush leaves the ps group fully untouched AND the big group's master
    exact, while both of the big group's tiers are re-ranked."""
    plan = make_plan(_mixed_cfg(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=1 << 18)
    asg = compile_assignment(plan)
    by_name = {plan.group(g).tables[0].name: s for g, s in asg.strategy.items()}
    assert by_name == {"tiny": "ps", "big": "picasso_l2"}
    gid_tiny = next(g.gid for g in plan.groups if g.tables[0].name == "tiny")
    gid_big = next(g.gid for g in plan.groups if g.tables[0].name == "big")

    eng = EmbeddingEngine(plan, AXES, 1, strategy=asg, cache_update="stale")
    assert eng.l2_on == {gid_tiny: False, gid_big: True}
    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    # make some big-group rows hot so the re-rank has a real signal
    big = emb0[str(gid_big)]
    emb0[str(gid_big)] = big._replace(
        counts=jnp.arange(big.counts.shape[0], dtype=jnp.int32))
    before_tiny = [np.asarray(x) for x in jax.tree.leaves(emb0[str(gid_tiny)])]
    before_big_w = np.asarray(big.w)
    especs = emb_specs(plan, AXES)
    out = jax.jit(shard_map(eng.flush, mesh=mesh1, in_specs=(especs,),
                            out_specs=especs, check_vma=False))(emb0)
    for a, b in zip(before_tiny, jax.tree.leaves(out[str(gid_tiny)])):
        np.testing.assert_array_equal(a, np.asarray(b))
    big2 = out[str(gid_big)]
    np.testing.assert_allclose(np.asarray(big2.w), before_big_w, atol=1e-6)
    k1, k2 = np.asarray(big2.cache.keys), np.asarray(big2.l2.keys)
    rows = plan.group(gid_big).rows
    assert (k1 < rows).all() and (k2 < rows).all()  # both tiers warmed
    assert not set(k1.tolist()) & set(k2.tolist())


# --------------------------------------------------------------- cost model
def test_estimate_l2_gain():
    plan = make_plan(_mixed_cfg(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=1 << 18)
    g = next(gr for gr in plan.groups if gr.tables[0].name == "big")
    assert estimate_l2_gain(g, 0, 0) == 0.0
    assert estimate_l2_gain(g, 8, 0) == 0.0
    # measured stats: exact share of the [h1, h1+h2) frequency band
    counts = np.zeros(g.rows)
    counts[:4] = 100.0   # L1 band
    counts[4:8] = 10.0   # L2 band
    assert estimate_l2_gain(g, 4, 4, counts) == pytest.approx(40.0 / 440.0)
    # full coverage absorbs everything L1 misses
    assert estimate_l2_gain(g, 8, g.rows) == pytest.approx(
        1.0 - 0.2)  # 1 - DEFAULT_HIT_RATIO prior for L1


def test_auto_routes_overflowing_groups_to_l2():
    """Acceptance: on the default synthetic workload with a constricted hot
    tier and an L2 budget, 'auto' assigns at least one group to picasso_l2
    — and only budgeted groups are ever offered the candidate."""
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=1 << 22)
    asg = compile_assignment(plan)
    assert "picasso_l2" in set(asg.strategy.values())
    for gid, sc in asg.scores.items():
        if plan.l2_rows.get(gid, 0) > 0:
            assert "picasso_l2" in sc.costs
            assert sc.costs["picasso_l2"] <= sc.costs["picasso"]
        else:
            assert "picasso_l2" not in sc.costs
    # without an L2 budget the scores are exactly the PR-2 candidates
    asg0 = compile_assignment(make_plan(cfg, world=1, per_device_batch=GB,
                                        hot_bytes=1 << 14))
    assert "picasso_l2" not in set(asg0.strategy.values())
    for sc in asg0.scores.values():
        assert set(sc.costs) == {"ps", "hybrid", "picasso"}
    # the engine resolves 'auto' straight onto the tier
    eng = EmbeddingEngine(plan, AXES, 1, strategy="auto")
    assert any(eng.l2_on.values())
    assert plan.strategy == eng.assignment  # recorded for later engines


# ------------------------------------------------------------- end to end
def test_l2_trains_and_serves_with_tier_metrics(mesh1, axes):
    """picasso_l2 end-to-end: train_step warms both tiers through the
    two-tier flush, per-tier counters reconcile with the total, and
    serve_step reads through the same tiers."""
    from repro.models.wdl import WDLModel
    from repro.serve.serve_step import ServeConfig, make_serve_step
    from repro.train.train_step import TrainConfig, init_state, make_train_step

    plan = make_plan(_cfg64(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=320,
                     flush_iters=2, warmup_iters=1)
    model = WDLModel(_cfg64(), plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1,
                       axes=axes)
    step, _ = make_train_step(model, plan, mesh1, axes, GB,
                              TrainConfig(strategy="picasso_l2"))
    rng = np.random.default_rng(0)
    l1_hits = l2_hits = 0
    for i in range(8):
        b = make_batch(_cfg64(), GB, rng)
        b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
        state, m = step(state, b)
        assert bool(jnp.isfinite(m["loss"]))
        assert set(m) >= {"cache_hits", "cache_hits/l1", "cache_hits/l2"}
        assert int(m["cache_hits"]) == (int(m["cache_hits/l1"])
                                        + int(m["cache_hits/l2"]))
        l1_hits += int(m["cache_hits/l1"])
        l2_hits += int(m["cache_hits/l2"])
    # after the flush both tiers hold 8+16 of the 64 rows: uniform synthetic
    # ids must hit each tier
    assert l1_hits > 0 and l2_hits > 0

    serve = make_serve_step(model, plan, mesh1, axes, GB,
                            scfg=ServeConfig(strategy="picasso_l2"))
    b = make_batch(_cfg64(), GB, rng)
    b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
    probs = serve(state, b)
    assert bool(jnp.isfinite(probs).all())


def test_pin_l2_to_host_is_safe_noop_on_cpu(mesh1):
    """The experimental host-placement hook: no mesh or no pinned_host
    memory kind (the CPU rig) -> state returned unchanged, never an error."""
    from repro.embedding.state import pin_l2_to_host
    plan = make_plan(_cfg64(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=320)
    emb = {str(g): s for g, s in
           init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    state = {"emb": emb}
    assert pin_l2_to_host(state) is state          # no mesh -> untouched
    out = pin_l2_to_host(state, mesh=mesh1)        # CPU: no pinned_host
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_metric_keys_static_and_mixed(mesh1):
    plan = make_plan(_cfg64(), world=1, per_device_batch=GB,
                     hot_bytes=1 << 14, l2_bytes=320)
    eng = EmbeddingEngine(plan, AXES, 1, strategy="picasso_l2")
    assert eng.metric_keys == ("overflow", "cache_hits", "distinct_ids",
                               "cache_hits/l1", "cache_hits/l2")
    mixed_plan = make_plan(_mixed_cfg(), world=1, per_device_batch=GB,
                           hot_bytes=1 << 14, l2_bytes=1 << 18)
    meng = EmbeddingEngine(mixed_plan, AXES, 1, strategy="mixed")
    assert set(meng.metric_keys) == {
        "overflow", "cache_hits", "distinct_ids",
        "overflow/ps", "overflow/picasso_l2",
        "cache_hits/ps", "cache_hits/picasso_l2",
        "cache_hits/l1", "cache_hits/l2"}
