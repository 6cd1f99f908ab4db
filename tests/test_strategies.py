"""EmbeddingEngine strategy registry + parity (single-device, in-process),
including per-group strategy mixing: broadcast-assignment parity with the
single-strategy engine, mixed ps+picasso training/serving, per-group cache
gating, and the stale-mode flush.

Multi-device parity of the same strategies lives in
test_distributed.py::test_strategy_parity_8dev.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FeatureField, InteractionSpec, WDLConfig
from repro.core import packed_embedding as pe
from repro.core.features import pack_group
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from jax import shard_map
from repro.dist.sharding import emb_specs, replicated
from repro.embedding.state import EmbeddingState, init_embedding_state
from repro.engine import (EmbeddingEngine, HybridStrategy, LookupStrategy,
                          PicassoStrategy, PSStrategy, available_strategies,
                          compile_assignment, get_strategy, register_strategy)

AXES = ("data", "model")
GB = 16


def _mixed_cfg():
    """One tiny table (dim 8) + one large table (dim 16): two packed groups
    the cost model assigns to different strategies."""
    fields = (FeatureField("tiny", 64, 8, max_len=1, pooling="sum"),
              FeatureField("big", 50_000, 16, max_len=1, pooling="sum"))
    return WDLConfig(name="mix", fields=fields, n_dense=0,
                     interactions=(InteractionSpec("fm"),), mlp_dims=(8,))


# --------------------------------------------------------------- registry
def test_registry_contents():
    names = available_strategies()
    assert {"picasso", "hybrid", "ps"} <= set(names)
    assert get_strategy("picasso") is PicassoStrategy
    assert get_strategy("hybrid") is HybridStrategy
    assert get_strategy("ps") is PSStrategy


def test_unknown_strategy_raises_with_menu():
    with pytest.raises(ValueError, match="picasso"):
        get_strategy("does-not-exist")


def test_train_step_validates_strategy_name(mesh1, axes):
    from repro.models.wdl import WDLModel
    from repro.train.train_step import TrainConfig, make_train_step
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, enable_cache=False)
    model = WDLModel(cfg, plan)
    with pytest.raises(ValueError, match="unknown lookup strategy"):
        make_train_step(model, plan, mesh1, axes, GB,
                        TrainConfig(strategy="nope"))


def test_custom_strategy_registers_and_resolves():
    @register_strategy("_test_dummy")
    class DummyStrategy(PicassoStrategy):
        pass

    try:
        assert get_strategy("_test_dummy") is DummyStrategy
        assert DummyStrategy.name == "_test_dummy"
    finally:
        from repro.engine import strategies as S
        S._REGISTRY.pop("_test_dummy", None)


# ----------------------------------------------------------------- parity
def _engine_roundtrip(mesh, strategy: str):
    """forward + backward of one synthetic batch through the bare engine."""
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, enable_cache=False,
                     exact_capacity=True)
    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    batch = make_batch(cfg, GB, np.random.default_rng(3))
    fields = jax.tree.map(jnp.asarray, batch["fields"])
    engine = EmbeddingEngine(plan, AXES, 1, strategy=strategy,
                             use_cache=False, lr_emb=0.1)
    especs = emb_specs(plan, AXES)

    def f(emb, fields):
        packed = {g.gid: pack_group(g, fields) for g in plan.groups}
        pooled, ctx = engine.forward(emb, packed)
        # deterministic synthetic loss grad: d(0.5*sum(pooled^2)) = pooled
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


def test_strategy_parity_forward_and_update(mesh1):
    """With exact capacity and no cache, all strategies produce matching
    pooled outputs and post-update embedding tables."""
    ref_pooled, ref_tables = _engine_roundtrip(mesh1, "picasso")
    for name in ("hybrid", "ps"):
        pooled, tables = _engine_roundtrip(mesh1, name)
        for gid in ref_pooled:
            np.testing.assert_allclose(pooled[gid], ref_pooled[gid],
                                       atol=1e-5, err_msg=f"{name}/pooled/{gid}")
        for k in ref_tables:
            np.testing.assert_allclose(tables[k], ref_tables[k],
                                       atol=1e-5, err_msg=f"{name}/table/{k}")


def test_broadcast_assignment_parity_bitwise(mesh1):
    """A {gid: name} assignment giving every group the *same* name must be
    bitwise-identical to the single-name engine (constructor sugar)."""
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, enable_cache=False,
                     exact_capacity=True)
    broadcast = {g.gid: "picasso" for g in plan.groups}
    ref_pooled, ref_tables = _engine_roundtrip(mesh1, "picasso")
    pooled, tables = _engine_roundtrip(mesh1, broadcast)
    for gid in ref_pooled:
        np.testing.assert_array_equal(pooled[gid], ref_pooled[gid])
    for k in ref_tables:
        np.testing.assert_array_equal(tables[k], ref_tables[k])


# ------------------------------------------------------------------ mixed
def test_mixed_engine_per_group_dispatch_and_gating(mesh1):
    """ps + picasso in one plan: per-group strategies, per-group cache
    gating (the tier participates only where the strategy uses it AND the
    plan budgets rows)."""
    cfg = _mixed_cfg()
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14)
    asg = compile_assignment(plan)
    gid_tiny = next(g.gid for g in plan.groups if g.tables[0].name == "tiny")
    gid_big = next(g.gid for g in plan.groups if g.tables[0].name == "big")
    assert asg.strategy == {gid_tiny: "ps", gid_big: "picasso"}

    eng = EmbeddingEngine(plan, AXES, 1, strategy=asg)
    assert eng.strategy_name == "mixed"
    assert isinstance(eng.strategies[gid_tiny], PSStrategy)
    assert isinstance(eng.strategies[gid_big], PicassoStrategy)
    # both groups have a cache budget, but only picasso's tier participates
    assert plan.cache_rows[gid_tiny] > 0 and plan.cache_rows[gid_big] > 0
    assert eng.cache_on == {gid_tiny: False, gid_big: True}
    assert eng.any_cache
    assert set(eng.metric_keys) == {"overflow", "cache_hits",
                                    "overflow/ps", "overflow/picasso",
                                    "cache_hits/ps", "cache_hits/picasso"}
    # single-strategy engines keep the lean metric pytree
    assert EmbeddingEngine(plan, AXES, 1).metric_keys == ("overflow",
                                                          "cache_hits")


def test_mixed_flush_skips_uncached_groups(mesh1):
    """flush must not touch groups whose assigned strategy never reads the
    tier, even when the plan budgets cache rows for them."""
    cfg = _mixed_cfg()
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14)
    asg = compile_assignment(plan)
    gid_tiny = next(g.gid for g in plan.groups if g.tables[0].name == "tiny")
    eng = EmbeddingEngine(plan, AXES, 1, strategy=asg)
    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    especs = emb_specs(plan, AXES)
    out = jax.jit(shard_map(eng.flush, mesh=mesh1, in_specs=(especs,),
                            out_specs=especs, check_vma=False))(emb0)
    for leaf_a, leaf_b in zip(jax.tree.leaves(emb0[str(gid_tiny)]),
                              jax.tree.leaves(out[str(gid_tiny)])):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_make_flush_fn_follows_plan_assignment(mesh1, axes):
    """A host-scheduled flush built without an explicit strategy must pick
    up the plan's recorded assignment — not broadcast picasso gating over
    PS groups whose (budgeted) tier the training path never populated."""
    from repro.train.train_step import make_flush_fn

    cfg = _mixed_cfg()
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14)
    # an engine built with 'mixed' records its compiled assignment on the
    # plan (the bench path: TrainConfig(strategy='mixed'), no launcher)
    eng = EmbeddingEngine(plan, AXES, 1, strategy="mixed")
    gid_tiny = next(g.gid for g in plan.groups if g.tables[0].name == "tiny")
    assert plan.strategy == eng.assignment
    assert plan.strategy[gid_tiny] == "ps" and plan.cache_rows[gid_tiny] > 0

    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    # snapshot before the call: the flush fn donates its input buffers
    before = [np.asarray(x) for x in jax.tree.leaves(emb0[str(gid_tiny)])]
    state = {"emb": emb0, "step": jnp.zeros((), jnp.int32)}
    out = make_flush_fn(plan, mesh1, axes)(state)
    for a, b in zip(before, jax.tree.leaves(out["emb"][str(gid_tiny)])):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_mixed_assignment_trains_and_serves(mesh1, axes):
    """Acceptance: a mixed plan (one ps group + one cached picasso group)
    trains end-to-end via train_step and serves via serve_step, with the
    per-strategy-class metric breakdown attributing hits to picasso only."""
    from repro.core.assign import apply_assignment
    from repro.dist.sharding import batch_specs, to_named
    from repro.models.wdl import WDLModel
    from repro.serve.serve_step import ServeConfig, make_serve_step
    from repro.train.train_step import TrainConfig, init_state, make_train_step

    cfg = _mixed_cfg()
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14,
                     flush_iters=2, warmup_iters=1)
    asg = compile_assignment(plan)
    assert set(asg.strategy.values()) == {"ps", "picasso"}
    apply_assignment(plan, asg)

    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1, axes=axes)
    step, _ = make_train_step(model, plan, mesh1, axes, GB,
                              TrainConfig(strategy="mixed"))
    rng = np.random.default_rng(0)
    hits = 0
    for i in range(5):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
        state, m = step(state, b)
        assert bool(jnp.isfinite(m["loss"]))
        # class totals reconcile, and the ps class never touches the tier
        assert int(m["cache_hits"]) == (int(m["cache_hits/ps"])
                                        + int(m["cache_hits/picasso"]))
        assert int(m["cache_hits/ps"]) == 0
        hits += int(m["cache_hits/picasso"])
    assert hits > 0  # the picasso group's tier warmed up after the flush

    serve = make_serve_step(model, plan, mesh1, axes, GB,
                            scfg=ServeConfig(strategy="mixed"))
    b = make_batch(cfg, GB, rng)
    b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
    probs = serve(state, b)
    assert bool(jnp.isfinite(probs).all())


# ------------------------------------------------------------------ flush
def _flush_fixture(mesh1, cache_update):
    """One 64-row cached group with marker rows in the tier and counts
    making rows 56..63 the hottest; returns (w0, flushed state)."""
    cfg = WDLConfig(name="f", fields=(FeatureField("a", 64, 4),), n_dense=0,
                    interactions=(InteractionSpec("fm"),), mlp_dims=(8,))
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14)
    (gid,) = [g.gid for g in plan.groups]
    h = plan.cache_rows[gid]
    assert h == 8
    st = init_embedding_state(jax.random.PRNGKey(1), plan)[gid]
    st = EmbeddingState(
        w=st.w, acc=st.acc,
        counts=jnp.arange(64, dtype=jnp.int32),        # row 63 hottest
        cache=pe.CacheState(keys=jnp.arange(h, dtype=jnp.int32),  # rows 0..7
                            rows=jnp.full((h, 4), 777.0),         # marker
                            acc=jnp.ones((h, 1))))
    eng = EmbeddingEngine(plan, AXES, 1, cache_update=cache_update)
    especs = emb_specs(plan, AXES)
    emb = {str(gid): st}
    out = jax.jit(shard_map(eng.flush, mesh=mesh1, in_specs=(especs,),
                            out_specs=especs, check_vma=False))(emb)
    return np.asarray(st.w), out[str(gid)]


def test_flush_psum_writes_back_and_reloads(mesh1):
    w0, st2 = _flush_fixture(mesh1, "psum")
    w2 = np.asarray(st2.w)
    np.testing.assert_allclose(w2[:8], 777.0)          # hot rows written back
    np.testing.assert_allclose(w2[8:], w0[8:], atol=1e-6)
    keys = np.sort(np.asarray(st2.cache.keys))
    np.testing.assert_array_equal(keys, np.arange(56, 64))  # new top-8
    for i, k in enumerate(np.asarray(st2.cache.keys)):
        np.testing.assert_allclose(np.asarray(st2.cache.rows)[i], w2[k],
                                   atol=1e-6)


def test_flush_stale_master_stays_exact(mesh1):
    """cache_update='stale': the master table is authoritative — flush must
    NOT write the (read-only, stale) tier back, only re-rank + reload it."""
    w0, st2 = _flush_fixture(mesh1, "stale")
    w2 = np.asarray(st2.w)
    np.testing.assert_allclose(w2, w0, atol=1e-6)      # no write-back at all
    keys = np.sort(np.asarray(st2.cache.keys))
    np.testing.assert_array_equal(keys, np.arange(56, 64))
    for i, k in enumerate(np.asarray(st2.cache.keys)):
        np.testing.assert_allclose(np.asarray(st2.cache.rows)[i], w0[k],
                                   atol=1e-6)          # reloaded from master


def test_hybrid_selectable_by_name_end_to_end(mesh1, axes):
    """'hybrid' resolves from the registry through TrainConfig and trains."""
    from repro.dist.sharding import batch_specs, to_named
    from repro.models.wdl import WDLModel
    from repro.train.train_step import TrainConfig, init_state, make_train_step
    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, enable_cache=False,
                     exact_capacity=True)
    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1, axes=axes)
    step, _ = make_train_step(model, plan, mesh1, axes, GB,
                              TrainConfig(strategy="hybrid", use_cache=False))
    b = make_batch(cfg, GB, np.random.default_rng(0))
    b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
    state, m = step(state, b)
    assert bool(jnp.isfinite(m["loss"]))
    # hybrid never touches the hot tier
    assert int(m["cache_hits"]) == 0
