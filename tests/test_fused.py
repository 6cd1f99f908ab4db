"""Fused sparse hot-path kernels (PR 5): parity of the Pallas kernels
(interpret mode) against the jnp reference chains, the gather+pool custom
VJP, bitwise dedup+adagrad, the narrow-row gather+project stitch (forward,
custom VJP, and standalone transpose), tier probes, per-strategy
fused-vs-reference
engine parity (incl. the picasso_l2 tiers), the no-[n,D]-intermediate
guarantee, a fused train smoke against the reference loss trajectory, and
the chunked/streaming retrieval top-k.

Every fused call here passes ``fused=True`` explicitly, so the file is
meaningful both in a normal CPU run and under the CI soak
(``REPRO_FORCE_PALLAS_INTERPRET=1``), where the 'reference' engine rows also
route their dense interaction kernels through the interpreter.
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
from repro.dist.sharding import batch_specs, emb_specs, replicated, to_named
from repro.embedding.state import EmbeddingState, init_embedding_state
from repro.engine import EmbeddingEngine
from repro.kernels import ops, ref

AXES = ("data", "model")
GB = 16
RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- kernels
def _pool_args(rng, n, d, n_bags, n_uniq=None):
    n_uniq = n_uniq or n
    rows_u = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    # surjective onto [0, n_uniq): every unique slot has >= 1 position
    inv = np.concatenate([np.arange(n_uniq), rng.integers(0, n_uniq, n - n_uniq)])
    inv = jnp.asarray(inv[rng.permutation(n)].astype(np.int32))
    w = jnp.asarray(rng.normal(size=n).astype(np.float32))
    seg = np.sort(np.concatenate(
        [np.arange(n_bags), rng.integers(0, n_bags, n - n_bags)]))
    return rows_u, inv, w, jnp.asarray(seg.astype(np.int32))


@pytest.mark.parametrize("n,d,n_bags,n_uniq", [(24, 8, 6, 24), (40, 16, 10, 17),
                                               (64, 4, 64, 30)])
def test_gather_pool_fused_matches_ref(n, d, n_bags, n_uniq):
    rng = np.random.default_rng(n)
    rows_u, inv, w, seg = _pool_args(rng, n, d, n_bags, n_uniq)
    got = ops.gather_pool(rows_u, inv, w, seg, n_bags, fused=True)
    exp = ref.gather_pool_ref(rows_u, inv, w, seg, n_bags)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_gather_pool_fused_uncovered_bag_is_zero():
    """A bag no position maps to must come out exactly zero in the fused
    path too (ghost coverage), not as an unwritten (garbage) output block —
    pinned because pool() is a public helper and the packed layout's
    every-bag-covered guarantee does not extend to future callers."""
    rng = np.random.default_rng(42)
    n, d, n_bags = 20, 8, 6
    rows_u, inv, w, _ = _pool_args(rng, n, d, n_bags)
    seg = jnp.asarray(np.sort(np.where(rng.integers(0, n_bags, n) == 3, 0,
                                       rng.integers(0, n_bags, n))
                              ).astype(np.int32))
    seg = jnp.where(seg == 3, 2, seg)    # bag 3 is empty
    got = ops.gather_pool(rows_u, inv, w, seg, n_bags, fused=True)
    exp = ref.gather_pool_ref(rows_u, inv, w, seg, n_bags)
    np.testing.assert_array_equal(np.asarray(got[3]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,d,n_bags,n_uniq", [(24, 8, 6, 24), (40, 16, 10, 17)])
def test_gather_pool_custom_vjp_parity(n, d, n_bags, n_uniq):
    """jax.grad through the fused custom VJP == jax.grad of the raw
    reference chain (no custom VJP at all)."""
    rng = np.random.default_rng(100 + n)
    rows_u, inv, w, seg = _pool_args(rng, n, d, n_bags, n_uniq)
    tgt = jnp.asarray(rng.normal(size=(n_bags, d)).astype(np.float32))

    def loss_fused(r):
        return jnp.sum((ops.gather_pool(r, inv, w, seg, n_bags, fused=True)
                        - tgt) ** 2)

    def loss_raw(r):
        return jnp.sum((ref.gather_pool_ref(r, inv, w, seg, n_bags) - tgt) ** 2)

    g_fused = jax.grad(loss_fused)(rows_u)
    g_raw = jax.grad(loss_raw)(rows_u)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_raw),
                               atol=1e-4, rtol=1e-4)
    # slots past n_uniq receive no positions: their grad must be EXACT zero
    # (the ghost rows of the fused transpose, not masked garbage)
    if n_uniq < n:
        np.testing.assert_array_equal(np.asarray(g_fused[n_uniq:]), 0.0)


def test_segment_grad_bitwise():
    rng = np.random.default_rng(5)
    n, d, n_bags, n_uniq = 48, 8, 12, 19
    _, inv, w, seg = _pool_args(rng, n, d, n_bags, n_uniq)
    g_bags = jnp.asarray(rng.normal(size=(n_bags, d)).astype(np.float32))
    got = ops.segment_grad(g_bags, seg, w, inv, n, fused=True)
    exp = ref.segment_grad_ref(g_bags, seg, w, inv, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("rows,d,m,hot", [(37, 8, 50, 37), (64, 16, 96, 5),
                                          (16, 4, 64, 2)])
def test_dedup_adagrad_matches_reference(rows, d, m, hot):
    """Duplicate-heavy id sets (m >> hot): the fused one-pass kernel against
    the argsort/segment_sum/scatter reference.

    The duplicate-grad accumulation order is identical (stable sort, run-
    sequential adds — pinned bitwise on the gsum in the kernel prototype),
    so UNTOUCHED rows must be bitwise-identical; touched rows are compared
    to 1-2 ULP because XLA fuses the final adagrad arithmetic
    (``acc + mean(square(gsum))``) with different reassociation inside the
    kernel graph than in the reference graph."""
    rng = np.random.default_rng(rows * m)
    w = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    acc = jnp.asarray(np.abs(rng.normal(size=(rows, 1))).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, hot, m).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    valid = jnp.asarray(rng.random(m) < 0.8)
    w2, acc2 = ops.dedup_adagrad(w, acc, idx, g, valid, 0.05, 1e-8, fused=True)
    wr, accr = ref.dedup_adagrad_ref(w, acc, idx, g, valid, 0.05, 1e-8)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(wr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc2), np.asarray(accr),
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones(rows, bool)
    touched = np.asarray(idx)[np.asarray(valid)]
    untouched[touched[touched < rows]] = False
    assert untouched.any()
    np.testing.assert_array_equal(np.asarray(w2)[untouched],
                                  np.asarray(w)[untouched])
    np.testing.assert_array_equal(np.asarray(acc2)[untouched],
                                  np.asarray(acc)[untouched])


def test_dedup_adagrad_all_invalid_is_identity():
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    acc = jnp.asarray(np.abs(rng.normal(size=(8, 1))).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 8, 12).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(12, 4)).astype(np.float32))
    w2, acc2 = ops.dedup_adagrad(w, acc, idx, g, jnp.zeros((12,), bool),
                                 0.05, 1e-8, fused=True)
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(acc2), np.asarray(acc))


@pytest.mark.parametrize("m,n,nd,d", [(24, 16, 4, 8), (40, 64, 8, 16),
                                      (7, 5, 3, 10)])
def test_gather_project_fused_matches_ref(m, n, nd, d):
    """The narrow-row stitch (picasso_narrow): gather [nd]-rows out of the
    routed buffer + up-project through the learned [nd, d] kernel, fused vs
    the take/matmul reference; not-kept positions exact zeros in both
    outputs."""
    rng = np.random.default_rng(200 + m)
    back = jnp.asarray(rng.normal(size=(m, nd)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    kept = jnp.asarray(rng.random(n) < 0.8)
    proj = jnp.asarray(rng.normal(size=(nd, d)).astype(np.float32))
    wf, nf = ops.gather_project(back, idx, kept, proj, fused=True)
    wr, nr = ref.gather_project_ref(back, idx, kept, proj)
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wr),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(nf), np.asarray(nr),
                               atol=1e-6, rtol=1e-6)
    drop = ~np.asarray(kept)
    np.testing.assert_array_equal(np.asarray(wf)[drop], 0.0)
    np.testing.assert_array_equal(np.asarray(nf)[drop], 0.0)


@pytest.mark.parametrize("m,n,nd,d", [(24, 16, 4, 8), (13, 40, 8, 16)])
def test_gather_project_custom_vjp_parity(m, n, nd, d):
    """jax.grad through the fused custom VJP (w.r.t. the routed buffer AND
    the projection) == jax.grad of the raw reference chain; duplicate idx
    accumulate."""
    rng = np.random.default_rng(300 + m)
    back = jnp.asarray(rng.normal(size=(m, nd)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    kept = jnp.asarray(rng.random(n) < 0.8)
    proj = jnp.asarray(rng.normal(size=(nd, d)).astype(np.float32))
    tgt = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(n, nd)).astype(np.float32))

    def loss(fn):
        def f(b, p):
            wide, narrow = fn(b, p)
            return jnp.sum((wide - tgt) ** 2) + jnp.sum(narrow * c)
        return f

    g_fused = jax.grad(loss(lambda b, p: ops.gather_project(
        b, idx, kept, p, fused=True)), argnums=(0, 1))(back, proj)
    g_raw = jax.grad(loss(lambda b, p: ref.gather_project_ref(
        b, idx, kept, p)), argnums=(0, 1))(back, proj)
    for gf, gr in zip(g_fused, g_raw):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-4)
    # buffer slots no kept position indexes get EXACT zero grad
    touched = np.zeros(m, bool)
    touched[np.asarray(idx)[np.asarray(kept)]] = True
    if (~touched).any():
        np.testing.assert_array_equal(np.asarray(g_fused[0])[~touched], 0.0)


def test_gather_project_grad_matches_ref():
    """The standalone transpose (the engine's explicit backward): fused vs
    the segment_sum reference, duplicate-heavy."""
    rng = np.random.default_rng(77)
    m, n, nd, d = 12, 48, 4, 8
    idx = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    kept = jnp.asarray(rng.random(n) < 0.8)
    proj = jnp.asarray(rng.normal(size=(nd, d)).astype(np.float32))
    g_wide = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    g_narrow = jnp.asarray(rng.normal(size=(n, nd)).astype(np.float32))
    got = ops.gather_project_grad(g_wide, g_narrow, idx, kept, proj, m,
                                  fused=True)
    exp = ref.gather_project_grad_ref(g_wide, g_narrow, idx, kept, proj, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


def test_tier_probe_matches_cache_probe():
    rng = np.random.default_rng(3)
    h, d, n = 16, 8, 40
    keys = jnp.asarray(np.sort(rng.choice(200, h, replace=False)).astype(np.int32))
    rows = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    uniq = jnp.sort(jnp.asarray(
        np.concatenate([np.asarray(keys)[:6], rng.integers(0, 200, n - 6)])
        .astype(np.int32)))
    uvalid = jnp.asarray(np.arange(n) < n - 4)
    hit, slot, prows = ops.tier_probe(uniq, uvalid, keys, rows, fused=True)
    hr, sr = pe.cache_probe(uniq, uvalid, keys)
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(hr))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(sr))
    exp = jnp.where(hr[:, None], jnp.take(rows, sr, axis=0), 0.0)
    np.testing.assert_array_equal(np.asarray(prows), np.asarray(exp))
    assert int(jnp.sum(hit)) >= 6 - 4  # the planted keys actually hit


# ------------------------------------------- no [n, D] per-id intermediate
def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for sub in vs:
                core = getattr(sub, "jaxpr", None)
                if core is None and hasattr(sub, "eqns"):
                    core = sub
                if core is not None and hasattr(core, "eqns"):
                    yield from _walk_eqns(core)


def _has_sub_jaxpr(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for sub in vs:
            if hasattr(sub, "eqns") or hasattr(getattr(sub, "jaxpr", None),
                                               "eqns"):
                return True
    return False


def _per_id_intermediates(jaxpr, shape):
    """LEAF eqns (outside pallas_call) producing an array of the per-id
    shape. Call wrappers (pjit / custom_vjp) merely forward their body's
    result — the body's own eqns are already checked by the recursion."""
    bad = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call" or _has_sub_jaxpr(eqn):
            continue  # kernel-internal blocks are [1, D], not [n, D]
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "shape", None) == shape:
                bad.append(eqn.primitive.name)
    return bad


def test_fused_pool_has_no_per_id_intermediate():
    """Acceptance: neither the fused forward nor the fused backward builds a
    [n, D] per-id array — the reference chains do (take/segment_sum), the
    pallas_call pipelines rows block-by-block. Asserted on the jaxpr: in the
    fused trace the only [n, D] values are the rows_u input and the [n, D]
    row-grad *output* of the backward pallas_call."""
    rng = np.random.default_rng(11)
    n, d, n_bags = 32, 8, 8
    rows_u, inv, w, seg = _pool_args(rng, n, d, n_bags, 20)

    fwd = jax.make_jaxpr(
        lambda r: ops.gather_pool(r, inv, w, seg, n_bags, fused=True))(rows_u)
    assert any(e.primitive.name == "pallas_call" for e in _walk_eqns(fwd.jaxpr))
    assert _per_id_intermediates(fwd.jaxpr, (n, d)) == []

    bwd = jax.make_jaxpr(jax.grad(
        lambda r: jnp.sum(
            ops.gather_pool(r, inv, w, seg, n_bags, fused=True) ** 2)))(rows_u)
    assert _per_id_intermediates(bwd.jaxpr, (n, d)) == []

    # the reference chain DOES materialize it (the thing being fused away)
    fwd_ref = jax.make_jaxpr(
        lambda r: ref.gather_pool_ref(r, inv, w, seg, n_bags))(rows_u)
    assert _per_id_intermediates(fwd_ref.jaxpr, (n, d)) != []


# --------------------------------------------- per-strategy engine parity
def _roundtrip(mesh, strategy, fused, cfg=None, **plan_kw):
    """forward + backward of one batch; returns (pooled, state leaves)."""
    cfg = cfg or get_config("deepfm", smoke=True)
    plan_kw.setdefault("enable_cache", False)
    plan_kw.setdefault("exact_capacity", True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, **plan_kw)
    emb0 = {str(g): s for g, s in
            init_embedding_state(jax.random.PRNGKey(0), plan).items()}
    batch = make_batch(cfg, GB, np.random.default_rng(3))
    fields = jax.tree.map(jnp.asarray, batch["fields"])
    engine = EmbeddingEngine(plan, AXES, 1, strategy=strategy,
                             use_cache=plan_kw.get("enable_cache", False),
                             lr_emb=0.1, use_fused_kernels=fused)
    especs = emb_specs(plan, AXES)

    def f(emb, fields):
        packed = {g.gid: pack_group(g, fields) for g in plan.groups}
        pooled, ctx = engine.forward(emb, packed)
        emb2, _m = engine.backward(emb, ctx, pooled)
        return pooled, emb2

    pooled_specs = {g.gid: jax.sharding.PartitionSpec(AXES, None, None)
                    for g in plan.groups}
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(especs, replicated(fields)),
                          out_specs=(pooled_specs, especs), check_vma=False))
    pooled, emb2 = g(emb0, fields)
    return (jax.tree.map(np.asarray, pooled),
            jax.tree.map(np.asarray, emb2))


@pytest.mark.parametrize("strategy", ["picasso", "hybrid", "ps"])
def test_strategy_fused_roundtrip_parity(mesh1, strategy):
    """Grad-parity per registry strategy: a full forward+backward with the
    fused kernels matches the reference engine (pooled outputs AND every
    post-update state leaf)."""
    p_ref, e_ref = _roundtrip(mesh1, strategy, False)
    p_fus, e_fus = _roundtrip(mesh1, strategy, True)
    for gid in p_ref:
        np.testing.assert_allclose(p_fus[gid], p_ref[gid], atol=1e-5,
                                   err_msg=f"{strategy}/pooled/{gid}")
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(e_ref)[0],
            jax.tree_util.tree_flatten_with_path(e_fus)[0]):
        np.testing.assert_allclose(b, a, atol=1e-5,
                                   err_msg=f"{strategy}/state/{ka}")


def _l2_engine_step(mesh, fused, cache_update="psum"):
    """picasso_l2 with BOTH tiers pre-warmed from master rows, so the fused
    tier probes, the psum L1 update, and the L2 update path all engage."""
    cfg = WDLConfig(name="l2f", fields=(FeatureField("a", 64, 4),), n_dense=0,
                    interactions=(InteractionSpec("fm"),), mlp_dims=(8,))
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14,
                     l2_bytes=320)
    (gid,) = [g.gid for g in plan.groups]
    h1, h2 = plan.cache_rows[gid], plan.l2_rows[gid]
    assert h1 > 0 and h2 > 0
    st = init_embedding_state(jax.random.PRNGKey(1), plan)[gid]
    batch = make_batch(cfg, GB, np.random.default_rng(2))
    fields = jax.tree.map(jnp.asarray, batch["fields"])
    # warm the tiers with ids the batch actually queries: pack_group
    # scrambles raw ids, so fixed key ranges would only hit by luck
    pb = pack_group(plan.groups[0], fields)
    uids = np.unique(np.asarray(pb.ids))
    rows_padded = st.w.shape[0]
    split = max(1, len(uids) // 2)

    def tier(vals, cap):
        keys = np.full((cap,), rows_padded, np.int32)
        keys[:min(len(vals), cap)] = vals[:cap]
        keys = jnp.asarray(np.sort(keys))
        ok = (keys < rows_padded)[:, None]
        safe = jnp.clip(keys, 0, rows_padded - 1)
        return pe.CacheState(
            keys,
            jnp.take(st.w, safe, axis=0) * ok.astype(st.w.dtype),
            jnp.take(st.acc, safe, axis=0) * ok.astype(st.acc.dtype))

    st = EmbeddingState(w=st.w, acc=st.acc, counts=st.counts,
                        cache=tier(uids[:split], h1),
                        l2=tier(uids[split:], h2))
    emb0 = {str(gid): st}
    engine = EmbeddingEngine(plan, AXES, 1, strategy="picasso_l2",
                             lr_emb=0.1, cache_update=cache_update,
                             use_fused_kernels=fused)
    especs = emb_specs(plan, AXES)

    def f(emb, fields):
        packed = {g.gid: pack_group(g, fields) for g in plan.groups}
        pooled, ctx = engine.forward(emb, packed)
        emb2, m = engine.backward(emb, ctx, pooled)
        return pooled, emb2, m

    pooled_specs = {g.gid: jax.sharding.PartitionSpec(AXES, None, None)
                    for g in plan.groups}
    mspecs = {k: jax.sharding.PartitionSpec() for k in engine.metric_keys}
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(especs, replicated(fields)),
                          out_specs=(pooled_specs, especs, mspecs),
                          check_vma=False))
    pooled, emb2, m = g(emb0, fields)
    return (jax.tree.map(np.asarray, pooled), jax.tree.map(np.asarray, emb2),
            {k: int(v) for k, v in m.items()})


@pytest.mark.parametrize("cache_update", ["psum", "stale"])
def test_picasso_l2_fused_tier_parity(mesh1, cache_update):
    """Fused vs reference through warm L1+L2 tiers: identical pooled rows,
    identical tier/master updates, identical per-tier hit counters — in both
    tier-update modes (psum tier adagrad / stale routed-to-owner)."""
    p_ref, e_ref, m_ref = _l2_engine_step(mesh1, False, cache_update)
    p_fus, e_fus, m_fus = _l2_engine_step(mesh1, True, cache_update)
    assert m_ref["cache_hits/l1"] > 0 and m_ref["cache_hits/l2"] > 0
    assert m_fus == m_ref
    for gid in p_ref:
        np.testing.assert_allclose(p_fus[gid], p_ref[gid], atol=1e-5)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(e_ref)[0],
            jax.tree_util.tree_flatten_with_path(e_fus)[0]):
        np.testing.assert_allclose(b, a, atol=1e-5,
                                   err_msg=f"l2/{cache_update}/state/{ka}")


# ------------------------------------------------------------ train smoke
def test_train_smoke_fused_matches_reference_loss(mesh1, axes):
    """End-to-end acceptance: a train smoke forced through the (interpreted)
    Pallas kernels reproduces the reference loss trajectory step for step."""
    from repro.train.train_step import TrainConfig, init_state, make_train_step

    cfg = get_config("deepfm", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=GB, hot_bytes=1 << 14,
                     flush_iters=3, warmup_iters=2)
    from repro.models.wdl import WDLModel
    model = WDLModel(cfg, plan)

    def run(fused):
        state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1,
                           axes=axes)
        step, _ = make_train_step(model, plan, mesh1, axes, GB,
                                  TrainConfig(strategy="picasso",
                                              use_fused_kernels=fused))
        rng = np.random.default_rng(0)
        losses, hits = [], 0
        for _ in range(8):
            b = make_batch(cfg, GB, rng)
            b = jax.device_put(b, to_named(mesh1, batch_specs(b, axes)))
            state, m = step(state, b)
            losses.append(float(m["loss"]))
            hits += int(m["cache_hits"])
        return np.asarray(losses), hits

    l_ref, _ = run(False)
    l_fus, hits_fus = run(True)
    assert hits_fus > 0  # the warm hot tier exercised the fused probe
    np.testing.assert_allclose(l_fus, l_ref, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- chunked retrieval top-k
def test_retrieval_streaming_topk_matches_unchunked(mesh1, axes):
    """n_candidates beyond the per-shard chunk capacity: scoring in
    fixed-size chunks with the streaming top-k merge returns exactly the
    single-shot result (scores AND ids)."""
    from repro.models.wdl import WDLModel
    from repro.serve.serve_step import make_retrieval_step
    from repro.train.train_step import init_state

    cfg = get_config("sasrec", smoke=True)
    plan = make_plan(cfg, world=1, per_device_batch=1, enable_cache=False,
                     exact_capacity=True)
    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1,
                       axes=axes)
    nc = 256
    user = make_batch(cfg, 1, np.random.default_rng(1))
    item_vocab = max(f.vocab for f in cfg.fields)
    cand = jnp.asarray(np.arange(nc, dtype=np.int32) % item_vocab)

    full = make_retrieval_step(model, plan, mesh1, axes, nc, top_k=10)
    sv_full, ids_full = full(state, user, cand)
    # chunk of 32 ids: 8 streamed merges; the engine capacity is sized to
    # the CHUNK, so nc strictly exceeds what one unchunked lookup could hold
    chunked = make_retrieval_step(model, plan, mesh1, axes, nc, top_k=10,
                                  score_chunk=32)
    sv_c, ids_c = chunked(state, user, cand)
    np.testing.assert_allclose(np.asarray(sv_c), np.asarray(sv_full),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids_c), np.asarray(ids_full))
    # a non-divisible chunk exercises the pad/mask tail
    ragged = make_retrieval_step(model, plan, mesh1, axes, nc, top_k=10,
                                 score_chunk=48)
    sv_r, ids_r = ragged(state, user, cand)
    np.testing.assert_allclose(np.asarray(sv_r), np.asarray(sv_full),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_full))


# ------------------------------------------------------- dispatch caching
def test_backend_dispatch_cached_and_resettable(monkeypatch):
    tpu = jax.default_backend() == "tpu"
    try:
        # start from a known state regardless of how this run was launched
        # (the CI soak sets REPRO_FORCE_PALLAS_INTERPRET for the whole file)
        monkeypatch.delenv("REPRO_FORCE_PALLAS_INTERPRET", raising=False)
        ops.reset_backend_cache()
        assert ops._kernel_on("fm_interaction") == tpu
        # cached: setting the env var mid-process has NO effect...
        monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
        assert ops._kernel_on("fm_interaction") == tpu
        # ...until the cache is reset (what a fresh process does)
        ops.reset_backend_cache()
        assert ops._kernel_on("fm_interaction") is True
        assert ops.resolve_fused("auto") is True
        # on a TPU the soak does nothing and the table decides each op:
        # never interpreted, a refused kernel stays on its reference even
        # when asked for, and the printed table says why
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ops.reset_backend_cache()
        assert not ops.interpret_mode()
        assert ops.resolve_fused("auto") is True
        assert ops._kernel_on("fm_interaction") is True
        assert ops._kernel_on("gather_pool", True) is False
        assert ops._kernel_on("fm_interaction", False) is False
        lines = dict(ln.split(None, 1) for ln in ops.dispatch_lines(True)[1:])
        assert lines["fm_interaction"] == "pallas"
        assert lines["gather_pool"] == \
            f"reference: {ops.TPU_KERNELS['gather_pool']}"
    finally:
        monkeypatch.undo()
        ops.reset_backend_cache()
    assert ops.resolve_fused(True) is True
    assert ops.resolve_fused("off") is False
    with pytest.raises(ValueError, match="use_fused_kernels"):
        ops.resolve_fused("definitely-not-a-mode")
