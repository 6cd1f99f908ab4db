"""Gradient compression: bf16/f8 psum payloads + error feedback, plus the
routed sparse-gradient path (PR 6) — per-mode compress/decompress roundtrips
('none' | 'fp16' | 'topk'), fused-kernel vs reference parity, zero-row
exactness (the dedup scatter's padded-slot contract), and the compressed
all_gather collective wrapper."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.kernels import ref
from repro.optim.grad_compression import (ROUTED_MODES, compress_rows,
                                          compressed_all_gather,
                                          compressed_psum, decompress_rows,
                                          topk_k, validate_routed_mode)

AXES = ("data", "model")


def _psum1(mesh, grads, mode, residual=None):
    def f(g, r):
        out, res = compressed_psum(g, AXES, mode=mode, residual=r)
        return out, res

    r0 = residual if residual is not None else jax.tree.map(jnp.zeros_like, grads)
    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False))(grads, r0)


def test_bf16_close(mesh1):
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)).astype(np.float32))}
    exact, _ = _psum1(mesh1, g, "none")
    comp, res = _psum1(mesh1, g, "bf16")
    rel = float(jnp.abs(comp["w"] - exact["w"]).max() / jnp.abs(exact["w"]).max())
    assert rel < 1e-2
    # error feedback residual holds the rounding error
    np.testing.assert_allclose(np.asarray(comp["w"] + res["w"]),
                               np.asarray(exact["w"]), atol=1e-6)


def test_error_feedback_accumulates(mesh1):
    """Over repeated steps with the same gradient, EF makes the *mean*
    compressed update converge to the true gradient."""
    g = {"w": jnp.full((32,), 0.001, jnp.float32)}  # tiny: heavy f8 rounding
    res = None
    total = jnp.zeros((32,))
    for _ in range(64):
        out, res = _psum1(mesh1, g, "f8", residual=res)
        total = total + out["w"]
    mean_err = float(jnp.abs(total / 64 - 0.001).max() / 0.001)
    assert mean_err < 0.05


# ------------------------------------------------ routed-path roundtrips
def _rows(m=23, d=16, zero_rows=(3, 11)):
    g = np.random.default_rng(0).normal(size=(m, d)).astype(np.float32)
    for r in zero_rows:
        g[r] = 0.0
    return jnp.asarray(g)


def test_validate_routed_mode():
    for m in ROUTED_MODES:
        assert validate_routed_mode(m) == m
    with pytest.raises(ValueError):
        validate_routed_mode("bf16")  # a psum mode, not a routed mode


@pytest.mark.parametrize("fused", [False, True])
def test_none_roundtrip_is_identity(fused):
    g = _rows()
    out = decompress_rows(compress_rows(g, "none", fused=fused),
                          g.shape[-1], "none", fused=fused)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(g))


@pytest.mark.parametrize("fused", [False, True])
def test_fp16_roundtrip(fused):
    g = _rows()
    out = decompress_rows(compress_rows(g, "fp16", fused=fused),
                          g.shape[-1], "fp16", fused=fused)
    # per-row amax scaling: error bounded by fp16 eps of the row max
    scale = np.abs(np.asarray(g)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(out) - np.asarray(g))
    assert (err <= scale * 2 ** -10 + 1e-8).all()
    # all-zero rows (padded / dropped bucket slots) roundtrip bitwise
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[11]), 0.0)


@pytest.mark.parametrize("fused", [False, True])
def test_topk_roundtrip_keeps_heaviest(fused):
    g = _rows()
    d = g.shape[-1]
    out = decompress_rows(compress_rows(g, "topk", fused=fused),
                          d, "topk", fused=fused)
    # exact on the kept coordinates, zero elsewhere == mask reference
    k = topk_k(d)
    order = np.argsort(-np.abs(np.asarray(g)), axis=-1, kind="stable")
    mask = np.zeros(g.shape, bool)
    np.put_along_axis(mask, order[:, :k], True, axis=-1)
    np.testing.assert_allclose(np.asarray(out),
                               np.where(mask, np.asarray(g), 0.0),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)


def test_topk_full_budget_is_exact():
    """k == d degenerates to a lossless permutation roundtrip."""
    g = _rows(m=7, d=4, zero_rows=())
    v, i = ref.topk_compress_ref(g, 4)
    out = ref.topk_decompress_ref(v, i, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=1e-7)


def test_fused_payloads_match_reference():
    """The Pallas (interpret) compressors produce byte-identical payloads to
    the jnp references — owners decompress the same numbers regardless of
    which side compressed."""
    g = _rows()
    qf, sf = compress_rows(g, "fp16", fused=True)
    qr, sr = compress_rows(g, "fp16", fused=False)
    np.testing.assert_array_equal(np.asarray(qf), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(sr))
    vf, idf = compress_rows(g, "topk", fused=True)
    vr, idr = compress_rows(g, "topk", fused=False)
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(idf), np.asarray(idr))


@pytest.mark.parametrize("mode", ["none", "fp16", "topk"])
def test_compressed_all_gather(mesh1, mode):
    """world=1 all_gather: the compressed wrapper must equal decompress
    (compress (g)) exactly — the collective is identity, so any difference
    is the wrapper mishandling the payload tree."""
    g = _rows()

    def f(x):
        return compressed_all_gather(x, AXES, mode=mode)

    got = jax.jit(shard_map(f, mesh=mesh1, in_specs=(P(),), out_specs=P(),
                            check_vma=False))(g)
    want = decompress_rows(compress_rows(g, mode), g.shape[-1], mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)
