"""Compile the Pallas kernels for a described (not attached) TPU v5e chip.

Nothing here runs on a device: each case lowers a kernel at the DeepFM main
path's shapes (d=10, a global batch of 16384 x 39 ids on one chip) and asks
the TPU compiler for the executable, which raises what the chip's compiler
would raise. Every kernel that ``ops.TPU_KERNELS`` turns on must compile,
and every kernel the table keeps on its reference must still be refused —
otherwise the table is stale in one direction or the other.

The topology is described inside a module-scoped fixture (never at import,
in ``conftest.py`` or in a ``skipif``): only one process at a time may load
the TPU library, so the call must run in the worker that was given this
file, after its first test has started.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.cross_layer import cross_layer_pallas
from repro.kernels.dot_interaction import dot_interaction_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.fm_interaction import fm_interaction_pallas
from repro.kernels.fused_embedding import (dedup_adagrad_pallas,
                                           gather_pool_pallas,
                                           gather_project_grad_pallas,
                                           gather_project_pallas,
                                           segment_grad_pallas,
                                           tier_probe_pallas)
from repro.kernels.grad_compress import (fp16_compress_pallas,
                                         fp16_decompress_pallas,
                                         topk_compress_pallas,
                                         topk_decompress_pallas)
from repro.kernels.interaction_bwd import (cross_layer_bwd_pallas,
                                           dot_interaction_bwd_pallas,
                                           fm_interaction_bwd_pallas)
from repro.optim.grad_compression import topk_k

B, F, D = 16384, 39, 10     # DeepFM: global batch on one chip, fields, width
N = B * F                   # ids per step
M = 1022364                 # routed rows (plan capacity at world 1)
H = 1 << 22                 # hot-tier rows of a 1 GiB budget
V = 1 << 24                 # master rows (one capped field's worth)
ND = 4                      # narrow width of picasso_narrow
K = topk_k(D)
f32, i32 = jnp.float32, jnp.int32


def _cases():
    """op -> (kernel callable, argument (shape, dtype) list)."""
    return {
        "embedding_bag": (
            lambda t, i, s, w: embedding_bag_pallas(t, i, s, w, N),
            [((V, D), f32), ((N,), i32), ((N,), i32), ((N,), f32)]),
        "gather_pool": (
            lambda r, i, w, s: gather_pool_pallas(r, i, w, s, N),
            [((N, D), f32), ((N,), i32), ((N,), f32), ((N,), i32)]),
        "segment_grad": (
            lambda g, s, w, i: segment_grad_pallas(g, s, w, i, N),
            [((N, D), f32), ((N,), i32), ((N,), f32), ((N,), i32)]),
        "dedup_adagrad": (
            lambda w, a, i, g, v: dedup_adagrad_pallas(w, a, i, g, v,
                                                       0.05, 1e-8),
            [((V, D), f32), ((V, 1), f32), ((M,), i32), ((M, D), f32),
             ((M,), jnp.bool_)]),
        "tier_probe": (
            tier_probe_pallas,
            [((N,), i32), ((N,), jnp.bool_), ((H,), i32), ((H, D), f32)]),
        "gather_project": (
            gather_project_pallas,
            [((M, ND), f32), ((N,), i32), ((N,), jnp.bool_), ((ND, D), f32)]),
        "gather_project_grad": (
            lambda gw, gn, i, k, p: gather_project_grad_pallas(gw, gn, i, k,
                                                               p, M),
            [((N, D), f32), ((N, ND), f32), ((N,), i32), ((N,), jnp.bool_),
             ((ND, D), f32)]),
        "fm_interaction": (fm_interaction_pallas, [((B, F, D), f32)]),
        "fm_interaction_bwd": (fm_interaction_bwd_pallas,
                               [((B, F, D), f32), ((B, 1), f32)]),
        "dot_interaction": (dot_interaction_pallas, [((B, F, D), f32)]),
        "dot_interaction_bwd": (dot_interaction_bwd_pallas,
                                [((B, F, D), f32),
                                 ((B, F * (F - 1) // 2), f32)]),
        "cross_layer": (cross_layer_pallas,
                        [((B, F * D), f32), ((B, F * D), f32),
                         ((F * D, F * D), f32), ((F * D,), f32)]),
        "cross_layer_bwd": (cross_layer_bwd_pallas,
                            [((B, F * D), f32), ((B, F * D), f32),
                             ((F * D, F * D), f32), ((F * D,), f32),
                             ((B, F * D), f32)]),
        "fp16_compress": (fp16_compress_pallas, [((M, D), f32)]),
        "fp16_decompress": (fp16_decompress_pallas,
                            [((M, D), jnp.float16), ((M, 1), f32)]),
        "topk_compress": (lambda g: topk_compress_pallas(g, K),
                          [((M, D), f32)]),
        "topk_decompress": (lambda v, i: topk_decompress_pallas(v, i, D),
                            [((M, K), f32), ((M, K), i32)]),
    }


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_op(topo):
    """op -> None if it compiles for one v5e chip, else the compiler's
    error text. Memoized, so the agreement test reuses the per-op compiles;
    the persistent compilation cache is off meanwhile (an entry written for
    a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    cases = _cases()
    results = {}
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(op):
        if op not in results:
            fn, shapes = cases[op]
            args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                    for s, dt in shapes]
            try:
                jax.jit(fn).lower(*args).compile()
                results[op] = None
            except Exception as e:  # noqa: BLE001 — the refusal IS the result
                results[op] = str(e).splitlines()[0][:300]
        return results[op]

    yield run
    jax.config.update("jax_enable_compilation_cache", was_on)


ON = sorted(op for op, v in ops.TPU_KERNELS.items() if v is True)


def test_every_op_has_a_compile_case():
    assert set(_cases()) == set(ops.TPU_KERNELS)


@pytest.mark.parametrize("op", ON)
def test_enabled_kernel_compiles_for_v5e(compile_op, op):
    err = compile_op(op)
    assert err is None, f"{op} is on for TPU but the compiler refuses it: {err}"


def test_fm_interaction_compiles_at_deepfm_width(compile_op):
    """DeepFM's interaction at 39 fields x d=10, both directions."""
    assert compile_op("fm_interaction") is None
    assert compile_op("fm_interaction_bwd") is None


def test_dispatch_table_agrees_with_compiler(compile_op):
    wrong = {}
    for op, entry in ops.TPU_KERNELS.items():
        err = compile_op(op)
        if (entry is True) != (err is None):
            wrong[op] = ("table: pallas, compiler: " + str(err)
                         if entry is True else
                         f"table: reference ({entry}), but it compiles")
    assert not wrong, wrong
