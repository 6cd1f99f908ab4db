"""jit'd dispatch wrappers for the Pallas kernels.

On TPU each op follows ``TPU_KERNELS``: the compiled Pallas kernel where the
table says ``True``, otherwise the pure-jnp reference for the reason the
table states. Nothing is decided by catching a compile error, and a TPU
process never interprets a kernel. Off TPU the reference executes (XLA fuses
it well), while tests exercise the kernels in ``interpret=True`` mode
against the same references; ``REPRO_FORCE_PALLAS_INTERPRET=1`` routes
*all* calls there through the interpreted kernels (slow; correctness soak).
The variable has no effect on a TPU.

Backend dispatch is resolved ONCE, at the first dispatched call (not inside
every traced call): the env var and ``jax.default_backend()`` are read one
time and cached, so the hot path never re-reads ``os.environ``. Call
``reset_backend_cache()`` after changing either (tests do).
``dispatch_lines`` renders what every op runs in this process, for the
launchers to print at startup.

The sparse hot-path ops (``gather_pool`` / ``segment_grad`` /
``dedup_adagrad`` / ``tier_probe`` / ``gather_project`` and the routed-grad
codecs) additionally take an explicit ``fused=`` override: ``None`` follows
the backend default above, ``True`` asks for the Pallas kernel (interpreted
off-TPU; on TPU only where ``TPU_KERNELS`` allows it), ``False`` forces the
jnp reference. ``resolve_fused`` maps the user-facing
``TrainConfig/ServeConfig.use_fused_kernels`` spelling (``'auto' | bool |
'on' | 'off'``) to that override once, at engine construction —
strategies then carry a plain static bool through their traces.

``gather_pool`` is a ``jax.custom_vjp``: its backward is the fused
``segment_grad`` pass (producing ``[n_rows, D]`` row grads directly), so
neither direction materializes the ``[n, D]`` per-id intermediate when
fused. Pooling weights are treated as non-learnable constants (their
cotangent is zero) — matching the engine, which only ever differentiates
with respect to the looked-up rows.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import ref
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

_ROW = ("(1, d) blocks and one-row DMAs of a [rows, 10] f32 array are "
        "refused (not aligned to the 8 x 128 tiling)")

#: What each op runs on a TPU: ``True`` is the compiled Pallas kernel, a
#: string is why the op runs its jnp reference there instead. Every reason
#: is a compile refusal at the DeepFM main path's shapes (d=10, 16384 x 39
#: ids per step); tests/test_tpu_compile.py compiles each kernel for a v5e
#: chip and fails when this table and the compiler disagree.
TPU_KERNELS: Dict[str, Union[bool, str]] = {
    "embedding_bag": f"{_ROW}; an 8-row-block variant compiles, but at "
                     "16384 x 39 ids its prefetched index vectors (3 x 5 MB) "
                     "overflow the 1 MiB SMEM",
    "gather_pool": "runs the embedding_bag kernel",
    "segment_grad": "runs the embedding_bag kernel",
    "dedup_adagrad": f"{_ROW}; XLA stores the master column-major "
                     "({0,1:T(8,128)}), so an in-place kernel would pay a "
                     "relayout copy of the whole table per step",
    "tier_probe": "(1, 1) hit/slot output blocks refused, and the whole "
                  "sorted key vector is sliced dynamically in VMEM",
    "gather_project": "(1, d) and (1, nd) output blocks refused",
    "gather_project_grad": "(1, d) and (1, nd) input blocks refused",
    "fm_interaction": True,
    "fm_interaction_bwd": True,
    "dot_interaction": True,
    "dot_interaction_bwd": "Mosaic: unsupported shape cast",
    "cross_layer": "Mosaic: unimplemented primitive dynamic_slice",
    "cross_layer_bwd": True,
    "fp16_compress": "Mosaic: failed to legalize tpu.pack_subelements",
    "fp16_decompress": "Mosaic: invalid vector type for load",
    "topk_compress": True,
    "topk_decompress": True,
}

#: the ops that take the engine's ``fused=`` override
_SPARSE_OPS = frozenset({
    "gather_pool", "segment_grad", "dedup_adagrad", "tier_probe",
    "gather_project", "gather_project_grad", "fp16_compress",
    "fp16_decompress", "topk_compress", "topk_decompress"})

# (on_tpu, interpret-soak), resolved once at first dispatch
_BACKEND: Optional[Tuple[bool, bool]] = None


def _backend() -> Tuple[bool, bool]:
    global _BACKEND
    if _BACKEND is None:
        tpu = jax.default_backend() == "tpu"
        # the soak is an off-TPU tool: a TPU process never interprets
        force = not tpu and bool(os.environ.get("REPRO_FORCE_PALLAS_INTERPRET"))
        _BACKEND = (tpu, force)
    return _BACKEND


def _kernel_on(op: str, fused: Optional[bool] = None) -> bool:
    """Whether this call of ``op`` runs its Pallas kernel (else reference):
    on TPU the table decides (``fused=False`` can still opt out), off TPU
    the interpret soak or an explicit ``fused`` does."""
    tpu, force = _backend()
    if tpu:
        return fused is not False and TPU_KERNELS[op] is True
    return force if fused is None else bool(fused)


def runs_kernel(op: str, fused: Optional[bool] = None) -> bool:
    """Whether a call of ``op`` with this ``fused`` override runs its Pallas
    kernel here (else its reference chain): for a caller that places a
    kernel's fused work apart from the reference chain's steps."""
    return _kernel_on(op, fused)


def dispatch_lines(fused: Optional[bool] = None) -> List[str]:
    """The backend, then one line per op saying what it runs in this
    process — the table the launchers print at startup. ``fused`` is the
    engine's resolved ``use_fused_kernels`` (it steers the sparse ops
    only)."""
    tpu, _ = _backend()
    lines = [f"backend {jax.default_backend()}, interpret mode "
             f"{'on' if _interpret() else 'off'}; kernels:"]
    for op, entry in TPU_KERNELS.items():
        f = fused if op in _SPARSE_OPS else None
        if _kernel_on(op, f):
            what = "pallas" if tpu else "pallas (interpret)"
        elif tpu and entry is not True and f is not False:
            what = f"reference: {entry}"
        else:
            what = "reference"
        lines.append(f"  {op:20s} {what}")
    return lines


# spelling -> resolved bool, memoized per process so repeated engine
# constructions skip the validation/branching. Keyed by the spelling itself;
# the 'auto'/None entries depend on _BACKEND, so the memo MUST die with it
# (reset_backend_cache clears both — an interpret-soak test that flipped the
# env var must not leak its resolved dispatch into later tests).
_RESOLVE_MEMO: dict = {}


def reset_backend_cache() -> None:
    """Forget the cached backend decision (tests that flip the env var) and
    the per-spelling ``resolve_fused`` memo derived from it."""
    global _BACKEND
    _BACKEND = None
    _RESOLVE_MEMO.clear()


def _interpret() -> bool:
    """Pallas kernels are interpreted exactly when not on a TPU."""
    return not _backend()[0]


def interpret_mode() -> bool:
    """Whether Pallas kernels run through the interpreter in this process
    (any TPU-less backend). Public so the bench harness can stamp its rows —
    interpreter timings must never be mistaken for silicon numbers."""
    return _interpret()


def resolve_fused(spec: Union[str, bool, None]) -> bool:
    """Map a ``use_fused_kernels`` spelling to a static bool, once.

    ``'auto'``/``None`` follow the backend (on for TPU, where
    ``TPU_KERNELS`` then gates each op, and under the interpret-soak env
    var; reference elsewhere); booleans and ``'on'``/``'off'`` force it.
    Raises on anything else so config typos fail at construction, not
    silently at dispatch. Resolutions are memoized per spelling;
    ``reset_backend_cache`` clears the memo together with the backend
    decision it is derived from."""
    try:
        return _RESOLVE_MEMO[spec]
    except KeyError:
        pass
    if spec is None or spec == "auto":
        out = any(_backend())
    elif isinstance(spec, bool):
        out = spec
    elif spec == "on":
        out = True
    elif spec == "off":
        out = False
    else:
        raise ValueError(
            f"use_fused_kernels must be 'auto', 'on', 'off' or a bool; got {spec!r}")
    _RESOLVE_MEMO[spec] = out
    return out


# ---------------------------------------------------------------------------
# dense / interaction kernels (cached backend dispatch + fused VJPs)
#
# ``pallas_call`` defines no VJP, so a bare dispatcher is only differentiable
# on the CPU reference branch — the train step would fail under jax.grad
# anywhere the Pallas branch is live (TPU, or the interpret soak). Each
# dispatcher is therefore a ``jax.custom_vjp``. On the Pallas branch the
# interaction backwards run their own fused kernels
# (``repro.kernels.interaction_bwd``) instead of re-materializing the
# reference transpose's HBM intermediates; on the CPU branch the backward IS
# ``jax.vjp`` of the same reference the forward ran, so CPU grads stay
# bitwise-unchanged. (``embedding_bag`` keeps the reference transpose: the
# engine's production sparse backward is the standalone ``segment_grad``
# pass, not this op's VJP.)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _embedding_bag(table, ids, seg, w, n_bags: int):
    if _kernel_on("embedding_bag"):
        # the kernel wants explicit weights; the reference keeps its
        # weightless fast path (no [n, D] multiply by runtime ones)
        wp = w if w is not None else jnp.ones_like(ids, table.dtype)
        return embedding_bag_pallas(table, ids, seg, wp, n_bags,
                                    interpret=_interpret())
    return ref.embedding_bag_ref(table, ids, seg, n_bags, w)


def _embedding_bag_fwd(table, ids, seg, w, n_bags: int):
    return _embedding_bag(table, ids, seg, w, n_bags), (table, ids, seg, w)


def _embedding_bag_bwd(n_bags: int, res, g):
    table, ids, seg, w = res
    if w is None:
        _, vjp = jax.vjp(
            lambda t: ref.embedding_bag_ref(t, ids, seg, n_bags, None), table)
        return vjp(g) + (None, None, None)
    _, vjp = jax.vjp(
        lambda t, w_: ref.embedding_bag_ref(t, ids, seg, n_bags, w_), table, w)
    gt, gw = vjp(g)
    return gt, None, None, gw


_embedding_bag.defvjp(_embedding_bag_fwd, _embedding_bag_bwd)


def embedding_bag(table, ids, seg, n_bags: int, weights: Optional[jnp.ndarray] = None):
    return _embedding_bag(table, ids, seg, weights, int(n_bags))


@jax.custom_vjp
def fm_interaction(fields):
    if _kernel_on("fm_interaction"):
        return fm_interaction_pallas(fields, interpret=_interpret())
    return ref.fm_interaction_ref(fields)


def _fm_fwd(fields):
    return fm_interaction(fields), fields


def _fm_bwd(fields, g):
    if _kernel_on("fm_interaction_bwd"):
        return (fm_interaction_bwd_pallas(fields, g, interpret=_interpret()),)
    _, vjp = jax.vjp(ref.fm_interaction_ref, fields)
    return vjp(g)


fm_interaction.defvjp(_fm_fwd, _fm_bwd)


@jax.custom_vjp
def dot_interaction(fields):
    if _kernel_on("dot_interaction"):
        return dot_interaction_pallas(fields, interpret=_interpret())
    return ref.dot_interaction_ref(fields)


def _dot_fwd(fields):
    return dot_interaction(fields), fields


def _dot_bwd(fields, g):
    if _kernel_on("dot_interaction_bwd"):
        return (dot_interaction_bwd_pallas(fields, g, interpret=_interpret()),)
    _, vjp = jax.vjp(ref.dot_interaction_ref, fields)
    return vjp(g)


dot_interaction.defvjp(_dot_fwd, _dot_bwd)


@jax.custom_vjp
def cross_layer(x0, x, w, b):
    if _kernel_on("cross_layer"):
        return cross_layer_pallas(x0, x, w, b, interpret=_interpret())
    return ref.cross_layer_ref(x0, x, w, b)


def _cross_fwd(x0, x, w, b):
    return cross_layer(x0, x, w, b), (x0, x, w, b)


def _cross_bwd(res, g):
    if _kernel_on("cross_layer_bwd"):
        return cross_layer_bwd_pallas(*res, g, interpret=_interpret())
    _, vjp = jax.vjp(ref.cross_layer_ref, *res)
    return vjp(g)


cross_layer.defvjp(_cross_fwd, _cross_bwd)


# ---------------------------------------------------------------------------
# fused sparse hot path: gather+pool (custom VJP), dedup+adagrad, tier probe
# ---------------------------------------------------------------------------


def _gather_pool_impl(rows_u, inv, weights, seg, n_bags: int,
                      fused: Optional[bool]):
    if _kernel_on("gather_pool", fused):
        return gather_pool_pallas(rows_u, inv, weights, seg, n_bags,
                                  interpret=_interpret())
    return ref.gather_pool_ref(rows_u, inv, weights, seg, n_bags)


def _segment_grad_impl(g_bags, seg, weights, inv, n_rows: int,
                       fused: Optional[bool]):
    if _kernel_on("segment_grad", fused):
        return segment_grad_pallas(g_bags, seg, weights, inv, n_rows,
                                   interpret=_interpret())
    return ref.segment_grad_ref(g_bags, seg, weights, inv, n_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gather_pool(rows_u, inv, weights, seg, n_bags: int,
                 fused: Optional[bool]):
    return _gather_pool_impl(rows_u, inv, weights, seg, n_bags, fused)


def _gather_pool_fwd(rows_u, inv, weights, seg, n_bags: int,
                     fused: Optional[bool]):
    out = _gather_pool_impl(rows_u, inv, weights, seg, n_bags, fused)
    return out, (inv, weights, seg, rows_u.shape[0])


def _gather_pool_bwd(n_bags: int, fused: Optional[bool], res, g):
    inv, weights, seg, n_rows = res
    g_rows = _segment_grad_impl(g, seg, weights, inv, n_rows, fused)
    # weights are pooling constants (see module docstring): zero cotangent
    return g_rows, None, jnp.zeros_like(weights), None


_gather_pool.defvjp(_gather_pool_fwd, _gather_pool_bwd)


def gather_pool(rows_u, inv, weights, seg, n_bags: int,
                fused: Optional[bool] = None):
    """Fused forward SegmentReduction ``bags[seg] += w * rows_u[inv]`` with a
    fused-transpose custom VJP. Requires ``seg`` sorted ascending and
    covering every bag (the packed-batch layout guarantees it)."""
    return _gather_pool(rows_u, inv, weights, seg, int(n_bags), fused)


def segment_grad(g_bags, seg, weights, inv, n_rows: int,
                 fused: Optional[bool] = None):
    """Transpose of ``gather_pool`` as a standalone op (the engine's explicit
    backward path): ``g_rows[u] = sum_{inv[i]=u} w[i] * g_bags[seg[i]]``."""
    return _segment_grad_impl(g_bags, seg, weights, inv, int(n_rows), fused)


def dedup_adagrad(w, acc, idx, g, valid, lr: float, eps: float,
                  fused: Optional[bool] = None):
    """Sum duplicate row grads and apply row-wise adagrad to the touched rows
    of ``(w, acc)`` in one pass (in-place scatter when fused). The fused
    kernel accumulates duplicates in the reference order — untouched rows
    stay bitwise identical, touched rows match to ~1 ULP of XLA-fusion
    reassociation in the adagrad arithmetic."""
    if _kernel_on("dedup_adagrad", fused):
        return dedup_adagrad_pallas(w, acc, idx, g, valid, float(lr),
                                    float(eps), interpret=_interpret())
    return ref.dedup_adagrad_ref(w, acc, idx, g, valid, lr, eps)


def tier_probe(uniq, uvalid, keys, rows, fused: Optional[bool] = None):
    """Probe one sorted-key cache tier: ``(hit, slot, rows)`` with miss rows
    exactly zero. ``slot`` is the clamped searchsorted position (the
    backward scatter reuses it)."""
    if _kernel_on("tier_probe", fused):
        return tier_probe_pallas(uniq, uvalid, keys, rows,
                                 interpret=_interpret())
    return ref.tier_probe_ref(uniq, uvalid, keys, rows)


def _gather_project_impl(back, idx, kept, proj, fused: Optional[bool]):
    if _kernel_on("gather_project", fused):
        return gather_project_pallas(back, idx, kept, proj,
                                     interpret=_interpret())
    return ref.gather_project_ref(back, idx, kept, proj)


def _gather_project_grad_impl(g_wide, g_narrow, idx, kept, proj, m: int,
                              fused: Optional[bool]):
    if _kernel_on("gather_project_grad", fused):
        return gather_project_grad_pallas(g_wide, g_narrow, idx, kept, proj,
                                          m, interpret=_interpret())
    return ref.gather_project_grad_ref(g_wide, g_narrow, idx, kept, proj, m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_project(back, idx, kept, proj, fused: Optional[bool]):
    return _gather_project_impl(back, idx, kept, proj, fused)


def _gather_project_fwd(back, idx, kept, proj, fused: Optional[bool]):
    out = _gather_project_impl(back, idx, kept, proj, fused)
    # the narrow residual is already kept-masked, so the projection cotangent
    # below needs no re-mask
    return out, (idx, kept, out[1], proj, back.shape[0])


def _gather_project_bwd(fused: Optional[bool], res, g):
    idx, kept, narrow, proj, m = res
    g_wide, g_narrow = g
    g_back = _gather_project_grad_impl(g_wide, g_narrow, idx, kept, proj,
                                       m, fused)
    g_proj = narrow.T @ g_wide          # [d, D], one MXU pass
    return g_back, None, None, g_proj


_gather_project.defvjp(_gather_project_fwd, _gather_project_bwd)


def gather_project(back, idx, kept, proj, fused: Optional[bool] = None):
    """Narrow-row stitch for hot/cold heterogeneous placement: gather
    ``[d]``-narrow rows out of the routed-back buffer and project them up
    through the learned per-group ``[d, D]`` map in one fused pass —
    ``(wide [n, D], narrow [n, d])``, with not-kept positions exact zeros in
    both. A ``jax.custom_vjp``: the backward folds the wide cotangent
    through ``proj^T`` and run-accumulates onto the buffer slots (no
    ``[n, d]``-then-``[n, D]`` chain in either direction), and the
    projection's gradient is one ``narrow^T @ g_wide`` matmul off the
    forward's residual."""
    return _gather_project(back, idx, kept, proj, fused)


def gather_project_grad(g_wide, g_narrow, idx, kept, proj, m: int,
                        fused: Optional[bool] = None):
    """Transpose of ``gather_project`` w.r.t. the routed buffer, standalone
    (the engine's explicit backward path): ``g_back[j] = sum_{idx[i]=j}
    kept[i] * (g_wide[i] @ proj^T + g_narrow[i])``."""
    return _gather_project_grad_impl(g_wide, g_narrow, idx, kept, proj,
                                     int(m), fused)


# ---------------------------------------------------------------------------
# routed-gradient wire compression (grad_compress modes; the collective
# wrappers live in repro.optim.grad_compression)
# ---------------------------------------------------------------------------


def compress_fp16(g, fused: Optional[bool] = None):
    """Per-row amax scale + float16 cast: ``(q [m, D] f16, scale [m, 1] f32)``.
    All-zero rows compress to exact zeros (padded bucket slots roundtrip
    bitwise)."""
    if _kernel_on("fp16_compress", fused):
        return fp16_compress_pallas(g, interpret=_interpret())
    return ref.fp16_compress_ref(g)


def decompress_fp16(q, scale, fused: Optional[bool] = None):
    if _kernel_on("fp16_decompress", fused):
        return fp16_decompress_pallas(q, scale, interpret=_interpret())
    return ref.fp16_decompress_ref(q, scale)


def compress_topk(g, k: int, fused: Optional[bool] = None):
    """Per-row magnitude top-k sparsification: ``(vals [m, k], idx [m, k])``,
    descending magnitude, ties toward the lower index."""
    if _kernel_on("topk_compress", fused):
        return topk_compress_pallas(g, int(k), interpret=_interpret())
    return ref.topk_compress_ref(g, int(k))


def decompress_topk(vals, idx, d: int, fused: Optional[bool] = None):
    if _kernel_on("topk_decompress", fused):
        return topk_decompress_pallas(vals, idx, int(d),
                                      interpret=_interpret())
    return ref.topk_decompress_ref(vals, idx, int(d))
