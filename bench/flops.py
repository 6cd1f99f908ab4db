"""Operations and bytes from shapes, for the step's share of peak and the
kernels' roofline shares. Every count is written beside its formula; B is
the batch, F the number of fields, d the embedding width, D the width of the
cross network's input. FLOPs count a multiply and an add as two.
"""
from __future__ import annotations

from typing import Dict

F32 = 4  # bytes


def _dims(cfg: Dict):
    f = len(cfg["categorical_vocab"]) + cfg.get("numeric_bucket_fields", 0)
    d = cfg["embed_dim"]
    base = f * d + cfg.get("n_dense", 0)
    return f, d, base


def mlp_flops(b: int, dims) -> int:
    """Forward 2*B*sum(d_in*d_out); the backward takes the input gradient and
    the weight gradient, twice the forward: 6*B*sum(d_in*d_out)."""
    return 6 * b * sum(i * o for i, o in zip(dims[:-1], dims[1:]))


def fm_fwd(b: int, f: int, d: int) -> Dict[str, int]:
    """FM forward kernel: per sample F*d adds for sum_f x, F*d multiplies
    for x^2, F*d adds for sum_f x^2, then d each for s^2, the subtraction
    and the sum over d: 3*B*F*d + 3*B*d. Reads [B, F, d], writes [B, 1]."""
    return {"flops": 3 * b * f * d + 3 * b * d, "bytes": F32 * (b * f * d + b)}


def fm_bwd(b: int, f: int, d: int) -> Dict[str, int]:
    """FM backward kernel ``g * (sum_f x - x)``: F*d adds for the sum, F*d
    subtractions, F*d multiplies: 3*B*F*d. Reads [B, F, d] and [B, 1],
    writes [B, F, d]."""
    return {"flops": 3 * b * f * d, "bytes": F32 * (2 * b * f * d + b)}


def linear_flops(b: int, f: int, d: int) -> int:
    """``sum_f x_f @ W_f`` with W_f [d, 1]: forward 2*B*F*d, input and weight
    gradients 2*B*F*d each: 6*B*F*d."""
    return 6 * b * f * d


def cross_layer_bwd(b: int, dd: int) -> Dict[str, int]:
    """One cross layer's backward kernel: z = x@W + b (2*B*D^2 + B*D),
    gz = g*x0 (B*D), gx0 = g*z (B*D), gx = gz@W^T + g (2*B*D^2 + B*D),
    gW = x^T@gz (2*B*D^2), gb = sum_B gz (B*D): 6*B*D^2 + 5*B*D. Reads x0,
    x, g [B, D], W [D, D], b [D]; writes gx0, gx [B, D], gW [D, D], gb [D]:
    4*(5*B*D + 2*D^2 + 2*D) bytes."""
    return {"flops": 6 * b * dd * dd + 5 * b * dd,
            "bytes": F32 * (5 * b * dd + 2 * dd * dd + 2 * dd)}


def cross_flops(b: int, dd: int, layers: int) -> int:
    """Cross network: per layer the matmul forward and its two gradients,
    6*B*D^2, and 3*B*D elementwise each way (bias, times x0, residual;
    g*x0, g*z, the bias gradient): layers * (6*B*D^2 + 6*B*D)."""
    return layers * (6 * b * dd * dd + 6 * b * dd)


def dense_step_flops(cfg: Dict, b: int) -> int:
    """The dense forward and backward of one step: interactions and MLP, no
    sparse operations, nothing recomputed."""
    f, d, base = _dims(cfg)
    total, deep_in = 0, base
    for it in cfg["interactions"]:
        if it["kind"] == "fm":
            total += fm_fwd(b, f, d)["flops"] + fm_bwd(b, f, d)["flops"]
        elif it["kind"] == "linear":
            total += linear_flops(b, f, d)
        elif it["kind"] == "cross":
            total += cross_flops(b, base, it["n_layers"])
    return total + mlp_flops(b, [deep_in] + list(cfg["mlp_dims"]) + [1])


def least_seconds(work: Dict[str, int], peak: Dict) -> float:
    """The roofline's least time: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
