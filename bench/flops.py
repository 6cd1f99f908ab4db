"""Operations and bytes from shapes, for the step's share of peak and the
kernels' roofline shares. Every count is written beside its formula; B is
the batch, F the number of fields, d the embedding width, D the width of the
cross network's input (the F pooled field rows, then the numeric features or
the bottom MLP's output), r a low-rank cross's rank. FLOPs count a multiply
and an add as two.
"""
from __future__ import annotations

from typing import Dict

F32 = 4  # bytes
PROBE_BYTES_PER_ID = 9


def _dims(cfg: Dict):
    f = len(cfg["categorical_vocab"]) + cfg.get("numeric_bucket_fields", 0)
    d = cfg["embed_dim"]
    bottom = cfg.get("bottom_mlp", [])
    base = f * d + (bottom[-1] if bottom else cfg.get("n_dense", 0))
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


def cross_lowrank_fwd(b: int, dd: int, r: int) -> Dict[str, int]:
    """One low-rank cross layer's forward ``x0 * ((x @ W_down) @ W_up + b)
    + x``: u = x@W_down (2*B*D*r), u@W_up (2*B*r*D), then the bias, times
    x0 and the residual (3*B*D): 4*B*D*r + 3*B*D. Reads x0, x [B, D],
    W_down [D, r], W_up [r, D], b [D]; writes [B, D]:
    4*(3*B*D + 2*D*r + D) bytes."""
    return {"flops": 4 * b * dd * r + 3 * b * dd,
            "bytes": F32 * (3 * b * dd + 2 * dd * r + dd)}


def cross_lowrank_bwd(b: int, dd: int, r: int) -> Dict[str, int]:
    """One low-rank cross layer's backward, z recomputed as
    ``cross_layer_bwd`` does: u = x@W_down (2*B*D*r), z = u@W_up + b
    (2*B*D*r + B*D), gz = g*x0 (B*D), gx0 = g*z (B*D), gu = gz@W_up^T
    (2*B*D*r), gx = gu@W_down^T + g (2*B*D*r + B*D), gW_up = u^T@gz
    (2*B*D*r), gW_down = x^T@gu (2*B*D*r), gb = sum_B gz (B*D):
    12*B*D*r + 5*B*D. Reads x0, x, g [B, D], W_down [D, r], W_up [r, D],
    b [D]; writes gx0, gx [B, D], gW_down [D, r], gW_up [r, D], gb [D]:
    4*(5*B*D + 4*D*r + 2*D) bytes."""
    return {"flops": 12 * b * dd * r + 5 * b * dd,
            "bytes": F32 * (5 * b * dd + 4 * dd * r + 2 * dd)}


def cross_flops(b: int, dd: int, layers: int, rank: int = 0) -> int:
    """Cross network: per layer the matmul forward and its two gradients,
    6*B*D^2, and 3*B*D elementwise each way (bias, times x0, residual;
    g*x0, g*z, the bias gradient): layers * (6*B*D^2 + 6*B*D). At low rank
    r the matmul is two, D to r and r to D, each 2*B*D*r forward and twice
    that backward: layers * (12*B*D*r + 6*B*D)."""
    per = 12 * b * dd * rank if rank else 6 * b * dd * dd
    return layers * (per + 6 * b * dd)


def dense_step_flops(cfg: Dict, b: int) -> int:
    """The dense forward and backward of one step: bottom MLP, interactions
    and top MLP, no sparse operations, nothing recomputed."""
    f, d, base = _dims(cfg)
    total = 0
    if cfg.get("bottom_mlp"):
        total += mlp_flops(b, [cfg["n_dense"]] + list(cfg["bottom_mlp"]))
    for it in cfg["interactions"]:
        if it["kind"] == "fm":
            total += fm_fwd(b, f, d)["flops"] + fm_bwd(b, f, d)["flops"]
        elif it["kind"] == "linear":
            total += linear_flops(b, f, d)
        elif it["kind"] == "cross":
            total += cross_flops(b, base, it["n_layers"], it.get("low_rank", 0))
    return total + mlp_flops(b, [base] + list(cfg["mlp_dims"]) + [1])


def tier_probe_least(n_distinct: float) -> Dict[str, float]:
    """The least a hot-tier probe must move for ``n_distinct`` distinct ids:
    read each id's key (4 B), write its slot (4 B) and its hit flag (1 B):
    9 bytes an id. No FLOPs. Nothing for the tier's key array, since a
    hashed tier reads only the slots it probes, nothing for padding, and
    nothing for the hit rows, which the stitch fetches."""
    return {"flops": 0, "bytes": PROBE_BYTES_PER_ID * n_distinct}


def least_seconds(work: Dict[str, int], peak: Dict) -> float:
    """The roofline's least time: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
