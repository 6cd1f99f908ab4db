"""Kernels (``kernels/fm_interaction.py``, ``kernels/interaction_bwd.py``):
the FM forward and backward Pallas kernels' least time on the roofline,
from their FLOPs and bytes (``bench/flops.py``), over their summed device
time in the trace (kernel operations are named after their jitted function:
``jvp_jit_fm_interaction_pallas__.N``, ``transpose_jvp_jit_fm_interaction_bwd_pallas___.N``).
Each call works on the chip's [B, F, d] field stack."""
FWD = r"(?<!transpose_)jvp_jit_fm_interaction_pallas"
BWD = r"fm_interaction_bwd_pallas"


def read(ctx):
    fl = ctx.flops
    b = ctx.global_batch // ctx.chips
    f = len(ctx.cfg["categorical_vocab"]) + ctx.cfg.get("numeric_bucket_fields", 0)
    d = ctx.cfg["embed_dim"]
    fwd = ctx.trace.matching(FWD)
    bwd = ctx.trace.matching(BWD)
    spent = sum(e[2] for e in fwd + bwd)
    if not fwd or not bwd or spent <= 0:
        return None
    least = (len(fwd) * fl.least_seconds(fl.fm_fwd(b, f, d), ctx.peak)
             + len(bwd) * fl.least_seconds(fl.fm_bwd(b, f, d), ctx.peak))
    return 100.0 * least / spent
