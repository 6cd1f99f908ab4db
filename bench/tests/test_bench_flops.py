"""FLOP and byte counts of ``bench/flops.py`` against counts worked by hand."""
import json
from pathlib import Path

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_deepfm_dense_step_flops():
    # MLP 390-400-400-400-1 at B=16384:
    #   sum(d_in*d_out) = 156000 + 160000 + 160000 + 400 = 476400
    #   6 * 16384 * 476400 = 46,832,025,600
    # FM forward 3*B*F*d + 3*B*d = 16384 * (1170 + 30) = 19,660,800
    # FM backward 3*B*F*d = 16384 * 1170 = 19,169,280
    # linear 6*B*F*d = 16384 * 2340 = 38,338,560
    want = 46_832_025_600 + 19_660_800 + 19_169_280 + 38_338_560
    assert want == 46_909_194_240
    assert flops.dense_step_flops(_cfg("deepfm-h24"), 16384) == want


def test_dcn_v2_dense_step_flops():
    # DCN-v2 as src/repro/configs/dcn_v2.py holds it: 26 fields at d=16,
    # 13 numeric features, 3 cross layers, MLP 1024-1024-512
    cfg = {"categorical_vocab": [1] * 26, "embed_dim": 16, "n_dense": 13,
           "interactions": [{"kind": "cross", "n_layers": 3}],
           "mlp_dims": [1024, 1024, 512]}
    # D = 26*16 + 13 = 429; cross: 3 * (6*B*D^2 + 6*B*D)
    #   = 3 * (6*16384*184041 + 6*16384*429) = 3 * (18,091,966,464 + 42,172,416)
    #   = 54,402,416,640
    # MLP 429-1024-1024-512-1: 439296 + 1048576 + 524288 + 512 = 2,012,672
    #   6 * 16384 * 2012672 = 197,853,708,288
    want = 54_402_416_640 + 197_853_708_288
    assert flops.dense_step_flops(cfg, 16384) == want


def test_fm_kernel_counts():
    # B=16384, F=39, d=10: B*F*d = 6,389,760
    assert flops.fm_fwd(16384, 39, 10) == {
        "flops": 3 * 6_389_760 + 3 * 163_840,           # 19,660,800
        "bytes": 4 * (6_389_760 + 16_384)}              # 25,624,576
    assert flops.fm_bwd(16384, 39, 10) == {
        "flops": 3 * 6_389_760,                         # 19,169,280
        "bytes": 4 * (2 * 6_389_760 + 16_384)}          # 51,183,616


def test_cross_layer_bwd_kernel_counts():
    # B=16384, D=429: B*D = 7,028,736, D^2 = 184,041
    w = flops.cross_layer_bwd(16384, 429)
    assert w["flops"] == 6 * 16384 * 184_041 + 5 * 7_028_736 == 18_127_110_144
    assert w["bytes"] == 4 * (5 * 7_028_736 + 2 * 184_041 + 2 * 429) == 142_050_480


def test_least_seconds_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds({"flops": 1000, "bytes": 5}, peak) == 10.0
    assert flops.least_seconds({"flops": 10, "bytes": 50}, peak) == 5.0
