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


def test_dlrm_dcnv2_dense_step_flops():
    # MLPerf's DLRM-DCNv2: 26 fields at d=128, 13 numeric features through a
    # bottom MLP 512-256-128, 3 cross layers at rank 512, top MLP
    # 1024-1024-512-256-1, at B=4096 (bags change no dense width)
    cfg = {"categorical_vocab": [1] * 26, "multi_hot": [100] * 26, "embed_dim": 128,
           "n_dense": 13, "bottom_mlp": [512, 256, 128],
           "interactions": [{"kind": "cross", "n_layers": 3, "low_rank": 512}],
           "mlp_dims": [1024, 1024, 512, 256]}
    # bottom 13-512-256-128: 6656 + 131072 + 32768 = 170,496
    #   6 * 4096 * 170496 = 4,190,109,696
    # D = 26*128 + 128 = 3456 (the bottom MLP's output, not the 13 features)
    # B*D = 14,155,776; B*D*r = 7,247,757,312
    # cross 3 * (12*B*D*r + 6*B*D) = 3 * (86,973,087,744 + 84,934,656)
    #   = 261,174,067,200
    # top 3456-1024-1024-512-256-1: 3538944 + 1048576 + 524288 + 131072 + 256
    #   = 5,243,136; 6 * 4096 * 5243136 = 128,855,310,336
    want = 4_190_109_696 + 261_174_067_200 + 128_855_310_336
    assert want == 394_219_487_232
    assert flops.dense_step_flops(cfg, 4096) == want
    # full rank, the same widths: 3 * (6*B*D^2 + 6*B*D), about 3.4 times more
    full = dict(cfg, interactions=[{"kind": "cross", "n_layers": 3}])
    assert flops.dense_step_flops(full, 4096) - want == \
        3 * 6 * 4096 * 3456 * 3456 - 3 * 12 * 4096 * 3456 * 512


def test_cross_lowrank_kernel_counts():
    # B=4096, D=3456, r=512: B*D = 14,155,776, B*D*r = 7,247,757,312,
    # D*r = 1,769,472
    fwd = flops.cross_lowrank_fwd(4096, 3456, 512)
    assert fwd["flops"] == 4 * 7_247_757_312 + 3 * 14_155_776 == 29_033_496_576
    assert fwd["bytes"] == 4 * (3 * 14_155_776 + 2 * 1_769_472 + 3456) == 184_038_912
    bwd = flops.cross_lowrank_bwd(4096, 3456, 512)
    assert bwd["flops"] == 12 * 7_247_757_312 + 5 * 14_155_776 == 87_043_866_624
    assert bwd["bytes"] == 4 * (5 * 14_155_776 + 4 * 1_769_472 + 2 * 3456) == 311_454_720


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
