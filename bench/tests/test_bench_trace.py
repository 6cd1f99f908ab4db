"""The trace reduction, on a hand-made trace and on a trimmed trace recorded
on the chip (``data/trace_v5e_trimmed.json``)."""
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def _hand_trace():
    host = [["window", 0, 100 * MS, ""],
            ["input_wait", 0, 5 * MS, ""],
            ["dispatch", 5 * MS, 10 * MS, ""],
            ["metrics_read", 15 * MS, 40 * MS, ""],
            ["input_wait", 60 * MS, 30 * MS, ""]]
    ops = [["fusion.1", 10 * MS, 30 * MS, "a"],
           ["fusion.2", 30 * MS, 20 * MS, "b"],              # overlaps fusion.1
           ["jit_fm_interaction_pallas.3", 70 * MS, 10 * MS, "pallas"],
           ["all-to-all.4", 95 * MS, 20 * MS, ""]]            # ends past the window
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops",
                                             "events": [["fusion.9", 0, 10 * MS, ""]]}]}]}


def test_hand_trace():
    red = tr.Reduction(_hand_trace())
    assert red.window_s == pytest.approx(0.1)
    # TPU:0 busy [10, 50] + [70, 80] + [95, 100] = 55 ms
    assert red.busy_s[0] == pytest.approx(0.055)
    assert red.busy_s[1] == pytest.approx(0.010)
    assert red.fullest == 0
    assert red.busy_mean_s == pytest.approx(0.0325)
    assert red.idle_share() == pytest.approx(0.45)
    assert red.op_s[0]["all-to-all.4"] == pytest.approx(0.005)
    # idle gaps [0,10] input_wait+dispatch -> dispatch 5 ms vs input_wait 5 ms:
    # the first span met wins a tie; [50,70] metrics_read 5 ms vs input_wait
    # 10 ms; [80,95] input_wait
    gaps = dict((round(s * 1e3), n) for n, s in red.gaps)
    assert gaps == {10: "input_wait", 20: "input_wait", 15: "input_wait"}
    assert [e[0] for e in red.matching("fm_interaction")] == ["jit_fm_interaction_pallas.3"]
    b = red.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.03)]
    assert len(b["idle_gaps"]) == 3


def test_no_window_or_no_device_is_refused():
    t = _hand_trace()
    t["planes"][0]["lines"][0]["events"] = t["planes"][0]["lines"][0]["events"][1:]
    with pytest.raises(ValueError):
        tr.Reduction(t)
    t = _hand_trace()
    t["planes"] = t["planes"][:1]
    with pytest.raises(ValueError):
        tr.Reduction(t)


def test_recorded_chip_trace():
    """1.5 s of a traced DeepFM window on a TPU v5 lite: busy time against a
    microsecond raster of the same events, and the kernels the roofline
    readers look for, found by their operation names."""
    import numpy as np
    t = json.loads((DATA / "trace_v5e_trimmed.json").read_text())
    red = tr.Reduction(t)
    assert red.window_s == pytest.approx(1.5)
    dev = [e for p in t["planes"] if p["name"] == "/device:TPU:0"
           for line in p["lines"] for e in line["events"]]
    t0 = red.t0
    raster = np.zeros(1_500_000, bool)
    for _, start, dur, _ in dev:
        a = max(0, (start - t0) // 1000)
        b = min(raster.size, -(-(start + dur - t0) // 1000))
        raster[a:b] = True
    assert red.busy_s[0] == pytest.approx(raster.sum() * 1e-6, abs=2e-3)
    assert 0 < red.idle_share() < 0.01
    assert sum(s for _, s in red.gaps) == pytest.approx(red.window_s - red.busy_s[0])
    assert [e[0] for e in red.matching(r"(?<!transpose_)jvp_jit_fm_interaction_pallas")] \
        == ["jvp_jit_fm_interaction_pallas__.2"]
    assert [e[0] for e in red.matching("fm_interaction_bwd_pallas")] \
        == ["transpose_jvp_jit_fm_interaction_bwd_pallas___.2"]
    # the tier probe's binary search is the longest operation (PERF.md section 5)
    assert red.breakdown()["device_ops"][0][0].startswith("while")
