"""The scoped reading of a trace (``bench/scopes.py``), on a hand-made trace
and on a trimmed trace recorded on the chip with the program's scopes
(``data/trace_v5e_scoped.json``), and the readers of the phases' device
time and the probe's roofline."""
import copy
import json
import types
from pathlib import Path

import pytest

from bench import flops, registry
from bench import scopes as sc
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def _scoped_trace():
    """A window with the program's scope paths on the device operations and
    the prefetcher's spans on the host: a ``while`` and its body both under
    ``tier_probe``."""
    host = [["window", 0, 100 * MS, ""],
            ["input_wait", 0, 5 * MS, ""],
            ["batch_wait", 1 * MS, 4 * MS, ""],
            ["dispatch", 5 * MS, 10 * MS, ""],
            ["metrics_read", 15 * MS, 40 * MS, ""],
            ["input_wait", 60 * MS, 30 * MS, ""]]
    producer = [["batch_make", 50 * MS, 15 * MS, ""],
                ["batch_put", 65 * MS, 3 * MS, ""]]
    ops = [["while.1", 10 * MS, 30 * MS, ""],
           ["fusion.2", 12 * MS, 26 * MS, ""],
           ["fusion.3", 40 * MS, 10 * MS, ""],
           ["fusion.6", 70 * MS, 5 * MS, ""],
           ["cond.4", 75 * MS, 5 * MS, ""],
           ["copy.5", 95 * MS, 20 * MS, ""]]               # ends past the window
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": host}]},
        {"name": "/host:program", "lines": [{"name": "spans", "events": producer}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]}],
        "scopes": {"while.1": "sparse_lookup/tier_probe",
                   "fusion.2": "sparse_lookup/tier_probe",
                   "fusion.3": "dense",
                   "fusion.6": "sparse_update/master_update",
                   "cond.4": "flush"}}


def test_scopes_on_a_hand_trace():
    red = sc.ScopedReduction(_scoped_trace())
    assert red.has_scopes
    # busy [10, 50] + [70, 80] + [95, 100] = 55 ms, as without scopes
    assert red.busy_s[0] == pytest.approx(0.055)
    assert red.idle_share() == pytest.approx(0.45)
    # the while [10, 40] and its body [12, 38] count once
    assert red.scope_s("tier_probe") == pytest.approx(0.030)
    assert red.scope_s("sparse_lookup") == pytest.approx(0.030)
    assert red.scope_s("dense") == pytest.approx(0.010)
    assert red.scope_s("sparse_update") == pytest.approx(0.005)
    assert red.scope_s("master_update") == pytest.approx(0.005)
    assert red.scope_s("flush") == pytest.approx(0.005)
    assert red.scope_s("shuffle") == 0.0
    assert red.unscoped_s == pytest.approx(0.005)            # copy.5 in [95, 100]
    b = red.breakdown()
    assert b["device_ops"][:2] == [["tier_probe:while.1", pytest.approx(0.030)],
                                   ["tier_probe:fusion.2", pytest.approx(0.026)]]
    assert ["copy.5", pytest.approx(0.005)] in b["device_ops"]


def test_scopes_leave_the_base_readings_as_they_are():
    t = _scoped_trace()
    red = sc.ScopedReduction(t)
    base = tr.Reduction(copy.deepcopy(t))
    assert red.busy_s == base.busy_s and red.op_s == base.op_s
    assert red.op_events == base.op_events and red.gaps == base.gaps
    assert red.window_s == base.window_s and red.idle_share() == base.idle_share()
    assert tr.Reduction.breakdown(red) == base.breakdown()


def test_gap_labels_join_harness_and_program_spans():
    """Each gap keeps its harness label, then the program span entered last
    among those open in it: [0, 10] input_wait (ties dispatch, first met)
    with batch_wait; [50, 70] input_wait with batch_put, entered after
    batch_make; [80, 95] input_wait with no program span."""
    red = sc.ScopedReduction(_scoped_trace())
    gaps = dict((round(s * 1e3), n) for n, s in red.labelled_gaps)
    assert gaps == {10: "input_wait:batch_wait", 20: "input_wait:batch_put",
                    15: "input_wait"}
    assert [s for _, s in red.labelled_gaps] == [s for _, s in red.gaps]


def _ctx(red, steps):
    return sc.Run(red, micro=1), types.SimpleNamespace(
        trace=red, steps=steps, n_steps=len(steps), chips=1, flops=flops,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e7})


@pytest.mark.parametrize("metric,want", [
    ("sparse_lookup_ms", 15.0),        # 30 ms over 2 steps
    ("sparse_update_ms", 2.5),         # 5 ms over 2 steps
    ("dense_fwd_bwd_ms", 5.0),         # 10 ms over 2 steps
    ("flush_device_ms", 5.0),          # 5 ms over 1 flush
    # 4,000 distinct ids x 9 B at 1e7 B/s = 3.6 ms, over the probe's 30 ms
    ("tier_probe_roofline", 12.0),
])
def test_scope_readers_on_a_hand_trace(metric, want, monkeypatch):
    steps = [{"flush": False}, {"flush": True}]
    read = registry.metric_reader(ROOT, metric)
    run, ctx = _ctx(sc.ScopedReduction(_scoped_trace()), steps)
    monkeypatch.setattr(sc, "of", lambda c: run)
    monkeypatch.setattr(sc, "distinct_rows", lambda c, micro: 4000)
    assert read(ctx) == pytest.approx(want)
    # a program that names no phases gives nothing to read
    monkeypatch.setattr(sc, "of", lambda c: None)
    assert read(ctx) is None


def test_tier_probe_least_bytes():
    # 168,000 distinct ids: 4 B key read + 4 B slot + 1 B hit flag each
    assert sc.tier_probe_least(168_000) == {"flops": 0, "bytes": 1_512_000}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(sc.tier_probe_least(168_000), peak) \
        == 1_512_000 / 819e9


def test_recorded_chip_trace_reads_as_the_harness_does():
    """0.99 s of a traced DeepFM window on a TPU v5 lite: the harness's own
    reduction of the same file reads the same busy time, idle share and
    time by operation."""
    t = json.loads((DATA / "trace_v5e_scoped.json").read_text())
    red = sc.ScopedReduction(t)
    base = tr.Reduction(t)
    assert red.window_s == pytest.approx(0.99)
    assert red.busy_s == base.busy_s and red.op_s == base.op_s
    assert red.busy_s[0] == pytest.approx(0.988404826, rel=1e-12)
    assert 0 < red.idle_share() < 0.01
    assert [e[0] for e in red.matching(r"(?<!transpose_)jvp_jit_fm_interaction_pallas")] \
        == ["jvp_jit_fm_interaction_pallas__.2"]
    assert [e[0] for e in red.matching("fm_interaction_bwd_pallas")] \
        == ["jvp_jit_fm_interaction_bwd_pallas__.2"]


def test_recorded_chip_trace_scopes():
    """On the chip's own names: the top-level scopes cover the busy time but
    for a few copies, the probe's ``while`` and its body count once, the
    probe holds its search and the stitch the hit rows' fetch, the longest
    operation is the probe's loop, and idle gaps carry the prefetcher's
    spans."""
    from repro import obs
    red = sc.ScopedReduction(json.loads((DATA / "trace_v5e_scoped.json").read_text()))
    assert red.has_scopes
    top = sum(red.scope_s(n) for n in obs.TOP_SCOPES)
    assert top + red.unscoped_s == pytest.approx(red.busy_s[0])
    assert top > 0.99 * red.busy_s[0]
    # fusion.316 is the body of while.21 (the compiled step's text): the
    # probe's scope holds the loop, once, and the hit check's key gather
    # (fusion.6); the hit rows' gather (fusion.9) is the stitch's
    loop, body = red.op_s[0]["while.21"], red.op_s[0]["fusion.316"]
    assert body == pytest.approx(loop, rel=0.01)
    assert red.scopes["fusion.6"] == "sparse_lookup/tier_probe"
    assert red.scopes["fusion.9"] == "sparse_lookup/stitch"
    assert loop + red.op_s[0]["fusion.6"] < red.scope_s("tier_probe") \
        < 1.06 * loop
    assert red.scope_s("sparse_lookup") > red.scope_s("tier_probe") > 0
    assert red.scope_s("dense") > 0 and red.scope_s("sparse_update") > 0
    assert red.breakdown()["device_ops"][0][0] == "tier_probe:while.21"
    assert any(":" in label for label, _ in red.labelled_gaps)
