"""One run of one cell: set-up, the measured window, the correctness check,
and the result line.

Set-up (``setup_s``, from process start): imports, the device check, the
compile cache, the plan and the jitted step (loaded from the persistent
compilation cache after a checkout's first run), the state made on the
device from the seed, and the first ``flush_iters + 1`` steps. The step
counter starts at ``flush_iters - 1``, so step 1 ends in a hot-tier flush
that loads the tier, the next steps read and update rows in the tier, and
the last set-up step flushes again with ~20 batches counted, as in a
deployment, writing the tier's rows back to the master; the window then
starts on the step right after a flush, so every run meets the flush at the
same phase. The reference follows all the set-up steps.

Window: whole flush periods (one flush in ``flush_iters`` steps), as many as
fit the asked seconds at the set-up's pace, at least one. The loop is
closed: step i+1 is dispatched as soon as step i is, and step i's counters
are read after that, so host reads do not hold the device. The window ends
on ``block_until_ready`` of the last step's state. Nothing may compile in it.

After the window: the peak device memory is read, the program's state is
freed, and the plain reference follows the set-up steps from the same seed;
the comparison decides ``correct``.
"""
from __future__ import annotations

import contextlib
import json
import math
import sys
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import correct as cmp
from bench import registry

COMPARED_STEPS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = ".bench_trace"


class NoChip(SystemExit):
    pass


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _host(x) -> float:
    return float(x)


def run(root: Path, cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        step_wrapper: Optional[Callable] = None,
        batch_fault: Optional[Callable] = None) -> Dict:
    bm = registry.load_benchmark(root)
    wl = registry.workload(bm, cell)
    cfg = registry.config(root, bm, wl["config"])
    mix = registry.traffic(root, wl["traffic"])
    ref = registry.reference(root, cfg)
    if int(cfg["chips"]) != int(wl["chips"]) or math.prod(cfg["mesh"]) != int(wl["chips"]):
        raise SystemExit(f"{cell}: configuration mesh {cfg['mesh']} does not "
                         f"match the cell's {wl['chips']} chips")

    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"bench: no TPU (JAX found {devs[0].platform}); "
                         "nothing was measured")
        if len(devs) < int(wl["chips"]):
            raise NoChip(f"bench: {cell} needs {wl['chips']} chips, JAX "
                         f"found {len(devs)}")
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs) if ev == COMPILE_EVENT else None)

    from bench import generator
    from bench import model as sutmod

    sut = sutmod.build(cfg, ref, step_wrapper)
    gb, fi = sut.global_batch, int(cfg["flush_iters"])
    log(f"{cell}: {len(sut.wdl.fields)} fields, {sum(f.vocab for f in sut.wdl.fields)} "
        f"rows, batch {gb}, mesh {tuple(cfg['mesh'])}, "
        f"{len(sut.plan.groups)} packed groups, micro {sut.plan.microbatch}")
    n_dense = cfg.get("n_dense", 0)
    n_setup = fi + 1
    state, source, prog, setup_metrics, period_s = first_steps(
        sut, seed, mix, batch_fault)
    t_ready = time.perf_counter()
    setup_overflow = sum(int(m["overflow"]) for m in setup_metrics)
    log("set-up cache_hits " + str([int(m["cache_hits"]) for m in setup_metrics]))
    del setup_metrics
    setup_s = t_ready - t_start
    n_compiles_setup = len(compiles)
    backup0 = source.stats["backup_served"]
    n_win = fi * max(1, round(seconds / period_s))
    log(f"set-up {setup_s:.3f} s ({n_compiles_setup} compiles); flush period "
        f"{period_s:.3f} s; window {n_win} steps")

    # ---- the measured window
    trace_dir = Path(root) / TRACE_DIR
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))
    steps: List[Dict] = []
    prev = None
    t_done_prev = t_ready
    with span("window"):
        t_begin = time.perf_counter()
        for i in range(n_win):
            t0 = time.perf_counter()
            with span("input_wait"):
                batch = next(source)
            t1 = time.perf_counter()
            with span("dispatch"):
                state, m = sut.step(state, batch)
            t2 = time.perf_counter()
            rec = {"input_wait_s": t1 - t0, "dispatch_s": t2 - t1}
            if prev is not None:
                with span("metrics_read"):
                    _read(prev)
                    prev["done_t"] = time.perf_counter()
            steps.append(rec)
            rec["_m"] = m
            prev = rec
        _read(prev)
        prev["done_t"] = time.perf_counter()
        jax.block_until_ready(state)
        t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_begin
    win_compiles = len(compiles) - n_compiles_setup
    failed = sum(1 for r in steps if r["overflow"] > 0)
    failed += source.stats["backup_served"] - backup0
    for k, r in enumerate(steps):
        # the step counter started at flush_iters - 1: window step k is the
        # program's step (fi - 1) + n_setup + k + 1, which flushes when it is
        # a multiple of flush_iters
        r["flush"] = ((fi - 1) + n_setup + k + 1) % fi == 0
        r["step_s"] = r["done_t"] - (steps[k - 1]["done_t"] if k else t_done_prev)
    dev_used = list(sut.mesh.devices.flat)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in dev_used)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(dev_used), "memory_peak_bytes": int(mem)}
    log(f"window {window_s:.3f} s, {n_win} steps, {win_compiles} compiles, "
        f"{failed} failed; peak {mem} bytes")

    # ---- free the program's state, then the reference
    source.close()
    del state, m, batch
    for r in steps:
        r.pop("_m", None)
    ref_batches = [generator.batch_at(mix, sut.field_pairs, n_dense, gb, seed, i)
                   for i in range(n_setup)]
    t_ref = time.perf_counter()
    want = ref.readings(cfg, seed, ref_batches)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    numbers = cmp.compare(prog, want)
    rows = cmp.checks(numbers, cfg["limits"],
                      {"window_compiles": (float(win_compiles), 0.0),
                       "setup_overflow": (float(setup_overflow), 0.0)})
    ok = cmp.passed(rows)

    result_metrics: Dict[str, Dict] = {}
    if not trace:
        vals = {"train_samples_per_s": n_win * gb / window_s,
                "peak_hbm_gib": mem / 2 ** 30,
                "setup_s": setup_s}
        for m in registry.end_to_end(bm, cell):
            result_metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    breakdown = None
    if trace:
        from bench import flops, peaks
        from bench import trace as tr
        red = tr.Reduction(tr.from_xplane(_xplane(trace_dir)))
        device["busy_s"] = red.busy_mean_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = types.SimpleNamespace(
            steps=steps, window_s=window_s, n_steps=n_win, global_batch=gb,
            chips=len(dev_used), cfg=cfg, trace=red, flops=flops,
            peak=peaks.peaks(devs[0].device_kind), mix=mix, seed=seed,
            first_window_batch=n_setup, field_pairs=sut.field_pairs,
            n_dense=n_dense)
        for m in registry.per_layer(bm, cell):
            v = registry.metric_reader(root, m["name"])(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": ok, "attempted": n_win, "failed": int(failed),
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, (v, at) in numbers.items():
        log(f"{n} {v!r} worst at {at}" + ("" if n in cfg["limits"] else " (not compared)"))
    for n, v, lim in rows:
        print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    return out


def first_steps(sut, seed: int, mix: Dict, batch_fault=None):
    """The program's state from the seed, its batch feed, and the set-up's
    ``flush_iters + 1`` steps through them, with what the check compares
    read out of the state as it goes: each step's loss, the first gradient
    after step 1, each leaf's change after step ``COMPARED_STEPS``, and each
    table's change in the master after the last step, a tier flush. Returns
    the state, the feed, the readings, the steps' metrics, and the seconds of
    a flush period at the pace of the steps after the compared ones."""
    import jax
    import jax.numpy as jnp

    from bench import generator, weights
    from bench import model as sutmod
    from repro.data.pipeline import device_put_stream
    from repro.dist.sharding import batch_specs

    fi = int(sut.cfg_json["flush_iters"])
    n_setup = fi + 1
    seed_w = weights.seed_words(seed)
    state = sutmod.init_state(sut, seed)
    grad_norms, change_norms, table_norms = sutmod.make_readers(sut)
    gen = generator.stream(mix, sut.field_pairs, sut.cfg_json.get("n_dense", 0),
                           sut.global_batch, seed)
    if batch_fault is not None:
        gen = map(batch_fault, gen)
    source = device_put_stream(gen, sut.mesh, lambda b: batch_specs(b, sut.axes))
    dense0 = jax.tree.map(jnp.copy, state["dense"])
    metrics, g1, ch = [], None, None
    for i in range(1, n_setup + 1):
        state, m = sut.step(state, next(source))
        metrics.append(m)
        if i == 1:
            g1 = grad_norms(state)
        if i == COMPARED_STEPS:
            ch = change_norms(state, dense0, seed_w)
            _host(m["loss"])
            t_paced = time.perf_counter()
    _host(metrics[-1]["loss"])
    jax.block_until_ready(state)
    period_s = (time.perf_counter() - t_paced) / (n_setup - COMPARED_STEPS) * fi
    tables = table_norms(state, seed_w)
    readings = {"loss": [_host(m["loss"]) for m in metrics[:COMPARED_STEPS]],
                "grad1": {k: _host(v) for k, v in g1.items()},
                "change": {k: _host(v) for k, v in ch.items()},
                "tables": {k: _host(v) for k, v in tables.items()}}
    return state, source, readings, metrics, period_s


def _read(rec: Dict):
    m = rec["_m"]
    rec["loss"] = _host(m["loss"])
    rec["cache_hits"] = int(m["cache_hits"])
    rec["overflow"] = int(m["overflow"])


def _xplane(d: Path) -> str:
    found = sorted(d.glob("**/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {d}")
    return str(found[-1])


def emit(result: Dict):
    print(json.dumps(result), flush=True)
