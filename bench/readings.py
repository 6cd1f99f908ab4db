"""Readings that set a cell's correctness limits; not run by the benchmark.

    python3 bench/readings.py --workload deepfm-h24.zipf --seeds 11 12 13

For each seed, in one process on the chip at the cell's own size, each kind
against the reference: ``program``, the program's set-up steps through the
window's step and feed (the lower readings); ``control``, the reference in
bfloat16 put in the program's place; ``default_precision``, the reference in
float32 with its matmuls at JAX's default precision (one bfloat16 pass on
the TPU) put in the program's place, the control for a configuration that
states ``matmul_precision: "highest"``; ``half_batch``, the reference with half
of each batch left out, the mean taken over the rest; ``no_write_back``, the
program with its tier flushes leaving the master as it was. A step that
returns its state unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
their definition and needs no run. Prints one JSON line per seed and kind,
with each number held against the configuration's limits and ``correct``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
KINDS = ("program", "control", "default_precision", "half_batch", "no_write_back")
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kinds", nargs="+", default=list(KINDS), choices=KINDS)
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import jax.numpy as jnp

    from bench import correct as cmp
    from bench import faults, generator, harness, registry
    from bench import model as sutmod
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("readings: no TPU; nothing was read")
    enable_compile_cache()
    bm = registry.load_benchmark(ROOT)
    wl = registry.workload(bm, args.workload)
    cfg = registry.config(ROOT, bm, wl["config"])
    mix = registry.traffic(ROOT, wl["traffic"])
    ref = registry.reference(ROOT, cfg)
    programs = {"program", "no_write_back"} & set(args.kinds)
    sut = sutmod.build(cfg, ref) if programs else None
    n_dense = cfg.get("n_dense", 0)
    fields = ref.fields(cfg)
    n_setup = int(cfg["flush_iters"]) + 1
    for seed in args.seeds:
        batches = [generator.batch_at(mix, fields, n_dense, cfg["global_batch"], seed, i)
                   for i in range(n_setup)]
        cands = {}
        for kind in sorted(programs):
            run = sut if kind == "program" else dataclasses.replace(
                sut, step=faults.no_write_back(int(cfg["flush_iters"]))(sut.step))
            state, source, cands[kind], _, _ = harness.first_steps(run, seed, mix)
            source.close()
            del state
        want = ref.readings(cfg, seed, batches)
        if "control" in args.kinds:
            cands["control"] = ref.readings(cfg, seed, batches, dtype=jnp.bfloat16)
        if "default_precision" in args.kinds:
            cands["default_precision"] = ref.readings(cfg, seed, batches,
                                                      precision="default")
        if "half_batch" in args.kinds:
            cands["half_batch"] = ref.readings(
                cfg, seed, [faults.half_batch(b) for b in batches])
        for kind, c in cands.items():
            nums = cmp.compare(c, want)
            rows = cmp.checks(nums, cfg["limits"])
            print(json.dumps({"seed": seed, "kind": kind, "correct": cmp.passed(rows),
                              **{k: v for k, (v, _) in nums.items()},
                              "limits": {n: lim for n, _, lim in rows},
                              "at": {k: a for k, (_, a) in nums.items()},
                              "loss": c["loss"], "ref_loss": want["loss"]}),
                  flush=True)
    print(f"readings: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)


if __name__ == "__main__":
    main()
