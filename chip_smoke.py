"""Smoke run of PICASSO training on a TPU, through ``repro.launch.train``.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: sharded vs one-device losses

One chip: the Pallas kernels that the dispatch table turns on are checked
against their jnp references at a small input, then DeepFM at its published
widths (39 fields, d=10, MLP 400-400-400, FM + linear) trains 24 steps at a
global batch of 16384 with ``--strategy picasso``, the hot-tier cache on and
``--fused-kernels auto``. Fields above ``CAP`` rows are cut to ``CAP`` (each
cut is printed) so the master tables fit one chip with headroom; no width
changes. The run must show finite losses, cache hits after the first
hot-tier flush (step 20), and interpret mode off.

``--chips 4``: the first 3 steps of the same run on a 4-device mesh, tables
row-sharded and ids routed by the all_to_all Shuffle, against the same
batches on a 1-device submesh in this process; the losses must agree to f32
tolerance. No other phase runs.

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CAP = 1 << 24       # rows per field: the capped master takes 6.2 GiB
BATCH = 16384
STEPS = 24          # hot tier: warm-up 10 steps, first flush at step 20
WARMUP = 2          # steps left out of the median step time
STEPS_4 = 3
RTOL_4 = 1e-4       # 4- vs 1-device losses: psum order differs, nothing else
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def deepfm_for_one_chip():
    """DeepFM with every field above ``CAP`` rows cut to ``CAP``."""
    from repro.configs import get_config
    cfg = get_config("deepfm")
    fields = []
    for f in cfg.fields:
        if f.vocab > CAP:
            log(f"reduce {f.name}: {f.vocab} -> {CAP} rows (ids drawn over "
                f"the cut vocabulary; width {f.dim} kept)")
            f = dataclasses.replace(f, vocab=CAP)
        fields.append(f)
    out = dataclasses.replace(cfg, fields=tuple(fields))
    log(f"deepfm: {len(out.fields)} fields, d={out.fields[0].dim}, mlp "
        f"{'-'.join(map(str, out.mlp_dims))}, rows "
        f"{sum(f.vocab for f in cfg.fields)} -> "
        f"{sum(f.vocab for f in out.fields)}")
    return out


def check_kernels():
    """Every kernel the table turns on, on the chip against its jnp
    reference at a small input. Kernels that multiply on the MXU get a
    bf16-pass tolerance: the references run at XLA's default precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (256, 39, 10), jnp.float32)
    x0 = jax.random.normal(keys[1], (256, 390), jnp.float32)
    w = jax.random.normal(keys[2], (390, 390), jnp.float32) / 20
    b = jnp.zeros((390,), jnp.float32)
    g = jax.random.normal(keys[3], (512, 10), jnp.float32)
    k = 3

    def grad_sum(fn, *args):
        return jax.grad(lambda *a: fn(*a).sum(), argnums=tuple(
            range(len(args))))(*args)

    cases = {  # op -> (kernel result, reference result, tolerance)
        "fm_interaction": (ops.fm_interaction(x), ref.fm_interaction_ref(x),
                           1e-5),
        "fm_interaction_bwd": (grad_sum(ops.fm_interaction, x),
                               grad_sum(ref.fm_interaction_ref, x), 1e-5),
        "dot_interaction": (ops.dot_interaction(x),
                            ref.dot_interaction_ref(x), 1e-2),
        "cross_layer_bwd": (grad_sum(ops.cross_layer, x0, x0, w, b),
                            grad_sum(ref.cross_layer_ref, x0, x0, w, b),
                            1e-2),
        "topk_compress": (ops.compress_topk(g, k), ref.topk_compress_ref(g, k),
                          0.0),
        "topk_decompress": (ops.decompress_topk(*ref.topk_compress_ref(g, k),
                                                10),
                            ref.topk_decompress_ref(
                                *ref.topk_compress_ref(g, k), 10), 0.0),
    }
    on = {op for op, v in ops.TPU_KERNELS.items() if v is True}
    if set(cases) != on:
        raise RuntimeError(f"kernel check covers {sorted(cases)}, the table "
                           f"turns on {sorted(on)}")
    for op, (got, want, tol) in cases.items():
        err = 0.0
        for a, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            a, e = np.asarray(a, np.float64), np.asarray(e, np.float64)
            if a.shape != e.shape or not np.isfinite(a).all():
                raise RuntimeError(f"{op}: shape {a.shape} vs {e.shape} or "
                                   "non-finite output")
            err = max(err, float(np.abs(a - e).max() / max(np.abs(e).max(),
                                                          1e-30)))
        log(f"kernel {op}: max error {err:.3g} of the reference's max "
            f"(limit {tol:g})")
        if err > tol:
            raise RuntimeError(f"{op} disagrees with its reference on the "
                               f"chip: {err:.3g} > {tol:g}")


def train(cfg, extra, compiles):
    """One ``repro.launch.train`` run; returns its history, checked."""
    import numpy as np

    from repro.launch import train as launcher
    argv = ["--arch", "deepfm", "--strategy", "picasso",
            "--fused-kernels", "auto", "--global-batch", str(BATCH),
            "--log-every", "1", "--seed", "0", *extra]
    log(f"train {' '.join(argv)}")
    n0 = len(compiles)
    t0 = time.perf_counter()
    rec = launcher.main(argv, cfg=cfg)
    wall = time.perf_counter() - t0
    hist = rec["history"]
    losses = [h["loss"] for h in hist]
    if not losses or not np.isfinite(losses).all():
        raise RuntimeError(f"non-finite or missing losses: {losses}")
    new = compiles[n0:]
    log(f"compile seconds {sum(s for _, s in new):.1f} over {len(new)} "
        f"programs (largest: " + ", ".join(
            f"{n} {s:.1f}s" for n, s in sorted(new, key=lambda c: -c[1])[:3])
        + f"); run wall seconds {wall:.1f}")
    log(f"losses {losses}")
    log(f"cache_hits {[h['cache_hits'] for h in hist]}")
    log(f"overflow {[h['overflow'] for h in hist]}")
    log(f"backup_served {rec['batches'].get('backup_served')} of "
        f"{rec['batches'].get('produced')} batches produced")
    if rec["batches"].get("backup_served"):
        raise RuntimeError("the batch producer fell behind: a step reused "
                           "a batch")
    return hist


def one_chip(compiles):
    import jax

    from repro.kernels import ops
    check_kernels()
    cfg = deepfm_for_one_chip()
    hist = train(cfg, ["--steps", str(STEPS), "--mesh", "1x1"], compiles)
    if len(hist) != STEPS:
        raise RuntimeError(f"{len(hist)} of {STEPS} steps logged")
    if max(h["cache_hits"] for h in hist) <= 0:
        raise RuntimeError("no hot-tier cache hits after the first flush")
    times = [h["seconds"] for h in hist[WARMUP:]]
    log(f"step seconds after {WARMUP} warm-up steps: median "
        f"{statistics.median(times):.6f}, min {min(times):.6f}, "
        f"max {max(times):.6f} (wall clock per step, host batch fetch "
        "included)")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")
    if ops.interpret_mode():
        raise RuntimeError("Pallas kernels ran in interpret mode")


def four_chips(compiles):
    import numpy as np
    cfg = deepfm_for_one_chip()
    steps = ["--steps", str(STEPS_4)]
    one = train(cfg, steps + ["--mesh", "1x1"], compiles)
    four = train(cfg, steps + ["--mesh", "4x1"], compiles)
    a = np.array([h["loss"] for h in four])
    e = np.array([h["loss"] for h in one])
    if a.shape != e.shape or a.shape != (STEPS_4,):
        raise RuntimeError(f"logged {a.shape} and {e.shape} losses")
    rel = float(np.max(np.abs(a - e) / np.abs(e)))
    log(f"4-device losses {a.tolist()}")
    log(f"1-device losses {e.tolist()}")
    log(f"max relative difference {rel:.3g} (limit {RTOL_4:g})")
    if rel > RTOL_4:
        raise RuntimeError("4-device and 1-device losses disagree")
    if any(h["overflow"] for h in one + four):
        raise RuntimeError("ids overflowed their Shuffle buckets")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
                 "nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devs)} devices")
    log(f"compile cache {enable_compile_cache()}")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append((kw.get("fun_name"), secs))
        if ev == COMPILE_EVENT else None)
    (four_chips if args.chips == 4 else one_chip)(compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
