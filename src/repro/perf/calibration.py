"""Calibration pass: microbench the priced ops, fit curves, cache to disk.

The same ops ``benchmarks/bench_kernels`` times in isolation are timed here
across a small size grid — through the **production dispatchers**
(``repro.kernels.ops`` with the default ``fused='auto'`` resolution), so the
curves price what the engine actually executes on this backend: real Pallas
kernels on TPU, the jnp reference chains on the CPU rig, the interpreter
only under the soak env var (and the calibration file is stamped with that,
so an interpreter-calibrated model is never silently reused on silicon).

Lifecycle (``get_cost_model`` — the single launcher entry point):

``off``   -> ``None``: ``repro.core.assign`` keeps its constant model,
             byte-for-byte today's behavior.
``auto``  -> load ``--calib-file`` if it exists and its backend stamp
             (backend name + interpret flag + format version) matches this
             process; otherwise run the microbenches and write the file.
``force`` -> always re-bench and overwrite the file.

The file keeps the raw ``(work, us)`` samples next to the fitted curves, so
``benchmarks/bench_calibrate`` can report measured-vs-predicted residuals
per op without re-benching.
"""
from __future__ import annotations

import json
import os
import pathlib
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro.perf.cost_model import PRICED_OPS, CostCurve, CostModel

CALIB_VERSION = 1
DEFAULT_CALIB_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "calibration.json")

# size grids: 'small' is the startup default (a few hundred ms of benching),
# 'tiny' is the smoke/CI grid. ns = ids per call, ds = row dims,
# wire_kb = per-shard payloads, mm = square-matmul sides.
GRIDS: Dict[str, Dict[str, Any]] = {
    "tiny": dict(ns=(32, 128), ds=(8,), wire_kb=(4, 32), mm=(16, 48),
                 iters=1, warmup=1),
    "small": dict(ns=(64, 256, 1024), ds=(8, 32), wire_kb=(4, 64, 512),
                  mm=(32, 64, 128), iters=3, warmup=1),
}

Samples = Dict[str, List[Tuple[float, float]]]


def backend_stamp() -> Dict[str, Any]:
    """What a calibration is valid for: re-fit when any of this changes."""
    import jax

    from repro.kernels import ops

    return {"version": CALIB_VERSION,
            "backend": str(jax.default_backend()),
            "interpret": bool(ops.interpret_mode())}


def _time(fn, *args, iters: int, warmup: int) -> float:
    import jax

    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


# ---------------------------------------------------------------------------
# per-op microbenches (production dispatchers, fused='auto')
# ---------------------------------------------------------------------------


def _bench_gather_pool(n: int, d: int, it: Mapping[str, int]) -> float:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    n_bags = max(4, n // 8)
    rows_u = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    inv = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    w = jnp.asarray(rng.normal(size=n).astype(np.float32))
    seg = np.sort(np.concatenate(
        [np.arange(n_bags), rng.integers(0, n_bags, n - n_bags)]))
    seg = jnp.asarray(seg.astype(np.int32))
    fn = jax.jit(lambda r: ops.gather_pool(r, inv, w, seg, n_bags))
    return _time(fn, rows_u, **it)


def _bench_dedup_adagrad(n: int, d: int, it: Mapping[str, int]) -> float:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(1)
    rows, hot = 4 * n, max(8, n // 8)
    w = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    acc = jnp.asarray(np.abs(rng.normal(size=(rows, 1))).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, hot, n).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    fn = jax.jit(lambda w_, a_: ops.dedup_adagrad(w_, a_, idx, g, valid,
                                                  0.05, 1e-8))
    return _time(fn, w, acc, **it)


def _bench_tier_probe(n: int, d: int, it: Mapping[str, int]) -> float:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(2)
    h = max(8, n // 2)
    keys = jnp.asarray(np.sort(rng.choice(10 * h, h, replace=False))
                       .astype(np.int32))
    rows = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    uniq = jnp.sort(jnp.asarray(rng.integers(0, 10 * h, n).astype(np.int32)))
    uvalid = jnp.asarray(np.arange(n) < int(0.9 * n))
    fn = jax.jit(lambda u: ops.tier_probe(u, uvalid, keys, rows))
    return _time(fn, uniq, **it)


def _bench_gather_project(n: int, d: int, it: Mapping[str, int]) -> float:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(3)
    nd = max(4, d // 4)
    back = jnp.asarray(rng.normal(size=(n, nd)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    kept = jnp.asarray(rng.random(n) < 0.9)
    proj = jnp.asarray(rng.normal(size=(nd, d)).astype(np.float32))
    fn = jax.jit(lambda b, p: ops.gather_project(b, idx, kept, p))
    return _time(fn, back, proj, **it)


def _wire_mesh():
    """1-D mesh over every local device: the wire curves measure the real
    collective fabric of this process (a single-device mesh degenerates to
    the local-copy cost, which is the honest world=1 wire price)."""
    import jax

    from repro.launch.mesh import make_mesh

    return make_mesh((len(jax.devices()),), ("wire",))


def _bench_wire(kind: str, per_shard_kb: int, mesh,
                it: Mapping[str, int]) -> Tuple[float, float]:
    """Returns (bytes_on_wire_per_shard, us) for one collective payload."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    world = int(mesh.devices.size)
    m = max(1, (per_shard_kb * 1024 // 4) // max(world, 1))
    if kind == "wire_a2a":
        # global [world*world, m] -> local [world, m]; all_to_all moves
        # ~world*m rows per shard
        x = jnp.zeros((world * world, m), jnp.float32)

        def local(y):
            return jax.lax.all_to_all(y, "wire", 0, 0)
    else:
        # global [world, m] -> local [1, m]; all_gather replicates world*m
        x = jnp.zeros((world, m), jnp.float32)

        def local(y):
            return jax.lax.all_gather(y, "wire", axis=0, tiled=True)

    x = jax.device_put(x, NamedSharding(mesh, P("wire", None)))
    f = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("wire", None),
                              out_specs=P("wire", None) if kind == "wire_a2a"
                              else P(None, None), check_vma=False))
    us = _time(f, x, **it)
    return float(world * m * 4), us


def _bench_matmul(k: int, it: Mapping[str, int]) -> float:
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(k, k)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(k, k)).astype(np.float32))
    fn = jax.jit(lambda a_, b_: a_ @ b_)
    return _time(fn, a, b, **it)


def run_calibration(grid: str = "small",
                    log: Optional[Callable[[str], None]] = None) -> Samples:
    """Run the microbench grid; returns per-op raw ``(work, us)`` samples."""
    if grid not in GRIDS:
        raise ValueError(f"unknown calibration grid {grid!r}; "
                         f"options: {sorted(GRIDS)}")
    g = GRIDS[grid]
    it = {"iters": g["iters"], "warmup": g["warmup"]}
    t0 = time.perf_counter()
    samples: Samples = {op: [] for op in PRICED_OPS}
    sparse = {"gather_pool": _bench_gather_pool,
              "dedup_adagrad": _bench_dedup_adagrad,
              "tier_probe": _bench_tier_probe,
              "gather_project": _bench_gather_project}
    for op, bench in sparse.items():
        for n in g["ns"]:
            for d in g["ds"]:
                samples[op].append((float(n * d), bench(n, d, it)))
    mesh = _wire_mesh()
    for kind in ("wire_a2a", "wire_ag"):
        for kb in g["wire_kb"]:
            samples[kind].append(_bench_wire(kind, kb, mesh, it))
    for k in g["mm"]:
        samples["dense_matmul"].append((float(k) ** 3, _bench_matmul(k, it)))
    if log:
        n_pts = sum(len(v) for v in samples.values())
        log(f"calibrated {len(samples)} ops / {n_pts} grid points "
            f"(grid={grid}) in {time.perf_counter() - t0:.1f}s")
    return samples


def fit_cost_model(samples: Samples, *,
                   hit_prior: Optional[float] = None) -> CostModel:
    """Fit the monotone curves and stamp the model for this backend."""
    stamp = backend_stamp()
    kw = {} if hit_prior is None else {"hit_prior": float(hit_prior)}
    return CostModel(
        curves={op: CostCurve.fit(pts) for op, pts in samples.items()},
        backend=stamp["backend"], interpret=stamp["interpret"],
        meta={"version": stamp["version"]}, **kw)


# ---------------------------------------------------------------------------
# cache file
# ---------------------------------------------------------------------------


def save_calibration(path: os.PathLike, samples: Samples,
                     model: CostModel) -> pathlib.Path:
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    payload = {**backend_stamp(), **model.to_json(),
               "samples": {op: [[float(x), float(y)] for x, y in pts]
                           for op, pts in samples.items()}}
    tmp = p.with_suffix(p.suffix + ".tmp")
    tmp.write_text(json.dumps(payload, indent=1) + "\n")
    tmp.replace(p)  # atomic: a concurrent 'auto' load never sees a torn file
    return p


def load_calibration(path: os.PathLike,
                     log: Optional[Callable[[str], None]] = None
                     ) -> Optional[CostModel]:
    """Load a cached calibration; ``None`` when missing, corrupt, or stamped
    for a different backend/interpret-mode/format (a mismatch must force a
    refit — interpreter curves reused on silicon would mis-rank every op)."""
    p = pathlib.Path(path)
    if not p.exists():
        return None
    try:
        data = json.loads(p.read_text())
    except (json.JSONDecodeError, OSError):
        if log:
            log(f"calibration file {p} unreadable; re-calibrating")
        return None
    stamp = backend_stamp()
    got = {k: data.get(k) for k in stamp}
    if got != stamp:
        if log:
            log(f"calibration stamp mismatch at {p} (file {got}, "
                f"process {stamp}); re-calibrating")
        return None
    try:
        model = CostModel.from_json(data)
    except (KeyError, ValueError, TypeError) as e:
        if log:
            log(f"calibration file {p} invalid ({e}); re-calibrating")
        return None
    return model


def load_samples(path: os.PathLike) -> Optional[Samples]:
    """Raw grid points persisted next to the fit (for residual reporting)."""
    p = pathlib.Path(path)
    if not p.exists():
        return None
    try:
        data = json.loads(p.read_text())
        return {op: [(float(x), float(y)) for x, y in pts]
                for op, pts in data.get("samples", {}).items()}
    except (json.JSONDecodeError, OSError, ValueError, TypeError):
        return None


def get_cost_model(mode: str, path: Optional[os.PathLike] = None, *,
                   grid: str = "small",
                   log: Optional[Callable[[str], None]] = None
                   ) -> Optional[CostModel]:
    """Launcher entry point for ``--calibrate {auto,force,off}``.

    ``off`` returns ``None`` (the constant model). ``auto`` loads the cached,
    backend-stamped file when valid, else benches and writes it. ``force``
    always re-benches. ``path=None`` uses ``DEFAULT_CALIB_PATH``.
    """
    if mode == "off":
        return None
    if mode not in ("auto", "force"):
        raise ValueError(f"--calibrate must be auto/force/off, got {mode!r}")
    p = pathlib.Path(path) if path else pathlib.Path(DEFAULT_CALIB_PATH)
    if mode == "auto":
        model = load_calibration(p, log=log)
        if model is not None:
            if log:
                log(f"loaded calibration from {p} "
                    f"(backend={model.backend}, interpret={model.interpret})")
            return model
    samples = run_calibration(grid, log=log)
    model = fit_cost_model(samples)
    save_calibration(p, samples, model)
    if log:
        log(f"wrote calibration to {p} (backend={model.backend})")
    return model
