import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the device
# count on first init). REPRO_XLA_FLAGS lets tests shrink the device count.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402

from repro.configs.base import get_shapes, list_archs, skipped_shapes  # noqa: E402
from repro.launch.cells import build_cell                              # noqa: E402
from repro.launch.mesh import make_production_mesh, make_mesh          # noqa: E402
from repro.launch.roofline import collective_bytes, count_ops, roofline_terms  # noqa: E402


def _measure(compiled, world: int) -> dict:
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    wire, per_op = collective_bytes(hlo, world)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": wire, "per_op": per_op, "hlo": hlo}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             mesh=None, smoke: bool = False, tag: str = "", plan_kw=None,
             save_hlo: bool = False, cell_kw=None) -> dict:
    from repro.launch.cells import arch_kind
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    world = int(mesh.devices.size)
    shape = next(s for s in get_shapes(arch, include_skipped=True) if s.name == shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": "x".join(map(str, mesh.devices.shape)),
           "world": world, "multi_pod": multi_pod, "tag": tag}
    t0 = time.time()
    try:
        kw = dict(cell_kw or {})
        if plan_kw and shape.kind in ("train", "serve", "retrieval"):
            kw["plan_kw"] = plan_kw
        cell = build_cell(arch, shape, mesh, smoke=smoke, **kw)
        lowered = cell.fn.lower(*cell.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not support it
            rec["memory"] = {"error": str(e)}

        m = _measure(compiled, world)
        flops, byts, wire, per_op = m["flops"], m["bytes"], m["wire"], m["per_op"]

        # XLA cost_analysis counts a while-loop body ONCE (it does NOT unroll
        # even trip-2 scans — verified empirically), so the LM layer stack's
        # true cost is reconstructed from two *unrolled* compiles at L=2 and
        # L=4: body = (u4-u2)/2, total = u2 + (L-2)*body. Exact for a
        # linear-in-L program. The full scanned compile above remains the
        # memory/compile-proof artifact.
        full_cfg = None
        if arch_kind(arch) == "lm" and not smoke:
            from repro.configs.base import get_config
            full_cfg = get_config(arch)
        if full_cfg is not None and full_cfg.n_layers > 4:
            ms = {}
            ckw = dict(kw)
            ckw["lm_kw"] = {**(kw.get("lm_kw") or {}), "unroll": True}
            for l_ov in (2, 4):
                c2 = build_cell(arch, shape, mesh, smoke=smoke,
                                n_layers_override=l_ov, **ckw)
                ms[l_ov] = _measure(c2.fn.lower(*c2.args).compile(), world)
            L = full_cfg.n_layers
            scale = (L - 2) / 2.0

            def extrap(key):
                return ms[2][key] + scale * (ms[4][key] - ms[2][key])

            rec["loop_corrected"] = True
            rec["uncorrected"] = {"flops": flops, "bytes": byts, "wire": wire}
            flops, byts, wire = extrap("flops"), extrap("bytes"), extrap("wire")
            per_op = {k: {kk: (ms[2]["per_op"].get(k, {}).get(kk, 0)
                              + scale * (ms[4]["per_op"].get(k, {}).get(kk, 0)
                                         - ms[2]["per_op"].get(k, {}).get(kk, 0)))
                          for kk in ("count", "bytes", "wire")}
                      for k in set(ms[2]["per_op"]) | set(ms[4]["per_op"])}

        rec["hlo_flops"] = flops
        rec["hlo_bytes"] = byts
        rec["collective_wire_bytes"] = wire
        rec["collectives"] = per_op
        rec["ops"] = {k: v for k, v in sorted(count_ops(m["hlo"]).items(),
                                              key=lambda kv: -kv[1])[:25]}
        rec.update(roofline_terms(flops, byts, wire))
        rec["model_flops"] = cell.model_flops / world  # per device
        rec["useful_ratio"] = (cell.model_flops / world / flops) if flops else None
        rec["note"] = cell.note
        rec["ok"] = True
        if save_hlo:
            (out_dir / f"{arch}__{shape_name}__{rec['mesh']}{tag}.hlo.txt").write_text(m["hlo"])
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh']}{tag}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower+compile every cell")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true", help="reduced configs (CI)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    archs = list_archs() if args.arch == "all" else [args.arch]
    for arch in archs:
        shapes = get_shapes(arch)
        skipped = dict(skipped_shapes(arch))
        names = [s.name for s in shapes] if args.shape == "all" else [args.shape]
        for sn in names:
            if sn in skipped:
                print(f"[skip] {arch} x {sn}: {skipped[sn][:80]}...")
                continue
            rec = run_cell(arch, sn, args.multi_pod, out, smoke=args.smoke,
                           tag=args.tag, save_hlo=args.save_hlo)
            status = "OK " if rec.get("ok") else "FAIL"
            print(f"[{status}] {arch} x {sn} ({rec['mesh']}): "
                  f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s "
                  f"bound={rec.get('bound')} step={rec.get('step_s', 0):.2e}s "
                  f"{rec.get('error', '')}", flush=True)


if __name__ == "__main__":
    main()
