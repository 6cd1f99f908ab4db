"""Paper Fig. 15: scale-out 1..128 executors.

No real cluster here, so scaling is evaluated on the dry-run cost model: a
subprocess per world size lowers the deepfm train step on w emulated devices
and reports the roofline step time; near-flat step time with growing world ==
near-linear throughput scaling (IPS = global_batch / step)."""
import json
import os
import subprocess
import sys

from benchmarks.common import emit

SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={W}"
from pathlib import Path
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import run_cell
mesh = make_mesh((max({W}//2,1), min({W},2)), ("data","model"))
rec = run_cell("deepfm", "train_batch", False, Path("results/bench_scaling"),
               mesh=mesh, smoke=False, tag="_w{W}")
print(json.dumps({{"world": {W}, "step_s": rec.get("step_s"),
                   "bound": rec.get("bound"), "ok": rec.get("ok")}}))
"""


def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # emulated host devices: the children never need (and, once this process
    # has touched JAX, could not take) an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    for w in (1, 2, 8, 32, 128):
        out = subprocess.run([sys.executable, "-c", SCRIPT.replace("{W}", str(w))
                              .replace("{{", "@@").replace("}}", "%%")
                              .replace("@@", "{").replace("%%", "}")],
                             capture_output=True, text=True, env=env, timeout=1800)
        line = [l for l in out.stdout.splitlines() if l.startswith("{")]
        if not line:
            emit(f"scaling/world={w}", 0.0, f"error={out.stderr[-200:]}")
            continue
        rec = json.loads(line[-1])
        ips = 65536 / rec["step_s"] if rec.get("step_s") else 0
        emit(f"scaling/world={w}", rec.get("step_s", 0) * 1e6,
             f"ips_model={ips:.0f};bound={rec.get('bound')}")


if __name__ == "__main__":
    run()
