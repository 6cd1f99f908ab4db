"""Multi-device correctness via subprocesses (the parent pytest process keeps
the default 1-device backend; children force 8 host devices)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_test_mesh
"""


def test_mp_lookup_8dev_exact():
    out = _run(HEADER + """
from repro.embedding.state import EmbeddingState
from repro.core import packed_embedding as pe
from repro.engine import PicassoStrategy
mesh = make_test_mesh(4, 2)
AXES=("data","model"); W, RPS, D, N = 8, 16, 5, 24
rng = np.random.default_rng(0)
table = jnp.asarray(rng.normal(size=(RPS*W, D)).astype(np.float32))
ids = jnp.asarray(rng.integers(0, RPS*W, size=(W, N)).astype(np.int32))
strat = PicassoStrategy(axes=AXES, world=W, capacity={0: N})
def f(tsh, ids_l):
    st = EmbeddingState(w=tsh, acc=jnp.zeros((RPS, 1)),
                        counts=jnp.zeros((RPS,), jnp.int32),
                        cache=pe.init_cache(0, D, RPS*W))
    rows_u, ctx = strat.lookup(st, 0, ids_l.reshape(-1))
    return jnp.take(rows_u, ctx.inv, axis=0).reshape(1, N, D)
got = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(AXES,None),P(AXES,None)),
                        out_specs=P(AXES,None,None), check_vma=False))(table, ids)
exp = np.asarray(table)[np.asarray(ids)]
print("MATCH", np.allclose(np.asarray(got), exp, atol=1e-6))
""")
    assert "MATCH True" in out


def test_train_converges_and_cache_kicks_in():
    out = _run(HEADER + """
from repro.configs import get_config
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.train.train_step import TrainConfig, init_state, make_train_step
mesh = make_test_mesh(4, 2); axes=("data","model"); GB=64
cfg = get_config("deepfm", smoke=True)
plan = make_plan(cfg, world=8, per_device_batch=8, hot_bytes=1<<14,
                 flush_iters=3, warmup_iters=2)
model = WDLModel(cfg, plan)
state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
step, _ = make_train_step(model, plan, mesh, axes, GB, TrainConfig())
rng = np.random.default_rng(0)
hits = []
for i in range(6):
    b = make_batch(cfg, GB, rng)
    b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
    state, m = step(state, b)
    hits.append(int(m["cache_hits"]))
    assert bool(jnp.isfinite(m["loss"]))
print("HITS_BEFORE", hits[0], "HITS_AFTER", hits[-1])
""")
    toks = out.split()
    assert int(toks[1]) == 0 and int(toks[3]) > 0  # cache warms up after flush


def test_strategy_parity_8dev():
    """All registry strategies are exact with the cache off and exact
    capacity: identical pooled outputs, loss trajectories, and post-update
    embedding tables on a 4x2 mesh (up to fp reassociation in the routed
    collectives). Includes the PR-6 decomposition baselines: 'mp_nodedup'
    (no K-Packed dedup — owner-side grad summation must recover the deduped
    math) and 'allgather_rows' (dedup'd replication)."""
    out = _run(HEADER + """
from repro.configs import get_config
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.train.train_step import TrainConfig, init_state, make_train_step
mesh = make_test_mesh(4, 2); axes=("data","model"); GB=32
cfg = get_config("dcn-v2", smoke=True)
BASELINES = ("hybrid", "ps", "mp_nodedup", "allgather_rows")
losses, tables = {}, {}
for strat in ("picasso",) + BASELINES:
    plan = make_plan(cfg, world=8, per_device_batch=4, enable_cache=False,
                     exact_capacity=True, n_micro=1)
    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
    step, _ = make_train_step(model, plan, mesh, axes, GB,
                              TrainConfig(strategy=strat, use_cache=False))
    rng = np.random.default_rng(1)
    ls = []
    for i in range(3):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        state, m = step(state, b)
        ls.append(float(m["loss"]))
    losses[strat] = ls
    tables[strat] = {k: np.asarray(jax.device_get(v.w))
                     for k, v in state["emb"].items()}
ldiff = max(abs(a-b) for base in BASELINES
            for a, b in zip(losses["picasso"], losses[base]))
wdiff = max(float(np.abs(tables["picasso"][k] - tables[base][k]).max())
            for base in BASELINES for k in tables["picasso"])
print("LDIFF", ldiff, "WDIFF", wdiff)
""")
    toks = out.split()
    assert float(toks[1]) < 1e-4 and float(toks[3]) < 1e-4


def test_overlap_parity_8dev():
    """The software-pipelined step (overlap='on') trains the identical loss
    trajectory as the synchronous step (overlap='off') on a 4x2 mesh with a
    real multi-chunk micro-batch pipeline and a warm hot tier — the handoff
    barriers only pin the schedule, never the values. Also pins that fp16
    routed-grad compression stays finite and fp16-close under overlap."""
    out = _run(HEADER + """
from repro.configs import get_config
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.train.train_step import TrainConfig, init_state, make_train_step
mesh = make_test_mesh(4, 2); axes=("data","model"); GB=64
cfg = get_config("deepfm", smoke=True)
plan = make_plan(cfg, world=8, per_device_batch=8, n_micro=2,
                 hot_bytes=1<<14, flush_iters=3, warmup_iters=2)
model = WDLModel(cfg, plan)
traj = {}
for mode, compress in (("off", "none"), ("on", "none"), ("on", "fp16")):
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
    step, _ = make_train_step(model, plan, mesh, axes, GB,
                              TrainConfig(overlap=mode, grad_compress=compress))
    rng = np.random.default_rng(0)
    ls = []
    for i in range(5):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        state, m = step(state, b)
        ls.append(float(m["loss"]))
    traj[(mode, compress)] = ls
exact = max(abs(a-b) for a, b in zip(traj[("off","none")], traj[("on","none")]))
comp = max(abs(a-b) for a, b in zip(traj[("on","none")], traj[("on","fp16")]))
finite = all(np.isfinite(traj[("on","fp16")]))
print("EXACT", exact, "COMP", comp, "FINITE", finite)
""")
    toks = out.split()
    assert float(toks[1]) == 0.0      # barriers are value-identity
    assert float(toks[3]) < 5e-2      # fp16 wire rounding only
    assert toks[5] == "True"


def test_cache_mode_is_exact():
    """HybridHash on (flush every step) == cache off: identical losses."""
    out = _run(HEADER + """
from repro.configs import get_config
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.train.train_step import TrainConfig, init_state, make_train_step
mesh = make_test_mesh(4, 2); axes=("data","model"); GB=32
cfg = get_config("deepfm", smoke=True)
traj = {}
for use_cache in (True, False):
    plan = make_plan(cfg, world=8, per_device_batch=4,
                     enable_cache=use_cache, exact_capacity=True,
                     hot_bytes=1<<14, flush_iters=1, warmup_iters=1, n_micro=1)
    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
    step, _ = make_train_step(model, plan, mesh, axes, GB,
                              TrainConfig(use_cache=use_cache))
    rng = np.random.default_rng(2)
    ls = []
    for i in range(5):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        state, m = step(state, b)
        ls.append(float(m["loss"]))
    traj[use_cache] = ls
print("DIFF", max(abs(a-b) for a,b in zip(traj[True], traj[False])))
""")
    diff = float(out.split()[-1])
    assert diff < 1e-3  # exact up to fp reassociation in the routed path


def test_fused_kernels_parity_8dev():
    """Fused Pallas sparse kernels == reference chains on a real 4x2 mesh
    (regression: interpret-mode prefetch-gather index maps combined with
    aliased ANY operands once mis-gathered on devices > 0 — the
    dedup kernel now pre-sorts its grads outside the kernel, and this test
    pins multi-device parity end to end, warm hot tier included)."""
    out = _run(HEADER + """
from repro.configs import get_config
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.train.train_step import TrainConfig, init_state, make_train_step
mesh = make_test_mesh(4, 2); axes=("data","model"); GB=64
cfg = get_config("deepfm", smoke=True)
plan = make_plan(cfg, world=8, per_device_batch=8, hot_bytes=1<<14,
                 l2_bytes=4096, flush_iters=3, warmup_iters=2)
model = WDLModel(cfg, plan)
traj = {}
for fused in (False, True):
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
    step, _ = make_train_step(model, plan, mesh, axes, GB,
                              TrainConfig(strategy="picasso_l2",
                                          use_fused_kernels=fused))
    rng = np.random.default_rng(0)
    ls, hits = [], 0
    for i in range(6):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        state, m = step(state, b)
        ls.append(float(m["loss"]))
        hits += int(m["cache_hits"])
    traj[fused] = (ls, hits)
ldiff = max(abs(a-b) for a, b in zip(traj[True][0], traj[False][0]))
print("LDIFF", ldiff, "HITS", traj[True][1], traj[False][1])
""")
    toks = out.split()
    assert float(toks[1]) < 1e-4          # fused == reference trajectories
    assert int(toks[3]) > 0 and int(toks[3]) == int(toks[4])


def test_elastic_reshard_parity_8dev():
    """Elastic parity on 8 host devices with all three PICASSO tiers in one
    mixed plan (picasso / picasso_l2 / picasso_narrow): train at world=8,
    reshard live to 4 and then 2 mid-run. The continued loss trajectory must
    be bit-identical to a fresh "process" that restores the world-4 host
    snapshot, rebuilds its own step, and replays the same batches through
    the same reshard sequence — and the final masters, slots, and counters
    must agree bitwise on every logical row."""
    out = _run(HEADER + """
from repro.configs.base import FeatureField, InteractionSpec, WDLConfig
from repro.core.assign import apply_assignment
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.launch.mesh import make_mesh
from repro.runtime import place_state, reshard_live
from repro.train.train_step import TrainConfig, init_state, make_train_step
axes = ("data", "model"); GB = 32
fields = (FeatureField("a", 1001, 8, max_len=2),
          FeatureField("b", 515, 16, max_len=1),
          FeatureField("c", 259, 4, max_len=3))
cfg = WDLConfig(name="elastic3", fields=fields, n_dense=0,
                interactions=(InteractionSpec("fm"),), mlp_dims=(16, 8))
MIX = {0: "picasso", 1: "picasso_l2", 2: "picasso_narrow"}
TCFG = TrainConfig(strategy="mixed")

def build(plan, mesh):
    model = WDLModel(cfg, plan)
    step, _ = make_train_step(model, plan, mesh, axes, GB, TCFG)
    return step

def seg(step, state, mesh, seed, n):
    rng = np.random.default_rng(seed)
    ls = []
    for _ in range(n):
        b = make_batch(cfg, GB, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        state, m = step(state, b)
        ls.append(float(m["loss"]))
    return state, ls

mesh8 = make_test_mesh(4, 2)
plan8 = make_plan(cfg, world=8, per_device_batch=GB // 8, hot_bytes=1 << 12,
                  l2_bytes=1 << 13, narrow_dim=4, flush_iters=2,
                  warmup_iters=1, mesh_shape=(4, 2))
apply_assignment(plan8, dict(MIX))
state = init_state(WDLModel(cfg, plan8), plan8, jax.random.PRNGKey(0),
                   mesh=mesh8, axes=axes)
state, _ = seg(build(plan8, mesh8), state, mesh8, seed=10, n=4)

# ---- live reshard 8 -> 4 and snapshot the migrated state to host --------
mesh4 = make_mesh((2, 2), axes)
plan4, state = reshard_live(plan8, state, 4, GB // 4, mesh=mesh4, axes=axes,
                            mesh_shape=(2, 2))
assert plan4.strategy == MIX, plan4.strategy
snap = jax.device_get(state)

# ---- continued run: 3 steps at 4, live reshard 4 -> 2, 3 steps at 2 -----
mesh2 = make_mesh((1, 2), axes)
state, ls_b = seg(build(plan4, mesh4), state, mesh4, seed=11, n=3)
plan2, state = reshard_live(plan4, state, 2, GB // 2, mesh=mesh2, axes=axes,
                            mesh_shape=(1, 2))
state, ls_c = seg(build(plan2, mesh2), state, mesh2, seed=12, n=3)

# ---- fresh "process": restore the snapshot, rebuild, replay -------------
fstate = place_state(snap, plan4, mesh4, axes)
fstate, fl_b = seg(build(plan4, mesh4), fstate, mesh4, seed=11, n=3)
fplan2, fstate = reshard_live(plan4, fstate, 2, GB // 2, mesh=mesh2,
                              axes=axes, mesh_shape=(1, 2))
fstate, fl_c = seg(build(fplan2, mesh2), fstate, mesh2, seed=12, n=3)

bitwise = (ls_b + ls_c) == (fl_b + fl_c)
wdiff = 0.0
for g in plan2.groups:
    a, b = state["emb"][str(g.gid)], fstate["emb"][str(g.gid)]
    n = max(g.table_offsets[t.name] + t.vocab for t in g.tables)
    for la, lb in ((a.w, b.w), (a.acc, b.acc), (a.counts, b.counts)):
        wdiff = max(wdiff, float(np.abs(np.asarray(la)[:n].astype(np.float64)
                                        - np.asarray(lb)[:n].astype(np.float64)).max()))
print("BITWISE", bitwise, "WDIFF", wdiff, "ROWS2",
      sum(g.rows for g in plan2.groups) % 2)
""", timeout=1200)
    toks = out.split()
    assert toks[1] == "True"            # loss trajectories bit-identical
    assert float(toks[3]) == 0.0        # masters/slots/counters bitwise
    assert int(toks[5]) == 0            # world-2 row cuts actually re-padded


def test_mini_dryrun_lowers_and_compiles():
    """Small-mesh dry-run: one cell per family lowers + compiles + reports
    roofline terms (the 512-device version runs in launch/dryrun.py)."""
    out = _run(HEADER + """
from pathlib import Path
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import run_cell
mesh = make_mesh((4,2), ("data","model"))
for arch, shape in [("dcn-v2","serve_p99"), ("yi-34b","decode_32k"),
                    ("schnet","minibatch_lg")]:
    rec = run_cell(arch, shape, False, Path("/tmp/repro_test_dryrun"),
                   mesh=mesh, smoke=True)
    print(arch, rec["ok"], rec.get("bound"), rec.get("error",""))
""", timeout=1200)
    lines = [l for l in out.splitlines() if l.strip()]
    for l in lines:
        assert " True " in l, l
