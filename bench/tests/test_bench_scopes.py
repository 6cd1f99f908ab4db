"""The program's named phases and its ``distinct_ids`` counter, on the CPU:
the compiled benchmark step carries the scope names in its HLO ``op_name``
metadata where ``bench/scopes.py`` looks for them, and the counter counts
the distinct ids each chip's lookups worked on."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import scopes as sc

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SEED = 3_000_000_021


def _ops_by_opcode(text, opcode):
    """Names of the instructions of ``opcode`` in an HLO module's text."""
    return [m.group(1) for m in
            (sc._INSTR.match(line) for line in text.splitlines()
             if f" {opcode}(" in line) if m]


@pytest.fixture(scope="module")
def step_hlo():
    """The tiny DeepFM benchmark step, compiled, and its scope map."""
    import jax

    from bench import generator, registry
    from bench import model as sutmod
    from repro.dist.sharding import batch_specs, to_named
    cfg = json.loads((DATA / "tiny-deepfm.json").read_text())
    sut = sutmod.build(cfg, registry.reference(ROOT, cfg))
    state = sutmod.init_state(sut, SEED)
    b = generator.batch_at(registry.traffic(ROOT, "zipf"), sut.fields, 0,
                           sut.global_batch, SEED, 0)
    b = jax.device_put(b, to_named(sut.mesh, batch_specs(b, sut.axes)))
    text = sut.step.lower(state, b).compile().as_text()
    return sut, text, sc.scopes_of_hlo(text)


def _under(path, scope):
    """Whether scope path ``path`` is ``scope`` or one of its sub-scopes."""
    return path == scope or path.startswith(scope + "/")


def test_step_names_every_phase(step_hlo):
    """Every phase a per-layer reader reads has a scope in the compiled
    step: the four top-level phases and the tier probe."""
    from repro import obs
    _, _, scopes = step_hlo
    named = {p for path in scopes.values() for p in path.split("/")}
    for name in (obs.SPARSE_LOOKUP, obs.DENSE, obs.SPARSE_UPDATE, obs.FLUSH,
                 obs.TIER_PROBE):
        assert name in named, name
    # every path starts at a top-level scope
    assert {path.split("/")[0] for path in scopes.values()} <= set(obs.TOP_SCOPES)


def test_indexed_probe_has_no_loop_and_flush_maps_to_its_scope(step_hlo):
    """The L1 tier carries its rank index, so ``tier_probe`` holds no
    ``while``; every ``while`` left in the step lies inside some scope, so
    its time is read under a phase; the step's flush ``conditional`` and
    the operations of its flush branch are under ``flush``."""
    import jax

    from bench import model as sutmod
    from bench import weights
    sut, text, scopes = step_hlo
    state = jax.eval_shape(sutmod.state_fn(sut), weights.seed_words(SEED))
    assert all(st.cache.index is not None for st in state["emb"].values())
    loops = [scopes.get(n, "") for n in _ops_by_opcode(text, "while")]
    assert not any(p.endswith("/tier_probe") for p in loops), loops
    assert all(loops), loops
    conds = _ops_by_opcode(text, "conditional")
    assert conds and all(_under(scopes.get(n, ""), "flush") for n in conds)
    assert sum(1 for p in scopes.values() if _under(p, "flush")) > len(conds)


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_probe_holds_the_search_and_stitch_the_hit_rows(monkeypatch, fused):
    """Where the tier-probe kernel does not run (on the TPU, see
    ``TPU_KERNELS``), ``tier_probe`` holds the L1 and L2 searches alone, with
    the fused flag on or off: every gather of 16-wide rows (the routed-back
    rows and both tiers' hit rows) is the stitch's."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import packed_embedding as pe
    from repro.kernels import ops
    from repro.launch.mesh import make_test_mesh
    monkeypatch.setattr(ops, "runs_kernel", lambda op, fused=None: False)
    axes = ("data", "model")

    def lookup(table, ids, hk, hr, l2k, l2r):
        return pe.mp_lookup(table, ids, axes=axes, world=1, capacity=40,
                            hot_keys=hk, hot_rows=hr, l2_keys=l2k,
                            l2_rows=l2r, fused=fused)[0]

    fn = jax.jit(shard_map(lookup, mesh=make_test_mesh(1, 1),
                           in_specs=(P(axes, None),) + (P(),) * 5,
                           out_specs=P(), check_vma=False))
    text = fn.lower(jnp.zeros((64, 16)), jnp.arange(40, dtype=jnp.int32),
                    jnp.arange(0, 64, 4, dtype=jnp.int32), jnp.ones((16, 16)),
                    jnp.arange(1, 64, 4, dtype=jnp.int32),
                    jnp.ones((16, 16))).compile().as_text()
    scopes = sc.scopes_of_hlo(text)
    rows = [scopes.get(m.group(1)) for m in map(sc._INSTR.match,
                                                 text.splitlines())
            if m and " = f32[40,1,16]{2,1,0} gather(" in m.string]
    assert rows.count("tier_probe") == 0
    assert rows.count("stitch") >= 3
    assert "tier_probe" in scopes.values()


def test_host_flush_is_named_too():
    """``make_flush_fn``'s program (the flush outside the step) carries the
    same scope: every operation with a scope is under ``flush``."""
    import jax

    from bench import registry
    from bench import model as sutmod
    from repro.train.train_step import make_flush_fn
    cfg = json.loads((DATA / "tiny-deepfm.json").read_text())
    sut = sutmod.build(cfg, registry.reference(ROOT, cfg))
    flush = make_flush_fn(sut.plan, sut.mesh, sut.axes, strategy="picasso")
    shapes = jax.eval_shape(lambda: sutmod.init_state(sut, SEED))
    scopes = sc.scopes_of_hlo(flush.lower(shapes).compile().as_text())
    assert scopes and all(_under(p, "flush") for p in scopes.values())


def test_scope_path_reads_op_names():
    assert sc.scope_path("jit(wrapped)/sparse_lookup/tier_probe/"
                         "jit(searchsorted)/vmap()/while") == "sparse_lookup/tier_probe"
    # a primitive that shares a scope's name is not a scope
    assert sc.scope_path("jit(wrapped)/sparse_lookup/tier_probe/gather") \
        == "sparse_lookup/tier_probe"
    assert sc.scope_path("jit(wrapped)/sparse_lookup/gather/gather") \
        == "sparse_lookup/gather"
    assert sc.scope_path("jit(wrapped)/dense/transpose(jvp(dense))/dot_general") == "dense"
    assert sc.scope_path("jit(wrapped)/flush/cond/branch_1_fun/flush/top_k") == "flush"
    assert sc.scope_path("jit(wrapped)/add") == ""
    # merged by the compiler from two places: the first one's
    assert sc.scope_path("jit(wrapped)/dense/jvp()/reshape;"
                         "jit(wrapped)/sparse_lookup/reshape") == "dense"
    text = ('  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata='
            '{op_name="jit(f)/sparse_lookup/tier_probe/while" source_file="x.py"}\n'
            '  ROOT %fusion.7 = f32[2]{0} fusion(%p), kind=kLoop, metadata='
            '{op_name="jit(f)/state[\\\'dense\\\']/step_misc/add"}\n'
            '  %copy.1 = f32[2]{0} copy(%p)\n')
    assert sc.scopes_of_hlo(text) == {"while.3": "sparse_lookup/tier_probe",
                                      "fusion.7": "step_misc"}


CHILD = r"""
import json, sys
root, cfg_name = sys.argv[1], sys.argv[2]
sys.path[:0] = [root + "/src", root]
from pathlib import Path
import jax
import types
from bench import generator, registry
from bench import model as sutmod
from bench import scopes as sc
from repro.dist.sharding import batch_specs, to_named
cfg = json.loads(Path(root, "bench/tests/data", cfg_name + ".json").read_text())
ref = registry.reference(Path(root), cfg)
sut = sutmod.build(cfg, ref)
state = sutmod.init_state(sut, 3000000021)
mix = registry.traffic(Path(root), "zipf")
chips = len(sut.mesh.devices.flat)
per = sut.global_batch // chips
micro = min(sut.plan.microbatch, per)
out = {"chips": chips, "got": [], "want": []}
n_dense = cfg.get("n_dense", 0)
for i in range(2):
    b = generator.batch_at(mix, sut.fields, n_dense, sut.global_batch, 3000000021, i)
    ctx = types.SimpleNamespace(cfg=cfg, mix=mix, fields=sut.fields,
                                n_dense=n_dense, global_batch=sut.global_batch,
                                seed=3000000021, first_window_batch=i, n_steps=1)
    want = sc.distinct_rows(ctx, micro)
    bd = jax.device_put(b, to_named(sut.mesh, batch_specs(b, sut.axes)))
    if i == 0:
        text = sut.step.lower(state, bd).compile().as_text()
        out["paths"] = sorted(set(sc.scopes_of_hlo(text).values()))
        # the benchmark's own compile of the step, on shapes alone, names
        # the same instructions alike
        out["same_text"] = sc.scopes_of_hlo(sc.step_text(ctx)[1]) == sc.scopes_of_hlo(text)
    state, m = sut.step(state, bd)
    out["got"].append(int(m["distinct_ids"]))
    out["want"].append(int(want))
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("cfg_name,chips", [("tiny-deepfm", 1), ("tiny-deepfm-x4", 4),
                                            ("tiny-multihot", 1)])
def test_distinct_ids_counts_each_chips_distinct_rows(cfg_name, chips):
    """``distinct_ids`` is the sum over chips, groups and micro-batches of
    the distinct rows a lookup worked on: the distinct (field, row) pairs of
    each chip's share of the batch, each field in its own table and every
    id of a bag counted, as the benchmark draws them again from the seed
    (``scopes.distinct_rows``).
    ``scopes.step_text``, which compiles the step from the configuration on
    shapes alone, maps the instructions as the step the run called does. A
    child process on four CPU devices, since the device count is fixed when
    JAX starts; the four-chip program also names its all_to_alls
    ``shuffle``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), cfg_name],
                       env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    assert res["chips"] == chips
    assert res["got"] == res["want"] and min(res["want"]) > 0
    assert res["same_text"]
    shuffled = {p for p in res["paths"] if p.endswith("/shuffle")}
    if chips > 1:
        assert {"sparse_lookup/shuffle", "sparse_update/shuffle"} <= shuffled
