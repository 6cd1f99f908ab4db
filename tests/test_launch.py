"""The training launcher as a process: a batch producer that dies fails the
run, the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, the
id -> row map is the same in every process, and ``--trace-dir`` profiles
one flush period."""
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SMOKE = ["--arch", "deepfm", "--smoke", "--global-batch", "32", "--steps", "6",
         "--log-every", "1"]

# the synthetic stream yields LIVE batches (two unless set), then its
# producer raises
DYING = """
import itertools, os, sys
import repro.data.synthetic as syn
real = syn.batch_stream

def dying(*a, **kw):
    yield from itertools.islice(real(*a, **kw), int(os.environ.get("LIVE", 2)))
    raise OSError("batch source lost")

syn.batch_stream = dying
from repro.launch.train import main
main(sys.argv[1:])
"""


def _run(code, args, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code, *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)


@pytest.mark.parametrize("supervised", [False, True],
                         ids=["plain", "supervised"])
def test_train_exits_nonzero_when_producer_dies(tmp_path, supervised):
    args = SMOKE + (["--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "50"]
                    if supervised else [])
    out = _run(DYING, args)
    assert out.returncode != 0, out.stdout[-2000:]
    assert "[train] done" not in out.stdout
    assert "BatchProducerError" in out.stderr
    assert "batch source lost" in out.stderr


def test_train_compile_cache_follows_env(tmp_path):
    cache = tmp_path / "jax_cache"
    out = _run("import sys\nfrom repro.launch.train import main\n"
               "main(sys.argv[1:])", SMOKE,
               {"JAX_COMPILATION_CACHE_DIR": str(cache),
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[train] batches {'produced'" in out.stdout
    assert any(cache.iterdir())


def test_enable_compile_cache_default_dir(monkeypatch):
    import jax

    from repro.launch.cache import DEFAULT_DIR, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() is None  # the CPU rig: no cache
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert DEFAULT_DIR == ROOT / ".jax_cache"
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


PACKED_IDS = """
import zlib
import jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.core.features import pack_group
from repro.core.packing import make_plan
from repro.data.synthetic import make_batch
cfg = get_config("deepfm", smoke=True)
plan = make_plan(cfg, world=1, per_device_batch=16)
fields = {n: {k: jnp.asarray(v) for k, v in f.items()}
          for n, f in make_batch(cfg, 16, seed=3)["fields"].items()}
ids = np.asarray(pack_group(plan.groups[0], fields).ids)
print(zlib.crc32(ids.tobytes()))
"""


def test_packed_ids_independent_of_hash_seed():
    """Packed row ids (and so the restored rows a checkpoint maps to, and
    the compiled step's cache key) must not depend on Python's per-process
    string-hash salt."""
    outs = []
    for seed in ("1", "2"):
        out = _run(PACKED_IDS, [], {"PYTHONHASHSEED": seed})
        assert out.returncode == 0, out.stderr[-2000:]
        outs.append(out.stdout.split()[-1])
    assert outs[0] == outs[1]


def test_train_trace_dir_profiles_one_flush_period(tmp_path):
    """``--trace-dir`` writes one profile of the first whole flush period
    after warm-up (the smoke plan flushes every 20 steps from step 10, so
    steps 21-40): one ``train`` step span each, and the batch pipeline's
    host spans beside them."""
    from jax.profiler import ProfileData

    from repro import obs
    args = ["--arch", "deepfm", "--smoke", "--global-batch", "32",
            "--steps", "42", "--log-every", "10", "--trace-dir", str(tmp_path)]
    out = _run("import sys\nfrom repro.launch.train import main\n"
               "main(sys.argv[1:])", args)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"[train] trace of steps 21-40 in {tmp_path}" in out.stdout
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1
    names = {}
    for p in ProfileData.from_file(str(found[0])).planes:
        for line in p.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    assert names.get(obs.STEP) == 20
    # the consumer waits once a step; the producer (a queue of depth 2) may
    # have made up to 3 of the period's batches before the trace started,
    # the two queued and the one in its hand, and may be inside a make or a
    # put when the trace stops
    assert names.get(obs.BATCH_WAIT) == 20
    for span in (obs.BATCH_MAKE, obs.BATCH_PUT):
        assert 20 - 3 - 1 <= names.get(span, 0) <= 21, span
    assert re.search(r"step +40 loss=\S+ hits=\d+/[1-9]\d* ovf=", out.stdout)


def test_train_trace_stops_when_the_run_fails_inside_it(tmp_path):
    """A run that fails inside the traced flush period still stops the
    profiler and writes its trace."""
    args = ["--arch", "deepfm", "--smoke", "--global-batch", "32",
            "--steps", "42", "--log-every", "10", "--trace-dir", str(tmp_path)]
    out = _run(DYING, args, {"LIVE": "30"})
    assert out.returncode != 0
    assert "batch source lost" in out.stderr
    assert ("[train] the run ended inside the traced flush period; trace of "
            f"steps 21-") in out.stdout
    assert len(list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))) == 1
