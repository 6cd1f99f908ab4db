"""The training launcher as a process: a batch producer that dies fails the
run, the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and
the id -> row map is the same in every process."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SMOKE = ["--arch", "deepfm", "--smoke", "--global-batch", "32", "--steps", "6",
         "--log-every", "1"]

# the synthetic stream yields two batches, then its producer raises
DYING = """
import itertools, sys
import repro.data.synthetic as syn
real = syn.batch_stream

def dying(*a, **kw):
    yield from itertools.islice(real(*a, **kw), 2)
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
