"""Checkpoint save/restore: atomic, async-capable, verified, elastic.

Layout: <dir>/step_<n>/ manifest.json + one .npy per leaf (zstd-compressed).
Embedding tables are stored *logically* (gathered, world-size padding kept but
recorded), so a checkpoint written on 512 chips restores onto any mesh: the
row space is world-independent (scramble + offsets derive from raw vocabs;
only the tail padding differs). A world-size mismatch is *detected* here
(``on_row_mismatch``) and re-cut by the elastic path
(``runtime.elastic.restore_elastic``), which remaps tier sentinel keys.

Integrity: every leaf's on-disk bytes are checksummed (crc32) into the
manifest at save time, and restore verifies them by default — a torn write,
a bad disk, or an injected fault (``runtime.chaos``) raises
``CheckpointCorrupt`` instead of silently loading poisoned state.
``restore_verified`` is the failover entry: it walks the available steps
newest-first, *quarantines* a corrupt checkpoint (``step_<n>`` ->
``step_<n>.corrupt``, kept for forensics, invisible to ``latest_step``/GC)
and falls back to the previous good one, so one bad snapshot never takes
down a resume.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.packed_embedding import with_rank_index

try:  # optional: plain .npy files when the container lacks zstandard
    import zstandard
except ImportError:
    zstandard = None

_SEP = "/"
_CORRUPT_SUFFIX = ".corrupt"


class CheckpointCorrupt(RuntimeError):
    """A checkpoint failed integrity verification (checksum mismatch, torn or
    missing leaf file, unreadable manifest). Distinct from a shape/world
    mismatch (``ValueError``): corruption means the *bytes* are wrong, and
    the recovery is to quarantine + fall back (``restore_verified``), not to
    reshard."""

    def __init__(self, msg: str, step: Optional[int] = None,
                 leaf: Optional[str] = None):
        super().__init__(msg)
        self.step = step
        self.leaf = leaf


def _flatten(tree) -> Dict[str, Any]:
    flat = {}

    def rec(prefix, node):
        if node is None:  # optional subtree (e.g. a group without an L2 tier)
            return
        if isinstance(node, dict):
            for k, v in node.items():
                rec(f"{prefix}{_SEP}{k}" if prefix else str(k), v)
        elif hasattr(node, "_fields"):  # NamedTuple
            for k in node._fields:
                rec(f"{prefix}{_SEP}{k}" if prefix else str(k), getattr(node, k))
        else:
            flat[prefix] = node

    rec("", tree)
    return flat


def _is_rank_index(name: str) -> bool:
    """A hot tier's rank index leaf (``cache/bits``, ``cache/base``): derived
    from the tier's keys, so a checkpoint may lack it."""
    return name.split(_SEP)[-2:] in (["cache", "bits"], ["cache", "base"])


def _with_rank_index(template, node):
    """An embedding state restored from a checkpoint written without its hot
    tier's rank index gets the index rebuilt from the tier's keys, over the
    restored master's row space."""
    cache = getattr(node, "cache", None)
    if (cache is None or getattr(template.cache, "bits", None) is None
            or (cache.bits is not None and cache.base is not None)):
        return node
    return node._replace(cache=with_rank_index(
        cache._replace(bits=None, base=None), node.w.shape[0]))


def _unflatten_into(template, flat: Dict[str, Any]):
    def rec(prefix, node):
        if node is None:
            return None
        if isinstance(node, dict):
            return {k: rec(f"{prefix}{_SEP}{k}" if prefix else str(k), v)
                    for k, v in node.items()}
        if hasattr(node, "_fields"):
            vals = {k: rec(f"{prefix}{_SEP}{k}" if prefix else str(k), getattr(node, k))
                    for k in node._fields}
            return _with_rank_index(node, type(node)(**vals))
        return flat.get(prefix) if _is_rank_index(prefix) else flat[prefix]

    return rec("", template)


def save_checkpoint(ckpt_dir: str, step: int, state: Any, keep: int = 3,
                    meta: Optional[Dict[str, Any]] = None) -> str:
    """Atomic checkpoint: write to tmp, fsync, rename.

    ``meta`` is an optional JSON-serializable sidecar stored in the manifest
    — the trainer records the live plan revision there
    (``repro.runtime.plan_meta``) so a resume can rebuild the *current*
    (possibly replanned) plan before shaping the restore template, instead
    of the seed plan the run started from.
    """
    ckpt_dir = Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    final = ckpt_dir / f"step_{step:08d}"
    tmp = Path(tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_"))
    flat = _flatten(jax.device_get(state))
    manifest = {}
    cctx = zstandard.ZstdCompressor(level=3) if zstandard is not None else None
    for name, arr in flat.items():
        arr = np.asarray(arr)
        fn = name.replace(_SEP, "__") + (".npy.zst" if cctx else ".npy")
        payload = _np_bytes(arr)
        data = cctx.compress(payload) if cctx else payload
        with open(tmp / fn, "wb") as f:
            f.write(data)
        # checksum of the bytes as they sit ON DISK (post-compression):
        # restore re-hashes exactly what it read, so any torn/corrupted
        # file is caught before a single byte is decompressed or parsed
        manifest[name] = {"file": fn, "shape": list(arr.shape),
                          "dtype": str(arr.dtype),
                          "crc32": zlib.crc32(data) & 0xFFFFFFFF}
    doc = {"step": step, "leaves": manifest}
    if meta is not None:
        doc["meta"] = meta
    (tmp / "manifest.json").write_text(json.dumps(doc))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    _gc_checkpoints(ckpt_dir, keep)
    return str(final)


def _np_bytes(arr: np.ndarray) -> bytes:
    import io
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _np_from_bytes(b: bytes) -> np.ndarray:
    import io
    return np.load(io.BytesIO(b), allow_pickle=False)


def _parse_step_dir(p: Path) -> Optional[int]:
    """``step_00000040`` -> 40; quarantined (``.corrupt``) or otherwise
    unparseable entries -> None (skipped everywhere)."""
    if not p.name.startswith("step_") or p.name.endswith(_CORRUPT_SUFFIX):
        return None
    try:
        return int(p.name.split("_")[1])
    except (IndexError, ValueError):
        return None


def _gc_checkpoints(ckpt_dir: Path, keep: int) -> None:
    # quarantined checkpoints are forensic evidence, never GC'd here
    steps = sorted(p for p in ckpt_dir.iterdir()
                   if _parse_step_dir(p) is not None)
    for p in steps[:-keep]:
        shutil.rmtree(p, ignore_errors=True)


def available_steps(ckpt_dir: str) -> List[int]:
    """Steps with a manifest on disk, ascending (quarantined dirs excluded)."""
    d = Path(ckpt_dir)
    if not d.exists():
        return []
    out = []
    for p in d.iterdir():
        s = _parse_step_dir(p)
        if s is not None and (p / "manifest.json").exists():
            out.append(s)
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = available_steps(ckpt_dir)
    return steps[-1] if steps else None


def quarantine_checkpoint(ckpt_dir: str, step: int) -> Optional[str]:
    """Rename ``step_<n>`` -> ``step_<n>.corrupt`` so every reader
    (``latest_step``/``available_steps``/GC/restore) stops seeing it, while
    the bytes stay on disk for postmortem. Returns the quarantine path, or
    ``None`` if the directory had already vanished (lost a prune race)."""
    src = Path(ckpt_dir) / f"step_{step:08d}"
    if not src.exists():
        return None
    dst = src.with_name(src.name + _CORRUPT_SUFFIX)
    if dst.exists():  # re-quarantine of a rewritten step: keep both
        n = 1
        while dst.with_name(f"{src.name}{_CORRUPT_SUFFIX}.{n}").exists():
            n += 1
        dst = dst.with_name(f"{src.name}{_CORRUPT_SUFFIX}.{n}")
    os.rename(src, dst)
    return str(dst)


def _read_manifest(ckpt_dir: str, step: int) -> Dict[str, Any]:
    """Manifest of one step; unreadable/unparseable -> CheckpointCorrupt,
    a missing directory -> FileNotFoundError (pruned, not corrupt)."""
    d = Path(ckpt_dir) / f"step_{step:08d}"
    if not d.exists():
        raise FileNotFoundError(f"no checkpoint step_{step:08d} under {ckpt_dir}")
    try:
        return json.loads((d / "manifest.json").read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointCorrupt(
            f"checkpoint step_{step:08d}: manifest unreadable ({e})",
            step=step) from e


def load_checkpoint_meta(ckpt_dir: str, step: Optional[int] = None
                         ) -> Optional[Dict[str, Any]]:
    """The ``meta`` sidecar of a checkpoint (``None`` if absent — e.g. a
    checkpoint written before replanning existed, or with replanning off).

    Callers that revise the plan from it must do so *before* building the
    restore template: tier shapes in the stored state follow the plan
    revision recorded here, not the seed plan. With ``step=None`` this walks
    back from the newest checkpoint past any with an unreadable manifest —
    a corrupt newest snapshot must not crash a resume before
    ``restore_verified`` even gets the chance to quarantine it.
    """
    if step is not None:
        return _read_manifest(ckpt_dir, step).get("meta")
    for s in reversed(available_steps(ckpt_dir)):
        try:
            return _read_manifest(ckpt_dir, s).get("meta")
        except CheckpointCorrupt:
            continue  # restore_verified will quarantine it
    return None


def restore_checkpoint(ckpt_dir: str, template: Any, step: Optional[int] = None,
                       shardings: Any = None,
                       on_row_mismatch: str = "error",
                       verify: bool = True) -> Tuple[Any, int]:
    """Restore into ``template`` (abstract or concrete pytree).

    ``verify`` (default on) re-hashes every leaf's on-disk bytes against the
    manifest's crc32 and raises ``CheckpointCorrupt`` on any mismatch,
    missing leaf file, or unreadable manifest — corruption is *detected*
    here; the quarantine + fallback policy lives in ``restore_verified``.
    Checkpoints written before checksums existed verify trivially (no crc32
    recorded -> nothing to check).

    ``on_row_mismatch`` decides what happens when a stored leaf's leading dim
    (world-padding) differs from the template's:

    - ``"error"`` (default): raise with the leaf name and both shapes, plus
      the elastic-restore pointer. A row mismatch means the checkpoint was
      written at a different world size, and blindly re-padding corrupts
      tier sentinel keys (an old-sentinel ``rows_padded_old`` entry becomes
      a valid-looking key into a padding row) — the caller must go through
      ``runtime.elastic.restore_elastic`` / ``embedding.state.reshard_state``
      instead, which remap the sentinels.
    - ``"keep"``: return the leaf at its STORED leading dim (the template's
      trailing dims must match). The elastic restore path uses this to pull
      the world-W state out before resharding it properly.
    - ``"repad"``: legacy behavior — zero-extend / truncate to the
      template's rows. Only safe for states without cache tiers (no
      sentinel keys), e.g. dense-only models.
    """
    if on_row_mismatch not in ("error", "keep", "repad"):
        raise ValueError(f"on_row_mismatch must be 'error', 'keep', or "
                         f"'repad', got {on_row_mismatch!r}")
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = Path(ckpt_dir) / f"step_{step:08d}"
    manifest = _read_manifest(ckpt_dir, step)["leaves"]
    dctx = zstandard.ZstdDecompressor() if zstandard is not None else None
    tflat = _flatten(template)
    out = {}
    for name, t in tflat.items():
        info = manifest.get(name)
        if info is None and _is_rank_index(name):
            continue  # rebuilt from the tier's keys (_with_rank_index)
        if info is None:
            raise KeyError(
                f"checkpoint step_{step:08d} has no leaf {name!r} — the "
                "template enables state the run that wrote it did not "
                "(e.g. an L2 tier turned on after checkpointing)")
        try:
            raw = (d / info["file"]).read_bytes()
        except OSError as e:
            raise CheckpointCorrupt(
                f"checkpoint step_{step:08d}: leaf file {info['file']} "
                f"unreadable ({e})", step=step, leaf=name) from e
        if verify and "crc32" in info:
            crc = zlib.crc32(raw) & 0xFFFFFFFF
            if crc != info["crc32"]:
                raise CheckpointCorrupt(
                    f"checkpoint step_{step:08d}: leaf {name!r} checksum "
                    f"mismatch (stored {info['crc32']:#010x}, on-disk "
                    f"{crc:#010x}) — torn write or disk corruption",
                    step=step, leaf=name)
        if info["file"].endswith(".zst"):
            if dctx is None:
                raise ImportError(
                    f"checkpoint leaf {info['file']} is zstd-compressed but "
                    "zstandard is not installed")
            try:
                raw = dctx.decompress(raw)
            except zstandard.ZstdError as e:
                # pre-checksum checkpoint with damaged bytes (crc32 would
                # have caught this above): still classified as corruption
                raise CheckpointCorrupt(
                    f"checkpoint step_{step:08d}: leaf {name!r} failed to "
                    f"decompress ({e})", step=step, leaf=name) from e
        try:
            arr = _np_from_bytes(raw)
        except ValueError as e:
            raise CheckpointCorrupt(
                f"checkpoint step_{step:08d}: leaf {name!r} is not a valid "
                f".npy payload ({e})", step=step, leaf=name) from e
        tshape = tuple(t.shape)
        if tuple(arr.shape) != tshape:
            if not (arr.ndim >= 1 and arr.shape[1:] == tshape[1:]):
                raise ValueError(f"{name}: stored {arr.shape} vs template {tshape}")
            if on_row_mismatch == "error":
                raise ValueError(
                    f"{name}: stored {arr.shape} vs template {tshape} — row "
                    "count (world padding) differs, so this checkpoint was "
                    "written at a different world size. Restore through the "
                    "elastic path (runtime.elastic.restore_elastic / "
                    "embedding.state.reshard_state), which remaps tier "
                    "sentinel keys; a blind re-pad would corrupt them.")
            if on_row_mismatch == "repad":
                new = np.zeros(tshape, arr.dtype)
                n = min(arr.shape[0], tshape[0])
                new[:n] = arr[:n]
                arr = new  # legacy elastic re-pad (no-tier states only)
            # 'keep': hand back the stored rows untouched for resharding
        out[name] = arr.astype(t.dtype)
    state = _unflatten_into(template, out)
    if shardings is not None:
        state = jax.device_put(state, shardings)
    return state, step


def restore_verified(ckpt_dir: str, template: Any, *,
                     step: Optional[int] = None, shardings: Any = None,
                     on_row_mismatch: str = "error",
                     quarantine: bool = True,
                     log: Optional[Callable[[str], None]] = None
                     ) -> Tuple[Any, int]:
    """Restore the newest checkpoint that passes integrity verification.

    Walks the available steps newest-first (or starts at ``step``); a
    checkpoint that raises ``CheckpointCorrupt`` is quarantined
    (``step_<n>`` -> ``step_<n>.corrupt``) and the walk falls back to the
    previous good one. Shape/world mismatches (``ValueError``) propagate —
    those are elastic-restore business, not corruption. Raises
    ``FileNotFoundError`` when no verifiable checkpoint remains.
    """
    log = log or (lambda s: None)
    steps = [s for s in reversed(available_steps(ckpt_dir))
             if step is None or s <= step]
    if not steps:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    for s in steps:
        try:
            return restore_checkpoint(ckpt_dir, template, step=s,
                                      shardings=shardings,
                                      on_row_mismatch=on_row_mismatch,
                                      verify=True)
        except CheckpointCorrupt as e:
            if quarantine:
                q = quarantine_checkpoint(ckpt_dir, s)
                log(f"quarantined corrupt checkpoint step {s}"
                    f"{' -> ' + q if q else ''} ({e}); falling back")
            else:
                log(f"corrupt checkpoint step {s} ({e}); falling back")
    raise FileNotFoundError(
        f"no verifiable checkpoint under {ckpt_dir}: all "
        f"{len(steps)} candidate(s) failed integrity checks")


class AsyncCheckpointer:
    """Snapshot-to-host then write in a background thread (training continues)."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[str] = None

    def save(self, step: int, state: Any,
             meta: Optional[Dict[str, Any]] = None) -> None:
        with obs.span(obs.CHECKPOINT):
            self.wait()
            # synchronous snapshot, async write
            host_state = jax.device_get(state)

        def work():
            self.last_path = save_checkpoint(self.ckpt_dir, step, host_state,
                                             self.keep, meta=meta)

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
