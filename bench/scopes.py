"""The program's named phases in a traced window: each device operation's
scope path, device time by scope, idle gaps labelled by the program's own
host spans, and the distinct rows the window's lookups worked on.

The program names its phases in one table, ``repro.obs``: a scope
(``jax.named_scope``) lands in the HLO ``op_name`` metadata of every
operation traced inside it, and a span (``TraceAnnotation``) is a host span
on the profiler's timeline. A scope path is the table's scope names in an
operation's ``op_name``, outermost first, joined by ``/``:
``sparse_lookup/tier_probe`` for the tier probe, the L1 tier's rank-index
read and the L2 tier's search (the hit rows' fetch is
``sparse_lookup/stitch``).

``of(ctx)`` serves the per-layer readers of a ``--trace 1`` run. After the
window it compiles the cell's step again from its configuration (the
compilation cache returns the executable the window ran; nothing runs on
the device) and maps each instruction of its text to a scope path
(``scopes_of_hlo``). It then reads the window's profile again with those
paths and the program's spans (``load``) into a ``ScopedReduction``: the
harness's ``bench/trace.py`` reduction, whose readings it leaves as they
are, with the scope readings added. A program without ``repro.obs`` names
no phases: ``of`` returns None and the readers report nothing.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from bench import trace as tr

try:
    from repro import obs
except ImportError:  # a program that names no phases
    obs = None

ROOT = Path(__file__).resolve().parents[1]
PROGRAM_SPANS = obs.SPANS if obs is not None else ()

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_path(op_name_meta: str) -> str:
    """The program's scope names in an HLO ``op_name``, outermost first.
    The last part names the primitive (``gather``, ``while``) and is left
    out; a transformation's wrapper (``transpose(jvp(dense))``) is looked
    through, a nested jit's (``jit(searchsorted)``) is not; a scope that
    repeats its parent (a flush inside the step's flush ``cond``) counts
    once. An operation the compiler merged from several joins their names
    with ``;`` and is taken as the first."""
    if obs is None:
        return ""
    path: List[str] = []
    for part in op_name_meta.split(";", 1)[0].split("/")[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) != "jit":
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part in obs.SCOPES and (not path or path[-1] != part):
            path.append(part)
    return "/".join(path)


def scopes_of_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> scope path, from the text of a compiled module
    (``jitted.lower(...).compile().as_text()``); instructions outside every
    scope are left out."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = scope_path(m.group(2))
            if path:
                out[m.group(1)] = path
    return out


def step_text(ctx):
    """The cell's system under test, built as the harness builds it, and
    the text of its compiled step, lowered on the shapes and shardings of
    the window's state and batches."""
    import jax

    from bench import generator, registry, weights
    from bench import model as sutmod
    from repro.dist.sharding import batch_specs, to_named

    sut = sutmod.build(ctx.cfg, registry.reference(ROOT, ctx.cfg))
    state = jax.eval_shape(sutmod.state_fn(sut), weights.seed_words(ctx.seed))
    batch = generator.batch_at(ctx.mix, ctx.fields, ctx.n_dense,
                               ctx.global_batch, ctx.seed, ctx.first_window_batch)
    batch = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch, to_named(sut.mesh, batch_specs(batch, sut.axes)))
    return sut, sut.step.lower(state, batch).compile().as_text()


def load(path: str, scopes: Dict[str, str]) -> Dict:
    """``trace.from_xplane``'s form of the profile at ``path``, with the
    program's host spans beside the harness's and ``scopes``, operation
    name -> scope path, as a field of its own."""
    from jax.profiler import ProfileData
    out = tr.from_xplane(path)
    spans = [[e.name, int(e.start_ns), int(e.duration_ns), ""]
             for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name in PROGRAM_SPANS]
    out["planes"].append({"name": "/host:program",
                          "lines": [{"name": "spans", "events": spans}]})
    out["scopes"] = dict(scopes)
    return out


class ScopedReduction(tr.Reduction):
    """``trace.Reduction`` with the program's names. Every reading of the
    base class is computed as there. Times in seconds, on the fullest
    device.

    ``has_scopes``     whether any operation in the window has a scope path
    ``scope_s(name)``  union of the intervals of the operations whose scope
                       path holds ``name``: a ``while`` and its body count once
    ``unscoped_s``     busy time outside every scope
    ``labelled_gaps``  the base class's gaps, each label followed by ``:``
                       and the innermost program span open in the gap, if any
    ``breakdown()``    the base's, each operation named
                       ``<innermost scope>:<operation>`` where it has a scope
    """

    def __init__(self, trace: Dict):
        super().__init__(trace)
        self.scopes: Dict[str, str] = trace.get("scopes", {})
        self._scoped: List[Tuple[int, int, str]] = []
        busy: List[Tuple[int, int]] = []
        program: List[Tuple[str, int, int]] = []
        for p in trace["planes"]:
            for line in p["lines"]:
                for name, start, dur, _ in line["events"]:
                    if p["name"].startswith("/host:"):
                        if name in PROGRAM_SPANS:
                            program.append((name, start, start + dur))
                        continue
                    if p["name"] != f"/device:TPU:{self.fullest}":
                        continue
                    a, b = max(start, self.t0), min(start + dur, self.t1)
                    if b > a:
                        busy.append((a, b))
                        if name in self.scopes:
                            self._scoped.append((a, b, self.scopes[name]))
        self.labelled_gaps = self._label(tr._union(busy), program)

    def _label(self, busy, program) -> List[Tuple[str, float]]:
        """The base class's gaps (the same intervals, in the same order),
        each with the program span entered last among those open in it: of
        nested spans, the innermost."""
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        assert len(gaps) == len(self.gaps)
        out = []
        for (label, secs), (a, b) in zip(self.gaps, gaps):
            inner = max(((s, name) for name, s, e in program
                         if min(b, e) - max(a, s) > 0), default=None)
            out.append((label if inner is None else f"{label}:{inner[1]}", secs))
        return out

    @property
    def has_scopes(self) -> bool:
        return bool(self._scoped)

    def scope_s(self, name: str) -> float:
        return _length([(a, b) for a, b, path in self._scoped
                        if name in path.split("/")])

    @property
    def unscoped_s(self) -> float:
        return self.busy_s[self.fullest] - _length(
            [(a, b) for a, b, _ in self._scoped])

    def breakdown(self) -> Dict:
        out = super().breakdown()
        inner = {n: p.rsplit("/", 1)[-1] for n, p in self.scopes.items()}
        out["device_ops"] = [[f"{inner[n]}:{n}" if n in inner else n, s]
                             for n, s in out["device_ops"]]
        out["idle_gaps"] = [[n, s] for n, s in
                            sorted(self.labelled_gaps, key=lambda g: -g[1])[:10]]
        return out


def _length(iv: List[Tuple[int, int]]) -> float:
    return sum(b - a for a, b in tr._union(iv)) * 1e-9


def distinct_rows(ctx, micro: int) -> int:
    """The distinct rows the window's lookups worked on, summed over steps,
    chips, micro-batches of ``micro`` samples and fields (one table a
    field), every id of a bag counted: what the program's ``distinct_ids``
    counter sums, drawn again from the seed with the reference's row map."""
    from bench import generator, registry
    ref = registry.reference(ROOT, ctx.cfg)
    total = 0
    for k in range(ctx.n_steps):
        b = generator.batch_at(ctx.mix, ctx.fields, ctx.n_dense,
                               ctx.global_batch, ctx.seed,
                               ctx.first_window_batch + k)
        for f in ctx.fields:
            rows = ref.hashed_rows(f.name, f.vocab, b["fields"][f.name]["ids"])
            for lo in range(0, ctx.global_batch, micro):
                total += np.unique(rows[lo:lo + micro]).size
    return total


class Run(NamedTuple):
    """What the scope readers of one traced run share: the scoped
    reduction, and the samples a micro-batch of the compiled step looks up
    on a chip."""

    red: ScopedReduction
    micro: int


_LAST: List = [None, None]   # (ctx.trace, Run) of the run being read


def of(ctx) -> Optional[Run]:
    """The scoped reading of this run's trace, made once for all readers;
    None for a program that names no phases."""
    if obs is None or ctx.trace is None:
        return None
    if _LAST[0] is not ctx.trace:
        from bench.harness import TRACE_DIR, _xplane, log
        sut, text = step_text(ctx)
        red = ScopedReduction(load(_xplane(ROOT / TRACE_DIR), scopes_of_hlo(text)))
        micro = min(sut.plan.microbatch, ctx.global_batch // ctx.chips)
        _LAST[:] = [ctx.trace, Run(red, micro)]
        if red.has_scopes:
            log("device seconds by scope: " + ", ".join(
                f"{n} {red.scope_s(n):.6f}" for n in obs.TOP_SCOPES)
                + f", unscoped {red.unscoped_s:.6f} of busy "
                f"{red.busy_s[red.fullest]:.6f}")
            log("scoped breakdown " + json.dumps(red.breakdown()))
    return _LAST[1] if _LAST[1].red.has_scopes else None
