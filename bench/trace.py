"""Reduction of a profiler trace of the measured window to device busy and
idle time, device time by operation, and idle gaps labelled by what the host
was doing.

``from_xplane`` turns the profiler's ``.xplane.pb`` into a small neutral
form that ``reduce`` reads (and that a test can keep as JSON): device planes
keep their operation line, the host plane keeps the benchmark's own spans:
``window`` around the measured steps, and inside it ``input_wait`` (the
next batch from the prefetcher), ``dispatch`` (the step call) and
``metrics_read`` (the previous step's counters to the host).
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("input_wait", "dispatch", "metrics_read")
WINDOW = "window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_DETAIL_STATS = ("long_name", "tf_op", "hlo_category", "source")


def from_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for p in pd.planes:
        if _DEVICE.match(p.name):
            lines = []
            for line in p.lines:
                if line.name != _OPS_LINE:
                    continue
                evs = []
                for e in line.events:
                    stats = dict(e.stats)
                    detail = " ".join([e.name] + [str(stats[k]) for k in _DETAIL_STATS
                                                  if k in stats])
                    evs.append([op_name(e.name), int(e.start_ns), int(e.duration_ns),
                                detail[:200]])
                lines.append({"name": line.name, "events": evs})
            planes.append({"name": p.name, "lines": lines})
        elif p.name.startswith("/host:"):
            evs = [[e.name, int(e.start_ns), int(e.duration_ns), ""]
                   for line in p.lines for e in line.events
                   if e.name in HOST_SPANS or e.name == WINDOW]
            if evs:
                planes.append({"name": p.name, "lines": [{"name": "spans", "events": evs}]})
    return {"planes": planes}


def op_name(event_name: str) -> str:
    """An operation's own name: a TPU trace names its events by the HLO
    instruction's text (``%fusion.12 = f32[...] fusion(%operand, ...)``),
    whose operands may name other operations."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Dict:
    p = Path(path)
    return json.loads(p.read_text()) if p.suffix == ".json" else from_xplane(path)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


class Reduction:
    """What a metric reader takes from the trace. Times in seconds.

    ``window_s``       length of the host's ``window`` span
    ``busy_s``         union of operation intervals inside the window, by device
    ``op_s``           device seconds by operation name, by device
    ``op_events``      ``(name, detail, seconds)`` of every operation in the window, by
                       device; a ``while`` encloses the operations of its body, so
                       ``op_s`` counts those twice while ``busy_s`` does not
    ``gaps``           idle gaps of the fullest device: ``(label, seconds)``
    """

    def __init__(self, trace: Dict):
        host = [e for p in trace["planes"] if p["name"].startswith("/host:")
                for line in p["lines"] for e in line["events"]]
        wins = [e for e in host if e[0] == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"the trace holds {len(wins)} window spans, not 1")
        self.t0 = wins[0][1]
        self.t1 = wins[0][1] + wins[0][2]
        self.window_s = (self.t1 - self.t0) * 1e-9
        spans = [(e[0], e[1], e[1] + e[2]) for e in host if e[0] in HOST_SPANS]
        self.busy_s: Dict[int, float] = {}
        self.op_s: Dict[int, Dict[str, float]] = {}
        self.op_events: Dict[int, List[Tuple[str, str, float]]] = {}
        busy_iv: Dict[int, List[Tuple[int, int]]] = {}
        for p in trace["planes"]:
            m = _DEVICE.match(p["name"])
            if not m:
                continue
            dev = int(m.group(1))
            iv, ops, evs = [], {}, []
            for line in p["lines"]:
                for name, start, dur, detail in line["events"]:
                    a, b = max(start, self.t0), min(start + dur, self.t1)
                    if b <= a:
                        continue
                    iv.append((a, b))
                    ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
                    evs.append((name, detail, (b - a) * 1e-9))
            busy_iv[dev] = _union(iv)
            self.busy_s[dev] = sum(b - a for a, b in busy_iv[dev]) * 1e-9
            self.op_s[dev] = ops
            self.op_events[dev] = evs
        if not self.busy_s:
            raise ValueError("the trace holds no TPU device plane")
        self.fullest = max(self.busy_s, key=self.busy_s.get)
        self.gaps = self._gaps(busy_iv[self.fullest], spans)

    def _gaps(self, busy, spans) -> List[Tuple[str, float]]:
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            overlap = {}
            for name, s, e in spans:
                o = min(b, e) - max(a, s)
                if o > 0:
                    overlap[name] = overlap.get(name, 0) + o
            label = max(overlap, key=overlap.get) if overlap else "other"
            out.append((label, (b - a) * 1e-9))
        return out

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def idle_share(self) -> float:
        """1 - busy/window on the fullest device."""
        return 1.0 - self.busy_s[self.fullest] / self.window_s

    def matching(self, pattern: str, dev: Optional[int] = None):
        """Operation events of ``dev`` (default: the fullest device) whose
        own name matches ``pattern``."""
        rx = re.compile(pattern)
        dev = self.fullest if dev is None else dev
        return [e for e in self.op_events[dev] if rx.search(e[0])]

    def breakdown(self) -> Dict:
        ops = sorted(self.op_s[self.fullest].items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}
