"""The comparison that decides ``correct`` for a training cell.

The program's first steps go through the window's own step and feed; the
plain reference follows the same steps from the same seed. Three numbers are
compared, each against the limit its configuration file states:

``loss_gap``    the largest ``|L - L_ref| / |L_ref|`` over the compared steps
``grad_gap``    the worst leaf's ``|n - n_ref| / max(n_ref, median n_ref)``,
                n the norm of the leaf's first gradient as the optimizer got it
``change_gap``  the same for the norm of each leaf's change after the last
                compared step, over the leaves the reference moves: a leaf
                whose reference gradient norm is under a thousandth of the
                median leaf's moves by round-off alone and is left out
``writeback_gap``  the same for the norm of each table's change after the
                set-up's second tier flush, read from the master table
                alone: the rows the hot tier held between the flushes reach
                it only through the flush's write-back

A number that is not finite reads as infinite, so it fails any limit. A
configuration compares the numbers its ``limits`` name; the others are
printed as readings only (see PERF.md for why a number goes uncompared).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

NOUGHT = 1e-3   # share of the median leaf's gradient under which a leaf is nought

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "writeback_gap")


def _finite(x: float) -> float:
    return x if math.isfinite(x) else math.inf


def _worst_leaf(cand: Dict[str, float], ref: Dict[str, float], leaves) -> Tuple[float, str]:
    med = statistics.median(ref[k] for k in leaves)
    worst, at = 0.0, ""
    for k in leaves:
        c = cand.get(k, math.nan)
        gap = _finite(abs(c - ref[k]) / max(ref[k], med)) if max(ref[k], med) > 0 else (
            0.0 if c == 0 else math.inf)
        if not gap <= worst:
            worst, at = gap, k
    return worst, at


def compare(cand: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """``{number: (value, where)}`` for a candidate's readings against the
    reference's (both as ``wdl_reference.readings`` returns them)."""
    if len(cand["loss"]) != len(ref["loss"]):
        raise ValueError("candidate and reference followed different step counts")
    loss, at = 0.0, ""
    for i, (c, r) in enumerate(zip(cand["loss"], ref["loss"])):
        gap = _finite(abs(c - r) / abs(r))
        if not gap <= loss:
            loss, at = gap, f"step {i + 1}"
    g_med = statistics.median(ref["grad1"].values())
    moved = [k for k, v in ref["grad1"].items() if v >= NOUGHT * g_med]
    return {"loss_gap": (loss, at),
            "grad_gap": _worst_leaf(cand["grad1"], ref["grad1"], list(ref["grad1"])),
            "change_gap": _worst_leaf(cand["change"], ref["change"], moved),
            "writeback_gap": _worst_leaf(cand["tables"], ref["tables"], list(ref["tables"]))}


def checks(numbers: Dict[str, Tuple[float, str]], limits: Dict[str, float],
           extra: Dict[str, Tuple[float, float]] = ()) -> List[Tuple[str, float, float]]:
    out = [(n, numbers[n][0], float(limits[n])) for n in NUMBERS if n in limits]
    out += [(n, v, lim) for n, (v, lim) in dict(extra).items()]
    return out


def passed(rows: List[Tuple[str, float, float]]) -> bool:
    return all(v <= lim for _, v, lim in rows)
