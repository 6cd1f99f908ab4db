"""Numeric anomaly guard: reject poisoned steps before they become state.

PICASSO's continuous-delivery loop (paper §V) races the clock on 1000+
nodes; a silent-NaN step does not *raise* — it trains the model onto garbage
and then gets checkpointed as "good", costing hours of retrain walltime when
someone finally notices the loss curve. The guard closes that hole at the
step boundary:

1. **Detection** reads the step's own metrics on the host: a non-finite
   loss, a non-finite gradient norm, or a gradient norm above the spike
   threshold marks the step anomalous. This costs one host sync per step —
   the honesty price of detection, the same sync the calibrated-cost-model
   feedback loop already pays.
2. **Rejection** returns the *prior* state: the batch is consumed (skipped),
   training continues on the next one. This requires the wrapped step to be
   built WITHOUT buffer donation (``make_train_step(..., donate=False)``) so
   the prior state's buffers are still alive — the guard trades donation's
   peak-memory saving for the ability to reject. Because donation only
   affects aliasing, never values, a guarded run on clean data is **bitwise
   identical** to an unguarded one (pinned by tests/test_faults.py); the
   guard adds no wrapper jit and runs the exact same executable.
3. **Rollback** is the escalation: ``k_rollback`` *consecutive* rejections
   means the problem is not one bad batch (the state itself may be poisoned,
   or the input stream is down), so the guard raises ``AnomalyRollback`` and
   the ``Supervisor`` restores the last verified checkpoint and replays.

The spike threshold is an EMA over accepted steps' gradient norms
(``spike_factor`` x EMA); during ``warmup_steps`` only the NaN/Inf checks
are armed, so early-training norm swings never false-positive.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro import obs


class AnomalyRollback(RuntimeError):
    """``k_rollback`` consecutive anomalous steps: the guard gives up on
    skip-and-continue and asks the supervisor for a checkpoint rollback.
    Classified transient by ``fault_tolerance.classify_failure``."""

    def __init__(self, msg: str, rejects: int = 0, state: Any = None):
        super().__init__(msg)
        self.rejects = rejects
        # the surviving (rejection-preserved) state rides on the exception,
        # so a supervisor with no checkpoint on disk can resume from it
        self.state = state


@dataclass(frozen=True)
class GuardConfig:
    """Static thresholds of the anomaly guard."""

    spike_factor: float = 10.0   # reject when grad_norm > factor * EMA
    ema_decay: float = 0.95      # EMA over accepted steps' grad norms
    warmup_steps: int = 10       # accepted steps before spike checks arm
    k_rollback: int = 3          # consecutive rejections -> AnomalyRollback
    metric: str = "grad_norm"    # metrics key carrying the norm (optional)


@dataclass
class GuardEvent:
    """One rejected step (kept in ``AnomalyGuard.events``)."""

    step: int            # accepted-step count when the rejection happened
    kind: str            # 'nonfinite' | 'spike'
    value: float         # the offending loss/grad-norm
    threshold: float     # the spike threshold in force (0 = not armed)
    consecutive: int     # consecutive rejections including this one

    def describe(self) -> str:
        return (f"guard: rejected step ({self.kind}: value={self.value:.4g}, "
                f"threshold={self.threshold:.4g}, "
                f"consecutive={self.consecutive})")


class AnomalyGuard:
    """Wrap a **non-donating** jitted ``step(state, batch) -> (state,
    metrics)`` with anomaly detection + rejection. Keeps the step signature,
    so it drops into ``Supervisor.run`` / ``run_stream`` / launcher loops
    unchanged; ``metrics["anomalous"]`` (0/1) is added for observability.

    The wrapped step MUST be built with ``donate=False``: rejection returns
    the input state, and a donating step would have freed those buffers.
    (On a rejected step the discarded new-state buffers are simply dropped.)

    ``rebind(step_fn)`` swaps the wrapped step (after a replan/reshard step
    rebuild) while keeping the EMA, counters, and event history — the
    numeric history of the run survives a plan revision.
    """

    def __init__(self, step_fn: Optional[Callable] = None,
                 cfg: GuardConfig = GuardConfig(),
                 log: Optional[Callable[[str], None]] = None):
        self.cfg = cfg
        self.log = log or (lambda s: None)
        self.ema: Optional[float] = None   # EMA of accepted grad norms
        self.accepted = 0                  # accepted steps (feeds warmup)
        self.rejected = 0                  # total rejections
        self.consecutive = 0               # current rejection streak
        self.events: List[GuardEvent] = []
        self._inner: Optional[Callable] = None
        if step_fn is not None:
            self.rebind(step_fn)

    def rebind(self, step_fn: Callable) -> "AnomalyGuard":
        """(Re)bind the wrapped step; EMA/counters/events carry over.
        Returns self (callable), so ``step = guard.rebind(make_step(...))``
        reads naturally at step-rebuild sites."""
        self._inner = step_fn
        return self

    @property
    def threshold(self) -> float:
        """Spike threshold currently in force (0 = disarmed)."""
        if self.ema is None or self.accepted < self.cfg.warmup_steps:
            return 0.0
        return self.cfg.spike_factor * self.ema

    def __call__(self, state, batch) -> Tuple[Any, Dict[str, Any]]:
        if self._inner is None:
            raise RuntimeError("AnomalyGuard has no step bound; call rebind()")
        new_state, metrics = self._inner(state, batch)
        thr = self.threshold
        with obs.span(obs.GUARD_SYNC):
            loss = float(metrics["loss"])  # host sync: see module docstring
            gn_m = metrics.get(self.cfg.metric)
            gn = float(gn_m) if gn_m is not None else None
        nonfinite = not np.isfinite(loss) or (gn is not None
                                              and not np.isfinite(gn))
        spike = (not nonfinite and gn is not None and thr > 0 and gn > thr)
        if not (nonfinite or spike):
            self.consecutive = 0
            self.accepted += 1
            if gn is not None:
                d = self.cfg.ema_decay
                self.ema = gn if self.ema is None else d * self.ema + (1 - d) * gn
            return new_state, {**metrics, "anomalous": 0}
        # rejected: the new state is discarded, the prior one lives on
        if nonfinite:
            kind = "nonfinite"
            value = loss if not np.isfinite(loss) else gn
        else:
            kind, value = "spike", gn
        self.rejected += 1
        self.consecutive += 1
        ev = GuardEvent(step=self.accepted, kind=kind, value=value,
                        threshold=thr, consecutive=self.consecutive)
        self.events.append(ev)
        self.log(ev.describe())
        if self.consecutive >= self.cfg.k_rollback:
            streak, self.consecutive = self.consecutive, 0
            raise AnomalyRollback(
                f"guard: {streak} consecutive anomalous steps (last: {kind} "
                f"value={value:.4g}) — requesting checkpoint rollback",
                rejects=streak, state=state)
        return state, {**metrics, "anomalous": 1}
