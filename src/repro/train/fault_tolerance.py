"""Fault tolerance for the training loop (designed for 1000+ nodes).

Mechanisms (paper §V notes Alibaba runs separate in-house failover [44,45];
here we build the framework-level pieces a deployment needs):

1. *Checkpoint/restart*: AsyncCheckpointer snapshots every N steps; on a
   transient step failure the supervisor restores the last *verified*
   checkpoint (per-leaf checksums; corrupt snapshots are quarantined and the
   chain falls back — see checkpoint.restore_verified) and rewinds the data
   stream to the restored step (ReplayableStream + per-index batch seeding),
   so replay is exact.
2. *Failure classification*: not every exception deserves a retry. Transient
   faults (node loss, I/O, numeric rollback requests) restore + replay under
   capped exponential backoff; fatal faults (shape/type/tracing errors,
   OOM of the host process, import breakage) re-raise immediately — retrying
   a deterministic bug burns the retry budget and hides the stack trace.
3. *Elastic re-mesh*: checkpoints are world-size independent (see
   checkpoint.py); ``Supervisor.remesh`` rebuilds plan/step for a new device
   count and reloads — scale-down on failure, scale-up on recovery.
4. *Straggler mitigation*: SPMD sync training has no PS-side stragglers; the
   residual risk is the input pipeline, handled by Prefetcher backup batches
   (data/pipeline.py). Cross-pod collectives use the hierarchical schedule
   planned by the mesh (pod axis outermost) so one slow DCI link bounds only
   the pod-level phase.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax

from repro import obs
from repro.train.checkpoint import AsyncCheckpointer, latest_step, restore_verified

log = logging.getLogger("repro.ft")


class StepFailure(RuntimeError):
    pass


#: exception types where a restore-and-replay retry cannot help: the same
#: code will deterministically fail again (tracing/shape/type bugs, kernel
#: lowering refusals, broken imports) or the process itself is compromised
#: (host OOM).
FATAL_TYPES = (TypeError, AttributeError, ImportError, NameError, MemoryError,
               ValueError, NotImplementedError)

#: message markers of device-side failures a retry cannot help: the device
#: is out of memory, or the compiler (XLA or Mosaic) refused the program
FATAL_MARKERS = ("RESOURCE_EXHAUSTED", "failed to compile",
                 "compile permanent error")


def classify_failure(e: BaseException) -> str:
    """'transient' (restore + replay may succeed) or 'fatal' (re-raise).

    Transient is the default: node loss, filesystem hiccups, injected chaos,
    and guard rollback requests all surface as RuntimeError/OSError
    subclasses. ``AnomalyRollback`` is transient by construction — the whole
    point of raising it is to trigger the restore path. An exception is
    fatal when it, or any exception it was raised from, is a
    ``FATAL_TYPES`` instance or names a ``FATAL_MARKERS`` failure.
    """
    while e is not None:
        if isinstance(e, FATAL_TYPES) or any(m in str(e)
                                             for m in FATAL_MARKERS):
            return "fatal"
        e = e.__cause__
    return "transient"


class Supervisor:
    """Wraps a train loop with checkpoint/restart + classified, bounded
    retries.

    ``shardings`` (settable at construction, via ``maybe_restore``/``run``,
    or directly after a reshard) are used for every restore so recovered
    state lands on the correct devices — the old retry path restored onto
    host-default placement and then trained cross-device.

    ``reset_after`` successful consecutive steps clear the failure counter:
    the retry budget bounds *failure density*, not total failures over an
    arbitrarily long run (three transient faults a day apart should never
    exhaust ``max_retries=3``). Default: two checkpoint intervals.
    """

    def __init__(self, ckpt_dir: str, ckpt_every: int = 100, max_retries: int = 3,
                 keep: int = 3, backoff_s: float = 0.5, backoff_cap_s: float = 30.0,
                 reset_after: Optional[int] = None, shardings: Any = None):
        self.ckpt = AsyncCheckpointer(ckpt_dir, keep=keep)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.reset_after = reset_after if reset_after is not None else 2 * ckpt_every
        self.failures = 0        # current failure density (resets on progress)
        self.total_failures = 0  # monotonic, for observability
        self.shardings = shardings
        # JSON sidecar written with every checkpoint (the trainer keeps this
        # pointing at the live plan revision — repro.runtime.plan_meta — and
        # refreshes it after each replan/migration)
        self.meta: Optional[Dict[str, Any]] = None

    def maybe_restore(self, template: Any, shardings: Any = None
                      ) -> Tuple[Any, int]:
        if shardings is not None:
            self.shardings = shardings
        try:
            state, step = restore_verified(self.ckpt_dir, template,
                                           shardings=self.shardings,
                                           log=log.warning)
        except FileNotFoundError:
            return template, 0
        log.info("restored checkpoint at step %d", step)
        return state, step

    def run(self, state: Any, step_fn: Callable, batches: Iterator,
            n_steps: int, start_step: int = 0,
            on_metrics: Optional[Callable[[int, Dict], None]] = None,
            fail_injector: Optional[Callable[[int], None]] = None,
            shardings: Any = None) -> Any:
        """Run ``n_steps``; on transient failure restore + replay, on fatal
        failure re-raise. ``fail_injector`` is the test hook that raises
        inside the loop to simulate node loss. If ``batches`` has a
        ``seek(step)`` method (ReplayableStream) the stream is rewound to
        the restored step so replay is exact; otherwise a warning notes the
        skipped batches."""
        if shardings is not None:
            self.shardings = shardings
        template = jax.tree.map(lambda x: x, state)
        step = start_step
        stream = iter(batches)
        seekable = hasattr(batches, "seek")
        warned_no_seek = False
        clean = 0  # consecutive successful steps since the last failure
        while step < n_steps:
            try:
                if fail_injector is not None:
                    fail_injector(step)
                batch = next(stream)
                with obs.step_span(step + 1):
                    state, metrics = step_fn(state, batch)
                step += 1
                clean += 1
                if self.failures and clean >= self.reset_after:
                    log.info("%d clean steps; resetting failure counter "
                             "(was %d)", clean, self.failures)
                    self.failures = 0
                if on_metrics is not None:
                    on_metrics(step, metrics)
                if step % self.ckpt_every == 0:
                    self.ckpt.save(step, state, meta=self.meta)
            except StopIteration:
                break
            except Exception as e:  # noqa: BLE001 — classified below
                if classify_failure(e) == "fatal":
                    log.error("step %d failed with fatal %s: %s — not "
                              "retrying", step, type(e).__name__, e)
                    raise
                self.failures += 1
                self.total_failures += 1
                clean = 0
                if self.failures > self.max_retries:
                    raise
                delay = min(self.backoff_s * (2 ** (self.failures - 1)),
                            self.backoff_cap_s)
                log.warning("step %d failed (%s: %s); restoring after %.2fs "
                            "backoff (failure %d/%d)", step,
                            type(e).__name__, e, delay, self.failures,
                            self.max_retries)
                if delay > 0:
                    time.sleep(delay)
                self.ckpt.wait()
                try:
                    state, step = restore_verified(self.ckpt_dir, template,
                                                   shardings=self.shardings,
                                                   log=log.warning)
                    log.info("rolled back to step %d", step)
                except FileNotFoundError:
                    # no verifiable checkpoint yet: restart from in-memory
                    # state. An AnomalyRollback carries the surviving
                    # (rejection-preserved) state — the caller's copy was
                    # donated to the guarded step.
                    recovered = getattr(e, "state", None)
                    if recovered is not None:
                        state = recovered
                    log.warning("no verifiable checkpoint; continuing from "
                                "in-memory state at step %d", step)
                if seekable:
                    batches.seek(step)
                    stream = iter(batches)
                elif not warned_no_seek:
                    warned_no_seek = True
                    log.warning("batch stream is not seekable; batches "
                                "between checkpoint and failure steps will "
                                "be skipped, replay is NOT exact")
        self.ckpt.wait()
        return state
