"""Runtime adaptation subsystem: the plan in motion.

``repro.core`` compiles a ``PicassoPlan`` once from structural priors;
``repro.runtime`` closes the loop at runtime — harvest the engine's live
frequency statistics, recompile the plan's revisable decisions (tier
budgets, per-group strategy mix), and migrate live training state across
plan revisions. See ``replanner`` for the full loop contract, ``elastic``
for world-size resharding (plan recut + exact state permutation + elastic
checkpoint restore), ``stream`` for the segmented streaming driver with
publish/pickup train-to-serve handoff, ``guard`` for numeric anomaly
detection/rejection, and ``chaos`` for the deterministic fault-injection
harness that proves the recovery paths.
"""
from repro.runtime.chaos import (ChaosController, ChaosFailure, ChaosStream,
                                 FaultPlan, parse_fault_plan)
from repro.runtime.elastic import (parse_mesh_shape, place_state,
                                   reshard_live, restore_elastic)
from repro.runtime.guard import AnomalyGuard, AnomalyRollback, GuardConfig
from repro.runtime.replanner import (ReplanEvent, Replanner, apply_plan_meta,
                                     plan_delta, plan_meta)
from repro.runtime.stream import (PublishPoller, load_published,
                                  poll_published, publish_state, run_stream)

__all__ = [
    "AnomalyGuard",
    "AnomalyRollback",
    "ChaosController",
    "ChaosFailure",
    "ChaosStream",
    "FaultPlan",
    "GuardConfig",
    "PublishPoller",
    "ReplanEvent",
    "Replanner",
    "apply_plan_meta",
    "load_published",
    "parse_fault_plan",
    "parse_mesh_shape",
    "place_state",
    "plan_delta",
    "plan_meta",
    "poll_published",
    "publish_state",
    "reshard_live",
    "restore_elastic",
    "run_stream",
]
