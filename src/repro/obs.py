"""Names of the train step's phases, and the two ways the program marks them.

Every name a trace reader looks for is defined here once; readers import it
from this table instead of spelling it again.

``scope(name)`` is ``jax.named_scope``: every operation traced inside it
carries the name in its HLO ``op_name`` metadata (nested scopes join with
``/``), so a profiler event can be put down to the phase that issued it. It
costs nothing at run time.

``span(name)`` is ``jax.profiler.TraceAnnotation``: a host span on the
profiler's timeline, beside the device planes. It does nothing when no
profiler is running. ``step_span(n)`` marks one train step for the
profiler's step view.

Device scopes (top level, then what nests in them):

  sparse_lookup  ``EmbeddingEngine.forward``: unique, tier_probe, partition,
                 shuffle, gather, stitch, pool
  dense          the dense forward and backward, the dense gradient psum
                 and the Adam/LAMB update
  sparse_update  ``EmbeddingEngine.backward``: segment_grad, shuffle,
                 master_update, tier_update, count_frequencies
  flush          ``EmbeddingEngine.flush`` (and the step's ``lax.cond``
                 around it)
  step_misc      batch packing and the step's metric reductions
"""
from __future__ import annotations

import jax

SPARSE_LOOKUP = "sparse_lookup"
DENSE = "dense"
SPARSE_UPDATE = "sparse_update"
FLUSH = "flush"
STEP_MISC = "step_misc"
TOP_SCOPES = (SPARSE_LOOKUP, DENSE, SPARSE_UPDATE, FLUSH, STEP_MISC)

UNIQUE = "unique"
TIER_PROBE = "tier_probe"
PARTITION = "partition"
SHUFFLE = "shuffle"
GATHER = "gather"
STITCH = "stitch"
POOL = "pool"
SEGMENT_GRAD = "segment_grad"
MASTER_UPDATE = "master_update"
TIER_UPDATE = "tier_update"
COUNT_FREQUENCIES = "count_frequencies"
SCOPES = TOP_SCOPES + (UNIQUE, TIER_PROBE, PARTITION, SHUFFLE, GATHER, STITCH,
                       POOL, SEGMENT_GRAD, MASTER_UPDATE, TIER_UPDATE,
                       COUNT_FREQUENCIES)

# host spans: the batch prefetcher's consumer and producer threads, and the
# launcher's host work between steps
BATCH_WAIT = "batch_wait"
BATCH_MAKE = "batch_make"
BATCH_PUT = "batch_put"
GUARD_SYNC = "guard_sync"
REPLAN_TIMER = "replan_timer"
CHECKPOINT = "checkpoint"
PUBLISH = "publish"
SPANS = (BATCH_WAIT, BATCH_MAKE, BATCH_PUT, GUARD_SYNC, REPLAN_TIMER,
         CHECKPOINT, PUBLISH)

STEP = "train"  # the step annotation's name


def scope(name: str):
    return jax.named_scope(name)


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def step_span(step_num: int):
    return jax.profiler.StepTraceAnnotation(STEP, step_num=step_num)
