"""Elastic resharding: move a live run (or a checkpoint) between world sizes.

PICASSO's deployment story is continuous delivery — daily retrains racing the
clock on whatever slice of the fleet is free — so the world size a run
*starts* at is not the world size it finishes (or serves) at. The packed row
space is world-independent by construction (scramble + table offsets derive
from raw vocabs; only the tail padding is ``_pad_to(logical, world)``), which
makes a W -> W' reshard a pure permutation:

1. ``core.packing.reshard_plan`` recuts each group's padded ``rows`` and the
   per-peer all_to_all capacities for the new shard count — every revisable
   decision (tier budgets, strategy mix, narrow widths, ``rev``) carries
   verbatim;
2. ``embedding.state.migrate_state`` (via ``_reshard_group_state``) performs
   the state-side permutation: master ``w``/``acc``/FCounter pad/truncate
   only ever padding rows, tier sentinel keys are remapped to the new
   ``rows_padded`` value, and every resident row / optimizer slot / counter
   survives bitwise;
3. ``place_state`` re-places the full state under the new mesh's
   NamedShardings — the actual all_to_all permutation of shard contents is
   ``jax.device_put`` re-laying out the logical arrays over the new mesh.

``restore_elastic`` is the checkpoint-side entry: ``plan_meta`` records the
world (and mesh shape) a checkpoint was written under, so a restore at a
different world is *detected* and routed through the same permutation instead
of shape-erroring (or worse, silently re-padding tier sentinels) against a
stale template.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax

from repro.core.packing import PicassoPlan, reshard_plan
from repro.dist.sharding import emb_specs, replicated, state_specs, to_named
from repro.embedding.state import migrate_state, reshard_state
from repro.train.checkpoint import load_checkpoint_meta, restore_checkpoint


def parse_mesh_shape(spec: Union[str, Sequence[int]], n_axes: int = 2
                     ) -> Tuple[int, ...]:
    """``'4x2'`` -> ``(4, 2)``; a bare ``'4'`` pads with 1s to ``n_axes``."""
    if isinstance(spec, (tuple, list)):
        shape = tuple(int(x) for x in spec)
    else:
        shape = tuple(int(x) for x in str(spec).lower().split("x"))
    if not shape or any(s <= 0 for s in shape):
        raise ValueError(f"mesh shape must be positive ints, got {spec!r}")
    if len(shape) < n_axes:
        shape = shape + (1,) * (n_axes - len(shape))
    return shape


def place_state(state: Any, plan: PicassoPlan, mesh, axes) -> Any:
    """``jax.device_put`` a full (or emb-only) state under ``plan``'s specs.

    This is the collective half of a reshard: the host/logical arrays are
    re-laid-out over ``mesh`` (masters row-sharded over the new world, tiers
    and dense replicated). Works for the train state (``emb/dense/opt/step``
    + any extra replicated leaves), the serve subset (``emb/dense``), or a
    bare per-group emb dict.
    """
    if isinstance(state, dict) and "emb" in state:
        specs = state_specs(plan, axes, state.get("dense"),
                            state.get("opt"))
        for k, v in state.items():
            if k not in specs:
                specs[k] = replicated(v)
        specs = {k: specs[k] for k in state}
        return jax.device_put(state, to_named(mesh, specs))
    return jax.device_put(state, to_named(mesh, emb_specs(plan, axes)))


def reshard_live(plan: PicassoPlan, state: Any, new_world: int,
                 per_device_batch: int, *, mesh=None, axes=None,
                 mesh_shape: Optional[Sequence[int]] = None,
                 use_cache: bool = True, use_l2: bool = True,
                 cache_update: str = "psum") -> Tuple[PicassoPlan, Any]:
    """One-call live reshard: recut the plan, permute the state, re-place.

    Returns ``(new_plan, new_state)``; with ``mesh=None`` the state comes
    back as host arrays (checkpoint-portability tests use this), else it is
    placed under ``mesh``'s shardings ready for a rebuilt jitted step.
    ``use_cache``/``use_l2``/``cache_update`` mirror the engine flags, same
    contract as ``migrate_state``.
    """
    new_plan = reshard_plan(plan, new_world, per_device_batch,
                            mesh_shape=mesh_shape)
    migrated = migrate_state(plan, new_plan, state, use_cache=use_cache,
                             use_l2=use_l2, cache_update=cache_update)
    if mesh is not None:
        migrated = place_state(migrated, new_plan, mesh, axes)
    return new_plan, migrated


def restore_elastic(ckpt_dir: str, plan: PicassoPlan, template: Any, *,
                    mesh=None, axes=None, step: Optional[int] = None,
                    log=None) -> Tuple[Any, int]:
    """Restore a checkpoint whose recorded world may differ from ``plan``'s.

    - recorded world matches (or the meta predates world recording): a plain
      ``restore_checkpoint`` — a *stale-meta* checkpoint at a mismatched
      world still fails, but with the row-mismatch diagnosis and the pointer
      here, not a bare shape error;
    - recorded world differs: the stored rows are pulled out as-is
      (``on_row_mismatch='keep'``) and re-cut by ``reshard_state`` — sentinel
      keys remapped, padding re-sliced, every logical row bitwise.

    ``template`` is shaped by the CURRENT plan (after ``apply_plan_meta``,
    so tier shapes already match the checkpointed revision). With ``mesh``
    the restored state is placed under ``plan``'s shardings.
    """
    log = log or (lambda s: None)
    meta = load_checkpoint_meta(ckpt_dir, step)
    world_ckpt = (meta or {}).get("world")
    if world_ckpt is not None and int(world_ckpt) != plan.world:
        state, s = restore_checkpoint(ckpt_dir, template, step=step,
                                      on_row_mismatch="keep")
        state = reshard_state(plan, state)
        log(f"restored world={int(world_ckpt)} checkpoint at "
            f"world={plan.world} (resharded step {s})")
    else:
        state, s = restore_checkpoint(ckpt_dir, template, step=step)
    if mesh is not None:
        state = place_state(state, plan, mesh, axes)
    return state, s
