"""Training launcher: PICASSO hybrid training of any WDL arch on the local
device set (or a forced host-device mesh), with checkpointing + fault
tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch deepfm --smoke \\
      --steps 100 --global-batch 256 --devices 8 --mesh 4x2

``main(argv, cfg)`` is the same run called from Python (``chip_smoke.py``
drives it so): ``cfg`` replaces the ``--arch``/``--smoke`` config, and the
return value is the run record.
"""
import argparse
import os
import time


def main(argv=None, cfg=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return
    ``{"history": [...], "batches": {...}}``: one ``{step, loss, cache_hits,
    overflow, seconds}`` entry per logged step (``seconds`` is the wall time
    per step since the previous log line, first step's compile included),
    and the batch prefetcher's ``produced``/``backup_served`` counters."""
    # registry import is jax-importing but backend-lazy: XLA_FLAGS set after
    # parsing (for --devices) is still honoured at first device query.
    from repro.engine import AUTO_NAMES, available_strategies

    names = available_strategies()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepfm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--devices", type=int, default=0, help="force host device count")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 (data x model)")
    ap.add_argument("--strategy", default="picasso",
                    choices=names + AUTO_NAMES,
                    help="EmbeddingEngine lookup strategy: one of "
                         f"{', '.join(names)} (broadcast to every packed "
                         f"group), or {'/'.join(AUTO_NAMES)} for the "
                         "per-group cost-model assignment")
    ap.add_argument("--l2-budget", type=int, default=0, metavar="BYTES",
                    help="host-memory L2 cache budget in bytes (0 disables; "
                         ">0 budgets an L2 tier behind the hot tier, used by "
                         "picasso_l2 and offered to the mixed/auto cost model)")
    ap.add_argument("--narrow-dim", type=int, default=0, metavar="D",
                    help="narrow master width for the picasso_narrow "
                         "hot/cold split (0 disables): cold ids are stored "
                         "and routed at this width and projected up to the "
                         "model dim at lookup, hot ids stay full-width in "
                         "the cache tiers; used by picasso_narrow and "
                         "offered to the mixed/auto cost model")
    ap.add_argument("--replan-iters", type=int, default=0, metavar="N",
                    help="adaptive replanning: every N steps harvest the live "
                         "FCounter, recompile tier budgets + the strategy "
                         "assignment from measured skew, and migrate state to "
                         "the new plan revision (0 disables)")
    ap.add_argument("--replan-hot-bytes", type=int, default=None,
                    metavar="BYTES",
                    help="hot-tier byte envelope for replan re-budgets "
                         "(default: keep the plan's compile-time envelope; "
                         "an explicit value retunes tier capacity at runtime)")
    ap.add_argument("--replan-l2-bytes", type=int, default=None,
                    metavar="BYTES",
                    help="L2 byte envelope for replan re-budgets (default: "
                         "keep the plan's compile-time envelope)")
    ap.add_argument("--pin-l2", action="store_true",
                    help="place L2 host-tier leaves (and narrow masters) in "
                         "pinned host memory, kept there across steps by "
                         "memory-kind-aware jit shardings (no-op on backends "
                         "without pinned_host, e.g. the CPU rig)")
    ap.add_argument("--calibrate", default="off",
                    choices=("auto", "force", "off"),
                    help="measured cost model for mixed/auto assignment and "
                         "replanning: 'auto' loads the backend-stamped "
                         "calibration file (--calib-file) or microbenches "
                         "the priced ops once and writes it, 'force' always "
                         "re-benches, 'off' keeps the hand-tuned constant "
                         "model (the default; bit-identical to previous "
                         "releases)")
    ap.add_argument("--calib-file", default="", metavar="PATH",
                    help="calibration cache location for --calibrate "
                         "(default: ~/.cache/repro/calibration.json); reused "
                         "only when its backend stamp matches this process")
    ap.add_argument("--fused-kernels", default="auto",
                    choices=("auto", "on", "off"),
                    help="fused Pallas sparse kernels (gather+pool custom "
                         "VJP, dedup+adagrad scatter, tier probes): 'auto' "
                         "uses them on a TPU (each op as the printed kernel "
                         "table allows) and off-TPU under "
                         "REPRO_FORCE_PALLAS_INTERPRET=1, 'on' forces them "
                         "(interpreted off-TPU, slow), 'off' forces the "
                         "reference jnp chains")
    ap.add_argument("--overlap", default="auto",
                    choices=("off", "on", "auto"),
                    help="software-pipelined train step: 'on' double-buffers "
                         "the sparse lookup of micro-batch i+1 behind a "
                         "handoff barrier while the dense stage of i runs, "
                         "'off' keeps the legacy (jaxpr-pinned) loop, 'auto' "
                         "enables overlap whenever the step has >1 "
                         "micro-batch; numerics are identical either way")
    ap.add_argument("--grad-compress", default="none",
                    choices=("none", "fp16", "topk"),
                    help="wire compression of the routed sparse-gradient "
                         "payload (the transposed-Shuffle all_to_all and the "
                         "PS/allgather_rows gradient all_gather): 'fp16' = "
                         "per-row amax-scaled float16 cast, 'topk' = per-row "
                         "magnitude top-(D/4) sparsification, 'none' keeps "
                         "training bitwise-exact")
    ap.add_argument("--reshard-to", default="", metavar="MESH",
                    help="elastic reshard target mesh, e.g. 2x2 or 4: at "
                         "--reshard-at the run recuts the plan for the new "
                         "world size, permutes the live state exactly (every "
                         "master row, adagrad slot, and FCounter survives "
                         "bitwise), rebuilds the jitted step, and continues "
                         "on the first prod(MESH) devices without restart")
    ap.add_argument("--reshard-at", type=int, default=0, metavar="STEP",
                    help="step at which to apply --reshard-to (0 with "
                         "--reshard-to set reshards at the first segment "
                         "boundary)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming driver: consume the unbounded batch "
                         "stream in --stream-segments segments of "
                         "--segment-steps (ignoring --steps), checkpoint "
                         "incrementally per segment, publish model deltas "
                         "to --publish-dir, and apply --reshard-to in place "
                         "at a segment boundary")
    ap.add_argument("--segment-steps", type=int, default=20, metavar="N",
                    help="steps per streaming segment (the checkpoint/"
                         "publish/resize granularity of --stream)")
    ap.add_argument("--stream-segments", type=int, default=3, metavar="K",
                    help="number of streaming segments to run under --stream")
    ap.add_argument("--publish-dir", default="", metavar="DIR",
                    help="streaming mode: publish the serveable state subset "
                         "(emb+dense) here at every segment boundary, with "
                         "an atomic LATEST pointer a running "
                         "repro.launch.serve --reload-dir process picks up "
                         "without restart")
    ap.add_argument("--guard", action="store_true",
                    help="numeric anomaly guard: wrap the jitted step with "
                         "NaN/Inf-loss and grad-norm-spike detection (EMA "
                         "threshold); an anomalous step is rejected in-jit "
                         "(prior state kept bitwise, batch skipped, event "
                         "logged), and K consecutive rejections roll back "
                         "to the last verified checkpoint")
    ap.add_argument("--chaos", default="", metavar="SPEC",
                    help="deterministic fault injection for recovery-path "
                         "testing: comma-separated kind@step tokens, kinds "
                         "nan (poison batch), crash (raise at step), ckpt "
                         "(corrupt newest checkpoint on disk), torn (tear "
                         "the published delta); e.g. 'nan@7,crash@13,"
                         "ckpt@20,torn@45'")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--no-interleave", action="store_true")
    ap.add_argument("--no-packing", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--learnable", action="store_true",
                    help="synthetic stream with a learnable CTR signal "
                         "(default: random labels) — smoke/CI runs assert "
                         "loss decrease on this")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lr-emb", type=float, default=0.05)
    ap.add_argument("--lr-dense", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.replan_iters < 0:
        ap.error("--replan-iters must be >= 0 (0 disables replanning)")
    if args.reshard_at and not args.reshard_to:
        ap.error("--reshard-at needs --reshard-to")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import logging

    import jax
    import numpy as np

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    # recovery events (rollbacks, quarantines, counter resets) are the
    # operator's window into the fault-tolerance subsystem: surface the
    # repro loggers at INFO without turning every library chatty
    logging.basicConfig(format="[%(name)s] %(levelname)s: %(message)s")
    logging.getLogger("repro").setLevel(logging.INFO)

    from repro.configs import get_config
    from repro.core.packing import make_plan
    from repro.data.pipeline import ReplayableStream, device_put_stream
    from repro.data.synthetic import batch_stream
    from repro.dist.sharding import batch_specs, to_named
    from repro.embedding.state import pin_l2_to_host, warn_pin_l2_limits
    from repro.launch.mesh import make_mesh
    from repro.models.wdl import WDLModel
    from repro.runtime import (AnomalyGuard, ChaosController, Replanner,
                               apply_plan_meta, parse_fault_plan,
                               parse_mesh_shape, plan_meta,
                               publish_state, reshard_live, restore_elastic,
                               run_stream)
    from repro.train.checkpoint import (AsyncCheckpointer, latest_step,
                                        load_checkpoint_meta)
    from repro.train.fault_tolerance import Supervisor
    from repro.train.train_step import TrainConfig, init_state, make_train_step

    nd = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (nd, 1)
    axes = ("data", "model")[: len(shape)]
    mesh = make_mesh(shape, axes)
    world = int(np.prod(shape))

    cost_model = None
    if args.calibrate != "off":
        from repro.perf import get_cost_model
        cost_model = get_cost_model(
            args.calibrate, args.calib_file or None,
            grid="tiny" if args.smoke else "small",
            log=lambda s: print(f"[train] calib {s}", flush=True))

    if cfg is None:
        cfg = get_config(args.arch, smoke=args.smoke)
    plan = make_plan(cfg, world=world, per_device_batch=args.global_batch // world,
                     enable_packing=not args.no_packing,
                     enable_cache=not args.no_cache,
                     n_micro=args.n_micro,
                     hot_bytes=1 << 24 if args.smoke else 1 << 30,
                     l2_bytes=args.l2_budget,
                     narrow_dim=args.narrow_dim or None,
                     flush_iters=20, warmup_iters=10,
                     mesh_shape=shape)
    meta = None
    if args.ckpt_dir:
        # a checkpointed run may have replanned: revise the structural plan
        # back to the checkpointed revision BEFORE shaping state/templates
        meta = load_checkpoint_meta(args.ckpt_dir)
        if meta is not None:
            plan = apply_plan_meta(plan, meta)
            print(f"[train] resumed plan rev {plan.rev} from checkpoint meta "
                  f"(strategy: {sorted(set(plan.strategy.values()))})")
    from repro.engine import maybe_compile
    from repro.kernels import ops
    if plan.strategy:
        # the plan already carries an assignment (checkpoint meta) — 'mixed'
        # makes every engine follow it instead of recompiling from priors
        strategy = "mixed"
    else:
        # per_device_batch=None: training issues plan.microbatch ids per step
        strategy = maybe_compile(plan, args.strategy,
                                 use_cache=not args.no_cache,
                                 cost_model=cost_model,
                                 log=lambda s: print(f"[train] {s}"))

    def wrap_timed(fn):
        """Measured-vs-predicted feedback: time each step (blocking on the
        loss scalar) and feed the wall time to the Replanner. Only wrapped
        when a calibrated cost model is live — the per-step sync it costs is
        exactly what the feedback loop needs to be honest."""
        if cost_model is None:
            return fn

        def timed(state, batch):
            t0 = time.perf_counter()
            out = fn(state, batch)
            jax.block_until_ready(out[1]["loss"])
            if replanner is not None:
                replanner.observe_timing((time.perf_counter() - t0) * 1e6)
            return out
        return timed

    guard = None
    if args.guard:
        guard = AnomalyGuard(log=lambda s: print(f"[train] {s}", flush=True))
    chaos = None
    if args.chaos:
        chaos = ChaosController(parse_fault_plan(args.chaos))
        print(f"[train] chaos plan armed: {args.chaos}", flush=True)

    cur_shardings = None  # NamedShardings of the live step's state output

    def build_step(plan):
        """(Re)build the jitted step against a plan revision. The guard (if
        armed) re-wraps the fresh step, carrying its EMA/event history across
        replan/reshard rebuilds; ``cur_shardings`` tracks the state placement
        so the Supervisor restores onto the correct devices."""
        nonlocal cur_shardings
        model = WDLModel(cfg, plan)
        spec = "mixed" if plan.strategy else strategy
        tcfg = TrainConfig(strategy=spec, use_cache=not args.no_cache,
                           use_interleave=not args.no_interleave,
                           use_fused_kernels=args.fused_kernels,
                           overlap=args.overlap,
                           grad_compress=args.grad_compress,
                           pin_l2=args.pin_l2,
                           lr_emb=args.lr_emb, lr_dense=args.lr_dense)
        # the guard needs the prior state alive to reject a step, so a
        # guarded step is built without buffer donation (bitwise-identical
        # numerics, higher peak memory — see runtime/guard.py)
        raw, sspecs = make_train_step(model, plan, mesh, axes,
                                      args.global_batch, tcfg,
                                      donate=guard is None)
        cur_shardings = to_named(mesh, sspecs)
        fn = guard.rebind(raw) if guard is not None else raw
        return model, tcfg, wrap_timed(fn)

    replanner = None
    model, tcfg, step_fn = build_step(plan)
    state = init_state(model, plan, jax.random.PRNGKey(args.seed), mesh=mesh, axes=axes)
    if args.pin_l2:
        warn_pin_l2_limits()  # one-time: unsupported-backend no-op notice
        state = pin_l2_to_host(state, mesh)

    if args.replan_iters:
        replanner = Replanner(
            plan, mesh, axes, strategy=args.strategy,
            hot_bytes=args.replan_hot_bytes, l2_bytes=args.replan_l2_bytes,
            use_cache=not args.no_cache, cache_update=tcfg.cache_update,
            cost_model=cost_model, pin_l2=args.pin_l2,
            log=lambda s: print(f"[train] replan {s}", flush=True))

    print(f"[train] {cfg.name}: {len(plan.groups)} packed groups, "
          f"micro={plan.microbatch}, ilv={len(plan.interleave)} waves, "
          f"world={world}, plan rev={plan.rev}")
    for line in ops.dispatch_lines(ops.resolve_fused(args.fused_kernels)):
        print(f"[train] {line}")

    # positional stream factory: ``make_source(i)`` opens the synthetic
    # stream at absolute batch index ``i`` on the CURRENT mesh (read at call
    # time, so a post-reshard rewrap targets the new device set). The
    # ReplayableStream on top gives the Supervisor an exact rewind after a
    # checkpoint rollback; prefetched-but-unconsumed batches lost when a
    # reshard closes the Prefetcher are simply regenerated, not skipped.
    def make_source(start):
        return device_put_stream(
            batch_stream(cfg, args.global_batch, seed=args.seed,
                         learnable=args.learnable, start=start),
            mesh, lambda b: batch_specs(b, axes))

    source = ReplayableStream(make_source)
    stream = source if chaos is None else chaos.wrap_stream(source)

    active_ckpt = None  # the live AsyncCheckpointer (chaos ckpt@ targets it)
    history = []
    t_log, step_log = time.perf_counter(), 0  # time and step of the last log

    def on_metrics(step, m):
        nonlocal t_log, step_log
        if replanner is not None:
            replanner.observe(m)
        if step % args.log_every == 0:
            rec = {"step": step, "loss": float(m["loss"]),
                   "cache_hits": int(m["cache_hits"]),
                   "overflow": int(m["overflow"])}
            now = time.perf_counter()  # float(loss) above waited for the step
            rec["seconds"] = (now - t_log) / max(step - step_log, 1)
            t_log, step_log = now, step
            history.append(rec)
            print(f"  step {step:5d} loss={rec['loss']:.4f} "
                  f"hits={rec['cache_hits']} ovf={rec['overflow']} "
                  f"{rec['seconds'] * 1e3:.1f}ms/step", flush=True)
        if chaos is not None:
            if args.ckpt_dir:
                chaos.after_checkpoint(step, args.ckpt_dir, active_ckpt)
            # raised here (inside the Supervisor's try block / run_stream's
            # step loop) a crash@ fault exercises the real recovery path in
            # BOTH driver modes: in-process restore+rewind under the
            # Supervisor, process-restart resume under --stream
            chaos.injector(step)

    reshard_pending = bool(args.reshard_to)

    def do_reshard(state, step):
        """In-place elastic reshard to --reshard-to: recut the plan, permute
        the state exactly, re-place it on the sub-mesh, rebuild the jitted
        step, and re-wrap the batch source. One-shot."""
        nonlocal plan, model, tcfg, step_fn, mesh, world, reshard_pending
        new_shape = parse_mesh_shape(args.reshard_to, len(axes))
        new_world = int(np.prod(new_shape))
        reshard_pending = False  # applied (or a no-op) — never re-fires
        if new_world == world:
            return state
        if args.global_batch % new_world:
            raise SystemExit(f"[train] --reshard-to {args.reshard_to}: "
                             f"global batch {args.global_batch} not divisible "
                             f"by new world {new_world}")
        print(f"[train] reshard world {world} -> {new_world} "
              f"(mesh {'x'.join(map(str, new_shape))}) at step {step}",
              flush=True)
        new_mesh = make_mesh(new_shape, axes)
        plan, state = reshard_live(
            plan, state, new_world, args.global_batch // new_world,
            mesh=new_mesh, axes=axes, mesh_shape=new_shape,
            use_cache=not args.no_cache, cache_update=tcfg.cache_update)
        mesh, world = new_mesh, new_world
        model, tcfg, step_fn = build_step(plan)  # build_step reads `mesh`
        # same factory, new mesh (make_source reads `mesh` at call time):
        # the old Prefetcher is closed and the stream reopens at its current
        # position on the new device set
        stream.rewrap(make_source)
        if replanner is not None:
            replanner.plan, replanner.mesh = plan, mesh
        if args.pin_l2:
            state = pin_l2_to_host(state, mesh)
        return state

    def next_boundary(step):
        """Next replan/reshard step strictly after ``step``."""
        ri = args.replan_iters
        b = min(args.steps, (step // ri + 1) * ri) if ri else args.steps
        if reshard_pending and step < args.reshard_at:
            b = min(b, args.reshard_at)
        return b

    def do_replan(state, step):
        """Harvest + recompile; on a real change, migrate + rebuild the step.
        Returns (state, migrated?)."""
        nonlocal plan, model, tcfg, step_fn
        out = replanner.maybe_replan(state, step=step)
        if out is None:
            return state, False
        plan, state = out
        model, tcfg, step_fn = build_step(plan)
        if args.pin_l2:
            state = pin_l2_to_host(state, mesh)
        return state, True

    if args.stream:
        # streaming driver: segments over the unbounded stream (--steps is
        # ignored); each segment boundary checkpoints, publishes, and may
        # apply the in-place reshard — no restart anywhere in the lifecycle
        ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        active_ckpt = ckpt
        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            state, start = restore_elastic(
                args.ckpt_dir, plan, state, mesh=mesh, axes=axes,
                log=lambda s: print(f"[train] elastic {s}", flush=True))
            stream.seek(start)  # resume replays from the exact batch index
            print(f"[train] stream resumed at step {start}", flush=True)

        publisher = None
        if args.publish_dir:
            def publisher(step, state):
                publish_state(args.publish_dir, step, state,
                              meta=plan_meta(plan))
                print(f"[stream] published step {step} -> {args.publish_dir}",
                      flush=True)
                if chaos is not None:
                    chaos.after_publish(step, args.publish_dir)

        def on_segment(seg, step, state):
            if reshard_pending and step >= args.reshard_at:
                state = do_reshard(state, step)
                return state, step_fn, stream
            return None

        state, last = run_stream(
            state, step_fn, stream,
            segment_steps=args.segment_steps,
            n_segments=args.stream_segments, start_step=start,
            checkpointer=ckpt, meta_fn=lambda: plan_meta(plan),
            publisher=publisher, on_metrics=on_metrics,
            on_segment=on_segment)
        if ckpt is not None:
            ckpt.wait()
        batches = source.stats
        stream.close()
        print(f"[train] batches {batches}")
        print(f"[train] stream done at step {last} (world={world})")
        return {"history": history, "batches": batches}

    if args.ckpt_dir:
        sup = Supervisor(args.ckpt_dir, ckpt_every=args.ckpt_every,
                         shardings=cur_shardings)
        active_ckpt = sup.ckpt
        # keep the plan sidecar on EVERY checkpoint: it records the world/
        # mesh the state was written under (elastic-restore detection) and —
        # for replanned runs — the plan revision; dropping it would make the
        # NEXT resume restore revision-shaped tiers into the seed-plan
        # template or shape-error on a world change
        sup.meta = plan_meta(plan)
        if meta is not None and int(meta.get("world", world)) != world:
            # checkpoint written at a different world size: route the restore
            # through the exact resharding path instead of the stale template
            state, start = restore_elastic(
                args.ckpt_dir, plan, state, mesh=mesh, axes=axes,
                log=lambda s: print(f"[train] elastic {s}", flush=True))
        else:
            state, start = sup.maybe_restore(state)
        stream.seek(start)  # resume replays from the exact batch index
        step = start
        # known limitation: a failure-restore *inside* a segment replays the
        # restored window without re-hitting an already-passed replan
        # boundary (the plan itself stays consistent — post-migration
        # checkpoints are written eagerly — but the replayed steps are folded
        # into the Replanner's metric window a second time, and the next
        # replan happens at the segment end rather than mid-replay)
        while step < args.steps:
            seg_end = next_boundary(step)
            state = sup.run(state, step_fn, stream, seg_end, start_step=step,
                            on_metrics=on_metrics, shardings=cur_shardings)
            step = seg_end
            if reshard_pending and step >= args.reshard_at \
                    and step < args.steps:
                state = do_reshard(state, step)
                # durable, mesh-consistent restore point: a later failure
                # must restore post-reshard row counts + the new world meta
                sup.meta = plan_meta(plan)
                sup.ckpt.save(step, state, meta=sup.meta)
                sup.ckpt.wait()
            if replanner is not None and step < args.steps:
                state, migrated = do_replan(state, step)
                if migrated:
                    # durable, plan-consistent restore point: a mid-segment
                    # failure must not restore pre-migration tier shapes
                    sup.meta = plan_meta(plan)
                    sup.ckpt.save(step, state, meta=sup.meta)
                    sup.ckpt.wait()
    else:
        it = iter(stream)
        t_log = time.perf_counter()
        for i in range(1, args.steps + 1):
            batch = next(it, None)
            if batch is None:  # the synthetic stream is endless
                raise RuntimeError(f"batch stream ended before step {i}")
            state, m = step_fn(state, batch)
            on_metrics(i, m)
            if reshard_pending and i >= args.reshard_at and i < args.steps:
                state = do_reshard(state, i)
                it = iter(stream)  # the Prefetcher was rebuilt for the new mesh
            if (replanner is not None and i % args.replan_iters == 0
                    and i < args.steps):
                state, _ = do_replan(state, i)
    if replanner is not None:
        n_mig = sum(1 for e in replanner.events if e.migrated)
        print(f"[train] replans: {len(replanner.events)} attempted, "
              f"{n_mig} migrated, final plan rev={plan.rev}")
    batches = source.stats
    stream.close()
    print(f"[train] batches {batches}")
    print("[train] done")
    return {"history": history, "batches": batches}


if __name__ == "__main__":
    main()
