"""Serving launcher: batched scoring or two-tower retrieval.

  PYTHONPATH=src python -m repro.launch.serve --arch deepfm --smoke \\
      --batch 512 --devices 8 --mesh 4x2
  PYTHONPATH=src python -m repro.launch.serve --arch sasrec --smoke --retrieval
"""
import argparse
import os


def main():
    # jax-importing but backend-lazy (see launch/train.py)
    from repro.engine import AUTO_NAMES, available_strategies

    names = available_strategies()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepfm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--n-requests", type=int, default=10)
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--candidates", type=int, default=65536)
    ap.add_argument("--n-candidates", type=int, default=None, metavar="N",
                    help="retrieval candidate count (canonical spelling; "
                         "falls back to --candidates when omitted). With "
                         "--score-chunk, N is no longer bound by per-shard "
                         "memory: chunked scoring streams a running top-k")
    ap.add_argument("--score-chunk", type=int, default=0, metavar="C",
                    help="retrieval: score the local candidate slice in "
                         "fixed chunks of C ids with a streaming top-k "
                         "merge (bounds per-shard memory; 0 = one chunk)")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--strategy", default="picasso",
                    choices=names + AUTO_NAMES,
                    help="EmbeddingEngine lookup strategy: one of "
                         f"{', '.join(names)} (broadcast to every packed "
                         f"group), or {'/'.join(AUTO_NAMES)} for the "
                         "per-group cost-model assignment")
    ap.add_argument("--l2-budget", type=int, default=0, metavar="BYTES",
                    help="host-memory L2 cache budget in bytes (0 disables; "
                         ">0 budgets an L2 tier behind the hot tier for the "
                         "scoring path)")
    ap.add_argument("--narrow-dim", type=int, default=0, metavar="D",
                    help="narrow master width for picasso_narrow groups "
                         "(0 disables): cold ids are stored at D columns and "
                         "up-projected at lookup; takes effect for groups "
                         "assigned 'picasso_narrow' (broadcast it or let "
                         "mixed/auto pick it per group)")
    ap.add_argument("--pin-l2", action="store_true",
                    help="place L2 host-tier leaves in pinned host memory "
                         "(pin_l2_to_host; no-op on backends without "
                         "pinned_host, e.g. the CPU rig)")
    ap.add_argument("--calibrate", default="off",
                    choices=("auto", "force", "off"),
                    help="measured cost model for the mixed/auto assignment: "
                         "'auto' loads the backend-stamped calibration file "
                         "(--calib-file) or benches once and writes it, "
                         "'force' always re-benches, 'off' (default) keeps "
                         "the constant model")
    ap.add_argument("--calib-file", default="", metavar="PATH",
                    help="calibration cache for --calibrate (default: "
                         "~/.cache/repro/calibration.json); reused only when "
                         "its backend stamp matches this process")
    ap.add_argument("--fused-kernels", default="auto",
                    choices=("auto", "on", "off"),
                    help="fused Pallas sparse kernels: 'auto' on a TPU "
                         "(as the printed kernel table allows) and off-TPU "
                         "under REPRO_FORCE_PALLAS_INTERPRET=1, 'on' forces "
                         "them, 'off' forces the jnp reference")
    ap.add_argument("--reload-dir", default="", metavar="DIR",
                    help="pick up model deltas a streaming trainer publishes "
                         "(repro.launch.train --stream --publish-dir DIR): "
                         "before each request, poll DIR/LATEST and hot-swap "
                         "the emb+dense state in place — no restart; deltas "
                         "published at a different world size are resharded "
                         "onto this server's mesh on load")
    ap.add_argument("--chaos", default="", metavar="SPEC",
                    help="fault injection for the serve reload path: "
                         "'torn@i' tears the newest published delta on disk "
                         "before request i (needs --reload-dir) — degraded-"
                         "mode serving must keep answering from the last "
                         "good state instead of crashing")
    args = ap.parse_args()
    if args.chaos and not args.reload_dir:
        ap.error("--chaos needs --reload-dir (faults target published deltas)")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.core.packing import make_plan
    from repro.engine import maybe_compile, resolve_assignment
    from repro.data.synthetic import make_batch
    from repro.dist.sharding import batch_specs, to_named
    from repro.launch.mesh import make_mesh
    from repro.models.wdl import WDLModel
    from repro.serve.serve_step import ServeConfig, make_retrieval_step, make_serve_step
    from repro.train.train_step import init_state

    nd = len(jax.devices())
    shape = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else (nd, 1)
    axes = ("data", "model")[: len(shape)]
    mesh = make_mesh(shape, axes)
    world = int(np.prod(shape))

    cost_model = None
    if args.calibrate != "off":
        from repro.perf import get_cost_model
        cost_model = get_cost_model(
            args.calibrate, args.calib_file or None,
            grid="tiny" if args.smoke else "small",
            log=lambda s: print(f"[serve] calib {s}", flush=True))

    def serve_cfg(plan, per_dev_batch, use_cache=True):
        # serving has no micro pipeline: the engine issues the full local
        # batch per step, so that is the id volume the cost model sees
        spec = maybe_compile(plan, args.strategy, per_device_batch=per_dev_batch,
                             use_cache=use_cache, cost_model=cost_model,
                             log=lambda s: print(f"[serve] {s}"))
        # record broadcast assignments (notably 'picasso_narrow', which
        # gates the master widths) on the plan before init_state sizes it
        resolve_assignment(plan, spec, world=world, use_cache=use_cache)
        return ServeConfig(strategy=spec, use_cache=use_cache,
                           use_fused_kernels=args.fused_kernels)

    from repro.kernels import ops
    for line in ops.dispatch_lines(ops.resolve_fused(args.fused_kernels)):
        print(f"[serve] {line}")

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.retrieval:
        plan = make_plan(cfg, world=world, per_device_batch=1, enable_cache=False,
                         exact_capacity=True, narrow_dim=args.narrow_dim or None)
        model = WDLModel(cfg, plan)
        n_cand = args.n_candidates or args.candidates
        nc = (n_cand // world) * world
        chunk = args.score_chunk or nc // world
        # the candidate tower dominates retrieval lookups: size the cost
        # model to its per-shard score chunk, not the batch-of-1 user tower
        from repro.core.features import field_index
        item_field = next(f.name for f in cfg.fields
                          if f.pooling == "none" and f.max_len > 1)
        ips = plan.group(field_index(plan)[item_field].gid).ids_per_sample
        proxy_batch = max(1, min(chunk, nc // world) // max(ips, 1))
        # resolve the strategy before init_state: a 'picasso_narrow'
        # assignment is recorded on the plan and gates the master widths
        scfg = serve_cfg(plan, proxy_batch, use_cache=False)
        state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
        step = make_retrieval_step(model, plan, mesh, axes, nc, top_k=10,
                                   scfg=scfg, score_chunk=args.score_chunk)
        user = make_batch(cfg, 1, np.random.default_rng(1))
        from jax.sharding import NamedSharding, PartitionSpec as P
        cand = jax.device_put(jnp.arange(nc, dtype=jnp.int32) % cfg.fields[0].vocab,
                              NamedSharding(mesh, P(axes)))
        scores, ids = step(state, user, cand)
        print("top-10:", np.asarray(ids), np.round(np.asarray(scores), 3))
        return

    plan = make_plan(cfg, world=world, per_device_batch=args.batch // world,
                     l2_bytes=args.l2_budget,
                     narrow_dim=args.narrow_dim or None,
                     mesh_shape=shape)
    if args.reload_dir:
        # shape the serve state by the PUBLISHED plan revision (tier budgets,
        # strategy, narrow widths) so hot-swapped deltas drop straight in;
        # rows still follow THIS server's world (deltas reshard on load)
        from repro.runtime import apply_plan_meta
        from repro.train.checkpoint import load_checkpoint_meta
        pub_meta = load_checkpoint_meta(args.reload_dir)
        if pub_meta is not None:
            plan = apply_plan_meta(plan, pub_meta)
            print(f"[serve] following published plan rev {plan.rev} "
                  f"from {args.reload_dir}")
    model = WDLModel(cfg, plan)
    scfg = serve_cfg(plan, args.batch // world)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh, axes=axes)
    if args.pin_l2:
        from repro.embedding.state import pin_l2_to_host, warn_pin_l2_limits
        warn_pin_l2_limits()
        state = pin_l2_to_host(state, mesh)
    serve = make_serve_step(model, plan, mesh, axes, args.batch, scfg=scfg)
    rng = np.random.default_rng(0)
    lat = []
    poller = None
    if args.reload_dir:
        # degraded-mode delta pickup: a torn/corrupt/pruned/mismatched delta
        # is skipped with capped backoff and the server keeps answering from
        # its last good state (PublishPoller only returns verified loads)
        from repro.runtime import PublishPoller, place_state
        poller = PublishPoller(args.reload_dir, plan=plan,
                               log=lambda s: print(s, flush=True))
    chaos_plan = None
    if args.chaos:
        from repro.runtime import parse_fault_plan
        from repro.runtime.chaos import tear_published
        chaos_plan = parse_fault_plan(args.chaos)
        torn_fired = set()
    for i in range(args.n_requests):
        if chaos_plan is not None and i in chaos_plan.torn_publish \
                and i not in torn_fired:
            torn_fired.add(i)
            print(f"[serve] chaos: tearing published delta before request "
                  f"{i}", flush=True)
            tear_published(args.reload_dir)
        if poller is not None:
            out = poller.poll({"emb": state["emb"], "dense": state["dense"]})
            if out is not None:
                loaded, s_pub = out
                state = {**state, **place_state(loaded, plan, mesh, axes)}
                print(f"[serve] reloaded published step {s_pub} "
                      f"from {args.reload_dir}", flush=True)
        b = make_batch(cfg, args.batch, rng)
        b = jax.device_put(b, to_named(mesh, batch_specs(b, axes)))
        t0 = time.perf_counter()
        probs = jax.block_until_ready(serve(state, b))
        lat.append(time.perf_counter() - t0)
    lat = np.array(lat[1:]) * 1e3
    print(f"[serve] {args.arch} B={args.batch}: p50={np.percentile(lat,50):.1f}ms "
          f"p99={np.percentile(lat,99):.1f}ms mean_prob={float(probs.mean()):.3f}")


if __name__ == "__main__":
    main()
