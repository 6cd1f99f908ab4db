"""Fault-tolerance subsystem: the full recovery matrix.

Every failure mode the runtime claims to survive is injected here and the
recovery is asserted *exactly* (bitwise where the contract says bitwise):

- numeric anomalies: guarded-vs-unguarded parity on clean data, NaN-batch
  rejection (state untouched, batch skipped), spike rejection, rollback
  after K consecutive rejections;
- checkpoint corruption: checksum detection, quarantine rename, fallback
  to the previous good snapshot, Supervisor replay exactness through it;
- supervisor policy: failure-density reset on sustained progress, fatal
  classification short-circuits retries;
- publish/serve: pruned-LATEST race returns the newest real delta, the
  poller keeps the last good state through a torn delta and recovers;
- streaming: a chaos crash mid-segment resumes bitwise-exactly from the
  segment checkpoint.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import BatchProducerError, ReplayableStream
from repro.data.synthetic import batch_stream
from repro.dist.sharding import batch_specs, to_named
from repro.models.wdl import WDLModel
from repro.core.packing import make_plan
from repro.runtime.chaos import (ChaosController, ChaosFailure, ChaosStream,
                                 FaultPlan, corrupt_checkpoint_file,
                                 parse_fault_plan, poison_batch,
                                 tear_published)
from repro.runtime.guard import AnomalyGuard, AnomalyRollback, GuardConfig
from repro.runtime.stream import (PublishPoller, poll_published,
                                  publish_state, run_stream)
from repro.train.checkpoint import (AsyncCheckpointer, CheckpointCorrupt,
                                    available_steps, latest_step,
                                    restore_checkpoint, restore_verified,
                                    save_checkpoint)
from repro.train.fault_tolerance import Supervisor, classify_failure
from repro.train.train_step import TrainConfig, init_state, make_train_step

GB = 64
PLAN_KW = dict(hot_bytes=1 << 14, l2_bytes=1 << 16, flush_iters=5,
               warmup_iters=2)


def _put(mesh, axes, batch):
    return jax.device_put(batch, to_named(mesh, batch_specs(batch, axes)))


def _setup(mesh1, axes, strategy="picasso", donate=True, **plan_kw):
    cfg = get_config("deepfm", smoke=True)
    kw = dict(PLAN_KW)
    kw.update(plan_kw)
    plan = make_plan(cfg, world=1, per_device_batch=GB, **kw)
    model = WDLModel(cfg, plan)
    state = init_state(model, plan, jax.random.PRNGKey(0), mesh=mesh1,
                       axes=axes)
    step, _ = make_train_step(model, plan, mesh1, axes, GB,
                              TrainConfig(strategy=strategy), donate=donate)
    return cfg, plan, model, state, step


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- toy guarded loop
# A scalar-ish step with a controllable gradient norm: batch "x" drives the
# update, so NaN/spike injection is exact and cheap.


def _toy_step():
    # non-donating, like any guard-compatible step (see runtime/guard.py)
    def raw(state, batch):
        g = jnp.mean(batch["x"]) * jnp.ones_like(state["w"])
        new = {"w": state["w"] - 0.1 * g, "step": state["step"] + 1}
        return new, {"loss": jnp.mean(batch["x"]) ** 2,
                     "grad_norm": jnp.sqrt(jnp.vdot(g, g))}
    return jax.jit(raw)


def _toy_state():
    return {"w": jnp.ones((3,), jnp.float32), "step": jnp.int32(0)}


def _toy_batch(i, poison=False):
    v = float("nan") if poison else 0.1 + 0.01 * (i % 7)
    return {"x": jnp.full((4,), v, jnp.float32)}


def _toy_stream(n=10_000, poison_at=()):
    def make(start):
        def gen():
            i = start
            while i < n:
                yield _toy_batch(i, poison=i in poison_at)
                i += 1
        return gen()
    return ReplayableStream(make)


# ------------------------------------------------------------ anomaly guard


def test_guard_clean_parity(mesh1, axes):
    """On clean data a guarded run is bitwise identical to the default
    (donating, unguarded) step: the guard runs the same executable modulo
    buffer donation, which affects aliasing but never values."""
    cfg, plan, model, state_a, step = _setup(mesh1, axes)  # donating ref
    _, _, _, state_b, gstep = _setup(mesh1, axes, donate=False)
    guard = AnomalyGuard(gstep)
    sa, sb = state_a, state_b
    for i, batch in zip(range(5), batch_stream(cfg, GB, seed=3)):
        b = _put(mesh1, axes, batch)
        sa, _ = step(sa, b)
        sb, m = guard(sb, b)
        assert m["anomalous"] == 0
    _leaves_equal(sa, sb)
    assert guard.accepted == 5 and guard.rejected == 0


def test_guard_nan_batch_rejected(mesh1, axes):
    """A poisoned batch is rejected (state untouched) and the run converges
    to the exact state of a run that never saw that batch."""
    cfg, plan, model, state_g, gstep = _setup(mesh1, axes, donate=False)
    _, _, _, state_r, step = _setup(mesh1, axes)
    guard = AnomalyGuard(gstep)
    batches = [b for _, b in zip(range(6), batch_stream(cfg, GB, seed=3))]
    for i, batch in enumerate(batches):
        b = _put(mesh1, axes, batch)
        if i == 3:
            b = poison_batch(b)
        state_g, m = guard(state_g, b)
        assert bool(m["anomalous"]) == (i == 3)
    # reference: same batches minus the poisoned index
    for i, batch in enumerate(batches):
        if i == 3:
            continue
        state_r, _ = step(state_r, _put(mesh1, axes, batch))
    _leaves_equal(state_g, state_r)
    assert guard.rejected == 1 and len(guard.events) == 1
    assert guard.events[0].kind == "nonfinite"


def test_guard_spike_rejection_and_threshold():
    step = _toy_step()
    guard = AnomalyGuard(step, GuardConfig(warmup_steps=3, spike_factor=10.0,
                                           k_rollback=99))
    s = _toy_state()
    for i in range(5):
        s, m = guard(s, _toy_batch(i))
    assert guard.threshold > 0
    before = np.asarray(s["w"]).copy()
    s, m = guard(s, {"x": jnp.full((4,), 1e6, jnp.float32)})
    assert bool(m["anomalous"])
    np.testing.assert_array_equal(np.asarray(s["w"]), before)
    assert guard.events[-1].kind == "spike"
    # accepted steps resume and the streak counter resets
    s, m = guard(s, _toy_batch(9))
    assert not bool(m["anomalous"]) and guard.consecutive == 0


def test_guard_rollback_after_k_carries_state():
    guard = AnomalyGuard(_toy_step(), GuardConfig(k_rollback=3))
    s = _toy_state()
    for i in range(4):
        s, _ = guard(s, _toy_batch(i))
    w_ok = np.asarray(s["w"]).copy()
    with pytest.raises(AnomalyRollback) as ei:
        for _ in range(3):
            s, _ = guard(s, _toy_batch(0, poison=True))
    # the exception carries the rejection-preserved state (the caller's
    # input buffers were donated): still exactly the pre-anomaly state
    np.testing.assert_array_equal(np.asarray(ei.value.state["w"]), w_ok)
    assert ei.value.rejects == 3
    assert classify_failure(ei.value) == "transient"


def test_guard_rebind_keeps_history():
    guard = AnomalyGuard(_toy_step(), GuardConfig(warmup_steps=2))
    s = _toy_state()
    for i in range(4):
        s, _ = guard(s, _toy_batch(i))
    ema = guard.ema
    guard.rebind(_toy_step())  # e.g. after a replan rebuild
    assert guard.ema == ema and guard.accepted == 4
    s, m = guard(s, _toy_batch(4))
    assert not bool(m["anomalous"])


# ------------------------------------------- supervisor rollback exactness


def test_supervisor_rollback_replay_exact(tmp_path):
    """Three consecutive transient NaN batches trigger the guard's rollback;
    the Supervisor restores the verified checkpoint and rewinds the stream;
    because the fault was transient (one-shot), the replay is clean and the
    final state is bitwise identical to a never-faulted run.

    (ckpt_every=5 keeps the checkpoint boundary out of the rejection streak
    at batches 5-7: a checkpoint taken *mid-streak* would legitimately pin
    the earlier rejections' skips — rejected batches behind the rollback
    target stay skipped by design.)"""
    def run(poison):
        guard = AnomalyGuard(_toy_step(), GuardConfig(k_rollback=3))
        stream = _toy_stream()
        if poison:
            stream = ChaosStream(stream, frozenset({5, 6, 7}))
        d = tmp_path / ("faulty" if poison else "clean")
        sup = Supervisor(str(d), ckpt_every=5, max_retries=3, backoff_s=0.0)
        out = sup.run(_toy_state(), guard, stream, n_steps=12)
        sup.ckpt.wait()
        return out, sup, guard

    clean, _, _ = run(poison=False)
    faulty, sup, guard = run(poison=True)
    _leaves_equal(clean, faulty)
    assert guard.rejected == 3
    assert sup.total_failures == 1  # one rollback, classified transient


def test_supervisor_restores_through_corrupt_checkpoint(tmp_path):
    """The newest checkpoint is corrupted on disk before the crash: restore
    must quarantine it, fall back to the previous good one, and the rewound
    replay still converges to the clean run's exact state."""
    def run(chaos):
        step = _toy_step()
        stream = _toy_stream()
        d = tmp_path / ("faulty" if chaos else "clean")
        sup = Supervisor(str(d), ckpt_every=2, max_retries=3, backoff_s=0.0)
        fired = set()

        def inject(i):
            if chaos and i == 7 and "crash" not in fired:
                fired.add("crash")
                # newest checkpoint (step 6) gets torn right before the crash
                sup.ckpt.wait()
                corrupt_checkpoint_file(str(d))
                raise ChaosFailure("injected crash at step 7")

        out = sup.run(_toy_state(), step, stream, n_steps=12,
                      fail_injector=inject)
        sup.ckpt.wait()
        return out, sup, d

    clean, _, _ = run(chaos=False)
    faulty, sup, d = run(chaos=True)
    _leaves_equal(clean, faulty)
    # the corrupt step-6 snapshot was quarantined, restore fell back to 4
    assert list(d.glob("step_*.corrupt"))
    assert sup.total_failures == 1


def test_supervisor_failure_counter_resets_on_progress(tmp_path):
    """Transient faults spread across a long run never exhaust max_retries:
    the density counter resets after reset_after clean steps."""
    sup = Supervisor(str(tmp_path), ckpt_every=2, max_retries=2,
                     reset_after=4, backoff_s=0.0)
    fired = set()

    def inject(i):
        # 3 transient faults, each separated by >= reset_after clean steps
        if i in (3, 9, 15) and i not in fired:
            fired.add(i)
            raise ChaosFailure(f"fault at {i}")

    out = sup.run(_toy_state(), _toy_step(), _toy_stream(), n_steps=20,
                  fail_injector=inject)
    assert int(out["step"]) == 20
    assert sup.total_failures == 3
    assert sup.failures <= 1  # density reset between faults


def test_supervisor_fatal_classification_short_circuits(tmp_path):
    """A deterministic bug (TypeError) must re-raise immediately instead of
    burning the retry budget on a restore loop."""
    assert classify_failure(TypeError("tracer leak")) == "fatal"
    assert classify_failure(ChaosFailure("node loss")) == "transient"
    sup = Supervisor(str(tmp_path), ckpt_every=2, max_retries=3,
                     backoff_s=0.0)

    def inject(i):
        if i == 3:
            raise TypeError("deterministic bug")

    with pytest.raises(TypeError):
        sup.run(_toy_state(), _toy_step(), _toy_stream(), n_steps=10,
                fail_injector=inject)
    assert sup.total_failures == 0  # never entered the retry path


class _MosaicLikeError(Exception):
    """Stands in for the kernel compiler's error type (not a public class)."""


@pytest.mark.parametrize("exc, kind", [
    (ValueError("block shape must be divisible by 8 and 128"), "fatal"),
    (NotImplementedError("Unimplemented primitive: dynamic_slice"), "fatal"),
    (_MosaicLikeError("INTERNAL: Mosaic failed to compile TPU kernel"),
     "fatal"),
    (RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space "
                  "hbm"), "fatal"),
    (RuntimeError("UNAVAILABLE: connection to peer lost"), "transient"),
    (OSError("checkpoint write failed"), "transient"),
])
def test_classify_failure_device_errors(exc, kind):
    """Lowering refusals, compile errors and device OOM repeat on every
    retry: they must re-raise, not enter the restore loop."""
    assert classify_failure(exc) == kind


def test_classify_failure_follows_cause():
    for cause, kind in ((TypeError("bad batch"), "fatal"),
                        (OSError("read timed out"), "transient")):
        try:
            raise BatchProducerError("batch producer thread died") from cause
        except BatchProducerError as e:
            assert classify_failure(e) == kind


# --------------------------------------------------- checkpoint corruption


def test_corrupt_checkpoint_quarantine_and_fallback(tmp_path):
    d = str(tmp_path)
    s4 = {"w": np.arange(4, dtype=np.float32)}
    s8 = {"w": np.arange(4, dtype=np.float32) * 2}
    save_checkpoint(d, 4, s4)
    save_checkpoint(d, 8, s8)
    corrupt_checkpoint_file(d)  # tears the newest (step 8)
    # direct restore of the torn step reports corruption, not garbage
    with pytest.raises(CheckpointCorrupt):
        restore_checkpoint(d, s8, step=8)
    # the verified walk quarantines step 8 and falls back to step 4
    state, step = restore_verified(d, s4)
    assert step == 4
    np.testing.assert_array_equal(state["w"], s4["w"])
    assert (tmp_path / "step_00000008.corrupt").exists()
    # quarantined snapshots are invisible to every reader
    assert latest_step(d) == 4
    assert available_steps(d) == [4]


def test_restore_verified_exhausted_raises(tmp_path):
    d = str(tmp_path)
    s = {"w": np.ones(3, np.float32)}
    save_checkpoint(d, 2, s)
    corrupt_checkpoint_file(d)
    with pytest.raises(FileNotFoundError):
        restore_verified(d, s)
    assert (tmp_path / "step_00000002.corrupt").exists()


# ------------------------------------------------------ publish/serve side


def _pub_state(k=1.0):
    return {"emb": {"t": np.full((4, 2), k, np.float32)},
            "dense": {"w": np.full((3,), k, np.float32)}}


def test_poll_published_pruned_latest_falls_back(tmp_path):
    d = str(tmp_path)
    publish_state(d, 10, _pub_state(1.0), keep=2)
    publish_state(d, 20, _pub_state(2.0), keep=2)
    # simulate the keep= race: LATEST names a step that was already pruned
    (tmp_path / "LATEST").write_text("99\n")
    assert poll_published(d) == 20  # newest delta actually on disk
    # garbage pointer: same fallback
    (tmp_path / "LATEST").write_text("not-a-step\n")
    assert poll_published(d) == 20
    # nothing newer than last_step -> None, not a crash
    assert poll_published(d, last_step=20) is None


def test_publish_poller_survives_torn_delta(tmp_path):
    d = str(tmp_path)
    template = _pub_state(0.0)
    poller = PublishPoller(d, max_backoff=4)
    assert poller.poll(template) is None  # nothing published yet

    publish_state(d, 10, _pub_state(1.0), keep=3)
    out = poller.poll(template)
    assert out is not None and out[1] == 10

    publish_state(d, 20, _pub_state(2.0), keep=3)
    tear_published(d)  # truncate a leaf of the step-20 delta
    assert poller.poll(template) is None  # torn delta skipped, not crashed
    assert poller.last_step == 10 and poller.failures == 1
    assert poller.skips_left > 0  # backoff armed

    publish_state(d, 30, _pub_state(3.0), keep=3)
    got = None
    for _ in range(6):  # a few polls burn the backoff window, then load
        got = poller.poll(template)
        if got is not None:
            break
    assert got is not None and got[1] == 30
    np.testing.assert_array_equal(got[0]["dense"]["w"],
                                  np.full((3,), 3.0, np.float32))
    assert poller.failures == 0  # clean load resets the backoff


# ------------------------------------------------------------- stream mode


def test_stream_crash_mid_segment_resumes_exact(tmp_path):
    """A chaos crash mid-segment kills the streaming driver; restarting from
    the segment checkpoint with the stream rewound reproduces the clean
    run's final state bitwise."""
    step = _toy_step()

    def clean_run():
        s, last = run_stream(_toy_state(), step, _toy_stream(),
                             segment_steps=5, n_segments=4,
                             log=lambda s: None)
        return s, last

    want, want_last = clean_run()
    assert want_last == 20

    d = str(tmp_path / "ckpt")
    ckpt = AsyncCheckpointer(d)
    chaos = ChaosController(FaultPlan(crash=frozenset({12})))
    stream = _toy_stream()
    with pytest.raises(ChaosFailure):
        run_stream(_toy_state(), step, stream, segment_steps=5, n_segments=4,
                   checkpointer=ckpt,
                   on_metrics=lambda i, m: chaos.injector(i),
                   log=lambda s: None)
    ckpt.wait()
    assert latest_step(d) == 10  # segment-2 boundary was durable

    # "process restart": restore the checkpoint, rewind the stream, finish
    state, start = restore_verified(d, _toy_state())
    stream.seek(start)
    got, last = run_stream(state, step, stream, segment_steps=5,
                           n_segments=2, start_step=start,
                           checkpointer=ckpt, log=lambda s: None)
    ckpt.wait()
    assert last == want_last
    _leaves_equal(want, got)


# -------------------------------------------------------- chaos primitives


def test_parse_fault_plan():
    p = parse_fault_plan("nan@7,nan@8,crash@13,ckpt@20,torn@45")
    assert p.nan_batch == frozenset({7, 8})
    assert p.crash == frozenset({13})
    assert p.corrupt_ckpt == frozenset({20})
    assert p.torn_publish == frozenset({45})
    assert bool(p) and not bool(FaultPlan())
    with pytest.raises(ValueError):
        parse_fault_plan("explode@3")
    with pytest.raises(ValueError):
        parse_fault_plan("nan@x")


def test_chaos_stream_one_shot_across_seek():
    """Poison fires once per index and does NOT re-fire on replay — the
    transient-corruption semantics that make rollback converge."""
    stream = ChaosStream(_toy_stream(), frozenset({2}))
    got = [next(stream) for _ in range(4)]
    assert np.isnan(np.asarray(got[2]["x"])).all()
    stream.seek(0)
    replay = [next(stream) for _ in range(4)]
    assert not any(np.isnan(np.asarray(b["x"])).any() for b in replay)


def test_batch_stream_start_is_positional(mesh1, axes):
    cfg = get_config("deepfm", smoke=True)
    a = [b for _, b in zip(range(6), batch_stream(cfg, GB, seed=7))]
    tail = [b for _, b in zip(range(2), batch_stream(cfg, GB, seed=7,
                                                     start=4))]
    for got, want in zip(tail, a[4:]):
        _leaves_equal(got, want)


def test_replayable_stream_seek_and_rewrap():
    def make(start):
        def gen():
            i = start
            while True:
                yield i
                i += 1
        return gen()

    rs = ReplayableStream(make)
    assert [next(rs) for _ in range(3)] == [0, 1, 2]
    rs.seek(1)
    assert next(rs) == 1 and rs.pos == 2
    rs.rewrap(lambda start: iter(range(start, start + 100)))
    assert next(rs) == 2  # same position, new factory
