"""Fault-tolerance tests: the retry helper, the fault-injection harness,
and the session-level recovery contract (docs/robustness.md).

The contract under test: crash at any step, resume from the last good step
checkpoint, and the final weights are BITWISE identical to an uninterrupted
run — because chunked step dispatch applies the exact same per-batch updates
in the exact same order as whole-epoch dispatch, and a v2 snapshot captures
the full resumable state (params, optimizer state, step cursor).
"""

import numpy as np
import pytest

from shallowspeed_tpu import faults, retry
from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.checkpoint import (
    CheckpointError,
    list_step_checkpoints,
    step_checkpoint_path,
)
from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl
from shallowspeed_tpu.observability.divergence import assert_models_equal
from shallowspeed_tpu.observability.health import HealthError

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
N, GBS = 256, 64  # 4 batches/epoch


@pytest.fixture()
def data_dir(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("recovery_data")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", N), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


def _session(data_dir, **kw):
    kw.setdefault("sizes", SIZES)
    kw.setdefault("global_batch_size", GBS)
    kw.setdefault("lr", 0.01)
    return TrainingSession(data_dir=data_dir, **kw)


# ---------------------------------------------------------------------------
# retry: the one backoff policy
# ---------------------------------------------------------------------------


def test_backoff_schedule_grows_capped_and_deterministic():
    plain = retry.backoff_delays(8, base=1.0, factor=2.0, max_delay=60.0, jitter=0)
    assert plain == [1, 2, 4, 8, 16, 32, 60, 60]  # exponential, then the cap
    a = retry.backoff_delays(8, base=1.0, max_delay=60.0, jitter=0.2, seed=7)
    b = retry.backoff_delays(8, base=1.0, max_delay=60.0, jitter=0.2, seed=7)
    assert a == b  # deterministic per (seed, attempt)
    assert a != retry.backoff_delays(8, base=1.0, max_delay=60.0, jitter=0.2, seed=8)
    for got, want in zip(a, plain):
        assert want * 0.8 <= got <= want * 1.2  # jitter stays in its band
    with pytest.raises(ValueError):
        retry.backoff_delay(-1)
    with pytest.raises(ValueError):
        retry.backoff_delay(0, factor=0.5)
    with pytest.raises(ValueError):
        retry.backoff_delay(0, jitter=1.5)


def test_retry_call_bounded_budget_and_exception_filter():
    calls, sleeps, seen = [], [], []

    def flaky():
        calls.append(1)
        raise OSError("transient")

    with pytest.raises(OSError):
        retry.retry_call(
            flaky, attempts=4, jitter=0, base=1.0,
            on_retry=lambda i, e, d: seen.append((i, d)),
            sleep=sleeps.append,
        )
    assert len(calls) == 4  # the TOTAL budget — strictly bounded
    assert sleeps == [1.0, 2.0, 4.0]  # attempts - 1 sleeps
    assert [i for i, _ in seen] == [0, 1, 2]

    # non-retried exception types propagate on the first attempt
    calls.clear()

    def fatal():
        calls.append(1)
        raise RuntimeError("logic bug")

    with pytest.raises(RuntimeError):
        retry.retry_call(fatal, attempts=4, sleep=lambda s: None)
    assert len(calls) == 1

    # success after failures returns the value
    state = iter([OSError("x"), OSError("y"), "ok"])

    def eventually():
        v = next(state)
        if isinstance(v, Exception):
            raise v
        return v

    assert retry.retry_call(eventually, attempts=3, sleep=lambda s: None) == "ok"
    with pytest.raises(ValueError):
        retry.retry_call(lambda: None, attempts=0)


# ---------------------------------------------------------------------------
# faults: the injection harness
# ---------------------------------------------------------------------------


def test_fault_spec_grammar_round_trip():
    plan = faults.FaultPlan.parse("die@step=7:mode=sigkill, nan@step=3")
    assert [repr(f) for f in plan.faults] == [
        "die@step=7:mode=sigkill", "nan@step=3"
    ]
    assert bool(plan) and not bool(faults.FaultPlan.parse(""))
    assert not faults.FaultPlan.parse(None)
    for bad in (
        "die",               # no step
        "die@mode=exc",      # still no step
        "explode@step=3",    # unknown kind
        "die@step=-1",       # negative step
        "die@step=3:mode=soft",   # unknown die mode
        "nan@step=3:mode=exc",    # nan takes no mode
        "die@step=3:color=red",   # unknown field
    ):
        with pytest.raises(ValueError, match="fault"):
            faults.FaultPlan.parse(bad)


def test_fault_plan_env_and_boundaries(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "die@step=11")
    plan = faults.from_env()
    assert plan.faults[0].step == 11 and plan.faults[0].mode == "exc"
    monkeypatch.delenv(faults.ENV_VAR)
    assert not faults.from_env()
    # make_plan normalizes the API surface
    assert faults.make_plan(plan) is plan
    assert faults.make_plan("nan@step=2").faults[0].kind == "nan"

    # first_in: earliest un-fired fault inside [lo, hi)
    plan = faults.FaultPlan.parse("die@step=9,nan@step=5")
    assert plan.first_in(0, 4) is None
    assert plan.first_in(4, 12).step == 5
    plan.faults[1].fired = True
    assert plan.first_in(4, 12).step == 9
    assert plan.first_in(10, 12) is None

    # the soft kill raises (and marks itself fired)
    f = faults.Fault("die", 3)
    with pytest.raises(faults.InjectedFault, match="die@step=3"):
        faults.FaultPlan([f]).fire_die(f)
    assert f.fired


def test_poison_nan_touches_exactly_one_leaf():
    import jax.numpy as jnp

    tree = [[{"W": jnp.ones((3, 3)), "b": jnp.ones((1, 3))}]]
    out = faults.poison_nan(tree)
    w = np.asarray(out[0][0]["W"])
    assert np.isnan(w).sum() == 1  # one poisoned element
    assert not np.isnan(np.asarray(out[0][0]["b"])).any()
    with pytest.raises(ValueError, match="no array leaf"):
        faults.poison_nan([])


def test_corrupt_checkpoint_bytes_deterministic(tmp_path):
    p = tmp_path / "f.bin"
    p.write_bytes(bytes(range(256)) * 8)
    before = p.read_bytes()
    offs = faults.corrupt_checkpoint_bytes(p, nbytes=4, seed=5)
    after = p.read_bytes()
    assert [i for i in range(len(before)) if before[i] != after[i]] == offs
    assert all(o >= 64 for o in offs)
    q = tmp_path / "q.bin"
    q.write_bytes(bytes(range(256)) * 8)
    assert faults.corrupt_checkpoint_bytes(q, nbytes=4, seed=5) == offs
    empty = tmp_path / "e.bin"
    empty.touch()
    with pytest.raises(ValueError, match="empty"):
        faults.corrupt_checkpoint_bytes(empty)


# ---------------------------------------------------------------------------
# the session-level recovery contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw",
    [
        dict(),
        # the dp2pp2 leg rides the slow tier (1-core wall budget);
        # make recovery-smoke drives chunked dispatch on a mesh
        pytest.param(
            dict(dp=2, pp=2, schedule="gpipe"), marks=pytest.mark.slow
        ),
    ],
    ids=["seq", "dp2pp2"],
)
def test_train_steps_chunked_is_bitwise_identical_to_epochs(data_dir, kw):
    """The preemption-safe unit's correctness: dispatching an epoch in
    uneven step chunks applies the same updates in the same order as
    whole-epoch dispatch — identical final hash AND identical recombined
    epoch mean loss."""
    whole = _session(data_dir, **kw)
    whole_losses = [whole.train_epoch() for _ in range(2)]

    chunked = _session(data_dir, **kw)
    losses, sizes = [], [1, 3, 2, 1, 1]  # uneven on purpose; 4 steps/epoch
    while chunked.epoch < 2:
        n = sizes[(chunked.global_step + chunked.epoch) % len(sizes)]
        _, epoch_loss = chunked.train_steps(n)
        if epoch_loss is not None:
            losses.append(epoch_loss)
    # digest-backed comparator: a mismatch names the first divergent
    # (layer, tensor) with ULP evidence instead of a bare hash diff
    assert_models_equal(chunked.params(), whole.params(), "chunked", "whole")
    np.testing.assert_allclose(losses, whole_losses, rtol=1e-6)

    # a mid-flight epoch refuses the whole-epoch/fused entry points
    chunked.train_steps(1)
    with pytest.raises(ValueError, match="mid-flight"):
        chunked.train_epoch()
    with pytest.raises(ValueError, match="mid-flight"):
        chunked.train_run(1)
    with pytest.raises(ValueError):
        chunked.train_steps(0)


@pytest.mark.slow  # 1-core wall budget; make recovery-smoke drives this end to end
def test_kill_and_resume_bitwise_equals_uninterrupted(data_dir, tmp_path):
    """The headline contract, session level: inject a die at step 5 of 8,
    resume from the surviving snapshots, and the final hash is bitwise
    identical to the uninterrupted twin — with the v4 checkpoint/recovery
    records telling the story. Sequential + momentum keeps this about the
    record stream and the cursor; the mesh layouts (and their optimizer
    states) are the fuzz lattice's kill-and-resume dimension."""
    twin = _session(data_dir, optimizer="momentum")
    for _ in range(2):
        twin.train_epoch()

    ck = tmp_path / "ck"
    jsonl = tmp_path / "killed.jsonl"
    with JsonlMetrics(jsonl) as m:
        run = _session(
            data_dir, optimizer="momentum",
            checkpoint_dir=ck, faults="die@step=5", metrics=m,
        )
        assert run.faults_active
        with pytest.raises(faults.InjectedFault):
            while run.epoch < 2:
                run.train_steps(2)
                run.save_step_checkpoint()
    # the chunk containing step 5 was truncated at the fault boundary, so
    # the fault fired BEFORE step 5 trained: snapshots at 2, 4 and the
    # truncated-chunk boundary 5 (a MID-epoch cursor: epoch 1, step 1)
    steps = [gs for gs, _ in list_step_checkpoints(ck)]
    assert steps == [2, 4, 5]
    recs = read_jsonl(jsonl)
    cks = [r for r in recs if r["kind"] == "checkpoint"]
    assert [r["global_step"] for r in cks] == [2, 4, 5]
    assert all(r["bytes"] > 0 and r["name"] == "step" for r in cks)

    jsonl2 = tmp_path / "resumed.jsonl"
    with JsonlMetrics(jsonl2) as m:
        res = _session(
            data_dir, optimizer="momentum",
            checkpoint_dir=ck, resume="auto", metrics=m,
        )
        assert res.resumed_from == str(step_checkpoint_path(ck, 5))
        assert res.epoch == 1 and res.step_in_epoch == 1  # 4 steps/epoch
        while res.epoch < 2:
            res.train_steps(2)
    assert_models_equal(res.params(), twin.params(), "resumed", "twin")
    rec = [r for r in read_jsonl(jsonl2) if r["kind"] == "recovery"]
    assert len(rec) == 1 and rec[0]["name"] == "resumed"
    assert rec[0]["global_step"] == 5 and rec[0]["skipped"] == []
    # the completing epoch's record covers only the tail THIS process
    # trained (steps 1-3 of epoch 1): stamped steps_counted, loss is the
    # tail mean, samples/s claims 3 batches — not the full epoch's 4
    eps = [
        r for r in read_jsonl(jsonl2)
        if r["kind"] == "event" and r["name"] == "epoch"
    ]
    assert [r["epoch"] for r in eps] == [1]
    assert eps[0]["steps_counted"] == 3


@pytest.mark.parametrize(
    "killed_kw,resumed_kw",
    [
        # zero2-dp2 -> zero1-dp4: the grad/state shards re-deal over a
        # WIDER dp axis at a LOWER stage
        pytest.param(
            dict(dp=2, pp=2, schedule="gpipe", zero=2),
            dict(dp=4, pp=2, schedule="gpipe", zero=1),
            id="zero2dp2-to-zero1dp4", marks=pytest.mark.slow,
        ),
        # zero3-dp2 -> sequential: params sharded at rest rehydrate into
        # the no-mesh layout (slow tier: the 1-core tier-1 wall budget
        # is tight; test_zero23's z3-save -> plain-load leg keeps the
        # logical-snapshot contract in tier-1)
        pytest.param(
            dict(dp=2, pp=2, schedule="gpipe", zero=3),
            dict(),
            id="zero3dp2-to-seq", marks=pytest.mark.slow,
        ),
    ],
)
def test_kill_resume_elastic_resharding(data_dir, tmp_path, killed_kw,
                                        resumed_kw):
    """ZeRO snapshots are LOGICAL (the zero1 checkpoint substrate keeps
    nothing layout-shaped on disk), so a run killed under one (stage, dp)
    point resumes under ANOTHER — elastic re-sharding. Bitwise at
    restore: the re-sharded resume and a same-layout resume of the same
    snapshot agree on params (hash) and on every optimizer-state leaf,
    and the re-sharded session trains on from the cursor."""
    ck = tmp_path / "ck"
    run = _session(
        data_dir, optimizer="momentum", checkpoint_dir=ck,
        faults="die@step=5", **killed_kw,
    )
    with pytest.raises(faults.InjectedFault):
        while run.epoch < 2:
            run.train_steps(2)
            run.save_step_checkpoint()
    assert [gs for gs, _ in list_step_checkpoints(ck)][-1] == 5

    res = _session(
        data_dir, optimizer="momentum", checkpoint_dir=ck, resume="auto",
        **resumed_kw,
    )
    same = _session(
        data_dir, optimizer="momentum", checkpoint_dir=ck, resume="auto",
        **killed_kw,
    )
    assert res.resumed_from == same.resumed_from
    assert res.model_hash() == same.model_hash()
    a, b = res.opt_state_logical(), same.opt_state_logical()
    assert sorted(a["parts"]) == sorted(b["parts"])
    import jax

    la, lb = jax.tree.leaves(a["parts"]), jax.tree.leaves(b["parts"])
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    while res.epoch < 2:
        res.train_steps(2)
    assert res.epoch == 2 and np.isfinite(res.accuracy())


def test_resume_auto_skips_corrupt_newest(data_dir, tmp_path):
    """Acceptance criterion end-to-end: corrupt the NEWEST snapshot with
    the fault harness; resume auto detects it via the checksum, falls back
    to the previous good one, and records the skip with its cause."""
    ck = tmp_path / "ck"
    run = _session(data_dir, checkpoint_dir=ck)
    run.train_steps(2)
    run.save_step_checkpoint()
    run.train_steps(2)
    run.save_step_checkpoint()
    faults.corrupt_checkpoint_bytes(step_checkpoint_path(ck, 4), seed=2)

    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 2))
    assert res.global_step == 2
    assert res._recovery["skipped"] and "step-00000004" in (
        res._recovery["skipped"][0]["path"]
    )

    # when EVERY snapshot is corrupt, resume refuses loudly (train.py maps
    # this to the exit-4 "unrecoverable checkpoint state" contract)
    faults.corrupt_checkpoint_bytes(step_checkpoint_path(ck, 2), seed=2)
    with pytest.raises(CheckpointError, match="no snapshot verifies"):
        _session(data_dir, checkpoint_dir=ck, resume="auto")


def test_resume_auto_fresh_start_and_validation(data_dir, tmp_path):
    res = _session(data_dir, checkpoint_dir=tmp_path / "empty", resume="auto")
    assert res.resumed_from is None and res.epoch == 0
    assert res._recovery["verdict"] == "fresh_start"
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _session(data_dir, resume="auto")
    with pytest.raises(ValueError, match="checkpoint_keep"):
        _session(data_dir, checkpoint_dir=tmp_path, checkpoint_keep=0)
    with pytest.raises(ValueError, match="no checkpoint_dir"):
        _session(data_dir).save_step_checkpoint()


def test_rotation_applied_by_session(data_dir, tmp_path):
    ck = tmp_path / "ck"
    run = _session(data_dir, checkpoint_dir=ck, checkpoint_keep=2)
    for _ in range(4):
        run.train_steps(1)
        run.save_step_checkpoint()
    assert [gs for gs, _ in list_step_checkpoints(ck)] == [3, 4]


def test_halt_flushes_resumable_snapshot(data_dir, tmp_path):
    """The health-halt contract: a NaN injected at step 3 halts the run,
    the halt path flushes a snapshot of the blown-up state (all_finite:
    false), and resume discovery SKIPS it — landing on the last healthy
    snapshot so the run is resumable from before the finding."""
    twin = _session(data_dir)
    for _ in range(2):
        twin.train_epoch()

    ck = tmp_path / "ck"
    run = _session(
        data_dir, checkpoint_dir=ck, health="halt", faults="nan@step=3",
    )
    with pytest.raises(HealthError):
        while run.epoch < 2:
            run.train_steps(2)
            run.save_step_checkpoint()
    # healthy snapshots at 2 and (the truncated chunk boundary) 3, plus
    # the halt flush at 4 — taken AFTER the poisoned step, so non-finite
    steps = [gs for gs, _ in list_step_checkpoints(ck)]
    assert steps == [2, 3, 4]

    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 3))
    skipped = res._recovery["skipped"]
    assert skipped and "non-finite" in skipped[0]["cause"]
    # the resumed run replays step 3 WITHOUT the poison and finishes on
    # the exact bits of the uninterrupted twin
    while res.epoch < 2:
        res.train_steps(2)
    assert_models_equal(res.params(), twin.params(), "resumed", "twin")


def test_multihost_explicit_join_retries_the_coordinator_race(monkeypatch):
    """Distributed init with an EXPLICIT coordinator retries through the
    shared backoff — a worker dialing a not-yet-listening coordinator waits
    out the race instead of crashing the fleet. The fake is STATEFUL the
    way jax really is (a failed connect leaves the client assigned, and a
    second initialize refuses with 'should only be called once'), so this
    pins the between-attempts state teardown, not just the retry loop.
    The no-coordinator path keeps its single-attempt fallback contract."""
    import jax

    from shallowspeed_tpu.parallel import multihost

    monkeypatch.setattr(multihost, "_distributed_is_initialized", lambda: False)
    monkeypatch.setattr(retry.time, "sleep", lambda s: None)
    calls, state = [], {"client": None}

    def racing_coordinator(**kw):
        if state["client"] is not None:
            raise RuntimeError(
                "distributed.initialize should only be called once"
            )
        state["client"] = "half-up"  # assigned BEFORE the connect, like jax
        calls.append(kw)
        if len(calls) < 3:
            raise RuntimeError("connection refused: coordinator not up yet")
        state["client"] = "connected"

    def fake_shutdown():
        if state["client"] == "half-up":
            state["client"] = None
            raise RuntimeError("shutdown of a never-connected client")
        state["client"] = None

    monkeypatch.setattr(jax.distributed, "initialize", racing_coordinator)
    monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)
    multihost.initialize("10.0.0.1:1234", num_processes=2, process_id=1)
    assert len(calls) == 3  # two refused dials, then the join
    assert state["client"] == "connected"
    assert calls[0]["coordinator_address"] == "10.0.0.1:1234"

    # budget exhausted -> the ORIGINAL error propagates (never the
    # 'called once' refusal); without a coordinator there is ONE attempt
    calls.clear()
    state["client"] = None

    def always_down(**kw):
        calls.append(kw)
        raise RuntimeError("still down")

    monkeypatch.setattr(jax.distributed, "initialize", always_down)
    with pytest.raises(RuntimeError, match="still down"):
        multihost.initialize("10.0.0.1:1234", num_processes=2, process_id=1)
    assert len(calls) == 4
    calls.clear()
    multihost.initialize()  # no coordinator: single-process fallback
    assert len(calls) == 1


def test_composed_faults_in_one_chunk_all_fire(data_dir):
    """The composed-spec contract the faults docstring advertises: a nan
    and a die inside the SAME dispatch chunk both land on their exact
    steps — after the nan fires at the chunk head, the chunk is truncated
    again at the die so it cannot be dispatched past."""
    run = _session(data_dir, faults="nan@step=3,die@step=5")
    with pytest.raises(faults.InjectedFault, match="die@step=5"):
        while run.epoch < 2:
            run.train_steps(8)  # whole-epoch chunks: both faults mid-chunk
    assert all(f.fired for f in run._faults.faults)
    assert run.global_step == 5  # died BEFORE step 5 trained


def test_composed_faults_at_the_same_step_all_fire(data_dir):
    """Two faults on the SAME step: after the nan fires at the chunk head,
    the die scheduled at that very step must still fire before the dispatch
    (a single-shot chunk-head check would leave it pending forever — every
    later search window starts past its step — and the harness would
    mistake the uninjected run for a survived crash)."""
    run = _session(data_dir, faults="nan@step=3,die@step=3")
    with pytest.raises(faults.InjectedFault, match="die@step=3"):
        while run.epoch < 2:
            run.train_steps(8)
    assert all(f.fired for f in run._faults.faults)
    assert run.global_step == 3  # died BEFORE step 3 trained


def test_halt_flush_never_rotates_away_the_good_snapshot(data_dir, tmp_path):
    """keep=1 + a halt flush: the non-finite halt snapshot must not rotate
    the single retained GOOD snapshot away — otherwise the flush would
    make the blow-up UNrecoverable, the opposite of its purpose."""
    ck = tmp_path / "ck"
    run = _session(
        data_dir, checkpoint_dir=ck, checkpoint_keep=1,
        health="halt", faults="nan@step=3",
    )
    with pytest.raises(HealthError):
        while run.epoch < 2:
            run.train_steps(2)
            run.save_step_checkpoint()
    # rotation kept only step-3 of the grid snapshots; the halt flush (4)
    # rode along WITHOUT rotating, so the good snapshot survived
    assert [gs for gs, _ in list_step_checkpoints(ck)] == [3, 4]
    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 3))


def test_grid_saves_never_rotate_away_the_last_finite_snapshot(
    data_dir, tmp_path
):
    """Fix for the silent-NaN hazard: WITHOUT --health halt, a blown-up
    run keeps writing grid snapshots (all_finite: false) — unconditional
    rotation would delete the last healthy snapshot within keep intervals
    and make resume auto permanently unrecoverable. Rotation only runs
    after FINITE saves, so the healthy snapshot survives the blow-up."""
    ck = tmp_path / "ck"
    run = _session(
        data_dir, checkpoint_dir=ck, checkpoint_keep=1, faults="nan@step=3"
    )
    while run.epoch < 2:
        run.train_steps(1)
        run.save_step_checkpoint()
    # steps 0-2 were healthy (keep=1 rotated normally, down to step-3);
    # step 3 trained on poisoned params, so snapshots 4..8 are non-finite
    # and accumulate UNrotated beside the surviving healthy one
    assert [gs for gs, _ in list_step_checkpoints(ck)] == [3, 4, 5, 6, 7, 8]
    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 3))
    assert len(res._recovery["skipped"]) == 5  # every non-finite snapshot


def test_pending_faults_refuse_stepless_entry_points(data_dir):
    """A plan that cannot fire must REFUSE, not silently skip: injections
    land on step boundaries, so a whole-epoch or fused-run dispatch with
    pending injections would sail past them — and a recovery driver would
    mistake the uninjected run for a survived crash."""
    run = _session(data_dir, faults="die@step=6")
    with pytest.raises(ValueError, match="train_steps"):
        run.train_epoch()
    with pytest.raises(ValueError, match="train_steps"):
        run.train_run(1)
    assert run.global_step == 0  # nothing trained

    # once every injection has FIRED, the stepless entry points are legal
    # again (nan@0 fires at the first chunk head; no health monitor, so
    # the poisoned run keeps training)
    run2 = _session(data_dir, faults="nan@step=0")
    run2.train_steps(4)  # fires the poison, finishes epoch 0
    assert not run2._faults.pending
    run2.train_epoch()


def test_sigkill_mode_parses_but_is_not_fired_in_process():
    """mode=sigkill is the subprocess shape (make recovery-smoke and the
    CLI test kill real train.py runs with it); in-process tests only check
    it parses and targets the right signal surface."""
    plan = faults.FaultPlan.parse("die@step=4:mode=sigkill")
    assert plan.faults[0].mode == "sigkill"


# ---------------------------------------------------------------------------
# the async checkpoint writer, session level (PR 12)
# ---------------------------------------------------------------------------


def test_save_fault_grammar_round_trip():
    """The @save= anchor joins the grammar: die/slow/corrupt parse (and
    refuse what they must), due_at_save fires each exactly once with the
    <= catch-up anchor, and save faults never count as pending STEP
    injections (a training entry point must not refuse a run over
    them)."""
    plan = faults.FaultPlan.parse(
        "die@save=2:mode=sigkill, slow@save=1:ms=50, corrupt@save=3"
    )
    assert [repr(f) for f in plan.faults] == [
        "die@save=2:mode=sigkill", "slow@save=1:ms=50", "corrupt@save=3"
    ]
    assert plan.pending == []  # step-pending stays empty: entry points run
    assert [f.kind for f in plan.pending_save] == ["die", "slow", "corrupt"]
    assert [f.kind for f in plan.due_at_save(0)] == []
    assert [f.kind for f in plan.due_at_save(1)] == ["slow"]
    plan.faults[1].fired = True
    # catch-up: an anchor whose exact save never ran fires on the next
    assert [f.kind for f in plan.due_at_save(5)] == ["die", "corrupt"]
    for bad in (
        "nan@save=1",            # nan is not a writer fault
        "error@save=1",          # error is dispatch-only
        "slow@save=1",           # slow needs ms
        "corrupt@save=1:ms=5",   # corrupt takes no ms
        "corrupt@dispatch=1",    # corrupt is save-only
        "die@save=1:step=2",     # exactly one anchor
    ):
        with pytest.raises(ValueError, match="fault"):
            faults.FaultPlan.parse(bad)


def test_corrupt_buffer_breaks_checksum_deterministically():
    from shallowspeed_tpu.checkpoint import content_checksum

    arrays = {"w0": np.arange(64, dtype=np.float32).reshape(8, 8)}
    stamped = content_checksum(arrays)
    offs = faults.corrupt_buffer(arrays, seed=4)
    assert offs and content_checksum(arrays) != stamped
    arrays2 = {"w0": np.arange(64, dtype=np.float32).reshape(8, 8)}
    assert faults.corrupt_buffer(arrays2, seed=4) == offs
    with pytest.raises(ValueError, match="corrupt"):
        faults.corrupt_buffer({})


@pytest.mark.slow  # 1-core wall budget; make recovery-smoke --async leg drives this end to end
def test_async_kill_and_resume_bitwise_equals_uninterrupted(
    data_dir, tmp_path
):
    """The headline contract survives the move off the step path: a run
    checkpointing ASYNCHRONOUSLY dies mid-run (die@step, with saves still
    in flight through the bounded writer), resume auto discovers only
    fully-verifying snapshots, and the finish is bitwise the twin's. The
    v8 checkpoint records carry async/queue-depth/off-path evidence."""
    twin = _session(data_dir, optimizer="momentum")
    for _ in range(2):
        twin.train_epoch()

    ck = tmp_path / "ck"
    jsonl = tmp_path / "killed.jsonl"
    with JsonlMetrics(jsonl) as m:
        run = _session(
            data_dir, optimizer="momentum", checkpoint_dir=ck,
            async_checkpoint=True, faults="die@step=5", metrics=m,
        )
        with pytest.raises(faults.InjectedFault):
            while run.epoch < 2:
                run.train_steps(2)
                run.save_step_checkpoint()
        run.close()  # the die left the writer alive: drain it
    recs = [r for r in read_jsonl(jsonl) if r["kind"] == "checkpoint"]
    assert recs and all(r["async"] is True for r in recs)
    assert all(
        r["verify_s"] >= 0 and r["write_s"] >= 0 and r["queue_depth"] >= 0
        for r in recs
    )
    # every discoverable snapshot fully verifies (no torn file ever
    # rename-visible), and resume lands on the newest one
    res = _session(
        data_dir, optimizer="momentum", checkpoint_dir=ck, resume="auto",
    )
    assert res._recovery["skipped"] == []
    assert res.global_step == 5
    while res.epoch < 2:
        res.train_steps(2)
    assert_models_equal(res.params(), twin.params(), "resumed", "twin")


def test_async_halt_flush_stays_synchronous_and_drains_first(
    data_dir, tmp_path
):
    """The PR 6 health-halt flush contract under async checkpointing: the
    halt snapshot is written SYNCHRONOUSLY (the process is unwinding — a
    snapshot parked in a daemon queue would die with it) after draining
    whatever the writer still holds, so discovery sees the full history:
    healthy grid saves, then the non-finite halt snapshot it skips."""
    jsonl = tmp_path / "halt.jsonl"
    ck = tmp_path / "ck"
    with JsonlMetrics(jsonl) as m:
        run = _session(
            data_dir, checkpoint_dir=ck, async_checkpoint=True,
            health="halt", faults="nan@step=3", metrics=m,
        )
        with pytest.raises(HealthError):
            while run.epoch < 2:
                run.train_steps(2)
                run.save_step_checkpoint()
    steps = [gs for gs, _ in list_step_checkpoints(ck)]
    assert steps == [2, 3, 4]
    recs = [r for r in read_jsonl(jsonl) if r["kind"] == "checkpoint"]
    by_reason = {r["name"]: r for r in recs}
    assert by_reason["halt"]["async"] is False  # the flush stayed sync
    assert by_reason["step"]["async"] is True
    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 3))


def test_writer_failure_surfaces_on_the_training_thread(data_dir, tmp_path):
    """A writer-side failure (here: an injected in-window die) must
    re-raise on the thread that owns the training loop — at the next
    save or drain — never vanish into a daemon-thread traceback."""
    ck = tmp_path / "ck"
    run = _session(
        data_dir, checkpoint_dir=ck, async_checkpoint=True,
        faults="die@save=1",
    )
    run.train_steps(1)
    run.save_step_checkpoint()  # save 0: fine
    run.train_steps(1)
    run.save_step_checkpoint()  # save 1: dies inside the write window
    with pytest.raises(faults.InjectedFault, match="die@save=1"):
        run.drain_checkpoints()
    # the failed save never became visible; the good one verifies
    assert [gs for gs, _ in list_step_checkpoints(ck)] == [1]


def test_resume_auto_reads_the_snapshot_exactly_once(
    data_dir, tmp_path, monkeypatch
):
    """The folded double read: discovery verifies (read+checksum) the
    chosen snapshot and resume assembles from THOSE arrays — one read
    total of the restored file, where PR 6 documented a deliberate
    second verify-read."""
    from shallowspeed_tpu import checkpoint as C

    ck = tmp_path / "ck"
    run = _session(data_dir, checkpoint_dir=ck)
    run.train_steps(2)
    run.save_step_checkpoint()
    reads = []
    real = C._read_arrays

    def counting(path):
        reads.append(str(path))
        return real(path)

    monkeypatch.setattr(C, "_read_arrays", counting)
    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.global_step == 2
    assert reads == [str(step_checkpoint_path(ck, 2))]  # exactly one read


def test_rotation_trusts_the_snapshot_it_just_wrote(
    data_dir, tmp_path, monkeypatch
):
    """Review fix: rotation inside run_save_stages must trust the snapshot
    written moments earlier in the same stage pipeline (its checksum was
    computed in-process) — otherwise EVERY rotating save re-reads and
    re-checksums its own file, the exact redundant verify-read the
    trusted ranking exists to skip."""
    from shallowspeed_tpu import checkpoint as C

    ck = tmp_path / "ck"
    run = _session(data_dir, checkpoint_dir=ck, checkpoint_keep=2)
    for _ in range(3):
        run.train_steps(1)
        run.save_step_checkpoint()
    verified = []
    real = C.verify_checkpoint

    def counting(path, **kw):
        verified.append(str(path))
        return real(path, **kw)

    monkeypatch.setattr(C, "verify_checkpoint", counting)
    run.train_steps(1)
    run.save_step_checkpoint()  # rotation fires (4 snapshots > keep=2)
    # this session wrote every candidate finite: rotation re-verifies NONE
    assert verified == []
    assert [gs for gs, _ in list_step_checkpoints(ck)] == [3, 4]


def test_corrupt_save_injection_never_rotates_away_the_good_snapshot(
    data_dir, tmp_path
):
    """Review fix: a corrupt@save-injected snapshot is finite in its
    metadata but can never verify — it must count as UNUSABLE everywhere
    the finite flag gates: no rotation off it, never added to the
    trusted set. With keep=1 the corrupted save must not delete the one
    good snapshot the fallback path exists to land on."""
    ck = tmp_path / "ck"
    run = _session(
        data_dir, checkpoint_dir=ck, checkpoint_keep=1,
        faults="corrupt@save=1",
    )
    run.train_steps(1)
    run.save_step_checkpoint()  # save 0: good (rotation may run)
    run.train_steps(1)
    run.save_step_checkpoint()  # save 1: corrupted in flight
    run.drain_checkpoints()
    # both files visible; the corrupt one neither rotated the good one
    # away nor entered the trusted set
    steps = [gs for gs, _ in list_step_checkpoints(ck)]
    assert steps == [1, 2]
    assert str(step_checkpoint_path(ck, 2)) not in run._trusted_snapshots
    res = _session(data_dir, checkpoint_dir=ck, resume="auto")
    assert res.resumed_from == str(step_checkpoint_path(ck, 1))
    assert res._recovery["skipped"] and "checksum" in (
        res._recovery["skipped"][0]["cause"]
    )
