"""MPMD per-stage runtime (parallel/mpmd.py): the lockstep twin parity
contract, the admission gate, runtime-independent checkpoints, and the
deferred-unstacking async snapshot.

The acceptance bar is BITWISE: the MPMD runtime reuses the lockstep
executor's per-slot expressions over the identical padded slot stacks
and accumulates gradients in the tick-table stream order, so every
trained weight must hash-equal the lockstep twin's — no tolerance, on
every lattice point (docs/numerics.md "Runtime equivalence")."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import schedules as S
from shallowspeed_tpu.optimizer import SGD, Adam, MomentumSGD
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.observability.divergence import assert_models_equal
from shallowspeed_tpu.parallel import mpmd
from shallowspeed_tpu.parallel.lowering import lower_schedule
from shallowspeed_tpu.parallel.mesh import make_mesh

SIZES = (40, 36, 32, 28, 24, 20, 14, 10)

# the named lattice: every point the mpmd-smoke gate and the ISSUE call
# out — dp, the two flat schedules, the split backward, tensor
# parallelism, interleaved virtual stages, and a 3-axis composition
LATTICE = {
    # name -> (dp, pp, tp, V, schedule, backward_split, optimizer)
    "gpipe-pp4": (1, 4, 1, 1, S.GPipeSchedule, False, SGD(0.01)),
    "pipedream-pp4": (1, 4, 1, 1, S.PipeDreamFlushSchedule, False, SGD(0.01)),
    "dp2-gpipe": (2, 2, 1, 1, S.GPipeSchedule, False, MomentumSGD(0.005, 0.9)),
    "bsplit-pp4": (1, 4, 1, 1, S.GPipeSchedule, True, SGD(0.01)),
    "tp2-pp2": (1, 2, 2, 1, S.GPipeSchedule, False, SGD(0.01)),
    "interleaved-V2": (1, 2, 1, 2, S.InterleavedSchedule, False, SGD(0.01)),
    "dp2-pp2-tp2": (
        2, 2, 2, 1, S.PipeDreamFlushSchedule, False, MomentumSGD(0.005, 0.9),
    ),
}


def _train_pair(dp, pp, tp, V, sched, bsplit, opt, sizes=SIZES, M=4, B=32,
                batches=2, data_seed=0, recompute=False, act="relu"):
    """Train the same two batches through the lockstep executor and the
    MPMD runner; returns (lockstep_leaves, mpmd_leaves, runner)."""
    spec = Mo.make_model_spec(sizes, pp * V, B, act=act)
    mesh = make_mesh(dp, pp, tp=tp)
    order = E.interleave_order(pp * V, pp) if V > 1 else None
    prog = lower_schedule(
        sched, M, pp, virtual=V, backward_split=bsplit, recompute=recompute
    )
    rng = np.random.RandomState(data_seed)
    X = rng.randn(batches, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[
        rng.randint(0, sizes[-1], (batches, B))
    ]

    stacked, flags = E.init_stacked(spec, mesh, order=order)
    ost = opt.init(stacked)
    step = E.make_pipeline_step(mesh, spec, prog, B // dp // M, opt)
    for i in range(batches):
        stacked, ost, _ = step(
            stacked, flags, ost, jnp.asarray(X[i]), jnp.asarray(Y[i])
        )
    lock = jax.tree.leaves(jax.device_get(stacked))

    stacked2, flags2 = E.init_stacked(spec, mesh, order=order)
    ost2 = opt.init(stacked2)
    runner = mpmd.MpmdTrainRunner(mesh, spec, prog, B // dp // M, opt)
    stacked2, ost2, _ = runner.run(stacked2, flags2, ost2, X, Y)
    got = jax.tree.leaves(jax.device_get(stacked2))
    return lock, got, runner


@pytest.mark.parametrize("layout", sorted(LATTICE))
def test_mpmd_bitwise_identical_to_lockstep(layout):
    """Every lattice point: MPMD epoch weights are BIT-identical to the
    lockstep twin's — same math, same padded widths, same accumulation
    order, different runtime."""
    lock, got, runner = _train_pair(*LATTICE[layout])
    assert runner.dispatch_count > 0 and runner.admission["findings"] == 0
    for a, b in zip(lock, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=layout
        )


# recompute rides the MPMD runtime too: the fwd_ns/recompute stage roles
# must reproduce the lockstep recompute executor bit-for-bit, on the flat
# schedules recompute supports (interleaved is lowering-refused)
RECOMPUTE_LATTICE = {
    # name -> (dp, pp, tp, sched, bsplit, opt, act)
    "gpipe-pp4-recompute": (
        1, 4, 1, S.GPipeSchedule, False, SGD(0.01), "relu",
    ),
    "pd-pp4-split-recompute-gelu": (
        1, 4, 1, S.PipeDreamFlushSchedule, True, SGD(0.01), "gelu",
    ),
    "dp2-pp2-recompute": (
        2, 2, 1, S.GPipeSchedule, False, MomentumSGD(0.005, 0.9), "relu",
    ),
    "tp2-pp2-recompute-gelu": (
        1, 2, 2, S.GPipeSchedule, False, SGD(0.01), "gelu",
    ),
}


@pytest.mark.parametrize(
    "layout",
    # the flagship gpipe point keeps tier-1 coverage (recompute-smoke
    # drives the split twin end to end); the split/dp/tp compositions
    # ride the slow tier (1-core wall budget)
    [lay if lay.startswith("gpipe") else
     pytest.param(lay, marks=pytest.mark.slow)
     for lay in sorted(RECOMPUTE_LATTICE)],
)
def test_mpmd_recompute_bitwise_identical_to_lockstep(layout):
    """recompute=True lattice: the MPMD runner's no-stash forward +
    recompute roles train bit-identically to the lockstep recompute
    executor on every supported layout."""
    dp, pp, tp, sched, bsplit, opt, act = RECOMPUTE_LATTICE[layout]
    lock, got, runner = _train_pair(
        dp, pp, tp, 1, sched, bsplit, opt, recompute=True, act=act,
    )
    assert runner.dispatch_count > 0 and runner.admission["findings"] == 0
    for a, b in zip(lock, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=layout
        )


@pytest.mark.parametrize(
    "seed",
    # seeds 1 and 4 are the two heaviest draws; they ride the slow tier
    # (1-core wall budget) while make mpmd-smoke and the recompute
    # lattice keep tier-1 mpmd coverage
    [s if s not in (1, 4) else pytest.param(s, marks=pytest.mark.slow)
     for s in range(6)],
)
def test_mpmd_fuzz_matches_lockstep(seed):
    """Random-lattice fuzz: runtime=mpmd as a fuzz dimension — random
    sizes, mesh shape, schedule, split backward and optimizer must stay
    bitwise against the lockstep twin, not just the handcrafted cases."""
    rng = np.random.RandomState(7000 + seed)
    dp, pp = [(2, 2), (1, 4), (2, 1)][seed % 3]
    tp = 2 if seed % 2 == 0 and dp * pp <= 4 else 1
    V = 2 if seed % 3 == 2 and pp > 1 else 1
    sched = (
        S.InterleavedSchedule
        if V > 1
        else [
            S.GPipeSchedule, S.PipeDreamFlushSchedule, S.NaiveParallelSchedule
        ][seed % 3]
    )
    bsplit = V == 1 and bool(seed % 2)
    opt = [SGD(0.01), MomentumSGD(0.005, 0.9), Adam(0.003)][seed % 3]
    n_sizes = pp * V * int(rng.randint(2, 4))
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(pp * rng.choice([1, 2]))
    B = int(dp * M * rng.choice([4, 8]))
    lock, got, _ = _train_pair(
        dp, pp, tp, V, sched, bsplit, opt, sizes=sizes, M=M, B=B,
        data_seed=8000 + seed,
    )
    label = f"seed={seed} dp={dp} pp={pp} tp={tp} V={V} bsplit={bsplit}"
    for a, b in zip(lock, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=label)


def test_tampered_tick_table_refused_before_any_dispatch(monkeypatch):
    """The admission gate: a tick program whose tables were tampered with
    is refused by the happens-before proof BEFORE any stage program is
    even BUILT (let alone compiled or dispatched) — the gate runs first
    in the runner constructor."""
    from shallowspeed_tpu.analysis import ProgramAnalysisError

    spec = Mo.make_model_spec(SIZES, 4, 32)
    mesh = make_mesh(1, 4)
    prog = lower_schedule(S.GPipeSchedule, 4, 4)
    # tamper: erase one forward send — its consumer's recv now has no
    # matching send, the exact corruption async dispatch would hang on
    send_fwd = np.array(prog.send_fwd)
    t, s = np.argwhere(send_fwd == 1)[0]
    send_fwd[t, s] = 0
    bad = dataclasses.replace(prog, send_fwd=send_fwd)

    def no_build(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("stage programs built before the admission gate")

    monkeypatch.setattr(mpmd, "_StagePrograms", no_build)
    with pytest.raises(ProgramAnalysisError):
        mpmd.MpmdTrainRunner(mesh, spec, bad, 8, SGD(0.01))
    # the serving-side gate: an inference table with a clobbered recv
    # slot is refused before any stage program exists
    iprog = lower_schedule(S.InferenceSchedule, 2, 4, training=False)
    rf = np.array(iprog.read_fwd_slot)
    hit = np.argwhere(rf != iprog.n_fwd_slots)[0]
    rf[hit[0], hit[1]] = iprog.n_fwd_slots  # drop the consuming read
    bad_inf = dataclasses.replace(iprog, read_fwd_slot=rf)
    with pytest.raises(ProgramAnalysisError):
        mpmd.MpmdInferenceRunner(mesh, spec, bad_inf, 8)


@pytest.mark.parametrize("dp,tp", [(1, 1), (1, 2), (2, 2)])
def test_stage_programs_census_clean_and_permute_free(dp, tp):
    """The defining MPMD property, proven from the compiled HLO on every
    sub-mesh shape (incl. the Megatron tp axis, whose structural psum
    floor must tolerate the non-relaying first stage's dead dx psum):
    relays left the program — no stage program lowers a
    collective-permute, every program passes its per-stage census, and
    none donates a buffer (every stage program is a dispatch path)."""
    from shallowspeed_tpu.observability import program_audit

    spec = Mo.make_model_spec((24, 20, 18, 16), 2, 16 * dp)
    mesh = make_mesh(dp, 2, tp=tp)
    prog = lower_schedule(S.GPipeSchedule, 2, 2)
    runner = mpmd.MpmdTrainRunner(mesh, spec, prog, 8, SGD(0.01))
    stacked, flags = E.init_stacked(spec, mesh)
    ost = SGD(0.01).init(stacked)
    cache = {}
    for s, role, variant in runner.planned_programs():
        jit_fn = runner.programs.get(s, role, variant)
        args = runner.example_args(
            s, role, variant, stacked, flags, ost, cache=cache
        )
        compiled = jit_fn.lower(*args).compile()
        sends = variant[2] if role in ("bwd", "bwd_in") else True
        rec = program_audit.audit_compiled(
            compiled,
            expected=mpmd.expected_stage_comms(role, spec, dp, tp, sends=sends),
        )
        label = f"dp{dp}tp{tp}:" + runner.programs.label(s, role, variant)
        assert rec["census_ok"] is not False, (label, rec.get("mismatches"))
        assert rec["census"].get("collective_permute", {}).get("count", 0) == 0, label
        program_audit.verify_dispatch_safety(compiled, context=label)


@pytest.fixture(scope="module")
def mpmd_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("mpmd_data")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 128), ("val", 64)):
        np.save(d / f"x_{suffix}.npy", rng.rand(n, 784).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)],
        )
    return d


def _session(data_dir, runtime, **kw):
    from shallowspeed_tpu.api import TrainingSession

    base = dict(
        pp=4, schedule="gpipe", global_batch_size=32, mubatches=4,
        data_dir=data_dir, runtime=runtime,
    )
    base.update(kw)
    return TrainingSession(**base)


def test_session_mpmd_hash_and_predict_parity(mpmd_data_dir):
    """TrainingSession(runtime='mpmd'): epoch weights hash-equal the
    lockstep twin's, and predict() — the serving dispatch path — is
    bitwise-equal row for row (the engine's parity contract holds across
    runtimes)."""
    a = _session(mpmd_data_dir, "lockstep")
    b = _session(mpmd_data_dir, "mpmd", audit=True)
    for _ in range(2):
        a.train_epoch()
        b.train_epoch()
    # digest-backed comparator: failure names the first divergent
    # (layer, tensor) instead of a bare cross-runtime hash mismatch
    assert_models_equal(a.params(), b.params(), "lockstep", "mpmd")
    x = np.random.RandomState(1).rand(50, 784).astype(np.float32)
    np.testing.assert_array_equal(a.predict(x), b.predict(x))
    # streaming submit returns the same rows as the blocking path
    one = x[:8]
    resolve = b.predict_async(one)
    np.testing.assert_array_equal(b.predict(one), resolve())


@pytest.mark.slow  # four full runs (both kill/resume directions) — slow
# tier per the 1-core wall budget; the per-runtime kill-resume legs and
# the lockstep-parity tests keep tier-1 coverage of each half
def test_kill_and_resume_is_runtime_independent(mpmd_data_dir, tmp_path):
    """Checkpoints are runtime-independent: a run killed under ONE
    runtime resumes under the OTHER and finishes on the uninterrupted
    twin's exact bits — both directions (the session state contract:
    the MPMD runner reassembles the same full-mesh arrays the lockstep
    program carries)."""
    from shallowspeed_tpu.faults import InjectedFault

    for killed_rt, resumed_rt in (("mpmd", "lockstep"), ("lockstep", "mpmd")):
        twin = _session(mpmd_data_dir, resumed_rt, optimizer="momentum")
        for _ in range(2):
            twin.train_epoch()

        ck = tmp_path / f"ck_{killed_rt}"
        run = _session(
            mpmd_data_dir, killed_rt, optimizer="momentum",
            checkpoint_dir=ck, faults="die@step=3",
        )
        with pytest.raises(InjectedFault):
            while run.epoch < 2:
                run.train_steps(2)
                run.save_step_checkpoint()
        res = _session(
            mpmd_data_dir, resumed_rt, optimizer="momentum",
            checkpoint_dir=ck, resume="auto",
        )
        assert res.resumed_from is not None and res.global_step == 3
        while res.epoch < 2:
            res.train_steps(2)
        assert_models_equal(
            res.params(), twin.params(),
            f"killed-{killed_rt}-resumed-{resumed_rt}", "twin",
        )


def test_async_checkpoint_defers_unstacking_bitwise(mpmd_data_dir, tmp_path):
    """The deferred-unstacking async save (ROADMAP item 5 follow-on):
    the writer-thread build produces a snapshot BYTE-identical to the
    synchronous on-path build — params AND optimizer state — so moving
    the logical reshaping off the step path changed cost, not content."""
    from shallowspeed_tpu.checkpoint import load_checkpoint

    paths = {}
    for name, async_ in (("sync", False), ("async", True)):
        run = _session(
            mpmd_data_dir, "mpmd", optimizer="momentum",
            checkpoint_dir=tmp_path / name, async_checkpoint=async_,
        )
        run.train_steps(2)
        paths[name] = run.save_step_checkpoint()
        run.drain_checkpoints()
        run.close()
    a = load_checkpoint(paths["sync"], 4, 32, with_opt_state=True)
    b = load_checkpoint(paths["async"], 4, 32, with_opt_state=True)
    for x, y in zip(jax.tree.leaves(a[0]), jax.tree.leaves(b[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a[3]), jax.tree.leaves(b[3])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


MPMD_REFUSED_KNOBS = {
    "zero1": dict(zero1=True),
    "clip_norm": dict(clip_norm=0.1),
    "pallas": dict(kernel_backend="pallas"),
    "record_steps": dict(record_steps=True),
}


@pytest.mark.parametrize("knob", sorted(MPMD_REFUSED_KNOBS))
def test_mpmd_refuses_unsupported_knobs(mpmd_data_dir, knob):
    """The feature envelope is enforced loudly at construction, knob by
    knob."""
    from shallowspeed_tpu.api import TrainingSession

    with pytest.raises(ValueError, match="mpmd"):
        TrainingSession(
            global_batch_size=32, mubatches=4, data_dir=mpmd_data_dir,
            runtime="mpmd", pp=4, schedule="gpipe",
            **MPMD_REFUSED_KNOBS[knob],
        )


def test_mpmd_refuses_no_mesh_and_fused_run(mpmd_data_dir):
    """MPMD needs stages (refused on the sequential path at construction),
    and the fused-run contract is refused at call time."""
    from shallowspeed_tpu.api import TrainingSession

    with pytest.raises(ValueError, match="sequential"):
        TrainingSession(
            global_batch_size=32, mubatches=4, data_dir=mpmd_data_dir,
            runtime="mpmd",
        )  # dp=pp=tp=1
    run = _session(mpmd_data_dir, "mpmd")
    with pytest.raises(ValueError, match="train_epoch"):
        run.train_run(1)
    with pytest.raises(ValueError, match="per-stage"):
        run.warm_run(1)
