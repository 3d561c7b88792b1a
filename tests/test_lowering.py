"""Lowering tests: replay tick programs symbolically and verify dataflow.

The tick program is the load-bearing artifact of the whole SPMD pipeline —
these tests interpret it with symbolic payloads (no arrays, no jax) and
assert that every stage's forward consumes exactly the right microbatch's
activations from its predecessor, every backward consumes the right gradient
from its successor, mailboxes never collide, and the tick counts match the
textbook formulas for each schedule.
"""

import numpy as np
import pytest

from shallowspeed_tpu import schedules as S
from shallowspeed_tpu.parallel.lowering import (
    OP_BWD,
    OP_BWD_W,
    OP_FWD,
    OP_NOOP,
    ScheduleLoweringError,
    lower_schedule,
    weighted_utilization,
)

TRAIN = [S.NaiveParallelSchedule, S.GPipeSchedule, S.PipeDreamFlushSchedule]
GRID = [(4, 1), (4, 2), (4, 4), (2, 4), (8, 4), (1, 3), (4, 8)]


def replay(p):
    """Symbolically execute a TickProgram; returns per-stage event log.

    Payloads are tuples ("act"|"grad", mubatch, from_stage). Raises on any
    mailbox misuse; returns events[(t, s)] = (op, mb, consumed_payload).
    Split programs additionally model the two-stash discipline: a B-input
    PEEKS the activation stash (written by the forward, still held) and
    fills a grad-stash slot; the matching B-weight frees both.
    """
    Kf, Kb, Ks = p.n_fwd_slots, p.n_bwd_slots, p.n_stash_slots
    Kg = p.n_gstash_slots
    fwd_mail = [[None] * Kf for _ in range(p.num_stages)]
    bwd_mail = [[None] * Kb for _ in range(p.num_stages)]
    stash = [[None] * Ks for _ in range(p.num_stages)]
    gstash = [[None] * Kg for _ in range(p.num_stages)]
    events = {}
    for t in range(p.num_ticks):
        outgoing = []  # (dst, direction, slot, payload)
        for s in range(p.num_stages):
            op, mb = int(p.op[t, s]), int(p.mb[t, s])
            consumed = None
            rf, rb = int(p.read_fwd_slot[t, s]), int(p.read_bwd_slot[t, s])
            if rf != Kf:
                consumed = fwd_mail[s][rf]
                assert consumed is not None, f"read from empty fwd slot at t={t} s={s}"
                fwd_mail[s][rf] = None
            if rb != Kb:
                assert consumed is None
                consumed = bwd_mail[s][rb]
                assert consumed is not None, f"read from empty bwd slot at t={t} s={s}"
                bwd_mail[s][rb] = None
            # activation stash: forwards write a free slot, the matching
            # backward (B-weight in a split program) reads and frees it
            sw, sr = int(p.stash_write[t, s]), int(p.stash_read[t, s])
            if sw != Ks:
                assert op == OP_FWD
                assert stash[s][sw] is None, f"stash overwrite t={t} s={s}"
                stash[s][sw] = mb
            if sr != Ks:
                assert op == (OP_BWD_W if p.backward_split else OP_BWD)
                assert stash[s][sr] == mb, (
                    f"backward reads wrong stash at t={t} s={s}: "
                    f"expected mb={mb}, slot holds {stash[s][sr]}"
                )
                stash[s][sr] = None
            if p.backward_split:
                sp = int(p.stash_peek[t, s])
                gw, gr = int(p.gstash_write[t, s]), int(p.gstash_read[t, s])
                if sp != Ks:
                    # B-input peek: the slot must hold THIS microbatch's
                    # residuals and must NOT be freed (B-weight frees it)
                    assert op == OP_BWD
                    assert stash[s][sp] == mb, f"B-in peeks wrong stash t={t} s={s}"
                if gw != Kg:
                    assert op == OP_BWD
                    assert gstash[s][gw] is None, f"grad-stash overwrite t={t} s={s}"
                    gstash[s][gw] = mb
                if gr != Kg:
                    assert op == OP_BWD_W
                    assert gstash[s][gr] == mb, (
                        f"B-weight reads wrong grad stash at t={t} s={s}"
                    )
                    gstash[s][gr] = None
                if p.is_training and op == OP_BWD:
                    assert sp != Ks and gw != Kg, f"B-in without stashes t={t} s={s}"
                if op == OP_BWD_W:
                    assert sr != Ks and gr != Kg, f"B-w without stashes t={t} s={s}"
            elif p.is_training and op == OP_BWD:
                assert sr != Ks, f"backward without stash read at t={t} s={s}"
            if op != OP_NOOP:
                events[(t, s)] = (op, mb, consumed)
            if p.send_fwd[t, s]:
                assert op == OP_FWD
                outgoing.append((s + 1, "fwd", ("act", mb, s)))
            if p.send_bwd[t, s]:
                assert op == OP_BWD  # B-weights never send
                outgoing.append((s - 1, "bwd", ("grad", mb, s)))
        for dst, direction, payload in outgoing:
            mail = fwd_mail if direction == "fwd" else bwd_mail
            slot_tab = p.in_fwd_slot if direction == "fwd" else p.in_bwd_slot
            slot = int(slot_tab[t, dst])
            assert slot != (Kf if direction == "fwd" else Kb), (
                f"payload to stage {dst} at t={t} has no assigned slot"
            )
            assert mail[dst][slot] is None, f"mailbox collision at t={t} dst={dst}"
            mail[dst][slot] = payload
    for s in range(p.num_stages):
        assert all(x is None for x in fwd_mail[s] + bwd_mail[s]), "leftover messages"
        assert all(x is None for x in stash[s]), "leaked activation stash"
        assert all(x is None for x in gstash[s]), "leaked grad stash"
    return events


@pytest.mark.parametrize("cls", TRAIN)
@pytest.mark.parametrize("M,St", GRID)
def test_dataflow_correctness(cls, M, St):
    p = lower_schedule(cls, M, St)
    events = replay(p)
    for (t, s), (op, mb, consumed) in events.items():
        if op == OP_FWD:
            if s == 0:
                assert consumed is None  # loads from the dataset
            else:
                assert consumed == ("act", mb, s - 1), (t, s, mb, consumed)
        elif op == OP_BWD:
            if s == St - 1:
                assert consumed is None  # consumes loaded targets
            else:
                assert consumed == ("grad", mb, s + 1), (t, s, mb, consumed)
    # every stage does M forwards and M backwards
    for s in range(St):
        ops_s = [v[0] for (t, ss), v in events.items() if ss == s]
        assert ops_s.count(OP_FWD) == M and ops_s.count(OP_BWD) == M


@pytest.mark.parametrize("M,St", GRID)
def test_inference_dataflow(M, St):
    p = lower_schedule(S.InferenceSchedule, M, St)
    events = replay(p)
    assert all(v[0] == OP_FWD for v in events.values())
    assert not p.is_training


class TestTickCounts:
    """Lowered latency must equal the textbook schedule depth."""

    @pytest.mark.parametrize("M,St", [(4, 2), (4, 4), (8, 4), (2, 4)])
    def test_gpipe(self, M, St):
        assert lower_schedule(S.GPipeSchedule, M, St).num_ticks == 2 * (M + St - 1)

    @pytest.mark.parametrize("M,St", [(4, 2), (4, 4), (8, 4)])
    def test_pipedream_no_slower_than_gpipe(self, M, St):
        assert (
            lower_schedule(S.PipeDreamFlushSchedule, M, St).num_ticks
            <= lower_schedule(S.GPipeSchedule, M, St).num_ticks
        )

    @pytest.mark.parametrize("M,St", [(4, 2), (4, 4)])
    def test_naive(self, M, St):
        assert lower_schedule(S.NaiveParallelSchedule, M, St).num_ticks == 2 * M * St

    @pytest.mark.parametrize("M,St", [(4, 4), (8, 2)])
    def test_inference(self, M, St):
        assert lower_schedule(S.InferenceSchedule, M, St).num_ticks == M + St - 1


class TestPipelineUtilization:
    def test_gpipe_bubble_fraction(self):
        """Busy ticks / total = M/(M+S-1) per phase — the GPipe bubble law."""
        M, St = 8, 4
        p = lower_schedule(S.GPipeSchedule, M, St)
        busy = (np.asarray(p.op) != OP_NOOP).sum()
        assert busy == 2 * M * St  # total work
        assert p.num_ticks == 2 * (M + St - 1)

    def test_naive_only_one_stage_active(self):
        p = lower_schedule(S.NaiveParallelSchedule, 4, 4)
        active_per_tick = (np.asarray(p.op) != OP_NOOP).sum(axis=1)
        assert (active_per_tick <= 1).all()


class TestValidation:
    def test_malformed_schedule_deadlocks(self):
        class Broken(S.Schedule):
            def steps(self):
                yield [S.ZeroGrad()]
                # stage 1 receives but stage 0 never sends -> deadlock
                if self.stage_id == 0:
                    yield [S.LoadMuBatchInput(mubatch_id=0), S.Forward(mubatch_id=0)]
                    yield [
                        S.LoadMuBatchTarget(mubatch_id=0),
                        S.BackwardGradAllReduce(mubatch_id=0),
                    ]
                else:
                    yield [S.RecvActivations(), S.Forward(mubatch_id=0)]
                    yield [S.BackwardGradAllReduce(mubatch_id=0)]
                yield [S.OptimizerStep()]

        with pytest.raises(ScheduleLoweringError):
            lower_schedule(Broken, 1, 2)

    def test_missing_optimizer_step_rejected(self):
        class NoOpt(S.Schedule):
            def steps(self):
                yield [S.ZeroGrad()]
                yield [S.LoadMuBatchInput(mubatch_id=0), S.Forward(mubatch_id=0)]
                yield [
                    S.LoadMuBatchTarget(mubatch_id=0),
                    S.BackwardGradAllReduce(mubatch_id=0),
                ]

        with pytest.raises(ScheduleLoweringError):
            lower_schedule(NoOpt, 1, 1, training=True)

    def test_out_of_order_consumer_pairs_correctly(self):
        """A receiver that consumes microbatches in a different order than its
        peer emits them must get the RIGHT payloads (mailbox binds messages by
        microbatch id, not FIFO position) — never silently mispair."""

        class Swapped(S.Schedule):
            # stage 0 sends fwd mb0 then mb1; stage 1 consumes mb1 first
            def steps(self):
                yield [S.ZeroGrad()]
                if self.stage_id == 0:
                    for mb in (0, 1):
                        yield [
                            S.LoadMuBatchInput(mubatch_id=mb),
                            S.Forward(mubatch_id=mb),
                            S.SendActivations(),
                        ]
                    for mb in (0, 1):
                        yield [
                            S.RecvOutputGrad(),
                            (S.BackwardGradAllReduce if mb == 1 else S.BackwardGradAcc)(
                                mubatch_id=mb
                            ),
                        ]
                else:
                    for mb in (1, 0):  # swapped consumption order
                        yield [S.RecvActivations(), S.Forward(mubatch_id=mb)]
                    for mb in (0, 1):
                        yield [
                            S.LoadMuBatchTarget(mubatch_id=mb),
                            (S.BackwardGradAllReduce if mb == 1 else S.BackwardGradAcc)(
                                mubatch_id=mb
                            ),
                            S.SendInputGrad(),
                        ]
                yield [S.OptimizerStep()]

        p = lower_schedule(Swapped, 2, 2)
        events = replay(p)  # replay asserts every consume matches its mubatch
        fwd_order_s1 = [
            v[1] for (t, s), v in sorted(events.items()) if s == 1 and v[0] == OP_FWD
        ]
        assert fwd_order_s1 == [1, 0]

    def test_incomplete_mubatch_coverage_rejected(self):
        class Skips(S.GPipeSchedule):
            def steps(self):
                for step in super().steps():
                    # drop forward of mubatch 1
                    yield [
                        c
                        for c in step
                        if not (isinstance(c, S.Forward) and c.mubatch_id == 1)
                    ]

        with pytest.raises(ScheduleLoweringError):
            lower_schedule(Skips, 2, 1)


# ---------------------------------------------------------------------------
# Split backward (B-input / B-weight)
# ---------------------------------------------------------------------------

SPLIT_TRAIN = TRAIN  # every flat training schedule lowers a split variant


@pytest.mark.parametrize("cls", SPLIT_TRAIN)
@pytest.mark.parametrize("M,St", [(4, 2), (4, 4), (8, 4), (2, 4)])
def test_split_dataflow_and_bin_ticks_match_unsplit(cls, M, St):
    """The split program's relays must be indistinguishable from the
    unsplit one: every B-input sits at EXACTLY the tick (and consumes
    exactly the payload) the combined backward would have, forwards are
    untouched, and the deferred B-weights pair one-to-one with their
    B-inputs through the stash discipline (replay() asserts it)."""
    u = lower_schedule(cls, M, St)
    p = lower_schedule(cls, M, St, backward_split=True)
    assert p.backward_split and not u.backward_split
    T = u.num_ticks
    assert p.num_ticks >= T
    # identical F and B(-input) placement over the unsplit makespan, and
    # nothing but B-weights in the extension
    assert ((p.op[:T] == OP_FWD) == (u.op == OP_FWD)).all()
    assert ((p.op[:T] == OP_BWD) == (u.op == OP_BWD)).all()
    assert np.isin(p.op[T:], (OP_NOOP, OP_BWD_W)).all()
    # same send tables over the shared prefix, none after (B-w never sends)
    assert (p.send_fwd[:T] == u.send_fwd).all() and (p.send_bwd[:T] == u.send_bwd).all()
    assert not p.send_fwd[T:].any() and not p.send_bwd[T:].any()
    events = replay(p)
    for (t, s), (op, mb, consumed) in events.items():
        if op == OP_BWD and s != St - 1:
            assert consumed == ("grad", mb, s + 1)
        elif op == OP_BWD_W:
            assert consumed is None
    # every stage: M forwards, M B-inputs, M B-weights
    for s in range(St):
        ops_s = [v[0] for (t, ss), v in events.items() if ss == s]
        assert ops_s.count(OP_FWD) == M
        assert ops_s.count(OP_BWD) == M
        assert ops_s.count(OP_BWD_W) == M


@pytest.mark.parametrize("cls", SPLIT_TRAIN)
@pytest.mark.parametrize("M,St", [(4, 4), (8, 4)])
def test_split_bweight_order_matches_backward_order(cls, M, St):
    """Per stage, B-weights execute in the B-input (= combined backward)
    order — the weight-grad accumulation-order contract behind bitwise
    parity."""
    p = lower_schedule(cls, M, St, backward_split=True)
    for s in range(St):
        bin_order = [int(p.mb[t, s]) for t in range(p.num_ticks) if p.op[t, s] == OP_BWD]
        bww_order = [
            int(p.mb[t, s]) for t in range(p.num_ticks) if p.op[t, s] == OP_BWD_W
        ]
        assert bww_order == bin_order


def test_split_weighted_bubble_shrinks_1f1b_p4_m8():
    """The acceptance criterion, from the ACTUAL lowered tick tables:
    split 1F1B at P=4, M=8 has a strictly smaller FLOP-weighted bubble
    fraction than unsplit 1F1B (and GPipe behaves the same way)."""
    u = lower_schedule(S.PipeDreamFlushSchedule, 8, 4)
    p = lower_schedule(S.PipeDreamFlushSchedule, 8, 4, backward_split=True)
    assert (1 - weighted_utilization(p)) < (1 - weighted_utilization(u))
    # pin the measured figures docs/lowering.md quotes (40% -> 11%)
    assert round((1 - weighted_utilization(u)) * 100) == 40
    assert round((1 - weighted_utilization(p)) * 100) == 11
    ug = lower_schedule(S.GPipeSchedule, 8, 4)
    pg = lower_schedule(S.GPipeSchedule, 8, 4, backward_split=True)
    assert (1 - weighted_utilization(pg)) < (1 - weighted_utilization(ug))


def test_split_anchor_is_final_bweight():
    """In a split stream the DP all-reduce anchor is the last B-WEIGHT,
    never a B-input (the gradient is incomplete until the last deferred
    wgrad lands)."""
    for cls in SPLIT_TRAIN:
        for stage in range(4):
            cmds = S.flat_commands(
                cls(num_micro_batches=4, num_stages=4, stage_id=stage,
                    backward_split=True)
            )
            ar = [c for c in cmds if isinstance(c, S.BackwardWeightGradAllReduce)]
            bww = [c for c in cmds if isinstance(c, S.BackwardWeightGradAcc)]
            assert len(ar) == 1 and bww[-1] is ar[0]
            assert not any(isinstance(c, S.BackwardGradAllReduce) for c in cmds)


class TestSplitValidation:
    def _lower_mangled(self, mangle):
        """Lower split GPipe with ``mangle`` applied to each stage's
        flattened command list (a deliberately broken stream generator)."""

        class Mangled(S.GPipeSchedule):
            def steps(self):
                cmds = [c for step in super().steps() for c in step]
                yield mangle(list(cmds))

        return lower_schedule(Mangled, 2, 2, backward_split=True)

    def test_misordered_bweight_stream_rejected(self):
        """The acceptance criterion: a B-weight stream whose order
        disagrees with the B-input order (breaking the accumulation-order
        contract) fails at lowering time — even though every B-weight
        still FOLLOWS its own B-input."""

        def defer_weights_reversed(cmds):
            # pull every B-weight out and append them all at the end in
            # REVERSED (= forward) order: GPipe's B-inputs ran in backward
            # order, so the accumulation order no longer matches
            ws = [c for c in cmds if isinstance(c, S.BackwardWeightGradAcc)]
            rest = [c for c in cmds if not isinstance(c, S.BackwardWeightGradAcc)]
            opt = rest.pop()  # OptimizerStep stays last
            return rest + list(reversed(ws)) + [opt]

        with pytest.raises(ScheduleLoweringError, match="order"):
            self._lower_mangled(defer_weights_reversed)

    def test_bweight_before_its_binput_rejected(self):
        def hoist_weight(cmds):
            i = next(
                i for i, c in enumerate(cmds)
                if isinstance(c, S.BackwardWeightGradAcc)
            )
            w = cmds.pop(i)
            # re-insert it before the backward phase begins: its B-input
            # (and everyone else's) has not run yet
            j = next(
                j for j, c in enumerate(cmds)
                if isinstance(
                    c,
                    (S.RecvOutputGrad, S.LoadMuBatchTarget, S.BackwardInputGradAcc),
                )
            )
            cmds.insert(j, w)
            return cmds

        with pytest.raises(ScheduleLoweringError, match="precedes"):
            self._lower_mangled(hoist_weight)

    def test_missing_bweight_rejected(self):
        def drop_weight(cmds):
            i = next(
                i for i, c in enumerate(cmds)
                if type(c) is S.BackwardWeightGradAcc
            )
            cmds.pop(i)
            return cmds

        with pytest.raises(ScheduleLoweringError):
            self._lower_mangled(drop_weight)

    def test_mixed_split_and_combined_rejected(self):
        def mix(cmds):
            # replace the first B-input/B-weight pair with a combined
            # backward: the stream now mixes both styles
            i = next(
                i for i, c in enumerate(cmds)
                if isinstance(c, S.BackwardInputGradAcc)
            )
            first = cmds[i]
            cmds[i] = S.BackwardGradAcc(mubatch_id=first.mubatch_id)
            j = next(
                j for j, c in enumerate(cmds)
                if isinstance(c, S.BackwardWeightGradAcc)
                and c.mubatch_id == first.mubatch_id
            )
            cmds.pop(j)
            return cmds

        with pytest.raises(ScheduleLoweringError, match="mixes"):
            self._lower_mangled(mix)

    def test_interleaved_split_rejected(self):
        with pytest.raises(ScheduleLoweringError, match="interleaved"):
            lower_schedule(S.InterleavedSchedule, 4, 4, virtual=2, backward_split=True)


# -- where a relay is due: the columns and perms the executor issues by ------


def _relay_programs():
    """One program of every schedule family the lowering takes, by id."""
    progs = {
        f"{cls.__name__}-m{M}-p{St}": (cls, M, St, {})
        for cls in TRAIN
        for M, St in [(4, 2), (8, 4), (4, 1)]
    }
    progs["inference-m4-p4"] = (S.InferenceSchedule, 4, 4, dict(training=False))
    progs["interleaved-m4-p2-v2"] = (S.InterleavedSchedule, 4, 2, dict(virtual=2))
    progs["interleaved-m8-p4-v2"] = (S.InterleavedSchedule, 8, 4, dict(virtual=2))
    progs["split-m8-p4"] = (S.PipeDreamFlushSchedule, 8, 4, dict(backward_split=True))
    progs["recompute-m4-p2"] = (S.GPipeSchedule, 4, 2, dict(recompute=True))
    return progs


RELAY_PROGRAMS = _relay_programs()


def test_relay_columns_of_the_benchmarks_program():
    """pipedream, M 4, pp 2 (the four-chip cell): 8 of the 20 relays are
    due, and ticks 3, 5, 7 and 9 have none in either direction."""
    p = lower_schedule(S.PipeDreamFlushSchedule, 4, 2)
    assert p.num_ticks == 10
    assert np.nonzero(p.relay_fwd)[0].tolist() == [0, 1, 4, 6]
    assert np.nonzero(p.relay_bwd)[0].tolist() == [2, 4, 6, 8]
    assert p.relay_perms() == ([(0, 1)], [(1, 0)])


@pytest.mark.parametrize("name", list(RELAY_PROGRAMS))
def test_relay_is_due_exactly_where_some_stage_sends(name):
    """``relay_*[t]`` is true exactly in the ticks in which some device
    sends, a perm holds exactly the pairs on which some tick sends, and
    every payload that is stored (a receive slot that is not trash) arrives
    in a due tick over a kept pair — so skipping the rest loses nothing.
    ``program_stats`` counts the same columns."""
    from shallowspeed_tpu.parallel.lowering import program_stats

    cls, M, St, kw = RELAY_PROGRAMS[name]
    p = lower_schedule(cls, M, St, **kw)
    fwd_perm, bwd_perm = p.relay_perms()
    for send, due, perm, slots, trash, step in (
        (p.send_fwd, p.relay_fwd, fwd_perm, p.in_fwd_slot, p.n_fwd_slots, 1),
        (p.send_bwd, p.relay_bwd, bwd_perm, p.in_bwd_slot, p.n_bwd_slots, -1),
    ):
        assert due.shape == (p.num_ticks,) and due.dtype == np.bool_
        for t in range(p.num_ticks):
            assert due[t] == bool(send[t].any())
        senders = [d for d in range(St) if send[:, d].any()]
        assert perm == [(d, (d + step) % St) for d in senders]
        for t, s in zip(*np.nonzero(slots != trash)):
            src = (s - step) % St
            assert due[t] and (src, s) in perm and send[t, src] == 1
    stats = program_stats(p)
    assert stats["relays_issued_fwd"] == int(p.relay_fwd.sum())
    assert stats["relays_issued_bwd"] == int(p.relay_bwd.sum())
    assert stats["relays_issued_fwd"] + stats["relays_issued_bwd"] <= 2 * p.num_ticks
    if not p.is_training:
        assert stats["relays_issued_bwd"] == 0 and bwd_perm == []


def test_wrap_pair_kept_only_by_an_interleaved_program():
    """Without virtual chunks nothing ever sends on the ring's wrap link
    (P-1 -> 0 forward, 0 -> P-1 backward) and the perms leave it out; with
    V = 2 the wrap IS a stage boundary and stays. One device has no pair."""
    flat = lower_schedule(S.PipeDreamFlushSchedule, 8, 4)
    assert flat.relay_perms() == (
        [(0, 1), (1, 2), (2, 3)], [(1, 0), (2, 1), (3, 2)]
    )
    ring = lower_schedule(S.InterleavedSchedule, 8, 4, virtual=2)
    fwd_perm, bwd_perm = ring.relay_perms()
    assert (3, 0) in fwd_perm and (0, 3) in bwd_perm
    assert len(fwd_perm) == len(bwd_perm) == 4
    assert lower_schedule(S.GPipeSchedule, 4, 1).relay_perms() == ([], [])
