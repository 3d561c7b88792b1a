"""Serving subsystem tests: slot geometry, ladder-bounded predict, engine
bitwise parity, loadgen determinism, the latency bench, and the report's
Serving section (docs/serving.md)."""

import json

import numpy as np
import pytest

from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.serving import slots as serving_slots
from shallowspeed_tpu.serving.engine import ServingEngine
from shallowspeed_tpu.serving import bench_serving, loadgen

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
N, GBS = 512, 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", N), ("val", 128)):
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
# slot geometry
# ---------------------------------------------------------------------------


def test_slot_helpers():
    assert serving_slots.default_slot_rows(1) == 8
    assert serving_slots.default_slot_rows(2) == 8
    assert serving_slots.default_slot_rows(3) == 9  # dp multiple
    assert serving_slots.slots_needed(1, 8) == 1
    assert serving_slots.slots_needed(8, 8) == 1
    assert serving_slots.slots_needed(9, 8) == 2
    ladder = serving_slots.validate_ladder((1, 2, 4))
    assert serving_slots.rung_for(1, ladder) == 1
    assert serving_slots.rung_for(3, ladder) == 4
    with pytest.raises(ValueError, match="top rung"):
        serving_slots.rung_for(5, ladder)
    with pytest.raises(ValueError, match="increasing"):
        serving_slots.validate_ladder((2, 2))
    with pytest.raises(ValueError, match="at least one row"):
        serving_slots.slots_needed(0, 8)


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(3)
    for dp in (1, 2, 4):
        slots = rng.randn(3, 8, 5).astype(np.float32)
        packed = serving_slots.pack_slots(slots, dp)
        assert packed.shape == (24, 5)
        back = serving_slots.unpack_slots(packed, 3, dp)
        np.testing.assert_array_equal(back.reshape(3, 8, 5), slots)
    # the executor mapping: replica r's contiguous block holds rows
    # [r*S/dp:(r+1)*S/dp) of every slot in slot order
    slots = np.arange(2 * 4 * 1, dtype=np.float32).reshape(2, 4, 1)
    packed = serving_slots.pack_slots(slots, 2)
    assert packed[:, 0].tolist() == [0, 1, 4, 5, 2, 3, 6, 7]


# ---------------------------------------------------------------------------
# ladder-bounded predict + eval routing (satellites 1 and 2)
# ---------------------------------------------------------------------------


def test_predict_cache_bounded_by_ladder(data_dir):
    """Repeated odd-sized predict() calls compile at most len(ladder)
    programs — the fix for the unbounded per-row-count cache."""
    run = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    rng = np.random.RandomState(7)
    for n in (1, 3, 5, 7, 9, 13, 17, 31, 33, 50, 63, 100, 129, 200):
        p = run.predict(rng.randn(n, SIZES[0]).astype(np.float32))
        assert p.shape == (n, SIZES[-1])
        np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-4)
    assert len(run._predict_cache) <= len(run.slot_ladder)
    # every cached key is a ladder rung, never a raw row count
    assert set(run._predict_cache) <= set(run.slot_ladder)


def test_predict_slot_aligned_stability(data_dir):
    """A slot's rows compute bitwise-identically whatever batch rides
    around them — the property the engine's parity contract rests on
    (slot-ALIGNED prefixes only: requests never share a slot)."""
    run = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    rng = np.random.RandomState(11)
    S = run.slot_rows
    x = rng.randn(4 * S, SIZES[0]).astype(np.float32)
    whole = run.predict(x)
    np.testing.assert_array_equal(whole[:S], run.predict(x[:S]))
    np.testing.assert_array_equal(whole[: 2 * S], run.predict(x[: 2 * S]))
    # determinism of the same call
    np.testing.assert_array_equal(whole, run.predict(x))


def test_mesh_accuracy_routed_through_serving_path_unchanged(data_dir, tmp_path):
    """Mesh eval flows through the SAME ladder slot programs serving uses,
    and the accuracy value is unchanged vs the sequential reference on
    identical weights."""
    seq = _session(data_dir)
    seq.train_epoch()
    ck = tmp_path / "eval.npz"
    seq.save(ck)
    mesh = _session(data_dir, dp=2, pp=2, schedule="gpipe", resume=ck)
    assert mesh.model_hash() == seq.model_hash()
    assert mesh.accuracy() == seq.accuracy()
    # eval populated the predict cache with ladder rungs only — the shared
    # compiled path, not a whole-split one-off program
    assert set(mesh._predict_cache) <= set(mesh.slot_ladder)


def test_predict_slot_rows_validation(data_dir):
    with pytest.raises(ValueError, match="multiple of dp"):
        _session(data_dir, dp=2, predict_slot_rows=9)
    with pytest.raises(ValueError, match="increasing"):
        _session(data_dir, predict_slot_ladder=(4, 2))


# ---------------------------------------------------------------------------
# engine: continuous batching + bitwise parity
# ---------------------------------------------------------------------------


def test_engine_bitwise_equals_direct_predict(data_dir):
    """The acceptance contract: every response under packed continuous
    batching is bitwise-equal to a direct predict() of the same rows."""
    run = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    eng = ServingEngine(run, slo_ms=10_000)
    rng = np.random.RandomState(5)
    payloads = [
        rng.randn(rows, SIZES[0]).astype(np.float32)
        for rows in (1, 3, 8, 9, 2, 17, 5, 4, 16, 7, 1, 33)
    ]
    for p in payloads:
        eng.submit(p)
    done = eng.drain()
    assert [r.id for r in done] == list(range(len(payloads)))  # FIFO
    for req in done:
        assert req.verdict == "ok"
        np.testing.assert_array_equal(req.result, run.predict(payloads[req.id]))
        assert req.enqueue_t <= req.dispatch_t <= req.complete_t
        assert req.latency_s >= req.queue_s >= 0
        assert req.slo_ok(10_000) is True


@pytest.mark.slow  # 1-core wall budget; make tp-smoke + serve-smoke drives this end to end
def test_engine_serves_tensor_parallel_layout(data_dir):
    """Serving under TP (satellite of the tp lattice): the rung programs
    route through the Megatron-sharded layers — strict audit enforces the
    forward-only contract (per-layer-pair tp all-reduces required,
    gradient collectives forbidden) before the first response, and every
    response stays bitwise-equal to a direct predict() of the same rows."""
    run = _session(data_dir, dp=2, tp=2, audit=True)
    eng = ServingEngine(run, slo_ms=10_000)
    rng = np.random.RandomState(6)
    payloads = [
        rng.randn(rows, SIZES[0]).astype(np.float32) for rows in (1, 9, 4, 17)
    ]
    for p in payloads:
        eng.submit(p)
    done = eng.drain()
    assert [r.verdict for r in done] == ["ok"] * len(payloads)
    for req in done:
        np.testing.assert_array_equal(req.result, run.predict(payloads[req.id]))


def test_engine_packing_capacity_and_accounting(data_dir):
    run = _session(data_dir, dp=2)  # pp=1: cheap programs
    S = run.slot_rows
    eng = ServingEngine(run, max_slots=4)
    rng = np.random.RandomState(9)
    for rows in (2 * S, S, 2 * S):  # 2 + 1 + 2 slots
        eng.submit(rng.randn(rows, SIZES[0]).astype(np.float32))
    first = eng.step()
    # 2+1 slots fit; adding the third request's 2 would exceed max_slots=4
    assert [r.id for r in first] == [0, 1]
    assert eng.queue_depth == 1
    second = eng.step()
    assert [r.id for r in second] == [2]
    st = eng.stats()
    assert st["completed"] == 3 and st["dispatches"] == 2
    # dispatch 1: 3 slots -> rung 4; dispatch 2: 2 slots -> rung 2
    assert st["slots_dispatched"] == 6
    assert st["useful_rows"] == 5 * S
    assert st["padding_waste"] == pytest.approx(1 - 5 / 6)
    assert st["queue_depth_max"] >= 2
    # oversized and malformed submissions are refused loudly
    with pytest.raises(ValueError, match="split it"):
        eng.submit(rng.randn(5 * S, SIZES[0]).astype(np.float32))
    with pytest.raises(ValueError, match="rows >= 1"):
        eng.submit(np.zeros((0, SIZES[0]), np.float32))
    # a packing capacity above the top rung has no program to dispatch on
    # — refused at configure time, not mid-traffic
    with pytest.raises(ValueError, match="top rung"):
        ServingEngine(run, max_slots=run.slot_ladder[-1] + 1)


def test_engine_admission_drop_and_sequential_parity(data_dir):
    """max_queue bounds admission (drops recorded, never silent), and the
    engine serves sequential sessions with the same parity contract."""
    run = _session(data_dir)  # sequential layout
    eng = ServingEngine(run, max_queue=2)
    rng = np.random.RandomState(13)
    payloads = [rng.randn(n, SIZES[0]).astype(np.float32) for n in (3, 1, 4)]
    reqs = [eng.submit(p) for p in payloads]
    assert [r.verdict for r in reqs] == ["queued", "queued", "dropped"]
    done = eng.drain()
    assert len(done) == 2
    for req in done:
        np.testing.assert_array_equal(req.result, run.predict(payloads[req.id]))
    st = eng.stats()
    assert st["dropped"] == 1 and st["completed"] == 2
    # sequential dispatches run only the OCCUPIED slots (no rung program
    # to round up to), so the padding accounting must not charge the rung
    # tail: 3 single-slot requests dispatch 3 slots, not rung_for(3)=4
    eng2 = ServingEngine(run)
    for p in payloads:
        eng2.submit(p)
    eng2.drain()
    st2 = eng2.stats()
    assert st2["dispatches"] == 1 and st2["slots_dispatched"] == 3
    S = run.slot_rows
    assert st2["padding_waste"] == pytest.approx(1 - (3 + 1 + 4) / (3 * S))
    # a long-lived engine keeps only scalar samples: completed Requests
    # (payloads + result arrays) belong to the caller, never the engine
    from collections import deque as _deque

    from shallowspeed_tpu.serving.engine import Request

    for v in vars(eng2).values():
        if isinstance(v, (list, _deque)):
            assert not any(isinstance(o, Request) for o in v)


def test_engine_emits_v5_records_and_queue_gauge(data_dir, tmp_path):
    from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl

    path = tmp_path / "serve.jsonl"
    m = JsonlMetrics(path)
    run = _session(data_dir, dp=2, metrics=m)
    eng = ServingEngine(run, slo_ms=10_000, metrics=m)
    rng = np.random.RandomState(1)
    for n in (1, 5, 9):
        eng.submit(rng.randn(n, SIZES[0]).astype(np.float32))
    eng.drain()
    eng.record_summary(offered_rps=123.0)
    m.close()
    recs = read_jsonl(path)
    reqs = [r for r in recs if r["kind"] == "request"]
    assert len(reqs) == 3 and all(r["name"] == "ok" for r in reqs)
    for r in reqs:
        assert r["latency_s"] > 0 and r["slots"] >= 1
        assert r["enqueue_ts"] <= r["dispatch_ts"] <= r["complete_ts"]
    summaries = [r for r in recs if r["kind"] == "serving"]
    assert len(summaries) == 1
    s = summaries[0]
    assert s["completed"] == 3 and s["offered_rps"] == 123.0
    assert s["p50_latency_s"] > 0 and s["latency_bound_s"] is not None
    assert any(
        r["kind"] == "gauge" and r["name"] == "serving.queue_depth"
        for r in recs
    )


# ---------------------------------------------------------------------------
# inference program stats + audit contract (satellite 3)
# ---------------------------------------------------------------------------


def test_inference_program_stats_per_rung():
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.parallel import lower_schedule
    from shallowspeed_tpu.parallel.lowering import (
        program_comm_bytes,
        program_stats,
    )
    from shallowspeed_tpu.parallel.executor import relay_width

    spec = Mo.make_model_spec(SIZES, 4, GBS)
    mb = 8  # slot_rows at dp=1
    for rung in (1, 2, 4, 8):
        prog = lower_schedule(S.InferenceSchedule, rung, 4, training=False)
        st = program_stats(prog)
        assert st["is_training"] is False
        assert st["cells_fwd"] == 4 * rung  # every stage forwards every slot
        assert st["cells_bwd"] == st["cells_bwd_in"] == st["cells_bwd_w"] == 0
        assert st["num_ticks"] == rung + 3  # M + P - 1 relay ticks
        comm = program_comm_bytes(prog, spec, mb)
        assert comm["relay_payload_bytes"] == 4 * mb * relay_width(spec)
        # forward-only: no backward relay is ever issued, and the forward
        # one in the ticks in which some stage has a payload due
        assert st["relays_issued_bwd"] == comm["relays_issued_bwd"] == 0
        assert st["relays_issued_fwd"] == int(prog.send_fwd.any(axis=1).sum())
        assert (
            comm["wire_bytes_per_device"]
            == st["relays_issued_fwd"] * comm["relay_payload_bytes"]
        )


def test_compiled_serving_census_clean_at_pp4(data_dir, tmp_path):
    """The audit's expected_comms verified clean on COMPILED serving
    programs at pp=4 — and strict audit would have raised before any
    request was served."""
    from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl

    path = tmp_path / "audit.jsonl"
    m = JsonlMetrics(path)
    run = _session(
        data_dir, pp=4, schedule="gpipe", metrics=m, audit=True
    )
    rng = np.random.RandomState(2)
    run.predict(rng.randn(3, SIZES[0]).astype(np.float32))  # rung 1
    run.predict(rng.randn(3 * run.slot_rows, SIZES[0]).astype(np.float32))
    m.close()
    audits = [
        r
        for r in read_jsonl(path)
        if r["kind"] == "xla_audit" and r["name"] == "inference_program"
    ]
    assert len(audits) == 2  # one per rung, deduped per compile variant
    for rec in audits:
        assert rec["census_ok"] is True
        assert rec["expected"]["inference"] is True
        # the serving contract: one-direction relay + the preds psum, no
        # gradient-sync collectives
        assert rec["census"]["collective_permute"]["count"] >= 1
        assert rec["census"]["all_reduce"]["count"] >= 1
        assert "reduce_scatter" not in rec["census"]
        assert "all_gather" not in rec["census"]


def test_inference_contract_rejects_training_census():
    """A serving program that lowered a gradient collective fails its
    contract (the deliberate-mismatch leg)."""
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.observability import program_audit
    from shallowspeed_tpu.parallel import lower_schedule

    spec = Mo.make_model_spec(SIZES, 4, GBS)
    prog = lower_schedule(S.InferenceSchedule, 2, 4, training=False)
    expected = program_audit.expected_comms(
        spec, 1, 4, prog=prog, mubatch_size=8
    )
    assert expected["inference"] is True
    good = {
        "collective_permute": {"count": 1, "bytes": 128},
        "all_reduce": {"count": 1, "bytes": 64},
    }
    assert program_audit.check_census(good, expected) == []
    leaked = dict(good, reduce_scatter={"count": 1, "bytes": 4096})
    assert any(
        "reduce_scatter" in msg
        for msg in program_audit.check_census(leaked, expected)
    )
    # a SECOND all-reduce beyond the preds psum reads as a leaked dp
    # gradient sync (the kind itself is lawful, so the count is the pin)
    doubled = dict(good, all_reduce={"count": 2, "bytes": 128})
    assert any(
        "at most ONE all-reduce" in msg
        for msg in program_audit.check_census(doubled, expected)
    )
    # a training program at the same layout still demands both directions
    tprog = lower_schedule(S.SCHEDULES["gpipe"], 4, 4)
    texp = program_audit.expected_comms(spec, 1, 4, prog=tprog, mubatch_size=8)
    assert any(
        "every direction" in msg
        for msg in program_audit.check_census(
            {"collective_permute": {"count": 1, "bytes": 128}}, texp
        )
    )


def test_inference_latency_bound(data_dir):
    run = _session(data_dir, pp=4, schedule="gpipe")
    bound = run.inference_latency_bound()
    # forward-only single-slot program: weighted makespan == tick count
    assert bound["ticks"] == 4 and bound["weighted_ticks"] == 4.0
    assert bound["seconds"] > 0 and "cpu" in bound["peak_source"]
    seq = _session(data_dir)
    sbound = seq.inference_latency_bound()
    assert sbound["ticks"] is None and sbound["seconds"] > 0


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------


def test_loadgen_seeded_and_deterministic():
    a1 = loadgen.poisson_arrivals(100.0, 50, seed=4)
    a2 = loadgen.poisson_arrivals(100.0, 50, seed=4)
    np.testing.assert_array_equal(a1, a2)
    assert len(a1) == 50 and np.all(np.diff(a1) > 0)
    # mean interarrival ~ 1/rate (loose: 50 samples)
    assert 0.3 / 100 < np.diff(a1).mean() < 3.0 / 100
    p1 = loadgen.request_payloads(10, 24, seed=4, rows_choices=(1, 2, 4))
    p2 = loadgen.request_payloads(10, 24, seed=4, rows_choices=(1, 2, 4))
    assert all(np.array_equal(a, b) for a, b in zip(p1, p2))
    assert {p.shape[0] for p in p1} <= {1, 2, 4}
    pool = np.arange(12, dtype=np.float32).reshape(4, 3)
    from_pool = loadgen.request_payloads(5, 3, seed=0, data=pool)
    for p in from_pool:
        assert all(any(np.array_equal(row, r) for r in pool) for row in p)
    with pytest.raises(ValueError):
        loadgen.poisson_arrivals(0, 5)


def test_closed_vs_open_loop_deadline_accounting(data_dir, monkeypatch):
    """Satellite pin (loadgen.py docstrings): the open loop backdates
    enqueue to the SCHEDULED arrival, so deadlines burn against queue
    backlog (coordinated-omission corrected — a backlogged stream sheds /
    misses); the closed loop never backdates, so deadlines score pure
    service latency and the same stream meets them all."""
    run = _session(data_dir)
    orig = run.predict

    def slow_predict(x):
        import time as _t

        _t.sleep(0.02)  # one dispatch >= 20 ms, deterministic ordering
        return orig(x)

    monkeypatch.setattr(run, "predict", slow_predict)
    rng = np.random.RandomState(21)
    payloads = [rng.randn(2, SIZES[0]).astype(np.float32) for _ in range(6)]
    # open loop: all six arrive at t=0 but serve one per dispatch — the
    # tail's deadline (60 ms) is provably dead after three 20 ms dispatches
    eng_open = ServingEngine(run, max_slots=1)
    done_open = loadgen.run_open_loop(
        eng_open, payloads, arrivals=[0.0] * 6, deadline_ms=60.0
    )
    assert len(done_open) == 6
    open_missed = [
        r for r in done_open if r.verdict == "expired" or r.slo_ok() is False
    ]
    assert open_missed, "backlogged open-loop stream must miss deadlines"
    # every request's clock starts at the shared scheduled arrival
    assert len({r.enqueue_t for r in done_open}) == 1
    # closed loop, same stream and deadline: admission waits for a free
    # slot, so each request's 60 ms covers only its own ~20 ms dispatch
    eng_closed = ServingEngine(run, max_slots=1)
    done_closed = loadgen.run_closed_loop(
        eng_closed, payloads, concurrency=1, deadline_ms=60.0
    )
    assert len(done_closed) == 6
    assert all(r.verdict == "ok" and r.slo_ok() is True for r in done_closed)
    # submit-time clocks: strictly increasing, never backdated
    ts = [r.enqueue_t for r in sorted(done_closed, key=lambda r: r.id)]
    assert all(b > a for a, b in zip(ts, ts[1:]))


def test_loadgen_drivers_complete_all(data_dir):
    run = _session(data_dir, dp=2)
    payloads = loadgen.request_payloads(15, SIZES[0], seed=6)
    arrivals = loadgen.poisson_arrivals(2000.0, 15, seed=6)
    eng = ServingEngine(run, slo_ms=10_000)
    done = loadgen.run_open_loop(eng, payloads, arrivals)
    assert len(done) == 15 and eng.queue_depth == 0
    # open loop backdates enqueue to the scheduled arrival
    t0 = min(r.enqueue_t for r in done)
    for req, arr in zip(sorted(done, key=lambda r: r.id), arrivals):
        assert req.enqueue_t == pytest.approx(t0 + arr - arrivals[0], abs=1e-6)
    eng2 = ServingEngine(run)
    seen_depth = []
    orig_step = eng2.step

    def spy_step():
        seen_depth.append(eng2.queue_depth)
        return orig_step()

    eng2.step = spy_step
    done2 = loadgen.run_closed_loop(eng2, payloads, concurrency=3)
    assert len(done2) == 15
    assert max(seen_depth) <= 3  # the fixed in-flight population bound


# ---------------------------------------------------------------------------
# bench_serving
# ---------------------------------------------------------------------------


def test_find_knee():
    rows = [
        {"offered_rps": 50, "p99_latency_s": 0.01, "achieved_rps": 49.0},
        {"offered_rps": 100, "p99_latency_s": 0.2, "achieved_rps": 60.0},
        {"offered_rps": 200, "p99_latency_s": 0.9, "achieved_rps": 61.0},
    ]
    assert bench_serving.find_knee(rows, slo_ms=50.0) == 100  # p99 breach
    assert bench_serving.find_knee(rows, slo_ms=None) == 100  # achieved sag
    assert bench_serving.find_knee(rows[:1], slo_ms=50.0) is None


def test_bench_serving_sweep_record(data_dir):
    run = _session(data_dir, dp=2)
    rec = bench_serving.sweep(
        run, rates=[500.0, 2000.0], n_requests=10, seed=3, slo_ms=10_000
    )
    assert rec["bench"] == "serving" and rec["bench_version"] == 1
    assert rec["config"]["dp"] == 2 and rec["config"]["seed"] == 3
    assert rec["latency_bound_s"] is not None
    assert [row["offered_rps"] for row in rec["sweep"]] == [500.0, 2000.0]
    for row in rec["sweep"]:
        assert row["completed"] == 10 and row["dropped"] == 0
        assert row["p50_latency_s"] > 0 and row["p99_latency_s"] > 0
        assert row["queue_depth_max"] >= 0
        assert 0 <= row["padding_waste"] < 1
    json.dumps(rec)  # the record is strict-JSON-able as published


# ---------------------------------------------------------------------------
# serve CLI + report Serving section
# ---------------------------------------------------------------------------


def test_serve_cli_verify_and_report_section(data_dir, tmp_path, capsys):
    """The serve entry point end-to-end, in-process: seeded Poisson load on
    dp=2 with --verify (bitwise parity) and --audit, schema-v5 records in
    the JSONL, and the report CLI rendering the Serving section with an
    SLO verdict — the make serve-smoke contract in miniature."""
    from shallowspeed_tpu.observability import read_jsonl
    from shallowspeed_tpu.observability.report import main as report_main
    from shallowspeed_tpu.serving.__main__ import main as serve_main

    out = tmp_path / "serve.jsonl"
    rc = serve_main(
        [
            "--dp", "2", "--schedule", "gpipe",
            "--global-batch-size", str(GBS),
            "--data-dir", str(data_dir),
            "--requests", "12", "--rate", "2000", "--seed", "0",
            "--slo-ms", "10000", "--verify", "--audit",
            "--slot-ladder", "1,2,4",
            "--metrics-out", str(out),
        ]
    )
    assert rc == 0
    text = capsys.readouterr().out
    assert "12/12 responses bitwise-equal" in text
    recs = read_jsonl(out)
    reqs = [r for r in recs if r["kind"] == "request"]
    assert len(reqs) == 12 and all(r["name"] == "ok" for r in reqs)
    assert [r for r in recs if r["kind"] == "serving"]
    audits = [r for r in recs if r["kind"] == "xla_audit"]
    assert audits and all(r["census_ok"] for r in audits)
    rc = report_main([str(out), "--format", "md", "--slo-ms", "10000"])
    assert rc == 0
    rendered = capsys.readouterr().out
    assert "## Serving" in rendered
    assert "SLO MET" in rendered
    assert "model floor" in rendered


def test_report_serving_section_from_requests_only(tmp_path, capsys):
    """A killed run's request records alone still render the section
    (percentiles recomputed), and the SLO verdict flips with --slo-ms."""
    from shallowspeed_tpu.observability.report import build_report, render

    recs = [
        {
            "v": 5, "ts": 0.0, "kind": "request", "name": "ok", "id": i,
            "rows": 2, "slots": 1, "latency_s": 0.010 + 0.001 * i,
            "queue_s": 0.001,
        }
        for i in range(10)
    ] + [
        {"v": 5, "ts": 0.0, "kind": "request", "name": "dropped", "id": 10,
         "rows": 1, "slots": 1, "latency_s": None, "queue_s": None},
    ]
    rep = build_report(recs, source="x", slo_ms=50.0)
    srv = rep["serving"]
    assert srv["completed"] == 10 and srv["dropped"] == 1
    assert 0.010 <= srv["p50_latency_s"] <= 0.020
    assert srv["slo_verdict"].startswith("SLO MET")
    tight = build_report(recs, source="x", slo_ms=1.0)["serving"]
    assert tight["slo_verdict"].startswith("SLO VIOLATED")
    none = build_report(recs, source="x")["serving"]
    assert "no SLO threshold" in none["slo_verdict"]
    out = render(rep, "md")
    assert "## Serving" in out and "DROPPED" in out
    # pre-v5 streams omit the section entirely
    old = build_report(
        [{"v": 1, "ts": 0.0, "kind": "event", "name": "epoch", "loss": 1.0}],
        source="y",
    )
    assert old["serving"] is None
    assert "## Serving" not in render(old, "md")
