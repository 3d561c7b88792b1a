"""Distributed request tracing tests: the span Tracer, the chain reader
(assembly, clock alignment, completeness refusal), phase attribution and
waterfalls — and the fleet-level legs:
handshake-aligned joins against an artificially skewed worker clock, and
the SIGKILL failover chain (docs/observability.md § Tracing).

Multi-process tests carry the ``fleet`` marker and skip-with-reason when
the platform cannot spawn worker processes (the test_fleet convention).
"""

import os
import time

import numpy as np
import pytest

from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl, tracing
from shallowspeed_tpu.observability.metrics import SCHEMA_VERSION
from shallowspeed_tpu.serving import fleet as fleet_mod
from shallowspeed_tpu.serving import loadgen
from shallowspeed_tpu.serving.fleet import ServingFleet

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
GBS = 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


# ---------------------------------------------------------------------------
# Tracer + reader units (no processes, no jax programs)
# ---------------------------------------------------------------------------


def test_tracer_emits_linked_closed_spans(tmp_path):
    path = tmp_path / "t.jsonl"
    with JsonlMetrics(path) as m:
        tr = tracing.Tracer(m, process="f")
        tid = tr.new_trace(7)
        assert tid == "f-7"
        root = tr.span("fleet.queue", tid, 1.0, 1.2, parent=None)
        route = tr.span("route", tid, 1.2, 1.21, parent=root, to_replica=0)
        ack = tr.span(
            "ack", tid, 1.5, 1.5, parent=route, terminal=True, verdict="ok"
        )
        assert root and route and ack and len({root, route, ack}) == 3
    recs = read_jsonl(path)
    spans = [r for r in recs if r["kind"] == "trace"]
    assert [s["name"] for s in spans] == ["fleet.queue", "route", "ack"]
    assert spans[1]["parent_id"] == root and spans[2]["terminal"] is True
    assert all(r["v"] == SCHEMA_VERSION for r in spans)


def test_tracer_disabled_costs_nothing():
    from shallowspeed_tpu.observability import NullMetrics

    tr = tracing.Tracer(NullMetrics(), process="e")
    assert tr.enabled is False
    assert tr.span("dispatch", "e-1", 0.0, 1.0) is None
    tr.clock_offset(0, 1.0, 0.001, 0.0005)  # no-op, no raise


def _span(name, trace_id, span_id, t0, t1, parent=None, clock="parent",
          replica_id=None, terminal=False, **fields):
    return {
        "v": SCHEMA_VERSION, "ts": 0.0, "kind": "trace", "name": name,
        "trace_id": trace_id, "span_id": span_id, "parent_id": parent,
        "t0": t0, "t1": t1, "clock": clock, "replica_id": replica_id,
        "terminal": terminal, **fields,
    }


def _offset(replica_id, offset_s, uncertainty_s=0.0001):
    return {
        "v": SCHEMA_VERSION, "ts": 0.0, "kind": "trace",
        "name": "clock_offset", "trace_id": None, "span_id": None,
        "parent_id": None, "t0": None, "t1": None, "clock": "parent",
        "replica_id": replica_id, "terminal": False,
        "offset_s": offset_s, "rtt_s": 2 * uncertainty_s,
        "uncertainty_s": uncertainty_s,
    }


def _request(trace_id, verdict="ok"):
    return {
        "v": SCHEMA_VERSION, "ts": 0.0, "kind": "request", "name": verdict,
        "id": 0, "trace_id": trace_id,
    }


def test_reader_aligns_worker_clock_exactly():
    """Worker spans shifted by a known offset land back on the parent
    timeline once the clock_offset record is applied — cross-process
    durations (including the pipe-hop gaps) reconstruct exactly."""
    off = 5.0  # worker clock runs 5 s ahead of the parent's
    recs = [
        _offset(0, off),
        _span("fleet.queue", "f-0", "f.1", 10.00, 10.01),
        _span("route", "f-0", "f.2", 10.01, 10.012, parent="f.1"),
        _span("worker.queue", "f-0", "r0.1", 10.02 + off, 10.05 + off,
              parent="f.2", clock="worker", replica_id=0),
        _span("dispatch", "f-0", "r0.2", 10.05 + off, 10.09 + off,
              parent="r0.1", clock="worker", replica_id=0),
        _span("ack", "f-0", "f.3", 10.10, 10.10, parent="r0.2",
              terminal=True, verdict="ok"),
        _request("f-0"),
    ]
    chains = tracing.assemble_chains(recs)
    chain = chains["f-0"]
    assert chain.alignment == "aligned"
    assert tracing.verify_terminal_chains(recs, chains) == []
    wq = next(s for s in chain.spans if s["name"] == "worker.queue")
    assert wq["t0_aligned"] == pytest.approx(10.02)
    phases = tracing.chain_phases(chain)
    assert phases["worker.queue"] == pytest.approx(0.03)
    assert phases["dispatch"] == pytest.approx(0.04)
    # the forward pipe hop (route end -> worker admission) charges to
    # route; the return hop (dispatch end -> ack) charges to ack
    assert phases["route"] == pytest.approx(0.002 + 0.008)
    assert phases["ack"] == pytest.approx(0.01)
    # phases cover the whole latency, exactly
    assert sum(phases.values()) == pytest.approx(chain.latency_s)
    assert chain.latency_s == pytest.approx(0.10)


def test_reader_flags_missing_alignment_as_degraded():
    """Worker spans with NO recorded offset are never silently joined:
    the chain is flagged, and completeness still holds (alignment
    quality and causal completeness are separate verdicts)."""
    recs = [
        _span("fleet.queue", "f-1", "f.1", 0.0, 0.1),
        _span("worker.queue", "f-1", "r3.1", 100.0, 100.2, parent="f.1",
              clock="worker", replica_id=3),
        _span("ack", "f-1", "f.2", 0.3, 0.3, parent="r3.1", terminal=True,
              verdict="ok"),
        _request("f-1"),
    ]
    chains = tracing.assemble_chains(recs)
    assert chains["f-1"].alignment == "missing"
    assert tracing.verify_terminal_chains(recs, chains) == []


def test_reader_refuses_orphan_and_unclosed_chains():
    """The completeness gate: a terminal request whose chain has an
    orphan span (parent id absent), an unclosed span, or no chain at all
    is REFUSED with the trace named — strict mode raises TraceError."""
    recs = [
        # orphan: parent f.99 never emitted
        _span("route", "t-a", "f.1", 0.0, 0.1, parent="f.99"),
        _span("ack", "t-a", "f.2", 0.2, 0.2, parent="f.1", terminal=True),
        _request("t-a"),
        # unclosed: t1 missing
        _span("dispatch", "t-b", "f.3", 0.0, None),
        _span("ack", "t-b", "f.4", 0.2, 0.2, parent="f.3", terminal=True),
        _request("t-b"),
        # no terminal span
        _span("fleet.queue", "t-c", "f.5", 0.0, 0.1),
        _request("t-c"),
        # no chain at all
        _request("t-d"),
        # and one healthy chain
        _span("ack", "t-e", "f.6", 0.0, 0.0, terminal=True, verdict="ok"),
        _request("t-e"),
    ]
    problems = tracing.verify_terminal_chains(recs)
    text = "\n".join(problems)
    assert "t-a" in text and "orphan" in text
    assert "t-b" in text and "unclosed" in text
    assert "t-c" in text and "no terminal" in text
    assert "t-d" in text and "no span chain" in text
    assert "t-e" not in text
    with pytest.raises(tracing.TraceError, match="t-a"):
        tracing.verify_terminal_chains(recs, strict=True)


def test_attribution_p99_conditional_and_slo_burn():
    """The makespan-quantization scoreboard: many fast queue-dominated
    chains plus one slow dispatch-dominated outlier — the MEAN
    attribution and the P99-CONDITIONAL attribution must disagree, the
    tail naming dispatch as dominant. SLO burn scores phase seconds
    against the deadline budget."""
    recs = []
    for i in range(50):
        t0 = float(i)
        recs += [
            _span("worker.queue", f"e-{i}", f"e.{3 * i + 1}", t0, t0 + 0.008),
            _span("dispatch", f"e-{i}", f"e.{3 * i + 2}", t0 + 0.008,
                  t0 + 0.010, parent=f"e.{3 * i + 1}"),
            _span("ack", f"e-{i}", f"e.{3 * i + 3}", t0 + 0.010, t0 + 0.010,
                  parent=f"e.{3 * i + 2}", terminal=True, verdict="ok",
                  deadline_ms=100.0),
            _request(f"e-{i}"),
        ]
    # the outlier: 1 s of dispatch
    recs += [
        _span("worker.queue", "e-x", "e.900", 90.0, 90.01),
        _span("dispatch", "e-x", "e.901", 90.01, 91.01, parent="e.900"),
        _span("ack", "e-x", "e.902", 91.01, 91.01, parent="e.901",
              terminal=True, verdict="ok", deadline_ms=100.0),
        _request("e-x"),
    ]
    chains = tracing.assemble_chains(recs)
    att = tracing.attribution(chains, worst_k=2)
    assert att["chains"] == 51
    # mean is time-weighted; the tail is dispatch
    assert att["p99_dominant_phase"] == "dispatch"
    assert att["phases_p99"]["dispatch"] > 0.95
    # queue dominates the typical request but not the tail
    assert att["phases_mean"]["worker.queue"] < 0.5
    assert att["slo_chains"] == 51
    assert att["slo_burn"]["dispatch"] > 0.0
    # worst-k is the outlier first; its waterfall renders bars + times
    worst = att["worst"]
    assert worst[0].trace_id == "e-x"
    lines = tracing.waterfall(worst[0])
    assert "e-x" in lines[0] and "ok" in lines[0]
    assert any("dispatch" in ln and "█" in ln for ln in lines[1:])


def test_engine_chains_complete_for_every_terminal_verdict(data_dir, tmp_path):
    """Standalone engine end to end: ok, expired and dropped requests all
    leave complete chains (trace_id stamped on their request records),
    attribution phases sum exactly to each chain's latency, and the
    report CLI renders the Tracing section from the same file."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.observability.report import build_report, render
    from shallowspeed_tpu.serving.engine import ServingEngine

    path = tmp_path / "serve.jsonl"
    m = JsonlMetrics(path)
    session = TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
        metrics=m, predict_slot_ladder=(1, 2),
    )
    engine = ServingEngine(session, metrics=m, slo_ms=5000, max_queue=4)
    engine.warm_ladder()
    rng = np.random.RandomState(1)
    for _ in range(4):
        engine.submit(rng.randn(2, SIZES[0]).astype(np.float32))
    # bounded admission: the 5th is dropped (terminal at submit)
    dropped = engine.submit(rng.randn(1, SIZES[0]).astype(np.float32))
    assert dropped.verdict == "dropped"
    engine.drain()
    # an already-expired deadline is shed at pack time
    engine.submit(
        rng.randn(1, SIZES[0]).astype(np.float32), deadline_ms=0.0001
    )
    time.sleep(0.005)
    engine.drain()
    m.close()
    recs = read_jsonl(path)
    chains = tracing.assemble_chains(recs)
    assert tracing.verify_terminal_chains(recs, chains) == []
    verdicts = {c.verdict for c in chains.values()}
    assert verdicts == {"ok", "dropped", "expired"}
    for c in chains.values():
        phases = tracing.chain_phases(c)
        assert sum(phases.values()) == pytest.approx(c.latency_s)
    # every terminal request record carries the join key
    reqs = [r for r in recs if r["kind"] == "request"]
    assert reqs and all(r.get("trace_id") in chains for r in reqs)
    report = build_report(recs, source="serve.jsonl", slo_ms=5000)
    assert report["tracing"]["problems"] == []
    text = render(report, "md")
    assert "## Tracing" in text
    assert "phase attribution (mean)" in text
    assert "slowest requests:" in text


def test_engine_failed_dispatch_exhaustion_chain(data_dir, tmp_path):
    """A permanently-failing dispatch: the retry budget exhausts, the
    request terminates as "error", and its chain is still complete —
    nothing ever vanishes from the trace either."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.serving.engine import ServingEngine

    path = tmp_path / "err.jsonl"
    m = JsonlMetrics(path)
    session = TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
        metrics=m, predict_slot_ladder=(1, 2),
    )
    engine = ServingEngine(session, metrics=m, retry=2)
    engine.warm_ladder()

    def boom(x):
        raise RuntimeError("injected dispatch failure")

    session.predict = boom
    engine.submit(np.zeros((1, SIZES[0]), np.float32))
    done = engine.drain()
    assert [r.verdict for r in done] == ["error"]
    m.close()
    recs = read_jsonl(path)
    chains = tracing.assemble_chains(recs)
    assert tracing.verify_terminal_chains(recs, chains) == []
    (chain,) = chains.values()
    assert chain.verdict == "error"
    assert [s["name"] for s in chain.spans] == ["worker.queue", "ack"]


# ---------------------------------------------------------------------------
# the fleet legs: skewed-clock alignment + SIGKILL failover chains
# ---------------------------------------------------------------------------


def _require_workers():
    if not fleet_mod.fleet_workers_supported():
        pytest.skip(
            "this platform cannot spawn fleet worker processes "
            "(multiprocessing spawn context unavailable or broken)"
        )


def _worker_config(data_dir, clock_offset_s=None):
    cfg = {
        "session": dict(
            sizes=SIZES,
            global_batch_size=GBS,
            lr=0.01,
            data_dir=os.fspath(data_dir),
            predict_slot_ladder=(1, 2),
        ),
        "engine": dict(retry=2, breaker_threshold=3),
        "verify": True,
    }
    if clock_offset_s is not None:
        cfg["clock_offset_s"] = clock_offset_s
    return cfg


def _drive_fleet(fleet, n_requests, rate=300.0, kill_after=None):
    """Seeded open-loop drive; optionally SIGKILL the busiest ready
    replica once ``kill_after`` requests completed. Returns (submitted,
    done, killed_replica_id)."""
    payloads = loadgen.request_payloads(n_requests, SIZES[0], seed=0)
    arrivals = loadgen.poisson_arrivals(rate, n_requests, seed=0)
    t0 = fleet.clock()
    i, killed = 0, None
    submitted, done = [], []
    while i < n_requests or fleet.queue_depth:
        now = fleet.clock() - t0
        while i < n_requests and arrivals[i] <= now:
            submitted.append(
                fleet.submit(payloads[i], arrival_t=t0 + arrivals[i])
            )
            i += 1
        done.extend(fleet.step())
        if kill_after is not None and killed is None and len(done) >= kill_after:
            ready = [
                r for r in fleet.replicas.values() if r.state == "ready"
            ]
            victim = max(ready, key=lambda r: (r.inflight, -r.replica_id))
            fleet.sigkill_replica(victim.replica_id)
            killed = victim.replica_id
        if not fleet.queue_depth and i < n_requests:
            time.sleep(max(0.0, arrivals[i] - (fleet.clock() - t0)))
    return submitted, done, killed


@pytest.mark.fleet
def test_skewed_worker_clock_alignment_reconstructs_durations(
    data_dir, tmp_path
):
    """Satellite: inject a +3 s artificial worker clock offset (the
    worker-config test hook) and prove the handshake-aligned join
    reconstructs correct span durations — the recovered offset matches
    the injection within its own recorded uncertainty bound, worker
    spans land INSIDE their request's parent-side window, and per-chain
    phases sum to the parent-measured latency. Also: the same stream
    with the offset records STRIPPED reads as alignment-degraded, with
    the report naming the unmapped replicas instead of joining raw
    clocks."""
    _require_workers()
    from shallowspeed_tpu.observability.report import build_report, render

    SKEW = 3.0
    path = tmp_path / "skew.jsonl"
    m = JsonlMetrics(path)
    with ServingFleet(
        _worker_config(data_dir, clock_offset_s=SKEW),
        n_replicas=2, slo_ms=5000, retry=2, metrics=m, seed=0,
    ) as fleet:
        fleet.start()
        submitted, _done, _ = _drive_fleet(fleet, 16)
        fleet.record_summary()
    m.close()
    assert all(r.verdict == "ok" for r in submitted)
    recs = read_jsonl(str(path) + "*")
    offsets = tracing.clock_offsets(recs)
    assert set(offsets) == {0, 1}
    for rid, off in offsets.items():
        # the NTP-style bound is a guarantee, not a heuristic: the
        # injected skew lies within offset ± uncertainty
        assert abs(off["offset_s"] - SKEW) <= off["uncertainty_s"], (
            rid, off,
        )
        assert off["uncertainty_s"] < 0.05
    chains = tracing.assemble_chains(recs)
    assert tracing.verify_terminal_chains(recs, chains) == []
    for c in chains.values():
        assert c.alignment == "aligned"
        # worker spans, aligned, sit inside the parent-side window
        # (slack = the recorded uncertainty, not the 3 s skew)
        slack = c.uncertainty_s + 1e-4
        for s in c.spans:
            if s.get("clock") == "worker":
                assert s["t0_aligned"] >= c.t0 - slack
                assert s["t1_aligned"] <= c.t_end + slack
        phases = tracing.chain_phases(c)
        assert sum(phases.values()) == pytest.approx(
            c.latency_s, abs=4 * c.uncertainty_s + 1e-6
        )
    # strip the offsets: the join must DEGRADE loudly, not guess
    stripped = [
        r for r in recs
        if not (r.get("kind") == "trace" and r.get("name") == "clock_offset")
    ]
    degraded = tracing.assemble_chains(stripped)
    assert all(c.alignment == "missing" for c in degraded.values())
    report = build_report(stripped, source="stripped")
    assert report["tracing"]["alignment_missing_replicas"] == [0, 1]
    assert "ALIGNMENT DEGRADED" in render(report, "md")


@pytest.mark.fleet
def test_sigkill_failover_chain_links_dead_replica_to_completion(
    data_dir, tmp_path
):
    """Satellite: SIGKILL a replica mid-soak (the fleet-smoke anchor) and
    assert the re-queued requests' chains carry a failover.requeue span
    linking the dead replica's partial chain to the surviving replica's
    completion — and NO terminal request is left with an orphan or
    unclosed chain, kill or no kill."""
    _require_workers()
    path = tmp_path / "kill.jsonl"
    m = JsonlMetrics(path)
    with ServingFleet(
        _worker_config(data_dir),
        n_replicas=3, slo_ms=5000, retry=3, metrics=m, seed=0,
    ) as fleet:
        fleet.start()
        submitted, _done, killed = _drive_fleet(fleet, 40, kill_after=5)
        stats = fleet.stats()
        fleet.record_summary()
    m.close()
    assert killed is not None
    assert all(r.verdict != "queued" for r in submitted)
    recs = read_jsonl(str(path) + "*")
    chains = tracing.assemble_chains(recs)
    # the hard gate: zero orphan/unclosed chains across the kill
    assert tracing.verify_terminal_chains(recs, chains) == []
    if stats["failover_requeued"]:
        failover = [
            c for c in chains.values()
            if any(s["name"] == "failover.requeue" for s in c.spans)
        ]
        assert failover, "failover ran but no chain carries its span"
        for c in failover:
            fo = next(s for s in c.spans if s["name"] == "failover.requeue")
            assert fo["from_replica"] == killed
            # the span's parent is the dead replica's partial chain (its
            # route span, or the worker's last shipped span) ...
            ids = {s["span_id"]: s for s in c.spans}
            assert fo["parent_id"] in ids
            # ... and the request still reached a terminal verdict with
            # the surviving replicas
            assert c.verdict in ("ok", "error")
            if c.verdict == "ok":
                served = next(
                    s for s in c.spans if s.get("terminal")
                )["replica_id_served"]
                assert served != killed


@pytest.mark.fleet
def test_fleet_chaos_record_carries_trace_verdict(data_dir, tmp_path):
    """The bench-level gate: fleet_chaos_soak's record carries the
    span-chain completeness verdict (trace_chains / trace_problems) that
    make trace-smoke asserts on."""
    _require_workers()
    from shallowspeed_tpu.serving.bench_serving import fleet_chaos_soak

    path = tmp_path / "soak.jsonl"
    m = JsonlMetrics(path)
    record = fleet_chaos_soak(
        _worker_config(data_dir),
        in_dim=SIZES[0],
        n_replicas=2,
        kill_after=4,
        n_requests=30,
        rate=300.0,
        seed=0,
        slo_ms=5000,
        metrics=m,
        retry=3,
    )
    m.close()
    assert record["silently_lost"] == []
    assert record["trace_chains"] is not None and record["trace_chains"] > 0
    assert record["trace_problems"] == []
