"""Serve entry point: wire a checkpoint to an engine — or a FLEET — and
drive it.

    python -m shallowspeed_tpu.serving [--dp N] [--pp M] [--schedule gpipe]
        [--checkpoint ck.npz] [--requests 200] [--rate 100] [--seed 0]
        [--slo-ms 50] [--verify] [--audit] [--metrics-out serve.jsonl]
        [--faults SPEC] [--retry-budget 2] [--breaker 3]
        [--fleet N] [--fleet-policy least_queue|p2c] [--fleet-retry 2]

Builds a ``TrainingSession`` on the requested layout (restoring
``--checkpoint`` through the PR6 loader when given — any saved layout serves
on any serving layout), wraps it in a ``ServingEngine``, and drives seeded
Poisson load through it in open- or closed-loop mode. ``--audit`` verifies
every compiled inference program's collective census against the
forward-only serving contract before it serves a request; ``--verify``
re-computes every ``"ok"`` response with a direct ``session.predict()`` of
the same rows and demands bitwise equality — the ``make serve-smoke``
contract. ``--faults`` injects the chaos plan (``@dispatch=`` grammar,
docs/robustness.md; also read from ``SHALLOWSPEED_FAULTS``, so a
subprocess can be killed without patching it). The loadgen drivers are
the operator loop: an injected ``die`` (mode=exc) is absorbed and the
loop re-enters with the queue intact, while ``mode=sigkill`` kills the
process honestly — the per-record-flushed JSONL keeps everything up to
the kill.

``--fleet N`` serves through a ``ServingFleet`` instead: N replica worker
processes (each its own JAX runtime + session on the requested layout,
ladder warmed before it takes traffic) behind the router
(docs/serving.md "Fleet"). Every per-engine flag applies PER REPLICA
(``--faults`` / ``SHALLOWSPEED_FAULTS`` inject into every worker — a
``die@dispatch=N:mode=sigkill`` plan kills replicas honestly and
exercises failover); ``--verify`` moves the bitwise-parity check into
each worker, per response. Without ``--checkpoint`` the replicas
initialize identically (deterministic seeded init), so fleet responses
stay replica-independent either way. Workers write per-replica
``<metrics-out>.r{replica_id}`` JSONL shards beside the parent's file.

Graceful drain: SIGTERM/SIGINT stop ADMISSION (no further requests are
submitted), drain everything already queued to a terminal verdict, flush
the metrics sink, and exit under the normal code contract — a preempted
server loses nothing it accepted.

With ``--metrics-out`` every request also leaves a schema-v10 span chain
(``trace`` records: queue/pack/dispatch/verify/ack — and, in fleet mode,
the cross-process fleet.queue/route/failover spans plus the per-replica
clock-offset handshake records in the parent file): render the Tracing
section with ``python -m shallowspeed_tpu.observability.report
<metrics-out>*`` to see per-phase latency attribution and the worst-k
request waterfalls (docs/observability.md § Tracing).

The stream also carries the live telemetry (schema v11): tumbling
``rollup`` windows and SLO ``alert`` transitions from the engine — or,
in fleet mode, from the parent AND each replica's ``.r*`` shard. Tail a
running server with ``python -m shallowspeed_tpu.observability.watch
<metrics-out> --follow``, or render a finished run with ``--once``.
``--knee-rps`` arms the knee-proximity alert rule with the measured
saturation knee from a ``bench_serving`` sweep record (the rule stays
off without it — measured evidence only, docs/observability.md § Live
telemetry & alerting).

Exit codes (aligned with train.py's documented contract):
  0  clean — including a signal-drained run whose accepted requests all
     served;
  1  failed responses: dropped / expired / error / unhealthy verdicts, or
     a bitwise mismatch under --verify (or an audit mismatch raising out
     of warm-up);
  2  usage errors (argparse);
  3  DEGRADED at exit — the health breaker is still open; in fleet mode,
     the fleet is still degraded (a QUORUM of replicas down) at exit
     (train.py's 3 is the health-monitor halt; this is its serving
     mirror).
"""

import argparse
import signal
import sys

import numpy as np


class GracefulStop:
    """The SIGTERM/SIGINT latch: ``install()`` registers both handlers
    (remembering the previous ones for ``restore()`` — the entry point is
    also invoked in-process by tests), the drivers poll ``stop()``."""

    def __init__(self):
        self.signum = None
        self._previous = {}

    def _handle(self, signum, frame):
        self.signum = signum

    def stop(self):
        return self.signum is not None

    def install(self):
        for s in (signal.SIGTERM, signal.SIGINT):
            self._previous[s] = signal.signal(s, self._handle)
        return self

    def restore(self):
        for s, h in self._previous.items():
            signal.signal(s, h)
        self._previous.clear()


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m shallowspeed_tpu.serving",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor (model-axis) parallelism: serve through Megatron-"
        "sharded layers (forward-only — one all-reduce per row-parallel "
        "layer; --audit verifies the per-layer-pair tp all-reduces and "
        "still forbids every gradient collective)",
    )
    ap.add_argument(
        "--schedule",
        choices=["naive", "gpipe", "pipedream", "interleaved"],
        default="gpipe",
    )
    ap.add_argument("--virtual-stages", type=int, default=1)
    ap.add_argument("--global-batch-size", type=int, default=128)
    ap.add_argument("--mubatches", type=int, default=4)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument(
        "--checkpoint",
        default=None,
        help="weights to serve (any layout's checkpoint restores onto the "
        "serving layout)",
    )
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rate", type=float, default=100.0, help="offered rps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rows", default="1,2,3,4,8", help="request row-count choices"
    )
    ap.add_argument("--slo-ms", type=float, default=None)
    ap.add_argument(
        "--knee-rps",
        type=float,
        default=None,
        help="measured saturation knee (bench_serving sweep record's "
        "knee_rps) — arms the knee-proximity alert rule; absent = rule off",
    )
    ap.add_argument(
        "--deadline-ms",
        type=float,
        default=None,
        help="per-request deadline tag (default: score against --slo-ms); "
        "expired deadlines are SHED with verdict 'expired' at pack time",
    )
    ap.add_argument(
        "--closed-loop",
        type=int,
        default=0,
        metavar="C",
        help="drive a fixed population of C in-flight requests instead of "
        "open-loop Poisson arrivals",
    )
    ap.add_argument(
        "--max-slots",
        type=int,
        default=None,
        help="packing capacity per dispatch (default: the ladder's top rung)",
    )
    ap.add_argument(
        "--slot-rows",
        type=int,
        default=None,
        help="global rows per microbatch slot (default: 8, rounded up to a "
        "dp multiple)",
    )
    ap.add_argument(
        "--slot-ladder",
        default=None,
        help="comma-separated slot counts per dispatch (default 1,2,4,8,16) "
        "— bounds compiled inference programs at one per rung",
    )
    ap.add_argument(
        "--faults",
        default=None,
        help="chaos injection spec (e.g. 'error@dispatch=4,slow@dispatch=6"
        ":ms=50'); default: the SHALLOWSPEED_FAULTS environment plan",
    )
    ap.add_argument(
        "--retry-budget",
        type=int,
        default=2,
        help="total dispatch attempts per request before verdict 'error' "
        "(the shared retry.RetryPolicy budget)",
    )
    ap.add_argument(
        "--breaker",
        type=int,
        default=3,
        help="consecutive failed dispatches that open the health breaker "
        "(degraded: admission refused; exit 3 if still open at exit)",
    )
    ap.add_argument(
        "--fleet",
        type=int,
        default=0,
        metavar="N",
        help="serve through a ServingFleet of N replica worker processes "
        "(each its own JAX runtime on this layout) instead of one "
        "in-process engine; exit 3 if a quorum of replicas is down at "
        "exit",
    )
    ap.add_argument(
        "--fleet-policy",
        choices=["least_queue", "p2c"],
        default="least_queue",
        help="fleet placement policy: least outstanding load, or "
        "power-of-two-choices",
    )
    ap.add_argument(
        "--fleet-retry",
        type=int,
        default=2,
        help="fleet-level placement budget per request (the shared "
        "retry.RetryPolicy, one attempt per routing) — failover and "
        "verdict reroutes consume it",
    )
    ap.add_argument(
        "--fleet-max-queue",
        type=int,
        default=None,
        help="bounded fleet queue: admissions beyond it are DROPPED "
        "(reason fleet_queue_full); default unbounded",
    )
    ap.add_argument(
        "--verify",
        action="store_true",
        help="re-compute every 'ok' response with a direct predict() of the "
        "same rows and demand bitwise equality (exit 1 on any mismatch)",
    )
    ap.add_argument(
        "--audit",
        action="store_true",
        help="census every compiled inference program against the "
        "forward-only serving contract before the first dispatch",
    )
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    if args.fleet:
        return _fleet_main(args)

    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.compile_cache import enable_compile_cache
    from shallowspeed_tpu.observability import JsonlMetrics
    from shallowspeed_tpu.serving.engine import ServingEngine
    from shallowspeed_tpu.serving.loadgen import (
        poisson_arrivals,
        request_payloads,
        run_closed_loop,
        run_open_loop,
    )

    enable_compile_cache()
    metrics = JsonlMetrics(args.metrics_out) if args.metrics_out else None
    session = TrainingSession(
        dp=args.dp,
        pp=args.pp,
        tp=args.tp,
        schedule=args.schedule,
        virtual_stages=args.virtual_stages,
        global_batch_size=args.global_batch_size,
        mubatches=args.mubatches,
        data_dir=args.data_dir,
        resume=args.checkpoint,
        metrics=metrics,
        audit=args.audit,
        predict_slot_rows=args.slot_rows,
        predict_slot_ladder=(
            tuple(int(r) for r in args.slot_ladder.split(","))
            if args.slot_ladder
            else None
        ),
    )
    engine = ServingEngine(
        session,
        max_slots=args.max_slots,
        slo_ms=args.slo_ms,
        metrics=metrics,
        retry=args.retry_budget,
        breaker_threshold=args.breaker,
        faults=args.faults,
        knee_rps=args.knee_rps,
    )
    payloads = request_payloads(
        args.requests,
        session.spec.sizes[0],
        seed=args.seed,
        rows_choices=tuple(int(r) for r in args.rows.split(",") if r.strip()),
    )
    print(
        f"serving: DP={args.dp} x PP={args.pp} ({args.schedule}), "
        f"slot_rows={session.slot_rows}, ladder={session.slot_ladder}, "
        f"{args.requests} requests"
        + (
            f" closed-loop C={args.closed_loop}"
            if args.closed_loop
            else f" @ {args.rate} rps Poisson (seed {args.seed})"
        )
        + (f", weights from {args.checkpoint}" if args.checkpoint else "")
    )
    # warm every ladder rung before traffic: the measured percentiles must
    # be serving latency, not XLA compile time (and under --audit this is
    # also where every inference program's census gets verified)
    engine.warm_ladder()
    stopper = GracefulStop().install()
    try:
        if args.closed_loop:
            done = run_closed_loop(
                engine, payloads, concurrency=args.closed_loop,
                deadline_ms=args.deadline_ms, should_stop=stopper.stop,
            )
        else:
            arrivals = poisson_arrivals(args.rate, args.requests, seed=args.seed)
            done = run_open_loop(
                engine, payloads, arrivals, deadline_ms=args.deadline_ms,
                should_stop=stopper.stop,
            )
    finally:
        stopper.restore()
    rec = engine.record_summary(
        offered_rps=None if args.closed_loop else args.rate
    )
    if stopper.stop():
        sig = signal.Signals(stopper.signum).name
        print(
            f"{sig} received: admission stopped, queue drained "
            f"({rec['completed']} served of {len(done)} accepted)"
        )

    def ms(v):
        return f"{v * 1e3:.2f} ms" if v is not None else "n/a"

    print(
        f"completed {rec['completed']}/{args.requests}, dropped "
        f"{rec['dropped']}, expired {rec['expired']}, errors "
        f"{rec['errors']}, unhealthy {rec['unhealthy']}, "
        f"{rec['dispatches']} dispatches "
        f"({rec['slots_dispatched']} slots"
        + (
            f", padding waste {rec['padding_waste'] * 100:.1f}%)"
            if rec["padding_waste"] is not None
            else ")"
        )
    )
    print(
        f"latency p50 {ms(rec['p50_latency_s'])}, p99 "
        f"{ms(rec['p99_latency_s'])}, model floor "
        f"{ms(rec['latency_bound_s'])} ({rec['latency_bound_source']})"
    )
    if rec["goodput_rps"] is not None:
        print(
            f"goodput {rec['goodput_rps']:.1f} rps ({rec['slo_met']}/"
            f"{rec['completed']} within SLO), queue depth max "
            f"{rec['queue_depth_max']}"
        )
    if rec["breaker_trips"] or rec["reloads"]:
        print(
            f"degradation: {rec['breaker_trips']} breaker trip(s), "
            f"{rec['reloads']} reload(s)"
            + (
                f", recovered in {rec['recovery_s'] * 1e3:.1f} ms"
                if rec["recovery_s"] is not None
                else ""
            )
        )
    failures = (
        rec["dropped"] + rec["expired"] + rec["errors"] + rec["unhealthy"]
    )
    if args.verify:
        served = [r for r in done if r.verdict == "ok"]
        mismatched = 0
        for req in sorted(served, key=lambda r: r.id):
            direct = session.predict(payloads[req.id])  # ids are submit order
            if not np.array_equal(req.result, direct):
                mismatched += 1
        print(
            f"verify: {len(served) - mismatched}/{len(served)} responses "
            "bitwise-equal to direct predict()"
            + ("" if mismatched == 0 else f" — {mismatched} MISMATCHED")
        )
        failures += mismatched
    if metrics is not None:
        metrics.close()
        print(
            f"telemetry written: {metrics.path} (request + trace records; "
            "the report CLI renders the Serving and Tracing sections)"
        )
    if engine.degraded:
        print("serving: engine DEGRADED at exit (breaker open)", file=sys.stderr)
        return 3
    if failures:
        print(
            f"serving: {failures} dropped/expired/errored/unhealthy/"
            "incorrect response(s)",
            file=sys.stderr,
        )
        return 1
    return 0


def _fleet_main(args):
    """The ``--fleet N`` serve path: N replica workers behind the router,
    the same seeded load, the same exit-code contract (module
    docstring)."""
    from shallowspeed_tpu.observability import JsonlMetrics
    from shallowspeed_tpu.serving.fleet import ServingFleet
    from shallowspeed_tpu.serving.loadgen import (
        payload_in_dim,
        poisson_arrivals,
        request_payloads,
        run_closed_loop,
        run_open_loop,
    )

    metrics = JsonlMetrics(args.metrics_out) if args.metrics_out else None
    worker_config = {
        "session": dict(
            dp=args.dp,
            pp=args.pp,
            tp=args.tp,
            schedule=args.schedule,
            virtual_stages=args.virtual_stages,
            global_batch_size=args.global_batch_size,
            mubatches=args.mubatches,
            data_dir=args.data_dir,
            resume=args.checkpoint,
            audit=args.audit,
            predict_slot_rows=args.slot_rows,
            predict_slot_ladder=(
                tuple(int(r) for r in args.slot_ladder.split(","))
                if args.slot_ladder
                else None
            ),
        ),
        "engine": dict(
            max_slots=args.max_slots,
            slo_ms=args.slo_ms,
            retry=args.retry_budget,
            breaker_threshold=args.breaker,
            faults=args.faults,
            knee_rps=args.knee_rps,
        ),
        "verify": args.verify,
    }
    fleet = ServingFleet(
        worker_config,
        n_replicas=args.fleet,
        policy=args.fleet_policy,
        max_queue=args.fleet_max_queue,
        slo_ms=args.slo_ms,
        retry=args.fleet_retry,
        metrics=metrics,
        seed=args.seed,
        knee_rps=args.knee_rps,
    )
    print(
        f"fleet: {args.fleet} replicas x (DP={args.dp} x PP={args.pp} x "
        f"TP={args.tp}, {args.schedule}), policy {args.fleet_policy}, "
        f"{args.requests} requests"
        + (
            f" closed-loop C={args.closed_loop}"
            if args.closed_loop
            else f" @ {args.rate} rps Poisson (seed {args.seed})"
        )
        + (f", weights from {args.checkpoint}" if args.checkpoint else "")
    )
    stopper = GracefulStop().install()
    try:
        fleet.start()  # every replica's ladder warmed before traffic
        payloads = request_payloads(
            args.requests,
            payload_in_dim(args.data_dir),
            seed=args.seed,
            rows_choices=tuple(
                int(r) for r in args.rows.split(",") if r.strip()
            ),
        )
        if args.closed_loop:
            done = run_closed_loop(
                fleet, payloads, concurrency=args.closed_loop,
                deadline_ms=args.deadline_ms, should_stop=stopper.stop,
            )
        else:
            arrivals = poisson_arrivals(args.rate, args.requests, seed=args.seed)
            done = run_open_loop(
                fleet, payloads, arrivals, deadline_ms=args.deadline_ms,
                should_stop=stopper.stop,
            )
        rec = fleet.record_summary(
            offered_rps=None if args.closed_loop else args.rate
        )
    finally:
        stopper.restore()
        fleet.stop()
    if stopper.stop():
        sig = signal.Signals(stopper.signum).name
        print(
            f"{sig} received: admission stopped, fleet drained "
            f"({rec['completed']} served)"
        )

    def ms(v):
        return f"{v * 1e3:.2f} ms" if v is not None else "n/a"

    print(
        f"completed {rec['completed']}/{args.requests}, dropped "
        f"{rec['dropped']}, expired {rec['expired']}, errors "
        f"{rec['errors']}, unhealthy {rec['unhealthy']}; latency p50 "
        f"{ms(rec['p50_latency_s'])}, p99 {ms(rec['p99_latency_s'])}"
    )
    routing = ", ".join(
        f"r{rid}: {n}" for rid, n in sorted(rec["routing"].items())
    )
    print(
        f"routing: {routing}"
        + (
            f" — skew {rec['routing_skew']:.2f}x"
            if rec["routing_skew"] is not None
            else ""
        )
    )
    if rec["failovers"] or rec["replicas_dead"]:
        print(
            f"failover: {rec['replicas_dead']} replica death(s), "
            f"{rec['failovers']} failover(s), {rec['failover_requeued']} "
            f"in-flight re-queued, {rec['reroutes']} reroute(s)"
            + (
                f", recovered in {rec['recovery_s'] * 1e3:.1f} ms"
                if rec["recovery_s"] is not None
                else ""
            )
        )
    if args.verify:
        served = rec["completed"]
        mism = rec["parity_mismatches"]
        print(
            f"verify: {served - mism}/{served} responses bitwise-equal to "
            "the serving replica's direct predict()"
            + ("" if mism == 0 else f" — {mism} MISMATCHED")
        )
    if metrics is not None:
        metrics.close()
        print(
            f"telemetry written: {metrics.path} (+ .r* replica shards; "
            "pass the glob to the report CLI for the merged Fleet and "
            "Tracing sections)"
        )
    failures = (
        rec["dropped"] + rec["expired"] + rec["errors"] + rec["unhealthy"]
        + rec["parity_mismatches"]
    )
    if rec["degraded"]:
        print(
            "serving: fleet DEGRADED at exit (quorum of replicas down)",
            file=sys.stderr,
        )
        return 3
    if failures:
        print(
            f"serving: {failures} dropped/expired/errored/unhealthy/"
            "incorrect response(s)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
