"""The MPMD-vs-lockstep scoreboard (ROADMAP item 1, the dispatch-roofline
payoff): three same-window measurements on the flagship gpipe-pp4 CPU
config, written as MPMD_r01.json beside the other bench records.

1. **Epoch pair** — the same training epochs dispatched through the
   lockstep SPMD program and the MPMD per-stage runtime, interleaved per
   trial (bench.py's slope protocol), per-leg minima. Both runtimes train
   the identical math (weights hash-equal — the in-suite lattice and
   ``make mpmd-smoke`` pin that bitwise), so the wall ratio is pure
   runtime cost.

2. **Dispatch probe pair** — ``measure_dispatch_overhead`` (PR 14) on
   both runtimes, over a BOUNDED 64-batch window where the profiler
   captures the full op-event stream (``events_per_batch`` is recorded
   as the saturation check). Running this bench surfaced a measurement
   caveat on DISPATCH_r01.json itself: over multi-second instrumented
   windows the profiler drops op events, collapsing the busy union and
   inflating the share — so the committed lockstep 0.728 overstates,
   and the full-epoch regime is recorded separately with its caveat.

3. **Serving burst p99** — R one-slot requests arriving at once, drained
   (a) through the lockstep rung program, one whole-rung makespan per
   request, vs (b) through the MPMD streaming chain (``predict_async``:
   request k enters stage 0 while request k-1 occupies a later stage).
   Latency is measured from the common arrival instant — the burst's
   p50/p99 show whether tail latency is makespan-quantized.

CPU-fallback caveat, as everywhere: emulated devices validate machinery
and RELATIVE ratios, not chip performance — but the dispatch-overhead
share is exactly the number that was eating the CPU wall, so CPU is the
honest place to measure its removal.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BENCH_VERSION = 1


def _make_session(runtime, data_dir, epochs_data=None):
    from shallowspeed_tpu.api import TrainingSession

    return TrainingSession(
        pp=4, schedule="gpipe", global_batch_size=128, mubatches=4,
        data_dir=data_dir, runtime=runtime,
    )


def bench_epoch_pair(data_dir, trials):
    """Interleaved same-window lockstep/mpmd epochs; per-leg minima."""
    legs = {"lockstep": [], "mpmd": []}
    sessions = {rt: _make_session(rt, data_dir) for rt in legs}
    for rt, s in sessions.items():
        s.train_epoch()  # compile outside the measured window
    for _ in range(trials):
        for rt, s in sessions.items():
            t0 = time.perf_counter()
            s.train_epoch()
            legs[rt].append(time.perf_counter() - t0)
    samples = sessions["lockstep"].batches_per_epoch * 128
    out = {}
    for rt, walls in legs.items():
        best = min(walls)
        out[rt] = {
            "epoch_wall_s": best,
            "samples_per_sec": samples / best,
            "trials_s": walls,
        }
    out["speedup_mpmd_vs_lockstep"] = (
        out["lockstep"]["epoch_wall_s"] / out["mpmd"]["epoch_wall_s"]
    )
    # keep the trained sessions for the probe legs (weights advance —
    # the probe's documented contract)
    return out, sessions


def bench_dispatch_probes(data_dir, work, repeats, probe_samples=8192):
    """The probe pair runs on a BOUNDED shard of the same data (64
    batches at the flagship batch size): on multi-second instrumented
    windows the profiler's event buffer drops op events, which collapses
    the busy union and INFLATES the overhead share — the probe is only
    a valid measurement while the trace holds the full event stream
    (``events_per_batch`` is recorded per leg as the saturation check;
    this is also the retroactive caveat on DISPATCH_r01.json's 0.728,
    measured over a ~13 s window where events were dropped)."""
    import shutil

    from shallowspeed_tpu.api import TrainingSession

    src = Path(data_dir) if data_dir else None
    probe = Path(work) / "probe_data"
    probe.mkdir(parents=True, exist_ok=True)
    if src is None:
        from shallowspeed_tpu.data import default_data_dir

        src = Path(default_data_dir())
    x = np.load(src / "x_train.npy", mmap_mode="r")[:probe_samples]
    y = np.load(src / "y_train.npy", mmap_mode="r")[:probe_samples]
    np.save(probe / "x_train.npy", np.asarray(x))
    np.save(probe / "y_train.npy", np.asarray(y))
    for f in ("x_val.npy", "y_val.npy"):
        shutil.copy(src / f, probe / f)

    out = {}
    for rt in ("lockstep", "mpmd"):
        s = TrainingSession(
            pp=4, schedule="gpipe", global_batch_size=128, mubatches=4,
            data_dir=str(probe), runtime=rt,
        )
        rec = s.measure_dispatch_overhead(repeats=repeats)
        row = {
            k: rec[k]
            for k in (
                "dispatch_overhead", "dispatch_overhead_instrumented",
                "host_wall_s", "device_busy_s", "device_comm_s",
                "device_compute_s", "op_events", "op_source",
                "profiler_inflation", "repeats", "runtime",
                # the machine-checked validity guard (the record computes
                # its own saturation verdict now — PR 16)
                "events_per_batch", "window_valid",
                "window_invalid_reason",
            )
        }
        row["batches_per_epoch"] = s.batches_per_epoch
        out[rt] = row
    lock = out["lockstep"]["dispatch_overhead"]
    mp = out["mpmd"]["dispatch_overhead"]
    if lock is not None and mp is not None:
        out["overhead_drop_same_window"] = lock - mp
    out["probe_samples"] = probe_samples
    out["protocol_note"] = (
        "bounded window: full op-event capture (events_per_batch is the "
        "saturation check); long instrumented windows drop events and "
        "inflate the share — see full_epoch_probe for that regime"
    )
    return out


def bench_full_epoch_probes(sessions, repeats):
    """The DISPATCH_r01 protocol verbatim (full-epoch windows) — kept
    for continuity, with the saturation caveat measured into the record
    (events_per_batch far below the bounded-window density means the
    profiler dropped events and the share is NOT a valid lower bound)."""
    out = {}
    for rt, s in sessions.items():
        rec = s.measure_dispatch_overhead(repeats=repeats)
        out[rt] = {
            k: rec[k]
            for k in (
                "dispatch_overhead", "host_wall_s", "device_busy_s",
                "device_comm_s", "op_events", "profiler_inflation",
                "runtime", "events_per_batch", "window_valid",
                "window_invalid_reason",
            )
        }
    out["caveat"] = (
        "multi-second instrumented windows: the profiler buffer drops op "
        "events (compare events_per_batch against the bounded-window "
        "probe), so these shares OVERSTATE overhead — recorded for "
        "continuity with DISPATCH_r01.json, not as the headline"
    )
    return out


def bench_serving_burst(sessions, n_requests):
    """R one-slot requests arriving at one instant; latency from the
    common arrival. The lockstep leg drains one whole-rung dispatch per
    request; the MPMD leg submits every chain before resolving any."""
    from shallowspeed_tpu.observability.stats import percentile

    rng = np.random.RandomState(3)
    rows = sessions["lockstep"].slot_rows
    reqs = [
        rng.rand(rows, 784).astype(np.float32) for _ in range(n_requests)
    ]
    out = {}
    # warm both dispatch paths outside the measured burst
    sessions["lockstep"].predict(reqs[0])
    sessions["mpmd"].predict_async(reqs[0])()

    t0 = time.perf_counter()
    lock_lat, lock_res = [], []
    for x in reqs:
        lock_res.append(sessions["lockstep"].predict(x))
        lock_lat.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    handles = [sessions["mpmd"].predict_async(x) for x in reqs]
    mp_lat, mp_res = [], []
    for h in handles:
        mp_res.append(h())
        mp_lat.append(time.perf_counter() - t0)
    for a, b in zip(lock_res, mp_res):
        np.testing.assert_array_equal(a, b)  # the parity contract, asserted
    for name, lats in (("lockstep", lock_lat), ("mpmd", mp_lat)):
        out[name] = {
            "p50_ms": 1e3 * percentile(lats, 50),
            "p99_ms": 1e3 * percentile(lats, 99),
            "max_ms": 1e3 * max(lats),
            "burst_drain_s": max(lats),
        }
    out["n_requests"] = n_requests
    out["slot_rows"] = rows
    out["p99_speedup_mpmd_vs_lockstep"] = (
        out["lockstep"]["p99_ms"] / out["mpmd"]["p99_ms"]
    )
    out["responses_bitwise_equal"] = True
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="record path (default: MPMD_r01.json at the repo "
                    "root)")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--probe-repeats", type=int, default=2)
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args(argv)

    import tempfile

    import jax

    work = Path(tempfile.mkdtemp(prefix="bench_mpmd_"))
    epoch_pair, sessions = bench_epoch_pair(args.data_dir, args.trials)
    probes = bench_dispatch_probes(args.data_dir, work, args.probe_repeats)
    full_probes = bench_full_epoch_probes(sessions, 1)
    serving = bench_serving_burst(sessions, args.requests)
    record = {
        "bench": "mpmd",
        "bench_version": BENCH_VERSION,
        "created": time.strftime("%Y-%m-%d %H:%M:%S"),
        "config": {
            "dp": 1, "pp": 4, "tp": 1, "schedule": "gpipe",
            "global_batch_size": 128, "mubatches": 4,
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        },
        "cpu_fallback_caveat": (
            "emulated CPU devices: machinery + relative ratios, not chip "
            "performance; the dispatch-overhead share is the CPU-honest "
            "number (it measures the host-issue wall the MPMD refactor "
            "exists to remove)"
            if jax.devices()[0].platform == "cpu"
            else None
        ),
        "protocol": (
            "same-window: lockstep/mpmd epochs interleaved per trial, "
            "per-leg minima; probes run back-to-back on the same trained "
            "sessions; serving burst latencies measured from one common "
            "arrival instant with responses asserted bitwise-equal"
        ),
        "baseline_dispatch_overhead": {
            "source": "DISPATCH_r01.json (PR 14, lockstep flagship)",
            "value": 0.728454944852902,
            "caveat": (
                "measured over a ~13 s instrumented window where the "
                "profiler dropped op events (its events_per_batch is "
                "~5x below the bounded-window density), so 0.728 "
                "overstates the lockstep share; the honest same-window "
                "pair is dispatch_probe below"
            ),
        },
        "epoch_pair": epoch_pair,
        "dispatch_probe": probes,
        "full_epoch_probe": full_probes,
        "serving_burst": serving,
    }
    out = Path(
        args.out
        if args.out
        else Path(__file__).resolve().parent.parent / "MPMD_r01.json"
    )
    out.write_text(json.dumps(record, indent=2) + "\n")
    print(f"record written: {out}")
    ep = epoch_pair
    print(
        f"epoch wall: lockstep {ep['lockstep']['epoch_wall_s']:.2f}s -> "
        f"mpmd {ep['mpmd']['epoch_wall_s']:.2f}s "
        f"({ep['speedup_mpmd_vs_lockstep']:.2f}x)"
    )
    print(
        "dispatch overhead (bounded window, full event capture): lockstep "
        f"{probes['lockstep']['dispatch_overhead']:.3f} -> mpmd "
        f"{probes['mpmd']['dispatch_overhead']:.3f} "
        f"(events/batch {probes['lockstep']['events_per_batch']:.0f} vs "
        f"{probes['mpmd']['events_per_batch']:.0f})"
    )
    print(
        "full-epoch probe (event-dropping regime, continuity only): "
        f"lockstep {full_probes['lockstep']['dispatch_overhead']:.3f} -> "
        f"mpmd {full_probes['mpmd']['dispatch_overhead']:.3f}"
    )
    print(
        f"serving burst p99: lockstep {serving['lockstep']['p99_ms']:.1f} ms "
        f"-> mpmd {serving['mpmd']['p99_ms']:.1f} ms "
        f"({serving['p99_speedup_mpmd_vs_lockstep']:.2f}x)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
