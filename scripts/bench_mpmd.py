"""The MPMD-vs-lockstep scoreboard: two same-window measurements on the
flagship gpipe-pp4 CPU config, written as MPMD_r01.json beside the other
bench records.

1. **Epoch pair** — the same training epochs dispatched through the
   lockstep SPMD program and the MPMD per-stage runtime, interleaved per
   trial (bench.py's slope protocol), per-leg minima. Both runtimes train
   the identical math (weights hash-equal — the in-suite lattice and
   ``make mpmd-smoke`` pin that bitwise), so the wall ratio is pure
   runtime cost.

2. **Serving burst p99** — R one-slot requests arriving at once, drained
   (a) through the lockstep rung program, one whole-rung makespan per
   request, vs (b) through the MPMD streaming chain (``predict_async``:
   request k enters stage 0 while request k-1 occupies a later stage).
   Latency is measured from the common arrival instant — the burst's
   p50/p99 show whether tail latency is makespan-quantized.

CPU-fallback caveat, as everywhere: emulated devices validate machinery
and RELATIVE ratios, not chip performance.
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
    # keep the trained sessions for the serving leg
    return out, sessions


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
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args(argv)

    import jax

    epoch_pair, sessions = bench_epoch_pair(args.data_dir, args.trials)
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
            "performance"
            if jax.devices()[0].platform == "cpu"
            else None
        ),
        "protocol": (
            "same-window: lockstep/mpmd epochs interleaved per trial, "
            "per-leg minima; serving burst latencies measured from one common "
            "arrival instant with responses asserted bitwise-equal"
        ),
        "epoch_pair": epoch_pair,
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
        f"serving burst p99: lockstep {serving['lockstep']['p99_ms']:.1f} ms "
        f"-> mpmd {serving['mpmd']['p99_ms']:.1f} ms "
        f"({serving['p99_speedup_mpmd_vs_lockstep']:.2f}x)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
