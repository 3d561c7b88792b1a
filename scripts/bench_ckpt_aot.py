"""The production-path-stall scoreboard (ROADMAP item 5, PR 12):

**Checkpoint overhead fraction, sync vs async** — the same dp2
training run checkpointing every step, measured as checkpoint wall /
(checkpoint + train-dispatch wall): the report Reliability section's
exact formula, with the async leg charging only the ON-PATH cost
(device->host snapshot + bounded-queue enqueue). Trials interleave
sync/async so the pair is same-window (bench.py's slope protocol), and
the async leg drains its writer before the clock stops — nothing
off-path is hidden outside the window.

Writes the versioned record beside bench_scaling's (CKPT_AOT_r01.json
at the repo root by default). CPU-fallback caveat applies as everywhere:
on emulated devices these validate machinery and RELATIVE ratios, not
chip performance.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BENCH_VERSION = 1


def _make_data(d):
    d.mkdir(parents=True, exist_ok=True)
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 512), ("val", 96)):
        np.save(d / f"x_{suffix}.npy", rng.rand(n, 784).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)],
        )


CKPT_SIZES = (784, 512, 512, 512, 256, 10)  # ~1M params: the regime where
# verification (sha256 over every byte) + the zip write dominate a save —
# the flagship MLP is so small that the device->host snapshot (which MUST
# stay on-path for consistency) hides the off-path win


def _ckpt_leg(data_dir, work, async_, steps, trial):
    """One checkpoint-overhead leg: train `steps` steps checkpointing
    every step; returns (ckpt_on_path_wall, train_wall)."""
    from shallowspeed_tpu.api import TrainingSession

    ck = work / f"ck_{'async' if async_ else 'sync'}_{trial}"
    run = TrainingSession(
        sizes=CKPT_SIZES, dp=2, global_batch_size=32, mubatches=2,
        data_dir=data_dir, checkpoint_dir=ck, async_checkpoint=async_,
        optimizer="momentum",  # optimizer state doubles the saved bytes
    )
    run.train_steps(1)  # compile outside the measured window
    ckpt_wall = 0.0
    train_wall = 0.0
    for _ in range(steps):
        t0 = time.perf_counter()
        run.train_steps(1)
        train_wall += time.perf_counter() - t0
        t0 = time.perf_counter()
        run.save_step_checkpoint()
        ckpt_wall += time.perf_counter() - t0
    # drain INSIDE the async leg's accounting window: the off-path work
    # must finish before the leg's clock stops, or the comparison would
    # credit async with work it merely deferred past the measurement
    t0 = time.perf_counter()
    run.close()
    drain_wall = time.perf_counter() - t0
    shutil.rmtree(ck, ignore_errors=True)
    return ckpt_wall, train_wall, drain_wall


def bench_checkpoint_overhead(data_dir, work, steps=16, trials=3):
    legs = {"sync": [], "async": []}
    # interleave the pair per trial: same-window ratios
    for trial in range(trials):
        for name, async_ in (("sync", False), ("async", True)):
            legs[name].append(_ckpt_leg(data_dir, work, async_, steps, trial))
    out = {}
    for name, rows in legs.items():
        ck = min(r[0] for r in rows)  # per-leg minima, like the bench
        tr = min(r[1] for r in rows)
        out[name] = {
            "checkpoint_wall_s": ck,
            "train_wall_s": tr,
            "drain_wall_s": min(r[2] for r in rows),
            "overhead_fraction": ck / (ck + tr) if (ck + tr) > 0 else None,
            "per_save_ms": 1e3 * ck / steps,
            "trials": [
                {"checkpoint_wall_s": a, "train_wall_s": b, "drain_wall_s": c}
                for a, b, c in rows
            ],
        }
    sync_f, async_f = (
        out["sync"]["overhead_fraction"], out["async"]["overhead_fraction"]
    )
    out["steps"] = steps
    out["overhead_ratio_async_vs_sync"] = (
        async_f / sync_f if sync_f else None
    )
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="record path (default: CKPT_AOT_r01.json at the "
                    "repo root)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument(
        "--archive-previous", action="store_true",
        help="snapshot the existing checkpoint_overhead section as a new "
        "checkpoint_overhead_r<N> round before writing (use when a code "
        "change makes the superseded numbers a different regime)",
    )
    args = ap.parse_args(argv)

    import jax

    work = Path(tempfile.mkdtemp(prefix="bench_ckpt_aot_"))
    data_dir = work / "data"
    _make_data(data_dir)
    record = {
        "bench": "ckpt_aot",
        "bench_version": BENCH_VERSION,
        "created": time.strftime("%Y-%m-%d %H:%M:%S"),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "cpu_fallback_caveat": (
            "emulated CPU devices: machinery + relative ratios, not chip "
            "performance"
            if jax.devices()[0].platform == "cpu"
            else None
        ),
        "protocol": (
            "same-window: sync/async legs interleaved per trial, per-leg "
            "minima; async leg drains its writer inside the window"
        ),
        "checkpoint_overhead": bench_checkpoint_overhead(
            data_dir, work, steps=args.steps, trials=args.trials
        ),
    }
    out = Path(
        args.out
        if args.out
        else Path(__file__).resolve().parent.parent / "CKPT_AOT_r01.json"
    )
    if out.exists():
        # preserve prior rounds instead of clobbering them: archived
        # checkpoint_overhead_r<N> sections carry forward, so the
        # scoreboard the docs
        # cite stays reproducible BY THIS SCRIPT; --archive-previous
        # additionally snapshots the current section as a new round
        # (used when a code change makes the old numbers a different
        # REGIME, not just a rerun — unconditional archiving would grow
        # one near-duplicate section per invocation)
        try:
            old = json.loads(out.read_text())
        except (OSError, json.JSONDecodeError):
            old = {}
        for k, v in old.items():
            if k.startswith("checkpoint_overhead_r"):
                record[k] = v
        if args.archive_previous and "checkpoint_overhead" in old:
            n = 1
            while f"checkpoint_overhead_r{n}" in record:
                n += 1
            record[f"checkpoint_overhead_r{n}"] = old["checkpoint_overhead"]
    out.write_text(json.dumps(record, indent=2) + "\n")
    co = record["checkpoint_overhead"]
    print(f"record written: {out}")
    print(
        "checkpoint overhead: sync "
        f"{co['sync']['overhead_fraction'] * 100:.1f}% -> async "
        f"{co['async']['overhead_fraction'] * 100:.1f}% "
        f"({co['sync']['per_save_ms']:.1f} -> "
        f"{co['async']['per_save_ms']:.1f} ms/save on-path)"
    )
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
