"""The ZeRO memory scoreboard (PR 20): same-window zero1/zero2/zero3
epochs on the compute-bound flagship zoo model (``mlp-wide``, Adam — two
params-shaped state parts, so the stage ladder has something to shard),
written as ZERO_r01.json beside the other bench records.

Two layouts, the dp2 flagship and the dp2 x pp2 composition:

1. **Measured peak HBM** — every leg compiles with ``audit=True`` and a
   metrics recorder, so the schema-v3 ``xla_audit`` record lands with the
   shared ``memory_stats`` analysis; the scoreboard reads the epoch
   program's measured ``peak_hbm_bytes`` per stage and asserts the
   flagship ladder is STRICTLY decreasing zero1 -> zero2 -> zero3 (the
   claim the stages exist to make). The analytical
   ``zero_peak_forecast`` (params+grads+state ÷ dp residency) is recorded
   next to each measurement — forecast vs measured is the calibration the
   report's OOM-forecast row rests on.

2. **Epoch pair** — the stages' training epochs interleaved per trial
   (bench.py's slope protocol), per-leg minima. On CPU the ZeRO collectives
   are op-issue-bound host work, so the walls show the stages' COST here,
   not their chip behavior — recorded with that caveat, the memory ladder
   is the headline.

The fixed-layout numerics contract rides along as a hash-pin pair per
layout at ``mubatches=1``: there the anchor zero-2 per-tick
reduce-scatter carries exactly one contribution per shard element, so
its final weights hash must equal zero-1's BITWISE (same tick table,
same update math, different residency). The measured-window legs run at
``mubatches=4``, where the sharded accumulator's microbatch-outer sum is
a different (equally valid) float reduction tree than zero-1's dp-outer
one — tolerance territory by design, see docs/performance.md.

CPU-fallback caveat, as everywhere: emulated devices validate machinery
and RELATIVE ratios, not chip performance — but ``peak_hbm_bytes`` comes
from XLA's own buffer-assignment analysis of the compiled program, which
is exactly the quantity the stages shrink.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BENCH_VERSION = 1
STAGES = (1, 2, 3)

LAYOUTS = (
    ("dp2", dict(dp=2)),
    ("dp2xpp2", dict(dp=2, pp=2, schedule="gpipe")),
)


def _synth_data(work, n_train=4096, n_val=512):
    """MNIST-shaped synthetic data (784 -> 10): the zoo models keep the
    784-wide input, and the scoreboard measures programs, not accuracy."""
    d = Path(work) / "data"
    d.mkdir(parents=True, exist_ok=True)
    rng = np.random.RandomState(0)
    for suffix, n in (("train", n_train), ("val", n_val)):
        x = rng.rand(n, 784).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
        np.save(d / f"x_{suffix}.npy", x)
        np.save(d / f"y_{suffix}.npy", y)
    return d


def _epoch_audit(path):
    """The epoch program's xla_audit record from a leg's metrics file."""
    recs = [json.loads(l) for l in open(path) if l.strip()]
    audits = [
        r for r in recs
        if r.get("kind") == "xla_audit" and r.get("name") == "epoch_program"
    ]
    assert audits, f"{path}: no epoch_program xla_audit record"
    rec = audits[-1]
    assert rec.get("census_ok"), f"{path}: census mismatch: {rec.get('mismatches')}"
    return rec


def bench_layout(name, kw, data_dir, work, trials, model, optimizer):
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.observability.metrics import JsonlMetrics

    sessions, metrics_paths = {}, {}
    for stage in STAGES:
        path = Path(work) / f"{name}_z{stage}.jsonl"
        metrics_paths[stage] = path
        sessions[stage] = TrainingSession(
            model=model, optimizer=optimizer, global_batch_size=128,
            mubatches=4, data_dir=str(data_dir), zero=stage, audit=True,
            metrics=JsonlMetrics(str(path)), **kw,
        )
    walls = {stage: [] for stage in STAGES}
    for stage, s in sessions.items():
        s.train_epoch()  # compile (and audit) outside the measured window
    for _ in range(trials):
        for stage, s in sessions.items():
            t0 = time.perf_counter()
            s.train_epoch()
            walls[stage].append(time.perf_counter() - t0)
    hashes = {stage: s.model_hash() for stage, s in sessions.items()}
    for s in sessions.values():
        s._metrics.close()

    # the fixed-layout hash pin: mubatches=1 legs, where anchor zero-2's
    # per-tick scatter is one contribution per element -> bitwise zero-1
    pin_hashes = {}
    for stage in (1, 2):
        s = TrainingSession(
            model=model, optimizer=optimizer, global_batch_size=128,
            mubatches=1, data_dir=str(data_dir), zero=stage, audit=True,
            **kw,
        )
        s.train_epoch()
        pin_hashes[stage] = s.model_hash()

    legs = {}
    for stage in STAGES:
        audit = _epoch_audit(metrics_paths[stage])
        mem = audit.get("memory") or {}
        forecast = (audit.get("expected") or {}).get("zero_forecast") or {}
        fc_stage = (forecast.get("stages") or {}).get(str(stage)) or {}
        legs[f"zero{stage}"] = {
            "peak_hbm_bytes": mem.get("peak_hbm_bytes"),
            "temp_bytes": mem.get("temp_size_in_bytes"),
            "argument_bytes": mem.get("argument_size_in_bytes"),
            "epoch_wall_s": min(walls[stage]),
            "trials_s": walls[stage],
            "model_hash": hashes[stage],
            "forecast_model_state_bytes": fc_stage.get("total_bytes"),
            "forecast": fc_stage,
        }
    peaks = [legs[f"zero{s}"]["peak_hbm_bytes"] for s in STAGES]
    out = {
        "legs": legs,
        "peak_ladder_bytes": peaks,
        "verdicts": {
            "peak_strictly_decreasing": bool(
                all(p is not None for p in peaks)
                and peaks[0] > peaks[1] > peaks[2]
            ),
            "zero2_hash_equals_zero1_at_mub1": pin_hashes[2] == pin_hashes[1],
        },
        "hash_pin_mub1": {f"zero{s}": pin_hashes[s] for s in (1, 2)},
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="record path (default: ZERO_r01.json at the repo "
                    "root)")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--model", default="mlp-wide")
    ap.add_argument("--optimizer", default="adam")
    args = ap.parse_args(argv)

    import tempfile

    import jax

    work = Path(tempfile.mkdtemp(prefix="bench_zero_"))
    data_dir = _synth_data(work)

    layouts = {}
    for name, kw in LAYOUTS:
        print(f"[{name}] measuring zero1/zero2/zero3 ...", flush=True)
        layouts[name] = bench_layout(
            name, kw, data_dir, work, args.trials, args.model, args.optimizer
        )

    record = {
        "bench": "zero_memory_scoreboard",
        "bench_version": BENCH_VERSION,
        "created": time.strftime("%Y-%m-%d %H:%M:%S"),
        "config": {
            "model": args.model, "optimizer": args.optimizer,
            "global_batch_size": 128, "mubatches": 4, "trials": args.trials,
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        },
        "cpu_fallback_caveat": (
            "emulated CPU devices: the memory ladder is XLA's own "
            "buffer-assignment peak of the compiled program (the honest "
            "quantity); the walls are op-issue-bound host dispatch, not "
            "chip behavior — ZeRO-3's per-tick gathers COST wall time "
            "here, the stage is a memory trade"
            if jax.devices()[0].platform == "cpu"
            else None
        ),
        "protocol": (
            "same-window: the three stages' epochs interleaved per trial, "
            "per-leg minima; every leg compiled under audit=True (census "
            "enforced at jit time) with the measured peak read from the "
            "epoch program's xla_audit record; zero2 final weights "
            "asserted hash-equal to zero1 per layout on the mubatches=1 "
            "hash-pin pair (per-tick scatter reassociates the microbatch "
            "sum at M>1)"
        ),
        "layouts": layouts,
    }
    out = Path(
        args.out
        if args.out
        else Path(__file__).resolve().parent.parent / "ZERO_r01.json"
    )
    out.write_text(json.dumps(record, indent=2) + "\n")
    print(f"record written: {out}")

    failed = []
    for name, lay in layouts.items():
        ladder = " -> ".join(
            f"z{s} {lay['legs'][f'zero{s}']['peak_hbm_bytes']:,} B"
            for s in STAGES
        )
        print(f"[{name}] measured peak HBM: {ladder}")
        for s in STAGES:
            leg = lay["legs"][f"zero{s}"]
            print(
                f"[{name}]   z{s}: forecast model state "
                f"{leg['forecast_model_state_bytes']:,} B, epoch wall "
                f"{leg['epoch_wall_s']:.2f}s"
            )
        for verdict, ok in lay["verdicts"].items():
            print(f"[{name}] {verdict}: {'OK' if ok else 'FAILED'}")
            if not ok:
                failed.append(f"{name}:{verdict}")
    if failed:
        print("FAILED verdicts:", ", ".join(failed))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
