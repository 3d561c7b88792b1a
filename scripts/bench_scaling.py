"""Scaling benchmark: the five reference configs, samples/sec + efficiency.

Measures MNIST-MLP training throughput for:
    seq          sequential (1 device)
    dp4          DP=4
    pp4-naive    PP=4, naive schedule
    pp4-gpipe    PP=4, GPipe
    dp2pp4-gpipe DP=2 x PP=4 (8 devices)

and reports samples/sec plus scaling efficiency vs the sequential run
(efficiency = throughput / (n_devices * seq_throughput)). Emits one JSON line
per config. Configs needing more devices than available are skipped with a
note (a single-chip host runs only `seq`; use the 8-virtual-device CPU mesh
to exercise the rest:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 ...).

NOTE on interpretation: pipeline parallelism on this tiny MLP exists to
demonstrate/validate the machinery (the reference is an educational
framework); per-device efficiency is expected to be <1 because the model is
far too small to fill a pipeline — the numbers quantify schedule overhead
(naive vs GPipe vs 1F1B bubbles), which is exactly what the reference's
pebble diagrams illustrate.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from shallowspeed_tpu.api import (  # the reference's canonical config
    FLAGSHIP_BATCH as B,
    FLAGSHIP_LR as LR,
    FLAGSHIP_MUBATCHES as M,
    FLAGSHIP_SIZES as SIZES,
)


def _data(nb, rng, sizes=SIZES):
    X = rng.rand(nb, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (nb, B))]
    return X, Y


# 16-size flagship-class list: 15 Linears over up to 8 stages, so every
# stage owns at least one Linear — avoids the reference's 0-Linear
# partitioning quirk that changes the MODEL when 8 stages meet 8 sizes
# (reference layers.py:253-257).
# Rows on this list compare against the seq16 reference row, not seq.
SIZES16 = (784, 256, 224, 192, 176, 160, 144, 128, 112, 96, 80, 64, 48, 32, 16, 10)


def bench_sequential(nb, reps, sizes=SIZES, act="relu"):
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD

    spec = Mo.make_model_spec(sizes, 1, B, act=act)
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
    epoch = trainer.make_train_epoch(spec, SGD(LR))
    X, Y = _data(nb, np.random.RandomState(0), sizes=sizes)
    Xe = jnp.asarray(X.reshape(nb, M, B // M, -1))
    Ye = jnp.asarray(Y.reshape(nb, M, B // M, -1))
    st = ()
    params, st, _ = epoch(params, st, Xe, Ye)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(reps):
        params, st, _ = epoch(params, st, Xe, Ye)
    jax.block_until_ready(params)
    return reps * nb * B / (time.perf_counter() - t0)


def _pipeline_epoch_setup(
    dp, pp, sched_name, nb, virtual=1, sizes=SIZES, zero1=False,
    optimizer=None, backward_split=False, tp=1,
    digests=False, act="relu", recompute=False,
):
    """Build one mesh config's epoch fn + initial state + data: the shared
    setup behind the plain timing rows and the same-window pairs. Returns
    the lowered TickProgram first, so pair benchmarks that record program
    metrics describe exactly the program they time."""
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.optimizer import SGD, make_optimizer
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh

    mesh = make_mesh(dp, pp, tp=tp)
    spec = Mo.make_model_spec(sizes, pp * virtual, B, act=act)
    order = E.interleave_order(pp * virtual, pp) if virtual > 1 else None
    prog = lower_schedule(
        S.SCHEDULES[sched_name], M, pp, virtual=virtual,
        backward_split=backward_split, recompute=recompute,
    )
    stacked, flags = E.init_stacked(spec, mesh, order=order)
    opt = make_optimizer(optimizer, 2e-4) if optimizer else SGD(LR)
    epoch = E.make_pipeline_epoch(
        mesh, spec, prog, B // dp // M, opt, zero1=zero1,
        with_digests=digests,
    )
    st = E.zero1_init_state(opt, spec, mesh) if zero1 else opt.init(stacked)
    X, Y = _data(nb, np.random.RandomState(0), sizes=sizes)
    return prog, epoch, stacked, flags, st, jnp.asarray(X), jnp.asarray(Y)


def bench_pipeline(
    dp, pp, sched_name, nb, reps, virtual=1, sizes=SIZES, zero1=False,
    optimizer=None,
):
    import jax

    _, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
        dp, pp, sched_name, nb, virtual=virtual, sizes=sizes, zero1=zero1,
        optimizer=optimizer,
    )
    stacked, st, _ = epoch(stacked, flags, st, Xj, Yj)
    jax.block_until_ready(stacked["W"])
    t0 = time.perf_counter()
    for _ in range(reps):
        stacked, st, _ = epoch(stacked, flags, st, Xj, Yj)
    jax.block_until_ready(stacked["W"])
    return reps * nb * B / (time.perf_counter() - t0)


# digests-off vs digests-on pairs: same-window via bench.py's
# interleaved-trial slope protocol, so each pair shares its contention
# window. The digest aux (per-layer uint32 checksums + norms as
# extra scan ys, one psum over the pipeline axes — docs/numerics.md
# § Divergence debugging) is designed to be cheap next to the matmuls;
# this pair MEASURES that claim instead of asserting it. Records carry
# `digests` so a multichip capture of these rows is self-describing.
DIGEST_PAIRS = [
    ("dp2-digests", dict(dp=2, pp=1, sched="gpipe")),
    ("pp4-gpipe-digests", dict(dp=1, pp=4, sched="gpipe")),
]


def bench_digest_pair(name, cfg, nb):
    """One digests-off-vs-on pair, same-window: returns a list of record
    dicts (one per mode) carrying the digests flag + vs_off ratio — the
    measured on-path overhead of the numerics-provenance aux."""
    from bench import make_run_k, slope_epoch_seconds_many

    dp, pp = cfg["dp"], cfg["pp"]
    modes = {f"{name}-off": False, f"{name}-on": True}
    run_ks = {}
    for label, dig in modes.items():
        _, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
            dp, pp, cfg["sched"], nb, digests=dig
        )

        # the digests leg returns a 4th output (the digest aux) — the
        # timed loop still carries it to the host boundary, which is the
        # honest cost, but bench's run_k unpacks 3
        def epoch_fn(p, s, X, Y, _epoch=epoch, _flags=flags):
            out = _epoch(p, _flags, s, X, Y)
            return out[0], out[1], out[2]

        run_ks[label] = make_run_k(epoch_fn, stacked, st, Xj, Yj)
    slopes = slope_epoch_seconds_many(run_ks, k1=1, k2=3, trials=2, min_delta_s=0)
    off_sps = nb * B / slopes[f"{name}-off"]
    records = []
    for label, dig in modes.items():
        sps = nb * B / slopes[label]
        records.append(
            {
                "config": label,
                "devices": dp * pp,
                "samples_per_sec": round(sps, 1),
                "digests": dig,
                "same_window": True,
                "vs_off": round(sps / off_sps, 4),
            }
        )
    return records


# tensor-parallel vs sequential pairs: same-window via the interleaved-trial
# slope protocol. TP's win is weight-bandwidth/matmul-size denominated (per-
# device weight memory and matmul FLOPs drop by tp at 2 all-reduces per layer
# pair); on emulated CPU devices the extra dispatch + memcpy "collectives"
# are pure overhead against an op-issue-bound MLP, so — exactly like the
# split-backward pairs — expect seq to win here and the
# ratio to mean something only on a real multi-chip mesh. Records carry tp,
# vs_seq and the mesh placement note so an on-chip run re-measures
# self-describing rows.
TP_PAIRS = [
    ("tp2", dict(dp=1, pp=1, tp=2)),
    ("dp2tp2", dict(dp=2, pp=1, tp=2)),
]


def bench_tp_pair(name, cfg, nb, sizes=SIZES, act="relu", model=None):
    """One sequential-vs-tp pair, same-window: returns a list of record
    dicts (one per mode) carrying tp + vs_seq + the mesh layout note."""
    import jax
    import jax.numpy as jnp

    from bench import make_run_k, slope_epoch_seconds_many

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD
    from shallowspeed_tpu.parallel.mesh import make_mesh_with_layout

    dp, pp, tp = cfg["dp"], cfg["pp"], cfg["tp"]
    run_ks = {}
    # sequential leg
    spec1 = Mo.make_model_spec(sizes, 1, B, act=act)
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec1))
    seq_epoch = trainer.make_train_epoch(spec1, SGD(LR))
    X, Y = _data(nb, np.random.RandomState(0), sizes=sizes)
    Xe = jnp.asarray(X.reshape(nb, M, B // M, -1))
    Ye = jnp.asarray(Y.reshape(nb, M, B // M, -1))

    def seq_fn(p, s, X_, Y_, _e=seq_epoch):
        return _e(p, s, X_, Y_)

    run_ks[f"{name}-seq"] = make_run_k(seq_fn, params, (), Xe, Ye)
    # tp leg: the shared mesh setup, plus the placement note for the
    # records (deterministic — same device order as the setup's mesh)
    mesh_layout = make_mesh_with_layout(dp, pp, tp=tp)[1]
    _, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
        dp, pp, "gpipe", nb, tp=tp, sizes=sizes, act=act,
    )

    def tp_fn(p, s, X_, Y_, _e=epoch, _f=flags):
        return _e(p, _f, s, X_, Y_)

    run_ks[f"{name}-tp"] = make_run_k(tp_fn, stacked, st, Xj, Yj)
    slopes = slope_epoch_seconds_many(run_ks, k1=1, k2=3, trials=2, min_delta_s=0)
    seq_sps = nb * B / slopes[f"{name}-seq"]
    records = []
    for label, tp_val, devices in (
        (f"{name}-seq", 1, 1),
        (f"{name}-tp", tp, dp * pp * tp),
    ):
        sps = nb * B / slopes[label]
        records.append(
            {
                "config": label,
                "devices": devices,
                "samples_per_sec": round(sps, 1),
                "model": model,
                "tp": tp_val,
                "mesh_layout": mesh_layout if tp_val > 1 else None,
                "same_window": True,
                "vs_seq": round(sps / seq_sps, 4),
            }
        )
    return records


# split-vs-unsplit backward pairs at pp4 (gpipe + 1F1B): same-window via the
# interleaved-trial slope protocol. The split
# schedule's win is FLOP-weighted bubble time (the record carries both
# programs' weighted bubble fractions); on emulated CPU devices the extra
# OP_BWD_W ticks are pure op-issue overhead with nothing to overlap, so
# expect the unsplit row to win here and the
# ratio to mean something only on a real multi-chip mesh.
SPLIT_PAIRS = [
    ("pp4-gpipe-split", dict(dp=1, pp=4, sched="gpipe")),
    ("pp4-pipedream-split", dict(dp=1, pp=4, sched="pipedream")),
]


def bench_split_pair(name, cfg, nb, sizes=SIZES, act="relu", model=None):
    """One unsplit-vs-split backward pair, same-window: returns a list of
    record dicts (one per mode) carrying backward_split + the lowered
    programs' weighted bubble fractions so a MULTICHIP capture of these
    rows is self-describing."""
    from bench import make_run_k, slope_epoch_seconds_many

    from shallowspeed_tpu.parallel.lowering import weighted_utilization

    dp, pp = cfg["dp"], cfg["pp"]
    modes = {f"{name}-unsplit": False, f"{name}-split": True}
    run_ks, wbubble = {}, {}
    for label, bs in modes.items():
        # the setup's own lowered program feeds the recorded metric, so
        # the weighted bubble always describes the program being timed
        prog, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
            dp, pp, cfg["sched"], nb, backward_split=bs, sizes=sizes, act=act,
        )
        wbubble[label] = round(1.0 - weighted_utilization(prog), 4)

        def epoch_fn(p, s, X, Y, _epoch=epoch, _flags=flags):
            return _epoch(p, _flags, s, X, Y)

        run_ks[label] = make_run_k(epoch_fn, stacked, st, Xj, Yj)
    slopes = slope_epoch_seconds_many(run_ks, k1=1, k2=3, trials=2, min_delta_s=0)
    unsplit_sps = nb * B / slopes[f"{name}-unsplit"]
    records = []
    for label, bs in modes.items():
        sps = nb * B / slopes[label]
        records.append(
            {
                "config": label,
                "devices": dp * pp,
                "samples_per_sec": round(sps, 1),
                "model": model,
                "backward_split": bs,
                "weighted_bubble_fraction": wbubble[label],
                "same_window": True,
                "vs_unsplit": round(sps / unsplit_sps, 4),
            }
        )
    return records


# stashed-vs-recompute pairs at pp4: same-window via the interleaved-trial
# slope protocol. Recompute trades the residual-stash footprint for a
# ~4/3 forward-FLOP tax (docs/lowering.md § Recompute ticks) — on a
# compute-bound model the tax should be VISIBLE here (vs_stashed < 1),
# which is the honest direction: this pair measures what recompute costs,
# the stash-peak fields record what it buys.
RECOMPUTE_PAIRS = [
    ("pp4-gpipe-recompute", dict(dp=1, pp=4, sched="gpipe")),
]


def bench_recompute_pair(name, cfg, nb, sizes=SIZES, act="relu", model=None):
    """One stashed-vs-recompute pair, same-window: returns a list of
    record dicts (one per mode) carrying the recompute flag, the lowered
    programs' stash peaks (the memory the tax buys back), and vs_stashed."""
    from bench import make_run_k, slope_epoch_seconds_many

    dp, pp = cfg["dp"], cfg["pp"]
    modes = {f"{name}-stashed": False, f"{name}-on": True}
    run_ks, peaks = {}, {}
    for label, rec in modes.items():
        prog, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
            dp, pp, cfg["sched"], nb, sizes=sizes, act=act, recompute=rec,
        )
        peaks[label] = {
            "stash_slots": int(prog.n_stash_slots),
            "xin_slots": int(prog.n_xin_slots),
        }

        def epoch_fn(p, s, X, Y, _epoch=epoch, _flags=flags):
            return _epoch(p, _flags, s, X, Y)

        run_ks[label] = make_run_k(epoch_fn, stacked, st, Xj, Yj)
    slopes = slope_epoch_seconds_many(run_ks, k1=1, k2=3, trials=2, min_delta_s=0)
    stashed_sps = nb * B / slopes[f"{name}-stashed"]
    records = []
    for label, rec in modes.items():
        sps = nb * B / slopes[label]
        records.append(
            {
                "config": label,
                "devices": dp * pp,
                "samples_per_sec": round(sps, 1),
                "model": model,
                "recompute": rec,
                **peaks[label],
                "same_window": True,
                "vs_stashed": round(sps / stashed_sps, 4),
            }
        )
    return records


# lockstep-vs-MPMD runtime pairs: same-window via the interleaved-trial
# slope protocol (the MPMD runner's ``run`` is epoch-shaped with the
# lockstep signature, so both legs time the identical loop). The MPMD
# per-stage runtime removes the lockstep lax.switch op-issue wall; on a
# dispatch-bound toy MLP that win was masked by the runtime's own host
# cost (MPMD_r01.json: 0.86x) — a compute-bound model is where it gets
# to show, or where the refutation earns its caveat.
MPMD_PAIRS = [
    ("pp4-gpipe-mpmd", dict(dp=1, pp=4, sched="gpipe")),
]


def bench_mpmd_pair(name, cfg, nb, sizes=SIZES, act="relu", model=None):
    """One lockstep-vs-MPMD runtime pair, same-window: returns a list of
    record dicts (one per mode) carrying runtime + vs_lockstep."""
    from bench import make_run_k, slope_epoch_seconds_many

    from shallowspeed_tpu.optimizer import SGD
    from shallowspeed_tpu.parallel import mpmd

    dp, pp = cfg["dp"], cfg["pp"]
    prog, epoch, stacked, flags, st, Xj, Yj = _pipeline_epoch_setup(
        dp, pp, cfg["sched"], nb, sizes=sizes, act=act,
    )

    def lockstep_fn(p, s, X, Y, _epoch=epoch, _flags=flags):
        return _epoch(p, _flags, s, X, Y)

    # the MPMD leg drives the SAME lowered program through the per-stage
    # runtime — with its OWN param/state buffers: the lockstep epoch
    # donates its inputs, so sharing one stacked tree across legs would
    # hand the runner deleted arrays
    from shallowspeed_tpu.parallel import make_mesh

    _, _, stacked2, flags2, st2, _, _ = _pipeline_epoch_setup(
        dp, pp, cfg["sched"], nb, sizes=sizes, act=act,
    )
    mesh = make_mesh(dp, pp)
    runner = mpmd.MpmdTrainRunner(mesh, _mpmd_spec(sizes, pp, act), prog,
                                  B // dp // M, SGD(LR))

    def mpmd_fn(p, s, X, Y, _r=runner, _flags=flags2):
        return _r.run(p, _flags, s, X, Y)

    run_ks = {
        f"{name}-lockstep": make_run_k(lockstep_fn, stacked, st, Xj, Yj),
        f"{name}-mpmd": make_run_k(mpmd_fn, stacked2, st2, Xj, Yj),
    }
    slopes = slope_epoch_seconds_many(run_ks, k1=1, k2=3, trials=2, min_delta_s=0)
    lockstep_sps = nb * B / slopes[f"{name}-lockstep"]
    records = []
    for label, rt in ((f"{name}-lockstep", "lockstep"), (f"{name}-mpmd", "mpmd")):
        sps = nb * B / slopes[label]
        records.append(
            {
                "config": label,
                "devices": dp * pp,
                "samples_per_sec": round(sps, 1),
                "model": model,
                "runtime": rt,
                "same_window": True,
                "vs_lockstep": round(sps / lockstep_sps, 4),
            }
        )
    return records


def _mpmd_spec(sizes, pp, act):
    from shallowspeed_tpu import model as Mo

    return Mo.make_model_spec(sizes, pp, B, act=act)


CONFIGS = [
    # the five reference configs...  (name, kwargs)
    ("seq", dict(dp=1, pp=1)),
    ("dp4", dict(dp=4, pp=1, sched="gpipe")),
    ("pp4-naive", dict(dp=1, pp=4, sched="naive")),
    ("pp4-gpipe", dict(dp=1, pp=4, sched="gpipe")),
    ("dp2pp4-gpipe", dict(dp=2, pp=4, sched="gpipe")),
    # ...plus the schedules/optimizers the reference never implemented
    ("pp4-pipedream", dict(dp=1, pp=4, sched="pipedream")),
    ("dp4-zero1-adam", dict(dp=4, pp=1, sched="gpipe", zero1=True,
                            optimizer="adam")),
    # 16-size rows (quirk-free 8-stage partition): their efficiency is
    # reported against seq16, the same model run sequentially
    ("seq16", dict(dp=1, pp=1, sizes=SIZES16)),
    ("pp4v2-interleaved-16", dict(dp=1, pp=4, sched="interleaved", virtual=2,
                                  sizes=SIZES16)),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=64, help="batches per rep")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument(
        "--model", default=None,
        help="model-zoo config (model.MODEL_ZOO) to bench instead of the "
        "flagship toy MLP: the compute-bound rows that unmask "
        "dispatch-bound ratios (docs/performance.md). Rows record the "
        "model name so captures stay self-describing.",
    )
    ap.add_argument(
        "--pairs-only", action="store_true",
        help="skip the plain throughput rows; run only the same-window "
        "pairs — the COMPUTE_r01.json protocol",
    )
    ap.add_argument(
        "--out", default=None,
        help="also write every emitted record into FILE as one JSON "
        "document ({bench, model, records: [...]})",
    )
    args = ap.parse_args()

    act = "relu"
    sizes = SIZES
    if args.model:
        from shallowspeed_tpu import model as Mo

        sizes, act = Mo.resolve_model(args.model)

    import jax

    n_dev = len(jax.devices())
    results = {}
    emitted = []

    def emit(rec):
        emitted.append(rec)
        print(json.dumps(rec))

    if not args.pairs_only:
        for name, cfg in CONFIGS:
            dp, pp = cfg.get("dp", 1), cfg.get("pp", 1)
            need = dp * pp
            if need > n_dev:
                emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
                continue
            if args.model and "sizes" in cfg:
                continue  # the 16-size quirk rows only describe the toy MLP
            row_sizes = cfg.get("sizes", sizes)
            if pp == 1 and dp == 1:
                sps = bench_sequential(
                    args.batches, args.reps, sizes=row_sizes, act=act
                )
            else:
                sps = bench_pipeline(
                    dp, pp, cfg["sched"], args.batches, args.reps,
                    virtual=cfg.get("virtual", 1), sizes=row_sizes,
                    zero1=cfg.get("zero1", False), optimizer=cfg.get("optimizer"),
                )
            results[name] = sps
            ref = "seq16" if row_sizes is SIZES16 else "seq"
            eff = (
                sps / (need * results[ref])
                if ref in results and name != ref
                else 1.0
            )
            emit(
                {
                    "config": name,
                    "devices": need,
                    "samples_per_sec": round(sps, 1),
                    "model": args.model,
                    "efficiency_vs_seq": round(eff, 4),
                }
            )

    pair_kwargs = dict(sizes=sizes, act=act, model=args.model)

    # the unsplit-vs-split backward pairs (same-window per pair)
    for name, cfg in SPLIT_PAIRS:
        need = cfg["dp"] * cfg["pp"]
        if need > n_dev:
            emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
            continue
        for rec in bench_split_pair(name, cfg, args.batches, **pair_kwargs):
            emit(rec)

    if not args.pairs_only:
        # the digests-off-vs-on pairs (same-window per pair): the measured
        # on-path overhead of the numerics-provenance aux
        for name, cfg in DIGEST_PAIRS:
            need = cfg["dp"] * cfg["pp"]
            if need > n_dev:
                emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
                continue
            for rec in bench_digest_pair(name, cfg, args.batches):
                emit(rec)

    # the sequential-vs-tensor-parallel pairs (same-window per pair)
    for name, cfg in TP_PAIRS:
        need = cfg["dp"] * cfg["pp"] * cfg["tp"]
        if need > n_dev:
            emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
            continue
        if args.pairs_only and cfg["dp"] > 1:
            continue  # COMPUTE protocol: tp2-vs-seq is the story row
        for rec in bench_tp_pair(name, cfg, args.batches, **pair_kwargs):
            emit(rec)

    # the stashed-vs-recompute pairs (same-window per pair)
    for name, cfg in RECOMPUTE_PAIRS:
        need = cfg["dp"] * cfg["pp"]
        if need > n_dev:
            emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
            continue
        for rec in bench_recompute_pair(name, cfg, args.batches, **pair_kwargs):
            emit(rec)

    # the lockstep-vs-MPMD runtime pairs (same-window per pair)
    for name, cfg in MPMD_PAIRS:
        need = cfg["dp"] * cfg["pp"]
        if need > n_dev:
            emit({"config": name, "skipped": f"needs {need} devices, have {n_dev}"})
            continue
        for rec in bench_mpmd_pair(name, cfg, args.batches, **pair_kwargs):
            emit(rec)

    if args.out:
        Path(args.out).write_text(
            json.dumps(
                {
                    "bench": "scaling",
                    "model": args.model,
                    "act": act,
                    "sizes": list(sizes),
                    "batches": args.batches,
                    "platform": jax.devices()[0].platform,
                    "device_kind": jax.devices()[0].device_kind,
                    "device_count": len(jax.devices()),
                    "n_devices": n_dev,
                    "cpu_fallback_caveat": (
                        "emulated CPU devices on one shared host core: "
                        "machinery + relative ratios, not chip performance"
                        if jax.devices()[0].platform == "cpu"
                        else None
                    ),
                    "records": emitted,
                },
                indent=1,
            )
            + "\n"
        )


if __name__ == "__main__":
    main()
