"""Training driver CLI — the TPU-native counterpart of the reference train.py.

Same surface: ``python train.py [--dp N] [--pp M] [--schedule naive|gpipe|pipedream]``
(reference train.py:62-74), same flagship model (sizes [784,128,127,126,125,
124,123,10], train.py:98), same constants (EPOCHS=20, GLOBAL_BATCH_SIZE=128,
N_MUBATCHES=4, lr=0.006), same epoch structure (per-epoch validation accuracy,
final replica-sync check).

Differences by design:
- no mpirun: ONE process drives the whole (dp, pp) device mesh; the two MPI
  communicators become mesh axes (parallel/mesh.py);
- the per-batch instruction streams are compiled once to a tick program and
  the whole epoch runs as one jitted scan on device;
- extra capability flags: checkpoints, resume, profiling, precision.

All wiring lives in shallowspeed_tpu.api.TrainingSession — this file is the
argument surface plus the reporting loop.

Examples:
    python train.py                      # sequential, 1 device
    python train.py --dp 8               # 8-way data parallel
    python train.py --pp 4 --schedule gpipe
    python train.py --dp 2 --pp 4 --schedule pipedream
JAX's default backend runs it (the TPU, on a host that has one). For tests,
any layout also runs on emulated CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python train.py --dp 2 --pp 4 --schedule gpipe
"""

import argparse
import contextlib
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dp", type=int, default=1, help="data-parallel replicas")
    ap.add_argument("--pp", type=int, default=1, help="pipeline stages")
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor (model-axis) parallelism: shard every Linear "
        "Megatron-style across tp devices — even layers column-parallel "
        "(W split on the output dim, no forward collective), odd layers "
        "row-parallel (W split on the input dim, one all-reduce over tp) — "
        "so each fwd+bwd pass costs 2 all-reduces per layer pair and "
        "per-device weight memory/matmul FLOPs drop by tp. Composes with "
        "--dp/--pp/--zero1/--backward-split into a "
        "dp x pp x tp lattice (needs dp*pp*tp devices; --audit verifies "
        "the per-axis collective census; see docs/performance.md for "
        "when it pays)",
    )
    ap.add_argument(
        "--schedule",
        choices=["naive", "gpipe", "pipedream", "interleaved"],
        default="naive",
        help="pipeline schedule (ignored unless --pp > 1); 'interleaved' is "
        "Megatron-style virtual-stage 1F1B (use with --virtual-stages)",
    )
    ap.add_argument(
        "--virtual-stages",
        type=int,
        default=1,
        help="virtual stages per device for --schedule interleaved: the model "
        "is cut into pp x V stages, stage s on device s %% pp — the "
        "pipeline-fill bubble shrinks ~V-fold (beyond the reference)",
    )
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--global-batch-size", type=int, default=128)
    ap.add_argument("--mubatches", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.006)
    ap.add_argument(
        "--optimizer",
        choices=["sgd", "momentum", "adam"],
        default="sgd",
        help="sgd = reference parity; momentum / adam = stateful optimizers "
        "(state is saved in checkpoints and restored on --resume, any "
        "layout). NOTE on lr: momentum's effective step is lr/(1-mu) — "
        "divide sgd's lr by ~1/(1-mu) (1e-3 reaches 99.65%% in 20 epochs; "
        "sgd's 6e-3 diverges late). adam's normalized step is ~lr per "
        "element — 2e-4 reaches 99.86%% after ONE epoch, but destabilizes on "
        "long runs; prefer sgd/momentum past a few epochs",
    )
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument(
        "--zero1",
        action="store_true",
        help="ZeRO-1: shard the optimizer state + update over the dp axis "
        "(reduce_scatter grads, per-replica chunk update, all_gather params; "
        "mesh layouts only — beyond the reference). Alias for --zero 1",
    )
    ap.add_argument(
        "--zero",
        type=int,
        choices=[0, 1, 2, 3],
        default=None,
        help="ZeRO stage on the dp axis (mesh layouts; supersedes --zero1): "
        "0 = replicate everything (the anchor all-reduce sync); 1 = shard "
        "the optimizer state + update; 2 = gradients also live as "
        "persistent reduce-scattered per-rank shards (bitwise-equal "
        "weights to --zero 1 at the same layout with --mubatches 1); "
        "3 = parameters sharded at rest too, all-gathered "
        "just-in-time per layer inside the tick scan (per-tick gradient "
        "reduce-scatter; cross-stage tolerance numerics, same-layout "
        "determinism). See docs/performance.md for when each stage pays",
    )
    ap.add_argument(
        "--backward-split",
        action="store_true",
        help="pipeline schedules (gpipe/pipedream/naive): two-stage backward "
        "— each microbatch's backward is split into the relay-critical "
        "B-input (d(loss)/d(input), at exactly the tick the combined "
        "backward would run, so upstream stages never wait longer) and a "
        "deferred B-weight (dW/db from the stashed activation + output-"
        "grad) packed into otherwise-idle bubble ticks (2BP, arXiv "
        "2405.18047). Bitwise-identical weights (the weight-grad "
        "accumulation order is preserved); shrinks the FLOP-weighted "
        "bubble fraction the report/show_schedule quote (see "
        "docs/performance.md for when it pays)",
    )
    ap.add_argument(
        "--model",
        choices=[
            "mnist-mlp", "mlp-wide", "mlp-deep", "transformer", "olmo-hybrid-7b",
            "solar-open2-250b",
        ],
        default=None,
        help="model-zoo configuration (model.MODEL_ZOO): a named (sizes, "
        "activation family) pair. 'mnist-mlp' is the reference 8-layer "
        "ReLU MLP (the default sizes); 'mlp-wide'/'mlp-deep' are compute-"
        "bound ReLU MLPs (512x6 / 2048x22) that unmask the scheduling "
        "wins CPU dispatch overhead hides on the tiny reference; "
        "'transformer' is the gelu-family block model (x @ W_up -> gelu "
        "-> @ W_down + residual per slot pair, Megatron-parity sharding). "
        "All zoo models keep the 784-wide MNIST input. 'olmo-hybrid-7b' is a "
        "TOKEN model (model.TOKEN_MODELS: Gated DeltaNet and full-attention "
        "layers, embedding, cross-entropy head; one pipeline stage's and one "
        "eighth of the vocabulary's share of the published model, from "
        "benchmarks/configs/olmo-hybrid-7b.json): it takes --seq-len, trains "
        "on a packed token set (tokens_train.npy, segments_train.npy) on "
        "one chip (dp = pp = tp = 1), and has no validation split, "
        "checkpoint or fused run yet (pass --no-eval). 'solar-open2-250b' is "
        "the second token family (per-channel delta-rule and gated "
        "grouped-query layers, a routed mixture of experts of which this "
        "chip holds 8 of 320; benchmarks/configs/solar-open2-250b.json)",
    )
    ap.add_argument(
        "--seq-len",
        type=int,
        default=None,
        help="a token model's sequence length: the rows of the token set "
        "hold seq_len + 1 ids",
    )
    ap.add_argument(
        "--recompute",
        action="store_true",
        help="pipeline schedules: activation recompute — forwards stash "
        "only the stage INPUT, and the stage forward re-runs inside the "
        "backward tick (OP_RECOMPUTE), shrinking the activation-stash "
        "lifetime from fwd->bwd to recompute->bwd (peak stash slots drop "
        "to 1 on gpipe/pipedream; ~4/3 FLOPs tax — see docs/lowering.md "
        "and docs/performance.md for when it pays). Bitwise-identical "
        "weights vs stashed training; mesh layouts only, not interleaved",
    )
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--no-eval", action="store_true", help="skip per-epoch accuracy")
    ap.add_argument(
        "--fused-run",
        action="store_true",
        help="run ALL epochs (+ per-epoch validation accuracy unless "
        "--no-eval) as one on-device program — works on every layout "
        "(sequential and DP x PP mesh). Prints the same per-epoch lines as "
        "the loop (without per-line clocks — everything returns in one "
        "dispatch). --profile-dir traces that single dispatch; --checkpoint "
        "writes once at the end instead of per epoch.",
    )
    ap.add_argument(
        "--checkpoint",
        default=None,
        help="path to save a checkpoint after each epoch (with --fused-run: "
        "the whole run is ONE dispatch, so exactly one checkpoint is saved, "
        "after it returns — the pinned contract)",
    )
    ap.add_argument(
        "--checkpoint-dir",
        default=None,
        help="directory for preemption-safe STEP checkpoints "
        "(step-<global_step>.npz, atomic + checksummed; see "
        "docs/robustness.md) — required by --checkpoint-every-steps and "
        "--resume auto",
    )
    ap.add_argument(
        "--checkpoint-every-steps",
        type=int,
        default=0,
        metavar="N",
        help="write a step checkpoint into --checkpoint-dir every N "
        "optimizer steps (0 = off). The epoch is dispatched in N-step "
        "chunks — bitwise-identical weights to whole-epoch dispatch — and "
        "a killed run resumes from the last snapshot with --resume auto",
    )
    ap.add_argument(
        "--keep",
        type=int,
        default=3,
        metavar="K",
        help="step-checkpoint retention: keep the newest K snapshots "
        "(older ones are rotated away; >1 keeps fallbacks for corrupt-"
        "newest recovery)",
    )
    ap.add_argument(
        "--async-checkpoint",
        action="store_true",
        help="write step checkpoints through the background writer: the "
        "step path pays only the device->host snapshot + a bounded-queue "
        "enqueue, while sha256/finiteness verification, the "
        "write-fsync-rename sequence and rotation run off-path "
        "(docs/robustness.md 'The async writer'). Crash windows are "
        "identical to the synchronous path — a kill at any instant "
        "leaves only fully-verifying snapshots discoverable — and the "
        "run drains the writer before exiting",
    )
    ap.add_argument(
        "--resume",
        default=None,
        help="checkpoint to resume from (any layout -> any layout), or "
        "'auto': discover the newest VERIFYING step checkpoint in "
        "--checkpoint-dir (corrupt/torn/non-finite snapshots are skipped), "
        "resume mid-epoch at its exact step — or start fresh when the "
        "directory is empty. With 'auto', --epochs is the run's TOTAL "
        "epoch target (so a killed-and-resumed run ends where its "
        "uninterrupted twin does); with an explicit path it stays the "
        "number of ADDITIONAL epochs (the historical contract)",
    )
    ap.add_argument(
        "--profile-dir",
        default=None,
        help="write a jax.profiler trace of one training epoch to this directory",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        help="record structured training telemetry (per-epoch loss, "
        "samples/s, MFU, grad-norm when clipping, per-step flight records, "
        "compile/lowering spans, pipeline program stats) to this JSONL "
        "file — see docs/observability.md for the schema; render it with "
        "`python -m shallowspeed_tpu.observability.report FILE`",
    )
    ap.add_argument(
        "--digests",
        action="store_true",
        help="numerics provenance: compute per-step per-LAYER digests "
        "(uint32 bitcast checksums of every post-update (W, b) block + "
        "param/grad block norms) inside the fused epoch program and "
        "stream them as schema-v12 digest records to --metrics-out; "
        "compare two runs' streams with `python -m "
        "shallowspeed_tpu.observability.divergence A.jsonl B.jsonl` to "
        "name the first divergent (step, layer, tensor)",
    )
    ap.add_argument(
        "--audit",
        action="store_true",
        help="XLA program audit: at jit time, census the compiled "
        "program's collectives (all-reduce / reduce-scatter / all-gather / "
        "collective-permute) and verify them against the layout's "
        "analytical comms contract — a mismatch aborts BEFORE the first "
        "dispatch. With --metrics-out the full audit (census, memory "
        "analysis, bytes/step comms model) lands as a schema-v3 "
        "xla_audit record; the report CLI renders its memory and comms "
        "sections",
    )
    ap.add_argument(
        "--health",
        choices=["record", "warn", "halt"],
        default=None,
        help="numerics health monitor over the per-step flight aux "
        "(NaN/Inf, rolling-window loss divergence, grad-norm spikes): "
        "'record' emits health records into --metrics-out, 'warn' also "
        "prints them, 'halt' additionally aborts the run (exit 3) at the "
        "first finding, naming the blown-up step",
    )
    ap.add_argument(
        "--fuse-mubatches",
        action="store_true",
        help="sequential path only: one full-batch forward/backward per step "
        "instead of the microbatch scan — same training (see docs/numerics.md), "
        "larger matmuls for the MXU",
    )
    ap.add_argument(
        "--megakernel",
        action="store_true",
        help="with --fuse-mubatches (SGD or momentum): run each training batch as "
        "ONE Pallas kernel — forward, head, backward and update in a single "
        "op (identical numerics; shortest possible serial op chain)",
    )
    ap.add_argument(
        "--epoch-kernel",
        action="store_true",
        help="with --fuse-mubatches (SGD or momentum): run each ENTIRE epoch as "
        "one Pallas kernel — the batch axis is the kernel grid and the "
        "params stay VMEM-resident across the epoch (identical numerics; "
        "one device op per epoch instead of one per batch)",
    )
    ap.add_argument(
        "--run-kernel",
        action="store_true",
        help="with --fuse-mubatches (SGD or momentum): run the whole "
        "multi-epoch training run as ONE Pallas kernel when dispatched via "
        "--fused-run --no-eval (grid = epochs x batches, params VMEM-resident "
        "for the entire run; identical numerics). Per-epoch runs and the "
        "evaluated fused run ride the epoch kernel",
    )
    ap.add_argument(
        "--weight-decay",
        type=float,
        default=0.0,
        help="decoupled weight decay, uniform over every param element "
        "(0 = reference parity)",
    )
    ap.add_argument(
        "--clip-norm",
        type=float,
        default=None,
        help="global-norm gradient clipping over ALL params (the norm spans "
        "stages/replicas on mesh layouts); off by default",
    )
    ap.add_argument(
        "--scan-unroll",
        type=int,
        default=1,
        help="lax.scan unroll factor for the per-batch epoch loop "
        "(throughput knob, bit-identical numerics)",
    )
    ap.add_argument(
        "--tick-unroll",
        type=int,
        default=1,
        help="lax.scan unroll factor for the pipeline tick loop (mesh "
        "layouts; throughput knob, bit-identical numerics)",
    )
    ap.add_argument(
        "--precision",
        choices=["highest", "default"],
        default="highest",
        help="matmul precision: 'highest' = fp32 parity with the NumPy "
        "reference; 'default' = let the MXU use fast (bf16-input) passes",
    )
    ap.add_argument(
        "--runtime",
        choices=["lockstep", "mpmd"],
        default="lockstep",
        help="pipeline runtime (mesh layouts): 'lockstep' runs the whole "
        "lattice as ONE SPMD program (tick scan, ppermute relays — the "
        "correctness oracle); 'mpmd' compiles one program per stage role "
        "and dispatches them asynchronously from the host with device-to-"
        "device relays (arXiv 2412.14374) — bitwise-identical weights, "
        "no noop-tick dispatches (docs/performance.md). mpmd drives the "
        "epoch loop (no --fused-run) and excludes --zero1/--clip-norm/"
        "--kernel-backend pallas for now",
    )
    ap.add_argument(
        "--kernel-backend",
        choices=["xla", "pallas"],
        default="xla",
        help="mesh layouts (--dp/--pp > 1): per-slot compute unit inside "
        "every pipeline tick — 'pallas' runs each slot as one fused "
        "flag-operand Pallas kernel (same math; see docs/performance.md). "
        "Sequential path: use --megakernel or SHALLOWSPEED_PALLAS=1",
    )
    args = ap.parse_args(argv)

    # fail fast on incoherent fault-tolerance flag combinations — at
    # argparse time, before any backend or data is touched
    if args.checkpoint_every_steps < 0:
        ap.error("--checkpoint-every-steps must be >= 0")
    if args.checkpoint_every_steps and args.checkpoint_dir is None:
        ap.error("--checkpoint-every-steps needs --checkpoint-dir")
    if args.checkpoint_every_steps and args.fused_run:
        ap.error(
            "--checkpoint-every-steps is incompatible with --fused-run: the "
            "fused run is ONE on-device dispatch, so there is no step "
            "boundary for the host to checkpoint at — drop --fused-run for "
            "preemption-safe runs (--checkpoint still saves once after the "
            "fused dispatch)"
        )
    if args.resume == "auto" and args.checkpoint_dir is None:
        ap.error("--resume auto discovers snapshots in --checkpoint-dir")
    if args.async_checkpoint and args.checkpoint_dir is None:
        ap.error("--async-checkpoint needs --checkpoint-dir")
    if args.resume == "auto" and args.fused_run:
        ap.error(
            "--resume auto may land mid-epoch, and the fused run has no "
            "mid-epoch entry point — drop --fused-run to recover"
        )
    if args.keep < 1:
        ap.error("--keep must be >= 1")
    if args.runtime == "mpmd" and args.fused_run:
        ap.error(
            "--runtime mpmd schedules per-stage programs from the host; "
            "the fused ONE-dispatch run is a lockstep contract — drop "
            "--fused-run (the epoch loop dispatches MPMD)"
        )
    if args.digests and args.fused_run:
        ap.error(
            "--digests rides the epoch/step scan aux, which the fused "
            "multi-epoch run program does not thread — drop --fused-run "
            "(the epoch/step loops stream digest records)"
        )
    if args.runtime == "mpmd" and (args.dp, args.pp, args.tp) == (1, 1, 1):
        ap.error(
            "--runtime mpmd needs a mesh layout (dp/pp/tp > 1): the "
            "sequential path has no pipeline stages to decompose"
        )
    if args.recompute and (args.dp, args.pp, args.tp) == (1, 1, 1):
        ap.error(
            "--recompute drops pipeline activation stashes; the "
            "sequential path holds no cross-tick stash — use a mesh "
            "layout (dp/pp/tp > 1)"
        )
    if args.recompute and args.virtual_stages > 1:
        ap.error(
            "--recompute is not supported with interleaved virtual "
            "stages (the chunked stash rotation is its own lifetime "
            "discipline)"
        )
    if args.zero1 and args.zero is not None and args.zero != 1:
        ap.error(
            f"conflicting dp-stage selectors: --zero1 and --zero {args.zero} "
            "— pass only --zero"
        )
    zero_stage = args.zero if args.zero is not None else (1 if args.zero1 else 0)
    if zero_stage == 3 and args.fused_run:
        ap.error(
            "--zero 3 is incompatible with --fused-run: the fused "
            "multi-epoch run's eval step consumes the full stacked layout "
            "every epoch, but stage 3 keeps parameters sharded at rest — "
            "drop --fused-run (the per-epoch loop dispatches ZeRO-3)"
        )
    if zero_stage == 3 and args.kernel_backend == "pallas":
        ap.error(
            "--zero 3 is incompatible with --kernel-backend pallas: the "
            "fused slot kernels consume resident {W, b} operands, but "
            "stage 3 materializes parameters per tick via all-gather — "
            "drop one of the two flags"
        )
    if zero_stage and args.runtime == "mpmd":
        ap.error(
            f"--runtime mpmd does not support --zero {zero_stage} yet: the "
            "ZeRO reduce-scatter/all-gather tail assumes the lockstep SPMD "
            "program's dp axis — drop one of the two flags"
        )
    if zero_stage >= 2 and args.digests:
        ap.error(
            f"--digests is incompatible with --zero {zero_stage}: the "
            "digest taps read the zero1 flat-chunk segment map, which "
            "stages 2-3 replace with the block-cyclic shard layout — drop "
            "one of the two flags"
        )
    # "plan is active" mirrors faults.FaultPlan.parse: any non-empty
    # comma-separated part is an injection (checked without importing the
    # package — argparse time stays jax-free)
    faults_env = os.environ.get("SHALLOWSPEED_FAULTS", "")
    if args.fused_run and any(p.strip() for p in faults_env.split(",")):
        ap.error(
            f"SHALLOWSPEED_FAULTS={faults_env!r} is set but --fused-run "
            "dispatches the whole run as ONE program — step-granular "
            "injections can never fire, and a recovery driver would "
            "mistake the uninjected run for a survived crash; drop "
            "--fused-run (the fault harness needs the step loop)"
        )

    import jax

    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.checkpoint import CheckpointError
    from shallowspeed_tpu.compile_cache import enable_compile_cache
    from shallowspeed_tpu.observability import HealthError, JsonlMetrics, capture

    enable_compile_cache()
    metrics = JsonlMetrics(args.metrics_out) if args.metrics_out else None
    try:
        run = TrainingSession(
            metrics=metrics,
            health=args.health,
            audit=args.audit,
            model=args.model,
            seq_len=args.seq_len,
            dp=args.dp,
            pp=args.pp,
            tp=args.tp,
            schedule=args.schedule,
            global_batch_size=args.global_batch_size,
            mubatches=args.mubatches,
            lr=args.lr,
            precision=args.precision,
            data_dir=args.data_dir,
            resume=args.resume,
            fuse_mubatches=args.fuse_mubatches,
            megakernel=args.megakernel,
            epoch_kernel=args.epoch_kernel,
            run_kernel=args.run_kernel,
            optimizer=args.optimizer,
            momentum=args.momentum,
            virtual_stages=args.virtual_stages,
            zero=zero_stage,
            backward_split=args.backward_split,
            recompute=args.recompute,
            scan_unroll=args.scan_unroll,
            tick_unroll=args.tick_unroll,
            weight_decay=args.weight_decay,
            clip_norm=args.clip_norm,
            kernel_backend=args.kernel_backend,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_keep=args.keep,
            async_checkpoint=args.async_checkpoint,
            runtime=args.runtime,
            digests=args.digests,
        )
    except CheckpointError as e:
        # unrecoverable checkpoint state: the named file (or every snapshot
        # in the discovery directory) fails verification — distinct exit
        # code so drivers can tell "restore is impossible" from a crash
        # (exit-code contract: README / docs/observability.md)
        print(f"CHECKPOINT UNRECOVERABLE: {e}", file=sys.stderr)
        if metrics is not None:
            metrics.close()
        sys.exit(4)
    if args.fused_run and run.step_in_epoch > 0:
        # the late half of the fail-fast net: an EXPLICIT --resume
        # snapshot's cursor is only known after reading it, so this
        # contract violation surfaces post-restore — same clean message
        # and exit code (2) as the argparse-time checks, never a raw
        # mid-flight traceback out of the fused dispatch
        if metrics is not None:
            metrics.close()
        ap.error(
            f"--resume {args.resume} restored a mid-epoch cursor (epoch "
            f"{run.epoch}, step {run.step_in_epoch}), and the fused run "
            "has no mid-epoch entry point — drop --fused-run to finish "
            "the epoch with the step loop"
        )
    if (
        args.dp == 1
        and args.pp == 1
        and args.virtual_stages == 1
        and args.tp == 1
    ):
        layout = "sequential"
    elif args.virtual_stages > 1:
        layout = f"interleaved pipeline, V={args.virtual_stages}"
    elif args.pp > 1:
        layout = f"{args.schedule} pipeline"
    elif args.dp > 1:
        layout = "data-parallel"
    else:
        layout = "tensor-parallel"
    if args.tp > 1 and layout != "tensor-parallel":
        layout += " + tensor-parallel"
    note = ""
    if args.resume:
        if run.resumed_from is not None:
            note = f" resumed at epoch {run.epoch}"
            if run.step_in_epoch:
                note += f", step {run.step_in_epoch}"
        else:  # --resume auto on an empty checkpoint dir
            note = " no resumable checkpoint found — fresh start"
    if args.runtime == "mpmd":
        layout += ", mpmd runtime"
    print(
        f"devices={jax.devices()} layout: DP={args.dp} x PP={args.pp} x "
        f"TP={args.tp} ({layout}) batches/epoch={run.batches_per_epoch}" + note
    )
    placed = run.placement()
    if placed is not None:
        print(
            f"mesh placement: {placed['layout']} device_ids="
            f"{placed['device_ids']} param bytes per device="
            f"{placed['param_bytes']}"
        )

    def profiled(i):
        # trace one post-compile epoch when asked (observability.capture =
        # jax.profiler.trace, or nothing without a directory)
        if args.profile_dir and i == min(1, args.epochs - 1):
            return capture(args.profile_dir)
        return contextlib.nullcontext()

    t0 = time.time()
    try:
        if args.fused_run and args.epochs > 0:
            # same accuracy semantics as the loop below — the "Epoch: N ...
            # Accuracy" line reports the model's accuracy BEFORE epoch N trains
            # (the initial one costs a single pre-run dispatch; the rest come
            # out of the fused program's per-epoch accuracies). No per-epoch
            # "Time Spent" here: all lines print after the single dispatch
            # returns, so a per-line cumulative clock would be misleading.
            if not args.no_eval:
                print(f"Epoch: {run.epoch}, Accuracy: {run.accuracy() * 100:.2f}%")
            start = run.epoch
            if args.profile_dir:
                # AOT-compile first so the trace holds steady-state execution,
                # not compilation (mirrors the loop mode's post-compile trace)
                run.warm_run(args.epochs, with_eval=not args.no_eval)
            with capture(args.profile_dir):
                losses, accs = run.train_run(args.epochs, with_eval=not args.no_eval)
            for e, loss in enumerate(losses):
                print(f"Epoch: {start + e}, mean train loss: {loss:.5f}")
                if not args.no_eval and e < len(losses) - 1:
                    print(f"Epoch: {start + e + 1}, Accuracy: {accs[e] * 100:.2f}%")
            if args.checkpoint:
                run.save(args.checkpoint)
            final_acc = accs[-1] if accs else run.accuracy()
        elif (
            args.checkpoint_every_steps
            or run.faults_active
            or run.step_in_epoch > 0
            or args.resume == "auto"
        ):
            # the preemption-safe STEP loop: the epoch is dispatched in
            # chunks cut at the checkpoint grid (and at fault-injection
            # steps), bitwise-identical to whole-epoch dispatch; a snapshot
            # is written whenever global_step lands on the grid. With
            # --resume auto, --epochs is the TOTAL target so a resumed run
            # ends exactly where its uninterrupted twin does — which is why
            # resume-auto runs ALWAYS take this loop, even when the restored
            # cursor sits on an epoch boundary and no step grid is active.
            every = args.checkpoint_every_steps
            target = (
                args.epochs if args.resume == "auto"
                else run.epoch + args.epochs
            )
            nb = run.batches_per_epoch
            # trace one post-compile epoch, like the plain loop's profiled()
            prof_epoch = (
                run.epoch + min(1, max(target - run.epoch - 1, 0))
                if args.profile_dir and target > run.epoch
                else None
            )
            while run.epoch < target:
                if run.step_in_epoch == 0 and not args.no_eval:
                    print(
                        f"Epoch: {run.epoch}, Time Spent: "
                        f"{time.time() - t0:.2f}s, "
                        f"Accuracy: {run.accuracy() * 100:.2f}%"
                    )
                if every > 0:
                    n = min(
                        every - run.global_step % every,
                        nb - run.step_in_epoch,
                    )
                else:
                    n = nb - run.step_in_epoch
                with (
                    capture(args.profile_dir)
                    if run.epoch == prof_epoch
                    else contextlib.nullcontext()
                ):
                    _, epoch_loss = run.train_steps(n)
                if every > 0 and run.global_step % every == 0:
                    run.save_step_checkpoint()
                if epoch_loss is not None:
                    print(
                        f"Epoch: {run.epoch - 1}, mean train loss: "
                        f"{epoch_loss:.5f}"
                    )
                    if args.checkpoint:
                        run.save(args.checkpoint)
            final_acc = None if args.no_eval and args.seq_len else run.accuracy()
        else:
            for i in range(args.epochs):
                if not args.no_eval:
                    print(
                        f"Epoch: {run.epoch}, Time Spent: {time.time() - t0:.2f}s, "
                        f"Accuracy: {run.accuracy() * 100:.2f}%"
                    )
                with profiled(i):
                    loss = run.train_epoch()
                print(f"Epoch: {run.epoch - 1}, mean train loss: {loss:.5f}")
                if args.checkpoint:
                    run.save(args.checkpoint)
            final_acc = None if args.no_eval and args.seq_len else run.accuracy()
    except HealthError as e:
        # --health halt fired: the finding is already recorded (and the
        # JSONL flushed) by the monitor; stop with a distinct exit code so
        # drivers can tell "numerics blew up" from an infrastructure crash
        print(f"HEALTH HALT: {e}", file=sys.stderr)
        if metrics is not None:
            metrics.close()
            print(f"telemetry written: {metrics.path}")
        sys.exit(3)
    finally:
        # EVERY exceptional exit drains the async checkpoint writer — a
        # KeyboardInterrupt or a failing eval must not strand accepted
        # snapshots in a daemon thread's queue. Best-effort only while
        # an exception is propagating (a drain failure must never mask
        # it); the clean path closes below, LOUDLY, so writer errors
        # still fail the run.
        if sys.exc_info()[0] is not None:
            try:
                run.close()
            except Exception as e:  # noqa: BLE001 — never mask the exit
                print(
                    f"checkpoint writer drain failed: {e}", file=sys.stderr
                )
    # drain the async checkpoint writer BEFORE claiming success: a clean
    # exit must leave every accepted snapshot durable (writer-side
    # failures re-raise here instead of dying silently in a daemon thread)
    run.close()
    print(
        f"Epoch: {run.epoch}, Time Spent: {time.time() - t0:.2f}s"
        + ("" if final_acc is None else f", Accuracy: {final_acc * 100:.2f}%")
    )
    run.assert_replicas_in_sync()
    if args.dp > 1:
        print("DP replicas in sync ✓")
    print("final model hash:", run.model_hash())
    if metrics is not None:
        metrics.close()
        print(f"telemetry written: {metrics.path}")


if __name__ == "__main__":
    main()
