"""High-level programmatic API: one object that wires the whole framework.

The reference's user assembles communicators, model, dataset, optimizer and
Worker by hand in train.py (train.py:87-129); here the same wiring is a
library object, so notebooks/tests/benchmarks get everything the CLI does:

    from shallowspeed_tpu.api import TrainingSession

    run = TrainingSession(dp=2, pp=4, schedule="gpipe", data_dir="data/mnist_784")
    for _ in range(20):
        loss = run.train_epoch()
        print(run.epoch, loss, run.accuracy())
    run.save("ck.npz")

Layouts are uniform: dp=pp=tp=1 uses the fast sequential jitted path,
anything else the SPMD pipeline executor — same weights either way (tested
layout equivalence; ``tp`` adds the Megatron model axis, whose split
contractions carry the same cross-layout float tolerance a dp-width change
does, while tp=1 programs stay byte-identical to the pre-TP anchors).
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from shallowspeed_tpu import faults as F
from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import schedules as S
from shallowspeed_tpu import trainer, utils
from shallowspeed_tpu.checkpoint import (
    AsyncCheckpointWriter,
    CheckpointError,
    assemble_checkpoint,
    build_snapshot,
    find_latest_good,
    load_checkpoint,
    run_save_stages,
    save_checkpoint,
    step_checkpoint_path,
)
from shallowspeed_tpu.data import Dataset, default_data_dir, packed_counts
from shallowspeed_tpu.observability import NullMetrics, costmodel, program_audit
from shallowspeed_tpu.observability import scopes
from shallowspeed_tpu.observability.flight import FlightRecorder
from shallowspeed_tpu.observability.health import HealthError, make_monitor
from shallowspeed_tpu.observability.slo import (
    LiveTelemetry,
    default_training_rules,
)
from shallowspeed_tpu.observability.spans import listen_to_compiles
from shallowspeed_tpu.optimizer import (
    is_stateless,
    join_state,
    WithGradScratch,
    make_optimizer,
    split_state,
)
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.parallel import lower_schedule
from shallowspeed_tpu.parallel.mesh import make_mesh_with_layout
from shallowspeed_tpu.parallel.lowering import program_flops, program_stats
from shallowspeed_tpu.serving import slots as serving_slots

# The reference's canonical training configuration (train.py:56-59,98,107) —
# the single source of truth for every benchmark script in this repo.
FLAGSHIP_SIZES = (784, 128, 127, 126, 125, 124, 123, 10)
FLAGSHIP_BATCH = 128
FLAGSHIP_MUBATCHES = 4
FLAGSHIP_LR = 0.006

# Matmul-precision names accepted everywhere a precision string is taken
# (TrainingSession, train.py --precision, bench.py) — single source of truth.
PRECISIONS = {
    "highest": lax.Precision.HIGHEST,
    "default": lax.Precision.DEFAULT,
}


class TrainingSession:
    """End-to-end training run: data + model + layout + optimizer + eval."""

    def __init__(
        self,
        sizes=FLAGSHIP_SIZES,
        model=None,
        seq_len=None,
        dp=1,
        pp=1,
        tp=1,
        schedule="gpipe",
        global_batch_size=128,
        mubatches=4,
        lr=0.006,
        precision="highest",
        data_dir=None,
        resume=None,
        devices=None,
        fuse_mubatches=False,
        optimizer="sgd",
        momentum=0.9,
        virtual_stages=1,
        zero1=False,
        zero=None,
        backward_split=False,
        recompute=False,
        scan_unroll=1,
        tick_unroll=1,
        weight_decay=0.0,
        clip_norm=None,
        megakernel=False,
        epoch_kernel=False,
        run_kernel=False,
        kernel_backend="xla",
        metrics=None,
        health=None,
        record_steps=None,
        digests=False,
        audit=False,
        checkpoint_dir=None,
        checkpoint_keep=3,
        async_checkpoint=False,
        checkpoint_queue=2,
        faults=None,
        predict_slot_rows=None,
        predict_slot_ladder=None,
        runtime="lockstep",
    ):
        # telemetry hook (observability package): None -> the null backend,
        # which drops everything but spans. Everything the session emits
        # (per-epoch training records, per-step flight records, MFU gauges,
        # pipeline program stats) flows through this one recorder; its spans
        # are ALWAYS written to the process's span log and the profiler's
        # trace (observability/spans.py), recorder or none, and the compile
        # listener puts each trace, lowering and backend compile beside them
        # (docs/observability.md).
        self._metrics = metrics if metrics is not None else NullMetrics()
        listen_to_compiles()
        with self._metrics.span("session/init"):
            # live telemetry (schema v11, docs/observability.md § Live
            # telemetry & alerting): per-step loss/throughput/MFU rollup
            # windows plus the trainer rule set (health-event alerts, the
            # checkpoint-overhead fraction vs its budget). Fed only inside
            # metrics-enabled blocks — a NullMetrics session pays nothing.
            self._telemetry = LiveTelemetry(
                "train", metrics=self._metrics, rules=default_training_rules()
            )
            # compiled-program audit (observability/program_audit.py): with a
            # metrics recorder attached, the jit-time collective census +
            # memory analysis is ALWAYS recorded (schema-v3 xla_audit record).
            # ``audit=True`` additionally ENFORCES the layout's comms contract:
            # the epoch/run program is compiled (even without metrics) and an
            # AuditMismatchError is raised when its collective census violates
            # the layout's analytical contract.
            self._audit_strict = bool(audit)
            self._audit_done = set()  # program names already audited
            # numerics health monitor: None, a policy string ("record" / "warn"
            # / "halt"), or a HealthMonitor instance (observability/health.py).
            # Checks run on host against the fused per-step aux after each
            # epoch's readback; under "halt" a finding raises HealthError AFTER
            # the epoch's update has been applied (the monitor observes the
            # fused program's outputs, it cannot unwind them).
            self._health = make_monitor(health)
            # model zoo (model.MODEL_ZOO / train.py --model): a named
            # compute-bound configuration — (sizes, activation family) — that
            # overrides ``sizes``. The family is STATIC program structure
            # (relu traces the historical expressions byte-identically; the
            # gelu family adds residual slots and f32 grad-multiplier masks),
            # and every zoo model keeps the 784-wide MNIST input so the data
            # pipeline, checkpoints and serving slots compose unchanged.
            # A TOKEN model (model.TOKEN_MODELS, or the keys of a published
            # config.json given as a dictionary) is a function of token ids of
            # ``seq_len`` a row: it has no ``sizes``, trains on a packed token
            # set, and runs the sequential path only (checked below, once the
            # layout is known).
            self.model_name = model
            token_config = (
                Mo.token_model_config(model) if Mo.is_token_model(model) else None
            )
            self._token = token_config is not None
            if self._token:
                if isinstance(model, dict):
                    self.model_name = model.get("model_type", "token-model")
                sizes, act = (), "relu"
            elif seq_len is not None:
                raise ValueError(
                    "seq_len is a token model's sequence length; "
                    f"model={model!r} takes rows of features"
                )
            elif model is not None:
                sizes, act = Mo.resolve_model(model)
            else:
                act = "relu"
            self._act = act
            if global_batch_size % dp != 0:
                raise ValueError("global batch size must be divisible by dp")
            local_batch = global_batch_size // dp
            if local_batch % mubatches != 0:
                raise ValueError("mubatches must divide the local batch")
            if tp < 1:
                raise ValueError(f"tp must be >= 1, got {tp}")
            self.dp, self.pp, self.tp = dp, pp, int(tp)
            self.B, self.M = global_batch_size, mubatches
            self.schedule = schedule
            if precision not in PRECISIONS:
                raise ValueError(
                    f"precision must be one of {sorted(PRECISIONS)}, got {precision!r}"
                )
            self._precision_name = precision  # the MFU peak is precision-classed
            if schedule not in S.SCHEDULES:
                raise ValueError(
                    f"schedule must be one of {sorted(S.SCHEDULES)}, got {schedule!r}"
                )
            self.precision = PRECISIONS[precision]
            if fuse_mubatches and not (
                dp == 1 and pp == 1 and virtual_stages == 1 and tp == 1
            ):
                raise ValueError(
                    "fuse_mubatches applies to the sequential path only; in the "
                    "pipeline executor microbatches are semantic (they ARE the "
                    "pipeline's unit of work)"
                )
            if megakernel and not fuse_mubatches:
                raise ValueError(
                    "megakernel runs the whole fused batch as one Pallas kernel; "
                    "it requires fuse_mubatches=True (sequential path)"
                )
            if epoch_kernel and not fuse_mubatches:
                raise ValueError(
                    "epoch_kernel runs the whole epoch as one Pallas kernel; "
                    "it requires fuse_mubatches=True (sequential path)"
                )
            if run_kernel and not fuse_mubatches:
                raise ValueError(
                    "run_kernel runs the whole multi-epoch run as one Pallas "
                    "kernel; it requires fuse_mubatches=True (sequential path)"
                )
            if run_kernel and (megakernel or epoch_kernel):
                raise ValueError(
                    "run_kernel subsumes the mega/epoch kernels; pass only "
                    "run_kernel=True"
                )
            self._run_kernel = bool(run_kernel)
            if kernel_backend not in ("xla", "pallas"):
                raise ValueError(
                    f"kernel_backend must be 'xla' or 'pallas', got {kernel_backend!r}"
                )
            if virtual_stages < 1:
                raise ValueError("virtual_stages must be >= 1")
            if virtual_stages > 1 and schedule != "interleaved":
                raise ValueError(
                    "virtual_stages > 1 requires schedule='interleaved' (the flat "
                    "schedules place exactly one stage per device)"
                )
            if scan_unroll < 1 or tick_unroll < 1:
                raise ValueError("scan_unroll/tick_unroll must be >= 1")
            self.V = virtual_stages
            self._sequential = dp == 1 and pp == 1 and virtual_stages == 1 and tp == 1
            self._kernel_backend = kernel_backend
            if self._token:
                wanted = {
                    "a mesh layout (dp, pp, tp or virtual_stages > 1)": (
                        not self._sequential
                    ),
                    "zero": bool(zero) or zero1,
                    "fuse_mubatches": fuse_mubatches,
                    "the pallas kernels (megakernel, epoch_kernel, run_kernel, "
                    "kernel_backend='pallas')": megakernel or epoch_kernel
                    or run_kernel or kernel_backend == "pallas",
                    "runtime='mpmd'": runtime != "lockstep",
                    "digests": digests,
                    "checkpoints (resume, checkpoint_dir)": resume is not None
                    or checkpoint_dir is not None,
                }
                refused = [what for what, asked in wanted.items() if asked]
                if refused:
                    raise ValueError(
                        f"token model {self.model_name!r} runs the sequential "
                        f"one-chip path (dp = pp = tp = 1) only; asked for: "
                        f"{'; '.join(refused)}. The mesh executor's stage "
                        "functions, its kernels and the checkpoint format are "
                        "written for stacks of {W, b} Linears (ROADMAP R0a, D2)"
                    )
            if kernel_backend == "pallas" and act != "relu":
                raise ValueError(
                    "kernel_backend='pallas' hard-codes the relu/identity slot "
                    "expressions; the gelu-family models (f32 grad-multiplier "
                    "masks, residual adds) run the XLA backend only"
                )
            if kernel_backend == "pallas" and tp > 1:
                raise ValueError(
                    "tensor parallelism (tp > 1) shards each slot's W across "
                    "the tp axis; the fused pallas flag kernels compute whole "
                    "slots — use kernel_backend='xla'"
                )
            if kernel_backend == "pallas" and self._sequential:
                raise ValueError(
                    "kernel_backend='pallas' selects the pipeline executor's "
                    "flag-operand kernels and needs a mesh layout (dp/pp > 1 or "
                    "virtual_stages > 1); on the sequential path use "
                    "megakernel=True or SHALLOWSPEED_PALLAS=1 instead"
                )
            if tick_unroll > 1 and self._sequential:
                raise ValueError(
                    "tick_unroll unrolls the pipeline tick loop; the sequential "
                    "path has no ticks — use scan_unroll"
                )
            # the dp-axis ZeRO stage (arXiv 2004.13336): ``zero`` in {0,1,2,3}
            # supersedes the historical ``zero1`` boolean — ``zero=1`` IS the
            # zero1 path, verbatim. Stage 2 shards gradients + optimizer state
            # (block-cyclic per-slot layout, bitwise-equal weights to stage 1
            # on clip-free runs); stage 3 additionally shards the params at
            # rest with just-in-time per-tick gathers.
            if zero is None:
                zero = 1 if zero1 else 0
            else:
                zero = int(zero)
                if zero not in (0, 1, 2, 3):
                    raise ValueError(f"zero must be one of 0/1/2/3, got {zero}")
                if zero1 and zero != 1:
                    raise ValueError(
                        f"conflicting dp-stage selectors: zero1=True but "
                        f"zero={zero} — pass only --zero"
                    )
            self._zero = zero
            self._zero1 = zero == 1
            # ZeRO-3 eval view: the {W, b} stacked layout rebuilt from the
            # at-rest shards for inference programs, cached by identity
            self._eval_stacked_cache = None
            if self._zero and self._sequential:
                if self._zero1:
                    raise ValueError(
                        "zero1 shards the optimizer update over the dp mesh "
                        "axis; the sequential path has no mesh — use dp/pp > 1"
                    )
                raise ValueError(
                    f"zero={zero} shards the update over the dp mesh axis; "
                    "the sequential path has no mesh — use dp/pp > 1"
                )
            if self._zero >= 2 and digests:
                raise ValueError(
                    "digests read the zero1 flat-chunk segment map; the "
                    "block-cyclic shard layout of zero>=2 has no flat chunk — "
                    "use --zero 1 or below with --digests"
                )
            if self._zero == 3 and kernel_backend == "pallas":
                raise ValueError(
                    "zero=3 all-gathers parameter segments inside every tick "
                    "branch; the fused pallas flag kernels take whole resident "
                    "slots — use kernel_backend='xla' with --zero 3"
                )
            self._backward_split = bool(backward_split)
            if self._backward_split:
                if self._sequential:
                    raise ValueError(
                        "backward_split is a pipeline-schedule property (B-input "
                        "at the relay tick, B-weight deferred into bubbles); the "
                        "sequential path has no schedule — use dp/pp > 1"
                    )
                if virtual_stages > 1:
                    raise ValueError(
                        "backward_split is not supported with interleaved "
                        "virtual stages (the chunked steady state interleaves "
                        "its own bubbles; splitting its backward is future work)"
                    )
                if kernel_backend == "pallas":
                    raise ValueError(
                        "backward_split needs the XLA per-slot backward; the "
                        "fused pallas flag kernel has no split halves"
                    )
            # activation recompute (docs/lowering.md "Recompute ticks"): drop
            # the forward's activation stashes, keep only the stage INPUT, and
            # re-run the stage forward inside the backward tick (OP_RECOMPUTE)
            # — a memory-for-FLOPs trade that shortens the stash lifetime from
            # fwd->bwd to recompute->bwd (arXiv 2004.09910's checkpointing,
            # tick-table form). Bitwise-identical training: the recompute
            # re-traces the character-identical forward expressions.
            self._recompute = bool(recompute)
            if self._recompute:
                if self._sequential:
                    raise ValueError(
                        "recompute drops pipeline activation stashes and "
                        "re-runs the stage forward at the backward tick; the "
                        "sequential path holds no cross-tick stash — use "
                        "dp/pp > 1"
                    )
                if virtual_stages > 1:
                    raise ValueError(
                        "recompute is not supported with interleaved virtual "
                        "stages (the chunked stash rotation is its own "
                        "lifetime discipline; recomputing it is future work)"
                    )
                if kernel_backend == "pallas":
                    raise ValueError(
                        "recompute re-runs the XLA per-slot forward inside "
                        "the backward tick; the fused pallas flag kernel has "
                        "no recompute branch"
                    )
            # pipeline runtime (docs/performance.md "The MPMD runtime"):
            # "lockstep" is the historical ONE-SPMD-program executor (the
            # correctness oracle); "mpmd" dispatches one compiled program per
            # stage role asynchronously from the host with device-to-device
            # relays (parallel/mpmd.py) — bitwise-identical weights, measured
            # lower op-issue overhead. The MPMD feature envelope is enforced
            # here: the knobs whose lockstep implementations live in the fused
            # program's tail (zero1, the cross-stage clip norm,
            # the pallas tick backend, the per-step flight aux) stay
            # lockstep-only until the per-stage update learns their math.
            if runtime not in ("lockstep", "mpmd"):
                raise ValueError(
                    f"runtime must be 'lockstep' or 'mpmd', got {runtime!r}"
                )
            self.runtime = runtime
            self._mpmd = None  # the train runner, built with the tick program
            self._mpmd_infer = None  # the streaming inference runner (lazy)
            if runtime == "mpmd":
                if self._sequential:
                    raise ValueError(
                        "runtime='mpmd' dispatches one program per pipeline "
                        "stage; the sequential path has no stages — use a mesh "
                        "layout (dp/pp/tp > 1)"
                    )
                if self._zero:
                    raise ValueError(
                        f"runtime='mpmd' does not support zero (stage "
                        f"{self._zero}) yet: the ZeRO reduce-scatter/all-gather "
                        "update spans the whole sharded param layout, not one "
                        "stage — use runtime='lockstep'"
                    )
                if clip_norm is not None:
                    raise ValueError(
                        "runtime='mpmd' does not support clip_norm yet: the "
                        "global norm spans every stage's gradient, which the "
                        "per-stage update programs cannot see — use "
                        "runtime='lockstep'"
                    )
                if kernel_backend != "xla":
                    raise ValueError(
                        "runtime='mpmd' uses the XLA per-slot stage functions; "
                        "kernel_backend='pallas' is lockstep-only"
                    )
                if record_steps:
                    raise ValueError(
                        "runtime='mpmd' does not thread the per-step flight aux "
                        "(loss/grad-norm/param-norm vectors ride the lockstep "
                        "epoch scan); pass record_steps=False or use "
                        "runtime='lockstep'"
                    )
                record_steps = False
                if digests:
                    raise ValueError(
                        "runtime='mpmd' does not thread the per-step digest aux "
                        "(the per-layer checksum grids ride the lockstep epoch "
                        "scan); pass digests=False or use runtime='lockstep'"
                    )

            self.epoch = 0
            # step cursor within the current epoch: 0 except after a mid-epoch
            # resume / between train_steps() chunks. global_step (property) is
            # the run-lifetime optimizer-step count — the unit the step
            # checkpoints, fault injections and flight records all share.
            self.step_in_epoch = 0
            # fault-tolerance wiring (docs/robustness.md): the step-checkpoint
            # directory + retention, the fault-injection plan (explicit arg, or
            # the SHALLOWSPEED_FAULTS env spec), and what resume discovered
            if checkpoint_keep < 1:
                raise ValueError("checkpoint_keep must be >= 1")
            self._ckpt_dir = checkpoint_dir
            self._ckpt_keep = int(checkpoint_keep)
            # paths THIS session wrote with all_finite=True: rotation trusts
            # them without re-reading (their checksums were computed in-process)
            self._trusted_snapshots = set()
            self._faults = F.make_plan(faults)
            # async checkpointing (docs/robustness.md "The async writer"):
            # save_step_checkpoint(async_=True) — or async_checkpoint=True as
            # the session default — keeps only the device->host snapshot on
            # the step path and hands verify/write/fsync/rename/rotate to a
            # single background writer behind a bounded queue. The writer is
            # created lazily on the first async save; save_seq is the
            # @save=N fault anchor, counted over EVERY save this process
            # attempts (sync, async, halt flush) so a spec replays
            # deterministically whichever mode is active.
            if checkpoint_queue < 1:
                raise ValueError("checkpoint_queue must be >= 1")
            self._async_ckpt_default = bool(async_checkpoint)
            self._ckpt_queue = int(checkpoint_queue)
            self._ckpt_writer = None
            self._save_seq = 0
            self.resumed_from = None  # path of the restored snapshot, if any
            self._recovery = None  # the recovery record's fields, if resume ran
            # per-epoch aggregation across train_steps() chunks. steps_counted
            # tracks how many steps THIS process dispatched: after a mid-epoch
            # resume it is smaller than batches_per_epoch (the head of the
            # epoch ran in the dead process), and the completing epoch's
            # loss/throughput are reported over the counted steps only
            self._epoch_loss_sum = 0.0
            self._epoch_wall = 0.0
            self._epoch_steps_counted = 0
            self._epoch_first_dispatch = False

            with self._metrics.span("session/data"):
                data_dir = data_dir or default_data_dir()
                self._data_dir = data_dir
                self._train_ds = Dataset(
                    data_dir, self.B, mubatch_size=local_batch // mubatches,
                    tokens=self._token,
                )
                self._train_ds.load(0, 1)
            # validation split is loaded lazily on the first accuracy() call, so
            # eval-free runs (train.py --no-eval, benchmarks) pay neither the host
            # load nor the device transfer
            self._vx = self._vy = None
            # inference slot geometry (serving/slots.py): predict(), mesh eval
            # and the serving engine all dispatch whole microbatch SLOTS of
            # ``slot_rows`` global rows, with per-dispatch slot counts rounded
            # up a fixed ladder — so the predict cache holds at most
            # len(ladder) compiled programs (one per rung) instead of one per
            # distinct row count, and a request slot computes bitwise-
            # identically in every rung program (docs/serving.md)
            if predict_slot_rows is None:
                self._slot_rows = serving_slots.default_slot_rows(dp)
            else:
                self._slot_rows = int(predict_slot_rows)
                if self._slot_rows < 1 or self._slot_rows % dp:
                    raise ValueError(
                        f"predict_slot_rows must be a positive multiple of dp="
                        f"{dp}, got {predict_slot_rows}"
                    )
            self._slot_ladder = serving_slots.validate_ladder(
                predict_slot_ladder
                if predict_slot_ladder is not None
                else serving_slots.DEFAULT_SLOT_LADDER
            )
            self._predict_cache = {}  # inference programs, keyed by ladder rung
            self._run_fns = {}  # fused multi-epoch programs, keyed by with_eval
            # AOT warm_run executables, keyed by (with_eval, epochs)
            self._compiled_runs = {}

            with self._metrics.span("session/data"):
                nb = self._train_ds.get_num_batches()
                if nb == 0:
                    raise ValueError(
                        f"training split has {self._train_ds.raw_len} samples — "
                        f"fewer than one global batch of {self.B}"
                    )
                # the orientation in which the training set is resident: the
                # sequential microbatch scan's choice, from the microbatch's shape
                # against the chip's tile, the first Linear's and the precision
                # (trainer.data_layout); a mesh places and slices its own batches
                # (executor.py) and stays row-major.
                # Provenance like the mesh's layout: a run's record has to say
                # which of the two epoch programs it timed
                mubatch_rows = local_batch // mubatches
                self._scan_path = None
                self._data_layout = (
                    trainer.data_layout(
                        mubatch_rows, sizes, self.precision,
                        scanned=not (
                            fuse_mubatches or megakernel or epoch_kernel or run_kernel
                        ),
                    )
                    if self._sequential and not self._token
                    else "row_major"
                )
                Xb, Yb = self._train_ds.epoch_arrays()
                if self._metrics.enabled:
                    self._metrics.event(
                        "data_layout",
                        layout=self._data_layout, mb=mubatch_rows, F=int(Xb.shape[-1]),
                    )
                if self._token:
                    if seq_len is None or Xb.shape[-1] != seq_len + 1:
                        raise ValueError(
                            f"the token set's rows hold {Xb.shape[-1]} ids; a token "
                            f"model needs seq_len (got {seq_len}) and rows of "
                            f"seq_len + 1"
                        )
                    # what the resident set holds, for the readers of a trace: plain
                    # numbers, handed to observability.scopes with the epoch
                    # program (``_run_epoch_program``)
                    self._token_counts = packed_counts(
                        self._train_ds.target_y[: nb * self.B]
                    )
                if self.runtime == "mpmd":
                    # the MPMD host scheduler feeds per-microbatch device_puts to
                    # the endpoint stages' sub-meshes itself; the epoch arrays
                    # stay host-side (numpy slices are the step-chunk unit)
                    self._X = Xb.reshape(nb, self.B, Xb.shape[-1])
                    self._Y = Yb.reshape(nb, self.B, Yb.shape[-1])
                else:
                    with self._metrics.span("device_put"):
                        if self._data_layout != "feature_major":
                            Xb = Xb.reshape(nb, self.B, Xb.shape[-1])
                        # else placed by microbatch, as the dataset hands it over:
                        # the chip stores THAT shape features-major, so
                        # trainer.feature_major below is one straight copy; from
                        # (nb, B, F) it compiles to two passes and a third
                        # set-sized buffer
                        self._X = jnp.asarray(Xb)
                        self._Y = jnp.asarray(Yb.reshape(nb, self.B, Yb.shape[-1]))
                self.batches_per_epoch = nb

            n_model_stages = pp * virtual_stages
            if self._token:
                self.spec = Mo.make_token_spec(
                    token_config, seq_len, self.B, mubatch_rows=mubatch_rows
                )
                # which form of the recurrent layers' scan the epoch program
                # holds (ops.scan_path or ops.kda_scan_path, from the shapes
                # alone) and how many layers' forwards run twice: provenance
                # like ``data_layout``'s, an event and counts beside the
                # program's
                scan_plan = Mo.token_scan_plan(self.spec, mubatches)
                self._scan_path = scan_plan["path"]
                self._token_counts.update(
                    scan_kernel_calls=scan_plan["kernel_calls_per_step"] * nb,
                    scan_pairs_read=scan_plan["pairs_read_per_step"] * nb,
                    recomputed_layer_passes=(
                        scan_plan["recomputed_layers"] * mubatches * nb
                    ),
                    acc_inplace_leaf_passes=(
                        trainer.accumulated_expert_leaves(self.spec, mubatches) * nb
                    ),
                )
                if self._metrics.enabled:
                    self._metrics.event("scan_path", **scan_plan)
                # a model with routed layers: what the experts held here were
                # routed comes with each epoch's loss (``_run_epoch_program``)
                # and starts at nothing
                if self.spec.routed_layers:
                    held = self.spec.experts_held
                    self._token_counts.update(
                        moe_layers=self.spec.routed_layers,
                        moe_experts_held=held[1] - held[0],
                        moe_rows_held=0, moe_load_max=0,
                    )
                # an id outside the table raises nothing on the device (the
                # lookup clamps it, the scatter-add drops it)
                ids = self._train_ds.input_X
                if ids.min() < 0 or ids.max() >= self.spec.vocab_size:
                    raise ValueError(
                        f"token ids span {ids.min()}..{ids.max()}; the model "
                        f"holds a vocabulary of {self.spec.vocab_size}"
                    )
            else:
                self.spec = Mo.make_model_spec(sizes, n_model_stages, self.B, act=act)
            # device-major stage placement for virtual chunks (identity otherwise)
            self._order = (
                E.interleave_order(n_model_stages, pp) if virtual_stages > 1 else None
            )
            if clip_norm is not None and clip_norm <= 0:
                raise ValueError("clip_norm must be positive (or None to disable)")
            opt = self._opt = make_optimizer(optimizer, lr, momentum, weight_decay)
            if self._token and trainer.token_step_is_scanned(mubatches):
                # the step loops over its microbatches and keeps its gradient
                # accumulator in the optimizer's state (a donated argument)
                opt = self._opt = WithGradScratch(opt)
            self._opt_config = {
                "name": optimizer,
                "lr": lr,
                "momentum": momentum,
                "weight_decay": weight_decay,
            }

            host_opt_state = None  # logical (per-stage ragged) saved state, if any
            verified = None  # (meta, arrays) of the snapshot discovery verified
            with self._metrics.span("session/resume"):
                if resume == "auto":
                    # crash-recovery discovery: newest VERIFYING snapshot in the
                    # checkpoint dir (corrupt/torn/non-finite ones are skipped with
                    # their causes recorded); an empty/missing dir is a fresh start,
                    # a dir with snapshots where NONE verifies is unrecoverable.
                    # with_arrays: discovery's verified read IS the load's read —
                    # one read, one checksum pass, and the discovery->load TOCTOU
                    # window (the snapshot rotting or rotating away between the
                    # verify and a re-read) is closed by construction instead of
                    # by the re-verification `load` used to repeat
                    if self._ckpt_dir is None:
                        raise ValueError(
                            "resume='auto' discovers snapshots in the step-checkpoint "
                            "directory — pass checkpoint_dir"
                        )
                    path, vmeta, varrays, skipped = find_latest_good(
                        self._ckpt_dir, with_arrays=True
                    )
                    if path is not None:
                        verified = (vmeta, varrays)
                    skipped_fields = [
                        {"path": str(p), "cause": cause} for p, cause in skipped
                    ]
                    if path is None and skipped:
                        # every candidate failed: corrupt/torn files, or non-finite
                        # blow-up snapshots that discovery skips BY DESIGN — name
                        # each cause so the operator can tell which they have
                        raise CheckpointError(
                            self._ckpt_dir,
                            "no snapshot verifies: "
                            + "; ".join(f"{p.name}: {c}" for p, c in skipped)
                            + " (non-finite snapshots are skipped by design — "
                            "delete the directory to start fresh)",
                        )
                    if path is None:
                        resume = None
                        self._recovery = {
                            "verdict": "fresh_start",
                            "resumed_from": None,
                            "skipped": skipped_fields,
                        }
                    else:
                        resume = path
                        self._recovery = {
                            "verdict": "resumed",
                            "resumed_from": str(path),
                            "skipped": skipped_fields,
                        }
                if resume is not None:
                    if verified is not None:
                        # resume-auto: assemble from the arrays discovery already
                        # read and checksummed — `load` does not touch the file
                        host_params, loaded_spec, meta, host_opt_state = (
                            assemble_checkpoint(
                                resume, verified[0], verified[1], n_model_stages,
                                self.B, with_opt_state=True,
                            )
                        )
                    else:  # explicit path: one read+verify via the loader
                        host_params, loaded_spec, meta, host_opt_state = (
                            load_checkpoint(
                                resume, n_model_stages, self.B, with_opt_state=True
                            )
                        )
                    self.resumed_from = str(resume)
                    if tuple(loaded_spec.sizes) != tuple(self.spec.sizes):
                        raise ValueError(
                            f"checkpoint sizes {loaded_spec.sizes} do not match the "
                            f"requested model sizes {self.spec.sizes}"
                        )
                    if getattr(loaded_spec, "act", "relu") != self.spec.act:
                        raise ValueError(
                            f"checkpoint activation family "
                            f"{getattr(loaded_spec, 'act', 'relu')!r} does not match "
                            f"the requested model's {self.spec.act!r} — the family "
                            f"is program structure, not a runtime knob"
                        )
                    saved_opt = meta.get("extra", {}).get("optimizer")
                    if saved_opt is not None:
                        # name must match, and for stateful optimizers so must the
                        # coefficient the saved state was accumulated under — a
                        # mismatch would silently reinterpret the velocity. lr is
                        # deliberately free (changing it on resume is a schedule, not
                        # a reinterpretation of saved state).
                        if saved_opt["name"] != optimizer:
                            raise ValueError(
                                f"checkpoint was trained with optimizer "
                                f"{saved_opt['name']!r}; resuming with {optimizer!r} "
                                f"would silently change the trajectory — pass "
                                f"optimizer={saved_opt['name']!r} to continue it, or "
                                f"start a fresh run without resume"
                            )
                        if (
                            optimizer == "momentum"
                            and saved_opt.get("momentum") != momentum
                        ):
                            raise ValueError(
                                f"checkpoint velocity was accumulated with "
                                f"momentum={saved_opt.get('momentum')}; resuming with "
                                f"momentum={momentum} would reinterpret it — pass "
                                f"the saved coefficient"
                            )
                        saved_wd = saved_opt.get("weight_decay", 0.0)
                        if saved_wd != weight_decay:
                            raise ValueError(
                                f"checkpoint was trained with weight_decay={saved_wd}; "
                                f"resuming with weight_decay={weight_decay} would "
                                f"silently change the trajectory — pass the saved "
                                f"value"
                            )
                    self.spec = loaded_spec
                    if meta.get("step_in_epoch") is not None:
                        # v2 step snapshot: ``epoch`` is the epoch IN PROGRESS and
                        # the cursor restarts mid-epoch. The bit-identity contract
                        # needs the identical deterministic data order, so the
                        # global batch size must match the saved run exactly.
                        if meta["global_batch_size"] != self.B:
                            raise ValueError(
                                f"mid-epoch resume needs the saved data order: "
                                f"checkpoint was taken at global_batch_size="
                                f"{meta['global_batch_size']}, this run uses {self.B}"
                            )
                        if not 0 <= meta["step_in_epoch"] < max(nb, 1):
                            raise ValueError(
                                f"checkpoint step_in_epoch {meta['step_in_epoch']} "
                                f"out of range for {nb} batches/epoch — different "
                                f"dataset?"
                            )
                        self.epoch = int(meta["epoch"])
                        self.step_in_epoch = int(meta["step_in_epoch"])
                    else:
                        # legacy epoch-boundary snapshot: ``epoch`` is the last
                        # COMPLETED epoch
                        self.epoch = meta["epoch"] + 1
            if resume is None:
                with self._metrics.span("session/weights"):
                    host_params = (
                        Mo.init_token_model if self._token else Mo.init_model
                    )(self.spec, self._metrics)

            # telemetry aux: when recording AND clipping, the epoch/run programs
            # also return the pre-clip global gradient norm (ordinary fused
            # outputs — never host callbacks inside the scan). The kernel paths
            # keep gradients in VMEM, so the aux is unavailable there; both
            # layouts' fused runs thread it (trainer.make_train_run and
            # executor.make_pipeline_run).
            kernel_path = megakernel or epoch_kernel or run_kernel
            aux_gnorm = (
                self._metrics.enabled and clip_norm is not None and not kernel_path
            )
            self._epoch_aux = aux_gnorm
            self._run_aux = aux_gnorm
            # flight-recorder aux: per-step (per-batch) loss / pre-clip grad
            # norm / post-update param norm vectors out of the SAME fused epoch
            # program. ``record_steps=None`` (default) auto-enables whenever
            # anything will consume them (a metrics recorder or a health
            # monitor); ``False`` opts a metrics session back out (epoch-level
            # telemetry only — the PR1 cost profile: no per-step param-norm in
            # the program, no per-step JSONL lines; health falls back to
            # epoch-granular checks); ``True`` forces the flight ring on even
            # without a recorder. The NullMetrics default without a monitor
            # keeps the uninstrumented program, so recording disabled stays
            # zero-overhead on the hot path.
            if record_steps is None:
                record_steps = self._metrics.enabled or self._health is not None
            elif record_steps and kernel_path:
                raise ValueError(
                    "record_steps is unavailable on the kernel paths: the "
                    "gradient never leaves the Pallas kernel's VMEM"
                )
            # numerics-provenance aux (docs/numerics.md "Divergence
            # debugging"): per-step per-layer digest grids (uint32 bitcast
            # checksums + block norms) out of the SAME fused epoch program,
            # emitted as schema-v12 ``digest`` records. Opt-in only — the
            # default keeps today's programs byte-identical.
            if digests and kernel_path:
                raise ValueError(
                    "digests is unavailable on the kernel paths: params/grads "
                    "never leave the Pallas kernel's VMEM, so the per-layer "
                    "digest aux cannot be threaded out"
                )
            self._digests = bool(digests)
            if self._digests and self._metrics.enabled:
                # replay provenance for the bisect CLI (observability/
                # divergence.py --bisect): everything needed to reconstruct a
                # numerically identical session and re-arm its injections —
                # ``die`` faults are stripped at replay time, step faults
                # (nan/flip) must fire again or the divergence won't reproduce
                self._metrics.event(
                    "digest_config",
                    sizes=list(sizes), model=model, dp=dp, pp=pp, tp=self.tp,
                    schedule=schedule, global_batch_size=global_batch_size,
                    mubatches=mubatches, lr=lr, precision=precision,
                    optimizer=optimizer, momentum=momentum,
                    virtual_stages=virtual_stages, zero1=zero1,
                    zero=self._zero,
                    backward_split=backward_split, recompute=recompute,
                    scan_unroll=scan_unroll,
                    tick_unroll=tick_unroll, weight_decay=weight_decay,
                    clip_norm=clip_norm, fuse_mubatches=fuse_mubatches,
                    data_dir=None if data_dir is None else str(data_dir),
                    faults=",".join(repr(f) for f in self._faults.faults),
                )
            self._step_aux = bool(record_steps) and not kernel_path
            self.flight = FlightRecorder() if self._step_aux else None
            if self.flight is not None:
                # the metrics cursor: resumed step records continue the global
                # numbering instead of restarting at 0
                self.flight.total_steps = self.global_step
            self._epoch_compiled = False  # compile-span already recorded?
            self._epoch_dispatched = False  # first train_epoch includes compile
            self._registered_shape = None  # Y of the program scopes.py knows
            self._cost_recorded = False  # cost_model event already emitted?
            self._cost_xla_recorded = False  # ... with the XLA cross-check leg?

            if self._sequential:
                with self._metrics.span("session/weights"):
                    with self._metrics.span("device_put"):
                        self._params = jax.tree.map(jnp.asarray, host_params)
                    # the host's copy goes here, in the phase that made it: at
                    # 0.9 to 1.3B parameters its release takes a third to half
                    # a second, which would otherwise fall after the root span
                    del host_params
                    if host_opt_state is not None and not is_stateless(opt):
                        self._opt_state = join_state(
                            opt,
                            {
                                k: jax.tree.map(jnp.asarray, v)
                                for k, v in host_opt_state["parts"].items()
                            },
                            {
                                k: jnp.asarray(v, jnp.float32)
                                for k, v in host_opt_state["scalars"].items()
                            },
                        )
                    else:
                        self._opt_state = opt.init(self._params)
                with self._metrics.span("session/program"):
                    self._epoch_fn = trainer.make_train_epoch(
                        self.spec, opt, precision=self.precision,
                        fuse_mubatches=fuse_mubatches, unroll=scan_unroll,
                        clip_norm=clip_norm, megakernel=megakernel,
                        epoch_kernel=epoch_kernel or run_kernel,
                        with_grad_norm=self._epoch_aux,
                        with_step_stats=self._step_aux,
                        with_digests=self._digests,
                        x_layout=self._data_layout,
                    )
                    self._predict = (
                        None if self._token
                        else trainer.make_predict(self.spec, precision=self.precision)
                    )
                    self._run_kwargs = dict(
                        precision=self.precision, fuse_mubatches=fuse_mubatches,
                        unroll=scan_unroll, clip_norm=clip_norm, megakernel=megakernel,
                        epoch_kernel=epoch_kernel or run_kernel,
                        x_layout=self._data_layout,
                    )
                with self._metrics.span("session/data"):
                    # one device program either way, the set in and the set out:
                    # the peak of two sets is this transient (PERF.md §4)
                    if self._data_layout == "feature_major":
                        self._Xe = trainer.feature_major(self._X)
                    else:
                        self._Xe = self._X.reshape(nb, self.M, self.B // self.M, -1)
                    self._Ye = self._Y.reshape(nb, self.M, self.B // self.M, -1)
                    # the microbatched views are the only users
                    self._X = self._Y = None
            else:
                with self._metrics.span("session/lower"):
                    self.mesh, self._mesh_layout = make_mesh_with_layout(
                        dp, pp, devices, tp
                    )
                    if self._metrics.enabled:
                        # placement provenance (topology-aware vs order-preserving):
                        # a bench record measured on one placement must say so —
                        # the two differ materially on a real slice
                        self._metrics.event(
                            "mesh_layout",
                            dp=dp, pp=pp, tp=self.tp, layout=self._mesh_layout,
                            n_devices=dp * pp * self.tp,
                        )
                    prog = lower_schedule(
                        S.SCHEDULES[schedule], mubatches, pp, virtual=self.V,
                        backward_split=self._backward_split,
                        recompute=self._recompute,
                    )
                    if self._metrics.enabled or self._audit_strict:
                        # program-level static analysis at lowering time, BEFORE
                        # anything compiles or dispatches: send/recv match, MPMD
                        # deadlock-freedom, stash lifetimes (analysis/;
                        # docs/static-analysis.md) — the machine-checked form of
                        # the invariants the lowering simulator constructs by
                        # simulation (the simulator is the spec, this is the proof)
                        self._record_static_analysis(prog, "epoch_program")
                    if self._metrics.enabled:
                        # per-tick program stats, recorded once at lowering time:
                        # the executor's runtime tick behaviour is fully determined
                        # by these static tables (ticks, sends, occupancy, bubble)
                        stats = program_stats(
                            prog, spec=self.spec,
                            mubatch_size=local_batch // mubatches, tp=self.tp,
                        )
                        if self._recompute:
                            # the stashed twin's footprint, lowered alongside (pure
                            # Python, no compile): the report CLI's Memory section
                            # renders the two peaks side by side from ONE stream —
                            # the saving is an artifact of both real tick tables,
                            # not a formula
                            twin = program_stats(
                                lower_schedule(
                                    S.SCHEDULES[schedule], mubatches, pp,
                                    virtual=self.V,
                                    backward_split=self._backward_split,
                                    recompute=False,
                                ),
                                spec=self.spec,
                                mubatch_size=local_batch // mubatches, tp=self.tp,
                            )
                            stats["stash_bytes_peak_stashed_twin"] = twin[
                                "stash_bytes_peak"
                            ]
                            stats["stash_slots_stashed_twin"] = twin["stash_slots"]
                        self._metrics.event(
                            "pipeline_program",
                            schedule=schedule, dp=dp, pp=pp, tp=self.tp,
                            virtual=self.V, model=self.model_name, **stats,
                        )
                        self._metrics.gauge(
                            "pipeline.bubble_fraction", stats["bubble_fraction"]
                        )
                with self._metrics.span("session/weights"):
                    with self._metrics.span("device_put"):
                        stacked_np, flags_np = E.stack_params(
                            host_params, self.spec, order=self._order, tp=self.tp
                        )
                        if self._zero == 3:
                            # ZeRO-3 params at rest: one (pp*tp, dp*csz3)
                            # block-cyclic array, each device holding only its own
                            # 1/dp shard — the {W,b} stacked layout never lands on
                            # device (predict/save rebuild it on demand)
                            self._stacked = {
                                "P": jax.device_put(
                                    E.zero_block_flatten_rows(
                                        stacked_np, self.spec, self.mesh
                                    ),
                                    E.zero1_part_sharding(self.mesh),
                                )
                            }
                            self._flags = E.put_pp(flags_np, self.mesh)
                        else:
                            self._stacked, self._flags = E.put_stacked(
                                stacked_np, flags_np, self.mesh
                            )
                        del host_params, stacked_np, flags_np  # as above
                    if self._zero >= 2:
                        self._opt_state = E.zero_block_state_from_logical(
                            host_opt_state, opt, self.spec, self.mesh, order=self._order
                        )
                    elif self._zero1:
                        self._opt_state = E.zero1_state_from_logical(
                            host_opt_state, opt, self.spec, self.mesh, order=self._order
                        )
                    elif host_opt_state is not None and not is_stateless(opt):
                        # stack + place each state part exactly like the params it
                        # mirrors (zero padding is consistent: padded grads are
                        # exactly zero, so padded state stays zero); scalars replicate
                        rep = NamedSharding(self.mesh, PartitionSpec())
                        self._opt_state = join_state(
                            opt,
                            {
                                k: E.put_stacked_tree(
                                    E.stack_params(
                                        v, self.spec, order=self._order, tp=self.tp
                                    )[0],
                                    self.mesh,
                                )
                                for k, v in host_opt_state["parts"].items()
                            },
                            {
                                k: jax.device_put(np.float32(v), rep)
                                for k, v in host_opt_state["scalars"].items()
                            },
                        )
                    else:
                        self._opt_state = opt.init(self._stacked)
                with self._metrics.span("session/program"):
                    if self.runtime == "mpmd":
                        from shallowspeed_tpu.observability.tracing import Tracer
                        from shallowspeed_tpu.parallel import mpmd

                        # the MPMD runner's constructor IS the admission gate:
                        # analyze_program must prove the tick tables deadlock-free
                        # before any stage program can be built or dispatched
                        self._mpmd = mpmd.MpmdTrainRunner(
                            self.mesh, self.spec, prog, local_batch // mubatches,
                            opt, precision=self.precision,
                            tracer=Tracer(self._metrics, process="m"),
                        )

                        def _mpmd_epoch(stacked, flags, opt_state, X, Y):
                            return self._mpmd.run(
                                stacked, flags, opt_state, X, Y,
                                trace_id=f"mpmd-{self.global_step}",
                            )

                        self._epoch_fn = _mpmd_epoch
                    else:
                        self._epoch_fn = E.make_pipeline_epoch(
                            self.mesh, self.spec, prog, local_batch // mubatches, opt,
                            precision=self.precision, zero=self._zero,
                            unroll=scan_unroll, tick_unroll=tick_unroll,
                            clip_norm=clip_norm, kernel_backend=kernel_backend,
                            with_grad_norm=self._epoch_aux,
                            with_step_stats=self._step_aux,
                            with_digests=self._digests,
                        )
                    self._prog = prog
                    self._mubatch_local = local_batch // mubatches
                    self._run_kwargs = dict(
                        precision=self.precision, unroll=scan_unroll,
                        tick_unroll=tick_unroll, zero=self._zero,
                        clip_norm=clip_norm, kernel_backend=kernel_backend,
                    )

            with self._metrics.span("session/program"):
                # analytical cost model + MFU accounting (observability/costmodel):
                # the model-FLOP numerator is known at construction; the XLA
                # cost_analysis cross-check attaches at jit time
                # (_ensure_epoch_compiled / warm_run). On mesh layouts the padded
                # hardware FLOPs come from the lowered tick tables
                # (lowering.program_flops), so the padding tax is recorded per
                # layout, not guessed.
                if self._sequential:
                    device = jax.devices()[0]
                    padded = None
                    self._mesh_layout = None
                else:
                    device = self.mesh.devices.flat[0]
                    padded = (
                        program_flops(
                            self._prog, self.spec, self._mubatch_local, tp=self.tp
                        )
                        * dp
                    )
                self._cost_model = costmodel.CostModel(
                    sizes=sizes if self._token else self.spec.sizes,
                    flops_per_sample=(
                        # attention over the pairs this set's packing admits
                        costmodel.token_train_flops_per_sample(
                            self.spec,
                            self._token_counts["pairs"] / self._token_counts["tokens"],
                        )
                        if self._token else None
                    ),
                    global_batch=self.B,
                    batches_per_epoch=self.batches_per_epoch,
                    n_devices=1 if self._sequential else dp * pp * self.tp,
                    platform=device.platform,
                    device_kind=device.device_kind,
                    precision=self._precision_name,
                    padded_flops_per_batch=padded,
                )
                # the layout's analytical comms contract (required/forbidden
                # collective kinds + bytes/step per mesh axis, derived from the
                # lowered tick tables) — what the compiled program's collective
                # census is audited against at jit time.
                self._expected_comms = program_audit.expected_comms(
                    self.spec,
                    dp,
                    pp,
                    prog=None if self._sequential else self._prog,
                    zero=self._zero,
                    mubatch_size=None if self._sequential else self._mubatch_local,
                    platform=device.platform,
                    device_kind=device.device_kind,
                    precision=self._precision_name,
                    tp=self.tp,
                    # only params-mirroring parts occupy per-layer bytes (Adam's
                    # "t" is a scalar) — the forecast prices what actually shards
                    opt_state_parts=sum(
                        1 for v in opt.state_layout().values() if v == "params"
                    ),
                )
            if self._recovery is not None and self._metrics.enabled:
                # one schema-v4 recovery record per resume decision: what was
                # restored (or that nothing was), where training restarts, and
                # every corrupt snapshot skipped on the way
                self._metrics.recovery(
                    self._recovery["verdict"],
                    resumed_from=self._recovery["resumed_from"],
                    epoch=self.epoch,
                    step_in_epoch=self.step_in_epoch,
                    global_step=self.global_step,
                    skipped=self._recovery["skipped"],
                )

    # -- training -----------------------------------------------------------

    def _mlp_only(self, what):
        """Refuse what is written for a stack of Linears on rows of features
        (inference slots, the validation split, the checkpoint format, the
        fused multi-epoch run) when the session trains a token model."""
        if self._token:
            raise ValueError(
                f"{what} is not available for token model "
                f"{self.model_name!r}: it is written for stacks of {{W, b}} "
                f"Linears on rows of features (ROADMAP R0a)"
            )

    def _epoch_args(self):
        """The layout's runtime argument tuple for one epoch."""
        if self._sequential:
            return (self._params, self._opt_state, self._Xe, self._Ye)
        return (self._stacked, self._flags, self._opt_state, self._X, self._Y)

    def _record_static_analysis(self, prog, program):
        """The program-level static passes (shallowspeed_tpu/analysis)
        over one lowered TickProgram: send/recv match & MPMD
        deadlock-freedom over the tables, stash-lifetime discipline.
        Run at lowering time — a violated contract raises
        ``ProgramAnalysisError`` BEFORE the program can compile or
        dispatch, with the evidence recorded first (schema-v9
        ``static_analysis`` record, findings count + the finding text),
        exactly the census's record-then-refuse shape."""
        from shallowspeed_tpu.analysis import (
            ProgramAnalysisError,
            analyze_program,
        )

        try:
            verdict = analyze_program(prog, program=program)
        except ProgramAnalysisError as e:
            if self._metrics.enabled:
                self._metrics.static_analysis(
                    program,
                    passes=["send_recv", "deadlock", "stash"],
                    findings=1,
                    finding=str(e),
                )
                self._metrics.flush()  # the refusal evidence hits disk first
            raise
        if self._metrics.enabled:
            self._metrics.static_analysis(
                program,
                **{k: v for k, v in verdict.items() if k != "program"},
            )
        return verdict

    def _ensure_epoch_compiled(self):
        """With metrics enabled, compile the epoch program once inside a
        ``jit_compile`` span (trace + lowering + XLA compile, timed as a
        first-class record) before the first dispatch. Steady-state dispatch
        stays on the jit wrapper's C++ fast path — on this backend a compiled
        executable's Python dispatch costs ~2-3% per epoch, so the compiled
        object is only the timing probe, not the call path. The probe does
        NOT warm the jit wrapper's own call cache (verified on jax 0.4.x:
        the first jit call still compiles), so the first dispatch pays a
        second compile — a deliberate one-time cost for an isolated
        compile-time record, and the reason the first ``epoch`` event is
        stamped ``includes_compile`` (its wall/samples_per_sec are NOT
        steady-state; consumers must not read them as such).

        ``audit=True`` also forces this compile (even metrics-less): the
        program audit needs the compiled object to verify the layout's
        collective contract before the first dispatch.

        On the MPMD runtime the "epoch program" is the per-stage program
        set: the warm pass compiles every planned stage program,
        censuses each against its per-stage contract
        (``mpmd.expected_stage_comms``) and proves it donation-free —
        then swaps the dispatch path onto the compiled executables."""
        if self.runtime == "mpmd":
            if self._epoch_compiled or not (
                self._metrics.enabled or self._audit_strict
            ):
                return
            self._mpmd.warm(
                self._stacked, self._flags, self._opt_state,
                self._mpmd_resolve,
            )
            self._epoch_compiled = True
            self._record_cost_model()
            return
        if self._epoch_compiled or not (self._metrics.enabled or self._audit_strict):
            return
        with self._metrics.span("jit_compile"):
            compiled = self._epoch_fn.lower(*self._epoch_args()).compile()
        self._metrics.counter("jit_compiles")
        # cost-model cross-check at jit time: pull the compiled epoch
        # program's XLA-reported FLOPs/bytes next to the analytical count
        self._cost_model.attach_compiled(compiled)
        # audit BEFORE latching the compiled flag: a strict mismatch must
        # leave the session un-warmed, so a caller that catches the error
        # and retries is re-audited (and re-refused), never silently
        # trained on the mislowered program
        self._record_audit(compiled, "epoch_program")
        self._epoch_compiled = True
        self._record_cost_model()

    def _mpmd_resolve(self, label, role, jit_fn, args, expected):
        """The MPMD warm pass's per-stage-program hook: compile each stage
        program, census it against its per-stage contract, and prove it
        donation-free (``verify_dispatch_safety`` — every stage program IS
        a dispatch path). Returns the executable the runner should
        dispatch, or None to keep the plain jit wrapper (nothing to
        verify)."""
        dedup = ("mpmd", label)
        if not (self._metrics.enabled or self._audit_strict):
            return None
        if dedup in self._audit_done:
            return None
        with self._metrics.span("jit_compile"):
            compiled = jit_fn.lower(*args).compile()
        self._metrics.counter("jit_compiles")
        self._record_audit(
            compiled, "mpmd_stage_program", dedup=dedup, expected=expected
        )
        # every stage program is a dispatch path: donation would be a
        # use-after-free against the next microbatch's read — proven
        # absent from the compiled HLO, unlatched like the census
        program_audit.verify_dispatch_safety(compiled, context=label)
        return compiled

    def _refuse_pending_faults(self, entry):
        """Injections fire at step boundaries, which only ``train_steps``
        has — a whole-epoch or fused-run dispatch would sail straight past
        them, and a recovery harness that expected the kill would conclude
        the crash/resume path works when nothing was injected. Refuse
        loudly instead of skipping silently."""
        if self._faults and self._faults.pending:
            raise ValueError(
                f"{entry}() cannot honor the pending fault injection(s) "
                f"{self._faults.pending!r}: injections land on step "
                "boundaries — drive this run with train_steps()"
            )

    def _ensure_chunk_audited(self, k0, k1):
        """Chunk-shaped sibling of ``_ensure_epoch_compiled``: a
        ``train_steps`` dispatch over batches [k0, k1) is a DISTINCT XLA
        program whenever the slice is shorter than the epoch, so the audit
        contract ("a mislowered layout never trains a step") must census
        that program, not the full-epoch one. Per distinct chunk length the
        sliced program is AOT-compiled once inside a ``jit_compile`` span
        and audited (the scan body — and therefore the collective census —
        is length-independent; only the trip count changes). Full-epoch
        slices take the normal epoch path; chunked-only sessions never pay
        the full-epoch compile their dispatches would not use."""
        if k1 - k0 == self.batches_per_epoch or self.runtime == "mpmd":
            # MPMD dispatches the same per-stage programs for any chunk
            # length (the host loop owns the batch axis), so there is no
            # distinct sliced program to audit
            self._ensure_epoch_compiled()
            return
        if not (self._metrics.enabled or self._audit_strict):
            return
        dedup = ("chunk", k1 - k0)
        if dedup in self._audit_done:
            return
        with self._metrics.span("jit_compile"):
            compiled = self._epoch_fn.lower(
                *self._sliced_epoch_args(k0, k1)
            ).compile()
        self._metrics.counter("jit_compiles")
        # audited (and marked done) only on a pass — same never-latch-a-
        # failure contract as the epoch path. No cost-model attach: the
        # cross-check is defined against the epoch program's shapes.
        self._record_audit(compiled, "chunk_program", dedup=dedup)
        self._record_cost_model()

    def _record_audit(self, compiled, program, dedup=None, expected=None):
        """Jit-time XLA program audit (observability/program_audit.py):
        census the compiled program's collectives, pull its memory
        analysis, and emit one schema-v3 ``xla_audit`` record per DISTINCT
        compiled program (``dedup`` names the compile variant; defaults to
        the program label). ``expected`` overrides the session's training
        contract — the inference programs audit against their own
        forward-only contract. Under ``audit=True`` a census that violates
        the layout's analytical comms contract raises AuditMismatchError —
        BEFORE the first dispatch, so a mislowered layout never trains a
        step (the program is marked audited only on a pass: a
        caught-and-retried failure re-audits and re-raises; its evidence
        records duplicate, which is the honest trade)."""
        dedup = dedup if dedup is not None else program
        if dedup in self._audit_done:
            return
        rec = program_audit.audit_compiled(
            compiled,
            expected=expected if expected is not None else self._expected_comms,
            platform=self._cost_model.platform,
            device_kind=self._cost_model.device_kind,
            n_devices=self._cost_model.n_devices,
        )
        if self._metrics.enabled:
            self._metrics.audit(program, **rec)
            self._metrics.flush()  # the mismatch evidence must hit disk first
        if self._audit_strict and rec.get("census_ok") is False:
            raise program_audit.AuditMismatchError(
                f"{program}: compiled collective census disagrees with the "
                f"layout contract (dp={self.dp}, pp={self.pp}, "
                f"zero={self._zero}): " + "; ".join(rec["mismatches"])
            )
        self._audit_done.add(dedup)

    def _record_cost_model(self):
        """Emit the cost_model event. Emitted once per
        session — except that a record written BEFORE the XLA cross-check
        attached (a warm_run-first session) is re-emitted once the compiled
        epoch program's cost_analysis exists, so the flops_ratio signal is
        never silently lost (consumers keep the last event)."""
        if not self._metrics.enabled:
            return
        has_xla = self._cost_model.xla_flops_per_epoch is not None
        if self._cost_recorded and (self._cost_xla_recorded or not has_xla):
            return
        self._metrics.event("cost_model", **self._cost_model.as_record())
        self._cost_recorded = True
        self._cost_xla_recorded = has_xla

    def _record_utilization(self, samples_per_sec):
        """Per-dispatch MFU accounting: achieved model-FLOP/s and MFU
        gauges (docs/observability.md). Returns the MFU (None when no peak
        is known for this platform)."""
        self._metrics.gauge(
            "achieved_flops_per_sec",
            self._cost_model.achieved_flops_per_sec(samples_per_sec),
        )
        mfu = self._cost_model.mfu(samples_per_sec)
        if mfu is not None:
            self._metrics.gauge("mfu", mfu)
        return mfu

    def _record_flight(self, epoch_index, aux):
        """Host side of the step-level flight recorder: read the fused
        per-step aux back (one readback per epoch, after the dispatch),
        ring-buffer it, stream schema-v2 ``step`` records, and run the
        numerics health checks (which may raise HealthError under
        policy='halt' — after this epoch's update was applied)."""
        losses = np.asarray(aux["step_loss"], np.float64)
        gns = np.asarray(aux["step_grad_norm"], np.float64)
        pns = np.asarray(aux["step_param_norm"], np.float64)
        first = self.flight.total_steps  # the ring owns the global numbering
        samples = self.flight.record_epoch(
            epoch_index, losses, gns, pns, first_step=first
        )
        if self._metrics.enabled:
            for s in samples:
                self._metrics.step("train", **s)
        if self._health is not None:
            findings = self._health.check_epoch(
                epoch_index, losses, gns, pns, first_step=first
            )
            self._note_health_findings(findings)
            self._health.dispatch(findings, self._metrics)

    def _record_digests(self, epoch_index, first_step, dig):
        """Host side of the numerics-provenance stream: read the fused
        per-step digest aux back (same single post-dispatch readback as
        the flight recorder) and emit one schema-v12 ``digest`` record per
        optimizer step, with the per-GLOBAL-layer checksum/norm lists in
        logical layer order on every layout (the mesh aux's (S, L) grids
        are indexed through the stacked-row permutation)."""
        host = {k: np.asarray(v) for k, v in dig.items()}
        rows = self._digest_layer_index()
        mesh = host["crc_w"].ndim == 3  # (nb, S, L) vs sequential (nb, L)
        nb = host["crc_w"].shape[0]
        for i in range(nb):
            fields = {}
            for k, a in host.items():
                col = a[i]
                vals = [col[r, l] for r, l in rows] if mesh else list(col)
                cast = int if k.startswith("crc") else float
                fields[k] = [cast(v) for v in vals]
            self._metrics.digest(
                "train",
                step=first_step + i,
                epoch=epoch_index,
                layers=len(rows),
                **fields,
            )

    def _digest_layer_index(self):
        """Per-global-layer (row, col) addresses into the digest aux's
        (S, L) grids, in logical layer order: stage s's layer l lives at
        row ``row_of[s]`` (the stacked-row permutation — identity unless
        virtual stages interleave) and column l. Sequential aux is already
        (L_total,) in logical order; the addresses still enumerate it."""
        idx = getattr(self, "_digest_rows", None)
        if idx is None:
            order = self._order or range(self.spec.n_stages)
            row_of = {s: r for r, s in enumerate(order)}
            idx = self._digest_rows = [
                (row_of[s], l)
                for s in range(self.spec.n_stages)
                for l in range(self.spec.stages[s].n_linears)
            ]
        return idx

    def _note_health_findings(self, findings):
        """Feed health findings to the alert rules BEFORE the policy
        dispatch: under ``halt`` the dispatch raises, and the
        ``training_health`` alert transition must already be in the
        stream when it does — the fleet surface watching many runs
        learns of the blow-up from the alert, not the stack trace."""
        if not findings:
            return
        t = time.perf_counter()
        for f in findings:
            self._telemetry.note_health(t, f["check"])

    @property
    def global_step(self):
        """Run-lifetime optimizer-step count — the unit step checkpoints,
        fault injections and flight-record numbering share."""
        return self.epoch * self.batches_per_epoch + self.step_in_epoch

    @property
    def faults_active(self):
        """True when a fault-injection plan is loaded (arg or env) — the
        driver must then use the step loop so injections land on their
        exact steps."""
        return bool(self._faults)

    def _sliced_epoch_args(self, k0, k1):
        """The layout's runtime argument tuple for batches [k0, k1) of the
        current epoch (the full-epoch tuple when k0=0, k1=nb)."""
        if self._sequential:
            return (self._params, self._opt_state, self._Xe[k0:k1], self._Ye[k0:k1])
        return (
            self._stacked, self._flags, self._opt_state,
            self._X[k0:k1], self._Y[k0:k1],
        )

    def _run_epoch_program(self, args):
        """Dispatch the epoch (or chunk) program on ``args``, keep the new
        state, read the mean loss back: ``(outputs, loss)``. The two halves
        are the spans ``epoch/dispatch`` and ``epoch/readback``: on the
        profiler's clock (a capture shows them beside the device's
        operations) and in the span log. What the host does between one
        epoch's readback and the next one's dispatch needs no span of its
        own: it is the stretch between those two. The first dispatch of
        each batch-axis length (a chunk and a whole epoch are two programs
        under one name) registers the program with ``observability.scopes``
        (the jitted callable and the arguments' shapes, no array), so that a
        trace reader can ask for the op index of the program that ran last;
        the MPMD runtime's epoch is a host loop over stage programs, not one
        program, and is not registered."""
        if args[-1].shape != self._registered_shape and self.runtime != "mpmd":
            self._program_name = scopes.register_program(self._epoch_fn, args)
            self._registered_shape = args[-1].shape
            if self._token:
                scopes.record_counts(self._program_name, self._token_counts)
        with self._metrics.span("epoch/dispatch"):
            out = self._epoch_fn(*args)
        if self._sequential:
            self._params, self._opt_state = out[0], out[1]
        else:
            self._stacked, self._opt_state = out[0], out[1]
        with self._metrics.span("epoch/readback"):
            loss = float(out[2])  # forces device completion
            if self._token and self.spec.routed_layers:
                # the program's last output, complete with the loss: the
                # pairs this epoch routed to the experts held, and the most
                # one of them took in a step
                held, most = (int(n) for n in np.asarray(out[-1]))
                self._token_counts.update(moe_rows_held=held, moe_load_max=most)
                scopes.record_counts(self._program_name, self._token_counts)
        return out, loss

    def train_steps(self, n):
        """Train up to ``n`` optimizer steps of the CURRENT epoch (clipped at
        the epoch boundary) — the preemption-safe unit: the epoch-scan
        program runs over a SLICE of the batch axis, so chunked dispatch
        applies the exact same per-batch updates in the exact same order as
        one whole-epoch dispatch (bitwise-identical weights; tested), while
        the host regains control between chunks to write step checkpoints.

        Fault-injection boundaries: when the active plan has a fault inside
        this chunk, the chunk is truncated so the fault's step starts the
        next call — ``die`` then kills the process (exception or SIGKILL)
        BEFORE that step trains, ``nan`` poisons the params so that step's
        gradients blow up.

        Returns ``(steps_trained, epoch_mean_loss_or_None)`` — the mean loss
        is reported once, on the call that completes the epoch (same
        definition as ``train_epoch``; the per-chunk means are recombined
        sample-weighted). After a mid-epoch resume the mean covers only the
        steps THIS process trained — the epoch's head belongs to the dead
        process's stream — and the epoch record carries ``steps_counted``
        to say so. Under health policy 'halt' a finding raises
        HealthError AFTER flushing a snapshot (when a checkpoint_dir is
        configured), so the blow-up is resumable.
        """
        nb = self.batches_per_epoch
        if n < 1:
            raise ValueError("n must be >= 1")
        k0 = self.step_in_epoch
        k1 = min(k0 + n, nb)
        g0 = self.epoch * nb + k0
        if self._faults:
            # EVERY un-fired fault scheduled at g0 fires before the dispatch
            # (same-step compositions like "nan@step=3,die@step=3" fire in
            # spec order — a single-shot check would leave the second one
            # pending forever, since later windows all start past g0); then
            # the next pending fault inside this chunk still truncates it,
            # or the chunk would dispatch straight past its step
            fault = self._faults.first_in(g0, g0 + (k1 - k0))
            while fault is not None and fault.step == g0:
                if fault.kind == "die":
                    self._faults.fire_die(fault)  # SIGKILL never returns
                elif fault.kind == "nan":
                    fault.fired = True
                    self.poison_weights()
                elif fault.kind == "flip":
                    fault.fired = True
                    self.flip_weights()
                fault = self._faults.first_in(g0, g0 + (k1 - k0))
            if fault is not None:
                k1 = k0 + (fault.step - g0)  # fault lands on a boundary
        epoch_index = self.epoch
        first_dispatch = self._metrics.enabled and not self._epoch_dispatched
        self._ensure_chunk_audited(k0, k1)
        t0 = time.perf_counter()
        with self._metrics.span("train_steps"):
            out, loss = self._run_epoch_program(self._sliced_epoch_args(k0, k1))
        wall = time.perf_counter() - t0
        aux = (
            out[3]
            if (self._epoch_aux or self._step_aux or self._digests)
            else None
        )
        if self._digests and self._metrics.enabled:
            self._record_digests(epoch_index, g0, aux["digests"])
        self._epoch_dispatched = True
        steps = k1 - k0
        self.step_in_epoch = k1
        self._epoch_loss_sum += loss * steps
        self._epoch_wall += wall
        self._epoch_steps_counted += steps
        self._epoch_first_dispatch = self._epoch_first_dispatch or first_dispatch
        epoch_loss = None
        if k1 == nb:
            # loss/throughput over the steps THIS process dispatched: after
            # a mid-epoch resume that is the epoch's tail only (the head's
            # evidence lives in the dead process's record stream), so the
            # record says so instead of diluting the mean by the full nb
            # and inflating samples/s with samples it never trained
            counted = self._epoch_steps_counted
            epoch_loss = self._epoch_loss_sum / counted
            if self._metrics.enabled:
                samples = counted * self.B
                ew = self._epoch_wall
                sps = samples / ew if ew > 0 else 0.0
                record = dict(
                    epoch=epoch_index,
                    loss=epoch_loss,
                    samples_per_sec=sps,
                    wall_s=ew,
                    chunked=True,  # wall spans >= 1 dispatches + host gaps
                )
                if counted < nb:
                    record["steps_counted"] = counted  # mid-epoch resume
                if self._epoch_first_dispatch:
                    record["includes_compile"] = True
                mfu = self._record_utilization(sps)
                if mfu is not None:
                    record["mfu"] = mfu
                self._metrics.event("epoch", **record)
                self._telemetry.note_step(
                    time.perf_counter(), loss=epoch_loss, step_s=ew,
                    throughput=sps, mfu=mfu,
                )
            self.epoch += 1
            self.step_in_epoch = 0
            self._epoch_loss_sum = 0.0
            self._epoch_wall = 0.0
            self._epoch_steps_counted = 0
            self._epoch_first_dispatch = False
        # flight + health LAST: session state is consistent if 'halt' raises
        try:
            if self._step_aux:
                self._record_flight(epoch_index, aux)
            elif self._health is not None:
                findings = self._health.check_epoch(epoch_index, [loss])
                self._note_health_findings(findings)
                self._health.dispatch(findings, self._metrics)
        except HealthError:
            self._flush_halt_checkpoint()
            raise
        return steps, epoch_loss

    def save_step_checkpoint(self, reason="step", rotate=True, async_=None):
        """Write the resumable snapshot at the current ``global_step`` into
        the session's checkpoint directory (``step-<global_step>.npz``:
        params + optimizer state + step cursor + content checksum), rotate
        retention down to ``checkpoint_keep``, and emit a schema-v4
        ``checkpoint`` record. Returns the written path.

        ``async_`` (default: the session's ``async_checkpoint`` setting):
        keep only stage 1 — the device->host snapshot — on the step path
        and hand verification (sha256 + finiteness), the
        write-fsync-rename sequence and rotation to the background writer
        (``checkpoint.AsyncCheckpointWriter``), behind a bounded
        ``checkpoint_queue``-deep in-flight window whose ``submit``
        BLOCKS when full (backpressure — a snapshot is never silently
        dropped, which would widen the replay window past the configured
        cadence). The stage order — and therefore every crash window —
        is byte-identical to the synchronous path (shared
        ``run_save_stages``); the ``checkpoint`` record is emitted from
        the writer on completion with ``async: true``, the queue depth
        sampled at enqueue, and the off-path ``verify_s``/``write_s``
        costs, while ``wall_s`` is the ON-PATH cost only. A writer-side
        failure re-raises on this thread at the next save or
        ``drain_checkpoints()``.

        Rotation is skipped when ``rotate=False`` (the halt flush opts out)
        AND whenever the snapshot just written is non-finite: once a run
        blows up, every grid save carries ``all_finite: false``, and
        unconditional rotation would delete the last healthy snapshot
        within ``keep`` intervals — making ``resume='auto'`` (which skips
        non-finite snapshots by design) permanently unrecoverable. Instead
        the non-finite evidence accumulates unrotated until finiteness
        returns; recoverability beats disk tidiness on a blown-up run.
        (``rotate_step_checkpoints`` itself also ranks fully-verifying
        snapshots above non-finite/corrupt ones, so when rotation does
        fire it reclaims the stale unusable pile, never a healthy
        snapshot.)"""
        self._mlp_only("save_step_checkpoint()")
        if self._ckpt_dir is None:
            raise ValueError(
                "no checkpoint_dir configured on this session"
            )
        if async_ is None:
            async_ = self._async_ckpt_default
        gs = self.global_step
        epoch, sie = self.epoch, self.step_in_epoch
        path = step_checkpoint_path(self._ckpt_dir, gs)
        save_seq = self._save_seq
        self._save_seq += 1
        rotate_dir = self._ckpt_dir if rotate else None
        t0 = time.perf_counter()
        if not async_:
            arrays, meta = build_snapshot(
                self.params(),
                self.spec,
                epoch,
                extra={"optimizer": self._opt_config},
                opt_state=self.opt_state_logical(),
                step_in_epoch=sie,
                global_step=gs,
            )

        def completion(result, on_path_wall, queue_depth=None):
            # runs inline (sync) or on the writer thread (async): update
            # the trusted set for rotation ranking, then emit the record.
            # "trusted" (not "all_finite"): a corrupt-injected snapshot is
            # finite in its metadata but can never verify — trusting it
            # would let rotation rank garbage above real fallbacks
            if result.get("trusted", result["all_finite"]):
                self._trusted_snapshots.add(str(path))
            if self._metrics.enabled:
                fields = dict(
                    path=str(path),
                    epoch=epoch,
                    step_in_epoch=sie,
                    global_step=gs,
                    bytes=result["bytes"],
                    wall_s=on_path_wall,
                    verify_s=result["verify_s"],
                    write_s=result["write_s"],
                )
                if queue_depth is not None:
                    fields["async"] = True
                    fields["queue_depth"] = queue_depth
                    fields["queued_s"] = result["queued_s"]
                    # the deferred logical-unstacking wall (off-path):
                    # what the step path stopped paying
                    fields["unstack_s"] = result.get("unstack_s", 0.0)
                else:
                    fields["async"] = False
                self._metrics.checkpoint(reason, **fields)

        # tuple(): an immutable point-in-time copy (a C-level, GIL-atomic
        # snapshot of the set). The writer thread's completion callbacks
        # keep adding to the live set while rotation — on EITHER thread —
        # iterates its trusted collection with syscalls in between; handing
        # rotation the live set would be a set-changed-during-iteration
        # crash waiting for a mixed sync/async save to land it.
        trusted_now = tuple(self._trusted_snapshots)
        if not async_:
            result = run_save_stages(
                path, arrays, meta,
                faults=self._faults, save_seq=save_seq,
                rotate_dir=rotate_dir, rotate_keep=self._ckpt_keep,
                trusted=trusted_now,
            )
            wall = time.perf_counter() - t0
            completion(result, wall)
            if self._metrics.enabled:
                self._telemetry.note_checkpoint(time.perf_counter(), wall)
            return path
        # async: the step path keeps ONLY the device->host readback (the
        # consistency point) — the logical unstacking (params()/
        # opt_state_logical's per-stage reshaping) and build_snapshot's
        # flattening run on the writer thread via the deferred build
        # (ROADMAP item 5 follow-on: it was the dominant on-path cost)
        raw_params, raw_state = self._snapshot_raw()
        spec, opt_cfg = self.spec, dict(self._opt_config)

        def build():
            params, opt_state = self._logical_from_raw(raw_params, raw_state)
            return build_snapshot(
                params, spec, epoch,
                extra={"optimizer": opt_cfg},
                opt_state=opt_state,
                step_in_epoch=sie,
                global_step=gs,
            )

        if self._ckpt_writer is None:
            self._ckpt_writer = AsyncCheckpointWriter(
                max_in_flight=self._ckpt_queue,
                faults=self._faults,
            )
        depth = self._ckpt_writer.queue_depth
        # on-path wall = snapshot + enqueue (the enqueue blocks only when
        # the bounded window is full — that stall IS the backpressure and
        # is charged honestly to the step path). The tiny event handshake
        # lets the writer-thread record carry the wall measured HERE,
        # without racing the submit return.
        wall_box = {}
        measured = threading.Event()

        def job_complete(result):
            measured.wait(timeout=60)
            completion(
                result, wall_box.get("wall", 0.0), queue_depth=depth
            )

        self._ckpt_writer.submit(
            path, None, None, save_seq,
            rotate_dir=rotate_dir, rotate_keep=self._ckpt_keep,
            trusted=trusted_now, on_complete=job_complete, build=build,
        )
        wall_box["wall"] = time.perf_counter() - t0
        measured.set()
        if self._metrics.enabled:
            # the ON-PATH wall only (snapshot + enqueue) — the overhead
            # fraction budgets what the step path pays, and this thread
            # owns the telemetry state (the writer thread must not)
            self._telemetry.note_checkpoint(
                time.perf_counter(), wall_box["wall"]
            )
        return path

    def drain_checkpoints(self):
        """Block until every async snapshot in flight is durable on disk
        (rename + fsync complete); writer-side failures re-raise here.
        No-op when nothing was ever saved asynchronously. ``close()``,
        the halt flush and ``train.py``'s exit all run this, so no exit
        path can leave a snapshot half-owned by a daemon thread."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.drain()

    def close(self):
        """Release the session's background resources: drain + stop the
        async checkpoint writer (re-raising any writer failure) and flush
        the metrics sink. Idempotent; the session remains usable for
        dispatch afterwards (a later async save just restarts a writer)."""
        if self._ckpt_writer is not None:
            writer, self._ckpt_writer = self._ckpt_writer, None
            writer.close()
        # close the trailing partial rollup window before the flush, so
        # the last training records are on disk with everything else
        self._telemetry.flush()
        self._metrics.flush()

    def _flush_halt_checkpoint(self):
        """The health monitor's halt policy flushes a snapshot BEFORE the
        HealthError propagates (when a checkpoint directory is configured):
        a finite finding (grad spike, divergence) is resumable from the
        halt step itself; a non-finite one writes an ``all_finite: false``
        snapshot that resume discovery SKIPS, landing on the last healthy
        step instead. Best-effort — a failing flush never masks the halt.

        Stays SYNCHRONOUS regardless of the session's async-checkpoint
        setting: the process is about to unwind, so the flush must be
        durable before the HealthError leaves this frame — a snapshot
        parked in a daemon writer's queue would die with the process.
        Any async saves already in flight are drained first (best-effort)
        so the halt snapshot can never rename ahead of an older one."""
        if self._ckpt_dir is None:
            return
        try:
            self.drain_checkpoints()
        except Exception as e:  # noqa: BLE001 — never mask the HealthError
            print(f"halt checkpoint drain failed: {e}", file=sys.stderr)
        try:
            self.save_step_checkpoint(reason="halt", rotate=False, async_=False)
            self._metrics.flush()
        except Exception as e:  # noqa: BLE001 — never mask the HealthError
            print(f"halt checkpoint flush failed: {e}", file=sys.stderr)

    def train_epoch(self) -> float:
        """One epoch over the training shard; returns the mean batch training
        loss (same definition on both layouts: global-batch-scaled MSE of each
        batch under its pre-update params, averaged over the epoch).

        With a metrics recorder attached, emits one ``epoch`` event per call
        (epoch index, loss, samples/s, wall seconds — plus the mean pre-clip
        grad norm when clipping) and a ``train_epoch`` span. The first
        recorded epoch carries ``includes_compile: true`` — the jit call
        cache is cold on the first dispatch, so that record's wall clock
        includes compilation and must not be read as steady-state."""
        if self.step_in_epoch != 0:
            raise ValueError(
                f"epoch {self.epoch} is mid-flight at step "
                f"{self.step_in_epoch} (resumed or chunked) — use "
                f"train_steps() to finish it"
            )
        self._refuse_pending_faults("train_epoch")
        first_dispatch = self._metrics.enabled and not self._epoch_dispatched
        self._ensure_epoch_compiled()
        epoch_index = self.epoch
        t0 = time.perf_counter()
        with self._metrics.span("train_epoch"):
            out, loss = self._run_epoch_program(self._epoch_args())
        aux = (
            out[3]
            if (self._epoch_aux or self._step_aux or self._digests)
            else None
        )
        if self._digests and self._metrics.enabled:
            self._record_digests(
                epoch_index, epoch_index * self.batches_per_epoch,
                aux["digests"],
            )
        if self._metrics.enabled:
            wall = time.perf_counter() - t0
            samples = self.batches_per_epoch * self.B
            sps = samples / wall if wall > 0 else 0.0
            record = dict(
                epoch=epoch_index,
                loss=loss,
                samples_per_sec=sps,
                wall_s=wall,
            )
            if self._epoch_aux:
                record["grad_norm"] = float(aux["grad_norm"])
            if first_dispatch:
                # the jit call cache was cold: this wall includes compile
                record["includes_compile"] = True
            mfu = self._record_utilization(sps)
            if mfu is not None:
                # stamped on the record too, so per-epoch MFU survives the
                # gauge's last-value-wins semantics (the first record's MFU
                # inherits its includes_compile caveat)
                record["mfu"] = mfu
            self._metrics.event("epoch", **record)
            if not first_dispatch:  # steady-state only, per the histogram's use
                self._metrics.observe("epoch.seconds", wall)
            self._telemetry.note_step(
                time.perf_counter(), loss=loss, step_s=wall,
                throughput=sps, mfu=mfu,
            )
        self._epoch_dispatched = True
        self.epoch += 1
        # flight recording + health checks LAST: session state is already
        # consistent when a 'halt' policy raises out of here (and the halt
        # path flushes a snapshot first, so the blow-up is resumable)
        try:
            if self._step_aux:
                self._record_flight(epoch_index, aux)
            elif self._health is not None:
                # no per-step aux (kernel paths can't thread it — gradients
                # never leave VMEM — or record_steps=False opted out): fall
                # back to epoch-granular loss checks
                findings = self._health.check_epoch(epoch_index, [loss])
                self._note_health_findings(findings)
                self._health.dispatch(findings, self._metrics)
        except HealthError:
            self._flush_halt_checkpoint()
            raise
        return loss

    def train_run(self, epochs: int, with_eval: bool = True):
        """Train ``epochs`` epochs; returns ``(losses, accuracies)`` as lists
        of floats (``accuracies`` is None when ``with_eval=False``).

        The ENTIRE run — every epoch and (when ``with_eval``) its full-split
        accuracy — is one on-device XLA program on EVERY layout
        (trainer.make_train_run sequentially, executor.make_pipeline_run on
        the mesh): zero host round-trips, so the loop form's per-epoch
        readbacks are gone. Matches the reference's epoch structure,
        train.py:132-137.
        """
        self._mlp_only("train_run()")
        if epochs <= 0:
            raise ValueError("epochs must be positive")
        if self.runtime == "mpmd":
            raise ValueError(
                "train_run() is the fused ONE-on-device-program contract, "
                "which the MPMD runtime (host-scheduled per-stage programs) "
                "deliberately does not have — drive MPMD sessions with "
                "train_epoch()/train_steps()"
            )
        if self.step_in_epoch != 0:
            raise ValueError(
                f"epoch {self.epoch} is mid-flight at step "
                f"{self.step_in_epoch} (resumed or chunked) — finish it with "
                f"train_steps() before a fused train_run()"
            )
        self._refuse_pending_faults("train_run")
        if self._digests:
            raise ValueError(
                "digests ride the epoch/step scan aux, which the fused "
                "multi-epoch run program does not thread — drive digest "
                "sessions with train_epoch()/train_steps()"
            )
        if with_eval and self._vx is None:
            self._load_val()
        if self._metrics.enabled or self._audit_strict:
            # AOT-compile first (inside warm_run's jit_compile span) so the
            # recorded dispatch wall time is steady-state execution — and,
            # under audit=True, so the run program's collective census is
            # verified before it ever dispatches
            self.warm_run(epochs, with_eval=with_eval)
        start = self.epoch
        t0 = time.perf_counter()
        with self._metrics.span("train_run"):
            compiled = self._compiled_runs.get((with_eval, epochs))
            if compiled is not None:
                out = compiled(*self._fused_run_args(with_eval))
            else:
                out = self._fused_run_fn(with_eval)(
                    *self._fused_run_args(with_eval), epochs
                )
            if self._run_aux:
                out, aux = out[:-1], out[-1]
            else:
                aux = None
            if with_eval:
                state, opt_state, losses, accs = out
            else:
                state, opt_state, losses = out
                accs = None
            losses = [float(v) for v in np.asarray(losses)]  # forces completion
            accs_f = [float(v) for v in np.asarray(accs)] if with_eval else None
        if self._sequential:
            self._params = state
        else:
            self._stacked = state
        self._opt_state = opt_state
        self.epoch += epochs
        gns = None if aux is None else np.asarray(aux["grad_norm"])
        if self._metrics.enabled:
            wall = time.perf_counter() - t0
            samples = self.batches_per_epoch * self.B
            # one fused dispatch -> per-epoch wall clocks don't exist; the
            # run-mean samples/s is attributed to every epoch record
            sps = epochs * samples / wall if wall > 0 else 0.0
            mfu = self._record_utilization(sps)
            for e, loss in enumerate(losses):
                record = dict(
                    epoch=start + e,
                    loss=loss,
                    samples_per_sec=sps,
                    wall_s=wall / epochs,
                    fused_run=True,
                )
                if accs_f is not None:
                    record["accuracy"] = accs_f[e]
                if gns is not None:
                    record["grad_norm"] = float(gns[e])
                if mfu is not None:
                    record["mfu"] = mfu
                self._metrics.event("epoch", **record)
                self._telemetry.note_step(
                    time.perf_counter(), loss=loss, step_s=wall / epochs,
                    throughput=sps, mfu=mfu,
                )
        if self._health is not None:
            # the fused run returns in one dispatch: epoch-granular checks
            # (per-epoch mean loss + mean grad norm when threaded)
            findings = self._health.check_run(
                start, losses, None if gns is None else [float(v) for v in gns]
            )
            self._note_health_findings(findings)
            self._health.dispatch(findings, self._metrics)
        return losses, accs_f

    def warm_run(self, epochs: int, with_eval: bool = True):
        """AOT-compile the fused ``train_run`` program without executing it.

        The compiled executable is cached and reused by the next
        ``train_run(epochs, with_eval)``, so e.g. a profiler trace around
        that call captures steady-state device execution, not compilation.
        """
        self._mlp_only("warm_run()")
        if epochs <= 0:
            raise ValueError("epochs must be positive")
        if self.runtime == "mpmd":
            raise ValueError(
                "warm_run() AOT-compiles the fused run program, which the "
                "MPMD runtime does not dispatch — the per-stage programs "
                "warm through the audit pass on the first epoch"
            )
        if with_eval and self._vx is None:
            self._load_val()
        key = (with_eval, epochs)
        if key not in self._compiled_runs:
            with self._metrics.span("jit_compile"):
                compiled = (
                    self._fused_run_fn(with_eval)
                    .lower(*self._fused_run_args(with_eval), epochs)
                    .compile()
                )
            self._metrics.counter("jit_compiles")
            # run-program audit BEFORE caching the executable: same layout
            # contract as the epoch program (the fused run is the same
            # collectives scanned over epochs, plus the eval relay) — a
            # fused-run-only session still gets its census verified, and a
            # strict mismatch leaves nothing cached for a retry to dispatch.
            # Dedup per (with_eval, epochs) VARIANT: each distinct compile
            # is a distinct program and every one that can dispatch must
            # have been audited
            self._record_audit(compiled, "run_program", dedup=("run", key))
            self._compiled_runs[key] = compiled
            # fused-run-only sessions still get the cost_model event (the
            # analytical leg; the XLA cross-check stays tied to the EPOCH
            # program so its per-epoch FLOPs aren't diluted by fused eval)
            self._record_cost_model()

    def _fused_run_fn(self, with_eval):
        """Build (once per with_eval) the layout's fused whole-run program."""
        if with_eval not in self._run_fns:
            if self._sequential:
                kwargs = dict(self._run_kwargs)
                if not with_eval and getattr(self, "_run_kernel", False):
                    # the eval-free run rides the whole-RUN kernel: one
                    # device op for all n_epochs (per-epoch eval needs
                    # per-epoch params, so the evaluated run keeps the
                    # epochs-outer scan over the epoch kernel)
                    kwargs["epoch_kernel"] = False
                    kwargs["run_kernel"] = True
                self._run_fns[with_eval] = trainer.make_train_run(
                    self.spec, self._opt, with_eval=with_eval,
                    with_grad_norm=self._run_aux, **kwargs
                )
            else:
                eval_kwargs = {}
                if with_eval:
                    rows = self._vx_padded.shape[0]
                    eval_kwargs = dict(
                        eval_prog=self._lower_inference_prog(),
                        eval_mubatch_size=rows // self.dp,
                    )
                self._run_fns[with_eval] = E.make_pipeline_run(
                    self.mesh, self.spec, self._prog, self._mubatch_local,
                    self._opt, with_grad_norm=self._run_aux,
                    **self._run_kwargs, **eval_kwargs,
                )
        return self._run_fns[with_eval]

    def _fused_run_args(self, with_eval):
        """The layout's runtime argument tuple for the fused run (everything
        except the static n_epochs)."""
        if self._sequential:
            base = (self._params, self._opt_state, self._Xe, self._Ye)
            return base + ((self._vx, self._vy) if with_eval else ())
        base = (self._stacked, self._flags, self._opt_state, self._X, self._Y)
        return base + ((self._vx_padded, self._vy_labels) if with_eval else ())

    # -- evaluation ---------------------------------------------------------

    def _load_val(self):
        """First-eval setup: load the split and (on mesh layouts) build ONE
        padded whole-split inference program instead of host-looping
        batch-sized steps — the full split flows through the pipeline in a
        single dispatch (the reference evaluates the whole split per epoch
        too, train.py:21-47, just one μbatch at a time)."""
        # global_batch_size=1 so drop-last keeps EVERY validation sample (the
        # reference's val loader silently drops the tail to a batch multiple;
        # we pad the ragged tail instead)
        val = Dataset(self._data_dir, 1, mubatch_size=1, validation=True)
        val.load(0, 1)
        self._vx = jnp.asarray(val.input_X)
        self._vy = jnp.asarray(val.target_y)
        if not self._sequential:
            n_val = self._vx.shape[0]
            # fused-run eval keeps its own whole-split program (one padded
            # microbatch inside the fused run — one row-shard per dp
            # replica); the interactive accuracy() path instead loops the
            # split through the SAME ladder-capped slot programs predict()
            # and the serving engine dispatch
            eval_rows = -(-n_val // self.dp) * self.dp
            self._vx_padded = jnp.pad(self._vx, ((0, eval_rows - n_val), (0, 0)))
            self._vy_labels = jnp.argmax(self._vy, 1)

    @property
    def sequential(self):
        """True on the single-device reference path (dp=pp=V=1) — no mesh,
        no tick programs; inference dispatches one fixed slot program per
        OCCUPIED slot (the serving engine's padding accounting keys off
        this: a sequential dispatch never pays the ladder rung tail)."""
        return self._sequential

    @property
    def slot_rows(self):
        """Global rows per inference microbatch slot (docs/serving.md)."""
        return self._slot_rows

    @property
    def slot_ladder(self):
        """Allowed slot counts per inference dispatch — the compile bound:
        at most len(slot_ladder) cached predict programs per session."""
        return self._slot_ladder

    def predict(self, x):
        """Softmax class probabilities for a (n, in_dim) batch on ANY layout
        (host numpy in, host numpy out). Rows are packed into fixed
        ``slot_rows``-row microbatch slots and dispatched through cached
        inference programs whose slot counts walk the ``slot_ladder`` —
        at most len(ladder) compiled programs ever, and each slot computes
        bitwise-identically in every rung program (the serving engine's
        parity contract rides on exactly this property)."""
        self._mlp_only("predict()")
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        out_dim = self.spec.out_dim
        if n == 0:
            return np.zeros((0, out_dim), np.float32)
        S_rows = self._slot_rows
        cap = self._slot_ladder[-1] * S_rows  # rows per ladder-capped chunk
        outs = []
        for i in range(0, n, cap):
            chunk = x[i : i + cap]
            m = serving_slots.slots_needed(chunk.shape[0], S_rows)
            if self._sequential:
                # one compiled (slot_rows, in_dim) program, dispatched per
                # slot: a fixed shape is what keeps each slot's rows
                # bitwise-stable against the batch around them. Only the m
                # OCCUPIED slots dispatch — the ladder round-up exists to
                # bound compiled-program count, and the sequential path has
                # exactly one program however many slots run, so the
                # pure-padding rung tail would be wasted work
                xb = np.pad(chunk, ((0, m * S_rows - chunk.shape[0]), (0, 0)))
                preds = np.concatenate(
                    [
                        np.asarray(
                            self._predict(
                                self._params,
                                jnp.asarray(xb[k * S_rows : (k + 1) * S_rows]),
                            )
                        )
                        for k in range(m)
                    ],
                    axis=0,
                )
            elif self.runtime == "mpmd":
                # MPMD streaming: each OCCUPIED slot is its own per-stage
                # chain — slot k enters stage 0 while slot k-1 occupies
                # stage 1 — so there is no rung program and therefore no
                # rung round-up (the compile bound is one fwd program per
                # stage, not one per ladder rung). Submit every slot
                # before materializing any: the chains pipeline.
                runner = self._mpmd_infer_runner()
                params, fls = self._mpmd_infer_views()
                xb = np.pad(chunk, ((0, m * S_rows - chunk.shape[0]), (0, 0)))
                handles = [
                    runner.submit(
                        params, fls, xb[k * S_rows : (k + 1) * S_rows]
                    )
                    for k in range(m)
                ]
                preds = np.concatenate(
                    [np.asarray(h) for h in handles], axis=0
                )
            else:
                rung = serving_slots.rung_for(m, self._slot_ladder)
                xb = np.pad(chunk, ((0, rung * S_rows - chunk.shape[0]), (0, 0)))
                step = self._inference_step(rung)
                packed = serving_slots.pack_slots(
                    xb.reshape(rung, S_rows, -1), self.dp
                )
                preds = serving_slots.unpack_slots(
                    np.asarray(
                        step(self._eval_stacked(), self._flags, jnp.asarray(packed))
                    ),
                    rung,
                    self.dp,
                )
            outs.append(preds[: chunk.shape[0], :out_dim])
        return np.concatenate(outs, axis=0)

    def _lower_inference_prog(self, mubatches=1):
        """The layout's inference TickProgram (interleaved-aware) — shared by
        the cached predict/serving programs (``mubatches`` = the ladder
        rung's slot count) and the fused train_run eval (one whole-split
        microbatch)."""
        if self.V > 1:
            return lower_schedule(
                S.InterleavedInferenceSchedule, mubatches, self.pp,
                training=False, virtual=self.V,
            )
        return lower_schedule(
            S.InferenceSchedule, mubatches, self.pp, training=False
        )

    def _eval_stacked(self):
        """The {W, b} stacked params the forward-only programs consume.
        Identity on every layout except ZeRO-3, where params at rest are
        per-rank block-cyclic shards: the eval view is rebuilt on host
        (one gather) and cached by the live array's identity — a weight
        update invalidates it, repeat dispatches between updates reuse it
        (same pattern as the MPMD inference view cache)."""
        if self._zero != 3:
            return self._stacked
        cached = self._eval_stacked_cache
        if cached is not None and cached[0] is self._stacked:
            return cached[1]
        host = E.zero_block_unflatten_rows(
            np.asarray(jax.device_get(self._stacked["P"])),
            self.spec, self.mesh,
        )
        ev = E.put_stacked_tree(host, self.mesh)
        self._eval_stacked_cache = (self._stacked, ev)
        return ev

    def _inference_step(self, n_slots):
        """Cached inference program for a ladder rung of ``n_slots``
        microbatch slots (mesh layouts; shared by predict(), the mesh
        accuracy() path and the serving engine). With metrics or strict
        audit enabled the compiled program is censused against the
        forward-only inference contract BEFORE it is cached — a serving
        program that lowers a gradient collective never serves a request
        (and, like every audit, a failure is never latched)."""
        step = self._predict_cache.get(n_slots)
        if step is None:
            prog = self._lower_inference_prog(n_slots)
            need_audit = self._metrics.enabled or self._audit_strict
            if need_audit:
                # the serving rung's tick tables get the same lowering-
                # time static passes as the epoch program — a malformed
                # inference program never compiles, let alone serves
                self._record_static_analysis(prog, f"inference_r{n_slots}")
            step = E.make_pipeline_step(
                self.mesh, self.spec, prog,
                self._slot_rows // self.dp, precision=self.precision,
                kernel_backend=self._kernel_backend,
            )
            if need_audit:
                expected = program_audit.expected_comms(
                    self.spec,
                    self.dp,
                    self.pp,
                    prog=prog,
                    mubatch_size=self._slot_rows // self.dp,
                    platform=self._cost_model.platform,
                    device_kind=self._cost_model.device_kind,
                    precision=self._precision_name,
                    tp=self.tp,
                )
                x_shape = jax.ShapeDtypeStruct(
                    (n_slots * self._slot_rows, self.spec.sizes[0]),
                    jnp.float32,
                )
                with self._metrics.span("jit_compile"):
                    compiled = step.lower(
                        self._eval_stacked(), self._flags, x_shape
                    ).compile()
                self._metrics.counter("jit_compiles")
                self._record_audit(
                    compiled,
                    "inference_program",
                    dedup=("inference", n_slots),
                    expected=expected,
                )
                # serving-path dispatch safety: the rung must donate
                # nothing (its params serve the very next request) —
                # proven from the compiled HLO, unlatched like the census
                program_audit.verify_dispatch_safety(
                    compiled, context=f"inference_r{n_slots}"
                )
            self._predict_cache[n_slots] = step
        return step

    def _mpmd_infer_runner(self):
        """The streaming MPMD inference runner (mesh mpmd sessions): ONE
        slot-shaped per-stage forward chain, admission-gated at build
        (``analyze_program`` over the inference tick tables) and — when
        metrics/audit are on — censused per stage program against
        the forward-only contract before the first request."""
        if self._mpmd_infer is None:
            from shallowspeed_tpu.parallel import mpmd

            prog = self._lower_inference_prog(1)
            runner = mpmd.MpmdInferenceRunner(
                self.mesh, self.spec, prog, self._slot_rows // self.dp,
                precision=self.precision,
            )
            if self._metrics.enabled or self._audit_strict:
                runner.warm(self._stacked, self._flags, self._mpmd_resolve)
            self._mpmd_infer = runner
        return self._mpmd_infer

    def _mpmd_infer_views(self):
        """The streaming runner's per-stage param/flag views, cached per
        LIVE weight arrays: rebuilding (and re-packing) per request would
        tax every dispatch; a hot weight reload swaps ``self._stacked``
        to a new object, which invalidates the cache by identity."""
        cached = getattr(self, "_mpmd_infer_view_cache", None)
        if (
            cached is not None
            and cached[0] is self._stacked  # kept alive by the cache
            and cached[1] is self._flags
        ):
            return cached[2], cached[3]
        runner = self._mpmd_infer_runner()
        params, fls = runner.views(self._stacked, self._flags)
        self._mpmd_infer_view_cache = (self._stacked, self._flags, params, fls)
        return params, fls

    def predict_async(self, x):
        """MPMD streaming submit (mesh mpmd sessions): issue ONE request
        of up to ``slot_rows`` rows through the per-stage chain and
        return a zero-argument resolver. Nothing blocks at submit, so
        consecutive requests pipeline across stages — request k enters
        stage 0 while request k-1 occupies a later stage. This is the
        measured tail-latency payoff next to the rung program's
        makespan-quantized dispatch (MPMD_r01.json)."""
        self._mlp_only("predict_async()")
        if self._sequential or self.runtime != "mpmd":
            raise ValueError(
                "predict_async streams through the MPMD per-stage chain — "
                "construct the session with runtime='mpmd' (mesh layout)"
            )
        x = np.asarray(x, np.float32)
        n, out_dim = x.shape[0], self.spec.out_dim
        if n < 1 or n > self._slot_rows:
            raise ValueError(
                f"predict_async takes one slot (1..{self._slot_rows} rows); "
                f"got {n} — larger requests go through predict()"
            )
        runner = self._mpmd_infer_runner()
        params, fls = self._mpmd_infer_views()
        xb = np.pad(x, ((0, self._slot_rows - n), (0, 0)))
        handle = runner.submit(params, fls, xb)

        def resolve():
            return np.asarray(handle)[:n, :out_dim]

        return resolve

    def inference_latency_bound(self):
        """Analytical latency floor for one request slot through this
        layout's inference program: the lockstep tick model's weighted
        makespan (ticks x per-tick cost from
        ``costmodel.PIPELINE_OP_COSTS``) at the platform peak — the
        model-side number the serving bench and report quote next to the
        measured percentiles (docs/serving.md)."""
        return costmodel.serving_latency_bound(
            prog=None if self._sequential else self._lower_inference_prog(1),
            spec=self.spec,
            slot_rows=self._slot_rows,
            dp=self.dp,
            platform=self._cost_model.platform,
            device_kind=self._cost_model.device_kind,
            precision=self._precision_name,
            tp=self.tp,
        )

    def accuracy(self) -> float:
        """Argmax accuracy over the full validation split."""
        self._mlp_only("accuracy()")
        if self._vx is None:
            self._load_val()
        with self._metrics.span("eval"):
            if self._sequential:
                acc = trainer.accuracy(
                    self._predict, self._params, self._vx, self._vy
                )
            else:
                # the split flows through the SAME ladder-capped slot
                # programs predict() and the serving engine dispatch — eval
                # exercises exactly the compiled path serving exercises
                n_val = self._vx.shape[0]
                preds = self.predict(np.asarray(self._vx))
                correct = int(
                    (np.argmax(preds, 1) == np.asarray(self._vy_labels)).sum()
                )
                acc = correct / max(n_val, 1)
        if self._metrics.enabled:
            self._metrics.gauge("val_accuracy", acc)
        return acc

    # -- state --------------------------------------------------------------

    def params(self):
        """Logical per-stage params (host numpy), layout-independent order."""
        return self._logical_params_from_raw(
            self._params if self._sequential else self._stacked
        )

    def poison_weights(self):
        """Fault-injection hook (faults.py): NaN one element of this
        session's live weights — the deterministic blow-up behind the
        training ``nan@step=N`` injection and the serving
        ``nan@dispatch=N`` injection (both drive this one method, so the
        poisoned state is identical either way)."""
        if self._sequential:
            self._params = F.poison_nan(self._params)
        else:
            self._stacked = F.poison_nan(self._stacked)

    def flip_weights(self):
        """Fault-injection hook (faults.py): XOR the lowest mantissa bit
        of one element of this session's live weights — the training
        ``flip@step=N`` injection. The result stays finite, so nothing in
        the loss/health stream moves; only the per-layer digest stream
        (``digests=True``) can name the (step, layer) it happened at —
        exactly what ``make diverge-smoke`` verifies."""
        if self._sequential:
            self._params = F.poison_bitflip(self._params)
        else:
            self._stacked = F.poison_bitflip(self._stacked)

    def load_weights(self, path, verified=None):
        """HOT-swap this session's weights from a checkpoint, between
        dispatches, WITHOUT touching the compiled program caches: the new
        arrays have the same shapes/shardings as the old (enforced — a
        checkpoint of different sizes is refused), so every cached
        epoch/run/inference program keeps dispatching with ZERO recompiles
        — the serving engine's hot-reload contract (every response
        dispatched after the swap is bitwise-equal to a direct
        ``predict()`` under the new weights, and the rung program cache
        survives; docs/robustness.md "Serving faults").

        Deliberately weights-ONLY: the optimizer state, epoch/step cursor
        and metrics numbering are untouched — this is a serving-side swap,
        not a training resume (use ``resume=`` at construction for that).
        Returns the checkpoint's metadata dict. Unreadable / corrupt files
        raise ``CheckpointError`` before any state changes.

        ``verified=(meta, arrays)``: the pair a ``with_arrays=True``
        discovery (``find_latest_good`` / ``find_newer_good``) already
        read and checksummed — the swap then assembles from those arrays
        instead of re-reading the file, so a reload is ONE verified read
        and the discovery->load TOCTOU window (the serving engine's
        watcher polls a directory a concurrent trainer keeps rotating)
        is closed by construction."""
        self._mlp_only("load_weights()")
        if verified is not None:
            host_params, loaded_spec, meta = assemble_checkpoint(
                path, verified[0], verified[1], self.pp * self.V, self.B
            )
        else:
            host_params, loaded_spec, meta = load_checkpoint(
                path, self.pp * self.V, self.B
            )
        if tuple(loaded_spec.sizes) != tuple(self.spec.sizes):
            raise ValueError(
                f"checkpoint sizes {loaded_spec.sizes} do not match this "
                f"session's model sizes {self.spec.sizes} — a hot reload "
                "must preserve every compiled program's shapes"
            )
        if getattr(loaded_spec, "act", "relu") != self.spec.act:
            raise ValueError(
                f"checkpoint activation family "
                f"{getattr(loaded_spec, 'act', 'relu')!r} does not match "
                f"this session's {self.spec.act!r} — a hot reload must "
                "preserve every compiled program's structure"
            )
        with self._metrics.span("device_put"):
            if self._sequential:
                self._params = jax.tree.map(jnp.asarray, host_params)
            elif self._zero == 3:
                # re-shard into the session's at-rest block-cyclic layout
                stacked_np, _ = E.stack_params(
                    host_params, self.spec, order=self._order, tp=self.tp
                )
                self._stacked = {
                    "P": jax.device_put(
                        E.zero_block_flatten_rows(
                            stacked_np, self.spec, self.mesh
                        ),
                        E.zero1_part_sharding(self.mesh),
                    )
                }
                self._eval_stacked_cache = None
            else:
                # keep the session's existing flags array (identical
                # content) — only the weight planes swap
                self._stacked, _ = E.put_stacked(
                    *E.stack_params(
                        host_params, self.spec, order=self._order, tp=self.tp
                    ),
                    self.mesh,
                )
        return meta

    def model_hash(self) -> str:
        if self._token:
            return utils.tree_hash(self.params())
        return utils.model_hash(self.params())

    @property
    def data_layout(self):
        """``"feature_major"`` or ``"row_major"``: the orientation in which
        this session keeps its training set resident, hence which epoch
        program it runs (``trainer.data_layout``; the ``data_layout``
        metrics event carries the same with ``mb`` and ``F``)."""
        return self._data_layout

    @property
    def scan_path(self):
        """``"pallas"`` or ``"xla"``: the form in which a token model's
        recurrent layers run their chunked scan (``ops.scan_path`` for the
        Gated DeltaNet's, ``ops.kda_scan_path`` for the per-channel rule's;
        the ``scan_path`` metrics event carries the same with the chunk, the
        head sizes and the kernel launches a step). ``None`` for an MLP."""
        return self._scan_path

    def placement(self):
        """Where the mesh put the parameters — None on the sequential path.
        ``layout`` is ``make_mesh_with_layout``'s provenance note,
        ``device_ids`` the mesh grid, ``param_bytes`` the parameter bytes
        each device actually holds (summed over ``addressable_shards``), so
        a run can show that every chip owns its shard rather than device 0
        owning everything."""
        if self._sequential:
            return None
        held = {}
        for leaf in jax.tree.leaves(self._stacked):
            for shard in leaf.addressable_shards:
                held[shard.device.id] = (
                    held.get(shard.device.id, 0) + shard.data.nbytes
                )
        return {
            "layout": self._mesh_layout,
            "device_ids": self.mesh.device_ids.tolist(),
            "param_bytes": dict(sorted(held.items())),
        }

    def assert_replicas_in_sync(self):
        if not self._sequential and self._zero != 3:
            # ZeRO-3 keeps no dp-replicated params to cross-check: each
            # rank owns a disjoint 1/dp shard at rest by construction
            utils.assert_dp_replicas_in_sync(self._stacked)

    def _snapshot_raw(self):
        """Stage 1 of a snapshot, the ONLY part that must stay on the
        step path for consistency: the device->host readback of the live
        params + optimizer state, in their RAW (stacked/flat) layout.
        Returns immutable host copies safe to hand to the async writer
        (the training loop keeps mutating the device arrays)."""
        raw_params = jax.device_get(
            self._params if self._sequential else self._stacked
        )
        raw_state = (
            None if is_stateless(self._opt) else jax.device_get(self._opt_state)
        )
        return raw_params, raw_state

    def _logical_params_from_raw(self, raw_params):
        """Raw (stacked/sequential) param arrays -> the logical per-stage
        list. Pure numpy on host arrays (``device_get`` is the identity
        there), so under async saves it runs on the writer thread, OFF
        the step path. The ONE implementation behind ``params()`` and
        the async snapshot build — they cannot drift."""
        if self._sequential:
            return jax.device_get(raw_params)
        if self._zero == 3:
            # params at rest are one block-cyclic row plane — rebuild the
            # stacked {W, b} layout on host before unstacking
            raw_params = E.zero_block_unflatten_rows(
                np.asarray(jax.device_get(raw_params["P"])),
                self.spec, self.mesh,
            )
        return E.unstack_params(raw_params, self.spec, order=self._order)

    def _logical_state_from_raw(self, raw_state):
        """Raw optimizer-state arrays -> the layout-independent logical
        form (``opt_state_logical()``'s output, same single-owner rule
        as ``_logical_params_from_raw``). None stays None (stateless)."""
        if raw_state is None:
            return None
        if self._zero >= 2:
            return E.zero_block_state_to_logical(
                raw_state, self._opt, self.spec, self.mesh, order=self._order
            )
        if self._zero1:
            return E.zero1_state_to_logical(
                raw_state, self._opt, self.spec, self.mesh, order=self._order
            )
        parts, scalars = split_state(self._opt, raw_state)
        if self._sequential:
            parts = {k: jax.device_get(v) for k, v in parts.items()}
        else:
            parts = {
                k: E.unstack_params(v, self.spec, order=self._order)
                for k, v in parts.items()
            }
        scalars = {k: float(jax.device_get(v)) for k, v in scalars.items()}
        return {"parts": parts, "scalars": scalars}

    def _logical_from_raw(self, raw_params, raw_state):
        """Both halves of a raw snapshot in logical form (the async
        writer-thread build)."""
        return (
            self._logical_params_from_raw(raw_params),
            self._logical_state_from_raw(raw_state),
        )

    def opt_state_logical(self):
        """Stateful-optimizer state in layout-independent logical form:
        ``{"parts": {key: ragged_list mirroring params()}, "scalars":
        {key: float}}`` per the optimizer's state_layout(); None for
        stateless optimizers."""
        if is_stateless(self._opt):
            return None
        return self._logical_state_from_raw(self._opt_state)

    def save(self, path):
        self._mlp_only("save()")
        save_checkpoint(
            path,
            self.params(),
            self.spec,
            self.epoch - 1,
            extra={"optimizer": self._opt_config},
            opt_state=self.opt_state_logical(),
        )
