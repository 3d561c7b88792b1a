"""Analytical cost model + XLA cost_analysis cross-check + MFU accounting.

"As fast as the hardware allows" is a ratio, and this module owns both of
its legs:

- the NUMERATOR is model FLOPs — the analytical count of useful training
  arithmetic (fwd ``2P`` + bwd ``4P`` per sample for ``P = sum(in*out)``,
  the standard MLP ledger; ``bench.flops_per_sample`` delegates here so the
  benchmark and the telemetry can never disagree on it). For pipeline
  layouts the PADDED hardware FLOPs (what the stacked-slot executor
  actually multiplies, computed from the lowered tick tables) are tracked
  alongside, so the padding tax is a recorded number, not folklore;
- the XLA leg: ``Compiled.cost_analysis()`` FLOPs/bytes pulled from the
  jit-compiled epoch program. The analytical count is CROSS-CHECKED against
  it (``flops_ratio``) — if the two diverge wildly, either the analytical
  model or the lowering regressed, and a consumer can see which epoch
  program to distrust;
- the DENOMINATOR is peak FLOP/s: per-chip datasheet numbers for the TPU
  precision classes, keyed by the chip's ``device_kind`` (a TPU the table
  does not know gets no peak and no MFU — never another chip's numbers), a
  clearly-labeled NOMINAL figure for host CPU (there is no single honest
  CPU peak; the source tag says so), or the ``SHALLOWSPEED_PEAK_FLOPS`` env
  override for any other hardware. Every MFU record carries the peak AND
  its source, so a number computed against the nominal CPU default cannot
  be misread as a datasheet MFU.

``MFU = samples_per_sec * model_flops_per_sample / (peak_per_chip * chips)``
— model FLOPs in the numerator (the Chowdhery et al. PaLM convention), so
padding and recomputation make MFU WORSE, never better.
"""

import os

# What ``jax.devices()[0].device_kind`` says on a TPU v5e (libtpu 0.0.34).
TPU_V5E = "TPU v5 lite"

# Per-chip peak model FLOP/s by (row, matmul-precision class), where the row
# is ``device_row(platform, device_kind)``: a TPU's ``device_kind``, or the
# platform name for a host CPU. The v5e rows are the ceilings bench.py's
# plausibility guard uses (fp32-accumulate fp32-input ~100 TF/s, bf16-input
# MXU passes ~200 TF/s). The CPU row is a NOMINAL single-socket figure
# (order 100 GFLOP/s fp32) — labeled as such in the source tag; override
# with SHALLOWSPEED_PEAK_FLOPS.
PEAK_FLOPS_PER_CHIP = {
    (TPU_V5E, "highest"): 100e12,
    (TPU_V5E, "default"): 200e12,
    ("cpu", "highest"): 2e11,
    ("cpu", "default"): 2e11,
}

ENV_PEAK = "SHALLOWSPEED_PEAK_FLOPS"

# Relative per-tick FLOP weights of the pipeline executor's compute ops, in
# units of one forward's matmul work (2P per microbatch, P = the padded
# per-slot weight count — every op runs the same slot stack, so the RATIOS
# are exact regardless of stage): a combined backward is dgrad 2P + wgrad 2P
# = 2 forwards; the split halves are one forward each. This is the single
# source for ``lowering.weighted_utilization`` / ``weighted_makespan`` —
# the metric that can see the split-backward win (equal-weight utilization
# counts a 4P backward cell and a 2P forward cell the same, so it scores a
# schedule that splits backwards WORSE while the lockstep step time drops).
PIPELINE_OP_COSTS = {
    "fwd": 1.0, "bwd": 2.0, "bwd_in": 1.0, "bwd_w": 1.0,
    # an OP_RECOMPUTE cell re-runs a full stage forward (torchgpipe
    # trade): same 2P matmul work as a forward tick
    "recompute": 1.0,
}


def mlp_train_flops_per_sample(sizes):
    """Analytical training FLOPs per sample: fwd 2P + bwd 4P (dgrad 2P +
    wgrad 2P) for P = sum(in*out) — bias adds, relu and the softmax head
    are O(width) noise against the O(width^2) matmuls and are not counted.
    The single source of truth (bench.flops_per_sample delegates here)."""
    sizes = tuple(sizes)
    return 6 * sum(sizes[i] * sizes[i + 1] for i in range(len(sizes) - 1))


def token_train_flops_per_token(spec, pairs_per_token=None):
    """Analytical training FLOPs per TOKEN of a token model
    (``model.TokenModelSpec``), forward and backward, recomputation not
    counted: 6 per weight of the head over the held vocabulary slice (the
    embedding is a lookup), and each layer's mixer's FLOPs and its
    feed-forward's, as their records in ``model`` count them
    (``spec.layer_records``). Attention's depend on the (query, key) pairs
    the mask admits, ``pairs_per_token``, and so on how the documents were
    packed (``data.packed_counts``); the causal mask's ``(seq_len + 1) / 2``
    where it is not given."""
    if pairs_per_token is None:
        pairs_per_token = (spec.seq_len + 1) / 2
    return 6 * spec.hidden_size * spec.vocab_size + sum(
        mixer.flops(spec, pairs_per_token) + ffn.flops(spec, pairs_per_token)
        for mixer, ffn in spec.layer_records
    )


def token_train_flops_per_sample(spec, pairs_per_token=None):
    """Per row of ``seq_len`` tokens (a token model's sample)."""
    return token_train_flops_per_token(spec, pairs_per_token) * spec.seq_len


def train_flops_per_sample(spec):
    """Either kind of model spec -> analytical training FLOPs per sample."""
    if hasattr(spec, "layer_types"):
        return token_train_flops_per_sample(spec)
    return mlp_train_flops_per_sample(spec.sizes)


def device_row(platform, device_kind):
    """-> ``(row, unknown_tag)``: the key every per-chip table in this
    package is looked up by, and the source tag to report when the table
    has no such row. TPUs differ by generation, so a TPU is keyed by its
    ``device_kind``; a host CPU by its platform name."""
    if platform == "tpu":
        return device_kind, f"unknown-device:{device_kind}"
    return platform, f"unknown-platform:{platform}"


def peak_flops_per_chip(platform, precision="highest", device_kind=None):
    """-> ``(peak_flops, source)`` for one chip; ``(None, source)`` when
    the device is not in the table. ``platform`` / ``device_kind`` are
    ``jax.devices()[0].platform`` / ``.device_kind``."""
    env = os.environ.get(ENV_PEAK)
    if env:
        return float(env), f"env:{ENV_PEAK}"
    row, unknown = device_row(platform, device_kind)
    if (row, precision) not in PEAK_FLOPS_PER_CHIP:
        return None, unknown
    source = "datasheet-v5e" if row == TPU_V5E else "nominal-cpu-default"
    return PEAK_FLOPS_PER_CHIP[(row, precision)], source


def serving_latency_bound(
    prog, spec, slot_rows, dp=1, platform="cpu", precision="highest", tp=1,
    device_kind=None,
):
    """Analytical latency floor for ONE request slot through the layout's
    inference program — the model-side number the serving bench and report
    quote next to the MEASURED p50/p99 (docs/serving.md).

    Mesh layouts (``prog`` = the single-slot lowered inference program):
    under the executor's lockstep tick model a dispatch takes
    ``weighted_makespan(prog)`` forward-units of work (for a forward-only
    program that is exactly its tick count x ``PIPELINE_OP_COSTS['fwd']``),
    and one forward-unit is ``2 * (slot_rows/dp) * padded_P`` FLOPs over
    the PADDED slot stack (``lowering.program_flops``'s per-cell ledger).
    Sequential (``prog=None``): one slot's logical forward,
    ``2 * P * slot_rows`` FLOPs. Divided by the platform's peak
    (``peak_flops_per_chip``) — a lower bound: dispatch overhead, relay
    bandwidth and queueing all sit on top of it, which is the point of
    printing it under the measured percentiles.

    Returns ``{"ticks", "weighted_ticks", "flops", "seconds",
    "peak_flops_per_chip", "peak_source"}`` (``seconds`` None when the
    platform peak is unknown; ``ticks`` None on the sequential path).
    """
    peak, source = peak_flops_per_chip(platform, precision, device_kind)
    if prog is None:
        flops = 2 * sum(
            spec.sizes[i] * spec.sizes[i + 1] for i in range(len(spec.sizes) - 1)
        ) * slot_rows
        ticks = weighted = None
    else:
        from shallowspeed_tpu.parallel.executor import slot_shapes
        from shallowspeed_tpu.parallel.lowering import weighted_makespan

        # per-DEVICE floor: the Megatron shards split every slot matmul,
        # so a tp rank executes 1/tp of the (tp-rounded) padded stack
        padded_p = sum(o * i for o, i in slot_shapes(spec, tp)) // max(tp, 1)
        weighted = weighted_makespan(prog)  # forward-units (fwd weight 1.0)
        ticks = int(prog.num_ticks)
        flops = weighted * 2 * (slot_rows // dp) * padded_p
    return {
        "ticks": ticks,
        "weighted_ticks": None if prog is None else float(weighted),
        "flops": float(flops),
        "seconds": (flops / peak) if peak else None,
        "peak_flops_per_chip": peak,
        "peak_source": source,
    }


def compiled_flops(compiled):
    """Pull ``(flops, bytes_accessed)`` from a jax ``Compiled``'s
    ``cost_analysis()`` dict (either field may be absent — e.g. some
    backends report no bytes). Returns ``(None, None)`` when the backend
    offers nothing: cost analysis is a cross-check, never a hard
    dependency."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend-optional surface
        return None, None
    if not isinstance(ca, dict):
        return None, None

    def _get(key):
        v = ca.get(key)
        try:
            v = float(v)
        except (TypeError, ValueError):
            return None
        return v if v > 0 else None

    return _get("flops"), _get("bytes accessed")


class CostModel:
    """One session's FLOP ledger: analytical model FLOPs, optional padded
    pipeline FLOPs, the XLA-compiled cross-check, and the MFU peak."""

    def __init__(
        self,
        sizes,
        global_batch,
        batches_per_epoch,
        n_devices=1,
        platform="cpu",
        precision="highest",
        padded_flops_per_batch=None,
        device_kind=None,
        flops_per_sample=None,
    ):
        self.sizes = tuple(sizes)
        self.global_batch = int(global_batch)
        self.batches_per_epoch = int(batches_per_epoch)
        self.n_devices = int(n_devices)
        self.platform = platform
        self.device_kind = device_kind
        self.precision = precision
        # a token model has no ``sizes``: its count is handed in
        self.flops_per_sample = (
            mlp_train_flops_per_sample(sizes)
            if flops_per_sample is None else flops_per_sample
        )
        self.flops_per_batch = self.flops_per_sample * self.global_batch
        self.flops_per_epoch = self.flops_per_batch * self.batches_per_epoch
        # hardware work actually dispatched per batch on padded-stack
        # layouts (lowering.program_flops x dp); None on the sequential path
        # where logical == padded
        self.padded_flops_per_batch = (
            None if padded_flops_per_batch is None else float(padded_flops_per_batch)
        )
        self.peak_flops_per_chip, self.peak_source = peak_flops_per_chip(
            platform, precision, device_kind
        )
        self.xla_flops_per_epoch = None
        self.xla_bytes_per_epoch = None

    def attach_compiled(self, compiled):
        """Record the compiled epoch program's cost_analysis numbers;
        returns True when the backend reported FLOPs."""
        flops, nbytes = compiled_flops(compiled)
        if flops is not None:
            self.xla_flops_per_epoch = flops
        if nbytes is not None:
            self.xla_bytes_per_epoch = nbytes
        return flops is not None

    @property
    def flops_ratio(self):
        """XLA-reported / analytical epoch FLOPs (the cross-check); None
        until a compiled program has been attached. This is a STRUCTURAL
        cross-check, not an equality: XLA's cost analysis counts each
        ``lax.scan`` body once regardless of trip count (observed on the
        CPU and TPU backends), so a whole-epoch program's ratio lands
        around ``1 / (batches x microbatches)``, padded pipeline layouts
        land higher by the padding tax, and a sudden order-of-magnitude
        MOVE of the ratio for the same layout is what flags a lowering or
        analytical-model regression. Recorded, never asserted blindly."""
        if self.xla_flops_per_epoch is None or self.flops_per_epoch <= 0:
            return None
        return self.xla_flops_per_epoch / self.flops_per_epoch

    @property
    def padded_ratio(self):
        """Padded / logical FLOPs per batch (the pipeline padding tax)."""
        if self.padded_flops_per_batch is None or self.flops_per_batch <= 0:
            return None
        return self.padded_flops_per_batch / self.flops_per_batch

    def achieved_flops_per_sec(self, samples_per_sec):
        """Model-FLOP throughput at an observed samples/s."""
        return samples_per_sec * self.flops_per_sample

    def mfu(self, samples_per_sec):
        """Model FLOP utilization against the layout's total peak (peak per
        chip x participating devices); None when no peak is known."""
        if not self.peak_flops_per_chip or samples_per_sec is None:
            return None
        total_peak = self.peak_flops_per_chip * max(1, self.n_devices)
        return self.achieved_flops_per_sec(samples_per_sec) / total_peak

    def as_record(self):
        """JSON-able snapshot — the ``cost_model`` event's field set."""
        rec = {
            "flops_per_sample": self.flops_per_sample,
            "flops_per_batch": self.flops_per_batch,
            "flops_per_epoch": self.flops_per_epoch,
            "batches_per_epoch": self.batches_per_epoch,
            "global_batch": self.global_batch,
            "n_devices": self.n_devices,
            "platform": self.platform,
            "device_kind": self.device_kind,
            "precision": self.precision,
            "peak_flops_per_chip": self.peak_flops_per_chip,
            "peak_source": self.peak_source,
            "xla_flops_per_epoch": self.xla_flops_per_epoch,
            "xla_bytes_per_epoch": self.xla_bytes_per_epoch,
            "flops_ratio": self.flops_ratio,
        }
        if self.padded_flops_per_batch is not None:
            rec["padded_flops_per_batch"] = self.padded_flops_per_batch
            rec["padded_ratio"] = self.padded_ratio
        return rec
