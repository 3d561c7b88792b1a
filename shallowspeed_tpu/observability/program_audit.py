"""XLA program audit: collective & memory introspection + a comms cost model.

The paper's correctness story is layout-invariance — seq, DP and pipeline
runs must be the *same computation* rearranged — but a FLOP model alone
(costmodel.py) never verifies what XLA actually compiled. This module owns
the compiled-program evidence:

- ``parse_collectives`` / ``collective_census``: parse ``Compiled.as_text()``
  (post-optimization HLO) and count the collective ops by kind — all-reduce,
  all-gather, reduce-scatter, collective-permute, all-to-all (async
  ``-start`` forms count once; their ``-done`` halves are skipped) — with
  per-op result-shape byte sizes. HLO holds each ``lax.scan`` body ONCE
  regardless of trip count, so the census is STRUCTURAL: it answers "which
  collectives exist in the program" (the layout contract), not "how many
  dynamic executions happen" (that is the analytical model's job below);
- ``memory_stats``: ``Compiled.memory_analysis()`` pulled through one shared
  helper (bench.py uses the same path) — argument
  / output / temp / alias split plus a ``peak_hbm_bytes`` estimate;
- ``expected_comms``: the ANALYTICAL comms contract derived from the layout
  spec and the lowered tick tables (``lowering.program_comm_bytes``) —
  which collective kinds the layout requires/forbids, and the bytes each
  device moves per optimizer step per mesh axis (dp ring all-reduce of the
  gradient, issued relays x relay width for the pipeline,
  reduce-scatter + all-gather under ZeRO-1), with a bandwidth-bound
  lower-bound step time against the interconnect peak and a comms- vs
  compute-bound verdict;
- ``check_census`` / ``verify_census``: the cross-check that FAILS LOUDLY
  (``AuditMismatchError``) when the compiled program's collective census
  disagrees with the layout's contract — "the DP all-reduce really is one
  psum" as a tested invariant, not prose;
- ``audit_compiled``: the full audit record (schema-v3 ``xla_audit`` kind;
  docs/observability.md) a ``TrainingSession`` emits at jit time.

Census contract semantics (why kinds, not exact op counts): XLA lowers a
pytree psum into one all-reduce per leaf (or fuses several into one
variadic op), version-dependently; loss psums, pmax replication and the
norm reductions add more. Exact all-reduce counts are therefore compiler
noise, but the KIND set is the layout's signature: a sequential program
must contain no collectives at all, a pipeline (pp > 1) program must
relay through collective-permutes (one per direction in which its tick
table ever sends: two for a training program, one for an inference one; at
pp == 1 no pair of devices ever sends and the executor emits none — the
census allows one, never demands it nor counts it as interconnect
traffic), dp > 1
without ZeRO-1 must all-reduce and must NOT reduce-scatter/all-gather,
and ZeRO-1 must reduce-scatter AND all-gather (even at dp=1 — the
chunked update always lowers both).
"""

import math
import os
import re

from shallowspeed_tpu.observability.costmodel import (
    TPU_V5E,
    device_row,
    peak_flops_per_chip,
    train_flops_per_sample,
)

# Collective HLO op names, in the spelling ``Compiled.as_text()`` uses.
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# Per-chip HBM capacity by ``costmodel.device_row``: the v5e datasheet figure
# (16 GiB HBM2) under that chip's device_kind, a clearly-labeled NOMINAL
# figure for host CPU (there is no single honest "device memory" for a
# host; the source tag says so). A TPU of another kind has no row. Override
# with SHALLOWSPEED_HBM_BYTES for any other hardware.
HBM_PER_CHIP = {
    TPU_V5E: 16 * 2**30,
    "cpu": 8 * 2**30,
}

# Per-chip interconnect bandwidth (bytes/s), same rows: the v5e datasheet
# aggregate ICI figure (1600 Gbps = 200 GB/s per chip), and a NOMINAL
# loopback figure for emulated host-CPU meshes (collectives there are
# memcpys; the tag says nominal). Override with SHALLOWSPEED_PEAK_BW_BYTES.
INTERCONNECT_BYTES_PER_SEC = {
    TPU_V5E: 200e9,
    "cpu": 10e9,
}

ENV_HBM = "SHALLOWSPEED_HBM_BYTES"
ENV_BW = "SHALLOWSPEED_PEAK_BW_BYTES"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# one HLO shape token: dtype[dims] with an optional layout suffix
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

# a collective instruction: "<lhs> = <result-type> <kind>[-start|-done](..."
# The result type is either one shape or a tuple of shapes; matching it
# before the op name keeps metadata op_name strings (later on the line)
# from ever matching. The tuple alternative must tolerate ONE level of
# nested parentheses: TPU post-optimization HLO writes tiled layouts like
# ``(f32[8,128]{1,0:T(8,128)}, ...)`` and async collectives return tuples,
# so a paren-naive tuple match would silently drop exactly the ops the
# audit exists to see.
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<rtype>\((?:[^()]|\([^()]*\))*\)|[a-z][a-z0-9]*\[[0-9,]*\]\S*)\s*"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")"
    r"(?P<phase>-start|-done)?(?:\.\d+)?\("
)


class AuditMismatchError(ValueError):
    """The compiled program's collective census violates the layout's
    analytical contract — either the lowering or the contract regressed."""


# the HLO module header's donation evidence: ``input_output_alias={ {0}:
# (0, {}, may-alias), {1,0}: (2, {1}, must-alias), ... }`` — each entry
# maps an output (tuple) index to the (parameter number, parameter tuple
# index, alias kind) whose buffer it reuses. jit's donate_argnums is what
# puts entries here; a program with NO donation has no such clause.
_ALIAS_MARKER = "input_output_alias={"
_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[0-9,\s]*)\}:\s*\(\s*(?P<param>\d+)\s*,\s*"
    r"\{(?P<pidx>[0-9,\s]*)\}\s*(?:,\s*(?P<kind>[a-z_-]+)\s*)?\)"
)


def _alias_block(hlo_text):
    """The brace-balanced body of the module header's
    ``input_output_alias={...}`` clause, or None when the program
    declares no aliasing. Brace-scanned, not regexed: the body nests
    one brace level per tuple index and a paren-naive match would
    truncate exactly the entries this pass exists to see."""
    start = hlo_text.find(_ALIAS_MARKER)
    if start < 0:
        return None
    i = start + len(_ALIAS_MARKER)
    depth = 1
    for j in range(i, min(len(hlo_text), i + 100_000)):
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return hlo_text[i:j]
    return hlo_text[i:]  # unterminated header: parse what is there


def parse_input_output_aliases(hlo_text):
    """Every input/output buffer alias the compiled program declares, as
    ``{"output_index", "param_number", "param_index", "kind"}`` dicts
    (``kind`` is ``may-alias``/``must-alias``; empty list = the whole
    parameter/output, not a tuple leaf). An empty list means the program
    donates nothing — the property the dispatch-safety pass proves."""
    body = _alias_block(hlo_text)
    if body is None:
        return []
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(body):
        out.append(
            {
                "output_index": [
                    int(v) for v in m.group("out").split(",") if v.strip()
                ],
                "param_number": int(m.group("param")),
                "param_index": [
                    int(v) for v in m.group("pidx").split(",") if v.strip()
                ],
                "kind": m.group("kind") or "may-alias",
            }
        )
    return out


def donation_census(hlo_text):
    """Aggregate donation evidence for one program: alias entry count,
    the distinct donated parameter numbers, and the per-kind split —
    the field set the ``static_analysis``/``xla_audit`` records carry."""
    aliases = parse_input_output_aliases(hlo_text)
    kinds = {}
    for a in aliases:
        kinds[a["kind"]] = kinds.get(a["kind"], 0) + 1
    return {
        "aliased_outputs": len(aliases),
        "donated_params": sorted({a["param_number"] for a in aliases}),
        "kinds": kinds,
    }


def check_dispatch_safety(hlo_text, context="compiled program"):
    """The dispatch-safety leg: a program that serves requests, or whose
    compiled executable is the dispatch path (the MPMD stage programs),
    must not donate its buffers — its params are reused by the very next
    dispatch, so donation there is a use-after-free by construction
    (serving/engine.py). Returns a list of human-readable
    mismatch strings (empty = dispatch-safe)."""
    census = donation_census(hlo_text)
    if not census["aliased_outputs"]:
        return []
    return [
        f"{context}: program donates its input buffers "
        f"(input_output_alias: {census['aliased_outputs']} aliased "
        f"output(s) over params {census['donated_params']}, kinds "
        f"{census['kinds']}) — dispatching it from a compiled "
        "executable or a serving path is the documented use-after-free "
        "hazard (docs/static-analysis.md, docs/robustness.md)"
    ]


def verify_dispatch_safety(compiled_or_text, context="compiled program"):
    """``check_dispatch_safety`` that fails loudly (AuditMismatchError,
    unlatched like the census — a caught-and-retried caller re-verifies
    and re-raises). Accepts a ``Compiled`` object or its ``as_text()``
    dump; returns the donation census record on a pass. A backend that
    exposes no HLO text yields ``None`` — no evidence, recorded as
    unverifiable, never a silent pass/fail."""
    text = compiled_or_text
    if not isinstance(text, str):
        try:
            text = compiled_or_text.as_text()
        except Exception:  # noqa: BLE001 — backend-optional surface
            text = None
    if text is None:
        return None
    mismatches = check_dispatch_safety(text, context=context)
    if mismatches:
        raise AuditMismatchError("; ".join(mismatches))
    return donation_census(text)


def _shape_bytes_each(type_str):
    """Byte size of every shape token in an HLO type (a shape, or a tuple
    of shapes), in order. Unknown dtypes count 0 bytes — the census must
    never crash on exotic types; the op is still counted."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dtype, 0))
    return sizes


def _shape_bytes(type_str, async_start=False):
    """Byte size of one HLO result type. Async ``-start`` ops return a
    tuple pairing the ALIASED operands with the results — ``(op_0..op_k,
    res_0..res_k)`` — so counting the whole tuple would double the op's
    real payload; for an even-length start tuple only the result half is
    summed (exact for same-shape in/out collectives like all-reduce and
    collective-permute, and the honest half for all-gather where the
    result leg IS the payload). Odd/unrecognized tuples fall back to the
    full sum."""
    sizes = _shape_bytes_each(type_str)
    if async_start and len(sizes) >= 2 and len(sizes) % 2 == 0:
        sizes = sizes[len(sizes) // 2:]
    return sum(sizes)


def parse_collectives(hlo_text):
    """All collective instructions in a post-optimization HLO dump.

    Returns a list of ``{"kind", "bytes"}`` dicts — ``kind`` uses
    underscores (``all_reduce``) for JSON-friendliness, ``bytes`` is the
    op's RESULT-shape size (what each participating device holds after the
    op; algorithmic wire bytes are the analytical model's concern). Async
    pairs count once: the ``-start`` op carries the collective, its
    ``-done`` half is skipped, and the start tuple's operand-alias legs
    are excluded from the byte count (see ``_shape_bytes``).
    """
    ops = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or m.group("phase") == "-done":
            continue
        ops.append(
            {
                "kind": m.group("kind").replace("-", "_"),
                "bytes": _shape_bytes(
                    m.group("rtype"), async_start=m.group("phase") == "-start"
                ),
            }
        )
    return ops


def census_of_ops(ops):
    """Aggregate a ``parse_collectives`` op list into the census shape:
    ``{kind: {"count": n, "bytes": summed result bytes}}``."""
    census = {}
    for op in ops:
        agg = census.setdefault(op["kind"], {"count": 0, "bytes": 0})
        agg["count"] += 1
        agg["bytes"] += op["bytes"]
    return census


def collective_census(hlo_text):
    """-> ``{kind: {"count": n, "bytes": summed result bytes}}``."""
    return census_of_ops(parse_collectives(hlo_text))


def memory_stats(compiled):
    """``Compiled.memory_analysis()`` as a plain dict — the ONE shared path
    (TrainingSession audits and bench.py's published record both read
    through here, so their byte accounting can never disagree).

    Fields (whichever the backend reports): ``argument_size_in_bytes``,
    ``output_size_in_bytes``, ``temp_size_in_bytes``,
    ``alias_size_in_bytes``, ``generated_code_size_in_bytes``, plus
    ``peak_hbm_bytes`` — the backend's explicit peak when it exposes one,
    else the live-buffer estimate ``arguments + outputs + temp - aliased``
    (donated buffers are counted once). All sizes are PER DEVICE: XLA's
    memory analysis reports the addressable shard (verified empirically —
    an argument sharded over N devices reports 1/N of its global bytes),
    so ``peak_hbm_bytes`` compares directly against one chip's capacity.
    Returns ``None`` when the backend offers nothing: memory analysis is
    evidence, never a hard dependency.
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — backend-optional surface
        return None
    if ma is None:
        return None
    out = {}
    for field in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak:
        out["peak_hbm_bytes"] = int(peak)
    elif out:
        out["peak_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out or None


def hbm_per_chip(platform, device_kind=None):
    """-> ``(capacity_bytes, source)`` for one chip; ``(None, source)``
    when the device is not in the table. Same provenance discipline as
    ``costmodel.peak_flops_per_chip``: a nominal figure is tagged nominal."""
    env = os.environ.get(ENV_HBM)
    if env:
        return float(env), f"env:{ENV_HBM}"
    row, unknown = device_row(platform, device_kind)
    if row not in HBM_PER_CHIP:
        return None, unknown
    source = "datasheet-v5e-hbm" if row == TPU_V5E else "nominal-cpu-default"
    return HBM_PER_CHIP[row], source


def interconnect_bytes_per_sec(platform, device_kind=None):
    """-> ``(bytes_per_sec, source)`` per chip; ``(None, source)`` when
    unknown. v5e: the aggregate ICI figure; CPU: a nominal loopback
    figure (emulated-mesh collectives are memcpys); env override for DCN
    or anything else."""
    env = os.environ.get(ENV_BW)
    if env:
        return float(env), f"env:{ENV_BW}"
    row, unknown = device_row(platform, device_kind)
    if row not in INTERCONNECT_BYTES_PER_SEC:
        return None, unknown
    source = "datasheet-v5e-ici" if row == TPU_V5E else "nominal-cpu-default"
    return INTERCONNECT_BYTES_PER_SEC[row], source


def zero_peak_forecast(spec, dp, pp, tp=1, state_parts=0, num_chunks=None):
    """The analytical per-device PARAM-STATE footprint at every ZeRO
    stage — the structural model behind the OOM-forecast headroom claim
    ("params + grads + state ÷ dp"), priced from the SAME layout math the
    executor shards with (``gradsync.stacked_flat_len`` /
    ``zero_block_slots``), so the forecast and the emitters can never
    disagree about a shard's bytes.

    Per stage: ``params_bytes`` (at rest), ``grads_bytes`` (the persistent
    gradient residency — full slabs at stages 0-1, the reduce-scattered
    shard at 2-3), ``state_bytes`` (``state_parts`` optimizer parts, full
    or sharded), ``transient_bytes`` (stage 3 only: one chunk's gathered
    params live inside a tick), and their ``total_bytes``.

    All figures are f32 model-state bytes per device — activations,
    mailboxes and XLA temps ride on top, so the measured ``peak_hbm_bytes`` exceeds the
    forecast by a (stage-independent) activation floor; what the forecast
    prices is the DELTA between stages, which is what the bench
    scoreboard verifies against measurements."""
    from shallowspeed_tpu.parallel.executor import (
        stacked_flat_len,
        zero_block_slots,
    )

    f = 4 * stacked_flat_len(spec, pp, tp)  # per-device stacked f32 bytes
    _, csz3 = zero_block_slots(spec, pp, dp, tp)
    shard = 4 * csz3  # the padded block-cyclic per-rank shard
    n = int(state_parts)
    chunks = int(num_chunks) if num_chunks else 1
    # string stage keys: the record round-trips through JSON (json turns
    # int keys into strings anyway — be the same shape before and after)
    stages = {
        "0": {"params_bytes": f, "grads_bytes": f, "state_bytes": n * f,
              "transient_bytes": 0},
        "1": {"params_bytes": f, "grads_bytes": f, "state_bytes": n * shard,
              "transient_bytes": 0},
        "2": {"params_bytes": f, "grads_bytes": shard,
              "state_bytes": n * shard, "transient_bytes": 0},
        "3": {"params_bytes": shard, "grads_bytes": shard,
              "state_bytes": n * shard,
              # JIT gathering keeps ONE chunk's params live at a time
              "transient_bytes": -(-f // chunks)},
    }
    for s in stages.values():
        s["total_bytes"] = (
            s["params_bytes"] + s["grads_bytes"] + s["state_bytes"]
            + s["transient_bytes"]
        )
    return {
        "stacked_param_bytes_per_device": f,
        "shard_bytes_per_device": shard,
        "state_parts": n,
        "stages": stages,
    }


def expected_comms(
    spec,
    dp,
    pp,
    prog=None,
    zero1=False,
    zero=None,
    mubatch_size=None,
    platform="cpu",
    precision="highest",
    tp=1,
    opt_state_parts=0,
    device_kind=None,
):
    """The layout's analytical comms contract, derived from the model spec
    and (on mesh layouts) the LOWERED tick tables — the numbers the
    compiled program is audited against, and the comms section of the run
    report.

    ``prog`` may be a TRAINING tick program (the default contract below) or
    an INFERENCE one (``prog.is_training`` False — the serving engine's
    compiled predict programs): inference keeps the pp-relay leg but
    forbids the ZeRO collectives outright, and pins ``all_reduce`` at AT
    MOST ONE op — the lawful preds psum (which survives compilation even
    at pp=1, measured) — so a serving program that lowers a gradient-sync
    reduce-scatter/all-gather, or a SECOND all-reduce beyond the preds
    psum, fails its audit before the first request is served.

    Returns a JSON-able dict:

    - ``required`` / ``forbidden``: collective kinds the layout's contract
      demands present / absent (see the module docstring for the
      semantics; ``check_census`` enforces them);
    - ``axes``: per-mesh-axis expected traffic, bytes PER DEVICE PER
      OPTIMIZER STEP (one global batch):

      * ``pp`` (pp > 1 only — at pp == 1 no device pair ever sends):
        one ppermute per direction, issued in the ticks in which some
        device has a payload due in it, over the pairs that ever send;
        payload ``mubatch_size x relay_width`` f32 — wire bytes are
        ``issued relays x payload`` on the busiest device, from the
        ACTUAL tick tables (``lowering.program_comm_bytes``): within an
        issued relay the devices with nothing due ship zero payloads and
        are counted, a tick with nothing due in a direction ships nothing;
        the useful (send-table) bytes ride alongside, and
        ``hlo_min_permute_ops`` is the number of directions that ever
        send, which ``check_census`` demands of the compiled program;
      * ``dp`` (no zero1): the gradient psum as a ring all-reduce —
        ``2 * (dp-1)/dp x grad_bytes`` where ``grad_bytes`` is this
        device's PADDED stacked gradient (slot stacks x 4 bytes);
      * ``dp`` (zero1): reduce-scatter + all-gather of the padded flat
        param vector, ``2 * (dp-1)/dp x flat_bytes``;

      the dp axis entry comes from ``gradsync.sync_comm_bytes``;

      * ``tp`` (tp > 1 only): the Megatron all-reduces — one psum over
        'tp' per row-parallel slot forward (plus the closing gather when
        the last slot is column-parallel) and one per column-parallel
        slot backward, i.e. 2 per layer pair per fwd+bwd pass. Site
        widths come from ``executor.tp_allreduce_sites`` (the REAL
        tp-rounded activation shapes), the per-step dynamic bytes from
        the tick program's cell counts (every (device, chunk) stage runs
        M microbatch passes per step), and ``hlo_min_all_reduce_ops`` is
        the STRUCTURAL floor ``check_census`` enforces: the compiled
        program must hold at least that many all-reduce ops (each psum
        site is a distinct op inside its tick branch; the dp sync, loss
        and norm reductions only add more). The tp gradient sync is
        deliberately absent — TP shards the weights, so the dp axis
        already moves 1/tp per device and no extra gradient collective
        exists over tp;

    - ``bytes_per_step_per_device``: the axes' total;
    - ``comms_time_per_step_s``: bandwidth-bound lower bound at the
      platform's interconnect peak (with provenance);
    - ``compute_time_per_step_s``: per-device padded-FLOP lower bound at
      the platform's matmul peak (``costmodel.peak_flops_per_chip``);
    - ``bound``: ``"comms"`` / ``"compute"`` — which lower bound dominates
      (None when either peak is unknown);
    - ``serial_bound_s`` / ``overlapped_bound_s``: the two step-time lower
      bounds — ``comm + compute`` prices the anchor sync (no gradient
      communication can start until the whole backward ends, nothing
      overlaps), ``max(comm, compute)`` a perfectly overlapped one; their
      gap is the overlap headroom, and ``model_hidden_comm_share`` (``min(comm,
      compute) / comm``) is the share of communication a perfect overlap
      hides — the model-side number next to the MEASURED overlap
      efficiency the report derives from a trace's comm/compute split.
    """
    if zero is None:
        zero = 1 if zero1 else 0
    zero = int(zero)
    sequential = prog is None
    axes = {}
    required, forbidden = [], []
    if sequential:
        # one device, one program: ANY collective is a contract violation
        forbidden = [k.replace("-", "_") for k in COLLECTIVE_KINDS]
        flops_per_step = train_flops_per_sample(spec) * spec.global_batch_size
    else:
        from shallowspeed_tpu.parallel.lowering import (
            program_comm_bytes,
            program_flops,
        )

        forbidden.append("all_to_all")
        inference = not prog.is_training
        if tp > 1:
            # the Megatron axis: its all-reduces exist in BOTH training and
            # inference programs (forward row-slot psums survive either
            # way), so the kind is required and a structural op-count floor
            # rides the axis entry for check_census
            from shallowspeed_tpu.parallel.executor import tp_allreduce_sites

            fwd_w, bwd_w = tp_allreduce_sites(spec, tp, training=not inference)
            cells = prog.num_chunks * prog.num_micro_batches
            # activation recompute re-runs the whole stage forward inside
            # the backward tick: every forward psum site fires TWICE per
            # (chunk, microbatch) — the comms side of the recompute tax —
            # and the OP_RECOMPUTE switch branch holds its own copy of the
            # forward psum ops, raising the structural op-count floor
            rec = bool(getattr(prog, "recompute", False))
            fwd_passes = 2 if rec else 1
            payload = 4 * mubatch_size * cells * (
                fwd_passes * sum(fwd_w) + sum(bwd_w)
            )
            axes["tp"] = {
                "kind": "all_reduce",
                "algorithm": "ring",
                "sites_fwd": len(fwd_w),
                "sites_bwd": len(bwd_w),
                "site_payload_bytes": [
                    4 * mubatch_size * w for w in list(fwd_w) + list(bwd_w)
                ],
                "allreduce_bytes_per_device": int(payload),
                "bytes_per_step_per_device": int(2 * (tp - 1) / tp * payload),
                "hlo_min_all_reduce_ops": (
                    fwd_passes * len(fwd_w) + len(bwd_w)
                ),
            }
            required.append("all_reduce")
        if pp > 1:
            # only a real pipeline axis relays: at pp == 1 no device pair
            # ever sends, and the executor emits no permute at all
            required.append("collective_permute")
            comm = program_comm_bytes(prog, spec, mubatch_size)
            # the relays follow the send tables: one permute per direction
            # that ever sends (an inference program has no backward one),
            # issued in that direction's due ticks over its sending pairs
            axes["pp"] = {
                "kind": "collective_permute",
                "ticks": comm["num_ticks"],
                "hlo_min_permute_ops": sum(
                    n > 0
                    for n in (comm["relays_issued_fwd"], comm["relays_issued_bwd"])
                ),
                "payload_bytes": comm["relay_payload_bytes"],
                "bytes_per_step_per_device": comm["wire_bytes_per_device"],
                "useful_bytes_per_step_per_device": comm["useful_bytes_per_device"],
            }
        if inference:
            # inference/serving program: a forward-only relay plus ONE
            # lawful reduction — the head stage's predictions are
            # psum-replicated over pp (executor: `lax.psum(preds, "pp")`;
            # non-head devices contribute zeros), required at pp > 1 and
            # allowed-but-degenerate at pp == 1. The ZeRO collectives are
            # training-only: a reduce-scatter or all-gather in a serving
            # program means the training lowering leaked into the
            # inference path.
            forbidden += ["reduce_scatter", "all_gather"]
            if pp > 1:
                required.append("all_reduce")
                from shallowspeed_tpu.parallel.executor import slot_shapes

                # the executor psums the PADDED head width — tp-rounded
                # when a tp axis is active (slot dims round to tp
                # multiples), so the contract sizes what really moves
                preds_bytes = (
                    4
                    * prog.num_micro_batches
                    * mubatch_size
                    * slot_shapes(spec, tp)[-1][0]
                )
                axes["preds"] = {
                    "kind": "all_reduce",
                    "bytes_per_step_per_device": int(
                        2 * (pp - 1) / pp * preds_bytes
                    ),
                }
        else:
            from shallowspeed_tpu.parallel.gradsync import sync_comm_bytes

            if zero >= 1:
                # every sharded stage lowers both collectives, dp=1
                # included: stages 1-2 in the tail (reduce-scatter the
                # grads / shards, all-gather the updated chunk), stage 3
                # per tick (reduce-scatter into the grad-shard carry,
                # all-gather the layer params just in time)
                required += ["reduce_scatter", "all_gather"]
            else:
                forbidden += ["reduce_scatter", "all_gather"]
                if dp > 1:
                    # "the DP all-reduce really is one psum": the kind
                    # must be there (leaf-count fusion makes exact op
                    # counts compiler noise — see the module docstring)
                    required.append("all_reduce")
            # the dp-axis byte model (the tail anchor, or the stage-2/3
            # per-tick schedule) has ONE definition:
            # gradsync.sync_comm_bytes. Stage 3's
            # gather traffic scales with the microbatch passes — recompute
            # re-gathers the layer params inside the backward tick, a
            # third pass per (chunk, microbatch)
            axes["dp"] = sync_comm_bytes(
                spec, dp, pp, zero=zero, tp=tp,
                mubatches=prog.num_micro_batches,
                gather_passes=(
                    3 if getattr(prog, "recompute", False) else 2
                ),
            )
        # per-device padded compute: the tick program's FLOPs are the whole
        # pp x tp group's; SPMD uniformity (and the Megatron shards) split
        # them evenly across devices
        flops_per_step = program_flops(prog, spec, mubatch_size, tp=tp) / (pp * tp)

    # a kind may be demanded by several axes (dp sync + tp psums are both
    # all-reduce); the contract lists it once
    required = list(dict.fromkeys(required))
    total = sum(a["bytes_per_step_per_device"] for a in axes.values())
    bw, bw_source = interconnect_bytes_per_sec(platform, device_kind)
    peak, peak_source = peak_flops_per_chip(platform, precision, device_kind)
    comms_t = (total / bw) if bw else None
    compute_t = (flops_per_step / peak) if peak else None
    bound = None
    serial_t = overlapped_t = hidden_share = None
    if comms_t is not None and compute_t is not None:
        bound = "comms" if comms_t > compute_t else "compute"
        # the two step-time lower bounds: the anchor's serial comm-then-
        # compute chain vs a perfectly overlapped sync
        serial_t = comms_t + compute_t
        overlapped_t = max(comms_t, compute_t)
        if comms_t > 0:
            hidden_share = min(comms_t, compute_t) / comms_t
    forecast = None
    if not sequential and prog.is_training:
        forecast = zero_peak_forecast(
            spec, dp, pp, tp=tp, state_parts=opt_state_parts,
            num_chunks=prog.num_chunks,
        )
    return {
        "dp": int(dp),
        "pp": int(pp),
        "tp": int(tp),
        "zero": zero,
        "zero1": zero == 1,
        "zero_forecast": forecast,
        "sequential": sequential,
        "inference": bool(prog is not None and not prog.is_training),
        "required": required,
        "forbidden": forbidden,
        "axes": axes,
        "bytes_per_step_per_device": total,
        "bandwidth_bytes_per_sec": bw,
        "bandwidth_source": bw_source,
        "comms_time_per_step_s": comms_t,
        "compute_flops_per_step_per_device": flops_per_step,
        "peak_flops_per_chip": peak,
        "peak_flops_source": peak_source,
        "compute_time_per_step_s": compute_t,
        "bound": bound,
        "serial_bound_s": serial_t,
        "overlapped_bound_s": overlapped_t,
        "model_hidden_comm_share": hidden_share,
    }


def check_census(census, expected):
    """Compare a compiled program's collective census against the layout
    contract. Returns a list of human-readable mismatch strings (empty =
    the census matches)."""
    mismatches = []
    for kind in expected.get("required", ()):
        if census.get(kind, {}).get("count", 0) < 1:
            mismatches.append(
                f"required collective {kind!r} is absent from the compiled "
                f"program (census: {sorted(census) or 'empty'})"
            )
    for kind in expected.get("forbidden", ()):
        n = census.get(kind, {}).get("count", 0)
        if n:
            mismatches.append(
                f"forbidden collective {kind!r} appears {n}x in the "
                "compiled program"
            )
    pp_axis = (expected.get("axes") or {}).get("pp") or {}
    want = pp_axis.get("hlo_min_permute_ops", 0)
    n = census.get("collective_permute", {}).get("count", 0)
    # one permute per direction in which the tick table ever sends: two for
    # a training pipeline, one for an inference one. Zero is the required-
    # kinds leg's to report.
    if 0 < n < want:
        mismatches.append(
            f"pipeline relay must permute in every direction its tick table "
            f"sends in (>= {want} collective-permutes); compiled program "
            f"has {n}"
        )
    tp_axis = (expected.get("axes") or {}).get("tp") or {}
    if expected.get("inference") and not tp_axis:
        # a forward-only program has exactly one lawful all-reduce — the
        # preds psum over pp (it survives compilation even at pp=1,
        # measured on the CPU backend) — so a second one means a
        # gradient-sync collective leaked into the serving path. Zero is
        # tolerated: a backend MAY elide the degenerate psum, and the
        # required-kinds leg above still demands it at pp > 1. At tp > 1
        # this exact pin is replaced by the tp-axis floor below (the
        # Megatron row-slot psums are lawful forward all-reduces); the
        # reduce-scatter/all-gather prohibition still catches a leaked
        # ZeRO gradient sync there.
        n = census.get("all_reduce", {}).get("count", 0)
        if n > 1:
            mismatches.append(
                "forward-only inference program must lower at most ONE "
                f"all-reduce (the preds psum); compiled program has {n} — "
                "a gradient sync leaked into the serving path"
            )
    if tp_axis:
        # the Megatron structural floor: each tp psum site is a distinct
        # all-reduce op inside its tick branch (HLO holds branch bodies
        # once); dp sync / loss / norm reductions only ADD ops, so a
        # census below the floor means the tp lowering dropped collectives
        need = int(tp_axis.get("hlo_min_all_reduce_ops", 0))
        n = census.get("all_reduce", {}).get("count", 0)
        if n < need:
            mismatches.append(
                f"tensor-parallel program must hold >= {need} all-reduce "
                f"ops ({tp_axis.get('sites_fwd')} forward + "
                f"{tp_axis.get('sites_bwd')} backward Megatron psum sites); "
                f"compiled program has {n}"
            )
        if expected.get("inference") and n > need + 1:
            # the forward-only UPPER pin survives tp: the lawful ops are
            # exactly the Megatron sites plus the one preds psum (the tp
            # psums form a dependency chain over distinct replica groups,
            # so no combiner can merge them) — anything beyond reads as a
            # leaked gradient all-reduce, same class the tp=1 at-most-one
            # pin catches
            mismatches.append(
                f"forward-only tensor-parallel program must lower at most "
                f"{need + 1} all-reduce ops ({need} Megatron sites + the "
                f"preds psum); compiled program has {n} — a gradient sync "
                "leaked into the serving path"
            )
    dp_axis = (expected.get("axes") or {}).get("dp") or {}
    need_ag = int(dp_axis.get("hlo_min_all_gather_ops", 0))
    if need_ag and expected.get("dp", 1) > 1:
        # the ZeRO-3 JIT-gather structural floor: every gather-bearing
        # tick branch (forward, backward, recompute) holds its own
        # all-gather ops in HLO (branch bodies lower once), and the tail
        # adds none — a census below the floor means a gather-bearing
        # branch lowered without its parameter gather
        n = census.get("all_gather", {}).get("count", 0)
        if n < need_ag:
            mismatches.append(
                f"zero-3 program must hold >= {need_ag} all-gather ops "
                "(one JIT parameter gather per gather-bearing tick "
                f"branch); compiled program has {n}"
            )
    return mismatches


def verify_census(census, expected, context="compiled program"):
    """``check_census`` that fails loudly — the tested layout invariant."""
    mismatches = check_census(census, expected)
    if mismatches:
        raise AuditMismatchError(
            f"{context}: collective census disagrees with the layout "
            "contract: " + "; ".join(mismatches)
        )


def audit_compiled(
    compiled, expected=None, platform=None, n_devices=1, device_kind=None
):
    """The full jit-time audit of one compiled program: collective census +
    memory analysis (+ the contract verdict when ``expected`` is given) —
    the field set of the schema-v3 ``xla_audit`` record.

    ``platform`` (with the chip's ``device_kind``) adds the HBM-capacity leg: ``memory_stats`` sizes are
    PER DEVICE (see its docstring), so ``peak_hbm_bytes`` is compared
    against one chip's capacity directly — no sharding approximation
    (``hbm_source`` carries the capacity's provenance, same honesty rule
    as the MFU peak).
    """
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — backend-optional surface
        text = None
    census = collective_census(text) if text else {}
    rec = {
        "hlo_available": text is not None,
        "census": census,
        "memory": memory_stats(compiled),
        "n_devices": int(n_devices),
    }
    if platform is not None:
        cap, src = hbm_per_chip(platform, device_kind)
        rec["platform"] = platform
        rec["device_kind"] = device_kind
        rec["hbm_per_chip"] = cap
        rec["hbm_source"] = src
        mem = rec["memory"]
        if cap and mem and mem.get("peak_hbm_bytes") is not None:
            rec["peak_hbm_per_chip_bytes"] = mem["peak_hbm_bytes"]
            rec["hbm_headroom_fraction"] = 1.0 - mem["peak_hbm_bytes"] / cap
    if expected is not None:
        mismatches = check_census(census, expected) if text else []
        rec["expected"] = expected
        rec["mismatches"] = mismatches
        # no HLO text -> nothing to audit; None, not a silent pass/fail
        rec["census_ok"] = (not mismatches) if text else None
    return rec


def format_bytes(n):
    """Human-readable byte count (shared by the report renderer)."""
    if n is None or not isinstance(n, (int, float)) or not math.isfinite(n):
        return "n/a"
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if abs(n) >= div:
            return f"{n / div:,.2f} {unit}"
    return f"{n:,.0f} B"


# ---------------------------------------------------------------------------
# The op index: every instruction of a compiled program, by name, with the
# scope the program gave it (observability/scopes.py) and a class
#
# A device trace names its events after HLO instructions (``fusion.194``,
# ``copy.148``). The index says what each of them IS: rules, in this order,
#   1. a fusion that holds a convolution/dot takes that member's scope (a
#      matmul with a relu epilogue is the matmul); otherwise
#   2. the instruction's own ``op_name`` has a known scope (a fusion carries
#      its root's ``op_name``) -> that scope's class;
#   3. data movement the compiler inserted carries no scope (``copy.148 =
#      f32[4,8192,2048]{1,2,0} copy(get-tuple-element(param))``): its operand
#      chain is followed back, across fusion, branch and loop boundaries, to
#      an entry parameter (named after the argument's pytree path) or to a
#      loop-carried value, which takes the class of the scoped instruction
#      that WRITES it in the loop body (the stash's ``.at[slot].set`` names
#      the stash); where the chain ends nowhere, a consumer's scope decides;
#   4. scalar integer/predicate results with no class -> ``control``;
# anything else is ``unattributed``. ``while``/``conditional``/``call`` are
# containers, and so is every COMPUTATION name: the v5e trace emits an event
# per executed branch computation (``region_4.7``), spanning the events of
# its instructions. The async halves (``slice-start``/``-done``,
# ``copy-start``/``-done``, ``async-*``) are NOT: they are the DMA's own time
# and have no events inside them.
# ---------------------------------------------------------------------------

_CONTAINER_OPCODES = ("while", "conditional", "call")
# opcodes that hand a value on unchanged (or re-laid-out): the chain of
# rule 3 walks through them
_PASS_THROUGH = (
    "copy", "copy-start", "copy-done", "slice-start", "slice-done", "bitcast",
    "reshape", "transpose", "convert", "optimization-barrier",
    "async-start", "async-update", "async-done",
)
# scope-less instructions that cut or join buffers: the chain walks through
# them too, trying each buffer they read (``_data_operands``)
_CUTS = ("fusion", "dynamic-slice", "slice", "pad", "concatenate", "custom-call")
_JOINS = ("fusion", "concatenate", "custom-call")
# opcodes rule 3 applies to when they carry no class of their own
_MOVES = _PASS_THROUGH + ("dynamic-update-slice", "broadcast") + _CUTS
_MATMUL_OPCODES = ("convolution", "dot")
# scopes that name a buffer, writes before reads: among a fusion's members
# they decide what a neighbouring copy moves (see _Origins._scoped)
_BUFFER_SCOPES = ("stash", "mail", "acc", "update", "batch", "unstash")

_INSTR_RE = re.compile(r"^\s+(?P<root>ROOT )?%?(?P<name>[^\s=]+) = (?P<rest>.*)$")
_COMP_RE = re.compile(r"^(?P<entry>ENTRY )?%?(?P<name>[^\s(]+) \(.*\) -> .*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALLED_RE = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
# entry parameter (pytree path of the argument) -> class
_ARGUMENT_CLASSES = (
    (("stacked", "params", "opt_state"), "update"),
    (("X", "Y", "x", "y", "xb", "yb"), "batch"),
    (("flags",), "control"),
)


def _balanced(text, start):
    """Index just past the parenthesis group opening at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse_instruction(line):
    m = _INSTR_RE.match(line)
    if not m:
        return None
    rest = m.group("rest")
    end = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    type_str, tail = rest[:end], rest[end:].lstrip()
    paren = tail.find("(")
    if paren < 0:
        return None
    close = _balanced(tail, paren)
    args, attrs = tail[paren + 1 : close - 1], tail[close:]
    op_name = _OP_NAME_RE.search(attrs)
    called = {}
    for key, comp in _CALLED_RE.findall(attrs):
        called[key] = comp
    branches = _BRANCHES_RE.search(attrs)
    if branches:
        called["branches"] = _OPERAND_RE.findall(branches.group(1)) or [
            b.strip() for b in branches.group(1).split(",")
        ]
    elif "true_computation" in called:
        # operand 0 is the predicate: true is the first argument's branch
        called["branches"] = [called["true_computation"], called["false_computation"]]
    index = re.search(r"\bindex=(\d+)", attrs)
    kind = re.search(r"\bkind=(k\w+)", attrs)
    opcode = tail[:paren]
    return {
        "name": m.group("name"),
        "root": bool(m.group("root")),
        "opcode": opcode,
        "type": type_str,
        "operands": _OPERAND_RE.findall(args),
        "number": int(args) if opcode == "parameter" and args.isdigit() else None,
        "index": int(index.group(1)) if index else None,
        "op_name": op_name.group(1).replace("\\'", "'") if op_name else "",
        "kind": kind.group(1) if kind else "",
        "called": called,
    }


def parse_hlo(hlo_text):
    """``(instructions by name, computations)`` of an HLO dump, where a
    computation is ``{"entry": bool, "instructions": [names], "root": name}``.
    Names are unique within a module; each instruction knows its
    ``computation``."""
    instrs, comps, current = {}, {}, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMP_RE.match(line)
            if m:
                current = m.group("name")
                comps[current] = {
                    "entry": bool(m.group("entry")), "instructions": [], "root": None,
                }
            continue
        if line.startswith("}"):
            current = None
            continue
        ins = _parse_instruction(line)
        if ins is None:
            continue
        ins["computation"] = current
        instrs.setdefault(ins["name"], ins)
        comps[current]["instructions"].append(ins["name"])
        if ins["root"]:
            comps[current]["root"] = ins["name"]
    return instrs, comps


def _data_operands(ins):
    """The operands that are buffers the instruction moves: all of a fusion's
    or a join's, the first of anything else (the rest are indices, pad
    values, update slices)."""
    return ins["operands"] if ins["opcode"] in _JOINS else ins["operands"][:1]


def _is_control_type(type_str):
    """Integer or predicate results of at most a tick table's size."""
    shapes = _SHAPE_RE.findall(type_str)
    return (
        bool(shapes)
        and all(dtype[0] in "su" or dtype == "pred" for dtype, _ in shapes)
        and sum(_shape_bytes_each(type_str)) <= 1024
    )


def _argument_class(path):
    head = re.split(r"[\[.']", path, maxsplit=1)[0]
    for names, cls in _ARGUMENT_CLASSES:
        if head in names:
            return cls
    return None


class _Origins:
    """Rule 3: where a moved value comes from and where it goes.

    ``of(name)`` follows the operand chain back, across fusion, branch and
    loop boundaries, to ``(class, what, firm)``: an entry parameter (``what``
    its pytree path), a loop-carried value (``what`` is ``<while>#<k>``,
    classed by the scoped instruction that writes it in the body) — both
    ``firm`` — or merely the scoped instruction that produced the value.
    ``sink(name)`` follows the users forward to the first scoped consumer,
    or into the loop-carried value the result initialises."""

    def __init__(self, instrs, comps, scope_of):
        self.instrs, self.comps, self.scope_of = instrs, comps, scope_of
        self.callers = {}  # computation -> (calling instruction, role, position)
        self.users = {}  # name -> [(using instruction, operand position)]
        self.params = {}  # computation -> {number: parameter instruction}
        for ins in instrs.values():
            called = ins["called"]
            for key in ("calls", "to_apply", "body", "condition"):
                if key in called:
                    self.callers[called[key]] = (ins, key, None)
            for b, comp in enumerate(called.get("branches", ())):
                self.callers[comp] = (ins, "branch", b)
            for position, operand in enumerate(ins["operands"]):
                self.users.setdefault(operand, []).append((ins, position))
            if ins["opcode"] == "parameter":
                self.params.setdefault(ins["computation"], {})[ins["number"] or 0] = ins
        self._memo = {}

    def _scoped(self, ins):
        """``(class, scope, False)`` of a scoped instruction as the producer
        or consumer of a BUFFER: a fusion that holds the stash's
        ``.at[slot].set`` beside the matmul that computes the value is, for
        the copy next to it, the stash."""
        found = [self.scope_of(ins["op_name"])]
        if ins["opcode"] == "fusion":
            members = self.comps.get(ins["called"].get("calls"), {})
            found += [
                self.scope_of(self.instrs[n]["op_name"])
                for n in members.get("instructions", ())
            ]
        found = [(cls, scope) for scope, cls in found if cls]
        if not found:
            return None
        cls, scope = min(
            found,
            key=lambda cs: _BUFFER_SCOPES.index(cs[1])
            if cs[1] in _BUFFER_SCOPES
            else len(_BUFFER_SCOPES),
        )
        return (cls, scope, False)

    def _root(self, comp):
        return self.comps.get(comp, {}).get("root")

    # -- backwards ---------------------------------------------------------

    def of(self, name, index=None, seen=frozenset()):
        key = (name, index)
        if key in self._memo:
            return self._memo[key]
        if key in seen or name not in self.instrs:
            return None
        found = self._walk(self.instrs[name], index, seen | {key})
        if not seen:  # only a walk that started here saw every path
            self._memo[key] = found
        return found

    def _walk(self, ins, index, seen):
        op, operands = ins["opcode"], ins["operands"]
        if op == "get-tuple-element":
            return self.of(operands[0], ins["index"], seen)
        if op == "tuple" and index is not None and index < len(operands):
            return self.of(operands[index], None, seen)
        if op == "parameter":
            return self._parameter(ins, index, seen)
        if op == "while" and index is not None:
            return self._carried(ins, index, seen)
        if op == "conditional" and index is not None:
            for comp in ins["called"].get("branches", ()):
                found = self.of(self._root(comp), index, seen)
                if found:
                    return found
            return None
        scoped = self._scoped(ins)
        if scoped:
            return scoped
        if op == "fusion" and index is not None:
            return self.of(self._root(ins["called"].get("calls")), index, seen)
        if op in _PASS_THROUGH or op in _CUTS:
            walks = (self.of(o, None, seen) for o in _data_operands(ins))
            return next(filter(None, walks), None)
        return None

    def _parameter(self, ins, index, seen):
        comp = ins["computation"]
        if self.comps[comp]["entry"]:
            cls = _argument_class(ins["op_name"])
            return (cls, f"arg {ins['op_name']}", True) if cls else None
        caller, role, position = self.callers.get(comp, (None, None, None))
        if caller is None:
            return None
        if role in ("body", "condition"):
            return self._carried(caller, index, seen) if index is not None else None
        if role == "branch":  # operand 0 selects; operand 1 + b feeds branch b
            if position + 1 >= len(caller["operands"]):
                return None
            return self.of(caller["operands"][position + 1], index, seen)
        number = ins["number"] or 0
        if number < len(caller["operands"]):
            return self.of(caller["operands"][number], index, seen)
        return None

    def _carried(self, loop, k, seen):
        """Element ``k`` of a loop's carried tuple: the class of what writes
        it in the body; where the body hands it on unchanged, of what it was
        initialised from."""
        found = self.of(self._root(loop["called"].get("body")), k, seen)
        if not found and loop["operands"]:
            found = self.of(loop["operands"][0], k, seen)
        if not found:
            return None
        what = found[1] if found[2] else f"{loop['name']}#{k} {found[1]}"
        return (found[0], what, True)

    # -- forwards ----------------------------------------------------------

    def sink(self, name, seen=frozenset()):
        if name in seen or len(seen) > 16:
            return None
        seen = seen | {name}
        for user, position in self.users.get(name, ()):
            op = user["opcode"]
            found = self._scoped(user)
            if found is None and op == "tuple":
                found = self._sink_element(user, position, seen)
            elif found is None and (
                op in _PASS_THROUGH
                or op in ("custom-call", "get-tuple-element", "fusion")
            ):
                found = self.sink(user["name"], seen)
            if found:
                return found
        return None

    def _sink_readers(self, holder, k, seen):
        """The consumers of element ``k`` of the tuple ``holder`` yields."""
        for user, _ in self.users.get(holder, ()):
            if user["opcode"] == "get-tuple-element" and user["index"] == k:
                found = self.sink(user["name"], seen)
                if found:
                    return found
        return None

    def _sink_element(self, tup, k, seen):
        """Where element ``k`` of a tuple goes: out of a branch (to the
        conditional's readers), round a loop (to the body's readers), or
        into a loop or a branch as its argument."""
        caller, role, _ = self.callers.get(tup["computation"], (None, None, None))
        if tup["root"] and role == "branch":
            return self._sink_readers(caller["name"], k, seen)
        if tup["root"] and role == "body":
            param = self.params.get(tup["computation"], {}).get(0)
            return param and self._sink_readers(param["name"], k, seen)
        for user, position in self.users.get(tup["name"], ()):
            if user["opcode"] == "while":
                return self._carried(user, k, frozenset())
            if user["opcode"] == "conditional" and position >= 1:
                branch = user["called"].get("branches", ())[position - 1 : position]
                param = branch and self.params.get(branch[0], {}).get(0)
                found = param and self._sink_readers(param["name"], k, seen)
                if found:
                    return found
        return None

    def moved(self, ins):
        """Rule 3 for one scope-less instruction: ``(class, via)`` or
        ``None``. Firm origins first, then a consumer, then a producer. Data
        staged for or taken from a relay is the mailbox's traffic: ``relay``
        is the collective-permute alone."""
        found = next(filter(None, map(self.of, _data_operands(ins))), None)
        if not (found and found[2]):
            consumer = self.sink(ins["name"])
            if consumer:
                found = (consumer[0], f"to {consumer[1]}", consumer[2])
            elif found:
                found = (found[0], f"from {found[1]}", False)
        if not found:
            return None
        return ("mailbox" if found[0] == "relay" else found[0], found[1])


def _result_bytes(ins):
    """Bytes an instruction yields. An async collective's ``-start`` tuple
    pairs operands with results (``_shape_bytes``); a DMA's (``copy-start``,
    ``slice-start``: destination, source and a context word, in either order)
    moves the smaller of its two buffers."""
    opcode = ins["opcode"]
    if opcode in ("copy-start", "slice-start", "async-start"):
        sizes = _shape_bytes_each(ins["type"])
        if len(sizes) == 3:
            return min(sizes[:2])
    return _shape_bytes(ins["type"], async_start=opcode.endswith("-start"))


def op_index(hlo_text):
    """``{instruction name: entry}`` for every instruction of every
    computation in a post-optimization HLO dump (``Compiled.as_text()``),
    plus one ``container`` entry per computation name. An entry holds
    ``opcode``, result ``bytes``, ``type`` (the result type, layouts cut),
    ``scope``, ``cls``, ``computation``, ``container`` (``while`` /
    ``conditional`` / ``call`` and computation names) and, where they say
    something, ``kind`` (a fusion's), ``mixed`` (a fusion whose members span
    more than one class) and ``via`` (rule 3: the argument or the
    loop-carried value the instruction moves). The rules are in the section
    comment above. Classes: those of ``observability/scopes.py`` plus
    ``control``, ``container``, ``unattributed``."""
    from shallowspeed_tpu.observability.scopes import scope_of

    instrs, comps = parse_hlo(hlo_text)
    origins = _Origins(instrs, comps, scope_of)
    fused = {
        ins["called"]["calls"]
        for ins in instrs.values()
        if ins["opcode"] == "fusion" and "calls" in ins["called"]
    }

    index = {}
    for ins in instrs.values():
        opcode = ins["opcode"]
        scope, cls = scope_of(ins["op_name"])
        entry = {
            "opcode": opcode,
            "bytes": _result_bytes(ins),
            "type": re.sub(r"\{[^{}]*\}", "", ins["type"])[:120],
            "scope": scope,
            "computation": ins["computation"],
            "container": opcode in _CONTAINER_OPCODES,
        }
        if entry["container"]:
            entry["cls"] = "container"
            index[ins["name"]] = entry
            continue
        if opcode == "fusion":
            members = [
                instrs[n]
                for n in comps.get(ins["called"].get("calls"), {}).get("instructions", ())
            ]
            member_scopes = [scope_of(m["op_name"]) for m in members]
            classes = {c for _, c in member_scopes if c}
            if len(classes) > 1:
                entry["mixed"] = sorted(classes)
            for member, (m_scope, m_cls) in zip(members, member_scopes):
                if member["opcode"] in _MATMUL_OPCODES and m_cls:
                    scope, cls = m_scope, m_cls
                    break
            if ins["kind"]:
                entry["kind"] = ins["kind"]
        if cls is None and opcode in _MOVES and ins["computation"] not in fused:
            found = origins.moved(ins)
            if found:
                cls, entry["via"] = found
        if cls is None and _is_control_type(ins["type"]):
            cls = "control"
        entry["scope"], entry["cls"] = scope, cls or "unattributed"
        index[ins["name"]] = entry
    for name in comps:
        index.setdefault(
            name,
            {
                "opcode": "computation", "bytes": 0, "type": "", "scope": None,
                "cls": "container", "computation": name, "container": True,
            },
        )
    return index
