"""Bucketed gradient synchronization: the DP sync as a PLANNED op sequence.

The reference's headline DP feature is a *computation-overlapped,
non-blocking* gradient all-reduce: its engine (pipe.py:302-327) issues one
MPI ``Iallreduce`` per parameter as soon as that parameter's backward
finishes, output layer first, and its docstring wishes it could bucket
small tensors together. Our executor historically collapsed all of that
into ONE whole-tree ``lax.psum`` at the ``BackwardGradAllReduce`` anchor —
correct, but a single fat dependency: XLA cannot start any gradient
communication until every leaf is ready, and nothing downstream (clip
norm, the optimizer update) can start until the whole sync returns.

This module restores the reference's structure in SPMD form. A
``BucketPlan`` greedily packs the per-device gradient leaves into byte-
bounded buckets in BACKWARD order (output layer first — the order the tick
loop finalizes them), and the emitters issue one collective per bucket:

- plain DP (``zero=0``): each bucket's leaves are flattened into one
  contiguous vector and ``lax.psum``'d — one all-reduce op per bucket in
  the compiled program (verified by the program audit's census contract).
  Buckets have no data dependence on each other, so XLA's latency-hiding
  scheduler is free to overlap bucket k's all-reduce with the consumers of
  already-synced buckets (norm partials, the elementwise update of their
  params);
- ZeRO-1: the padded flat gradient is viewed as a ``(dp, chunk)`` matrix
  (row d = the chunk replica d updates) and each bucket is a COLUMN range,
  reduce-scattered with ``scatter_dimension=0, tiled=False`` — every
  device receives exactly the same contiguous chunk slice the anchor
  layout gives it, so the optimizer-state layout, the checkpoint mapping
  and the single deferred ``all_gather`` of the updated chunk are all
  untouched by bucketing;
- ZeRO-2 (bucketed): asking for ``grad_bucket_bytes`` at stage 2 keeps
  the FULL-slab gradient accumulators through the scan (that is what
  keeps the tail sync bitwise-equal to zero-1 at any microbatch count)
  and buckets the tail reduce-scatter: each slot's slab deals into its
  own ``(dp, V*k)`` column-block matrix — executor's block-cyclic
  layout — so each bucket is a ``(slot, start, stop)`` column range of
  one slot's matrix, emitted in the same backward order. Concatenating a
  slot's bucket outputs reproduces the anchor shard segment exactly.
  The ANCHOR stage-2 program (no bucket plan) instead earns the grads÷dp
  residency row by reduce-scattering PER TICK into a persistent
  per-rank shard carry — sharing ZeRO-3's per-slot scatter emitter, and
  trading the reassociated (dp x microbatch) sum order for it (bitwise
  vs zero-1 only at ``mubatches=1``; see docs/performance.md);
- ZeRO-3 has nothing for this module to plan: the gradient reduce-scatter
  happens PER TICK inside the scan (one collective per layer slot as its
  backward finishes — the reference's per-parameter Iallreduce, finally
  literal), so the executor refuses ``grad_bucket_bytes`` at stage 3 and
  ``sync_comm_bytes`` prices the per-tick schedule analytically instead.

Numerics contract: ``psum``/``psum_scatter`` reduce ELEMENTWISE, and
flatten/concat/slice are exact data movement, so per-bucket sync is
**bitwise identical** to the same tail collective unbucketed — the
NumPy-oracle parity and cross-layout fuzz tests run unchanged over every
bucket size (tests/test_gradsync.py asserts the bit-equality directly).
At stage 2 the bucketed program's bitwise peer is ZERO-1 (both sum
dp-outer in full slabs), not the anchor stage-2 program, whose per-tick
scatter sums microbatch-outer. ``bucket_bytes
= 0`` disables planning entirely: the executor keeps its legacy anchor
collective, same program byte for byte.

The plan is pure host data (derived deterministically from the model spec
and the knob), so the executor, the TrainingSession audit contract
(observability/program_audit.expected_comms) and the bench rows all build
the SAME plan and can never disagree about bucket count or sizes.
"""

import dataclasses

import jax.numpy as jnp
from jax import lax

from shallowspeed_tpu.observability.scopes import scoped


@dataclasses.dataclass(frozen=True)
class BucketLeaf:
    """One gradient leaf of the executor's per-device stacked tree."""

    kind: str  # "W" | "b"
    slot: int  # layer-slot index (executor.slot_shapes order)
    shape: tuple  # per-device stacked shape: (V, o, i) for W, (V, o) for b

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= int(d)
        return n

    @property
    def nbytes(self):
        return 4 * self.size  # f32 gradients


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """A static bucketing of one layout's gradient sync.

    ``mode="dp"``: ``buckets`` is a tuple of leaf groups (each a tuple of
    ``BucketLeaf``), in backward order — the emitter issues one flat
    ``psum`` per group. ``mode="zero1"``: ``buckets`` is a tuple of
    ``(start, stop)`` column ranges over the per-replica chunk — the
    emitter issues one ``psum_scatter`` per range (``dp`` records the
    replica count the ranges were planned for). ``mode="zero2"``:
    ``buckets`` is a tuple of ``(slot_index, start, stop)`` column ranges
    over one slot's ``(dp, V*k)`` block-cyclic matrix
    (``executor.zero_block_slots`` order), emitted in backward order.
    """

    mode: str  # "dp" | "zero1" | "zero2"
    bucket_bytes: int  # the --grad-bucket-bytes knob that built the plan
    buckets: tuple
    dp: int = 1  # zero1/zero2: replicas (census result bytes = grad / dp)

    @property
    def num_buckets(self):
        return len(self.buckets)

    def bucket_grad_bytes(self):
        """Per-bucket synced-gradient payload in bytes (what the byte
        budget bounds): the full leaf bytes for DP buckets, ``dp x width``
        scattered columns for ZeRO-1/2 buckets."""
        if self.mode == "dp":
            return [sum(l.nbytes for l in group) for group in self.buckets]
        if self.mode == "zero2":
            return [4 * self.dp * (b - a) for _, a, b in self.buckets]
        return [4 * self.dp * (b - a) for a, b in self.buckets]

    def bucket_census_bytes(self):
        """Per-bucket expected HLO RESULT bytes — what the program audit
        matches against ``parse_collectives``: an all-reduce returns the
        full bucket on every device; a reduce-scatter returns 1/dp of it."""
        if self.mode == "dp":
            return self.bucket_grad_bytes()
        if self.mode == "zero2":
            return [4 * (b - a) for _, a, b in self.buckets]
        return [4 * (b - a) for a, b in self.buckets]

    def total_grad_bytes(self):
        return sum(self.bucket_grad_bytes())

    def describe(self):
        """JSON-able plan summary (metrics / bench record lines)."""
        return {
            "mode": self.mode,
            "grad_bucket_bytes": int(self.bucket_bytes),
            "num_buckets": self.num_buckets,
            "bucket_grad_bytes": self.bucket_grad_bytes(),
            "bucket_census_bytes": self.bucket_census_bytes(),
            "total_grad_bytes": self.total_grad_bytes(),
        }


def _stacked_leaves(spec, pp, tp=1):
    """The executor's per-device gradient leaves in BACKWARD order: the
    tick loop's ``_stage_bwd`` finalizes slot L-1 (the output layer) first
    and computes each slot's dW and db together, so the bucket order is
    [W_{L-1}, b_{L-1}, ..., W_0, b_0]. Under tp the leaves are this rank's
    Megatron shards (``executor.tp_local_dims``) — the dp sync moves 1/tp
    of the gradient per device, which is the TP memory/bandwidth story the
    comms model quotes."""
    from shallowspeed_tpu.parallel.executor import slot_shapes, tp_local_dims

    dims = slot_shapes(spec, tp)
    w_dims, b_widths, _, _ = tp_local_dims(dims, tp)
    V = spec.n_stages // pp
    leaves = []
    for l in reversed(range(len(dims))):
        o, i = w_dims[l]
        leaves.append(BucketLeaf("W", l, (V, o, i)))
        leaves.append(BucketLeaf("b", l, (V, b_widths[l])))
    return leaves


def plan_dp_buckets(spec, pp, bucket_bytes, tp=1):
    """Greedy byte-bounded bucketing of the stacked gradient tree for the
    plain-DP all-reduce. Returns None when ``bucket_bytes`` is falsy (the
    legacy whole-tree anchor psum). Every leaf lands in exactly one
    bucket; backward order is preserved; a bucket is closed as soon as
    adding the next leaf would exceed the budget (a single oversized leaf
    still gets its own bucket — the plan never splits a leaf)."""
    if not bucket_bytes:
        return None
    bucket_bytes = int(bucket_bytes)
    buckets, current, current_bytes = [], [], 0
    for leaf in _stacked_leaves(spec, pp, tp):
        if current and current_bytes + leaf.nbytes > bucket_bytes:
            buckets.append(tuple(current))
            current, current_bytes = [], 0
        current.append(leaf)
        current_bytes += leaf.nbytes
    if current:
        buckets.append(tuple(current))
    return BucketPlan(mode="dp", bucket_bytes=bucket_bytes, buckets=tuple(buckets))


def plan_zero1_buckets(spec, dp, pp, bucket_bytes, tp=1):
    """Byte-bounded bucketing of the ZeRO-1 reduce-scatter: column ranges
    over the per-replica chunk of the padded flat gradient. Each bucket
    covers ``dp x width`` gradient elements (one width-slice of EVERY
    replica's chunk), so the scatter's output concatenation reproduces the
    anchor chunk exactly. Returns None when ``bucket_bytes`` is falsy."""
    if not bucket_bytes:
        return None
    bucket_bytes = int(bucket_bytes)
    from shallowspeed_tpu.parallel.executor import stacked_flat_len

    csz = -(-stacked_flat_len(spec, pp, tp) // dp)
    width = max(1, bucket_bytes // (4 * dp))
    ranges = tuple(
        (a, min(a + width, csz)) for a in range(0, csz, width)
    )
    return BucketPlan(
        mode="zero1", bucket_bytes=bucket_bytes, buckets=ranges, dp=int(dp)
    )


def plan_zero2_buckets(spec, dp, pp, bucket_bytes, tp=1):
    """Byte-bounded bucketing of the ZeRO-2 per-slot reduce-scatters:
    ``(slot_index, start, stop)`` column ranges over each slot's
    ``(dp, V*k)`` block-cyclic matrix, in BACKWARD emission order (the
    tick loop finalizes slot L-1 first, dW and db together — the same
    order the DP planner walks). Each bucket scatters ``dp x width``
    gradient elements; concatenating a slot's bucket outputs in ascending
    range order reproduces its anchor shard segment exactly. Returns None
    when ``bucket_bytes`` is falsy."""
    if not bucket_bytes:
        return None
    bucket_bytes = int(bucket_bytes)
    from shallowspeed_tpu.parallel.executor import zero_block_slots

    slots, _ = zero_block_slots(spec, pp, dp, tp)
    L = len(slots) // 2
    width = max(1, bucket_bytes // (4 * dp))
    buckets = []
    for l in reversed(range(L)):
        for si in (l, L + l):  # W_l then b_l, mirroring _stacked_leaves
            cols = slots[si].rows * slots[si].k
            for a in range(0, cols, width):
                buckets.append((si, a, min(a + width, cols)))
    return BucketPlan(
        mode="zero2", bucket_bytes=bucket_bytes, buckets=tuple(buckets),
        dp=int(dp),
    )


def plan_buckets(spec, dp, pp, bucket_bytes, zero1=False, zero=None, tp=1):
    """The one layout->plan dispatch: the executor's emitters, the
    session's audit contract and the bench rows all plan through here, so
    they can never pick different planners for the same layout. ``zero``
    selects the dp stage (``zero1`` kept as the stage-1 alias); stage 3
    has no plan — its sync is per tick. Returns None when
    ``bucket_bytes`` is falsy (the legacy anchor sync)."""
    if zero is None:
        zero = 1 if zero1 else 0
    zero = int(zero)
    if zero == 3:
        if bucket_bytes:
            raise ValueError(
                "zero=3 syncs gradients per tick — there is no tail "
                "collective to bucket (grad_bucket_bytes must be 0)"
            )
        return None
    if zero == 2:
        return plan_zero2_buckets(spec, dp, pp, bucket_bytes, tp=tp)
    if zero == 1 or zero1:
        return plan_zero1_buckets(spec, dp, pp, bucket_bytes, tp=tp)
    return plan_dp_buckets(spec, pp, bucket_bytes, tp=tp)


@scoped("sync/dp")
def psum_bucketed(grads, plan, axis_name="dp"):
    """Per-bucket DP gradient sync: for each bucket, flatten its leaves
    into ONE contiguous vector, ``lax.psum`` it (one all-reduce op per
    bucket in the compiled program), and scatter the summed values back
    into the tree. Elementwise reduction + exact data movement = bitwise
    identical to the whole-tree anchor psum.

    ``grads``: the executor's per-device ``{"W": tuple, "b": tuple}``.
    Returns the same structure, fully summed over ``axis_name``.
    """
    out = {"W": list(grads["W"]), "b": list(grads["b"])}
    for group in plan.buckets:
        flat = jnp.concatenate(
            [grads[l.kind][l.slot].reshape(-1) for l in group]
        )
        summed = lax.psum(flat, axis_name)
        off = 0
        for l in group:
            out[l.kind][l.slot] = summed[off : off + l.size].reshape(l.shape)
            off += l.size
    return {"W": tuple(out["W"]), "b": tuple(out["b"])}


@scoped("sync/dp")
def psum_scatter_bucketed(gvec_padded, plan, axis_name="dp"):
    """Per-bucket ZeRO-1 gradient sync: view the padded flat gradient as
    ``(dp, chunk)`` — row d is the contiguous chunk replica d updates —
    and reduce-scatter each COLUMN range with ``scatter_dimension=0,
    tiled=False`` (one reduce-scatter op per bucket). Concatenating the
    per-bucket outputs reproduces this replica's anchor chunk exactly
    (same elements, same order), so the chunked update, the optimizer-
    state layout and the deferred all_gather are untouched by bucketing.
    """
    csz = gvec_padded.shape[0] // plan.dp
    mat = gvec_padded.reshape(plan.dp, csz)
    pieces = [
        lax.psum_scatter(
            mat[:, a:b], axis_name, scatter_dimension=0, tiled=False
        )
        for a, b in plan.buckets
    ]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def sync_comm_bytes(
    spec, dp, pp, zero1=False, plan=None, tp=1, zero=None,
    mubatches=1, gather_passes=2,
):
    """The dp-axis leg of the analytical comms contract
    (observability/program_audit.expected_comms): ring-algorithm wire
    bytes PER DEVICE PER STEP for the gradient sync at every ZeRO stage,
    with the bucketing plan's per-collective breakdown when one is active.

    Stage 0 (plain DP): one all-reduce of the stacked gradient —
    ``2 (dp-1)/dp x 4*flat``. Stage 1 (ZeRO-1): reduce-scatter + deferred
    all-gather of the padded FLAT vector — the same ``2 (dp-1)/dp`` total
    over ``4*csz*dp`` (ring all-reduce IS RS+AG, so stages 0 and 1 tie on
    wire bytes). Stage 2 (ZeRO-2): the ANCHOR program reduce-scatters
    per tick into the persistent gradient shard (x ``mubatches``) and
    all-gathers the updated-param chunk once — the grad-sync leg proper
    moves HALF the anchor all-reduce's bytes per contribution (scatter
    results are 1/dp), paid once per microbatch; a BUCKETED stage-2 plan
    keeps the full-slab accumulators and the single byte-bucketed tail
    reduce-scatter (zero-1's wire total over the block-cyclic
    ``4*csz3*dp``). Stage 3 (ZeRO-3): the per-tick reduce-scatter plus
    ``gather_passes`` just-in-time param-gather sweeps per microbatch
    (forward + backward [+ recompute]) — the gather schedule MULTIPLIES
    dp traffic by the microbatch count, the price of never holding the
    params (quoted honestly; the win is memory, not wire bytes).

    Bucketing never changes a stage's TOTAL bytes — only how many ops
    carry them, which is exactly what the census contract verifies. Under
    tp each device syncs only its Megatron shard, so the dp payload
    shrinks by exactly tp (tensor parallelism composes with — never
    multiplies — the gradient-sync traffic).
    """
    from shallowspeed_tpu.parallel.executor import (
        stacked_flat_len,
        zero_block_slots,
    )

    if zero is None:
        zero = 1 if zero1 else 0
    zero = int(zero)
    flat = stacked_flat_len(spec, pp, tp)
    if zero >= 2:
        _, csz3 = zero_block_slots(spec, pp, dp, tp)
        payload = 4 * csz3 * dp  # the per-slot padded block-cyclic deal
        if zero == 3:
            M = int(mubatches)
            passes = int(gather_passes)
            rs_bytes = (dp - 1) / dp * M * payload
            ag_bytes = (dp - 1) / dp * M * passes * payload
            axis = {
                "kind": "reduce_scatter+all_gather",
                "algorithm": "ring",
                "grad_bytes_per_device": M * payload,
                "bytes_per_step_per_device": rs_bytes + ag_bytes,
                "reduce_scatter_bytes_per_step_per_device": rs_bytes,
                "scatter_schedule": "per_tick",
                "scatter_mubatches": M,
                "gather": {
                    "schedule": "per_tick",
                    "passes": passes,
                    "mubatches": M,
                    "bytes_per_step_per_device": ag_bytes,
                },
                # gathers live in distinct lax.switch branch computations
                # (forward / backward [/ recompute]) — XLA's combiners can
                # merge within a branch but never across branches, so the
                # compiled program must keep at least one per pass
                "hlo_min_all_gather_ops": passes,
            }
        elif plan is None:
            # anchor ZeRO-2: per-tick reduce-scatter into the persistent
            # shard (one contribution per microbatch), one deferred
            # all-gather of the updated-param chunk
            M = int(mubatches)
            rs_bytes = (dp - 1) / dp * M * payload
            ag_bytes = (dp - 1) / dp * payload
            axis = {
                "kind": "reduce_scatter+all_gather",
                "algorithm": "ring",
                "grad_bytes_per_device": M * payload,
                "bytes_per_step_per_device": rs_bytes + ag_bytes,
                "reduce_scatter_bytes_per_step_per_device": rs_bytes,
                "scatter_schedule": "per_tick",
                "scatter_mubatches": M,
            }
        else:
            # bucketed ZeRO-2: full-slab accumulators, one byte-bucketed
            # tail reduce-scatter + the deferred param all-gather —
            # zero-1's wire total over the block-cyclic payload
            axis = {
                "kind": "reduce_scatter+all_gather",
                "algorithm": "ring",
                "grad_bytes_per_device": payload,
                "bytes_per_step_per_device": 2 * (dp - 1) / dp * payload,
            }
    elif zero == 1:
        csz = -(-flat // dp)
        payload = 4 * csz * dp  # the padded flat vector
        axis = {
            "kind": "reduce_scatter+all_gather",
            "algorithm": "ring",
            "grad_bytes_per_device": payload,
            "bytes_per_step_per_device": 2 * (dp - 1) / dp * payload,
        }
    else:
        payload = 4 * flat  # this device's padded stacked gradient
        axis = {
            "kind": "all_reduce",
            "algorithm": "ring",
            "grad_bytes_per_device": payload,
            "bytes_per_step_per_device": 2 * (dp - 1) / dp * payload,
        }
    axis["zero"] = zero
    axis["mode"] = "anchor" if plan is None else "bucketed"
    if plan is not None:
        axis["grad_bucket_bytes"] = int(plan.bucket_bytes)
        axis["num_buckets"] = plan.num_buckets
        axis["bucket_grad_bytes"] = plan.bucket_grad_bytes()
        axis["bucket_census_bytes"] = plan.bucket_census_bytes()
    return axis
