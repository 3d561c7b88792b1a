"""The dp-axis byte model of the gradient synchronization.

The executor syncs gradients over the ``dp`` mesh axis with ONE tail
collective per ZeRO stage (``parallel/executor.make_pipeline_step``): a
whole-tree ``lax.psum`` at the ``BackwardGradAllReduce`` anchor (stage 0),
a reduce-scatter of the padded flat gradient plus a deferred all-gather of
the updated chunk (stage 1), or a per-tick reduce-scatter into a persistent
per-rank shard (stages 2-3, with stage 3's just-in-time parameter
gathers). This module prices that traffic: ``sync_comm_bytes`` is the one
definition of the bytes each device moves per optimizer step over ``dp``,
read by the audit contract
(``observability/program_audit.expected_comms``) and the run report.
"""


def sync_comm_bytes(
    spec, dp, pp, zero1=False, tp=1, zero=None, mubatches=1, gather_passes=2,
):
    """The dp-axis leg of the analytical comms contract
    (observability/program_audit.expected_comms): ring-algorithm wire
    bytes PER DEVICE PER STEP for the gradient sync at every ZeRO stage.

    Stage 0 (plain DP): one all-reduce of the stacked gradient —
    ``2 (dp-1)/dp x 4*flat``. Stage 1 (ZeRO-1): reduce-scatter + deferred
    all-gather of the padded FLAT vector — the same ``2 (dp-1)/dp`` total
    over ``4*csz*dp`` (ring all-reduce IS RS+AG, so stages 0 and 1 tie on
    wire bytes). Stage 2 (ZeRO-2): the program reduce-scatters per tick
    into the persistent gradient shard (x ``mubatches``) and all-gathers
    the updated-param chunk once — the grad-sync leg proper moves HALF
    the stage-0 all-reduce's bytes per contribution (scatter results are
    1/dp), paid once per microbatch, over the block-cyclic
    ``4*csz3*dp``. Stage 3 (ZeRO-3): the per-tick reduce-scatter plus
    ``gather_passes`` just-in-time param-gather sweeps per microbatch
    (forward + backward [+ recompute]) — the gather schedule MULTIPLIES
    dp traffic by the microbatch count, the price of never holding the
    params (quoted honestly; the win is memory, not wire bytes).

    Under tp each device syncs only its Megatron shard, so the dp payload
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
        else:
            # ZeRO-2: per-tick reduce-scatter into the persistent
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
    return axis
