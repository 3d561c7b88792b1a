"""The dp-axis byte model of the gradient sync (parallel/gradsync.py).

``sync_comm_bytes`` prices the ONE tail collective each ZeRO stage emits:
the ring totals per device per step, recomputed here from the stacked
layout's own sizes. The executor-level bit-equality of the layouts lives in
tests/test_fuzz_layouts.py and tests/test_zero23.py; the compiled census
against this model in tests/test_program_audit.py.
"""

import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu.parallel import gradsync
from shallowspeed_tpu.parallel.executor import (
    slot_shapes,
    stacked_flat_len,
    zero_block_slots,
)

SIZES = (48, 40, 36, 32, 28, 24, 18, 10)
DP, PP, M = 2, 2, 4


def _spec(pp=1, B=64):
    return Mo.make_model_spec(SIZES, pp, B)


@pytest.mark.parametrize("zero", [0, 1, 2, 3], ids=lambda z: f"zero{z}")
def test_sync_comm_bytes_anchor_model(zero):
    """Per stage: the collective kinds, the gradient payload and the ring
    wire bytes ``(dp-1)/dp x payload`` per reduce-scatter or all-gather
    leg, from the flat layout (stages 0-1) or the block-cyclic one (2-3)."""
    spec = _spec(pp=PP)
    dims = slot_shapes(spec)
    V = spec.n_stages // PP
    flat = sum(V * o * i for o, i in dims) + sum(V * o for o, _ in dims)
    assert stacked_flat_len(spec, PP) == flat
    axis = gradsync.sync_comm_bytes(
        spec, DP, PP, zero=zero, mubatches=M, gather_passes=2
    )
    assert axis["zero"] == zero and axis["algorithm"] == "ring"
    assert "mode" not in axis and "num_buckets" not in axis
    ring = (DP - 1) / DP
    if zero == 0:
        assert axis["kind"] == "all_reduce"
        assert axis["grad_bytes_per_device"] == 4 * flat
        assert axis["bytes_per_step_per_device"] == 2 * ring * 4 * flat
        return
    assert axis["kind"] == "reduce_scatter+all_gather"
    if zero == 1:
        payload = 4 * -(-flat // DP) * DP  # the padded flat vector
        assert axis["grad_bytes_per_device"] == payload
        assert axis["bytes_per_step_per_device"] == 2 * ring * payload
        return
    _, csz3 = zero_block_slots(spec, PP, DP, 1)
    payload = 4 * csz3 * DP
    # stages 2-3 scatter once per microbatch into the persistent shard
    assert axis["scatter_schedule"] == "per_tick"
    assert axis["scatter_mubatches"] == M
    assert axis["grad_bytes_per_device"] == M * payload
    scatter = ring * M * payload
    assert axis["reduce_scatter_bytes_per_step_per_device"] == scatter
    if zero == 2:
        # one deferred all-gather of the updated chunk
        assert "gather" not in axis
        assert axis["bytes_per_step_per_device"] == scatter + ring * payload
    else:
        gather = ring * M * 2 * payload  # forward + backward, per microbatch
        assert axis["gather"]["bytes_per_step_per_device"] == gather
        assert axis["hlo_min_all_gather_ops"] == 2
        assert axis["bytes_per_step_per_device"] == scatter + gather


def test_sync_comm_bytes_zero1_flag_and_tp_shard():
    """``zero1=True`` is stage 1, and under tp each device syncs only its
    Megatron shard: the stage-0 payload is this rank's 1/tp of the slots."""
    spec = _spec(pp=1)
    assert gradsync.sync_comm_bytes(spec, DP, 1, zero1=True) == (
        gradsync.sync_comm_bytes(spec, DP, 1, zero=1)
    )
    one = gradsync.sync_comm_bytes(spec, DP, 1, tp=1)
    two = gradsync.sync_comm_bytes(spec, DP, 1, tp=2)
    assert two["grad_bytes_per_device"] == 4 * stacked_flat_len(spec, 1, 2)
    assert two["grad_bytes_per_device"] < one["grad_bytes_per_device"]
