"""Bucketed gradient synchronization (parallel/gradsync.py).

The BucketPlan's structural guarantees — greedy byte budget, backward
(output-layer-first) order, every-leaf-exactly-once coverage — plus the
emitters' numerics contract: per-bucket collectives are BITWISE identical
to the anchor collective they replace, on both the plain-DP (psum) and
ZeRO-1 (psum_scatter) paths. The executor-level end-to-end bit-equality
lives in tests/test_fuzz_layouts.py; this file pins the layer itself.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu.parallel import gradsync
from shallowspeed_tpu.parallel.executor import slot_shapes
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

SIZES = (48, 40, 36, 32, 28, 24, 18, 10)


def _spec(pp=1, B=64):
    return Mo.make_model_spec(SIZES, pp, B)


# ---------------------------------------------------------------------------
# BucketPlan structure
# ---------------------------------------------------------------------------


def test_plan_dp_buckets_budget_order_and_coverage():
    """The greedy split honors the byte budget, preserves backward order
    (output layer first, W before b within a slot), and covers every
    stacked gradient leaf exactly once."""
    spec = _spec(pp=2)
    dims = slot_shapes(spec)
    L = len(dims)
    budget = 4096
    plan = gradsync.plan_dp_buckets(spec, 2, budget)
    assert plan.mode == "dp" and plan.bucket_bytes == budget
    assert plan.num_buckets >= 2  # this model does not fit one 4 KiB bucket

    flat_leaves = [l for group in plan.buckets for l in group]
    # coverage: every (kind, slot) exactly once
    assert sorted((l.kind, l.slot) for l in flat_leaves) == sorted(
        [("W", l) for l in range(L)] + [("b", l) for l in range(L)]
    )
    # backward order: slots descend; W precedes b within a slot
    keys = [(-l.slot, 0 if l.kind == "W" else 1) for l in flat_leaves]
    assert keys == sorted(keys)
    # budget: a multi-leaf bucket never exceeds it (an oversized single
    # leaf is allowed its own bucket — the plan never splits a leaf)
    for group, nbytes in zip(plan.buckets, plan.bucket_grad_bytes()):
        assert nbytes == sum(l.nbytes for l in group)
        if len(group) > 1:
            assert nbytes <= budget
    # totals: bucketing moves op granularity, never bytes
    total = sum(l.nbytes for l in flat_leaves)
    assert plan.total_grad_bytes() == total
    V = spec.n_stages // 2
    flat = sum(V * o * i for o, i in dims) + sum(V * o for o, _ in dims)
    assert total == 4 * flat


def test_plan_dp_buckets_edge_budgets():
    spec = _spec(pp=1)
    assert gradsync.plan_dp_buckets(spec, 1, 0) is None
    assert gradsync.plan_dp_buckets(spec, 1, None) is None
    # a 1-byte budget: every leaf its own bucket (never split, never drop)
    plan = gradsync.plan_dp_buckets(spec, 1, 1)
    assert all(len(g) == 1 for g in plan.buckets)
    assert plan.num_buckets == 2 * len(slot_shapes(spec))
    # a huge budget: one bucket holding everything
    plan = gradsync.plan_dp_buckets(spec, 1, 1 << 30)
    assert plan.num_buckets == 1


def test_plan_zero1_buckets_tile_the_chunk():
    """ZeRO-1 buckets are column ranges tiling [0, chunk) exactly; each
    covers dp x width gradient elements within the byte budget."""
    spec = _spec(pp=2)
    dp = 2
    dims = slot_shapes(spec)
    V = spec.n_stages // 2
    flat = sum(V * o * i for o, i in dims) + sum(V * o for o, _ in dims)
    csz = -(-flat // dp)
    budget = 4096
    plan = gradsync.plan_zero1_buckets(spec, dp, 2, budget)
    assert plan.mode == "zero1" and plan.dp == dp
    # ranges tile the chunk: contiguous, in order, no gaps or overlaps
    assert plan.buckets[0][0] == 0 and plan.buckets[-1][1] == csz
    for (a0, b0), (a1, b1) in zip(plan.buckets, plan.buckets[1:]):
        assert b0 == a1 and a0 < b0
    # budget bounds the synced gradient payload (dp x width x 4B)
    for nbytes in plan.bucket_grad_bytes():
        assert nbytes <= budget
    # census result bytes are the scatter's per-device output (1/dp)
    assert [g // dp for g in plan.bucket_grad_bytes()] == (
        plan.bucket_census_bytes()
    )
    assert plan.total_grad_bytes() == 4 * dp * csz
    assert gradsync.plan_zero1_buckets(spec, dp, 2, 0) is None


def test_plan_describe_is_json_able():
    spec = _spec(pp=1)
    for plan in (
        gradsync.plan_dp_buckets(spec, 1, 4096),
        gradsync.plan_zero1_buckets(spec, 2, 1, 4096),
    ):
        desc = json.loads(json.dumps(plan.describe()))
        assert desc["num_buckets"] == plan.num_buckets
        assert desc["grad_bucket_bytes"] == 4096
        assert sum(desc["bucket_grad_bytes"]) == desc["total_grad_bytes"]


# ---------------------------------------------------------------------------
# emitters: bitwise identity with the anchor collectives
# ---------------------------------------------------------------------------


def _dp_mesh(dp):
    return Mesh(np.array(jax.devices()[:dp]), ("dp",))


def test_psum_bucketed_bitwise_matches_anchor_psum():
    """One flat psum per bucket == the whole-tree anchor psum, bit for
    bit, on every leaf — the elementwise-reduction equivalence the whole
    feature rests on."""
    spec = _spec(pp=1)
    dims = slot_shapes(spec)
    dp = 4
    mesh = _dp_mesh(dp)
    rng = np.random.RandomState(0)
    gW = tuple(
        jnp.asarray(rng.randn(dp, 1, o, i).astype(np.float32)) for o, i in dims
    )
    gb = tuple(
        jnp.asarray(rng.randn(dp, 1, o).astype(np.float32)) for o, _ in dims
    )

    for budget in (1, 2048, 1 << 30):
        plan = gradsync.plan_dp_buckets(spec, 1, budget)

        def anchor(*leaves):
            nW = len(dims)
            tree = {
                "W": tuple(l[0] for l in leaves[:nW]),
                "b": tuple(l[0] for l in leaves[nW:]),
            }
            out = lax.psum(tree, "dp")
            return tuple(x[None] for x in out["W"] + out["b"])

        def bucketed(*leaves):
            nW = len(dims)
            tree = {
                "W": tuple(l[0] for l in leaves[:nW]),
                "b": tuple(l[0] for l in leaves[nW:]),
            }
            out = gradsync.psum_bucketed(tree, plan)
            return tuple(x[None] for x in out["W"] + out["b"])

        args = gW + gb
        specs = tuple(P("dp") for _ in args)
        run_a = jax.jit(
            shard_map(
                anchor, mesh=mesh, in_specs=specs, out_specs=specs,
                check_vma=False,
            )
        )
        run_b = jax.jit(
            shard_map(
                bucketed, mesh=mesh, in_specs=specs, out_specs=specs,
                check_vma=False,
            )
        )
        for a, b in zip(run_a(*args), run_b(*args)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"budget={budget}"
            )


def test_psum_scatter_bucketed_bitwise_matches_anchor_scatter():
    """Per-bucket column scatters of the (dp, chunk) view reproduce the
    anchor's tiled flat scatter exactly — same elements, same order."""
    dp = 4
    mesh = _dp_mesh(dp)
    csz = 301
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(dp, dp * csz).astype(np.float32))

    def anchor(x):
        return lax.psum_scatter(
            x[0], "dp", scatter_dimension=0, tiled=True
        )[None]

    for budget in (4 * dp * 1, 4 * dp * 64, 1 << 30):
        # a hand-built flat plan over the chunk (spec-independent)
        width = max(1, budget // (4 * dp))
        plan = gradsync.BucketPlan(
            mode="zero1",
            bucket_bytes=budget,
            buckets=tuple(
                (a, min(a + width, csz)) for a in range(0, csz, width)
            ),
            dp=dp,
        )

        def bucketed(x):
            return gradsync.psum_scatter_bucketed(x[0], plan)[None]

        run_a = jax.jit(
            shard_map(
                anchor, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                check_vma=False,
            )
        )
        run_b = jax.jit(
            shard_map(
                bucketed, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                check_vma=False,
            )
        )
        np.testing.assert_array_equal(
            np.asarray(run_a(g)), np.asarray(run_b(g)),
            err_msg=f"budget={budget}",
        )


# ---------------------------------------------------------------------------
# the comms-byte model
# ---------------------------------------------------------------------------


def test_sync_comm_bytes_totals_invariant_under_bucketing():
    """Bucketing changes op granularity, never wire bytes: the per-step
    totals match the anchor's for both sync flavors, and the bucketed
    entry carries the plan's breakdown."""
    spec = _spec(pp=2)
    for zero1 in (False, True):
        plan = (
            gradsync.plan_zero1_buckets(spec, 2, 2, 4096)
            if zero1
            else gradsync.plan_dp_buckets(spec, 2, 4096)
        )
        anchor = gradsync.sync_comm_bytes(spec, 2, 2, zero1=zero1, plan=None)
        bucketed = gradsync.sync_comm_bytes(spec, 2, 2, zero1=zero1, plan=plan)
        assert anchor["mode"] == "anchor" and bucketed["mode"] == "bucketed"
        assert (
            bucketed["bytes_per_step_per_device"]
            == anchor["bytes_per_step_per_device"]
        )
        assert bucketed["num_buckets"] == plan.num_buckets
        assert sum(bucketed["bucket_grad_bytes"]) == (
            bucketed["grad_bytes_per_device"]
        )


def test_zero1_plan_single_bucket_degenerates_cleanly():
    """A budget larger than the whole chunk yields one bucket whose
    scatter is the anchor scatter in (dp, chunk) form."""
    spec = _spec(pp=1)
    plan = gradsync.plan_zero1_buckets(spec, 2, 1, 1 << 30)
    assert plan.num_buckets == 1
    (a, b) = plan.buckets[0]
    assert a == 0 and b == plan.total_grad_bytes() // (4 * 2)
