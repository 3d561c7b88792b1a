"""SPMD executor tests on the 8-virtual-device CPU mesh.

The correctness bar is the reference's own: every distributed layout must
reproduce SEQUENTIAL training (SURVEY §3.3 — the three-sums gradient ledger),
and DP replicas must end bit-identical. These run the real shard_map +
ppermute + psum code paths, which the reference never covered with tests at
all (its multi-process checks were runtime asserts only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import schedules as S
from shallowspeed_tpu import trainer, utils
from shallowspeed_tpu.optimizer import SGD
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.parallel import lower_schedule, make_mesh

SIZES = (784, 128, 127, 126, 125, 124, 123, 10)  # flagship, uneven stages
SMALL = (24, 20, 18, 16, 14, 12, 11, 10)  # same shape class, faster
B, M, LR = 64, 4, 0.01
NB = 3  # batches


def _data(sizes, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(NB, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (NB, B))]
    return X, Y


def _sequential_params(sizes, X, Y):
    spec = Mo.make_model_spec(sizes, 1, B)
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
    step = trainer.make_train_step(spec, SGD(LR))
    st = ()
    for i in range(NB):
        params, st = step(
            params,
            st,
            jnp.asarray(X[i].reshape(M, B // M, sizes[0])),
            jnp.asarray(Y[i].reshape(M, B // M, sizes[-1])),
        )
    return [l for stage in params for l in stage]


def _pipeline_params(sizes, X, Y, dp, pp, sched_cls, use_epoch=False):
    mesh = make_mesh(dp, pp)
    spec = Mo.make_model_spec(sizes, pp, B)
    prog = lower_schedule(sched_cls, M, pp)
    stacked, flags = E.init_stacked(spec, mesh)
    mb_sz = B // dp // M
    if use_epoch:
        epoch = E.make_pipeline_epoch(mesh, spec, prog, mb_sz, SGD(LR))
        stacked, _, _ = epoch(stacked, flags, (), jnp.asarray(X), jnp.asarray(Y))
    else:
        step = E.make_pipeline_step(mesh, spec, prog, mb_sz, SGD(LR))
        for i in range(NB):
            stacked, _, _ = step(stacked, flags, (), jnp.asarray(X[i]), jnp.asarray(Y[i]))
    return stacked, spec, flags, mesh


def _assert_matches_sequential(sizes, stacked, spec, rtol=3e-4, atol=3e-6):
    X, Y = _data(sizes)
    want = _sequential_params(sizes, X, Y)
    got = [l for stage in E.unstack_params(stacked, spec) for l in stage]
    assert len(want) == len(got)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a["W"]), b["W"], rtol=rtol, atol=atol)
        np.testing.assert_allclose(
            np.asarray(a["b"]).reshape(-1), b["b"].reshape(-1), rtol=rtol, atol=atol
        )


LAYOUTS = [
    (1, 1, S.GPipeSchedule),
    (4, 1, S.NaiveParallelSchedule),
    (8, 1, S.GPipeSchedule),
    (1, 4, S.NaiveParallelSchedule),
    (1, 4, S.GPipeSchedule),
    (1, 4, S.PipeDreamFlushSchedule),
    (2, 4, S.GPipeSchedule),
    (2, 4, S.PipeDreamFlushSchedule),
    (2, 2, S.NaiveParallelSchedule),
]


@pytest.mark.parametrize("dp,pp,sched", LAYOUTS)
def test_layout_equals_sequential(dp, pp, sched):
    """The headline invariant: any DP x PP x schedule == sequential."""
    X, Y = _data(SMALL)
    stacked, spec, _, _ = _pipeline_params(SMALL, X, Y, dp, pp, sched)
    _assert_matches_sequential(SMALL, stacked, spec)


def test_pp8_with_linear_on_last_stage_equals_sequential():
    """PP=8 parity needs a size list whose last stage owns a Linear: with
    exactly 8 sizes the reference's partitioning gives the last stage zero
    Linears, so its 'no relu on the final Linear' rule never fires and the
    PP=8 model architecturally differs from sequential (reference
    layers.py:253-257 — a faithful quirk, covered in test_model). 16 sizes
    give stage 7 a real Linear and exact parity."""
    sizes16 = (24, 22, 21, 20, 19, 18, 17, 16, 16, 15, 14, 13, 13, 12, 11, 10)
    X, Y = _data(sizes16)
    stacked, spec, _, _ = _pipeline_params(sizes16, X, Y, 1, 8, S.GPipeSchedule)
    _assert_matches_sequential(sizes16, stacked, spec)


def test_flagship_dp2_pp4_gpipe_equals_sequential():
    """Full-size model (784-wide, uneven 2/2/2/1 stages) on the full mesh."""
    X, Y = _data(SIZES)
    stacked, spec, _, _ = _pipeline_params(SIZES, X, Y, 2, 4, S.GPipeSchedule)
    _assert_matches_sequential(SIZES, stacked, spec)


def test_pallas_kernel_backend_matches_xla_on_mesh():
    """The executor's Pallas backend (flag-operand fused kernels, the traced
    relu flag as a kernel operand) must reproduce the XLA backend bit-for-bit
    on the mesh path: same dots at the same precision, flag-selected relu.
    Interpret mode off-TPU, the real kernels on hardware — same contract."""
    X, Y = _data(SMALL)
    mesh = make_mesh(2, 4)
    spec = Mo.make_model_spec(SMALL, 4, B)
    prog = lower_schedule(S.GPipeSchedule, M, 4)
    mb_sz = B // 2 // M
    results = {}
    for kb in ("xla", "pallas"):
        stacked, flags = E.init_stacked(spec, mesh)
        step = E.make_pipeline_step(mesh, spec, prog, mb_sz, SGD(LR), kernel_backend=kb)
        losses = []
        for i in range(NB):
            stacked, _, loss = step(
                stacked, flags, (), jnp.asarray(X[i]), jnp.asarray(Y[i])
            )
            losses.append(float(loss))
        results[kb] = (jax.device_get(stacked), losses)
    assert results["xla"][1] == results["pallas"][1]
    for a, b in zip(
        jax.tree.leaves(results["xla"][0]), jax.tree.leaves(results["pallas"][0])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pallas_kernel_backend_tiled_slots_match_xla(monkeypatch):
    """Slots beyond the single-block VMEM budget no longer reject the pallas
    backend: they auto-dispatch to the grid-tiled flag kernels. Budget
    forced to 0 so EVERY slot takes the tiled path. Tolerance, not
    bit-equality: tiling pads the contraction dim to a tile boundary, which
    reassociates the dot's reduction tree vs XLA's unpadded dot (adding
    exact zeros is a no-op, but the grouping of the NONZERO partial sums
    changes) — same reason TestTiledKernels uses allclose. Bit-identity
    holds for the single-block regime (test_pallas_kernel_backend_matches_
    xla_on_mesh); multi-tile contraction math is covered at kernel level in
    test_pallas_ops.TestTiledFlagKernels."""
    from shallowspeed_tpu import pallas_ops

    monkeypatch.setattr(pallas_ops, "SINGLE_BLOCK_BUDGET_BYTES", 0)
    monkeypatch.setattr(pallas_ops, "TILE", 128)
    X, Y = _data(SMALL)
    mesh = make_mesh(1, 2)
    spec = Mo.make_model_spec(SMALL, 2, B)
    prog = lower_schedule(S.GPipeSchedule, M, 2)
    mb_sz = B // M
    results = {}
    for kb in ("xla", "pallas"):
        stacked, flags = E.init_stacked(spec, mesh)
        step = E.make_pipeline_step(mesh, spec, prog, mb_sz, SGD(LR), kernel_backend=kb)
        losses = []
        for i in range(NB):
            stacked, _, loss = step(
                stacked, flags, (), jnp.asarray(X[i]), jnp.asarray(Y[i])
            )
            losses.append(float(loss))
        results[kb] = (jax.device_get(stacked), losses)
    np.testing.assert_allclose(
        results["xla"][1], results["pallas"][1], rtol=1e-6, atol=0
    )
    for a, b in zip(
        jax.tree.leaves(results["xla"][0]), jax.tree.leaves(results["pallas"][0])
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_epoch_scan_matches_per_batch():
    X, Y = _data(SMALL)
    a, spec, _, _ = _pipeline_params(SMALL, X, Y, 2, 4, S.GPipeSchedule)
    b, _, _, _ = _pipeline_params(SMALL, X, Y, 2, 4, S.GPipeSchedule, use_epoch=True)
    ua = [l for st in E.unstack_params(a, spec) for l in st]
    ub = [l for st in E.unstack_params(b, spec) for l in st]
    for x, y in zip(ua, ub):
        np.testing.assert_allclose(x["W"], y["W"], rtol=1e-6, atol=1e-7)


def test_schedules_agree_with_each_other():
    """naive, gpipe and pipedream must produce identical updates — they
    reorder the same microbatch work."""
    X, Y = _data(SMALL)
    results = []
    for sched in (S.NaiveParallelSchedule, S.GPipeSchedule, S.PipeDreamFlushSchedule):
        stacked, spec, _, _ = _pipeline_params(SMALL, X, Y, 1, 4, sched)
        results.append([l for st in E.unstack_params(stacked, spec) for l in st])
    for other in results[1:]:
        for a, b in zip(results[0], other):
            np.testing.assert_allclose(a["W"], b["W"], rtol=1e-5, atol=1e-7)


def test_dp_replicas_stay_in_sync():
    X, Y = _data(SMALL)
    stacked, spec, flags, mesh = _pipeline_params(SMALL, X, Y, 4, 2, S.GPipeSchedule)
    utils.assert_dp_replicas_in_sync(stacked)


def test_padded_regions_stay_zero():
    """The zero-padding invariant after real training steps (per-slot stacks)."""
    X, Y = _data(SMALL)
    stacked, spec, _, _ = _pipeline_params(SMALL, X, Y, 2, 4, S.GPipeSchedule)
    Ws = [np.asarray(jax.device_get(w)) for w in stacked["W"]]
    bs = [np.asarray(jax.device_get(b)) for b in stacked["b"]]
    for s, sspec in enumerate(spec.stages):
        for l in range(len(Ws)):
            if l < sspec.n_linears:
                out_d, in_d = sspec.local_sizes[l + 1], sspec.local_sizes[l]
                block = Ws[l][s].copy()
                block[:out_d, :in_d] = 0
                assert (block == 0).all(), f"stage {s} layer {l} leaked outside block"
                assert (bs[l][s, out_d:] == 0).all()
            else:
                assert (Ws[l][s] == 0).all() and (bs[l][s] == 0).all()


def test_pipeline_inference_matches_sequential_predict():
    X, Y = _data(SMALL)
    mesh = make_mesh(2, 4)
    spec = Mo.make_model_spec(SMALL, 4, B)
    eval_prog = lower_schedule(S.InferenceSchedule, M, 4, training=False)
    stacked, flags = E.init_stacked(spec, mesh)
    eval_step = E.make_pipeline_step(mesh, spec, eval_prog, B // 2 // M)
    preds = eval_step(stacked, flags, jnp.asarray(X[0]))

    spec1 = Mo.make_model_spec(SMALL, 1, B)
    params1 = jax.tree.map(jnp.asarray, Mo.init_model(spec1))
    want = trainer.make_predict(spec1)(params1, jnp.asarray(X[0]))
    np.testing.assert_allclose(
        np.asarray(preds)[:, : SMALL[-1]], np.asarray(want), rtol=2e-4, atol=1e-5
    )
    assert (np.asarray(preds)[:, SMALL[-1] :] == 0).all()


def test_tick_and_batch_unroll_bit_identical():
    """Scan unroll factors are scheduling-only: identical results."""
    X, Y = _data(SMALL)
    mesh = make_mesh(2, 4)
    spec = Mo.make_model_spec(SMALL, 4, B)
    prog = lower_schedule(S.GPipeSchedule, M, 4)
    outs = []
    for unroll, tick_unroll in ((1, 1), (2, 4)):
        stacked, flags = E.init_stacked(spec, mesh)
        epoch = E.make_pipeline_epoch(
            mesh, spec, prog, B // 2 // M, SGD(LR),
            unroll=unroll, tick_unroll=tick_unroll,
        )
        stacked, _, loss = epoch(stacked, flags, (), jnp.asarray(X), jnp.asarray(Y))
        outs.append((E.unstack_params(stacked, spec), float(loss)))
    assert outs[0][1] == outs[1][1]
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), outs[0][0], outs[1][0]
    )


def test_train_loss_decreases():
    rng = np.random.RandomState(7)
    labels = rng.randint(0, 10, (8, B))
    centers = rng.randn(10, SMALL[0]).astype(np.float32) * 2
    X = np.stack([centers[lb] + 0.1 * rng.randn(B, SMALL[0]).astype(np.float32) for lb in labels])
    Y = np.eye(10, dtype=np.float32)[labels]
    mesh = make_mesh(2, 4)
    spec = Mo.make_model_spec(SMALL, 4, B)
    prog = lower_schedule(S.GPipeSchedule, M, 4)
    stacked, flags = E.init_stacked(spec, mesh)
    step = E.make_pipeline_step(mesh, spec, prog, B // 2 // M, SGD(0.05))
    losses = []
    for e in range(6):
        for i in range(8):
            stacked, _, loss = step(stacked, flags, (), jnp.asarray(X[i]), jnp.asarray(Y[i]))
        losses.append(float(loss))
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert losses[-1] < losses[0] - 5e-3, losses


def test_relay_width_is_true_boundary_maximum():
    """The pp-axis payload/mailbox width must be the widest inter-stage
    boundary, not the model input width (VERDICT round-1 weak #2: sizing to
    D_in=784 shipped ~6x the needed bytes per tick)."""
    from shallowspeed_tpu.api import FLAGSHIP_SIZES

    spec = Mo.make_model_spec(FLAGSHIP_SIZES, 4, B)
    w = E.relay_width(spec)
    assert w == max(s.in_dim for s in spec.stages[1:])
    assert w == 127  # stage boundaries 127/125/123 — and far below 784
    assert w < spec.stages[0].in_dim
    # degenerate single-stage model: no boundary to relay
    assert E.relay_width(Mo.make_model_spec((8, 4), 1, B)) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bool_])
def test_stash_slots_are_feature_major_and_round_trip(dtype):
    """A ring is ``(slots, width, mb)``; what ``_stash`` parks comes back
    from ``_unstash`` bit for bit as the ``(mb, width)`` value it was, and
    no other slot is touched."""
    slots, mb, width = 3, 8, 5
    val = jnp.asarray(np.random.RandomState(0).randn(mb, width) > 0.3, dtype)
    if dtype == jnp.float32:
        val = val * jnp.float32(np.pi)
    ring = E._stash(jnp.zeros((slots, width, mb), dtype), 1, val)
    assert ring.shape == (slots, width, mb)
    np.testing.assert_array_equal(np.asarray(ring[1]), np.asarray(val).T)
    np.testing.assert_array_equal(np.asarray(E._unstash(ring, 1)), np.asarray(val))
    assert not np.asarray(ring[0]).any() and not np.asarray(ring[2]).any()


def _ppermutes(jaxpr, under=()):
    """``[(perm, names of the primitives it is nested under)]`` for every
    ``ppermute`` of a (closed) jaxpr, sub-jaxprs included."""
    found = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "ppermute":
            found.append((tuple(eqn.params["perm"]), under))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _ppermutes(sub, under + (eqn.primitive.name,))
    return found


RELAY_LAYOUTS = {
    # id: (dp, pp, schedule, lower_schedule kwargs) -> the perms issued
    "pipedream-pp2": (2, 2, S.PipeDreamFlushSchedule, {}),
    "gpipe-pp4": (1, 4, S.GPipeSchedule, {}),
    "interleaved-pp2-v2": (1, 2, S.InterleavedSchedule, dict(virtual=2)),
    "inference-pp4": (1, 4, S.InferenceSchedule, dict(training=False)),
    "dp-only": (2, 1, S.GPipeSchedule, {}),
}


@pytest.mark.parametrize("layout", list(RELAY_LAYOUTS))
def test_relays_are_issued_where_the_send_tables_say(layout):
    """The traced step holds one ``ppermute`` per direction that ever sends,
    over that direction's sending pairs only (a flat program drops the
    ring's wrap pair, an interleaved one keeps it, inference has no backward
    relay, one pp device has none at all), each under a ``cond`` of its own:
    the relay's predicate, outside the op-code ``switch``."""
    dp, pp, sched, kw = RELAY_LAYOUTS[layout]
    V = kw.get("virtual", 1)
    training = kw.get("training", True)
    mesh = make_mesh(dp, pp)
    spec = Mo.make_model_spec(SMALL, pp * V, B)
    prog = lower_schedule(sched, M, pp, **kw)
    order = E.interleave_order(pp * V, pp) if V > 1 else None
    stacked, flags = E.init_stacked(spec, mesh, order)
    step = E.make_pipeline_step(
        mesh, spec, prog, B // dp // M, SGD(LR) if training else None
    )
    X, Y = _data(SMALL)
    args = (stacked, flags, (), X[0], Y[0]) if training else (stacked, flags, X[0])
    found = _ppermutes(jax.make_jaxpr(step)(*args))

    fwd_perm, bwd_perm = prog.relay_perms()
    assert sorted(perm for perm, _ in found) == sorted(
        tuple(p) for p in (fwd_perm, bwd_perm) if p
    )
    for _, under in found:
        # scan (ticks) > cond (the relay's predicate); the switch is a cond
        # too, and a relay nested in it would show two
        assert under[-2:] == ("scan", "cond") and under.count("cond") == 1
    wrap_fwd, wrap_bwd = (pp - 1, 0), (0, pp - 1)
    if layout == "interleaved-pp2-v2":
        assert wrap_fwd in fwd_perm and wrap_bwd in bwd_perm
    elif pp > 1:
        assert wrap_fwd not in fwd_perm and wrap_bwd not in bwd_perm
    if layout == "inference-pp4":
        assert len(found) == 1
    if layout == "dp-only":
        assert found == []
