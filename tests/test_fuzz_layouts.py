"""Randomized cross-layout consistency: the strongest generic property.

For randomly generated model shapes (monotone-decreasing, like the reference
family), random DP x PP layouts and random schedules, pipeline training must
match sequential training float-for-float. Any latent bug in stage
partitioning, per-slot padding, mailbox routing, microbatch ordering or the
gradient ledger shows up here as a weight mismatch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import schedules as S
from shallowspeed_tpu import trainer
from shallowspeed_tpu.optimizer import SGD, Adam, MomentumSGD
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.observability.divergence import assert_models_equal
from shallowspeed_tpu.parallel import lower_schedule, make_mesh

SCHEDS = [S.NaiveParallelSchedule, S.GPipeSchedule, S.PipeDreamFlushSchedule]


def _random_case(seed):
    rng = np.random.RandomState(seed)
    dp, pp = [(1, 2), (2, 2), (1, 4), (2, 4), (4, 2), (4, 1)][seed % 6]
    # stage_size >= 2 keeps >= 1 Linear on the last stage (exact parity regime)
    n_sizes = pp * rng.randint(2, 4)
    n_sizes = max(n_sizes, 2)
    # monotone-decreasing widths ending in a class count no wider than any
    # hidden width (the documented passthrough constraint for uneven stages)
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    if len(sizes) % pp != 0:
        sizes = (sizes[0] + 2,) + sizes
        while len(sizes) % pp != 0:
            sizes = (sizes[0] + 2,) + sizes
    M = rng.choice([1, 2, 4])
    B = int(dp * M * rng.choice([4, 8]))
    sched = SCHEDS[seed % 3]
    return sizes, dp, pp, int(M), B, sched


@pytest.mark.parametrize("seed", range(12))
def test_random_layout_matches_sequential(seed):
    sizes, dp, pp, M, B, sched = _random_case(seed)
    spec_pp = Mo.make_model_spec(sizes, pp, B)
    if spec_pp.stages[-1].n_linears == 0:
        pytest.skip("zero-linear last stage differs architecturally (documented)")
    rng = np.random.RandomState(100 + seed)
    X = rng.randn(2, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, B))]

    # sequential
    spec1 = Mo.make_model_spec(sizes, 1, B)
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec1))
    step1 = trainer.make_train_step(spec1, SGD(0.01))
    st = ()
    for i in range(2):
        params, st = step1(
            params,
            st,
            jnp.asarray(X[i].reshape(M, B // M, -1)),
            jnp.asarray(Y[i].reshape(M, B // M, -1)),
        )
    want = [l for stage in params for l in stage]

    # pipeline
    mesh = make_mesh(dp, pp)
    prog = lower_schedule(sched, M, pp)
    stacked, flags = E.init_stacked(spec_pp, mesh)
    step = E.make_pipeline_step(mesh, spec_pp, prog, B // dp // M, SGD(0.01))
    for i in range(2):
        stacked, _, _ = step(stacked, flags, (), jnp.asarray(X[i]), jnp.asarray(Y[i]))
    got = [l for stage in E.unstack_params(stacked, spec_pp) for l in stage]

    assert len(want) == len(got)
    for a, b in zip(want, got):
        np.testing.assert_allclose(
            np.asarray(a["W"]), b["W"], rtol=5e-4, atol=5e-6,
            err_msg=f"case: sizes={sizes} dp={dp} pp={pp} M={M} B={B} {sched.__name__}",
        )
        np.testing.assert_allclose(
            np.asarray(a["b"]).reshape(-1), b["b"].reshape(-1), rtol=5e-4, atol=5e-6
        )

    # inference path on the trained pipeline weights vs sequential predict
    eval_prog = lower_schedule(S.InferenceSchedule, M, pp, training=False)
    eval_step = E.make_pipeline_step(mesh, spec_pp, eval_prog, B // dp // M)
    preds = np.asarray(eval_step(stacked, flags, jnp.asarray(X[0])))
    seq_preds = np.asarray(trainer.make_predict(spec1)(params, jnp.asarray(X[0])))
    np.testing.assert_allclose(
        preds[:, : sizes[-1]], seq_preds, rtol=1e-3, atol=1e-5,
        err_msg=f"eval case: sizes={sizes} dp={dp} pp={pp} M={M}",
    )
    assert (preds[:, sizes[-1] :] == 0).all()


OPTS = [SGD(0.01), MomentumSGD(0.005, 0.9), Adam(0.003)]


def _random_case_r2(seed):
    """Round-2 feature fuzz: optimizer x zero1 x virtual stages, drawn from
    INDEPENDENT seed bits so every pairing (incl. zero1 + interleaved, and
    zero1 over a 4-way dp axis) occurs across the 12 seeds."""
    rng = np.random.RandomState(1000 + seed)
    V = [1, 2][seed % 2]
    zero1 = bool((seed // 2) % 2)
    dp, pp = [(2, 2), (1, 4), (4, 2)][(seed // 4) % 3]
    n_stages = pp * V
    # every stage gets >= 2 sizes (>= 1 Linear) -> exact-parity regime;
    # n_sizes is a multiple of n_stages by construction
    n_sizes = n_stages * int(rng.randint(2, 4))
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(pp * rng.choice([1, 2]))  # interleaved needs M % pp == 0
    B = int(dp * M * rng.choice([4, 8]))
    opt = OPTS[seed % 3]
    sched = S.InterleavedSchedule if V > 1 else SCHEDS[seed % 3]
    clip = [None, 0.05][(seed // 3) % 2]  # independent of the other bits
    # per-step loop vs fused whole-run program: offset the parity per mesh
    # block so every mesh (incl. the 2x2 square and 4-way dp) sees BOTH
    # execution modes across the 12 seeds
    fused = bool((seed + seed // 4) % 2)
    return sizes, dp, pp, V, M, B, opt, zero1, sched, clip, fused


def _assert_lattice_case_matches_sequential(
    sizes, dp, pp, V, M, B, opt, zero1, sched, clip, fused, data_seed,
    kb="xla", label_extra="", bsplit=False, tp=1, act="relu",
    recompute=False, zero=None,
):
    """The ONE sequential-vs-pipeline comparison harness behind the r2, r3
    and r4 lattice fuzz families: train two batches sequentially (the
    oracle) and through the mesh pipeline with the given feature
    combination, then compare every trained weight. ``tp > 1`` adds the
    Megatron model axis (same tolerance: its psums reassociate a split
    contraction, exactly like the dp sum). ``act`` picks the activation
    family (the model-zoo dimension); ``recompute`` drops the forward
    stash and re-runs the stage forward at the backward boundary — both
    must be invisible here. ``zero`` (superseding the ``zero1`` bool when
    set) picks the dp-axis ZeRO stage: 2-3 carry the cross-layout
    tolerance like tp (the per-tick scatter reassociates the microbatch
    sum), which is exactly what this oracle already prices."""
    spec_pp = Mo.make_model_spec(sizes, pp * V, B, act=act)
    assert spec_pp.stages[-1].n_linears > 0  # generator guarantees parity regime

    rng = np.random.RandomState(data_seed)
    X = rng.randn(2, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, B))]

    spec1 = Mo.make_model_spec(sizes, 1, B, act=act)
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec1))
    step1 = trainer.make_train_step(spec1, opt, clip_norm=clip)
    st = opt.init(params)
    for i in range(2):
        params, st = step1(
            params,
            st,
            jnp.asarray(X[i].reshape(M, B // M, -1)),
            jnp.asarray(Y[i].reshape(M, B // M, -1)),
        )
    want = [l for stage in params for l in stage]

    mesh = make_mesh(dp, pp, tp=tp)
    order = E.interleave_order(pp * V, pp) if V > 1 else None
    prog = lower_schedule(
        sched, M, pp, virtual=V, backward_split=bsplit, recompute=recompute
    )
    stacked, flags = E.init_stacked(spec_pp, mesh, order=order)
    zstage = (1 if zero1 else 0) if zero is None else int(zero)
    if zstage >= 2:
        ost = E.zero_block_init_state(opt, spec_pp, mesh)
    elif zstage == 1:
        ost = E.zero1_init_state(opt, spec_pp, mesh)
    else:
        ost = opt.init(stacked)
    if zstage == 3:
        rows = E.zero_block_flatten_rows(
            jax.device_get(stacked), spec_pp, mesh)
        stacked = {"P": jax.device_put(rows, E.zero1_part_sharding(mesh))}
    if fused:
        # same two batches as one epoch inside the fused whole-run program
        run = E.make_pipeline_run(
            mesh, spec_pp, prog, B // dp // M, opt, zero=zstage,
            clip_norm=clip, kernel_backend=kb,
        )
        stacked, ost, _ = run(stacked, flags, ost, jnp.asarray(X), jnp.asarray(Y), 1)
    else:
        step = E.make_pipeline_step(
            mesh, spec_pp, prog, B // dp // M, opt, zero=zstage,
            clip_norm=clip, kernel_backend=kb,
        )
        for i in range(2):
            stacked, ost, _ = step(
                stacked, flags, ost, jnp.asarray(X[i]), jnp.asarray(Y[i])
            )
    if zstage == 3:
        stacked = E.zero_block_unflatten_rows(
            np.asarray(jax.device_get(stacked["P"])), spec_pp, mesh)
    got = [l for s in E.unstack_params(stacked, spec_pp, order=order) for l in s]
    assert len(want) == len(got)

    label = (
        f"sizes={sizes} dp={dp} pp={pp} tp={tp} V={V} M={M} B={B} "
        f"{type(opt).__name__} zero={zstage} clip={clip} fused={fused} "
        f"bsplit={bsplit} act={act} rec={recompute} "
        f"{sched.__name__}{label_extra}"
    )
    # Adam's early update direction is ~g/|g| per element: near-zero second
    # moments amplify ulp-level cross-layout reassociation of g, so its
    # tolerance is an order looser than the mul/add optimizers'
    rtol, atol = (5e-3, 5e-5) if isinstance(opt, Adam) else (5e-4, 5e-6)
    for a, b in zip(want, got):
        np.testing.assert_allclose(
            np.asarray(a["W"]), b["W"], rtol=rtol, atol=atol, err_msg=label
        )
        np.testing.assert_allclose(
            np.asarray(a["b"]).reshape(-1), b["b"].reshape(-1),
            rtol=rtol, atol=atol, err_msg=label,
        )


@pytest.mark.parametrize(
    "seed",
    # seed 5 deterministically draws the heaviest combo (~10s alone) —
    # it rides the slow tier (1-core wall budget), still in the full suite
    [pytest.param(s, marks=pytest.mark.slow) if s == 5 else s for s in range(12)],
)
def test_random_r2_feature_combo_matches_sequential(seed):
    """Random (optimizer, zero1, virtual-stage) combinations must still equal
    sequential training with the same optimizer — the round-2 features
    compose, not just work in isolation."""
    sizes, dp, pp, V, M, B, opt, zero1, sched, clip, fused = _random_case_r2(seed)
    _assert_lattice_case_matches_sequential(
        sizes, dp, pp, V, M, B, opt, zero1, sched, clip, fused,
        data_seed=2000 + seed,
    )


def _random_case_r3(seed):
    """Round-5 feature fuzz (round-4 verdict #3), round-10 extension: the
    full lattice — optimizer x zero1 x kernel_backend x virtual stages x
    epoch-vs-step x backward splitting x TENSOR
    PARALLELISM — from independent seed bits, so pallas-backend
    interactions (e.g. zero1 x pallas x interleaved),
    split-backward and Megatron-tp interactions get randomized coverage,
    not just their dedicated tests. tp rides its own bit wherever it is
    supported (the xla backend; the pallas flag kernels compute whole
    slots), so it crosses dp/pp/zero1/clip/fused-run and the
    split backward across the seeds."""
    rng = np.random.RandomState(3000 + seed)
    kb = ["xla", "pallas"][seed % 2]
    rng.choice(3)  # a retired draw: keeps the seed's later draws in place
    V = [1, 2][(seed // 2) % 2]
    dp, pp = [(2, 2), (1, 4), (2, 1)][(seed // 4) % 3]
    opt = OPTS[(seed + seed // 2) % 3]
    zero1 = bool((seed // 3) % 2)
    clip = [None, 0.05][(seed // 6) % 2]
    fused = bool((seed + seed // 4) % 2)  # per-step loop vs whole-run program
    # split backward rides its own bit wherever it is supported (flat
    # schedules on the xla backend), so it meets zero1, clipping
    # and the fused-run path across the seeds
    bsplit = bool((seed + seed // 3) % 2) and V == 1 and kb == "xla"
    # the tp axis: every (dp, pp) block here fits x2 on the 8 emulated
    # devices ((2,2)->8, (1,4)->8, (2,1)->4)
    tp = 2 if kb == "xla" and ((seed + seed // 6) % 2) else 1
    n_stages = pp * V
    n_sizes = n_stages * int(rng.randint(2, 4))
    n_sizes = max(n_sizes, 2)
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(pp * rng.choice([1, 2]))  # interleaved needs M % pp == 0
    B = int(dp * M * rng.choice([4, 8]))
    sched = S.InterleavedSchedule if V > 1 else SCHEDS[seed % 3]
    return (
        sizes, dp, pp, V, M, B, opt, zero1, kb, sched, clip, fused, bsplit, tp,
    )


@pytest.mark.parametrize(
    "seed",
    # seed 7 deterministically draws the heaviest combo (~7s alone) —
    # it rides the slow tier (1-core wall budget), still in the full suite
    [pytest.param(s, marks=pytest.mark.slow) if s == 7 else s for s in range(12)],
)
def test_random_r3_kernel_backend_combo_matches_sequential(seed):
    """Random (optimizer, zero1, kernel_backend, virtual, epoch-vs-step,
    backward-split, tp) combinations must still equal
    sequential training — the pallas executor backend,
    the split backward and Megatron tensor parallelism
    compose with every other feature, not just dp=pp=1."""
    (
        sizes, dp, pp, V, M, B, opt, zero1, kb, sched, clip, fused, bsplit, tp,
    ) = _random_case_r3(seed)
    _assert_lattice_case_matches_sequential(
        sizes, dp, pp, V, M, B, opt, zero1, sched, clip, fused,
        data_seed=4000 + seed, kb=kb, label_extra=f" kb={kb}",
        bsplit=bsplit, tp=tp,
    )


def _random_case_r4(seed):
    """Round-19 feature fuzz: the MODEL and RECOMPUTE dimensions —
    activation family (relu vs the transformer-style gelu+residual
    slots) and pipeline activation recompute — from independent seed
    bits, crossed with dp x pp x tp x zero1 x
    backward-split x epoch-vs-step, so recompute meets every shipped
    feature across the 12 seeds, not just its dedicated twins. Recompute
    needs a flat pipeline schedule (pp > 1, V == 1); gelu is excluded
    from the pallas backend only, which this family never draws."""
    rng = np.random.RandomState(7000 + seed)
    act = ["relu", "gelu"][seed % 2]
    recompute = bool((seed // 2) % 2)
    dp, pp = [(1, 4), (2, 2), (1, 2)][(seed // 4) % 3]
    opt = OPTS[(seed + seed // 3) % 3]
    zero1 = bool((seed // 3) % 2)
    clip = [None, 0.05][(seed + seed // 2) % 2]
    fused = bool((seed + seed // 4) % 2)
    rng.choice(2)  # a retired draw: keeps the seed's later draws in place
    bsplit = bool((seed + seed // 6) % 2)
    tp = 2 if (seed + seed // 5) % 2 and dp * pp <= 4 else 1
    per = int(rng.randint(2, 4))
    if act == "gelu":
        # gelu slot parity needs an even per-stage slice (model.py)
        per += per % 2
    n_sizes = pp * per
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(rng.choice([2, 4]))
    B = int(dp * M * rng.choice([4, 8]))
    sched = SCHEDS[seed % 3]
    return (
        sizes, dp, pp, M, B, opt, zero1, sched, clip, fused, bsplit,
        tp, act, recompute,
    )


@pytest.mark.parametrize(
    "seed",
    # seeds 2 and 3 (relu+recompute, gelu+recompute — the new lattice
    # dimension) keep tier-1 coverage; the rest ride the slow tier
    # (1-core wall budget)
    [s if s in (2, 3) else pytest.param(s, marks=pytest.mark.slow)
     for s in range(12)],
)
def test_random_r4_model_recompute_combo_matches_sequential(seed):
    """Random (activation family, recompute) combinations crossed with
    dp/pp/tp/zero1/backward-split must still equal sequential
    training — the model zoo and the recompute tick are invisible to the
    math on every layout, not just the flagship relu-MLP."""
    (
        sizes, dp, pp, M, B, opt, zero1, sched, clip, fused, bsplit,
        tp, act, recompute,
    ) = _random_case_r4(seed)
    _assert_lattice_case_matches_sequential(
        sizes, dp, pp, 1, M, B, opt, zero1, sched, clip, fused,
        data_seed=8000 + seed, bsplit=bsplit, tp=tp, act=act,
        recompute=recompute,
    )


def _random_case_r5(seed):
    """Round-20 feature fuzz: the ZeRO STAGE dimension — ``zero`` in
    {0,1,2,3} cycling every 4 seeds so each stage meets three different
    feature draws — crossed with tp x backward-split x
    interleaved virtual stages x epoch-vs-step. Stage constraints mirror
    the executor's refusals: stage 3
    keeps params sharded at rest (the fused whole-run program's eval
    view is an API-level refusal, so the fused bit only rides stages
    0-2)."""
    rng = np.random.RandomState(9000 + seed)
    zero = seed % 4
    dp, pp = [(2, 2), (4, 2), (2, 1)][(seed // 4) % 3]
    V = 2 if (seed // 2) % 2 and pp > 1 else 1
    opt = OPTS[(seed + seed // 3) % 3]
    clip = [None, 0.05][(seed + seed // 2) % 2]
    if zero != 3:
        rng.choice(2)  # a retired draw: keeps the seed's later draws in place
    bsplit = bool((seed + seed // 6) % 2) and V == 1 and pp > 1
    tp = 2 if (seed + seed // 5) % 2 and dp * pp <= 4 else 1
    fused = bool((seed + seed // 4) % 2) and zero != 3
    n_stages = pp * V
    n_sizes = n_stages * int(rng.randint(2, 4))
    widths = sorted(rng.randint(8, 48, size=n_sizes - 1).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(pp * rng.choice([1, 2]))  # interleaved needs M % pp == 0
    B = int(dp * M * rng.choice([4, 8]))
    sched = S.InterleavedSchedule if V > 1 else (
        S.PipeDreamFlushSchedule if bsplit else SCHEDS[seed % 3])
    return sizes, dp, pp, V, M, B, opt, zero, sched, clip, fused, bsplit, tp


@pytest.mark.parametrize(
    "seed",
    # seed 3 (zero=3 — the most exotic point of the new lattice
    # dimension) keeps tier-1 coverage; the rest ride the slow tier
    # (1-core wall budget; stage 2 has dedicated tier-1 legs in
    # test_zero23.py)
    [s if s == 3 else pytest.param(s, marks=pytest.mark.slow)
     for s in range(12)],
)
def test_random_r5_zero_stage_combo_matches_sequential(seed):
    """Random ZeRO-stage draws crossed with tp/backward-split/
    interleaved must still equal sequential training — the dp-axis
    residency lattice is invisible to the math on every layout."""
    (
        sizes, dp, pp, V, M, B, opt, zero, sched, clip, fused, bsplit, tp,
    ) = _random_case_r5(seed)
    _assert_lattice_case_matches_sequential(
        sizes, dp, pp, V, M, B, opt, False, sched, clip, fused,
        data_seed=9500 + seed, bsplit=bsplit, tp=tp, zero=zero,
    )


BSPLIT_LAYOUTS = {
    # layout -> (dp, pp, zero1, schedule, clip)
    "pp4-gpipe": (1, 4, False, S.GPipeSchedule, None),
    "pp4-pipedream-clip": (1, 4, False, S.PipeDreamFlushSchedule, 0.05),
    "dp2pp2-clip": (2, 2, False, S.GPipeSchedule, 0.05),
    "zero1": (2, 2, True, S.PipeDreamFlushSchedule, None),
    "dp2-naive": (2, 1, False, S.NaiveParallelSchedule, None),
}


@pytest.mark.parametrize("layout", sorted(BSPLIT_LAYOUTS))
def test_backward_split_bitwise_identical_to_unsplit(layout):
    """The split-backward acceptance criterion: two-stage backward (B-input
    at the combined backward's tick, B-weight deferred into bubbles) is
    BITWISE identical to the unsplit schedule — final weights, loss AND
    the pre-clip global grad norm — across dp x pp x clip
    combinations, GPipe and 1F1B (and naive) alike. The lowering enforces
    the weight-grad accumulation order this equality depends on."""
    dp, pp, zero1, sched, clip = BSPLIT_LAYOUTS[layout]
    sizes = (40, 36, 32, 28, 24, 20, 14, 10)
    M, B = 4, 32
    spec = Mo.make_model_spec(sizes, pp, B)
    mesh = make_mesh(dp, pp)
    rng = np.random.RandomState(11)
    X = rng.randn(2, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, B))]

    def train(bsplit):
        opt = SGD(0.01)
        prog = lower_schedule(sched, M, pp, backward_split=bsplit)
        stacked, flags = E.init_stacked(spec, mesh)
        ost = E.zero1_init_state(opt, spec, mesh) if zero1 else opt.init(stacked)
        step = E.make_pipeline_step(
            mesh, spec, prog, B // dp // M, opt, zero1=zero1,
            clip_norm=clip, with_grad_norm=True,
        )
        for i in range(2):
            stacked, ost, loss, gnorm = step(
                stacked, flags, ost, jnp.asarray(X[i]), jnp.asarray(Y[i])
            )
        return jax.device_get(stacked), float(loss), float(gnorm)

    base_w, base_loss, base_gn = train(False)
    w, loss, gn = train(True)
    assert loss == base_loss, layout
    assert gn == base_gn, layout
    for a, b in zip(jax.tree.leaves(base_w), jax.tree.leaves(w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=layout)


RECOMPUTE_LAYOUTS = {
    # layout -> (dp, pp, tp, zero1, schedule, bsplit, act)
    "pp4-gpipe": (1, 4, 1, False, S.GPipeSchedule, False, "relu"),
    "pp4-pipedream-split": (
        1, 4, 1, False, S.PipeDreamFlushSchedule, True, "relu",
    ),
    "dp2pp2-gelu": (2, 2, 1, False, S.GPipeSchedule, False, "gelu"),
    "zero1": (2, 2, 1, True, S.PipeDreamFlushSchedule, False, "relu"),
    "tp2-gelu": (1, 2, 2, False, S.GPipeSchedule, False, "gelu"),
}


@pytest.mark.parametrize(
    "layout",
    # the two pp4 layouts (plain + split, the recompute-smoke pair) keep
    # tier-1 coverage; the dp/zero1/tp compositions ride the slow tier
    # (1-core wall budget), still in the full suite
    [lay if lay.startswith("pp4") else
     pytest.param(lay, marks=pytest.mark.slow)
     for lay in sorted(RECOMPUTE_LAYOUTS)],
)
def test_recompute_bitwise_identical_to_stashed(layout):
    """The recompute acceptance criterion (arXiv 2004.09910): dropping the
    forward activation stash and re-running the stage forward inside the
    backward tick is BITWISE identical to stashed training — final
    weights, loss AND the pre-clip global grad norm — across dp x pp x
    tp x zero1 x split-backward and both activation
    families, with global-norm clipping active the whole time. The
    recompute forward re-executes character-identical slot expressions,
    so there is no tolerance to hide behind. The same pair of lowered
    programs must also PROVE the memory win: ``assert_recompute_peak_drop``
    replays both tick tables and refuses unless the recompute program's
    stash peak is strictly below its stashed twin's."""
    from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop

    dp, pp, tp, zero1, sched, bsplit, act = RECOMPUTE_LAYOUTS[layout]
    sizes = (40, 36, 32, 28, 24, 20, 14, 10)
    M, B = 4, 32
    spec = Mo.make_model_spec(sizes, pp, B, act=act)
    mesh = make_mesh(dp, pp, tp=tp)
    rng = np.random.RandomState(13)
    X = rng.randn(2, B, sizes[0]).astype(np.float32)
    Y = np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, B))]
    progs = {
        rec: lower_schedule(sched, M, pp, backward_split=bsplit, recompute=rec)
        for rec in (False, True)
    }
    drop = assert_recompute_peak_drop(progs[False], progs[True])
    assert (
        drop["stash_peak_recompute"] < drop["stash_peak_stashed"]
        or drop["stash_peak_stashed"] == 1
    ), (layout, drop)

    def train(rec):
        opt = SGD(0.01)
        stacked, flags = E.init_stacked(spec, mesh)
        ost = E.zero1_init_state(opt, spec, mesh) if zero1 else opt.init(stacked)
        step = E.make_pipeline_step(
            mesh, spec, progs[rec], B // dp // M, opt, zero1=zero1,
            clip_norm=0.05, with_grad_norm=True,
        )
        for i in range(2):
            stacked, ost, loss, gnorm = step(
                stacked, flags, ost, jnp.asarray(X[i]), jnp.asarray(Y[i])
            )
        return jax.device_get(stacked), float(loss), float(gnorm)

    base_w, base_loss, base_gn = train(False)
    w, loss, gn = train(True)
    assert loss == base_loss, layout
    assert gn == base_gn, layout
    for a, b in zip(jax.tree.leaves(base_w), jax.tree.leaves(w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=layout)


KILL_RESUME_LAYOUTS = {
    # layout -> (killed-run session kwargs, resumed-run session kwargs) —
    # they differ only for the elastic case, which restores a dp=2 run's
    # snapshot onto a dp=4 mesh (same global batch, so the deterministic
    # data order — the bit-identity prerequisite — is unchanged)
    "dp2": (dict(dp=2), dict(dp=2)),
    "gpipe-pp4": (
        dict(pp=4, schedule="gpipe", mubatches=4),
        dict(pp=4, schedule="gpipe", mubatches=4),
    ),
    "zero1": (
        dict(dp=2, pp=2, schedule="gpipe", zero1=True, optimizer="momentum"),
        dict(dp=2, pp=2, schedule="gpipe", zero1=True, optimizer="momentum"),
    ),
    "bsplit": (
        dict(pp=4, schedule="pipedream", backward_split=True, mubatches=4),
        dict(pp=4, schedule="pipedream", backward_split=True, mubatches=4),
    ),
    # activation recompute rides the same contract: the recompute tick is
    # program structure, not state — snapshots hold logical params only
    "recompute": (
        dict(pp=4, schedule="gpipe", recompute=True, mubatches=4),
        dict(pp=4, schedule="gpipe", recompute=True, mubatches=4),
    ),
    "elastic-dp2-to-dp4": (
        dict(dp=2, optimizer="momentum"),
        dict(dp=4, optimizer="momentum"),
    ),
    # tensor parallelism rides the same contract: a tp2 run's snapshot is
    # layout-free host data (the stacked tp shards reassemble to logical
    # params before saving), so kill-and-resume at tp2 is bitwise...
    "tp2": (dict(tp=2), dict(tp=2)),
    # ...and a dp2 snapshot restores onto a tp2 mesh exactly (the elastic
    # leg: exact at the restore point, cross-layout tolerance at the
    # finish line — the Megatron psums reassociate the split
    # contractions, like a dp-width change reassociates the all-reduce)
    "elastic-dp2-to-tp2": (
        dict(dp=2, optimizer="momentum"),
        dict(tp=2, optimizer="momentum"),
    ),
}


@pytest.fixture(scope="module")
def session_data_dir(tmp_path_factory):
    sizes = (24, 20, 18, 16, 14, 12, 11, 10)
    d = tmp_path_factory.mktemp("kill_resume_data")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        np.save(d / f"x_{suffix}.npy", rng.randn(n, sizes[0]).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], n)],
        )
    return d


@pytest.mark.parametrize(
    "layout",
    [
        # the elastic restores run two full sessions each and are the
        # slowest legs — exotic layouts ride the slow tier (1-core wall
        # budget); the same-layout legs keep tier-1 coverage. The
        # recompute leg rides slow too: checkpoints are recompute-
        # agnostic by construction and make recompute-smoke drives the
        # same parity end to end
        pytest.param(lay, marks=pytest.mark.slow)
        if lay.startswith("elastic") or lay == "recompute"
        else lay
        for lay in sorted(KILL_RESUME_LAYOUTS)
    ],
)
def test_kill_and_resume_bitwise_identical_to_uninterrupted(
    layout, session_data_dir, tmp_path
):
    """The kill-and-resume lattice dimension (docs/robustness.md): on every
    feature layout — dp, pipeline, ZeRO-1, split
    backward — a run killed by an injected fault at a mid-epoch step and
    resumed from its last step snapshot finishes on exactly the bits of
    the uninterrupted twin. The ELASTIC dp=2 -> dp=4 restore is exact at
    the restore point (same logical snapshot, bit-identical load onto the
    wider mesh) and float-equivalent at the finish line — a different dp
    width reassociates the gradient all-reduce sum, so the cross-WIDTH
    comparison carries the repo's cross-layout tolerance, not bitwise."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.faults import InjectedFault

    kw_killed, kw_resumed = KILL_RESUME_LAYOUTS[layout]
    # pp=4 needs 8 sizes (2 per stage); everything shallower runs a 3-layer
    # model — the recovery contract is about state capture, not depth, and
    # compile time is what this lattice mostly spends
    pp = kw_killed.get("pp", 1)
    common = dict(
        sizes=(24, 20, 18, 16, 14, 12, 11, 10) if pp == 4 else (24, 18, 14, 10),
        global_batch_size=64,  # 4 steps/epoch over the 256-sample shard
        lr=0.01,
        data_dir=session_data_dir,
    )
    twin = TrainingSession(**common, **kw_killed)
    for _ in range(2):
        twin.train_epoch()

    ck = tmp_path / "ck"
    run = TrainingSession(
        **common, **kw_killed, checkpoint_dir=ck, faults="die@step=5"
    )
    with pytest.raises(InjectedFault):
        while run.epoch < 2:
            run.train_steps(2)
            run.save_step_checkpoint()

    res = TrainingSession(
        **common, **kw_resumed, checkpoint_dir=ck, resume="auto"
    )
    assert res.resumed_from is not None and res.global_step == 5, layout
    elastic = kw_killed != kw_resumed
    if elastic:
        # the restore itself is exact across widths: at the restore point
        # the dp=4 session's layout-independent hash equals the snapshot's
        # logical params hash, bit for bit
        from shallowspeed_tpu import utils
        from shallowspeed_tpu.checkpoint import load_checkpoint

        snap_params, _, _ = load_checkpoint(res.resumed_from, 1)
        assert_models_equal(
            res.params(), snap_params, f"resumed[{layout}]", "snapshot"
        )
    while res.epoch < 2:
        res.train_steps(2)
    if not elastic:
        assert_models_equal(
            res.params(), twin.params(), f"resumed[{layout}]", "twin"
        )
    else:
        want = [l for st in twin.params() for l in st]
        got = [l for st in res.params() for l in st]
        for a, b in zip(want, got):
            np.testing.assert_allclose(
                np.asarray(a["W"]), np.asarray(b["W"]),
                rtol=3e-4, atol=3e-6, err_msg=layout,
            )


@pytest.mark.parametrize("seed", range(12))
def test_random_kernel_variant_fuzz(seed):
    """Sequential kernel-variant fuzz: random single-stage shapes, optimizer,
    clip and weight decay — the mega- and epoch-kernels must stay
    BIT-identical to the fused-XLA epoch, not just at the handcrafted
    shapes of their dedicated tests."""
    rng = np.random.RandomState(5000 + seed)
    L = int(rng.randint(2, 6))
    widths = sorted(rng.randint(8, 40, size=L).tolist(), reverse=True)
    sizes = tuple(widths) + (int(rng.randint(4, min(8, min(widths)) + 1)),)
    M = int(rng.choice([1, 2, 4]))
    B = int(M * rng.choice([4, 8]))
    nb = int(rng.randint(1, 4))
    opt = OPTS[seed % 2]  # the kernels' optimizers: SGD, momentum (no adam)
    clip = [None, 0.05][(seed // 2) % 2]

    X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
    Y = jnp.asarray(
        np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (nb, M, B // M))]
    )
    spec = Mo.make_model_spec(sizes, 1, B)
    label = f"sizes={sizes} M={M} B={B} nb={nb} {type(opt).__name__} clip={clip}"
    out = {}
    for name, kw in {
        "xla": {},
        "mega": {"megakernel": True},
        "epoch": {"epoch_kernel": True},
    }.items():
        params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
        st = opt.init(params)
        epoch = trainer.make_train_epoch(
            spec, opt, fuse_mubatches=True, clip_norm=clip, **kw
        )
        params, st, loss = epoch(params, st, X, Y)
        out[name] = (jax.device_get(params), jax.device_get(st), float(loss))
    # the whole-RUN kernel at n_epochs=1 must land on the same bits too
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
    st = opt.init(params)
    run = trainer.make_train_run(
        spec, opt, fuse_mubatches=True, with_eval=False, run_kernel=True,
        clip_norm=clip,
    )
    p_r, st_r, losses_r = run(params, st, X, Y, 1)
    out["run"] = (
        jax.device_get(p_r), jax.device_get(st_r), float(losses_r[0])
    )
    for other in ("mega", "epoch", "run"):
        assert out["xla"][2] == out[other][2], label
        for tree_idx in (0, 1):
            for a, b in zip(
                jax.tree.leaves(out["xla"][tree_idx]),
                jax.tree.leaves(out[other][tree_idx]),
            ):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=label
                )


# ---------------------------------------------------------------------------
# the async-save dimension of the kill-resume lattice (PR 12)
# ---------------------------------------------------------------------------

ASYNC_KILL_LAYOUTS = {
    "dp2": ["--dp", "2"],
    "gpipe-pp4": ["--pp", "4", "--schedule", "gpipe"],
    "tp2": ["--tp", "2"],
}


@pytest.fixture(scope="module")
def flagship_data_dir(tmp_path_factory):
    """784-dim synthetic data: the subprocess legs drive the real train.py,
    which trains the flagship model."""
    d = tmp_path_factory.mktemp("async_kill_data")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        np.save(d / f"x_{suffix}.npy", rng.rand(n, 784).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)],
        )
    return d


@pytest.mark.parametrize(
    "layout",
    [
        "dp2",
        # the writer-window contract is layout-free host-side snapshot
        # logic, so one tier-1 subprocess leg suffices; the tp2/pp4 twins
        # ride the slow tier (1-core wall budget), still in the full suite
        pytest.param("gpipe-pp4", marks=pytest.mark.slow),
        pytest.param("tp2", marks=pytest.mark.slow),
    ],
)
def test_async_save_sigkill_in_writer_window_resumes_bitwise(
    layout, flagship_data_dir, tmp_path
):
    """The async-save dimension of the kill-resume lattice
    (docs/robustness.md "The async writer's crash windows"): a REAL
    train.py process checkpointing through the background writer is
    SIGKILLed at a fault-injected point INSIDE the writer's
    write/verify/rename window (die@save=N — after the temp file is
    durable, before the rename), across dp2 / gpipe-pp4 / tp2. The
    contract: `find_latest_good` never sees a torn or unverified file
    (only older fully-verifying snapshots are discoverable; the victim's
    temp is rename-invisible), and the resumed run finishes bitwise
    identical to the uninterrupted twin."""
    import os
    import re
    import subprocess
    import sys
    from pathlib import Path

    from shallowspeed_tpu.checkpoint import (
        find_latest_good,
        list_step_checkpoints,
    )

    root = Path(__file__).resolve().parent.parent
    lflags = ASYNC_KILL_LAYOUTS[layout]
    common = [
        "--data-dir", str(flagship_data_dir), "--epochs", "2",
        "--global-batch-size", "32", "--no-eval",
    ]

    def run(args, check=True, faults_spec=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("SHALLOWSPEED_FAULTS", None)
        if faults_spec:
            env["SHALLOWSPEED_FAULTS"] = faults_spec
        r = subprocess.run(
            [sys.executable, str(root / "train.py"), *args],
            capture_output=True, text=True, timeout=540, cwd=root, env=env,
        )
        if check:
            assert r.returncode == 0, r.stderr[-2000:]
        return r

    twin = run(common + lflags)
    twin_hash = re.search(r"final model hash: ([0-9a-f]{40})", twin.stdout)
    assert twin_hash, twin.stdout

    ck = tmp_path / "ck"
    killed_args = common + lflags + [
        "--checkpoint-dir", str(ck), "--checkpoint-every-steps", "3",
        "--async-checkpoint",
    ]
    r = run(
        killed_args, check=False, faults_spec="die@save=2:mode=sigkill"
    )
    assert r.returncode == -9, (r.returncode, r.stderr[-1000:])
    # saves land at steps 3, 6, 9, ... — save seq 2 (step 9) was killed
    # INSIDE the window: its temp is durable but never renamed, so
    # discovery sees only the older fully-verifying snapshots
    steps = [gs for gs, _ in list_step_checkpoints(ck)]
    assert steps == [3, 6], (layout, steps)
    p, meta, skipped = find_latest_good(ck)
    assert p is not None and p.name == "step-00000006.npz", layout
    assert skipped == [], (layout, skipped)  # nothing torn is discoverable

    resumed = run(killed_args + ["--resume", "auto"])
    assert "resumed at epoch" in resumed.stdout, resumed.stdout
    res_hash = re.search(
        r"final model hash: ([0-9a-f]{40})", resumed.stdout
    )
    assert res_hash and res_hash.group(1) == twin_hash.group(1), layout
