"""ZeRO-2/3 on the dp axis: persistent gradient shards and JIT-gathered
parameters (arXiv 2004.13336 stages 2-3 on the zero1 checkpoint substrate).

The numerics contract the stages ship under (docs/performance.md):

- **ZeRO-2** reduce-scatters PER TICK into a persistent per-rank
  shard carry — that is what earns the grads÷dp residency row on the
  memory scoreboard (scripts/bench_zero.py). The shard sums
  microbatch-outer where zero-1's full-slab accumulator sums dp-outer, a
  different (equally valid) float reduction tree: bitwise-equal to
  zero-1 exactly at ``mubatches=1`` (one contribution per element — the
  psum_scatter value IS the psum chunk), tolerance-plus-determinism
  above it;
- **ZeRO-3** shards parameters at rest and all-gathers them just in time
  per tick; it shares the stage-2 scatter tree, so it carries the
  same tolerance contract plus same-layout A/B bit-determinism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import schedules as S
from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.optimizer import Adam, MomentumSGD
from shallowspeed_tpu.parallel import executor as E
from shallowspeed_tpu.parallel import lower_schedule, make_mesh

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
B, M, LR, NB = 64, 4, 0.01, 3


def _data(seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(NB, B, SIZES[0]).astype(np.float32)
    Y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, 10, (NB, B))]
    return X, Y


def _run(opt, dp, pp, zero, virtual=1, split=False, mub=M):
    X, Y = _data()
    mesh = make_mesh(dp, pp)
    spec = Mo.make_model_spec(SIZES, pp * virtual, B)
    order = E.interleave_order(pp * virtual, pp) if virtual > 1 else None
    sched = S.InterleavedSchedule if virtual > 1 else (
        S.PipeDreamFlushSchedule if split else S.GPipeSchedule)
    prog = lower_schedule(sched, mub, pp, virtual=virtual,
                          backward_split=split)
    stacked, flags = E.init_stacked(spec, mesh, order=order)
    if zero == 0:
        st = opt.init(stacked)
    elif zero == 1:
        st = E.zero1_init_state(opt, spec, mesh)
    else:
        st = E.zero_block_init_state(opt, spec, mesh)
    if zero == 3:
        host = jax.device_get(stacked)
        rows = E.zero_block_flatten_rows(host, spec, mesh)
        stacked = {"P": jax.device_put(rows, E.zero1_part_sharding(mesh))}
    step = E.make_pipeline_step(
        mesh, spec, prog, B // dp // mub, opt, zero=zero)
    for i in range(NB):
        stacked, st, loss = step(
            stacked, flags, st, jnp.asarray(X[i]), jnp.asarray(Y[i]))
    if zero == 3:
        host = E.zero_block_unflatten_rows(
            np.asarray(jax.device_get(stacked["P"])), spec, mesh)
        flat = [l for s in E.unstack_params(host, spec, order=order)
                for l in s]
    else:
        flat = [l for s in E.unstack_params(stacked, spec, order=order)
                for l in s]
    return flat, st, float(loss), (spec, mesh, order)


def _assert_layers(a, b, exact, rtol=1e-5, atol=1e-6):
    for x, y in zip(a, b):
        for k in ("W", "b"):
            if exact:
                np.testing.assert_array_equal(
                    np.asarray(x[k]), np.asarray(y[k]))
            else:
                np.testing.assert_allclose(
                    np.asarray(x[k]), np.asarray(y[k]), rtol=rtol, atol=atol)


@pytest.mark.slow
@pytest.mark.parametrize("opt", [MomentumSGD(LR, 0.9), Adam(LR)])
@pytest.mark.parametrize("dp,pp,virtual", [(2, 2, 1), (2, 2, 2)])
def test_zero2_anchor_tracks_zero1(opt, dp, pp, virtual):
    """Anchor stage 2's per-tick scatter sums microbatch-outer where
    zero-1 sums dp-outer: same math, reassociated — tolerance at M>1.
    (Slow tier, wall budget: tier-1 pins the chain z1 ~ z3 (tolerance,
    test_zero3_tracks_zero1) == z2 (bitwise, the session census test)
    plus z2 == z1 exactly at mubatches=1.)"""
    z1, _, _, _ = _run(opt, dp, pp, 1, virtual=virtual)
    z2, _, _, _ = _run(opt, dp, pp, 2, virtual=virtual)
    _assert_layers(z1, z2, exact=False)


@pytest.mark.slow
def test_zero23_deterministic():
    """Same layout, same data -> the reassociated tree is FIXED: two
    stage-2 (or stage-3) runs must agree bitwise, so the M>1 tolerance
    above is a reassociation allowance, not nondeterminism laundering.
    (Slow tier: the 1-core tier-1 wall budget is tight; the session
    census test pins z2==z3 bitwise in tier-1.)"""
    opt = MomentumSGD(LR, 0.9)
    a, _, _, _ = _run(opt, 2, 2, 2)
    b, _, _, _ = _run(opt, 2, 2, 2)
    _assert_layers(a, b, exact=True)
    c, _, _, _ = _run(opt, 2, 2, 3)
    d, _, _, _ = _run(opt, 2, 2, 3)
    _assert_layers(c, d, exact=True)


@pytest.mark.parametrize(
    "opt", [MomentumSGD(LR, 0.9),
            pytest.param(Adam(LR), marks=pytest.mark.slow)])
def test_zero2_anchor_bitwise_at_single_microbatch(opt):
    """mubatches=1: one contribution per shard element, so the per-tick
    psum_scatter value IS the corresponding psum chunk — bitwise zero-1
    (the fixed-layout hash pin the bench and zero-smoke assert)."""
    z1, _, _, _ = _run(opt, 2, 2, 1, mub=1)
    z2, _, _, _ = _run(opt, 2, 2, 2, mub=1)
    _assert_layers(z1, z2, exact=True)


@pytest.mark.parametrize(
    "dp,pp,virtual", [(2, 2, 1),
                      pytest.param(2, 2, 2, marks=pytest.mark.slow)])
def test_zero3_tracks_zero1(dp, pp, virtual):
    opt = MomentumSGD(LR, 0.9)
    z1, _, _, _ = _run(opt, dp, pp, 1, virtual=virtual)
    z3, _, _, _ = _run(opt, dp, pp, 3, virtual=virtual)
    _assert_layers(z1, z3, exact=False)


@pytest.mark.slow
def test_split_backward_zero23():
    """PipeDream backward-split composes with both stages: the B-weight
    tick contributes its grads through the same per-tick scatter.
    (Slow tier: the r5 fuzz lattice crosses split-backward with the zero
    dimension in tier-1.)"""
    opt = MomentumSGD(LR, 0.9)
    z1, _, _, _ = _run(opt, 2, 2, 1, split=True)
    z2, _, _, _ = _run(opt, 2, 2, 2, split=True)
    z3, _, _, _ = _run(opt, 2, 2, 3, split=True)
    _assert_layers(z1, z2, exact=False)
    _assert_layers(z1, z3, exact=False)


@pytest.mark.slow
def test_zero2_state_is_block_cyclic_sharded():
    opt = MomentumSGD(LR, 0.9)
    _, st, _, (spec, mesh, _) = _run(opt, 4, 2, 2)
    _, csz3 = E.zero_block_len(spec, mesh)
    vel = st[""]  # momentum's single params-shaped state part
    assert vel.shape == (2, 4 * csz3)
    assert all(s.data.shape == (1, csz3) for s in vel.addressable_shards)
    assert float(jnp.abs(vel).sum()) > 0


def test_zero3_params_at_rest_are_sharded():
    opt = MomentumSGD(LR, 0.9)
    _, _, _, (spec, mesh, _) = _run(opt, 2, 2, 3)
    # the executor's at-rest layout: one (1, csz3) row block per dp rank
    _, csz3 = E.zero_block_len(spec, mesh)
    rows = E.zero_block_flatten_rows(
        jax.device_get(E.init_stacked(spec, mesh)[0]), spec, mesh)
    assert rows.shape == (2, 2 * csz3)


def _write_dataset(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 64)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)


def test_session_zero23_audited_epochs(tmp_path):
    """TrainingSession surface under audit=True (census enforced at jit
    time): stages 2-3 train, track zero-1 within tolerance, and the
    recorded forecast prices the stage ladder from the shared layout
    math."""
    _write_dataset(tmp_path)
    kw = dict(
        sizes=SIZES, global_batch_size=B, lr=0.01, data_dir=tmp_path,
        optimizer="momentum", dp=2, pp=2, schedule="gpipe", audit=True,
    )
    runs = {}
    for zero in (2, 3):
        s = TrainingSession(zero=zero, **kw)
        s.train_epoch()
        s.assert_replicas_in_sync()
        runs[zero] = s
    # stages 2 and 3 run the SAME per-tick scatter tree (stage 3 only
    # adds the param gathers, which are exact) -> bitwise-equal weights;
    # tracking zero-1 itself is pinned at executor level and by the r5
    # fuzz lattice's sequential oracle
    p2 = [l for st in runs[2].params() for l in st]
    p3 = [l for st in runs[3].params() for l in st]
    _assert_layers(p2, p3, exact=True)
    zf = runs[2]._expected_comms["zero_forecast"]
    t = {k: v["total_bytes"] for k, v in zf["stages"].items()}
    assert t["2"] < t["1"] <= t["0"]
    # stage 2's dp axis declares the per-tick scatter schedule the census
    # (and the report's Comms line) render
    dp_axis = runs[2]._expected_comms["axes"]["dp"]
    assert dp_axis["zero"] == 2
    assert dp_axis["scatter_schedule"] == "per_tick"
    g3 = runs[3]._expected_comms["axes"]["dp"]["gather"]
    assert g3["schedule"] == "per_tick" and g3["passes"] >= 2


@pytest.mark.slow
def test_session_zero2_hash_pin_at_single_microbatch(tmp_path):
    """Slow tier: the same pin runs at executor level in tier-1
    (test_zero2_anchor_bitwise_at_single_microbatch) and end-to-end in
    `make zero-smoke` + the CLI leg."""
    _write_dataset(tmp_path)
    kw = dict(
        sizes=SIZES, global_batch_size=B, mubatches=1, lr=0.01,
        data_dir=tmp_path, optimizer="momentum", dp=2, pp=2,
        schedule="gpipe",
    )
    hashes = {}
    for zero in (1, 2):
        s = TrainingSession(zero=zero, **kw)
        s.train_epoch()
        hashes[zero] = s.model_hash()
    assert hashes[2] == hashes[1]


def test_session_zero3_checkpoint_reloads_everywhere(tmp_path):
    """Stage-3 snapshots are LOGICAL (the zero1 substrate): a z3 save
    hot-reloads into a plain session bitwise — elastic re-sharding for
    free."""
    _write_dataset(tmp_path)
    kw = dict(
        sizes=SIZES, global_batch_size=B, lr=0.01, data_dir=tmp_path,
        optimizer="momentum",
    )
    z3 = TrainingSession(dp=2, pp=2, schedule="gpipe", zero=3, **kw)
    z3.train_epoch()
    ck = tmp_path / "z3.npz"
    z3.save(ck)
    plain = TrainingSession(**kw)
    plain.load_weights(ck)
    assert plain.model_hash() == z3.model_hash()


SESSION_REFUSALS = {
    # id -> (constructor arguments, the refusal's own words)
    "zero-out-of-range": (dict(zero=5), "zero must be one of"),
    "zero1-against-zero": (dict(zero1=True, zero=2), "conflicting dp-stage"),
    "zero-on-no-mesh": (dict(zero=2), "shards the update"),
    "zero2-digests": (dict(zero=2, dp=2, digests=True), "digests"),
    "zero3-pallas": (dict(zero=3, dp=2, kernel_backend="pallas"), "pallas"),
    "zero-mpmd": (dict(zero=2, dp=2, pp=2, runtime="mpmd"), "mpmd"),
}


@pytest.mark.parametrize("case", sorted(SESSION_REFUSALS))
def test_session_refusals(case):
    """Each constructor refusal of the dp-stage knobs, by name: refused
    before any data is read (the data directory does not exist)."""
    kwargs, words = SESSION_REFUSALS[case]
    with pytest.raises(ValueError, match=words):
        TrainingSession(sizes=SIZES, data_dir="/nonexistent", **kwargs)
