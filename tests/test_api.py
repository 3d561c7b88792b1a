"""TrainingSession API tests: layout uniformity, resume, hash stability."""

import numpy as np
import pytest

from shallowspeed_tpu.api import TrainingSession

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
N, GBS = 512, 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", N), ("val", 128)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


def _session(data_dir, **kw):
    kw.setdefault("sizes", SIZES)
    kw.setdefault("global_batch_size", GBS)
    kw.setdefault("lr", 0.01)
    return TrainingSession(data_dir=data_dir, **kw)


def test_layouts_converge_to_same_hash_class(data_dir):
    """Sequential, DP, PP and DP x PP sessions train to matching weights."""
    results = {}
    for name, kw in {
        "seq": dict(),
        "dp2pp4": dict(dp=2, pp=4, schedule="gpipe"),
        "pp4": dict(pp=4, schedule="pipedream"),
    }.items():
        run = _session(data_dir, **kw)
        for _ in range(2):
            run.train_epoch()
        run.assert_replicas_in_sync()
        results[name] = [l for st in run.params() for l in st]
        assert run.epoch == 2
    for other in ("dp2pp4", "pp4"):
        for a, b in zip(results["seq"], results[other]):
            np.testing.assert_allclose(
                np.asarray(a["W"]), np.asarray(b["W"]), rtol=3e-4, atol=3e-6
            )


def test_train_epoch_returns_decreasing_loss(data_dir):
    run = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    losses = [run.train_epoch() for _ in range(3)]
    assert losses[2] < losses[0]


def test_accuracy_runs_all_layouts(data_dir):
    for kw in (dict(), dict(pp=4, schedule="gpipe")):
        run = _session(data_dir, **kw)
        acc = run.accuracy()
        assert 0.0 <= acc <= 1.0


def test_predict_agrees_across_layouts(data_dir):
    """Public predict(): same probabilities on every layout, ragged batch."""
    x = np.random.RandomState(7).randn(13, SIZES[0]).astype(np.float32)
    runs = [
        _session(data_dir, **kw)
        for kw in (
            dict(),
            dict(dp=2, pp=2, schedule="gpipe"),
            dict(pp=2, schedule="interleaved", virtual_stages=2),
        )
    ]
    preds = [r.predict(x) for r in runs]
    for p in preds:
        assert p.shape == (13, SIZES[-1])
        np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-4)
    for p in preds[1:]:
        np.testing.assert_allclose(p, preds[0], rtol=2e-4, atol=2e-5)


def test_save_resume_round_trip(data_dir, tmp_path):
    run = _session(data_dir)
    run.train_epoch()
    ck = tmp_path / "ck.npz"
    run.save(ck)
    resumed = _session(data_dir, dp=2, pp=4, schedule="gpipe", resume=ck)
    assert resumed.epoch == 1
    assert resumed.model_hash() == run.model_hash()  # layout-independent hash


def test_momentum_resume_matches_uninterrupted_run(data_dir, tmp_path):
    """Velocity is checkpointed: save-after-epoch-1 + resume must reproduce
    the uninterrupted 2-epoch trajectory bit-for-bit on the same layout, and
    within float tolerance across layouts (velocity re-partitioned like the
    weights)."""
    ref = _session(data_dir, optimizer="momentum")
    ref.train_epoch()
    ref.train_epoch()

    run = _session(data_dir, optimizer="momentum")
    run.train_epoch()
    ck = tmp_path / "m.npz"
    run.save(ck)

    resumed = _session(data_dir, optimizer="momentum", resume=ck)
    resumed.train_epoch()
    assert resumed.model_hash() == ref.model_hash()

    resumed_pp = _session(
        data_dir, optimizer="momentum", dp=2, pp=4, schedule="gpipe", resume=ck
    )
    resumed_pp.train_epoch()
    want = [l for st in ref.params() for l in st]
    got = [l for st in resumed_pp.params() for l in st]
    for a, b in zip(want, got):
        np.testing.assert_allclose(
            np.asarray(a["W"]), np.asarray(b["W"]), rtol=3e-4, atol=3e-6
        )

    # cross-layout state round-trip: save from the mesh layout, resume seq
    ck2 = tmp_path / "m2.npz"
    resumed_pp.save(ck2)
    back = _session(data_dir, optimizer="momentum", resume=ck2)
    st = back.opt_state_logical()
    assert st is not None
    vel = st["parts"][""]
    assert sum(float(np.abs(np.asarray(l["W"])).sum()) for s in vel for l in s) > 0


def test_adam_pipeline_equals_sequential_and_resumes(data_dir, tmp_path):
    """Adam's multi-part state (m, v, step count) through the full surface:
    layout parity, checkpoint round-trip, bit-exact same-layout resume."""
    ref = _session(data_dir, optimizer="adam")
    ref.train_epoch()
    ref.train_epoch()

    pp = _session(data_dir, optimizer="adam", dp=2, pp=4, schedule="gpipe")
    pp.train_epoch()
    pp.train_epoch()
    want = [l for st in ref.params() for l in st]
    got = [l for st in pp.params() for l in st]
    for a, b in zip(want, got):
        np.testing.assert_allclose(
            np.asarray(a["W"]), np.asarray(b["W"]), rtol=3e-4, atol=3e-6
        )

    run = _session(data_dir, optimizer="adam")
    run.train_epoch()
    ck = tmp_path / "a.npz"
    run.save(ck)
    st = run.opt_state_logical()
    assert set(st["parts"]) == {"m", "v"} and st["scalars"]["t"] > 0
    resumed = _session(data_dir, optimizer="adam", resume=ck)
    resumed.train_epoch()
    assert resumed.model_hash() == ref.model_hash()

    # and across layouts, through the stacked-state path
    resumed_pp = _session(
        data_dir, optimizer="adam", dp=2, pp=2, schedule="pipedream", resume=ck
    )
    resumed_pp.train_epoch()
    got2 = [l for s in resumed_pp.params() for l in s]
    for a, b in zip(want, got2):
        np.testing.assert_allclose(
            np.asarray(a["W"]), np.asarray(b["W"]), rtol=3e-4, atol=3e-6
        )


def test_optimizer_mismatch_on_resume_rejected(data_dir, tmp_path):
    run = _session(data_dir, optimizer="momentum")
    run.train_epoch()
    ck = tmp_path / "m.npz"
    run.save(ck)
    with pytest.raises(ValueError, match="optimizer"):
        _session(data_dir, optimizer="sgd", resume=ck)
    with pytest.raises(ValueError, match="momentum"):
        _session(data_dir, optimizer="momentum", momentum=0.5, resume=ck)


def test_invalid_config_rejected(data_dir):
    with pytest.raises(ValueError):
        _session(data_dir, dp=3)  # 64 % 3 != 0
    with pytest.raises(ValueError):
        _session(data_dir, mubatches=7)
    with pytest.raises(ValueError):
        _session(data_dir, precision="float32")
    with pytest.raises(ValueError):
        _session(data_dir, pp=2, schedule="1f1b")  # not a registered name
    with pytest.raises(ValueError):
        _session(data_dir, global_batch_size=4096)  # > training split


def test_backward_split_validation(data_dir):
    with pytest.raises(ValueError, match="sequential path has no schedule"):
        _session(data_dir, backward_split=True)  # dp=pp=1: no schedule
    with pytest.raises(ValueError, match="interleaved"):
        _session(data_dir, pp=2, schedule="interleaved", virtual_stages=2,
                 backward_split=True)
    with pytest.raises(ValueError, match="pallas"):
        _session(data_dir, pp=2, schedule="gpipe", kernel_backend="pallas",
                 backward_split=True)


def test_recompute_validation(data_dir):
    """The recompute refusal matrix: every unsupported combination is
    refused at construction with an error naming the reason, not at the
    first backward tick."""
    with pytest.raises(ValueError, match="no cross-tick stash"):
        _session(data_dir, recompute=True)  # dp=pp=1: nothing stashed
    with pytest.raises(ValueError, match="interleaved virtual"):
        _session(data_dir, pp=2, schedule="interleaved", virtual_stages=2,
                 recompute=True)
    with pytest.raises(ValueError, match="no recompute branch"):
        _session(data_dir, pp=2, schedule="gpipe", kernel_backend="pallas",
                 recompute=True)


def test_model_zoo_validation(data_dir):
    """Zoo resolution refusals: unknown names list the zoo; gelu-family
    models refuse the relu-only pallas backend by name."""
    with pytest.raises(ValueError, match="unknown model"):
        _session(data_dir, model="mnist-cnn")
    with pytest.raises(ValueError, match="gelu-family"):
        _session(data_dir, model="transformer", pp=2, schedule="gpipe",
                 kernel_backend="pallas")


def test_backward_split_session_matches_unsplit(data_dir):
    """Split vs unsplit THROUGH the session surface (per-epoch loop and
    the fused run, ZeRO-1 included): identical model hashes — the split
    schedule changes tick packing, never the training computation."""
    runs = {}
    for bs in (False, True):
        run = _session(data_dir, pp=4, schedule="pipedream", backward_split=bs)
        run.train_epoch()
        runs[bs] = run.model_hash()
        fused = _session(data_dir, dp=2, pp=2, schedule="gpipe", zero1=True,
                         clip_norm=0.05, backward_split=bs)
        fused.train_run(1, with_eval=False)
        runs[f"z1-{bs}"] = fused.model_hash()
    assert runs[False] == runs[True]
    assert runs["z1-False"] == runs["z1-True"]


def test_train_run_matches_epoch_loop(data_dir):
    """The fused multi-epoch program (one dispatch for every epoch + its
    on-device full-split accuracy) must reproduce the looped
    train_epoch()/accuracy() path: same losses, same accuracies, same
    final weights."""
    looped = _session(data_dir)
    loop_losses, loop_accs = [], []
    for _ in range(3):
        loop_losses.append(looped.train_epoch())
        loop_accs.append(looped.accuracy())

    fused = _session(data_dir)
    losses, accs = fused.train_run(3)
    assert fused.epoch == 3
    assert np.allclose(losses, loop_losses, rtol=1e-6, atol=0)
    assert np.allclose(accs, loop_accs, atol=1e-6)
    assert fused.model_hash() == looped.model_hash()

    # a second fused run continues from the advanced state
    more_losses, _ = fused.train_run(2)
    assert fused.epoch == 5
    assert more_losses[0] < losses[0]


def test_train_run_mesh_fused(data_dir):
    """Mesh layouts run the whole multi-epoch program on-device too
    (executor.make_pipeline_run) and agree with the sequential run and with
    the mesh epoch loop."""
    run = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    losses, accs = run.train_run(2)
    assert len(losses) == len(accs) == 2 and run.epoch == 2

    seq = _session(data_dir)
    seq_losses, seq_accs = seq.train_run(2)
    assert np.allclose(losses, seq_losses, rtol=1e-5)
    assert np.allclose(accs, seq_accs, atol=1e-6)

    looped = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    loop_losses = [looped.train_epoch() for _ in range(2)]
    assert np.allclose(losses, loop_losses, rtol=1e-6)
    assert run.model_hash() == looped.model_hash()

    # losses-only variant and the interleaved inference-program branch
    ne = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    ne_losses, ne_accs = ne.train_run(2, with_eval=False)
    assert ne_accs is None and np.allclose(ne_losses, losses, rtol=1e-6)
    iv = _session(data_dir, pp=2, virtual_stages=2, schedule="interleaved")
    iv_losses, iv_accs = iv.train_run(2)
    assert len(iv_losses) == len(iv_accs) == 2


def test_train_run_rejects_nonpositive_epochs(data_dir):
    with pytest.raises(ValueError, match="epochs"):
        _session(data_dir).train_run(0)


def test_train_run_without_eval(data_dir):
    """with_eval=False: no val split load, accs is None, same training."""
    ref = _session(data_dir)
    ref_losses, _ = ref.train_run(2)
    run = _session(data_dir)
    losses, accs = run.train_run(2, with_eval=False)
    assert accs is None and run._vx is None  # val split never loaded
    assert np.allclose(losses, ref_losses, rtol=1e-6, atol=0)
    assert run.model_hash() == ref.model_hash()


def test_warm_run_precompiles_and_matches(data_dir):
    """warm_run AOT-compiles the fused program; the next train_run reuses the
    executable and produces identical results to the un-warmed path."""
    ref = _session(data_dir)
    ref_losses, ref_accs = ref.train_run(2)

    warmed = _session(data_dir)
    warmed.warm_run(2)
    assert (True, 2) in warmed._compiled_runs
    losses, accs = warmed.train_run(2)
    assert np.allclose(losses, ref_losses, rtol=1e-6, atol=0)
    assert np.allclose(accs, ref_accs, atol=1e-6)
    assert warmed.model_hash() == ref.model_hash()

    # mesh layout too
    m = _session(data_dir, dp=2, pp=2, schedule="gpipe")
    m.warm_run(2)
    m_losses, _ = m.train_run(2)
    assert np.allclose(m_losses, ref_losses, rtol=1e-5)


def test_kernel_backend_pallas_matches_xla_via_api(data_dir):
    """The executor's Pallas backend through the product surface
    (TrainingSession(kernel_backend="pallas")): bit-identical training and
    evaluation vs the XLA backend on a DP x PP mesh."""
    runs = {}
    for kb in ("xla", "pallas"):
        run = _session(data_dir, dp=2, pp=2, schedule="gpipe", kernel_backend=kb)
        losses = [run.train_epoch() for _ in range(2)]
        runs[kb] = (
            losses,
            [l for st in run.params() for l in st],
            run.accuracy(),
        )
    assert runs["xla"][0] == runs["pallas"][0]
    for a, b in zip(runs["xla"][1], runs["pallas"][1]):
        np.testing.assert_array_equal(np.asarray(a["W"]), np.asarray(b["W"]))
        np.testing.assert_array_equal(np.asarray(a["b"]), np.asarray(b["b"]))
    assert runs["xla"][2] == runs["pallas"][2]


def test_kernel_backend_validation(data_dir):
    with pytest.raises(ValueError, match="kernel_backend"):
        _session(data_dir, kernel_backend="mosaic")
    # the sequential path has its own pallas routes (megakernel /
    # SHALLOWSPEED_PALLAS); the executor backend needs a mesh
    with pytest.raises(ValueError, match="mesh layout"):
        _session(data_dir, kernel_backend="pallas")


def test_epoch_kernel_matches_fused_via_api(data_dir):
    """TrainingSession(epoch_kernel=True): the whole-epoch Pallas kernel
    through the product surface trains bit-identically to the fused XLA
    path (and its epoch losses match)."""
    runs = {}
    for kw in ({}, {"epoch_kernel": True}):
        run = _session(data_dir, fuse_mubatches=True, **kw)
        losses = [run.train_epoch() for _ in range(2)]
        runs[bool(kw)] = (losses, run.model_hash())
    assert runs[False][0] == runs[True][0]
    assert runs[False][1] == runs[True][1]


def test_momentum_epoch_kernel_checkpoint_resume_cross_layout(data_dir, tmp_path):
    """Optimizer state PRODUCED BY the epoch kernel (momentum's velocity
    mirror, advanced inside the kernel) must ride the checkpoint
    protocol like scan-produced state: resuming an interrupted kernel run
    reproduces the uninterrupted trajectory bit-for-bit, and the same
    checkpoint resumes onto a DP x PP mesh."""
    kw = dict(
        optimizer="momentum", lr=1e-3, fuse_mubatches=True, epoch_kernel=True
    )
    ref = _session(data_dir, **kw)
    ref.train_epoch()
    ref.train_epoch()

    run = _session(data_dir, **kw)
    run.train_epoch()
    ck = tmp_path / "momentum_kernel.npz"
    run.save(ck)
    resumed = _session(data_dir, resume=ck, **kw)
    resumed.train_epoch()
    assert resumed.model_hash() == ref.model_hash()

    # cross-layout: the kernel-trained state stacks onto a mesh session
    mesh = _session(
        data_dir, optimizer="momentum", lr=1e-3, dp=2, pp=2, schedule="gpipe",
        resume=ck,
    )
    mesh.train_epoch()
    np.testing.assert_allclose(
        np.concatenate([
            np.asarray(l["W"]).ravel() for st in mesh.params() for l in st
        ]),
        np.concatenate([
            np.asarray(l["W"]).ravel() for st in ref.params() for l in st
        ]),
        rtol=2e-4, atol=2e-6,
    )


def test_run_kernel_via_api_matches_epoch_kernel(data_dir):
    """TrainingSession(run_kernel=True): the eval-free fused run is ONE
    device op and must reproduce the epoch-kernel session's losses and
    final hash; the evaluated surfaces (train_epoch, accuracy) still work
    and ride the epoch kernel."""
    from shallowspeed_tpu.api import TrainingSession

    losses = {}
    hashes = {}
    for kw in ({"epoch_kernel": True}, {"run_kernel": True}):
        run = TrainingSession(
            sizes=SIZES, data_dir=data_dir, fuse_mubatches=True,
            global_batch_size=32, mubatches=2, **kw,
        )
        losses[tuple(kw)], _ = run.train_run(2, with_eval=False)
        hashes[tuple(kw)] = run.model_hash()
        assert 0.0 <= run.accuracy() <= 1.0  # eval path still alive
    assert losses[("epoch_kernel",)] == losses[("run_kernel",)]
    assert hashes[("epoch_kernel",)] == hashes[("run_kernel",)]


def test_run_kernel_api_validation(data_dir):
    from shallowspeed_tpu.api import TrainingSession
    import pytest as _pytest

    with _pytest.raises(ValueError, match="fuse_mubatches"):
        TrainingSession(sizes=SIZES, data_dir=data_dir, run_kernel=True)
    with _pytest.raises(ValueError, match="subsumes"):
        TrainingSession(
            sizes=SIZES, data_dir=data_dir, fuse_mubatches=True,
            run_kernel=True, epoch_kernel=True,
        )


def test_run_kernel_state_rides_checkpoint_protocol(data_dir, tmp_path):
    """Optimizer state produced INSIDE the whole-run kernel (momentum's
    velocity mirror advanced across a multi-epoch grid) must ride
    the checkpoint protocol: save after a 2-epoch one-op run, resume, and
    land bit-for-bit on the uninterrupted 4-epoch one-op run."""
    kw = dict(
        optimizer="momentum", lr=1e-3, fuse_mubatches=True, run_kernel=True
    )
    ref = _session(data_dir, **kw)
    ref.train_run(4, with_eval=False)

    run = _session(data_dir, **kw)
    run.train_run(2, with_eval=False)
    ck = tmp_path / "run_kernel.npz"
    run.save(ck)
    resumed = _session(data_dir, resume=ck, **kw)
    resumed.train_run(2, with_eval=False)
    assert resumed.model_hash() == ref.model_hash()
