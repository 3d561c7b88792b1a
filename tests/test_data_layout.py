"""The orientation of the resident training set (trainer.data_layout): which
sessions keep X feature-major, that such a session trains what the row-major
program trains, and that slicing, chunking and the fused run take the new
orientation as they are."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import trainer
from shallowspeed_tpu.api import PRECISIONS, TrainingSession
from shallowspeed_tpu.model import init_model, make_model_spec
from shallowspeed_tpu.observability.metrics import JsonlMetrics, read_jsonl
from shallowspeed_tpu.optimizer import SGD

SIZES = (24, 12, 12, 12, 12, 12, 11, 10)  # the first Linear halves its input
N, GBS, M = 1536, 512, 4  # three steps of four 128-row microbatches
MB = GBS // M


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("data_layout")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", N), ("val", 128)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(d / f"x_{suffix}.npy", x)
        np.save(d / f"y_{suffix}.npy", y)
    return d


def _session(data_dir, **kw):
    kw.setdefault("sizes", SIZES)
    kw.setdefault("global_batch_size", GBS)
    kw.setdefault("lr", 0.01)
    return TrainingSession(data_dir=data_dir, **kw)


def _leaves(run):
    return [np.asarray(a) for a in jax.tree.leaves(run.params())]


@pytest.fixture(scope="module")
def row_major_epoch(data_dir):
    """Weights and loss after one epoch of ``make_train_epoch`` on row-major
    arrays: the program every session ran before the orientation existed."""
    x = np.load(data_dir / "x_train.npy").reshape(N // GBS, M, MB, SIZES[0])
    y = np.load(data_dir / "y_train.npy").reshape(N // GBS, M, MB, SIZES[-1])
    spec = make_model_spec(SIZES, 1, GBS)
    params = jax.tree.map(jnp.asarray, init_model(spec))
    opt = SGD(0.01)
    epoch = trainer.make_train_epoch(spec, opt, precision=PRECISIONS["highest"])
    params, _, loss = epoch(params, opt.init(params), jnp.asarray(x), jnp.asarray(y))
    return [np.asarray(a) for a in jax.tree.leaves(params)], float(loss)


def _assert_close(got, want):
    # the same rows, products and precision; only the order of a sum inside
    # one matmul may differ: far inside the cross-layout rtol 3e-4
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


MNIST = (784, 128, 127, 126, 125, 124, 123, 10)
DEEP = (784,) + (2048,) * 22 + (10,)


@pytest.mark.parametrize(
    "mubatch_rows, sizes, precision, scanned, want",
    [
        (2048, MNIST, "highest", True, "feature_major"),  # mnist-mlp.seq-b8192
        (16384, MNIST, "highest", True, "feature_major"),
        (32, MNIST, "highest", True, "row_major"),  # mnist-mlp.seq-b128: 32 rows on 128 lanes
        (16384, DEEP, "default", True, "row_major"),  # mlp-deep.seq-b65536
        (2048, MNIST, "default", True, "row_major"),  # packed bfloat16 operand
        (2048, (784, 2048, 10), "highest", True, "row_major"),  # a wider first Linear
        (2048, (780, 128, 10), "highest", True, "row_major"),  # 780 features: no 8 sublanes
        (192, MNIST, "highest", True, "row_major"),
        (2048, MNIST, "highest", False, "row_major"),  # fused and kernel paths
    ],
)
def test_rule_reads_what_the_session_can_observe(
    mubatch_rows, sizes, precision, scanned, want
):
    got = trainer.data_layout(
        mubatch_rows, sizes, PRECISIONS[precision], scanned=scanned
    )
    assert got == want


def test_feature_major_session_trains_what_the_row_major_program_trains(
    data_dir, row_major_epoch
):
    run = _session(data_dir)
    assert run.data_layout == "feature_major"
    assert run._Xe.shape == (N // GBS, M, SIZES[0], MB)
    assert run._Ye.shape == (N // GBS, M, MB, SIZES[-1])  # Y stays as it was
    loss = run.train_epoch()
    want, want_loss = row_major_epoch
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _assert_close(_leaves(run), want)


def test_mid_epoch_slices_and_the_fused_run_take_the_orientation_unchanged(
    data_dir, row_major_epoch
):
    """``_sliced_epoch_args``, ``_fused_run_args`` and ``make_train_run``
    slice or scan axis 0 only, and share ``_make_epoch_core``: on the new
    orientation they run as they are, bitwise equal to the whole epoch."""
    whole = _session(data_dir)
    whole_loss = whole.train_epoch()

    chunked = _session(data_dir)
    assert chunked._sliced_epoch_args(1, 2)[2].shape == (1, M, SIZES[0], MB)
    assert chunked.train_steps(1) == (1, None)
    steps, loss = chunked.train_steps(5)  # clipped at the epoch's end
    assert steps == 2 and loss == pytest.approx(whole_loss, rel=1e-6)

    fused = _session(data_dir)
    assert fused._fused_run_args(False)[2].shape == (N // GBS, M, SIZES[0], MB)
    losses, accs = fused.train_run(1, with_eval=False)
    assert accs is None and losses == [pytest.approx(whole_loss, rel=1e-6)]

    for other in (chunked, fused):
        for a, b in zip(_leaves(other), _leaves(whole)):
            np.testing.assert_array_equal(a, b)
    _assert_close(_leaves(whole), row_major_epoch[0])


@pytest.mark.parametrize(
    "kw",
    [
        dict(global_batch_size=128),  # 32-row microbatches
        dict(fuse_mubatches=True),
        dict(precision="default"),
        dict(dp=2, pp=2, schedule="gpipe"),  # a mesh places its own batches
    ],
    ids=["mb32", "fused", "default", "mesh"],
)
def test_everything_else_stays_row_major(data_dir, row_major_epoch, kw):
    run = _session(data_dir, **kw)
    assert run.data_layout == "row_major"
    if run.placement() is None:
        assert run._Xe.shape[-1] == SIZES[0]
    run.train_epoch()
    if kw.get("global_batch_size", GBS) == GBS and "precision" not in kw:
        # the same steps at the same precision: the same weights
        for a, b in zip(_leaves(run), row_major_epoch[0]):
            np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-6)


@pytest.mark.parametrize(
    "kw, want",
    [
        (dict(), "feature_major"),
        (dict(dp=2, pp=2, schedule="gpipe"), "row_major"),
    ],
    ids=["seq", "mesh"],
)
def test_the_record_says_which_orientation_ran(data_dir, tmp_path, kw, want):
    """One ``data_layout`` event on both paths, with the microbatch's shape;
    a recorded session runs the instrumented epoch program (per-step aux),
    which reads the same orientation."""
    path = tmp_path / "run.jsonl"
    with JsonlMetrics(path) as m:
        run = _session(data_dir, metrics=m, **kw)
        loss = run.train_epoch()
    (event,) = [r for r in read_jsonl(path) if r.get("name") == "data_layout"]
    assert event["kind"] == "event" and event["layout"] == want == run.data_layout
    assert event["mb"] == GBS // kw.get("dp", 1) // M and event["F"] == SIZES[0]
    assert np.isfinite(loss)
    # an MLP has no scan: no path, and no event of that name
    assert run.scan_path is None
    assert not [r for r in read_jsonl(path) if r.get("name") == "scan_path"]


def test_trainer_refuses_the_orientation_off_the_scanned_path():
    with pytest.raises(ValueError, match="microbatch scan"):
        trainer.make_train_epoch(
            make_model_spec(SIZES, 1, GBS), SGD(0.01), fuse_mubatches=True,
            x_layout="feature_major",
        )
