"""The comparison that decides ``correct``, and the generator: a wrong update
fails at the configurations' written tolerances, an equal one passes, and the
training set depends on the seed alone."""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

import cells
import check
import datagen

BENCH = Path(__file__).resolve().parents[1]


def load_reference(name):
    return cells.load_module(BENCH / "references" / f"{name}.py")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A tiny relu MLP, its data, and the reference's own two steps on it."""
    config = json.loads((BENCH / "configs" / "mnist-mlp.json").read_text())
    config["session"]["sizes"] = [20, 16, 12, 10]
    X, Y = datagen.make_dataset(3, 64, 20, 10, tmp_path_factory.mktemp("d"))
    xb = np.array(X).reshape(2, 4, 8, 20)
    yb = np.array(Y).reshape(2, 4, 8, 10)
    rng = np.random.default_rng(0)
    sizes = config["session"]["sizes"]
    start = [
        {"W": rng.normal(0, 0.3, (o, i)).astype(np.float32), "b": np.zeros((1, o), np.float32)}
        for i, o in zip(sizes, sizes[1:])
    ]
    reference = load_reference(config["reference"])
    params, losses = reference.make_reference(config)(start, xb, yb)
    return config, reference, start, (xb, yb), params, losses


def test_equal_runs_pass(trained):
    config, _, start, _, params, losses = trained
    report = check.compare(params, params, start, config["check"])
    assert report["ok"] and report["worst"] == 0.0
    assert all(np.isfinite(losses)) and 0.5 < losses[0] < 1.0  # ten classes: 0.9


def test_an_update_off_by_one_percent_fails_the_written_tolerance(trained):
    config, reference, start, (xb, yb), params, _ = trained
    off = copy.deepcopy(config)
    off["session"]["lr"] = config["session"]["lr"] * 1.01
    wrong, _ = reference.make_reference(off)(start, xb, yb)
    report = check.compare(wrong, params, start, config["check"])
    assert not report["ok"]
    assert report["worst"] == pytest.approx(10.0, rel=0.2)  # 1% against 0.1%


def test_a_difference_in_the_last_bit_of_a_weight_passes(trained):
    config, _, start, _, params, _ = trained
    nudged = copy.deepcopy(params)
    w = nudged[0]["W"]
    nudged[0]["W"] = np.nextafter(w, np.float32(np.inf), dtype=np.float32)
    assert check.compare(nudged, params, start, config["check"])["ok"]


def test_a_wrong_loss_fails_where_the_prefix_has_one(trained):
    config, _, start, _, params, losses = trained
    mean = sum(losses) / len(losses)
    good = check.compare(params, params, start, config["check"], loss=mean, ref_loss=mean)
    bad = check.compare(
        params, params, start, config["check"], loss=mean * 1.001, ref_loss=mean
    )
    assert good["ok"] and not bad["ok"]


def test_reference_rejects_what_it_does_not_cover(trained):
    config, reference, *_ = trained
    other = copy.deepcopy(config)
    other["session"]["optimizer"] = "adam"
    with pytest.raises(ValueError, match="relu MLPs under SGD"):
        reference.make_reference(other)


def test_model_arithmetic():
    config = json.loads((BENCH / "configs" / "mnist-mlp.json").read_text())
    reference = load_reference(config["reference"])
    sizes = config["session"]["sizes"]
    P = sum(a * b for a, b in zip(sizes, sizes[1:]))
    assert reference.train_flops_per_sample(config) == 6 * P
    # one Linear of i x o at r rows: fwd and wgrad move r*i + i*o + r*o words
    # each, and the first Linear has no dgrad
    one = {"session": {"sizes": [3, 5]}}
    assert reference.matmul_bytes_per_sample(one, 2) == 4 * 2 * (2 * 3 + 3 * 5 + 2 * 5) / 2


def test_the_training_set_depends_on_the_seed_alone(tmp_path, monkeypatch):
    a, ya = datagen.make_dataset(5, 5000, 784, 10, tmp_path / "a")
    monkeypatch.setattr(datagen, "_threads", lambda: 1)
    b, yb = datagen.make_dataset(5, 5000, 784, 10, tmp_path / "b")
    c, _ = datagen.make_dataset(6, 5000, 784, 10, tmp_path / "c")
    assert np.array_equal(a, b) and np.array_equal(ya, yb)
    assert not np.array_equal(a, c)
    # prepare_data's shape: mean-centred, a range of exactly one, one-hot rows
    assert abs(float(np.mean(a, dtype=np.float64))) < 1e-6
    assert float(a.max() - a.min()) == pytest.approx(1.0, abs=1e-6)
    assert np.array_equal(ya.sum(axis=1), np.ones(5000, np.float32))
    # and it is what data.Dataset reads back
    from shallowspeed_tpu.data import Dataset

    ds = Dataset(tmp_path / "a", 1000, 250)
    ds.load(0, 1)
    assert np.array_equal(ds.input_X, np.array(a))
