"""The comparison that decides ``correct``: a wrong update fails at the
configurations' written tolerances, an equal one passes, and every tensor of
every layer is held, whatever it is called."""

import copy
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import cells
import check

BENCH = Path(__file__).resolve().parents[1]


def load_reference(name):
    return cells.load_module(BENCH / "references" / f"{name}.py")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A tiny relu MLP, its data, and the reference's own two steps on it."""
    config = json.loads((BENCH / "configs" / "mnist-mlp.json").read_text())
    config["session"]["sizes"] = [20, 16, 12, 10]
    generator = cells.load_module(BENCH / "datasets" / "gaussian_clusters.py")
    arrays = generator.make_dataset(
        3, 64, config["session"], config["data"], tmp_path_factory.mktemp("d")
    )
    xb, yb = check.prefix(arrays, steps=2, batch=32, mubatches=4)
    assert xb.shape == (2, 4, 8, 20) and yb.shape == (2, 4, 8, 10)
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
    # what the comparison said of these layers when it knew "W" and "b" by
    # name (the parent of PR 26, commit de813f1): W before b, layer by layer
    assert report["per_tensor"] == pytest.approx(
        [1.194, 9.997, 1.72, 9.998, 2.335, 9.998], abs=2e-3
    )
    assert report["where"]["layer"] == 2 and report["where"]["tensor"] == "b"
    assert report["where"]["gap"] == pytest.approx(2.7093886939984793e-06, rel=1e-3)


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


def block(rng):
    """A layer that is no Linear: a norm's scale, a router, a convolution's
    taps, an expert's two matrices."""
    f = lambda *shape: rng.normal(0, 0.3, shape).astype(np.float32)  # noqa: E731
    return {
        "norm": {"scale": f(16)},
        "mixer": {"conv": {"taps": f(4, 16)}, "W": f(16, 16), "b": f(1, 16)},
        "router": f(8, 16),
        "experts": {"down": f(8, 16, 32), "up": f(8, 32, 16)},
    }


LEAVES = [
    "experts/down", "experts/up", "mixer/W", "mixer/b", "mixer/conv/taps",
    "norm/scale", "router",
]


def moved(layers_, by):
    return jax.tree.map(lambda w: w * np.float32(1 + by), layers_)


def test_every_leaf_of_a_nested_layer_is_walked_in_sorted_order():
    rng = np.random.default_rng(1)
    assert list(check.tensors(block(rng))) == LEAVES
    assert list(check.tensors({"b": 1, "W": 2})) == ["W", "b"]


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("leaf", LEAVES)
def test_one_nudged_leaf_fails_and_is_named(layer, leaf):
    rng = np.random.default_rng(2)
    tolerance = {"update_rtol": 0.001, "weight_ulps": 2, "loss_rtol": 1e-6}
    start = [block(rng), block(rng)]
    reference = moved(start, 0.01)
    system = moved(start, 0.01)
    assert check.compare(system, reference, start, tolerance)["ok"]
    node = system[layer]
    *parents, last = leaf.split("/")
    for key in parents:
        node = node[key]
    node[last] = node[last] * np.float32(1.0001)  # 1% of the update, 10x allowed
    report = check.compare(system, reference, start, tolerance)
    assert not report["ok"]
    assert (report["where"]["layer"], report["where"]["tensor"]) == (layer, leaf)
    assert report["worst"] == pytest.approx(10.0, rel=0.05)
    over = [r > 1 for r in report["per_tensor"]]
    assert len(over) == 2 * len(LEAVES) and sum(over) == 1
    assert over.index(True) == layer * len(LEAVES) + LEAVES.index(leaf)


def test_runs_that_hold_other_tensors_are_not_compared():
    rng = np.random.default_rng(3)
    start = [block(rng)]
    lacking = moved(start, 0.01)
    del lacking[0]["router"]
    with pytest.raises(ValueError, match="layer 0.*router"):
        check.compare(lacking, moved(start, 0.01), start, {})
    with pytest.raises(ValueError, match="1 layers against the reference's 2"):
        check.compare(start, start + start, start, {})


def test_the_control_one_precision_step_down_is_not_correct():
    """``tolerance_probe.py`` at the rehearsal's size: the reference under
    ``bfloat16``, one step below ``mlp-deep``'s stated ``default``, put in the
    program's place, is outside the tolerance; under the stated policy it is
    the reference itself. (A CPU multiplies in one precision, so ``highest``
    against ``default`` shows on the chip only: ``PERF.md`` section 4.)"""
    import tolerance_probe

    cell = "mlp-deep.seq-b65536"
    lowered = tolerance_probe.main(cell, "bfloat16", seed=2147483659, rehearse=True)
    assert not lowered["ok"] and lowered["worst"] > 1.0
    stated = tolerance_probe.main(cell, "default", seed=2147483659, rehearse=True)
    assert stated["ok"] and stated["worst"] == 0.0
