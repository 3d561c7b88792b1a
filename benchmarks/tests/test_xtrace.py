"""(b) the trace reduction: the interval arithmetic on intervals small enough
to do by hand, and the readers on traces recorded on a v5e (PR 22) and cut to
a few milliseconds (``xtrace.cut``), with every expected number worked out
from the recorded events."""

from pathlib import Path

import pytest

import cells
import xtrace

HERE = Path(__file__).resolve().parent
ONE_CHIP = HERE / "recorded" / "v5e_1chip_mnist_b128.json.gz"
FOUR_CHIPS = HERE / "recorded" / "v5e_4chip_dp2pp2.json.gz"


def reader(name):
    return cells.load_module(HERE.parent / "layer_metrics" / f"{name}.py").read


def ev(name, start, end, kind=""):
    return [name, float(start), float(end - start), kind]


# -- by hand ----------------------------------------------------------------


def test_union_subtract_gaps():
    merged = xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8), (20, 21)])
    assert merged == [(0, 3), (5, 8), (20, 21)]
    assert xtrace.total(merged) == 7
    assert xtrace.subtract(merged, [(2, 6), (7.5, 30)]) == [(0, 2), (6, 7.5)]
    assert xtrace.subtract(merged, []) == merged
    assert xtrace.gaps(merged, 0, 25) == [(3, 5), (8, 20), (21, 25)]


def test_a_loop_is_not_busy_time_its_body_is():
    events = [
        ev("while.1", 0, 100),
        ev("fusion.1", 0, 10),
        ev("conditional.2", 20, 60),
        ev("fusion.2", 20, 30),
        ev("all-reduce.3", 40, 60),
        ev("copy.4", 100, 130),   # a DMA copy that runs beside the next ops
        ev("fusion.5", 105, 110),  # inside copy.4 in time, not its child
    ]
    leaf = xtrace.leaves(events)
    assert [e[0] for e in leaf] == [
        "fusion.1", "fusion.2", "all-reduce.3", "copy.4", "fusion.5"
    ]
    assert xtrace.total(xtrace.union(xtrace.spans(leaf))) == 70  # not 130


def test_exposed_communication_is_what_no_compute_covers():
    plane = {
        "name": "/device:TPU:0",
        "lines": [
            {"name": "XLA Ops", "events": [
                ev("fusion.1", 0, 50, "kOutput"),
                ev("all-reduce.1", 40, 80),          # 40..50 hidden, 50..80 exposed
                ev("collective-permute.2", 80, 90),  # 80..85 exposed
                ev("fusion.2", 85, 120, "kLoop"),    # covers 85..90 of the relay
            ]},
            {"name": "XLA Modules", "events": [ev("jit_step(1)", 0, 120)]},
        ],
    }
    dev = xtrace.reduce_device(plane)
    assert dev["window"] == (0, 120)
    assert len(dev["leaf"]) == 4  # overlapping is not containing
    assert xtrace.total(dev["busy"]) == 120
    assert xtrace.total(dev["comm"]) == 50
    assert xtrace.total(dev["compute"]) == 85
    assert dev["exposed_comm"] == [(50, 85)]
    assert xtrace.reduce_device({"name": "/device:TPU:1", "lines": []}) is None


def test_names():
    text = ("%fusion.194 = f32[32,128]{1,0:T(8,128)S(1)} fusion(f32[128,784]{1,0} "
            "%get-tuple-element.1279), kind=kOutput, calls=%fused_computation.93.clone")
    assert xtrace.short_name(text) == "fusion.194"
    assert xtrace.hlo_kind(text) == "kOutput"
    assert xtrace.hlo_kind("%copy.1 = f32[2]{0} copy(f32[2]{0} %x)") == ""
    assert xtrace.op_family("convolution_add_fusion.27") == "convolution_add_fusion"
    assert xtrace.op_family("fused_computation.93.clone.clone") == "fused_computation"
    assert xtrace.is_comm("all-reduce-start.3") and xtrace.is_comm("collective-permute-done")
    assert not xtrace.is_comm("fusion.3")


# -- one chip, recorded -------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """6 ms of ``mnist-mlp`` at batch 128 on one v5e: the last half millisecond
    of one epoch program, the host's 2.26 ms between two executions, the next
    program's 2.69 ms copy of the training set and its first steps."""
    trace = xtrace.load_json(ONE_CHIP)
    return {
        "traced": {
            "trace": trace,
            "devices": xtrace.reduce_trace(trace),
            "epoch_s": [0.0809, 0.0811, 0.0815],
        },
        "session": {"steps_per_epoch": 2048, "batch": 128},
    }


def test_one_chip_idle_share_and_host_gap(one_chip):
    (dev,) = one_chip["traced"]["devices"]
    assert dev["window"] == (78_000_000.0, 84_000_000.0)
    assert len(dev["ops"]) == 3897 and len(dev["leaf"]) == 3871
    assert sum(e[0] == "while.125" for e in dev["ops"]) == 26  # one per step
    assert xtrace.total(dev["busy"]) == pytest.approx(3_694_252.0)
    assert reader("device_idle_share")(one_chip) == pytest.approx(38.4291, abs=1e-3)
    assert reader("host_gap_ms_per_epoch")(one_chip) == pytest.approx(2.260752)
    assert dev["comm"] == [] and dev["exposed_comm"] == []
    assert reader("comm_exposed_share")(one_chip) is None
    assert reader("relay_ms_per_step")(one_chip) is None


def test_one_chip_ops_per_step(one_chip):
    (dev,) = one_chip["traced"]["devices"]
    # 6 ms at 2048 steps per 81.1 ms epoch
    steps = xtrace.steps_in_window(one_chip, dev)
    assert steps == pytest.approx(6e-3 * 2048 / 0.0811)
    assert reader("device_ops_per_step")(one_chip) == pytest.approx(3897 / steps)


def test_one_chip_breakdown(one_chip):
    traced = one_chip["traced"]
    ops = xtrace.top_device_ops(traced["devices"])
    assert ops[0][0] == "copy" and ops[0][1] == pytest.approx(0.002830694)
    assert [name for name, _ in ops[1:3]] == ["convolution_add_fusion", "fusion"]
    gaps = dict(xtrace.top_idle_gaps(traced["trace"], traced["devices"]))
    # the host sat in the loss readback while the chip waited for its next program
    assert gaps["host: $array.py:631 _value"] == pytest.approx(0.002261742)
    assert gaps["in jit_epoch_core: between ops"] == pytest.approx(4.4006e-05)


# -- four chips, recorded -------------------------------------------------------


@pytest.fixture(scope="module")
def four_chips():
    """120 ms of ``mlp-deep`` on dp 2 x pp 2 (1F1B, global batch 65,536): the
    end of an epoch's last step with its gradient all-reduce, the host's gap
    (device 0 re-slices the training set for the mesh meanwhile), and the
    first ticks of the next epoch."""
    trace = xtrace.load_json(FOUR_CHIPS)
    return {
        "traced": {
            "trace": trace,
            "devices": xtrace.reduce_trace(trace),
            "epoch_s": [1.0148],
        },
        "session": {"steps_per_epoch": 4, "batch": 65536},
    }


def test_four_chips_every_chip_is_reduced_by_itself(four_chips):
    devs = four_chips["traced"]["devices"]
    assert [d["name"] for d in devs] == [f"/device:TPU:{i}" for i in range(4)]
    assert [len(d["ops"]) for d in devs] == [1287, 960, 960, 1277]
    for dev in devs:
        assert dev["window"] == (1_100_000_000.0, 1_220_000_000.0)
        kinds = [xtrace.op_family(e[0]) for e in dev["leaf"] if xtrace.is_comm(e[0])]
        assert kinds.count("collective-permute-start") == 8
        assert kinds.count("collective-permute-done") == 7
        assert kinds.count("all-reduce") == 2
        # one core, one queue: a collective on it is never behind compute
        assert dev["exposed_comm"] == dev["comm"]
        assert xtrace.total(dev["busy"]) == pytest.approx(
            xtrace.total(dev["compute"]) + xtrace.total(dev["comm"])
        )
    assert xtrace.total(devs[1]["comm"]) == pytest.approx(51_032_396.0)
    assert xtrace.main_module(devs).startswith("jit_epoch_core(")


def test_four_chips_readers(four_chips):
    # 120 ms of a 1014.8 ms epoch of 4 steps
    steps = 0.120 * 4 / 1.0148
    dev1 = four_chips["traced"]["devices"][1]
    assert xtrace.steps_in_window(four_chips, dev1) == pytest.approx(steps)
    # chip 1 sat in relays for 46.25 ms of the 120: the most of the four
    assert reader("relay_ms_per_step")(four_chips) == pytest.approx(46.252243 / steps)
    # the all-reduce: 4.78 ms of 120 ms on the worst chip, none of it hidden
    assert reader("comm_exposed_share")(four_chips) == pytest.approx(
        100 * 4_781_951.0 / 120e6
    )
    # chips 0, 1, 2, 3 waited 7.70, 26.32, 17.61, 17.50 ms for their next epoch
    assert reader("host_gap_ms_per_epoch")(four_chips) == pytest.approx(17.551668)
    assert reader("device_idle_share")(four_chips) == pytest.approx(21.9805, abs=1e-3)
    assert reader("stage_idle_share")(four_chips) == pytest.approx(49.9439, abs=1e-3)
    assert reader("device_ops_per_step")(four_chips) == pytest.approx(
        (1287 + 960 + 960 + 1277) / 4 / steps
    )


def test_four_chips_breakdown(four_chips):
    traced = four_chips["traced"]
    ops = dict(xtrace.top_device_ops(traced["devices"]))
    assert ops["collective-permute-start"] == pytest.approx(0.029518234)
    assert ops["all-reduce"] == pytest.approx(0.004780682)
    gaps = xtrace.top_idle_gaps(traced["trace"], traced["devices"])
    assert gaps[0][0] == "host: $array.py:631 _value"  # the loss readback


def test_cut_clips_and_json_round_trips(tmp_path, one_chip):
    trace = one_chip["traced"]["trace"]
    part = xtrace.cut(trace, 80_000_000.0, 81_000_000.0)
    for plane in part["planes"]:
        for line in plane["lines"]:
            assert line["events"]
            for e in line["events"]:
                assert e[1] >= 80_000_000.0 and e[1] + e[2] <= 81_000_000.0
    xtrace.save_json(part, tmp_path / "t.json.gz")
    assert xtrace.load_json(tmp_path / "t.json.gz") == part
    assert xtrace.reduce_trace({"planes": []}) == []
