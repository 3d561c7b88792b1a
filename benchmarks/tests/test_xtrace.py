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
MISLABELLED = HERE / "recorded" / "v5e_4chip_dp2pp2_scopes.json.gz"


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


def test_the_window_opens_at_the_first_execution_labelled_as_the_main_module():
    def chip(n, ops, modules):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
        ]}

    trace = {"planes": [
        # runs a second program between epochs; the trace began inside one
        # of the epoch program's executions, which carries the other's label
        chip(0, [ev("region.7", 0, 30), ev("region.8", 5, 25), ev("copy.1", 32, 38),
                 ev("fusion.2", 60, 80), ev("while.3", 85, 100), ev("fusion.4", 90, 100)],
             [ev("jit__multi_slice(1)", 0, 30), ev("jit__multi_slice(1)", 31, 39),
              ev("jit_epoch_core(2)", 50, 100)]),
        # runs nothing else; its execution is labelled 3 ns before its first
        # operation starts, and that label stays
        chip(1, [ev("fusion.2", 3, 40), ev("fusion.4", 50, 100)],
             [ev("jit_epoch_core(2)", 0, 100)]),
        # no execution of the main module: nothing the trace names
        chip(2, [ev("region.1", 0, 100)], [ev("jit__multi_slice(1)", 0, 100)]),
    ]}
    chip0, chip1 = xtrace.reduce_trace(trace)
    assert chip0["name"] == "/device:TPU:0" and chip1["name"] == "/device:TPU:1"
    assert chip0["window"] == (50, 100)
    assert [e[0] for e in chip0["ops"]] == ["fusion.2", "while.3", "fusion.4"]
    assert [e[0] for e in chip0["modules"]] == ["jit_epoch_core(2)"]
    assert chip0["mislabelled_ns"] == 30 + 20 + 6  # loops and bodies alike
    assert xtrace.total(chip0["busy"]) == 30
    assert chip1["window"] == (3, 100) and chip1["mislabelled_ns"] == 0
    assert len(chip1["ops"]) == 2 and len(chip1["modules"]) == 1
    assert xtrace.main_module([chip0, chip1]) == "jit_epoch_core(2)"
    assert dict(xtrace.top_device_ops([chip0, chip1])) == {"fusion": (30 + 87) / 2e9}


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


# -- four chips, recorded, chip 0's head mislabelled ---------------------------


@pytest.fixture(scope="module")
def mislabelled():
    """130 ms of the same cell around an epoch boundary (PR 24). On chip 0
    the first 48 ms belong to an execution that was under way when the trace
    began: the profiler labelled it ``jit__multi_slice`` and named every one
    of its operations ``region.<n>``. -> the run reduced plane by plane with
    nothing dropped (what the readers saw until PR 26), and as
    ``reduce_trace`` reduces it."""
    import yardstick

    trace = xtrace.load_json(MISLABELLED)
    cell = cells.load_cell("mlp-deep.dp2pp2-b65536")
    model = cells.load_module(HERE.parent / "references" / "mlp_sgd.py")

    def run_of(devices):
        return {
            "traced": {"trace": trace, "devices": devices, "epoch_s": [1.015155]},
            "session": {"steps_per_epoch": 4, "batch": 65536},
            "cell": cell,
            "model": model,
            "peaks": yardstick.peaks_for("TPU v5 lite"),
            "flops_per_sample": model.train_flops_per_sample(cell["config"]),
        }

    whole = [xtrace.reduce_device(p) for p in xtrace.device_planes(trace)]
    return run_of(whole), run_of(xtrace.reduce_trace(trace))


def regions(dev):
    return sum(xtrace.op_family(e[0]) == "region" for e in dev["ops"])


def test_a_chips_head_before_its_first_main_execution_is_dropped(mislabelled):
    whole, named = (run["traced"]["devices"] for run in mislabelled)
    assert [regions(d) for d in whole] == [543, 0, 0, 0]
    assert [regions(d) for d in named] == [0, 0, 0, 0]
    main = xtrace.main_module(named)
    assert main.startswith("jit_epoch_core(") and main == xtrace.main_module(whole)
    first = min(e[1] for e in whole[0]["modules"] if e[0] == main)
    assert first == 1_168_144_127.0
    assert named[0]["window"] == (first, whole[0]["window"][1])
    assert len(whole[0]["ops"]) == 1412 and len(named[0]["ops"]) == 866
    assert all(e[1] >= first for e in named[0]["ops"] + named[0]["modules"])
    # its device time, loops and their bodies both counted, as optable did
    assert named[0]["mislabelled_ns"] == pytest.approx(138_366_946.0)
    # chips 1 to 3 ran nothing but the epoch program: nothing to drop
    assert named[1:] == whole[1:]


def test_the_readers_count_over_what_is_left(mislabelled):
    whole, named = mislabelled
    chip0 = named["traced"]["devices"][0]
    steps = xtrace.steps_in_window(named, chip0)
    assert steps == pytest.approx((1.25e9 - 1_168_144_127.0) / 1e9 * 4 / 1.015155)
    # region was the largest "operation" of the breakdown; it is none
    assert xtrace.top_device_ops(whole["traced"]["devices"])[0][0] == "region"
    ops = dict(xtrace.top_device_ops(named["traced"]["devices"]))
    assert "region" not in ops
    assert ops["collective-permute-start"] == pytest.approx(0.02567899975)
    per_chip = [
        len(d["ops"]) / xtrace.steps_in_window(named, d)
        for d in named["traced"]["devices"]
    ]
    assert reader("device_ops_per_step")(named) == pytest.approx(sum(per_chip) / 4)
    # chip 0's matmul fusions were divided into the steps of the whole
    # window, their mislabelled half not among them: 206% of the roofline.
    # (Around an epoch boundary the steps of 130 ms are fewer than the time
    # says, so these shares all read high; a traced run holds whole epochs.)
    roofline = cells.load_module(HERE.parent / "layer_metrics" / "matmul_roofline.py")

    def share(run, dev):
        matmul_s = sum(e[2] for e in dev["leaf"] if roofline.is_matmul(e)) / 1e9
        samples = xtrace.steps_in_window(run, dev) * 65536
        return roofline.bound(run, samples)[0] / matmul_s

    assert share(whole, whole["traced"]["devices"][0]) == pytest.approx(2.0615448)
    assert share(named, chip0) == pytest.approx(1.2980735)
    assert share(named, named["traced"]["devices"][1]) == pytest.approx(1.4581088)
    assert roofline.read(whole) == pytest.approx(150.83483)
    assert roofline.read(named) == pytest.approx(131.74805)
    # the idle gaps are those of a chip whose window is whole: chip 0's, cut,
    # holds no epoch boundary and so none of the host's gaps
    trace = named["traced"]["trace"]
    assert dict(xtrace.top_idle_gaps(trace, [chip0])) == {
        "in jit_epoch_core: between ops": pytest.approx(0.018817554)
    }
    gaps = xtrace.top_idle_gaps(trace, named["traced"]["devices"])
    assert gaps == xtrace.top_idle_gaps(trace, named["traced"]["devices"][1:2])
    assert gaps[0][0].startswith("host: ") and gaps[0][1] > 0.02


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
