"""PR 37's readers: the set-up readers on the program's own span log
(``hostlog.py``) and the gap readers on traces (``gapsplit.py``): the recorded
four-chip stretch of PR 24 (one epoch boundary, read by hand in ISSUE 37), and
traces made by hand with a known skew between the two clocks."""

import statistics
import time
from pathlib import Path

import numpy as np
import pytest

import cells
import gapsplit
import hostlog
import xtrace

HERE = Path(__file__).resolve().parent
FOUR_CHIPS = HERE / "recorded" / "v5e_4chip_dp2pp2_scopes.json.gz"
ONE_CHIP = HERE / "recorded" / "v5e_1chip_mnist_b128.json.gz"
MAIN = "jit_epoch_core(1)"
GAP_READERS = (
    "gap_tail_ms_per_epoch", "gap_between_ms_per_epoch", "gap_head_ms_per_epoch",
    "host_device_clock_slack_ms",
)
SETUP_READERS = (
    "init_data_s", "init_weights_s", "init_program_s", "init_unnamed_s",
    "trace_lower_s", "backend_compile_s", "cache_load_s",
    "max_traces_of_one_function",
)


def reader(name):
    return cells.load_module(HERE.parent / "layer_metrics" / f"{name}.py").read


def traced_run(trace):
    return {
        "traced": {
            "trace": trace, "devices": xtrace.reduce_trace(trace), "epoch_s": [0.7],
        },
        "session": {"steps_per_epoch": 4},
    }


def ev(name, start, end):
    return [name, float(start), float(end - start), ""]


PERIOD, BUSY = 13_000.0, 9_000.0


def loop_trace(epochs, chips, skew=0.0):
    """A training loop by hand: epoch ``k``'s dispatch begins at ``k *
    PERIOD``; chip ``c`` begins ``heads[c]`` later; all end together, ``BUSY``
    after the last began; the readback ends ``tail`` after that. The device's
    clock reads ``skew`` more than the host's at every instant."""
    heads = [300.0 + 700.0 * c for c in range(chips)]
    tail = 200.0
    host, planes = [], []
    modules = {c: [] for c in range(chips)}
    for k in range(epochs):
        at = k * PERIOD
        host.append(ev("epoch/dispatch", at, at + 100.0))
        for c in range(chips):
            modules[c].append(
                ev(MAIN, at + heads[c] + skew, at + heads[-1] + BUSY + skew)
            )
        host.append(ev("epoch/readback", at + 100.0, at + heads[-1] + BUSY + tail))
    for c in range(chips):
        ops = [ev("fusion.1", m[1], m[1] + m[2]) for m in modules[c]]
        planes.append({
            "name": f"/device:TPU:{c}",
            "lines": [
                {"name": xtrace.MODULES_LINE, "events": modules[c]},
                {"name": xtrace.OPS_LINE, "events": ops},
            ],
        })
    planes.append({"name": xtrace.HOST_PLANE, "lines": [{"name": "loop", "events": host}]})
    return {"planes": planes}, heads, tail


@pytest.fixture(scope="module")
def four_chips():
    return xtrace.load_json(FOUR_CHIPS)


# -- the recorded four-chip stretch (Motivation 4 of ISSUE 37) ----------------


def test_the_recorded_stretch_reads_the_heads_and_the_stagger(four_chips):
    found = gapsplit.split(four_chips, xtrace.reduce_trace(four_chips))
    heads = {
        name: round(chip["heads"][0] / 1e6, 1) for name, chip in found["chips"].items()
    }
    assert heads == {
        "/device:TPU:0": 4.5, "/device:TPU:1": 23.2,
        "/device:TPU:2": 14.3, "/device:TPU:3": 23.3,
    }
    read = gapsplit.summary(found)
    assert round(read["stagger_ms"], 1) == 18.8
    # chip 0's first execution is mislabelled and dropped by the reduction:
    # it has a head and no whole boundary; the others' gaps are the hand's
    assert {n: round(g, 1) for n, g in read["gap_ms_by_chip"].items()} == {
        "/device:TPU:1": 26.7, "/device:TPU:2": 17.8, "/device:TPU:3": 26.8,
    }
    # the chip whose gap is largest: readback ends 3.3 ms after the chip,
    # the host is between two epochs for 0.2 ms, the launch takes 23.3
    assert read["chip"] == "/device:TPU:3"
    assert round(read["tail_ms"], 1) == 3.3
    assert round(read["between_ms"], 2) == 0.20
    assert round(read["head_ms"], 1) == 23.3
    # the offset of the clocks lies between -3.3 and +4.5 ms, 7.8 ms wide
    assert [round(x, 1) for x in read["clock_offset_ms"]] == [-3.3, 4.5]
    assert round(read["clock_slack_ms"], 1) == 7.8


@pytest.mark.parametrize(
    "name,want",
    [
        ("gap_tail_ms_per_epoch", 3.278329),
        ("gap_between_ms_per_epoch", 0.2032),
        ("gap_head_ms_per_epoch", 23.27157),
        ("launch_stagger_ms_per_epoch", 18.777516),
        ("host_device_clock_slack_ms", 7.770577),
    ],
)
def test_each_gap_reader_on_the_recorded_stretch(four_chips, capsys, name, want):
    run = traced_run(four_chips)
    assert reader(name)(run) == pytest.approx(want, abs=1e-6)
    assert capsys.readouterr().out.count("bench: gaps: ") == 1
    reader(name)(run)  # computed and printed once per run
    assert "bench: gaps" not in capsys.readouterr().out


def test_the_per_chip_gaps_bracket_the_median_over_chips(four_chips):
    run = traced_run(four_chips)
    by_chip = gapsplit.read(run)["gap_ms_by_chip"].values()
    assert min(by_chip) <= reader("host_gap_ms_per_epoch")(run) <= max(by_chip)


# -- by hand ------------------------------------------------------------------


@pytest.mark.parametrize("chips", [1, 4])
def test_tail_between_and_head_are_the_gap_exactly(chips):
    trace, heads, tail = loop_trace(epochs=5, chips=chips)
    devices = xtrace.reduce_trace(trace)
    found = gapsplit.split(trace, devices)
    gaps = []
    for c, (name, chip) in enumerate(sorted(found["chips"].items())):
        assert len(chip["boundaries"]) == 4 and len(chip["heads"]) == 5
        for b in chip["boundaries"]:
            assert b["tail"] + b["between"] + b["head"] == b["gap"]
            assert b["head"] == heads[c]
            assert b["tail"] == tail
            assert b["between"] == PERIOD - heads[-1] - BUSY - tail
            gaps.append(b["gap"])
    # the gap is the one host_gap_ms_per_epoch takes its median of
    run = traced_run(trace)
    assert reader("host_gap_ms_per_epoch")(run) == statistics.median(gaps) / 1e6
    read = gapsplit.read(run)
    assert read["tail_ms"] + read["between_ms"] + read["head_ms"] == pytest.approx(
        read["gap_ms"]
    )
    # the largest gap is the last chip's: all end together and it starts last
    assert read["chip"] == f"/device:TPU:{chips - 1}"
    if chips == 1:
        assert read["stagger_ms"] is None
        assert reader("launch_stagger_ms_per_epoch")(run) is None
    else:
        assert read["stagger_ms"] == pytest.approx((heads[-1] - heads[0]) / 1e6)


@pytest.mark.parametrize("skew", [-150.0, 0.0, 250.0])
def test_the_offsets_interval_holds_a_known_skew(skew):
    """Head 300 and tail 200 on the host's clock; the device's clock reads
    ``skew`` more. Causality gives an interval that holds the skew and is as
    wide as the smallest head plus the smallest tail, whatever the skew."""
    trace, heads, tail = loop_trace(epochs=4, chips=1, skew=skew)
    read = gapsplit.read(traced_run(trace))
    lo, hi = read["clock_offset_ms"]
    assert lo <= skew / 1e6 <= hi
    assert read["clock_slack_ms"] == pytest.approx((heads[0] + tail) / 1e6)
    assert read["head_ms"] == pytest.approx((heads[0] + skew) / 1e6)
    assert read["tail_ms"] == pytest.approx((tail - skew) / 1e6)
    assert read["between_ms"] == pytest.approx(
        (PERIOD - heads[0] - BUSY - tail) / 1e6
    )


@pytest.mark.parametrize("name", GAP_READERS + ("launch_stagger_ms_per_epoch",))
def test_a_gap_reader_reads_nothing_without_two_executions_or_spans(name):
    one_execution, _, _ = loop_trace(epochs=1, chips=1)
    no_spans = xtrace.load_json(ONE_CHIP)  # two executions, no program span
    for trace in (one_execution, no_spans, {"planes": []}):
        assert reader(name)(traced_run(trace)) is None
    assert reader(name)({"traced": None}) is None


def test_the_first_execution_of_a_trace_has_no_dispatch_in_it():
    trace, heads, _ = loop_trace(epochs=3, chips=1)
    host = trace["planes"][-1]["lines"][0]["events"]
    del host[0]  # the trace began after the first dispatch did
    found = gapsplit.split(trace, xtrace.reduce_trace(trace))
    chip = found["chips"]["/device:TPU:0"]
    assert chip["heads"] == [heads[0]] * 2 and len(chip["boundaries"]) == 2


def test_the_log_is_tied_to_the_profilers_clock_by_the_dispatch_spans(monkeypatch):
    from shallowspeed_tpu.observability import spans

    log = spans.SpanLog()
    monkeypatch.setattr(spans, "_LOG", log)
    durations = [510, 730, 640, 580, 905, 777, 612]
    for k, d in enumerate(durations):
        log.add(spans.Entry("train_epoch/epoch/dispatch", 1_000 * k, d, 0, "epoch/dispatch"))
        log.add(spans.Entry("train_epoch/epoch/readback", 1_000 * k + d, 300, 0, "epoch/readback"))
    offset = 123_456
    # the trace holds the third to the sixth, each annotation 2 ns wider
    events = [
        ev("epoch/dispatch", 1_000 * k + offset - 1, 1_000 * k + offset + d + 1)
        for k, d in list(enumerate(durations))[2:6]
    ]
    trace = {"planes": [{"name": xtrace.HOST_PLANE, "lines": [{"name": "t", "events": events}]}]}
    tie = gapsplit.log_tie(trace)
    assert tie == {"offset_ns": offset - 1, "spread_us": 0.0, "spans": 4}
    assert gapsplit.log_tie({"planes": []}) is None


# -- the set-up readers on a real session's log ---------------------------------


@pytest.fixture(scope="module")
def session_run(tmp_path_factory):
    """A tiny CPU session built, stepped and run as ``run.py`` does, then the
    window 'opens'; what a reference would compile afterwards is outside."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    d = tmp_path_factory.mktemp("hostspans")
    rng = np.random.RandomState(0)
    np.save(d / "x_train.npy", rng.randn(256, 24).astype(np.float32))
    np.save(d / "y_train.npy", np.eye(10, dtype=np.float32)[rng.randint(0, 10, 256)])
    began = time.perf_counter()
    session = TrainingSession(
        sizes=(24, 20, 18, 10), global_batch_size=64, data_dir=str(d)
    )
    init_s = time.perf_counter() - began
    session.train_steps(2)
    while session.step_in_epoch:
        session.train_steps(4)
    session.train_epoch()
    run = {"window": {"opened": time.perf_counter()}, "setup": {"init_s": init_s}}
    session.train_epoch()
    jax.jit(lambda x: jnp.cos(x) + 2)(jnp.ones(3))  # after the window opened
    return run


def test_the_init_readers_sum_to_the_stopwatch_around_the_session(session_run, capsys):
    values = {name: reader(name)(session_run) for name in SETUP_READERS[:4]}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert sum(values.values()) == pytest.approx(
        session_run["setup"]["init_s"], rel=0.05
    )
    assert values["init_unnamed_s"] < 0.1 * session_run["setup"]["init_s"]
    out = capsys.readouterr().out  # the attribution, once per run
    assert out.count("bench: setup spans: ") == 1 and '"most_traced"' in out


def test_the_compile_readers_are_unions_that_cover_every_compile_event(session_run):
    split = hostlog.compile_split(session_run)
    assert reader("trace_lower_s")(session_run) == split["trace_lower"] > 0
    assert reader("backend_compile_s")(session_run) == split["backend"] >= 0
    assert reader("cache_load_s")(session_run) == split["cache_load"] >= 0
    # tracing and lowering, compiling and loading do not overlap on one thread
    assert split["trace_lower"] + split["backend"] + split["cache_load"] == (
        pytest.approx(split["all"], rel=1e-6)
    )
    # what compiled after the window opened is not set-up
    entries = hostlog.setup_entries(session_run)
    assert entries and all("lambda" not in (e.fun_name or "") for e in entries)


def test_the_most_traced_function_is_counted_and_named(session_run):
    name, count = hostlog.most_traced(session_run)
    assert reader("max_traces_of_one_function")(session_run) == count >= 1
    assert isinstance(name, str)


@pytest.mark.parametrize("name", SETUP_READERS)
def test_a_setup_reader_reads_nothing_from_a_program_without_the_log(
    monkeypatch, session_run, name
):
    monkeypatch.setattr(hostlog, "_spans", lambda: None)
    assert reader(name)(dict(session_run)) is None
