"""(c) the class table (``optable.py``) and the readers built on it, on a
pair recorded on a v5e (PR 24): 130 ms of the four-chip cell's trace around an
epoch boundary, cut with ``xtrace.cut``, and the op index of the program it
ran (``scopes.program_index``, restricted to the instructions in the cut).
The cut holds what makes the join hard: on chip 0 the head of the window
belongs to an execution the profiler labelled as another program and named
``region.<n>`` throughout, with two ``jit__multi_slice`` runs after it, and
only then comes an execution labelled as the epoch program; on chips 1 to 3 an epoch ends, the chip idles, the next begins."""

import gzip
import json
from pathlib import Path

import pytest

import cells
import optable
import xtrace
from shallowspeed_tpu.observability import scopes

HERE = Path(__file__).resolve().parent
TRACE = HERE / "recorded" / "v5e_4chip_dp2pp2_scopes.json.gz"
INDEX = HERE / "recorded" / "v5e_4chip_dp2pp2_scopes_index.json.gz"
BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
FOUR_CHIPS = "mlp-deep.dp2pp2-b65536"
NEW = [
    m["name"]
    for m in BENCH["per_layer"]
    if FOUR_CHIPS in m.get("workloads", [FOUR_CHIPS])
    and (HERE.parent / "layer_metrics" / f"{m['name']}.py").read_text().count("optable")
]


def reader(name):
    return cells.load_module(HERE.parent / "layer_metrics" / f"{name}.py").read


def recorded_run():
    trace = xtrace.load_json(TRACE)
    return {
        "traced": {
            "trace": trace,
            "devices": xtrace.reduce_trace(trace),
            "epoch_s": [1.015155],  # the traced run's median epoch, host clock
        },
        "session": {"steps_per_epoch": 4, "batch": 65536},
    }


@pytest.fixture()
def index(monkeypatch):
    with gzip.open(INDEX, "rt") as f:
        found = json.load(f)
    monkeypatch.setattr(scopes, "program_index", lambda name: found)
    return found


def test_the_rows_sum_to_the_non_container_device_time(index, capsys):
    run = recorded_run()
    found = optable.table(run)
    assert found["module"] == "jit_epoch_core"
    assert capsys.readouterr().out.startswith("bench: scopes: {")
    assert optable.table(run) is found and capsys.readouterr().out == ""
    for dev, chip in zip(run["traced"]["devices"], found["chips"]):
        expected = sum(
            ev[2]
            for ev in dev["ops"]
            if not (index.get(ev[0]) or {}).get("container")
            and not xtrace.op_family(ev[0]).startswith(xtrace.CONTAINERS)
        )
        rows = sum(row["ms_per_step"] for row in chip["classes"].values())
        assert rows * chip["steps"] == pytest.approx(expected / 1e6, rel=1e-9)
        assert chip["resolved"] == pytest.approx(100.0)
        assert chip["coverage"] > 99.9 and chip["moved_named"] > 99.9
        assert sum(chip["moved_by_class"].values()) <= rows
    chip0, chip1 = found["chips"][:2]
    # chip 0: 48 ms of the cut precede the first execution labelled as the
    # epoch program, every operation in them named region.<n>;
    # xtrace.reduce_trace has dropped them (test_xtrace.py)
    assert chip0["mislabelled_ms"] > 40 and chip1["mislabelled_ms"] == 0
    assert chip0["steps"] < 0.7 * chip1["steps"]
    assert set(found["containers"]) == {"conditional", "while"}
    # by index and by name agree where the trace names the program's own
    # operations, which is now all that is left on every chip
    for chip in found["chips"]:
        for by_index, by_name in chip["idle"].values():
            assert by_index == pytest.approx(by_name)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_reads_a_number(index, name):
    value = reader(name)(recorded_run())
    assert isinstance(value, float) and value >= 0
    if name == "scope_coverage_share":
        assert 99.9 < value <= 100.0


def test_the_class_metrics_are_the_table_rows(index):
    run = recorded_run()
    chips = optable.table(run)["chips"]
    for name, cls in (("stash_ms_per_step", "stash"), ("linear_ms_per_step", "linear")):
        assert reader(name)(run) == max(
            chip["classes"][cls]["ms_per_step"] for chip in chips
        )
    assert reader("dispatch_ms_per_epoch")(run) == pytest.approx(5.948631)


@pytest.mark.parametrize("name", [n for n in NEW if n != "dispatch_ms_per_epoch"])
def test_no_scope_in_the_index_reads_nothing(monkeypatch, capsys, name):
    """Executables loaded from a compile cache another tree filled carry no
    scope (the cache key ignores them): no table, and the log says why."""
    with gzip.open(INDEX, "rt") as f:
        stale = {k: {**e, "scope": None} for k, e in json.load(f).items()}
    monkeypatch.setattr(scopes, "program_index", lambda name: stale)
    assert reader(name)(recorded_run()) is None
    assert "holds no scope" in capsys.readouterr().out


def test_nothing_to_read_without_a_device_plane_or_a_registered_program(monkeypatch):
    run = recorded_run()
    run["traced"]["devices"] = []
    assert optable.table(run) is None
    monkeypatch.setattr(scopes, "program_index", lambda name: None)
    assert optable.table(recorded_run()) is None
    assert optable.host_span_ms({"traced": None}, "epoch/dispatch") is None
