"""(a) ``run.py --rehearse`` end to end for every cell of ``BENCHMARK.json``
at tiny size on a CPU, the four-chip cell on four virtual devices: the last
line parses, carries the contract's keys and no device metric; and without
``--rehearse`` a CPU is refused with no record."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
RECORD_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def run_cell(workload, chips, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    command = [sys.executable, *BENCH["command"][1:], "--workload", workload,
               "--seed", "7", "--seconds", "1", *extra]
    return subprocess.run(
        command, cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize(
    "cell", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]]
)
def test_rehearsal_prints_the_contracts_record_and_no_device_metric(cell, trace):
    done = run_cell(cell["name"], cell["chips"], "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    record = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(record) == RECORD_KEYS  # the numbers compared come last
    assert record["correct"] is True
    # each number compared beside its limit, in the record and as the last
    # lines of stderr
    compared = record["compared"]
    assert compared["update_gap_over_allowed"]["value"] <= 1.0
    assert compared["update_gap_over_allowed"]["limit"] == 1.0
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    last = done.stderr.strip().splitlines()[-len(compared):]
    assert [line.split()[2].rstrip(":") for line in last] == list(compared)
    assert all(line.startswith("bench: compared ") for line in last)
    assert record["attempted"] > 0 and record["failed"] == 0
    assert record["metrics"] == {}
    assert set(record["device"]) == {"platform", "kind", "count"}
    assert record["device"]["platform"] == "cpu"
    assert record["device"]["count"] == cell["chips"]
    # the readers did run: the names they produced are on an earlier line
    names = {m["name"] for m in BENCH["per_layer" if trace == "1" else "end_to_end"]}
    said = next(
        line for line in done.stdout.splitlines() if "rehearsed, not published" in line
    )
    assert names & set(json.loads(said.split(": ", 2)[2]))


def test_a_cpu_is_refused_on_the_measurement_path():
    cell = BENCH["workloads"][0]
    done = run_cell(cell["name"], cell["chips"], "--trace", "0")
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_too_few_devices_are_refused():
    cell = next(w for w in BENCH["workloads"] if w["chips"] == 4)
    done = run_cell(cell["name"], 1, "--trace", "0", "--rehearse")
    assert done.returncode != 0
    assert "needs 4 chip(s)" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_every_named_file_exists_and_every_metric_has_a_reader():
    bench_dir = ROOT / BENCH["paths"][0]
    for config in BENCH["configs"]:
        assert (ROOT / config["file"]).is_file()
    for cell in BENCH["workloads"]:
        assert (bench_dir / "mixes" / f"{cell['traffic']}.json").is_file()
    for metric in BENCH["per_layer"]:
        assert (bench_dir / "layer_metrics" / f"{metric['name']}.py").is_file()
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
