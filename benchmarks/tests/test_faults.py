"""``correct`` comes out false when the timed path is broken underneath
(``faulty_run.py``): the whole of a run but the look for a chip, at the
rehearsal's sizes, once for each fault a training cell can have."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ONE_CHIP, FOUR_CHIPS = "mnist-mlp.seq-b8192", "mlp-deep.dp2pp2-b65536"


def faulty(fault, cell, chips):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    done = subprocess.run(
        [sys.executable, "benchmarks/tests/faulty_run.py", fault, "--workload", cell,
         "--seed", "2147483659", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    verdicts = next(
        json.loads(line.split(": ", 2)[2])
        for line in done.stdout.splitlines() if line.startswith("bench: verdicts:")
    )
    return json.loads(done.stdout.strip().splitlines()[-1]), verdicts


@pytest.mark.parametrize(
    "fault, cell, chips, at_least",
    [
        # after == start: the gap is the whole update, 1 / update_rtol allowed
        ("state_unchanged", ONE_CHIP, 1, 19.0),
        ("half_the_batch", ONE_CHIP, 1, 3.0),
        ("half_the_batch", FOUR_CHIPS, 4, 3.0),
        ("no_exchange", FOUR_CHIPS, 4, 3.0),
    ],
)
def test_a_broken_program_is_not_correct(fault, cell, chips, at_least):
    record, verdicts = faulty(fault, cell, chips)
    assert record["correct"] is False and verdicts["reference"] is False
    gap = record["compared"]["update_gap_over_allowed"]
    assert gap["limit"] == 1.0 and gap["value"] > at_least
    if fault == "no_exchange":
        assert verdicts["replicas_in_sync"] is False
    else:
        assert verdicts["replicas_in_sync"] is True
