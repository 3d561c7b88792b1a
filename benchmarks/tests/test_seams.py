"""A configuration that is no MLP goes through the harness with files of its
own: ``throwaway/`` holds one (not in ``BENCHMARK.json``) whose generator is
``packed_tokens``, which states no ``sizes``, and whose reference counts by
the sequence length. The program has no such model, so a rehearsal of it is
followed as far as the ``TrainingSession(...)`` call."""

import faulthandler
from pathlib import Path

import numpy as np
import pytest

import cells
import check
import run

THROWAWAY = Path(__file__).resolve().parent / "throwaway"
CELL = "token-model.seq-s4096-b32"


@pytest.fixture()
def throwaway(monkeypatch):
    monkeypatch.setattr(cells, "ROOT", THROWAWAY)
    monkeypatch.setattr(cells, "MIXES", THROWAWAY / "mixes")
    monkeypatch.setattr(run, "HERE", THROWAWAY)  # its references/


def test_the_cost_functions_see_the_job_shape(throwaway):
    cell = cells.load_cell(CELL)
    assert "seq_len" not in cells._read_json(THROWAWAY / "token-model.json")["session"]
    assert cell["session"]["seq_len"] == 4096 and cell["session"]["lr"] == 0.006
    assert cell["config"]["session"] is cell["session"]
    reference = cells.load_module(THROWAWAY / "references" / "token_counter.py")
    assert reference.train_flops_per_sample(cell["config"]) == 6 * 1_000_000 * 4096
    assert reference.matmul_bytes_per_sample(cell["config"], 8) == 500_000 + 8 * 4096
    # the rehearsal's sizes are laid over both files before they are merged
    small = cells.load_cell(CELL, rehearse=True)
    assert small["session"]["seq_len"] == 256 and small["session"]["mubatches"] == 4
    assert small["config"]["data"] == {"generator": "packed_tokens", "vocab_size": 512}


def test_a_mix_overrides_the_configuration_for_the_present_cells_too():
    cell = cells.load_cell("mlp-deep.dp2pp2-b65536")
    assert cell["session"]["precision"] == "default" and cell["session"]["dp"] == 2
    assert cell["session"]["sizes"][0] == 784
    assert cell["config"]["session"] is cell["session"]


def test_a_configuration_without_a_generator_is_a_bad_cell(throwaway, tmp_path):
    cell = cells.load_cell(CELL)
    for data in ({}, {"generator": "no_such_generator"}):
        broken = {**cell, "config": {**cell["config"], "data": data}}
        with pytest.raises(cells.BadCell, match="generator"):
            cells.make_dataset(broken, 1, 8, tmp_path)


class Reached(Exception):
    """The harness has called into the program."""


def test_a_rehearsal_of_a_token_model_reaches_the_training_session(
    throwaway, monkeypatch
):
    import shallowspeed_tpu.api

    seen = {}

    def session(data_dir, **kwargs):
        seen["kwargs"] = kwargs
        seen["files"] = {p.name: np.load(p) for p in Path(data_dir).iterdir()}
        raise Reached

    cut = check.prefix

    def prefix(*args):
        seen["prefix"] = cut(*args)
        return seen["prefix"]

    monkeypatch.setattr(check, "prefix", prefix)
    monkeypatch.setattr(shallowspeed_tpu.api, "TrainingSession", session)
    try:
        with pytest.raises(Reached):
            run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
                      "--rehearse"])
    finally:
        faulthandler.cancel_dump_traceback_later()  # run.main's watchdog
    # the job's shape, merged, and nothing of the harness's own
    assert seen["kwargs"]["seq_len"] == 256 and seen["kwargs"]["model"] == "token-model"
    assert seen["kwargs"]["global_batch_size"] == 8 and "sizes" not in seen["kwargs"]
    # the set the generator wrote is where the session was told to look
    assert sorted(seen["files"]) == ["segments_train.npy", "tokens_train.npy"]
    tokens = seen["files"]["tokens_train.npy"]
    assert tokens.shape == (96, 257) and tokens.dtype == np.int32 and tokens.max() < 512
    # the checked prefix: 2 steps of 4 microbatches of 2 rows, of each array
    ids, segments = seen["prefix"]
    assert ids.shape == segments.shape == (2, 4, 2, 257) and ids.dtype == np.int32
    assert np.array_equal(ids.reshape(16, 257), tokens[:16])
    # and the set is removed again whatever the session did
    assert not (run.WORK_DIR / CELL).exists()
