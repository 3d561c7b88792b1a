"""Reads ``BENCHMARK.json`` and the data files a cell names. Nothing about
any one cell, configuration, mix or metric is written in code: a cell is
``{name, config, traffic, chips, why}``, the configuration is the JSON file
its entry names, the traffic mix is ``mixes/<traffic>.json``, and the
training set is drawn by ``datasets/<generator>.py``, which the
configuration's ``data`` block names."""

import copy
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
MIXES = HERE / "mixes"


class BadCell(Exception):
    """The workload, or a file it names, is missing or malformed."""


def load_module(path):
    """A generator, a reference or a reader, found by the name a data file
    gives it."""
    spec = importlib.util.spec_from_file_location(f"bench_{Path(path).stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path):
    try:
        return json.loads(Path(path).read_text())
    except (OSError, ValueError) as e:
        raise BadCell(f"cannot read {path}: {e}") from e


def _merge(base, over):
    """``over`` laid on ``base``, group by group (the rehearsal sizes)."""
    out = copy.deepcopy(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


def _applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def make_dataset(cell, seed, rows, data_dir):
    """The cell's training set from the seed, by the generator its
    configuration names: ``datasets/<generator>.py``'s ``make_dataset(seed,
    rows, session, data, data_dir)`` writes the files the session's loader
    reads into ``data_dir`` and returns what it wrote, a tuple of arrays
    whose first axis is ``rows``."""
    data = cell["config"].get("data")
    if not data or "generator" not in data:
        raise BadCell(f"{cell['name']}: the configuration names no data.generator")
    path = HERE / "datasets" / f"{data['generator']}.py"
    if not path.is_file():
        raise BadCell(f"{cell['name']}: no generator {path}")
    return load_module(path).make_dataset(seed, rows, cell["session"], data, data_dir)


def load_cell(workload, rehearse=False):
    """-> dict with the cell's entry, its configuration and mix (rehearsal
    sizes laid over them when asked), the job's shape ``session`` (the mix's
    keyword arguments laid over the configuration's; the configuration is
    handed on with this merged ``session``, so that a reference's cost
    functions see the sequence length or the layout), and the metrics the
    cell reports."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BadCell(
            f"no workload {workload!r} in BENCHMARK.json (has: {sorted(by_name)})"
        )
    entry = by_name[workload]
    config_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if config_entry is None:
        raise BadCell(f"{workload}: no configuration {entry['config']!r}")
    config = _read_json(ROOT / config_entry["file"])
    mix = _read_json(MIXES / f"{entry['traffic']}.json")
    if rehearse:
        config = _merge(config, config.get("rehearse", {}))
        mix = _merge(mix, mix.get("rehearse", {}))
    if mix.get("chips") != entry["chips"]:
        raise BadCell(
            f"{workload}: the cell asks for {entry['chips']} chip(s), its mix "
            f"{entry['traffic']!r} is laid out for {mix.get('chips')}"
        )
    session = {**config.get("session", {}), **mix.get("session", {})}
    return {
        "name": workload,
        "chips": entry["chips"],
        "config": {**config, "session": session},
        "mix": mix,
        "session": session,
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, workload)],
    }
