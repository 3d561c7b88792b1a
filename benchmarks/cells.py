"""Reads ``BENCHMARK.json`` and the data files a cell names. Nothing about
any one cell, configuration, mix or metric is written in code: a cell is
``{name, config, traffic, chips, why}``, the configuration is the JSON file
its entry names, and the traffic mix is ``mixes/<traffic>.json``."""

import copy
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class BadCell(Exception):
    """The workload, or a file it names, is missing or malformed."""


def load_module(path):
    """A reference or a reader, found by the name a data file gives it."""
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


def load_cell(workload, rehearse=False):
    """-> dict with the cell's entry, its configuration and mix (rehearsal
    sizes laid over them when asked), and the metrics it reports."""
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
    mix = _read_json(HERE / "mixes" / f"{entry['traffic']}.json")
    if rehearse:
        config = _merge(config, config.get("rehearse", {}))
        mix = _merge(mix, mix.get("rehearse", {}))
    if mix.get("chips") != entry["chips"]:
        raise BadCell(
            f"{workload}: the cell asks for {entry['chips']} chip(s), its mix "
            f"{entry['traffic']!r} is laid out for {mix.get('chips')}"
        )
    return {
        "name": workload,
        "chips": entry["chips"],
        "config": config,
        "mix": mix,
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, workload)],
    }
