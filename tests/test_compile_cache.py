"""The persistent compile cache (shallowspeed_tpu/compile_cache.py), driven
through the real CLI: a second process of the same tree loads what the first
compiled, and a damaged entry costs a recompile, never the run.

Each run is ``train.py`` for one tiny epoch in its own process under
``JAX_COMPILATION_CACHE_DIR=<tmp>``; the store is ``<tmp>/<CACHE_TAG>/``, one
``*-cache`` file per compiled program.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from shallowspeed_tpu.observability.scopes import CACHE_TAG

ROOT = Path(__file__).resolve().parent.parent

LAYOUTS = {
    "seq": [],
    "dp2": ["--dp", "2"],
}


@pytest.fixture(scope="module")
def tiny_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("cache_data")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 128), ("val", 96)):
        np.save(d / f"x_{suffix}.npy", rng.rand(n, 784).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)],
        )
    return d


def _train(cache_dir, data_dir, flags):
    """One epoch in a new process -> (model hash, stderr)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    r = subprocess.run(
        [sys.executable, str(ROOT / "train.py"), "--data-dir", str(data_dir),
         "--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", *flags],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    found = re.search(r"final model hash: ([0-9a-f]{40})", r.stdout)
    assert found, r.stdout[-500:]
    return found.group(1), r.stderr


def _entries(cache_dir):
    """name -> (size, mtime_ns) of every entry in the tree's store."""
    store = Path(cache_dir) / CACHE_TAG
    return {
        p.name: (p.stat().st_size, p.stat().st_mtime_ns)
        for p in store.glob("*-cache")
    }


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_second_process_compiles_nothing(layout, tiny_data, tmp_path):
    """After the first run the store holds the epoch program; the second
    process writes no entry and touches none, and trains to the same hash."""
    cache = tmp_path / "cache"
    first_hash, _ = _train(cache, tiny_data, LAYOUTS[layout])
    first = _entries(cache)
    assert any(name.startswith("jit_epoch_core-") for name in first), first
    assert all(size > 0 for size, _ in first.values())
    second_hash, _ = _train(cache, tiny_data, LAYOUTS[layout])
    assert _entries(cache) == first
    assert second_hash == first_hash


def test_truncated_entries_recompile_cleanly(tiny_data, tmp_path):
    """Every entry emptied on disk: the next process still exits 0 with the
    same hash (JAX warns about each unreadable entry and recompiles)."""
    cache = tmp_path / "cache"
    first_hash, _ = _train(cache, tiny_data, [])
    names = set(_entries(cache))
    for name in names:
        (cache / CACHE_TAG / name).write_bytes(b"")
    again_hash, stderr = _train(cache, tiny_data, [])
    assert again_hash == first_hash
    assert "Traceback" not in stderr
    assert set(_entries(cache)) == names
