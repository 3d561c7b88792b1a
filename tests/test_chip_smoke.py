"""chip_smoke.py off the chip: its legs run small on the emulated CPU mesh,
its gate refuses a CPU, a failing leg fails the script, and the compile-cache
helper puts the cache where the contract says.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


@pytest.fixture(scope="module")
def tiny_data(smoke, tmp_path_factory):
    """Two global batches of 784-wide rows (and a short val split)."""
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 10, 2 * smoke.BATCH + 64)
    x = rng.randn(len(labels), 784).astype(np.float32) * 0.3
    x[np.arange(len(labels)), labels] += 2.0  # learnable in a few steps
    y = np.eye(10, dtype=np.float32)[labels]
    n = 2 * smoke.BATCH
    dst = tmp_path_factory.mktemp("smoke_data")
    smoke.write_split(dst, x[:n], y[:n], x[n:], y[n:])
    return dst


def test_legs_pass_small_on_the_cpu_mesh(smoke, tiny_data, tmp_path):
    """Every leg, through train.main, at the reference model's full width on
    two batches — leg C on a 2x2 corner of the 8 emulated devices."""
    a = smoke.run_leg(
        "leg A", smoke.leg_reference,
        tiny_data, tiny_data, tmp_path, tmp_path, epochs=2,
    )
    b = smoke.run_leg(
        "leg B", smoke.leg_deep_one_chip,
        tiny_data, tmp_path, tmp_path, model="mnist-mlp",
    )
    c = smoke.run_leg(
        "leg C", smoke.leg_deep_mesh,
        tiny_data, tmp_path, tmp_path, b["checkpoint"], model="mnist-mlp",
    )
    assert a["init_hash"] == smoke.INIT_HASH["mnist-mlp"]
    assert a["oracle_gap"]["steps"] == 2 and a["oracle_gap"]["of_tolerance"] <= 1
    assert a["losses"][1] < a["losses"][0]
    assert set(c["param_bytes"]) == {0, 1, 2, 3}
    assert c["cross_layout_gap"]["of_tolerance"] <= 1
    for facts in (a, b, c):
        assert facts["wall_s"] >= facts["compile_s"] >= 0
    # the compile seconds come from the package's own listener (the span
    # log), and every leg compiles or loads something
    assert a["compile_s"] > 0 and c["compile_s"] > 0
    assert a["cache_hits"] + a["cache_misses"] > 0
    assert (tmp_path / "legC.log").read_text().count("DP replicas in sync") == 1


def test_head_split_takes_the_first_rows(smoke, tiny_data, tmp_path):
    dst = smoke.head_split(tiny_data, tmp_path / "head", smoke.BATCH, 8)
    assert np.array_equal(
        np.load(dst / "x_train.npy"),
        np.load(tiny_data / "x_train.npy")[: smoke.BATCH],
    )
    assert len(np.load(dst / "y_val.npy")) == 8
    with pytest.raises(RuntimeError, match="fewer than"):
        smoke.head_split(tiny_data, tmp_path / "big", 10**6, 8)


def test_script_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode != 0
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("chip_smoke: FAIL device") and "'cpu'" in last
    assert '"ok"' not in r.stdout


def _fake_chip(smoke, monkeypatch, tiny_data, tmp_path, count):
    monkeypatch.setattr(
        smoke, "find_chip",
        lambda: {"platform": "tpu", "kind": "fake", "count": count},
    )
    monkeypatch.setattr(
        smoke, "make_data",
        lambda *_a: {"full": tiny_data, "oracle": tiny_data, "deep": tiny_data},
    )
    monkeypatch.setattr(smoke, "WORK_DIR", tmp_path / "work")
    monkeypatch.setattr(smoke, "OUT_DIR", tmp_path / "out")


def test_a_failing_leg_fails_the_script(smoke, tiny_data, tmp_path, monkeypatch, capsys):
    """No swallowed phase: leg A raising ends the run, non-zero, named on
    the last line, with no result object printed and leg B never started."""
    started = []

    def boom(*_a, **_k):
        raise RuntimeError("boom")

    _fake_chip(smoke, monkeypatch, tiny_data, tmp_path, count=1)
    monkeypatch.setattr(smoke, "leg_reference", boom)
    monkeypatch.setattr(
        smoke, "leg_deep_one_chip", lambda *_a, **_k: started.append("B")
    )
    assert smoke.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1] == "chip_smoke: FAIL leg A: RuntimeError: boom"
    assert not any(line.startswith("{") for line in out)
    assert started == []


def test_result_line_and_unrun_leg_are_printed(smoke, tiny_data, tmp_path, monkeypatch, capsys):
    """With legs that pass: the last line is the result object, exactly, and
    on fewer than four devices leg C is reported as not run, not skipped
    silently."""
    _fake_chip(smoke, monkeypatch, tiny_data, tmp_path, count=1)
    monkeypatch.setattr(smoke, "leg_reference", lambda *_a, **_k: {})
    monkeypatch.setattr(
        smoke, "leg_deep_one_chip", lambda *_a, **_k: {"checkpoint": "x"}
    )
    assert smoke.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "fake", "count": 1},
    }
    assert "chip_smoke: leg C: not run, 1 device(s)" in out
    summary = json.loads((tmp_path / "out" / "run1" / "summary.json").read_text())
    assert summary["leg C"] == "not run, 1 device(s)"
    assert not (tmp_path / "work").exists()


def test_compile_cache_dir_policy(tmp_path, monkeypatch):
    """``<base>/<scope tag>``: base is <checkout>/.jax_cache whatever the
    working directory, or JAX_COMPILATION_CACHE_DIR when that is set."""
    from shallowspeed_tpu.compile_cache import enable_compile_cache
    from shallowspeed_tpu.observability.scopes import CACHE_TAG

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        for name in ("a", "b"):
            (tmp_path / name).mkdir()
            monkeypatch.chdir(tmp_path / name)
            jax.config.update("jax_compilation_cache_dir", None)
            assert enable_compile_cache() == str(ROOT / ".jax_cache" / CACHE_TAG)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
        jax.config.update("jax_compilation_cache_dir", "as-jax-read-it")
        assert enable_compile_cache() == str(tmp_path / "given" / CACHE_TAG)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
