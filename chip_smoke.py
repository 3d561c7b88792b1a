#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python3 chip_smoke.py

Drives the training path once through the entry points a user would call
(``prepare_data.prepare`` then ``train.main(argv)``, i.e. ``train.py``) at the
full published width of two zoo models, with seeded random weights and seeded
synthetic data, and checks the results by the repo's own means:

- leg A, one chip: ``train.py`` sequential on ``mnist-mlp``. The init hash
  equals the hash the same sizes give on a CPU; a handful of steps agree with
  the NumPy oracle (``tests/oracle_numpy.py``) inside ``ORACLE_TOL`` — at a
  learning rate chosen so that one matmul run as a single bf16 pass fails
  it (see ``ORACLE_LR``); a few epochs' losses are finite and falling.
- leg B, one chip: ``train.py --model mlp-deep``, one epoch of ``DEEP_STEPS``
  steps, ``--checkpoint``.
- leg C, every chip (needs >= 4): ``train.py --dp 2 --pp 2 --schedule
  pipedream --model mlp-deep`` on leg B's data; the replicas are in sync,
  every chip holds its own parameter shard, and the saved logical tree equals
  leg B's inside ``CROSS_LAYOUT_TOL``. With fewer devices the summary says
  ``leg C: not run, N device(s)``.

It runs everything in THIS process (a chip belongs to one process, so no
child is ever started), refuses any platform but ``tpu``, gives every leg a
wall-clock limit, and exits non-zero naming the first leg that failed. The
last line of stdout on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Per-leg wall time is split into compile (JAX's own compile events, cache
loads included) and run — facts about the smoke, not performance numbers.
Logs and ``summary.json`` go under ``chiprun_out/chip_smoke/``; the generated
data and checkpoints live under ``data/chip_smoke/`` and are removed at the
end.

The legs are functions of directories and sizes so ``tests/test_chip_smoke.py``
can run them small on the emulated CPU mesh; ``main()`` itself never accepts
a CPU.
"""

import ast
import contextlib
import faulthandler
import importlib.util
import io
import json
import math
import os
import re
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"  # copied back by the chip tool
WORK_DIR = ROOT / "data" / "chip_smoke"  # generated inputs; removed at exit

BATCH = 128  # train.py's default global batch (4 microbatches of 32)
# tests/test_trainer.py:50-51 — the NumPy-oracle parity tolerance, on the
# parameters after ORACLE_STEPS steps at learning rate ORACLE_LR. The rate is
# what makes that tolerance bite on this data: the synthetic features are
# small, so at train.py's default 0.006 five steps move a weight by ~1e-6
# and only a forward matmul at bf16 shows (1.1x the tolerance; a backward
# one stays under 0.02x). Measured on a v5e (PR 21) with ONE matmul of ONE
# layer (first, middle, last) dropped from Precision.HIGHEST to a single
# bf16 pass: a forward or input-gradient matmul is 126x to 2,950x outside
# the tolerance already at 1.0 and 4.0; a weight-gradient matmul, whose
# error reaches only its own layer, still passes at 4.0 (0.96x to 0.99x)
# and is 7x to 2,250x outside at 8.0, where the untouched fp32 run sits 23x
# inside (0.044).
ORACLE_TOL = {"rtol": 2e-4, "atol": 2e-6}
ORACLE_LR = 8.0
ORACLE_STEPS = 5
# docs/numerics.md "What is NOT bit-identical": two layouts reassociate the
# same float sums, so logical trees agree to this "after a few steps", not
# bitwise. DEEP_STEPS is how few: mlp-deep (22 equal relu layers) amplifies
# rounding, and on ONE v5e two sequential programs that differ only in
# reassociation (--mubatches 2 against 4) sit at 1e-4 of this tolerance
# after 4 steps, 0.14 after 8, 1.1 after 16 and 9.5 after 64 (PR 21) — so a
# longer epoch would fail leg C with no layout at fault. After 4 steps the
# tree has moved 1.6e-4 from init, 50 times the tolerance's floor: a layout
# bug that halves or doubles an update is far outside it.
CROSS_LAYOUT_TOL = {"rtol": 3e-4, "atol": 3e-6}
DEEP_STEPS = 4
# utils.model_hash of the untrained model, as computed on a CPU host. Init is
# host NumPy seeded per layer from its dims, so it must not depend on the
# device, the layout or the machine.
INIT_HASH = {"mnist-mlp": "3555e8d14c8766953213e1ed746e350e97309bbe"}
LEG_LIMIT_S = {"import": 120, "data": 180, "leg A": 300, "leg B": 240, "leg C": 420}

_LOSS_RE = re.compile(r"^Epoch: \d+, mean train loss: (\S+)$", re.M)
_HASH_RE = re.compile(r"^final model hash: ([0-9a-f]{40})$", re.M)
_PLACED_RE = re.compile(
    r"^mesh placement: (\S+) device_ids=(\[.*\]) param bytes per device="
    r"(\{.*\})$",
    re.M,
)


class LegFailed(Exception):
    """A named phase of the smoke failed; ``__cause__`` says how."""

    def __init__(self, leg):
        super().__init__(leg)
        self.leg = leg


def require(ok, message):
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not ok:
        raise RuntimeError(message)


# -- what JAX itself says about compiling -------------------------------------


def compile_mark():
    """Where the program's record of its compiles stands now: the span log of
    ``observability.spans``, fed by the package's one ``jax.monitoring``
    listener (``enable_compile_cache`` registers it). Before the import leg
    has loaded the package nothing has compiled under the listener."""
    spans = sys.modules.get("shallowspeed_tpu.observability.spans")
    cache = spans.log().cache if spans else {"hits": 0, "misses": 0}
    return time.perf_counter_ns(), cache["hits"], cache["misses"]


def compile_since(mark):
    """``(compile_seconds, cache_hits, cache_misses)`` since ``mark``: wall
    time spent tracing, lowering and compiling (or loading from the persistent
    cache), as the union of the log's compile events, since nested traces
    report nested events; and the persistent cache's hits and misses."""
    from shallowspeed_tpu.observability import spans

    since, hits, misses = mark
    log = spans.log()
    events = [
        e for e in log.entries()
        if e.start >= since
        and e.name in ("compile/trace", "compile/lower", "compile/backend")
    ]
    return (
        spans.covered_ns(events) / 1e9,
        log.cache["hits"] - hits,
        log.cache["misses"] - misses,
    )


# -- running one leg under a limit ---------------------------------------------


def _expire(leg, limit_s):
    # a collective that disagrees across devices hangs inside the runtime
    # and never raises: name the leg, show where every thread is, and leave
    # without waiting for the stuck thread
    print(f"chip_smoke: FAIL {leg}: wall-clock limit of {limit_s}s hit", flush=True)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    os._exit(3)


def run_leg(leg, fn, *args, **kwargs):
    """Run ``fn`` as the phase named ``leg`` under its wall-clock limit.
    Returns the leg's facts plus its timing; any failure (``train.main``
    exits through ``SystemExit``) becomes ``LegFailed(leg)``."""
    limit_s = LEG_LIMIT_S[leg]
    watchdog = threading.Timer(limit_s, _expire, (leg, limit_s))
    watchdog.daemon = True
    mark = compile_mark()
    t0 = time.perf_counter()
    watchdog.start()
    try:
        facts = fn(*args, **kwargs) or {}
    except (Exception, SystemExit) as e:
        raise LegFailed(leg) from e
    finally:
        watchdog.cancel()
    wall = time.perf_counter() - t0
    compile_s, hits, misses = compile_since(mark)
    facts.update(
        wall_s=round(wall, 2),
        compile_s=round(compile_s, 2),
        run_s=round(wall - compile_s, 2),
        cache_hits=hits,
        cache_misses=misses,
    )
    print(
        f"chip_smoke: {leg}: ok in {wall:.1f}s (compile {compile_s:.1f}s, "
        f"run {wall - compile_s:.1f}s; persistent cache {hits} hit(s), "
        f"{misses} miss(es))",
        flush=True,
    )
    return facts


class _Tee(io.TextIOBase):
    def __init__(self, *sinks):
        self._sinks = sinks

    def write(self, text):
        for sink in self._sinks:
            sink.write(text)
        return len(text)

    def flush(self):
        for sink in self._sinks:
            sink.flush()


def run_train(argv, log_path):
    """``python train.py <argv>`` in this process; returns what it printed
    (also echoed, and kept in ``log_path``)."""
    import train

    argv = [str(a) for a in argv]
    print(f"chip_smoke: $ train.py {' '.join(argv)}", flush=True)
    kept = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, kept)):
        train.main(argv)
    text = kept.getvalue()
    log_path.write_text(text)
    return text


def _losses(text):
    return [float(v) for v in _LOSS_RE.findall(text)]


def _final_hash(text):
    found = _HASH_RE.findall(text)
    require(len(found) == 1, f"expected one 'final model hash' line, got {found}")
    return found[0]


# -- data ---------------------------------------------------------------------


def write_split(dst, x_train, y_train, x_val, y_val):
    """A data directory in the format ``data.Dataset`` reads."""
    import numpy as np

    dst.mkdir(parents=True, exist_ok=True)
    np.save(dst / "x_train.npy", x_train)
    np.save(dst / "y_train.npy", y_train)
    np.save(dst / "x_val.npy", x_val)
    np.save(dst / "y_val.npy", y_val)


def head_split(src, dst, train_rows, val_rows):
    """The first rows of ``src``'s splits as a data directory of their own."""
    import numpy as np

    parts = [
        np.load(src / name, mmap_mode="r")[:rows]
        for name, rows in (
            ("x_train.npy", train_rows), ("y_train.npy", train_rows),
            ("x_val.npy", val_rows), ("y_val.npy", val_rows),
        )
    ]
    require(
        len(parts[0]) == train_rows and len(parts[2]) == val_rows,
        f"{src} holds fewer than {train_rows}/{val_rows} rows",
    )
    write_split(dst, *parts)
    return dst


def make_data(work_dir, oracle_steps, deep_steps):
    """Seeded synthetic data through ``prepare_data`` (no network, no
    sklearn data), plus two head slices: ``oracle_steps`` batches for the
    NumPy comparison and ``deep_steps`` for the mlp-deep legs."""
    import prepare_data

    full = work_dir / "synthetic"
    prepare_data.prepare(full, "synthetic")
    return {
        "full": full,
        "oracle": head_split(
            full, work_dir / "oracle", oracle_steps * BATCH, BATCH
        ),
        "deep": head_split(
            full, work_dir / "deep", deep_steps * BATCH, 8 * BATCH
        ),
    }


# -- comparisons ----------------------------------------------------------------


def _blocks(stages):
    """``[(W, b), ...]`` in global layer order from a per-stage params list."""
    return [(layer["W"], layer["b"]) for stage in stages for layer in stage]


def _logical_blocks(ckpt):
    """The blocks of a ``train.py --checkpoint`` file, whatever layout wrote
    it."""
    from shallowspeed_tpu.checkpoint import load_checkpoint

    stages, _spec, _meta = load_checkpoint(ckpt, 1)
    return _blocks(stages)


def tree_gap(got, want, rtol, atol):
    """How far ``got`` is from ``want``: the largest absolute difference and
    the largest difference as a multiple of ``atol + rtol * |want|`` (<= 1
    means inside the tolerance, ``numpy.allclose``'s rule). float32
    throughout: the difference of two nearby float32 values is exact."""
    import numpy as np

    max_abs = worst = 0.0
    for g_layer, w_layer in zip(got, want, strict=True):
        for g, w in zip(g_layer, w_layer, strict=True):
            g = np.asarray(g, np.float32).reshape(-1)
            w = np.asarray(w, np.float32).reshape(-1)
            diff = np.abs(g - w)
            require(bool(np.isfinite(diff).all()), "non-finite parameter")
            max_abs = max(max_abs, float(diff.max()))
            diff /= np.float32(atol) + np.float32(rtol) * np.abs(w)
            worst = max(worst, float(diff.max()))
    return {"max_abs": max_abs, "of_tolerance": worst}


def oracle_gap(ckpt, data_dir, sizes, lr, mubatches=4):
    """Train the NumPy oracle on every batch of ``data_dir`` at learning rate
    ``lr`` and measure the checkpoint against it."""
    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "oracle_numpy", ROOT / "tests" / "oracle_numpy.py"
    )
    oracle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(oracle)

    x = np.load(data_dir / "x_train.npy").astype(np.float32)
    y = np.load(data_dir / "y_train.npy").astype(np.float32)
    steps = len(x) // BATCH
    x = x[: steps * BATCH].reshape(steps, mubatches, BATCH // mubatches, -1)
    y = y[: steps * BATCH].reshape(steps, mubatches, BATCH // mubatches, -1)
    params = oracle.init_params(sizes)
    for step in range(steps):
        params = oracle.train_step(params, x[step], y[step], lr, BATCH)
    gap = tree_gap(_logical_blocks(ckpt), params, **ORACLE_TOL)
    gap["steps"] = steps
    return gap


# -- the legs -------------------------------------------------------------------


def leg_reference(data_dir, oracle_dir, work_dir, out_dir, model="mnist-mlp", epochs=3):
    """Leg A: the reference model, sequential, one chip."""
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import utils

    sizes, _act = Mo.resolve_model(model)
    common = ["--model", model]

    # the untrained model, after its round trip through the device
    text = run_train(
        common + ["--data-dir", data_dir, "--epochs", 0], out_dir / "legA_init.log"
    )
    init_hash = _final_hash(text)
    host_hash = utils.model_hash(Mo.init_model(Mo.make_model_spec(sizes, 1, BATCH)))
    require(
        init_hash == host_hash == INIT_HASH.get(model, host_hash),
        f"init hash {init_hash} (device) / {host_hash} (host) / "
        f"{INIT_HASH.get(model)} (pinned on CPU) disagree",
    )

    # a handful of steps against the NumPy oracle
    ckpt = work_dir / "legA_oracle.npz"
    run_train(
        common + ["--data-dir", oracle_dir, "--epochs", 1, "--no-eval",
                  "--lr", ORACLE_LR, "--checkpoint", ckpt],
        out_dir / "legA_oracle.log",
    )
    gap = oracle_gap(ckpt, oracle_dir, sizes, ORACLE_LR)
    print(
        f"chip_smoke: leg A: gap to the NumPy oracle after {gap['steps']} steps "
        f"at lr {ORACLE_LR:g}: max |diff| {gap['max_abs']:.3e}, "
        f"{gap['of_tolerance']:.3g} of the "
        f"tolerance (rtol {ORACLE_TOL['rtol']:g}, atol {ORACLE_TOL['atol']:g})",
        flush=True,
    )
    require(gap["of_tolerance"] <= 1.0, f"outside the oracle tolerance: {gap}")

    # a few epochs: finite and falling
    text = run_train(
        common + ["--data-dir", data_dir, "--epochs", epochs],
        out_dir / "legA_train.log",
    )
    losses = _losses(text)
    require(len(losses) == epochs, f"expected {epochs} epoch losses, got {losses}")
    require(all(math.isfinite(v) for v in losses), f"non-finite loss in {losses}")
    require(
        all(b < a for a, b in zip(losses, losses[1:])), f"losses not falling: {losses}"
    )
    return {
        "init_hash": init_hash,
        "oracle_gap": gap,
        "losses": losses,
        "final_hash": _final_hash(text),
    }


def leg_deep_one_chip(data_dir, work_dir, out_dir, model="mlp-deep"):
    """Leg B: the widest model the repo supports, one epoch, one chip."""
    from shallowspeed_tpu import model as Mo

    ckpt = work_dir / "legB.npz"
    text = run_train(
        ["--model", model, "--data-dir", data_dir, "--epochs", 1,
         "--checkpoint", ckpt],
        out_dir / "legB.log",
    )
    losses = _losses(text)
    require(
        len(losses) == 1 and math.isfinite(losses[0]), f"epoch loss: {losses}"
    )
    require(ckpt.is_file(), f"{ckpt} was not written")
    # how far one epoch moved the tree: what leg C's gap is to be read against
    sizes, _act = Mo.resolve_model(model)
    init = Mo.init_model(Mo.make_model_spec(sizes, 1, BATCH))
    moved = tree_gap(_logical_blocks(ckpt), _blocks(init), **CROSS_LAYOUT_TOL)[
        "max_abs"
    ]
    print(f"chip_smoke: leg B: one epoch moved the tree by max |diff| {moved:.3e}")
    return {
        "loss": losses[0],
        "final_hash": _final_hash(text),
        "checkpoint": str(ckpt),
        "moved_from_init": moved,
    }


def leg_deep_mesh(
    data_dir, work_dir, out_dir, reference_ckpt, model="mlp-deep", dp=2, pp=2
):
    """Leg C: the same model and data over a dp x pp mesh; the logical tree
    must equal the one-chip tree of ``reference_ckpt``."""
    ckpt = work_dir / "legC.npz"
    text = run_train(
        ["--dp", dp, "--pp", pp, "--schedule", "pipedream", "--model", model,
         "--data-dir", data_dir, "--epochs", 1, "--checkpoint", ckpt],
        out_dir / "legC.log",
    )
    losses = _losses(text)
    require(
        len(losses) == 1 and math.isfinite(losses[0]), f"epoch loss: {losses}"
    )
    # train.py prints this only after assert_replicas_in_sync() returned
    require("DP replicas in sync" in text, "replica sync check did not run")

    placed = _PLACED_RE.findall(text)
    require(len(placed) == 1, "train.py printed no mesh placement line")
    layout, device_ids, held = placed[0]
    held = ast.literal_eval(held)
    require(
        len(held) == dp * pp and min(held.values()) > 0
        and max(held.values()) < sum(held.values()),
        f"parameters are not spread over {dp * pp} devices: {held}",
    )

    gap = tree_gap(
        _logical_blocks(ckpt), _logical_blocks(reference_ckpt), **CROSS_LAYOUT_TOL
    )
    print(
        f"chip_smoke: leg C: gap to leg B's tree: max |diff| {gap['max_abs']:.3e}, "
        f"{gap['of_tolerance']:.3g} of the cross-layout tolerance (rtol "
        f"{CROSS_LAYOUT_TOL['rtol']:g}, atol {CROSS_LAYOUT_TOL['atol']:g}); "
        f"mesh {layout} {device_ids}, parameter bytes per device {held}",
        flush=True,
    )
    require(gap["of_tolerance"] <= 1.0, f"outside the cross-layout tolerance: {gap}")
    return {
        "loss": losses[0],
        "final_hash": _final_hash(text),
        "mesh_layout": layout,
        "device_ids": ast.literal_eval(device_ids),
        "param_bytes": held,
        "cross_layout_gap": gap,
    }


# -- the smoke --------------------------------------------------------------------


def find_chip():
    """What JAX found, printed; the device dict only if it is a TPU."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']} devices={device['count']}",
        flush=True,
    )
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: FAIL device: JAX found platform "
            f"{device['platform']!r} ({device['kind']}), not a tpu — this "
            "script measures nothing on anything else",
            flush=True,
        )
        return None
    return device


def _load_program():
    # the whole program, imported up front so that a checkout that is
    # missing it fails here, by name, before any leg starts
    import prepare_data  # noqa: F401
    import train  # noqa: F401
    from shallowspeed_tpu.compile_cache import enable_compile_cache

    return {"cache_dir": enable_compile_cache()}


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def run_smoke(n_devices, work_dir, out_dir):
    """Every phase, in order, each under its limit. Returns the summary;
    raises ``LegFailed`` at the first failure."""
    summary = {"import": run_leg("import", _load_program)}
    cache_dir = summary["import"]["cache_dir"]
    entries_before = _cache_entries(cache_dir)
    work_dir.mkdir(parents=True, exist_ok=True)
    out_dir.mkdir(parents=True, exist_ok=True)

    dirs = {}
    summary["data"] = run_leg(
        "data",
        lambda: dirs.update(make_data(work_dir, ORACLE_STEPS, DEEP_STEPS)),
    )
    summary["leg A"] = run_leg(
        "leg A", leg_reference,
        dirs["full"], dirs["oracle"], work_dir, out_dir,
    )
    summary["leg B"] = run_leg(
        "leg B", leg_deep_one_chip, dirs["deep"], work_dir, out_dir
    )
    if n_devices >= 4:
        summary["leg C"] = run_leg(
            "leg C", leg_deep_mesh, dirs["deep"], work_dir, out_dir,
            summary["leg B"]["checkpoint"],
        )
    else:
        summary["leg C"] = f"not run, {n_devices} device(s)"
        print(f"chip_smoke: leg C: {summary['leg C']}", flush=True)
    summary["compile_cache"] = {
        "dir": cache_dir,
        "entries_before": entries_before,
        "entries_after": _cache_entries(cache_dir),
    }
    return summary


def _next_run_dir(out_dir):
    # two smokes in one chip call must not overwrite each other's logs
    k = 1
    while (out_dir / f"run{k}").exists():
        k += 1
    return out_dir / f"run{k}"


def main():
    device = find_chip()
    if device is None:
        return 1
    out_dir = _next_run_dir(OUT_DIR)
    try:
        summary = run_smoke(device["count"], WORK_DIR, out_dir)
    except LegFailed as failed:
        traceback.print_exception(failed, file=sys.stderr)
        cause = failed.__cause__
        print(
            f"chip_smoke: FAIL {failed.leg}: {type(cause).__name__}: {cause}",
            flush=True,
        )
        return 1
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    summary["device"] = device
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=2) + "\n")
    cache = summary["compile_cache"]
    print(
        f"chip_smoke: compile cache {cache['dir']}: {cache['entries_before']} "
        f"entries before, {cache['entries_after']} after; summary in "
        f"{out_dir / 'summary.json'}",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
