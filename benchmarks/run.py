#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process builds one ``TrainingSession`` through the public API from the
cell's configuration and mix (``cells.py``), on data drawn from the seed by
the generator the configuration names (``datasets/``), trains the first steps
for the reference check, warms up the one epoch program the cell runs, and
then calls ``train_epoch()`` back to back for ``--seconds``: one dispatch and
one loss readback per epoch, ``train.py``'s own loop. It times that loop
itself with the host clock (every call ends in a readback, which cannot return
before the device is done), reads the peak memory from the devices, and only
then runs the plain reference (``references/``) and compares (``check.py``).
The last line of stdout is the record; its last key, ``compared``, and the
last lines of stderr hold each number that was compared beside its limit.
With ``--trace 1`` a short stretch of the same loop is traced afterwards and
the record carries the cell's per-layer metrics, each computed by its own
reader under ``layer_metrics/``.

The three end-to-end metrics: ``samples_per_s`` is the samples of one epoch
over the median time from one epoch's completed readback to the next;
``peak_hbm_bytes`` is ``peak_memory_bytes`` below; ``setup_s`` runs from
process start to the window's opening, less the accelerator runtime's own
start (``backend_s`` on the log line).

It measures only on an accelerator whose ``device_kind`` is in ``peaks.json``,
with at least the chips the cell asks for: anything else is a non-zero exit
and no record. ``--rehearse`` runs the same path at the mix's and the
configuration's ``rehearse`` sizes on whatever JAX finds (a CPU), and prints a
record with no metric in it: a CPU yields no device number.
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))  # the system under test

import cells  # noqa: E402
import check  # noqa: E402
import xtrace  # noqa: E402
import yardstick  # noqa: E402

WORK_DIR = ROOT / "data" / "bench"  # generated inputs and traces; git-ignored
HARD_LIMIT_S = 1150  # a hung collective never raises: dump every stack, leave


def say(message):
    print(f"bench: {message}", flush=True)


class Refused(Exception):
    """This machine cannot measure the cell; nothing is printed as a result."""


def claim_devices(cell, rehearse):
    import jax

    devices = jax.devices()  # starts the accelerator's runtime
    first = devices[0]
    if len(devices) < cell["chips"]:
        raise Refused(
            f"{cell['name']} needs {cell['chips']} chip(s), JAX found "
            f"{len(devices)} ({first.platform})"
        )
    if rehearse:
        return devices, None
    if first.platform == "cpu":
        raise Refused(
            "JAX found no accelerator (platform 'cpu'); there is no CPU "
            "fallback on the measurement path (--rehearse rehearses it)"
        )
    return devices, yardstick.peaks_for(first.device_kind)


def peak_memory_bytes(devices):
    """The most any chip held, read after the window. The runtime counts
    arrays (``bytes_in_use``) and the memory a loaded program reserves for its
    temporaries (``bytes_reserved``: activations, gradient accumulators)
    apart, so ``peak_bytes_in_use`` alone never sees a training step's
    working memory. A chip's peak is the larger of the most its arrays ever
    took and what it holds now, with the window's program loaded, plus the
    largest reservation."""
    peak = 0
    for d in devices:
        m = d.memory_stats() or {}
        peak = max(
            peak,
            m.get("peak_bytes_in_use", 0),
            m.get("bytes_in_use", 0) + m.get("peak_bytes_reserved", 0),
        )
    return peak


# -- the measured loop -----------------------------------------------------


def run_window(loop, seconds):
    """Call ``loop()`` back to back until ``seconds`` have passed; the window
    opens now (the caller has just completed a readback) and closes on the
    readback of the epoch that crosses the mark."""
    epochs, failed = [], 0
    opened_wall = time.time()
    opened = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        try:
            loss = loop()
        except Exception:  # noqa: BLE001 — an epoch that raises is a failed operation of the record; the session's state is unknown after it, so the window ends
            traceback.print_exc()
            failed += 1
            epochs.append((t0, time.perf_counter(), math.nan))
            break
        t1 = time.perf_counter()
        epochs.append((t0, t1, loss))
        if not math.isfinite(loss):
            failed += 1
        if t1 - opened >= seconds:
            break
    return {
        "epochs": epochs,
        "failed": failed,
        "opened": opened,
        "closed": epochs[-1][1],
        "opened_wall": opened_wall,
        "closed_wall": time.time(),
    }


def run_traced(loop, trace_dir, trace_ms, keep=None):
    """Trace ``trace_ms`` of the same loop. The loop runs on a thread of its
    own so that the stretch may be shorter than one epoch: an epoch of the
    small-batch cells is millions of device events, and the profiler takes
    seconds per hundred thousand. ``keep``: also write the trace, as read,
    to that ``.json.gz`` (to look at by hand, or to record a test's trace)."""
    import jax

    stop, warm = threading.Event(), threading.Event()
    epoch_s, errors = [], []

    def train():
        try:
            while not stop.is_set():
                t0 = time.perf_counter()
                loop()
                epoch_s.append(time.perf_counter() - t0)
                warm.set()
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread below
            errors.append(e)
            warm.set()

    thread = threading.Thread(target=train, name="bench-loop")
    thread.start()
    warm.wait()
    jax.profiler.start_trace(str(trace_dir))
    time.sleep(trace_ms / 1e3)
    stop.set()
    jax.profiler.stop_trace()
    thread.join()
    if errors:
        raise errors[0]
    path = xtrace.newest_xplane(trace_dir)
    trace = xtrace.load_xplane(path) if path else {"planes": []}
    if keep:
        Path(keep).parent.mkdir(parents=True, exist_ok=True)
        xtrace.save_json(trace, keep)
    return {
        "trace": trace,
        "devices": xtrace.reduce_trace(trace),
        "epoch_s": epoch_s,
    }


# -- one run ---------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse",
        action="store_true",
        help="tiny sizes, any platform, no metric in the record",
    )
    ap.add_argument(
        "--keep-trace",
        metavar="FILE.json.gz",
        help="with --trace 1: also write the trace, as read, to this file",
    )
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True)

    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    config, mix = cell["config"], cell["mix"]
    if mix["loop"] != "train_epoch":
        raise cells.BadCell(f"mix loop {mix['loop']!r}: only train_epoch is driven")
    model = cells.load_module(HERE / "references" / f"{config['reference']}.py")

    import jax

    from compile_clock import CompileClock
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.compile_cache import enable_compile_cache

    marks = {"imports_s": time.perf_counter() - _T0}
    t = time.perf_counter()
    devices, peaks = claim_devices(cell, args.rehearse)
    marks["backend_s"] = time.perf_counter() - t
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    say(
        f"{cell['name']} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind} ({devices[0].platform}), compile cache {cache_dir}"
    )

    # data from the seed, written where data.Dataset reads it, removed again
    # as soon as the session holds it
    kwargs = cell["session"]
    batch, mubatches = kwargs["global_batch_size"], kwargs["mubatches"]
    steps = config["check"]["steps"]
    work_dir = WORK_DIR / cell["name"]
    data_dir = work_dir / "data"
    t = time.perf_counter()
    arrays = cells.make_dataset(cell, args.seed, mix["dataset_rows"], data_dir)
    prefix = check.prefix(arrays, steps, batch, mubatches)
    del arrays
    marks["data_s"] = time.perf_counter() - t

    t = time.perf_counter()
    try:
        session = TrainingSession(data_dir=str(data_dir), **kwargs)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    marks["init_s"] = time.perf_counter() - t
    steps_per_epoch = session.batches_per_epoch
    per_epoch = steps_per_epoch * batch
    if steps > steps_per_epoch:
        raise cells.BadCell(f"check.steps {steps} exceeds one epoch of {cell['name']}")

    # the checked prefix: the first steps from init, through the program's own
    # chunked-epoch entry point; then the rest of that epoch, then one whole
    # epoch through train_epoch(), which is the program the window runs
    t = time.perf_counter()
    start = check.layers(session.params())
    marks["params_s"] = time.perf_counter() - t
    t = time.perf_counter()
    _, loss_first = session.train_steps(steps)
    marks["prefix_s"] = time.perf_counter() - t
    after = check.layers(session.params())
    prefix_loss = loss_first  # the epoch's mean loss, if the prefix was the epoch
    t = time.perf_counter()
    while session.step_in_epoch:
        _, loss_first = session.train_steps(steps_per_epoch)
    marks["first_epoch_s"] = time.perf_counter() - t
    t = time.perf_counter()
    loop = getattr(session, mix["loop"])
    loss_warm = loop()
    marks["warm_epoch_s"] = time.perf_counter() - t
    marks["compile_s"] = clock.seconds()
    # everything from process start to here, less the accelerator runtime's
    # own start (8 to 13 s on a v5e host and drifting by seconds between
    # processes: the machine's, not this repository's, and on its own enough
    # to move a median by the bound)
    setup_s = time.perf_counter() - _T0 - marks["backend_s"]

    window = run_window(loop, args.seconds)
    memory_peak = peak_memory_bytes(devices)
    epochs = window["epochs"]
    good = [e for e in epochs if math.isfinite(e[2])]
    elapsed = window["closed"] - window["opened"]
    # the median time from one epoch's completed readback to the next: a
    # stall of the machine's that hits one epoch in a hundred is not the
    # program's pace (the plain mean is printed beside it)
    done = [window["opened"]] + [e[1] for e in epochs]
    between = sorted(b - a for a, b in zip(done, done[1:]))
    samples_per_s = per_epoch / between[len(between) // 2] if good else 0.0
    mean_rate = len(good) * per_epoch / elapsed
    compiles = clock.count_between(window["opened_wall"], window["closed_wall"])
    epoch_ms = sorted((t1 - t0) * 1e3 for t0, t1, _ in epochs)
    say(
        f"window {elapsed:.3f} s, {len(epochs)} epochs of {per_epoch} samples "
        f"(ms: min {epoch_ms[0]:.3f} median {epoch_ms[len(epoch_ms) // 2]:.3f} "
        f"max {epoch_ms[-1]:.3f}), "
        f"{samples_per_s:.6g} samples/s (mean over the window {mean_rate:.6g}); "
        f"set-up {setup_s:.2f} s "
        + " ".join(f"{k}={v:.2f}" for k, v in marks.items())
        + f"; cache hits {clock.hits} misses {clock.misses}; losses "
        f"{loss_first:.6g} -> {loss_warm:.6g} -> {epochs[-1][2]:.6g}; memory "
        + json.dumps([d.memory_stats() for d in devices[: cell["chips"]]])
    )

    traced = None
    if args.trace:
        try:
            traced = run_traced(
                loop, work_dir / "trace", mix["trace_ms"], keep=args.keep_trace
            )
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)

    # what `correct` needs beyond the window, all of it outside the window
    verdicts = {
        "epochs_finite": window["failed"] == 0 and bool(good),
        # training made progress: some later epoch's mean loss is below the
        # first epoch's (not "the last one's": at batch 128 some seeds train
        # to 2e-5 and then collapse to one class, which is the optimizer's
        # doing at this learning rate and not a fault of the system)
        "loss_fell": math.isfinite(loss_first)
        and min([loss_warm] + [e[2] for e in good]) < loss_first,
        "no_compile_in_window": compiles == 0,
    }
    try:
        session.assert_replicas_in_sync()
        verdicts["replicas_in_sync"] = True
    except ValueError as e:
        say(f"replicas out of sync: {e}")
        verdicts["replicas_in_sync"] = False
    # the reference trains at the cell's own microbatch size and needs room
    # for it: give back everything the system holds on the chips first
    del session, loop
    jax.clear_caches()
    gc.collect()
    ref_params, ref_losses = model.make_reference(config)(start, *prefix)
    report = check.compare(
        after, ref_params, start, config["check"],
        loss=prefix_loss, ref_loss=sum(ref_losses) / len(ref_losses),
    )
    verdicts["reference"] = report["ok"]
    say(f"reference check: {json.dumps(report)}")
    # each number that was compared, beside its limit (the booleans among the
    # verdicts are on the line after); a gap that is not finite reads 1e300

    def beside(value, limit):
        return {"value": value if math.isfinite(value) else 1e300, "limit": limit}

    compared = {
        "update_gap_over_allowed": beside(report["worst"], 1.0),
        "compiles_in_window": beside(compiles, 0),
    }
    if "loss_gap" in report:
        compared["loss_gap_over_ref_loss"] = beside(
            report["loss_gap"] / abs(report["ref_loss"]), config["check"]["loss_rtol"]
        )

    flops = model.train_flops_per_sample(config)
    if peaks is not None:
        yardstick.check_plausible(samples_per_s, flops, cell["chips"], peaks)
    run = {
        "cell": cell,
        "model": model,
        "peaks": peaks,
        "flops_per_sample": flops,
        "session": {"batch": batch, "steps_per_epoch": steps_per_epoch},
        "window": {**window, "samples_per_s": samples_per_s},
        "setup": {**marks, "setup_s": setup_s},
        "compiles_in_window": compiles,
        "traced": traced,
    }
    if args.trace:
        wanted, values = cell["per_layer"], {}
        for metric in wanted:
            reader = cells.load_module(HERE / "layer_metrics" / f"{metric['name']}.py")
            value = reader.read(run)
            if value is not None:
                values[metric["name"]] = value
    else:
        wanted = cell["end_to_end"]
        values = {
            "samples_per_s": samples_per_s,
            "peak_hbm_bytes": memory_peak,
            "setup_s": setup_s,
        }
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted
        if m["name"] in values
    }
    say(f"verdicts: {json.dumps(verdicts)}")

    first = devices[0]
    record = {
        "correct": all(verdicts.values()),
        "attempted": len(epochs),
        "failed": window["failed"],
        "metrics": metrics,
        "device": {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": len(devices),
        },
    }
    if args.rehearse:
        # every number above came from a CPU at toy sizes: show that the path
        # produced them, and publish none
        say(f"rehearsed, not published: {json.dumps(sorted(metrics))}")
        record["metrics"] = {}
    else:
        record["device"]["memory_peak_bytes"] = memory_peak
        devs = traced["devices"] if traced else []
        if devs:
            record["device"]["busy_s"] = (
                sum(xtrace.total(d["busy"]) for d in devs) / len(devs) / 1e9
            )
            record["device"]["window_s"] = sum(map(xtrace.window_s, devs)) / len(devs)
            record["breakdown"] = {
                "device_ops": xtrace.top_device_ops(devs),
                "idle_gaps": xtrace.top_idle_gaps(traced["trace"], devs),
            }
    record["compared"] = compared  # last in the record, and last on stderr
    faulthandler.cancel_dump_traceback_later()
    for name, number in compared.items():
        print(
            f"bench: compared {name}: {number['value']!r} limit {number['limit']!r}",
            file=sys.stderr, flush=True,
        )
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (Refused, cells.BadCell, yardstick.UnknownDevice,
            yardstick.ImplausibleRate) as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
