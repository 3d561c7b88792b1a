"""The chip's idle gap between two executions of the epoch program, split by
what the host was doing (PR 37).

Everything here is on the profiler's clock: the host spans ``epoch/dispatch``
and ``epoch/readback`` (``jax.profiler.TraceAnnotation``, written by
``TrainingSession._run_epoch_program``) and the chips' ``XLA Modules`` events
lie in one xplane. One epoch on the host is ``dispatch`` (the call into the
epoch program returns at once), ``readback`` (blocks until the loss is on the
host), then the session's bookkeeping until the next ``dispatch``. For the
boundary between executions ``E`` and ``E'`` of the main module on one chip,
with ``R`` the readback whose end is nearest ``E``'s end and ``D'`` the
dispatch whose start is nearest ``E'``'s start:

    tail    = R.end - E.end       the loss reaches the host, the loop wakes
    between = D'.start - R.end    host clock only: the same for every chip
    head    = E'.start - D'.start the next execution reaches the chip

and ``tail + between + head`` is ``E'.start - E.end`` exactly, the gap that
``host_gap_ms_per_epoch`` takes its median of. A head needs no earlier
execution, so a chip whose first execution the reduction dropped
(``xtrace.named_from``) still has the head of its second.

**The clocks.** The device's events are on the device's clock, converted; the
host's on the host's. Causality bounds the difference ``d`` (device reading
less host reading of one instant) from every epoch: an execution cannot begin
before its dispatch does (``d <= head``) and a readback cannot end before its
execution does (``d >= -tail``), so ``d`` lies in ``[-min tail, +min head]``
and ``clock_slack_ms`` is that interval's width: how far any tail or head may
be off. ``log_tie`` ties the span log's clock (``time.perf_counter_ns``) to
the profiler's through the dispatch spans that are in both.
"""

import json
import statistics

import xtrace

DISPATCH = "epoch/dispatch"
READBACK = "epoch/readback"
_KEY = "_gapsplit"


def host_spans(trace, name):
    """``[(start_ns, end_ns), ...]`` of the host spans called ``name``, in
    order, from every thread of the host's plane."""
    return sorted(
        (ev[1], ev[1] + ev[2])
        for plane in trace["planes"]
        if plane["name"] == xtrace.HOST_PLANE
        for line in plane["lines"]
        for ev in line["events"]
        if ev[0] == name
    )


def _nearest(spans, at, edge):
    return min(spans, key=lambda s: abs(s[edge] - at)) if spans else None


def split(trace, devices):
    """-> ``{"chips": {name: {"heads": [ns], "boundaries": [{"tail",
    "between", "head", "gap", "dispatch"}]}}, "launches": {dispatch_start:
    {name: start}}, "period": ns}`` or ``None`` where the trace holds no
    dispatch span or no chip holds two executions."""
    dispatches = host_spans(trace, DISPATCH)
    readbacks = host_spans(trace, READBACK)
    main = xtrace.main_module(devices) if devices else None
    if main is None or not dispatches:
        return None
    runs = {
        dev["name"]: sorted(
            (ev[1], ev[1] + ev[2]) for ev in dev["modules"] if ev[0] == main
        )
        for dev in devices
    }
    steps = [
        b[0] - a[0] for found in runs.values() for a, b in zip(found, found[1:])
    ]
    if not steps:
        return None
    # an execution belongs to a dispatch only if the two begin within half an
    # epoch of each other: the first execution of a trace was dispatched
    # before the trace began
    period = statistics.median(steps)

    def dispatch_of(execution):
        found = _nearest(dispatches, execution[0], 0)
        return found if abs(execution[0] - found[0]) < period / 2 else None

    def readback_of(execution):
        found = _nearest(readbacks, execution[1], 1)
        if found is None or abs(execution[1] - found[1]) >= period / 2:
            return None
        return found

    chips, launches = {}, {}
    for name, found in runs.items():
        heads, boundaries = [], []
        for i, execution in enumerate(found):
            dispatch = dispatch_of(execution)
            if dispatch is None:
                continue
            head = execution[0] - dispatch[0]
            heads.append(head)
            launches.setdefault(dispatch[0], {})[name] = execution[0]
            readback = readback_of(found[i - 1]) if i else None
            if readback is None:
                continue
            boundaries.append(
                {
                    "tail": readback[1] - found[i - 1][1],
                    "between": dispatch[0] - readback[1],
                    "head": head,
                    "gap": execution[0] - found[i - 1][1],
                    "dispatch": dispatch[0],
                }
            )
        chips[name] = {"heads": heads, "boundaries": boundaries}
    return {"chips": chips, "launches": launches, "period": period}


def summary(found):
    """The numbers the readers publish, in ms, from ``split``'s result: the
    three medians on the chip whose median gap is largest, each chip's median
    gap and head, the launch stagger (median over the dispatches that
    launched every chip of last start less first start; ``None`` on one
    chip) and the clock's interval."""
    ms = 1e-6
    with_gaps = {
        name: chip for name, chip in found["chips"].items() if chip["boundaries"]
    }
    if not with_gaps:
        return None

    def median(chip, key):
        return statistics.median(b[key] for b in chip["boundaries"])

    worst = max(with_gaps, key=lambda name: median(with_gaps[name], "gap"))
    n_chips = len(found["chips"])
    staggers = [
        max(starts.values()) - min(starts.values())
        for starts in found["launches"].values()
        if len(starts) == n_chips
    ]
    heads = [h for chip in found["chips"].values() for h in chip["heads"]]
    tails = [b["tail"] for chip in with_gaps.values() for b in chip["boundaries"]]
    return {
        "chip": worst,
        "tail_ms": median(with_gaps[worst], "tail") * ms,
        "between_ms": median(with_gaps[worst], "between") * ms,
        "head_ms": median(with_gaps[worst], "head") * ms,
        "gap_ms": median(with_gaps[worst], "gap") * ms,
        "boundaries": len(with_gaps[worst]["boundaries"]),
        "gap_ms_by_chip": {
            name: median(chip, "gap") * ms for name, chip in with_gaps.items()
        },
        "head_ms_by_chip": {
            name: statistics.median(chip["heads"]) * ms
            for name, chip in found["chips"].items()
            if chip["heads"]
        },
        "stagger_ms": (
            statistics.median(staggers) * ms if n_chips > 1 and staggers else None
        ),
        "clock_offset_ms": [-min(tails) * ms, min(heads) * ms],
        "clock_slack_ms": (min(tails) + min(heads)) * ms,
    }


def log_tie(trace):
    """The span log's clock against the profiler's, from the ``epoch/dispatch``
    spans that are in both: ``{"offset_ns": median of (trace start - log
    start), "spread_us": max - min of it, "spans": n}``, or ``None`` without
    a log or with fewer than two such spans in the trace. The trace's spans
    are found in the log as the run of entries whose durations fit best (a
    ``TraceAnnotation`` encloses the log's interval by about a microsecond)."""
    try:
        from shallowspeed_tpu.observability import spans
        logged = [e for e in spans.log().entries() if e.name == DISPATCH]
    except (ImportError, AttributeError):
        return None
    traced = host_spans(trace, DISPATCH)
    if len(traced) < 2 or len(logged) < len(traced):
        return None
    durations = [end - start for start, end in traced]

    def misfit(shift):
        return sum(
            abs(d - logged[shift + i].duration) for i, d in enumerate(durations)
        )

    shift = min(range(len(logged) - len(traced) + 1), key=misfit)
    offsets = [
        start - logged[shift + i].start for i, (start, _) in enumerate(traced)
    ]
    return {
        "offset_ns": statistics.median(offsets),
        "spread_us": (max(offsets) - min(offsets)) / 1e3,
        "spans": len(traced),
    }


def read(run):
    """``summary`` of the run's traced stretch (computed and printed once, as
    ``bench: gaps: {...}`` with the log's tie), or ``None``."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    if not run["traced"]:
        return None
    trace = run["traced"]["trace"]
    found = split(trace, xtrace.traced_devices(run))
    if found is None:
        return None
    run[_KEY] = summary(found)
    print(
        "bench: gaps: " + json.dumps({**(run[_KEY] or {}), "log_tie": log_tie(trace)}),
        flush=True,
    )
    return run[_KEY]
