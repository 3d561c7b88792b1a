"""From a profiler trace to intervals: the reduction every traced metric and
the ``breakdown`` are computed with.

A trace is read once into plain lists (``load_xplane``; the same structure
round-trips through JSON, which is how the recorded trace under ``tests/`` is
kept), and everything below is arithmetic on ``[start_ns, end_ns)`` intervals.

What a v5e trace looks like (jax 0.9, looked at by hand, PR 22): one plane per
chip named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text
(``%fusion.194 = f32[32,128]{...} fusion(...), kind=kOutput, calls=...``); a
``while`` or a ``conditional`` is an event that contains its body's events, so
busy time is the union of everything BUT those (``leaves``). The line ``XLA Modules`` holds
one event per program execution. ``/host:CPU`` holds the host threads, with
the Python frames (``$api.py:1822 train_epoch``) and the runtime's own spans
(``PjitFunction(epoch_core)``, ``np.asarray(jax.Array)``) on the same clock to
within about a millisecond.
"""

import gzip
import json
import re
import statistics
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"

# HLO spellings of communication (with their async -start/-done halves); the
# list is observability/trace_stats.py's, copied so that it cannot move.
COMM_PREFIXES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
    "send",
    "recv",
)


def short_name(text):
    """``%fusion.194 = f32[...] fusion(...)`` -> ``fusion.194``."""
    return text.split(" = ", 1)[0].lstrip("%")


def hlo_kind(text):
    """The fusion kind XLA prints in the instruction (``kOutput`` is a fusion
    around a matrix multiplication or convolution), or ``""``."""
    m = re.search(r"\bkind=(k\w+)", text)
    return m.group(1) if m else ""


def op_family(name):
    """``convolution_add_fusion.27`` -> ``convolution_add_fusion``: the name
    without the numbering that tells one instance from the next."""
    return re.sub(r"(\.clone|\.\d+)+$", "", name)


def is_comm(name):
    return name.lower().startswith(COMM_PREFIXES)


# -- reading ---------------------------------------------------------------


def load_xplane(path):
    """``*.xplane.pb`` -> ``{"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, dur_ns, kind], ...]}]}]}``. Device op names
    are cut to their short form; ``kind`` is the fusion kind or ``""``."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        on_device = bool(_DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if on_device and line.name != MODULES_LINE:
                    name, kind = short_name(e.name), hlo_kind(e.name)
                else:
                    name, kind = e.name, ""
                events.append([name, float(e.start_ns), float(e.duration_ns), kind])
            events.sort(key=lambda ev: (ev[1], -ev[2]))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def newest_xplane(trace_dir):
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def save_json(trace, path):
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_json(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(trace):
    """The chips' planes, in chip order."""
    planes = [p for p in trace["planes"] if _DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def line_events(plane, line_name):
    return [
        ev
        for line in plane["lines"]
        if line["name"] == line_name
        for ev in line["events"]
    ]


# -- interval arithmetic ---------------------------------------------------


def spans(events):
    return [(ev[1], ev[1] + ev[2]) for ev in events]


def union(intervals):
    """Merged, sorted, disjoint intervals covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(merged):
    return sum(end - start for start, end in merged)


def subtract(merged, holes):
    """The part of ``merged`` no interval of ``holes`` covers (both already
    merged): ``|union(comm) - union(compute)|`` is exposed communication."""
    out, j = [], 0
    for start, end in merged:
        cursor = start
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi)`` between the busy ones."""
    return subtract([(lo, hi)], merged)


CONTAINERS = ("while", "conditional", "call")


def leaves(events):
    """The events that are work: everything but the control flow that merely
    spans its body (``while``, ``conditional``, ``call``). Counting a loop as
    busy would hide every gap inside it. Told by the instruction's name and
    not by containment: a long DMA ``copy`` runs beside the core's next
    operations and contains them in time without being their parent."""
    return [ev for ev in events if not op_family(ev[0]).startswith(CONTAINERS)]


# -- one device, reduced ---------------------------------------------------


def main_module(devices):
    """The program the loop runs: the module name with the most device time
    (``devices``: anything with the chips' ``modules`` events)."""
    seconds = {}
    for dev in devices:
        for ev in dev["modules"]:
            seconds[ev[0]] = seconds.get(ev[0], 0.0) + ev[2]
    return max(seconds, key=seconds.get) if seconds else None


def named_from(modules, main):
    """Where the part of a chip's trace that names the operations correctly
    begins: at the start of the first execution labelled as the main module
    (``None``: the chip has no such execution). On a chip that runs a second
    program between epochs (chip 0 of a mesh: ``jit__multi_slice``) the
    execution already under way when the trace starts carries that program's
    label and every one of its operations is named ``region.<n>``: no
    operation of the program's, and nothing to count a step's work by."""
    starts = [ev[1] for ev in modules if ev[0] == main]
    return min(starts) if starts else None


def reduce_device(plane, main=None):
    """Everything the readers need from one chip's plane, or ``None`` when no
    operation ran on it in the trace. The window is the span of the chip's own
    events: its clock and the host's differ by about a millisecond, and the
    chip is inside a program at both edges of a window cut out of a training
    loop, so the span neither hides nor invents idle time. With ``main`` (the
    trace's main module, as ``reduce_trace`` passes it) the window begins
    where ``named_from`` says, the events before it are dropped, and
    ``mislabelled_ns`` is the device time of the operations that went."""
    ops = line_events(plane, OPS_LINE)
    if not ops:
        return None
    modules = line_events(plane, MODULES_LINE)
    lo = min(ev[1] for ev in ops)
    hi = max(ev[1] + ev[2] for ev in ops)
    mislabelled = 0.0
    if main is not None:
        first = named_from(modules, main)
        if first is None:
            return None
        # an execution may be labelled a little before its first operation
        # starts: the window still opens no earlier than the chip's events
        lo = max(lo, first)
        mislabelled = sum(ev[2] for ev in ops if ev[1] < first)
        ops = [ev for ev in ops if ev[1] >= first]
        modules = [ev for ev in modules if ev[1] >= first]
        if not ops:
            return None
    leaf = leaves(ops)
    busy = union(spans(leaf))
    comm = union(spans([ev for ev in leaf if is_comm(ev[0])]))
    compute = union(spans([ev for ev in leaf if not is_comm(ev[0])]))
    return {
        "name": plane["name"],
        "window": (lo, hi),
        "mislabelled_ns": mislabelled,
        "ops": ops,
        "leaf": leaf,
        "busy": busy,
        "comm": comm,
        "compute": compute,
        "exposed_comm": subtract(comm, compute),
        "modules": modules,
    }


def reduce_trace(trace):
    """The chips of the trace, each reduced over the part of its span that
    the trace names correctly (``named_from``)."""
    planes = device_planes(trace)
    main = main_module(
        [{"modules": line_events(plane, MODULES_LINE)} for plane in planes]
    )
    reduced = [reduce_device(plane, main) for plane in planes]
    return [dev for dev in reduced if dev is not None]


# -- the breakdown ---------------------------------------------------------


def top_device_ops(devices, n=10):
    """``[[family, seconds], ...]``: device time by op family, averaged over
    the chips, largest first."""
    seconds = {}
    for dev in devices:
        for ev in dev["leaf"]:
            family = op_family(ev[0])
            seconds[family] = seconds.get(family, 0.0) + ev[2]
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / len(devices)] for name, ns in ranked]


def _host_lines(trace):
    for plane in trace["planes"]:
        if plane["name"] == HOST_PLANE:
            return [line["events"] for line in plane["lines"] if line["events"]]
    return []


def _innermost_host_span(host_lines, at_ns):
    """What the host was doing at ``at_ns``: the shortest span covering it on
    the thread that runs the training loop (the one whose covering spans are
    the most numerous: it is inside ``train_epoch`` all the time)."""
    best = None
    for events in host_lines:
        covering = [ev for ev in events if ev[1] <= at_ns < ev[1] + ev[2]]
        if covering and (best is None or len(covering) > len(best)):
            best = covering
    if not best:
        return "host: no span"
    return min(best, key=lambda ev: ev[2])[0]


def top_idle_gaps(trace, devices, n=10):
    """``[[what, seconds], ...]``: the idle time of one chip's window, summed
    by what covers it: the chip whose window is longest (a chip whose
    mislabelled head was dropped may hold no epoch boundary, and so none of
    the host's gaps), of several the first. A gap inside a program execution
    is the device's own (``in <module>: between ops``); one between two
    executions is the host's, named by the innermost host span at its
    middle."""
    if not devices:
        return []
    dev = min(
        devices, key=lambda d: (-window_s(d), int(d["name"].rsplit(":", 1)[1]))
    )
    host_lines = _host_lines(trace)
    inside = union(spans(dev["modules"]))
    seconds = {}
    for start, end in gaps(dev["busy"], *dev["window"]):
        middle = (start + end) / 2
        owner = next(
            (ev for ev in dev["modules"] if ev[1] <= middle < ev[1] + ev[2]), None
        )
        if owner is not None and subtract([(start, end)], inside) == []:
            what = f"in {owner[0].split('(')[0]}: between ops"
        else:
            what = f"host: {_innermost_host_span(host_lines, middle)}"
        seconds[what] = seconds.get(what, 0.0) + (end - start)
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return [[what, ns / 1e9] for what, ns in ranked]


# -- helpers the readers share ----------------------------------------------


def traced_devices(run):
    """The reduced chips of the run's trace; empty without ``--trace 1`` and
    where no operation ran on a device (a CPU rehearsal)."""
    return run["traced"]["devices"] if run["traced"] else []


def window_s(dev):
    return (dev["window"][1] - dev["window"][0]) / 1e9


def steps_in_window(run, dev):
    """Optimizer steps the chip completed inside its traced window, from the
    host's clock around the epochs that ran while the trace was on: the
    window may begin and end anywhere inside an epoch program, and a step
    leaves no marker of its own in the trace."""
    epoch_s = statistics.median(run["traced"]["epoch_s"])
    return window_s(dev) * run["session"]["steps_per_epoch"] / epoch_s


def cut(trace, lo, hi):
    """The same trace with only what lies in ``[lo, hi)``, events that reach
    over an edge clipped to it: how the recorded traces under ``tests/`` were
    cut out of whole ones."""

    def clipped(events):
        return [
            [ev[0], max(ev[1], lo), min(ev[1] + ev[2], hi) - max(ev[1], lo), ev[3]]
            for ev in events
            if ev[1] < hi and ev[1] + ev[2] > lo
        ]

    planes = []
    for plane in trace["planes"]:
        lines = [
            {"name": line["name"], "events": clipped(line["events"])}
            for line in plane["lines"]
        ]
        lines = [line for line in lines if line["events"]]
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}
