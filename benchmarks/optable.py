"""The class table of a traced run: device time by what the program says the
work is, not by what the compiler called the instruction.

``table(run)`` joins the events of every chip's ``XLA Ops`` line that lie
inside an execution of the main module (``xtrace.main_module``; instruction
names repeat between programs, and chip 0 also runs ``jit__multi_slice``)
with the op index of that program (``shallowspeed_tpu.observability.scopes.
program_index(<module name before "(">)``: the session registered the program
at its first dispatch, and the index is built from the compiled text in this
process, after the session is gone). An index entry gives each event a class
(``linear``, ``stash``, ``mailbox``, ``grad_acc``, ``batch``, ``update``,
``sync``, ``relay``, ``pointwise``, ``control``, ``unattributed``) or says it
is a container (``while``, ``conditional``, a branch computation's own
event), which is dropped: its instructions are events of their own. Events
outside the main module's executions are ``other_program``; events the index
does not know are ``unresolved``.

It returns ``None`` where there is nothing to read, and every reader built
on it (``layer_metrics/scope_coverage_share.py`` and the ``*_ms_per_step`` /
``control_ops_per_step`` files) then returns ``None`` too: without a device
plane (a CPU rehearsal), without ``observability.scopes`` (a program older
than the scopes), and where the index holds no scope at all (executables
loaded from a compile cache another tree filled: the cache key ignores
scopes). Otherwise::

    {"module": "jit_epoch_core", "chips": [{
        "steps": optimizer steps in the chip's window, which begins where
            the trace names the operations correctly
            (``xtrace.reduce_device``); every per-step number below is over
            these,
        "mislabelled_ms": device time of the operations dropped before it,
        "classes": {cls: {"ms_per_step": .., "ops_per_step": ..}},
        "resolved": share of non-container time whose name is in the index,
        "coverage": share of it in a class other than unattributed/unresolved,
        "kOutput_ms_per_step": time in XLA's kind=kOutput fusions (what
            matmul_roofline divides by), beside classes["linear"],
        "moved_by_class": the time in copy / copy-start / copy-done /
            slice-start / slice-done per class, "moved_named" the share of it
            in a class but unattributed, and "moved" the twelve largest
            things moved, a carry leaf or an argument:
            [[via, class, ms_per_step, MB_per_step], ...],
        "idle": stage_idle_share / device_idle_share / comm_exposed_share
            with containers dropped by the index and, beside each, with
            containers told by name as xtrace.leaves does (what the readers
            publish)}],
     "containers": events dropped, by family: [count, summed ms],
     "unattributed": the ten largest unattributed/unresolved/mixed
        instructions: [name, opcode, type, class, ms_per_step]}

The table is computed once per run, kept on ``run``, and printed once as
``bench: scopes: {...}``, so a traced run's log holds the whole attribution.
"""

import bisect
import json
import statistics

import xtrace

MOVES = (
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "async-start", "async-done",
)
SYNC = ("all-reduce", "reduce-scatter", "all-gather")
UNNAMED = ("unattributed", "unresolved")
_KEY = "_optable"


def _index_for(module):
    try:
        from shallowspeed_tpu.observability.scopes import program_index
    except ImportError:  # a program from before the scopes
        return None, "the program has no observability.scopes"
    index = program_index(module)
    if index is None:
        return None, f"no program registered as {module}"
    if not any(e["scope"] for e in index.values()):
        return None, (
            f"the index of {module} holds no scope: executables loaded from "
            "a compile cache that another tree filled"
        )
    return index, None


def _inside(starts, ends, at):
    i = bisect.bisect_right(starts, at) - 1
    return i >= 0 and at < ends[i]


def _shares(dev, leaf, module):
    """The three idle shares of the existing readers over ``leaf`` events,
    in the chip's window."""
    lo, hi = dev["window"]
    compute = xtrace.union(
        xtrace.spans([ev for ev in leaf if not xtrace.is_comm(ev[0])])
    )
    envelope = xtrace.union(
        xtrace.spans([ev for ev in dev["modules"] if ev[0] == module])
    )
    sync = xtrace.union(
        xtrace.spans([ev for ev in leaf if ev[0].lower().startswith(SYNC)])
    )
    busy = xtrace.union(xtrace.spans(leaf))
    return {
        "stage_idle_share": 100.0
        * xtrace.total(xtrace.subtract(envelope, compute))
        / max(xtrace.total(envelope), 1.0),
        "device_idle_share": 100.0 * (1.0 - xtrace.total(busy) / max(hi - lo, 1.0)),
        "comm_exposed_share": 100.0
        * xtrace.total(xtrace.subtract(sync, compute))
        / max(hi - lo, 1.0),
    }


def _chip(run, dev, module, index, containers, loose):
    runs = sorted(
        (ev[1], ev[1] + ev[2]) for ev in dev["modules"] if ev[0] == module
    )
    starts, ends = [r[0] for r in runs], [r[1] for r in runs]
    steps = xtrace.steps_in_window(run, dev)
    classes, moved, leaf = {}, {}, []
    known = total = k_output = 0.0
    for ev in dev["ops"]:
        name, at, dur, kind = ev
        ours = _inside(starts, ends, at)
        entry = index.get(name) if ours else None
        if entry is None:
            if xtrace.op_family(name).startswith(xtrace.CONTAINERS):
                continue
            cls = "unresolved" if ours else "other_program"
        elif entry["container"]:
            family = containers.setdefault(xtrace.op_family(name), [0, 0.0])
            family[0] += 1
            family[1] += dur / 1e6
            continue
        else:
            cls = entry["cls"]
            known += dur
            if kind == "kOutput":
                k_output += dur
            if entry["opcode"] in MOVES:
                via = entry.get("via", entry["scope"] or "?")
                slot = moved.setdefault((via, cls), [0.0, 0.0])
                slot[0] += dur
                if not entry["opcode"].endswith("-done"):  # once per transfer
                    slot[1] += entry["bytes"]
        leaf.append(ev)
        total += dur
        agg = classes.setdefault(cls, [0.0, 0])
        agg[0] += dur
        agg[1] += 1
        if cls in UNNAMED or (entry and "mixed" in entry):
            label = cls if cls in UNNAMED else "mixed " + "+".join(entry["mixed"])
            what = (entry["opcode"], entry["type"]) if entry else ("?", "?")
            slot = loose.setdefault(name, [*what, label, 0.0])
            slot[3] += dur / 1e6 / steps
    unnamed = sum(classes.get(c, [0.0])[0] for c in UNNAMED)
    other = classes.get("other_program", [0.0])[0]
    moved_by_class = {}
    for (_, cls), (ns, _) in moved.items():
        moved_by_class[cls] = moved_by_class.get(cls, 0.0) + ns / 1e6 / steps
    by_index = _shares(dev, leaf, module)
    by_name = _shares(dev, dev["leaf"], module)
    return {
        "name": dev["name"],
        "steps": steps,
        "mislabelled_ms": dev["mislabelled_ns"] / 1e6,
        "classes": {
            cls: {"ms_per_step": ns / 1e6 / steps, "ops_per_step": n / steps}
            for cls, (ns, n) in sorted(classes.items())
        },
        "resolved": 100.0 * known / max(total - other, 1.0),
        "coverage": 100.0 * (total - unnamed) / max(total, 1.0),
        "kOutput_ms_per_step": k_output / 1e6 / steps,
        "moved_by_class": dict(sorted(moved_by_class.items())),
        "moved_named": 100.0
        * (1.0 - moved_by_class.get("unattributed", 0.0)
           / max(sum(moved_by_class.values()), 1e-9)),
        "moved": [
            [via, cls, ns / 1e6 / steps, nbytes / 1e6 / steps]
            for (via, cls), (ns, nbytes) in sorted(
                moved.items(), key=lambda kv: -kv[1][0]
            )[:12]
        ],
        "idle": {k: [by_index[k], by_name[k]] for k in by_index},
    }


def table(run):
    """The class table of the run's trace (see the module docstring), or
    ``None``; computed and printed once."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    devices = xtrace.traced_devices(run)
    main = xtrace.main_module(devices) if devices else None
    if main is None:
        return None
    module = main.split("(")[0]
    index, why_not = _index_for(module)
    if index is None:
        print(f"bench: scopes: none: {why_not}", flush=True)
        return None
    containers, loose = {}, {}
    chips = [_chip(run, dev, main, index, containers, loose) for dev in devices]
    found = {
        "module": module,
        "chips": chips,
        "containers": {
            family: [n, ms] for family, (n, ms) in sorted(containers.items())
        },
        # unnamed instructions first, then mixed fusions, largest first
        "unattributed": [
            [name, opcode, type_, label, ms / len(chips)]
            for name, (opcode, type_, label, ms) in sorted(
                loose.items(), key=lambda kv: (kv[1][2] not in UNNAMED, -kv[1][3])
            )[:10]
        ],
    }
    print(f"bench: scopes: {json.dumps(found)}", flush=True)
    run[_KEY] = found
    return found


def class_value(run, cls, field="ms_per_step"):
    """``field`` of one class on the chip where it is largest; ``None``
    where there is no table, 0.0 where the class never ran."""
    found = table(run)
    if found is None:
        return None
    return max(
        chip["classes"].get(cls, {}).get(field, 0.0) for chip in found["chips"]
    )


def host_span_ms(run, name):
    """Median duration (ms) of the host spans called ``name`` in the trace
    (``jax.profiler.TraceAnnotation``, on the profiler's clock), or ``None``
    where the program wrote none."""
    if not run["traced"]:
        return None
    durations = [
        ev[2] / 1e6
        for plane in run["traced"]["trace"]["planes"]
        if plane["name"] == xtrace.HOST_PLANE
        for line in plane["lines"]
        for ev in line["events"]
        if ev[0] == name
    ]
    return statistics.median(durations) if durations else None
