"""The program's own span log, as the set-up readers see it (PR 37).

``shallowspeed_tpu.observability.spans.log()`` holds one entry per closed host
span and per compile event of the process, ``(path, start, duration, thread,
name, fun_name)`` on ``time.perf_counter_ns()``: the clock ``run.py`` opens
its window on, so "before the window" is a comparison of two numbers. The log
outlives the session and ``jax.clear_caches()``. A set-up reader takes the
entries that CLOSED before ``run["window"]["opened"]``; each returns ``None``
where the program keeps no such log (a program older than PR 37).

The phases of ``TrainingSession.__init__`` are the direct children of its root
span ``session/init``; the three compile readers are lengths of unions
(``xtrace.union``), because a function traced inside another's trace reports
a nested event.
"""

import json

import xtrace

PHASES = (
    "session/data", "session/weights", "session/lower", "session/program",
    "session/resume",
)
ROOT = "session/init"
_KEY = "_hostlog"


def _spans():
    try:
        from shallowspeed_tpu.observability import spans
    except ImportError:
        return None
    return spans if hasattr(spans, "log") else None


def setup_entries(run):
    """The log's entries that closed before the window opened, or ``None``."""
    spans = _spans()
    if spans is None:
        return None
    opened_ns = run["window"]["opened"] * 1e9
    return [e for e in spans.log().entries() if e.start + e.duration <= opened_ns]


def _blocks(entries):
    """Each phase's blocks under the root, in order, in seconds (a phase may
    open more than once: the resident set is oriented after the weights are
    placed)."""
    return {
        name: [e.duration / 1e9 for e in entries if e.path == f"{ROOT}/{name}"]
        for name in PHASES
    }


def init_split(run):
    """``{"root": s, "unnamed": s, "session/data": s, ...}`` in seconds: the
    root span of the session's construction, each phase summed over its
    blocks, and what no phase covers. ``None`` without a log or without a root
    (no session was built under the spans)."""
    entries = setup_entries(run)
    if not entries:
        return None
    root = sum(e.duration for e in entries if e.path == ROOT)
    if not root:
        return None
    found = {name: sum(blocks) for name, blocks in _blocks(entries).items()}
    found["root"] = root / 1e9
    found["unnamed"] = found["root"] - sum(found[name] for name in PHASES)
    return found


def _intervals(entries, *names):
    return xtrace.union(
        (e.start, e.start + e.duration) for e in entries if e.name in names
    )


def compile_split(run):
    """``{"trace_lower": s, "backend": s, "cache_load": s, "all": s}``: the
    lengths of the unions of the compile events before the window: tracing and
    lowering (Python's share), the backend's compiles less the loads from the
    persistent cache inside them, those loads, and everything together (what
    the benchmark's own ``compile_s`` measures from outside)."""
    entries = setup_entries(run)
    if entries is None:
        return None
    loads = _intervals(entries, "compile/cache_load")
    backend = _intervals(entries, "compile/backend")
    return {
        "trace_lower": xtrace.total(
            _intervals(entries, "compile/trace", "compile/lower")
        ) / 1e9,
        "backend": xtrace.total(xtrace.subtract(backend, loads)) / 1e9,
        "cache_load": xtrace.total(loads) / 1e9,
        "all": xtrace.total(
            _intervals(
                entries, "compile/trace", "compile/lower", "compile/backend",
                "compile/cache_load",
            )
        ) / 1e9,
    }


def most_traced(run):
    """``(fun_name, traces)`` of the function the program traced most often
    (the listener's count of ``compile/trace`` events under an open host span:
    the program's own, not the harness's reference), or ``None``."""
    spans = _spans()
    if spans is None or not spans.log().traces:
        return None
    return max(spans.log().traces.items(), key=lambda kv: kv[1])


def report(run):
    """Print the set-up's split once per run (``bench: setup spans: {...}``):
    the phases (with each phase's blocks, the placements inside them and the
    part that was compiling), the compile phases, the most traced functions
    and the largest compile events by function, so that a traced run's log
    holds the attribution and not only the thirteen numbers."""
    if _KEY in run:
        return
    run[_KEY] = True
    entries = setup_entries(run)
    if entries is None:
        return
    spans = _spans()
    compiles = sorted(
        (e for e in entries if e.name.startswith("compile/")),
        key=lambda e: -e.duration,
    )[:8]
    inside = [e for e in entries if e.path.startswith(ROOT + "/")]
    compiling = {
        name: xtrace.total(
            xtrace.union(
                (e.start, e.start + e.duration) for e in inside
                if e.path.startswith(f"{ROOT}/{name}/compile/")
            )
        ) / 1e9
        for name in PHASES
    }
    traces = sorted(spans.log().traces.items(), key=lambda kv: -kv[1])[:5]
    print(
        "bench: setup spans: "
        + json.dumps(
            {
                "init": init_split(run),
                # each phase's blocks in order, the placements inside them,
                # and the part of each phase that was compiling
                "init_blocks": _blocks(inside),
                "init_device_put": [
                    [e.path[len(ROOT) + 1:], e.duration / 1e9]
                    for e in inside if e.name == "device_put"
                ],
                "init_compiling": compiling,
                "compile": compile_split(run),
                "most_traced": traces,
                "largest_compile_events": [
                    [e.name, e.fun_name, e.duration / 1e9, e.path] for e in compiles
                ],
                "log": {"entries": len(entries), "dropped": spans.log().dropped},
            }
        ),
        flush=True,
    )
