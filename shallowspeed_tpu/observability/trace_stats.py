"""Summarize a jax.profiler chrome-trace: the roofline evidence extractor.

Parses the ``*.trace.json.gz`` a capture leaves in artifacts/tpu_trace*/ and
reports the numbers docs/performance.md's roofline section rests on — device
op count, wall span, per-op issue rate, functional-unit overlap, and the op
breakdown — so the "latency-roofline" verdict is recomputable from the
committed artifact instead of hand-derived prose.

Importable (promoted from scripts/ — ``scripts/trace_stats.py`` remains as a
thin CLI shim):

    from shallowspeed_tpu.observability import trace_stats
    stats = trace_stats.summarize("artifacts/.../xyz.trace.json.gz")

CLI (same surface as before):

    python scripts/trace_stats.py artifacts/tpu_trace
    python scripts/trace_stats.py path/to/xyz.trace.json.gz --json
"""

import argparse
import collections
import gzip
import json
import sys
from pathlib import Path

# Device-op name prefixes that are COMMUNICATION, not compute — the HLO
# collective spellings (incl. their async -start/-done halves) plus the
# point-to-point ops. Everything else on the device timeline counts as
# compute, so ``comm_fraction`` is directly comparable against the
# analytical comms model's bound verdict (program_audit.expected_comms).
COMM_OP_PREFIXES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
    "send",
    "recv",
)


def is_comm_op(name):
    """True when a device-op name is a communication op (collective or
    point-to-point), by HLO-name prefix."""
    n = str(name).lower()
    return n.startswith(COMM_OP_PREFIXES)


def _union_us(events):
    """Total covered length of the [ts, ts+dur) intervals of ``events``."""
    covered, end = 0.0, None
    for s, e in sorted((ev["ts"], ev["ts"] + ev.get("dur", 0)) for ev in events):
        if end is None or s > end:
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    return covered


def find_traces(path):
    """A file path as-is, or every ``*.trace.json.gz`` under a directory."""
    p = Path(path)
    if p.is_file():
        return [p]
    return sorted(p.rglob("*.trace.json.gz"))


def summarize(trace_path):
    """Device-op statistics for one chrome trace (dict, JSON-able).

    Keys: ``device_ops``, ``span_ms`` (first-op-start to last-op-end wall on
    the device timeline), ``busy_ms`` (summed op durations), ``ns_per_op_issued``
    (serial issue rate — the latency-roofline number), ``unit_overlap``
    (busy/span; >1 means functional units overlap, the op stream rather than
    FLOPs is the bottleneck when this is high while MXU% is low),
    ``top_ops`` (count per op-name prefix), and the comm/compute split —
    ``comm_ops`` / ``comm_ms`` / ``compute_ms`` / ``comm_fraction`` (comm
    busy time over total busy time, classified by ``is_comm_op``) — so the
    MEASURED communication share of a capture is directly comparable
    against the analytical comms model's verdict
    (program_audit.expected_comms). From the same split come the overlap
    numbers:
    ``exposed_comm_ms`` — timeline time where communication ran with NO
    compute op in flight on the same device (a per-pid interval-union
    sweep: ``|union(comm) \\ union(compute)|`` summed over device pids —
    busy-time arithmetic would be fooled by multi-device traces and by
    functional-unit overlap, where summed busy time exceeds the span) —
    and ``overlap_efficiency`` — the hidden-comm share
    ``1 - exposed_comm / comm_union`` (None when the trace has no comm
    ops; ``comm_union_ms`` — the comm-interval union — is the
    denominator rather than summed comm busy time, so collectives that
    merely overlap EACH OTHER do not count as hidden behind compute):
    1.0 means every communication microsecond rode behind compute, 0.0
    means the sync was fully serial. ``{"device_ops": 0}`` when the
    trace holds no device ops.
    """
    with gzip.open(trace_path) as f:
        tr = json.load(f)
    events = tr.get("traceEvents", [])
    # device pid: the process named like a device (e.g. '/device:TPU:0')
    dev_pids = {
        e["pid"]
        for e in events
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and "/device:" in str(e.get("args", {}).get("name", ""))
    }
    # thread names, to exclude the whole-module envelope event from op stats
    module_tids = {
        (e["pid"], e["tid"])
        for e in events
        if e.get("ph") == "M"
        and e.get("name") == "thread_name"
        and "Modules" in str(e.get("args", {}).get("name", ""))
    }
    ops = [
        e
        for e in events
        if e.get("ph") == "X"
        and e.get("pid") in dev_pids
        and (e["pid"], e.get("tid")) not in module_tids
    ]
    if not ops:
        return {"trace": str(trace_path), "device_ops": 0}
    t0 = min(e["ts"] for e in ops)
    t1 = max(e["ts"] + e.get("dur", 0) for e in ops)
    span_us = t1 - t0
    busy_us = sum(e.get("dur", 0) for e in ops)
    comm = [e for e in ops if is_comm_op(e["name"])]
    comm_us = sum(e.get("dur", 0) for e in comm)
    kinds = collections.Counter(e["name"].split(".")[0] for e in ops)
    # exposed comm per DEVICE pid: comm-interval time not covered by any
    # compute interval on the same device — |union(all) - union(compute)|
    # (compute on another chip cannot hide this chip's collective, and
    # the interval union is immune to busy-sum > span unit overlap). The
    # efficiency denominator is the comm interval UNION, not summed busy
    # time: two collectives overlapping each other hide nothing behind
    # compute, and must not inflate the hidden share.
    exposed_us = 0.0
    comm_union_us = 0.0
    for pid in {e["pid"] for e in comm}:
        dev = [e for e in ops if e["pid"] == pid]
        compute_cover = _union_us(e for e in dev if not is_comm_op(e["name"]))
        exposed_us += _union_us(dev) - compute_cover
        comm_union_us += _union_us(e for e in dev if is_comm_op(e["name"]))
    return {
        "trace": str(trace_path),
        "device_ops": len(ops),
        "span_ms": round(span_us / 1e3, 3),
        "busy_ms": round(busy_us / 1e3, 3),
        # serial issue rate: ops retired per wall time on the device —
        # the latency-roofline number (238 ns/op measured round 2)
        "ns_per_op_issued": round(1e3 * span_us / len(ops), 1),
        # >1 means functional units overlap; the op stream, not FLOPs,
        # is the bottleneck when this is high while MXU% is low
        "unit_overlap": round(busy_us / span_us, 2),
        # the measured comm/compute split (busy-time attribution) — the
        # observed counterpart of the comms model's bound verdict
        "comm_ops": len(comm),
        "comm_ms": round(comm_us / 1e3, 3),
        "compute_ms": round((busy_us - comm_us) / 1e3, 3),
        "comm_fraction": round(comm_us / busy_us, 4) if busy_us else 0.0,
        # the measured overlap story (see the docstring): how much of the
        # comm timeline was exposed vs hidden behind compute
        "exposed_comm_ms": round(exposed_us / 1e3, 3),
        "comm_union_ms": round(comm_union_us / 1e3, 3),
        "overlap_efficiency": (
            round(1.0 - exposed_us / comm_union_us, 4)
            if comm_union_us
            else None
        ),
        "top_ops": dict(kinds.most_common(8)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", help="trace dir or a *.trace.json.gz file")
    ap.add_argument("--json", action="store_true", help="one JSON line per trace")
    args = ap.parse_args(argv)
    traces = find_traces(args.path)
    if not traces:
        print(f"no *.trace.json.gz under {args.path}", file=sys.stderr)
        sys.exit(1)
    for t in traces:
        s = summarize(t)
        if args.json:
            from shallowspeed_tpu.observability.metrics import json_safe

            print(json.dumps(json_safe(s), allow_nan=False))
        else:
            print(f"{s['trace']}:")
            for k, v in s.items():
                if k != "trace":
                    print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
