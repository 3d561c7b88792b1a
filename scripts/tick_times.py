"""Per tick of the lockstep executor's step, from a trace the benchmark kept:
how long the tick's branch ran on each chip, how long the chip then sat in
its relays' ``collective-permute-start`` (waiting for the partner stage) and
``-done`` (the transfer), and how long the whole tick lasted.

    python3 benchmarks/run.py --workload mlp-deep.dp2pp2-b65536 --seed 1 \\
        --seconds 10 --trace 1 --keep-trace chiprun_out/trace.json.gz   # on the chip
    python3 scripts/tick_times.py chiprun_out/trace.json.gz             # anywhere

A tick is one ``conditional`` event inside a ``while`` event that holds as
many of them as the step has ticks (10 for pipedream, M 4, pp 2); the
figures are medians over the steps the trace holds whole. PERF.md §5 reads
the four-chip cell with it.
"""

import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmarks"))
import xtrace  # noqa: E402


def tick_rows(plane, ticks_per_step):
    """``[tick][step] -> (branch, cp_start, cp_done, tick)`` in ms."""
    events = xtrace.line_events(plane, xtrace.OPS_LINE)
    named = lambda prefix: [e for e in events if e[0].startswith(prefix)]  # noqa: E731
    conds, relays = named("conditional"), named("collective-permute")
    rows, seen = [[] for _ in range(ticks_per_step)], set()
    # innermost first: a step loop that runs one step holds the same ticks
    for _, w_start, w_dur, _ in sorted(named("while"), key=lambda e: e[2]):
        w_end = w_start + w_dur
        inside = [c for c in conds if w_start <= c[1] and c[1] + c[2] <= w_end]
        if len(inside) != ticks_per_step or inside[0][1] in seen:
            continue  # the step loop, or a tick loop the trace cut
        seen.add(inside[0][1])
        for i, (_, start, dur, _) in enumerate(inside):
            end = inside[i + 1][1] if i + 1 < ticks_per_step else w_end
            mine = [e for e in relays if start + dur <= e[1] < end]
            half = lambda word: sum(e[2] for e in mine if word in e[0]) / 1e6  # noqa: E731
            rows[i].append((dur / 1e6, half("start"), half("done"), (end - start) / 1e6))
    return rows


def main(argv):
    ticks_per_step = int(argv[2]) if len(argv) > 2 else 10
    for plane in xtrace.device_planes(xtrace.load_json(argv[1])):
        rows = tick_rows(plane, ticks_per_step)
        print(f"{plane['name']}: {len(rows[0])} whole steps")
        totals = [0.0] * 4
        for i, row in enumerate(rows):
            if not row:
                continue
            med = [statistics.median(r[j] for r in row) for j in range(4)]
            totals = [t + m for t, m in zip(totals, med)]
            print(
                f"  tick {i}: branch {med[0]:7.2f}  cp-start {med[1]:6.2f}  "
                f"cp-done {med[2]:6.2f}  tick {med[3]:7.2f} ms"
            )
        print(
            f"  sum:    branch {totals[0]:7.2f}  cp-start {totals[1]:6.2f}  "
            f"cp-done {totals[2]:6.2f}  tick {totals[3]:7.2f} ms"
        )


if __name__ == "__main__":
    main(sys.argv)
