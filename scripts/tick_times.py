"""Per tick of the executor's step, from a trace the benchmark kept: how long
the tick's branch ran on each chip, how many relays the tick issued, how long
the chip then sat in their ``collective-permute-start`` (waiting for the
partner stage) and ``-done`` (the transfer), and how long the whole tick
lasted.

    python3 benchmarks/run.py --workload mlp-deep.dp2pp2-b65536 --seed 1 \\
        --seconds 10 --trace 1 --keep-trace chiprun_out/trace.json.gz   # on the chip
    python3 scripts/tick_times.py chiprun_out/trace.json.gz             # anywhere

A tick is one event of the op-code ``switch``'s ``conditional`` inside a
``while`` event that holds as many of them as the step has ticks (10 for
pipedream, M 4, pp 2). The relays have conditionals of their own (scope
``relay``: one per direction, taken in the ticks in which the tick table has
a payload due); a kept trace carries instruction names and no scopes, so the
switch is told from them as the conditional that takes longest, and a relay
counts as issued where a ``collective-permute-start`` ran before the next
tick's switch. The figures are medians over the steps the trace holds whole.
PERF.md §5 reads the four-chip cell with it.
"""

import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmarks"))
import xtrace  # noqa: E402


def tick_rows(plane, ticks_per_step):
    """``[tick][step] -> (branch, relays issued, cp_start, cp_done, tick)``,
    times in ms."""
    events = xtrace.line_events(plane, xtrace.OPS_LINE)
    named = lambda prefix: [e for e in events if e[0].startswith(prefix)]  # noqa: E731
    conds, relays = named("conditional"), named("collective-permute")
    rows, seen = [[] for _ in range(ticks_per_step)], set()
    # innermost first: a step loop that runs one step holds the same ticks
    for _, w_start, w_dur, _ in sorted(named("while"), key=lambda e: e[2]):
        w_end = w_start + w_dur
        by_name = {}
        for c in conds:
            if w_start <= c[1] and c[1] + c[2] <= w_end:
                by_name.setdefault(c[0], []).append(c)
        once_a_tick = [evs for evs in by_name.values() if len(evs) == ticks_per_step]
        if not once_a_tick:
            continue  # the step loop, or a tick loop the trace cut
        switch = max(once_a_tick, key=lambda evs: sum(e[2] for e in evs))
        if switch[0][1] in seen:
            continue
        seen.add(switch[0][1])
        for i, (_, start, dur, _) in enumerate(switch):
            end = switch[i + 1][1] if i + 1 < ticks_per_step else w_end
            mine = [e for e in relays if start + dur <= e[1] < end]
            half = lambda word: [e[2] / 1e6 for e in mine if word in e[0]]  # noqa: E731
            rows[i].append(
                (dur / 1e6, len(half("start")), sum(half("start")),
                 sum(half("done")), (end - start) / 1e6)
            )
    return rows


def main(argv):
    ticks_per_step = int(argv[2]) if len(argv) > 2 else 10
    for plane in xtrace.device_planes(xtrace.load_json(argv[1])):
        rows = tick_rows(plane, ticks_per_step)
        print(f"{plane['name']}: {len(rows[0])} whole steps")
        totals = [0.0] * 5
        for i, row in enumerate(rows):
            if not row:
                continue
            med = [statistics.median(r[j] for r in row) for j in range(5)]
            totals = [t + m for t, m in zip(totals, med)]
            print(
                f"  tick {i}: branch {med[0]:7.2f}  relays {med[1]:2.0f}  "
                f"cp-start {med[2]:6.2f}  cp-done {med[3]:6.2f}  tick {med[4]:7.2f} ms"
            )
        print(
            f"  sum:    branch {totals[0]:7.2f}  relays {totals[1]:2.0f}  "
            f"cp-start {totals[2]:6.2f}  cp-done {totals[3]:6.2f}  tick {totals[4]:7.2f} ms"
        )


if __name__ == "__main__":
    main(sys.argv)
