"""Smoke tests for the developer tools (pebble renderer, scaling bench CLI)."""

import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_show_schedule_renders_all(capsys):
    scripts_dir = str(ROOT / "scripts")
    sys.path.insert(0, scripts_dir)
    try:
        import show_schedule
    finally:
        sys.path.remove(scripts_dir)
    for name in ("gpipe", "naive", "pipedream", "inference"):
        show_schedule.render(name, 4, 4)
    out = capsys.readouterr().out
    assert "utilization" in out
    assert "F0" in out and "B0" in out
    # GPipe's lowered latency shows up in the header
    assert "gpipe  M=4 S=4: 14 ticks" in out


def test_show_schedule_renders_split_cells(capsys):
    """--backward-split diagrams: b<m> B-input cells at the combined
    backward's ticks, W<m> B-weight cells in the bubbles, and BOTH
    utilization figures in the header."""
    scripts_dir = str(ROOT / "scripts")
    sys.path.insert(0, scripts_dir)
    try:
        import show_schedule
    finally:
        sys.path.remove(scripts_dir)
    show_schedule.render("pipedream", 4, 4, backward_split=True)
    out = capsys.readouterr().out
    assert "b0" in out and "W0" in out and "B0" not in out
    assert "split-bwd" in out
    assert "weighted" in out
    # the README's quoted split diagram header
    assert "15 ticks" in out


def test_weighted_utilization_matches_documented_figures():
    """docs/lowering.md's weighted-bubble table (1F1B M=8: 40% -> 11%
    split; GPipe M=4: 43% -> 33%) must be computable from the lowered
    tick tables — and the weights come from the cost model's single
    source (fwd 1, combined bwd 2, split halves 1)."""
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.observability.costmodel import PIPELINE_OP_COSTS
    from shallowspeed_tpu.parallel.lowering import (
        lower_schedule,
        weighted_makespan,
        weighted_utilization,
    )

    assert PIPELINE_OP_COSTS == {
        "fwd": 1.0, "bwd": 2.0, "bwd_in": 1.0, "bwd_w": 1.0, "recompute": 1.0,
    }
    pd8 = lower_schedule(S.PipeDreamFlushSchedule, 8, 4)
    pd8s = lower_schedule(S.PipeDreamFlushSchedule, 8, 4, backward_split=True)
    assert round((1 - weighted_utilization(pd8)) * 100) == 40
    assert round((1 - weighted_utilization(pd8s)) * 100) == 11
    g4 = lower_schedule(S.GPipeSchedule, 4, 4)
    g4s = lower_schedule(S.GPipeSchedule, 4, 4, backward_split=True)
    assert round((1 - weighted_utilization(g4)) * 100) == 43
    assert round((1 - weighted_utilization(g4s)) * 100) == 33
    # the lockstep tick model: GPipe M=4 P=4 = 7 fwd-phase ticks (max
    # weight 1) + 7 bwd-phase ticks (max weight 2) = 21 forward-units
    assert weighted_makespan(g4) == 21.0
    assert weighted_makespan(g4s) == float(g4s.num_ticks)  # all ticks weight 1


def test_utilization_matches_documented_bubble_figures():
    """The docs' bubble-shrink claims (docs/lowering.md: flat 1F1B 57% vs
    interleaved V=2 73% at P=4, M=4; GPipe M/(M+S-1) per phase) must be
    computable from the lowered tick tables, not hand-written prose."""
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.parallel.lowering import lower_schedule, utilization

    flat = lower_schedule(S.PipeDreamFlushSchedule, 4, 4)
    inter = lower_schedule(S.InterleavedSchedule, 4, 4, virtual=2)
    gpipe = lower_schedule(S.GPipeSchedule, 4, 4)
    # exact active-cell counts: every device computes V*M forwards + V*M
    # backwards, so active = P * 2*V*M cells out of num_ticks * P
    assert utilization(flat) == (2 * 4 * 4) / (flat.num_ticks * 4)
    assert utilization(inter) == (2 * 2 * 4 * 4) / (inter.num_ticks * 4)
    # the documented headline figures
    assert round(utilization(flat) * 100) == 57
    assert round(utilization(gpipe) * 100) == 57
    assert round(utilization(inter) * 100) == 73
    assert utilization(inter) > utilization(flat)  # the V-fold fill shrink
    # inference relay: M/(M+S-1) utilization exactly
    inf = lower_schedule(S.InferenceSchedule, 4, 4)
    assert abs(utilization(inf) - 4 / (4 + 4 - 1)) < 1e-12


def test_trace_stats_reproduces_roofline_numbers():
    """docs/performance.md's latency-roofline evidence (63,238 device ops in
    ~15 ms = ~238 ns/op issued, ~2.9x unit overlap) must be recomputable
    from the committed chip trace by scripts/trace_stats.py."""
    scripts_dir = str(ROOT / "scripts")
    sys.path.insert(0, scripts_dir)
    try:
        import trace_stats
    finally:
        sys.path.remove(scripts_dir)
    # pinned to the specific committed round-2 trace file (not a directory
    # glob): new captures write per-round dirs (artifacts/tpu_trace_r<N>),
    # so future chip runs can never silently re-target this assertion
    frozen = (
        ROOT / "artifacts" / "tpu_trace" / "plugins" / "profile"
        / "2026_07_29_10_39_10" / "runsc.trace.json.gz"
    )
    assert frozen.is_file(), "committed chip trace missing"
    s = trace_stats.summarize(frozen)
    assert s["device_ops"] == 63238
    assert 230 <= s["ns_per_op_issued"] <= 250
    assert 2.5 <= s["unit_overlap"] <= 3.5
    # matmuls present and dominated in count by small fusions — the
    # op-stream (not FLOPs) picture the roofline section describes
    assert s["top_ops"].get("convolution_add_fusion", 0) > 10000
    # a sequential chip trace has no collectives: the overlap split must
    # say so (no comm -> no efficiency claim), not fabricate a number
    assert s["comm_ops"] == 0 and s["comm_ms"] == 0.0
    assert s["exposed_comm_ms"] == 0.0
    assert s["overlap_efficiency"] is None


def test_train_cli_help():
    r = subprocess.run(
        [sys.executable, str(ROOT / "train.py"), "--help"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0
    for flag in (
        "--dp", "--pp", "--schedule", "--checkpoint", "--resume",
        "--precision", "--backward-split",
    ):
        assert flag in r.stdout


def _import_bench():
    sys.path.insert(0, str(ROOT))
    try:
        import bench
    finally:
        sys.path.remove(str(ROOT))
    return bench


def test_slope_timing_per_leg_minima(monkeypatch):
    """The slope estimator must take per-leg minima BEFORE differencing, so a
    disturbed leg in one trial cannot corrupt the estimate."""
    bench = _import_bench()
    fake = {"t": 0.0}
    monkeypatch.setattr(bench.time, "perf_counter", lambda: fake["t"])
    calls = {"n": 0}

    def run_k(k):
        disturbed = 0.5 if calls["n"] == 0 else 0.0  # first small leg only
        calls["n"] += 1
        fake["t"] += 0.1 + 0.01 * k + disturbed  # constant + per-epoch cost

    est = bench.slope_epoch_seconds_many(
        {"cell": run_k}, k1=2, k2=8, trials=3, min_delta_s=0
    )["cell"]
    assert abs(est - 0.01) < 1e-12  # constants and the disturbed leg cancel out


def test_slope_timing_rejects_non_positive_slope(monkeypatch):
    """If more epochs never cost more time, the device isn't executing the
    work (the async-dispatch failure mode) — the protocol must refuse."""
    import pytest

    bench = _import_bench()
    fake = {"t": 0.0}
    monkeypatch.setattr(bench.time, "perf_counter", lambda: fake["t"])

    def run_k(k):
        fake["t"] += 0.1  # pure constant: dispatch-only, no real execution

    with pytest.raises(RuntimeError, match="slope timing failed"):
        bench.slope_epoch_seconds_many({"cell": run_k}, trials=2)


_V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
_CPU = {"platform": "cpu", "device_kind": "cpu", "device_count": 8}


def test_build_record_labeling_rules():
    """Every labeling rule of the published bench record, unit-level: the
    device it names, and the plausibility + cross-check SUSPECT tags."""
    bench = _import_bench()

    # clean chip pair -> untagged metric that names the device
    r, w = bench.build_record({"default": 5e6, "highest": 3e6}, 1000.0, _V5E)
    assert r["metric"] == "mnist_mlp_train_samples_per_sec_per_chip" and not w
    assert (r["platform"], r["device_kind"], r["device_count"]) == (
        "tpu", "TPU v5 lite", 1,
    )
    assert r["vs_baseline"] == 5000.0 and r["vs_baseline_fp32_highest"] == 3000.0
    # there is no fallback vocabulary left in the record
    assert not any("fallback" in k.lower() or "backend" in k for k in r)

    # a CPU run is named as one, under the same metric name
    r, _ = bench.build_record({"default": 5e4}, 1000.0, _CPU)
    assert r["metric"] == "mnist_mlp_train_samples_per_sec_per_chip"
    assert r["platform"] == "cpu" and r["value_fp32_highest"] is None

    # implausible FLOP rate -> SUSPECT_TIMING (default ceiling 200 TFLOP/s)
    too_fast = 300e12 / bench.flops_per_sample()
    r, w = bench.build_record({"default": too_fast}, 1000.0, _V5E)
    assert r["metric"].endswith("_SUSPECT_TIMING") and "ceiling" in w[0]

    # headline > 2x the whole-run cross-check -> SUSPECT_TIMING (once)
    r, w = bench.build_record({"default": 5e6}, 1000.0, _V5E, crosscheck=2e6)
    assert r["metric"].count("_SUSPECT_TIMING") == 1 and "cross-check" in w[0]
    assert r["whole_run_crosscheck_sps"] == 2e6


def test_build_record_mfu_companions():
    """The record carries MFU alongside samples/s against the measuring
    device's per-chip peak, with the peak + source recorded so a
    nominal-CPU MFU is self-describing and an unknown TPU gets none."""
    bench = _import_bench()
    fps = bench.flops_per_sample()
    r, _ = bench.build_record({"default": 5e6, "highest": 3e6}, 1000.0, _V5E)
    assert abs(r["mfu"] - 5e6 * fps / 200e12) < 1e-6  # rounded to 6 places
    assert abs(r["mfu_fp32_highest"] - 3e6 * fps / 100e12) < 1e-6
    assert r["mfu_peak_flops"] == 200e12
    assert r["mfu_peak_source"] == "datasheet-v5e"
    # cpu cells get the clearly-tagged nominal peak
    r, _ = bench.build_record({"default": 5e4}, 1000.0, _CPU)
    assert r["mfu_peak_source"] == "nominal-cpu-default" and r["mfu"] > 0
    # a TPU that is not in the table: no peak, no MFU, and the tag says why
    v4 = dict(_V5E, device_kind="TPU v4")
    r, _ = bench.build_record({"default": 5e6}, 1000.0, v4)
    assert r["mfu"] is None and r["mfu_peak_flops"] is None
    assert r["mfu_peak_source"] == "unknown-device:TPU v4"
    # peak_hbm_bytes rides the record when the memory audit (the shared
    # program_audit.memory_stats path) reported one — null otherwise
    assert r["peak_hbm_bytes"] is None
    r, _ = bench.build_record(
        {"default": 5e4}, 1000.0, _CPU, peak_hbm_bytes=123456
    )
    assert r["peak_hbm_bytes"] == 123456


def test_bench_main_measures_in_process_and_fails_loudly(monkeypatch, capsys):
    """main() spawns nothing, prints one record naming the device JAX
    reports, and lets a failing phase end the run (no caught-and-continue)."""
    import json as _json

    import jax
    import pytest

    bench = _import_bench()
    monkeypatch.setattr(bench, "numpy_baseline_sps", lambda: 1000.0)
    monkeypatch.setattr(
        bench, "jax_sps_many", lambda ps: {"default": 5e4, "highest": 4e4}
    )
    monkeypatch.setattr(
        bench, "crosscheck_whole_run_sps", lambda *a, **k: 4.5e4
    )
    assert not hasattr(bench, "subprocess")
    bench.main()
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert len(lines) == 1
    rec = _json.loads(lines[0])
    dev = jax.devices()
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
        dev[0].platform, dev[0].device_kind, len(dev),
    )
    assert rec["value"] == 5e4 and rec["peak_hbm_bytes"]

    def broken(*a, **k):
        raise RuntimeError("cross-check died")

    monkeypatch.setattr(bench, "crosscheck_whole_run_sps", broken)
    with pytest.raises(RuntimeError, match="cross-check died"):
        bench.main()
    assert not capsys.readouterr().out.strip()  # no record without a measurement


def test_slope_timing_interleaved_same_window(monkeypatch):
    """slope_epoch_seconds_many must interleave configs WITHIN each trial
    (so a noisy window hits all configs equally) and estimate each
    config's slope with the same per-leg-minimum discipline."""
    bench = _import_bench()
    fake = {"t": 0.0}
    monkeypatch.setattr(bench.time, "perf_counter", lambda: fake["t"])
    order = []

    def make_run_k(name, per_epoch):
        def run_k(k):
            order.append(name)
            # trial 2 of 3 is globally disturbed: both configs see it, so
            # per-leg minima drop it for both and the ratio stays truthful
            disturbed = 0.7 if len(order) // 4 == 1 else 0.0
            fake["t"] += 0.05 + per_epoch * k + disturbed
        return run_k

    slopes = bench.slope_epoch_seconds_many(
        {"a": make_run_k("a", 0.01), "b": make_run_k("b", 0.02)},
        trials=3,
        min_delta_s=0,  # fixed legs: this test pins the interleaving order
    )
    assert abs(slopes["a"] - 0.01) < 1e-12
    assert abs(slopes["b"] - 0.02) < 1e-12
    # interleaving: each trial visits a then b before the next trial
    assert order[:4] == ["a", "a", "b", "b"]


def test_slope_timing_adapts_legs_past_constant_hiding(monkeypatch):
    """A whole leg's device work can hide inside the dispatch+readback
    constants (wall = max(constants, device_time)), making the naive
    fixed-leg delta pure noise. The estimator must measure the zero-epoch
    constants, grow the small leg until device time is resolvable ABOVE
    them, and then recover the true per-epoch cost exactly (both legs
    unhidden => constants cancel)."""
    bench = _import_bench()
    fake = {"t": 0.0}
    monkeypatch.setattr(bench.time, "perf_counter", lambda: fake["t"])
    CONSTANTS, PER_EPOCH = 0.08, 0.001

    def run_k(k):
        fake["t"] += max(CONSTANTS, PER_EPOCH * k)  # k epochs fully overlapped

    slopes = bench.slope_epoch_seconds_many({"cell": run_k}, trials=3)
    assert abs(slopes["cell"] - PER_EPOCH) < 1e-12


def test_slope_timing_failures_dict_salvages_good_configs(monkeypatch):
    """With a `failures` dict, one unresolvable config must not discard the
    other configs' completed measurements."""
    bench = _import_bench()
    fake = {"t": 0.0}
    monkeypatch.setattr(bench.time, "perf_counter", lambda: fake["t"])

    def good(k):
        fake["t"] += 0.1 + 0.01 * k

    def stuck(k):
        fake["t"] += 0.1  # pure constant: never resolves

    failures = {}
    slopes = bench.slope_epoch_seconds_many(
        {"good": good, "stuck": stuck}, trials=2, failures=failures
    )
    assert abs(slopes["good"] - 0.01) < 1e-12
    assert "good" not in failures
    assert "stuck" in failures and "stuck" not in slopes


def test_tick_times_splits_a_tick_into_branch_wait_and_transfer():
    """scripts/tick_times.py on a hand-made plane: one whole step of two
    ticks (a ``while`` holding two events of the switch's ``conditional``), a
    step loop around it that is not a tick loop, and the relays, each in a
    conditional of its own that the second tick does not take."""
    scripts_dir = str(ROOT / "scripts")
    sys.path.insert(0, scripts_dir)
    try:
        import tick_times
    finally:
        sys.path.remove(scripts_dir)
    ms = 1e6
    events = [
        ["while.9", 0, 100 * ms, ""],  # the step loop: one conditional-free level up
        ["while.1", 10 * ms, 40 * ms, ""],
        ["conditional.1", 10 * ms, 5 * ms, ""],  # the switch: tick 0's branch
        ["conditional.2", 15 * ms, 9 * ms, ""],  # a relay's, taken
        ["collective-permute-start.1", 15 * ms, 7 * ms, ""],  # waits for the partner
        ["collective-permute-done.1", 22 * ms, 2 * ms, ""],
        ["conditional.3", 24 * ms, 1 * ms, ""],  # the other relay's, taken
        ["collective-permute-start.2", 24 * ms, 0, ""],
        ["collective-permute-done.2", 24 * ms, 1 * ms, ""],
        ["conditional.1", 30 * ms, 12 * ms, ""],  # tick 1's branch
        ["conditional.2", 42 * ms, 0, ""],  # nothing due: no relay issued
        ["conditional.3", 42 * ms, 0, ""],
    ]
    plane = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": events}]}
    rows = tick_times.tick_rows(plane, 2)
    assert rows == [[(5.0, 2, 7.0, 3.0, 20.0)], [(12.0, 0, 0.0, 0.0, 20.0)]]
    assert tick_times.tick_rows(plane, 10) == [[] for _ in range(10)]
    # a program that relays every tick at the loop's own level (before the
    # relays followed the send tables) reads the same way
    events = [e for e in events if e[0] not in ("conditional.2", "conditional.3")]
    events += [
        ["collective-permute-start.1", 42 * ms, 0, ""],
        ["collective-permute-done.1", 45 * ms, 3 * ms, ""],
    ]
    plane = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": events}]}
    assert tick_times.tick_rows(plane, 2) == [
        [(5.0, 2, 7.0, 3.0, 20.0)], [(12.0, 1, 0.0, 3.0, 20.0)]
    ]
