"""Cost model (analytical FLOPs, cost_analysis cross-check, MFU) and the
run-report CLI: round-trip on a real TrainingSession JSONL, baseline
regression gating, v1-file compatibility, rendering formats.
"""

import json

import numpy as np
import pytest

from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl
from shallowspeed_tpu.observability import costmodel, report
from shallowspeed_tpu.observability.metrics import SCHEMA_VERSION

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
N, GBS = 256, 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", N), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_analytical_flops_single_source_of_truth():
    """bench.flops_per_sample and the cost model must be the same number."""
    # direct formula check: 6 * sum(in*out)
    assert costmodel.mlp_train_flops_per_sample((3, 4, 5)) == 6 * (12 + 20)
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "bench_for_report_test",
        Path(__file__).resolve().parent.parent / "bench.py",
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.flops_per_sample() == costmodel.mlp_train_flops_per_sample(
        bench.SIZES
    )


def test_peak_flops_table_and_env_override(monkeypatch):
    peak, src = costmodel.peak_flops_per_chip("tpu", "default", costmodel.TPU_V5E)
    assert peak == 200e12 and src == "datasheet-v5e"
    # keyed by device_kind: a TPU the table does not know gets no peak (and
    # so no MFU), not the v5e's
    peak, src = costmodel.peak_flops_per_chip("tpu", "highest", "TPU v4")
    assert peak is None and src == "unknown-device:TPU v4"
    peak, src = costmodel.peak_flops_per_chip("tpu", "highest")
    assert peak is None and src == "unknown-device:None"
    cm = costmodel.CostModel(
        (3, 4, 5), 10, 7, platform="tpu", device_kind="TPU v4"
    )
    assert cm.mfu(1e6) is None and cm.as_record()["device_kind"] == "TPU v4"
    peak, src = costmodel.peak_flops_per_chip("cpu", "highest")
    assert peak and src == "nominal-cpu-default"
    peak, src = costmodel.peak_flops_per_chip("gpu", "highest")
    assert peak is None and "unknown" in src
    monkeypatch.setenv(costmodel.ENV_PEAK, "5e12")
    peak, src = costmodel.peak_flops_per_chip("gpu", "highest")
    assert peak == 5e12 and src.startswith("env:")


def test_cost_model_mfu_arithmetic(monkeypatch):
    monkeypatch.setenv(costmodel.ENV_PEAK, "1e9")
    cm = costmodel.CostModel(
        sizes=(3, 4, 5), global_batch=10, batches_per_epoch=7, n_devices=4
    )
    fps = costmodel.mlp_train_flops_per_sample((3, 4, 5))
    assert cm.flops_per_epoch == fps * 10 * 7
    assert cm.achieved_flops_per_sec(100.0) == 100.0 * fps
    # MFU divides by peak x devices
    assert cm.mfu(100.0) == pytest.approx(100.0 * fps / (1e9 * 4))
    rec = cm.as_record()
    json.dumps(rec)  # JSON-able as-is
    assert rec["peak_source"].startswith("env:")
    assert rec["flops_ratio"] is None  # no compiled program attached yet


def test_cost_model_xla_crosscheck_on_real_compile():
    """Compiled.cost_analysis() of a real sequential epoch program attaches
    and yields a positive FLOP count (the cross-check leg); skipped when
    this jax/backend exposes no cost analysis."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD

    B, M = 32, 4
    spec = Mo.make_model_spec(SIZES, 1, B)
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.rand(2, M, B // M, SIZES[0]).astype(np.float32))
    Y = jnp.asarray(
        np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], (2, M, B // M))]
    )
    params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
    epoch = trainer.make_train_epoch(spec, SGD(0.01))
    compiled = epoch.lower(params, (), X, Y).compile()
    flops, _ = costmodel.compiled_flops(compiled)
    if flops is None:
        pytest.skip("backend exposes no cost_analysis flops")
    cm = costmodel.CostModel(sizes=SIZES, global_batch=B, batches_per_epoch=2)
    assert cm.attach_compiled(compiled)
    assert cm.xla_flops_per_epoch > 0
    # structural cross-check only: scan bodies are counted once by XLA's
    # analysis, so the ratio sits well below 1 but must stay sane
    assert 0 < cm.flops_ratio < 100


def test_pipeline_padded_flops_from_tick_tables():
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.parallel.executor import slot_shapes
    from shallowspeed_tpu.parallel.lowering import lower_schedule, program_flops

    B, M, P = 32, 4, 4
    spec = Mo.make_model_spec(SIZES, P, B)
    prog = lower_schedule(S.GPipeSchedule, M, P)
    mb = B // M
    flops = program_flops(prog, spec, mb)
    # every device runs M forwards (2x) + M backwards (4x) over the padded
    # slot stack: (2*M*P + 4*M*P) * mb * padded_P
    padded_p = sum(o * i for o, i in slot_shapes(spec))
    assert flops == (2 * M * P + 4 * M * P) * mb * padded_p
    # the padded program always does at least the logical work
    assert flops >= costmodel.mlp_train_flops_per_sample(SIZES) * B


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def _train_jsonl(data_dir, tmp_path, name, epochs=2):
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / name
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m, health="record", clip_norm=1.0,
        )
        for _ in range(epochs):
            run.train_epoch()
    return path


def test_report_round_trip_on_real_run(data_dir, tmp_path, capsys):
    """The acceptance contract: a fresh train_epoch JSONL renders MFU, the
    span breakdown and a health verdict, and the CLI exits 0."""
    path = _train_jsonl(data_dir, tmp_path, "run.jsonl")
    assert report.main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "MFU" in out and "%" in out
    assert "Span breakdown" in out
    assert "train_epoch" in out and "jit_compile" in out
    assert "health" in out and "ok" in out
    assert "Step loss" in out  # sparkline section

    # json format is machine-parseable and carries the same facts
    assert report.main([str(path), "--format", "json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["steps"] == 2 * 4  # 2 epochs x 4 batches
    assert rep["throughput_samples_per_sec"] > 0
    assert rep["mfu"] is not None and rep["health"]["verdict"] == "ok"
    assert rep["cost_model"]["flops_per_sample"] == (
        costmodel.mlp_train_flops_per_sample(SIZES)
    )
    assert rep["steady_epochs"] == 1  # first epoch includes compile

    # text format renders too
    assert report.main([str(path), "--format", "text"]) == 0


def test_report_baseline_regression_gate(data_dir, tmp_path, capsys):
    """--baseline exits nonzero (2) on an injected >10% throughput
    regression and 0 when within the threshold."""
    path = _train_jsonl(data_dir, tmp_path, "cur.jsonl")
    records = read_jsonl(path)
    cur = report.build_report(records)["throughput_samples_per_sec"]

    def synth_baseline(name, sps):
        p = tmp_path / name
        with JsonlMetrics(p) as m:
            m.event("epoch", epoch=0, loss=0.5, samples_per_sec=sps, wall_s=1.0)
        return p

    fast = synth_baseline("fast.jsonl", cur * 1.5)  # we regressed >10% vs this
    slow = synth_baseline("slow.jsonl", cur * 0.95)  # within threshold
    assert report.main([str(path), "--baseline", str(fast)]) == 2
    assert "REGRESSION" in capsys.readouterr().err
    assert report.main([str(path), "--baseline", str(slow)]) == 0
    # a generous threshold un-gates the fast baseline
    assert (
        report.main([str(path), "--baseline", str(fast), "--threshold", "0.9"]) == 0
    )

    # bench-style JSON baselines work too
    bench_rec = tmp_path / "bench.json"
    bench_rec.write_text(
        json.dumps({"metric": "x", "value": cur * 2.0, "unit": "samples/s"})
    )
    assert report.main([str(path), "--baseline", str(bench_rec)]) == 2
    capture_rec = tmp_path / "cap.json"
    capture_rec.write_text(json.dumps({"headline_best_sps": cur * 0.5}))
    assert report.main([str(path), "--baseline", str(capture_rec)]) == 0
    # a baseline with no recognizable throughput is a load error (1)
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"published": {}}))
    assert report.main([str(path), "--baseline", str(empty)]) == 1


def test_report_regression_gate_skipped_for_compile_polluted_runs(
    tmp_path, capsys
):
    """A run whose ONLY epoch record includes compile time must not be
    gated against a steady-state baseline — that would flag a spurious
    regression on every 1-epoch job."""
    short = tmp_path / "short.jsonl"
    with JsonlMetrics(short) as m:
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=100.0,
                wall_s=10.0, includes_compile=True)
    base = tmp_path / "steady.jsonl"
    with JsonlMetrics(base) as m:
        m.event("epoch", epoch=3, loss=0.5, samples_per_sec=1000.0, wall_s=1.0)
    assert report.main([str(short), "--baseline", str(base)]) == 0
    err = capsys.readouterr().err
    assert "regression gate skipped" in err
    rep = report.build_report(read_jsonl(short))
    assert rep["throughput_includes_compile"] is True
    # the asymmetric direction: a compile-polluted BASELINE must be
    # refused, not silently trusted (an understated baseline would let
    # real regressions pass the gate)
    assert report.main([str(base), "--baseline", str(short)]) == 1
    assert "compile-polluted" in capsys.readouterr().err


def test_report_mfu_carries_compile_caveat(tmp_path, capsys):
    path = tmp_path / "one.jsonl"
    with JsonlMetrics(path) as m:
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=100.0,
                wall_s=10.0, includes_compile=True, mfu=0.01)
    rep = report.build_report(read_jsonl(path))
    assert rep["mfu"] == 0.01 and rep["mfu_includes_compile"] is True
    assert report.main([str(path), "--format", "text"]) == 0
    out = capsys.readouterr().out
    assert "(includes compile)" in out


def test_report_accepts_schema_v1_files(tmp_path, capsys):
    """The v3 reader/report accept v1 files unchanged (compat rule)."""
    path = tmp_path / "v1.jsonl"
    v1 = [
        {"v": 1, "ts": 0.0, "kind": "meta", "name": "metrics",
         "schema": "shallowspeed_tpu.metrics"},
        {"v": 1, "ts": 1.0, "kind": "event", "name": "epoch", "epoch": 0,
         "loss": 0.4, "samples_per_sec": 1234.0, "wall_s": 1.0},
        {"v": 1, "ts": 2.0, "kind": "span", "name": "train_epoch",
         "path": "train_epoch", "depth": 0, "seconds": 1.0},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in v1))
    recs = read_jsonl(path)  # strict: v1 < v2 is fine
    assert len(recs) == 3
    assert report.main([str(path), "--format", "text"]) == 0
    out = capsys.readouterr().out
    assert "1,234" in out
    # and a NEWER schema is still refused loudly
    future = tmp_path / "future.jsonl"
    future.write_text(json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n")
    assert report.main([str(future)]) == 1


def test_report_flags_nan_steps_and_halt_verdict(tmp_path, capsys):
    path = tmp_path / "nan.jsonl"
    with JsonlMetrics(path) as m:
        m.event("epoch", epoch=0, loss=float("nan"), samples_per_sec=10.0,
                wall_s=1.0)
        for i, loss in enumerate([0.5, 0.4, float("nan"), 9.0]):
            m.step("train", step=i, epoch=0, loss=loss)
        m.health("non_finite", epoch=0, step=2, value=None, action="halt",
                 detail="loss is nan")
    assert report.main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "HALTED: non_finite at epoch 0, step 2" in out
    assert "NON-FINITE" in out
    assert "x" in report.sparkline([0.5, float("nan"), 0.5])


def _audit_record_with_bounds():
    """A minimal xla_audit record carrying the comms model's overlap
    fields (the shape TrainingSession(audit=True) emits)."""
    return {
        "v": SCHEMA_VERSION, "ts": 0.0, "kind": "xla_audit",
        "name": "epoch_program", "hlo_available": True,
        "census": {"all_reduce": {"count": 3, "bytes": 3072}},
        "memory": None, "n_devices": 2,
        "expected": {
            "dp": 2, "pp": 1, "zero1": False, "sequential": False,
            "required": ["all_reduce"], "forbidden": [],
            "axes": {"dp": {"kind": "all_reduce", "zero": 0,
                            "grad_bytes_per_device": 3072,
                            "bytes_per_step_per_device": 3072}},
            "bytes_per_step_per_device": 3072,
            "comms_time_per_step_s": 4e-6,
            "compute_time_per_step_s": 1e-6,
            "bound": "comms",
            "serial_bound_s": 5e-6,
            "overlapped_bound_s": 4e-6,
            "model_hidden_comm_share": 0.25,
        },
        "mismatches": [], "census_ok": True,
    }


def test_report_overlap_row_model_and_measured(tmp_path, capsys):
    """The overlap-efficiency row: the comms model's hidden-comm bound by
    default, upgraded to the measured trace split when one is given."""
    path = tmp_path / "ov.jsonl"
    path.write_text(json.dumps(_audit_record_with_bounds()) + "\n")
    assert report.main([str(path), "--format", "text"]) == 0
    out = capsys.readouterr().out
    assert "overlap efficiency" in out
    assert "25.00% of comm hideable (model bound; anchor sync)" in out
    assert "serial (anchor)" in out and "max(comm, compute)" in out

    records = read_jsonl(path)
    rep = report.build_report(
        records,
        trace={
            "overlap_efficiency": 0.87, "comm_ms": 10.0,
            "exposed_comm_ms": 1.3, "comm_fraction": 0.2,
        },
    )
    assert rep["overlap"]["source"] == "measured"
    assert rep["overlap"]["hidden_comm_share"] == 0.87
    # the model's bounds survive alongside the measured share
    assert rep["overlap"]["serial_bound_s"] == 5e-6
    out = report.render(rep, "text")
    assert "87.00% of comm hidden (measured" in out


def test_report_trace_flag_measures_overlap(tmp_path, capsys):
    """--trace: a chrome trace's comm/compute split feeds the measured
    overlap-efficiency row (exposed = span not coverable by compute)."""
    import gzip

    trace = {
        "traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            # comm spans the full 100 us; compute covers 60 of them ->
            # 40 us exposed of 100 us comm -> 60% hidden
            {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce.1",
             "ts": 0, "dur": 100},
            {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.2",
             "ts": 0, "dur": 60},
        ]
    }
    tpath = tmp_path / "x.trace.json.gz"
    with gzip.open(tpath, "wt") as f:
        json.dump(trace, f)
    from shallowspeed_tpu.observability import trace_stats

    s = trace_stats.summarize(tpath)
    assert s["comm_ms"] == 0.1 and s["compute_ms"] == 0.06
    assert s["exposed_comm_ms"] == pytest.approx(0.04)
    assert s["overlap_efficiency"] == pytest.approx(0.6)

    path = tmp_path / "run.jsonl"
    path.write_text(json.dumps(_audit_record_with_bounds()) + "\n")
    assert report.main(
        [str(path), "--format", "text", "--trace", str(tmp_path)]
    ) == 0
    out = capsys.readouterr().out
    assert "60.00% of comm hidden (measured" in out


def test_trace_overlap_survives_multidevice_and_unit_overlap(tmp_path):
    """The exposure math is a per-device interval union, so it is not
    fooled by (a) several device pids sharing one wall span or (b)
    functional-unit overlap where summed busy time exceeds the span —
    busy-sum arithmetic would report exposed=0 for any such trace."""
    import gzip

    trace = {
        "traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "/device:TPU:1"}},
            # device 0: comm [0,100], compute [0,40]+[20,60] on two unit
            # threads (busy 100+40+40=180 > span 100) -> union(compute) =
            # [0,60], exposed comm = 40
            {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce.1",
             "ts": 0, "dur": 100},
            {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1",
             "ts": 0, "dur": 40},
            {"ph": "X", "pid": 1, "tid": 3, "name": "fusion.2",
             "ts": 20, "dur": 40},
            # device 1: comm [0,50] + comm [25,75] (mutually overlapping
            # — must NOT count as hidden: the union, 75, is the
            # denominator) fully under compute [0,100] -> 0 exposed
            # (device 0's compute must NOT be credited here either)
            {"ph": "X", "pid": 2, "tid": 1, "name": "all-reduce.2",
             "ts": 0, "dur": 50},
            {"ph": "X", "pid": 2, "tid": 3, "name": "all-reduce.3",
             "ts": 25, "dur": 50},
            {"ph": "X", "pid": 2, "tid": 2, "name": "fusion.3",
             "ts": 0, "dur": 100},
        ]
    }
    tpath = tmp_path / "multi.trace.json.gz"
    with gzip.open(tpath, "wt") as f:
        json.dump(trace, f)
    from shallowspeed_tpu.observability import trace_stats

    s = trace_stats.summarize(tpath)
    assert s["comm_ms"] == pytest.approx(0.2)  # summed busy time
    assert s["comm_union_ms"] == pytest.approx(0.175)  # 100 + 75
    assert s["exposed_comm_ms"] == pytest.approx(0.04)
    # hidden share over the comm interval UNION: 1 - 40/175
    assert s["overlap_efficiency"] == pytest.approx(1 - 40 / 175, abs=1e-3)


def test_sparkline_shapes():
    assert report.sparkline([]) == ""
    assert len(report.sparkline(list(range(1000)), width=60)) == 60
    flat = report.sparkline([2.0, 2.0, 2.0])
    assert len(set(flat)) == 1  # constant series renders uniformly
    line = report.sparkline([1, 2, 3, 4, 5, 6, 7, 8])
    assert line[0] == report.BLOCKS[0] and line[-1] == report.BLOCKS[-1]


def test_report_unreadable_run_exits_1(tmp_path, capsys):
    missing = tmp_path / "nope.jsonl"
    assert report.main([str(missing)]) == 1
    assert "cannot read" in capsys.readouterr().err


def test_report_without_audit_records_omits_sections(tmp_path, capsys):
    """No xla_audit record -> no Memory/Comms sections (and no crash);
    the JSON rendering carries xla_audit: null so consumers can tell
    'not audited' from 'audited clean'."""
    path = tmp_path / "plain.jsonl"
    with JsonlMetrics(path) as m:
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=10.0, wall_s=1.0)
    rep = report.build_report(read_jsonl(path))
    assert rep["xla_audit"] is None
    assert report.main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "Memory (compiled program)" not in out
    assert "Comms (XLA program audit)" not in out
    assert report.main([str(path), "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["xla_audit"] is None


def test_report_weighted_bubble_row(tmp_path, capsys):
    """The pipeline_program event's FLOP-weighted bubble renders as its
    own row — tagged with the split-backward note when the program
    deferred its weight grads, the plain FLOP-weighted note otherwise."""
    for split in (False, True):
        path = tmp_path / f"run_{split}.jsonl"
        with JsonlMetrics(path) as m:
            m.event(
                "pipeline_program", schedule="pipedream", dp=1, pp=4,
                bubble_fraction=0.27 if not split else 0.11,
                weighted_bubble_fraction=0.40 if not split else 0.11,
                backward_split=split,
            )
            m.event("epoch", epoch=0, loss=0.5, samples_per_sec=10.0, wall_s=1.0)
        rep = report.build_report(read_jsonl(path))
        assert rep["weighted_bubble_fraction"] == (0.40 if not split else 0.11)
        assert rep["backward_split"] is split
        assert report.main([str(path), "--format", "md"]) == 0
        out = capsys.readouterr().out
        assert "weighted bubble" in out
        if split:
            assert "split backward" in out
        else:
            assert "FLOP-weighted ticks" in out


def test_report_reads_multihost_shard_glob(tmp_path, capsys):
    """The report CLI accepts a glob of multihost JSONL shards (and the
    bare-path fallback): per-host epoch records merge into one report."""
    for idx, loss in ((0, 0.5), (1, 0.25)):
        (tmp_path / f"run.jsonl.p{idx}").write_text(
            json.dumps({"v": SCHEMA_VERSION, "ts": float(idx), "kind": "event",
                        "name": "epoch", "epoch": 0, "loss": loss,
                        "samples_per_sec": 100.0, "wall_s": 1.0}) + "\n"
        )
    glob_arg = str(tmp_path / "run.jsonl.p*")
    assert report.main([glob_arg, "--format", "json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["epochs"] == 2
    # bare path that never existed resolves to its shards
    assert report.main([str(tmp_path / "run.jsonl"), "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["epochs"] == 2


def test_report_reliability_section(tmp_path, capsys):
    """The schema-v4 Reliability story: checkpoint overhead + cadence from
    the checkpoint records, and the recovery verdict with steps-lost-to-
    replay MEASURED from the killed run's step records when the streams
    are concatenated (the `make recovery-smoke` shape)."""
    killed = tmp_path / "killed.jsonl"
    with JsonlMetrics(killed) as m:
        with m.span("train_steps"):
            pass
        for gs in (4, 8):
            m.checkpoint(
                "step", path=f"/ck/step-{gs:08d}.npz", epoch=0,
                step_in_epoch=gs, global_step=gs, bytes=4096, wall_s=0.25,
            )
        for s in range(12):  # the dead run trained through step 11
            m.step("train", step=s, epoch=0, loss=0.5)
    resumed = tmp_path / "resumed.jsonl"
    with JsonlMetrics(resumed) as m:
        m.recovery(
            "resumed", resumed_from="/ck/step-00000008.npz", epoch=0,
            step_in_epoch=8, global_step=8,
            skipped=[{"path": "/ck/step-00000012.npz",
                      "cause": "content checksum mismatch"}],
        )
        m.event("epoch", epoch=0, loss=0.4, samples_per_sec=10.0, wall_s=1.0)
    combined = tmp_path / "combined.jsonl"
    combined.write_text(killed.read_text() + resumed.read_text())

    rep = report.build_report(read_jsonl(combined))
    rel = rep["reliability"]
    assert rel["checkpoints"] == 2
    assert rel["checkpoint_wall_s"] == pytest.approx(0.5)
    assert 0 < rel["checkpoint_overhead_fraction"] <= 1
    assert rel["checkpoint_cadence_steps"] == 4
    assert rel["recovery"]["verdict"] == "resumed"
    # the kill happened after step 11 trained, the restore landed on 8
    assert rel["recovery"]["steps_lost_to_replay"] == 12 - 8
    assert rel["recovery"]["skipped"][0]["cause"] == "content checksum mismatch"

    assert report.main([str(combined), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "## Reliability" in out
    assert "recovery: resumed from /ck/step-00000008.npz" in out
    assert "steps lost to replay: 4" in out
    assert "1 corrupt snapshot(s) skipped" in out

    # the resumed stream ALONE has no step evidence before the recovery
    # record: the loss is honestly unknown, never guessed
    rep2 = report.build_report(read_jsonl(resumed))
    assert rep2["reliability"]["recovery"]["steps_lost_to_replay"] is None
    assert report.main([str(resumed), "--format", "md"]) == 0
    assert "steps lost to replay: unknown" in capsys.readouterr().out

    # a kill that landed exactly on a checkpointed step is a MEASURED 0,
    # not unknown — the killed run's evidence IS in the stream
    zero = tmp_path / "zero.jsonl"
    with JsonlMetrics(zero) as m:
        for s in range(8):  # trained through step 7, snapshot at 8
            m.step("train", step=s, epoch=0, loss=0.5)
        m.recovery(
            "resumed", resumed_from="/ck/step-00000008.npz", epoch=0,
            step_in_epoch=8, global_step=8, skipped=[],
        )
    rep3 = report.build_report(read_jsonl(zero))
    assert rep3["reliability"]["recovery"]["steps_lost_to_replay"] == 0
    assert report.main([str(zero), "--format", "md"]) == 0
    assert "steps lost to replay: 0" in capsys.readouterr().out


def test_report_reliability_omitted_without_v4_records(tmp_path, capsys):
    """Pre-v4 runs render exactly as before: reliability is null in JSON
    and the section is absent from the text rendering; a fresh_start
    recovery renders its own verdict line."""
    plain = tmp_path / "plain.jsonl"
    with JsonlMetrics(plain) as m:
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=10.0, wall_s=1.0)
    assert report.build_report(read_jsonl(plain))["reliability"] is None
    assert report.main([str(plain), "--format", "md"]) == 0
    assert "Reliability" not in capsys.readouterr().out

    fresh = tmp_path / "fresh.jsonl"
    with JsonlMetrics(fresh) as m:
        m.recovery("fresh_start", resumed_from=None, epoch=0,
                   step_in_epoch=0, global_step=0, skipped=[])
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=10.0, wall_s=1.0)
    assert report.main([str(fresh), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "recovery: fresh start" in out


def test_report_fleet_section(tmp_path, capsys):
    """The Fleet section: a v7 fleet summary + fleet_health stream renders
    replica lifecycle, failover, elasticity, routing skew, per-replica
    verdict rows and the availability verdict; runs without fleet records
    render exactly as before (fleet is null / section absent)."""
    path = tmp_path / "fleet.jsonl"
    with JsonlMetrics(path) as m:
        for rid in (0, 1, 2):
            m.fleet_health("replica_spawned", replica_id=rid, checkpoint=None)
            m.fleet_health("replica_ready", replica_id=rid, wall_s=1.2)
        m.fleet_health("replica_sigkill", replica_id=1, pid=123)
        m.fleet_health("replica_dead", replica_id=1, inflight=3, error=None)
        m.fleet_health("failover", replica_id=1, requeued=3, exhausted=0)
        m.fleet_health("scale_up", replica_id=3, replacement=True, target=3)
        m.fleet(
            "summary",
            completed=90, dropped=0, expired=0, errors=0, unhealthy=0,
            availability=1.0, failovers=1, failover_requeued=3,
            failover_exhausted=0, reroutes=2, replicas_target=3,
            replicas_started=4, replicas_ready=3, replicas_dead=1,
            replicas_retired=0, scale_ups=1, scale_downs=0,
            scale_up_s=1.4, degraded=False, recovery_s=0.004,
            routing={0: 44, 1: 6, 2: 40, 3: 0}, routing_skew=1.47,
            per_replica={
                0: {"state": "ready", "routed": 44, "verdicts": {"ok": 44}},
                1: {"state": "dead", "routed": 6, "verdicts": {"ok": 5}},
            },
            p50_latency_s=0.004, p99_latency_s=0.012,
        )
    rep = report.build_report(read_jsonl(path))
    fl = rep["fleet"]
    assert fl["failovers"] == 1 and fl["failover_requeued"] == 3
    assert fl["sigkills_injected"] == 1
    assert fl["degraded_at_exit"] is False
    assert "recovered from 1 replica death" in fl["verdict"]
    assert report.main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "## Fleet" in out
    assert "1 DIED (1 SIGKILL injected)" in out
    assert "failover: 1 event(s), 3 in-flight request(s) re-queued" in out
    assert "skew 1.47x" in out
    assert "replica 1 [dead]" in out
    assert "availability 100.0%" in out

    # killed-parent fallback: fleet_health events alone still fold
    partial = tmp_path / "partial.jsonl"
    with JsonlMetrics(partial) as m:
        m.fleet_health("replica_spawned", replica_id=0, checkpoint=None)
        m.fleet_health("replica_dead", replica_id=0, inflight=2, error=None)
        m.fleet_health("failover", replica_id=0, requeued=2, exhausted=0)
        m.fleet_health("fleet_degraded", replica_id=None, healthy=0,
                       target=1, quorum=1)
    fl2 = report.build_report(read_jsonl(partial))["fleet"]
    assert fl2["replicas_dead"] == 1 and fl2["failover_requeued"] == 2
    assert fl2["degraded_at_exit"] is True
    assert "DEGRADED" in fl2["verdict"]

    # no fleet records -> section omitted, JSON carries fleet: null
    plain = tmp_path / "noval.jsonl"
    with JsonlMetrics(plain) as m:
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=10.0, wall_s=1.0)
    assert report.build_report(read_jsonl(plain))["fleet"] is None
    assert report.main([str(plain), "--format", "md"]) == 0
    assert "## Fleet" not in capsys.readouterr().out


def test_report_reliability_async_rows(tmp_path, capsys):
    """The schema-v8 Reliability additions: async saves render their
    off-path accounting next to the (now on-path-only) overhead
    fraction, and the Degradation breaker line carries
    the reload's single-read verify time. A line of the kind v8 added,
    from an old file, is read and renders nothing."""
    path = tmp_path / "v8.jsonl"
    with JsonlMetrics(path) as m:
        with m.span("train_steps"):
            pass
        for gs in (4, 8):
            m.checkpoint(
                "step", path=f"/ck/step-{gs:08d}.npz", epoch=0,
                step_in_epoch=gs, global_step=gs, bytes=4096,
                wall_s=0.002, **{"async": True}, queue_depth=1,
                verify_s=0.1, write_s=0.15, queued_s=0.001,
            )
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps({"v": 8, "ts": 0.0, "kind": "aot_cache",
                            "name": "hit", "program": "inference_r4"}) + "\n")
    rep = report.build_report(read_jsonl(path))
    rel = rep["reliability"]
    assert rel["checkpoints_async"] == 2
    assert rel["checkpoint_off_path_s"] == pytest.approx(0.5)
    # on-path wall only: async saves cost milliseconds on the step path
    assert rel["checkpoint_wall_s"] == pytest.approx(0.004)
    assert "aot_cache" not in rel

    assert report.main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "async checkpointing: 2 of 2 saves off-path" in out
    assert "executable cache" not in out

    # reload verify accounting reaches the Degradation breaker line
    deg = tmp_path / "deg.jsonl"
    with JsonlMetrics(deg) as m:
        m.serving("summary", completed=5, dropped=0, breaker_trips=1,
                  reloads=1, recovery_s=0.02)
        m.serving_health("breaker_open", dispatch=3, consecutive_failures=3)
        m.reload("ok", path="/ck/step-00000008.npz", step=8,
                 reason="breaker", wall_s=0.03, verify_s=0.012)
    rep3 = report.build_report(read_jsonl(deg))
    assert rep3["serving"]["degradation"]["reload_verify_s"] == pytest.approx(
        0.012
    )
    assert report.main([str(deg), "--format", "md"]) == 0
    out3 = capsys.readouterr().out
    assert "snapshot verify" in out3 and "single-read" in out3
