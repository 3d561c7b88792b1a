"""Observability subsystem tests: recorders, spans, JSONL schema, trace
stats, and the TrainingSession telemetry wiring (sequential + mesh layouts).
"""

import gzip
import json
import sys

import numpy as np
import pytest

from shallowspeed_tpu.observability import (
    SCHEMA_VERSION,
    JsonlMetrics,
    MetricsRecorder,
    NullMetrics,
    read_jsonl,
    span,
    trace_stats,
)
from shallowspeed_tpu.observability.spans import KEEP

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
# recorders
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_math():
    m = MetricsRecorder()
    m.counter("steps")
    m.counter("steps")
    m.counter("samples", 128)
    m.counter("samples", 64)
    m.gauge("lr", 0.1)
    m.gauge("lr", 0.05)  # last value wins
    for v in (1.0, 3.0, 2.0):
        m.observe("loss", v)
    s = m.summary()
    assert s["counters"] == {"steps": 2.0, "samples": 192.0}
    assert s["gauges"] == {"lr": 0.05}
    h = s["histograms"]["loss"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
    assert abs(h["mean"] - 2.0) < 1e-12


def test_timer_records_duration():
    m = MetricsRecorder()
    with m.timer("work") as t:
        sum(range(1000))
    assert t.seconds is not None and t.seconds >= 0
    h = m.summary()["histograms"]["work.seconds"]
    assert h["count"] == 1 and h["min"] == t.seconds


def test_span_nesting_paths_and_depths():
    # names come from spans.HOST_SPANS (tests/test_spans.py holds the refusal)
    m = MetricsRecorder()
    with m.span("train_run"):
        with m.span("train_epoch"):
            with m.span("epoch/dispatch"):
                pass
        with m.span("eval"):
            pass
    paths = [p for p, _ in m.spans]
    # spans record on EXIT, innermost first
    assert paths == [
        "train_run/train_epoch/epoch/dispatch", "train_run/train_epoch",
        "train_run/eval", "train_run",
    ]
    # standalone spans (no recorder) still time and nest
    with span("train_epoch") as sa:
        with span("device_put") as sb:
            pass
    assert sa.path == "train_epoch" and sb.path == "train_epoch/device_put"
    assert sb.depth == 1
    assert sa.seconds >= sb.seconds >= 0


def test_null_metrics_hot_path_zero_net_allocation():
    """The disabled recorder must cost nothing measurable: after warmup, a
    large burst of hot-path calls leaves the interpreter's allocated-block
    count unchanged (no per-call objects survive, no hidden aggregation).
    Its one live method is ``span``: a real span into the process's span log,
    which is bounded (first and newest ``spans.KEEP``), so once the log is
    full an entry leaves for each one that arrives."""
    m = NullMetrics()

    def burst(n):
        for _ in range(n):
            m.counter("x")
            m.counter("x", 2.0)
            m.gauge("g", 1.0)
            m.observe("h", 0.5)
            with m.timer("t"):
                pass
            with m.span("eval"):
                pass
            m.audit("a")  # the v3 audit hook keeps the guarantee too
            m.checkpoint("c")  # ... and the v4 fault-tolerance hooks
            m.recovery("r")
            m.request("q")  # ... and the v5 serving hooks
            m.serving("s")
            m.serving_health("b")  # ... and the v6 degradation hooks
            m.reload("r")
            m.trace("t")  # ... and the v10 tracing hook
            m.rollup("w")  # ... and the v11 live-telemetry hooks
            m.alert("a")
            m.digest("d")  # ... and the v12 numerics-provenance hook
            m.autoscale("a")  # ... and the v13 capacity hook

    burst(2 * KEEP)  # warm up caches (method cache, code objects), fill the log
    # background threads (XLA's pools) can allocate a handful of blocks at
    # any moment, so take the min over a few trials: a REAL per-call leak
    # (one surviving object per call) would show up as >= 30000 blocks in
    # EVERY trial, while an idle interpreter shows ~0 in at least one
    deltas = []
    for _ in range(5):
        before = sys.getallocatedblocks()
        burst(5000)
        deltas.append(abs(sys.getallocatedblocks() - before))
    assert min(deltas) <= 16, (
        f"null backend leaked {min(deltas)} blocks per 5000-call burst"
    )
    assert m.enabled is False


def test_jsonl_schema_round_trip(tmp_path):
    path = tmp_path / "m.jsonl"
    with JsonlMetrics(path) as m:
        m.counter("epochs")
        m.gauge("lr", 0.006)
        m.observe("loss", 0.5)
        with m.timer("compile"):
            pass
        with m.span("train_epoch"):
            pass
        m.event("epoch", epoch=0, loss=0.5, samples_per_sec=1234.5)
    # raw file: every line is valid JSON and carries the schema version
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert all(rec["v"] == SCHEMA_VERSION for rec in lines)
    assert lines[0]["kind"] == "meta" and "schema" in lines[0]
    # reader round-trip preserves kinds and fields
    recs = read_jsonl(path)
    kinds = [r["kind"] for r in recs]
    assert kinds == ["meta", "counter", "gauge", "histogram", "timer", "span",
                     "event"]
    ev = recs[-1]
    assert ev["name"] == "epoch" and ev["loss"] == 0.5
    assert ev["samples_per_sec"] == 1234.5
    assert all("ts" in r for r in recs)


def test_read_jsonl_rejects_newer_schema(tmp_path):
    path = tmp_path / "future.jsonl"
    path.write_text(json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(path)
    assert read_jsonl(path, strict=False)[0]["v"] == SCHEMA_VERSION + 1


def test_jsonl_survives_abandonment(tmp_path):
    """Per-record flushing: everything recorded before a kill is on disk."""
    path = tmp_path / "m.jsonl"
    m = JsonlMetrics(path)
    m.counter("a")
    # no close() — simulate the process dying here
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "counter"]
    m.close()
    with pytest.raises(ValueError, match="closed"):
        m.counter("b")


# ---------------------------------------------------------------------------
# trace_stats (importable module + synthetic fixture)
# ---------------------------------------------------------------------------


def _write_synthetic_trace(path):
    """Two device ops (10us + 30us, 20us gap) + host noise + module envelope."""
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "python host"}},
        {"ph": "M", "pid": 1, "tid": 9, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        # the whole-module envelope: must be EXCLUDED from op stats
        {"ph": "X", "pid": 1, "tid": 9, "name": "jit_step", "ts": 0, "dur": 60},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0, "dur": 10},
        {"ph": "X", "pid": 1, "tid": 1, "name": "convolution.2", "ts": 30,
         "dur": 30},
        # host-side op: wrong pid, excluded
        {"ph": "X", "pid": 2, "tid": 1, "name": "hostop", "ts": 0, "dur": 999},
    ]
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_trace_stats_summarize_synthetic(tmp_path):
    trace = tmp_path / "x.trace.json.gz"
    _write_synthetic_trace(trace)
    s = trace_stats.summarize(trace)
    assert s["device_ops"] == 2
    assert s["span_ms"] == 0.06  # 0..60us
    assert s["busy_ms"] == 0.04  # 10 + 30
    assert s["ns_per_op_issued"] == 30000.0  # 60us / 2 ops
    assert abs(s["unit_overlap"] - 0.67) < 1e-9
    assert s["top_ops"] == {"fusion": 1, "convolution": 1}
    # an all-compute trace: the comm split exists and is zero
    assert s["comm_ops"] == 0 and s["comm_ms"] == 0.0
    assert s["compute_ms"] == 0.04 and s["comm_fraction"] == 0.0


def test_trace_stats_comm_compute_split(tmp_path):
    """Device ops split into comm vs compute by HLO-name prefix — the
    measured comm share the analytical comms model's bound verdict is
    compared against (docs/observability.md)."""
    trace = tmp_path / "comm.trace.json.gz"
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0, "dur": 30},
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce.3", "ts": 30,
         "dur": 10},
        {"ph": "X", "pid": 1, "tid": 2, "name": "collective-permute-start.1",
         "ts": 40, "dur": 15},
        {"ph": "X", "pid": 1, "tid": 1, "name": "reduce-scatter.2", "ts": 55,
         "dur": 5},
    ]
    with gzip.open(trace, "wt") as f:
        json.dump({"traceEvents": events}, f)
    s = trace_stats.summarize(trace)
    assert s["comm_ops"] == 3
    assert s["comm_ms"] == 0.03  # 10 + 15 + 5 us
    assert s["compute_ms"] == 0.03
    assert s["comm_fraction"] == 0.5
    assert trace_stats.is_comm_op("all-gather-done.7")
    assert not trace_stats.is_comm_op("fusion.all")


def test_trace_stats_find_traces_and_empty(tmp_path):
    (tmp_path / "sub").mkdir()
    trace = tmp_path / "sub" / "y.trace.json.gz"
    _write_synthetic_trace(trace)
    found = trace_stats.find_traces(tmp_path)
    assert found == [trace]
    empty = tmp_path / "empty.trace.json.gz"
    with gzip.open(empty, "wt") as f:
        json.dump({"traceEvents": []}, f)
    assert trace_stats.summarize(empty) == {"trace": str(empty), "device_ops": 0}


def test_trace_stats_script_shim_reexports():
    """scripts/trace_stats.py stays a working import surface (and the
    package module is importable exactly as the acceptance criterion asks)."""
    from pathlib import Path

    scripts_dir = str(Path(__file__).resolve().parent.parent / "scripts")
    sys.path.insert(0, scripts_dir)
    try:
        import trace_stats as shim
    finally:
        sys.path.remove(scripts_dir)
    assert shim.summarize is trace_stats.summarize
    assert shim.find_traces is trace_stats.find_traces


# ---------------------------------------------------------------------------
# program stats (lowering-time pipeline telemetry)
# ---------------------------------------------------------------------------


def test_program_stats_match_lowered_tables():
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.parallel.lowering import (
        lower_schedule,
        program_stats,
        utilization,
    )

    prog = lower_schedule(S.GPipeSchedule, 4, 4)
    stats = program_stats(prog)
    assert stats["num_ticks"] == prog.num_ticks
    assert stats["num_stages"] == 4 and stats["num_micro_batches"] == 4
    assert stats["is_training"] is True
    # every device runs M forwards + M backwards
    assert stats["active_cells"] == 4 * 2 * 4
    assert abs(stats["utilization"] - utilization(prog)) < 1e-12
    assert abs(stats["bubble_fraction"] - (1 - utilization(prog))) < 1e-12
    # sends: stages 0..P-2 send M activations fwd, stages 1..P-1 M grads bwd
    assert stats["sends_fwd"] == 3 * 4 and stats["sends_bwd"] == 3 * 4
    assert len(stats["stage_occupancy"]) == 4
    assert all(0 < o <= 1 for o in stats["stage_occupancy"])
    # JSON-serializable as-is (the JSONL sink emits it verbatim)
    json.dumps(stats)


# ---------------------------------------------------------------------------
# trainer/executor grad-norm aux
# ---------------------------------------------------------------------------


def test_trainer_grad_norm_aux_matches_plain_epoch():
    """with_grad_norm changes ONLY the arity: params/loss stay bitwise
    identical, and the aux norm is finite and positive."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD

    B, M = 32, 4
    spec = Mo.make_model_spec(SIZES, 1, B)
    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.rand(2, M, B // M, SIZES[0]).astype(np.float32))
    Y = jnp.asarray(
        np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], (2, M, B // M))]
    )
    p0 = jax.tree.map(jnp.asarray, Mo.init_model(spec))
    plain = trainer.make_train_epoch(spec, SGD(0.01), clip_norm=1.0)
    aux_fn = trainer.make_train_epoch(
        spec, SGD(0.01), clip_norm=1.0, with_grad_norm=True
    )
    p1, _, loss1 = plain(jax.tree.map(jnp.copy, p0), (), X, Y)
    p2, _, loss2, aux = aux_fn(jax.tree.map(jnp.copy, p0), (), X, Y)
    assert float(loss1) == float(loss2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    gn = float(aux["grad_norm"])
    assert np.isfinite(gn) and gn > 0


def test_trainer_grad_norm_rejects_kernel_paths():
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD

    spec = Mo.make_model_spec(SIZES, 1, 32)
    with pytest.raises(ValueError, match="VMEM"):
        trainer.make_train_epoch(
            spec, SGD(0.01), fuse_mubatches=True, megakernel=True,
            with_grad_norm=True,
        )
    with pytest.raises(ValueError, match="VMEM"):
        trainer.make_train_run(
            spec, SGD(0.01), fuse_mubatches=True, epoch_kernel=True,
            with_grad_norm=True,
        )


def test_executor_grad_norm_matches_sequential():
    """The mesh aux norm equals the sequential aux norm for the same model
    and data (same ledger, reduced over the mesh axes)."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.optimizer import SGD
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh

    B, M = 32, 4
    rng = np.random.RandomState(5)
    Xb = rng.randn(B, SIZES[0]).astype(np.float32)
    Yb = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], B)]

    spec1 = Mo.make_model_spec(SIZES, 1, B)
    p0 = jax.tree.map(jnp.asarray, Mo.init_model(spec1))
    seq = trainer.make_train_epoch(spec1, SGD(0.01), with_grad_norm=True)
    _, _, _, aux_seq = seq(
        p0, (),
        jnp.asarray(Xb.reshape(1, M, B // M, -1)),
        jnp.asarray(Yb.reshape(1, M, B // M, -1)),
    )

    mesh = make_mesh(2, 2)
    spec = Mo.make_model_spec(SIZES, 2, B)
    prog = lower_schedule(S.GPipeSchedule, M, 2)
    stacked, flags = E.init_stacked(spec, mesh)
    step = E.make_pipeline_step(
        mesh, spec, prog, B // 2 // M, SGD(0.01), with_grad_norm=True
    )
    _, _, loss, gnorm = step(
        stacked, flags, (), jnp.asarray(Xb), jnp.asarray(Yb)
    )
    np.testing.assert_allclose(
        float(gnorm), float(aux_seq["grad_norm"]), rtol=2e-4
    )

    # zero1 path computes the same norm from the scattered chunks
    from shallowspeed_tpu.optimizer import MomentumSGD

    opt_z = MomentumSGD(0.01, 0.9)
    st_z, fl_z = E.init_stacked(spec, mesh)
    oz = E.zero1_init_state(opt_z, spec, mesh)
    step_z = E.make_pipeline_step(
        mesh, spec, prog, B // 2 // M, opt_z, zero1=True, clip_norm=1.0,
        with_grad_norm=True,
    )
    _, _, _, gnorm_z = step_z(st_z, fl_z, oz, jnp.asarray(Xb), jnp.asarray(Yb))
    np.testing.assert_allclose(float(gnorm_z), float(gnorm), rtol=2e-4)


# ---------------------------------------------------------------------------
# TrainingSession end-to-end telemetry
# ---------------------------------------------------------------------------


def _epoch_events(recs):
    return [r for r in recs if r.get("kind") == "event" and r.get("name") == "epoch"]


@pytest.mark.parametrize(
    "kw", [dict(), dict(dp=2, pp=2, schedule="gpipe")], ids=["seq", "dp2pp2"]
)
def test_session_emits_per_epoch_records(data_dir, tmp_path, kw):
    """The acceptance contract: >= 1 record per epoch with epoch/loss/
    samples_per_sec plus a compile-time span record, on the single-device
    AND the dp=2,pp=2 CPU-mesh layouts."""
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "metrics.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m, **kw,
        )
        for _ in range(2):
            run.train_epoch()
    recs = read_jsonl(path)
    epochs = _epoch_events(recs)
    assert len(epochs) == 2
    for i, r in enumerate(epochs):
        assert r["epoch"] == i
        assert np.isfinite(r["loss"])
        assert r["samples_per_sec"] > 0
    # the first dispatch compiles (the AOT probe can't warm the jit call
    # cache), so its record is honestly flagged and later ones are not
    assert epochs[0]["includes_compile"] is True
    assert "includes_compile" not in epochs[1]
    spans = [r for r in recs if r.get("kind") == "span"]
    assert any(s["name"] == "jit_compile" for s in spans)
    assert any(s["name"] == "train_epoch" for s in spans)
    assert any(s["name"] == "device_put" for s in spans)
    # the recorder's stream holds the construction's phases too, each with
    # its nesting path
    paths = {s["path"] for s in spans}
    assert {"session/init", "session/init/session/data",
            "session/init/session/weights/device_put",
            "session/init/session/program",
            "train_epoch/epoch/dispatch", "train_epoch/epoch/readback"} <= paths
    if kw:  # mesh layout: lowering span + the static program stats event
        assert "session/init/session/lower" in paths
        progs = [r for r in recs if r.get("name") == "pipeline_program"]
        assert len(progs) == 1
        assert progs[0]["schedule"] == "gpipe" and progs[0]["num_stages"] == 2
        assert 0.0 < progs[0]["bubble_fraction"] < 1.0


def test_session_records_grad_norm_when_clipping(data_dir, tmp_path):
    from shallowspeed_tpu.api import TrainingSession

    for kw in (dict(), dict(dp=2, pp=2, schedule="gpipe")):
        path = tmp_path / "gn.jsonl"
        with JsonlMetrics(path) as m:
            run = TrainingSession(
                sizes=SIZES, global_batch_size=GBS, lr=0.01, clip_norm=1.0,
                data_dir=data_dir, metrics=m, **kw,
            )
            run.train_epoch()
        (rec,) = _epoch_events(read_jsonl(path))
        assert np.isfinite(rec["grad_norm"]) and rec["grad_norm"] > 0


def test_session_fused_run_emits_per_epoch_records(data_dir, tmp_path):
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "run.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, clip_norm=1.0,
            data_dir=data_dir, metrics=m,
        )
        losses, accs = run.train_run(3)
    recs = read_jsonl(path)
    epochs = _epoch_events(recs)
    assert len(epochs) == 3
    for e, r in enumerate(epochs):
        assert r["epoch"] == e and r["fused_run"] is True
        assert r["loss"] == losses[e] and r["accuracy"] == accs[e]
        assert np.isfinite(r["grad_norm"]) and r["samples_per_sec"] > 0
    assert any(
        r.get("kind") == "span" and r["name"] == "jit_compile" for r in recs
    )


def test_jsonl_stays_strict_json_under_non_finite_values(tmp_path):
    """The blow-up evidence must stay parseable: non-finite floats are
    sanitized to "NaN"/"Infinity"/"-Infinity" strings so every line is
    STRICT JSON (json.dumps's default would write bare NaN tokens exactly
    on the records the health feature exists to produce)."""
    path = tmp_path / "nan.jsonl"
    with JsonlMetrics(path) as m:
        m.step("train", step=0, epoch=0, loss=float("nan"),
               grad_norm=float("inf"), param_norm=-float("inf"))
        m.health("non_finite", epoch=0, step=0, value=float("nan"),
                 action="halt", detail="loss is nan")
        m.event("weird", nested={"a": [1.0, float("nan")]})

    def no_constants(name):  # bare NaN/Infinity tokens are a parse error
        raise ValueError(f"non-strict JSON token {name!r}")

    lines = path.read_text().splitlines()
    recs = [json.loads(l, parse_constant=no_constants) for l in lines]
    step = recs[1]
    assert step["loss"] == "NaN" and step["grad_norm"] == "Infinity"
    assert step["param_norm"] == "-Infinity"
    assert recs[2]["value"] == "NaN"
    assert recs[3]["nested"]["a"] == [1.0, "NaN"]


def test_schema_v2_and_v3_kinds(tmp_path):
    """Schema v2/v3: the step/health/xla_audit record kinds round-trip with
    the version stamp, and NullMetrics no-ops them."""
    path = tmp_path / "v3.jsonl"
    with JsonlMetrics(path) as m:
        m.step("train", step=0, epoch=0, loss=0.5, grad_norm=0.1, param_norm=9.0)
        m.health("non_finite", epoch=0, step=3, action="warn", detail="x")
        m.audit(
            "epoch_program",
            census={"all_reduce": {"count": 14, "bytes": 4096}},
            census_ok=True,
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "step", "health", "xla_audit"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["step"] == 0 and recs[1]["param_norm"] == 9.0
    assert recs[2]["name"] == "non_finite" and recs[2]["action"] == "warn"
    assert recs[3]["name"] == "epoch_program" and recs[3]["census_ok"] is True
    assert recs[3]["census"]["all_reduce"]["count"] == 14
    n = NullMetrics()
    n.step("train", loss=0.5)
    n.health("non_finite", step=1)
    n.audit("epoch_program", census_ok=True)


def test_schema_v3_reader_accepts_v1_and_v2_unchanged(tmp_path):
    """The compat contract (docs/observability.md): v3 is additive, so the
    v3 reader accepts v1 AND v2 files unchanged, the strict refusal stays
    one-directional (only records NEWER than the reader), and the new
    xla_audit kind round-trips through the non-finite-float sanitizer."""
    # v1 and v2 files, as their writers produced them
    v1 = tmp_path / "v1.jsonl"
    v1.write_text(
        json.dumps({"v": 1, "ts": 0.0, "kind": "event", "name": "epoch",
                    "epoch": 0, "loss": 0.5}) + "\n"
    )
    v2 = tmp_path / "v2.jsonl"
    v2.write_text(
        json.dumps({"v": 2, "ts": 0.0, "kind": "step", "name": "train",
                    "step": 0, "loss": 0.5}) + "\n"
        + json.dumps({"v": 2, "ts": 0.0, "kind": "health",
                      "name": "non_finite", "action": "warn"}) + "\n"
    )
    assert read_jsonl(v1)[0]["loss"] == 0.5
    assert [r["kind"] for r in read_jsonl(v2)] == ["step", "health"]
    # one-directional: only NEWER records are refused
    v4 = tmp_path / "v4.jsonl"
    v4.write_text(json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(v4)
    # xla_audit through the sanitizer: a non-finite nested field (e.g. an
    # unknown-peak division) stays STRICT JSON
    path = tmp_path / "audit.jsonl"
    with JsonlMetrics(path) as m:
        m.audit(
            "epoch_program",
            expected={"comms_time_per_step_s": float("inf"),
                      "bytes": [1.0, float("nan")]},
            census_ok=True,
        )
    raw = [json.loads(l, parse_constant=lambda s: (_ for _ in ()).throw(
        ValueError(s))) for l in path.read_text().splitlines()]
    assert raw[1]["expected"]["comms_time_per_step_s"] == "Infinity"
    assert raw[1]["expected"]["bytes"] == [1.0, "NaN"]
    assert read_jsonl(path)[1]["census_ok"] is True


def test_schema_v4_checkpoint_and_recovery_kinds(tmp_path):
    """Schema v4 (additive): the checkpoint/recovery record kinds round-trip
    with the version stamp, the v4 reader accepts v1-v3 files unchanged
    (the refusal stays one-directional), and NullMetrics no-ops the new
    hooks."""
    path = tmp_path / "v4.jsonl"
    with JsonlMetrics(path) as m:
        m.checkpoint(
            "step", path="/tmp/ck/step-00000008.npz", epoch=1,
            step_in_epoch=0, global_step=8, bytes=4096, wall_s=0.01,
        )
        m.recovery(
            "resumed", resumed_from="/tmp/ck/step-00000008.npz", epoch=1,
            step_in_epoch=0, global_step=8,
            skipped=[{"path": "/tmp/ck/step-00000012.npz",
                      "cause": "content checksum mismatch"}],
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "checkpoint", "recovery"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["name"] == "step" and recs[1]["global_step"] == 8
    assert recs[2]["name"] == "resumed"
    assert recs[2]["skipped"][0]["cause"] == "content checksum mismatch"
    # v1-v3 files load unchanged under the v4 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (2, {"kind": "step", "name": "train", "step": 0, "loss": 0.5}),
        (3, {"kind": "xla_audit", "name": "epoch_program", "census_ok": True}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    v5 = tmp_path / "v5.jsonl"
    v5.write_text(json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(v5)
    n = NullMetrics()
    n.checkpoint("step", global_step=8)
    n.recovery("resumed", global_step=8)


def test_schema_v5_request_and_serving_kinds(tmp_path):
    """Schema v5 (additive): the request/serving record kinds round-trip
    with the version stamp AND the non-finite sanitizer, the v5 reader
    accepts v1-v4 files unchanged, a newer file is refused (the strict
    check stays one-directional), and NullMetrics no-ops the new hooks."""
    path = tmp_path / "v5.jsonl"
    with JsonlMetrics(path) as m:
        m.request(
            "ok", id=3, rows=5, slots=1, enqueue_ts=1.0, dispatch_ts=1.5,
            complete_ts=2.0, latency_s=1.0, queue_s=0.5, deadline_ms=None,
            slo_ok=True,
        )
        m.request(
            "dropped", id=4, rows=2, slots=1, enqueue_ts=2.0,
            dispatch_ts=None, complete_ts=None,
            latency_s=float("nan"),  # through the sanitizer
            queue_s=None, deadline_ms=10.0, slo_ok=False,
        )
        m.serving(
            "summary", completed=7, dropped=1, offered_rps=100.0,
            p50_latency_s=0.01, p99_latency_s=float("inf"),
            goodput_rps=88.0, padding_waste=0.25, queue_depth_max=3,
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "request", "request", "serving"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["name"] == "ok" and recs[1]["slo_ok"] is True
    assert recs[2]["name"] == "dropped" and recs[2]["latency_s"] == "NaN"
    assert recs[3]["p99_latency_s"] == "Infinity"
    assert recs[3]["goodput_rps"] == 88.0
    # every line stays STRICT JSON (no bare NaN/Infinity tokens)
    raw = [json.loads(l, parse_constant=lambda s: (_ for _ in ()).throw(
        ValueError(s))) for l in path.read_text().splitlines()]
    assert len(raw) == 4
    # v1-v4 files load unchanged under the v5 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (2, {"kind": "step", "name": "train", "step": 0, "loss": 0.5}),
        (3, {"kind": "xla_audit", "name": "epoch_program", "census_ok": True}),
        (4, {"kind": "checkpoint", "name": "step", "global_step": 8}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    # one-directional refusal: a newer file fails loudly
    v6 = tmp_path / "newer.jsonl"
    v6.write_text(json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(v6)
    n = NullMetrics()
    n.request("ok", id=0, rows=1)
    n.serving("summary", completed=1)


def test_schema_v6_serving_health_and_reload_kinds(tmp_path):
    """Schema v6 (additive): the serving_health/reload record kinds — the
    serving degradation evidence stream — round-trip with the version
    stamp AND the non-finite sanitizer, the reader accepts v1-v5 files
    unchanged, a future-versioned file is refused (the strict check stays
    one-directional), and NullMetrics no-ops the new hooks."""
    path = tmp_path / "v6.jsonl"
    with JsonlMetrics(path) as m:
        m.serving_health(
            "breaker_open", dispatch=7, consecutive_failures=3,
        )
        m.serving_health(
            "unhealthy_dispatch", dispatch=6,
            worst_value=float("nan"),  # through the sanitizer
        )
        m.reload(
            "ok", path="/tmp/ck/step-00000008.npz", step=8, reason="breaker",
            wall_s=0.01, programs_cached=3,
        )
        m.reload(
            "failed", path="/tmp/ck", reason="watch",
            error="checksum mismatch", wall_s=float("inf"),
        )
        # the v6-extended request verdicts ride the existing kind
        m.request("expired", id=1, rows=2, slots=1, attempts=0,
                  reason="deadline")
        m.request("error", id=2, rows=1, slots=1, attempts=2,
                  reason="InjectedFault: injected")
        m.request("unhealthy", id=3, rows=1, slots=1, attempts=0)
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == [
        "meta", "serving_health", "serving_health", "reload", "reload",
        "request", "request", "request",
    ]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["name"] == "breaker_open" and recs[1]["dispatch"] == 7
    assert recs[2]["worst_value"] == "NaN"
    assert recs[3]["name"] == "ok" and recs[3]["step"] == 8
    assert recs[4]["wall_s"] == "Infinity"
    assert [r["name"] for r in recs[5:]] == ["expired", "error", "unhealthy"]
    assert recs[6]["attempts"] == 2
    # every line stays STRICT JSON (no bare NaN/Infinity tokens)
    raw = [json.loads(l, parse_constant=lambda s: (_ for _ in ()).throw(
        ValueError(s))) for l in path.read_text().splitlines()]
    assert len(raw) == 8
    # v1-v5 files load unchanged under the v6 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (2, {"kind": "step", "name": "train", "step": 0, "loss": 0.5}),
        (3, {"kind": "xla_audit", "name": "epoch_program", "census_ok": True}),
        (4, {"kind": "checkpoint", "name": "step", "global_step": 8}),
        (5, {"kind": "serving", "name": "summary", "completed": 7}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    # one-directional refusal: a future-versioned file fails loudly
    v_next = tmp_path / "vnext.jsonl"
    v_next.write_text(
        json.dumps({"v": SCHEMA_VERSION + 1, "kind": "event"}) + "\n"
    )
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(v_next)
    n = NullMetrics()
    n.serving_health("breaker_open", dispatch=1)
    n.reload("ok", path="x")


def test_schema_v7_fleet_kinds(tmp_path):
    """Schema v7 (additive): the fleet/fleet_health record kinds — the
    serving fleet's evidence stream, every event tagged replica_id —
    round-trip with the version stamp, and the reader accepts v1-v6
    files unchanged. (The version pin and the one-ahead refusal live
    with the NEWEST schema's test — test_schema_v9_static_analysis —
    so a bump edits exactly one test.)"""
    path = tmp_path / "v7.jsonl"
    with JsonlMetrics(path) as m:
        m.fleet_health("replica_spawned", replica_id=0, checkpoint=None)
        m.fleet_health("replica_ready", replica_id=0, wall_s=1.5)
        m.fleet_health("replica_dead", replica_id=0, inflight=3, error=None)
        m.fleet_health("failover", replica_id=0, requeued=3, exhausted=0)
        m.fleet(
            "summary",
            completed=40, dropped=0, failovers=1, reroutes=2,
            routing={0: 21, 1: 19}, routing_skew=1.05,
            per_replica={0: {"routed": 21, "verdicts": {"ok": 21}}},
            recovery_s=0.004,
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == [
        "meta", "fleet_health", "fleet_health", "fleet_health",
        "fleet_health", "fleet",
    ]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert all(
        "replica_id" in r for r in recs if r["kind"] == "fleet_health"
    )
    assert recs[4]["name"] == "failover" and recs[4]["requeued"] == 3
    assert recs[5]["routing"] == {"0": 21, "1": 19}  # JSON stringifies keys
    # v1-v6 files load unchanged under the v7 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (5, {"kind": "serving", "name": "summary", "completed": 7}),
        (6, {"kind": "serving_health", "name": "breaker_open", "dispatch": 3}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    n = NullMetrics()
    n.fleet("summary", completed=1)
    n.fleet_health("replica_dead", replica_id=0)


def test_schema_v8_async_ckpt(tmp_path):
    """Schema v8 (additive): the async-writer fields on checkpoint and
    verify_s on reload — round-trip with the version stamp — and the v8
    reader accepts v1-v7 files unchanged. The kind v8 added has no writer
    any more; a line of it from an old file still reads. (Version pin +
    one-ahead refusal live with the newest schema's test, per the bump
    convention.)"""
    path = tmp_path / "v8.jsonl"
    with JsonlMetrics(path) as m:
        m.checkpoint(
            "step", path="ck/step-00000004.npz", global_step=4, bytes=100,
            wall_s=0.001, **{"async": True}, queue_depth=1,
            verify_s=0.0005, write_s=0.002, queued_s=0.0001,
        )
        m.reload("ok", path="ck/step-00000008.npz", step=8, reason="watch",
                 wall_s=0.01, verify_s=0.004)
    recs = read_jsonl(path)
    kinds = [r["kind"] for r in recs]
    assert kinds == ["meta", "checkpoint", "reload"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    ck = recs[1]
    assert ck["async"] is True and ck["queue_depth"] == 1
    assert ck["verify_s"] == 0.0005 and ck["write_s"] == 0.002
    assert recs[2]["verify_s"] == 0.004
    # v1-v7 files load unchanged under the v8 reader
    for v, rec in (
        (4, {"kind": "checkpoint", "name": "step", "global_step": 2}),
        (6, {"kind": "reload", "name": "ok", "path": "x"}),
        (7, {"kind": "fleet", "name": "summary", "completed": 3}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    # a v8 file written while the executable cache existed still reads,
    # strictly: the kind stays in the kind -> version table
    old = tmp_path / "old-v8.jsonl"
    old.write_text(
        json.dumps({"v": 8, "ts": 0.0, "kind": "aot_cache", "name": "hit",
                    "program": "inference_r4", "key": "ab12"}) + "\n"
    )
    assert read_jsonl(old, strict=True)[0]["name"] == "hit"
    assert not hasattr(NullMetrics(), "aot_cache")


def test_schema_v9_static_analysis(tmp_path):
    """Schema v9 (additive): the static_analysis kind (one verdict per
    analyzed program: pass list, per-pass stats, finding count) plus the
    SCHEMA_KINDS registry — round-trip with the version stamp, the v9
    reader accepts v1-v8 files unchanged, and NullMetrics no-ops the
    hook. (Version pin + one-ahead refusal live with the newest schema's
    test — test_schema_v10_trace — per convention.)"""
    from shallowspeed_tpu.observability.metrics import SCHEMA_KINDS

    assert SCHEMA_KINDS["static_analysis"] == 9
    path = tmp_path / "v9.jsonl"
    with JsonlMetrics(path) as m:
        m.static_analysis(
            "epoch_program",
            passes=["send_recv", "deadlock", "stash"],
            findings=0,
            send_recv={"sends_fwd": 12, "sends_bwd": 12},
            stash={"stash": {"peak": 4}},
        )
        m.static_analysis(
            "inference_r2",
            passes=["send_recv", "deadlock", "stash"],
            findings=1,
            finding="tick 3 stage 1: reads fwd mailbox slot 0 which holds"
                    " no message",
        )
        m.static_analysis("lint", passes=["BLE001"], findings=0)
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == [
        "meta", "static_analysis", "static_analysis", "static_analysis",
    ]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["findings"] == 0 and recs[1]["send_recv"]["sends_fwd"] == 12
    assert "tick 3" in recs[2]["finding"]
    # v1-v8 files load unchanged under the current reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (3, {"kind": "xla_audit", "name": "epoch_program", "census": {}}),
        (8, {"kind": "aot_cache", "name": "hit", "program": "x"}),
    ):
        p = tmp_path / f"old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    NullMetrics().static_analysis("epoch_program", findings=0)


def test_schema_v10_trace(tmp_path):
    """Schema v10 (additive): the ``trace`` kind — one closed span per
    record with trace/span/parent ids, raw clock-domain endpoints and the
    terminal flag, plus the ``clock_offset`` alignment records — round
    trips with the version stamp (non-finite endpoint values survive the
    strict-JSON sanitizer as strings), the v10+ reader accepts v1-v9
    files unchanged, and NullMetrics no-ops the hook. (The version pin
    and one-ahead refusal moved to the v11 test — the newest-schema
    convention.)"""
    from shallowspeed_tpu.observability.metrics import SCHEMA_KINDS

    assert SCHEMA_KINDS["trace"] == 10
    path = tmp_path / "v10.jsonl"
    with JsonlMetrics(path) as m:
        m.trace(
            "worker.queue", trace_id="f-3", span_id="r0.1", parent_id="f.2",
            t0=10.5, t1=10.9, clock="worker", replica_id=0, terminal=False,
        )
        m.trace(
            "ack", trace_id="f-3", span_id="f.9", parent_id="r0.4",
            t0=11.0, t1=11.0, clock="parent", replica_id=None,
            terminal=True, verdict="ok",
        )
        m.trace(
            "clock_offset", trace_id=None, span_id=None, parent_id=None,
            t0=None, t1=None, clock="parent", replica_id=0,
            offset_s=3.0001, rtt_s=0.0004, uncertainty_s=0.0002,
        )
        # a blown-up duration must survive as STRICT JSON (the sanitizer
        # contract every schema bump re-proves on its new kind)
        m.trace(
            "dispatch", trace_id="f-4", span_id="r0.2", parent_id=None,
            t0=1.0, t1=float("nan"), clock="worker", replica_id=0,
            terminal=False,
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta"] + ["trace"] * 4
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["trace_id"] == "f-3" and recs[1]["parent_id"] == "f.2"
    assert recs[2]["terminal"] is True and recs[2]["verdict"] == "ok"
    assert recs[3]["name"] == "clock_offset" and recs[3]["offset_s"] == 3.0001
    assert recs[4]["t1"] == "NaN"  # sanitized, line stayed parseable
    # v1-v9 files load unchanged under the v10 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (5, {"kind": "request", "name": "ok", "id": 1}),
        (9, {"kind": "static_analysis", "name": "lint", "findings": 0}),
    ):
        p = tmp_path / f"trace-old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    NullMetrics().trace("worker.queue", trace_id="x")


def test_schema_v11_rollup_alert(tmp_path):
    """Schema v11 (additive): the ``rollup`` (closed tumbling-window
    summary) and ``alert`` (firing/resolved transition) kinds round trip
    with the version stamp, the v11+ reader accepts v1-v10 files
    unchanged, and NullMetrics no-ops both new hooks. (The version pin
    and one-ahead refusal moved to the v12 test — the newest-schema
    convention.)"""
    from shallowspeed_tpu.observability.metrics import SCHEMA_KINDS

    assert SCHEMA_KINDS["rollup"] == 11
    assert SCHEMA_KINDS["alert"] == 11
    path = tmp_path / "v11.jsonl"
    with JsonlMetrics(path) as m:
        m.rollup(
            "serving", window_start=12.0, window_end=13.0, window_s=1.0,
            seq=0, counters={"ok": 41, "terminal": 42}, late=0,
            rates={"terminal": {"rate": 42.0, "ewma": 40.1}},
            gauges={"queue_depth": {"last": 3, "min": 0, "max": 7}},
            quantiles={"latency_s": {"p50": 0.004, "p99": 0.02}},
            replica_id=None,
        )
        m.alert(
            "breaker_open", rule="breaker_open", state="firing",
            severity="page", t=12.75, value="breaker_open",
            threshold=None, burn_fast=None, burn_slow=None,
            reason="health event 'breaker_open'", replica_id=0,
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "rollup", "alert"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert recs[1]["counters"]["terminal"] == 42
    assert recs[1]["quantiles"]["latency_s"]["p99"] == 0.02
    assert recs[2]["state"] == "firing" and recs[2]["replica_id"] == 0
    # v1-v10 files load unchanged under the v11 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (5, {"kind": "request", "name": "ok", "id": 1}),
        (10, {"kind": "trace", "name": "ack", "trace_id": "f-1"}),
    ):
        p = tmp_path / f"rollup-old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    NullMetrics().rollup("serving", counters={})
    NullMetrics().alert("breaker_open", state="firing")


def test_schema_v12_digest(tmp_path):
    """Schema v12 (additive): the ``digest`` kind — one numerics-provenance
    row per optimizer step, with per-global-layer crc/norm lists — round
    trips with the version stamp AND the non-finite sanitizer, the v12
    reader accepts v1-v11 files unchanged, and NullMetrics no-ops the
    hook. (The version pin and one-ahead refusal moved to the v13 test —
    the newest-schema convention.)"""
    from shallowspeed_tpu.observability.metrics import SCHEMA_KINDS

    assert SCHEMA_KINDS["digest"] == 12
    path = tmp_path / "v12.jsonl"
    with JsonlMetrics(path) as m:
        m.digest(
            "train", step=7, epoch=1, layers=2,
            crc_w=[0x89BB9AF3, 1], crc_b=[0, 0xFFFFFFFF],
            pnorm_w=[3.25, 0.5], pnorm_b=[0.125, 0.0625],
            # a blown-up run's norms must survive as STRICT JSON (the
            # sanitizer contract every schema bump re-proves)
            gnorm_w=[float("nan"), 1.0], gnorm_b=[0.5, float("inf")],
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "digest"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    d = recs[1]
    assert d["step"] == 7 and d["layers"] == 2
    assert d["crc_w"] == [0x89BB9AF3, 1] and d["crc_b"][1] == 0xFFFFFFFF
    assert d["gnorm_w"][0] == "NaN" and d["gnorm_b"][1] == "Infinity"
    # v1-v11 files load unchanged under the v12 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (5, {"kind": "request", "name": "ok", "id": 1}),
        (11, {"kind": "alert", "name": "breaker_open", "state": "firing"}),
    ):
        p = tmp_path / f"digest-old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    NullMetrics().digest("train", step=0, crc_w=[])


def test_schema_v13_autoscale(tmp_path):
    """Schema v13 (additive): the ``autoscale`` kind — one capacity
    decision with its evidence (rule, direction, fleet size before/
    after, rollup window, flap flag) — round trips with the version
    stamp, the v13 reader accepts v1-v12 files unchanged, a v14 file is
    refused, and NullMetrics no-ops the hook. Carries the version pin
    and the one-ahead refusal (the newest-schema convention)."""
    from shallowspeed_tpu.observability.metrics import SCHEMA_KINDS

    assert SCHEMA_VERSION == 13
    # the registry IS the docstring's kind list: every recorder hook has
    # a registered kind, and the newest kinds carry the newest version
    assert SCHEMA_KINDS["autoscale"] == 13
    assert max(SCHEMA_KINDS.values()) == SCHEMA_VERSION
    path = tmp_path / "v13.jsonl"
    with JsonlMetrics(path) as m:
        m.autoscale(
            "scale_out", direction="out", rule="knee_proximity", t=12.5,
            replicas_before=1, replicas_after=2, replicas_ready=1,
            queue_depth=4, window_end=12.0, value=43.7, threshold=40.5,
            flap=False, reason="admitted rate within 10% of the knee",
            leg="autoscaled",
        )
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["meta", "autoscale"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    d = recs[1]
    assert d["name"] == "scale_out" and d["direction"] == "out"
    assert d["replicas_before"] == 1 and d["replicas_after"] == 2
    assert d["rule"] == "knee_proximity" and d["flap"] is False
    # v1-v12 files load unchanged under the v13 reader
    for v, rec in (
        (1, {"kind": "event", "name": "epoch", "epoch": 0, "loss": 0.5}),
        (5, {"kind": "request", "name": "ok", "id": 1}),
        (11, {"kind": "alert", "name": "breaker_open", "state": "firing"}),
        (12, {"kind": "digest", "name": "train", "step": 0, "crc_w": [1]}),
    ):
        p = tmp_path / f"autoscale-old-v{v}.jsonl"
        p.write_text(json.dumps({"v": v, "ts": 0.0, **rec}) + "\n")
        assert read_jsonl(p)[0]["kind"] == rec["kind"]
    # one-directional refusal: a v14 file fails loudly
    v14 = tmp_path / "v14.jsonl"
    v14.write_text(json.dumps({"v": 14, "kind": "event"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_jsonl(v14)
    NullMetrics().autoscale("scale_out", direction="out")


def test_replica_shard_suffix_and_fallback_read(tmp_path):
    """Fleet workers reuse the multihost shard convention as .r{id}:
    replica_shard_path names each worker's own JSONL shard, an explicit
    glob merges parent + shards, and the bare-path fallback resolves a
    missing base to its .r shards (never to look-alike neighbors)."""
    from shallowspeed_tpu.observability.metrics import replica_shard_path

    base = tmp_path / "fleet.jsonl"
    assert replica_shard_path(base, 2) == str(base) + ".r2"
    for rid in (0, 1):
        with JsonlMetrics(replica_shard_path(base, rid)) as m:
            m.request("ok", id=rid, rows=1, slots=1)
    # a look-alike neighbor must never be merged by the BARE-PATH
    # fallback (an explicit glob is the caller's own choice)
    decoy = tmp_path / "fleet.jsonl.rpartial"
    decoy.write_text("not json\n")
    # bare-path fallback: base missing -> its .r shards, sorted
    recs = read_jsonl(base)
    assert [r["id"] for r in recs if r["kind"] == "request"] == [0, 1]
    decoy.unlink()
    # parent + shards via explicit glob once the base exists too
    with JsonlMetrics(base) as m:
        m.fleet("summary", completed=2)
    recs2 = read_jsonl(str(base) + "*")
    kinds = [r["kind"] for r in recs2 if r["kind"] != "meta"]
    assert kinds.count("request") == 2 and kinds.count("fleet") == 1


def test_percentile_single_shared_definition():
    """Satellite: the ONE percentile helper equals np.percentile exactly
    (not approximately) on arbitrary data, ignores None samples, and
    returns None — never 0.0 — when nothing was measured. The engine
    summary, fleet summary and report fallback all call it, so p99 can
    no longer disagree with itself across consumers."""
    from shallowspeed_tpu.observability import percentile

    rng = np.random.RandomState(7)
    for n in (1, 2, 3, 10, 100, 101):
        vals = list(rng.exponential(0.01, size=n))
        for q in (0, 50, 90, 99, 100):
            assert percentile(vals, q) == float(
                np.percentile(np.asarray(vals, np.float64), q)
            )
    assert percentile([None, 3.0, None, 1.0], 50) == 2.0
    assert percentile([], 99) is None
    assert percentile([None, None], 99) is None


def test_throughput_window_single_shared_definition():
    """Satellite: the ONE first-enqueue -> last-complete window helper
    (the engine's and fleet's previously copy-pasted
    _first_enqueue_t/_last_complete_t bookkeeping). Min-enqueue /
    max-complete whatever the call order, None until BOTH ends exist —
    an unmeasured window must not read as an instant one — and reset
    clears it for the bench sweep's per-rate boundary."""
    from shallowspeed_tpu.observability import ThroughputWindow

    w = ThroughputWindow()
    assert w.window_s is None
    w.note_enqueue(10.0)
    assert w.window_s is None  # half a window is no window
    w.note_complete(11.5)
    assert w.window_s == 1.5
    # out-of-order notes keep the extremes (completions finish out of
    # enqueue order under continuous batching)
    w.note_enqueue(9.0)
    w.note_complete(11.0)
    assert w.window_s == 2.5
    w.reset()
    assert w.window_s is None and w.first_enqueue_t is None


def test_jsonl_multihost_shard_suffix_and_glob_read(tmp_path, monkeypatch):
    """Multihost JSONL safety: under process_count > 1 every host writes
    its own .p{index} shard (no interleaved writes into one file), and
    read_jsonl accepts a glob of shards — plus the bare-path auto-fallback
    the report CLI rides."""
    import jax

    from shallowspeed_tpu.parallel import multihost

    base = tmp_path / "multi.jsonl"
    # a live 2-process distributed runtime, as the probe sees it (the
    # distributed-state gate first — it keeps the probe from initializing the
    # backend in single-process runs — then the public process surface)
    monkeypatch.setattr(multihost, "_distributed_is_initialized", lambda: True)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    with JsonlMetrics(base) as m:
        assert m.path == str(base) + ".p1"
        m.event("epoch", epoch=0, loss=0.5)
    assert not base.exists()
    shard1 = tmp_path / "multi.jsonl.p1"
    assert shard1.exists()
    # a second host's shard, written independently
    shard0 = tmp_path / "multi.jsonl.p0"
    shard0.write_text(
        json.dumps({"v": SCHEMA_VERSION, "ts": 0.0, "kind": "event",
                    "name": "epoch", "epoch": 0, "loss": 0.25}) + "\n"
    )
    # explicit glob: sorted shard order, concatenated
    recs = read_jsonl(str(base) + ".p*")
    assert [r["loss"] for r in recs if r["kind"] == "event"] == [0.25, 0.5]
    # bare-path fallback: the unsharded name resolves to its shards
    recs2 = read_jsonl(base)
    assert len(recs2) == len(recs)
    # a missing glob refuses loudly
    with pytest.raises(FileNotFoundError):
        read_jsonl(str(tmp_path / "nope-*.jsonl"))


def test_shard_path_single_process_is_identity(tmp_path):
    """With one jax process (the normal case) the path is untouched."""
    from shallowspeed_tpu.observability.metrics import _shard_path

    assert _shard_path(tmp_path / "x.jsonl") == str(tmp_path / "x.jsonl")


@pytest.mark.parametrize(
    "kw", [dict(), dict(dp=2, pp=2, schedule="gpipe")], ids=["seq", "dp2pp2"]
)
def test_session_emits_step_records(data_dir, tmp_path, kw):
    """The flight recorder: one schema-v2 step record per optimizer step on
    BOTH layouts, globally numbered, with finite loss/grad/param norms, and
    the ring buffer holding the same samples."""
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "steps.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m, **kw,
        )
        for _ in range(2):
            run.train_epoch()
    recs = read_jsonl(path)
    steps = [r for r in recs if r["kind"] == "step"]
    nb = run.batches_per_epoch
    assert len(steps) == 2 * nb
    assert [s["step"] for s in steps] == list(range(2 * nb))
    assert steps[nb]["epoch"] == 1
    for s in steps:
        assert np.isfinite(s["loss"])
        assert np.isfinite(s["grad_norm"]) and s["grad_norm"] > 0
        assert np.isfinite(s["param_norm"]) and s["param_norm"] > 0
    assert len(run.flight) == 2 * nb and run.flight.total_steps == 2 * nb
    assert run.flight.last(1)[0]["step"] == 2 * nb - 1


def test_record_steps_false_opts_out_of_flight_aux(data_dir, tmp_path):
    """record_steps=False keeps a metrics session at the PR1 cost profile:
    epoch events only, no step records, no per-step aux in the program."""
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "optout.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m, record_steps=False,
        )
        run.train_epoch()
    assert run._step_aux is False and run.flight is None
    recs = read_jsonl(path)
    assert [r for r in recs if r["kind"] == "step"] == []
    assert len(_epoch_events(recs)) == 1
    # record_steps=True forces the flight ring on even without a recorder
    run2 = TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
        record_steps=True,
    )
    run2.train_epoch()
    assert len(run2.flight) == run2.batches_per_epoch


def test_warm_run_first_session_still_records_xla_crosscheck(data_dir, tmp_path):
    """A train_run-before-train_epoch session must not lose the XLA
    cost_analysis leg: the early (analytical-only) cost_model event is
    upgraded once the epoch program compiles (last event wins)."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.observability.costmodel import compiled_flops

    path = tmp_path / "runfirst.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m,
        )
        run.train_run(1, with_eval=False)
        run.train_epoch()
    events = [r for r in read_jsonl(path) if r.get("name") == "cost_model"]
    if compiled_flops(run._epoch_fn.lower(*run._epoch_args()).compile())[0] is None:
        pytest.skip("backend exposes no cost_analysis flops")
    assert events[0]["xla_flops_per_epoch"] is None  # pre-compile record
    assert events[-1]["xla_flops_per_epoch"] > 0  # upgraded record
    assert events[-1]["flops_ratio"] > 0


def test_step_aux_matches_epoch_mean(data_dir, tmp_path):
    """The per-step vectors are the same numbers the epoch aggregates: the
    mean of step losses IS the epoch loss record."""
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "agg.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m,
        )
        loss = run.train_epoch()
    steps = [r for r in read_jsonl(path) if r["kind"] == "step"]
    np.testing.assert_allclose(
        np.mean([s["loss"] for s in steps]), loss, rtol=1e-6
    )


def test_session_emits_cost_model_and_mfu(data_dir, tmp_path):
    """MFU accounting: the cost_model event (analytical + XLA cross-check
    legs, peak provenance) and per-epoch mfu/achieved_flops gauges."""
    from shallowspeed_tpu.api import TrainingSession
    from shallowspeed_tpu.observability.costmodel import (
        mlp_train_flops_per_sample,
    )

    path = tmp_path / "mfu.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m, dp=2, pp=2, schedule="gpipe",
        )
        run.train_epoch()
    recs = read_jsonl(path)
    (cost,) = [r for r in recs if r.get("name") == "cost_model"]
    fps = mlp_train_flops_per_sample(SIZES)
    assert cost["flops_per_sample"] == fps
    assert cost["flops_per_epoch"] == fps * GBS * run.batches_per_epoch
    assert cost["n_devices"] == 4 and cost["peak_flops_per_chip"] > 0
    assert "peak_source" in cost
    # padded pipeline FLOPs from the actual tick tables: >= logical
    assert cost["padded_ratio"] >= 1.0
    gauges = {r["name"]: r["value"] for r in recs if r["kind"] == "gauge"}
    # the FLOPs of an epoch are a field of the cost_model event (above); the
    # gauge that repeated them had no reader and went (PR 37)
    assert "model_flops" not in gauges
    assert gauges["achieved_flops_per_sec"] > 0
    assert 0 < gauges["mfu"] < 1.5  # a utilization, not a raw FLOP count
    (ep,) = _epoch_events(recs)
    assert np.isfinite(ep["mfu"])


def test_mesh_fused_run_reports_grad_norm(data_dir, tmp_path):
    """The satellite contract: make_pipeline_run now threads the grad-norm
    aux, so MESH fused-run epoch records carry grad_norm too (this was the
    documented gap in docs/observability.md)."""
    from shallowspeed_tpu.api import TrainingSession

    path = tmp_path / "meshrun.jsonl"
    with JsonlMetrics(path) as m:
        run = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, clip_norm=1.0,
            data_dir=data_dir, metrics=m, dp=2, pp=2, schedule="gpipe",
        )
        losses, accs = run.train_run(2)
    epochs = _epoch_events(read_jsonl(path))
    assert len(epochs) == 2
    for e, r in enumerate(epochs):
        assert r["fused_run"] is True and r["loss"] == losses[e]
        assert np.isfinite(r["grad_norm"]) and r["grad_norm"] > 0


def test_executor_step_stats_param_norm_matches_unstacked():
    """The mesh per-step param norm is the LOGICAL norm: padded entries are
    exactly zero, so the stacked pp-psum'd norm equals the norm of the
    unstacked parameters."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.optimizer import SGD, global_norm
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh

    B, M = 32, 4
    rng = np.random.RandomState(7)
    Xb = rng.randn(B, SIZES[0]).astype(np.float32)
    Yb = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], B)]
    mesh = make_mesh(2, 2)
    spec = Mo.make_model_spec(SIZES, 2, B)
    prog = lower_schedule(S.GPipeSchedule, M, 2)
    stacked, flags = E.init_stacked(spec, mesh)
    step = E.make_pipeline_step(
        mesh, spec, prog, B // 2 // M, SGD(0.01), with_step_stats=True
    )
    new_stacked, _, loss, gnorm, pnorm = step(
        stacked, flags, (), jnp.asarray(Xb), jnp.asarray(Yb)
    )
    logical = E.unstack_params(new_stacked, spec)
    expect = float(global_norm(jax.tree.map(jnp.asarray, logical)))
    np.testing.assert_allclose(float(pnorm), expect, rtol=2e-5)
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


def test_session_metrics_do_not_change_training(data_dir, tmp_path):
    """Telemetry is observation only: the recorded run trains to the exact
    same weights as the unrecorded one."""
    from shallowspeed_tpu.api import TrainingSession

    plain = TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir
    )
    with JsonlMetrics(tmp_path / "p.jsonl") as m:
        recorded = TrainingSession(
            sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
            metrics=m,
        )
        l1 = [plain.train_epoch() for _ in range(2)]
        l2 = [recorded.train_epoch() for _ in range(2)]
    assert l1 == l2
    assert plain.model_hash() == recorded.model_hash()
