"""XLA program audit tests: HLO collective parsing, memory analysis through
the shared helper, the analytical comms model, and the census-vs-contract
invariant across every layout (seq / DP / pipeline / ZeRO-1) — the
acceptance criterion that "the DP all-reduce really is one psum" is a
tested property of the COMPILED program, not prose.
"""

import json

import numpy as np
import pytest

from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl
from shallowspeed_tpu.observability import program_audit as pa

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
# HLO parsing
# ---------------------------------------------------------------------------

_SYNTHETIC_HLO = """\
HloModule jit_epoch, entry_computation_layout={...}

%region_0.4 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main {
  %p0 = f32[2,4]{1,0} parameter(0)
  %all-reduce.1 = f32[2,4]{1,0} all-reduce(f32[2,4]{1,0} %p0), replica_groups={{0,2},{1,3}}, to_apply=%region_0.4, metadata={op_name="jit(f)/psum"}
  %cp = f32[2,4]{1,0} collective-permute(f32[2,4]{1,0} %all-reduce.1), source_target_pairs={{0,1},{1,0}}
  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %p1), to_apply=%region_0.4
  %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
  %rs = f32[4]{0} reduce-scatter(f32[8]{0} %ard), dimensions={0}, to_apply=%region_0.4
  %ag = f32[8]{0} all-gather(f32[4]{0} %rs), dimensions={0}
  %tup = (f32[4]{0}, bf16[2,2]{1,0}) all-gather(f32[2]{0} %rs, bf16[1,2]{1,0} %x), dimensions={0}
  ROOT %out = f32[8]{0} copy(f32[8]{0} %ag)
}
"""


def test_parse_collectives_counts_kinds_and_bytes():
    """Kinds, byte sizes (incl. tuple results and bf16), async -start
    counted once with its -done half skipped, metadata op_name strings
    never matched."""
    ops = pa.parse_collectives(_SYNTHETIC_HLO)
    kinds = sorted(o["kind"] for o in ops)
    assert kinds == [
        "all_gather", "all_gather", "all_reduce", "all_reduce",
        "collective_permute", "reduce_scatter",
    ]
    census = pa.collective_census(_SYNTHETIC_HLO)
    assert census["all_reduce"]["count"] == 2  # plain + -start (not -done)
    assert census["all_reduce"]["bytes"] == 2 * 4 * 4 + 8 * 4
    assert census["collective_permute"] == {"count": 1, "bytes": 32}
    assert census["reduce_scatter"] == {"count": 1, "bytes": 16}
    # tuple result: f32[8] one op + (f32[4] + bf16[2,2]) the other
    assert census["all_gather"]["count"] == 2
    assert census["all_gather"]["bytes"] == 8 * 4 + (4 * 4 + 2 * 2 * 2)
    assert "all_to_all" not in census


def test_parse_collectives_ignores_non_collective_lines():
    hlo = "%f = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop\n%c = f32[] copy(%y)\n"
    assert pa.parse_collectives(hlo) == []
    assert pa.collective_census(hlo) == {}


def test_parse_collectives_tpu_tiled_layouts():
    """TPU post-optimization HLO: tiled layouts put PARENTHESES inside the
    result type (``{1,0:T(8,128)}``) and async collectives return tuples —
    a paren-naive tuple match would drop exactly the ops the audit exists
    to see (a correct dp program would then fail its own contract)."""
    hlo = (
        "%ars = (f32[8,128]{1,0:T(8,128)}, f32[8,128]{1,0:T(8,128)}) "
        "all-reduce-start(f32[8,128]{1,0:T(8,128)} %p), to_apply=%sum\n"
        "%ard = f32[8,128]{1,0:T(8,128)} all-reduce-done(%ars)\n"
        "%cp = f32[4,128]{1,0:T(8,128)(4,1)} collective-permute("
        "f32[4,128]{1,0:T(8,128)(4,1)} %x), source_target_pairs={{0,1}}\n"
    )
    census = pa.collective_census(hlo)
    assert census["all_reduce"]["count"] == 1  # -start counted, -done not
    # the start tuple pairs the aliased operand with the result; only the
    # result leg counts, so the payload is not double-counted
    assert census["all_reduce"]["bytes"] == 8 * 128 * 4
    assert census["collective_permute"]["count"] == 1
    assert census["collective_permute"]["bytes"] == 4 * 128 * 4


# ---------------------------------------------------------------------------
# the contract check (fails loudly on a mismatched census)
# ---------------------------------------------------------------------------


def test_check_census_contract_rules():
    seq = {"required": [], "forbidden": ["all_reduce", "collective_permute"]}
    assert pa.check_census({}, seq) == []
    assert pa.check_census({"all_reduce": {"count": 3, "bytes": 1}}, seq)

    dp = {"required": ["all_reduce", "collective_permute"],
          "forbidden": ["reduce_scatter", "all_gather"],
          "axes": {"pp": {"hlo_min_permute_ops": 2}}}
    ok = {"all_reduce": {"count": 14, "bytes": 1},
          "collective_permute": {"count": 2, "bytes": 1}}
    assert pa.check_census(ok, dp) == []
    # missing required kind
    assert pa.check_census({"collective_permute": {"count": 2, "bytes": 1}}, dp)
    # forbidden kind present (a ZeRO-1 lowering leaking into plain DP)
    bad = dict(ok, reduce_scatter={"count": 1, "bytes": 9})
    assert any("reduce_scatter" in m for m in pa.check_census(bad, dp))
    # a one-directional relay is a broken pipeline, even though the kind
    # is present
    one_way = dict(ok, collective_permute={"count": 1, "bytes": 1})
    assert any("every direction" in m for m in pa.check_census(one_way, dp))
    # unless the tick table sends one way only (an inference program)
    fwd_only = dict(dp, axes={"pp": {"hlo_min_permute_ops": 1}})
    assert pa.check_census(one_way, fwd_only) == []


def test_verify_census_raises_loudly_on_mismatch():
    """The acceptance criterion's negative leg: a deliberately mismatched
    census fails with AuditMismatchError naming the violation."""
    expected = {"required": ["all_reduce"], "forbidden": ["all_gather"]}
    census = {"all_gather": {"count": 1, "bytes": 64}}
    with pytest.raises(pa.AuditMismatchError, match="all_reduce"):
        pa.verify_census(census, expected)
    with pytest.raises(pa.AuditMismatchError, match="forbidden"):
        pa.verify_census(
            {"all_reduce": {"count": 1, "bytes": 4},
             "all_gather": {"count": 1, "bytes": 64}},
            expected,
        )
    # matching census passes silently
    pa.verify_census({"all_reduce": {"count": 5, "bytes": 4}}, expected)


# ---------------------------------------------------------------------------
# the analytical comms model
# ---------------------------------------------------------------------------


def _mesh_session(data_dir, **kw):
    from shallowspeed_tpu.api import TrainingSession

    return TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=data_dir,
        record_steps=False, **kw,
    )


def test_expected_comms_pipeline_bytes_from_tick_tables(data_dir):
    """The pp-axis wire bytes are issued relays x payload x the pairs a
    device is the source of, from the ACTUAL lowered tables, with the
    send-table useful bytes alongside."""
    from shallowspeed_tpu.parallel.executor import relay_width
    from shallowspeed_tpu.parallel.lowering import program_comm_bytes

    run = _mesh_session(data_dir, pp=4, schedule="gpipe")
    exp = run._expected_comms
    prog, spec, mb = run._prog, run.spec, run._mubatch_local
    payload = 4 * mb * relay_width(spec)
    comm = program_comm_bytes(prog, spec, mb)
    assert comm["relay_payload_bytes"] == payload
    # gpipe M 4 pp 4: 7 ticks forward-due, 7 backward-due, of 16; a middle
    # device is a source in both directions, the ends in one
    issued = int(prog.relay_fwd.sum()), int(prog.relay_bwd.sum())
    assert issued == (comm["relays_issued_fwd"], comm["relays_issued_bwd"])
    assert sum(issued) < 2 * prog.num_ticks
    assert comm["wire_bytes_by_device"] == [
        issued[0] * payload, sum(issued) * payload, sum(issued) * payload,
        issued[1] * payload,
    ]
    assert comm["wire_bytes_per_device"] == sum(issued) * payload
    sends = int(np.sum(prog.send_fwd) + np.sum(prog.send_bwd))
    assert comm["useful_sends"] == sends
    assert comm["useful_bytes_per_device"] == sends * payload / prog.num_stages

    pp_axis = exp["axes"]["pp"]
    assert pp_axis["bytes_per_step_per_device"] == comm["wire_bytes_per_device"]
    # useful <= wire: the relay's own padding tax is visible
    assert pp_axis["useful_bytes_per_step_per_device"] < pp_axis[
        "bytes_per_step_per_device"
    ]
    assert exp["required"] == ["collective_permute"]  # dp=1: no psum demanded
    assert "reduce_scatter" in exp["forbidden"]


def test_expected_comms_dp_ring_and_zero1_bytes(data_dir):
    """dp ring all-reduce moves 2(dp-1)/dp x padded grad bytes; ZeRO-1
    moves the same factor of the padded FLAT vector via reduce-scatter +
    all-gather (and requires both kinds, dp=1 included)."""
    from shallowspeed_tpu.parallel.executor import slot_shapes

    run = _mesh_session(data_dir, dp=2, pp=2, schedule="gpipe")
    exp = run._expected_comms
    dims = slot_shapes(run.spec)
    V = run.spec.n_stages // 2
    flat = sum(V * o * i for o, i in dims) + sum(V * o for o, _ in dims)
    assert exp["axes"]["dp"]["grad_bytes_per_device"] == 4 * flat
    assert exp["axes"]["dp"]["bytes_per_step_per_device"] == pytest.approx(
        2 * (2 - 1) / 2 * 4 * flat
    )
    assert "all_reduce" in exp["required"]
    assert exp["bytes_per_step_per_device"] == pytest.approx(
        exp["axes"]["dp"]["bytes_per_step_per_device"]
        + exp["axes"]["pp"]["bytes_per_step_per_device"]
    )

    z1 = _mesh_session(data_dir, dp=2, pp=2, schedule="gpipe", zero1=True)
    zexp = z1._expected_comms
    csz = -(-flat // 2)
    assert zexp["axes"]["dp"]["grad_bytes_per_device"] == 4 * csz * 2
    assert set(zexp["required"]) >= {"reduce_scatter", "all_gather",
                                     "collective_permute"}
    # ZeRO-1 at dp=1 still lowers both collectives — the contract says so
    z1s = _mesh_session(data_dir, dp=1, pp=2, schedule="gpipe", zero1=True)
    assert set(z1s._expected_comms["required"]) >= {"reduce_scatter",
                                                    "all_gather"}


def test_expected_comms_sequential_forbids_everything(data_dir):
    run = _mesh_session(data_dir)
    exp = run._expected_comms
    assert exp["sequential"] is True
    assert exp["required"] == []
    assert set(exp["forbidden"]) == {
        "all_reduce", "all_gather", "reduce_scatter", "collective_permute",
        "all_to_all",
    }
    assert exp["bytes_per_step_per_device"] == 0
    assert exp["bound"] == "compute"  # comms lower bound is zero


def test_bandwidth_and_hbm_provenance(monkeypatch):
    from shallowspeed_tpu.observability.costmodel import TPU_V5E

    bw, src = pa.interconnect_bytes_per_sec("tpu", TPU_V5E)
    assert bw == pa.INTERCONNECT_BYTES_PER_SEC[TPU_V5E] and "datasheet" in src
    bw, src = pa.interconnect_bytes_per_sec("cpu")
    assert "nominal" in src
    _, src = pa.interconnect_bytes_per_sec("rocm")
    assert "unknown-platform" in src
    # a TPU that is not a v5e gets no row at all — never v5e's numbers
    for kind in ("TPU v4", None):
        bw, src = pa.interconnect_bytes_per_sec("tpu", kind)
        assert bw is None and src == f"unknown-device:{kind}"
        cap, src = pa.hbm_per_chip("tpu", kind)
        assert cap is None and src == f"unknown-device:{kind}"
    monkeypatch.setenv(pa.ENV_BW, "123.0")
    bw, src = pa.interconnect_bytes_per_sec("tpu", "TPU v4")
    assert bw == 123.0 and src == f"env:{pa.ENV_BW}"

    cap, src = pa.hbm_per_chip("tpu", TPU_V5E)
    assert cap == pa.HBM_PER_CHIP[TPU_V5E] and "datasheet" in src
    monkeypatch.setenv(pa.ENV_HBM, "456")
    cap, src = pa.hbm_per_chip("cpu")
    assert cap == 456.0 and src == f"env:{pa.ENV_HBM}"


# ---------------------------------------------------------------------------
# real compiled programs: the invariant across layouts (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw, present, absent",
    [
        (dict(), (), ("all_reduce", "collective_permute", "reduce_scatter",
                      "all_gather")),
        (dict(dp=2), ("all_reduce",),
         ("collective_permute", "reduce_scatter", "all_gather")),
        (dict(pp=4, schedule="gpipe"), ("collective_permute",),
         ("reduce_scatter", "all_gather")),
        (dict(dp=2, pp=2, schedule="gpipe", zero1=True),
         ("collective_permute", "reduce_scatter", "all_gather"), ()),
    ],
    ids=["seq", "dp2", "gpipe-pp4", "zero1"],
)
def test_compiled_census_matches_layout_contract(data_dir, kw, present, absent):
    """Each layout's COMPILED epoch program contains exactly the collective
    kinds its contract names: none sequentially, the dp grad all-reduce
    under DP, both relay permutes under pipeline, reduce-scatter +
    all-gather under ZeRO-1 — and audit_compiled agrees (census_ok)."""
    run = _mesh_session(data_dir, **kw)
    compiled = run._epoch_fn.lower(*run._epoch_args()).compile()
    rec = pa.audit_compiled(
        compiled, expected=run._expected_comms, platform="cpu",
        n_devices=run._cost_model.n_devices,
    )
    assert rec["hlo_available"] is True
    assert rec["census_ok"] is True, rec["mismatches"]
    census = rec["census"]
    for kind in present:
        assert census.get(kind, {}).get("count", 0) >= 1, (kind, census)
    for kind in absent:
        assert kind not in census, (kind, census)
    if "collective_permute" in present:
        assert census["collective_permute"]["count"] >= 2  # both directions
    # memory analysis through the shared helper: a positive peak and the
    # headroom leg against the (nominal) cpu capacity
    assert rec["memory"]["peak_hbm_bytes"] > 0
    assert rec["hbm_per_chip"] > 0 and "nominal" in rec["hbm_source"]
    assert rec["hbm_headroom_fraction"] < 1.0


def test_compiled_split_backward_census_and_tick_model(data_dir):
    """A --backward-split session's COMPILED program still satisfies the
    layout contract (both relay permutes, no dp collectives at dp=1), and
    its comms model honestly derives from the SPLIT tick tables: more
    ticks than the unsplit twin (the deferred B-weights extend the
    program; the uniform per-tick permutes really ship those extra zero
    payloads) at the same useful send count."""
    run = _mesh_session(data_dir, pp=4, schedule="pipedream", backward_split=True)
    ref = _mesh_session(data_dir, pp=4, schedule="pipedream")
    compiled = run._epoch_fn.lower(*run._epoch_args()).compile()
    rec = pa.audit_compiled(
        compiled, expected=run._expected_comms, platform="cpu",
        n_devices=run._cost_model.n_devices,
    )
    assert rec["census_ok"] is True, rec["mismatches"]
    assert rec["census"]["collective_permute"]["count"] >= 2
    split_pp = run._expected_comms["axes"]["pp"]
    ref_pp = ref._expected_comms["axes"]["pp"]
    assert split_pp["ticks"] > ref_pp["ticks"]
    assert split_pp["payload_bytes"] == ref_pp["payload_bytes"]
    assert (
        split_pp["useful_bytes_per_step_per_device"]
        == ref_pp["useful_bytes_per_step_per_device"]
    )
    # identical padded FLOPs: the split spreads the backward's work over
    # two cells, it never adds or recomputes any
    assert (
        run._cost_model.padded_flops_per_batch
        == ref._cost_model.padded_flops_per_batch
    )


def test_expected_comms_overlap_bounds(data_dir):
    """The two step-time lower bounds of a session's contract hold their
    defining relations (serial = comm + compute, overlapped = max), and
    the dp axis carries the anchor's byte model and nothing per bucket."""
    exp = _mesh_session(data_dir, dp=2, pp=2, schedule="gpipe")._expected_comms
    dpax = exp["axes"]["dp"]
    assert dpax["kind"] == "all_reduce" and dpax["zero"] == 0
    assert "mode" not in dpax and "num_buckets" not in dpax
    ct, xt = exp["comms_time_per_step_s"], exp["compute_time_per_step_s"]
    assert exp["serial_bound_s"] == pytest.approx(ct + xt)
    assert exp["overlapped_bound_s"] == pytest.approx(max(ct, xt))
    assert exp["model_hidden_comm_share"] == pytest.approx(min(ct, xt) / ct)
    assert exp["serial_bound_s"] >= exp["overlapped_bound_s"]


@pytest.mark.parametrize(
    "kw, kind",
    [
        (dict(dp=2), "all_reduce"),
        (dict(dp=2, pp=2, schedule="gpipe", zero1=True), "reduce_scatter"),
    ],
    ids=["dp2", "zero1"],
)
def test_compiled_census_matches_anchor_sync(data_dir, kw, kind):
    """The COMPILED program really contains the layout's tail sync: the
    stage-0 all-reduces carry every gradient byte of the contract (XLA
    lowers the pytree psum per leaf or fused), ZeRO-1 lowers ONE flat
    reduce-scatter whose result is this rank's chunk — and audit_compiled
    agrees (census_ok)."""
    run = _mesh_session(data_dir, **kw)
    compiled = run._epoch_fn.lower(*run._epoch_args()).compile()
    rec = pa.audit_compiled(
        compiled, expected=run._expected_comms, platform="cpu",
        n_devices=run._cost_model.n_devices,
    )
    assert rec["census_ok"] is True, rec["mismatches"]
    grad_bytes = run._expected_comms["axes"]["dp"]["grad_bytes_per_device"]
    ops = pa.parse_collectives(compiled.as_text())
    sizes = [o["bytes"] for o in ops if o["kind"] == kind]
    if kind == "all_reduce":
        # the gradient tree plus the scalar loss psums
        assert grad_bytes <= sum(sizes) <= grad_bytes + 64, sizes
    else:
        assert sizes == [grad_bytes // 2], sizes


def test_session_audit_true_raises_on_contract_violation(data_dir, monkeypatch):
    """TrainingSession(audit=True) fails loudly BEFORE the first dispatch
    when the census disagrees with the contract (forced here by breaking
    the contract, not the lowering — same mismatch path)."""
    run = _mesh_session(data_dir, dp=2, audit=True)
    run._expected_comms = dict(
        run._expected_comms, required=["all_to_all"], forbidden=["all_reduce"]
    )
    with pytest.raises(pa.AuditMismatchError, match="all_to_all"):
        run.train_epoch()
    # a caught-and-retried failure is re-audited and re-refused — the
    # mismatch is never latched as 'audited' (no silent training after)
    with pytest.raises(pa.AuditMismatchError, match="all_to_all"):
        run.train_epoch()


def test_expected_comms_pp1_permutes_are_not_interconnect_traffic(data_dir):
    """dp-only (pp=1) mesh layouts: no device pair ever sends, so the
    executor emits no relay permute (the compiled census of the dp2 layout
    above holds none) and the contract neither requires one nor counts
    interconnect bytes for it: the bandwidth bound reflects only the real
    dp all-reduce traffic."""
    run = _mesh_session(data_dir, dp=2)
    exp = run._expected_comms
    assert "collective_permute" not in exp["required"]
    assert "collective_permute" not in exp["forbidden"]
    assert "pp" not in exp["axes"]
    assert exp["bytes_per_step_per_device"] == exp["axes"]["dp"][
        "bytes_per_step_per_device"
    ]


def test_memory_stats_shared_helper_fields():
    """The one shared memory_analysis path: field split + peak estimate
    (args + outputs + temp - aliased when no explicit peak)."""
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x * 2.0).lower(jnp.ones((128, 128))).compile()
    mem = pa.memory_stats(compiled)
    if mem is None:  # backend without memory_analysis: helper stays quiet
        pytest.skip("backend exposes no memory_analysis")
    assert mem["peak_hbm_bytes"] > 0
    est = (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0)
        - mem.get("alias_size_in_bytes", 0)
    )
    assert mem["peak_hbm_bytes"] == est or mem["peak_hbm_bytes"] > 0

    class _NoMA:
        def memory_analysis(self):
            raise NotImplementedError

    assert pa.memory_stats(_NoMA()) is None


# ---------------------------------------------------------------------------
# end to end: the xla_audit record in the JSONL + the report sections
# ---------------------------------------------------------------------------


def test_session_emits_xla_audit_record_and_report_sections(
    data_dir, tmp_path, capsys
):
    """Acceptance: a CPU run's JSONL contains an xla_audit record whose
    census matches the contract, and the report CLI renders the memory
    (peak HBM + headroom) and comms (bytes/step + bound verdict) sections
    with exit 0."""
    from shallowspeed_tpu.observability.report import main as report_main

    path = tmp_path / "audit.jsonl"
    with JsonlMetrics(path) as m:
        run = _mesh_session(data_dir, dp=2, pp=2, schedule="gpipe",
                            metrics=m, audit=True)
        run.train_epoch()
    recs = read_jsonl(path)
    audits = [r for r in recs if r.get("kind") == "xla_audit"]
    assert len(audits) == 1
    rec = audits[0]
    assert rec["name"] == "epoch_program"
    assert rec["census_ok"] is True
    assert rec["census"]["all_reduce"]["count"] >= 1
    assert rec["census"]["collective_permute"]["count"] >= 2
    assert rec["expected"]["bytes_per_step_per_device"] > 0
    assert rec["expected"]["bound"] in ("comms", "compute")
    assert rec["memory"]["peak_hbm_bytes"] > 0

    assert report_main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "Memory (compiled program)" in out
    assert "peak HBM" in out and "headroom" in out
    assert "Comms (XLA program audit)" in out
    assert "matches the layout contract" in out
    assert "-bound" in out  # the comms- vs compute-bound verdict


def test_report_renders_contract_mismatch_and_oom_forecast(tmp_path, capsys):
    """The report's negative paths: a census_ok=False record renders the
    MISMATCH loudly, and a peak beyond capacity renders the OOM forecast."""
    from shallowspeed_tpu.observability.metrics import SCHEMA_VERSION
    from shallowspeed_tpu.observability.report import main as report_main

    path = tmp_path / "bad.jsonl"
    rec = {
        "v": SCHEMA_VERSION, "ts": 0.0, "kind": "xla_audit",
        "name": "epoch_program", "hlo_available": True,
        "census": {"all_gather": {"count": 1, "bytes": 64}},
        "memory": {"peak_hbm_bytes": 32 * 2**30},
        "n_devices": 1, "platform": "cpu", "hbm_per_chip": 8 * 2**30,
        "hbm_source": "nominal-cpu-default",
        "peak_hbm_per_chip_bytes": 32 * 2**30,
        "hbm_headroom_fraction": 1.0 - 32 / 8,
        "expected": {"required": ["all_reduce"], "forbidden": [],
                     "axes": {}, "bytes_per_step_per_device": 0},
        "mismatches": ["required collective 'all_reduce' is absent"],
        "census_ok": False,
    }
    path.write_text(json.dumps(rec) + "\n")
    assert report_main([str(path), "--format", "text"]) == 0
    out = capsys.readouterr().out
    assert "CONTRACT MISMATCH" in out and "all_reduce" in out
    assert "OOM FORECAST" in out


def test_audited_run_jsonl_and_overlap_report(data_dir, tmp_path, capsys):
    """End-to-end for the overlap observability loop: the JSONL carries a
    census-clean audit with the anchor's byte model, and the report
    renders the overlap-efficiency row plus the serial-vs-overlapped step
    bounds."""
    from shallowspeed_tpu.observability.report import main as report_main

    path = tmp_path / "audited.jsonl"
    with JsonlMetrics(path) as m:
        run = _mesh_session(data_dir, dp=2, metrics=m, audit=True)
        run.train_epoch()
    recs = read_jsonl(path)
    assert not [r for r in recs if r.get("name") == "grad_sync_plan"]
    audit = [r for r in recs if r.get("kind") == "xla_audit"][-1]
    assert audit["census_ok"] is True
    assert audit["expected"]["axes"]["dp"]["kind"] == "all_reduce"

    assert report_main([str(path), "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "overlap efficiency" in out
    assert "comm hideable (model bound; anchor sync)" in out
    assert "gradient sync: bucketed" not in out
    assert "serial (anchor)" in out and "overlapped (perfect)" in out


def test_fused_run_audits_run_program(data_dir, tmp_path):
    """A fused-run-only session still gets its census verified/recorded —
    as the run_program audit."""
    path = tmp_path / "run.jsonl"
    with JsonlMetrics(path) as m:
        run = _mesh_session(data_dir, dp=2, metrics=m, audit=True)
        run.train_run(2, with_eval=False)
        # a DIFFERENT run variant is a different compiled program — it
        # must be audited too (per-variant dedup, not per-label)
        run.train_run(1, with_eval=False)
    audits = [r for r in read_jsonl(path) if r.get("kind") == "xla_audit"]
    assert [a["name"] for a in audits] == ["run_program", "run_program"]
    for a in audits:
        assert a["census_ok"] is True
        assert a["census"]["all_reduce"]["count"] >= 1


def test_chunked_train_steps_audits_chunk_programs(data_dir, tmp_path):
    """A train_steps slice shorter than the epoch is a DISTINCT XLA
    program — the audit contract ("a mislowered layout never trains a
    step") must census IT, not the never-dispatched full-epoch program.
    One audit per distinct chunk length (the scan body is
    length-independent), and a full-epoch slice takes the epoch path."""
    path = tmp_path / "chunks.jsonl"
    with JsonlMetrics(path) as m:
        run = _mesh_session(data_dir, dp=2, metrics=m, audit=True)
        assert run.batches_per_epoch == 4
        run.train_steps(1)
        run.train_steps(1)  # same chunk length: deduped, no second audit
        run.train_steps(2)  # new chunk length: its own audit
    audits = [r for r in read_jsonl(path) if r.get("kind") == "xla_audit"]
    assert [a["name"] for a in audits] == ["chunk_program", "chunk_program"]
    for a in audits:
        assert a["census_ok"] is True
        assert a["census"]["all_reduce"]["count"] >= 1

    # a slice spanning the whole epoch is the epoch program (and a chunked
    # session that later goes whole-epoch reuses that one audit)
    with JsonlMetrics(tmp_path / "full.jsonl") as m:
        run2 = _mesh_session(data_dir, dp=2, metrics=m, audit=True)
        run2.train_steps(run2.batches_per_epoch)
        run2.train_epoch()
    audits2 = [
        r for r in read_jsonl(tmp_path / "full.jsonl")
        if r.get("kind") == "xla_audit"
    ]
    assert [a["name"] for a in audits2] == ["epoch_program"]


def test_chunked_train_steps_audit_refuses_before_dispatch(data_dir):
    """audit=True refuses a mislowered CHUNK program before it trains a
    step — same unlatched strictness as the epoch path."""
    run = _mesh_session(data_dir, dp=2, audit=True)
    run._expected_comms = dict(
        run._expected_comms, required=["all_to_all"], forbidden=["all_reduce"]
    )
    with pytest.raises(pa.AuditMismatchError, match="all_to_all"):
        run.train_steps(1)
    assert run.step_in_epoch == 0  # nothing trained
    with pytest.raises(pa.AuditMismatchError, match="all_to_all"):
        run.train_steps(1)
