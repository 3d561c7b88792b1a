"""The host half of tracing (observability/spans.py): the span vocabulary, the
always-on span log and its bounds, nesting per thread, the package's one
compile listener with its four phases and its count of traces per function,
and the phases of ``TrainingSession.__init__`` accounting for their root.
"""

import importlib.util
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.compile_cache import enable_compile_cache
from shallowspeed_tpu.observability import MetricsRecorder, NullMetrics, spans

ROOT = Path(__file__).resolve().parent.parent
SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
PHASES = (
    "session/data", "session/weights", "session/lower", "session/program",
    "session/resume",
)


def _since(mark, name=None):
    """The log's entries that began after ``mark`` (perf_counter_ns)."""
    return [
        e for e in spans.log().entries()
        if e.start >= mark and (name is None or e.name == name)
    ]


# -- the vocabulary ----------------------------------------------------------


@pytest.mark.parametrize("name", ["outer", "schedule_lower", "epoch", "", "Eval"])
def test_span_refuses_a_name_that_is_not_a_host_span(name):
    with pytest.raises(ValueError, match="HOST_SPANS"):
        spans.span(name)
    with pytest.raises(ValueError, match="HOST_SPANS"):
        NullMetrics().span(name)
    with pytest.raises(ValueError, match="HOST_SPANS"):
        MetricsRecorder().span(name)


def test_every_host_span_opens_and_the_list_has_no_duplicate():
    assert len(set(spans.HOST_SPANS)) == len(spans.HOST_SPANS)
    mark = time.perf_counter_ns()
    for name in spans.HOST_SPANS:
        with spans.span(name) as s:
            pass
        assert s.path == name and s.depth == 0 and s.seconds >= 0
    assert [e.name for e in _since(mark)] == list(spans.HOST_SPANS)


def test_api_spells_a_host_span_one_way_and_only_with_names_of_the_list():
    import re

    text = (ROOT / "shallowspeed_tpu" / "api.py").read_text()
    assert "host_span" not in text
    used = set(re.findall(r'self\._metrics\.span\("([^"]+)"\)', text))
    assert used and used <= set(spans.HOST_SPANS)
    assert len(re.findall(r"\.span\(", text)) == len(
        re.findall(r"self\._metrics\.span\(", text)
    )


# -- the log -----------------------------------------------------------------


def test_the_log_keeps_the_first_and_the_newest_entries_after_100000_spans():
    log = spans.SpanLog(keep=8)
    for i in range(100_000):
        log.add(spans.Entry("eval", i, 1, 0, "eval"))
    kept = [e.start for e in log.entries()]
    assert kept == list(range(8)) + list(range(99_992, 100_000))
    assert log.dropped == 100_000 - 16


def test_100000_real_spans_grow_nothing(monkeypatch):
    log = spans.SpanLog(keep=16)
    monkeypatch.setattr(spans, "_LOG", log)
    with spans.span("session/init"):  # set-up stays readable
        pass
    metrics = NullMetrics()
    for _ in range(100_000):
        with metrics.span("epoch/dispatch"):
            pass
    entries = log.entries()
    assert len(entries) == 32 and entries[0].name == "session/init"
    assert spans.log() is log


def test_the_anchor_puts_an_entry_on_the_wall_clock():
    log = spans.log()
    assert abs(log.wall_ns(time.perf_counter_ns()) - time.time_ns()) < 50_000_000


def test_covered_ns_counts_nested_and_overlapping_events_once():
    def entry(start, end):
        return spans.Entry("compile/trace", start, end - start, 0, "compile/trace")

    assert spans.covered_ns([]) == 0
    assert spans.covered_ns(
        [entry(0, 10), entry(2, 4), entry(8, 15), entry(20, 21)]
    ) == 16


# -- spans -------------------------------------------------------------------


def test_null_metrics_span_writes_the_log_and_a_recorders_record_is_unchanged():
    mark = time.perf_counter_ns()
    with NullMetrics().span("train_epoch") as outer:
        with NullMetrics().span("epoch/dispatch"):
            pass
    recorder = MetricsRecorder()
    emitted = []
    recorder._emit = emitted.append
    with recorder.span("train_epoch"):
        with recorder.span("epoch/readback") as inner:
            pass
    assert outer.metrics is None
    assert [(e.path, e.name) for e in _since(mark)] == [
        ("train_epoch/epoch/dispatch", "epoch/dispatch"),
        ("train_epoch", "train_epoch"),
        ("train_epoch/epoch/readback", "epoch/readback"),
        ("train_epoch", "train_epoch"),
    ]
    assert all(e.thread == threading.get_ident() for e in _since(mark))
    # the recorder's span record: the same five fields as before the log
    assert emitted[0] == {
        "kind": "span", "name": "epoch/readback",
        "path": "train_epoch/epoch/readback", "depth": 1,
        "seconds": inner.seconds,
    }
    assert [p for p, _ in recorder.spans] == [
        "train_epoch/epoch/readback", "train_epoch"
    ]
    # the log's entry and the recorder's record are one measurement
    logged = _since(mark, "epoch/readback")[0]
    assert logged.duration / 1e9 == inner.seconds


def test_nesting_paths_are_kept_per_thread():
    mark = time.perf_counter_ns()
    inside = threading.Barrier(2)

    def work(outer, leaf):
        with spans.span(outer):
            inside.wait(timeout=10)  # both threads are inside their outer span
            with spans.span(leaf):
                inside.wait(timeout=10)

    threads = [
        threading.Thread(target=work, args=("train_epoch", "epoch/dispatch")),
        threading.Thread(target=work, args=("train_steps", "epoch/readback")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    found = {e.path: e.thread for e in _since(mark)}
    assert set(found) == {
        "train_epoch", "train_epoch/epoch/dispatch",
        "train_steps", "train_steps/epoch/readback",
    }
    assert found["train_epoch"] == found["train_epoch/epoch/dispatch"]
    assert found["train_epoch"] != found["train_steps"]


def test_a_span_left_open_by_an_exception_does_not_corrupt_later_paths():
    with pytest.raises(RuntimeError):
        with spans.span("session/init"):
            spans.span("session/data").__enter__()  # never exited
            raise RuntimeError("refused")
    with spans.span("train_epoch") as later:
        pass
    assert later.path == "train_epoch" and later.depth == 0


def test_capture_without_a_directory_is_no_context():
    with spans.capture(None):
        pass
    with spans.capture(""):
        pass


# -- the compile listener ------------------------------------------------------


def _registered(listener, listeners):
    return sum(1 for found in listeners if found is listener)


def test_the_compile_listener_is_registered_once_however_many_sessions(data_dir):
    for _ in range(3):
        enable_compile_cache()
        spans.listen_to_compiles()
    for _ in range(2):
        TrainingSession(sizes=SIZES, global_batch_size=64, data_dir=data_dir)
    from jax._src import monitoring  # the public module has no getters

    assert _registered(
        spans._on_compile_closed, monitoring.get_event_time_span_listeners()
    ) == 1
    assert _registered(
        spans._on_duration, monitoring.get_event_duration_listeners()
    ) == 1
    assert _registered(spans._on_event, monitoring.get_event_listeners()) == 1


@pytest.fixture()
def private_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, and the
    suite's own settings back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cached", [False, True], ids=["cache_off", "cache_on"])
def test_compile_phases_and_the_count_of_traces_per_function(private_cache, cached):
    from jax.experimental.compilation_cache import compilation_cache

    spans.listen_to_compiles()
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()

    def a_function_of_this_test(x):
        return jnp.sin(x) @ x + 1.0

    jitted = jax.jit(a_function_of_this_test)
    x = jnp.ones((16, 16))
    before = dict(spans.log().cache)
    traces = spans.log().traces
    base = traces.get("a_function_of_this_test", 0)  # the other case's
    for call in range(2):
        mark = time.perf_counter_ns()
        with spans.span("train_steps"):
            with spans.span("epoch/dispatch"):
                jitted(x).block_until_ready()
        found = {
            e.name: e for e in _since(mark)
            if e.fun_name and "a_function_of_this_test" in e.fun_name
        }
        # the function's own three phases, nested under the open spans
        assert {"compile/trace", "compile/lower", "compile/backend"} <= set(found)
        assert found["compile/trace"].path == (
            "train_steps/epoch/dispatch/compile/trace"
        )
        assert all(e.duration > 0 for e in found.values())
        assert traces["a_function_of_this_test"] == base + call + 1
        loaded = "compile/cache_load" in found
        # with the persistent cache, the second compile is a load from it
        assert loaded == (cached and call == 1)
        if loaded:
            load, backend = found["compile/cache_load"], found["compile/backend"]
            assert backend.start <= load.start
            assert load.start + load.duration <= backend.start + backend.duration
        jax.clear_caches()  # the next call traces, lowers and compiles again
    hits = spans.log().cache["hits"] - before["hits"]
    assert (hits >= 1) == cached
    # helpers traced inside the function's trace are counted under the open
    # span, and logged only if they took long (they are inside the union)
    assert traces.get("sin", 0) >= 1
    # no trace is counted where no span is open
    n = traces["a_function_of_this_test"]
    jitted(x).block_until_ready()
    assert traces["a_function_of_this_test"] == n


def test_compile_events_land_on_the_logs_clock():
    spans.listen_to_compiles()

    def another_function_of_this_test(x):
        return x * 3.0

    t0 = time.perf_counter_ns()
    jax.jit(another_function_of_this_test)(jnp.ones(4)).block_until_ready()
    t1 = time.perf_counter_ns()
    found = [
        e for e in spans.log().entries()
        if e.fun_name and "another_function_of_this_test" in e.fun_name
    ]
    # a fourth, compile/cache_load, where the suite's persistent cache answers
    assert {"compile/trace", "compile/lower", "compile/backend"} <= {
        e.name for e in found
    }
    for e in found:  # wall-clock events, converted: inside the call, to a ms
        assert t0 - 1_000_000 <= e.start and e.start + e.duration <= t1 + 1_000_000
        assert e.path == e.name  # no span was open


# -- the session's phases ------------------------------------------------------


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


@pytest.fixture()
def token_dir(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "packed_tokens", ROOT / "benchmarks" / "datasets" / "packed_tokens.py"
    )
    packed = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(packed)
    packed.make_dataset(5, 8, {"seq_len": 48}, {"vocab_size": 96}, tmp_path)
    return tmp_path


TOKEN_MODEL = dict(
    model_type="olmo_hybrid", vocab_size=96, hidden_size=32, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-6,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=6,
    linear_value_head_dim=12, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True,
)
FAMILIES = {
    "mlp_sequential": dict(sizes=SIZES, global_batch_size=64),
    "mlp_mesh_4_devices": dict(
        sizes=SIZES, global_batch_size=64, dp=2, pp=2, schedule="pipedream"
    ),
    "token": dict(
        model=TOKEN_MODEL, seq_len=48, global_batch_size=4, mubatches=2,
        optimizer="sgd",
    ),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_the_phases_of_session_init_account_for_their_root(
    family, data_dir, token_dir
):
    kwargs = FAMILIES[family]
    where = token_dir if "model" in kwargs else data_dir
    mark = time.perf_counter_ns()
    session = TrainingSession(data_dir=str(where), **kwargs)
    entries = _since(mark)
    (root,) = [e for e in entries if e.path == "session/init"]
    children = [e for e in entries if e.path in {f"session/init/{p}" for p in PHASES}]
    # every phase but the mesh's lowering opens on every path
    assert {e.name for e in children} == set(PHASES) - (
        {"session/lower"} if session.sequential else set()
    )
    assert all(e.start >= root.start for e in children)
    named = sum(e.duration for e in children)
    assert named <= root.duration
    assert named >= 0.95 * root.duration, (
        {e.name: e.duration / 1e6 for e in children}, root.duration / 1e6
    )
    # the placements are leaves of the phase that places something
    puts = {e.path for e in entries if e.name == "device_put"}
    assert "session/init/session/weights/device_put" in puts
    assert "session/init/session/data/device_put" in puts
    # and a default session's loop writes the log, recorder or none
    mark = time.perf_counter_ns()
    session.train_epoch()
    assert [e.path for e in _since(mark) if not e.name.startswith("compile/")] == [
        "train_epoch/epoch/dispatch", "train_epoch/epoch/readback", "train_epoch",
    ]
