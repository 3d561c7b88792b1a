"""Host spans: the one source of host-side timing, always on.

A span marks a named phase of host work (a phase of ``TrainingSession``'s
construction, a device put, an epoch's dispatch or its readback) in three
places at once:

- **the span log** (``log()``): one ``Entry`` per closed span on
  ``time.perf_counter_ns()``, whatever recorder is attached and with none.
  Process-wide, bounded (the first ``KEEP`` entries, which hold the set-up,
  and the newest ``KEEP``), readable after the session is gone and after
  ``jax.clear_caches()``, as ``scopes.program_index`` is;
- **the profiler's trace**: the body runs under
  ``jax.profiler.TraceAnnotation``, so a capture (``capture(logdir)`` /
  ``jax.profiler.trace``) shows the span on the host's timeline beside the
  device's operations, on the profiler's clock;
- **the metrics stream**, where a recorder is attached
  (``metrics.span(name)``): a ``span`` record with the nesting path
  (``"train_epoch/epoch/dispatch"``), the depth and the seconds.

``HOST_SPANS`` is the one list of names the package uses, as
``scopes.SCOPES`` is for the device's programs; ``span()`` refuses any other,
so a reader can look a span up by name. Nesting is kept per thread.

``listen_to_compiles()`` puts what JAX says about compiling into the same log:
every trace, lowering, backend compile and load from the persistent cache as
an entry named ``compile/trace``, ``compile/lower``, ``compile/backend`` or
``compile/cache_load`` with the function's name, nested under whichever span
was open on that thread, and a count of traces per function.
"""

import collections
import contextlib
import threading
import time
from typing import NamedTuple, Optional

import jax.monitoring
import jax.profiler
from jax.profiler import TraceAnnotation

HOST_SPANS = (
    # TrainingSession.__init__ and its phases
    "session/init",
    "session/data",
    "session/weights",
    "session/lower",
    "session/program",
    "session/resume",
    # under session/weights: the host drawing the model's initial leaves
    # (init.draw_leaves, the pool and its wait; placement is device_put's)
    "draw",
    # a leaf of whichever phase or call places something on the devices
    "device_put",
    # the audit's ahead-of-time probe compile (not the compile a run pays)
    "jit_compile",
    # the training entry points and the two halves of one program call
    "train_steps",
    "train_epoch",
    "train_run",
    "epoch/dispatch",
    "epoch/readback",
    "eval",
    # written by the compile listener, never opened by hand
    "compile/trace",
    "compile/lower",
    "compile/backend",
    "compile/cache_load",
)
_NAMES = frozenset(HOST_SPANS)

KEEP = 4096  # entries kept from the start of the process, and as many newest
# a compile event inside another one (a jnp helper traced inside the epoch
# program's trace) is counted, and logged only if it took this long: the
# outermost events are the union, and set-up must stay within the first KEEP
NESTED_COMPILE_MIN_NS = 1_000_000

_tls = threading.local()


def _stack():
    """The names of the spans open on this thread, outermost first."""
    try:
        return _tls.stack
    except AttributeError:
        _tls.ident = threading.get_ident()
        stack = _tls.stack = []
        return stack


class Entry(NamedTuple):
    """One closed span, or one compile event, of the log."""

    path: str  # the names of the open spans and this one, joined by "/"
    start: int  # time.perf_counter_ns()
    duration: int  # ns
    thread: int  # threading.get_ident()
    name: str  # of HOST_SPANS: the path's last name
    fun_name: Optional[str] = None  # compile events: the function JAX names


class SpanLog:
    """The first ``keep`` entries and the newest ``keep``, in closing order.
    ``anchor`` is one ``(perf_counter_ns, time_ns)`` pair taken when the log
    was made, so an entry can be put on the wall clock (``wall_ns``), which is
    the clock of JAX's compile events. ``traces`` counts the ``compile/trace``
    events that closed under an open span, per function (nested ones too);
    ``cache`` counts the persistent cache's hits and misses."""

    def __init__(self, keep=KEEP):
        self.keep = keep
        self.anchor = (time.perf_counter_ns(), time.time_ns())
        self.dropped = 0
        self.traces = {}
        self.cache = {"hits": 0, "misses": 0}
        self._first = []
        self._room = keep  # places left among the first
        self._newest = collections.deque(maxlen=keep)

    def add(self, entry):
        # appends are atomic; two threads racing past the check keep one
        # entry too many, which costs nothing
        if self._room > 0:
            self._room -= 1
            self._first.append(entry)
        else:
            if len(self._newest) == self.keep:
                self.dropped += 1
            self._newest.append(entry)

    def entries(self):
        return self._first + list(self._newest)

    def wall_ns(self, perf_ns):
        return perf_ns - self.anchor[0] + self.anchor[1]


_LOG = SpanLog()


def log():
    """The process's span log."""
    return _LOG


class Span:
    """Context manager timing one named phase into the log (and into a
    recorder, when bound to one through ``metrics.span(name)``). ``.seconds``,
    ``.path`` and ``.depth`` are set on exit. One instance per ``with``.
    Enter and exit make few Python calls on purpose: the profiler's Python
    tracer charges each one to the traced loop."""

    __slots__ = (
        "name", "metrics", "path", "depth", "seconds", "_t0", "_ann", "_open",
    )

    def __init__(self, name, metrics=None):
        if name not in _NAMES:
            raise ValueError(
                f"{name!r} is not a host span of this package; add it to "
                "observability.spans.HOST_SPANS (and to the span table of "
                "docs/observability.md) first"
            )
        self.name = name
        self.metrics = metrics
        self.path = None
        self.depth = None
        self.seconds = None

    def __enter__(self):
        try:
            stack = self._open = _tls.stack
        except AttributeError:
            stack = self._open = _stack()
        self.depth = len(stack)
        self.path = "/".join(stack + [self.name])
        # enter the annotation BEFORE pushing: if it raises, __exit__ never
        # runs, and a pushed-but-never-popped name would corrupt every later
        # span's path in this thread for the rest of the process
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        duration = time.perf_counter_ns() - self._t0
        self.seconds = duration / 1e9
        self._ann.__exit__(exc_type, exc, tb)
        # back to this span's depth, whatever an inner span that never
        # exited (an exception between its enter and its with) left there
        del self._open[self.depth:]
        _LOG.add(
            tuple.__new__(
                Entry, (self.path, self._t0, duration, _tls.ident, self.name, None)
            )
        )
        if self.metrics is not None:
            self.metrics._record_span(self)
        return False


def span(name, metrics=None):
    """Free-function spelling: ``with span("eval"): ...``."""
    return Span(name, metrics=metrics)


def capture(logdir):
    """``jax.profiler.trace(logdir)``, or nothing when ``logdir`` is empty, so
    that a call site needs no conditional."""
    if not logdir:
        return contextlib.nullcontext()
    return jax.profiler.trace(str(logdir))


# -- what JAX says about compiling ------------------------------------------

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_listening = False


def _compiling():
    """The functions whose compile events are open on this thread."""
    found = getattr(_tls, "compiling", None)
    if found is None:
        found = _tls.compiling = []
    return found


def _compile_entry(name, closed_s, seconds, fun_name):
    """An event that closed at ``closed_s`` on the wall clock, put on the
    log's clock by its distance from now (so the two clocks' drift since the
    anchor does not enter)."""
    duration = int(seconds * 1e9)
    closed = time.perf_counter_ns() - int((time.time() - closed_s) * 1e9)
    _LOG.add(
        Entry(
            "/".join(_stack() + [name]), closed - duration, duration,
            threading.get_ident(), name, fun_name,
        )
    )


def _on_compile_opened(event, value, fun_name=None, **_):
    # JAX reports an event's start as a scalar when it opens
    if event in _COMPILE_PHASES:
        _compiling().append(fun_name)


def _on_compile_closed(event, start, end, fun_name=None, **_):
    name = _COMPILE_PHASES.get(event)
    if name is None:
        return
    inside = _compiling()
    if inside:
        inside.pop()
    if name == "compile/trace" and _stack():
        # the program's own traces: a caller's arithmetic outside every span
        # (a benchmark's reference) is logged below and not counted
        _LOG.traces[fun_name] = _LOG.traces.get(fun_name, 0) + 1
    if inside and (end - start) * 1e9 < NESTED_COMPILE_MIN_NS:
        return
    _compile_entry(name, end, end - start, fun_name)


def _on_duration(event, seconds, **_):
    # reported when the load returns, inside its function's compile/backend
    if event == _CACHE_LOAD:
        inside = _compiling()
        _compile_entry(
            "compile/cache_load", time.time(), seconds,
            inside[-1] if inside else None,
        )


def _on_event(event, **_):
    which = _CACHE_COUNTS.get(event)
    if which is not None:
        _LOG.cache[which] += 1


def listen_to_compiles():
    """Register the package's one set of ``jax.monitoring`` listeners; calling
    it again does nothing (JAX has no way to take a listener back)."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_scalar_listener(_on_compile_opened)
    jax.monitoring.register_event_time_span_listener(_on_compile_closed)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def covered_ns(entries):
    """The length of the union of the entries' intervals: nested and
    overlapping events are counted once."""
    length, reach = 0, -1
    for start, end in sorted((e.start, e.start + e.duration) for e in entries):
        if end > reach:
            length += end - max(start, reach)
            reach = end
    return length
