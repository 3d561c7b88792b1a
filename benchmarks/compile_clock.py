"""What JAX itself says about compiling (``chip_smoke.py``'s listener, copied
so that the benchmark does not depend on a script it may outlive): every
``/jax/core/compile/*`` span with its wall-clock start and end, tracing,
lowering, XLA compilation and loads from the persistent cache alike, plus the
persistent cache's hits and misses."""

import math

import jax.monitoring


class CompileClock:
    def __init__(self):
        self.spans = []  # (start, end) in time.time() seconds
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event.startswith("/jax/core/compile/"):
            self.spans.append((start, end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self, until=math.inf):
        """Wall time inside compile spans that began before ``until``. Nested
        traces report nested spans, so it is the union's length, not a sum."""
        length, reach = 0.0, -math.inf
        for start, end in sorted(s for s in self.spans if s[0] < until):
            if end > reach:
                length += end - max(start, reach)
                reach = end
        return length

    def count_between(self, opened, closed):
        """Compile spans that began inside ``[opened, closed]``: each is a
        program traced, lowered, compiled or loaded inside the window."""
        return sum(1 for start, _ in self.spans if opened <= start <= closed)
