"""Bounded exponential-backoff retry: the ONE retry policy for flaky host I/O.

Three consumers share this module so their retry behaviour can never drift:

- checkpoint writes (``checkpoint.save_checkpoint`` retries the atomic
  tmp-write + rename on transient ``OSError`` — a preemption-safe step
  checkpoint that dies to one flaky NFS write defeats its purpose);
- distributed init (``parallel.multihost.initialize`` with an EXPLICIT
  coordinator retries the join — the coordinator process races the workers
  up on real clusters);
- the serving engine's dispatch recovery (``serving/engine.py`` re-queues
  a failed batch and retries each request under a ``RetryPolicy`` budget —
  the policy as a VALUE, for consumers that own their own retry loop —
  and its hot weight reload reads checkpoints through ``retry_call``).

Policy: delay for attempt ``i`` (0-based, i.e. before retry ``i+1``) is
``min(base * factor**i, max_delay)`` plus uniform jitter in
``[-jitter, +jitter] * delay``. Jitter is DETERMINISTIC given ``seed`` —
everything in this repo that can replay must replay (the same property the
checkpoints guarantee), and the tests pin the schedule.
"""

import random
import time


def backoff_delay(
    attempt, base=1.0, factor=2.0, max_delay=60.0, jitter=0.1, seed=None
):
    """Delay in seconds before retry ``attempt + 1`` (attempt is 0-based).

    Exponential growth capped at ``max_delay``, with deterministic uniform
    jitter of ±``jitter`` (a fraction of the delay) drawn from a string
    seed over (seed, attempt) — the same pair always produces the same
    delay (independent of PYTHONHASHSEED), so schedules are reproducible
    and testable. ``jitter=0`` disables it. Never returns a negative delay.
    """
    if attempt < 0:
        raise ValueError("attempt must be >= 0")
    if base < 0 or factor < 1.0 or max_delay < 0:
        raise ValueError("need base >= 0, factor >= 1, max_delay >= 0")
    if not 0 <= jitter < 1:
        raise ValueError("jitter must be in [0, 1)")
    delay = min(base * factor**attempt, max_delay)
    if jitter:
        rng = random.Random(f"{seed}:{attempt}")
        delay *= 1.0 + rng.uniform(-jitter, jitter)
    return max(0.0, delay)


def backoff_delays(attempts, **kwargs):
    """The full schedule: ``[backoff_delay(0), ..., backoff_delay(n-1)]``."""
    return [backoff_delay(i, **kwargs) for i in range(attempts)]


class RetryPolicy:
    """The backoff policy as a value: a bounded total-attempts budget plus
    the ``backoff_delay`` schedule, passable to consumers that own their
    own retry loop (the serving engine's dispatch recovery re-queues a
    failed batch and retries it on a LATER ``step()`` call, so it cannot
    hand control to ``retry_call`` — but its budget and delays must follow
    the same policy every other retry in this repo follows).

    ``attempts`` is the TOTAL budget, ``retry_call``'s exact contract: a
    unit of work may run at most ``attempts`` times, with ``delay(i)``
    seconds before retry ``i + 1``. ``base=0`` (the serving default) makes
    every delay 0 — bounded retries, no stall."""

    __slots__ = ("attempts", "base", "factor", "max_delay", "jitter", "seed")

    def __init__(
        self, attempts=3, base=0.1, factor=2.0, max_delay=5.0, jitter=0.1,
        seed=None,
    ):
        if attempts < 1:
            raise ValueError("attempts must be >= 1")
        self.attempts = int(attempts)
        self.base = base
        self.factor = factor
        self.max_delay = max_delay
        self.jitter = jitter
        self.seed = seed
        # validate eagerly — a bad policy must fail at configure time,
        # not on the first failure it was meant to absorb
        backoff_delay(
            0, base=base, factor=factor, max_delay=max_delay, jitter=jitter,
            seed=seed,
        )

    def delay(self, attempt):
        """Seconds to wait before retry ``attempt + 1`` (0-based)."""
        return backoff_delay(
            attempt, base=self.base, factor=self.factor,
            max_delay=self.max_delay, jitter=self.jitter, seed=self.seed,
        )

    def exhausted(self, attempts_used):
        """True once ``attempts_used`` has consumed the whole budget."""
        return attempts_used >= self.attempts

    def __repr__(self):
        return (
            f"RetryPolicy(attempts={self.attempts}, base={self.base}, "
            f"factor={self.factor}, max_delay={self.max_delay})"
        )


def retry_call(
    fn,
    *,
    attempts=3,
    base=0.1,
    factor=2.0,
    max_delay=5.0,
    jitter=0.1,
    seed=None,
    retry_on=(OSError,),
    on_retry=None,
    sleep=time.sleep,
):
    """Call ``fn()`` with bounded exponential-backoff retries.

    Retries only on exception types in ``retry_on`` (everything else —
    including the final failing attempt — propagates unwrapped, so callers'
    existing except clauses keep working). ``on_retry(attempt, exc, delay)``
    is the observability hook (attempt is 0-based); ``sleep`` is injectable
    for tests. ``attempts`` is the TOTAL call budget (>= 1), so the worst
    case is strictly bounded: ``attempts`` calls and ``attempts - 1`` sleeps.
    """
    if attempts < 1:
        raise ValueError("attempts must be >= 1")
    for attempt in range(attempts):
        try:
            return fn()
        except retry_on as e:
            if attempt == attempts - 1:
                raise
            delay = backoff_delay(
                attempt, base=base, factor=factor, max_delay=max_delay,
                jitter=jitter, seed=seed,
            )
            if on_retry is not None:
                on_retry(attempt, e, delay)
            sleep(delay)
