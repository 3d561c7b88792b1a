"""Test config: force an 8-device virtual CPU platform BEFORE jax imports.

This is how the reference's biggest testing gap (no distributed tests at all,
SURVEY §4) gets closed without a TPU pod: every DP/PP layout runs SPMD on
8 emulated host devices, so mesh/collective code paths are exercised for real.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from shallowspeed_tpu.compile_cache import enable_compile_cache  # noqa: E402

# Persistent XLA compilation cache, placed by the one helper every entry
# point uses (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache):
# the suite's wall clock is dominated by repeated pipeline-step compiles
# (dozens of distinct mesh programs). With the cache warm, recompiles of
# unchanged programs are disk loads; measured ~5x on a representative
# pipeline-step compile. Keyed by HLO + compile options, so source changes
# re-compile exactly what they invalidate.
enable_compile_cache()


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the marker gates tests whose coverage is
    # duplicated by a Makefile smoke target (e.g. the CLI SIGKILL round
    # trip, recovery-smoke's in-suite twin) out of the bounded gate
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')"
    )
    # fleet tests spawn real worker processes (multiprocessing spawn +
    # their own JAX runtimes); they skip-with-reason on platforms that
    # cannot spawn workers — mirroring the multihost collectives skip —
    # so tier-1 stays green on constrained runners
    config.addinivalue_line(
        "markers",
        "fleet: multi-process serving-fleet tests (skipped when the "
        "platform cannot spawn worker processes)",
    )
