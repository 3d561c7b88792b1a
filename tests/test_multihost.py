"""Multi-host tests: single-process no-op semantics AND real multi-process
``jax.distributed`` runs (localhost coordinator, CPU backend) — a 2-process
fleet exercising cross-process collectives + the pipeline executor, and a
4-process 2x2 mesh where every axis crosses process boundaries with
cross-process replica-sync verification. The environment's stand-in for the
reference's ``mpirun -n N`` multi-process mode (reference train.py:87-94)."""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from shallowspeed_tpu.parallel import make_mesh, multihost


def test_initialize_is_noop_single_process():
    multihost.initialize()  # must not raise without a coordinator
    assert jax.process_count() == 1


def test_shard_batch_for_process_places_on_mesh():
    mesh = make_mesh(2, 4)
    x = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    arr = multihost.shard_batch_for_process(x, mesh, P("dp"))
    assert arr.shape == (16, 3)
    np.testing.assert_array_equal(np.asarray(arr), x)
    # sharded over dp, replicated over pp: 8 devices, 2 distinct row-shards
    # (keyed by str: shard.index is a tuple of slices, unhashable < py3.12)
    assert len({str(s.index) for s in arr.addressable_shards}) == 2


def _run_worker_fleet(worker, n_procs, timeout=240):
    """Spawn ``n_procs`` cooperating jax.distributed workers on a fresh
    localhost coordinator port and collect one JSON line from each; retries
    on the (racy) port pick. Returns (outs, errs); outs is None on failure."""
    env = dict(os.environ)

    def attempt():
        # bind-close-reuse port picking is racy on a busy host; the caller
        # retries with a fresh port if the coordinator loses the race
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(pid), str(port)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                env=env,
                text=True,
            )
            for pid in range(n_procs)
        ]
        outs, errs = [], []
        try:
            for p in procs:
                try:
                    out, err = p.communicate(timeout=timeout)
                except subprocess.TimeoutExpired:
                    # e.g. workers connected to a port-race winner and hung —
                    # kill and let the caller retry on a fresh port
                    errs.append("worker timed out (port race?)")
                    return None, errs
                errs.append(err)
                if p.returncode != 0:
                    return None, errs
                outs.append(json.loads(out.strip().splitlines()[-1]))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
                    p.wait(timeout=30)
        return outs, errs

    outs = None
    for _ in range(3):
        outs, errs = attempt()
        if outs is not None:
            break
        # old jaxlib (< 0.5): the CPU backend has no cross-process
        # collectives at all — the capability under test does not exist in
        # this environment, so skipping (with the backend's own words) is
        # the honest outcome; on a capable jaxlib the fleet still runs
        if any(
            "Multiprocess computations aren't implemented on the CPU backend"
            in (e or "")
            for e in errs
        ):
            pytest.skip(
                "this jaxlib's CPU backend does not implement multiprocess "
                "collectives (XlaRuntimeError: 'Multiprocess computations "
                "aren't implemented on the CPU backend')"
            )
    assert outs is not None, f"workers failed 3x:\n{errs[-1][-3000:]}"
    return outs


def test_two_process_distributed_training_step():
    """Spawn 2 cooperating processes that form a 4-device global runtime and
    run a cross-process psum + pipeline training steps (flat GPipe and
    interleaved virtual stages — see _multihost_worker.py). Verifies
    multihost.initialize, process-local batch feeding, and that both
    processes agree on the (replicated) losses."""
    outs = _run_worker_fleet(Path(__file__).parent / "_multihost_worker.py", 2)
    assert all(o["psum_ok"] for o in outs)
    for key in ("loss", "loss_z", "loss_i", "loss_run", "loss_pallas"):
        losses = sorted((o["pid"], o[key]) for o in outs)
        assert losses[0][1] == pytest.approx(losses[1][1], rel=1e-6)
        assert np.isfinite(losses[0][1]) and losses[0][1] > 0


def test_four_process_2x2_mesh_cross_process_sync():
    """4 processes x 1 device: a 2x2 mesh where BOTH axes cross process
    boundaries (dp psum across {0,2}/{1,3}, tick ppermutes across
    {0,1}/{2,3}) — the layout a real pod runs. Two stateful training steps
    with utils.assert_dp_replicas_in_sync_global after each (each process
    sees one device, so only the cross-process check compares anything),
    plus the negative control: an injected process-divergent array must be
    DETECTED by the checker on every process (see _multihost_worker4.py)."""
    outs = _run_worker_fleet(
        Path(__file__).parent / "_multihost_worker4.py", 4, timeout=300
    )
    assert len(outs) == 4
    assert all(o["sync_ok"] for o in outs)
    assert all(o["desync_detected"] for o in outs)
    for key in ("loss", "loss2"):
        vals = [o[key] for o in outs]
        assert all(v == pytest.approx(vals[0], rel=1e-6) for v in vals)
        assert np.isfinite(vals[0]) and vals[0] > 0
    assert outs[0]["loss2"] < outs[0]["loss"]  # training actually progressed
