"""Serving fleet tests: router placement/quorum/failover bookkeeping as
pure logic, and the multi-process fleet itself — replica workers, death ->
failover requeue, elastic scale, loadgen graceful drain, the CLI's
quorum-down exit code (docs/serving.md "Fleet", docs/robustness.md).

The multi-process tests carry the ``fleet`` marker and skip-with-reason
when the platform cannot spawn worker processes (the multihost
collectives skip, mirrored); the router/request tests run everywhere.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from shallowspeed_tpu.serving import fleet as fleet_mod
from shallowspeed_tpu.serving import loadgen, router
from shallowspeed_tpu.serving.fleet import ServingFleet

SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
GBS = 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(tmp_path / f"x_{suffix}.npy", x)
        np.save(tmp_path / f"y_{suffix}.npy", y)
    return tmp_path


def test_fleet_refuses_an_accelerator_host_unless_cpu_was_chosen(monkeypatch):
    """The fleet is CPU-only and says so: where the default backend is a
    chip it will not quietly become a set of CPU replicas."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(fleet_mod.FleetError, match="JAX_PLATFORMS=cpu"):
        fleet_mod.require_cpu_host()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    fleet_mod.require_cpu_host()  # chosen on purpose: fine
    assert fleet_mod._child_env({"dp": 1})["JAX_PLATFORMS"] == "cpu"


# ---------------------------------------------------------------------------
# router: pure placement/quorum logic (no processes)
# ---------------------------------------------------------------------------


def _ready(rid, queue_depth=0, inflight=0, degraded=False):
    info = router.ReplicaInfo(rid)
    info.state = "ready"
    info.queue_depth = queue_depth
    info.inflight = inflight
    info.degraded = degraded
    return info


def test_quorum_majority_of_target():
    assert [router.quorum(n) for n in (1, 2, 3, 4, 5)] == [1, 2, 2, 3, 3]


def test_least_queue_places_on_lowest_load():
    r = router.Router(policy="least_queue", seed=0)
    replicas = [_ready(0, queue_depth=4), _ready(1, inflight=1), _ready(2)]
    assert r.place(replicas).replica_id == 2
    # load counts BOTH heartbeated queue depth and un-acked in-flight
    replicas[2].inflight = 5
    assert r.place(replicas).replica_id == 1


def test_placement_skips_unroutable_replicas():
    r = router.Router(seed=0)
    degraded = _ready(0, degraded=True)
    starting = router.ReplicaInfo(1)  # state "starting"
    draining = _ready(2)
    draining.state = "draining"
    assert r.place([degraded, starting, draining]) is None
    healthy = _ready(3, queue_depth=99)
    assert r.place([degraded, starting, draining, healthy]).replica_id == 3


def test_p2c_seeded_and_prefers_less_loaded():
    """Power-of-two-choices: two seeded candidates, the less-loaded wins —
    and the same seed replays the same placement sequence."""
    def run(seed):
        r = router.Router(policy="p2c", seed=seed)
        replicas = [_ready(i, queue_depth=i) for i in range(6)]
        return [r.place(replicas).replica_id for _ in range(30)]

    a, b = run(7), run(7)
    assert a == b  # seeded -> replayable
    # the heaviest replica (load 5) can only win a draw against itself,
    # which sampling-without-replacement forbids
    assert 5 not in a


def test_tie_break_spreads_instead_of_pinning():
    """Equal-load ties draw from the seeded stream: a fixed tie-break
    would pin every low-load request to replica 0 and read as
    pathological routing skew."""
    r = router.Router(policy="least_queue", seed=3)
    replicas = [_ready(i) for i in range(3)]
    placed = [r.place(replicas).replica_id for _ in range(60)]
    assert set(placed) == {0, 1, 2}


def test_bounded_fleet_queue_and_requeue_at_head():
    r = router.Router(max_queue=2, seed=0)
    reqs = [
        router.FleetRequest(i, np.zeros((1, 4), np.float32), None, float(i))
        for i in range(4)
    ]
    assert r.admit(reqs[0]) and r.admit(reqs[1])
    assert not r.admit(reqs[2])  # bound hit -> caller drops, never silence
    # failover re-admission goes to the HEAD in original submit order
    r.requeue_head([reqs[2], reqs[3]])
    assert [q.id for q in r.queue] == [2, 3, 0, 1]


def test_routing_skew_definition():
    assert router.routing_skew([]) is None
    assert router.routing_skew([0, 0]) is None
    assert router.routing_skew([10, 10]) == 1.0
    assert router.routing_skew([30, 10, 20]) == pytest.approx(1.5)


def test_fleet_request_accounting():
    req = router.FleetRequest(0, np.zeros((3, 4), np.float32), 100.0, 10.0)
    assert req.rows == 3 and req.verdict == "queued"
    assert req.latency_s is None and req.slo_ok() is None
    # the worker is told the REMAINING deadline: fleet queue wait already
    # burned against the budget (coordinated-omission, one level up)
    assert req.remaining_deadline_ms(10.04) == pytest.approx(60.0)
    req.route_t = 10.05
    req.complete_t = 10.08
    assert req.queue_s == pytest.approx(0.05)
    assert req.latency_s == pytest.approx(0.08)
    assert req.slo_ok() is True  # its own 100 ms tag
    assert req.slo_ok(slo_ms=1.0) is True  # own tag wins over the SLO
    untagged = router.FleetRequest(1, np.zeros((1, 4), np.float32), None, 0.0)
    untagged.complete_t = 2.0
    assert untagged.remaining_deadline_ms(1.0) is None
    assert untagged.slo_ok(slo_ms=1000.0) is False


# ---------------------------------------------------------------------------
# the multi-process fleet
# ---------------------------------------------------------------------------


def _require_workers():
    if not fleet_mod.fleet_workers_supported():
        pytest.skip(
            "this platform cannot spawn fleet worker processes "
            "(multiprocessing spawn context unavailable or broken)"
        )


def _worker_config(data_dir, ck=None, **engine_kw):
    return {
        "session": dict(
            sizes=SIZES,
            global_batch_size=GBS,
            lr=0.01,
            data_dir=os.fspath(data_dir),
            resume=None if ck is None else os.fspath(ck),
            # a two-rung ladder keeps each worker's warm-up to two small
            # compiles — the tests measure fleet behavior, not XLA
            predict_slot_ladder=(1, 2),
        ),
        "engine": dict(retry=2, breaker_threshold=3, **engine_kw),
        "verify": True,
    }


@pytest.fixture(scope="module")
def fleet_checkpoint(tmp_path_factory):
    """One seed checkpoint every fleet test serves (saved via the PR 6
    path, restored by every worker through the loader)."""
    from shallowspeed_tpu.api import TrainingSession

    base = tmp_path_factory.mktemp("fleet_ck")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(base / f"x_{suffix}.npy", x)
        np.save(base / f"y_{suffix}.npy", y)
    session = TrainingSession(
        sizes=SIZES, global_batch_size=GBS, lr=0.01, data_dir=base
    )
    session.train_epoch()
    ck = base / "serve.npz"
    session.save(ck)
    return base, ck, session


@pytest.mark.fleet
@pytest.mark.slow  # 1-core wall budget; make fleet-smoke drives this end to end
def test_fleet_serves_failover_and_scales(fleet_checkpoint):
    """The tentpole end to end, in one fleet lifetime: 3 checkpoint-loaded
    replicas serve a seeded open-loop stream (worker-verified bitwise
    parity); one replica is SIGKILLed mid-stream — its un-acked in-flight
    requests fail over (requeue-at-head) and every admitted request still
    reaches a terminal verdict; a replacement scales up from the same
    weights without raising the quorum bar; a scale-down drains and
    retires cleanly."""
    _require_workers()
    data_dir, ck, parent_session = fleet_checkpoint
    done = []
    with ServingFleet(
        _worker_config(data_dir, ck),
        n_replicas=3,
        slo_ms=5000,
        retry=2,
        seed=0,
    ) as fleet:
        fleet.start()
        assert fleet.n_ready == 3 and not fleet.degraded
        payloads = loadgen.request_payloads(40, SIZES[0], seed=0)
        arrivals = loadgen.poisson_arrivals(400.0, 40, seed=0)
        t0 = fleet.clock()
        i, killed, scaled = 0, False, False
        submitted = []
        while i < 40 or fleet.queue_depth:
            now = fleet.clock() - t0
            while i < 40 and arrivals[i] <= now:
                submitted.append(
                    fleet.submit(payloads[i], arrival_t=t0 + arrivals[i])
                )
                i += 1
            done.extend(fleet.step())
            if not killed and len(done) >= 5:
                infos = [r for r in fleet.replicas.values() if r.state == "ready"]
                victim = max(infos, key=lambda r: (r.inflight, -r.replica_id))
                fleet.sigkill_replica(victim.replica_id)
                killed = True
            if killed and not scaled and any(
                r.state == "dead" for r in fleet.replicas.values()
            ):
                fleet.scale_up(wait_ready=False)  # replacement, off-path
                scaled = True
            if not fleet.queue_depth and i < 40:
                time.sleep(max(0.0, arrivals[i] - (fleet.clock() - t0)))
        assert killed and scaled
        # terminal-verdict contract, fleet-wide: nothing admitted is still
        # "queued", SIGKILL or not
        assert all(r.verdict != "queued" for r in submitted)
        # 2 healthy of target 3 is a quorum: the kill must not have
        # degraded admission, so nothing was dropped
        assert all(r.verdict == "ok" for r in submitted)
        # worker-side bitwise parity held on every ok response
        assert fleet.parity_mismatches == 0
        assert all(r.parity_ok for r in done if r.verdict == "ok")
        # ...and the fleet's responses match the PARENT's own session on
        # the same checkpoint (cross-process determinism of the slot
        # programs — the fleet-level parity claim)
        sample = next(r for r in done if r.verdict == "ok")
        np.testing.assert_array_equal(
            sample.result, parent_session.predict(sample.x)
        )
        fleet.wait_ready()  # let the replacement finish warming
        stats = fleet.stats()
        assert stats["replicas_dead"] == 1
        assert stats["failovers"] >= 1 or stats["failover_requeued"] >= 0
        assert stats["scale_ups"] == 1 and stats["scale_up_s"] is not None
        assert stats["replicas_target"] == 3  # replacement, not growth
        assert stats["availability"] == 1.0
        assert stats["recovery_s"] is not None
        assert not fleet.degraded
        # drain-and-retire: the newest routable replica leaves cleanly
        retired = fleet.scale_down()
        deadline = time.time() + 60
        while (
            fleet.replicas[retired].state != "retired"
            and time.time() < deadline
        ):
            fleet.step()
        assert fleet.replicas[retired].state == "retired"
        assert fleet.target_replicas == 2


@pytest.mark.fleet
def test_loadgen_open_loop_should_stop_drains_fleet(fleet_checkpoint):
    """Satellite: the loadgen drivers run unchanged over the router.
    A seeded open-loop stream stopped mid-flight (should_stop) stops
    ADMISSION but drains everything already admitted to a terminal
    verdict, and the coordinated-omission backdating survives the fleet
    hop (enqueue timestamps equal the scheduled arrivals)."""
    _require_workers()
    data_dir, ck, _ = fleet_checkpoint
    with ServingFleet(
        _worker_config(data_dir, ck), n_replicas=2, slo_ms=5000, seed=0
    ) as fleet:
        fleet.start()
        payloads = loadgen.request_payloads(24, SIZES[0], seed=1)
        arrivals = loadgen.poisson_arrivals(300.0, 24, seed=1)
        seen = []
        orig_submit = fleet.submit

        def tracking_submit(x, deadline_ms=None, arrival_t=None):
            req = orig_submit(x, deadline_ms=deadline_ms, arrival_t=arrival_t)
            seen.append((req, arrival_t))
            return req

        fleet.submit = tracking_submit
        stop_after = 10

        def should_stop():
            return len(seen) >= stop_after

        done = loadgen.run_open_loop(
            fleet, payloads, arrivals, deadline_ms=None,
            should_stop=should_stop,
        )
        # admission stopped mid-stream; everything admitted drained to a
        # terminal verdict — the graceful-drain contract, fleet-wide
        assert stop_after <= len(seen) < 24
        assert fleet.queue_depth == 0
        assert all(req.verdict != "queued" for req, _ in seen)
        assert done and {r.verdict for r in done} == {"ok"}
        # coordinated-omission accounting preserved across the router:
        # every enqueue timestamp IS the scheduled arrival it was
        # backdated to
        for req, arrival_t in seen:
            assert req.enqueue_t == pytest.approx(arrival_t)


@pytest.mark.fleet
def test_fleet_cli_exit_3_when_quorum_down(data_dir):
    """Satellite: the serve CLI's fleet exit-code contract. A 1-replica
    fleet whose only replica is SIGKILLed by the env fault plan (the
    engine's own chaos grammar, inherited by the worker) leaves the
    fleet quorum-down at exit -> documented exit code 3, with every
    admitted request still reaching a terminal verdict first."""
    _require_workers()
    # the CLI serves the flagship model: 784-wide data for this one
    rng = np.random.RandomState(0)
    flag_dir = data_dir / "flagship"
    flag_dir.mkdir()
    for suffix, n in (("train", 256), ("val", 96)):
        np.save(flag_dir / f"x_{suffix}.npy",
                rng.rand(n, 784).astype(np.float32))
        np.save(flag_dir / f"y_{suffix}.npy",
                np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["SHALLOWSPEED_FAULTS"] = "die@dispatch=1:mode=sigkill"
    proc = subprocess.run(
        [
            sys.executable, "-m", "shallowspeed_tpu.serving",
            "--fleet", "1", "--data-dir", os.fspath(flag_dir),
            "--global-batch-size", str(GBS),
            "--slot-ladder", "1,2",
            "--requests", "12", "--rate", "300", "--seed", "0",
        ],
        env=env,
        cwd=Path(__file__).resolve().parent.parent,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert "DEGRADED at exit (quorum of replicas down)" in proc.stderr
    # the kill cost capacity, never silence: the summary still accounts
    # every admitted request as a terminal verdict
    assert "completed" in proc.stdout


def test_fleet_rejects_bad_config():
    with pytest.raises(ValueError, match="n_replicas"):
        ServingFleet({}, n_replicas=0)
    with pytest.raises(ValueError, match="inflight_window"):
        ServingFleet({}, n_replicas=1, inflight_window=0)
    with pytest.raises(ValueError, match="policy"):
        router.Router(policy="round_robin")
