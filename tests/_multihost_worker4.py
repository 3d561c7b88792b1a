"""Worker for the 4-process multihost test (spawned by test_multihost.py).

Four of these form a 4-device global runtime (ONE emulated CPU device per
process) and build a 2x2 mesh where EVERY mesh axis spans process
boundaries — the layout nothing in the 2-process test exercises:

  - the dp axis crosses processes {0,2} and {1,3}: the per-batch gradient
    psum is a true cross-process collective;
  - the pp axis crosses processes {0,1} and {2,3}: every tick's ppermute
    relay crosses a process boundary;
  - each process addresses exactly ONE device, so the LOCAL replica-sync
    assert can see nothing — only the cross-process check
    (utils.assert_dp_replicas_in_sync_global) actually compares replicas.

Phases: two momentum-SGD pipeline steps (state carried) with the global
sync assert after each; then a NEGATIVE control — a deliberately
process-divergent replicated array must make the global checker raise on
every process (a checker that can't detect desync proves nothing).

Prints one JSON line {"pid", "sync_ok", "desync_detected", "loss",
"loss2"}; any failure exits non-zero and fails the parent test.
"""

import json
import os
import sys


def main():
    pid, port = int(sys.argv[1]), int(sys.argv[2])
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=1"]
    )

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax

    jax.config.update("jax_platforms", "cpu")

    from shallowspeed_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=f"localhost:{port}", num_processes=4, process_id=pid
    )

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu import utils
    from shallowspeed_tpu.optimizer import MomentumSGD
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh

    assert jax.process_count() == 4, jax.process_count()
    assert len(jax.local_devices()) == 1
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    assert len(devs) == 4
    # rows = dp, cols = pp: dp row 0 is processes {0,1}, row 1 is {2,3};
    # the dp collective pairs {0,2}/{1,3} and the pp relay pairs {0,1}/{2,3}
    # — every axis crosses processes
    mesh = make_mesh(2, 2, devices=devs)

    SIZES, B, M = (12, 10, 9, 8), 16, 2
    spec = Mo.make_model_spec(SIZES, 2, B)
    prog = lower_schedule(S.GPipeSchedule, M, 2)

    def put_global(x, pspec):
        sh = NamedSharding(mesh, pspec)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    st_np, fl_np = E.stack_params(Mo.init_model(spec), spec)
    stacked = jax.tree.map(lambda x: put_global(x, P("pp")), st_np)
    fl = jax.tree.map(lambda x: put_global(x, P("pp")), fl_np)

    rng = np.random.RandomState(0)
    X = rng.randn(B, SIZES[0]).astype(np.float32)
    Y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], B)]
    half = B // 2
    row = pid // 2  # this process's dp row
    xg = multihost.shard_batch_for_process(
        X[row * half : (row + 1) * half], mesh, P("dp")
    )
    yg = multihost.shard_batch_for_process(
        Y[row * half : (row + 1) * half], mesh, P("dp")
    )

    opt = MomentumSGD(0.05, 0.9)
    ost = opt.init({"W": stacked["W"], "b": stacked["b"]})
    step = E.make_pipeline_step(mesh, spec, prog, half // M, opt)

    # sync_ok is WIRED, not asserted-by-construction: a desync makes this
    # worker print sync_ok=false and exit non-zero (both visible upstream)
    sync_ok = True
    try:
        stacked, ost, loss = step(stacked, fl, ost, xg, yg)
        utils.assert_dp_replicas_in_sync_global(stacked)
        stacked, ost, loss2 = step(stacked, fl, ost, xg, yg)
        utils.assert_dp_replicas_in_sync_global(stacked)
        utils.assert_dp_replicas_in_sync_global(ost)  # momentum state too
    except ValueError as e:
        print(json.dumps({"pid": pid, "sync_ok": False, "error": str(e)}))
        sys.exit(1)

    # negative control: a "replicated" array whose process-3 copy diverges
    # MUST be caught (every device holds the full array = same shard index)
    bad_local = np.full((2, 3), 1.0 + (0.5 if pid == 3 else 0.0), np.float32)
    bad = multihost.shard_batch_for_process(bad_local, mesh, P())
    desync_detected = False
    try:
        utils.assert_dp_replicas_in_sync_global(bad)
    except ValueError:
        desync_detected = True

    print(
        json.dumps(
            {
                "pid": pid,
                "sync_ok": sync_ok,
                "desync_detected": desync_detected,
                "loss": float(loss),
                "loss2": float(loss2),
            }
        )
    )


if __name__ == "__main__":
    main()
