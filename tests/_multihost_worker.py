"""Worker process for the REAL multi-host test (spawned by test_multihost.py).

Two of these run concurrently, each contributing 2 emulated CPU devices to a
4-device global runtime via ``jax.distributed`` — the JAX-native analogue of
the reference's ``mpirun -n N`` launch (reference train.py:87-94). Together
they exercise the full multihost surface:

  1. ``multihost.initialize`` against a localhost coordinator;
  2. ``multihost.shard_batch_for_process`` building a global batch from
     per-process shards;
  3. a cross-process ``psum`` over the ``dp`` axis (the DP gradient
     all-reduce path);
  4. one REAL pipeline-executor training step (DP=2 x PP=2, GPipe) over the
     process-spanning mesh, with ``dp`` laid across the process boundary the
     way it would be laid across hosts on a pod;
  5. the same step under ZeRO-1 + gradient clipping: the reduce_scatter that
     shards the gradient and the all_gather that rebuilds the params both
     cross the process boundary;
  6. the same with interleaved virtual stages (P=2 x V=2): ring relays stay
     on-process while the dp reduce crosses the boundary;
  7. the fused multi-epoch program (make_pipeline_run): two epochs in one
     dispatch with the cross-process dp psum inside the epochs-outer scan;
  8. the same step on the PALLAS kernel backend (flag-operand kernels,
     interpret mode on these CPU workers): the per-slot kernel units
     compose with jax.distributed and match the xla backend's loss.

Prints one JSON line {"pid", "psum_ok", "loss", "loss_z", "loss_i",
"loss_run", "loss_pallas"} on success; any assertion failure exits non-zero
and fails the parent test.
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
        flags + ["--xla_force_host_platform_device_count=2"]
    )

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax

    jax.config.update("jax_platforms", "cpu")

    from shallowspeed_tpu.parallel import multihost

    # must run BEFORE any backend-initializing call
    multihost.initialize(
        coordinator_address=f"localhost:{port}", num_processes=2, process_id=pid
    )

    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import schedules as S
    from shallowspeed_tpu.optimizer import SGD
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.local_devices()) == 2
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    assert len(devs) == 4
    # dp rows == processes: stage relays (every tick) stay process-local,
    # the once-per-batch dp psum crosses the process boundary — the layout
    # multihost.py prescribes for real pods (pp on ICI, dp outer)
    mesh = make_mesh(2, 2, devices=devs)

    # --- cross-process DP psum over a process-locally-fed global array -----
    local = np.full((1, 4), float(pid + 1), np.float32)
    arr = multihost.shard_batch_for_process(local, mesh, P("dp"))
    summed = jax.jit(
        shard_map(
            lambda x: lax.psum(x, "dp"), mesh=mesh, in_specs=P("dp"), out_specs=P()
        )
    )(arr)
    np.testing.assert_array_equal(np.asarray(summed), np.full((1, 4), 3.0))

    # --- one real pipeline training step over the process-spanning mesh ----
    SIZES, B, M = (12, 10, 9, 8), 16, 2
    spec = Mo.make_model_spec(SIZES, 2, B)
    prog = lower_schedule(S.GPipeSchedule, M, 2)
    stacked, fl = E.stack_params(Mo.init_model(spec), spec)

    def put_global(x, pspec):
        sh = NamedSharding(mesh, pspec)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    def init_global(spec_, order=None):
        st, flg = E.stack_params(Mo.init_model(spec_), spec_, order=order)
        st = jax.tree.map(lambda x: put_global(x, P("pp")), st)
        flg = jax.tree.map(lambda x: put_global(x, P("pp")), flg)
        return st, flg

    stacked = jax.tree.map(lambda x: put_global(x, P("pp")), stacked)
    fl = jax.tree.map(lambda x: put_global(x, P("pp")), fl)

    rng = np.random.RandomState(0)
    X = rng.randn(B, SIZES[0]).astype(np.float32)
    Y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], B)]
    half = B // 2
    xg = multihost.shard_batch_for_process(X[pid * half : (pid + 1) * half], mesh, P("dp"))
    yg = multihost.shard_batch_for_process(Y[pid * half : (pid + 1) * half], mesh, P("dp"))

    step = E.make_pipeline_step(mesh, spec, prog, half // M, SGD(0.05))
    _, _, loss = step(stacked, fl, (), xg, yg)

    # --- ZeRO-1 across the process boundary --------------------------------
    # dp spans the two processes, so the reduce_scatter that shards the
    # gradient and the all_gather that rebuilds the params BOTH cross it.
    from shallowspeed_tpu.optimizer import MomentumSGD

    opt_z = MomentumSGD(0.05, 0.9)
    st_z, fl_z = init_global(spec)
    oz = E.zero1_init_state(opt_z, spec, mesh)
    step_z = E.make_pipeline_step(
        mesh, spec, prog, half // M, opt_z, zero1=True, clip_norm=1.0
    )
    _, oz, loss_z = step_z(st_z, fl_z, oz, xg, yg)

    # --- interleaved virtual stages under the distributed runtime ---------
    # P=2 x V=2 = 4 model stages on each process's pp pair (ring relays incl.
    # the chunk wrap stay on-process) while the dp gradient reduce crosses
    # the process boundary — the recommended pod layout, in miniature.
    SIZES_I = (12, 11, 10, 9, 9, 8, 8, 8)  # len % (P*V=4) == 0, head owns a Linear
    spec_i = Mo.make_model_spec(SIZES_I, 4, B)
    order = E.interleave_order(4, 2)
    prog_i = lower_schedule(S.InterleavedSchedule, M, 2, virtual=2)
    st_i, fl_i = init_global(spec_i, order=order)
    step_i = E.make_pipeline_step(mesh, spec_i, prog_i, half // M, SGD(0.05))
    _, _, loss_i = step_i(st_i, fl_i, (), xg, yg)

    # --- fused multi-epoch run across the process boundary -----------------
    # the epochs-outer scan (make_pipeline_run) compiled once, executing two
    # epochs with the dp psum crossing processes inside a single dispatch
    st_r, fl_r = init_global(spec)
    run = E.make_pipeline_run(mesh, spec, prog, half // M, SGD(0.05))
    _, _, losses_r = run(st_r, fl_r, (), xg[None], yg[None], 2)
    losses_r = np.asarray(losses_r)
    assert losses_r.shape == (2,) and losses_r[1] < losses_r[0]

    # --- pallas kernel backend under the distributed runtime ---------------
    # identical init to the first GPipe step, so the flag kernels' loss must
    # match the xla backend's across the process-spanning mesh
    st_p, fl_p = init_global(spec)
    step_p = E.make_pipeline_step(
        mesh, spec, prog, half // M, SGD(0.05), kernel_backend="pallas"
    )
    _, _, loss_p = step_p(st_p, fl_p, (), xg, yg)
    np.testing.assert_allclose(float(loss_p), float(loss), rtol=1e-6)

    print(
        json.dumps(
            {
                "pid": pid,
                "psum_ok": True,
                "loss": float(loss),
                "loss_z": float(loss_z),
                "loss_i": float(loss_i),
                "loss_run": float(losses_r[-1]),
                "loss_pallas": float(loss_p),
            }
        )
    )


if __name__ == "__main__":
    main()
