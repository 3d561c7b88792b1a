"""Pallas kernel tests (interpreter mode on CPU, real kernels on TPU).

Verifies the fused linear+relu forward/backward kernels against the XLA path
and that the whole model trains identically with the Pallas backend enabled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import ops, pallas_ops, trainer
from shallowspeed_tpu.optimizer import SGD, Adam, MomentumSGD

RNG = np.random.RandomState(0)


def r(*shape):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32))


class TestKernels:
    def test_fwd_matches_xla(self):
        x, w, b = r(16, 24), r(20, 24), r(1, 20)
        y, mask = pallas_ops.linear_relu_fwd(x, w, b)
        y_ref = ops.relu(ops.linear(x, w, b))
        mask_ref = ops.linear(x, w, b) > 0
        np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(mask) > 0, np.asarray(mask_ref))

    def test_bwd_matches_xla(self):
        x, w = r(16, 24), r(20, 24)
        g = r(16, 20)
        mask = (r(16, 20) > 0).astype(jnp.float32)
        dx, dw, db = pallas_ops.linear_relu_bwd(g, mask, x, w)
        dx_r, dw_r, db_r = ops.linear_grad(g * mask, x, w)
        np.testing.assert_allclose(dx, dx_r, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dw, dw_r, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(db).reshape(-1), db_r, rtol=1e-5, atol=1e-6)

    def test_bwd_matches_autograd(self):
        x, w, b = r(8, 12), r(10, 12), r(1, 10)

        def f_ref(x, w, b):
            return (ops.relu(ops.linear(x, w, b)) ** 2).sum()

        y, mask = pallas_ops.linear_relu_fwd(x, w, b)
        g = 2 * y
        dx, dw, db = pallas_ops.linear_relu_bwd(g, mask, x, w)
        gx, gw, gb = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, b)
        np.testing.assert_allclose(dx, gx, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(dw, gw, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(db, gb, rtol=1e-4, atol=1e-5)


class TestTiledKernels:
    """Grid-tiled variants on ragged shapes: multi-tile grids in every
    dimension plus edge padding, checked against the XLA path."""

    MB, DIN, DOUT, TILE = 300, 260, 200, 128  # 3x3x2 tiles, all ragged

    def test_tiled_fwd_matches_xla(self):
        x, w, b = r(self.MB, self.DIN), r(self.DOUT, self.DIN), r(1, self.DOUT)
        y, mask = pallas_ops.linear_relu_fwd_tiled(x, w, b, tile=self.TILE)
        z = np.asarray(ops.linear(x, w, b))
        # contraction order differs between the tiled kernel and XLA, so z
        # values within float noise of 0 may legitimately flip relu sides
        np.testing.assert_allclose(y, np.maximum(z, 0), rtol=1e-5, atol=1e-4)
        stable = np.abs(z) > 1e-4
        np.testing.assert_array_equal(
            (np.asarray(mask) > 0)[stable], (z > 0)[stable]
        )

    def test_tiled_bwd_matches_xla(self):
        x, w = r(self.MB, self.DIN), r(self.DOUT, self.DIN)
        g = r(self.MB, self.DOUT)
        mask = (r(self.MB, self.DOUT) > 0).astype(jnp.float32)
        dx, dw, db = pallas_ops.linear_relu_bwd_tiled(g, mask, x, w, tile=self.TILE)
        dx_r, dw_r, db_r = ops.linear_grad(g * mask, x, w)
        np.testing.assert_allclose(dx, dx_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(dw, dw_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(db).reshape(-1), db_r, rtol=1e-4, atol=1e-4
        )

    def test_dispatch_picks_tiled_beyond_budget(self, monkeypatch):
        fits = pallas_ops._fwd_bytes(128, 784, 128) <= pallas_ops.SINGLE_BLOCK_BUDGET_BYTES
        assert fits  # flagship layers stay single-block
        assert pallas_ops._fwd_bytes(4096, 8192, 4096) > pallas_ops.SINGLE_BLOCK_BUDGET_BYTES
        assert pallas_ops._bwd_bytes(4096, 8192, 4096) > pallas_ops.SINGLE_BLOCK_BUDGET_BYTES

        # run the PUBLIC entry points down the tiled branch: budget forced to
        # 0 and unique shapes so jit can't serve a cached single-block trace
        monkeypatch.setattr(pallas_ops, "SINGLE_BLOCK_BUDGET_BYTES", 0)
        monkeypatch.setattr(pallas_ops, "TILE", 128)
        mb, din, dout = 37, 29, 23
        x, w, b = r(mb, din), r(dout, din), r(1, dout)
        y, mask = pallas_ops.linear_relu_fwd(x, w, b)
        z = np.asarray(ops.linear(x, w, b))
        np.testing.assert_allclose(y, np.maximum(z, 0), rtol=1e-5, atol=1e-4)
        g = r(mb, dout)
        dx, dw, db = pallas_ops.linear_relu_bwd(g, mask, x, w)
        dx_r, dw_r, db_r = ops.linear_grad(
            g * jnp.asarray(mask), x, w
        )
        np.testing.assert_allclose(dx, dx_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(dw, dw_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(db).reshape(-1), db_r, rtol=1e-4, atol=1e-4
        )


class TestTiledFlagKernels:
    """Grid-tiled flag-operand variants (the executor's oversize-slot path):
    ragged multi-tile grids in every dimension, BOTH flag values, against
    the XLA expression. Tolerances, not bit-equality: a multi-tile
    contraction reassociates the sum vs XLA's full dot."""

    MB, DIN, DOUT, TILE = 300, 260, 200, 128  # 3x3x2 tiles, all ragged

    @pytest.mark.parametrize("flag", [0, 1])
    def test_tiled_flag_fwd_matches_xla(self, flag):
        x, w, b = r(self.MB, self.DIN), r(self.DOUT, self.DIN), r(1, self.DOUT)
        y, mask = pallas_ops.linear_flag_fwd_tiled(
            x, w, b, jnp.int32(flag), tile=self.TILE
        )
        z = np.asarray(ops.linear(x, w, b))
        expect = np.maximum(z, 0) if flag else z
        np.testing.assert_allclose(y, expect, rtol=1e-5, atol=1e-4)
        stable = np.abs(z) > 1e-4  # float noise near 0 may flip the mask
        np.testing.assert_array_equal(
            (np.asarray(mask) > 0)[stable], (z > 0)[stable]
        )

    @pytest.mark.parametrize("flag", [0, 1])
    def test_tiled_flag_bwd_matches_xla(self, flag):
        x, w = r(self.MB, self.DIN), r(self.DOUT, self.DIN)
        g = r(self.MB, self.DOUT)
        mask = (r(self.MB, self.DOUT) > 0).astype(jnp.float32)
        dx, dw, db = pallas_ops.linear_flag_bwd_tiled(
            g, mask, x, w, jnp.int32(flag), tile=self.TILE
        )
        ge = g * mask if flag else g
        dx_r, dw_r, db_r = ops.linear_grad(ge, x, w)
        np.testing.assert_allclose(dx, dx_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(dw, dw_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(db).reshape(-1), db_r, rtol=1e-4, atol=1e-4
        )

    def test_flag_dispatch_picks_tiled_beyond_budget(self, monkeypatch):
        """The PUBLIC executor entry points route over-budget shapes to the
        tiled flag kernels (this was a build-time rejection until r04)."""
        monkeypatch.setattr(pallas_ops, "SINGLE_BLOCK_BUDGET_BYTES", 0)
        monkeypatch.setattr(pallas_ops, "TILE", 128)
        mb, din, dout = 37, 29, 23
        x, w, b = r(mb, din), r(dout, din), r(1, dout)
        for flag in (0, 1):
            y, mask = pallas_ops.linear_flag_fwd(x, w, b, jnp.int32(flag))
            z = np.asarray(ops.linear(x, w, b))
            expect = np.maximum(z, 0) if flag else z
            np.testing.assert_allclose(y, expect, rtol=1e-5, atol=1e-4)
            g = r(mb, dout)
            dx, dw, db = pallas_ops.linear_flag_bwd(
                g, jnp.asarray(mask), x, w, jnp.int32(flag)
            )
            ge = g * jnp.asarray(mask) if flag else g
            dx_r, dw_r, db_r = ops.linear_grad(ge, x, w)
            np.testing.assert_allclose(dx, dx_r, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(dw, dw_r, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(db).reshape(-1), db_r, rtol=1e-4, atol=1e-4
            )


class TestModelIntegration:
    def test_training_identical_with_pallas_backend(self):
        SIZES, B, M = (20, 16, 12, 10), 32, 4
        rng = np.random.RandomState(1)
        X = rng.randn(3, M, B // M, SIZES[0]).astype(np.float32)
        Y = np.eye(SIZES[-1], dtype=np.float32)[
            rng.randint(0, SIZES[-1], (3, M, B // M))
        ]
        results = []
        for use_pallas in (False, True):
            ops.set_pallas(use_pallas)
            try:
                spec = Mo.make_model_spec(SIZES, 1, B)
                params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
                step = trainer.make_train_step(spec, SGD(0.01))
                st = ()
                for i in range(3):
                    params, st = step(params, st, jnp.asarray(X[i]), jnp.asarray(Y[i]))
                results.append([l for s in params for l in s])
            finally:
                ops.set_pallas(False)
        for a, b in zip(*results):
            np.testing.assert_allclose(
                np.asarray(a["W"]), np.asarray(b["W"]), rtol=1e-5, atol=1e-7
            )


class TestMegaKernel:
    """The whole-training-step kernel (fused_train_call, step mode): one op per
    batch — forward, grouped-softmax MSE head, backward, SGD update. The
    bar is BIT-identity with the fused XLA path at both precision classes
    (same dots, same grouped stability max, same update expression)."""

    def _epoch_pair(self, sizes, B, M, nb, precision, lr=0.01, wd=0.0):
        rng = np.random.RandomState(2)
        X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[
                rng.randint(0, sizes[-1], (nb, M, B // M))
            ]
        )
        spec = Mo.make_model_spec(sizes, 1, B)
        out = {}
        for mk in (False, True):
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            epoch = trainer.make_train_epoch(
                spec, SGD(lr, weight_decay=wd), precision=precision,
                fuse_mubatches=True, megakernel=mk,
            )
            params, _, loss = epoch(params, (), X, Y)
            out[mk] = (jax.device_get(params), float(loss))
        return out

    @pytest.mark.parametrize("precision", [None, jax.lax.Precision.HIGHEST])
    def test_epoch_bit_identical_to_fused_xla(self, precision):
        out = self._epoch_pair((20, 16, 12, 10), 32, 4, 3, precision)
        assert out[False][1] == out[True][1]
        for a, b in zip(out[False][0][0], out[True][0][0]):
            np.testing.assert_array_equal(np.asarray(a["W"]), np.asarray(b["W"]))
            np.testing.assert_array_equal(np.asarray(a["b"]), np.asarray(b["b"]))

    def test_flagship_shape_with_weight_decay(self):
        out = self._epoch_pair(
            (784, 128, 127, 126, 125, 124, 123, 10), 128, 4, 2,
            jax.lax.Precision.HIGHEST, wd=1e-4,
        )
        assert out[False][1] == out[True][1]
        for a, b in zip(out[False][0][0], out[True][0][0]):
            np.testing.assert_array_equal(np.asarray(a["W"]), np.asarray(b["W"]))

    def test_fused_run_megakernel_matches(self):
        """The whole-run program (epochs-outer scan + on-device eval) built
        over the mega-kernel batch body reproduces the XLA run exactly."""
        sizes, B, M = (20, 16, 12, 10), 32, 4
        rng = np.random.RandomState(3)
        X = jnp.asarray(rng.rand(2, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, M, B // M))]
        )
        vx = jnp.asarray(rng.rand(16, sizes[0]).astype(np.float32))
        vy = jnp.asarray(np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], 16)])
        spec = Mo.make_model_spec(sizes, 1, B)
        res = {}
        for mk in (False, True):
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            run = trainer.make_train_run(
                spec, SGD(0.01), fuse_mubatches=True, megakernel=mk
            )
            params, _, losses, accs = run(params, (), X, Y, vx, vy, 3)
            res[mk] = (np.asarray(losses), np.asarray(accs))
        np.testing.assert_array_equal(res[False][0], res[True][0])
        np.testing.assert_array_equal(res[False][1], res[True][1])

    def test_megakernel_guards(self):
        class NotAnOptimizer:
            pass

        spec = Mo.make_model_spec((20, 16, 12, 10), 1, 32)
        with pytest.raises(ValueError, match="fuse_mubatches"):
            trainer.make_train_epoch(spec, SGD(0.01), megakernel=True)
        for unsupported in (NotAnOptimizer(), Adam(2e-4)):
            # adam: its traced-exponent bias correction (scalar b ** t)
            # does not compile under Mosaic, so it has no kernel variant
            with pytest.raises(ValueError, match="SGD and momentum"):
                trainer.make_train_epoch(
                    spec, unsupported, fuse_mubatches=True, megakernel=True
                )
        spec2 = Mo.make_model_spec((20, 16, 12, 10), 2, 32)
        with pytest.raises(ValueError, match="single-stage"):
            trainer.make_train_epoch(
                spec2, SGD(0.01), fuse_mubatches=True, megakernel=True
            )
        with pytest.raises(ValueError, match="VMEM"):
            huge = Mo.make_model_spec((4096, 4096, 10), 1, 2048)
            trainer.make_train_epoch(
                huge, SGD(0.01), fuse_mubatches=True, megakernel=True
            )


class TestEpochKernel:
    """The whole-EPOCH kernel (fused_train_call, epoch_mode): the batch axis is the
    Pallas grid, params ride the revisited output blocks — one device op per
    epoch. The bar is BIT-identity with the fused XLA epoch (and hence the
    per-batch mega-kernel) at both precision classes."""

    def _epoch_triple(self, sizes, B, M, nb, precision, lr=0.01, wd=0.0):
        rng = np.random.RandomState(2)
        X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[
                rng.randint(0, sizes[-1], (nb, M, B // M))
            ]
        )
        spec = Mo.make_model_spec(sizes, 1, B)
        out = {}
        for name, kw in {
            "xla": {},
            "mega": {"megakernel": True},
            "epoch": {"epoch_kernel": True},
        }.items():
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            epoch = trainer.make_train_epoch(
                spec, SGD(lr, weight_decay=wd), precision=precision,
                fuse_mubatches=True, **kw,
            )
            params, _, loss = epoch(params, (), X, Y)
            out[name] = (jax.device_get(params), float(loss))
        return out

    @pytest.mark.parametrize("precision", [None, jax.lax.Precision.HIGHEST])
    def test_epoch_kernel_bit_identical(self, precision):
        out = self._epoch_triple((20, 16, 12, 10), 32, 4, 3, precision)
        for other in ("mega", "epoch"):
            assert out["xla"][1] == out[other][1]
            for a, b in zip(out["xla"][0][0], out[other][0][0]):
                np.testing.assert_array_equal(np.asarray(a["W"]), np.asarray(b["W"]))
                np.testing.assert_array_equal(np.asarray(a["b"]), np.asarray(b["b"]))

    def test_flagship_shape_with_weight_decay(self):
        out = self._epoch_triple(
            (784, 128, 127, 126, 125, 124, 123, 10), 128, 4, 2,
            jax.lax.Precision.HIGHEST, wd=1e-4,
        )
        assert out["xla"][1] == out["epoch"][1]
        for a, b in zip(out["xla"][0][0], out["epoch"][0][0]):
            np.testing.assert_array_equal(np.asarray(a["W"]), np.asarray(b["W"]))

    def test_fused_run_epoch_kernel_matches(self):
        """The whole-run program (epochs-outer scan + on-device eval) built
        over the epoch-kernel core reproduces the XLA run exactly — 20
        epochs become ~20 device ops plus eval."""
        sizes, B, M = (20, 16, 12, 10), 32, 4
        rng = np.random.RandomState(3)
        X = jnp.asarray(rng.rand(2, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], (2, M, B // M))]
        )
        vx = jnp.asarray(rng.rand(16, sizes[0]).astype(np.float32))
        vy = jnp.asarray(np.eye(sizes[-1], dtype=np.float32)[rng.randint(0, sizes[-1], 16)])
        spec = Mo.make_model_spec(sizes, 1, B)
        res = {}
        for ek in (False, True):
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            run = trainer.make_train_run(
                spec, SGD(0.01), fuse_mubatches=True, epoch_kernel=ek
            )
            params, _, losses, accs = run(params, (), X, Y, vx, vy, 3)
            res[ek] = (np.asarray(losses), np.asarray(accs))
        np.testing.assert_array_equal(res[False][0], res[True][0])
        np.testing.assert_array_equal(res[False][1], res[True][1])

    def test_epoch_kernel_guards(self):
        spec = Mo.make_model_spec((20, 16, 12, 10), 1, 32)
        with pytest.raises(ValueError, match="fuse_mubatches"):
            trainer.make_train_epoch(spec, SGD(0.01), epoch_kernel=True)
        with pytest.raises(ValueError, match="exclusive"):
            trainer.make_train_epoch(
                spec, SGD(0.01), fuse_mubatches=True, megakernel=True,
                epoch_kernel=True,
            )


class TestMomentumKernels:
    """Heavy-ball variants of the step and epoch kernels: same bar as SGD —
    BIT-identity (params, velocity state, loss) with the fused XLA path
    through optimizer.MomentumSGD."""

    def test_step_and_epoch_momentum_bit_identical(self):
        from shallowspeed_tpu.optimizer import MomentumSGD

        sizes, B, M, nb = (20, 16, 12, 10), 32, 4, 3
        rng = np.random.RandomState(5)
        X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[
                rng.randint(0, sizes[-1], (nb, M, B // M))
            ]
        )
        spec = Mo.make_model_spec(sizes, 1, B)
        opt = MomentumSGD(0.01, momentum=0.9, weight_decay=1e-4)
        out = {}
        for name, kw in {
            "xla": {},
            "mega": {"megakernel": True},
            "epoch": {"epoch_kernel": True},
        }.items():
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            st = opt.init(params)
            epoch = trainer.make_train_epoch(
                spec, opt, fuse_mubatches=True, **kw
            )
            # two epochs so a nonzero velocity feeds the second one
            params, st, _ = epoch(params, st, X, Y)
            params, st, loss = epoch(params, st, X, Y)
            out[name] = (jax.device_get(params), jax.device_get(st), float(loss))
        for other in ("mega", "epoch"):
            assert out["xla"][2] == out[other][2]
            for tree_idx in (0, 1):  # params, then velocity state
                for a, b in zip(
                    jax.tree.leaves(out["xla"][tree_idx]),
                    jax.tree.leaves(out[other][tree_idx]),
                ):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_state_mirror_vmem_accounting(self):
        # exact accounting: each state mirror adds EXACTLY in+out copies
        # (2 x params floats) — an undercount would approve configs that
        # OOM VMEM at Mosaic compile time on chip
        sizes = (700, 700, 10)
        params = 700 * 700 + 700 + 700 * 10 + 10
        for n in (1, 2):
            assert pallas_ops._kernel_bytes(8, sizes, state_mirrors=n) == (
                pallas_ops._kernel_bytes(8, sizes) + n * 4 * 2 * params
            )
        # boundary: this config fits the SGD budget but NOT the momentum
        # budget — the validator must catch the difference
        assert pallas_ops.train_step_kernel_fits(128, sizes)
        assert not pallas_ops.train_step_kernel_fits(128, sizes, state_mirrors=1)
        # the flagship class fits with room for a second mirror
        assert pallas_ops.train_step_kernel_fits(
            128, (784, 128, 10), state_mirrors=2
        )


class TestClipKernels:
    """Global-norm clipping INSIDE the mega/epoch kernels (round-4 verdict
    item #4): with a clip tight enough to bind on every batch, the kernel
    variants must stay BIT-identical (params, optimizer state, loss) to the
    fused XLA path, whose clip goes through optimizer.clip_tree. Also checks
    the clip actually changed training (vs the unclipped kernel run)."""

    def _run(self, opt, kw, clip, seed=9, epochs=2):
        sizes, B, M, nb = (20, 16, 12, 10), 32, 4, 3
        rng = np.random.RandomState(seed)
        X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[
                rng.randint(0, sizes[-1], (nb, M, B // M))
            ]
        )
        spec = Mo.make_model_spec(sizes, 1, B)
        params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
        st = opt.init(params)
        epoch = trainer.make_train_epoch(
            spec, opt, fuse_mubatches=True, clip_norm=clip, **kw
        )
        loss = None
        for _ in range(epochs):
            params, st, loss = epoch(params, st, X, Y)
        return jax.device_get(params), jax.device_get(st), float(loss)

    @pytest.mark.parametrize(
        "opt",
        [
            SGD(0.01, weight_decay=1e-4),
            MomentumSGD(0.01, 0.9),
        ],
        ids=["sgd", "momentum"],
    )
    def test_clip_bit_identical_across_variants(self, opt):
        CLIP = 0.05  # far below the natural grad norm: binds every batch
        outs = {
            name: self._run(opt, kw, CLIP)
            for name, kw in {
                "xla": {},
                "mega": {"megakernel": True},
                "epoch": {"epoch_kernel": True},
            }.items()
        }
        for other in ("mega", "epoch"):
            assert outs["xla"][2] == outs[other][2]
            for tree_idx in (0, 1):  # params, then optimizer state
                for a, b in zip(
                    jax.tree.leaves(outs["xla"][tree_idx]),
                    jax.tree.leaves(outs[other][tree_idx]),
                ):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the clip is live: the clipped epoch-kernel run differs from the
        # unclipped one
        unclipped = self._run(opt, {"epoch_kernel": True}, None)
        assert outs["epoch"][2] != unclipped[2]


class TestRunKernel:
    """The whole-RUN kernel (fused_train_call, n_epochs): the grid is
    (epochs, batches), params + optimizer state VMEM-resident for the whole
    run — ONE device op for the entire training run. The bar is BIT-identity
    (params, state, per-epoch losses) with looping the epoch kernel, and
    hence with fused XLA."""

    def _data(self, sizes, B, M, nb, seed=11):
        rng = np.random.RandomState(seed)
        X = jnp.asarray(rng.rand(nb, M, B // M, sizes[0]).astype(np.float32))
        Y = jnp.asarray(
            np.eye(sizes[-1], dtype=np.float32)[
                rng.randint(0, sizes[-1], (nb, M, B // M))
            ]
        )
        return X, Y

    @pytest.mark.parametrize(
        "opt,clip",
        [
            (SGD(0.01, weight_decay=1e-4), None),
            (MomentumSGD(0.01, 0.9), 0.05),
        ],
        ids=["sgd", "momentum+clip"],
    )
    def test_run_kernel_bit_identical_to_epoch_loop(self, opt, clip):
        sizes, B, M, nb, E = (20, 16, 12, 10), 32, 4, 3, 4
        X, Y = self._data(sizes, B, M, nb)
        spec = Mo.make_model_spec(sizes, 1, B)

        params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
        st = opt.init(params)
        epoch = trainer.make_train_epoch(
            spec, opt, fuse_mubatches=True, epoch_kernel=True, clip_norm=clip
        )
        want_losses = []
        for _ in range(E):
            params, st, loss = epoch(params, st, X, Y)
            want_losses.append(float(loss))
        want = (jax.device_get(params), jax.device_get(st))

        params2 = jax.tree.map(jnp.asarray, Mo.init_model(spec))
        st2 = opt.init(params2)
        run = trainer.make_train_run(
            spec, opt, fuse_mubatches=True, run_kernel=True, with_eval=False,
            clip_norm=clip,
        )
        params2, st2, losses = run(params2, st2, X, Y, E)
        got = (jax.device_get(params2), jax.device_get(st2))

        np.testing.assert_array_equal(
            np.asarray(losses), np.asarray(want_losses, np.float32)
        )
        for tree_idx in (0, 1):
            for a, b in zip(
                jax.tree.leaves(want[tree_idx]), jax.tree.leaves(got[tree_idx])
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_run_kernel_matches_fused_xla_run(self):
        """End of the ladder meets the start: the one-op run reproduces the
        fused-XLA whole-run program's losses exactly."""
        sizes, B, M, nb, E = (20, 16, 12, 10), 32, 4, 2, 3
        X, Y = self._data(sizes, B, M, nb, seed=13)
        spec = Mo.make_model_spec(sizes, 1, B)
        out = {}
        for name, kw in {
            "xla": {},
            "run": {"run_kernel": True},
        }.items():
            params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
            run = trainer.make_train_run(
                spec, SGD(0.01), fuse_mubatches=True, with_eval=False, **kw
            )
            params, _, losses = run(params, (), X, Y, E)
            out[name] = (jax.device_get(params), np.asarray(losses))
        np.testing.assert_array_equal(out["xla"][1], out["run"][1])
        for a, b in zip(
            jax.tree.leaves(out["xla"][0]), jax.tree.leaves(out["run"][0])
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_run_kernel_guards(self):
        spec = Mo.make_model_spec((20, 16, 12, 10), 1, 32)
        with pytest.raises(ValueError, match="with_eval"):
            trainer.make_train_run(
                spec, SGD(0.01), fuse_mubatches=True, run_kernel=True
            )
        with pytest.raises(ValueError, match="subsumes"):
            trainer.make_train_run(
                spec, SGD(0.01), fuse_mubatches=True, run_kernel=True,
                epoch_kernel=True, with_eval=False,
            )
        with pytest.raises(ValueError, match="epoch_mode"):
            pallas_ops.fused_train_call(
                [{"W": jnp.zeros((4, 4)), "b": jnp.zeros(4)}],
                jnp.zeros((8, 4)), jnp.zeros((8, 4)),
                epoch_mode=False, relu_flags=(False,), group_rows=8,
                batch_size=8, lr=0.1, weight_decay=0.0, precision=None,
                n_epochs=2,
            )

    def test_run_kernel_rejects_zero_epochs(self):
        spec = Mo.make_model_spec((20, 16, 12, 10), 1, 32)
        X, Y = self._data((20, 16, 12, 10), 32, 4, 2)
        run = trainer.make_train_run(
            spec, SGD(0.01), fuse_mubatches=True, run_kernel=True,
            with_eval=False,
        )
        params = jax.tree.map(jnp.asarray, Mo.init_model(spec))
        with pytest.raises(ValueError, match="n_epochs >= 1"):
            run(params, (), X, Y, 0)


def test_interpret_rule_is_cpu_only(monkeypatch):
    """Interpreter on a host CPU, Mosaic on a TPU, an error under any other
    backend name — never a silent interpreted run on unknown hardware."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert pallas_ops._interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        pallas_ops._interpret()
