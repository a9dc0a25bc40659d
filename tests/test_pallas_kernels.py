"""Authored Pallas kernels — correctness vs reference math (interpret mode on
the CPU mesh; on TPU the same kernels compile through Mosaic)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.pallas import flash_attention, fused_layer_norm
from paddle_tpu.kernels.pallas.flash_attention import _reference

R = np.random.RandomState(3)


def _qkv(b=2, h=2, s=64, d=32):
    return (jnp.asarray(R.randn(b, h, s, d).astype(np.float32)),
            jnp.asarray(R.randn(b, h, s, d).astype(np.float32)),
            jnp.asarray(R.randn(b, h, s, d).astype(np.float32)))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        b, h, s, d = q.shape
        ref = _reference(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                         v.reshape(b * h, s, d), 1 / np.sqrt(d), causal)
        np.testing.assert_allclose(np.asarray(out).reshape(b * h, s, d),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_ragged_blocks(self):
        # seq not divisible by block: 48 with block 32
        q, k, v = _qkv(s=48)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        b, h, s, d = q.shape
        ref = _reference(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                         v.reshape(b * h, s, d), 1 / np.sqrt(d), True)
        np.testing.assert_allclose(np.asarray(out).reshape(b * h, s, d),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_causal_cross_lengths_bottom_right(self):
        # sq < sk (decode-with-kv-cache shape): mask must be bottom-right
        # aligned so the LAST query row sees the full key prefix, matching
        # _reference's tril(k=sk-sq)
        b, h, sq, sk, d = 1, 2, 4, 64, 16
        q = jnp.asarray(R.randn(b, h, sq, d).astype(np.float32))
        k = jnp.asarray(R.randn(b, h, sk, d).astype(np.float32))
        v = jnp.asarray(R.randn(b, h, sk, d).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=4, block_k=16)
        ref = _reference(q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
                         v.reshape(b * h, sk, d), 1 / np.sqrt(d), True)
        np.testing.assert_allclose(np.asarray(out).reshape(b * h, sq, d),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)
        # forward must now agree with the function the recompute-VJP
        # backward differentiates (the round-2 advisor divergence)
        with pytest.raises(NotImplementedError):
            flash_attention(k, q, v[:, :, :sq, :], causal=True)  # sq > sk

    def test_grads_match_reference(self):
        q, k, v = _qkv(b=1, h=2, s=32, d=16)
        b, h, s, d = q.shape

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=16,
                                    block_k=16) ** 2).sum()

        def fr(q, k, v):
            return (_reference(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                               v.reshape(b * h, s, d), 1 / np.sqrt(d), True)
                    ** 2).sum()

        ga = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a).ravel(),
                                       np.asarray(b_).ravel(),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        q, k, v = _qkv(s=32, d=32)
        q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        assert out.dtype == jnp.bfloat16
        b, h, s, d = q.shape
        ref = _reference(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                         v.reshape(b * h, s, d), 1 / np.sqrt(d), False)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)).reshape(b * h, s, d),
            np.asarray(ref.astype(jnp.float32)), rtol=5e-2, atol=5e-2)


class TestFusedLayerNorm:
    def _ref(self, x, g, b, eps=1e-5):
        mu = x.mean(-1, keepdims=True)
        rs = jax.lax.rsqrt(x.var(-1, keepdims=True) + eps)
        return (x - mu) * rs * g + b

    def test_forward(self):
        x = jnp.asarray(R.randn(100, 64).astype(np.float32))
        g = jnp.asarray(R.randn(64).astype(np.float32))
        b = jnp.asarray(R.randn(64).astype(np.float32))
        y = fused_layer_norm(x, g, b, block_rows=32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(self._ref(x, g, b)),
                                   rtol=1e-5, atol=1e-5)

    def test_3d_input(self):
        x = jnp.asarray(R.randn(4, 7, 32).astype(np.float32))
        g = jnp.ones(32, jnp.float32)
        b = jnp.zeros(32, jnp.float32)
        y = fused_layer_norm(x, g, b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(self._ref(x, g, b)),
                                   rtol=1e-5, atol=1e-5)

    def test_grads(self):
        x = jnp.asarray(R.randn(100, 64).astype(np.float32))
        g = jnp.asarray(R.randn(64).astype(np.float32))
        b = jnp.asarray(R.randn(64).astype(np.float32))

        def loss(x, g, b):
            return (fused_layer_norm(x, g, b, block_rows=32) ** 2).sum()

        def rloss(x, g, b):
            return (self._ref(x, g, b) ** 2).sum()

        ga = jax.grad(loss, argnums=(0, 1, 2))(x, g, b)
        gb = jax.grad(rloss, argnums=(0, 1, 2))(x, g, b)
        for a, b_, name in zip(ga, gb, ["dx", "dgamma", "dbeta"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3, err_msg=name)


# (b, h, sq, sk, d, dtype, forced (block_q, block_k) or None for `_plan`'s)
FLASH_SHAPES = {
    "32": (1, 2, 32, 32, 16, jnp.float32, (16, 16)),
    "48-ragged": (1, 2, 48, 48, 16, jnp.float32, (16, 16)),
    "16x64": (1, 2, 16, 64, 16, jnp.float32, (16, 16)),
    # the training cell's head width at a cut-down length, `_plan`'s tiles:
    # one cell holds the whole sequence and the walk is unrolled; two
    # heads share a group of 128 lanes
    "256-d64": (1, 2, 256, 256, 64, jnp.float32, None),
    "256-d64-bf16": (1, 2, 256, 256, 64, jnp.bfloat16, None),
    # the cell's own length and width: the tiles the chip is handed there
    "1024-d64-bf16": (1, 2, 1024, 1024, 64, jnp.bfloat16, None),
    "300-ragged": (1, 2, 300, 300, 64, jnp.float32, None),
    "128x384": (1, 2, 128, 384, 32, jnp.float32, None),
    # more tiles than a cell holds: grid cells, loops with traced bounds,
    # masked and unmasked tiles apart
    "384-tiles128": (1, 2, 384, 384, 64, jnp.float32, (128, 128)),
    "384-tiles128-bf16": (1, 2, 384, 384, 64, jnp.bfloat16, (128, 128)),
    "200x328-tiles128": (1, 2, 200, 328, 64, jnp.float32, (128, 128)),
    # how heads meet lanes: several groups of two heads over two batch
    # rows (the training cell's 12 heads), four heads a group, a head that
    # owns its lanes, and three heads of 64, which no group of lanes holds
    # whole: they go to the batch axis
    "12-heads-d64-bf16": (2, 12, 256, 256, 64, jnp.bfloat16, None),
    "4-heads-d32": (2, 4, 256, 256, 32, jnp.float32, None),
    "d128": (1, 2, 256, 256, 128, jnp.float32, None),
    "3-heads-d64": (2, 3, 200, 200, 64, jnp.float32, None),
    "3-heads-200x328-tiles128": (2, 3, 200, 328, 64, jnp.float32,
                                 (128, 128)),
}


class TestFlashPlan:
    """What `_plan` and `_pack` hand the chip, from the shapes alone."""

    @pytest.mark.parametrize("heads,d,pack", [
        (12, 64, 2), (16, 64, 2),      # GPT-2 small and 345M: two a group
        (4, 32, 4), (2, 128, 1), (8, 256, 1),
        (2, 16, 2), (1, 64, 1),        # all the heads in under 128 lanes
        (3, 64, 0), (12, 80, 0),       # no group holds whole heads
    ])
    def test_heads_that_share_a_group_of_lanes(self, heads, d, pack):
        from paddle_tpu.kernels.pallas.flash_attention import _pack
        assert _pack(heads, d) == pack

    @pytest.mark.parametrize("call,plan", [
        # the training cell, S 1,024 / D 64 bf16, two heads a group of
        # lanes: the whole sequence a cell, tiles of 256
        ((1024, 1024, 64, 2, 2), (256, 256, 1024, 1024, 2)),
        # ... and in float32
        ((1024, 1024, 64, 4, 2), (256, 256, 1024, 1024, 2)),
        # a small S: one tile a head
        ((128, 128, 64, 2, 2), (128, 128, 128, 128, 2)),
        # ragged lengths round up to the tile
        ((300, 300, 64, 4, 2), (256, 256, 512, 512, 2)),
        ((100, 260, 16, 4, 2), (104, 256, 104, 512, 2)),
        # more tiles than a walk unrolls: a tile a cell, traced bounds
        ((8192, 8192, 128, 2, 1), (256, 256, 256, 256, 1)),
    ])
    def test_tiles_and_cells(self, call, plan):
        from paddle_tpu.kernels.pallas.flash_attention import _plan
        assert tuple(_plan(*call)) == plan

    def test_forced_tiles_make_cells_of_one_tile(self):
        from paddle_tpu.kernels.pallas.flash_attention import _plan
        assert tuple(_plan(384, 384, 64, 4, 2, block_q=128,
                           block_k=128)) == (128, 128, 128, 128, 2)


class TestFlashBackwardKernels:
    """The authored Pallas forward and backward (one kernel recomputing
    p from the saved logsumexp) vs reference-math output and grads."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", FLASH_SHAPES)
    def test_grads_match_reference(self, causal, shape):
        b, h, sq, sk, d, dtype, blocks = FLASH_SHAPES[shape]
        mk = lambda s: jnp.asarray(  # noqa: E731
            R.randn(b, h, s, d).astype(np.float32)).astype(dtype)
        q, k, v = mk(sq), mk(sk), mk(sk)
        kw = {} if blocks is None else {"block_q": blocks[0],
                                        "block_k": blocks[1]}
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

        def f(q, k, v):
            out = flash_attention(q, k, v, causal=causal, **kw)
            return (f32(out) ** 2).sum(), out

        def fr(q, k, v):
            out = _reference(f32(q).reshape(b * h, sq, d),
                             f32(k).reshape(b * h, sk, d),
                             f32(v).reshape(b * h, sk, d),
                             1 / np.sqrt(d), causal).reshape(b, h, sq, d)
            return (out ** 2).sum(), out

        ga, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        gb, ref = jax.grad(fr, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        assert out.dtype == dtype
        # bf16: the probabilities and ds are rounded to bf16 for their
        # products, the reference keeps float32 throughout
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        for a, b_, name in zip((out,) + ga, (ref,) + gb, ("out", "dq",
                                                          "dk", "dv")):
            a, b_ = np.asarray(f32(a)), np.asarray(f32(b_))
            np.testing.assert_allclose(
                a.ravel(), b_.ravel(), rtol=tol,
                atol=tol * max(1.0, np.abs(b_).max()), err_msg=name)

    def test_float32_inputs_keep_float32_products(self):
        # inputs whose information sits below bf16's eight bits: a kernel
        # that rounded its operands to bf16 would answer for other inputs
        b, h, s, d = 1, 2, 256, 64
        mk = lambda: jnp.asarray(  # noqa: E731
            (1.0 + R.randn(b, h, s, d) * 2.0 ** -10).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        out = np.asarray(flash_attention(q, k, v, causal=True))
        rounded = [x.astype(jnp.bfloat16).astype(jnp.float32)
                   .reshape(b * h, s, d) for x in (q, k, v)]
        ref = np.asarray(_reference(q.reshape(b * h, s, d),
                                    k.reshape(b * h, s, d),
                                    v.reshape(b * h, s, d), 1 / np.sqrt(d),
                                    True)).reshape(out.shape)
        lossy = np.asarray(_reference(*rounded, 1 / np.sqrt(d),
                                      True)).reshape(out.shape)
        assert np.abs(lossy - ref).max() > 1e-4        # the test can see it
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)

    def test_bf16_grads_finite_and_close(self):
        b, h, s, d = 1, 2, 32, 32
        mk = lambda: jnp.asarray(
            R.randn(b, h, s, d).astype(np.float32)).astype(jnp.bfloat16)
        q, k, v = mk(), mk(), mk()

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=16,
                                    block_k=16).astype(jnp.float32)
                    ** 2).sum()

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for a in g:
            arr = np.asarray(a.astype(jnp.float32))
            assert np.isfinite(arr).all()
            assert np.abs(arr).max() > 0
