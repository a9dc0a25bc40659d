"""Kernel-level parity tests: fused LM-head CE and XLA flash attention
(OpTest-style numpy/naive oracles; ref methodology `op_test.py:327`)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _naive_attention(q, k, v, causal):
    D = q.shape[-1]
    s = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhqd,bhkd->bhqk", (q * s).astype(jnp.float32),
                        k.astype(jnp.float32))
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        m = jnp.arange(Sk)[None, :] <= (jnp.arange(Sq)[:, None] + (Sk - Sq))
        logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


class TestXlaFlash:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_bwd_parity_f32(self, causal):
        from paddle_tpu.kernels.flash_attention import _xla_flash
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
                   for _ in range(3))
        o = _xla_flash(q, k, v, causal, None)
        ref = _naive_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g = jax.grad(lambda q, k, v: (_xla_flash(q, k, v, causal, None)
                                      ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (_naive_attention(q, k, v, causal)
                                       ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_qblocked_causal(self):
        """S > 2048 exercises the q-blocked loop with causal K-prefix slicing."""
        from paddle_tpu.kernels.flash_attention import _xla_flash
        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(1, 2, 4096, 8), jnp.float32)
                   for _ in range(3))
        o = _xla_flash(q, k, v, True, None)
        ref = _naive_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_cache_offset(self):
        """Sq < Sk (KV cache decode): causal offset measured on full K."""
        from paddle_tpu.kernels.flash_attention import _xla_flash
        rng = np.random.RandomState(2)
        k, v = (jnp.asarray(rng.randn(1, 2, 128, 8), jnp.float32)
                for _ in range(2))
        q = jnp.asarray(rng.randn(1, 2, 16, 8), jnp.float32)
        o = _xla_flash(q, k, v, True, None)
        ref = _naive_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestFusedCE:
    def _ref(self, h, w, lab):
        logits = (h @ w.T).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        valid = (lab >= 0) & (lab < w.shape[0])
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(logits, safe[:, None], 1)[:, 0]
        return jnp.where(valid, lse - picked, 0.0)

    def test_fwd_bwd_parity(self):
        from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(32, 16), jnp.float32)
        w = jnp.asarray(rng.randn(64, 16) * 0.1, jnp.float32)
        lab = jnp.asarray(rng.randint(0, 64, 32), jnp.int32)
        np.testing.assert_allclose(
            np.asarray(fused_linear_cross_entropy(h, w, lab)),
            np.asarray(self._ref(h, w, lab)), rtol=5e-3, atol=5e-3)
        g = jax.grad(lambda h, w: fused_linear_cross_entropy(h, w, lab).mean(),
                     argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h, w: self._ref(h, w, lab).mean(),
                      argnums=(0, 1))(h, w)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3)

    def test_ignore_index(self):
        """-100-padded labels: zero loss and zero grad, never inf/NaN
        (regression: unhandled out-of-range labels picked -inf)."""
        from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(8, 16), jnp.float32)
        w = jnp.asarray(rng.randn(64, 16) * 0.1, jnp.float32)
        lab = jnp.asarray([3, -100, 7, -100, 1, 2, -100, 5], jnp.int32)
        loss = fused_linear_cross_entropy(h, w, lab)
        assert np.all(np.isfinite(np.asarray(loss)))
        assert float(loss[1]) == 0.0 and float(loss[3]) == 0.0
        dh = jax.grad(lambda h: fused_linear_cross_entropy(h, w, lab).sum())(h)
        assert np.all(np.isfinite(np.asarray(dh)))
        np.testing.assert_array_equal(np.asarray(dh[1]), 0.0)


    # (N, hidden, V) -> the forward's body: the Pallas kernel where `_plan`
    # fits the shapes (several row blocks and vocabulary tiles at these
    # sizes), XLA's where the vocabulary is no multiple of a tile or the
    # rows of a block
    BODIES = {"3x5_tiles": ((384, 128, 640), "pallas"),
              "1x2_tiles": ((256, 128, 1024), "pallas"),
              "ragged_vocabulary": ((384, 128, 600), "xla"),
              "ragged_rows": ((200, 128, 640), "xla")}

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", BODIES)
    def test_forward_bodies_agree(self, monkeypatch, case, dtype):
        """On a TPU's name the forward is the Pallas kernel (here in the
        interpreter) wherever the plan fits, else XLA's body; either way
        loss, dh and dW are the reference's and the other body's, and the
        trace counts the body it took, once."""
        from paddle_tpu.kernels import registry
        from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
        from paddle_tpu.kernels.pallas import fused_ce as kernel
        from paddle_tpu.observability import metrics
        (n, hid, v), body = self.BODIES[case]
        plan = kernel._plan(n, hid, v)
        assert (plan is not None) == (body == "pallas")
        rng = np.random.RandomState(1)
        h = jnp.asarray(rng.randn(n, hid), dtype)
        w = jnp.asarray(rng.randn(v, hid) * 0.1, dtype)
        lab = rng.randint(0, v, n)
        tile = plan.tile_v if plan else 128
        # ignored rows, a label in the last tile (its last column), one on
        # a tile's first column
        lab[[3, n - 1]], lab[5], lab[6] = -100, v - 1, tile
        lab = jnp.asarray(lab, jnp.int32)

        def head(h, w):
            loss = fused_linear_cross_entropy(h, w, lab)
            return loss.sum() / (n - 2), loss

        step = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
            head, argnums=(0, 1), has_aux=True))(h, w)
        count = lambda b: metrics.counter(  # noqa: E731
            f"kernel.fused_ce.forward.{b}").value
        was = {b: count(b) for b in ("pallas", "xla")}
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        (_, loss), (dh, dw) = step()
        other = "xla" if body == "pallas" else "pallas"
        assert count(body) == was[body] + 1 and count(other) == was[other]
        monkeypatch.undo()
        (_, loss_x), (dh_x, dw_x) = step()
        assert count("xla") == was["xla"] + 1 + (body == "xla")

        f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
        assert f32(loss)[3] == 0.0 and f32(loss)[n - 1] == 0.0
        assert not f32(dh)[3].any()
        np.testing.assert_allclose(f32(loss), f32(loss_x), rtol=0, atol=2e-6)
        ref, (rdh, rdw) = jax.value_and_grad(
            lambda h, w: self._ref(h, w, lab).sum() / (n - 2),
            argnums=(0, 1))(h.astype(jnp.float32), w.astype(jnp.float32))
        np.testing.assert_allclose(f32(loss).sum() / (n - 2), float(ref),
                                   rtol=2e-3)
        # dlogits is rounded to bf16 by both bodies, and the results to
        # the inputs' dtype
        near = 1e-4 if dtype == jnp.float32 else 1e-2
        for got, xla, want in ((dh, dh_x, rdh), (dw, dw_x, rdw)):
            scale = float(np.abs(f32(want)).max())
            np.testing.assert_allclose(f32(got), f32(xla), rtol=0,
                                       atol=near * scale)
            np.testing.assert_allclose(f32(got), f32(want), rtol=0,
                                       atol=3e-2 * scale)

    def test_partitioned_program_takes_xla_body(self, monkeypatch):
        """Under an installed multi-device mesh the trace is a program
        GSPMD partitions; a Mosaic kernel cannot join it."""
        import types
        from paddle_tpu.kernels import fused_ce, registry
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        assert fused_ce._pallas_plan(384, 128, 640) is not None
        monkeypatch.setattr(fused_ce, "get_mesh",
                            lambda: types.SimpleNamespace(size=1))
        assert fused_ce._pallas_plan(384, 128, 640) is not None
        monkeypatch.setattr(fused_ce, "get_mesh",
                            lambda: types.SimpleNamespace(size=4))
        assert fused_ce._pallas_plan(384, 128, 640) is None


class TestFusedOptimizerStateRetention:
    def test_freeze_unfreeze_keeps_moments(self):
        """Changing the grad-bearing param set must spill+reseed flat state,
        not silently zero the moments (regression)."""
        m = nn.Linear(4, 4)
        opt = paddle.optimizer.Adam(learning_rate=0.1,
                                    parameters=m.parameters())
        x = paddle.randn([2, 4])
        # step 1: bias frozen
        m.bias.stop_gradient = True
        (m(x) ** 2).sum().backward()
        opt.step()
        opt.clear_grad()
        sd1 = opt.state_dict()
        wkey = next(k for k in sd1 if k.endswith("_moment1_0")
                    and m.weight.name in k)
        m1 = np.array(sd1[wkey]._data)
        assert np.abs(m1).sum() > 0
        # step 2: bias unfrozen -> group rebuild must keep weight moments
        m.bias.stop_gradient = False
        (m(x) ** 2).sum().backward()
        opt.step()
        opt.clear_grad()
        sd2 = opt.state_dict()
        m2 = np.array(sd2[wkey]._data)
        # moment1 = 0.9*m1 + 0.1*g, with m1 != 0 the decayed part must survive
        assert np.abs(m2 - 0.9 * m1).max() < np.abs(m1).max(), (m1, m2)

    def test_lars_not_fused(self):
        from paddle_tpu.optimizer.optimizers import LarsMomentum
        assert LarsMomentum._FUSABLE is False


class TestSdpaDropout:
    def test_attention_dropout_actually_applied(self):
        """_sdpa_xla must apply dropout (regression: dropout_p was ignored)."""
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 16, 4, 8).astype(np.float32))
        out_nodrop = F.scaled_dot_product_attention(
            q, q, q, dropout_p=0.9, is_causal=True, training=False)
        out_drop = F.scaled_dot_product_attention(
            q, q, q, dropout_p=0.9, is_causal=True, training=True)
        a = np.asarray(out_nodrop._data)
        b = np.asarray(out_drop._data)
        assert not np.allclose(a, b), "dropout_p had no effect in training"
        # and two training calls differ (rng advances)
        c = np.asarray(F.scaled_dot_product_attention(
            q, q, q, dropout_p=0.9, is_causal=True, training=True)._data)
        assert not np.allclose(b, c)


class TestFusedRotaryEmbedding:
    """`incubate.nn.fused_rotary_position_embedding` against a numpy
    half-rotation (the pair of element i < D / 2 is i + D / 2)."""

    @staticmethod
    def _case(seed=0, b=2, h=3, s=40, d=16):
        rng = np.random.RandomState(seed)
        q, k, w = (rng.randn(b, h, s, d).astype(np.float32)
                   for _ in range(3))
        ang = np.outer(np.arange(s), 1e4 ** (-np.arange(d // 2) * 2.0 / d))
        return q, k, w, np.cos(ang).astype(np.float32), \
            np.sin(ang).astype(np.float32)

    @staticmethod
    def _rot(x, cos, sin):
        x1, x2 = np.split(x, 2, axis=-1)
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def test_forward_matches_numpy(self):
        from paddle_tpu.incubate.nn import fused_rotary_position_embedding
        q, k, _, cos, sin = self._case()
        qr, kr = fused_rotary_position_embedding(
            *(paddle.to_tensor(a) for a in (q, k, cos, sin)))
        assert tuple(qr.shape) == q.shape and tuple(kr.shape) == k.shape
        np.testing.assert_allclose(np.asarray(qr._data),
                                   self._rot(q, cos, sin), atol=1e-6)
        np.testing.assert_allclose(np.asarray(kr._data),
                                   self._rot(k, cos, sin), atol=1e-6)
        # a rotation: the norm of every pair, so of every row, is kept
        np.testing.assert_allclose(np.linalg.norm(np.asarray(qr._data), axis=-1),
                                   np.linalg.norm(q, axis=-1), rtol=1e-5)

    def test_gradient_is_the_inverse_rotation(self):
        from paddle_tpu.incubate.nn import fused_rotary_position_embedding
        q, k, w, cos, sin = self._case(1)
        qt, kt = paddle.to_tensor(q), paddle.to_tensor(k)
        qt.stop_gradient = kt.stop_gradient = False
        qr, kr = fused_rotary_position_embedding(
            qt, kt, paddle.to_tensor(cos), paddle.to_tensor(sin))
        ((qr * paddle.to_tensor(w)).sum() + (kr * 2.0).sum()).backward()
        # d/dq sum(w * R q) = R^T w: the rotation by the negated angle
        np.testing.assert_allclose(np.asarray(qt.grad._data),
                                   self._rot(w, cos, -sin), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(kt.grad._data),
            self._rot(np.full_like(k, 2.0), cos, -sin), atol=1e-5)


class TestXlaFlashAgainstPlainAttention:
    """`_xla_flash` (the blockwise arm, custom VJP) against plain
    full-materialization attention written here: Sq != Sk, causal and not,
    forward and gradient."""

    @staticmethod
    def _plain(q, k, v, causal):
        import jax
        import jax.numpy as jnp
        sq, sk = q.shape[2], k.shape[2]
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            mask = jnp.arange(sk)[None, :] <= (jnp.arange(sq)[:, None]
                                               + (sk - sq))
            logits = jnp.where(mask[None, None], logits, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)

    def test_forward_matches(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import _xla_flash
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 3, 32, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 3, 48, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 3, 48, 16).astype(np.float32))
        for causal in (False, True):
            a = self._plain(q, k, v, causal)
            b = _xla_flash(q, k, v, causal, None)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    def test_grads_match(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import _xla_flash
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
        ga = jax.grad(lambda q_: (self._plain(
            q_, q_, q_, True) ** 2).sum())(q)
        gb = jax.grad(lambda q_: (_xla_flash(
            q_, q_, q_, True, None) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-4, atol=1e-4)