"""Plain GPT-2 (Radford et al. 2019): forward, loss, gradients and AdamW.

The yardstick's reference. Straightforward ``jax.numpy`` in float32 with
matrix multiplications at ``highest`` precision; no kernels, no cache, no
batching tricks. It imports nothing of ``paddle_tpu`` and is handed only the
weights the benchmark itself made from the seed.

Weights are a dict ``{"top": {...}, "blocks": {...}}``: ``top`` holds
``wte`` [V, H], ``wpe`` [P, H], ``ln_f.weight``, ``ln_f.bias``; ``blocks``
holds each per-layer leaf stacked on a leading layer axis, linear weights
laid out ``[in, out]`` (``y = x @ W + b``), the qkv projection ``[H, 3H]``
with q, k, v as consecutive thirds. Layer norm has eps 1e-5, the MLP uses
the tanh GELU, attention scales by ``1/sqrt(head_dim)``, and the output head
is tied to ``wte``.

``precision`` states the arithmetic of every matrix multiplication:
``"f32"`` is the reference itself; ``"bf16"`` and ``"fp8"`` round both
operands to that type first (accumulating in float32, gradients passed
straight through the rounding), which is how the controls stand in for a
program that computes one step lower than its configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_LEAVES = (
    "ln_1.weight", "ln_1.bias",
    "attn.qkv_proj.weight", "attn.qkv_proj.bias",
    "attn.out_proj.weight", "attn.out_proj.bias",
    "ln_2.weight", "ln_2.bias",
    "mlp.fc_in.weight", "mlp.fc_in.bias",
    "mlp.fc_out.weight", "mlp.fc_out.bias",
)
TOP_LEAVES = ("wte", "wpe", "ln_f.weight", "ln_f.bias")
LN_EPS = 1e-5
_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def _round_through(x, to):
    """``x`` rounded to ``to`` and back, with the gradient passed straight
    through: the forward product sees rounded operands, the backward pass
    is not itself quantized (an fp8 cast would flush small gradients to
    zero, which no fp8 training path does: they scale). So the control is
    the mildest lower precision, and a real one would read farther off."""
    return x + jax.lax.stop_gradient(x.astype(to).astype(jnp.float32) - x)


def _mm(eq, a, b, precision):
    """einsum in float32 at highest precision, operands first rounded to
    ``precision`` (nothing for "f32")."""
    to = _ROUND[precision]
    if to is not None:
        a, b = _round_through(a, to), _round_through(b, to)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p, num_heads, precision):
    """One transformer block over x [B, S, H]; ``p`` holds this layer's
    leaves (no layer axis)."""
    b, s, h = x.shape
    dh = h // num_heads
    a = layer_norm(x, p["ln_1.weight"], p["ln_1.bias"])
    qkv = _mm("bsh,hk->bsk", a, p["attn.qkv_proj.weight"], precision) \
        + p["attn.qkv_proj.bias"]
    q, k, v = (t.reshape(b, s, num_heads, dh)
               for t in jnp.split(qkv, 3, axis=-1))
    sc = _mm("bqnd,bknd->bnqk", q, k, precision) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    ctx = _mm("bnqk,bknd->bqnd", pr, v, precision).reshape(b, s, h)
    x = x + _mm("bsh,hk->bsk", ctx, p["attn.out_proj.weight"], precision) \
        + p["attn.out_proj.bias"]
    a = layer_norm(x, p["ln_2.weight"], p["ln_2.bias"])
    m = gelu_tanh(_mm("bsh,hk->bsk", a, p["mlp.fc_in.weight"], precision)
                  + p["mlp.fc_in.bias"])
    return x + _mm("bsk,kh->bsh", m, p["mlp.fc_out.weight"], precision) \
        + p["mlp.fc_out.bias"]


def hidden(weights, ids, num_heads, precision="f32"):
    """[B, S] ids -> final-layer-norm hidden states [B, S, H]."""
    top, blocks = weights["top"], weights["blocks"]
    s = ids.shape[-1]
    x = top["wte"][ids] + top["wpe"][None, :s]

    def body(x, p):
        return block(x, p, num_heads, precision), None

    # rematerialised layer by layer: the same values, and a backward pass
    # that keeps one layer's float32 activations instead of all of them
    x, _ = jax.lax.scan(jax.checkpoint(body), x, blocks)
    return layer_norm(x, top["ln_f.weight"], top["ln_f.bias"])


def logits(weights, ids, num_heads, precision="f32"):
    """[B, S] ids -> [B, S, V] float32 logits through the tied head."""
    h = hidden(weights, ids, num_heads, precision)
    return _mm("bsh,vh->bsv", h, weights["top"]["wte"], precision)


def loss(weights, ids, labels, num_heads, precision="f32"):
    """Mean next-token cross entropy over every position of [B, S]."""
    lg = logits(weights, ids, num_heads, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss_and_grads(weights, ids, labels, num_heads, precision="f32",
                   row_block=None):
    """Loss and its gradient over the whole batch, taken ``row_block`` rows
    at a time (all rows at once when None) so that float32 activations of
    a full-size batch fit beside the weights. Rows weigh equally, so the
    mean of the blocks' means is the batch mean."""
    n = ids.shape[0]
    rb = n if row_block is None else int(row_block)
    if n % rb:
        raise ValueError(f"batch {n} is not a multiple of row_block {rb}")
    vg = jax.value_and_grad(loss)
    if rb == n:
        return vg(weights, ids, labels, num_heads, precision)
    xs = ids.reshape(n // rb, rb, -1)
    ys = labels.reshape(n // rb, rb, -1)

    def body(carry, xy):
        lsum, gsum = carry
        l, g = vg(weights, xy[0], xy[1], num_heads, precision)
        return (lsum + l, jax.tree_util.tree_map(jnp.add, gsum, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, weights)
    (lsum, gsum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero), (xs, ys))
    k = jnp.float32(n // rb)
    return lsum / k, jax.tree_util.tree_map(lambda a: a / k, gsum)


def adamw_init(weights):
    z = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"m": z, "v": jax.tree_util.tree_map(jnp.zeros_like, weights)}


def adamw_update(weights, grads, state, t, *, lr, beta1=0.9, beta2=0.999,
                 eps=1e-8, weight_decay=0.01):
    """Decoupled-decay Adam (Loshchilov & Hutter), step number ``t`` from 1:
    ``p <- p - lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``, every leaf
    decayed."""
    t = jnp.float32(t)

    def one(p, g, m, v):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        upd = (m / (1 - beta1 ** t)) / (jnp.sqrt(v / (1 - beta2 ** t)) + eps)
        return p - lr * (upd + weight_decay * p), m, v

    out = jax.tree_util.tree_map(one, weights, grads, state["m"], state["v"])
    pick = lambda i: jax.tree_util.tree_map(          # noqa: E731
        lambda _, o: o[i], weights, out)
    return pick(0), {"m": pick(1), "v": pick(2)}
