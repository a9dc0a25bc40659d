"""incubate.nn fused layers (ref: `python/paddle/incubate/nn/` —
FusedMultiHeadAttention, FusedFeedForward, FusedMultiTransformer).

On TPU "fused" means: one traced region XLA/Pallas fuses — attention goes through
the flash-attention kernel, the MLP is a single jit region.
"""
from paddle_tpu.kernels import registry
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.layers.transformer import MultiHeadAttention
from paddle_tpu.nn.layers.common import Linear, Dropout
from paddle_tpu.nn.layers.norm import LayerNorm
from paddle_tpu.nn import functional as F


class FusedMultiHeadAttention(Layer):
    """ref `incubate/nn/layer/fused_transformer.py` FusedMultiHeadAttention."""

    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None, normalize_before=False,
                 need_weights=False, qkv_weight_attr=None, qkv_bias_attr=None,
                 linear_weight_attr=None, linear_bias_attr=None,
                 pre_ln_scale_attr=None, pre_ln_bias_attr=None,
                 ln_scale_attr=None, ln_bias_attr=None, epsilon=1e-5,
                 nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.attn = MultiHeadAttention(embed_dim, num_heads,
                                       dropout=attn_dropout_rate)
        self.ln = LayerNorm(embed_dim, epsilon=epsilon)
        self.dropout = Dropout(dropout_rate)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        residual = query
        x = self.ln(query) if self.normalize_before else query
        out = self.attn(x, key, value, attn_mask, cache)
        out = residual + self.dropout(out if not isinstance(out, tuple)
                                      else out[0])
        if not self.normalize_before:
            out = self.ln(out)
        return out


class FusedFeedForward(Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1, epsilon=1e-5,
                 activation="relu", act_dropout_rate=None, normalize_before=False,
                 linear1_weight_attr=None, linear1_bias_attr=None,
                 linear2_weight_attr=None, linear2_bias_attr=None,
                 ln1_scale_attr=None, ln1_bias_attr=None, ln2_scale_attr=None,
                 ln2_bias_attr=None, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.ln = LayerNorm(d_model, epsilon=epsilon)
        self.dropout = Dropout(dropout_rate)
        self.act_dropout = Dropout(act_dropout_rate if act_dropout_rate
                                   is not None else dropout_rate)
        self.activation = getattr(F, activation)

    def forward(self, src):
        residual = src
        x = self.ln(src) if self.normalize_before else src
        x = self.linear2(self.act_dropout(self.activation(self.linear1(x))))
        out = residual + self.dropout(x)
        if not self.normalize_before:
            out = self.ln(out)
        return out


class FusedTransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None, act_dropout_rate=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead, dropout_rate,
            attn_dropout_rate if attn_dropout_rate is not None else dropout_rate,
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate,
            activation=activation, act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before)

    def forward(self, src, src_mask=None, cache=None):
        out = self.fused_attn(src, attn_mask=src_mask, cache=cache)
        return self.ffn(out)


registry.register_op("fused_layernorm", impls=("pallas",))


class FusedLayerNorm(Layer):
    """LayerNorm over the authored Pallas kernel
    (`paddle_tpu/kernels/pallas/fused_layernorm.py` — the counterpart of the
    reference's fused_layernorm CUDA kernels). Single pass per row for the
    forward; analytic one-pass backward with in-kernel dgamma/dbeta partials."""

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        import numpy as _np
        from paddle_tpu.core.tensor import Parameter
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        if len(normalized_shape) != 1:
            raise ValueError("FusedLayerNorm fuses over the last axis only")
        d = int(normalized_shape[0])
        self.epsilon = epsilon
        self.weight = Parameter(_np.ones(d, _np.float32))
        self.bias = Parameter(_np.zeros(d, _np.float32))
        # registry-routed (kernels/registry.py): one pallas impl today —
        # interpret mode off-TPU inside the kernel. Resolved ONCE at
        # layer construction (forward runs EAGERLY per call — a
        # per-forward dispatch would count thousands of times per step
        # and drown the 'which kernel serves traffic' snapshot); a
        # future xla candidate lands as a registry drop-in here
        self._ln_impl = registry.dispatch("fused_layernorm")

    def forward(self, x):
        from paddle_tpu.core.autograd import apply
        from paddle_tpu.kernels.pallas import fused_layer_norm
        from paddle_tpu.ops.common import ensure_tensor
        x = ensure_tensor(x)
        return apply(
            lambda a, g, b: fused_layer_norm(a, g, b, eps=self.epsilon),
            x, self.weight, self.bias, op_name="fused_layer_norm")


def fused_rotary_position_embedding(q, k, cos, sin, name=None):
    """Rotary position embedding of q and k (ref newer-branch `fused_rope`),
    by the plain half-rotation, which XLA fuses: the pair of element
    ``i < D / 2`` is ``i + D / 2``. q/k: [B, H, S, D] tensors; cos/sin:
    [S, D/2]."""
    import jax.numpy as jnp
    from paddle_tpu.core.autograd import apply
    from paddle_tpu.ops.common import ensure_tensor

    def rotate(a, b, c, s):
        def one(x):
            x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
            return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                                   axis=-1).astype(x.dtype)
        c, s = c.astype(jnp.float32), s.astype(jnp.float32)
        return one(a), one(b)

    q, k = ensure_tensor(q), ensure_tensor(k)
    cos, sin = ensure_tensor(cos), ensure_tensor(sin)
    return apply(rotate, q, k, cos, sin, op_name="fused_rope", n_outputs=2)


class FusedMultiTransformer(Layer):
    """N pre-LN decoder layers in one traced region
    (ref `incubate/nn/layer/fused_transformer.py` FusedMultiTransformer — the
    reference fuses all layers into one CUDA op, `fused_multi_transformer_op.cu`;
    here the whole stack is one jit region XLA fuses, with attention on the
    flash kernel)."""

    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate=0.0,
                 activation="gelu", normalize_before=True, num_layers=1,
                 epsilon=1e-5, **kwargs):
        super().__init__()
        if not normalize_before:
            raise NotImplementedError(
                "FusedMultiTransformer is pre-LN only (ref constraint)")
        self.layers = []
        for i in range(num_layers):
            blk = FusedTransformerEncoderLayer(
                embed_dim, num_heads, dim_feedforward, dropout_rate,
                activation=activation, normalize_before=True)
            self.add_sublayer(f"layer_{i}", blk)
            self.layers.append(blk)

    def forward(self, src, attn_mask=None, caches=None, **kwargs):
        out = src
        new_caches = [] if caches is not None else None
        for i, blk in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            out = blk(out, src_mask=attn_mask, cache=cache)
            if isinstance(out, tuple):
                out, c = out
                if new_caches is not None:
                    new_caches.append(c)
        return (out, new_caches) if caches is not None else out
