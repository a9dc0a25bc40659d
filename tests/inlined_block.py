"""The GPT block stack as it was before PR 39: a Python loop that inlines
the block once a layer, each layer's leaves read at a STATIC index. The
package runs the block as one traced function called once a layer
(`models/gpt.py::_block_stack`); this is the oracle its tests hold that to
(tests/test_block_trace.py, tests/test_served_layout.py). Not a test
module."""
import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt


def served_get(p, layer, suffix):
    """Layer ``layer``'s leaf of the served layout (`serving_params`)."""
    return gpt._deq(p["blocks." + suffix][layer])


def state_dict_get(p, layer, suffix):
    """The same leaf of the state_dict itself, as the family served it
    before PR 37."""
    return gpt._deq(p[f"gpt.h.{layer}.{suffix}"])


def inlined_block_stack(get=served_get):
    """A drop-in for `gpt._block_stack` (``monkeypatch.setattr(gpt,
    "_block_stack", inlined_block_stack())``) that reads its leaves through
    ``get(p, layer, suffix)``."""
    def stack(p, x, nl, nh, dh, attend, carry):
        lead = x.shape[:-1]
        for i in range(nl):
            hpre = gpt._ln_ref(x, get(p, i, "ln_1.weight"),
                               get(p, i, "ln_1.bias"))
            qkv = hpre @ get(p, i, "attn.qkv_proj.weight") + \
                get(p, i, "attn.qkv_proj.bias")
            q, k, v = jnp.split(qkv, 3, axis=-1)
            att, carry = attend(i, q.reshape(*lead, nh, dh),
                                k.reshape(*lead, nh, dh),
                                v.reshape(*lead, nh, dh), carry)
            att = att.reshape(*lead, nh * dh)
            att = att @ get(p, i, "attn.out_proj.weight") + \
                get(p, i, "attn.out_proj.bias")
            x = x + att
            hpre = gpt._ln_ref(x, get(p, i, "ln_2.weight"),
                               get(p, i, "ln_2.bias"))
            m = hpre @ get(p, i, "mlp.fc_in.weight") + \
                get(p, i, "mlp.fc_in.bias")
            m = jax.nn.gelu(m, approximate=True)
            m = m @ get(p, i, "mlp.fc_out.weight") + \
                get(p, i, "mlp.fc_out.bias")
            x = x + m
        return x, carry
    return stack
