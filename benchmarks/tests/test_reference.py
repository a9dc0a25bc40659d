"""The plain reference against ``paddle_tpu.models.gpt`` at a tiny size on
the CPU, both in float32: the same weights give the same logits, loss and
gradients. (The reference itself imports nothing of the program; this test
is where the two meet.)"""
import numpy as np
import pytest

import tiny  # noqa: F401 — puts the benchmark on sys.path
from harness import weights as W
from reference import gpt2

CFG = dict(n_embd=64, n_layer=2, n_head=2, n_inner=256, n_positions=64,
           vocab_size=500)
ROWS = 512


@pytest.fixture(scope="module")
def both():
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401 — the program
    from paddle_tpu.models.gpt import (GPTConfig, scan_logits, scan_loss,
                                       stack_gpt_params)
    w = W.make(CFG, ROWS, seed=5)
    named = W.program_names(w)
    pcfg = GPTConfig(vocab_size=ROWS, hidden_size=64, num_layers=2,
                     num_heads=2, intermediate_size=256,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     attention_dropout=0.0, recompute=False, fused_ce=False)
    stacked = stack_gpt_params(named)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 500, (3, 33)).astype(np.int32))
    return w, stacked, pcfg, ids, scan_logits, scan_loss


def test_logits_agree(both):
    w, stacked, pcfg, ids, scan_logits, _ = both
    mine = np.asarray(gpt2.logits(w, ids[:, :-1], 2))
    theirs = np.asarray(scan_logits(stacked, ids[:, :-1], pcfg))
    assert np.abs(mine - theirs).max() < 2e-5 * np.abs(theirs).max() + 1e-6


def test_loss_and_gradients_agree(both):
    import jax
    w, stacked, pcfg, ids, _, scan_loss = both
    x, y = ids[:, :-1], ids[:, 1:]
    l_ref, g_ref = gpt2.loss_and_grads(w, x, y, 2)
    l_prog, g_prog = jax.value_and_grad(
        lambda s: scan_loss(s, x, y, pcfg, training=False))(stacked)
    assert abs(float(l_ref) - float(l_prog)) < 1e-5 * float(l_prog)
    pairs = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
             "ln_f.weight": "gpt.ln_f.weight", "ln_f.bias": "gpt.ln_f.bias"}
    for k, name in pairs.items():
        a, b = np.asarray(g_ref["top"][k]), np.asarray(g_prog["top"][name])
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max() + 1e-9, k
    for k in gpt2.BLOCK_LEAVES:
        a, b = np.asarray(g_ref["blocks"][k]), np.asarray(g_prog["blocks"][k])
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max() + 1e-9, k


def test_row_blocks_do_not_change_the_result(both):
    w, _, _, ids, _, _ = both
    ids4 = np.concatenate([np.asarray(ids), np.asarray(ids[:1])])
    x, y = ids4[:, :-1], ids4[:, 1:]
    l1, g1 = gpt2.loss_and_grads(w, x, y, 2)
    l2, g2 = gpt2.loss_and_grads(w, x, y, 2, row_block=2)
    assert abs(float(l1) - float(l2)) < 1e-6
    a, b = np.asarray(g1["top"]["wte"]), np.asarray(g2["top"]["wte"])
    assert np.abs(a - b).max() < 1e-6


def test_adamw_first_step_moves_every_weight_by_about_lr(both):
    w, _, _, ids, _, _ = both
    x, y = ids[:, :-1], ids[:, 1:]
    _, g = gpt2.loss_and_grads(w, x, y, 2)
    new, st = gpt2.adamw_update(w, g, gpt2.adamw_init(w), 1, lr=1e-3,
                                weight_decay=0.0)
    d = np.asarray(new["blocks"]["mlp.fc_in.weight"]
                   - w["blocks"]["mlp.fc_in.weight"])
    gg = np.asarray(g["blocks"]["mlp.fc_in.weight"])
    big = np.abs(gg) > 1e-6
    assert np.allclose(d[big], -1e-3 * np.sign(gg[big]), rtol=1e-2)
    assert np.allclose(np.asarray(st["m"]["top"]["wpe"]),
                       0.1 * np.asarray(g["top"]["wpe"]), rtol=1e-5)
