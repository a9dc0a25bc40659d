"""Scan-over-layers training step (paddle_tpu/train + models/gpt.py scan_*).

The contract under test, in dependency order:

1. stack/unstack converters are exact inverses (checkpoints + decode paths
   keep the per-layer layout as truth);
2. the scanned forward/loss is numerically identical to the unrolled Layer
   forward, for eval AND train, across every recompute_granularity;
3. the donated fused step's loss trajectory matches the eager unrolled
   Layer+Optimizer path;
4. ZeRO-1 is a pure layout change: bit-for-bit on a 1-device mesh, and on
   a dp>1 mesh the per-replica opt-state bytes drop ~1/dp while losses
   stay within float ulps;
5. gradient-accumulation microbatching matches the full-batch step;
6. the Engine and hapi Model routes reach the fused step and train.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM, scan_logits,
                                   scan_loss, stack_gpt_params,
                                   unstack_gpt_params)
from paddle_tpu.train import ScanTrainStep, ScanUnsupported


def _cfg(**over):
    kw = dict(vocab_size=128, hidden_size=32, num_layers=3, num_heads=2,
              intermediate_size=64, max_position_embeddings=16,
              hidden_dropout=0.0, attention_dropout=0.0)
    kw.update(over)
    return GPTConfig(**kw)


def _model(cfg, seed=0, opt_cls=None, **opt_kw):
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    opt_cls = opt_cls or paddle.optimizer.AdamW
    opt = opt_cls(learning_rate=1e-3, parameters=m.parameters(), **opt_kw)
    return m, opt


def _batch(cfg, b=4, s=12, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (b, s + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)


def _eager_losses(m, opt, x, y, steps):
    m.train()
    out = []
    for _ in range(steps):
        _, loss = m(paddle.Tensor(x, _internal=True),
                    labels=paddle.Tensor(y, _internal=True))
        loss.backward()
        opt.step()
        opt.clear_grad()
        out.append(float(loss))
    return out


# ------------------------------------------------------------- converters


def test_stack_unstack_roundtrip_exact():
    cfg = _cfg()
    m, _ = _model(cfg)
    params = {k: t._data for k, t in m.state_dict().items()}
    stacked = stack_gpt_params(params)
    assert set(stacked["blocks"]) and set(stacked["top"])
    for leaf in stacked["blocks"].values():
        assert leaf.shape[0] == cfg.num_layers
    back = unstack_gpt_params(stacked)
    assert set(back) == set(params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(back[k]))


def test_stack_preserves_mp_sharding():
    from paddle_tpu.distributed.mesh import auto_mesh, set_mesh
    set_mesh(None)
    mesh = auto_mesh(mp=2, dp=4)
    try:
        cfg = _cfg(hidden_size=64, num_heads=4)
        m, _ = _model(cfg)
        params = {k: t._data for k, t in m.state_dict().items()}
        qkv = params["gpt.h.0.attn.qkv_proj.weight"]
        assert isinstance(qkv.sharding, NamedSharding)
        stacked = stack_gpt_params(params, mesh=mesh)
        leaf = stacked["blocks"]["attn.qkv_proj.weight"]
        assert isinstance(leaf.sharding, NamedSharding)
        assert tuple(leaf.sharding.spec) == (None,) + tuple(qkv.sharding.spec)
    finally:
        set_mesh(None)


# ----------------------------------------------------- forward/loss parity


def test_scan_forward_matches_unrolled_eval():
    cfg = _cfg(fused_ce=False)
    m, _ = _model(cfg)
    m.eval()
    stacked = stack_gpt_params({k: t._data for k, t in m.state_dict().items()})
    x, _ = _batch(cfg)
    got = np.asarray(scan_logits(stacked, jnp.asarray(x), cfg))
    want = np.asarray(m(paddle.Tensor(x, _internal=True)).numpy())
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("fused_ce", [False, True])
def test_scan_loss_matches_unrolled_train(fused_ce):
    cfg = _cfg(fused_ce=fused_ce)
    m, _ = _model(cfg)
    m.train()
    stacked = stack_gpt_params({k: t._data for k, t in m.state_dict().items()})
    x, y = _batch(cfg)
    _, loss = m(paddle.Tensor(x, _internal=True),
                labels=paddle.Tensor(y, _internal=True))
    got = float(scan_loss(stacked, jnp.asarray(x), jnp.asarray(y), cfg,
                          training=True))
    assert abs(got - float(loss)) < 1e-6, (got, float(loss))


def test_scan_loss_mask_matches_unrolled():
    cfg = _cfg(fused_ce=False)
    m, _ = _model(cfg)
    m.train()
    stacked = stack_gpt_params({k: t._data for k, t in m.state_dict().items()})
    x, y = _batch(cfg)
    mask = (np.arange(x.shape[1])[None, :] < 7).astype(np.float32) * \
        np.ones((x.shape[0], 1), np.float32)
    _, loss = m(paddle.Tensor(x, _internal=True),
                labels=paddle.Tensor(y, _internal=True),
                loss_mask=paddle.Tensor(mask, _internal=True))
    got = float(scan_loss(stacked, jnp.asarray(x), jnp.asarray(y), cfg,
                          loss_mask=jnp.asarray(mask), training=True))
    assert abs(got - float(loss)) < 1e-6, (got, float(loss))


@pytest.mark.parametrize("recompute,gran", [(True, "full"), (False, "mlp"),
                                            (False, "mlp_up")])
def test_recompute_variants_identical_grads(recompute, gran):
    """Remat policies must not change numerics — same loss, and the same
    grads as the no-remat scan to f32 rounding (the recomputed forward
    fuses differently, which moves the last ulp)."""
    base = _cfg()
    m, _ = _model(base)
    stacked = stack_gpt_params({k: t._data for k, t in m.state_dict().items()})
    x, y = _batch(base)
    x, y = jnp.asarray(x), jnp.asarray(y)

    def lg(cfg):
        return jax.value_and_grad(
            lambda p: scan_loss(p, x, y, cfg, training=True))(stacked)

    l0, g0 = lg(base)
    cfg = dataclasses.replace(base, recompute=recompute,
                              recompute_granularity=gran)
    l1, g1 = lg(cfg)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


def test_scan_train_attention_dropout_unsupported():
    cfg = _cfg(attention_dropout=0.1)
    m, opt = _model(cfg)
    with pytest.raises(ScanUnsupported):
        ScanTrainStep(m, opt)


# --------------------------------------------------------- the fused step


def test_scan_step_matches_eager_unrolled_trajectory():
    cfg = _cfg()
    x, y = _batch(cfg)
    m1, o1 = _model(cfg)
    ref = _eager_losses(m1, o1, x, y, steps=3)
    m2, o2 = _model(cfg)
    step = ScanTrainStep(m2, o2, microbatches=1)
    got = [step.step(x, y) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # params synced back match the eager-trained model's closely
    step.sync_to_model()
    a = np.asarray(m2.state_dict()["gpt.h.0.mlp.fc_in.weight"]._data)
    b = np.asarray(m1.state_dict()["gpt.h.0.mlp.fc_in.weight"]._data)
    np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("opt_cls", [paddle.optimizer.SGD,
                                     paddle.optimizer.Momentum,
                                     paddle.optimizer.Adam,
                                     paddle.optimizer.Adagrad,
                                     paddle.optimizer.RMSProp])
def test_scan_step_optimizer_family(opt_cls):
    cfg = _cfg(num_layers=2)
    x, y = _batch(cfg)
    m1, o1 = _model(cfg, opt_cls=opt_cls)
    ref = _eager_losses(m1, o1, x, y, steps=2)
    m2, o2 = _model(cfg, opt_cls=opt_cls)
    step = ScanTrainStep(m2, o2)
    got = [step.step(x, y) for _ in range(2)]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_scan_step_grad_clip_matches_eager():
    cfg = _cfg()
    x, y = _batch(cfg)
    m1, o1 = _model(cfg, grad_clip=nn.ClipGradByGlobalNorm(0.05))
    ref = _eager_losses(m1, o1, x, y, steps=3)
    m2, o2 = _model(cfg, grad_clip=nn.ClipGradByGlobalNorm(0.05))
    step = ScanTrainStep(m2, o2)
    got = [step.step(x, y) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_microbatch_accumulation_matches_full_batch():
    cfg = _cfg()
    x, y = _batch(cfg, b=8)
    m1, o1 = _model(cfg)
    full = [ScanTrainStep(m1, o1, microbatches=1).step(x, y)
            for _ in range(1)]
    m2, o2 = _model(cfg)
    step = ScanTrainStep(m2, o2, microbatches=4)
    micro = [step.step(x, y)]
    np.testing.assert_allclose(micro, full, rtol=1e-5, atol=1e-6)
    # the accumulated grads drive the SAME next-step loss
    m3, o3 = _model(cfg)
    s3 = ScanTrainStep(m3, o3, microbatches=1)
    l2_full = [s3.step(x, y), s3.step(x, y)][1]
    l2_micro = step.step(x, y)
    np.testing.assert_allclose(l2_micro, l2_full, rtol=1e-4, atol=1e-5)


def test_scan_step_batch_not_divisible_raises():
    cfg = _cfg()
    x, y = _batch(cfg, b=4)
    m, opt = _model(cfg)
    step = ScanTrainStep(m, opt, microbatches=3)
    with pytest.raises(ValueError, match="not divisible"):
        step.step(x, y)


def test_scan_step_amp_o2_master_weights():
    """bf16 params under amp O2: the step updates f32 MASTERS (kept in the
    donated opt state) and down-casts, tracking the eager O2 trajectory."""
    cfg = _cfg(num_layers=2)
    x, y = _batch(cfg)

    def mk():
        m, opt = _model(cfg)
        return paddle.amp.decorate(m, opt, level="O2", dtype="bfloat16")

    m1, o1 = mk()
    m1.train()
    ref = []
    for _ in range(3):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = m1(paddle.Tensor(x, _internal=True),
                         labels=paddle.Tensor(y, _internal=True))
        loss.backward()
        o1.step()
        o1.clear_grad()
        ref.append(float(loss))

    m2, o2 = mk()
    step = ScanTrainStep(m2, o2)
    leaf = step._params["blocks"]["mlp.fc_in.weight"]
    assert leaf.dtype == jnp.bfloat16
    st = step._opt_state["blocks"]["mlp.fc_in.weight"]
    assert st["master"].dtype == jnp.float32
    got = [step.step(x, y) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=2e-3)   # bf16 rounding
    step.sync_to_model()
    assert m2.state_dict()["gpt.h.0.mlp.fc_in.weight"]._data.dtype \
        == jnp.bfloat16


def test_scan_step_dropout_trains_finite():
    cfg = _cfg(hidden_dropout=0.1)
    x, y = _batch(cfg)
    m, opt = _model(cfg)
    step = ScanTrainStep(m, opt, microbatches=2)
    losses = [step.step(x, y) for _ in range(2)]
    assert all(np.isfinite(v) for v in losses), losses


def test_scan_step_lr_schedule_no_retrace():
    """lr is a program INPUT: scheduler updates must not retrace."""
    cfg = _cfg(num_layers=2)
    x, y = _batch(cfg)
    m, opt = _model(cfg)
    step = ScanTrainStep(m, opt)
    step.step(x, y)
    opt.set_lr(5e-4)
    step.step(x, y)
    opt.set_lr(1e-4)
    step.step(x, y)
    assert step.compile_count == 1


def test_sync_to_model_feeds_checkpoint_and_eager_resume():
    """After fused steps, state_dict must carry the trained params AND the
    optimizer accumulators, and an eager step can resume from them."""
    cfg = _cfg()
    x, y = _batch(cfg)
    m1, o1 = _model(cfg)
    ref = _eager_losses(m1, o1, x, y, steps=3)

    m2, o2 = _model(cfg)
    step = ScanTrainStep(m2, o2)
    [step.step(x, y) for _ in range(2)]
    step.sync_to_model()
    sd = o2.state_dict()
    assert any(k.endswith("_moment1_0") for k in sd), list(sd)[:4]
    # eager step 3 resumes from the synced moments
    m2.train()
    _, loss = m2(paddle.Tensor(x, _internal=True),
                 labels=paddle.Tensor(y, _internal=True))
    loss.backward()
    o2.step()
    o2.clear_grad()
    assert abs(float(loss) - ref[2]) < 1e-5, (float(loss), ref[2])


# ------------------------------------------------------------------ ZeRO-1


def test_zero1_bit_identical_single_device_mesh():
    from paddle_tpu.distributed.mesh import auto_mesh, set_mesh
    set_mesh(None)
    mesh = auto_mesh(dp=1, devices=jax.devices()[:1])
    try:
        cfg = _cfg()
        x, y = _batch(cfg)
        m1, o1 = _model(cfg)
        base = [ScanTrainStep(m1, o1, zero1=False, mesh=mesh).step(x, y)
                for _ in range(1)]
        m2, o2 = _model(cfg)
        z = ScanTrainStep(m2, o2, zero1=True, mesh=mesh)
        got = [z.step(x, y)]
        assert got == base, (got, base)   # bit-for-bit
    finally:
        set_mesh(None)


def test_zero1_dp_mesh_shards_opt_state_and_matches():
    from paddle_tpu.distributed.mesh import auto_mesh, set_mesh
    set_mesh(None)
    mesh = auto_mesh(dp=8)
    try:
        cfg = _cfg(hidden_size=64, num_heads=4)
        x, y = _batch(cfg, b=8)
        sh = NamedSharding(mesh, PartitionSpec("dp", None))
        xs = jax.device_put(x, sh)
        ys = jax.device_put(y.astype(np.int32), sh)

        m1, o1 = _model(cfg)
        base = ScanTrainStep(m1, o1, zero1=False, mesh=mesh)
        base_bytes = base.opt_state_bytes()
        l_base = [base.step(xs, ys) for _ in range(3)]

        m2, o2 = _model(cfg)
        z = ScanTrainStep(m2, o2, zero1=True, mesh=mesh)
        z_bytes = z.opt_state_bytes()
        l_z = [z.step(xs, ys) for _ in range(3)]

        # layout-only change: losses agree to float ulps
        np.testing.assert_allclose(l_z, l_base, rtol=1e-6, atol=1e-7)
        # per-replica state ~1/dp (replicated small leaves give it slack)
        assert z_bytes <= base_bytes / 8 * 1.5, (z_bytes, base_bytes)
        assert base.compile_count == 1 and z.compile_count == 1
        from paddle_tpu.observability import metrics
        assert metrics.snapshot()["gauges"]["train.opt_state_bytes"] \
            == z_bytes
    finally:
        set_mesh(None)


def test_zero1_auto_enables_on_dp_mesh():
    from paddle_tpu.distributed.mesh import auto_mesh, set_mesh
    set_mesh(None)
    mesh = auto_mesh(dp=8)
    try:
        m, opt = _model(_cfg())
        step = ScanTrainStep(m, opt, mesh=mesh)     # zero1="auto"
        assert step.zero1 is True
    finally:
        set_mesh(None)
    m, opt = _model(_cfg())
    step = ScanTrainStep(m, opt, mesh=None)
    assert step.zero1 is False


# ------------------------------------------------------------ route tests


def test_engine_routes_gpt_to_scan_step():
    from paddle_tpu.distributed.auto_parallel import Engine, Strategy
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    cfg = _cfg()
    m, opt = _model(cfg)
    s = Strategy()
    s.gradient_merge.enable = True
    s.gradient_merge.k_steps = 2
    eng = Engine(model=m, loss=None, optimizer=opt, strategy=s)
    eng.prepare()
    assert eng.train_step_kind == "scan"
    assert eng._scan_step.microbatches == 2
    x, y = _batch(cfg)
    hist = eng.fit([(x, y)] * 4, epochs=2)
    assert hist[-1]["loss"] < hist[0]["loss"]
    # evaluate syncs the trained params back into the Layer model
    ev = eng.evaluate([(x, y)])
    assert np.isfinite(ev["loss"])


def test_engine_non_gpt_falls_back_to_unrolled():
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    eng = Engine(model=net, loss=nn.CrossEntropyLoss(), optimizer=opt)
    eng.prepare()
    assert eng.train_step_kind == "unrolled"


def test_hapi_fit_accumulate_routes_gpt_fused():
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    cfg = _cfg()
    m, opt = _model(cfg)
    hm = Model(m)
    hm.prepare(optimizer=opt)
    x, y = _batch(cfg)

    class DS:
        def __iter__(self):
            for _ in range(6):
                yield (x, y)

    hm.fit(DS(), epochs=1, accumulate_grad_batches=2, verbose=0)
    assert hm._fused_step is not None
    assert opt._global_step == 3          # 6 batches / k=2
    # eval path sees the trained weights (sync happened)
    logs = hm.evaluate(DS())
    assert np.isfinite(logs["loss"]) if "loss" in logs else True


def test_hapi_generic_accumulation_matches_big_batch():
    """Non-GPT net: k=2 accumulation over two half-batches == one step on
    the concatenated batch (linear model + mean loss => identical grads)."""
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    rng = np.random.RandomState(0)
    X = rng.randn(8, 8).astype(np.float32)
    Y = rng.randint(0, 4, 8).astype(np.int64)

    def mk():
        paddle.seed(7)
        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        hm = Model(net)
        hm.prepare(optimizer=opt, loss=nn.CrossEntropyLoss())
        return net, hm

    net_a, hm_a = mk()

    class Halves:
        def __iter__(self):
            yield (X[:4], Y[:4])
            yield (X[4:], Y[4:])

    hm_a.fit(Halves(), epochs=1, accumulate_grad_batches=2, verbose=0)

    net_b, hm_b = mk()

    class Full:
        def __iter__(self):
            yield (X, Y)

    hm_b.fit(Full(), epochs=1, verbose=0)
    wa = np.asarray(net_a.state_dict()["weight"]._data)
    wb = np.asarray(net_b.state_dict()["weight"]._data)
    np.testing.assert_allclose(wa, wb, rtol=1e-6, atol=1e-7)


def test_engine_gradient_merge_folds_k_batches():
    """k_steps LOADER batches = ONE optimizer apply (reference
    gradient_merge semantics), partial group flushed at epoch end."""
    from paddle_tpu.distributed.auto_parallel import Engine, Strategy
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    cfg = _cfg()
    m, opt = _model(cfg)
    s = Strategy()
    s.gradient_merge.enable = True
    s.gradient_merge.k_steps = 2
    eng = Engine(model=m, loss=None, optimizer=opt, strategy=s)
    eng.prepare()
    x, y = _batch(cfg)
    eng.fit([(x, y)] * 5, epochs=1)      # 5 batches: 2 applies + 1 partial
    assert opt._global_step == 3, opt._global_step


def test_engine_rejects_nondefault_cross_entropy():
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    m, opt = _model(_cfg())
    eng = Engine(model=m, loss=nn.CrossEntropyLoss(label_smoothing=0.1),
                 optimizer=opt)
    eng.prepare()
    assert eng.train_step_kind == "unrolled"


def test_hapi_fused_ragged_final_group_no_crash():
    """drop_last=False tail: a short final batch inside a full k-group must
    run (as one microbatch), not crash on divisibility."""
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    cfg = _cfg()
    m, opt = _model(cfg)
    hm = Model(m)
    hm.prepare(optimizer=opt)
    x, y = _batch(cfg, b=4)

    class Ragged:
        def __iter__(self):
            yield (x, y)
            yield (x[:3], y[:3])         # short tail lands inside the group

    hm.fit(Ragged(), epochs=1, accumulate_grad_batches=2, verbose=0)
    assert opt._global_step == 1


def test_hapi_load_not_clobbered_by_dirty_fused_step(tmp_path):
    """load() after fused training must win: a later sync must not write
    the pre-load weights back over the loaded checkpoint."""
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    cfg = _cfg()
    m, opt = _model(cfg)
    hm = Model(m)
    hm.prepare(optimizer=opt)
    hm.save(str(tmp_path / "init"))      # checkpoint the UNtrained weights
    w0 = np.asarray(m.state_dict()["gpt.h.0.mlp.fc_in.weight"]._data).copy()
    x, y = _batch(cfg)

    class DS:
        def __iter__(self):
            for _ in range(4):
                yield (x, y)

    hm.fit(DS(), epochs=1, accumulate_grad_batches=2, verbose=0)
    hm.load(str(tmp_path / "init"))      # back to the untrained checkpoint
    hm.evaluate(DS())                    # used to sync stale params back
    w1 = np.asarray(m.state_dict()["gpt.h.0.mlp.fc_in.weight"]._data)
    np.testing.assert_array_equal(w0, w1)


def test_hapi_generic_partial_flush_rescales():
    """3 batches at k=2: the leftover single-batch flush must apply the
    MEAN gradient of its group (scale k/pending), i.e. match an explicit
    two-fit schedule with the same groups."""
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    rng = np.random.RandomState(0)
    X = [rng.randn(4, 8).astype(np.float32) for _ in range(3)]
    Y = [rng.randint(0, 4, 4).astype(np.int64) for _ in range(3)]

    def mk():
        paddle.seed(7)
        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        hm = Model(net)
        hm.prepare(optimizer=opt, loss=nn.CrossEntropyLoss())
        return net, hm

    net_a, hm_a = mk()

    class Three:
        def __iter__(self):
            for i in range(3):
                yield (X[i], Y[i])

    hm_a.fit(Three(), epochs=1, accumulate_grad_batches=2, verbose=0)

    net_b, hm_b = mk()

    class First2:
        def __iter__(self):
            yield (X[0], Y[0])
            yield (X[1], Y[1])

    class Last1:
        def __iter__(self):
            yield (X[2], Y[2])

    hm_b.fit(First2(), epochs=1, accumulate_grad_batches=2, verbose=0)
    hm_b.fit(Last1(), epochs=1, verbose=0)
    wa = np.asarray(net_a.state_dict()["weight"]._data)
    wb = np.asarray(net_b.state_dict()["weight"]._data)
    np.testing.assert_allclose(wa, wb, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------- emission regressions


def test_bench_emission_survives_dead_backend(tmp_path):
    """bench.py must emit the structured `backend_error` record on EVERY
    exit path, even when the backend raises at initialisation — and then
    exit non-zero: there is no fallback to another platform."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # break the backend via a poisoned sitecustomize-style preload
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import jax\n"
        "def _boom(*a, **k):\n"
        "    raise RuntimeError('Unable to initialize backend: UNAVAILABLE')\n"
        "jax._src.xla_bridge.backends = _boom\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{tmp_path}:{env.get('PYTHONPATH', '')}"
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=240, cwd=repo, env=env)
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, (proc.stdout, proc.stderr[-2000:])
    d = json.loads(lines[-1])
    assert d["metric"] == "smoke_step_time_seconds"
    assert d["ok"] is False
    assert "UNAVAILABLE" in (d.get("backend_error") or ""), d


def test_multichip_partial_emission_and_rung_budget():
    """A hung rung burns ITS budget and the gate still emits the structured
    partial + final records (no rc=124-with-log-tail failure mode)."""
    import json
    import __graft_entry__ as g

    calls = []

    def ok_rung(n, ctx):
        calls.append("ok")
        return {"serial_losses": [1.0]}

    def failing(n, ctx):
        raise AssertionError("synthetic failure")

    def consumer(n, ctx):
        assert ctx["serial_losses"] == [1.0]
        calls.append("consumer")
        return {}

    orig = g._RUNGS
    g._RUNGS = [("a", 30, ok_rung), ("bad", 30, failing),
                ("c", 30, consumer)]
    try:
        with pytest.raises(RuntimeError) as ei:
            g.dryrun_multichip(8)   # backend is up: in-process mode
        msg = str(ei.value)
        assert "bad" in msg and "synthetic failure" in msg
        assert calls == ["ok", "consumer"]   # failure did not stop the gate
        bad = json.loads(msg[msg.index("{"):])
        assert bad["bad"]["ok"] is False
    finally:
        g._RUNGS = orig


def test_scan_train_rung_runs_in_process():
    """The new multichip rung end-to-end on the 8-virtual-device backend."""
    import __graft_entry__ as g
    from paddle_tpu.distributed.mesh import set_mesh
    set_mesh(None)
    payload = g._rung_scan_train(8, {})
    assert payload["opt_state_bytes"] < payload["opt_state_replicated_bytes"]
