"""Quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # one host with four (run by hand)

One process, the only one that touches JAX. It refuses to run without a TPU.
Default run, GPT-2 small at full published width (12 layers, hidden 768, 12
heads, MLP 3072, vocab 50304, context 1024), random weights from ``--seed``:

- ``train``   bf16 O2 + AdamW through ``paddle.jit.to_static`` (the recipe of
              ``bench.py::bench_gpt2``), B16 x S1024, then the same model
              through ``ScanTrainStep``;
- ``serve``   those weights behind ``InferenceServer`` + ``DecodeEngine``
              (prefix cache on, KV pool sized from ``memory_stats()``),
              concurrent ragged GENERATEs over the wire protocol, greedy
              parity with ``model.fast_generate``;
- ``kernels`` every authored Pallas kernel forced once, compiled, against
              its XLA arm.

``--four-chips`` runs only ``ScanTrainStep`` on ``auto_mesh(dp=2, mp=2)``
against the same steps on device 0 alone.

Every phase runs even when an earlier one failed, so one chip call shows
every fault; any failure exits non-zero. Earlier output lines are JSON
records of what was seen; the last line is the verdict the driver reads.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run drives. ``FULL`` is the only size the script runs; the
    CPU rehearsal in tests/test_tpu_compile.py passes a toy one."""
    vocab: int = 50304
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp: int = 3072
    seq: int = 1024
    batch: int = 16
    train_steps: int = 4
    # ScanTrainStep keeps every layer's residuals for the backward scan:
    # at B16 x S1024 without remat one microbatch needs 17 GB of the chip's
    # 15.75 (asked of the compiler, PERF.md), two need 12.7 —
    # bench.py::bench_train_step runs this size with two as well
    scan_microbatches: int = 2
    prompt_lens: tuple = (17, 128, 300, 900)
    repeat: int = 2                  # index of the prompt sent twice
    new_tokens: int = 64
    max_slots: int = 8
    # Share of the free device bytes given to the KV pool. Every step
    # program copies one whole pool into a padded tiled layout (3.7x its
    # bytes of temporaries, asked of the compiler: PERF.md), so K + V can
    # hold about a quarter of the chip until the pool's layout is changed
    kv_fraction: float = 0.25
    # serving widths for the kernels phase: (heads, head_dim) of GPT-2
    # small and GPT-2 345M; page 16, 64 pages per slot, 32 sequences,
    # prefill chunk 256, layer-norm rows
    kernel_widths: tuple = ((12, 64), (16, 64))
    kernel_batch: int = 32
    page_size: int = 16
    pages_per_slot: int = 64
    chunk: int = 256
    ln_rows: int = 16384
    four_chip_microbatches: int = 4


FULL = Sizes()

# |loss_a - loss_b| / loss bounds, with their origin
BF16_STEP1_RTOL = 2.0 ** -8   # one bf16 unit roundoff: the two trainers run
#                               the same bf16 forward in different programs
F32_MESH_RTOL = 1e-3          # __graft_entry__._PARITY_RTOL, the repo's own
#                               f32 bound for mesh-vs-serial loss parity
BF16_KERNEL_TOL = 2e-2        # ~2.5 bf16 ulps of the largest reference value
# Greedy decode in bf16: two programs round the same logits differently, and
# among 50k near-tied logits the argmax flips (on the chip the engine and
# fast_generate part ways after 3 to 33 tokens, PERF.md). What must hold is
# that every emitted token is a maximum of the f32 reference logits to
# within bf16's reach: eight unit roundoffs (2^-8) of the largest logit. A
# token from a wrong page, position or weight misses by the logits' whole
# spread, tens of times more.
GREEDY_LOGIT_RTOL = 8 * 2.0 ** -8


def emit(record):
    print(json.dumps(record, default=str), flush=True)


def require_tpu():
    """The platform assertion: the first JAX device, which must be a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax reports platform "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run")
    return dev


def free_bytes(dev):
    """Device bytes not in use right now, from the runtime's own counters."""
    stats = dev.memory_stats()
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


def bytes_in_use(dev):
    stats = dev.memory_stats()
    return None if not stats else int(stats["bytes_in_use"])


def _cfg(sz):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                     num_layers=sz.layers, num_heads=sz.heads,
                     intermediate_size=sz.mlp,
                     max_position_embeddings=sz.seq, hidden_dropout=0.0,
                     attention_dropout=0.0, recompute=False)


def _model_and_opt(sz, seed, amp=True):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(_cfg(sz))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    if amp:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    return model, opt


def _batch(sz, seed):
    ids = np.random.RandomState(seed).randint(0, sz.vocab,
                                              (sz.batch, sz.seq + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)


def _timed(fn):
    """(seconds, value) of ``fn()``; fn must end in a device sync."""
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def _check_losses(name, losses, sz, fails):
    want = float(np.log(sz.vocab))
    if not all(np.isfinite(v) for v in losses):
        fails.append(f"{name}: non-finite loss {losses}")
    elif abs(losses[0] - want) > 0.5:
        fails.append(f"{name}: first loss {losses[0]:.3f} is not near "
                     f"ln({sz.vocab}) = {want:.3f}")
    elif not losses[-1] < losses[0]:
        fails.append(f"{name}: loss did not fall on a repeated batch: "
                     f"{losses}")


# ---------------------------------------------------------------- train


def phase_train(sz, seed, dev):
    """to_static O2 AdamW steps, then ScanTrainStep on a fresh copy of the
    same seeded model. Returns (record, failures, trained model)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.train import ScanTrainStep

    fails, rec = [], {"phase": "train", "batch": sz.batch, "seq": sz.seq}
    x_np, y_np = _batch(sz, seed)

    model, opt = _model_and_opt(sz, seed)
    rec["n_params"] = sum(int(np.prod(p.shape)) for p in model.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)

    def one():
        loss = train_step(x, y)
        jax.block_until_ready(loss._data)
        return float(loss)

    times, losses = zip(*[_timed(one) for _ in range(sz.train_steps)])
    rec["to_static"] = {"first_call_s": times[0],
                        "steady_step_s": float(np.median(times[2:])),
                        "step_s": list(times), "losses": list(losses)}
    _check_losses("to_static", losses, sz, fails)
    del train_step, model, opt, x, y
    gc.collect()
    rec["bytes_in_use_between_trainers"] = bytes_in_use(dev)

    model, opt = _model_and_opt(sz, seed)
    step = ScanTrainStep(model, opt, microbatches=sz.scan_microbatches)
    times2, losses2 = zip(*[_timed(lambda: step.step(x_np, y_np))
                            for _ in range(sz.train_steps)])
    rec["scan"] = {"first_call_s": times2[0],
                   "steady_step_s": float(np.median(times2[2:])),
                   "step_s": list(times2), "losses": list(losses2),
                   "compile_count": step.compile_count,
                   "microbatches": sz.scan_microbatches}
    _check_losses("scan", losses2, sz, fails)
    if step.compile_count != 1:
        fails.append(f"scan: compiled {step.compile_count} times, not once")
    gap = abs(losses[0] - losses2[0]) / abs(losses[0])
    rec["step1_rel_gap"] = gap
    if not gap <= BF16_STEP1_RTOL:
        fails.append(f"step-1 loss: to_static {losses[0]:.5f} vs scan "
                     f"{losses2[0]:.5f}, relative gap {gap:.2e} > "
                     f"{BF16_STEP1_RTOL:.2e}")
    step.sync_to_model()
    del step, opt
    gc.collect()
    rec["peak_bytes_in_use"] = peak_bytes(dev)
    return rec, fails, model


# ---------------------------------------------------------------- serve


def _greedy_gaps(model, sz, seqs, prompt_lens):
    """Per sequence, how far each GENERATED token's f32 reference logit lies
    below that position's largest: 0 where the token is the reference's own
    argmax. The reference is the training forward definition (`scan_hidden`
    over the stacked layers, full sequence at once) on the same weights
    upcast to f32, matmuls at highest precision — independent of both
    decode paths. Returns (list of gap arrays, largest |logit| seen)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.autograd import x64_off_scope
    from paddle_tpu.models.gpt import scan_hidden, stack_gpt_params

    ids = np.zeros((len(seqs), sz.seq), np.int32)
    for i, s_ in enumerate(seqs):
        ids[i, :len(s_)] = s_                    # causal: the tail is inert
    stacked = stack_gpt_params({k: t._data.astype(jnp.float32)
                                for k, t in model.state_dict().items()})

    def logits_of(stacked_, ids_):
        h = scan_hidden(stacked_, ids_, model.cfg, training=False)
        return h @ stacked_["top"]["gpt.wte.weight"].T

    with x64_off_scope(), jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(logits_of)(stacked, jnp.asarray(ids)))
    gaps = []
    for i, (s_, n0) in enumerate(zip(seqs, prompt_lens)):
        t = np.arange(n0, len(s_))
        row = logits[i, t - 1]                   # position t-1 predicts t
        gaps.append(row.max(-1) - row[np.arange(len(t)), np.asarray(s_)[t]])
    return gaps, float(np.abs(logits).max())


def phase_serve(sz, seed, dev, model):
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.inference.serve import InferenceServer, RemotePredictor
    from paddle_tpu.observability import metrics

    fails, rec = [], {"phase": "serve", "new_tokens": sz.new_tokens}
    model.eval()
    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(0, sz.vocab, n).astype(np.int32)
               for n in sz.prompt_lens]
    prompts.append(prompts[sz.repeat])       # sent after the others: a hit

    def reference(p):
        ids = paddle.to_tensor(p[None])
        return np.asarray(
            model.fast_generate(ids, max_new_tokens=sz.new_tokens).numpy())[0]

    t_ref, refs = _timed(lambda: [reference(p) for p in prompts[:-1]])
    refs.append(refs[sz.repeat])
    rec["reference_s"] = t_ref

    # K + V bytes of one page across all layers, bf16
    page_bytes = 2 * sz.layers * sz.page_size * sz.hidden * 2
    free = free_bytes(dev)
    num_pages = int(free * sz.kv_fraction) // page_bytes
    rec.update(free_bytes_before_pool=free, page_bytes=page_bytes,
               num_pages=num_pages, pool_tokens=num_pages * sz.page_size)
    eng = DecodeEngine(model, EngineConfig(
        page_size=sz.page_size, max_slots=sz.max_slots, max_seq_len=sz.seq,
        num_pages=num_pages, prefix_cache=True))
    rec["pool_bytes_measured"] = free - free_bytes(dev)
    rec["donate"] = eng._donate
    tail = len(prompts[-1]) - sz.page_size * (
        (len(prompts[-1]) - 1) // sz.page_size)
    rec["warmup_s"], _ = _timed(lambda: eng.warmup(
        prompt_lens=sz.prompt_lens, tail_lens=(tail,)))

    srv = InferenceServer(None, engine=eng, auth_name="chip-smoke")
    server = threading.Thread(target=srv.serve_forever, daemon=True)
    server.start()
    watched = ("jit.compile_count", "engine.compile_count")
    before = metrics.snapshot()["counters"]
    outs, lat, errors = {}, {}, []

    def client(i):
        try:
            cli = RemotePredictor(port=srv.port, secret="chip-smoke")
            lat[i], outs[i] = _timed(lambda: cli.generate(
                prompts[i], max_new_tokens=sz.new_tokens))
            cli.close()
        except Exception as e:  # noqa: BLE001 — reported as a phase failure
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    try:
        first = [threading.Thread(target=client, args=(i,))
                 for i in range(len(prompts) - 1)]
        t0 = time.perf_counter()
        for t in first:
            t.start()
        for t in first:
            t.join(timeout=600)
        client(len(prompts) - 1)
        rec["serve_wall_s"] = time.perf_counter() - t0
        after = metrics.snapshot()["counters"]
    finally:
        cli = RemotePredictor(port=srv.port, secret="chip-smoke")
        cli.shutdown_server()
        cli.close()
        server.join(timeout=60)
    fails += errors
    if server.is_alive():
        fails.append("server thread did not stop")
    answered = [i for i in range(len(prompts)) if outs.get(i) is not None]
    fails += [f"request {i}: no answer" for i in range(len(prompts))
              if i not in answered]
    got = [np.asarray(outs[i]) for i in answered]
    generated = sum(len(g) - len(prompts[i]) for g, i in zip(got, answered))
    for g, i in zip(got, answered):
        if len(g) != len(prompts[i]) + sz.new_tokens \
                or not np.array_equal(g[:len(prompts[i])], prompts[i]):
            fails.append(f"request {i}: answer of {len(g)} ids does not "
                         f"extend its {len(prompts[i])}-token prompt by "
                         f"{sz.new_tokens}")
    rec["identical_to_fast_generate"] = [
        bool(np.array_equal(g, refs[i])) for g, i in zip(got, answered)]
    # every emitted token against the f32 reference logits — the engine's
    # and, as the control, fast_generate's own
    lens = [len(prompts[i]) for i in answered]
    gaps, top = _greedy_gaps(model, sz, got + [refs[i] for i in answered],
                             lens + lens)
    tol = GREEDY_LOGIT_RTOL * top
    rec.update(
        max_abs_logit=top, greedy_gap_tol=tol,
        engine_max_gap=[float(g.max()) for g in gaps[:len(got)]],
        fast_generate_max_gap=[float(g.max()) for g in gaps[len(got):]])
    for who, part, idx in (("engine", gaps[:len(got)], answered),
                           ("fast_generate", gaps[len(got):], answered)):
        for g, i in zip(part, idx):
            if not g.max() <= tol:
                fails.append(
                    f"request {i} (prompt {len(prompts[i])}): {who} emitted "
                    f"a token {g.max():.4f} below the reference's best "
                    f"logit at generated position {int(g.argmax())}; "
                    f"tolerance {tol:.4f}")
    if len(answered) == len(prompts):   # same prompt, cold and as a hit
        rec["prefix_hit_identical_to_cold"] = bool(
            np.array_equal(got[-1], got[sz.repeat]))
    rec["request_s"] = [lat.get(i) for i in range(len(prompts))]
    rec["generated_tokens"] = generated
    rec["compiles_in_window"] = {
        k: after.get(k, 0) - before.get(k, 0) for k in watched}
    if any(rec["compiles_in_window"].values()):
        fails.append(f"compiled after warmup: {rec['compiles_in_window']}")
    rec["prefix_hits"] = after.get("engine.prefix_hit", 0) \
        - before.get("engine.prefix_hit", 0)
    rec["prefix_pages_reused"] = after.get("engine.prefix_pages_reused", 0) \
        - before.get("engine.prefix_pages_reused", 0)
    if rec["prefix_hits"] < 1:
        fails.append("the repeated prompt did not hit the prefix cache")
    rec["peak_bytes_in_use"] = peak_bytes(dev)
    return rec, fails


# -------------------------------------------------------------- kernels


def _close(name, got, want, tol, fails):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        fails.append(f"{name}: shape {got.shape} vs {want.shape} or "
                     "non-finite values")
        return None
    err = float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))
    if err > tol:
        fails.append(f"{name}: max error {err:.3e} > {tol:.1e}")
    return err


def _run_compiled(name, fn, args, on_tpu, fails):
    """jit ``fn``, require the Mosaic custom call in what was lowered (on a
    TPU: compiled, not interpreted), run it, return (outputs, seconds)."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    if on_tpu and "tpu_custom_call" not in lowered.as_text():
        fails.append(f"{name}: no tpu_custom_call in the lowered program")
    exe = lowered.compile()
    jax.block_until_ready(exe(*args))
    return _timed(lambda: jax.block_until_ready(exe(*args)))[::-1]


def phase_kernels(sz, seed, dev):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.autograd import x64_off_scope
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels.flash_attention import flash_attention_fn
    from paddle_tpu.kernels.pallas import fused_layer_norm

    on_tpu = dev.platform == "tpu"
    fails, rows = [], []
    rng = np.random.RandomState(seed + 2)
    bf = jnp.bfloat16

    def rand(*shape, dtype=bf):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)

    def compare(name, impl, got, want, secs, tol):
        errs = [_close(name, g, w, tol, fails) for g, w in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]
        rows.append({"kernel": name, "impl": impl, "seconds": secs,
                     "max_err": errs})

    def pair(name, flag, forced, build, args, tol=BF16_KERNEL_TOL,
             select=lambda o: o):
        """Run ``build()`` under flag=forced and flag="xla", compare.
        ``build`` returns a NEW function each call: the impl is chosen at
        trace time, and jit would reuse the first trace of a shared one."""
        out = {}
        for impl in (forced, "xla"):
            set_flags({flag: impl})
            try:
                with x64_off_scope():
                    if impl == forced:
                        out[impl], secs = _run_compiled(
                            name, build(), args, on_tpu, fails)
                    else:
                        out[impl] = jax.block_until_ready(
                            jax.jit(build())(*args))
            finally:
                set_flags({flag: "auto"})
        compare(name, forced, select(out[forced]), select(out["xla"]),
                secs, tol)

    def sq(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()

    # flash attention, forward and backward, [B, S, H, D] through the
    # normal entry (registry.dispatch under the forced flag)
    for h, d in sz.kernel_widths:
        b = max(1, sz.batch * sz.heads // h)
        q, k, v = (rand(b, sz.seq, h, d) for _ in range(3))
        pair(f"flash_fwd h{h}", "tpu_flash_impl", "authored",
             lambda: flash_attention_fn(causal=True), (q, k, v))
        pair(f"flash_bwd h{h}", "tpu_flash_impl", "authored",
             lambda: jax.grad(sq(flash_attention_fn(causal=True)),
                              argnums=(0, 1, 2)), (q, k, v), tol=3e-2)

    # paged decode and ragged prefill over a float and an int8 pool, in
    # the engine's stored layout [nl, P, ps, h*d], read at the last layer
    ps, maxp, nb = sz.page_size, sz.pages_per_slot, sz.kernel_batch
    nl = 2

    def int8_pool(pool, h):
        vals, scales = pa.quantize_kv(pool.reshape(*pool.shape[:3], h, -1))
        return vals.reshape(pool.shape), scales

    for h, d in sz.kernel_widths:
        pool = 1 + nb * maxp
        kf, vf = rand(nl, pool, ps, h * d), rand(nl, pool, ps, h * d)
        kq, ks = int8_pool(kf, h)
        vq, vs = int8_pool(vf, h)
        table = jnp.asarray(1 + rng.permutation(nb * maxp).astype(np.int32)
                            .reshape(nb, maxp))
        pos = jnp.asarray(rng.randint(0, maxp * ps, nb).astype(np.int32))
        qd = rand(nb, h, d)
        # (the int8 pools' scales are the two trailing positional operands)
        paged = lambda: lambda *a: pa.paged_attention(         # noqa: E731
            *a, layer=nl - 1)
        prefill = lambda: lambda *a: pa.prefill_attention(     # noqa: E731
            *a, layer=nl - 1)
        pair(f"paged h{h}", "tpu_paged_impl", "pallas", paged,
             (qd, kf, vf, table, pos))
        pair(f"paged_int8 h{h}", "tpu_paged_impl", "pallas", paged,
             (qd, kq, vq, table, pos, ks, vs))
        c = sz.chunk
        start = jnp.int32(min(3 * ps + 5, maxp * ps - c))
        valid = jnp.int32(c - 7)
        qp = rand(1, c, h, d)
        live = lambda o: o[0, :int(valid)]          # noqa: E731 — padding
        #                                             rows differ by design
        pair(f"prefill h{h}", "tpu_prefill_impl", "pallas", prefill,
             (qp, kf, vf, table[0], start, valid), select=live)
        pair(f"prefill_int8 h{h}", "tpu_prefill_impl", "pallas", prefill,
             (qp, kq, vq, table[0], start, valid, ks, vs), select=live)

    # fused layer norm (fwd, bwd): one arm, compared with plain jax.numpy
    def ln_ref(x, g, b_):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        rs = jax.lax.rsqrt(((xf - mu) ** 2).mean(-1, keepdims=True) + 1e-5)
        return ((xf - mu) * rs * g + b_).astype(x.dtype)

    for h, d in sz.kernel_widths:
        hid = h * d
        x = rand(sz.ln_rows, hid)
        g, b_ = rand(hid, dtype=jnp.float32), rand(hid, dtype=jnp.float32)
        for tag, wrap, tol in (("fwd", lambda f: f, BF16_KERNEL_TOL),
                               ("bwd", lambda f: jax.grad(
                                   sq(f), argnums=(0, 1, 2)), 3e-2)):
            name = f"layernorm_{tag} d{hid}"
            with x64_off_scope():
                got, secs = _run_compiled(name, wrap(fused_layer_norm),
                                          (x, g, b_), on_tpu, fails)
                want = jax.jit(wrap(ln_ref))(x, g, b_)
            compare(name, "pallas", got, want, secs, tol)
    # the fused cross-entropy head's forward kernel (logsumexp and the
    # label's logit a row, taken from tiles in VMEM) against XLA's body
    from paddle_tpu.kernels import fused_ce
    from paddle_tpu.kernels.pallas import fused_ce as ce_kernel
    n, hid, v = (-(-x // 128) * 128 for x in (sz.ln_rows, sz.hidden,
                                                sz.vocab))
    h, w = rand(n, hid), rand(v, hid) * 0.05
    lab = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    plan = ce_kernel._plan(n, hid, v)
    name = f"fused_ce_fwd {n}x{hid}x{v}"
    with x64_off_scope():
        got, secs = _run_compiled(
            name, lambda *a: ce_kernel.forward(
                *a, plan=plan, interpret=not on_tpu)[1:], (h, w, lab),
            on_tpu, fails)
        want = jax.jit(fused_ce._xla_stats)(h, w, lab)
    compare(name, "pallas", got, want, secs, 1e-4)
    return {"phase": "kernels", "kernels": rows,
            "peak_bytes_in_use": peak_bytes(dev)}, fails


# ----------------------------------------------------------- four chips


@contextlib.contextmanager
def _stderr_to_file():
    """Send fd 2 through a file for the block (XLA warns from C++), then
    replay it; yields a dict whose ``text`` is what was written."""
    got = {"text": ""}
    sys.stderr.flush()
    saved = os.dup(2)
    with tempfile.TemporaryFile(mode="w+b") as tmp:
        os.dup2(tmp.fileno(), 2)
        try:
            yield got
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
            tmp.seek(0)
            got["text"] = tmp.read().decode(errors="replace")
            sys.stderr.write(got["text"])


def phase_four_chips(sz, seed, devices):
    """f32 ScanTrainStep on auto_mesh(dp=2, mp=2) over four devices against
    the same steps on devices[0] alone, same process."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.distributed.mesh import auto_mesh, get_mesh, set_mesh
    from paddle_tpu.train import ScanTrainStep

    fails, rec = [], {"phase": "four_chips", "mesh": {"dp": 2, "mp": 2},
                      "batch": sz.batch, "seq": sz.seq, "dtype": "float32",
                      "microbatches": sz.four_chip_microbatches}
    x_np, y_np = _batch(sz, seed)
    y_np = y_np.astype(np.int32)
    prev = get_mesh()
    try:
        set_mesh(None)
        with jax.default_device(devices[0]):
            model, opt = _model_and_opt(sz, seed, amp=False)
            one = ScanTrainStep(model, opt,
                                microbatches=sz.four_chip_microbatches)
            t1, l1 = zip(*[_timed(lambda: one.step(x_np, y_np))
                           for _ in range(sz.train_steps)])
        del one, model, opt
        gc.collect()

        mesh = auto_mesh(dp=2, mp=2, devices=devices[:4])
        model, opt = _model_and_opt(sz, seed, amp=False)
        step = ScanTrainStep(model, opt, zero1=True, mesh=mesh,
                             microbatches=sz.four_chip_microbatches)
        # placement is real before any compute
        sharded = 0
        for tree, what in ((step._params, "param"),
                           (step._opt_state, "zero-1 state")):
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                spec = tuple(getattr(leaf.sharding, "spec", ()))
                if not any(s is not None for s in spec):
                    continue
                sharded += 1
                shards = leaf.addressable_shards
                want = leaf.sharding.shard_shape(leaf.shape)
                if len(shards) != 4 or len({s.device for s in shards}) != 4 \
                        or any(s.data.shape != want for s in shards):
                    fails.append(
                        f"{what} {jax.tree_util.keystr(path)}: "
                        f"{len(shards)} shards "
                        f"{[s.data.shape for s in shards]}, want 4 x {want}")
        rec["sharded_leaves"] = sharded
        mp_leaves = [
            leaf for leaf in jax.tree_util.tree_leaves(step._params)
            if "mp" in tuple(getattr(leaf.sharding, "spec", ()))]
        rec["mp_sharded_params"] = len(mp_leaves)
        if not mp_leaves:
            fails.append("no parameter is sharded over mp")
        used = [d.memory_stats() for d in devices[:4]]
        if all(used):
            rec["bytes_in_use_per_device"] = [int(u["bytes_in_use"])
                                              for u in used]
            low = min(rec["bytes_in_use_per_device"][1:])
            if low < rec["bytes_in_use_per_device"][0] // 8:
                fails.append("devices 1-3 hold almost nothing: "
                             f"{rec['bytes_in_use_per_device']}")
        sh = NamedSharding(mesh, PartitionSpec("dp", None))
        x, y = jax.device_put(x_np, sh), jax.device_put(y_np, sh)
        with _stderr_to_file() as err:
            t4, l4 = zip(*[_timed(lambda: step.step(x, y))
                           for _ in range(sz.train_steps)])
        rec["involuntary_full_rematerialization_warnings"] = \
            err["text"].count("nvoluntary full rematerialization")
        if step.compile_count != 1:
            fails.append(f"mesh step compiled {step.compile_count} times")
    finally:
        set_mesh(prev)
    rec["one_chip"] = {"losses": list(l1), "first_call_s": t1[0],
                       "steady_step_s": float(np.median(t1[2:]))}
    rec["mesh"].update(losses=list(l4), first_call_s=t4[0],
                       steady_step_s=float(np.median(t4[2:])),
                       opt_state_bytes_per_replica=step.opt_state_bytes())
    _check_losses("one chip", l1, sz, fails)
    _check_losses("mesh", l4, sz, fails)
    gaps = [abs(a - b) / max(abs(a), 1.0) for a, b in zip(l1, l4)]
    rec["rel_gap_per_step"] = gaps
    if not max(gaps) <= F32_MESH_RTOL:
        fails.append(f"mesh loss parity: gaps {gaps} > {F32_MESH_RTOL}")
    rec["peak_bytes_in_use"] = [peak_bytes(d) for d in devices[:4]]
    return rec, fails


# ------------------------------------------------------------------ run


def _guard(name, fn, *args):
    """Run one phase; an exception is that phase's failure, not the end of
    the run (the next phase may still say something)."""
    t0 = time.perf_counter()
    try:
        rec, fails, *rest = fn(*args)
    except Exception as e:  # noqa: BLE001 — reported below, exit is non-zero
        traceback.print_exc()
        rec, fails, rest = {"phase": name}, [
            f"{name}: {type(e).__name__}: {e}"], [None]
    rec["wall_s"] = time.perf_counter() - t0
    rec["failures"] = fails
    emit(rec)
    return fails, (rest[0] if rest else None)


def main(argv=None):
    ap = argparse.ArgumentParser("chip_smoke")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp2 x mp2 mesh step against the same "
                         "steps on one device; needs four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax
    import jaxlib
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: needs {want} chip(s), jax reports "
                         f"{len(devices)}")
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.kernels import registry
    from paddle_tpu.observability import metrics
    cache_dir = compile_cache.enable()
    emit({"phase": "env", "platform": dev.platform,
          "device_kind": dev.device_kind, "device_count": len(devices),
          "jax": jax.__version__, "jaxlib": jaxlib.__version__,
          "libtpu": importlib.metadata.version("libtpu"),
          "compile_cache_dir": cache_dir,
          "compile_cache_warm": os.path.isdir(cache_dir)
          and bool(os.listdir(cache_dir)), "seed": args.seed})

    fails = []
    if args.four_chips:
        fails += _guard("four_chips", phase_four_chips, FULL, args.seed,
                        devices)[0]
    else:
        f, model = _guard("train", phase_train, FULL, args.seed, dev)
        fails += f
        if model is None:       # the train phase died: serve fresh weights
            gc.collect()
            model = _model_and_opt(FULL, args.seed)[0]
        fails += _guard("serve", phase_serve, FULL, args.seed, dev, model)[0]
        del model
        gc.collect()
        fails += _guard("kernels", phase_kernels, FULL, args.seed, dev)[0]
    table = {repr(k): v for k, v in registry.table().items()}
    for key, (_, per_impl) in registry.table().items():
        fails += [f"registry candidate {impl} of {key} raised: {res}"
                  for impl, res in per_impl.items() if isinstance(res, str)]
    emit({"phase": "summary", "failures": fails,
          "kernel_dispatch": {
              k: v for k, v in metrics.snapshot()["counters"].items()
              if k.startswith("kernel.dispatch.") and v},
          "registry_table": table})
    if fails:
        print("chip_smoke: FAILED\n  " + "\n  ".join(fails), file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
