"""Runner for ``"kind": "train"`` traffic: one seeded batch, steps back to
back through the program's own compiled step.

The configuration's ``train`` group gives the precision and the optimizer;
the step is captured by ``paddle.jit.to_static`` (the recipe of
``chip_smoke.phase_train``). Set-up builds ONE step object,
drives it through three steps from the seed (these also compile and warm
it), and hands the same object to the window. After the window the program
is freed and the plain reference follows those three steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import check, device, train_reference, weights as W


def _batch(traffic, vocab, seed):
    rng = np.random.RandomState((int(seed) + 0x5EED) % (2 ** 32))
    ids = rng.randint(0, vocab, (traffic["batch"], traffic["seq"] + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _gpt_config(cfg, vocab_rows):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=vocab_rows, hidden_size=cfg["n_embd"],
                     num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                     intermediate_size=cfg["n_inner"],
                     max_position_embeddings=cfg["n_positions"],
                     hidden_dropout=cfg["resid_pdrop"],
                     attention_dropout=cfg["attn_pdrop"], recompute=False)


def _model_and_opt(cfg, vocab_rows, tr, seed, named):
    """The program's model holding the seeded weights (each placed where
    the model put its own parameter) and its AdamW, decorated O2 for
    bfloat16."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(int(seed) % (2 ** 31))
    model = GPTForCausalLM(_gpt_config(cfg, vocab_rows))
    for name, t in model.state_dict().items():
        t._write(jax.device_put(named[name], t._data.sharding))
    opt = paddle.optimizer.AdamW(
        learning_rate=tr["learning_rate"], beta1=tr["beta1"],
        beta2=tr["beta2"], epsilon=tr["epsilon"],
        weight_decay=tr["weight_decay"], parameters=model.parameters())
    if tr["precision"] == "bf16":
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    return model, opt


class ToStatic:
    """The program's captured step: ``dispatch()`` starts a step and returns
    its loss before the step has run, so the window keeps one step in
    flight; ``wait`` blocks on it. ``moment1()`` / ``master()`` give the
    optimizer's first moment and the float32 parameters by parameter
    name."""

    def __init__(self, cfg, vocab_rows, tr, seed, named, x, y):
        import paddle_tpu as paddle
        model, opt = _model_and_opt(cfg, vocab_rows, tr, seed, named)
        amp = tr["precision"] == "bf16"

        @paddle.jit.to_static
        def train_step(xx, yy):
            if amp:
                with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                    _, loss = model(xx, labels=yy)
            else:
                _, loss = model(xx, labels=yy)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        self.model, self.opt, self._step = model, opt, train_step
        self.x = paddle.to_tensor(x)
        self.y = paddle.to_tensor(y.astype(np.int64))
        self.chips = 1

    def dispatch(self):
        return self._step(self.x, self.y)

    @staticmethod
    def wait(loss):
        import jax
        jax.block_until_ready(loss._data)
        return loss

    def _params(self):
        return dict(self.model.state_dict())

    def moment1(self):
        return {n: self.opt.get_state_array("moment1", p)
                for n, p in self._params().items()}

    def master(self):
        out = {}
        for n, p in self._params().items():
            m = self.opt._master_weights.get(id(p))
            m = m if m is not None else getattr(p, "_master", None)
            out[n] = (m._data if hasattr(m, "_data") else m) \
                if m is not None else p._data
        return out

    def compiled_memory(self):
        """``memory_analysis()`` of the captured step: lowered again from
        the shapes of what it was called with (the compile is a cache
        hit)."""
        import jax
        c = self._step.concrete_program(self.x, self.y)
        sds = lambda a: jax.ShapeDtypeStruct(      # noqa: E731
            a.shape, a.dtype, sharding=a.sharding)
        state = [sds(t._data) for t in c.state_tensors]
        grads = [sds(t._grad._data) for t, m in zip(c.state_tensors,
                                                    c.grad_mask) if m]
        args = [sds(self.x._data), sds(self.y._data)]
        return c.jitted.lower(state, grads, args).compile().memory_analysis()


def _norms(arrays: dict, minus: dict | None = None) -> dict:
    """{parameter name: array} -> {parameter name: norm}, computed on the
    device in one call; ``minus[name]`` is subtracted first."""
    import jax
    import jax.numpy as jnp
    keys = list(arrays)

    def f(xs, subs):
        out = []
        for x, s in zip(xs, subs):
            x = x.astype(jnp.float32)
            if s is not None:
                x = x - s
            out.append(jnp.sqrt(jnp.sum(x ** 2)))
        return out

    subs = [None if minus is None else minus[k] for k in keys]
    with jax.enable_x64(False):
        vals = jax.jit(f)([arrays[k] for k in keys], subs)
    return {k: float(v) for k, v in zip(keys, vals)}


def run(ctx):
    import jax
    from paddle_tpu.observability import metrics

    cell, seed = ctx["cell"], ctx["seed"]
    cfg, traffic = cell["config"], cell["traffic"]
    tr = cfg["train"]
    devices = ctx["devices"]
    vocab_rows = cfg["assumed"]["vocab_rows"]
    x, y = _batch(traffic, cfg["vocab_size"], seed)

    w = W.make(cfg, vocab_rows, seed)
    named = W.program_names(w)
    trainer = ToStatic(cfg, vocab_rows, tr, seed, named, x, y)
    del named, w

    # three steps from the seed through the window's own call and feed:
    # they compile, warm up and are what the reference follows
    losses = []
    prog = {}
    for t in range(1, 4):
        out = trainer.wait(trainer.dispatch())
        losses.append(float(out))
        if t == 1:
            b1 = tr["beta1"]
            prog["grad_norms"] = {k: v / (1.0 - b1) for k, v in _norms(
                trainer.moment1()).items()}
    # the seeded values again (the program's step donated the first copy)
    w0 = W.program_names(W.make(cfg, vocab_rows, seed))
    prog["delta_norms"] = _norms(trainer.master(), minus=w0)
    prog["losses"] = losses
    del w0
    gc.collect()

    seq_tokens = traffic["batch"] * traffic["seq"]
    # one more step outside the window so that the first timed step starts
    # from a drained device
    trainer.wait(trainer.dispatch())
    snap0 = metrics.snapshot()["counters"]
    tracer = ctx["tracer"]
    step_ends = []
    t_open = time.perf_counter()
    deadline = t_open + ctx["seconds"]
    tracer.window_opened(t_open)
    pending = None
    n_steps = 0
    while True:
        now = time.perf_counter()
        tracer.poll(now)
        if now >= deadline:
            break
        with tracer.span("bench.train.dispatch"):
            nxt = trainer.dispatch()
        n_steps += 1
        if pending is not None:        # one step stays in flight
            with tracer.span("bench.train.wait"):
                trainer.wait(pending)
            step_ends.append(time.perf_counter())
        pending = nxt
    if pending is not None:
        trainer.wait(pending)
        step_ends.append(time.perf_counter())
    t_close = time.perf_counter()
    tracer.window_closed(t_close)
    snap1 = metrics.snapshot()["counters"]
    mem = device.memory_reading(devices)
    try:
        temp = int(trainer.compiled_memory().temp_size_in_bytes)
    except Exception as e:  # noqa: BLE001 — said aloud, not fatal
        print(f'{{"note": "no memory_analysis of the step: '
              f'{type(e).__name__}: {str(e)[:200]}"}}', flush=True)
        temp = 0

    chips = len(devices)
    tps = n_steps * seq_tokens / (t_close - t_open) / chips
    edges = [t_open] + step_ends
    step_s = [b - a for a, b in zip(edges[:-1], edges[1:])]
    print(f'{{"note": "train window", "steps": {n_steps}, '
          f'"window_s": {t_close - t_open:.4f}}}', flush=True)

    # free the program, then the reference follows the three steps
    del trainer, pending
    gc.collect()
    checks = check.Checks(cfg["limits"])
    t_ref = time.perf_counter()
    w = W.make(cfg, vocab_rows, seed)
    ref = train_reference.run(
        w, jax.numpy.asarray(x), jax.numpy.asarray(y), cfg["n_head"], tr,
        row_block=tr.get("reference_row_block", 2))
    del w
    gc.collect()
    compare(checks, prog, ref)
    print(f'{{"note": "reference", "seconds": '
          f'{time.perf_counter() - t_ref:.3f}}}', flush=True)

    return {
        "e2e": {"train_tokens_per_s": tps},
        "t_open": t_open, "t_close": t_close,
        "counters_open": snap0, "counters_close": snap1,
        "step_seconds": step_s, "records": [],
        "attempted": n_steps, "failed": 0 if np.isfinite(losses).all()
        else n_steps,
        "checks": checks, "memory": mem,
        "program_temp_bytes": temp, "program_temp_of": "to_static",
        "tokens_per_step": seq_tokens, "seq": traffic["seq"],
        "chips": chips,
    }


def compare(checks, prog, ref):
    """Program against reference: each step's loss, the worst leaf's
    gradient norm at step 1 and the worst leaf's norm of change after
    three steps."""
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        checks.add(f"loss_step{i}", check.rel_gap(a, b), "loss_rel_gap")
    g, where = check.worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    checks.add("grad_norm_worst_leaf", g, note=where)
    d, where = check.worst_leaf_gap(prog["delta_norms"], ref["delta_norms"])
    checks.add("delta_norm_worst_leaf", d, note=where)
