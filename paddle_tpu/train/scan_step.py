"""Scan-over-layers donated GPT train step.

ONE jitted program per (shape, microbatch count) holding the entire
training hot path:

- forward/backward as `jax.lax.scan` over the STACKED [nl, ...] block
  leaves (models/gpt.py `scan_loss`) — compile wall is O(1) in depth
  instead of O(nl), which is what lets the 8-device CPU dryrun finish;
- gradient-accumulation microbatching: a scan over microbatches
  accumulates grads in f32 and the optimizer applies ONCE;
- ZeRO-1 (arxiv 2004.13336): optimizer moments (and fp32 masters) are
  laid out and constrained sharded over the `dp` mesh axis, so each
  replica materializes 1/dp of the optimizer state and computes only its
  shard of the weight update; GSPMD re-gathers the updated params;
- buffer donation (`donate_argnums=(0, 1)`): params + optimizer state
  update in place, no step-to-step copy of the model.

The paddle `Optimizer` object stays the checkpoint truth: the step seeds
its state FROM the optimizer's accumulators and `sync_to_model()` writes
params/moments back before any state_dict/eval consumer reads them.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core.autograd import x64_off_scope
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import get_mesh
from paddle_tpu.observability import metrics
from paddle_tpu.observability.flight_recorder import (Watchdog,
                                                      default_deadline,
                                                      flight)
from paddle_tpu.testing import faults


# Dense bf16 peak FLOP/s of ONE chip, keyed by `jax.devices()[0].device_kind`,
# each with its source. The one peak table in the repo (bench.py imports
# `peak_flops`, so its MFU and the `train.mfu` gauge cannot disagree). A
# device that is not in it is an error, not a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # Google Cloud documentation, "TPU v5e"
}


def peak_flops(device_kind=None) -> float:
    """Per-chip MFU denominator for ``device_kind`` (default: the first
    device's). Raises for a kind that has no published peak on record."""
    kind = jax.devices()[0].device_kind if device_kind is None \
        else device_kind
    try:
        return PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {kind!r}: add it to "
            "PEAK_BF16_FLOPS with its source") from None


def _tpu_kind():
    """``device_kind`` of the chip the step runs on, or None off-TPU —
    a CPU run has no model FLOP/s utilization to report."""
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else None


class ScanUnsupported(ValueError):
    """(model, optimizer, config) cannot take the scanned fused train-step
    path; callers fall back to the unrolled per-layer capture."""


def _leaf_keys(tree):
    for grp in ("blocks", "top"):
        for k in tree[grp]:
            yield grp, k


def _layer_param_name(grp, key):
    return f"gpt.h.0.{key}" if grp == "blocks" else key


class ScanTrainStep:
    """Captured donated train step for a GPTForCausalLM.

    model       : GPTForCausalLM (attention_dropout must be 0 to train)
    optimizer   : a _FUSABLE paddle optimizer (SGD/Momentum/Adam/AdamW/
                  Adagrad/RMSProp/Adadelta/Adamax) whose grad_clip is None
                  or ClipGradByGlobalNorm
    microbatches: default split of each step's batch (scan + f32 grad
                  accumulation, single optimizer apply)
    zero1       : True / False / "auto" (on when the mesh's dp axis > 1)
    grad_reducer: optional ``(loss, grads) -> (loss, grads)`` host hook
                  for CROSS-PROCESS data parallelism (multi-host fleets
                  whose jaxlib cannot compile one program over all
                  processes — `train/elastic.py` FleetReducer averages
                  through the coordination-service KV). When set the step
                  SPLITS into two programs: a grads program (loss +
                  pre-clip f32 grads out), the reducer on the host, then
                  a donated apply program (finite-check + clip + fused
                  update over the REDUCED values, so every rank skips or
                  applies identically). None (the default) keeps the
                  single fused program — bit-identical to before.
    """

    def __init__(self, model, optimizer, *, microbatches=1, zero1="auto",
                 mesh=None, axis="dp", use_loss_mask=False, seed=0,
                 grad_reducer=None):
        from paddle_tpu.models.gpt import GPTForCausalLM
        from paddle_tpu.nn.clip import ClipGradByGlobalNorm
        if not isinstance(model, GPTForCausalLM):
            raise ScanUnsupported(
                f"scan train step needs GPTForCausalLM, got "
                f"{type(model).__name__}")
        cfg = model.cfg
        if cfg.attention_dropout:
            raise ScanUnsupported(
                "attention_dropout > 0 has no scan-path implementation")
        names_update = getattr(optimizer, "functional_update", None)
        if names_update is None or not getattr(optimizer, "_FUSABLE", False):
            raise ScanUnsupported(
                f"{type(optimizer).__name__} has no pure fused update")
        if getattr(optimizer, "_l1_decay", 0.0):
            raise ScanUnsupported("L1 decay is not scan-fusable")
        clip = optimizer._grad_clip
        if clip is not None and not isinstance(clip, ClipGradByGlobalNorm):
            raise ScanUnsupported(
                f"{type(clip).__name__} is not scan-fusable (only "
                "ClipGradByGlobalNorm)")
        self._clip_norm = float(clip.clip_norm) if clip is not None else None
        self.model, self.opt, self.cfg = model, optimizer, cfg
        self.microbatches = max(1, int(microbatches))
        self.mesh = mesh if mesh is not None else get_mesh()
        self._axis = axis
        dp = self.mesh.shape.get(axis, 1) if self.mesh is not None else 1
        self.zero1 = bool(dp > 1) if zero1 == "auto" else bool(zero1)
        self.use_loss_mask = bool(use_loss_mask)
        self._state_names, self._update = optimizer.functional_update()
        self._key = jax.random.PRNGKey(seed)
        self._dirty = False
        self._compiles = 0
        self._seen_sigs = set()
        # bad-step containment (docs/ROBUSTNESS.md "Training fault
        # tolerance"): the program reduces an all-finite flag over loss +
        # grads and SKIPS the optimizer apply when it trips — same program,
        # zero recompiles. The host-side ladder lives in CheckpointManager.
        self.bad_steps = 0
        self.consecutive_bad_steps = 0
        self.last_step_ok = True
        self._grad_reducer = grad_reducer
        self.refresh_from_model()
        if self.mesh is not None:
            # pin the output placements to the input placements: params and
            # opt state come back exactly where they went in, so the SECOND
            # step sees identical (aval, sharding) signatures and the
            # program compiles exactly once on the mesh
            repl = NamedSharding(self.mesh, PartitionSpec())
            out_sh = (repl, repl, self._param_sh, self._state_sh)
        else:
            out_sh = None
        if grad_reducer is None:
            self._jit = jax.jit(self._make_step_fn(), donate_argnums=(0, 1),
                                **({"out_shardings": out_sh}
                                   if out_sh is not None else {}))
            self._jit_grads = self._jit_apply = None
        else:
            # split pipeline: grads out (params NOT donated — the apply
            # still reads them), host reduce, donated apply. Two programs,
            # each compiling exactly once (test_no_retrace pin).
            self._jit = None
            self._jit_grads = jax.jit(self._make_grads_fn())
            self._jit_apply = jax.jit(
                self._make_apply_fn(), donate_argnums=(0, 1),
                **({"out_shardings": out_sh} if out_sh is not None else {}))

    # ------------------------------------------------------------- state io

    def refresh_from_model(self):
        """(Re)pull params from the model and optimizer state from the
        optimizer's accumulators (zeros where absent), applying ZeRO-1
        placements to the state leaves. Called at init and after any
        out-of-band eager update (hapi ragged batch, set_state_dict)."""
        from paddle_tpu.models.gpt import stack_gpt_params
        from paddle_tpu.distributed.sharding import zero1_partition_spec
        state = self.model.state_dict()
        self._param_objs = dict(state)
        nl = self.cfg.num_layers
        self._params = stack_gpt_params(
            {k: t._data for k, t in state.items()}, mesh=self.mesh)
        opt, meta, opt_state = self.opt, {}, {"blocks": {}, "top": {}}
        param_sh = {"blocks": {}, "top": {}}
        state_sh = {"blocks": {}, "top": {}}
        replicated = NamedSharding(self.mesh, PartitionSpec()) \
            if self.mesh is not None else None
        use_master = bool(getattr(opt, "_use_master_weights", False))
        for grp, key in _leaf_keys(self._params):
            leaf = self._params[grp][key]
            pobjs = ([state[f"gpt.h.{i}.{key}"] for i in range(nl)]
                     if grp == "blocks" else [state[key]])
            lws = {opt._lr_wd_of(p, 1.0) for p in pobjs}
            if len(lws) != 1:
                raise ScanUnsupported(
                    f"per-layer lr/weight-decay differ across the stacked "
                    f"leaf {key!r}: {sorted(lws)} — the scanned step "
                    "updates all layers of a leaf with one (lr, wd)")
            lr_mult, wd = lws.pop()
            sh = getattr(leaf, "sharding", None)
            base_spec = tuple(sh.spec) if isinstance(sh, NamedSharding) \
                else None
            zspec = zero1_partition_spec(
                leaf.shape, self.mesh, self._axis,
                base_spec=base_spec) if self.zero1 else None
            zsh = NamedSharding(self.mesh, zspec) if zspec is not None \
                else None
            master = use_master and leaf.dtype != jnp.float32
            psh = sh if isinstance(sh, NamedSharding) else replicated
            if psh is not None and not isinstance(sh, NamedSharding):
                # commit unplaced params to the mesh (replicated) so the
                # step-1 and step-2 input signatures match (compile once)
                leaf = jax.device_put(leaf, psh)
                self._params[grp][key] = leaf
            ssh = zsh if zsh is not None else (replicated or None)
            meta[(grp, key)] = {
                "lr_mult": float(lr_mult), "wd": float(wd),
                "zsh": zsh,
                "psh": psh,
                "master": master,
                "need_clip": all(getattr(p, "need_clip", True)
                                 for p in pobjs),
            }
            param_sh[grp][key] = psh
            st = {}
            for name in self._state_names:
                arrs = [opt.get_state_array(name, p) for p in pobjs]
                if all(a is None for a in arrs):
                    stacked = opt._functional_state_init(name, leaf.shape)
                else:
                    stacked = jnp.stack([
                        jnp.asarray(a, jnp.float32) if a is not None
                        else opt._functional_state_init(name, leaf.shape[1:])
                        for a in arrs])
                    if grp == "top":
                        stacked = stacked[0]
                st[name] = jax.device_put(stacked, ssh) if ssh is not None \
                    else stacked
            if master:
                srcs = []
                for p in pobjs:
                    m = opt._master_weights.get(id(p))
                    m = m._data if m is not None else getattr(
                        p, "_master", None)
                    m = m._data if isinstance(m, Tensor) else m
                    srcs.append(jnp.asarray(m if m is not None else p._data,
                                            jnp.float32))
                mast = jnp.stack(srcs) if grp == "blocks" else srcs[0]
                st["master"] = jax.device_put(mast, ssh) if ssh is not None \
                    else mast
            opt_state[grp][key] = st
            state_sh[grp][key] = {n: ssh for n in st}
        self._meta = meta
        self._opt_state = opt_state
        self._param_sh = param_sh
        self._state_sh = state_sh
        self._dirty = False
        metrics.gauge("train.opt_state_bytes").set(self.opt_state_bytes())
        metrics.gauge("train.zero1").set(1.0 if self.zero1 else 0.0)

    def sync_to_model(self):
        """Write the step's params back into the model's Parameters and its
        optimizer state back into the accumulators/master weights, so
        state_dict / eval / the decode paths see the trained values."""
        from paddle_tpu.models.gpt import unstack_gpt_params
        arrs = unstack_gpt_params(self._params)
        nl = self.cfg.num_layers
        for name, t in self._param_objs.items():
            t._write(arrs[name])
        for grp, key in _leaf_keys(self._params):
            st = self._opt_state[grp][key]
            pobjs = ([self._param_objs[f"gpt.h.{i}.{key}"]
                      for i in range(nl)] if grp == "blocks"
                     else [self._param_objs[key]])
            for name in self._state_names:
                for i, p in enumerate(pobjs):
                    self.opt.set_state_array(
                        name, p, st[name][i] if grp == "blocks"
                        else st[name])
            if "master" in st:
                for i, p in enumerate(pobjs):
                    self.opt.set_master_array(
                        p, st["master"][i] if grp == "blocks"
                        else st["master"])
        self._dirty = False

    @property
    def dirty(self):
        return self._dirty

    @property
    def compile_count(self):
        return self._compiles

    def opt_state_bytes(self):
        """Per-replica optimizer-state footprint: each leaf counted at its
        SHARD size, so ZeRO-1 shows the ~1/dp saving the sharding buys."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self._opt_state):
            sh = getattr(leaf, "sharding", None)
            shape = sh.shard_shape(leaf.shape) if hasattr(sh, "shard_shape") \
                else leaf.shape
            total += int(np.prod(shape) or 1) * leaf.dtype.itemsize
        return total

    # ------------------------------------------------------------- the step

    def _make_grads_fn(self):
        """(params, xs, ys, ms, key_data, poison) -> (loss, f32 grads) —
        the forward/backward half: scan over layers, microbatch
        accumulation, NO optimizer math. Standalone program in reducer
        mode; inlined by `_make_step_fn` for the fused single-program
        path (identical op sequence either way)."""
        from paddle_tpu.models.gpt import scan_loss
        cfg, mesh = self.cfg, self.mesh
        use_mask = self.use_loss_mask

        def loss_fn(params, x, y, m, key):
            if mesh is not None and "dp" in mesh.axis_names \
                    and x.shape[0] % mesh.shape["dp"] == 0:
                sh = NamedSharding(mesh, PartitionSpec("dp", None))
                x = jax.lax.with_sharding_constraint(x, sh)
                y = jax.lax.with_sharding_constraint(y, sh)
            return scan_loss(params, x, y, cfg, loss_mask=m, training=True,
                             dropout_key=key)

        def grads_of(params, xs, ys, ms, keys):
            def one(x, y, m, k):
                return jax.value_and_grad(loss_fn)(params, x, y, m, k)

            if xs.shape[0] == 1:
                loss, g = one(xs[0], ys[0],
                              ms[0] if ms is not None else None, keys[0])
                return loss, jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), g)

            def micro(carry, inp):
                gacc, lacc = carry
                if ms is None:
                    x, y, k = inp
                    m = None
                else:
                    x, y, m, k = inp
                l, g = one(x, y, m, k)
                gacc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l), None

            zeros = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            xs_in = (xs, ys, keys) if ms is None else (xs, ys, ms, keys)
            (gsum, lsum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), xs_in)
            inv = 1.0 / xs.shape[0]
            return lsum * inv, jax.tree_util.tree_map(
                lambda a: a * inv, gsum)

        def grads_fn(params, xs, ys, ms, key_data, poison):
            key = jax.random.wrap_key_data(key_data)
            mkeys = jax.random.split(key, xs.shape[0])
            loss, grads = grads_of(params, xs, ys, ms if use_mask else None,
                                   mkeys)
            # poison: 0.0 normally, NaN when the train.step_nan fault is
            # armed — rides the loss into the finite reduce so chaos tests
            # drive the skip path through the SAME compiled program(s). In
            # reducer mode the poisoned loss travels THROUGH the reduce,
            # so one rank's injected NaN skips the step on every rank.
            return loss + poison, grads

        return grads_fn

    def _make_apply_fn(self):
        """(params, opt_state, loss, grads, lr, t) -> (loss, ok,
        new_params, new_state) — the optimizer half: all-finite reduce,
        global-norm clip, fused update, in-program bad-step skip."""
        names, update = self._state_names, self._update
        meta, clip_norm = self._meta, self._clip_norm

        def apply_fn(params, opt_state, loss, grads, lr, t):
            # all-finite reduce over loss + raw (pre-clip) grads: one
            # non-finite value anywhere makes ok False and the apply below
            # becomes the identity — the step is SKIPPED in-program, no
            # host round-trip, no recompile (test_no_retrace.py pin)
            ok = jnp.isfinite(loss)
            for gk in _leaf_keys(grads):
                ok = ok & jnp.all(jnp.isfinite(grads[gk[0]][gk[1]]))
            if clip_norm is not None:
                sq = jnp.zeros((), jnp.float32)
                for gk in _leaf_keys(grads):
                    if meta[gk]["need_clip"]:
                        sq = sq + jnp.sum(grads[gk[0]][gk[1]] ** 2)
                gn = jnp.sqrt(sq)
                scale = clip_norm / jnp.maximum(gn, clip_norm)
                grads = jax.tree_util.tree_map(lambda a: a * scale, grads)
            new_params = {"blocks": {}, "top": {}}
            new_state = {"blocks": {}, "top": {}}
            for grp, k in _leaf_keys(params):
                p, g = params[grp][k], grads[grp][k]
                st, mt = opt_state[grp][k], meta[(grp, k)]
                st0 = st               # pre-update state: the skip target
                if mt["zsh"] is not None:
                    # ZeRO-1: grads + moments dp-sharded, so the update math
                    # partitions over dp and each replica touches only its
                    # shard; the downcast param below is constrained back to
                    # the param's own placement and GSPMD all-gathers it
                    g = jax.lax.with_sharding_constraint(g, mt["zsh"])
                    st = {n: jax.lax.with_sharding_constraint(v, mt["zsh"])
                          for n, v in st.items()}
                p32 = st["master"] if mt["master"] else (
                    p.astype(jnp.float32) if p.dtype != jnp.float32 else p)
                new_p32, new_sts = update(
                    p32, g, [st[n] for n in names],
                    lr * mt["lr_mult"], jnp.asarray(mt["wd"], jnp.float32),
                    t)
                out = dict(zip(names, new_sts))
                if mt["master"]:
                    out["master"] = new_p32
                if mt["zsh"] is not None:
                    out = {n: jax.lax.with_sharding_constraint(v, mt["zsh"])
                           for n, v in out.items()}
                new_p = new_p32.astype(p.dtype)
                # non-finite step: keep the OLD params/state (NaNs computed
                # on the not-taken side are discarded by the select)
                new_p = jnp.where(ok, new_p, p)
                out = {n: jnp.where(ok, v, st0[n]) for n, v in out.items()}
                if mt["psh"] is not None:
                    new_p = jax.lax.with_sharding_constraint(new_p, mt["psh"])
                new_params[grp][k] = new_p
                new_state[grp][k] = out
            return loss, ok, new_params, new_state

        return apply_fn

    def _make_step_fn(self):
        """The fused single-program path: grads half composed with apply
        half inside ONE donated program — the exact op sequence the
        pre-split implementation traced, so losses stay bit-identical."""
        grads_fn = self._make_grads_fn()
        apply_fn = self._make_apply_fn()

        def step_fn(params, opt_state, xs, ys, ms, lr, t, key_data, poison):
            loss, grads = grads_fn(params, xs, ys, ms, key_data, poison)
            return apply_fn(params, opt_state, loss, grads, lr, t)

        return step_fn

    def step(self, x, y, loss_mask=None, microbatches=None):
        """One fused train step. x: [B, S] int ids, y: [B, S] labels
        (paddle Tensors or arrays); B must divide by the microbatch count.
        Returns the mean f32 loss as a python float."""
        # int32 ids/labels + an x64-disabled trace: the program must not mix
        # s64 loop indices into the SPMD-partitioned scan backward (XLA's
        # partitioner rejects s64/s32 compares on the dus indices), and the
        # vocab never exceeds int32 anyway. Same convention as the decode
        # paths (flash kernel x64_off).
        xd = x._data if hasattr(x, "_data") else jnp.asarray(x)
        yd = y._data if hasattr(y, "_data") else jnp.asarray(y)
        xd = xd.astype(jnp.int32) if xd.dtype != jnp.int32 else xd
        yd = yd.astype(jnp.int32) if yd.dtype != jnp.int32 else yd
        m = self.microbatches if microbatches is None else int(microbatches)
        b = xd.shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by microbatches {m}")
        xs = xd.reshape(m, b // m, *xd.shape[1:])
        ys = yd.reshape(m, b // m, *yd.shape[1:])
        if self.use_loss_mask:
            if loss_mask is None:
                raise ValueError("step captured with use_loss_mask=True "
                                 "needs a loss_mask")
            md = loss_mask._data if hasattr(loss_mask, "_data") \
                else jnp.asarray(loss_mask)
            ms = md.reshape(m, b // m, *md.shape[1:])
        else:
            ms = jnp.zeros((m, 1), jnp.float32)    # placeholder, DCE'd
        lr = jnp.asarray(self.opt.get_lr(), jnp.float32)
        t = jnp.asarray(self.opt._global_step + 1, jnp.float32)
        self._key, sub = jax.random.split(self._key)
        # train.step_nan chaos site: poison is a PROGRAM INPUT (0.0 or NaN),
        # so an injected bad step exercises the warm program, not a retrace
        injected = faults.ENABLED and faults.fire("train.step_nan")
        poison = jnp.asarray(float("nan") if injected else 0.0, jnp.float32)
        before = self._cache_size()
        # dispatch marker BEFORE the jit call: if the step (or its compile)
        # wedges, the watchdog dump's last ring event shows WHERE — a
        # post-hoc record would vanish with the hang
        flight.record("train.dispatch", step=self.opt._global_step + 1,
                      shape=str(tuple(xs.shape)))
        t0 = time.perf_counter()
        with x64_off_scope():
            if self._grad_reducer is None:
                loss, ok, self._params, self._opt_state = self._jit(
                    self._params, self._opt_state, xs, ys, ms, lr, t,
                    jax.random.key_data(sub), poison)
            else:
                # split pipeline (cross-process dp): local grads program,
                # host-side reduce over the fleet (the reducer raises
                # typed PeerLost when a peer dies mid-step), donated
                # apply over the REDUCED loss+grads — ok/skip decisions
                # are computed from identical values on every rank
                g_loss, grads = self._jit_grads(
                    self._params, xs, ys, ms,
                    jax.random.key_data(sub), poison)
                g_loss, grads = self._grad_reducer(g_loss, grads)
                grads = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a, jnp.float32), grads)
                loss, ok, self._params, self._opt_state = self._jit_apply(
                    self._params, self._opt_state,
                    jnp.asarray(g_loss, jnp.float32), grads, lr, t)
        lossf = float(loss)                        # sync: real device time
        okb = bool(ok)
        dt = time.perf_counter() - t0
        after = self._cache_size()
        if before >= 0 and after >= 0:
            compiled = after > before
        else:
            # jax internals moved (_cache_size gone): fall back to tracking
            # input signatures ourselves — one compile per distinct shape
            sig = (xs.shape, ys.shape, str(xs.dtype))
            compiled = sig not in self._seen_sigs
            self._seen_sigs.add(sig)
        tokens = int(np.prod(xd.shape))
        from paddle_tpu.models.gpt import analytic_flops_per_token
        flops = analytic_flops_per_token(self.cfg, int(xd.shape[-1])) * tokens
        # flops covers the whole global batch, so the peak must cover the
        # whole mesh — a per-chip denominator would read ~device_count too
        # high and clamp at 1.0 exactly on multichip deployments
        n_dev = self.mesh.size if self.mesh is not None else 1
        kind = _tpu_kind()
        mfu = None if kind is None else min(
            1.0, flops / (max(dt, 1e-9) * peak_flops(kind) * n_dev))
        if compiled:
            self._compiles += 1
            metrics.counter("train.compile_count").inc()
        elif okb:
            metrics.gauge("train.step_ms").set(dt * 1e3)
            metrics.histogram("train.step_seconds").observe(dt)
            # goodput + model FLOPs utilization from the ANALYTIC flop
            # count (models/gpt.py, 6N + attention term) — STEADY steps
            # only, like step_ms: a compile step's dt would read as a
            # collapsed mfu and fake the exact alarm the gauge exists to
            # raise (mfu down while step_ms holds = the batch shrank)
            if mfu is not None:
                metrics.gauge("train.mfu").set(mfu)
            metrics.gauge("train.goodput_tokens_per_s").set(
                tokens / max(dt, 1e-9))
        metrics.counter("train.steps").inc()
        metrics.counter("train.microbatches").inc(m)
        self.last_step_ok = okb
        if not okb:
            # non-finite loss/grads: the program kept the old params/state,
            # so the step NEVER HAPPENED as far as the optimizer clock, the
            # lr schedule, and the token/goodput accounting are concerned.
            # The host only counts it and flight-records — the rollback
            # ladder (M consecutive) is CheckpointManager.after_step's job.
            self.bad_steps += 1
            self.consecutive_bad_steps += 1
            metrics.counter("train.bad_steps").inc()
            flight.record("train.bad_step", step=self.opt._global_step + 1,
                          loss=lossf, consecutive=self.consecutive_bad_steps,
                          injected=bool(injected))
            return lossf
        self.consecutive_bad_steps = 0
        metrics.counter("train.tokens").inc(tokens)
        flight.record("train.step", step=self.opt._global_step + 1,
                      loss=lossf, ms=round(dt * 1e3, 3),
                      mfu=None if mfu is None else round(mfu, 5),
                      compiled=bool(compiled))
        self.opt._global_step += 1
        self.opt._sync_lr_tensor(self.opt.get_lr())
        self._dirty = True
        return lossf

    def _cache_size(self):
        try:
            if self._jit is not None:
                return self._jit._cache_size()
            # split (reducer) mode: compile accounting covers BOTH programs
            return (self._jit_grads._cache_size()
                    + self._jit_apply._cache_size())
        except Exception:  # noqa: BLE001 — jax internals moved
            return -1

    def start_watchdog(self, deadline_s=None, dump_dir=None,
                       interval_s=None):
        """Arm a stall watchdog over the train loop: if `step()` stops
        completing (a wedged device call, a hung collective) for
        ``deadline_s`` (default ``PADDLE_WATCHDOG_S``; <= 0 disables and
        returns None), the flight-recorder ring + metrics snapshot dump to
        a JSON file. The driver owns the lifecycle: call before the loop,
        `.stop()` after — an armed watchdog treats the loop as always-busy,
        so don't leave it running across eval/checkpoint pauses longer
        than the deadline."""
        deadline = default_deadline() if deadline_s is None \
            else float(deadline_s)
        if deadline <= 0:
            return None
        return Watchdog("train",
                        progress=lambda: self.opt._global_step,
                        deadline_s=deadline, dump_dir=dump_dir,
                        interval_s=interval_s).start()

    __call__ = step
