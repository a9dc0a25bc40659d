"""What can be asked of the chip without the chip.

- Every authored Pallas kernel, compiled by the TPU's own compiler for a
  described (not attached) ``v5e:2x2`` topology at GPT-2 small and GPT-2
  345M widths, forward and — where it has one — backward. Interpret mode
  cannot see what these see: a block shape that is not (8, 128)-tileable,
  an i64 index from a Python int under global x64, a DMA slice below one
  tile, too much VMEM. A compile that passes is not a chip run.
- The engine's two hot step programs whole, at GPT-2 medium's serving
  shapes: no copy of the KV pool is left in them. The same for
  Phi-4-mini-flash's decode step and prefill chunk at its published widths
  and full depth: no copy of a pool or of a state stack.
- ``chip_smoke.py``'s phases end to end at a toy size on the CPU, with the
  platform assertion steered from here.
- The compile-cache placement rule and the "no backend at import" rule the
  one-process-per-chip contract rests on.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BF16 = jnp.bfloat16
# (heads, head_dim, hidden) of GPT-2 small and GPT-2 345M
WIDTHS = {"small": (12, 64, 768), "345m": (16, 64, 1024)}
PAGE, PAGES_PER_SLOT, SLOTS, CHUNK = 16, 64, 32, 256


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip; compile cache off
    around these compiles (an entry written for a described chip cannot be
    read back without one — the next run would only warn)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_to_a_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def _sq(fn):
    return lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()


# [B, H, S, D] of the calls compiled: the two widths at batch 8, and the
# training cell's own call (gpt2s-train-b16s1024)
FLASH_CALLS = {"small": (8, 12, 1024, 64), "345m": (8, 16, 1024, 64),
               "cell": (16, 12, 1024, 64)}


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("call", FLASH_CALLS)
def test_flash_attention_compiles_for_v5e(chip, call, bwd):
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct(FLASH_CALLS[call], BF16, sharding=chip)

    def fn(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, interpret=False)

    _compiles_to_a_kernel(
        jax.grad(_sq(fn), argnums=(0, 1, 2)) if bwd else fn, q, q, q)


def score_block_ops(hlo_text, seq_k, rows=256):
    """``[(opcode, name, shape)]`` of every instruction of the optimized
    HLO, outside fusion bodies, whose result (or a member of its tuple)
    ends in ``[.., r, seq_k]`` with ``r >= rows``: a block of attention
    scores or probabilities that exists in HBM. A kernel's custom call
    holds its blocks in VMEM and gives back ``[B, S, H * D]`` and one
    statistic a query (``[B, H, 1, S]``: one row, not a block)."""
    import re
    bodies, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None and " = " in line:
            bodies[name].append(line)
    fused = {m.group(1) for lines in bodies.values() for ln in lines
             for m in [re.search(r" fusion\(.*calls=%([\w.\-]+)", ln)] if m}
    found = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) "
                         r"([a-z][a-z\-]*)\(", ln)
            if not m or m.group(3) in ("parameter", "get-tuple-element"):
                continue
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2)):
                d = [int(x) for x in dims.split(",") if x]
                if len(d) >= 2 and d[-1] == seq_k and d[-2] >= rows:
                    found.append((m.group(3), m.group(1), m.group(2)))
                    break
    return found


def test_score_block_ops_sees_a_block_of_scores():
    """The reader the next test rests on, on a hand-written module: a
    product and a fusion that give a block of scores count, what a
    fusion holds inside, the custom call's ``[B, S, H * D]`` output and
    its row of statistics a head, and a block of fewer rows do not."""
    text = """\
%fused_exp (p: bf16[16,12,256,1024]) -> bf16[16,12,256,1024] {
  %e = f32[16,12,256,1024]{3,2,1,0} exponential(%p)
  ROOT %c = bf16[16,12,256,1024]{3,2,1,0} convert(%e)
}
ENTRY %main (q: bf16[16,12,1024,64]) -> bf16[16,12,1024,64] {
  %q = bf16[16,12,1024,64]{3,2,1,0} parameter(0)
  %convolution.1 = bf16[16,12,256,1024]{3,2,1,0} convolution(%qb, %k), dim_labels=0bf_0oi->0bf
  %fusion.2 = (f32[16,12,256]{2,1,0}, bf16[16,12,256,1024]{3,2,1,0}) fusion(%convolution.1), kind=kLoop, calls=%fused_exp
  %dot.3 = f32[1024,1024]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  %fusion.4 = bf16[16,12,128,1024]{3,2,1,0} fusion(%x), kind=kLoop, calls=%fused_small
  %custom-call.5 = (bf16[16,1024,768]{2,1,0}, f32[16,12,1,1024]{3,2,1,0}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"
  ROOT %copy.6 = bf16[16,12,1024,64]{3,2,1,0} copy(%o)
}
"""
    assert [(op, name) for op, name, _ in score_block_ops(text, 1024)] \
        == [("convolution", "convolution.1"), ("fusion", "fusion.2"),
            ("dot", "dot.3")]


@pytest.mark.parametrize("impl,clean", [("authored", True), ("xla", False)])
def test_attention_layer_keeps_its_scores_off_hbm_on_v5e(chip, monkeypatch,
                                                         impl, clean):
    """One attention layer's forward + backward at the training cell's
    shape, through ``flash_attention_fn`` with the arm forced: under the
    Pallas arm no instruction outside the custom calls gives a block of
    scores; under the XLA arm the reader finds the blocks it unrolls."""
    from paddle_tpu.framework.flags import flag_value, set_flags
    from paddle_tpu.kernels.flash_attention import flash_attention_fn
    from paddle_tpu.kernels.pallas import _compat
    was = flag_value("tpu_flash_impl")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    set_flags({"tpu_flash_impl": impl})
    try:
        q = jax.ShapeDtypeStruct((16, 1024, 12, 64), BF16, sharding=chip)
        step = jax.grad(_sq(flash_attention_fn(causal=True)),
                        argnums=(0, 1, 2))
        text = jax.jit(step).lower(q, q, q).compile().as_text()
    finally:
        set_flags({"tpu_flash_impl": was})
    blocks = score_block_ops(text, 1024)
    if clean:
        assert text.count("tpu_custom_call") >= 2       # forward, backward
        assert blocks == []
    else:
        assert "tpu_custom_call" not in text and blocks


# the training cell's head: rows, hidden, vocabulary (gpt2s-train-b16s1024)
HEAD = (16 * 1024, 768, 50304)


def logit_reduce_ops(hlo_text, rows, cols):
    """``[(name, operand)]`` of every fusion of the optimized HLO's ENTRY
    computation that holds a ``reduce`` and takes a float32 operand of
    ``rows`` by ``cols`` or more, either way round: a pass over a block of
    logits in HBM for a row statistic. A product that gives the row
    maximum as a second result reads ``h`` and ``W`` and not logits; the
    backward's products read logits and hold no ``reduce``."""
    import re
    bodies = dict(re.findall(r"^%([\w.\-]+) \(.*?\{$(.*?)^\}", hlo_text,
                             re.M | re.S))
    found = []
    for ln in hlo_text[hlo_text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .+? fusion\(.*"
                     r"calls=%([\w.\-]+)", ln)
        if not m or " reduce(" not in bodies[m.group(2)]:
            continue
        for shape, a, b in re.findall(r"= (f32\[(\d+),(\d+)\])\S* parameter\(",
                                      bodies[m.group(2)]):
            a, b = int(a), int(b)
            if (a == rows and b >= cols) or (b == rows and a >= cols):
                found.append((m.group(1), shape))
                break
    return found


def test_logit_reduce_ops_sees_a_pass_over_logits():
    """The reader the next test rests on, on a hand-written module: the
    sum of exponentials over a held block of logits counts, whichever way
    round the block lies; the product with the row maximum beside it, a
    product fed from the logits and a reduction of something narrower do
    not."""
    text = """\
%fused_sum (p0: f32[16384,12576], p1: f32[16384]) -> f32[16384] {
  %p0 = f32[16384,12576]{0,1:T(8,128)} parameter(0)
  %p1 = f32[16384]{0:T(1024)} parameter(1)
  %e = f32[16384,12576]{0,1:T(8,128)} exponential(%p0)
  ROOT %r = f32[16384]{0:T(1024)} reduce(%e, %c), dimensions={1}, to_apply=%add
}
%fused_sum_t (p0: f32[50304,16384]) -> f32[16384] {
  %p0 = f32[50304,16384]{1,0:T(8,128)} parameter(0)
  ROOT %r = f32[16384]{0:T(1024)} reduce(%p0, %c), dimensions={0}, to_apply=%add
}
%fused_product (p0: bf16[16384,768], p1: bf16[50304,768]) -> (f32[16384], f32[16384,12576]) {
  %p0 = bf16[16384,768]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[50304,768]{1,0:T(8,128)(2,1)} parameter(1)
  %conv = f32[16384,12576]{0,1:T(8,128)} convolution(%p0, %s), dim_labels=bf_oi->bf
  %m = f32[16384]{0:T(1024)} reduce(%conv, %c), dimensions={1}, to_apply=%max
  ROOT %t = (f32[16384]{0:T(1024)}, f32[16384,12576]{0,1:T(8,128)}) tuple(%m, %conv)
}
%fused_grad (p0: f32[16384,12576], p1: bf16[16384,768]) -> bf16[12576,768] {
  %p0 = f32[16384,12576]{0,1:T(8,128)} parameter(0)
  %p1 = bf16[16384,768]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %conv = bf16[12576,768]{1,0:T(8,128)(2,1)} convolution(%d, %p1), dim_labels=fb_io->bf
}
%fused_narrow (p0: f32[16384,768]) -> f32[16384] {
  %p0 = f32[16384,768]{1,0:T(8,128)} parameter(0)
  ROOT %r = f32[16384]{0:T(1024)} reduce(%p0, %c), dimensions={1}, to_apply=%add
}
ENTRY %main (h: bf16[16384,768], w: bf16[50304,768]) -> f32[] {
  %fusion.1 = (f32[16384]{0:T(1024)}, f32[16384,12576]{0,1:T(8,128)}) fusion(%h, %w), kind=kOutput, calls=%fused_product
  %exponential_reduce_fusion = f32[16384]{0:T(1024)} fusion(%gte.1, %gte.0), kind=kLoop, calls=%fused_sum
  %reduce_fusion.2 = f32[16384]{0:T(1024)} fusion(%custom-call.3), kind=kLoop, calls=%fused_sum_t
  %fusion.4 = bf16[12576,768]{1,0:T(8,128)(2,1)} fusion(%gte.1, %h), kind=kOutput, calls=%fused_grad
  %fusion.5 = f32[16384]{0:T(1024)} fusion(%dh), kind=kLoop, calls=%fused_narrow
}
"""
    assert logit_reduce_ops(text, 16384, 4096) == [
        ("exponential_reduce_fusion", "f32[16384,12576]"),
        ("reduce_fusion.2", "f32[50304,16384]")]


def test_fused_ce_forward_kernel_compiles_for_v5e(chip):
    from paddle_tpu.kernels.pallas import fused_ce as kernel
    n, hid, v = HEAD
    plan = kernel._plan(n, hid, v)
    assert plan is not None
    _compiles_to_a_kernel(
        lambda h, w, lab: kernel.forward(h, w, lab, plan=plan,
                                         interpret=False),
        jax.ShapeDtypeStruct((n, hid), BF16, sharding=chip),
        jax.ShapeDtypeStruct((v, hid), BF16, sharding=chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip))


@pytest.mark.parametrize("body", ["pallas", "xla"])
def test_head_takes_its_statistics_in_the_kernel_on_v5e(chip, monkeypatch,
                                                        body):
    """The head's forward + backward at the training cell's shape. Under
    the Pallas body: one custom call and the backward's two products (a
    third would be the forward's made again), no pass over the logits
    for a statistic, nothing but the kernel gives a result as large as a
    slice of them (no copy or transpose in front of the backward), and
    the logits are held once. Under XLA's body, which the CPU's name
    selects: twelve products (four slices of the vocabulary), four
    passes."""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
    from paddle_tpu.kernels.pallas import _compat
    if body == "pallas":
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    n, hid, v = HEAD
    compiled = jax.jit(jax.value_and_grad(
        lambda h, w, lab: fused_linear_cross_entropy(h, w, lab).mean(),
        argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((n, hid), BF16, sharding=chip),
        jax.ShapeDtypeStruct((v, hid), BF16, sharding=chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)).compile()
    text = compiled.as_text()
    products = text.count(" convolution(")
    kernels = text.count('custom_call_target="tpu_custom_call"')
    passes = logit_reduce_ops(text, n, 4096)
    slice_sized = pool_sized_ops(text, n * (v // 4))
    assert compiled.memory_analysis().temp_size_in_bytes <= 3.4e9
    if body == "pallas":
        assert (products, kernels) == (2, 1)
        assert passes == [] and slice_sized == []
    else:
        assert (products, kernels) == (12, 0)
        assert len(passes) == 4 and len(slice_sized) == 4


LAYERS = 12             # of the stored pool the kernels are handed


def _pool(nh, dh, kv, chip):
    """Shapes of one pool and its scales, and the ``layer=`` to call with:
    the stored stack ``[nl, P, page, nh*dh]`` read at its last layer, or
    (``per-layer``) one layer's ``[P, page, nh, dh]`` with no layer, the
    form the benchmark's selection probe passes."""
    pages = 1 + SLOTS * PAGES_PER_SLOT
    dtype = {"int8": jnp.int8, "f32": jnp.float32}.get(kv.split("-")[0], BF16)
    if kv.endswith("per-layer"):
        shape, lead, layer = (pages, PAGE, nh, dh), (), None
    else:
        shape, lead, layer = (LAYERS, pages, PAGE, nh * dh), (LAYERS,), \
            LAYERS - 1
    return (jax.ShapeDtypeStruct(shape, dtype, sharding=chip),
            jax.ShapeDtypeStruct(lead + (pages, PAGE, nh), jnp.float32,
                                 sharding=chip), layer)


POOL_FORMS = ["bf16", "int8", "bf16-per-layer"]


# the decode kernel's block and dot forms follow the pool's width and
# dtype: GPT-2 large's width and a float32 pool besides
DECODE_WIDTHS = {**WIDTHS, "large": (20, 64, 1280)}


@pytest.mark.parametrize("kv", POOL_FORMS + ["f32"])
@pytest.mark.parametrize("width", DECODE_WIDTHS)
def test_paged_decode_attention_compiles_for_v5e(chip, width, kv):
    from paddle_tpu.kernels.pallas.paged_attention import paged_attention
    nh, dh, _ = DECODE_WIDTHS[width]
    pool, scales, layer = _pool(nh, dh, kv, chip)
    q = jax.ShapeDtypeStruct((SLOTS, nh, dh), BF16, sharding=chip)
    table = jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32,
                                 sharding=chip)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=chip)
    _compiles_to_a_kernel(
        lambda q_, k_, v_, t_, p_, *s: paged_attention(
            q_, k_, v_, t_, p_, layer=layer, interpret=False,
            **dict(zip(("k_scale", "v_scale"), s))),
        q, pool, pool, table, pos, *((scales, scales) if kv == "int8" else ()))


@pytest.mark.parametrize("kv", POOL_FORMS)
@pytest.mark.parametrize("width", WIDTHS)
def test_ragged_prefill_attention_compiles_for_v5e(chip, width, kv):
    from paddle_tpu.kernels.pallas.prefill_attention import prefill_attention
    nh, dh, _ = WIDTHS[width]
    pool, scales, layer = _pool(nh, dh, kv, chip)
    q = jax.ShapeDtypeStruct((CHUNK, nh, dh), BF16, sharding=chip)
    row = jax.ShapeDtypeStruct((PAGES_PER_SLOT,), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    _compiles_to_a_kernel(
        lambda q_, k_, v_, t_, s_, n_, *sc: prefill_attention(
            q_, k_, v_, t_, s_, n_, layer=layer, interpret=False,
            **dict(zip(("k_scale", "v_scale"), sc))),
        q, pool, pool, row, scalar, scalar,
        *((scales, scales) if kv == "int8" else ()))


# ------------------------- whole step programs: no copy of the pool left

# gpt2-medium as benchmarks/configs/gpt2-medium.json serves it
MEDIUM = dict(layers=24, heads=16, hidden=1024, vocab=50304, positions=1024,
              slots=24, pages=1537, page=16, per_slot=64, chunk=256)


def _medium_params(chip):
    """Shapes of gpt2-medium's served parameters in bf16 (no array is
    made): `models/gpt.py::serving_params`, a block's 8 vectors stacked by
    layer, its 4 matrices a tuple of per-layer leaves, and the 4 top
    leaves."""
    h, v, nl = MEDIUM["hidden"], MEDIUM["vocab"], MEDIUM["layers"]

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=chip)
    params = {"gpt.wte.weight": leaf(v, h),
              "gpt.wpe.weight": leaf(MEDIUM["positions"], h),
              "gpt.ln_f.weight": leaf(h), "gpt.ln_f.bias": leaf(h)}
    for name, shape in [
            ("ln_1", (h,)), ("ln_2", (h,)), ("attn.qkv_proj", (h, 3 * h)),
            ("attn.out_proj", (h, h)), ("mlp.fc_in", (h, 4 * h)),
            ("mlp.fc_out", (4 * h, h))]:
        params[f"blocks.{name}.weight"] = leaf(nl, *shape) \
            if len(shape) == 1 else tuple(leaf(*shape) for _ in range(nl))
        params[f"blocks.{name}.bias"] = leaf(nl, shape[-1])
    return params


def pool_sized_ops(hlo_text, pool_elems, in_place=("scatter",)):
    """``[(opcode, name, shape)]`` of every materialised ``copy``,
    ``slice``, ``transpose`` or ``fusion`` of the optimized HLO whose
    output holds ``pool_elems`` elements or more — a layer pool relaid,
    sliced out or viewed — other than the pool's in-place update (a fusion
    whose root is one of ``in_place``: the scatter; for a state stack
    updated a layer at a time also ``dynamic-update-slice``). Instructions
    inside a fusion's body materialise nothing and are not counted."""
    import re
    bodies, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None and " = " in line:
            bodies[name].append(line)
    fused = {m.group(1) for lines in bodies.values() for ln in lines
             for m in [re.search(r" fusion\(.*calls=%([\w.\-]+)", ln)] if m}
    found = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) "
                         r"([a-z][a-z\-]*)\(", ln)
            if not m or m.group(3) not in ("copy", "slice", "transpose",
                                           "fusion"):
                continue
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
            if not sizes or max(sizes) < pool_elems:
                continue
            if m.group(3) == "fusion":
                body = bodies[re.search(r"calls=%([\w.\-]+)", ln).group(1)]
                if any(re.match(r"\s*ROOT %\S+ = .+? (?:"
                                + "|".join(in_place) + r")\(", b)
                       for b in body):
                    continue                  # the in-place pool update
            found.append((m.group(3), m.group(1), m.group(2)))
    return found


def test_pool_sized_ops_sees_a_relayout():
    """The reader the next test rests on, on four lines of HLO: a copy and
    a slice of a pool count, the scatter fusion and a fusion's inside do
    not, nor does anything smaller than a layer pool."""
    text = """\
%fused_scatter (p: bf16[2,8,4,16]) -> bf16[2,8,4,16] {
  %c = bf16[2,8,4,16]{3,2,1,0} copy(%p)
  ROOT %s = bf16[2,8,4,16]{3,2,1,0:T(8,128)(2,1)} scatter(%c, %i, %u), to_apply=%r
}
ENTRY %main (a: bf16[2,8,4,16]) -> bf16[2,8,4,16] {
  %copy.1 = bf16[2,8,4,2,8]{4,3,2,1,0:T(8,128)(2,1)} copy(%a)
  %slice.2 = bf16[1,8,4,16]{3,2,1,0} slice(%a), slice={[0:1], [0:8]}
  %copy.3 = bf16[8,16]{1,0} copy(%q)
  ROOT %fusion.4 = bf16[2,8,4,16]{3,2,1,0} fusion(%a), kind=kLoop, calls=%fused_scatter
}
"""
    assert [(op, name) for op, name, _ in pool_sized_ops(text, 8 * 4 * 16)] \
        == [("copy", "copy.1"), ("slice", "slice.2")]


PROGRAMS = ["decode_step", "prefill_chunk_step", "prefill_step"]


@pytest.fixture(scope="module")
def medium_compiled(chip):
    """``get(program)``: one of the engine's step programs (decode step, a
    prefill chunk, a one-shot prefill: each takes the token chain and
    returns the next one), whole, at gpt2-medium's serving shapes with the
    Pallas arms pinned, compiled for the described chip as the engine
    lowers it: ``(params, cache, *small)``, the cache donated whole.
    Compiled once for the tests that read it; ``get.lowered[program]`` is
    the text the compiler was handed."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.models import gpt
    m, done = MEDIUM, {}
    cfg = gpt.GPTConfig(vocab_size=m["vocab"], hidden_size=m["hidden"],
                        num_layers=m["layers"], num_heads=m["heads"],
                        max_position_embeddings=m["positions"])
    pool = jax.ShapeDtypeStruct(
        (m["layers"], m["pages"], m["page"], m["hidden"]), BF16,
        sharding=chip)

    def get(program):
        if program in done:
            return done[program]
        cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                            state=(), keys=None, heads=m["heads"])
        if program == "decode_step":
            up = step_upload(m["slots"], m["per_slot"], sampling=False)
            step = decode_program(gpt, cfg, up)
        else:
            up = prefill_upload(m["chunk"], m["per_slot"], sampling=False,
                                chunk=program == "prefill_chunk_step")
            step = prefill_program(gpt, cfg, up)
        small = (jax.ShapeDtypeStruct((m["slots"],), jnp.int32,
                                      sharding=chip),
                 up.spec(sharding=chip))
        # the kernels ask the default backend, which is the CPU here
        was = _compat.default_interpret
        _compat.default_interpret = lambda: False
        set_flags({"tpu_paged_impl": "pallas", "tpu_prefill_impl": "pallas"})
        try:
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                _medium_params(chip), cache, *small)
            get.lowered[program] = lowered.as_text()
            done[program] = lowered.compile()
        finally:
            set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})
            _compat.default_interpret = was
        return done[program]
    get.lowered = {}
    return get


@pytest.mark.parametrize("program", PROGRAMS)
def test_step_program_copies_no_layer_pool_on_v5e(medium_compiled, program):
    """The engine's decode step, one prefill chunk and a one-shot prefill
    (`medium_compiled`): the optimized HLO holds no copy, slice,
    transpose or fusion of a layer pool's size (1537 x 16 x 1024 elements)
    but the in-place update, and the compiler's temporaries stay under one
    layer's pool (they were 7.3 GB: PERF.md, PR 26)."""
    m = MEDIUM
    compiled = medium_compiled(program)
    text = compiled.as_text()
    chain, _ = compiled.out_info
    assert chain.shape == (m["slots"],) and chain.dtype == jnp.int32
    assert text.count("tpu_custom_call") >= m["layers"]
    layer_pool = m["pages"] * m["page"] * m["hidden"]
    assert pool_sized_ops(text, layer_pool) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * layer_pool          # bf16 bytes
    assert mem.alias_size_in_bytes >= 2 * 2 * m["layers"] * layer_pool


def weight_shaped_ops(hlo_text, shapes):
    """``[(opcode, name, shape)]`` of every ``copy``, ``slice``,
    ``dynamic-slice``, ``transpose`` or ``fusion`` of the optimized HLO's
    ENTRY computation with a result of one of ``shapes`` (dimensions of 1
    dropped) that lives in HBM: a layer's weight copied out of its stack.
    A result in the chip's fast memory (``S(1)`` in its layout: the
    compiler's own prefetch of an operand) is no copy in HBM, and neither
    is what a fusion's body holds."""
    import re
    shapes = {tuple(s) for s in shapes}
    found = []
    for ln in hlo_text[hlo_text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][a-z\-]*)\(",
                     ln)
        if not m or m.group(3) not in ("copy", "slice", "dynamic-slice",
                                       "transpose", "fusion"):
            continue
        for dims, layout in re.findall(r"\w+\[([\d,]*)\](\{[^}]*\})?",
                                       m.group(2)):
            shape = tuple(int(d) for d in dims.split(",") if d and d != "1")
            if shape in shapes and "S(1)" not in layout:
                found.append((m.group(3), m.group(1), m.group(2)))
    return found


def test_weight_shaped_ops_sees_a_layer_copied_out_of_its_stack():
    """The reader the next test rests on, on a few lines of HLO: a slice, a
    copy and a fusion result of a weight's shape in HBM count (a leading 1
    or not); a prefetch into fast memory, a smaller result, a parameter and
    a fusion's inside do not."""
    text = """\
%fused_dot (p: bf16[3,8,16]) -> bf16[4,16] {
  %s = bf16[1,8,16]{2,1,0} slice(%p), slice={[1:2], [0:8], [0:16]}
  ROOT %d = bf16[4,16]{1,0} convolution(%x, %s)
}
ENTRY %main (a: bf16[3,8,16]) -> bf16[4,16] {
  %a = bf16[3,8,16]{2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.1 = bf16[1,8,16]{2,1,0:T(8,128)(2,1)} slice(%a), slice={[0:1], [0:8], [0:16]}
  %copy.2 = bf16[8,16]{1,0:T(8,128)(2,1)} copy(%b)
  %copy.3 = bf16[8,16]{1,0:T(8,128)(2,1)S(1)} copy(%b)
  %fusion.4 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%widen
  %copy.5 = bf16[16]{0} copy(%c)
  ROOT %fusion.6 = bf16[4,16]{1,0} fusion(%a), kind=kOutput, calls=%fused_dot
}
"""
    assert [(op, name) for op, name, _ in
            weight_shaped_ops(text, [(8, 16)])] == \
        [("slice", "slice.1"), ("copy", "copy.2"), ("fusion", "fusion.4")]


@pytest.mark.parametrize("program", PROGRAMS)
def test_step_program_prefetches_its_matrices_and_copies_none_on_v5e(
        medium_compiled, program):
    """The same three programs (`medium_compiled`) take 108 parameters (4
    matrices a layer, 8 stacks of vectors, 4 top leaves; the state_dict's
    292 cost a launch 0.25 ms more of the host's time) and read a layer's
    vectors out of their stacks in place. The optimized HLO holds no copy,
    slice, transpose or fusion result IN HBM of a matrix's shape, and the
    compiler still prefetches matrices into fast memory under the ops
    before their products, which it can do for an operand of a layer's
    size and not for a 24-layer stack (PERF.md, PR 37: with the matrices
    stacked too the programs held 0 such prefetches and the decode program
    ran 2.04 ms a step on the chip, not 1.85)."""
    import re
    m = MEDIUM
    compiled = medium_compiled(program)
    h, nl = m["hidden"], m["layers"]
    assert len(jax.tree_util.tree_leaves(compiled.args_info[0][0])) \
        == 4 * nl + 8 + 4
    mats = [(h, 3 * h), (h, 4 * h), (4 * h, h), (h, h)]
    text = compiled.as_text()
    assert weight_shaped_ops(text, mats) == []
    # a prefetched matrix: the result, in fast memory (S(1)), of an async
    # copy, or of the pieces of a sliced one put together (ConcatBitcast)
    fast = re.findall(r"= bf16\[(\d+),(\d+)\]\{[^}]*S\(1\)\} (?:copy-done\(|"
                      r"custom-call\(.*custom_call_target=\"ConcatBitcast\")",
                      text)
    assert sum((int(a), int(b)) in mats for a, b in fast) >= nl
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 2 * 12 * nl * h * h  # all weights


def entry_ops(hlo_text):
    """``Counter`` of ``(opcode, result shape)`` over the optimized HLO's
    ENTRY computation, layouts dropped."""
    import collections
    import re
    found = collections.Counter()
    for ln in hlo_text[hlo_text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([a-z][a-z\-]*)\(", ln)
        if m:
            found[m.group(2), re.sub(r"\{[^}]*\}", "", m.group(1))] += 1
    return found


def test_decode_step_keeps_its_op_families_on_v5e(medium_compiled):
    """The decode step compiled for the chip is the program it was while
    the block was inlined a layer at a time (the compiler inlines the
    block's calls before any other pass: PERF.md, PR 39). A layer is two
    in-place scatters into the pools, two fusions that end in a layer norm's
    statistics (and one for the final norm) and one attention kernel."""
    m = MEDIUM
    nl, b, h = m["layers"], m["slots"], m["hidden"]
    ops = entry_ops(medium_compiled("decode_step").as_text())
    pool = f"bf16[{nl},{m['pages']},{m['page']},{h}]"
    assert ops["fusion", pool] == 2 * nl
    assert ops["fusion", f"(f32[{b}], bf16[{b},{h}])"] == 2 * nl + 1
    assert ops["custom-call", f"bf16[{b},1,{h}]"] == nl
    # the whole program, by opcode: what it was before the kernel learnt to
    # keep a pair's value lanes for another family (PR 49)
    assert sum(n for (op, _), n in ops.items() if op == "fusion") == 322
    assert sum(n for (op, _), n in ops.items() if op == "custom-call") == 58


@pytest.mark.parametrize("program", ["prefill_chunk_step", "prefill_step"])
def test_prefill_program_lowers_one_kernel_body_and_one_block_on_v5e(
        medium_compiled, program):
    """What a prefill program hands the compiler holds ONE Mosaic kernel
    body, in the prefill kernel's one function, called by the block's one
    function, which the program calls once a layer: it held 24 bodies, and
    tracing and lowering them was 4.8-5.2 s of each prefill program's 5.8-
    6.9 s of every start (PERF.md, PR 39). The guard that keeps a start
    from growing back needs no clock."""
    import re
    medium_compiled(program)
    text = medium_compiled.lowered[program]
    nl = MEDIUM["layers"]
    assert text.count("tpu_custom_call") == 1
    for fn, calls in [("_stored_call", 1), ("block", nl)]:
        assert len(re.findall(rf"func\.func private @{fn}\(", text)) == 1
        assert len(re.findall(rf"call @{fn}\(", text)) == calls


# Phi-4-mini-flash as benchmarks/configs/phi-4-mini-flash.json serves it
FLASH = dict(slots=64, page=16, per_slot=128, chunk=256)


@pytest.mark.parametrize("program", PROGRAMS)
def test_hybrid_step_program_copies_no_pool_or_state_on_v5e(chip, program,
                                                            monkeypatch):
    """Phi-4-mini-flash's decode step, one prefill chunk and a one-shot
    prefill of a chunk's length (each takes the token chain and returns
    the next one), whole, at the published widths and all 32 layers,
    compiled for the described chip with pool, rings and state donated and
    with the arms a TPU run takes (the code that picks them asks JAX for
    its backend, which here is the CPU: the test steers it). The optimized
    HLO holds no copy, slice, transpose or fusion of the size of a state
    stack (SSM: 9 x 64 x 16 x 5120; convolution: 9 x 64 x 15360), of the
    page pool or of all the window rings but their in-place updates;
    everything donated is aliased; and the program fits the chip beside its
    10 GB of arguments. The decode step reads the shared cache inside the
    paged decode kernel (`kernels/diff_attention.py::diff_attention_paged`,
    its ``pallas`` arm): one Mosaic call for the full layer and one in the
    scan's body for the seven cross layers, each under the scope
    ``shared_kv_attn``; the gather of every slot's page row (K and V,
    ``[slots * pages_per_slot, page, 1280]``) and the ``[slots, 4,
    2048]`` float32 scores of the plain-XLA arm are gone, and with them
    0.6 GB of the program's temporaries. (A chunk's attention scores over
    its slot's gathered row, ``[.., 256, 2048]`` float32, are larger than a
    state stack and are no copy of anything.)"""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import phi4flash as phi
    cfg = phi.Phi4FlashConfig()
    f = FLASH
    slots, per_slot = f["slots"], f["per_slot"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in phi.leaf_shapes(cfg).items()}
    pool = sds((1, 1 + slots * per_slot, f["page"], cfg.kv_width), BF16)
    state = tuple(sds(s, d) for _, _, s, d in
                  phi.state_arrays(cfg, slots, f["page"], BF16))
    cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                        state=state, keys=None, heads=cfg.num_kv_heads)
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(phi, cfg, up)
    else:
        up = prefill_upload(f["chunk"], per_slot, sampling=False,
                            chunk=program == "prefill_chunk_step")
        step = prefill_program(phi, cfg, up)
    small = (sds((slots,), jnp.int32), up.spec(sharding=chip))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, *small).compile()
    text = compiled.as_text()
    chain, _ = compiled.out_info
    assert chain.shape == (slots,) and chain.dtype == jnp.int32
    elems = {name: int(np.prod(s)) for name, _, s, _ in
             phi.state_arrays(cfg, slots, f["page"], BF16)}
    pool_elems = int(np.prod(pool.shape))
    updates = ("scatter", "dynamic-update-slice")
    # nothing of a state stack's size but the stacks' in-place updates
    big = pool_sized_ops(text, min(elems["conv"], elems["ssm"]), updates)
    scores = [op for op in big if op[0] == "fusion" and
              f",{f['chunk']},{per_slot * f['page']}]" in op[2]]
    rest = [op for op in big if op not in scores]
    assert rest == [], rest
    gathered = f"[{slots * per_slot},{f['page']},{cfg.kv_width}]"
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode_step":
        assert scores == [] and gathered not in text
        assert f"f32[{slots},4,{per_slot * f['page']}]" not in text
        assert len(calls) == 2 and all(
            f"f32[{slots},4,{cfg.kv_width}]" in ln
            and "shared_kv_attn" in ln for ln in calls)
    else:
        assert calls == []
    mem = compiled.memory_analysis()
    donated = 2 * (2 * pool_elems + 2 * cfg.n_front * elems["win_k.0"]
                   + elems["conv"]) + 4 * elems["ssm"]
    assert mem.alias_size_in_bytes >= donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13e9
    assert mem.temp_size_in_bytes < 0.2e9


# Granite-4.0-H-Small as benchmarks/configs/granite-4.0-h-small.json serves
# it: one chip's share (10 layers, 36 of 72 experts, half the vocabulary)
GRANITE = dict(slots=64, page=16, per_slot=256, pages=16385, chunk=512)


def _granite_config():
    from paddle_tpu.models import granitemoehybrid as gm
    return gm.GraniteMoeHybridConfig(
        vocab_size=50176, layer_types=gm.PERIOD, experts_held=(0, 36))


@pytest.mark.parametrize("kernel", ["paged_attention", "ssm2_update"])
def test_granite_decode_kernel_compiles_for_v5e(chip, kernel):
    """The two Pallas kernels of Granite-4.0-H's decode step, each alone:
    paged attention at 32 query heads of 128 over a pool of 8 (rows of 1,024
    lanes), scores times 1/128; and the SSM update over the stored stack of
    9 layers x 64 slots x 128 x 8,192 float32 at a traced layer, aliased. (A
    grouped prefill has the xla arm alone.)"""
    g = GRANITE

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    if kernel == "paged_attention":
        from paddle_tpu.kernels.pallas.paged_attention import paged_attention
        pool = spec((1, g["pages"], g["page"], 8 * 128), BF16)
        _compiles_to_a_kernel(
            lambda q, k, v, t, p: paged_attention(
                q, k, v, t, p, layer=0, interpret=False, scale=1 / 128),
            spec((g["slots"], 32, 128), BF16), pool, pool,
            spec((g["slots"], g["per_slot"])), spec((g["slots"],)))
    else:
        from paddle_tpu.kernels.ssm2 import ssm2_update
        b, h, p, n = g["slots"], 128, 64, 128
        f32 = jnp.float32
        _compiles_to_a_kernel(
            lambda s, dt, x, bm, cm, a, d, act, lyr: ssm2_update(
                s, dt, x, bm, cm, a, d, act, layer=lyr, impl="pallas",
                interpret=False),
            spec((9, b, n, h * p), f32), spec((b, h), f32),
            spec((b, h, p), f32), spec((b, n), f32), spec((b, n), f32),
            spec((h,), f32), spec((h,), f32), spec((b,), jnp.bool_),
            spec(()))


@pytest.mark.parametrize("program", PROGRAMS)
def test_granite_step_program_copies_no_state_or_expert_on_v5e(
        chip, program, monkeypatch):
    """Granite-4.0-H-Small's decode step, one prefill chunk of 512 and a
    one-shot prefill of that length, whole, at the published widths with
    the chip's share of the experts, compiled for the described chip with
    pool and state donated and the routing counts behind the token chain,
    with the arms a TPU run takes (the code that picks them asks JAX for its
    backend, which here is the CPU: the test steers it). The optimized HLO
    holds no copy, slice, transpose or fusion of the size of the SSM stack
    (9 x 64 x 128 x 8,192 float32, 2.4 GB: stored with heads and head width
    as two axes it was relaid whole in and out of the prefill programs), of
    the page pool or of one layer's held experts (36 x 768 x 4,096 and up:
    the ragged product copied them out of their stack) but the stacks'
    in-place updates; everything donated is aliased; the decode step updates
    the SSM stack inside a kernel (the plain arm wrote a layer's new slab,
    268 MB, beside the stack first); and the program fits the chip beside
    its 13 GB of arguments."""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import granitemoehybrid as gm
    cfg = _granite_config()
    g = GRANITE
    slots, per_slot = g["slots"], g["per_slot"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in gm.leaf_shapes(cfg).items()}
    pool = sds((cfg.n_attention, g["pages"], g["page"], cfg.kv_width), BF16)
    specs = gm.state_arrays(cfg, slots, g["page"], BF16)
    cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                        state=tuple(sds(s, d) for _, _, s, d in specs),
                        keys=None, heads=cfg.num_kv_heads)
    n = gm.step_counts(cfg)
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(gm, cfg, up, n)
    else:
        up = prefill_upload(g["chunk"], per_slot, sampling=False,
                            chunk=program == "prefill_chunk_step")
        step = prefill_program(gm, cfg, up, n)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots + n,), jnp.int32),
        up.spec(sharding=chip)).compile()
    chain, _ = compiled.out_info
    assert chain.shape == (slots + n,) and chain.dtype == jnp.int32
    elems = {name: int(np.prod(s)) for name, _, s, _ in specs}
    experts = cfg.n_held * cfg.hidden_size * cfg.intermediate_size
    text = compiled.as_text()
    big = pool_sized_ops(text, experts, ("scatter", "dynamic-update-slice"))
    assert big == [], big
    # decode: paged attention and the SSM update (one a run of Mamba
    # layers) are kernels; a prefill takes the plain attention arm
    kernels = text.count("custom_call_target=\"tpu_custom_call\"")
    assert kernels == (1 + len([r for r in cfg.runs() if r[0] == "mamba"])
                       if program == "decode_step" else 0)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (2 * int(np.prod(pool.shape))
                                           + elems["conv"]) + 4 * elems["ssm"]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.6e9
    assert mem.temp_size_in_bytes < (0.05e9 if program == "decode_step"
                                     else 0.4e9)
    # the op families by which the cell's kernel shares find these kernels
    # in a device trace (opcode and result shape, the sizes filled in from
    # the committed configuration) are in the program that makes them
    import json
    import re
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench)
    from harness import granite_bytes, spec as harness_spec, trace
    with open(os.path.join(bench, "configs",
                           "granite-4.0-h-small.json")) as f:
        shapes = granite_bytes.trace_shapes(json.load(f))
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    made_by = {"decode_step": ("moe_experts", "ssm2_update"),
               "prefill_chunk_step": ("ssm2_scan",), "prefill_step": ()}
    for kernel in made_by[program]:
        metric = harness_spec.layer_metric(f"{kernel}_roofline_share")
        for pattern in metric["patterns"]:
            want = pattern.format(**shapes)
            assert [f for f in families if re.search(want, f)], want


def _experts_kernels(text):
    """The Mosaic calls of an optimized HLO whose op name holds the scope
    ``moe_experts``: the routed experts' two products a sparse layer where
    the ``pallas`` arm of `kernels/moe.py` was taken."""
    from harness import trace
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and trace._scope((trace._OP_NAME.search(ln) or [None, ""])[1],
                             frozenset(("moe_experts",)))]


def _under_scope(text, scope):
    """``[(family, largest float32 result in elements, line)]`` of the
    materialised instructions of an optimized HLO whose name stack holds
    ``scope`` innermost among the cell's scopes: what the benchmark's
    reader adds up as that kernel's device time (`harness/trace.py`).
    Instructions inside a fusion's body materialise nothing."""
    import re
    from harness import trace
    wanted = frozenset(("mla", "select", "indexer", "window_mla", "moe"))
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", text))
    found, comp = [], None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            comp = head.group(1)
        m = trace._OP_NAME.search(ln)
        if not m or " = " not in ln or comp in fused \
                or trace._scope(m.group(1), wanted) != scope:
            continue
        fam = trace.family(ln.strip().removeprefix("ROOT "))
        f32 = [int(np.prod([int(d) for d in dims.split(",") if d]))
               for dims in re.findall(r"f32\[([\d,]*)\]",
                                      fam.split(" ", 1)[1])]
        found.append((fam, max(f32, default=0), ln.strip()))
    return found


# the chunk's latent attention alone (kernels/pallas/latent_prefill.py) at
# the two cells' shapes: dots3-note's 128 heads under the selection's mask
# and under the causal one, GigaChat's 64 heads under the causal one
@pytest.mark.parametrize("call", ["dots3-selected", "dots3-causal",
                                  "giga-causal"])
def test_latent_prefill_kernel_compiles_for_v5e(chip, call):
    from paddle_tpu.kernels.pallas import latent_prefill as kernel
    heads, keys = (64, 3584) if call == "giga-causal" else (128, 33792)
    t, dn, rope, dv, rank, width = 512, 128, 64, 128, 512, 640
    plan = kernel.plan(t, heads, dn, rope, dv, rank, width, PAGE)
    assert plan is not None and heads % plan.heads == 0
    keys += -keys % plan.block

    def spec(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    args = [spec((t, heads, dn)), spec((t, heads, rope)),
            spec((rank, heads * (dn + dv))), spec((keys, width)),
            spec((t,), jnp.int32)]
    if call == "dots3-selected":
        args.append(spec((t, keys), jnp.int8))
    compiled = jax.jit(lambda *a: kernel.latent_prefill(
        *a, plan=plan, rank=rank, rope=rope, dv=dv, scale=192 ** -0.5,
        interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing of the size of a group's scores beside the kernel: queries,
    # rows and mask in, the output out
    assert compiled.memory_analysis().temp_size_in_bytes < 40e6


# a decode step's paged absorbed walk alone
# (kernels/pallas/latent_decode.py) at the two cells' shapes: Kimi's 32
# slots over rows of 1,632 pages in a pool of five layers, GigaChat's 128
# slots over rows of 224 in a pool of one
@pytest.mark.parametrize("call", ["kimi", "giga"])
def test_latent_decode_kernel_compiles_for_v5e(chip, call):
    from paddle_tpu.kernels.pallas import latent_decode as kernel
    slots, row, pages, layers = (32, 1632, 40961, 5) if call == "kimi" \
        else (128, 224, 28673, 1)
    heads, rank, width = 64, 512, 640
    plan = kernel.plan(heads, width, rank, PAGE, 2, slots, row)
    assert plan is not None and plan.block % plan.run == 0

    def spec(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    compiled = jax.jit(lambda *a: kernel.latent_decode_paged(
        *a, plan=plan, rank=rank, scale=192 ** -0.5,
        interpret=False)).lower(
        spec((slots, heads, width)), spec((layers, pages, PAGE, width)),
        spec((), jnp.int32), spec((slots, row), jnp.int32),
        spec((slots,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read where it lies: beside the kernel only what is found
    # in the page table (the clipped table, its runs, the pages a slot has)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


# dots3-note-prev as benchmarks/configs/dots3-note-prev.json serves it: one
# chip's share of eight (layers 0-4, 32 of 256 experts, 19,008 rows)
@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_dots3_step_program_relays_no_pool_and_keeps_its_families_on_v5e(
        chip, program, monkeypatch):
    """dots3-note's decode step and one prefill chunk of 512, whole, at the
    published widths with the chip's share, compiled for the described
    chip with the pools and rings donated and the counts behind the token
    chain. The latent pool's rows are 640 wide: the optimized HLO holds no
    copy, slice, transpose or fusion of the size of a pool but the pools'
    in-place scatters (rows of 576 made the chip store the pool with its
    pages as the minor dimension, and both programs copied 0.93 GB in and
    out: PERF.md, PR 40); everything donated is aliased; the program fits
    the chip beside its 10.8 GB of arguments; and the op families by which
    the cell's kernel shares find these kernels in a device trace are in
    the program that makes them. With the arms a TPU run takes (the code
    that picks them asks JAX for its backend, which here is the CPU: the
    test steers it), a chunk's latent attention is found as the benchmark's
    reader finds it since PR 44, by the program's scope: Pallas custom calls
    whose ``op_name`` holds ``mla`` (one a full layer and mask kind), and no
    float32 ``[heads, chunk, keys]`` scores left under that scope."""
    import json
    import re
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import dots3note as dm
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench)
    from harness import dots3_bytes, spec as harness_spec, trace
    with open(os.path.join(bench, "configs", "dots3-note-prev.json")) as f:
        cfgj = json.load(f)
    cfg = harness_spec._module("runners", "serve_dots3").model_config(cfgj)
    sv = cfgj["serve"]
    slots, page, pages = sv["max_slots"], sv["page_size"], sv["num_pages"]
    per_slot = sv["max_seq_len"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in dm.leaf_shapes(cfg).items()}
    lat = sds((2, pages, page, cfg.latent_width), BF16)
    kix = sds((2, pages, page, cfg.index_head_dim), BF16)
    specs = dm.state_arrays(cfg, slots, page, BF16)
    cache = DeviceCache(k=lat, v=kix, k_scale=None, v_scale=None,
                        state=tuple(sds(s, d) for _, _, s, d in specs),
                        keys=None, heads=1)
    n = dm.step_counts(cfg)
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(dm, cfg, up, n)
    else:
        up = prefill_upload(sv["prefill_chunk_tokens"], per_slot,
                            sampling=False, chunk=True)
        step = prefill_program(dm, cfg, up, n)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots + n,), jnp.int32),
        up.spec(sharding=chip)).compile()
    text = compiled.as_text()
    big = pool_sized_ops(text, int(np.prod(kix.shape)))
    assert big == [], big
    mem = compiled.memory_analysis()
    donated = 2 * (int(np.prod(lat.shape)) + int(np.prod(kix.shape))
                   + sum(int(np.prod(s)) for _, _, s, _ in specs))
    assert mem.alias_size_in_bytes >= donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    shapes = dots3_bytes.trace_shapes(cfgj)
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    for name in ("latent_attn", "index_select", "window_latent_attn",
                 "dots3_experts"):
        metric = harness_spec.layer_metric(f"{name}_roofline_share")
        found = [bool([f for f in families
                       if re.search(p.format(**shapes), f)])
                 for p in metric["patterns"]]
        print(program, name, found)
        if (program, name) == ("prefill_chunk_step", "latent_attn"):
            # the chunk's walk is a kernel now: none of its four families
            # is left, and the scope the metric asks for finds it
            assert not any(found), (metric["patterns"], found)
            assert metric["scopes"] == ["mla"]
            continue
        if name == "dots3_experts":
            # both call sites take the repo's own grouped product (PR 51):
            # none of the three families is left (the dense arm's float32
            # [512, 32, 3072], the ragged products' [192, .]); the scope
            # the metric asks for is on two Mosaic calls a sparse layer
            assert not any(found), (metric["patterns"], found)
            assert metric["scopes"] == ["moe_experts"]
            assert "ragged-dot" not in text
            assert len(_experts_kernels(text)) \
                == 2 * (cfg.n_layers - cfg.first_dense)
            continue
        # each kernel's patterns name a chunk's ops and a decode step's:
        # some of them are in each program, all of them in the two
        assert any(found), (name, metric["patterns"], found)
    under = _under_scope(text, "mla")
    calls = [ln for f, _, ln in under if f.startswith("custom-call")
             and "tpu_custom_call" in ln]
    # a full layer's chunk attends under the causal mask or the
    # selection's: two kernels a layer, one of them runs
    assert len(calls) == (2 * len(cfg.full_layers)
                          if program == "prefill_chunk_step" else 0)
    scores = shapes["head_block"] * shapes["chunk"] * shapes["key_block"]
    big = [(f, n) for f, n, _ in under if n >= scores]
    assert big == [], big


# GigaChat3.5-432B-A28B as benchmarks/configs/gigachat3.5-432b-a28b.json
# serves it: one chip's share of sixteen (published layers 2-6, 16 of 256
# experts, 16,032 rows)
def test_deltanet_update_kernel_compiles_for_v5e(chip):
    """The delta rule's decode update alone at the published widths: 64
    value heads of 128 x 128, the state stack 4 x 128 x 64 x 128 x 128
    float32 (2.15 GB) at a traced layer, aliased; two transposes of a
    broadcast key and two sublane sums a head inside."""
    from paddle_tpu.kernels import deltanet
    f32 = jnp.float32

    def spec(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    b, h, d = 128, 64, 128
    compiled = jax.jit(
        lambda s, g, beta, q, k, v, act, lyr: deltanet.deltanet_update(
            s, g, beta, q, k, v, act, layer=lyr, impl="pallas",
            interpret=False), donate_argnums=(0,)).lower(
        spec(deltanet.state_shape(4, b, h, d, d)), spec((b, h)),
        spec((b, h)), spec((b, h, d)), spec((b, h, d)), spec((b, h, d)),
        spec((b,), jnp.bool_), spec((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 4 * b * h * d * d
    assert mem.temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_giga_step_program_fits_and_keeps_its_families_on_v5e(
        chip, program, monkeypatch):
    """GigaChat3.5's decode step and one prefill chunk of 512, whole, at
    the published widths with the chip's share, compiled for the described
    chip with the arms a TPU run takes, the latent pool and the recurrent
    state donated and the counts behind the token chain: everything
    donated is aliased (no copy of the 2.15 GB state stack), the decode
    step updates the state inside a kernel a linear layer, the program fits
    the chip beside its 12.3 GB of arguments, and the op families by which
    the cell's kernel shares find these kernels in a device trace are in
    the program that makes them."""
    import json
    import re
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import gigachat35 as gm
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench)
    from harness import giga_bytes, spec as harness_spec, trace
    with open(os.path.join(bench, "configs",
                           "gigachat3.5-432b-a28b.json")) as f:
        cfgj = json.load(f)
    cfg = harness_spec._module("runners", "serve_giga").model_config(cfgj)
    assert sum(int(np.prod(s)) for s in gm.leaf_shapes(cfg).values()) \
        == cfgj["assumed"]["parameters"] == 4731722752
    sv = cfgj["serve"]
    slots, page, pages = sv["max_slots"], sv["page_size"], sv["num_pages"]
    per_slot = sv["max_seq_len"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in gm.leaf_shapes(cfg).items()}
    lat = sds((1, pages, page, cfg.latent_width), BF16)
    specs = gm.state_arrays(cfg, slots, page, BF16)
    cache = DeviceCache(k=lat, v=sds((0, 1, page, 0), BF16), k_scale=None,
                        v_scale=None,
                        state=tuple(sds(s, d) for _, _, s, d in specs),
                        keys=None, heads=1)
    n = gm.step_counts(cfg)
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(gm, cfg, up, n)
    else:
        up = prefill_upload(sv["prefill_chunk_tokens"], per_slot,
                            sampling=False, chunk=True)
        step = prefill_program(gm, cfg, up, n)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots + n,), jnp.int32),
        up.spec(sharding=chip)).compile()
    text = compiled.as_text()
    kernels = text.count("custom_call_target=\"tpu_custom_call\"")
    # the one full layer attends inside a kernel in both programs: a decode
    # step's paged absorbed walk (kernels/pallas/latent_decode.py) beside
    # the linear layers' updates, a chunk's per-head walk (latent_prefill)
    # and a chunk's routed experts in two kernels a sparse layer (kernels/
    # pallas/grouped_experts.py; a decode step of 128 tokens hits every
    # held expert and keeps the dense arm)
    sparse = len(cfg.layer_types) - cfg.first_dense
    assert kernels == len(cfg.full_layers) + (
        len(cfg.linear_layers) if program == "decode_step" else 2 * sparse)
    assert len(_experts_kernels(text)) \
        == (0 if program == "decode_step" else 2 * sparse)
    assert "ragged-dot" not in text
    under = _under_scope(text, "mla")
    calls = [ln for f, _, ln in under if f.startswith("custom-call")
             and "tpu_custom_call" in ln]
    assert len(calls) == len(cfg.full_layers)
    from paddle_tpu.kernels import mla
    if program == "decode_step":
        # nothing of the XLA walk is left under that scope: no `while`, no
        # gather of every slot's block of pages into a copy, no float32
        # [slots, heads, block] scores
        assert not [f for f, _, _ in under if f.startswith("while")]
        gathered = slots * mla.DECODE_KEY_BLOCK // page
        assert f"bf16[{gathered},{page},{cfg.latent_width}]" not in text
        scores = slots * cfg.num_heads * mla.DECODE_KEY_BLOCK
    else:
        # no float32 [heads, chunk, keys] scores left under that scope
        scores = mla.HEAD_BLOCK * sv["prefill_chunk_tokens"] * mla.KEY_BLOCK
    big = [(f, n) for f, n, _ in under if n >= scores]
    assert big == [], big
    # no rematerialized instruction reads a donated array: short of memory
    # at 128 slots the compiler rematerializes, and a clone of an in-place
    # update that reads what it replaces ran TWICE on the chip (the
    # convolution's state as a stack: kernels/deltanet.py)
    clones = [ln.strip()[:160] for ln in text.splitlines()
              if re.match(r"\s*%[\w.\-]*remat[\w.\-]* = ", ln)
              and "%cache_" in ln]
    assert clones == [], clones
    mem = compiled.memory_analysis()
    donated = 2 * int(np.prod(lat.shape)) \
        + 4 * sum(int(np.prod(s)) for _, _, s, _ in specs)
    assert mem.alias_size_in_bytes >= donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.0e9
    assert mem.temp_size_in_bytes < 0.4e9
    shapes = giga_bytes.trace_shapes(cfgj)
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    made_by = {"decode_step": ("deltanet_update", "giga_experts"),
               "prefill_chunk_step": ("deltanet_chunk", "giga_experts")}
    # both programs' walks are the kernels counted above: none of the XLA
    # walks' families is left, and the scope the metric asks for finds them
    metric = harness_spec.layer_metric("latent_paged_attn_roofline_share")
    assert metric["scopes"] == ["mla"]
    assert not [f for f in families for p in metric["patterns"]
                if re.search(p.format(**shapes), f)]
    for name in made_by[program]:
        metric = harness_spec.layer_metric(f"{name}_roofline_share")
        found = [bool([f for f in families
                       if re.search(p.format(**shapes), f)])
                 for p in metric["patterns"]]
        print(program, name, found)
        if (program, name) == ("prefill_chunk_step", "giga_experts"):
            # the chunk's family (the dense arm's [512, 16, 4096]) went
            # with the arm: the scope the metric asks for is on the Mosaic
            # calls counted above
            assert not any(found), (metric["patterns"], found)
            assert metric["scopes"] == ["moe_experts"]
            continue
        # a kernel's patterns name both arms' ops, a chunk's and a decode
        # step's: some of them are in each program
        assert any(found), (name, metric["patterns"], found)
    other = "deltanet_chunk" if program == "decode_step" \
        else "deltanet_update"
    # the decode update's own patterns match nothing a chunk makes (the
    # chunk's closing state is the chunk metric's)
    if other == "deltanet_update":
        metric = harness_spec.layer_metric(f"{other}_roofline_share")
        for p in metric["patterns"]:
            assert not [f for f in families
                        if re.search(p.format(**shapes), f)], p


# Brumby-14B-Base as benchmarks/configs/brumby-14b-base.json serves it: one
# pipeline stage of five (8 layers, the embedding and the head)
BRUMBY = dict(slots=16, page=16, chunk=512, layers=8)


def test_retention_update_kernel_compiles_for_v5e(chip):
    """The retention decode update alone at the published widths: 40 query
    heads over 8 key-value heads of 128, the state stack 8 x 16 x 8 x 65 x
    128 x 128 float32 (4.36 GB) at a traced layer, aliased with the
    normaliser; lane rotations, a transpose and a lane sum inside."""
    from paddle_tpu.kernels import retention
    b = BRUMBY
    f32 = jnp.float32

    def spec(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    s, z = retention.state_shapes(b["layers"], b["slots"], 8, 128)
    _compiles_to_a_kernel(
        lambda s, z, lg, q, k, v, act, lyr: retention.retention_update(
            s, z, lg, q, k, v, act, layer=lyr, impl="pallas",
            interpret=False),
        spec(s), spec(z), spec((b["slots"], 8)), spec((b["slots"], 40, 128)),
        spec((b["slots"], 8, 128)), spec((b["slots"], 8, 128)),
        spec((b["slots"],), jnp.bool_), spec((), jnp.int32))


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_brumby_step_program_relays_no_state_and_copies_no_weight_on_v5e(
        chip, program, monkeypatch):
    """Brumby-14B-Base's decode step and one prefill chunk of 512, whole, at
    the published widths and the benchmark's 8 layers, with NO page pool
    (empty pools, uploads with a page table of no width), compiled for the
    described chip with the state donated and the arms a TPU run takes. The
    eight layers are one loop over stacked leaves read at a traced index:
    the optimized HLO holds no copy, slice, transpose or fusion of the size
    of one layer's smallest large matrix (5,120 x 5,120) but the stacks'
    in-place updates, so no stacked weight is copied out and neither state
    stack (2.18 G and 17 M elements) is relaid; everything donated is
    aliased; the decode step updates the state inside ONE kernel, the chunk
    in plain XLA; the program fits the chip beside its 12.8 GB of
    arguments; and the op families by which the cell's two kernel shares
    find their time in a device trace are in the program that makes them,
    one family a pattern."""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import brumby as bm
    b = BRUMBY
    cfg = bm.BrumbyConfig(num_layers=b["layers"])
    slots = b["slots"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in bm.leaf_shapes(cfg).items()}
    pool = sds((0, 1, b["page"], cfg.kv_width), BF16)
    specs = bm.state_arrays(cfg, slots, b["page"], BF16)
    cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                        state=tuple(sds(s, d) for _, _, s, d in specs),
                        keys=None, heads=cfg.num_kv_heads)
    if program == "decode_step":
        up = step_upload(slots, 0, sampling=False)
        assert up.shape == (slots, 3)
        step = decode_program(bm, cfg, up)
    else:
        up = prefill_upload(b["chunk"], 0, sampling=False, chunk=True)
        step = prefill_program(bm, cfg, up)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots,), jnp.int32),
        up.spec(sharding=chip)).compile()
    text = compiled.as_text()
    big = pool_sized_ops(text, cfg.hidden_size * cfg.q_width,
                         ("scatter", "dynamic-update-slice"))
    assert big == [], big
    kernels = text.count("custom_call_target=\"tpu_custom_call\"")
    assert kernels == (1 if program == "decode_step" else 0)
    elems = {name: int(np.prod(s)) for name, _, s, _ in specs}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * sum(elems.values())
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.0e9
    assert mem.temp_size_in_bytes < (1e6 if program == "decode_step"
                                     else 0.2e9)
    import json
    import re
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench)
    from harness import brumby_bytes, spec as harness_spec, trace
    with open(os.path.join(bench, "configs", "brumby-14b-base.json")) as f:
        shapes = brumby_bytes.trace_shapes(json.load(f))
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    kernel = {"decode_step": "retention_update",
              "prefill_chunk_step": "retention_chunk"}[program]
    metric = harness_spec.layer_metric(f"{kernel}_roofline_share")
    for pattern in metric["patterns"]:
        want = pattern.format(**shapes)
        assert len([f for f in families if re.search(want, f)]) == 1, want
    other = {"decode_step": "retention_chunk",
             "prefill_chunk_step": "retention_update"}[program]
    for pattern in harness_spec.layer_metric(
            f"{other}_roofline_share")["patterns"]:
        want = pattern.format(**shapes)
        assert not [f for f in families if re.search(want, f)], want


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("width", WIDTHS)
def test_fused_layernorm_compiles_for_v5e(chip, width, bwd):
    from paddle_tpu.kernels.pallas.fused_layernorm import fused_layer_norm
    hid = WIDTHS[width][2]
    x = jax.ShapeDtypeStruct((16384, hid), BF16, sharding=chip)
    g = jax.ShapeDtypeStruct((hid,), jnp.float32, sharding=chip)

    def fn(x_, g_, b_):
        return fused_layer_norm(x_, g_, b_, interpret=False)

    _compiles_to_a_kernel(
        jax.grad(_sq(fn), argnums=(0, 1, 2)) if bwd else fn, x, g, g)


# ------------------------------------------------ chip_smoke, rehearsed


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke
    return chip_smoke


@pytest.fixture()
def toy(smoke, monkeypatch):
    """A toy size, and the two device reads the CPU cannot answer steered
    from here: which device the run is on, and how many bytes are free."""
    monkeypatch.setattr(smoke, "require_tpu", lambda: jax.devices()[0])
    monkeypatch.setattr(smoke, "free_bytes", lambda dev: 16 << 20)
    return dataclasses.replace(
        smoke.FULL, vocab=256, hidden=32, layers=2, heads=2, mlp=64,
        seq=32, batch=4, train_steps=3, prompt_lens=(5, 21), repeat=1,
        new_tokens=4, max_slots=2, kernel_widths=((2, 16),), kernel_batch=2,
        page_size=4, pages_per_slot=4, chunk=8, ln_rows=32,
        four_chip_microbatches=2)


def test_chip_smoke_refuses_to_run_without_a_tpu(smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.require_tpu()
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""                # no result of any kind
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_train_and_serve_phases_at_toy_size(smoke, toy):
    dev = smoke.require_tpu()
    rec, fails, model = smoke.phase_train(toy, 0, dev)
    assert fails == [], fails
    assert rec["scan"]["compile_count"] == 1
    assert rec["to_static"]["losses"][-1] < rec["to_static"]["losses"][0]
    assert rec["step1_rel_gap"] <= smoke.BF16_STEP1_RTOL
    rec, fails = smoke.phase_serve(toy, 0, dev, model)
    assert fails == [], fails
    assert rec["generated_tokens"] == 3 * toy.new_tokens
    assert rec["prefix_hits"] == 1
    assert rec["compiles_in_window"] == {"jit.compile_count": 0,
                                         "engine.compile_count": 0}
    assert rec["num_pages"] == (16 << 20) * toy.kv_fraction // (
        2 * toy.layers * toy.page_size * toy.hidden * 2)


def test_chip_smoke_kernels_phase_at_toy_size(smoke, toy):
    rec, fails = smoke.phase_kernels(toy, 0, smoke.require_tpu())
    assert fails == [], fails
    names = {row["kernel"].split()[0] for row in rec["kernels"]}
    assert names == {"flash_fwd", "flash_bwd", "paged", "paged_int8",
                     "prefill", "prefill_int8", "layernorm_fwd",
                     "layernorm_bwd", "fused_ce_fwd"}


def test_chip_smoke_four_chip_phase_on_virtual_devices(smoke, toy):
    rec, fails = smoke.phase_four_chips(toy, 0, jax.devices()[:4])
    assert fails == [], fails
    assert rec["mp_sharded_params"] > 0 and rec["sharded_leaves"] > 0
    assert max(rec["rel_gap_per_step"]) <= smoke.F32_MESH_RTOL


def test_chip_smoke_fails_on_a_wrong_answer(smoke, toy, monkeypatch):
    """The checks are live: a loss that does not fall is a failure."""
    fails = []
    smoke._check_losses("x", [6.2, 6.3], toy, fails)
    smoke._check_losses("y", [1.0, 0.9], toy, fails)
    smoke._check_losses("z", [6.2, float("nan")], toy, fails)
    smoke._close("k", np.ones(3), np.ones(3) * 1.5, 2e-2, fails)
    assert len(fails) == 4, fails


# ------------------------------------------- compile cache, one process


def test_compile_cache_honours_the_environment_variable(monkeypatch):
    from paddle_tpu.framework import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/outside")
    assert compile_cache.enable() == "/somewhere/outside"
    # jax reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    from paddle_tpu.framework import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable()
        assert got == os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable() == got        # no pid, clock, tempfile
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_importing_the_package_initialises_no_backend():
    """A launcher that imports paddle_tpu must not take the chip from the
    children it starts: importing sets config, nothing more."""
    code = ("import paddle_tpu, paddle_tpu.distributed.launch.main, "
            "paddle_tpu.train.elastic, paddle_tpu.inference.serve, "
            "paddle_tpu.serving.router\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_launcher_refuses_several_processes_per_host_off_cpu(monkeypatch,
                                                             capsys):
    from paddle_tpu.distributed.launch.main import launch
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as ei:
        launch(["--nproc_per_node", "2", "train.py"])
    assert ei.value.code == 2
    assert "one process drives all chips" in capsys.readouterr().err
