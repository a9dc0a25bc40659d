"""What can be asked of the chip without the chip, for the ``solar_open2``
family (tests/test_tpu_compile.py says what such a compile sees and does
not): Solar-Open2-250B as benchmarks/configs/solar-open2-250b.json serves
it, one chip's share of 8 (4 layers, 40 of 320 experts, 24,576 rows), its
decode step over 48 slots of 17,920 positions and one prefill chunk of 512,
whole, compiled for a described v5e with the arms a TPU run takes; and the
per-channel update kernel alone."""
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

BF16 = jnp.bfloat16
CELL = "solaropen2-serve-docqa"


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip; compile cache off
    around these compiles (tests/test_tpu_compile.py::chip)."""
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


def test_the_per_channel_update_kernel_compiles_for_v5e(chip):
    """`deltanet_update` with a log decay a key channel, at the cell's
    sizes (48 slots, 64 heads of 128 x 128, a stack of three layers):
    Mosaic takes the kernel, and the stack is aliased to the result (the
    state crosses HBM once each way)."""
    from paddle_tpu.kernels import deltanet

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    b, h, d = 48, 64, 128
    fn = jax.jit(lambda s, g, beta, q, k, v, act: deltanet.deltanet_update(
        s, g, beta, q, k, v, act, layer=1, impl="pallas", interpret=False),
        donate_argnums=(0,))
    compiled = fn.lower(sds((3, b, h, d, d)), sds((b, h, d)), sds((b, h)),
                        sds((b, h, d)), sds((b, h, d)), sds((b, h, d)),
                        sds((b,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * b * h * d * d * 4
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_solar_step_program_fits_and_names_its_kernels_on_v5e(
        chip, program, monkeypatch):
    """The twin pools and the state donated and the counts behind the token
    chain: the pools and the matrix state are aliased (no copy of their
    4.2 GB), the program fits the chip beside its 10.8 GB of arguments, and
    EVERY scope the cell's metric files ask for is on the ops of the
    program that makes it: a decode step's three per-channel updates are
    one Mosaic call each under ``kda_update`` and its grouped-query walk one
    under ``gqa_decode``; a chunk's per-channel form is under ``kda_chunk``
    (XLA: no Mosaic call) and its walk under ``gqa_chunk``."""
    from paddle_tpu.kernels import registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import solar_open2 as sm
    from paddle_tpu.observability import metrics
    from harness import spec as harness_spec, trace
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "solar-open2-250b.json")) as f:
        cfgj = json.load(f)
    cfg = harness_spec._module("runners", "serve_solar").model_config(cfgj)
    assert sum(int(np.prod(s)) for s in sm.leaf_shapes(cfg).values()) \
        == cfgj["parameters"] == 3308377920
    sv = cfgj["serve"]
    slots, page, pages = sv["max_slots"], sv["page_size"], sv["num_pages"]
    per_slot = sv["max_seq_len"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in sm.leaf_shapes(cfg).items()}
    pool = sds((1, pages, page, cfg.kv_width), BF16)
    state = tuple(sds(s, d) for _, _, s, d in
                  sm.state_arrays(cfg, slots, page, BF16))
    assert sum(int(np.prod(s.shape)) * 4 for s in state) == slots * 13467648
    cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                        state=state, keys=None, heads=cfg.num_kv_heads)
    n = sm.step_counts(cfg)
    ops = ("kda_update.pallas", "kda_update.xla", "kda_chunk.xla",
           "paged_attention.pallas", "prefill_attention.xla",
           "deltanet_update.pallas", "deltanet_chunk.xla",
           "moe_experts.pallas", "moe_experts.grouped", "moe_experts.dense")
    count = {o: metrics.counter(f"kernel.dispatch.{o}") for o in ops}
    built = {o: c.value for o, c in count.items()}
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(sm, cfg, up, n)
    else:
        up = prefill_upload(sv["prefill_chunk_tokens"], per_slot,
                            sampling=False, chunk=True)
        step = prefill_program(sm, cfg, up, n)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots + n,), jnp.int32),
        up.spec(sharding=chip)).compile()
    text = compiled.as_text()
    grew = {o: c.value - built[o] for o, c in count.items()}
    # the per-channel ops are counted apart from the scalar rule's, and a
    # linear layer each
    want = {o: 0 for o in ops}
    want.update({"kda_update.pallas": 3, "paged_attention.pallas": 1}
                if program == "decode_step" else
                {"kda_chunk.xla": 3, "prefill_attention.xla": 1})
    # every layer routes, and in both programs through the repo's own
    # grouped product (a step of 48 tokens hits 70% of the 40 held experts,
    # a chunk of 512 would bind ``dense`` by its FLOPs)
    want["moe_experts.pallas"] = cfg.num_layers
    assert grew == want
    # every scope the cell's metric files ask for, on the ops of the
    # program that makes it (`harness/trace.py`: the innermost wanted scope
    # of a name stack)
    cell = harness_spec.cell(CELL)
    asked = sorted({s for m in cell["per_layer"] for s in
                    harness_spec.layer_metric(m["name"]).get("scopes", ())})
    assert asked == ["conv", "gqa_chunk", "gqa_decode", "kda_chunk",
                     "kda_update", "moe_experts"]
    under = {s: [] for s in asked}
    for ln in text.splitlines():
        m = trace._OP_NAME.search(ln)
        if m and " = " in ln:
            scope = trace._scope(m.group(1), frozenset(asked))
            if scope:
                under[scope].append(ln.strip())
    mine, others = (("kda_update", "gqa_decode"), ("kda_chunk", "gqa_chunk"))[
        ::1 if program == "decode_step" else -1]
    assert all(under[s] for s in mine + ("conv", "moe_experts"))
    assert not any(under[s] for s in others)
    mosaic = {s: len([ln for ln in under[s] if "tpu_custom_call" in ln])
              for s in asked}
    # two Mosaic calls a layer under ``moe_experts`` (kernels/pallas/
    # grouped_experts.py), no ragged product left (the chip's compiler
    # strips those of every scope), and no custom call with a result the
    # share's ``unnamed`` patterns would add to the scope a second time
    assert mosaic["moe_experts"] == 2 * cfg.num_layers
    assert "ragged-dot" not in text
    from harness import solar_bytes
    shapes = solar_bytes.trace_shapes(cfgj)
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    for p in harness_spec.layer_metric(
            "solar_experts_roofline_share")["unnamed"]:
        assert not [f for f in families if re.search(p.format(**shapes), f)]
    if program == "decode_step":
        assert (mosaic["kda_update"], mosaic["gqa_decode"]) == (3, 1)
        # the update rewrites the stack where it lies: no copy of a layer's
        # slab [48, 64, 128, 128] is made on the way in or out
        assert f"f32[{slots},64,128,128]" not in text
    else:
        assert mosaic["kda_chunk"] == mosaic["gqa_chunk"] == 0
        # the chunk form's products are per sub-chunk of 64 and all heads
        fams = {trace.family(ln.removeprefix("ROOT "))
                for ln in under["kda_chunk"]}
        assert any(re.search(r"f32\[64,64,64\]", f) for f in fams)
        assert any(re.search(r"f32\[64,4,16,64\]|f32\[64,4,64,128\]", f)
                   for f in fams), sorted(fams)[:40]
    clones = [ln.strip()[:160] for ln in text.splitlines()
              if re.match(r"\s*%[\w.\-]*remat[\w.\-]* = ", ln)
              and "%cache_" in ln]
    assert clones == [], clones
    mem = compiled.memory_analysis()
    # both pools and the matrix state stack go back where they came from
    held = 2 * int(np.prod(pool.shape)) * 2 + 3 * slots * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= held
    assert 10.7e9 < mem.argument_size_in_bytes < 10.9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.3e9
    # a chunk's grouped-query walk goes by blocks of 2,048 keys over this
    # long a row (`pa.LONG_ROW`): the one-shot form's float32 scores [8, 8,
    # 512, 17920] were 2.35 GB of 2.41 GB of temporaries
    assert "f32[1,8,8,512,17920]" not in text
    assert mem.temp_size_in_bytes \
        <= (0.06e9 if program == "decode_step" else 0.45e9)
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
