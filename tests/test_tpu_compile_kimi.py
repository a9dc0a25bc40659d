"""What can be asked of the chip without the chip, for the ``kimi_k2``
family (tests/test_tpu_compile.py says what such a compile sees and does
not): Kimi-K2.7-Code as benchmarks/configs/kimi-k2.7-code.json serves it,
one chip's share of 32 (5 layers, 12 of 384 experts, 20,480 rows), its
decode step over 32 slots of 26,112 positions and one prefill chunk of 512,
whole, compiled for a described v5e with the arms a TPU run takes."""
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
SCOPES = ("mla_decode", "mla_chunk", "moe_experts")


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


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_kimi_step_program_fits_and_names_its_kernels_on_v5e(
        chip, program, monkeypatch):
    """The latent pool donated and the counts behind the token chain: the
    pool is aliased (no copy of its 4.19 GB), the program fits the chip
    beside its 11.2 GB of arguments, a chunk's five layers attend inside
    the latent-prefill kernel with no float32 ``[heads, chunk, keys]``
    scores left, and the scopes by which the cell's kernel shares find
    their kernels in a device trace are on the ops of the program that
    makes them."""
    from paddle_tpu.kernels import mla, registry
    from paddle_tpu.kernels.pallas import _compat
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    monkeypatch.setattr(_compat, "default_interpret", lambda: False)
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import kimi_k2 as km
    from harness import spec as harness_spec, trace
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-k2.7-code.json")) as f:
        cfgj = json.load(f)
    cfg = harness_spec._module("runners", "serve_kimi").model_config(cfgj)
    assert sum(int(np.prod(s)) for s in km.leaf_shapes(cfg).values()) \
        == cfgj["parameters"] == 3496763904
    sv = cfgj["serve"]
    slots, page, pages = sv["max_slots"], sv["page_size"], sv["num_pages"]
    per_slot = sv["max_seq_len"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = {k: sds(s, BF16) for k, s in km.leaf_shapes(cfg).items()}
    lat = sds((cfg.num_layers, pages, page, cfg.latent_width), BF16)
    cache = DeviceCache(k=lat, v=sds((0, 1, page, 0), BF16), k_scale=None,
                        v_scale=None, state=(), keys=None, heads=1)
    n = km.step_counts(cfg)
    from paddle_tpu.observability import metrics
    arms = {a: metrics.counter(f"kernel.dispatch.mla_decode_paged.{a}")
            for a in ("xla", "pallas")}
    block = metrics.counter("kernel.paged_block.mla_decode_paged.64")
    built = [arms["xla"].value, arms["pallas"].value, block.value]
    experts = metrics.counter("kernel.dispatch.moe_experts.pallas")
    built_experts, sparse = experts.value, cfg.num_layers - cfg.first_dense
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(km, cfg, up, n)
    else:
        up = prefill_upload(sv["prefill_chunk_tokens"], per_slot,
                            sampling=False, chunk=True)
        step = prefill_program(km, cfg, up, n)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((slots + n,), jnp.int32),
        up.spec(sharding=chip)).compile()
    text = compiled.as_text()
    # which arm the program was built with, and the block it took: a layer
    # of a decode step each, a chunk none
    walks = cfg.num_layers if program == "decode_step" else 0
    assert [arms["xla"].value, arms["pallas"].value, block.value] \
        == [built[0], built[1] + walks, built[2] + walks]
    # the ops a device trace will show, by the scope the reader finds them
    # under (`harness/trace.py`: the innermost wanted scope of a name stack)
    under = {s: [] for s in SCOPES}
    for ln in text.splitlines():
        m = trace._OP_NAME.search(ln)
        if m and " = " in ln:
            scope = trace._scope(m.group(1), frozenset(SCOPES))
            if scope:
                under[scope].append(ln.strip())
    mine = "mla_decode" if program == "decode_step" else "mla_chunk"
    other = "mla_chunk" if program == "decode_step" else "mla_decode"
    assert under[mine] and under["moe_experts"] and not under[other]
    # the routed experts' two products are the repo's own kernels in both
    # programs (kernels/pallas/grouped_experts.py): two Mosaic calls a
    # sparse layer, their op names carrying the scope; no ragged product is
    # left (the chip's compiler strips those of every scope), and no custom
    # call has a result the share's ``unnamed`` patterns would add to the
    # scope's seconds a second time
    assert experts.value - built_experts == sparse
    assert len([ln for ln in under["moe_experts"]
                if "tpu_custom_call" in ln]) == 2 * sparse
    assert "ragged-dot" not in text
    from harness import kimi_bytes
    shapes = kimi_bytes.trace_shapes(cfgj)
    families = {trace.family(ln.strip().removeprefix("ROOT "))
                for ln in text.splitlines() if " = " in ln}
    for p in harness_spec.layer_metric(
            "kimi_experts_roofline_share")["unnamed"]:
        assert not [f for f in families if re.search(p.format(**shapes), f)]
    kernels = [ln for ln in under[mine] if "tpu_custom_call" in ln]
    # one Mosaic call a latent layer, its op name carrying the scope: the
    # paged absorbed walk (kernels/pallas/latent_decode.py) or the chunk's
    # per-head walk (latent_prefill.py)
    assert len(kernels) == cfg.num_layers
    if program == "decode_step":
        # nothing of the XLA walk is left: no `while` a layer, no gather of
        # every slot's block of pages into a copy, no float32 [slots,
        # heads, block] scores
        assert not [ln for ln in under[mine] if re.search(r" while\(", ln)]
        gathered = slots * mla.DECODE_KEY_BLOCK // page
        assert f"bf16[{gathered},{page},{cfg.latent_width}]" not in text
        scores = slots * cfg.num_heads * mla.DECODE_KEY_BLOCK
    else:
        scores = mla.HEAD_BLOCK * sv["prefill_chunk_tokens"] * mla.KEY_BLOCK
    for ln in under[mine]:
        fam = trace.family(ln.removeprefix("ROOT "))
        big = [d for d in re.findall(r"f32\[([\d,]*)\]",
                                     fam.split(" ", 1)[1])
               if np.prod([int(x) for x in d.split(",") if x]) >= scores]
        assert big == [], ln[:200]
    clones = [ln.strip()[:160] for ln in text.splitlines()
              if re.match(r"\s*%[\w.\-]*remat[\w.\-]* = ", ln)
              and "%cache_" in ln]
    assert clones == [], clones
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * int(np.prod(lat.shape))
    assert 11.1e9 < mem.argument_size_in_bytes < 11.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    # a decode step no larger than under the XLA walk (0.066 GB: PERF.md
    # section 4, PR 47); a chunk holds its 4,096 sorted rows going into and
    # coming out of the experts' kernels (2 x 58.7 MB, where the dense arm
    # held one float32 [512, 12, 4096]: 0.114 GB then)
    assert mem.temp_size_in_bytes \
        <= (0.066e9 if program == "decode_step" else 0.140e9)
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
