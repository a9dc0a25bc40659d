"""What can be asked of the chip without the chip.

- Every authored Pallas kernel, compiled by the TPU's own compiler for a
  described (not attached) ``v5e:2x2`` topology at GPT-2 small and GPT-2
  345M widths, forward and — where it has one — backward. Interpret mode
  cannot see what these see: a block shape that is not (8, 128)-tileable,
  an i64 index from a Python int under global x64, a DMA slice below one
  tile, too much VMEM. A compile that passes is not a chip run.
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


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("width", WIDTHS)
def test_flash_attention_compiles_for_v5e(chip, width, bwd):
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    nh, dh, _ = WIDTHS[width]
    q = jax.ShapeDtypeStruct((8, nh, 1024, dh), BF16, sharding=chip)

    def fn(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, interpret=False)

    _compiles_to_a_kernel(
        jax.grad(_sq(fn), argnums=(0, 1, 2)) if bwd else fn, q, q, q)


def _pool(nh, dh, dtype, chip):
    pages = 1 + SLOTS * PAGES_PER_SLOT
    return (jax.ShapeDtypeStruct((pages, PAGE, nh, dh), dtype, sharding=chip),
            jax.ShapeDtypeStruct((pages, PAGE, nh), jnp.float32,
                                 sharding=chip))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("width", WIDTHS)
def test_paged_decode_attention_compiles_for_v5e(chip, width, kv):
    from paddle_tpu.kernels.pallas.paged_attention import paged_attention
    nh, dh, _ = WIDTHS[width]
    pool, scales = _pool(nh, dh, BF16 if kv == "bf16" else jnp.int8, chip)
    q = jax.ShapeDtypeStruct((SLOTS, nh, dh), BF16, sharding=chip)
    table = jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32,
                                 sharding=chip)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=chip)
    _compiles_to_a_kernel(
        lambda q_, k_, v_, t_, p_, *s: paged_attention(
            q_, k_, v_, t_, p_, interpret=False,
            **dict(zip(("k_scale", "v_scale"), s))),
        q, pool, pool, table, pos, *((scales, scales) if kv == "int8" else ()))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("width", WIDTHS)
def test_ragged_prefill_attention_compiles_for_v5e(chip, width, kv):
    from paddle_tpu.kernels.pallas.prefill_attention import prefill_attention
    nh, dh, _ = WIDTHS[width]
    pool, scales = _pool(nh, dh, BF16 if kv == "bf16" else jnp.int8, chip)
    q = jax.ShapeDtypeStruct((CHUNK, nh, dh), BF16, sharding=chip)
    row = jax.ShapeDtypeStruct((PAGES_PER_SLOT,), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    _compiles_to_a_kernel(
        lambda q_, k_, v_, t_, s_, n_, *sc: prefill_attention(
            q_, k_, v_, t_, s_, n_, interpret=False,
            **dict(zip(("k_scale", "v_scale"), sc))),
        q, pool, pool, row, scalar, scalar,
        *((scales, scales) if kv == "int8" else ()))


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


@pytest.mark.parametrize("width", WIDTHS)
def test_fused_rope_compiles_for_v5e(chip, width):
    from paddle_tpu.kernels.pallas.rotary import apply_rotary_emb
    nh, dh, _ = WIDTHS[width]
    q = jax.ShapeDtypeStruct((8, nh, 1024, dh), BF16, sharding=chip)
    cs = jax.ShapeDtypeStruct((1024, dh // 2), jnp.float32, sharding=chip)
    _compiles_to_a_kernel(
        lambda q_, k_, c_, s_: apply_rotary_emb(q_, k_, c_, s_,
                                                interpret=False),
        q, q, cs, cs)


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
                     "layernorm_bwd", "rope"}


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
