"""`kernels/diff_attention.py::diff_attention_paged`, the decode step's read
of Phi-4-mini-flash's shared page pool: its ``pallas`` arm (the paged
decode kernel with a row keeping its PAIR's value lanes,
`kernels/pallas/paged_attention.py` "Differential pairs") in the
interpreter against its ``xla`` arm, the choice between them, and what the
step programs expose of it.

- parity at the tiny preset's head layout and at the published one (40
  query heads over 20 K/V heads of 64, page 16), every case one test;
- a dead slot takes no turn and fetches nothing, a live one fetches its own
  pages and never the trash page;
- the arm follows what the call can see and is counted with its block;
- the decode program names the scope ``shared_kv_attn`` on its ops and the
  chunk program does not.
"""
import contextlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.kernels import diff_attention as da, registry  # noqa: E402
from paddle_tpu.kernels.pallas import _compat  # noqa: E402
from paddle_tpu.kernels.pallas import paged_attention as ppa  # noqa: E402
from paddle_tpu.kernels.paged_attention import TRASH_PAGE  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402

PS, ROW = 16, 128                 # a page, a slot's row of pages
FULL = PS * ROW
# (nq, nkv, hd): the tiny preset's heads (a row of 32 lanes, which the op
# itself hands to the xla arm: `forced` calls the kernel all the same) and
# the published ones
LAYOUTS = {"tiny": (4, 2, 16), "published": (40, 20, 64)}
# tokens each slot holds (0: a dead slot); ``scatter``: page ids in any
# order, else a slot's ids ascend one by one; ``poison``: the pallas arm
# reads a pool whose trash page is NaN
CASES = {
    "one_token": dict(lens=[1, 1, 1]),
    "on_a_page_boundary": dict(lens=[PS, 2 * PS, 17 * PS]),
    "one_past_a_page_boundary": dict(lens=[PS + 1, 2 * PS + 1, 16 * PS + 1]),
    "a_full_row": dict(lens=[FULL, 5, FULL - 1]),
    "a_dead_slot_between_live_ones": dict(lens=[40, 0, 300, 0, 7]),
    "page_ids_not_consecutive": dict(lens=[100, 260, 37], scatter=True),
    "nan_in_the_trash_page": dict(lens=[3 * PS, 1, 200], poison=True),
}


def _inputs(layout, lens, dtype=jnp.float32, scatter=False, seed=0):
    """(the op's arguments, its head counts): every slot's pages its own,
    the rest of its row the trash page."""
    nq, nkv, hd = LAYOUTS[layout]
    rng = np.random.RandomState(seed)
    has = [-(-n // PS) for n in lens]
    pages = 1 + sum(has) + 3
    ids = 1 + (rng.permutation(pages - 1) if scatter
               else np.arange(pages - 1))
    table = np.full((len(lens), ROW), TRASH_PAGE, np.int32)
    at = 0
    for i, n in enumerate(has):
        table[i, :n] = ids[at:at + n]
        at += n

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    return dict(
        q=rand(len(lens), nq * hd), k_pages=rand(1, pages, PS, nkv * hd),
        v_pages=rand(1, pages, PS, nkv * hd), page_table=jnp.asarray(table),
        pos=jnp.asarray(np.asarray(lens, np.int32) - 1), lam=0.37, l0=0.2,
        subln_w=1 + 0.1 * rand(2 * hd)), dict(nq=nq, nkv=nkv)


@contextlib.contextmanager
def forced(arm):
    """`diff_attention_paged` takes ``arm``, the kernel in the interpreter,
    whatever `_paged_arm` would say of the call."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(da, "_paged_arm", lambda pool: arm)
        m.setattr(_compat, "default_interpret", lambda: True)
        yield


def _poisoned(x):
    return dict(x, **{p: x[p].at[:, TRASH_PAGE].set(jnp.nan)
                      for p in ("k_pages", "v_pages")})


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_pallas_arm_matches_the_xla_arm(layout, case):
    c = dict(CASES[case])
    lens, poison = np.asarray(c.pop("lens")), c.pop("poison", False)
    x, heads = _inputs(layout, lens, **c)
    with forced("xla"):
        want = np.asarray(da.diff_attention_paged(**x, **heads))
    with forced("pallas"):
        got = np.asarray(da.diff_attention_paged(
            **(_poisoned(x) if poison else x), **heads))
    assert np.isfinite(got).all()
    # a dead slot is zeros in both arms
    assert np.all(got[lens == 0] == 0.0) and np.all(want[lens == 0] == 0.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.abs(want[lens > 0]).max() > 0.1


def test_pallas_arm_rounds_where_the_xla_arm_does():
    """On a bfloat16 pool both arms mix from probabilities rounded to
    bfloat16 and accumulate in float32: they agree to the output's own
    rounding (the trash page NaN under the pallas arm, a dead slot
    between)."""
    x, heads = _inputs("published", [300, 17, 0, 1000], jnp.bfloat16,
                       scatter=True)
    with forced("xla"):
        want = np.asarray(da.diff_attention_paged(**x, **heads), np.float32)
    with forced("pallas"):
        got = np.asarray(da.diff_attention_paged(**_poisoned(x), **heads),
                         np.float32)
    assert got.dtype == want.dtype and np.all(got[2] == 0.0)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_a_dead_slot_takes_no_turn_and_the_trash_page_is_never_fetched():
    """What the kernel fetched, by its own count: the pages a live slot
    has, none for a dead one (whose row, and every row's tail, names the
    trash page), with dead slots first, last and side by side."""
    lens = np.asarray([0, 0, 33, 0, 16, 2048, 0])
    x, heads = _inputs("published", lens)
    x = _poisoned(x)
    b = len(lens)
    out, visits = ppa.paged_attention(
        x["q"].reshape(b, heads["nq"], -1), x["k_pages"], x["v_pages"],
        x["page_table"], x["pos"], layer=0, interpret=True,
        return_visits=True, value_heads=2)
    assert out.shape == (b, 40, 128) and out.dtype == jnp.float32
    assert np.asarray(visits)[:, 0].tolist() == [0, 0, 3, 0, 1, 128, 0]
    out = np.asarray(out)
    assert np.isfinite(out).all() and np.all(out[lens == 0] == 0.0)


@pytest.mark.parametrize("backend,lanes,arm", [
    ("cpu", 128, "xla"), ("tpu", 128, "pallas"), ("tpu", 32, "xla")])
def test_the_arm_follows_the_backend_and_the_shapes_and_is_counted(
        backend, lanes, arm, monkeypatch):
    """The ``pallas`` arm where the backend is a TPU (here: its name
    steered, the kernel in the interpreter) and a pool row is whole lane
    tiles, the ``xla`` arm anywhere else; the registry counts which, and
    the block a kernel was built with."""
    assert registry.ops()["diff_attention_paged"].impls == ("xla", "pallas")
    monkeypatch.setitem(LAYOUTS, "mine", (4, 2, lanes // 2))
    x, heads = _inputs("mine", [19, 0, 40])
    want = da.diff_attention_paged(**x, **heads)
    monkeypatch.setattr(registry, "backend", lambda: backend)
    monkeypatch.setattr(_compat, "default_interpret", lambda: True)
    names = {a: f"kernel.dispatch.diff_attention_paged.{a}"
             for a in ("xla", "pallas")}
    names["block"] = "kernel.paged_block.diff_attention_paged.16"
    before = {k: metrics.counter(n).value for k, n in names.items()}
    got = da.diff_attention_paged(**x, **heads)
    after = {k: metrics.counter(n).value for k, n in names.items()}
    assert after == {**before, arm: before[arm] + 1,
                     "block": before["block"] + (arm == "pallas")}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step"])
def test_only_the_decode_program_names_the_scope_shared_kv_attn(program):
    """The benchmark finds the shared cache's decode attention in a device
    trace by the scope ``shared_kv_attn`` (its rule for an op's scope:
    `harness/trace.py::_scope`). The decode program compiled on the CPU has
    ops under it, among them the ``xla`` arm's products, and counts that
    arm once for the full layer and once for the scan's body; a chunk's
    attention over the same cache is under no such name (the trace does not
    keep which program an op ran in)."""
    from harness import trace
    from paddle_tpu.inference.cache import DeviceCache
    from paddle_tpu.inference.programs import (decode_program,
                                               prefill_program,
                                               prefill_upload, step_upload)
    from paddle_tpu.models import phi4flash as phi
    cfg = phi.tiny_config()
    slots, page, per_slot, chunk = 3, 4, 16, 8
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    params = {k: sds(s) for k, s in phi.leaf_shapes(cfg).items()}
    pool = sds((1, 1 + slots * per_slot, page, cfg.kv_width))
    state = tuple(sds(s, d) for _, _, s, d in
                  phi.state_arrays(cfg, slots, page, f32))
    cache = DeviceCache(k=pool, v=pool, k_scale=None, v_scale=None,
                        state=state, keys=None, heads=cfg.num_kv_heads)
    if program == "decode_step":
        up = step_upload(slots, per_slot, sampling=False)
        step = decode_program(phi, cfg, up)
    else:
        up = prefill_upload(chunk, per_slot, sampling=False, chunk=True)
        step = prefill_program(phi, cfg, up)
    built = metrics.counter("kernel.dispatch.diff_attention_paged.xla")
    before = built.value
    text = jax.jit(step).lower(params, cache, sds((slots,), jnp.int32),
                               up.spec()).compile().as_text()
    under = [m.group(1) for m in map(trace._OP_NAME.search,
                                     text.splitlines())
             if m and trace._scope(m.group(1), {"shared_kv_attn"})]
    if program == "decode_step":
        assert built.value == before + 2
        assert any("dot_general" in name for name in under)
    else:
        assert built.value == before and under == []
