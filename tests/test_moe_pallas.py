"""The ``pallas`` arm of `kernels/moe.py::routed_experts`
(kernels/pallas/grouped_experts.py), in the interpreter: against the
``grouped`` arm, whose plan it keeps and whose two `jax.lax.ragged_dot` calls
it replaces, and against the benchmark's plain float32 experts
(benchmarks/reference/gigachat35.py, which imports nothing of paddle_tpu);
and the registry's rule at the ten call sites the benchmark's cells make.
That the kernels lower through Mosaic at the families' widths and leave no
``ragged-dot`` in a step program: tests/test_tpu_compile*.py."""
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.kernels import moe, registry  # noqa: E402
from paddle_tpu.kernels.pallas import grouped_experts as ge  # noqa: E402
from reference import gigachat35 as ref  # noqa: E402

# held, top_k, tokens of a router over 16 experts, [d, f] = [32, 16]; then
# what is odd about the case. ``dead``: every n-th token is not valid (0:
# all valid, 1: none is); ``never`` / ``always``: a held expert no token or
# every token chooses; ``tm``: the row tile (None: the plan's 16)
CASES = {
    "plain": dict(held=(4, 8), top_k=3, tokens=12),
    "all-held": dict(held=(0, 16), top_k=2, tokens=8),
    "one-held": dict(held=(5, 6), top_k=4, tokens=20),
    "softmax": dict(held=(4, 8), top_k=3, tokens=12, scoring="softmax"),
    "dead-rows": dict(held=(4, 8), top_k=3, tokens=12, dead=3),
    "no-row-at-all": dict(held=(4, 8), top_k=3, tokens=12, dead=1),
    "limit": dict(held=(4, 8), top_k=3, tokens=12, limit=0.4),
    "an-expert-with-no-row": dict(held=(4, 8), top_k=3, tokens=12, never=5),
    "no-held-expert-chosen": dict(held=(4, 6), top_k=2, tokens=9,
                                  never=(4, 5)),
    "every-row-on-one-expert": dict(held=(4, 8), top_k=1, tokens=24,
                                    always=6),
    "rows-not-whole-tiles": dict(held=(4, 8), top_k=3, tokens=13),
    "groups-across-tiles": dict(held=(0, 8), top_k=4, tokens=24, tm=8),
    "one-token": dict(held=(0, 8), top_k=2, tokens=1),
    "bfloat16": dict(held=(4, 8), top_k=3, tokens=12, dtype=jnp.bfloat16,
                     limit=2.0),
}


def _inputs(case, d=32, f=16, e=16):
    rs = np.random.RandomState(7)
    lo, hi = case["held"]
    dtype = case.get("dtype", jnp.float32)
    x = rs.randn(case["tokens"], d).astype(np.float32)
    x[:, 0] = 1.0
    router = rs.randn(d, e).astype(np.float32) * 0.3
    bias = rs.randn(e) * 0.05          # chosen by sigmoid(logit) + bias
    for ex in np.atleast_1d(case.get("never", ())):
        bias[ex] = -5.0
    if "always" in case:
        bias[case["always"]] = 5.0
    w1 = rs.randn(hi - lo, d, 2 * f) * 0.3
    w2 = rs.randn(hi - lo, f, d) * 0.3
    dead = case.get("dead", 0)
    valid = None if not dead else \
        jnp.asarray(np.arange(case["tokens"]) % dead != 0)
    return (jnp.asarray(x, dtype), jnp.asarray(router), jnp.asarray(w1, dtype),
            jnp.asarray(w2, dtype), valid, jnp.asarray(bias, jnp.float32))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_pallas_arm_is_the_grouped_arm_and_the_plain_experts(
        name, monkeypatch):
    """Every assignment to a held expert computed, whatever the groups look
    like: the result is the ``grouped`` arm's (the same roundings: exactly
    so in float32 up to the order of a product's sums) and the reference's
    plain experts, every token through each held expert in float32; a dead
    token gets zero; ``counts`` does not depend on the arm."""
    case = CASES[name]
    x, router, w1, w2, valid, bias = _inputs(case)
    lo, hi = case["held"]
    scoring = case.get("scoring", "sigmoid")
    limit = case.get("limit")
    if "tm" in case:
        monkeypatch.setattr(ge, "STEP_ROWS", case["tm"])
    kw = dict(top_k=case["top_k"], held=case["held"], scoring=scoring,
              bias=bias if scoring == "sigmoid" else None, scale=2.5,
              limit=limit, valid=valid,
              counts=jnp.arange(hi - lo + 1, dtype=jnp.int32))
    with jax.default_matmul_precision("highest"):
        got, c_got = moe.routed_experts(x, router, w1, w2, impl="pallas",
                                        **kw)
        grouped, c_grouped = moe.routed_experts(x, router, w1, w2,
                                                impl="grouped", **kw)
        _, c_dense = moe.routed_experts(x, router, w1, w2, impl="dense",
                                        **kw)
    assert got.dtype == x.dtype and got.shape == x.shape
    assert c_got.tolist() == c_grouped.tolist() == c_dense.tolist()
    low = x.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(grouped, np.float32),
                               atol=2e-2 if low else 2e-6, rtol=2e-6)
    live = np.ones(case["tokens"], bool) if valid is None \
        else np.asarray(valid)
    assert float(jnp.abs(got[~live].astype(jnp.float32)).sum()) == 0.0
    if scoring == "sigmoid" and not low:
        s = types.SimpleNamespace(top_k=case["top_k"], route_scale=2.5,
                                  limit=limit)
        gates = ref.route(x, router, bias, s, "f32")
        want = sum(gates[:, lo + i:lo + i + 1] * ref.gated(
            x, w1[i], w2[i], s, "f32" if limit is not None else "no_clamp")
            for i in range(hi - lo))
        np.testing.assert_allclose(got[live], want[live], atol=1e-5,
                                   rtol=1e-5)
    hits = np.asarray(c_got - kw["counts"])
    if name in ("no-row-at-all", "no-held-expert-chosen"):
        assert hits[:-1].sum() == 0 and float(jnp.abs(got).max()) == 0.0
    elif name == "an-expert-with-no-row":
        assert hits[1] == 0 and hits[:-1].sum() > 0
    elif name == "every-row-on-one-expert":
        assert hits[:-1].tolist() == [0, 0, 24, 0]
    if name not in ("no-row-at-all", "no-held-expert-chosen"):
        assert float(jnp.abs(got.astype(jnp.float32)).max()) > 0.02


def test_the_visits_name_each_tile_an_expert_has_a_row_in():
    """Groups of 0, 5, 0, 12, 3, 0 rows over tiles of 8 (20 of 32 rows in a
    group): the experts with rows in order, each over the tiles its rows
    lie in; a tile two groups share visited by each in consecutive steps;
    the tile past the last group by none."""
    sizes = jnp.asarray([0, 5, 0, 12, 3, 0], jnp.int32)
    tile, expert, offsets, steps = ge._visits(sizes, 4, 8)
    n = int(steps[0])
    assert n == 5 and tile.shape == expert.shape == (4 + 6,)
    assert list(zip(expert[:n].tolist(), tile[:n].tolist())) == [
        (1, 0), (3, 0), (3, 1), (3, 2), (4, 2)]
    assert offsets.tolist() == [0, 0, 5, 5, 17, 20, 20]
    # no group at all: no step
    assert int(ge._visits(jnp.zeros(6, jnp.int32), 4, 8)[3][0]) == 0
    # every row in one group: its tiles in order, once each
    tile, expert, _, steps = ge._visits(
        jnp.asarray([0, 32, 0], jnp.int32), 4, 8)
    assert int(steps[0]) == 4 and tile[:4].tolist() == [0, 1, 2, 3] \
        and expert[:4].tolist() == [1] * 4


@pytest.mark.parametrize("rows,d,f,want", [
    (384, 4096, 1280, (16, 1280, 4096)),     # Solar's decode step
    (4096, 4096, 1280, (64, 1280, 4096)),    # and its chunk
    (192, 5120, 1536, (16, 768, 5120)),      # dots3
    (256, 7168, 2048, (16, 512, 3584)),      # Kimi's decode step
    (24, 32, 16, (16, 16, 32)),              # the interpreter's tiny sizes
])
def test_the_plan_reads_its_tiles_off_the_shapes(rows, d, f, want):
    """Rows a tile from the rows a call makes, columns a block the widest
    whole lane tiles that divide the width of which the double-buffered
    blocks fit the budget; a width that is no whole lane tiles in one
    block."""
    got = ge.plan(rows, d, f, 2)
    assert tuple(got) == want
    assert 2 * 2 * d * got.up * 2 <= ge.VMEM_BUDGET
    assert ge.plan(rows, d, f, 2, tm=8, up=128).tm == 8


# the ten call sites of `routed_experts` in the benchmark's cells: the
# router's experts, the experts a token, the held ones, tokens a call, the
# hidden size and an expert's width; the arm the rule takes on one TPU, and
# why (the readings: kernels/moe.py's docstring)
SITES = {
    "dots3-step": (256, 8, 32, 24, 5120, 1536, "pallas"),    # 53% hit
    "dots3-chunk": (256, 8, 32, 512, 5120, 1536, "pallas"),  # FLOP-bound
    "kimi-step": (384, 8, 12, 32, 7168, 2048, "pallas"),     # 49% hit
    "kimi-chunk": (384, 8, 12, 512, 7168, 2048, "pallas"),
    "solar-step": (320, 8, 40, 48, 4096, 1280, "pallas"),    # 70% hit
    "solar-chunk": (320, 8, 40, 512, 4096, 1280, "pallas"),
    "giga-step": (256, 8, 16, 128, 7168, 2048, "dense"),     # 98% hit, and
    #                                  its FLOPs hide under the read
    "giga-chunk": (256, 8, 16, 512, 7168, 2048, "pallas"),
    "granite-step": (72, 10, 36, 64, 4096, 768, "dense"),    # 7.2 experts
    "granite-chunk": (72, 10, 36, 512, 4096, 768, "dense"),  # a chosen one
}


def _ctx(site):
    experts, top_k, held, tokens, hidden, width, _ = SITES[site]
    return dict(experts=experts, top_k=top_k, held=held, tokens=tokens,
                hidden=hidden, width=width)


@pytest.mark.parametrize("site", sorted(SITES))
def test_the_rule_at_the_cells_call_sites(site, monkeypatch):
    """On one TPU: ``pallas`` where ``dense`` would be bound by its FLOPs
    or a call leaves a good part of the held experts unhit, ``dense`` in
    between and for a router that wastes little; ``grouped`` first
    nowhere, so no step program holds a ``ragged-dot``. Off it (a CPU, a
    mesh): ``grouped`` where few experts are hit, else ``dense``, never
    ``pallas``."""
    cands = registry.ops()["moe_experts"].candidates
    off = cands(_ctx(site))
    assert "pallas" not in off
    tokens, want = SITES[site][3], SITES[site][-1]
    assert off[0] == ("grouped" if want == "pallas" and tokens < 240
                      else "dense")
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    on = cands(_ctx(site))
    assert on[0] == want and sorted(on) == ["dense", "grouped", "pallas"]
    assert on[-1] == "grouped"


_WIDE = dict(hidden=4096, width=1280)


@pytest.mark.parametrize("ctx,first", [
    # the cuts, in the quantities the rule is stated in
    (dict(experts=256, top_k=8, tokens=239, **_WIDE), "dense"),
    (dict(experts=256, top_k=8, tokens=240, **_WIDE), "pallas"),
    (dict(experts=320, top_k=8, tokens=63, **_WIDE), "pallas"),  # 79.7% hit
    (dict(experts=320, top_k=8, tokens=64, **_WIDE), "dense"),   # 80.2%
    (dict(experts=128, top_k=8, tokens=512, **_WIDE), "pallas"),  # 16 to 1
    (dict(experts=120, top_k=8, tokens=512, **_WIDE), "dense"),   # 15 to 1
    (dict(experts=72, top_k=10, tokens=8, **_WIDE), "dense"),
    # a width that is no whole lane tiles is not Mosaic's
    (dict(experts=256, top_k=8, tokens=24, hidden=5120, width=1500),
     "grouped"),
    (dict(experts=256, top_k=8, tokens=512, hidden=5100, width=1536),
     "dense"),
    ({}, "dense"),
])
def test_the_rule_cuts_where_it_says(ctx, first, monkeypatch):
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    assert registry.ops()["moe_experts"].candidates(ctx)[0] == first


def test_a_mesh_keeps_the_mosaic_arm_out(monkeypatch):
    """Under a multi-device mesh the trace is a program GSPMD partitions,
    which a Mosaic kernel cannot join: the rule falls back to the arms XLA
    can partition; a forced arm wins either way and is counted."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(registry, "backend", lambda: "tpu")
    cands = registry.ops()["moe_experts"].candidates
    assert cands(_ctx("solar-step"))[0] == "pallas"
    monkeypatch.setattr(mesh_mod, "get_mesh",
                        lambda: types.SimpleNamespace(size=4))
    assert cands(_ctx("solar-step")) == ["grouped", "dense"]
    assert cands(_ctx("solar-chunk")) == ["dense", "grouped"]
    n = metrics.counter("kernel.dispatch.moe_experts.pallas")
    was = n.value
    assert registry.dispatch("moe_experts", forced="pallas",
                             ctx=_ctx("granite-step")) == "pallas"
    assert n.value == was + 1
    assert not hasattr(moe, "GROUPED_FROM") \
        and not hasattr(moe, "GROUPED_UP_TO")
