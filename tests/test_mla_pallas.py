"""The Pallas arms of `kernels/mla.py` against its XLA arms, in the
interpreter on the CPU at tiny shapes. `latent_prefill`
(`kernels/pallas/latent_prefill.py`): the causal mask and the selection's,
padding queries, both groupings of heads, the blocks visited, and the
one-pass mask against `chosen` walked block by block. `latent_decode_paged`
(`kernels/pallas/latent_decode.py`): ragged lengths, dead slots, shared
and trash-padded page rows, the pages fetched and the copies that bring
them."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import mla, registry
from paddle_tpu.kernels.pallas import latent_decode as decode_kernel
from paddle_tpu.kernels.pallas import latent_prefill as kernel
from paddle_tpu.observability import metrics

T, DN, ROPE, DV, RANK, WIDTH = 128, 128, 64, 128, 128, 256
PAGE, DI, HI = 16, 32, 2
PAGES = 40                          # a page row of 640 keys
SCALE = (DN + ROPE) ** -0.5


def _inputs(seed, heads, start, valid=T, dtype=jnp.float32):
    """A sequence of ``start + valid`` tokens in a pool of shuffled pages and
    a chunk of T queries at positions ``start..``, the last ``T - valid``
    padding."""
    rng = np.random.RandomState(seed)
    pool_pages = PAGES + 3
    lat = rng.randn(2, pool_pages, PAGE, WIDTH).astype(np.float32)
    lat[..., RANK + ROPE:] = 0.0
    row = rng.permutation(np.arange(1, pool_pages))[:PAGES].astype(np.int32)
    pos = start + np.arange(T)
    qpos = np.where(np.arange(T) < valid, pos, -1).astype(np.int32)
    return dict(
        q_nope=jnp.asarray(rng.randn(T, heads, DN), dtype),
        q_rope=jnp.asarray(rng.randn(T, heads, ROPE), dtype),
        lat_pool=jnp.asarray(lat, dtype), layer=1, row=jnp.asarray(row),
        qpos=jnp.asarray(qpos),
        w_ukv=jnp.asarray(rng.randn(RANK, heads * (DN + DV)) / RANK ** 0.5,
                          dtype))


def _select(seed, x, topk, ties):
    """`index_threshold`'s (scores, cut, room) for the chunk; with ``ties``
    the scores take few distinct values, so many keys sit on each cut."""
    rng = np.random.RandomState(seed + 1)
    qi = rng.randn(T, HI, DI).astype(np.float32)
    w = np.abs(rng.randn(T, HI)).astype(np.float32)
    kix = rng.randn(2, PAGES + 3, PAGE, DI).astype(np.float32)
    if ties:
        qi, kix, w = np.sign(qi), np.sign(kix), np.ones_like(w)
    return mla.index_threshold(jnp.asarray(qi), jnp.asarray(w),
                               jnp.asarray(kix), 1, x["row"], x["qpos"],
                               topk, block=256, score_block=128)


def _both(x, select, *, heads, block):
    want, n_want = mla.latent_prefill(
        x["q_nope"], x["q_rope"], x["lat_pool"], x["layer"], x["row"],
        x["qpos"], x["w_ukv"], rank=RANK, rope=ROPE, dv=DV, scale=SCALE,
        select=select, key_block=128, head_block=2)
    plan = kernel.plan(T, x["q_nope"].shape[1], DN, ROPE, DV, RANK, WIDTH,
                       PAGE, heads=heads, block=block)
    assert plan is not None
    got, n_got = mla._pallas_prefill(
        x["q_nope"], x["q_rope"], x["lat_pool"], x["layer"], x["row"],
        x["qpos"], x["w_ukv"], plan, rank=RANK, rope=ROPE, dv=DV,
        scale=SCALE, select=select)
    return (np.asarray(want, np.float32), int(n_want),
            np.asarray(got, np.float32), int(n_got))


CASES = {
    # (a) the causal mask: nothing before the chunk, one block, several
    # blocks with a ragged last one
    "causal-keys-in-sight-0": dict(start=0, block=128),
    "causal-one-block": dict(start=64, block=256),
    "causal-ragged-last-block": dict(start=300, block=128),
    # (b) a selection whose cuts hold ties that straddle block edges
    "select-ties-across-blocks": dict(start=300, block=128, topk=96,
                                      ties=True),
    "select-no-ties": dict(start=480, block=128, topk=64, ties=False),
    "select-fewer-keys-than-topk": dict(start=0, block=128, topk=256,
                                        ties=True),
    # (c) padding queries
    "causal-padding-queries": dict(start=200, block=128, valid=70),
    "select-padding-queries": dict(start=200, block=128, valid=70, topk=96,
                                   ties=True),
    "all-padding": dict(start=0, block=128, valid=0),
    # (d) the other grouping of heads
    "causal-four-heads-in-twos": dict(start=150, block=128, heads=4,
                                      group=2),
    "select-four-heads-whole": dict(start=150, block=128, heads=4, group=4,
                                    topk=96, ties=True),
    "bf16": dict(start=300, block=128, topk=96, ties=True,
                 dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", CASES)
def test_pallas_arm_matches_xla_arm(case):
    c = dict(CASES[case])
    heads, dtype = c.get("heads", 8), c.get("dtype", jnp.float32)
    x = _inputs(7, heads, c["start"], c.get("valid", T), dtype)
    select = _select(7, x, c["topk"], c["ties"]) if "topk" in c else None
    want, n_want, got, n_got = _both(x, select, heads=c.get("group", 4),
                                     block=c["block"])
    assert n_got == n_want
    live = np.asarray(x["qpos"]) >= 0
    assert np.all(got[~live] == 0.0) and np.all(want[~live] == 0.0)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    if live.any():
        assert np.abs(want[live]).max() > 0.01


@pytest.mark.parametrize("block", [32, 128, 320, 640])
@pytest.mark.parametrize("start,topk", [(300, 96), (0, 64), (500, 8)])
def test_one_pass_mask_is_chosen_walked_block_by_block(block, start, topk):
    """`chosen_mask` at any block equals `chosen` walked in blocks of 128
    as the XLA arm walks it, bit for bit, with ties on the cut across block
    edges, and counts the same pairs."""
    x = _inputs(11, 8, start, valid=100)
    scores, cut, room = _select(11, x, topk, ties=True)
    qpos = x["qpos"]
    width = PAGES * PAGE
    keep, n = mla.chosen_mask((scores, cut, room), qpos, width, block=block)
    seen = jnp.zeros(T, jnp.int32)
    want = np.zeros((T, width), bool)
    for i in range(width // 128):
        s = i * 128 + jnp.arange(128)
        k, seen = mla.chosen(scores[:, i * 128:(i + 1) * 128], cut, room,
                             seen, s[None, :] <= qpos[:, None])
        want[:, i * 128:(i + 1) * 128] = np.asarray(k)
    assert keep.dtype == jnp.int8
    assert np.array_equal(np.asarray(keep), want.astype(np.int8))
    assert int(n) == int(want.sum())
    live = np.asarray(qpos) >= 0
    # exactly topk keys a live query with that many in sight, ties and all
    per_query = want.sum(axis=1)
    assert np.array_equal(per_query[live],
                          np.minimum(np.asarray(qpos)[live] + 1, topk))
    on_cut = (np.asarray(scores)[:, :width] == np.asarray(cut)[:, None]) \
        & (np.arange(width)[None, :] <= np.asarray(qpos)[:, None])
    if topk < start:
        assert (on_cut.sum(axis=1)[live] > 1).any()      # there WERE ties


@pytest.mark.parametrize("start,valid,visited", [
    (0, T, 1), (100, T, 2), (300, T, 4), (300, 10, 3), (500, T, 5),
    (0, 0, 0)])
def test_blocks_past_the_furthest_query_are_not_visited(start, valid,
                                                        visited):
    """(e) every group of heads visits ``(max(qpos) + block) // block`` key
    blocks of the five the page row holds, whatever the row could hold."""
    x = _inputs(3, 8, start, valid)
    plan = kernel.plan(T, 8, DN, ROPE, DV, RANK, WIDTH, PAGE, heads=4,
                       block=128)
    lat = x["lat_pool"][1, x["row"]].reshape(-1, WIDTH)
    out, visits = kernel.latent_prefill(
        x["q_nope"], x["q_rope"], x["w_ukv"], lat, x["qpos"], plan=plan,
        rank=RANK, rope=ROPE, dv=DV, scale=SCALE, interpret=True,
        return_visits=True)
    assert lat.shape[0] // 128 == 5
    assert np.asarray(visits).tolist() == [visited, visited]
    assert np.isfinite(np.asarray(out)).all()


def test_plan_follows_the_shapes():
    """The cell's call (128 heads) and GigaChat's (64) get one plan; odd
    shapes get none and keep the XLA arm."""
    assert kernel.plan(512, 128, 128, 64, 128, 512, 640, 16) \
        == kernel.plan(512, 64, 128, 64, 128, 512, 640, 16) \
        == kernel.Plan(8, 512)
    for odd in [dict(t=500), dict(dn=192), dict(rank=576), dict(width=576),
                dict(rope=0), dict(dv=64), dict(page_size=24)]:
        a = dict(t=512, h=128, dn=128, rope=64, dv=128, rank=512, width=640,
                 page_size=16)
        a.update(odd)
        assert kernel.plan(**a) is None, odd


@pytest.mark.parametrize("backend,arm", [("cpu", "xla"), ("tpu", "pallas")])
def test_the_arm_follows_the_backend_and_is_counted(backend, arm,
                                                    monkeypatch):
    """`latent_prefill` takes the Pallas arm where the backend is a TPU
    (here: its name steered, the kernel in the interpreter) and the XLA arm
    on the CPU, counts which in the registry's own counter, and both give
    one answer."""
    assert registry.ops()["mla_attention"].impls == ("xla", "pallas")
    x = _inputs(5, 8, 200)
    select = _select(5, x, 96, ties=True)
    want, n_want = mla.latent_prefill(
        x["q_nope"], x["q_rope"], x["lat_pool"], x["layer"], x["row"],
        x["qpos"], x["w_ukv"], rank=RANK, rope=ROPE, dv=DV, scale=SCALE,
        select=select)
    monkeypatch.setattr(registry, "backend", lambda: backend)
    name = f"kernel.dispatch.mla_attention.{arm}"
    before = metrics.counter(name).value
    got, n_got = mla.latent_prefill(
        x["q_nope"], x["q_rope"], x["lat_pool"], x["layer"], x["row"],
        x["qpos"], x["w_ukv"], rank=RANK, rope=ROPE, dv=DV, scale=SCALE,
        select=select)
    assert metrics.counter(name).value == before + 1
    assert int(n_got) == int(n_want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# ------------------------------------------------ the paged absorbed decode

# (heads, row width, rank, rope, page, pages a row, pages a turn, pages a
# merged copy): Kimi's and GigaChat's row at a short table, and a small one
DECODE_SHAPES = {"kimi-row": (64, 640, 512, 64, 16, 24, 8, 4),
                 "small": (8, 256, 128, 64, 8, 24, 4, 2)}
# each slot's position (negative: dead) as (pages before it, offset in its
# page); ``shared``: slots 0 and 1 hold the SAME leading page ids
DECODE_CASES = {
    "ragged-lengths": dict(at=[(23, -1), (9, 3), (4, 0), (16, -1), (13, 5)]),
    "ends-mid-page-and-mid-block": dict(at=[(10, 2), (5, 1), (17, 6)]),
    "a-dead-slot": dict(at=[(6, 3), None, (11, 0), None]),
    "one-live-slot": dict(at=[None, None, (14, 4), None]),
    "a-shared-context": dict(at=[(19, 1), (21, 5), (7, 2)], shared=16),
    "rows-padded-with-the-trash-page": dict(at=[(3, 1), (0, 2), (12, 0)],
                                            ordered=True),
    "one-key": dict(at=[(0, 0), (8, 3), (0, 0)]),
    "all-dead": dict(at=[None, None]),
}


def _decode_inputs(shape, at, shared=0, ordered=False, seed=0,
                   dtype=jnp.float32):
    """Slots at the positions ``at`` over a pool of shuffled (or, ``ordered``,
    consecutive) pages. A row's entries past its sequence's pages name the
    trash page. Returns the call's operands and the pages each slot has."""
    h, w, rank, rope, ps, row, _, _ = DECODE_SHAPES[shape]
    rng = np.random.RandomState(seed)
    b = len(at)
    qpos = np.array([-1 if a is None else a[0] * ps + a[1] % ps if a[1] >= 0
                     else (a[0] + 1) * ps - 1 for a in at], np.int32)
    has = np.where(qpos >= 0, qpos // ps + 1, 0)
    pool_pages = 1 + b * row
    pool = rng.randn(2, pool_pages, ps, w).astype(np.float32)
    pool[..., rank + rope:] = 0.0
    ids = np.arange(1, pool_pages) if ordered \
        else rng.permutation(np.arange(1, pool_pages))
    table = np.full((b, row), mla.TRASH_PAGE, np.int32)
    for i in range(b):
        table[i, :has[i]] = ids[i * row:i * row + has[i]]
    if shared:
        table[1, :shared] = table[0, :shared]
    q = rng.randn(b, h, w).astype(np.float32)
    q[..., rank + rope:] = 0.0
    return dict(q=jnp.asarray(q, dtype), pool=jnp.asarray(pool, dtype),
                table=jnp.asarray(table), qpos=jnp.asarray(qpos)), has


def _decode_both(shape, x, poison=True):
    """The XLA arm's output, and the Pallas arm's with what it fetched. The
    Pallas arm reads a pool whose trash page is NaN: a fetch of it would
    reach the output through the mix (0 x NaN)."""
    h, w, rank, rope, ps, row, block, run = DECODE_SHAPES[shape]
    scale = (rank // 4 + rope) ** -0.5
    want = mla.latent_decode_paged(x["q"], x["pool"], 1, x["table"],
                                   x["qpos"], rank=rank, scale=scale,
                                   key_block=block * ps)
    plan = decode_kernel.plan(h, w, rank, ps, x["pool"].dtype.itemsize,
                              x["q"].shape[0], row, block=block, run=run)
    assert plan == decode_kernel.Plan(block, run)
    pool = x["pool"].at[:, mla.TRASH_PAGE].set(jnp.nan) if poison \
        else x["pool"]
    got, visits = decode_kernel.latent_decode_paged(
        x["q"], pool, 1, x["table"], x["qpos"], plan=plan, rank=rank,
        scale=scale, interpret=True, return_visits=True)
    return (np.asarray(want, np.float32), np.asarray(got, np.float32),
            np.asarray(visits))


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_pallas_decode_arm_matches_xla_arm(shape, case):
    c = dict(DECODE_CASES[case])
    x, has = _decode_inputs(shape, c.pop("at"), **c)
    want, got, visits = _decode_both(shape, x)
    live = has > 0
    # a dead slot is zeros in both arms and fetches nothing; a live one
    # fetches the pages it has and no other (never the trash page)
    assert np.all(got[~live] == 0.0) and np.all(want[~live] == 0.0)
    assert visits[:, 0].tolist() == has.tolist()
    assert np.all(visits[~live] == 0) and np.all(visits[:, 1] <= has)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if live.any():
        assert np.abs(want[live]).max() > 0.01


def test_pallas_decode_arm_rounds_where_the_xla_arm_does():
    """In the pool's served type: the probabilities are rounded to bf16
    before the mix in both arms, the scores and the softmax float32."""
    x, _ = _decode_inputs("kimi-row", DECODE_CASES["ragged-lengths"]["at"],
                          dtype=jnp.bfloat16)
    want, got, _ = _decode_both("kimi-row", x)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_a_run_of_consecutive_pages_is_one_copy(shape):
    """The same rows behind consecutive page ids and behind shuffled ones
    give one output; the consecutive table costs a copy a whole group of
    ``run`` pages, the shuffled one a copy a page."""
    ps, row, run = (DECODE_SHAPES[shape][i] for i in (4, 5, 7))
    at = [(row - 1, -1), (9, 3), None, (2, 1)]
    x, has = _decode_inputs(shape, at, ordered=True)
    pages = x["pool"].shape[1]
    perm = np.concatenate([[0], np.random.RandomState(4).permutation(
        np.arange(1, pages))])                  # old id -> new id
    shuffled = dict(x, table=jnp.asarray(perm)[x["table"]],
                    pool=x["pool"][:, jnp.asarray(np.argsort(perm))])
    want, got, visits = _decode_both(shape, x)
    want_s, got_s, visits_s = _decode_both(shape, shuffled)
    assert np.array_equal(want, want_s)
    assert np.array_equal(got, got_s)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert visits[:, 0].tolist() == visits_s[:, 0].tolist() == has.tolist()
    # whole groups by one copy each, the rest page by page
    assert visits[:, 1].tolist() == (has // run + has % run).tolist()
    # shuffled ids: a copy a page but where the shuffle left a run whole
    assert visits_s[:, 1].sum() > visits[:, 1].sum()
    assert np.all(visits_s[:, 1] <= has)


def test_decode_plan_follows_the_shapes():
    """Kimi's call (32 slots, rows of 1,632 pages) and GigaChat's (128
    slots, rows of 224) get one block, read off the row's shape inside the
    VMEM budget; a short row gets a block no wider than itself; odd shapes
    get none and keep the XLA arm."""
    p = decode_kernel.plan
    assert p(64, 640, 512, 16, 2, 32, 1632) == p(64, 640, 512, 16, 2, 128, 224) \
        == decode_kernel.Plan(decode_kernel.BLOCK_KEYS // 16,
                              decode_kernel.RUN_PAGES)
    assert p(64, 640, 512, 16, 2, 32, 20) == decode_kernel.Plan(32, 16)
    wide = p(128, 2048, 1024, 16, 2, 32, 1632)  # a wider row: a smaller block
    assert wide is not None and wide.block < 64 and wide.block % wide.run == 0
    assert decode_kernel._turn_bytes(wide.block * 16, 128, 2048, 1024, 2) \
        <= decode_kernel.VMEM_BUDGET
    for odd in [dict(width=576), dict(rank=448), dict(rank=768, width=640),
                dict(page_size=8), dict(page_size=24), dict(h=12),
                dict(itemsize=8), dict(block=12),
                # a table scalar memory cannot hold (1 MiB on a v5e): the
                # published 262,144 positions at 32 slots, 256 slots here
                dict(row_pages=16384), dict(slots=256)]:
        a = dict(h=64, width=640, rank=512, page_size=16, itemsize=2,
                 slots=32, row_pages=1632)
        a.update(odd)
        assert p(**a) is None, odd
    # a float32 pool's page of 8 rows is a whole tile
    assert p(64, 640, 512, 8, 4, 128, 224) is not None


@pytest.mark.parametrize("backend,arm", [("cpu", "xla"), ("tpu", "pallas")])
def test_the_decode_arm_follows_the_backend_and_is_counted(backend, arm,
                                                           monkeypatch):
    """`latent_decode_paged` takes the Pallas arm where the backend is a
    TPU (here: its name steered, the kernel in the interpreter) and the XLA
    arm on the CPU, counts which and the block it took in the registry's
    counters, and both give one answer."""
    assert registry.ops()["mla_decode_paged"].impls == ("xla", "pallas")
    h, w, rank, rope, ps, row, _, _ = DECODE_SHAPES["small"]
    x, _ = _decode_inputs("small", [(19, 1), None, (7, 2)])
    call = lambda: mla.latent_decode_paged(          # noqa: E731
        x["q"], x["pool"], 1, x["table"], x["qpos"], rank=rank, scale=0.1)
    want = call()
    monkeypatch.setattr(registry, "backend", lambda: backend)
    block = decode_kernel.plan(h, w, rank, ps, 4, 3, row).block
    names = [f"kernel.dispatch.mla_decode_paged.{arm}"] + [
        f"kernel.paged_block.mla_decode_paged.{block}"] * (arm == "pallas")
    before = [metrics.counter(n).value for n in names]
    other = metrics.counter("kernel.dispatch.mla_decode_paged."
                            + ("xla" if arm == "pallas" else "pallas")).value
    got = call()
    assert [metrics.counter(n).value for n in names] \
        == [v + 1 for v in before]
    assert metrics.counter(
        "kernel.dispatch.mla_decode_paged."
        + ("xla" if arm == "pallas" else "pallas")).value == other
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
