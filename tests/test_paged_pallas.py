"""Ragged paged-attention Pallas kernel: interpret-mode parity with the XLA
reference on CPU, the length-aware page-loop stop, the dispatch switch
(`FLAGS_tpu_paged_impl`), the autotune entry, and the overflow-to-trash
coordinate fix.

The load-bearing contracts:
- pallas(interpret) == xla reference on every ragged shape (same f32 masked
  softmax, so the engine's token-identical guarantee survives the kernel
  swap), on the STORED pool ``[nl, P, ps, nh*dh]`` read at its first and
  its last layer, and bit-identical to the per-layer form of that layer;
- the kernel's page-loop trip count is ``ceil((pos+1)/page_size)`` — it
  scales with each sequence's TRUE length, never with ``pages_per_slot``;
- positions past a slot's capacity route to TRASH_PAGE instead of silently
  corrupting the last page.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.pallas import paged_attention as ppa
from paddle_tpu.observability import metrics


NL = 3                  # layers of the test pools; every layer is noise


def _random_case(rng, b, nh, dh, ps, maxp, num_pages, pos):
    """Pools in the stored layout [NL, num_pages, ps, nh*dh]. Distinct
    non-trash pages per (slot, page), and other values in every layer, so
    any wrong page or layer read shows up as a numeric mismatch, not a
    coincidence."""
    q = jnp.asarray(rng.randn(b, nh, dh).astype(np.float32))
    shape = (NL, num_pages, ps, nh * dh)
    kp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    vp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    perm = 1 + rng.permutation(num_pages - 1)[:b * maxp]
    pt = jnp.asarray(perm.reshape(b, maxp).astype(np.int32))
    return q, kp, vp, pt, jnp.asarray(np.asarray(pos, np.int32))


def _pool_case(kv, rng, b, nh, dh, ps, maxp, lens):
    """(q, k_pool, v_pool, table, pos, scales) on a pool of dtype ``kv``;
    ``scales`` is the keyword dict of an int8 pool, else empty."""
    q, kp, vp, pt, pos = _random_case(rng, b, nh, dh, ps, maxp,
                                      2 + b * maxp,
                                      [n - 1 for n in lens])
    scales = {}
    if kv == "int8":
        def quantized(pool):
            vals, s = pa.quantize_kv(pool.reshape(*pool.shape[:3], nh, dh))
            return vals.reshape(pool.shape), s
        (kp, ks), (vp, vs) = quantized(kp), quantized(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    elif kv == "bf16":
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    return q, kp, vp, pt, pos, scales


@pytest.mark.parametrize("layer", [0, NL - 1])
class TestPallasParity:
    """pallas(interpret) on the stored pool at ``layer`` vs the XLA
    reference, elementwise, and vs both per-layer forms, bit for bit."""

    def _check(self, layer, b, nh, dh, ps, maxp, pos, seed=0):
        rng = np.random.RandomState(seed)
        num_pages = 1 + b * maxp
        q, kp, vp, pt, pos = _random_case(rng, b, nh, dh, ps, maxp,
                                          num_pages, pos)
        want = pa._xla_paged_attention(q, kp, vp, pt, pos, layer)
        got, visits = ppa.paged_attention(q, kp, vp, pt, pos, layer=layer,
                                          interpret=True, return_visits=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # the forms without ``layer=``: that layer's pool alone, merged
        # rank 3 or [P, ps, nh, dh] as the benchmark's probe passes it
        for shape in [(num_pages, ps, nh * dh), (num_pages, ps, nh, dh)]:
            one = ppa.paged_attention(q, kp[layer].reshape(shape),
                                      vp[layer].reshape(shape), pt, pos,
                                      interpret=True)
            np.testing.assert_array_equal(np.asarray(one), np.asarray(got))
        return np.asarray(visits)

    def test_ragged_length_mix(self, layer):
        # lengths spanning 1 token .. full capacity across the batch
        self._check(layer, b=4, nh=2, dh=16, ps=4, maxp=5,
                    pos=[0, 6, 13, 19])

    def test_page_boundary_crossings(self, layer):
        # pos exactly at the last slot of a page and first of the next
        self._check(layer, b=4, nh=2, dh=16, ps=4, maxp=4, pos=[3, 4, 7, 8])

    def test_single_token_batch(self, layer):
        self._check(layer, b=3, nh=2, dh=8, ps=8, maxp=6, pos=[0, 0, 0])

    def test_full_pool_batch(self, layer):
        # every sequence at capacity: the stop equals pages_per_slot
        v = self._check(layer, b=3, nh=2, dh=16, ps=4, maxp=3,
                        pos=[11, 11, 11])
        assert (v == 3).all()

    def test_jit_composes(self, layer):
        # the engine calls the kernel from inside a jitted decode step,
        # with the layer a constant of the trace
        rng = np.random.RandomState(3)
        q, kp, vp, pt, pos = _random_case(rng, 2, 2, 16, 4, 3, 7, [2, 9])
        f = jax.jit(lambda *a: ppa.paged_attention(*a, layer=layer,
                                                   interpret=True))
        np.testing.assert_allclose(
            np.asarray(f(q, kp, vp, pt, pos)),
            np.asarray(pa._xla_paged_attention(q, kp, vp, pt, pos, layer)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [0, NL - 1])
@pytest.mark.parametrize("kv", ["bf16", "f32", "int8"])
def test_stored_and_per_layer_forms_agree_on_both_arms(kv, layer):
    """The public op on every pool dtype the engine stores: within an arm
    the stored pool read with ``layer=`` and that layer's pool passed alone
    (rank 4, the probe's form) give the same bits; across arms the same
    attention to rounding."""
    from paddle_tpu.framework.flags import set_flags
    nh, dh, ps = 2, 16, 4
    q, kp, vp, pt, pos, scales = _pool_case(
        kv, np.random.RandomState(11), b=3, nh=nh, dh=dh, ps=ps, maxp=4,
        lens=[1, 7, 16])
    one = {k: v[layer] for k, v in scales.items()}
    outs = {}
    try:
        for impl in ("xla", "pallas"):
            set_flags({"tpu_paged_impl": impl})
            stored = pa.paged_attention(q, kp, vp, pt, pos, layer=layer,
                                        **scales)
            alone = pa.paged_attention(
                q, kp[layer].reshape(-1, ps, nh, dh),
                vp[layer].reshape(-1, ps, nh, dh), pt, pos, **one)
            np.testing.assert_array_equal(np.asarray(stored, np.float32),
                                          np.asarray(alone, np.float32))
            outs[impl] = np.asarray(stored, np.float32)
    finally:
        set_flags({"tpu_paged_impl": "auto"})
    tol = 2e-2 if kv == "bf16" else 1e-5
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=tol,
                               atol=tol)


# A loop turn of the kernel takes block_pages pages: at these shapes 16
# pages of 16 tokens, so a row of 40 pages is two and a half blocks.
BLOCKED = dict(b=3, nh=2, dh=16, ps=16, maxp=40)
# the middle sequence's length; its neighbours (17 and 530 tokens) make the
# copy ring run on from one grid cell into the next
LENGTHS = {"inside-a-block": 300, "at-a-block-edge": 256,
           "first-of-the-next-block": 257, "one-page": 16, "one-token": 1,
           "full-capacity": 640, "past-capacity": 700}


def _fetched(lens, ps, maxp):
    return np.minimum((np.asarray(lens) + ps - 1) // ps, maxp)


@pytest.mark.parametrize("layer", [0, NL - 1])
@pytest.mark.parametrize("kv", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("case", LENGTHS)
def test_blocked_walk_matches_the_xla_arm(case, kv, layer):
    """Sequences that end inside a block, at its edge, after one page and
    at capacity, on every pool dtype, at the first and last layer: the
    XLA arm's attention to rounding, and ``visits`` the pages the
    sequence has, clamped to its row."""
    c = BLOCKED
    assert ppa.block_pages(c["ps"], c["nh"] * c["dh"], 2) == 16
    lens = [17, LENGTHS[case], 530]
    q, kp, vp, pt, pos, scales = _pool_case(
        kv, np.random.RandomState(5), lens=lens, **c)
    want = pa._xla_paged_attention(q, kp, vp, pt, pos, layer, **scales)
    got, visits = ppa.paged_attention(q, kp, vp, pt, pos, layer=layer,
                                      interpret=True, return_visits=True,
                                      **scales)
    tol = 2e-2 if kv == "bf16" else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_array_equal(np.asarray(visits)[:, 0],
                                  _fetched(lens, c["ps"], c["maxp"]))


@pytest.mark.parametrize("kv", ["bf16", "f32", "int8"])
def test_unused_table_entries_are_never_dereferenced(kv):
    """Entries of a table row past the pages its sequence has may hold
    anything, out-of-range page ids too: on the chip a copy from such a
    page halts the core. The kernel clamps a page id into the pool, so a
    dereference here would read the pool's first or last page: both are
    poisoned, and one fetched row of them would turn the output NaN (a
    masked position's 0 times NaN). An int8 pool's poison sits in its
    scales, whose window the op zeroes past the pages a sequence has:
    there the wild ids show that the window's gather stays sound."""
    c = BLOCKED
    lens = [17, 300, 1, 640]
    b = len(lens)
    q, kp, vp, pt, pos, scales = _pool_case(
        kv, np.random.RandomState(6), lens=lens, **{**c, "b": b})
    last = kp.shape[1] - 1                  # the table names neither
    pt = jnp.asarray(1 + np.random.RandomState(6).permutation(last - 1)
                     .reshape(b, c["maxp"]).astype(np.int32))
    if kv == "int8":
        scales = {k: v.at[:, jnp.asarray([0, last])].set(jnp.nan)
                  for k, v in scales.items()}
    else:
        kp, vp = (x.at[:, jnp.asarray([0, last])].set(jnp.nan)
                  for x in (kp, vp))
    used = np.arange(c["maxp"])[None, :] < _fetched(
        lens, c["ps"], c["maxp"])[:, None]
    wild = np.where(used, np.asarray(pt),
                    np.resize([2 ** 30, -5, last + 1], used.shape))
    want = pa._xla_paged_attention(
        q, kp, vp, jnp.asarray(np.where(used, np.asarray(pt), 1)), pos, 1,
        **scales)
    got, visits = ppa.paged_attention(
        q, kp, vp, jnp.asarray(wild, jnp.int32), pos, layer=1,
        interpret=True, return_visits=True, **scales)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    tol = 2e-2 if kv == "bf16" else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_array_equal(np.asarray(visits)[:, 0],
                                  _fetched(lens, c["ps"], c["maxp"]))


def test_f32_query_on_a_bf16_pool_loses_nothing():
    """The single-pass form takes the left operand as bf16 pieces that sum
    to it: an f32 query against a bf16 pool agrees with the f32 reference
    to f32 rounding, not to bf16's."""
    c = BLOCKED
    q, kp, vp, pt, pos, _ = _pool_case(
        "f32", np.random.RandomState(7), lens=[17, 300, 530], **c)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    want = pa._xla_paged_attention(q, kp, vp, pt, pos, 1)
    got = ppa.paged_attention(q, kp, vp, pt, pos, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    x = jnp.asarray(np.random.RandomState(8).randn(4, 9).astype(np.float32))
    pieces = ppa._bf16_pieces(x)
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    np.testing.assert_array_equal(
        np.asarray(sum(p.astype(jnp.float32) for p in pieces)),
        np.asarray(x))


@pytest.mark.parametrize("shape,pages", [
    ((16, 1024, 2), 16),      # GPT-2 medium's bf16 pool: 256 tokens a turn
    ((16, 768, 1), 16),       # int8
    ((16, 1280, 4), 16),      # GPT-2 large in float32: still inside VMEM
    ((16, 4096, 4), 4),       # a wide float32 pool: halved to fit
    ((512, 1024, 2), 1),      # a page larger than a block: one page
    ((4, 32, 4), 64),
])
def test_block_follows_the_pools_shape(shape, pages):
    assert ppa.block_pages(*shape) == pages


def test_every_build_counts_the_block_it_chose():
    rng = np.random.RandomState(9)
    q, kp, vp, pt, pos = _random_case(rng, 2, 2, 16, 4, 3, 7, [2, 9])
    counter = metrics.counter("kernel.paged_block.64")
    before = counter.value
    ppa.paged_attention(q, kp, vp, pt, pos, layer=0, interpret=True)
    assert counter.value == before + 1


class TestLengthAwareStop:
    """Compute/DMA scale with pos, not pages_per_slot — the ragged claim."""

    def test_trip_count_tracks_pos_not_capacity(self):
        rng = np.random.RandomState(1)
        b, nh, dh, ps, maxp = 4, 2, 16, 4, 16        # 64-token slots
        pos = [0, 5, 17, 63]
        q, kp, vp, pt, posj = _random_case(rng, b, nh, dh, ps, maxp,
                                           1 + b * maxp, pos)
        _, visits = ppa.paged_attention(q, kp, vp, pt, posj, layer=1,
                                        interpret=True, return_visits=True)
        visits = np.asarray(visits)
        want = np.array([(p + ps) // ps for p in pos])   # ceil((pos+1)/ps)
        for h in range(nh):
            np.testing.assert_array_equal(visits[:, h], want)
        # a 1-token sequence touches ONE page of its 16-page slot
        assert visits[0, 0] == 1 and visits[0, 0] < maxp

    def test_walk_never_leaves_the_page_table_row(self):
        """A position past the slot's capacity must not index past the
        page-table row: on the chip that page id is garbage and the DMA it
        feeds halts the core (interpret mode clamps and hides it)."""
        rng = np.random.RandomState(4)
        b, nh, dh, ps, maxp = 2, 2, 16, 4, 4         # 16-token slots
        q, kp, vp, pt, posj = _random_case(rng, b, nh, dh, ps, maxp,
                                           1 + b * maxp, [15, 40])
        _, visits = ppa.paged_attention(q, kp, vp, pt, posj, layer=1,
                                        interpret=True, return_visits=True)
        np.testing.assert_array_equal(np.asarray(visits)[:, 0], [maxp, maxp])

    def test_pages_needed_formula(self):
        assert int(ppa.pages_needed(jnp.int32(0), 4)) == 1
        assert int(ppa.pages_needed(jnp.int32(3), 4)) == 1
        assert int(ppa.pages_needed(jnp.int32(4), 4)) == 2
        assert int(ppa.pages_needed(jnp.int32(15), 4)) == 4


class TestDispatchSwitch:
    """FLAGS_tpu_paged_impl routing + the impl observability counter."""

    @pytest.fixture(autouse=True)
    def _restore_flag(self):
        from paddle_tpu.framework.flags import set_flags
        yield
        set_flags({"tpu_paged_impl": "auto"})

    def _case(self):
        rng = np.random.RandomState(2)
        return _random_case(rng, 2, 2, 8, 4, 3, 7, [2, 9])

    def test_explicit_impls_agree(self):
        from paddle_tpu.framework.flags import set_flags
        q, kp, vp, pt, pos = self._case()
        set_flags({"tpu_paged_impl": "xla"})
        a = pa.paged_attention(q, kp, vp, pt, pos, layer=1)
        set_flags({"tpu_paged_impl": "pallas"})
        b = pa.paged_attention(q, kp, vp, pt, pos, layer=1)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_layer_past_the_stack_is_refused_while_tracing(self):
        # on the chip it would be a wild DMA (a halted core), not an error
        q, kp, vp, pt, pos = self._case()
        with pytest.raises(IndexError, match=f"layer {NL} of a pool"):
            pa.paged_attention(q, kp, vp, pt, pos, layer=NL)

    def test_impl_counter_counts_dispatches(self):
        from paddle_tpu.framework.flags import set_flags
        q, kp, vp, pt, pos = self._case()
        set_flags({"tpu_paged_impl": "xla"})
        before = metrics.counter("paged_attention.impl.xla").value
        pa.paged_attention(q, kp, vp, pt, pos, layer=1)
        assert metrics.counter("paged_attention.impl.xla").value == before + 1
        set_flags({"tpu_paged_impl": "pallas"})
        before_p = metrics.counter("paged_attention.impl.pallas").value
        pa.paged_attention(q, kp, vp, pt, pos, layer=1)
        assert metrics.counter(
            "paged_attention.impl.pallas").value == before_p + 1

    def test_auto_pins_xla_off_tpu(self):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.kernels import registry
        registry.clear()
        set_flags({"tpu_paged_impl": "auto"})
        q, kp, vp, pt, pos = self._case()
        before = metrics.counter("paged_attention.impl.xla").value
        pa.paged_attention(q, kp, vp, pt, pos, layer=1)
        assert metrics.counter("paged_attention.impl.xla").value == before + 1
        key = [k for k in registry.table() if k[0] == "paged"]
        assert key and registry.table()[key[0]][0] == "xla"
        registry.clear()


class TestPagedAutotune:
    @staticmethod
    def _select():
        from paddle_tpu.kernels import registry
        key, measure = pa._paged_selection(2, 4, 4, 2, 8, jnp.float32)
        return registry.dispatch("paged_attention", key=key, measure=measure)

    def test_tpu_measures_both_candidates(self, monkeypatch):
        from paddle_tpu.kernels import registry
        registry.clear()
        monkeypatch.setattr(registry, "backend", lambda: "tpu")
        measured = []

        def fake_measure(fn, args, warmup=1, reps=3):
            measured.append(len(measured))
            return [5.0, 1.0][len(measured) - 1]     # pallas wins

        monkeypatch.setattr(registry, "measure", fake_measure)
        assert self._select() == "pallas"
        assert len(measured) == 2        # both candidates timed
        # cached: second lookup measures nothing
        assert self._select() == "pallas" and len(measured) == 2
        registry.clear()

    def test_cpu_pins_xla_without_measuring(self, monkeypatch):
        from paddle_tpu.kernels import registry
        registry.clear()
        monkeypatch.setattr(
            registry, "measure",
            lambda *a, **kw: pytest.fail("one candidate: nothing to time"))
        assert self._select() == "xla"
        registry.clear()


class TestOverflowToTrash:
    """Regression: pos past the slot's capacity used to be CLIPPED into the
    last page, silently corrupting its KV — it must spill to TRASH_PAGE."""

    def test_token_coords_overflow_routes_to_trash(self):
        ps, maxp = 4, 2                               # capacity 8 tokens
        pt = jnp.asarray([[1, 2]], jnp.int32)
        active = jnp.asarray([True])
        page, off = pa.token_page_coords(pt, jnp.asarray([8], jnp.int32),
                                         active, ps)
        assert int(page[0]) == pa.TRASH_PAGE          # NOT page 2
        # in-range positions still map normally
        page, _ = pa.token_page_coords(pt, jnp.asarray([7], jnp.int32),
                                       active, ps)
        assert int(page[0]) == 2

    def test_token_write_overflow_leaves_last_page_intact(self):
        ps, maxp = 2, 2
        kp = jnp.zeros((1, 4, ps, 4))                 # [nl, P, ps, nh*dh]
        vp = jnp.zeros_like(kp)
        k = jnp.ones((1, 1, 4))
        pt = jnp.asarray([[1, 2]], jnp.int32)
        kp2, _ = pa.write_token_kv(kp, vp, k, k, pt,
                                   jnp.asarray([4], jnp.int32),   # capacity!
                                   jnp.asarray([True]), 0)
        assert np.asarray(kp2)[0, pa.TRASH_PAGE].sum() == 4
        assert np.asarray(kp2)[0, 1:].sum() == 0      # page 2 NOT corrupted

    def test_prompt_coords_overflow_routes_to_trash(self):
        ps = 2
        pt = jnp.asarray([1, 2], jnp.int32)           # capacity 4 tokens
        page, _ = pa.prompt_page_coords(pt, jnp.int32(6), 6, ps)
        assert np.asarray(page)[:4].tolist() == [1, 1, 2, 2]
        assert (np.asarray(page)[4:] == pa.TRASH_PAGE).all()
