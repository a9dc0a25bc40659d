"""Authored Pallas TPU kernels of the routed experts' two products over rows
sorted by expert: the ``pallas`` arm of `kernels/moe.py::routed_experts`.

The rows arrive as `kernels/moe.py::_sorted_rows` leaves them: every
assignment that landed on a held expert, expert by expert (``sizes`` rows
each, an expert that got none a group of none), then the rows that are no
held expert's, in no group. A group's rows go through that expert's two
matrices and no other's; no row is dropped and there is no capacity.
`jax.lax.ragged_dot` does the same in two custom calls that walk every row
tile of the buffer; here

- **the visits come from ``sizes`` by scalar prefetch** (`_visits`): one
  grid step a (row tile, expert) pair that shares a row. A tile past the
  last group is never visited, an expert with no row is never read, a tile
  that two groups share is visited once for each with the other's rows
  masked. The number of visits is the grid's own (traced) extent;
- **an expert's matrix is read ONCE**: a block holds the whole contraction
  (``[d, tn]`` columns of ``w1[e]``, ``[f, tn]`` of ``w2[e]``), so the next
  row tile of the same expert finds the block it needs already in VMEM and
  the pipeline fetches nothing; the column tiles are the OUTER grid axis, so
  a row tile that two groups share is revisited in consecutive steps and its
  output block stays where it is between them;
- **the first product** takes the activated and the linear half of the same
  columns (two views of ``w1``), holds both float32 results in VMEM, clamps
  them with ``limit``, multiplies ``silu(u) * v`` by the row's gate there and
  writes ``x.dtype [rows, f]``; **the second** accumulates in float32 and
  writes ``x.dtype [rows, d]``: the roundings of the ``grouped`` arm, which
  is the oracle of this one (tests/test_moe_pallas.py).

What is not written is not read: a row in no group is masked by its gate of
zero after the call, as the ``grouped`` arm does, and a row tile nobody
visited is never fetched. Both results are ``x.dtype``, never float32: a
trace names a Mosaic call by its result, and the accepted shares add every
``custom-call f32[rows, width]`` they find to the experts' scope
(benchmarks/layer_metrics/{kimi,solar}_experts_roofline_share.json).

The tiles are read off the shapes (`plan`): rows a tile from the rows a
call makes, columns a block from what the double-buffered blocks may hold of
``VMEM_BUDGET``. A smaller row tile masks less and re-reads fewer rows, a
larger one latches an expert's columns into the MXU less often: a layer alone,
512 tokens at 8 a token, read 2.42 / 2.27 / 2.24 / 2.27 ms at 16 / 32 / 64 /
128 rows with 40 experts ``[4096, 1280]`` and 2.85 / 2.70 / 2.62 / 2.63 with
32 of ``[5120, 1536]``; a decode step's few rows read the same at 8, 16 and 32
(1.57 / 1.56 / 1.54 at 48 tokens; my chip run, PR 51). A budget of 16, 40 or
80 MB moved no reading by more than 4%. Inside a step program the two kernels
ran at 84-86% of what reading the held experts takes in a chunk and at the
read of the hit ones in a decode step; what the call costs beyond them is
the plan's sort and its two gathers of rows (15% of the call). On a TPU the
kernels compile through Mosaic (tests/test_tpu_compile*.py); elsewhere they
run in the interpreter.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope
from paddle_tpu.kernels.moe import _gated

_LANES = 128
STEP_ROWS = 16          # rows a tile where a call makes few (a decode step)
CHUNK_ROWS = 64         # and where it makes many (a chunk)
MANY_ROWS = 2048        # rows a call from which the larger tile is taken
VMEM_BUDGET = 40 << 20  # bytes the double-buffered weight blocks may take


class Plan(NamedTuple):
    """``tm`` rows a tile, ``up`` / ``down`` columns a block of the first /
    second product."""
    tm: int
    up: int
    down: int


def _columns(width, depth, itemsize, views):
    """The widest block of ``width`` columns, a whole number of lane tiles
    that divides it, of which ``views`` double-buffered blocks ``depth``
    deep fit the budget; ``width`` itself where it is no whole lane tiles
    (the interpreter's tiny sizes)."""
    if width % _LANES:
        return width
    fits = [c for c in range(_LANES, width + 1, _LANES) if width % c == 0
            and 2 * views * depth * c * itemsize <= VMEM_BUDGET]
    return max(fits, default=_LANES)


def plan(rows, d, f, itemsize, tm=None, up=None, down=None):
    """The tiles of a call of ``rows`` rows through experts ``[d, 2 f]`` and
    ``[f, d]``; ``tm`` / ``up`` / ``down`` override them (tests and
    readings)."""
    return Plan(tm or (STEP_ROWS if rows < MANY_ROWS else CHUNK_ROWS),
                up or _columns(f, d, itemsize, 2),
                down or _columns(d, f, itemsize, 1))


def _visits(sizes, tiles, tm):
    """The grid's steps from ``sizes`` [held] over ``tiles`` row tiles of
    ``tm``: (row tile [V], expert [V], row offsets [held + 1], steps [1]),
    int32, V = tiles + held the most a call can make. Step v visits one row
    tile of one expert that has a row in it, experts in order, an expert's
    tiles in order: a tile that groups share is visited by each in
    consecutive steps."""
    held = sizes.shape[0]
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(spans, dtype=jnp.int32)
    v = jnp.arange(tiles + held, dtype=jnp.int32)
    expert = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                                 dtype=jnp.int32), held - 1)
    tile = first[expert] + v - (upto - spans)[expert]
    return (jnp.clip(tile, 0, tiles - 1), expert,
            jnp.concatenate([jnp.zeros(1, jnp.int32), ends]), upto[-1:])


def _store(o_ref, new, tile_ref, expert_ref, off_ref):
    # the rows of this step's expert in this step's tile take ``new``; the
    # others keep what an earlier visit of the tile left, zeros on the first
    v = pl.program_id(1)
    tile, e = tile_ref[v], expert_ref[v]

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    row = tile * o_ref.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (row >= off_ref[e]) & (row < off_ref[e + 1])
    o_ref[...] = jnp.where(mine, new, o_ref[...].astype(jnp.float32)) \
        .astype(o_ref.dtype)


def _up_kernel(tile_ref, expert_ref, off_ref, x_ref, g_ref, wu_ref, wv_ref,
               o_ref, *, limit):
    x = x_ref[...]
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    v = jnp.dot(x, wv_ref[...], preferred_element_type=jnp.float32)
    _store(o_ref, _gated(u, v, g_ref[...], limit), tile_ref, expert_ref,
           off_ref)


def _down_kernel(tile_ref, expert_ref, off_ref, a_ref, w_ref, o_ref):
    _store(o_ref, jnp.dot(a_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32),
           tile_ref, expert_ref, off_ref)


@functools.partial(jax.jit, static_argnames=("plan", "limit", "interpret"))
def grouped_experts(rows, gate, w1, w2, sizes, *, plan, limit=None,
                    interpret=False):
    """``rows`` [M, d] sorted by expert (M whole tiles of ``plan.tm``),
    ``gate`` [M] float32 (zero for a row in no group), ``w1`` [held, d, 2 f],
    ``w2`` [held, f, d], ``sizes`` [held] int32 rows an expert. Returns [M,
    d] in ``rows``' type: each group's rows through its expert, gate folded
    in; a row in no group holds whatever was there (mask it by its gate)."""
    m, d = rows.shape
    held, f = w2.shape[:2]
    tm, tn, tc = plan
    if m % tm or f % tn or d % tc or w1.shape != (held, d, 2 * f):
        raise ValueError(f"rows {rows.shape}, experts {w1.shape} / "
                         f"{w2.shape}: not whole tiles of {plan}")
    tile, expert, offsets, steps = _visits(sizes.astype(jnp.int32),
                                           m // tm, tm)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            # in sequence: a tile two groups share is revisited
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weight blocks (`plan`), the row blocks and a step's
            # float32 results beside them
            vmem_limit_bytes=VMEM_BUDGET + (24 << 20))}

    def call(kernel, operands, in_specs, width, cols):
        with x64_off_scope():
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=3,
                    grid=(width // cols, steps[0]),
                    in_specs=in_specs,
                    out_specs=pl.BlockSpec(
                        (tm, cols), lambda j, v, t, e, o: (t[v], j))),
                out_shape=jax.ShapeDtypeStruct((m, width), rows.dtype),
                interpret=interpret, **params,
            )(tile, expert, offsets, *operands)

    def row_block(width):
        return pl.BlockSpec((tm, width), lambda j, v, t, e, o: (t[v], 0))

    halves = f // tn
    act = call(
        functools.partial(_up_kernel, limit=limit),
        (rows, gate.astype(jnp.float32)[:, None], w1, w1),
        [row_block(d), row_block(1),
         pl.BlockSpec((None, d, tn), lambda j, v, t, e, o: (e[v], 0, j)),
         pl.BlockSpec((None, d, tn),
                      lambda j, v, t, e, o: (e[v], 0, halves + j))],
        f, tn)
    return call(
        _down_kernel, (act, w2),
        [row_block(f),
         pl.BlockSpec((None, f, tc), lambda j, v, t, e, o: (e[v], 0, j))],
        d, tc)
