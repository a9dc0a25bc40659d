"""In-repo authored Pallas TPU kernels.

The counterpart of the reference's hand-written fused CUDA kernels
(`paddle/phi/kernels/fusion/`, `paddle/fluid/operators/fused/`): where the
reference writes .cu files per op, this framework authors Mosaic-compiled
Pallas kernels for the ops XLA does not already fuse optimally.

Kernels:
- :mod:`flash_attention` — online-softmax attention forward
  (≈ `fused_attention_op.cu` but flash; the reference has NO flash kernel,
  SURVEY §5.7).
- :mod:`paged_attention` — ragged paged-attention decode step (arxiv
  2604.15464): grid over sequences, a block of a sequence's pages a loop
  turn with the next turns' page copies in flight, only the pages of each
  sequence's true length fetched. The serving engine's hot kernel
  (`FLAGS_tpu_paged_impl`).
- :mod:`prefill_attention` — the ragged PREFILL twin (r15): grid over
  chunk-row blocks, scalar-prefetched (start, valid), page walk
  bounded by the request's true uncached tail — chunked prefill, prefix
  tails, and the PTKS1 prefill-worker stream all ride it
  (`FLAGS_tpu_prefill_impl`; op `prefill_attention`, registered and
  measured in `kernels/paged_attention.py`).
- :mod:`fused_ce` — forward of the fused LM-head + cross-entropy: one
  product whose tiles give their row maximum, ``sum(exp)`` and label's
  logit while in VMEM (≈ `c_softmax_with_cross_entropy_op.cu`; the custom
  VJP and the selection live in `kernels/fused_ce.py`).
- :mod:`latent_prefill` — a prefill chunk's latent (MLA) attention per
  head: grid over groups of heads and blocks of keys, a block's keys and
  values expanded from the latent rows and its scores kept in VMEM under
  the causal mask or the indexer's selection, blocks past the furthest
  query skipped (the arm and its plan are chosen in `kernels/mla.py`).
- :mod:`latent_decode` — a decode step's latent (MLA) attention in the
  absorbed form over the paged latent pool: grid over sequences, pages
  copied from the pool where they lie into a ring of VMEM buffers (a run
  of consecutive page ids by one copy), each sequence to its own length,
  a block's scores kept in VMEM (the arm and its plan are chosen in
  `kernels/mla.py`).
- :mod:`grouped_experts` — the routed experts' two products over rows
  sorted by expert: grid over the (row tile, expert) pairs that share a
  row, read off the group sizes by scalar prefetch; an unhit expert not
  read, a hit one read once, the gated activation applied on the float32
  result in VMEM (the arm is chosen in `kernels/moe.py`).
- :mod:`fused_layernorm` — single-pass layernorm fwd + analytic bwd
  (≈ `fused_layernorm` kernels in `phi/kernels/fusion/`).

All kernels run under ``interpret=True`` on CPU for tests; on TPU they compile
through Mosaic (`tests/test_tpu_compile.py` asks the chip's compiler for each).
"""
from paddle_tpu.kernels.pallas.flash_attention import flash_attention  # noqa: F401
from paddle_tpu.kernels.pallas.fused_layernorm import fused_layer_norm  # noqa: F401
from paddle_tpu.kernels.pallas import paged_attention as paged_attention  # noqa: F401,PLC0414
from paddle_tpu.kernels.pallas import prefill_attention as prefill_attention  # noqa: F401,PLC0414
