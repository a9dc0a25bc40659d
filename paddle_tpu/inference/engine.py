"""Batched decode engine: paged KV cache + bucketed prefill + continuous
batching, with a DE-SYNCHRONIZED step loop.

`GPTForCausalLM.fast_generate` decodes ONE request per compiled program with
a dense per-request cache; a serving process needs to decode MANY requests
of different lengths concurrently without recompiling. This engine is the
host-side scheduler the MPMD pipeline work (arxiv 2412.14374) argues for —
Python owns admission/retirement, the device runs fixed-shape steps:

- **Paged KV cache** (arxiv 2604.15464): one fixed pool of token pages
  (`kernels/paged_attention.py`) shared by all slots; a host-side allocator
  hands pages to sequences at admission and reclaims them at retirement.
- **The model seam** (`inference/family.py`): the engine imports no model.
  `model.engine_family()` supplies the step functions the programs trace
  (`models/gpt.py`, `models/phi4flash.py`) and the kinds and shapes of
  state a sequence keeps. Three kinds live in the cache manager: PAGED K/V
  that grows (the pool below), WINDOW K/V (a ring of `window / page + 1`
  pages a slot, constant in sequence length) and RECURRENT state (fixed
  size a slot; read as zero by the chunk that starts a sequence, carried
  from chunk to chunk and into decode). For a model that keeps the last
  two, whatever would restore a sequence from pages alone — prefix
  reuse, speculation, hand-off, migration, tier spill — refuses by typed
  error (docs/SERVING.md "The model seam").
- **The cache seam** (`inference/cache.py`, `inference/programs.py`): all
  of that state — pools, an int8 pool's scales, rings, recurrent state,
  the sampler's key chains — is ONE `DeviceCache`. Every step program is
  ``exe(params, cache, *small) -> (*lead, cache)`` with the cache donated
  whole; the engine replaces its cache at every call and never names a
  leaf. The page allocator lives with it; the prefix store and the tiers
  are still the engine's.
- **Fixed-shape decode step**: every step runs the family's `decode_step` on
  all `max_slots` slots — active or not — in ONE device call. Slot churn
  only changes the *contents* of the page table / active mask, never a
  shape, so after warmup there are ZERO recompiles (continuous batching;
  guarded by tests/test_no_retrace.py).
- **Bucketed prefill**: prompts are padded to the next power-of-two bucket,
  so prefill compiles O(log max_seq_len) programs instead of one per
  prompt length. Programs are AOT-compiled (`jit.lower().compile()`), so a
  shape drift RAISES instead of silently recompiling.
- **Decode-priority chunked prefill** (`EngineConfig.prefill_chunk_tokens`):
  a long prompt is split into fixed-size chunks, ONE chunk enqueued per
  step AFTER the decode dispatch, so in-flight decodes keep their token
  cadence instead of stalling for the whole prefill wall — the first rung
  of prefill/decode disaggregation (ROADMAP item 1). The chunk program is
  one AOT shape regardless of prompt length.
- **Page-granular KV handoff** (`prefill_export` / `import_request` /
  :class:`KVHandoff`): a request's page-table rows + page contents
  serialize into a replica-independent blob, so a prefill finished on one
  replica resumes decode on another token-identically — the transfer
  primitive full disaggregation rides (docs/SERVING.md).
- **Live request migration** (`drain(migrate=True)` / `take_migrated` /
  `submit_import`): a draining replica no longer waits out its in-flight
  work — the driver harvests the in-flight window and exports every live
  slot MID-DECODE as a `KVHandoff` (context = prompt + delivered tokens
  whose KV is resident; the last sampled token rides as the seed, exactly
  like `prefill_export`'s first token), detaching slots and pages without
  finishing the request futures; queued / chunk-prefilling requests leave
  as cold (prompt-only) items. The receive side is a thread-safe
  `submit_import` MAILBOX the peer's driver applies between fixed-shape
  steps — the same discipline as cancellation — so migration never
  perturbs a program shape and the resumed decode is TOKEN-IDENTICAL to
  an uninterrupted run (docs/SERVING.md "Live migration").
- **Prefix caching** (`EngineConfig.prefix_cache`): full prompt-prefix
  pages are rolling-hashed into a per-engine prefix store over the page
  pool; a submit whose leading pages match attaches them by page-table
  reference (refcounted copy-on-write sharing — the page holding the last
  prompt token is always recomputed, never shared) and prefills ONLY the
  uncached tail through the chunk program. Refcount-0 cached pages stay
  resident and are LRU-evicted under pool pressure; eviction can never
  touch a live slot's pages (docs/SERVING.md "Prefix caching").
- **KV tiering** (`EngineConfig.kv_host_tier_bytes` /
  ``kv_disk_tier_bytes``): a capacity hierarchy under the prefix store —
  eviction DEMOTES a page's contents (values + int8 scales) into a
  bounded host-RAM tier and from there to a bounded disk tier, framed
  ``PTKT1`` blobs keyed by the same page-chain hashes (`kv_tiers.py`);
  a submit that misses HBM but hits a tier RE-UPLOADS the pages with one
  batched `import_pages` scatter and prefills only the remaining tail —
  token-identical to a cold prefill, zero new programs. Corrupt or stale
  tier entries refuse typed and read as misses; the serve STATS export
  (`tier_hashes`) advertises spilled chains so the router's fleet
  directory routes them to the replica that can re-upload
  (docs/SERVING.md "KV tiering").
- **Speculative decoding** (`EngineConfig.speculate_k`): a self-drafting
  n-gram proposer (suffix lookup over each slot's own tokens, zero extra
  model) drafts up to k tokens per slot per step; ONE fixed-shape verify
  program (`models/gpt.py::verify_step`) scores all k+1 positions over the
  paged gather and accepts the longest matching draft prefix plus one
  corrected token — 1..k+1 tokens per step, bit-identical to plain greedy
  decode (parity-tested). Rollback of rejected tokens is host-side length
  bookkeeping: their stale KV sits past every live position and is
  rewritten before any query attends it.
- **De-synchronized hot path**: the per-slot host mirrors (token, length,
  flags, page-table row) are fused into ONE packed int32 upload per step
  (`engine.h2d_transfers` counts them — exactly one per step); sampled
  tokens chain step-to-step ON DEVICE, and their readback is DEFERRED — up
  to ``EngineConfig.inflight`` steps stay in flight before the host blocks
  on the oldest step's token ids (`engine.d2h_transfers`; the ONLY blocking
  readback in the loop). Host admission/retirement bookkeeping runs while
  the device chews on the just-dispatched step; each working step is an
  `engine.step` span whose children `engine.admit`, `engine.dispatch`,
  `engine.prefill_launch` and `engine.harvest` (the blocking readback
  alone) put the overlap on the timeline, beside the device's ops while a
  `jax.profiler` session runs (docs/OBSERVABILITY.md "Spans").

All compiled programs take the weights as inputs — `refresh_params` swaps
them without recompiling. The engine is greedy-only by design: batched
sampling needs per-slot PRNG threading, which rides on top of this layout
(docs/SERVING.md).

Thread model: `submit()` is safe from any thread; `step()` /
`run_until_idle()` / `serve_loop()` must run on ONE driver thread (the
serve process dedicates a thread; tests/bench call them inline).
"""
from __future__ import annotations

import hashlib
import json
import queue as _queue
import struct
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.framework import compile_cache
from paddle_tpu.inference.cache import DeviceCache, PageAllocator
from paddle_tpu.inference.errors import (Cancelled, DeadlineExceeded,
                                         HandoffCorrupt, Overloaded,
                                         PageLayoutUnsupported,
                                         RecurrentStateUnsupported,
                                         from_wire)
from paddle_tpu.inference.family import family_of
from paddle_tpu.inference.programs import (FLAG_ACTIVE, FLAG_FRESH,
                                           decode_program, prefill_program,
                                           prefill_upload, step_upload,
                                           verify_program)
from paddle_tpu.kernels.paged_attention import TRASH_PAGE
from paddle_tpu.observability import metrics
from paddle_tpu.observability.flight_recorder import (Watchdog,
                                                      default_deadline,
                                                      flight)
from paddle_tpu.observability.tracing import RequestTrace, new_span_id
from paddle_tpu.observability.usage import emit_request as _emit_usage
from paddle_tpu.testing import faults

# JAX's trace, lower and compile events land on the span ring from here on,
# under whatever span is open (`engine.compile:<program>`)
compile_cache.listen()

__all__ = ["EngineConfig", "PageAllocator", "GenerateRequest", "DecodeEngine",
           "KVHandoff", "MigrationItem", "DeadlineExceeded", "Cancelled",
           "Overloaded", "HandoffCorrupt", "pack_migration",
           "unpack_migration"]


@dataclass
class EngineConfig:
    """Scheduler knobs (docs/SERVING.md).

    page_size    : tokens per KV page (16 keeps page waste < 1 page/seq
                   while the page table stays small)
    max_slots    : decode batch width B — every step computes all B slots
    max_seq_len  : per-sequence capacity (prompt + generated), rounded up
                   to whole pages; defaults to the model's position table
    num_pages    : total pool size; default fits max_slots full sequences
                   plus the reserved trash page
    min_bucket   : smallest prefill bucket (pow-2 padding starts here)
    eos_id       : optional token id that retires a slot early
    donate       : donate cache buffers into the step program (defaults to
                   on for real accelerators, off on CPU where PJRT ignores
                   donation and warns)
    inflight     : decode steps (and prefills' first tokens, each an entry
                   of the same fifo) kept in flight before the host blocks
                   on the oldest one's sampled tokens (deferred readback; 1
                   restores the synchronous loop). EOS detection lags by up
                   to this many steps — the surplus tokens are discarded at
                   harvest, never delivered
    prefill_chunk_tokens : when set, prompts LONGER than this are prefilled
                   in fixed-size chunks of this many tokens, ONE chunk per
                   engine step scheduled AFTER the decode dispatch
                   (decode-priority): running requests keep decoding while
                   a long prompt fills. None (default) keeps the one-shot
                   bucketed prefill; prompts <= the chunk size always take
                   the one-shot path
    prefix_cache : share full prompt-prefix pages copy-on-write across
                   requests (docs/SERVING.md "Prefix caching"): a submit
                   whose leading pages hash-match an earlier prompt's
                   attaches them by page-table reference and prefills ONLY
                   the uncached tail. Refcount-0 cached pages stay resident
                   and are LRU-evicted under pool pressure. Per-request
                   opt-out via ``submit(..., cache=False)``
    kv_host_tier_bytes : KV tiering (docs/SERVING.md "KV tiering"): bound
                   on a host-RAM spill tier under the HBM prefix store.
                   When set, a prefix page evicted under pool pressure
                   DEMOTES — its contents (values + int8 scales) spill as
                   a checksummed ``PTKT1`` blob keyed by the same rolling
                   page-chain hash — instead of discarding; a later submit
                   that misses HBM but hits the tier RE-UPLOADS the pages
                   (one batched device transfer) and prefills only the
                   remaining tail, token-identical to a cold prefill.
                   None/0 (default) disables tiering entirely
    kv_disk_tier_bytes : bound on the disk tier below the host tier (host
                   LRU overflow demotes here; disk overflow discards).
                   Works alone too — spills go straight to disk. None/0
                   (default) disables the disk tier
    kv_disk_tier_dir : directory for disk-tier blobs (OWNED by the
                   engine's tier store — stale ``.ptkt`` files are purged
                   at construction). None with a disk bound set uses a
                   fresh temp directory
    speculate_k  : when set (>= 1), every decode step drafts up to k tokens
                   per slot from a self-drafting n-gram proposer and
                   verifies all k+1 positions in ONE fixed-shape program
                   (`models/gpt.py::verify_step`) — between 1 and k+1
                   tokens emitted per step, bit-identical to plain greedy
                   decode. Readback is synchronous in this mode (the host
                   needs each step's accepted tokens to draft the next),
                   so ``inflight`` does not apply. Per-request opt-out via
                   ``submit(..., speculate=False)``
    max_queue_depth  : admission control (docs/ROBUSTNESS.md): a submit
                   arriving with this many requests already queued fails
                   FAST with a typed ``Overloaded`` error instead of
                   joining an unbounded queue — the router resubmits it
                   elsewhere, the client gets a bounded answer. None
                   (default) keeps the queue unbounded
    max_queue_tokens : same, bounding the SUM of queued prompt tokens
                   (a few giant prompts can overload a queue long before
                   max_queue_depth does). Backlog-only: an empty queue
                   always admits, so one prompt larger than the bound is
                   never shed with a retry-forever Overloaded
    kv_dtype     : page-pool storage dtype: "native" (default — follow the
                   weights), "f32", "bf16", or "int8"
                   (docs/QUANTIZATION.md). "int8" stores pages int8 with a
                   per-token-slot per-head f32 scale pool [nl, P, ps, nh]
                   written by the same scatters; every read (XLA gather or
                   Pallas page DMA) dequantizes in-register after the copy.
                   ~3.8x more tokens per pool byte at dh=64, so a fixed
                   byte budget admits ~2x+ the concurrent slots
                   (bench_quant asserts >= 1.9x); token parity vs f32 is
                   bounded, not bit-exact — all int8 PATHS (one-shot /
                   chunked / prefix-hit / handoff / speculative) stay
                   token-identical to each other
    weight_dtype : "native" (default) or "int8": convert the GPT matmul
                   leaves to int8 + per-output-channel scales at engine
                   construction (quantization/serving.py), dequantized at
                   use inside the same AOT programs — same program count,
                   zero extra recompiles (tests/test_no_retrace.py)
    sampling     : enable the FUSED ON-DEVICE SAMPLER (kernels/
                   sampling.py, registry op `fused_sampling`): every step
                   program applies temperature/top-k + the categorical
                   draw to the logits ON DEVICE with per-slot PRNG key
                   chains, so `submit(..., temperature=, top_k=, seed=)`
                   samples with ZERO extra host round-trips —
                   `engine.d2h_transfers` stays token-harvest-only and
                   `engine.logits_readback` pins to 0. Per-slot params
                   ride the packed state upload (one warm program for
                   every request's knobs); greedy requests on a sampling
                   engine run the argmax arm bit-identically to a
                   non-sampling engine. Default off: the greedy-only
                   program shapes stay byte-identical to every prior
                   round
    dedup_capacity : bound on the idempotency dedup table (docs/
                   ROBUSTNESS.md "Control-plane HA"): requests submitted
                   with a client-generated ``request_key`` are remembered
                   here — a resubmit of an IN-FLIGHT key attaches to the
                   existing request's future (``engine.dedup_hits``), a
                   resubmit of a COMPLETED key replays the cached answer
                   verbatim (``engine.dedup_replays``) — so an ambiguous
                   wire death costs at most one generation fleet-wide.
                   LRU-evicted past the bound; 0 disables dedup (every
                   keyed submit executes — legacy at-least-once)
    """
    page_size: int = 16
    max_slots: int = 8
    max_seq_len: int | None = None
    num_pages: int | None = None
    min_bucket: int = 16
    eos_id: int | None = None
    donate: bool | None = None
    inflight: int = 2
    prefill_chunk_tokens: int | None = None
    prefix_cache: bool = True
    kv_host_tier_bytes: int | None = None
    kv_disk_tier_bytes: int | None = None
    kv_disk_tier_dir: str | None = None
    speculate_k: int | None = None
    max_queue_depth: int | None = None
    max_queue_tokens: int | None = None
    kv_dtype: str = "native"
    weight_dtype: str = "native"
    sampling: bool = False
    dedup_capacity: int = 1024


class GenerateRequest:
    """One queued/running generation. `result()` blocks until the sequence
    retires and returns prompt + generated ids (fast_generate's contract).
    ``trace`` is the request's :class:`RequestTrace` — serve passes one
    created at wire-accept so TTFT/e2e include the wire wait; a direct
    `submit()` gets a fresh one. ``deadline_s`` starts the request's
    deadline clock HERE (construction = wire accept / submit): past it the
    engine retires the request with a typed ``DeadlineExceeded`` at the
    next enforcement point (admission, step start, or harvest — never
    mid-device-call; docs/ROBUSTNESS.md)."""

    def __init__(self, prompt: np.ndarray, max_new_tokens: int, trace=None,
                 cache: bool = True, speculate: bool = True,
                 deadline_s: float | None = None,
                 request_key: bytes | None = None,
                 temperature: float = 1.0, top_k: int = 0, seed: int = 0):
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.generated: list[int] = []
        self.submit_t = time.perf_counter()
        self.trace = trace if trace is not None else RequestTrace()
        self.cache = bool(cache)          # prefix-cache participation
        self.speculate = bool(speculate)  # n-gram drafting participation
        # fused on-device sampling params (EngineConfig.sampling): the
        # defaults are the greedy arm — bit-identical to a non-sampling
        # engine, key chain never advances
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self._seed_key = None    # lazily materialized PRNGKey(seed) words
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.deadline_t = None if deadline_s is None \
            else time.monotonic() + float(deadline_s)
        self.page_hashes: list[bytes] = []  # rolling full-page prompt hashes
        # client-generated idempotency key (16 bytes on the wire): the
        # engine's dedup table attaches resubmits of this key to THIS
        # future instead of re-running the generation
        self.request_key = None if request_key is None \
            else bytes(request_key)
        self.imported = False           # resumed from a KV handoff
        self.tenant = None              # reserved multi-tenant identity
        # usage metering (observability/usage.py): per-request mirrors of
        # the engine's aggregate token counters, folded into ONE
        # UsageRecord at first _finish. All accounting happens at the
        # admission/prefill/harvest/detach events that already exist —
        # never inside the packed step path.
        self.u_prefill_computed = 0     # prompt tokens a prefill ran over
        self.u_prefill_saved = 0        # prompt tokens answered from cache
        self.u_generated = 0            # tokens delivered to the future
        self.u_spec_accepted = 0        # of those, speculation's surplus
        self.u_page_steps = 0           # KV pages held x decode steps held
        self.u_migrations = 0           # times this request moved engines
        self.u_admit_step = None        # step_seq at slot placement
        self._usage_emitted = False
        self._waiters = 0               # live result() waiters (serve tier)
        self._wlock = threading.Lock()
        self._done = threading.Event()
        self._error: str | None = None

    def add_waiter(self):
        """One more party is blocked on this future (a serve connection
        thread, possibly a dedup-attached resubmit). The serving layer's
        disconnect-cancel consults `waiters` so one client hanging up
        cannot kill a generation another attached client still wants."""
        with self._wlock:
            self._waiters += 1

    def remove_waiter(self) -> int:
        """Detach one waiter; returns the REMAINING count. The decrement
        and the read are one atomic step so an abandoning wait can decide
        'was I the last?' without racing another waiter's exit — two
        waits timing out in the same poll tick must elect exactly one
        canceller, not zero."""
        with self._wlock:
            self._waiters = max(0, self._waiters - 1)
            return self._waiters

    @property
    def waiters(self) -> int:
        with self._wlock:
            return self._waiters

    def expired(self, now: float | None = None) -> bool:
        return self.deadline_t is not None and \
            (time.monotonic() if now is None else now) >= self.deadline_t

    @property
    def request_id(self) -> str:
        return self.trace.request_id

    def _finish(self, error: str | None = None):
        self.trace.mark_done(error)
        self._error = error
        self._done.set()
        # every termination path funnels through here (retire, reap,
        # abort, deadline, migration splice) — the ONE usage-metering
        # emission point; the latch keeps a double _finish single-billed
        with self._wlock:
            first = not self._usage_emitted
            self._usage_emitted = True
        if first:
            _emit_usage(self, error)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError("generation still running")
        if self._error is not None:
            # typed where the error string carries a known type name
            # ("DeadlineExceeded: ...", "Cancelled: ...") so callers can
            # except-clause on the class; everything else stays the
            # RuntimeError it always was
            raise from_wire(self._error)
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, self.prompt.dtype)])


_NGRAM_NS = (3, 2, 1)          # longest-match-first draft lookup order


class _DraftIndex:
    """Per-slot n-gram index for the self-drafting proposer: ``{n-gram ->
    most recent start position that has >= 1 following token}``, maintained
    O(1) per generated token so drafting costs O(k) host work per step —
    never an O(context) rescan on the latency-critical step loop. An
    n-gram is registered only once its follower exists, so a draft lookup
    always has at least one token to propose."""

    __slots__ = ("hist", "maps")

    def __init__(self, prompt):
        self.hist: list[int] = []
        self.maps = {n: {} for n in _NGRAM_NS}
        for t in prompt:
            self.append(int(t))

    def append(self, tok: int):
        h = self.hist
        p = len(h)
        h.append(int(tok))
        for n in _NGRAM_NS:
            if p >= n:                 # grams ending at p-1 gained a follower
                self.maps[n][tuple(h[p - n:p])] = p - n

    def draft(self, k: int) -> list[int]:
        h = self.hist
        for n in _NGRAM_NS:
            if len(h) <= n:
                continue
            j = self.maps[n].get(tuple(h[-n:]))
            if j is not None:
                return h[j + n:j + n + k]
        return []


def _blob_digest(body: bytes) -> str:
    """blake2b content checksum of a wire blob's body — the one digest
    implementation both `KVHandoff` and the ``PTMG1`` migration blob
    stamp into their headers and verify on unpack."""
    return hashlib.blake2b(body, digest_size=16).hexdigest()


def _read_blob_head(buf: bytes, magic_len: int, what: str):
    """Parse a checksummed wire blob's ``u32 header_len | JSON header``
    and VERIFY the header's ``sum`` digest over the body (everything past
    the header) before any payload byte is interpreted. Returns
    ``(head, body_offset)``. An unparseable header or a digest mismatch —
    truncation, bit flip, torn transfer — raises the typed
    :class:`HandoffCorrupt` refusal; a header WITHOUT ``sum`` (a
    pre-checksum build's blob) loads unverified, the same legacy rule as
    unstamped checkpoints."""
    try:
        (hlen,) = struct.unpack("<I", buf[magic_len:magic_len + 4])
        head = json.loads(buf[magic_len + 4:magic_len + 4 + hlen].decode())
        if not isinstance(head, dict):
            raise ValueError(f"header is {type(head).__name__}, not object")
    except (struct.error, ValueError) as e:
        raise HandoffCorrupt(
            f"{what} blob header unparseable ({type(e).__name__}: {e}) — "
            f"truncated or corrupted transfer") from e
    off = magic_len + 4 + hlen
    want = head.get("sum")
    if want is not None:
        got = _blob_digest(buf[off:])
        if got != want:
            raise HandoffCorrupt(
                f"{what} blob failed its content checksum over "
                f"{len(buf) - off} body bytes — truncated or bit-flipped "
                f"transfer, refusing to decode garbage context")
    return head, off


@dataclass
class KVHandoff:
    """A request's paged KV state, detached from any engine — the
    page-granular handoff primitive (docs/SERVING.md "KV handoff format").

    `DecodeEngine.prefill_export` produces one (prompt KV pages + the first
    sampled token); `DecodeEngine.import_request` on ANY engine with the
    same model geometry resumes decode from it, token-identical to having
    prefilled locally. Only page IDS change across the transfer — contents
    move bit-exact — so prefill/decode disaggregation is a page copy, not a
    tensor-relayout problem.

    ``pack()``/``unpack()`` define the wire blob:
    ``b"PTKV1\\n" | u32 header_len | JSON header | prompt int32 | k | v
    [| k_scales | v_scales]``
    where the header carries page_size, dtype, prompt_len, first_token and
    the ``[nl, n_pages, page_size, nh, dh]`` pages shape — plus, for int8
    pools, the ``[nl, n_pages, page_size, nh]`` scales shape: the listed
    pages' f32 scales travel WITH their values, so an imported int8 page
    dequantizes bit-identically to where it was prefilled. A float-pool
    blob has no scales section and an int8 engine refuses it (and vice
    versa) via the dtype check in `import_request` — never a silent cast.

    Wire integrity (docs/ROBUSTNESS.md "Wire integrity"): the header also
    carries ``sum``, a blake2b content checksum of the BODY (everything
    after the header). `unpack` verifies it FIRST — a truncated or
    bit-flipped transfer raises a typed :class:`HandoffCorrupt` refusal
    instead of decoding garbage context (the checkpoint checksum
    discipline applied to the wire). Blobs from pre-checksum builds carry
    no ``sum`` and load unverified (legacy, same rule as unstamped
    checkpoints).
    """
    prompt: np.ndarray          # [S0] int32
    first_token: int            # sampled from the prefill's last logits
    k_pages: np.ndarray         # [nl, n_pages, page_size, nh, dh]
    v_pages: np.ndarray
    page_size: int
    cache_dtype: str            # numpy dtype name of the pool
    k_scales: np.ndarray | None = None   # [nl, n_pages, page_size, nh] f32
    v_scales: np.ndarray | None = None   # (int8 pools only)
    # fused-sampler state for a SAMPLED request's handoff
    # (EngineConfig.sampling): {"temperature": f, "top_k": i, "key":
    # [k0, k1]} — the per-slot PRNG chain AS ADVANCED so far, so decode on
    # the importing engine continues the bit-identical sampled sequence.
    # None (incl. every legacy blob) = greedy. A sampled handoff into a
    # non-sampling engine is a loud refusal (`_check_handoff`).
    sample: dict | None = None

    MAGIC = b"PTKV1\n"

    def pack(self) -> bytes:
        head = {
            "page_size": int(self.page_size), "dtype": self.cache_dtype,
            "first_token": int(self.first_token),
            "prompt_len": int(self.prompt.size),
            "pages_shape": [int(d) for d in self.k_pages.shape]}
        if self.sample is not None:
            head["sample"] = self.sample
        parts = [
            np.ascontiguousarray(self.prompt, np.int32).tobytes(),
            np.ascontiguousarray(self.k_pages).tobytes(),
            np.ascontiguousarray(self.v_pages).tobytes()]
        if self.k_scales is not None:
            head["scales_shape"] = [int(d) for d in self.k_scales.shape]
            parts += [
                np.ascontiguousarray(self.k_scales, np.float32).tobytes(),
                np.ascontiguousarray(self.v_scales, np.float32).tobytes()]
        body = b"".join(parts)
        head["sum"] = _blob_digest(body)
        hb = json.dumps(head).encode()
        return b"".join([self.MAGIC, struct.pack("<I", len(hb)), hb, body])

    @classmethod
    def unpack(cls, buf: bytes) -> "KVHandoff":
        m = len(cls.MAGIC)
        if buf[:m] != cls.MAGIC:
            raise ValueError("not a KV handoff blob (bad magic)")
        head, off = _read_blob_head(buf, m, "KV handoff")
        s0 = int(head["prompt_len"])
        prompt = np.frombuffer(buf, np.int32, count=s0, offset=off).copy()
        off += 4 * s0
        if head["dtype"] == "bfloat16":
            import ml_dtypes
            dt = np.dtype(ml_dtypes.bfloat16)
        else:
            dt = np.dtype(head["dtype"])
        shape = tuple(head["pages_shape"])
        n = int(np.prod(shape))
        k = np.frombuffer(buf, dt, count=n, offset=off).reshape(shape).copy()
        off += n * dt.itemsize
        v = np.frombuffer(buf, dt, count=n, offset=off).reshape(shape).copy()
        off += n * dt.itemsize
        ks = vs = None
        if (dt == np.int8) != ("scales_shape" in head):
            raise ValueError(
                f"KV handoff blob dtype {head['dtype']!r} "
                f"{'missing its' if dt == np.int8 else 'carries unexpected'}"
                f" scales section — refusing a silently mis-scaled import")
        if "scales_shape" in head:
            sshape = tuple(head["scales_shape"])
            if sshape != shape[:-1]:
                raise ValueError(
                    f"KV handoff scales shape {sshape} does not match "
                    f"pages shape {shape} — expected {shape[:-1]}")
            ns = int(np.prod(sshape))
            ks = np.frombuffer(buf, np.float32, count=ns,
                               offset=off).reshape(sshape).copy()
            off += ns * 4
            vs = np.frombuffer(buf, np.float32, count=ns,
                               offset=off).reshape(sshape).copy()
        return cls(prompt=prompt, first_token=int(head["first_token"]),
                   k_pages=k, v_pages=v, page_size=int(head["page_size"]),
                   cache_dtype=head["dtype"], k_scales=ks, v_scales=vs,
                   sample=head.get("sample"))


@dataclass
class MigrationItem:
    """One request leaving a draining engine (docs/SERVING.md "Live
    migration"). WARM items (``handoff`` set) left mid-decode: the handoff's
    prompt is the full resident CONTEXT — original prompt + every delivered
    token whose KV is on device — and its first_token is the last sampled
    token, riding as the seed exactly like `prefill_export`'s. COLD items
    (``prompt`` set) never reached a seeded slot (queued, or mid
    chunk-prefill) and re-enter a peer through plain `submit`.

    ``max_new_tokens`` is the PEER-facing budget: for a warm item the seed
    counts as the peer's first emission, so it is ``original budget -
    delivered + 1`` — the peer's answer (context + its generated tokens) is
    then exactly the uninterrupted run's full sequence. ``deadline_ms`` is
    the REMAINING deadline budget at export. ``request`` is the source-local
    future the serving layer splices the peer's tokens into; it never
    crosses the wire (`pack_migration` drops it). ``tag`` is the request's
    CANCEL wire tag, if one was registered: it travels WITH the request so
    the peer can register it too — a client cancel issued after the
    migration still reaches the engine actually decoding (serve.py).
    ``cache``/``speculate`` carry the request's per-request opt-outs: a
    ``cache=False`` submit promised its KV would never be shared, and a
    migration must not quietly re-enroll it in the peer's prefix store.
    ``request_key`` is the request's idempotency key, if the client sent
    one: it rides the ``PTMG1`` header so the peer registers the resumed
    request in ITS dedup table — exactly-once survives a drain (a client
    resubmitting the key after the migration attaches to the moved
    request instead of re-running it)."""
    max_new_tokens: int
    handoff: KVHandoff | None = None
    prompt: np.ndarray | None = None     # cold items only
    deadline_ms: int | None = None
    request: GenerateRequest | None = None
    tag: bytes | None = None
    cache: bool = True
    speculate: bool = True
    request_key: bytes | None = None
    # COLD sampled items re-enter a peer through plain submit, so the
    # sampler restarts from scratch: {"temperature": f, "top_k": i,
    # "seed": i}. WARM items carry their advanced chain inside
    # ``handoff.sample`` instead. None = greedy (every legacy blob).
    sample: dict | None = None
    # fleet trace context (hex): the ORIGINAL trace id minted at ingress
    # rides the PTMG1 header so the peer's spans land in the same stitched
    # trace; ``parent_span`` is the SOURCE process's span id.
    trace_id: str | None = None
    parent_span: str | None = None


MIG_MAGIC = b"PTMG1\n"


def pack_migration(item: MigrationItem) -> bytes:
    """Serialize a :class:`MigrationItem` for the OP_MIGRATE wire op:
    ``b"PTMG1\\n" | u32 header_len | JSON header | body`` where the body is
    the PTKV1 handoff blob (warm) or the bare int32 prompt (cold). The
    header's ``sum`` digest covers the body, verified by
    `unpack_migration` (docs/ROBUSTNESS.md "Wire integrity") — for a warm
    item the inner PTKV1 blob carries its OWN checksum too, so corruption
    is caught whichever layer unpacks first."""
    head = {"max_new_tokens": int(item.max_new_tokens),
            "deadline_ms": 0 if item.deadline_ms is None
            else int(item.deadline_ms),
            "warm": item.handoff is not None}
    if item.tag is not None:
        head["tag"] = bytes(item.tag).hex()
    if item.request_key is not None:
        head["key"] = bytes(item.request_key).hex()
    if not item.cache:
        head["cache"] = False
    if not item.speculate:
        head["speculate"] = False
    if item.sample is not None:
        head["sample"] = item.sample
    if item.trace_id is not None:
        head["trace"] = item.trace_id
    if item.parent_span is not None:
        head["parent"] = item.parent_span
    if item.handoff is None:
        if item.prompt is None:
            raise ValueError("cold migration item has no prompt")
        head["prompt_len"] = int(item.prompt.size)
        body = np.ascontiguousarray(item.prompt, np.int32).tobytes()
    else:
        body = item.handoff.pack()
    head["sum"] = _blob_digest(body)
    hb = json.dumps(head).encode()
    return b"".join([MIG_MAGIC, struct.pack("<I", len(hb)), hb, body])


def unpack_migration(buf: bytes) -> MigrationItem:
    """Wire blob -> :class:`MigrationItem` (``request`` is None — the
    receiving engine creates its own future). Verifies the header's body
    checksum FIRST — a damaged blob raises the typed
    :class:`HandoffCorrupt` refusal before any payload is interpreted."""
    m = len(MIG_MAGIC)
    if buf[:m] != MIG_MAGIC:
        raise ValueError("not a migration blob (bad magic)")
    head, off = _read_blob_head(buf, m, "PTMG1 migration")
    dl = int(head.get("deadline_ms", 0)) or None
    mnt = int(head["max_new_tokens"])
    tag = bytes.fromhex(head["tag"]) if "tag" in head else None
    key = bytes.fromhex(head["key"]) if "key" in head else None
    cache = bool(head.get("cache", True))
    speculate = bool(head.get("speculate", True))
    sample = head.get("sample")
    trace_id = head.get("trace")
    parent_span = head.get("parent")
    if head.get("warm"):
        return MigrationItem(max_new_tokens=mnt, deadline_ms=dl, tag=tag,
                             cache=cache, speculate=speculate,
                             request_key=key, sample=sample,
                             trace_id=trace_id, parent_span=parent_span,
                             handoff=KVHandoff.unpack(buf[off:]))
    s0 = int(head["prompt_len"])
    prompt = np.frombuffer(buf, np.int32, count=s0, offset=off).copy()
    return MigrationItem(max_new_tokens=mnt, deadline_ms=dl, tag=tag,
                         cache=cache, speculate=speculate,
                         request_key=key, prompt=prompt, sample=sample,
                         trace_id=trace_id, parent_span=parent_span)


class DecodeEngine:
    """Continuous-batching decode over a paged KV cache for one model of a
    family that declares itself through ``model.engine_family()``.

    >>> eng = DecodeEngine(model)                    # snapshots the weights
    >>> reqs = [eng.submit(ids, max_new_tokens=32) for ids in prompts]
    >>> eng.run_until_idle()
    >>> outs = [r.result() for r in reqs]
    """

    def __init__(self, model, engine_config: EngineConfig | None = None):
        with metrics.span("engine.init", cat="startup"):
            self._init(model, engine_config or EngineConfig())

    def _init(self, model, ecfg: EngineConfig):
        self.cfg = model.cfg
        self.ecfg = ecfg
        # the model seam (inference/family.py): the model object says which
        # step functions the programs trace and what a sequence keeps
        fam = self._fam = family_of(model)
        self._steps = fam.steps
        self._stateful = fam.state is not None
        self._nh, self._dh = fam.kv_heads, fam.head_dim
        self._nl = fam.kv_layers
        self._refuse_config(ecfg)
        self._load_params(model)
        self._served_dtype = self._params[fam.table_key].dtype
        ps = ecfg.page_size
        max_seq = ecfg.max_seq_len or fam.max_positions
        max_seq = min(max_seq, fam.max_positions)
        self.max_seq_len = max_seq
        # a family with no layer in the page pool (``kv_layers`` 0: all it
        # keeps of a sequence is fixed-size state) gets no pool, no
        # allocator and a page table of no width: its admission is bounded
        # by slots alone and a sequence's length costs it no memory
        self._pooled = fam.kv_layers > 0
        if not (self._pooled or self._stateful):
            raise ValueError(f"the {fam.name} family keeps neither pages "
                             "nor state: nothing a sequence could live in")
        self.pages_per_slot = -(-max_seq // ps) if self._pooled else 0
        self.slot_capacity = self.pages_per_slot * ps \
            if self._pooled else max_seq                  # tokens per slot
        num_pages = (ecfg.num_pages or
                     1 + ecfg.max_slots * self.pages_per_slot) \
            if self._pooled else 1
        self.allocator = PageAllocator(num_pages) if self._pooled else None
        if ecfg.donate is None:
            self._donate = jax.default_backend() != "cpu"
        else:
            self._donate = bool(ecfg.donate)

        B, maxp = ecfg.max_slots, self.pages_per_slot
        # everything a step program updates in place: page pools, an int8
        # pool's scales, the family's state beside the pool, the sampler's
        # key chains. Every program takes it whole, donated, and returns it
        # (inference/cache.py); `_kc` ... `_state` below are views of it
        with metrics.span("engine.cache_alloc", cat="startup") as sp:
            self._cache = DeviceCache.allocate(fam, ecfg, num_pages,
                                               self._served_dtype)
            sp.args["bytes"] = sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(self._cache))
        self._cdtype = self._cache.k.dtype
        self._quant_kv = self._cache.k_scale is not None
        self.kv_bytes_per_token = self._cache.bytes_per_token
        self._window_pages = -(-fam.window_tokens // ps) + 1 \
            if fam.window_tokens else 0
        # published for the router's fleet prefix directory: affinity
        # hashing needs the fleet's page size (docs/SERVING.md
        # "Disaggregated serving")
        metrics.gauge("engine.page_size").set(ps)
        # host-side mirrors of the per-slot state, fused into ONE packed
        # int32 upload per step; sampled tokens live on device and the
        # _tokens column is consulted only for slots seeded with a token
        # the host holds (`_seed_first_token`: imports, speculation)
        self._page_table = np.full((B, maxp), TRASH_PAGE, np.int32)
        self._lengths = np.zeros(B, np.int32)
        self._tokens = np.zeros(B, np.int32)
        self._active = np.zeros(B, bool)      # dispatchable this step
        self._fresh = np.zeros(B, bool)       # admitted since last dispatch
        self._budget = np.zeros(B, np.int32)  # tokens left to dispatch
        self._slot_req: list[GenerateRequest | None] = [None] * B
        self._slot_pages: list[list[int]] = [[] for _ in range(B)]
        self._slot_draft: list[_DraftIndex | None] = [None] * B
        # device-resident sampled-token chain + deferred-readback fifo of
        # (device tokens, [(slot, request)] snapshot, dispatch t0): a
        # decode step's, or a prefill's with the one slot it admitted.
        # Behind the slots' tokens the chain carries the family's running
        # step counts, if it hands any back (inference/family.py): they
        # reach the host on the tokens' readback (`_harvest_one`)
        self._n_counts = int(fam.step_counts)
        self._tok_dev = jnp.zeros(B + self._n_counts, jnp.int32)
        self._counts_seen = np.zeros(self._n_counts, np.uint32)
        # fused on-device sampling (EngineConfig.sampling): per-slot
        # (temperature, top_k) host mirrors ride the packed upload; the
        # PRNG key chains live in the cache, so sampled decode reads back
        # TOKENS only
        self._sampling = bool(ecfg.sampling)
        self._temps = np.ones(B, np.float32)
        self._topks = np.zeros(B, np.int32)
        self._inflight: deque = deque()

        self._queue: deque[GenerateRequest] = deque()
        self._qlock = threading.Lock()
        self._work = threading.Condition(self._qlock)
        self._programs: dict = {}     # the engine's ProgramCache analog
        # ids of the programs compiled and not launched yet: a program's
        # first launch says `first=true` on its span (`_note_first`)
        self._unlaunched: set[int] = set()
        self._dead: str | None = None  # set by abort(); submits then fail fast
        self._draining = False        # drain(): refuse NEW submits only
        self._queue_tokens = 0        # sum of queued prompt tokens (_qlock)
        # cancellation mailbox: any thread posts request_id -> reason, the
        # driver applies it between fixed-shape steps (_reap)
        self._cancels: dict[str, str] = {}
        # idempotency dedup table (docs/ROBUSTNESS.md "Control-plane HA"):
        # client request_key -> GenerateRequest, LRU-bounded at
        # ecfg.dedup_capacity. A resubmit of an IN-FLIGHT key attaches to
        # the existing future; a COMPLETED key replays its answer (tokens
        # or typed error) verbatim — an ambiguous wire death costs at
        # most one generation per engine. Guarded by _qlock.
        self._dedup: OrderedDict[bytes, GenerateRequest] = OrderedDict()
        # live-migration state (docs/SERVING.md "Live migration"): the
        # OUTBOUND side is driver-only — drain(migrate=True) posts a flag,
        # step() exports every live request into _migrated and sets the
        # event take_migrated() waits on. The INBOUND side is a mailbox:
        # submit_import() posts (handoff, request) from any thread and the
        # driver places it between fixed-shape steps (_apply_imports), the
        # same discipline as cancellation
        self._migrate_requested = False
        self._migrated: list[MigrationItem] = []
        self._migrate_done = threading.Event()
        self._imports: deque = deque()
        # prefill-stream mailbox (docs/SERVING.md "Disaggregated
        # serving"): submit_prefill_stream posts (ids, cache, sink) from
        # any thread; the DRIVER runs the chunked prefill between
        # fixed-shape steps and streams PTKS1 records into the sink —
        # the same mailbox discipline as cancellation and imports, so a
        # prefill worker's connection threads never touch device state
        self._prefill_jobs: deque = deque()
        self._deg = 0                 # applied degradation level (driver)
        # chunked-prefill progress: slot -> {"req", "done", "t0"}; slots
        # here are occupied (slot_req set, pages held) but NOT decode-active
        self._prefilling: dict[int, dict] = {}
        if ecfg.prefill_chunk_tokens is not None \
                and int(ecfg.prefill_chunk_tokens) < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, "
                f"got {ecfg.prefill_chunk_tokens}")
        if ecfg.speculate_k is not None and int(ecfg.speculate_k) < 1:
            raise ValueError(
                f"speculate_k must be >= 1, got {ecfg.speculate_k}")
        self._spec = ecfg.speculate_k is not None
        self._spec_k = int(ecfg.speculate_k) if self._spec else 0
        # the batched step's packed upload (a verify step's when
        # speculating): laid out once, written by `_packed_state`, read
        # by the program (inference/programs.py)
        self._step_upload = step_upload(B, maxp, self._sampling,
                                        k=self._spec_k)
        # prefix cache: rolling full-page hash -> resident page, plus the
        # reverse map and the LRU of refcount-0 ("idle") cached pages the
        # allocator retains for us. All mutations happen on the driver
        # thread (admission/retire) — submit only COMPUTES hashes.
        self._prefix_enabled = bool(ecfg.prefix_cache)
        self._prefix_pages: dict[bytes, int] = {}
        self._page_hash: dict[int, bytes] = {}
        self._prefix_idle: OrderedDict[int, None] = OrderedDict()
        if self._pooled:
            self.allocator.retain_hook = self._retain_page
            self.allocator.evict_hook = self._evict_prefix_pages
        # KV tiering (docs/SERVING.md "KV tiering"): bounded host-RAM /
        # disk spill tiers under the HBM store — eviction demotes page
        # contents instead of discarding them, and a tier hit re-uploads
        # via one batched import_pages scatter (kv_tiers.py)
        self._tiers = None
        if self._prefix_enabled and (ecfg.kv_host_tier_bytes
                                     or ecfg.kv_disk_tier_bytes):
            from paddle_tpu.inference.kv_tiers import KVTierStore
            self._tiers = KVTierStore(
                host_bytes=ecfg.kv_host_tier_bytes,
                disk_bytes=ecfg.kv_disk_tier_bytes,
                disk_dir=ecfg.kv_disk_tier_dir,
                page_shape=(self._nl, ps, self._nh, self._dh),
                dtype=np.dtype(self._cdtype).name,
                scales=self._quant_kv)
        self.step_seq = 0             # advances once per step(); the
        #                               watchdog's progress reading

        self._m_hit = metrics.counter("engine.cache_hit")
        self._m_miss = metrics.counter("engine.cache_miss")
        self._m_compiles = metrics.counter("engine.compile_count")
        self._m_steps = metrics.counter("engine.steps")
        self._m_tokens = metrics.counter("engine.tokens")
        self._m_requests = metrics.counter("engine.requests")
        self._m_h2d = metrics.counter("engine.h2d_transfers")
        self._m_d2h = metrics.counter("engine.d2h_transfers")
        # pinned-to-zero proof of the fused sampler: NO engine path reads
        # logits back to the host (sampling included) — the counter exists
        # so tests/bench can assert the absence (docs/OBSERVABILITY.md)
        self._m_logits_rb = metrics.counter("engine.logits_readback")
        self._m_chunks = metrics.counter("engine.prefill_chunks")
        self._m_prefill_launches = metrics.counter("engine.prefill_launches")
        # first tokens by the way they reached the host: through the fifo
        # (`_harvest_one`), or read inside admission (`_first_token`)
        self._m_first_deferred = metrics.counter(
            "engine.first_tokens_deferred")
        self._m_first_sync = metrics.counter("engine.first_tokens_sync")
        self._m_prefill_tokens = metrics.counter("engine.prefill_tokens")
        self._m_win_recycled = metrics.counter(
            "engine.window_pages_recycled")
        self._m_state_resets = metrics.counter("engine.state_resets")
        self._m_state_carries = metrics.counter("engine.state_carries")
        self._m_prefix_hit = metrics.counter("engine.prefix_hit")
        self._m_prefix_miss = metrics.counter("engine.prefix_miss")
        self._m_prefix_reused = metrics.counter("engine.prefix_pages_reused")
        self._m_prefix_evict = metrics.counter("engine.prefix_evictions")
        # the eviction split (docs/OBSERVABILITY.md): demoted pages moved
        # to a spill tier (recoverable), discarded ones are lost; the
        # legacy total above stays their sum for existing dashboards
        self._m_prefix_demote = metrics.counter(
            "engine.prefix_evictions_demoted")
        self._m_prefix_discard = metrics.counter(
            "engine.prefix_evictions_discarded")
        self._m_spill_fail = metrics.counter("engine.kvtier.spill_fail")
        self._m_reupload_fail = metrics.counter(
            "engine.kvtier.reupload_fail")
        self._m_reup_host = metrics.counter("engine.kvtier.reuploads_host")
        self._m_reup_disk = metrics.counter("engine.kvtier.reuploads_disk")
        self._h_spill = metrics.histogram("engine.kvtier.spill_ms")
        self._h_reupload = metrics.histogram("engine.kvtier.reupload_ms")
        self._g_prefix_pages = metrics.gauge("engine.prefix_pages")
        self._g_prefix_bytes = metrics.gauge("engine.prefix_store_bytes")
        self._m_spec_steps = metrics.counter("engine.spec_steps")
        self._m_spec_drafted = metrics.counter("engine.spec_drafted")
        self._m_spec_accepted = metrics.counter("engine.spec_accepted")
        self._g_spec_rate = metrics.gauge("engine.spec_accept_rate")
        self._g_spec_tps = metrics.gauge("engine.spec_tokens_per_step")
        self._m_shed = metrics.counter("engine.shed")
        self._m_dedup_hits = metrics.counter("engine.dedup_hits")
        self._m_dedup_replays = metrics.counter("engine.dedup_replays")
        self._m_mig_out = metrics.counter("engine.migrations_out")
        self._m_mig_in = metrics.counter("engine.migrations_in")
        self._m_cancelled = metrics.counter("engine.cancelled")
        self._m_deadline = metrics.counter("engine.deadline_exceeded")
        self._g_deg = metrics.gauge("engine.degradation_level")
        self._g_occupancy = metrics.gauge("engine.batch_occupancy")
        self._g_queue = metrics.gauge("engine.queue_depth")
        self._g_tps = metrics.gauge("engine.tokens_per_s")
        self._g_inflight = metrics.gauge("engine.steps_in_flight")
        self._h_wait = metrics.histogram("engine.queue_wait_seconds")
        self._h_step = metrics.histogram("engine.step_seconds")
        self._h_prefill = metrics.histogram("engine.prefill_seconds")

    # read-only views of the cache's leaves under the names they had as
    # engine attributes: tests read them, and the benchmark frees the
    # program's buffers by them (benchmarks/runners/serve_hybrid.py)
    _kc = property(lambda self: self._cache.k)
    _vc = property(lambda self: self._cache.v)
    _ks = property(lambda self: self._cache.k_scale)
    _vs = property(lambda self: self._cache.v_scale)
    _state = property(lambda self: self._cache.state)

    # ------------------------------------------------------- the model seam

    def _load_params(self, model):
        """The engine's one copy of the served weights, as every launch
        hands it to the runtime: the family's leaves (stacked by layer),
        matmul leaves int8 + per-channel scales under ``weight_dtype``
        (dequantized at use inside the same AOT programs; the conversion
        wall lands in engine.quant_dequant_ms). A QuantizedLeaf is part of
        the traced pytree STRUCTURE, so a refresh quantizes again or the
        next warm call would be a structure mismatch, not a hot swap."""
        with metrics.span("engine.load_params", cat="startup") as sp:
            params = self._fam.params(model)
            if self.ecfg.weight_dtype not in ("native", None):
                if self._fam.quantize is None:
                    raise ValueError(
                        f"weight_dtype={self.ecfg.weight_dtype!r}: the "
                        f"{self._fam.name} family supplies no weight "
                        "quantizer")
                params = self._fam.quantize(params, self.ecfg.weight_dtype)
            self._params = params
            # what a launch flattens, checks and holds:
            # docs/OBSERVABILITY.md
            sp.args["leaves"] = len(jax.tree_util.tree_leaves(params))
        metrics.gauge("engine.param_leaves").set(sp.args["leaves"])

    def _refuse_stateful(self, what: str):
        """A model that keeps window or recurrent state beside the page
        pool cannot be restored from pages alone: whatever would rebuild a
        sequence from them refuses, by name, instead of serving from half
        of its state (docs/SERVING.md "What refuses")."""
        if self._stateful:
            raise RecurrentStateUnsupported(
                f"{what}: the {self._fam.name} family keeps window and "
                "recurrent state per sequence beside the page pool; pages "
                "alone cannot restore a sequence")

    def _refuse_unmovable(self, what: str):
        """Whatever ships a sequence's pages through a wire blob (hand-off,
        migration, the spill tiers): refused for a model with state beside
        the pool, and for one whose page row is not K and V heads
        (`ModelFamily.page_rows`), since every blob is laid out as twin K
        and V pools of ``[.., kv heads, head dim]``."""
        self._refuse_stateful(what)
        if self._fam.page_rows:
            parts = " + ".join(f"{n} ({w})" for n, w in self._fam.page_rows)
            raise PageLayoutUnsupported(
                f"{what}: the {self._fam.name} family's page row is "
                f"{parts}, not K and V heads, and the blob's layout is "
                "twin K and V pools")

    def _refuse_config(self, ecfg: EngineConfig):
        """What this family cannot be configured with, refused before
        anything is allocated."""
        if ecfg.kv_host_tier_bytes or ecfg.kv_disk_tier_bytes:
            self._refuse_unmovable("EngineConfig.kv_*_tier_bytes (tier "
                                   "spill)")
        if not self._stateful:
            if ecfg.speculate_k is not None \
                    and not hasattr(self._steps, "verify_step"):
                raise ValueError(
                    f"speculate_k={ecfg.speculate_k}: the {self._fam.name} "
                    "family supplies no verify_step")
            return
        if ecfg.prefix_cache:
            self._refuse_stateful("EngineConfig.prefix_cache=True (prefix "
                                  "reuse; pass prefix_cache=False)")
        if ecfg.speculate_k is not None:
            self._refuse_stateful("EngineConfig.speculate_k (speculation "
                                  "rolls rejected tokens back by length "
                                  "alone)")
        if ecfg.kv_dtype == "int8":
            raise ValueError(
                f"kv_dtype='int8': the {self._fam.name} family's step "
                "functions take no scale pools")

    # ------------------------------------------------------------- programs

    def _compiled(self, key, build):
        """AOT program cache: compile once per key; later shape drift raises
        inside the executable instead of silently retracing."""
        exe = self._programs.get(key)
        if exe is None:
            self._m_miss.inc()
            flight.record("engine.compile_start", program=str(key))
            blocks = [metrics.counter("model.block_traces"),
                      metrics.counter("model.block_calls")]
            was = [c.value for c in blocks]
            with metrics.span(f"engine.compile:{key[0]}",
                              cat="compile") as sp:
                exe = self._programs[key] = build()
                # a family that runs its block as one traced function
                # (models/gpt.py::_block_stack) says how often the block's
                # code was traced and how often it was applied: 1 and nl
                traces, calls = (c.value - w for c, w in zip(blocks, was))
                if calls:
                    sp.args.update(block_traces=traces, block_calls=calls)
            self._unlaunched.add(id(exe))
            self._m_compiles.inc()
            metrics.histogram("engine.compile_seconds").observe(sp.dur)
        else:
            self._m_hit.inc()
        return exe

    def _note_first(self, exe, sp):
        """``first=true`` on the span of a compiled program's FIRST launch
        (`engine.dispatch`, `engine.prefill_launch`): the runtime loads the
        executable onto the chip inside that call, so it is the long one."""
        if id(exe) in self._unlaunched:
            self._unlaunched.discard(id(exe))
            sp.args["first"] = True

    def _build(self, program, *small):
        """Compile one step program ahead of time against this engine's
        parameters and cache: ``exe(params, cache, *small) -> (*lead,
        cache)``, the cache donated whole (inference/programs.py)."""
        return jax.jit(
            program, donate_argnums=(1,) if self._donate else ()).lower(
                self._params, self._cache, *small).compile()

    def _step_exe(self):
        """The batched step: decode, or ONE verify program when
        speculating, regardless of which slots drafted how much (tests/
        test_no_retrace.py). The paged-attention impl is baked into the
        traced decode program, so the flag is part of the cache key:
        flipping it compiles a new program instead of being silently
        ignored (same rule as tpu_flash_impl in the jit ProgramCache)."""
        from paddle_tpu.framework.flags import flag_value
        if self._spec:
            make, key = verify_program, ("verify", self._spec_k)
        else:
            make = decode_program
            key = ("decode", flag_value("tpu_paged_impl"))
        up = self._step_upload
        return self._compiled(key, lambda: self._build(
            make(self._steps, self.cfg, up, self._n_counts), self._tok_dev,
            up.spec()))

    def _prefill_upload(self, tokens: int, chunk: bool):
        return prefill_upload(tokens, self.pages_per_slot, self._sampling,
                              chunk)

    def _prefill_exe(self, tokens: int, chunk: bool = False):
        """A one-shot prefill of a ``tokens`` bucket, or (``chunk``) the
        chunk program, which serves two callers with one shape family:
        decode-priority chunked prefill (tokens = prefill_chunk_tokens)
        and the prefix-cache TAIL prefill (tokens = the tail's pow-2
        bucket). The prefill-attention impl is baked into the traced
        program (kernels/registry.py), so the flag keys the cache."""
        from paddle_tpu.framework.flags import flag_value

        def build():
            up = self._prefill_upload(tokens, chunk)
            return self._build(
                prefill_program(self._steps, self.cfg, up, self._n_counts),
                self._tok_dev, up.spec())
        return self._compiled(
            ("prefill_chunk" if chunk else "prefill", tokens,
             flag_value("tpu_prefill_impl")), build)

    def _use_chunked(self, prompt_len: int) -> bool:
        c = self.ecfg.prefill_chunk_tokens
        return c is not None and prompt_len > int(c)

    def bucket_for(self, prompt_len: int) -> int:
        """Next power-of-two >= prompt_len (floor min_bucket, capped at the
        position table so wpe[:bucket] stays in range)."""
        b = max(self.ecfg.min_bucket, 1 << max(0, prompt_len - 1).bit_length())
        return min(b, self._fam.max_positions)

    def warmup(self, prompt_lens=(1,), tail_lens=()):
        """Compile the decode/verify step + the prefill programs (buckets
        or the chunk program) covering ``prompt_lens``. ``tail_lens``
        front-loads the prefix-cache TAIL chunk programs (one per pow-2
        tail bucket) so a server's first cache hit doesn't pay a compile
        inside a request's TTFT. Optional — programs also compile lazily on
        first use — but lets servers front-load compiles before traffic.
        One `engine.warmup` span, the parent of its `engine.compile:*`."""
        with metrics.span("engine.warmup", cat="startup") as sp:
            before = len(self._programs)
            self._step_exe()
            need_chunk = False
            for s in prompt_lens:
                if self._use_chunked(int(s)):
                    need_chunk = True
                else:
                    self._prefill_exe(self.bucket_for(int(s)))
            for t in tail_lens:
                if self.ecfg.prefill_chunk_tokens is not None:
                    need_chunk = True
                else:
                    self._prefill_exe(self.bucket_for(int(t)), chunk=True)
            if need_chunk:
                self._prefill_exe(int(self.ecfg.prefill_chunk_tokens),
                                  chunk=True)
            sp.args["compiled"] = len(self._programs) - before

    def refresh_params(self, model):
        """Swap in current weights; programs take params as inputs, so this
        never recompiles. The prefix store is FLUSHED — host and disk
        spill tiers included: cached OR spilled pages hold KV computed
        under the old weights, and a hit (or tier re-upload) after the
        swap would silently condition new-weights decode on stale KV."""
        self._load_params(model)
        self._flush_prefix()

    # --------------------------------------------------------- prefix cache

    def _page_hashes(self, ids: np.ndarray) -> list[bytes]:
        """Rolling hash over the prompt's FULL token pages: ``h_i =
        H(h_{i-1} | page_i tokens)``. Chained keys mean a page is only
        reusable when every page before it matches too — a lookup walks the
        chain from page 0 and stops at the first miss. The ONE
        implementation lives in `serving/disagg.py` — the router's fleet
        prefix directory keys on the same hashes (docs/SERVING.md
        "Disaggregated serving")."""
        from paddle_tpu.serving.disagg import prompt_page_hashes
        return prompt_page_hashes(ids, self.ecfg.page_size)

    def prefix_hashes(self) -> list[str]:
        """Hex digests of every page the prefix store currently indexes —
        the serve STATS payload exports these so the router's fleet
        directory can key shared-prefix traffic onto this replica.
        Thread-safe snapshot (a concurrent driver mutation just means
        the list is a step stale — the directory is best-effort)."""
        return [h.hex() for h in list(self._prefix_pages)]

    def _update_prefix_gauges(self):
        """The prefix store's observable size: indexed page count plus
        the bytes those pages pin in the pool
        (``engine.prefix_store_bytes`` — the fleet directory's capacity
        yardstick, docs/OBSERVABILITY.md)."""
        n = len(self._page_hash)
        self._g_prefix_pages.set(n)
        self._g_prefix_bytes.set(
            n * self.ecfg.page_size * self.kv_bytes_per_token)

    def _retain_page(self, page: int) -> bool:
        """Allocator retain hook: a refcount-0 page the prefix store still
        indexes stays resident (LRU-tracked) instead of rejoining the free
        list — its contents are a future request's prefill. Under
        degradation level >= 2 retention stops: freed pages go straight
        back to the free list (capacity over cache warmth) — but their
        contents DEMOTE to the host tier first when one is configured
        (docs/ROBUSTNESS.md "Pressure ladder"), so shedding HBM warmth no
        longer throws the prefill work away."""
        if self._deg >= 2:
            h = self._page_hash.pop(page, None)
            if h is not None and self._prefix_pages.get(h) == page:
                del self._prefix_pages[h]
            self._prefix_idle.pop(page, None)
            if h is not None:
                demoted = self._spill_pages([page], [h])
                self._m_prefix_evict.inc()
                self._m_prefix_demote.inc(demoted)
                self._m_prefix_discard.inc(1 - demoted)
            self._update_prefix_gauges()
            return False
        if page in self._page_hash:
            self._prefix_idle[page] = None        # most-recently idled last
            return True
        return False

    def _evict_prefix_pages(self, n: int) -> list[int]:
        """Allocator evict hook: surrender up to n LRU refcount-0 cached
        pages under pool pressure, dropping their store entries. Live
        (refcount > 0) pages are never offered — eviction cannot touch a
        running slot. With a tier store configured the surrendered pages'
        CONTENTS spill to host RAM / disk first (`_spill_pages`), so the
        eviction is a demotion, not a loss."""
        out, hashes = [], []
        while len(out) < n and self._prefix_idle:
            page, _ = self._prefix_idle.popitem(last=False)
            h = self._page_hash.pop(page)
            if self._prefix_pages.get(h) == page:
                del self._prefix_pages[h]
            out.append(page)
            hashes.append(h)
        if out:
            demoted = self._spill_pages(out, hashes)
            self._m_prefix_evict.inc(len(out))
            self._m_prefix_demote.inc(demoted)
            self._m_prefix_discard.inc(len(out) - demoted)
        self._update_prefix_gauges()
        return out

    def _spill_pages(self, pages: list[int], hashes: list[bytes]) -> int:
        """Demote evicted refcount-0 prefix pages into the tier store:
        ONE batched `export_pages` gather pulls their contents (values +
        int8 scales) off the device, then each page lands as a framed,
        checksummed blob under its chain hash (kv_tiers.py). Returns the
        number of pages demoted — 0 when no tiers are configured or the
        spill failed (``kvtier.spill_fail`` fault / an I/O error): the
        economy degrades to plain discard, an eviction NEVER fails."""
        if self._tiers is None or not pages:
            return 0
        t0 = time.perf_counter()
        try:
            if faults.ENABLED and faults.fire("kvtier.spill_fail"):
                raise faults.FaultInjected(
                    "injected spill failure (kvtier.spill_fail)")
            kb, vb, ksb, vsb = self._cache.export_pages(pages)
            for i, h in enumerate(hashes):
                self._tiers.put(h, kb[:, i], vb[:, i],
                                None if ksb is None else ksb[:, i],
                                None if vsb is None else vsb[:, i])
        except Exception as e:  # noqa: BLE001 — spill is best-effort
            self._m_spill_fail.inc()
            flight.record("engine.kvtier.spill_fail", pages=len(pages),
                          error=f"{type(e).__name__}: {e}")
            return 0
        self._h_spill.observe((time.perf_counter() - t0) * 1e3)
        flight.record("engine.kvtier.spill", pages=len(pages))
        return len(pages)

    def _tier_reupload(self, hashes: list[bytes], prompt_len: int,
                       shared: list[int], pages: list[int]) -> int:
        """Continue a prefix lookup PAST the HBM store into the host/disk
        tiers and re-upload the hits into this request's leading fresh
        ``pages``: one batched `import_pages` scatter per pool (pages and
        scales are immutable once full, so the re-uploaded KV is
        bit-identical to what was spilled). Returns how many leading
        fresh pages now hold valid KV — the caller starts its prefill
        after them, exactly like an HBM hit. 0 on miss, typed tier
        refusal, or an armed ``kvtier.reupload_fail``: the request just
        cold-prefills, tiers never fail a request."""
        if self._tiers is None or not hashes or not pages:
            return 0
        limit = (int(prompt_len) - 1) // self.ecfg.page_size
        want = hashes[len(shared):limit][:len(pages)]
        entries = []
        for h in want:
            e = self._tiers.get(h)
            if e is None:
                break                 # chained hashes: stop at first miss
            entries.append(e)
        if not entries:
            return 0
        n = len(entries)
        t0 = time.perf_counter()
        try:
            if faults.ENABLED and faults.fire("kvtier.reupload_fail"):
                raise faults.FaultInjected(
                    "injected re-upload failure (kvtier.reupload_fail)")
            def stack(f):
                return np.stack([getattr(e, f) for e in entries], axis=1)
            self._cache = self._cache.import_pages(
                pages[:n], stack("k"), stack("v"),
                *((stack("ks"), stack("vs")) if self._quant_kv else ()))
        except Exception as e:  # noqa: BLE001 — degrade to cold prefill
            self._m_reupload_fail.inc()
            flight.record("engine.kvtier.reupload_fail", pages=n,
                          error=f"{type(e).__name__}: {e}")
            return 0
        for e in entries:
            (self._m_reup_host if e.tier == "host"
             else self._m_reup_disk).inc()
        self._h_reupload.observe((time.perf_counter() - t0) * 1e3)
        flight.record("engine.kvtier.reupload", pages=n,
                      from_host=sum(1 for e in entries
                                    if e.tier == "host"),
                      from_disk=sum(1 for e in entries
                                    if e.tier == "disk"))
        return n

    def tier_hashes(self) -> list[str]:
        """Hex chain hashes of every SPILLED page (host tier first) — the
        serve STATS payload advertises these alongside `prefix_hashes`
        so the router's fleet directory routes a spilled prefix to the
        replica that can re-upload it instead of re-prefilling anywhere
        (docs/SERVING.md "KV tiering")."""
        return [] if self._tiers is None else self._tiers.hashes()

    def _flush_prefix(self):
        """Drop EVERY prefix-store entry: idle cached pages return to the
        free list immediately; pages still owned by live slots merely lose
        their index (the retain hook declines them at retirement). The
        host/disk tiers flush too — spilled KV is the same stale-weights
        hazard as resident KV. Used by `refresh_params` — KV cached under
        old weights must never serve a new-weights request."""
        idle = list(self._prefix_idle)
        self._prefix_idle.clear()
        self._prefix_pages.clear()
        self._page_hash.clear()
        if idle:
            self.allocator.reclaim(idle)
        if self._tiers is not None:
            self._tiers.flush()
        self._update_prefix_gauges()

    def _prefix_lookup(self, hashes: list[bytes]) -> list[int]:
        """Longest cached prefix: pages for the leading run of hash hits."""
        pages = []
        for h in hashes:
            p = self._prefix_pages.get(h)
            if p is None:
                break
            pages.append(p)
        return pages

    def _attach_prefix(self, pages: list[int]):
        """A hit: grow the shared pages' refcounts and pull any idle ones
        off the LRU (they are live again)."""
        self.allocator.share(pages)
        for p in pages:
            self._prefix_idle.pop(p, None)

    def _register_prefix(self, hashes: list[bytes], pages: list[int]):
        """Index a freshly prefilled prompt's full pages in the store (the
        shared leading pages of a hit are already indexed — first writer
        wins; contents are identical by construction)."""
        for h, p in zip(hashes, pages):
            if h in self._prefix_pages or p in self._page_hash:
                continue
            self._prefix_pages[h] = p
            self._page_hash[p] = h
        self._update_prefix_gauges()

    # ------------------------------------------------------------ admission

    def submit(self, prompt_ids, max_new_tokens=32, trace=None,
               cache=True, speculate=True,
               deadline_s=None, request_key=None,
               temperature=1.0, top_k=0, seed=0) -> GenerateRequest:
        """Queue one prompt (1-D or [1, S] int array). Thread-safe.
        ``trace``: a `RequestTrace` created upstream (serve's wire-accept)
        so the SLO clock starts there; default starts it here.
        ``cache=False`` keeps this prompt out of the prefix cache (neither
        reuses nor registers pages); ``speculate=False`` disables n-gram
        drafting for this request on a speculating engine — both default
        on, gated by the engine-level knobs. ``deadline_s`` bounds the
        request end to end: past it the engine retires it with a typed
        ``DeadlineExceeded`` instead of tokens (enforced at admission —
        an expired request never reaches a prefill program — and at every
        harvest; docs/ROBUSTNESS.md). Raises typed ``Overloaded`` when
        the queue is past `EngineConfig.max_queue_depth` /
        ``max_queue_tokens`` — admission control fails fast so the router
        can place the work elsewhere.

        ``request_key`` (docs/ROBUSTNESS.md "Control-plane HA"): a
        client-generated 16-byte idempotency key. A resubmit of a key
        whose request is still IN FLIGHT returns the SAME
        :class:`GenerateRequest` (the resubmit attaches to the running
        generation instead of re-running prefill+decode —
        ``engine.dedup_hits``); a key that already COMPLETED replays the
        cached answer or typed error verbatim (``engine.dedup_replays``).
        A key whose attempt was CANCELLED re-executes: the cancel meant
        no answer was produced, and the resubmit is a live client asking
        again. Absent key = legacy at-least-once, exactly the old
        behavior. Dedup hits bypass admission control — attaching to
        work already paid for costs nothing, so a draining or shedding
        engine still answers them.

        ``temperature``/``top_k``/``seed`` (``EngineConfig.sampling``):
        the fused on-device sampler's per-request knobs — the SAME
        semantics and key discipline as ``fast_generate`` (temperature
        before the top-k mask, one key split from ``PRNGKey(seed)`` per
        sampled token), bit-identical output for a shared seed at B=1.
        Non-greedy params on an engine built without ``sampling=True``
        are a loud ValueError — there is no host-sampled fallback (that
        fallback would be a per-step logits readback, exactly what the
        fused sampler exists to kill)."""
        ids = np.asarray(
            prompt_ids._data if hasattr(prompt_ids, "_data") else prompt_ids)
        ids = np.ascontiguousarray(ids).reshape(-1).astype(np.int32)
        if ids.size == 0:
            raise ValueError("empty prompt")
        n = int(max_new_tokens)
        if n < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n}: a "
                             "request that can never emit would occupy a "
                             "slot it can never retire from")
        if ids.size + n > self.max_seq_len:
            raise ValueError(
                f"prompt {ids.size} + max_new_tokens {n} exceeds engine "
                f"max_seq_len={self.max_seq_len}")
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self._check_sample_params(temperature, top_k)
        key = self._dedup_key(request_key)
        req = GenerateRequest(ids, n, trace=trace, cache=cache,
                              speculate=speculate, deadline_s=deadline_s,
                              request_key=key, temperature=temperature,
                              top_k=top_k, seed=seed)
        # double-checked admission: the FIRST check fails a shed/dead/
        # draining submit fast, BEFORE the O(prompt) blake2b pass below —
        # admission control exists for exactly the moments that pass
        # would hurt most. The hash then runs on the submitter's thread
        # with no lock held (never on the driver, never under _qlock),
        # and the SECOND check inside the enqueue lock re-validates
        # (state may have moved during the hash; the rare wasted hash of
        # a late shed is the cheap side of that race). The dedup lookup
        # runs BEFORE each admission check: an attach/replay must succeed
        # on a draining or full engine.
        smp = (float(temperature), int(top_k), int(seed))
        with self._qlock:
            prev = self._dedup_lookup(key, ids, n, sample=smp)
            if prev is not None:
                return prev
            self._check_admission(ids.size)
        if self._prefix_enabled and req.cache:
            req.page_hashes = self._page_hashes(ids)
        with self._work:
            # authoritative dedup check, atomic with the enqueue: two
            # concurrent resubmits of one key must not both enqueue
            prev = self._dedup_lookup(key, ids, n, sample=smp)
            if prev is not None:
                return prev
            self._check_admission(ids.size)
            # trace/ring entries only for ACCEPTED submits: a rejected one
            # must not leave a phantom never-retired request in a watchdog
            # post-mortem
            req.trace.mark_submit()
            flight.record("engine.submit", request_id=req.request_id,
                          prompt_len=int(ids.size), max_new_tokens=n)
            self._queue.append(req)
            self._queue_tokens += int(ids.size)
            self._g_queue.set(len(self._queue))
            self._register_dedup(key, req)
            self._work.notify()
        self._m_requests.inc()
        return req

    def _check_sample_params(self, temperature, top_k):
        """Typed refusal for sampling params the engine cannot honor —
        a silent greedy fallback would return wrong-distribution tokens."""
        t, k = float(temperature), int(top_k)
        if t <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        if k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if (t != 1.0 or k != 0) and not self._sampling:
            raise ValueError(
                "sampled generation (temperature/top_k) needs "
                "EngineConfig(sampling=True): the fused on-device sampler "
                "is compiled into the step programs, not a per-step host "
                "round-trip")

    # ------------------------------------------------- idempotency dedup

    def _dedup_key(self, request_key) -> bytes | None:
        """Normalize + validate one wire request key (None passes
        through; dedup disabled drops it)."""
        if request_key is None or not self.ecfg.dedup_capacity:
            return None
        key = bytes(request_key)
        if len(key) != 16:
            raise ValueError(
                f"request_key must be exactly 16 bytes, got {len(key)}")
        return key

    def _dedup_lookup(self, key: bytes | None, ids: np.ndarray | None,
                      mnt: int | None,
                      sample: tuple | None = None) -> GenerateRequest | None:
        """One dedup probe (caller holds ``_qlock``): returns the request
        to attach to / replay, or None for a miss. A key reused for a
        DIFFERENT prompt, budget, or sampling params — ``sample`` is the
        submit's (temperature, top_k, seed) — is a client bug and refused
        loudly: silently answering with another DISTRIBUTION's tokens
        would be far worse than failing (skipped for migrated-in
        requests, whose context legitimately grew past the original
        prompt and whose seed did not travel)."""
        if key is None:
            return None
        prev = self._dedup.get(key)
        if prev is None:
            return None
        if ids is not None and not prev.imported and (
                int(prev.max_new_tokens) != int(mnt)
                or not np.array_equal(prev.prompt, ids)
                or (sample is not None
                    and sample != (prev.temperature, prev.top_k,
                                   prev.seed))):
            raise ValueError(
                "request_key reused for a different request (prompt, "
                "max_new_tokens, or temperature/top_k/seed mismatch) — "
                "an idempotency key names ONE logical request")
        if not prev.done:
            self._dedup.move_to_end(key)
            self._m_dedup_hits.inc()
            # a pending disconnect-cancel for the original attempt is
            # void: a new party just asked for this answer (the resubmit
            # IS the evidence the client still wants it)
            self._cancels.pop(prev.request_id, None)
            flight.record("engine.dedup_attach",
                          request_id=prev.request_id)
            return prev
        if prev._error is not None and prev._error.startswith("Cancelled"):
            # a cancelled attempt produced no answer; the resubmit is a
            # fresh attempt (at-most-once holds: the first never ran to
            # completion). Drop the entry so the new request registers.
            del self._dedup[key]
            return None
        self._dedup.move_to_end(key)
        self._m_dedup_replays.inc()
        flight.record("engine.dedup_replay", request_id=prev.request_id)
        return prev

    def _register_dedup(self, key: bytes | None, req: GenerateRequest):
        """Remember a freshly accepted keyed request (caller holds
        ``_qlock``); LRU-evict past the configured bound."""
        if key is None:
            return
        self._dedup[key] = req
        self._dedup.move_to_end(key)
        cap = int(self.ecfg.dedup_capacity)
        while len(self._dedup) > cap:
            self._dedup.popitem(last=False)

    def _check_admission(self, n_tokens: int):
        """Refuse-or-pass gate for one submit. Caller holds ``_qlock``.
        Raises the typed not-taking-work errors (dead/draining) or the
        SHED rung of the pressure ladder: past the configured queue bound
        the submit fails fast with a typed, resubmittable ``Overloaded``
        instead of joining a queue it would only time out in."""
        self._refuse_not_accepting()
        mqd, mqt = self.ecfg.max_queue_depth, self.ecfg.max_queue_tokens
        if mqd is not None and len(self._queue) >= int(mqd):
            self._m_shed.inc()
            raise Overloaded(
                f"engine queue full: depth {len(self._queue)} >= "
                f"max_queue_depth {int(mqd)}")
        # backlog bound only: an EMPTY queue always admits — a single
        # prompt bigger than the bound would otherwise shed with a
        # "retry elsewhere" error that every identically-configured
        # replica repeats forever (max_seq_len already validated the
        # prompt itself)
        if mqt is not None and self._queue and \
                self._queue_tokens + n_tokens > int(mqt):
            self._m_shed.inc()
            raise Overloaded(
                f"engine queue full: {self._queue_tokens} queued + "
                f"{n_tokens} new tokens > max_queue_tokens {int(mqt)}")

    def cancel(self, request_id: str,
               reason: str = "cancelled by client") -> bool:
        """Cancel a queued or running request by id. Thread-safe: posts to
        the driver's cancellation mailbox; the driver retires the slot and
        reclaims its pages (shared prefix-cache pages via the per-owner
        refcounted free — a cancel can never free a page another slot
        still attends) BETWEEN fixed-shape steps, so cancellation never
        perturbs a program shape (tests/test_no_retrace.py). Returns True
        when the id names a request the engine still owes an answer;
        False for unknown/already-finished ids (idempotent — a retirement
        racing the cancel is a no-op, not an error). The mailbox post is
        UNCONDITIONAL: a live request caught mid-admission (popped from
        the queue, slot not yet published) is visible in neither
        structure, and its cancel must still land — the return value may
        then be a conservative False while the cancel takes effect; a
        post for a truly unknown id is discarded at the next `_reap`
        swap."""
        with self._work:
            if self._dead is not None:
                return False
            self._cancels[request_id] = reason
            known = any(r.request_id == request_id for r in self._queue) \
                or any(r.request_id == request_id
                       for _, r in self._imports)
            self._work.notify()
        # slot/prefilling membership is driver-owned state; this read is a
        # benign race (a stale True just means the reap finds nothing)
        return known or any(
            r is not None and r.request_id == request_id and not r.done
            for r in self._slot_req)

    # ------------------------------------------- cancellation / deadlines

    def _reap(self):
        """Driver-side enforcement point, run at every step start BEFORE
        admission/dispatch: apply posted cancellations and expire blown
        deadlines. A queued request leaves the FIFO here — before its
        prefill (or next chunk) is ever dispatched, so a dead request
        costs zero prefill tokens (`engine.prefill_tokens` pins this) —
        and a slotted one retires between fixed-shape steps, freeing its
        slot and pages (per-owner refcounted free: shared prefix pages
        survive for other owners)."""
        with self._qlock:
            cancels, self._cancels = self._cancels, {}
            now = time.monotonic()
            drop = []
            for req in self._queue:
                if req.request_id in cancels:
                    drop.append((req, f"Cancelled: "
                                      f"{cancels[req.request_id]}"))
                elif req.expired(now):
                    drop.append((req, self._deadline_error(req)))
            for req, _ in drop:
                self._queue.remove(req)
                self._queue_tokens -= int(req.prompt.size)
            if drop:
                self._g_queue.set(len(self._queue))
            # the import mailbox is cancellable too: a deferred migration
            # import whose sender gave up (disconnect, wait budget) must
            # not later claim a slot and decode into a dead future
            drop_imports = [(h, req) for h, req in self._imports
                            if req.request_id in cancels]
            if drop_imports:
                # rebuild instead of deque.remove: equality on the
                # (KVHandoff, req) tuple hits the dataclass __eq__ over
                # numpy page arrays — "truth value is ambiguous" on the
                # driver thread the moment two deferred imports share a
                # shape. Filter by request identity like abort() does.
                keep = [(h, req) for h, req in self._imports
                        if req.request_id not in cancels]
                self._imports.clear()
                self._imports.extend(keep)
        for req, err in drop:
            self._count_reap(err)
            flight.record("engine.reap", request_id=req.request_id,
                          where="queue", error=err)
            req._finish(err)
        for _, req in drop_imports:
            err = f"Cancelled: {cancels[req.request_id]}"
            self._count_reap(err)
            flight.record("engine.reap", request_id=req.request_id,
                          where="import_mailbox", error=err)
            req._finish(err)
        now = time.monotonic()
        for slot in range(self.ecfg.max_slots):
            req = self._slot_req[slot]
            if req is None or req.done:
                continue
            if req.request_id in cancels:
                err = f"Cancelled: {cancels[req.request_id]}"
            elif req.expired(now):
                err = self._deadline_error(req)
            else:
                continue
            self._count_reap(err)
            flight.record("engine.reap", request_id=req.request_id,
                          where="slot", error=err)
            self._retire(slot, error=err)

    @staticmethod
    def _deadline_error(req: GenerateRequest) -> str:
        return (f"DeadlineExceeded: request deadline "
                f"({req.deadline_s:g}s) passed after "
                f"{len(req.generated)} generated tokens")

    def _count_reap(self, err: str):
        (self._m_deadline if err.startswith("DeadlineExceeded")
         else self._m_cancelled).inc()

    # -------------------------------------------------- degradation ladder

    def _pressure(self) -> float:
        """Queue pressure in [0, inf): the occupied fraction of whichever
        admission-control bound is closest to tripping. Caller holds
        ``_qlock``. 0.0 when no bound is configured (the ladder is
        inert without admission control — pressure has no yardstick)."""
        frac = 0.0
        if self.ecfg.max_queue_depth:
            frac = max(frac, len(self._queue)
                       / int(self.ecfg.max_queue_depth))
        if self.ecfg.max_queue_tokens:
            frac = max(frac, self._queue_tokens
                       / int(self.ecfg.max_queue_tokens))
        return frac

    def _apply_degradation(self):
        """Degrade BEFORE shedding (docs/ROBUSTNESS.md "Pressure
        ladder"): level 1 (pressure >= 0.5) turns speculation off —
        verify-step overhead stops competing with the backlog; level 2
        (>= 0.75) additionally stops retaining prefix-cache pages and
        returns the idle ones to the free list — capacity over cache
        warmth — DEMOTING their contents to the host tier first when KV
        tiering is configured, so the warmth is recoverable by re-upload
        instead of lost; level 3 (>= 1.0) is the shed threshold `submit`
        enforces.
        Levels drop back automatically as the queue drains. Driver-thread
        only (mutates the prefix store/allocator)."""
        with self._qlock:
            frac = self._pressure()
        target = 3 if frac >= 1.0 else 2 if frac >= 0.75 \
            else 1 if frac >= 0.5 else 0
        if target == self._deg:
            return
        if target >= 2 > self._deg:
            self._shrink_prefix()
        self._deg = target
        self._g_deg.set(target)
        flight.record("engine.degradation", level=target,
                      pressure=round(frac, 3))

    def _shrink_prefix(self):
        """Degradation level >= 2: return every IDLE cached page to the
        free list (same store bookkeeping as pressure eviction, so their
        contents demote to the spill tiers first when configured — live
        slots' pages only lose their index via the retain hook declining
        them at retirement)."""
        idle = self._evict_prefix_pages(len(self._prefix_idle))
        if idle:
            self.allocator.reclaim(idle)

    def _free_slots(self):
        # occupancy, not the dispatch mask: a slot whose budget is spent
        # stays occupied until its pending tokens are harvested
        return [i for i in range(self.ecfg.max_slots)
                if self._slot_req[i] is None]

    def _occupied(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def _admit(self):
        """Drain the queue into free slots while pages allow: assign slot,
        attach the longest cached prefix (prefix cache), allocate fresh
        pages for the rest, launch the uncached tail's prefill (its first
        token stays on the chip: `_first_token`). Returns how many
        requests it placed."""
        placed = 0
        while True:
            slots = self._free_slots()
            if not slots:
                return placed
            with self._qlock:
                if not self._queue:
                    self._g_queue.set(0)
                    return placed
                req = self._queue[0]
                if req.done or req.expired():
                    # cancelled/aborted/expired while queued: skipped
                    # BEFORE any prefill program runs — zero prefill
                    # tokens spent on a request nobody will read
                    # (engine.prefill_tokens pins this)
                    self._queue.popleft()
                    self._queue_tokens -= int(req.prompt.size)
                    self._g_queue.set(len(self._queue))
                    if not req.done:
                        err = self._deadline_error(req)
                        self._count_reap(err)
                        flight.record("engine.reap",
                                      request_id=req.request_id,
                                      where="admission", error=err)
                        req._finish(err)
                    continue
                total = -(-(req.prompt.size + req.max_new_tokens)
                          // self.ecfg.page_size) if self._pooled else 0
                shared: list[int] = []
                if self._prefix_enabled and req.cache:
                    # a long shared context is thousands of pages a hit:
                    # the walk of the hash chain and the refcounts are a
                    # span of their own (docs/OBSERVABILITY.md)
                    with metrics.span("engine.prefix_attach",
                                      cat="engine") as psp:
                        shared = self._prefix_lookup(req.page_hashes)
                        # the page holding the LAST prompt token is always
                        # recomputed, never shared (the copy-on-write "last
                        # partial page" copy): the tail prefill needs >= 1
                        # real token to produce the first sampled output
                        shared = shared[:(req.prompt.size - 1)
                                        // self.ecfg.page_size]
                        if shared:
                            # claim the cached pages BEFORE alloc: alloc
                            # may evict refcount-0 cached pages under
                            # pressure, and claiming makes these ones live
                            # (un-evictable)
                            self._attach_prefix(shared)
                        psp.args["pages"] = len(shared)
                pages = self.allocator.alloc(total - len(shared)) \
                    if self._pooled else []
                if pages is None:
                    if shared:
                        self.allocator.free(shared)  # back to idle cache
                    if not (self._occupied() or self._inflight):
                        # nothing will ever retire to free pages: the pool
                        # itself is too small for this request (report the
                        # TOTAL need — a post-sharing count could look
                        # satisfiable next to the pool size)
                        self._queue.popleft()
                        self._queue_tokens -= int(req.prompt.size)
                        self._g_queue.set(len(self._queue))
                        req._finish(error=f"request needs {total} pages, "
                                    f"pool has "
                                    f"{self.allocator.num_pages - 1}")
                        continue
                    return placed          # wait for a retirement
                if self._prefix_enabled and req.cache:
                    (self._m_prefix_hit if shared
                     else self._m_prefix_miss).inc()
                    self._m_prefix_reused.inc(len(shared))
                self._queue.popleft()
                self._queue_tokens -= int(req.prompt.size)
                self._g_queue.set(len(self._queue))
            self._h_wait.observe(time.perf_counter() - req.submit_t)
            # KV tiering: continue the chain past the HBM store — a
            # host/disk hit re-uploads into the leading fresh pages and
            # the prefill below covers only what no tier held
            n_up = 0
            if self._prefix_enabled and req.cache:
                n_up = self._tier_reupload(req.page_hashes,
                                           req.prompt.size, shared, pages)
            self._place(req, slots[0], shared + pages, len(shared) + n_up)
            placed += 1

    def _place(self, req: GenerateRequest, slot: int, pages: list[int],
               n_shared: int = 0):
        """``pages``: the slot's allocation in token order — ``n_shared``
        leading prefix-cache pages (already refcounted) then fresh ones.
        Prefill covers only positions past the shared pages."""
        req.trace.mark_admitted()
        flight.record("engine.admit", request_id=req.request_id,
                      slot=slot, pages=len(pages), shared=n_shared,
                      prompt_len=int(req.prompt.size))
        maxp = self.pages_per_slot
        cached = n_shared * self.ecfg.page_size   # tokens already resident
        row = np.full(maxp, TRASH_PAGE, np.int32)
        row[:len(pages)] = pages
        self._page_table[slot] = row
        self._slot_req[slot] = req
        self._slot_pages[slot] = pages
        # usage metering: prompt tokens the caches answered (prefix-store
        # pages + tier re-uploads) vs the step clock at placement (the
        # page-step occupancy integral closes at _detach_slot)
        req.u_prefill_saved = min(cached, int(req.prompt.size))
        req.u_admit_step = self.step_seq
        if self._sampling:
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
        if self._stateful:
            # the slot's rings and recurrent state are another sequence's:
            # the chunk that starts at 0 reads them as empty
            self._m_state_resets.inc()
        if self._use_chunked(req.prompt.size - cached):
            # decode-priority chunked prefill: the slot holds its pages but
            # stays decode-inactive; step() runs ONE chunk per step after
            # the decode dispatch (`_advance_prefill`) until the prompt is
            # fully cached, then the slot joins the decode batch. A prefix
            # hit just starts the chunk cursor past the shared pages.
            self._lengths[slot] = 0
            self._prefilling[slot] = {"req": req, "done": cached,
                                      "t0": time.perf_counter()}
            return
        t0 = time.perf_counter()
        toks = self._run_prefill(req.prompt, row, start=cached,
                                 slot=slot, req=req)
        self._first_token(slot, req, toks, t0)

    def _put_sampler(self, up, packed, slot, req, final=None):
        """Write the fields a SAMPLING engine's prefill upload carries
        (inference/programs.py::prefill_upload). ``slot`` None routes the
        chain write to the scratch row B (slotless export/stream
        prefills); ``req`` None (or a greedy request) rides the argmax arm
        with a frozen zero key. The PRNGKey(seed) materialization (a tiny
        device round trip) happens once per REQUEST, cached — and only
        for the upload that consumes it (the one-shot / FINAL chunk):
        intermediate chunks never sample, so they ship zero key words."""
        packed[up["key_slot"]] = self.ecfg.max_slots if slot is None \
            else int(slot)
        packed[up["temp"]] = np.float32(
            1.0 if req is None else req.temperature).view(np.int32)
        if req is not None:
            if final is None or final:
                if req._seed_key is None:
                    req._seed_key = np.asarray(
                        jax.random.PRNGKey(int(req.seed)), np.uint32)
                packed[up["seed"]] = req._seed_key.view(np.int32)
            packed[up["top_k"]] = int(req.top_k)

    def _launch_prefill(self, sp, tokens: int, chunk: bool, ids: np.ndarray,
                        where: dict, row: np.ndarray, slot, req,
                        final=None):
        """Pack ONE prefill upload (``ids`` into a ``tokens``-wide program,
        ``where`` = its length, or its start and valid count) and enqueue
        its program: one fused upload, no readback. Returns the token
        chain the program returns: the engine's own from here on, with
        ``slot``'s entry set to the sampled token (by a one-shot launch or
        a FINAL chunk). A slotless launch (``slot`` None: export, stream)
        gets its token in entry 0 of a chain that only its caller reads;
        the engine's chain is not donated and stays as it was. The single
        owner of the host side of `prefill_upload` for the one-shot,
        interleaved, back-to-back and prefix-tail paths. ``sp`` is the
        caller's open `engine.prefill_launch` span (`_note_first`)."""
        up = self._prefill_upload(tokens, chunk)
        packed = np.zeros(up.shape, np.int32)
        packed[up["ids"]][:ids.size] = ids
        for name, value in where.items():
            packed[up[name]] = value
        packed[up["row"]] = row
        packed[up["slot"]] = 0 if slot is None else slot
        if chunk:
            packed[up["final"]] = bool(final)
        if self._sampling:
            self._put_sampler(up, packed, slot, req, final)
        exe = self._prefill_exe(tokens, chunk)
        self._note_first(exe, sp)
        self._m_h2d.inc()
        self._m_prefill_launches.inc()
        self._m_prefill_tokens.inc(int(ids.size))
        if req is not None:
            req.u_prefill_computed += int(ids.size)
        toks, self._cache = exe(self._params, self._cache, self._tok_dev,
                                jax.device_put(packed))
        if slot is not None:
            self._tok_dev = toks
        return toks

    def _run_prefill(self, ids: np.ndarray, row: np.ndarray,
                     start: int = 0, slot=None, req=None):
        """Fill ``row``'s pages with the prompt's KV from position
        ``start`` on (0 = whole prompt; a prefix-cache hit passes the
        cached token count) — one-shot bucketed, back-to-back chunks, or a
        bucketed TAIL chunk — and return the last launch's token chain
        (`_launch_prefill`), unread. Shared by `_place` (which passes
        ``slot``/``req``: the slot's chain entry takes the first token, and
        a sampling engine seeds the slot's key chain) and `prefill_export`
        (which has no slot to interleave around, so its chunks run
        consecutively)."""
        s0 = ids.size
        if start or self._use_chunked(s0):
            # chunk-program prefill from ``start`` on: the configured chunk
            # size when chunking is on, else the tail's own pow-2 bucket
            # (one program per bucket, AOT). A prefix-cache tail attends
            # its queries over the SHARED pages + its own writes, masked by
            # absolute position — zero prefill work for cached pages.
            c = int(self.ecfg.prefill_chunk_tokens) \
                if self.ecfg.prefill_chunk_tokens is not None \
                else self.bucket_for(s0 - start)
            toks = None
            for done in range(start, s0, c):
                toks = self._run_chunk(ids, done, row, c, slot=slot,
                                       req=req, final=done + c >= s0,
                                       kind="tail")
        else:
            with metrics.span("engine.prefill_launch", cat="engine",
                              kind="oneshot", tokens=int(s0),
                              request_id=req and req.request_id) as sp:
                if self._stateful:
                    self._count_window_pages(0, s0)
                toks = self._launch_prefill(sp, self.bucket_for(s0), False,
                                            ids, dict(length=s0), row, slot,
                                            req)
        return toks

    def _count_window_pages(self, lo: int, hi: int):
        """`engine.window_pages_recycled` for positions ``lo .. hi - 1`` of
        one slot: each page opened past the ring's capacity reuses a page
        the window slid out of."""
        if not self._window_pages:
            return
        ps = self.ecfg.page_size
        first = max(-(-lo // ps), self._window_pages)
        self._m_win_recycled.inc(max(0, -(-hi // ps) - first))

    def _read_first_token(self, toks, slot=None) -> int:
        """Block on a prefill's first token, here and now: entry ``slot``
        of the chain its launch returned (a slotless launch's: entry 0).
        For who cannot wait for the fifo: export and stream prefills, whose
        caller takes the token away, and a speculating engine."""
        with metrics.span("engine.harvest", cat="engine", of="prefill",
                          tokens=1):
            first = int(np.asarray(toks)[slot or 0])
        self._m_d2h.inc()
        return first

    def _run_chunk(self, ids: np.ndarray, done: int, row: np.ndarray,
                   c: int | None = None, slot=None, req=None,
                   final: bool = False, kind: str = "chunk"):
        """Enqueue ONE prefill chunk (``ids[done:done+c]`` against page
        ``row``) for the interleaved (`_advance_prefill`), back-to-back
        (`_run_prefill`), and prefix-tail paths. Returns the token chain
        its program returns (`_launch_prefill`; only the FINAL chunk writes
        a token into it; no readback here). On a sampling engine the FINAL
        chunk samples through the fused sampler and seeds ``slot``'s key
        chain.
        ``kind`` names the launch on its `engine.prefill_launch` span:
        ``chunk`` (one a step, interleaved with decode, or a stream's) or
        ``tail`` (run to the end inside admission by `_run_prefill`)."""
        c = int(self.ecfg.prefill_chunk_tokens) if c is None else int(c)
        chunk = ids[done:done + c]
        carried = {"state_carried": done > 0} if self._stateful else {}
        with metrics.span("engine.prefill_launch", cat="engine", kind=kind,
                          tokens=int(chunk.size),
                          request_id=req and req.request_id,
                          **carried) as sp:
            if self._stateful:
                if done > 0:
                    self._m_state_carries.inc()
                self._count_window_pages(done, done + int(chunk.size))
            toks = self._launch_prefill(
                sp, c, True, chunk, dict(start=done, valid=chunk.size), row,
                slot, req, final)
        self._m_chunks.inc()
        return toks

    def _first_token(self, slot: int, req: GenerateRequest, toks, t0):
        """``slot``'s prompt is cached and its first token is entry
        ``slot`` of ``toks``, the chain the last prefill launch (begun at
        ``t0``) returned: the slot decodes from the next dispatch on. The
        token stays on the chip, where that step reads it, and reaches the
        host through the fifo like every later one (`_harvest_one`), so
        admission waits for nothing. A speculating engine drafts on the
        host from the first token and harvests every step where it
        dispatched it: it reads the token here."""
        if self._spec:
            first = self._read_first_token(toks, slot)
            self._m_first_sync.inc()
            self._h_prefill.observe(time.perf_counter() - t0)
            self._seed_first_token(slot, req, first)
            return
        self._activate(slot, req)
        self._inflight.append((toks, [(slot, req)], t0))
        self._g_inflight.set(len(self._inflight))

    def _activate(self, slot: int, req: GenerateRequest):
        """The slot's context is resident (prefilled here, or imported):
        it decodes from the next dispatch on."""
        self._lengths[slot] = req.prompt.size
        self._budget[slot] = req.max_new_tokens - 1
        # a budget of one token is spent by the prefill: the slot stays
        # occupied and undispatched until its first token retires it
        self._active[slot] = self._budget[slot] > 0
        if self._prefix_enabled and req.cache:
            # the prompt's full pages are resident and correct for every
            # program launched from here on (the device runs them in
            # order): index them for future submits (shared leading pages
            # of a hit are already indexed; chunked and imported pages are
            # equally cache-eligible since all land here)
            self._register_prefix(req.page_hashes, self._slot_pages[slot])

    def _seed_first_token(self, slot: int, req: GenerateRequest,
                          first: int):
        """Activate the slot with a first token the HOST holds (a KV
        import's, or one a speculating engine read): deliver it now, and
        hand it to the next step through the upload's fresh flag.
        Prefill-latency accounting stays with the CALLERS that actually
        ran a prefill — a KV import must not land a ~0 s observation in
        the histogram."""
        self._activate(slot, req)
        self._tokens[slot] = first
        self._fresh[slot] = True
        if self._spec and req.speculate:
            # O(prompt) once at admission, O(1) per token after: the
            # drafter must not rescan the history inside the step loop
            idx = _DraftIndex(req.prompt)
            idx.append(first)
            self._slot_draft[slot] = idx
        req.generated.append(first)
        req.trace.mark_first_token()
        req.u_generated += 1
        self._m_tokens.inc()
        if req.max_new_tokens == 1 or first == self.ecfg.eos_id:
            self._retire(slot)

    def _advance_prefill(self):
        """Run ONE prefill chunk for the oldest prefilling slot. Called
        AFTER the decode dispatch (decode-priority): the chunk queues
        behind the step already in flight instead of delaying it, and the
        next decode step queues behind the chunk — the long prompt's
        prefill wall is spread one chunk per step across the decode
        cadence. Returns True when a chunk ran (step() then knows this
        step did work even with zero decode-active slots)."""
        if not self._prefilling:
            return False
        slot = next(iter(self._prefilling))
        st = self._prefilling[slot]
        req = st["req"]
        c = int(self.ecfg.prefill_chunk_tokens)
        done = st["done"]
        toks = self._run_chunk(req.prompt, done, self._page_table[slot],
                               slot=slot, req=req,
                               final=done + c >= req.prompt.size)
        st["done"] = min(done + c, req.prompt.size)
        if st["done"] >= req.prompt.size:
            del self._prefilling[slot]
            self._first_token(slot, req, toks, st["t0"])
        return True

    def _detach_slot(self, slot: int):
        """Release a slot's device-facing state — pages (per-owner
        refcounted free: shared prefix pages survive for other owners),
        mirrors, draft index — WITHOUT touching the request future. Shared
        by `_retire` (which then finishes the future) and the migration
        export (which hands the future to the serving layer instead)."""
        self._prefilling.pop(slot, None)
        req = self._slot_req[slot]
        if req is not None and req.u_admit_step is not None:
            # close the occupancy integral analytically — pages held x
            # steps held — so the step loop never does usage work
            req.u_page_steps += len(self._slot_pages[slot]) * max(
                0, self.step_seq - req.u_admit_step)
            req.u_admit_step = None
        if self._slot_pages[slot]:
            self.allocator.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._slot_req[slot] = None
        self._slot_draft[slot] = None
        self._active[slot] = False
        self._fresh[slot] = False
        self._budget[slot] = 0
        self._page_table[slot] = TRASH_PAGE
        self._lengths[slot] = 0
        if self._sampling:
            self._temps[slot] = 1.0     # greedy defaults; the stale key
            self._topks[slot] = 0       # row is re-seeded at next prefill

    def _retire(self, slot: int, error: str | None = None):
        req = self._slot_req[slot]
        self._detach_slot(slot)
        if req is not None:
            flight.record("engine.retire", request_id=req.request_id,
                          slot=slot, tokens=len(req.generated), error=error)
            req._finish(error)

    # ----------------------------------------------------------------- step

    def _packed_state(self, drafts=None, draft_lens=None) -> np.ndarray:
        """The host mirrors as the batched step's ONE upload (`step_upload`;
        a verify step's carries its drafts)."""
        up = self._step_upload
        packed = np.empty(up.shape, np.int32)
        packed[:, up["token"]] = self._tokens
        packed[:, up["length"]] = self._lengths
        packed[:, up["flags"]] = (self._active.astype(np.int32) * FLAG_ACTIVE
                                  | self._fresh.astype(np.int32) * FLAG_FRESH)
        packed[:, up["table"]] = self._page_table
        if drafts is not None:
            packed[:, up["draft_len"]] = draft_lens
            packed[:, up["drafts"]] = drafts
        if self._sampling:
            packed[:, up["temp"]] = self._temps.view(np.int32)
            packed[:, up["top_k"]] = self._topks
        return packed

    def _dispatch(self):
        """Enqueue ONE fixed-shape decode step: one fused host->device
        upload, no readback — tokens (and, on a sampling engine, the
        per-slot PRNG key chains) stay on device for the next step."""
        with metrics.span("engine.dispatch", cat="engine",
                          active=int(np.count_nonzero(self._active))) as sp:
            exe = self._step_exe()
            self._note_first(exe, sp)
            self._m_h2d.inc()
            state = jax.device_put(self._packed_state())
            t0 = time.perf_counter()
            self._tok_dev, self._cache = exe(self._params, self._cache,
                                             self._tok_dev, state)
            snapshot = [(int(i), self._slot_req[i])
                        for i in np.flatnonzero(self._active)]
            self._inflight.append((self._tok_dev, snapshot, t0))
            self._g_inflight.set(len(self._inflight))
            # host bookkeeping for the step just enqueued: each active slot
            # advances one position; a slot at its token budget stops being
            # dispatched but stays occupied until its tokens are harvested
            if self._window_pages:
                # a ring wraps onto a page it already used: one recycled
                # for each active slot whose new token opens a page past
                # the ring's capacity
                self._m_win_recycled.inc(int(np.count_nonzero(
                    self._active
                    & (self._lengths % self.ecfg.page_size == 0)
                    & (self._lengths >= self._window_pages
                       * self.ecfg.page_size))))
            self._lengths[self._active] += 1
            self._budget[self._active] -= 1
            self._fresh[:] = False
            self._active &= self._budget > 0
            self._m_steps.inc()

    # ----------------------------------------------------- speculative step

    def _dispatch_spec(self):
        """Enqueue ONE speculative verify step: draft on host (n-gram),
        upload the fused state, return the un-read device handles. The
        harvest is SYNCHRONOUS later in the same step() — the host needs
        each step's accepted tokens to draft the next step's proposals, so
        the in-flight window cannot apply; the >1 tokens an accepted step
        emits amortize the readback it forces."""
        K, B = self._spec_k, self.ecfg.max_slots
        drafts = np.zeros((B, K), np.int32)
        draft_lens = np.zeros(B, np.int32)
        # degradation level >= 1: stop drafting (zero-draft verify steps
        # emit exactly 1 token — SAME warm program, so the ladder never
        # compiles anything mid-overload; tests/test_no_retrace.py)
        active = () if self._deg >= 1 else np.flatnonzero(self._active)
        for slot in active:
            idx = self._slot_draft[slot]
            budget = int(self._budget[slot])   # tokens this step may emit
            if idx is None or budget <= 1:
                continue                       # <=1 left: drafting is waste
            d = idx.draft(K)                   # n-gram proposer: the tokens
            n = min(len(d), K, budget - 1)     # that followed this suffix's
            if n > 0:                          # most recent occurrence
                drafts[slot, :n] = d[:n]
                draft_lens[slot] = n
        with metrics.span("engine.dispatch", cat="engine",
                          active=int(np.count_nonzero(self._active))) as sp:
            exe = self._step_exe()
            self._note_first(exe, sp)
            self._m_h2d.inc()
            state = jax.device_put(self._packed_state(drafts, draft_lens))
            emitted_dev, n_emit_dev, self._tok_dev, self._cache = exe(
                self._params, self._cache, self._tok_dev, state)
            snapshot = [(int(i), self._slot_req[i])
                        for i in np.flatnonzero(self._active)]
            self._fresh[:] = False
            self._m_steps.inc()
            self._m_spec_steps.inc()
            self._m_spec_drafted.inc(int(draft_lens.sum()))
        return emitted_dev, n_emit_dev, snapshot

    def _harvest_spec(self, emitted_dev, n_emit_dev, snapshot) -> int:
        """Read back the verify step's emitted tokens and apply them:
        append 1..k+1 tokens per slot (clamped to budget, truncated at
        EOS), roll lengths forward by exactly the accepted count — the
        page-granular 'rollback' of rejected tokens is just NOT advancing
        past them; their stale KV sits beyond every live position and is
        rewritten before any later query can attend it."""
        with metrics.span("engine.harvest", cat="engine",
                          of="decode") as sp:
            emitted = np.asarray(emitted_dev)
            n_emit = np.asarray(n_emit_dev)
            sp.args["tokens"] = int(sum(n_emit[slot] for slot, _ in snapshot))
        self._m_d2h.inc()
        harvested = accepted = 0
        for slot, req in snapshot:
            if req.done or self._slot_req[slot] is not req:
                continue
            n = min(int(n_emit[slot]), int(self._budget[slot]))
            toks = [int(t) for t in emitted[slot, :n]]
            if self.ecfg.eos_id is not None and self.ecfg.eos_id in toks:
                toks = toks[:toks.index(self.ecfg.eos_id) + 1]
            n = len(toks)
            req.generated.extend(toks)
            idx = self._slot_draft[slot]
            if idx is not None:
                for t in toks:
                    idx.append(t)
            req.trace.mark_tokens(n)
            req.u_generated += n
            req.u_spec_accepted += n - 1
            harvested += n
            accepted += n - 1
            self._lengths[slot] += n
            self._budget[slot] -= n
            self._tokens[slot] = toks[-1]
            self._fresh[slot] = True      # host-authoritative after clamping
            if self._budget[slot] <= 0 or toks[-1] == self.ecfg.eos_id \
                    or len(req.generated) >= req.max_new_tokens:
                self._retire(slot)
            elif req.expired():
                err = self._deadline_error(req)
                self._count_reap(err)
                self._retire(slot, error=err)
        self._m_tokens.inc(harvested)
        self._m_spec_accepted.inc(accepted)
        drafted = self._m_spec_drafted.value
        if drafted:
            self._g_spec_rate.set(self._m_spec_accepted.value / drafted)
        if snapshot:
            self._g_spec_tps.set(harvested / len(snapshot))
        return harvested

    def _harvest_one(self) -> int:
        """Block on the OLDEST in-flight entry's token chain (the only
        blocking readback in the loop) and deliver its snapshot's tokens:
        a decode step's, or the first token of the one request a prefill
        launch admitted (`_first_token`; ``t0`` is then when that prefill
        began). Append to each snapshot request, retire slots that hit
        max_new_tokens or EOS. A request's first token is stamped here,
        when the host holds it."""
        toks_dev, snapshot, t0 = self._inflight.popleft()
        self._g_inflight.set(len(self._inflight))

        def owed(slot, req):
            # not EOS-retired earlier in the fifo, cancelled, expired or
            # aborted since: such a request's tokens are dropped
            return not req.done and self._slot_req[slot] is req
        firsts = sum(not req.generated and owed(slot, req)
                     for slot, req in snapshot)
        with metrics.span("engine.harvest", cat="engine",
                          of="prefill" if firsts else "decode",
                          tokens=len(snapshot)):
            toks_np = np.asarray(toks_dev)
        self._m_d2h.inc()
        if self._n_counts:
            # the family's running totals rode the same array: what they
            # grew by since the last entry (modulo 2**32) is this one's
            seen = toks_np[-self._n_counts:].astype(np.uint32)
            grown = (seen - self._counts_seen).astype(np.int64)
            self._counts_seen = seen
            if self._fam.on_counts is not None:
                self._fam.on_counts(grown)
        if firsts:
            self._m_first_deferred.inc(firsts)
            self._h_prefill.observe(time.perf_counter() - t0)
        n = 0
        for slot, req in snapshot:
            if not owed(slot, req):
                continue
            tok = int(toks_np[slot])
            if req.generated:
                req.trace.mark_tokens(1)
            else:
                req.trace.mark_first_token()
            req.generated.append(tok)
            req.u_generated += 1
            n += 1
            if len(req.generated) >= req.max_new_tokens \
                    or tok == self.ecfg.eos_id:
                self._retire(slot)
            elif req.expired():
                # harvest-side deadline enforcement: the tokens already
                # cost device time, but nobody inside the deadline will
                # read them — typed error, slot + pages back to the pool
                err = self._deadline_error(req)
                self._count_reap(err)
                self._retire(slot, error=err)
        self._m_tokens.inc(n)
        return n

    def step(self) -> bool:
        """Admit waiting requests, enqueue ONE batched decode step plus at
        most one prefill chunk, harvest steps past the in-flight window.
        Returns False when fully idle."""
        self.step_seq += 1
        with metrics.span("engine.step", cat="engine",
                          step_seq=self.step_seq) as sp:
            if faults.ENABLED:
                faults.fire("engine.step_delay")   # armed: sleeps delay_s
                faults.fire("engine.crash")    # armed with exc=: raises —
                #                                serve_loop aborts waiters
            with metrics.span("engine.admit", cat="engine") as adm:
                self._reap()
                if self._migrate_requested:
                    self._do_migrate_out()
                self._apply_imports()
                streamed = self._apply_prefill_jobs()
                self._apply_degradation()
                admitted = self._admit()
                # capacity tripwire: a token at pos >= slot_capacity would
                # spill to the trash page on device
                # (kernels/paged_attention.py); the engine retires the
                # sequence with an error instead of scheduling it
                for slot in np.flatnonzero(
                        self._active & (self._lengths >= self.slot_capacity)):
                    self._retire(int(slot), error=(
                        f"sequence hit slot capacity {self.slot_capacity} "
                        f"(pages_per_slot * page_size); token at position "
                        f"{int(self._lengths[slot])} cannot be cached"))
                n_active = int(self._active.sum())
                working = bool(n_active or self._inflight
                               or self._prefilling)
                # an idle poll stays off the span ring (serve_loop polls
                # twenty times a second for as long as nobody calls)
                idle = not (working or admitted or streamed)
                if idle:
                    adm.discard()
                adm.args.update(admitted=admitted, queued=len(self._queue))
            sp.args.update(active=n_active, inflight=len(self._inflight))
            self._g_occupancy.set(n_active)
            if working:
                # idle polls stay out of the ring: an hour of idle
                # serve_loop must not evict the events around the last
                # real work
                flight.record("engine.step", step_seq=self.step_seq,
                              occupancy=n_active,
                              inflight=len(self._inflight))
            harvested = 0
            spec_pending = None
            if n_active:
                if self._spec:
                    spec_pending = self._dispatch_spec()
                else:
                    self._dispatch()
            # decode-priority: the chunk enqueues AFTER the decode step, so
            # the in-flight decodes' cadence bounds how much a long prompt
            # can add per step (one chunk), never the whole prefill wall
            self._advance_prefill()
            if spec_pending is not None:
                # synchronous harvest (after the chunk enqueued, so chunked
                # prefill keeps its decode-priority slot in the device
                # queue): the host needs the accepted tokens to draft the
                # next step
                harvested += self._harvest_spec(*spec_pending)
            elif n_active:
                while len(self._inflight) >= max(1, self.ecfg.inflight):
                    harvested += self._harvest_one()
            elif self._inflight:
                # nothing dispatchable: drain the fifo so budget-spent
                # slots retire (freeing pages/slots for the next admission)
                harvested += self._harvest_one()
            elif idle:
                sp.discard()           # nor in the step histogram
                with self._qlock:
                    return bool(self._queue) or bool(self._imports) \
                        or bool(self._prefill_jobs)
        dt = sp.dur
        self._h_step.observe(dt)
        if harvested:
            self._g_tps.set(harvested / dt if dt > 0 else 0.0)
        return self._has_work()

    def run_until_idle(self, max_steps: int | None = None):
        """Drive step() until queue, slots and the in-flight window drain
        (tests/bench)."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                raise RuntimeError(
                    f"engine still busy after {max_steps} steps")

    # ----------------------------------------------------------- KV handoff

    def prefill_export(self, prompt_ids) -> KVHandoff:
        """Run this engine's prefill for ``prompt_ids`` and export the
        result as a detached :class:`KVHandoff` instead of entering decode
        — the prefill half of prefill/decode disaggregation. Pages are
        borrowed from the pool for the duration of the call and freed
        before returning. Driver-thread only (runs device programs)."""
        self._refuse_unmovable("prefill_export (KV hand-off)")
        ids = np.asarray(
            prompt_ids._data if hasattr(prompt_ids, "_data") else prompt_ids)
        ids = np.ascontiguousarray(ids).reshape(-1).astype(np.int32)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size >= self.max_seq_len:
            raise ValueError(
                f"prompt {ids.size} leaves no room to decode within "
                f"max_seq_len={self.max_seq_len}")
        n_src = -(-ids.size // self.ecfg.page_size)
        shared: list[int] = []
        hashes: list[bytes] = []
        if self._prefix_enabled:
            # the export path serves the fleet's REPEATED prompts — it gets
            # the same cached-prefix attach as submit (last prompt-token
            # page always recomputed), so only the tail prefills
            hashes = self._page_hashes(ids)
            shared = self._prefix_lookup(hashes)
            shared = shared[:(ids.size - 1) // self.ecfg.page_size]
            if shared:
                self._attach_prefix(shared)
        pages = self.allocator.alloc(n_src - len(shared))
        if pages is None:
            if shared:
                self.allocator.free(shared)
            raise RuntimeError(
                f"prefill_export needs {n_src} pages "
                f"({len(shared)} cached), "
                f"{self.allocator.free_pages} free")
        n_up = 0
        if self._prefix_enabled:
            # counted only once the export can actually proceed (same rule
            # as _admit): a failed alloc must not inflate hit/reuse stats
            (self._m_prefix_hit if shared else self._m_prefix_miss).inc()
            self._m_prefix_reused.inc(len(shared))
            n_up = self._tier_reupload(hashes, ids.size, shared, pages)
        all_pages = shared + pages
        row = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
        row[:n_src] = all_pages
        try:
            first = self._read_first_token(self._run_prefill(
                ids, row,
                start=(len(shared) + n_up) * self.ecfg.page_size))
            t0 = time.perf_counter()
            k_np, v_np, ks_np, vs_np = self._cache.export_pages(
                all_pages)
            if self._quant_kv:
                metrics.histogram("engine.quant_dequant_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            if self._prefix_enabled:
                # the freshly prefilled pages are cache-eligible: register
                # BEFORE freeing so the retain hook keeps them resident —
                # a local resubmit of this prompt then skips the prefill
                self._register_prefix(hashes, all_pages)
        finally:
            self.allocator.free(all_pages)
        metrics.counter("engine.kv_exports").inc()
        return KVHandoff(prompt=ids, first_token=first, k_pages=k_np,
                         v_pages=v_np, page_size=int(self.ecfg.page_size),
                         cache_dtype=np.dtype(self._cdtype).name,
                         k_scales=ks_np, v_scales=vs_np)

    # ------------------------------------------------- prefill page stream

    def submit_prefill_stream(self, prompt_ids, cache: bool = True,
                              trace_ctx=None):
        """Thread-safe send side of DISAGGREGATED prefill (docs/
        SERVING.md "Disaggregated serving"): post one prompt to the
        prefill-job mailbox and return a queue the DRIVER fills as its
        chunked prefill runs — ``("count", n_records)`` first, then one
        ``("rec", bytes)`` per PTKS1 stream record AS EACH CHUNK'S PAGES
        COMPLETE (header, page batches, final record with the seed
        token), then ``("done", None)``; any failure ends the stream
        with ``("err", "<Type>: <msg>")`` instead. The serving layer
        relays records to the chosen decode replica as they land, so the
        wire transfer overlaps the prefill compute.

        The prefix cache applies exactly as in `prefill_export`: cached
        leading pages are attached (and exported — the decode replica
        does not share this store) without re-running their prefill, so
        a fleet-shared prompt costs this worker only its uncached tail;
        ``cache=False`` keeps the prompt out of the store entirely.

        ``trace_ctx`` is an optional ``(trace_id, parent_span)`` hex pair
        (docs/OBSERVABILITY.md "Fleet tracing"): it rides the PTKS1
        header so the decode side joins the same stitched trace, and the
        prefill wall lands as a span in this process's trace ring."""
        self._refuse_unmovable("submit_prefill_stream (KV hand-off)")
        ids = np.asarray(
            prompt_ids._data if hasattr(prompt_ids, "_data") else prompt_ids)
        ids = np.ascontiguousarray(ids).reshape(-1).astype(np.int32)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size >= self.max_seq_len:
            raise ValueError(
                f"prompt {ids.size} leaves no room to decode within "
                f"max_seq_len={self.max_seq_len}")
        sink: _queue.Queue = _queue.Queue()
        with self._work:
            self._refuse_not_accepting()
            self._prefill_jobs.append((ids, bool(cache), trace_ctx, sink))
            self._work.notify()
        return sink

    def _apply_prefill_jobs(self):
        """Driver-side mailbox drain (every step start): run each posted
        prefill-stream job to completion, streaming records into its
        sink. A job failure travels to the waiting connection thread as
        a terminal ``("err", ...)`` item — never onto the driver."""
        if not self._prefill_jobs:
            return False
        ran = False
        while True:
            with self._qlock:
                if not self._prefill_jobs:
                    break
                ids, cache, trace_ctx, sink = self._prefill_jobs.popleft()
            ran = True
            fleet = (*trace_ctx, new_span_id()) if trace_ctx else None
            try:
                with metrics.span("engine.prefill_stream", cat="engine",
                                  fleet=fleet,
                                  prompt_len=int(ids.size)) as sp:
                    sp.args["records"] = self._run_prefill_stream(
                        ids, cache, sink, trace_ctx=trace_ctx)
                sink.put(("done", None))
            except Exception as e:  # noqa: BLE001 — surface to the sender
                sink.put(("err", f"{type(e).__name__}: {e}"))
        return ran

    def _run_prefill_stream(self, ids: np.ndarray, cache: bool, sink,
                            trace_ctx=None):
        """Driver-thread body of one prefill-stream job: chunked prefill
        with a PTKS1 record emitted as each chunk completes its pages.
        Pages are borrowed from the pool for the duration and freed
        before returning (the freshly prefilled ones stay indexed in the
        prefix store, like `prefill_export`). A ``trace_ctx`` rides the
        PTKS1 header. Returns the number of records streamed."""
        from paddle_tpu.serving.disagg import (pack_stream_final,
                                               pack_stream_header,
                                               pack_stream_pages)
        ps = self.ecfg.page_size
        s0 = int(ids.size)
        n_src = -(-s0 // ps)
        shared: list[int] = []
        hashes: list[bytes] = []
        if self._prefix_enabled and cache:
            hashes = self._page_hashes(ids)
            shared = self._prefix_lookup(hashes)
            shared = shared[:(s0 - 1) // ps]
            if shared:
                self._attach_prefix(shared)
        pages = self.allocator.alloc(n_src - len(shared))
        if pages is None:
            if shared:
                self.allocator.free(shared)
            raise RuntimeError(
                f"prefill stream needs {n_src} pages "
                f"({len(shared)} cached), "
                f"{self.allocator.free_pages} free")
        n_up = 0
        if self._prefix_enabled and cache:
            (self._m_prefix_hit if shared else self._m_prefix_miss).inc()
            self._m_prefix_reused.inc(len(shared))
            # KV tiering: a spilled prefix re-uploads into the leading
            # fresh pages — the router routed this prompt HERE because
            # this replica advertised the spilled chain (tier_hashes)
            n_up = self._tier_reupload(hashes, s0, shared, pages)
        n_res = len(shared) + n_up    # resident pages needing no prefill
        all_pages = shared + pages
        row = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
        row[:n_src] = all_pages
        start = n_res * ps
        c = int(self.ecfg.prefill_chunk_tokens) \
            if self.ecfg.prefill_chunk_tokens is not None \
            else self.bucket_for(s0 - start)
        # the record plan is fixed before any device work: one page batch
        # for the cached + re-uploaded prefix (already resident), one per
        # chunk that COMPLETES >= 1 page, and the final record carrying
        # the tail
        chunk_starts = list(range(start, s0, c))
        batches, cursor = [], n_res
        for a in chunk_starts:
            done_pages = min(a + c, s0) // ps
            batches.append((cursor, done_pages - cursor))
            cursor = done_pages
        n_records = 2 + (1 if n_res else 0) \
            + sum(1 for _, n in batches if n > 0)
        sink.put(("count", n_records))

        def _blobs(p0, n):
            return self._cache.export_pages(all_pages[p0:p0 + n])

        try:
            seq = 0
            sink.put(("rec", pack_stream_header(
                seq, ids, ps, np.dtype(self._cdtype).name,
                [self._nl, ps, self._nh, self._dh], n_src, n_records,
                self._quant_kv, trace_ctx=trace_ctx)))
            seq += 1
            if n_res:
                sink.put(("rec",
                          pack_stream_pages(seq, 0,
                                            *_blobs(0, n_res))))
                seq += 1
            toks = None
            for a, (p0, n) in zip(chunk_starts, batches):
                toks = self._run_chunk(ids, a, row, c, final=a + c >= s0)
                if n > 0:
                    sink.put(("rec",
                              pack_stream_pages(seq, p0, *_blobs(p0, n))))
                    seq += 1
            first = self._read_first_token(toks)
            sink.put(("rec", pack_stream_final(
                seq, first, cursor, *_blobs(cursor, n_src - cursor))))
            if self._prefix_enabled and cache:
                self._register_prefix(hashes, all_pages)
        finally:
            self.allocator.free(all_pages)
        metrics.counter("engine.kv_stream_exports").inc()
        flight.record("engine.prefill_stream", prompt_len=s0,
                      records=n_records, cached_pages=len(shared),
                      reuploaded_pages=n_up)
        return n_records

    def import_request(self, handoff: KVHandoff, max_new_tokens=32,
                       trace=None, cache=True,
                       speculate=True) -> GenerateRequest:
        """Resume decode from a :class:`KVHandoff` exported on ANOTHER
        engine/replica: allocate a slot + pages here, scatter the imported
        page contents in, and continue decoding — token-identical to having
        prefilled locally (the first decode step writes the first token's
        KV at position S0 exactly as the local flow would). Driver-thread
        only, and placement is immediate: the handoff path does its own
        admission control upstream, so a full engine raises instead of
        queueing. Pass the ORIGINATING request's ``trace`` to keep SLO
        accounting honest across the transfer — with the default fresh
        trace, TTFT on this engine measures only the import itself."""
        self._refuse_unmovable("import_request (KV hand-off)")
        req = self._build_import_request(handoff, max_new_tokens,
                                         trace=trace, cache=cache,
                                         speculate=speculate)
        with self._work:
            self._refuse_not_accepting()
            req.trace.mark_submit()
        slots = self._free_slots()
        if not slots:
            raise RuntimeError("no free slot for KV import")
        need = -(-(int(req.prompt.size) + req.max_new_tokens)
                 // self.ecfg.page_size)
        pages = self.allocator.alloc(need)
        if pages is None:
            raise RuntimeError(
                f"KV import needs {need} pages, "
                f"{self.allocator.free_pages} free")
        self._place_import(req, handoff, slots[0], pages)
        return req

    def _build_import_request(self, handoff: KVHandoff, max_new_tokens,
                              deadline_s=None, trace=None, cache=True,
                              speculate=True,
                              request_key=None) -> GenerateRequest:
        """Shared validation for BOTH import paths (`import_request` and
        the migration mailbox `submit_import`): check the handoff and the
        budget on the CALLING thread — a refusal must travel back to the
        sender, never surface on the driver — and build the request
        future. Both paths accept the same handoffs by construction; the
        caller applies `_refuse_not_accepting` under its own ``_work``
        acquisition (the mailbox path must refuse and append atomically)."""
        self._check_handoff(handoff)
        ids = np.ascontiguousarray(handoff.prompt).reshape(-1)\
            .astype(np.int32)
        n = int(max_new_tokens)
        if n < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n}")
        if ids.size + n > self.max_seq_len:
            raise ValueError(
                f"prompt {ids.size} + max_new_tokens {n} exceeds engine "
                f"max_seq_len={self.max_seq_len}")
        smp = handoff.sample or {}
        req = GenerateRequest(ids, n, trace=trace, cache=cache,
                              speculate=speculate, deadline_s=deadline_s,
                              request_key=self._dedup_key(request_key),
                              temperature=smp.get("temperature", 1.0),
                              top_k=smp.get("top_k", 0))
        req.imported = True
        if self._prefix_enabled and req.cache:
            # imported pages are cache-eligible: _seed_first_token indexes
            # them, so a shared-prefix submit AFTER the import reuses them
            # — unless the request opted out (the opt-out survives the
            # migration: a cache=False promise holds on every engine)
            req.page_hashes = self._page_hashes(ids)
        return req

    def _refuse_not_accepting(self):
        """Typed not-taking-work refusals (dead/draining). Caller holds
        ``_work`` (or ``_qlock`` on the submit path)."""
        if self._dead is not None:
            raise RuntimeError(f"engine stopped: {self._dead}")
        if self._draining:
            raise RuntimeError(
                "engine draining: not accepting new requests")

    def _check_handoff(self, handoff: KVHandoff):
        """Geometry/dtype refusal shared by `import_request` and the
        migration mailbox (`submit_import`) — a mismatched handoff must
        fail LOUDLY on the posting thread, never silently cast on the
        driver."""
        if int(handoff.page_size) != int(self.ecfg.page_size):
            raise ValueError(
                f"page_size mismatch: handoff {handoff.page_size} vs "
                f"engine {self.ecfg.page_size}")
        if handoff.cache_dtype != np.dtype(self._cdtype).name:
            raise ValueError(
                f"cache dtype mismatch: handoff {handoff.cache_dtype} vs "
                f"engine {np.dtype(self._cdtype).name} — a silent cast "
                f"would break bit-identical decode (kv_dtype must match "
                f"across a handoff)")
        if handoff.sample is not None and not self._sampling:
            raise ValueError(
                "handoff carries fused-sampler state but this engine was "
                "built without EngineConfig(sampling=True) — a greedy "
                "resume would silently change the request's distribution")
        if self._quant_kv and handoff.k_scales is None:
            raise ValueError(
                "int8 KV handoff is missing its scale blobs — refusing a "
                "silently mis-scaled import")
        if self._quant_kv and \
                tuple(handoff.k_scales.shape) != tuple(handoff.k_pages
                                                       .shape[:-1]):
            raise ValueError(
                f"KV handoff scales shape {handoff.k_scales.shape} does "
                f"not match pages shape {handoff.k_pages.shape}")
        nl, n_src, ps, nh, dh = handoff.k_pages.shape
        if (nl, ps, nh, dh) != (self._nl, self.ecfg.page_size, self._nh,
                                self._dh):
            raise ValueError(
                f"cache geometry mismatch: handoff pages "
                f"{handoff.k_pages.shape} vs engine [nl={self._nl}, "
                f"ps={self.ecfg.page_size}, nh={self._nh}, dh={self._dh}]")
        if n_src != -(-int(handoff.prompt.size) // self.ecfg.page_size):
            raise ValueError(
                f"handoff has {n_src} pages for a {handoff.prompt.size}-"
                f"token prompt at page_size {self.ecfg.page_size}")

    def _place_import(self, req: GenerateRequest, handoff: KVHandoff,
                      slot: int, pages: list[int]):
        """Driver-thread placement of a VALIDATED handoff: scatter the
        imported page contents into this pool's pages, publish the slot,
        seed the first token. Shared by `import_request` (immediate,
        raises on a full engine) and `_apply_imports` (the migration
        mailbox, which defers instead)."""
        n_src = handoff.k_pages.shape[1]
        self._m_requests.inc()
        req.trace.mark_admitted()
        flight.record("engine.kv_import", request_id=req.request_id,
                      slot=slot, pages=len(pages),
                      prompt_len=int(req.prompt.size))
        self._cache = self._cache.import_pages(
            pages[:n_src], handoff.k_pages, handoff.v_pages,
            handoff.k_scales, handoff.v_scales)
        row = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
        row[:len(pages)] = pages
        self._page_table[slot] = row
        self._slot_req[slot] = req
        self._slot_pages[slot] = pages
        # usage metering: the whole imported context arrived as resident
        # KV — all of it is prefill work this engine did NOT run
        req.u_prefill_saved = int(req.prompt.size)
        req.u_admit_step = self.step_seq
        if self._sampling:
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            if handoff.sample is not None:
                # resume the ADVANCED chain exactly where the exporter
                # left it (host write outside the step loop — imports are
                # admission-rate events, never per-step)
                self._cache = self._cache.with_key_chain(
                    slot, handoff.sample["key"])
        metrics.counter("engine.kv_imports").inc()
        self._seed_first_token(slot, req, int(handoff.first_token))

    # ------------------------------------------------------ live migration

    def submit_import(self, handoff: KVHandoff, max_new_tokens=32,
                      deadline_s=None, trace=None, cache=True,
                      speculate=True, request_key=None) -> GenerateRequest:
        """Thread-safe receive side of live migration (docs/SERVING.md
        "Live migration"): validate the handoff HERE on the posting thread
        (loud geometry/dtype refusal travels back to the sender), post it
        to the import mailbox, and return the request future immediately.
        The DRIVER applies the mailbox between fixed-shape steps
        (`_apply_imports`) — the same discipline as cancellation — so a
        peer's connection threads never touch device state and the
        resumed decode is token-identical with zero recompiles
        (tests/test_no_retrace.py). Unlike `import_request`, a full
        engine DEFERS the placement to a later step instead of raising;
        an engine that could never fit it answers a typed error."""
        self._refuse_unmovable("submit_import (migration)")
        # double-checked like submit(): fail a draining/dead engine fast,
        # BEFORE the O(context) blake2b pass in _build_import_request —
        # the drain fallback chain probes peers exactly when that pass
        # hurts most. The second check below is the authoritative one,
        # atomic with the mailbox append.
        with self._work:
            self._refuse_not_accepting()
        req = self._build_import_request(handoff, max_new_tokens,
                                         deadline_s=deadline_s,
                                         trace=trace, cache=cache,
                                         speculate=speculate,
                                         request_key=request_key)
        with self._work:
            self._refuse_not_accepting()
            req.trace.mark_submit()
            flight.record("engine.migrate_in", request_id=req.request_id,
                          context_len=int(req.prompt.size),
                          max_new_tokens=req.max_new_tokens)
            self._imports.append((handoff, req))
            # the key rode the PTMG1 header: register the resumed request
            # in THIS engine's dedup table (overwriting any stale entry —
            # the migration is the authoritative owner of the key now),
            # so a client resubmit after the drain attaches instead of
            # re-running the generation
            self._register_dedup(req.request_key, req)
            self._work.notify()
        return req

    def _apply_imports(self):
        """Driver-side mailbox drain, run at every step start: place each
        posted handoff into a free slot. No slot/pages RIGHT NOW is a
        deferral while the engine still has retiring work; on an idle
        engine it is a typed failure (nothing will ever free capacity)."""
        if not self._imports:
            return
        retry = []
        while True:
            with self._qlock:
                if not self._imports:
                    break
                handoff, req = self._imports.popleft()
            if req.done:
                continue
            if req.expired():
                err = self._deadline_error(req)
                self._count_reap(err)
                req._finish(err)
                continue
            slots = self._free_slots()
            need = -(-(req.prompt.size + req.max_new_tokens)
                     // self.ecfg.page_size)
            pages = self.allocator.alloc(need) if slots else None
            if pages is None:
                if self._occupied() or self._inflight or self._prefilling:
                    retry.append((handoff, req))  # capacity will free up
                    continue
                req._finish(f"KV import needs a slot and {need} pages; "
                            f"engine has {len(slots)} free slots, "
                            f"{self.allocator.free_pages} free pages and "
                            f"no retiring work")
                continue
            self._m_mig_in.inc()
            self._place_import(req, handoff, slots[0], pages)
        if retry:
            with self._qlock:
                self._imports.extend(retry)

    @staticmethod
    def _cold_sample(req: GenerateRequest) -> dict | None:
        """A COLD migration item's sampler params ({"temperature",
        "top_k", "seed"}): the peer restarts the chain from the seed —
        nothing was sampled yet, so the restarted sequence is the
        uninterrupted one. None for greedy requests."""
        if req.temperature != 1.0 or req.top_k != 0:
            return {"temperature": float(req.temperature),
                    "top_k": int(req.top_k), "seed": int(req.seed)}
        return None

    @staticmethod
    def _deadline_ms_left(req: GenerateRequest,
                          now: float | None = None) -> int | None:
        if req.deadline_t is None:
            return None
        now = time.monotonic() if now is None else now
        return max(1, int((req.deadline_t - now) * 1000))

    def _do_migrate_out(self):
        """Driver-side migration export (drain(migrate=True)): harvest the
        whole in-flight window so every delivered token is settled, then
        export each live slot MID-DECODE as a warm :class:`MigrationItem`
        — context = prompt + delivered tokens whose KV is resident, the
        last sampled token riding as the seed — detaching slots and pages
        WITHOUT finishing the request futures. Queued and chunk-prefilling
        requests (no seeded KV worth moving) leave cold, and an un-applied
        import mailbox is re-exported warm as-is. `take_migrated` hands
        the items to the serving layer."""
        self._migrate_requested = False
        while self._inflight:
            self._harvest_one()
        self._g_inflight.set(0)
        items: list[MigrationItem] = []
        now = time.monotonic()
        for slot in range(self.ecfg.max_slots):
            req = self._slot_req[slot]
            if req is None or req.done:
                continue
            if req.expired(now):
                err = self._deadline_error(req)
                self._count_reap(err)
                self._retire(slot, error=err)
                continue
            left = self._deadline_ms_left(req, now)
            if slot in self._prefilling or not req.generated:
                # mid-chunk-prefill: the cheap move is to re-prefill on
                # the peer (cold), not to ship a partial page set
                item = MigrationItem(max_new_tokens=req.max_new_tokens,
                                     prompt=req.prompt, deadline_ms=left,
                                     request=req, cache=req.cache,
                                     speculate=req.speculate,
                                     request_key=req.request_key,
                                     sample=self._cold_sample(req),
                                     trace_id=req.trace.trace_id,
                                     parent_span=req.trace.span_id)
            else:
                # warm: KV is resident for prompt + generated[:-1] (the
                # last sampled token's KV is written by the NEXT step,
                # which will now run on the peer)
                ctx = int(self._lengths[slot])
                n_src = -(-ctx // self.ecfg.page_size)
                k_np, v_np, ks_np, vs_np = self._cache.export_pages(
                    self._slot_pages[slot][:n_src])
                context = np.concatenate(
                    [req.prompt, np.asarray(req.generated[:-1], np.int32)])
                handoff = KVHandoff(
                    prompt=context, first_token=int(req.generated[-1]),
                    k_pages=k_np, v_pages=v_np,
                    page_size=int(self.ecfg.page_size),
                    cache_dtype=np.dtype(self._cdtype).name,
                    k_scales=ks_np, v_scales=vs_np)
                if self._sampling and (req.temperature != 1.0
                                       or req.top_k != 0):
                    # the slot's ADVANCED chain rides the handoff: decode
                    # on the peer continues the bit-identical sampled
                    # sequence (the readback is migration-time only,
                    # never on the step loop)
                    handoff.sample = {
                        "temperature": float(req.temperature),
                        "top_k": int(req.top_k),
                        "key": self._cache.key_chain(slot)}
                # the seed counts as the peer's first emission, so the
                # peer budget is remaining + 1 — its full answer is then
                # exactly the uninterrupted run's sequence
                item = MigrationItem(
                    max_new_tokens=req.max_new_tokens
                    - len(req.generated) + 1,
                    handoff=handoff, deadline_ms=left, request=req,
                    cache=req.cache, speculate=req.speculate,
                    request_key=req.request_key,
                    trace_id=req.trace.trace_id,
                    parent_span=req.trace.span_id)
            flight.record("engine.migrate_out", request_id=req.request_id,
                          warm=item.handoff is not None,
                          delivered=len(req.generated))
            self._detach_slot(slot)
            items.append(item)
        with self._qlock:
            queued = list(self._queue)
            self._queue.clear()
            self._queue_tokens = 0
            self._g_queue.set(0)
            imports = list(self._imports)
            self._imports.clear()
        for req in queued:
            if req.done:
                continue
            if req.expired(now):
                err = self._deadline_error(req)
                self._count_reap(err)
                req._finish(err)
                continue
            items.append(MigrationItem(
                max_new_tokens=req.max_new_tokens, prompt=req.prompt,
                deadline_ms=self._deadline_ms_left(req, now), request=req,
                cache=req.cache, speculate=req.speculate,
                request_key=req.request_key,
                sample=self._cold_sample(req),
                trace_id=req.trace.trace_id,
                parent_span=req.trace.span_id))
        for handoff, req in imports:
            # a warm import this engine never placed migrates onward as-is
            if req.done:
                continue
            items.append(MigrationItem(
                max_new_tokens=req.max_new_tokens, handoff=handoff,
                deadline_ms=self._deadline_ms_left(req, now), request=req,
                cache=req.cache, speculate=req.speculate,
                request_key=req.request_key,
                trace_id=req.trace.trace_id,
                parent_span=req.trace.span_id))
        self._m_mig_out.inc(len(items))
        for item in items:
            if item.request is not None:
                item.request.u_migrations += 1
        self._g_occupancy.set(0)
        with self._qlock:
            self._migrated.extend(items)
        flight.record("engine.migrated", count=len(items))
        self._migrate_done.set()

    def take_migrated(self, timeout: float | None = None) \
            -> list[MigrationItem]:
        """Block until the driver has exported the in-flight work a
        `drain(migrate=True)` requested, then hand the items (futures
        still UNFINISHED) to the caller — the serving layer ships them to
        peers and splices the answers into the original futures. Raises
        ``TimeoutError`` if the driver did not reach the export inside
        ``timeout`` (wedged step)."""
        if not self._migrate_done.wait(timeout):
            raise TimeoutError(
                "migration export still pending (driver has not reached "
                "a step boundary)")
        with self._qlock:
            items, self._migrated = self._migrated, []
        return items

    # ------------------------------------------------------------ watchdog

    def active_traces(self):
        """Traces of every request the engine still owes an answer —
        queued, slotted, or awaiting in-flight harvest (these are what a
        watchdog dump lists as the stalled requests)."""
        with self._qlock:
            reqs = list(self._queue)
        reqs += [r for r in self._slot_req if r is not None]
        for _, snapshot, _ in list(self._inflight):
            reqs += [r for _, r in snapshot]
        seen, traces = set(), []
        for r in reqs:
            if id(r) not in seen and not r.done:
                seen.add(id(r))
                traces.append(r.trace)
        return traces

    def _has_work(self) -> bool:
        with self._qlock:
            queued = bool(self._queue) or bool(self._imports) \
                or bool(self._prefill_jobs)
        return queued or bool(self._inflight) or bool(self._prefilling) \
            or self._occupied()

    def start_watchdog(self, deadline_s=None, dump_dir=None,
                       interval_s=None):
        """Arm a stall watchdog over this engine's step loop: if the engine
        has work but `step_seq` stops advancing for ``deadline_s``
        (default ``PADDLE_WATCHDOG_S``, 300 s; <= 0 disables and returns
        None), the flight-recorder ring + the stalled requests' traces +
        the metrics snapshot dump to a JSON file (`observability/
        flight_recorder.py`). `serve_loop` arms one automatically; direct
        `step()`/`run_until_idle()` drivers opt in by calling this."""
        deadline = default_deadline() if deadline_s is None \
            else float(deadline_s)
        if deadline <= 0:
            return None
        return Watchdog("engine", progress=lambda: self.step_seq,
                        busy=self._has_work, deadline_s=deadline,
                        dump_dir=dump_dir, traces=self.active_traces,
                        interval_s=interval_s).start()

    # ---------------------------------------------------------- serve loop

    def drain(self, migrate: bool = False):
        """Refuse NEW submits while everything already accepted runs to
        completion — the first half of graceful shutdown
        (`InferenceServer.drain`, docs/SERVING.md). Unlike `abort`, nothing
        in flight is failed; callers poll `_has_work()` / watch their
        requests to know when the engine has quiesced.

        ``migrate=True`` (docs/SERVING.md "Live migration"): instead of
        waiting out the in-flight generations, the DRIVER exports every
        live request at its next step boundary — mid-decode slots as warm
        KV handoffs, queued/prefilling requests cold — without finishing
        their futures; `take_migrated` hands the items to the serving
        layer, which ships them to a peer and answers the original
        futures. Scale-down then costs one step + the transfer, not the
        longest running generation."""
        if migrate:
            self._refuse_unmovable("drain(migrate=True) (migration)")
        with self._work:
            self._draining = True
            if migrate:
                self._migrate_requested = True
            self._work.notify()
        metrics.counter("engine.drains").inc()

    def abort(self, reason: str):
        """Fail every queued and in-flight request with ``reason``, reclaim
        their pages, and refuse future submits. Blocked `result()` callers
        get the error immediately instead of hanging to their timeout."""
        with self._qlock:
            self._dead = reason
            queued = list(self._queue)
            self._queue.clear()
            self._queue_tokens = 0
            self._cancels.clear()
            self._g_queue.set(0)
            imports = list(self._imports)
            self._imports.clear()
            migrated = list(self._migrated)
            self._migrated.clear()
            prefill_jobs = list(self._prefill_jobs)
            self._prefill_jobs.clear()
        for req in queued:
            req._finish(reason)
        for _, req in imports:          # un-applied migration imports
            req._finish(reason)
        for *_, sink in prefill_jobs:    # un-run prefill-stream jobs
            sink.put(("err", reason))
        for item in migrated:
            # exported but never taken (take_migrated timed out / was
            # skipped): the futures are detached from every engine
            # structure, so nobody else will ever answer them
            if item.request is not None and not item.request.done:
                item.request._finish(reason)
        # a migrate drain waiting in take_migrated must fail FAST, not
        # burn its whole deadline on a driver that will never reach the
        # export (the items are drained — abort already answered every
        # future with the typed reason)
        self._migrate_done.set()
        self._inflight.clear()               # undelivered device tokens
        self._g_inflight.set(0)
        for slot in range(self.ecfg.max_slots):
            if self._slot_req[slot] is not None:
                self._retire(slot, error=reason)
        self._g_occupancy.set(0)

    def serve_loop(self, stop_event: threading.Event, idle_wait=0.05):
        """Drain loop for a dedicated engine thread (inference/serve.py):
        steps while there is work, parks on the submit condition when idle.
        On exit — clean shutdown OR a step raising (device OOM, AOT shape
        error) — every outstanding request is aborted so no connection
        thread is left blocking on a future nobody will fulfil. A stall
        watchdog (`start_watchdog`) guards the loop: a step that wedges in
        the device leaves a flight-recorder dump instead of a silent hang."""
        watchdog = self.start_watchdog()
        try:
            while not stop_event.is_set():
                if self.step():
                    continue
                with self._work:
                    if not self._queue:
                        self._work.wait(idle_wait)
        except Exception as e:  # noqa: BLE001 — surface to every waiter
            metrics.counter("engine.loop_errors").inc()
            self.abort(f"engine loop died: {type(e).__name__}: {e}")
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
        self.abort("engine stopped (server shutdown)")
