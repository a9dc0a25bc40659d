"""Typed serving errors shared by the engine, the serve wire layer, the
router, and wire clients (docs/ROBUSTNESS.md).

The serving contract is "every request terminates in bounded time with
either tokens or a TYPED error": an overloaded fleet must answer
``Overloaded``, a blown deadline ``DeadlineExceeded``, a client-abandoned
request ``Cancelled`` — never a raw socket traceback or an indefinite
hang. On the wire every error travels as one line, ``<TypeName>: <text>``
(the format `InferenceServer._send_err` has always used); this module owns
the classes and the two conversions:

- `from_wire(msg)`: wire/engine error string -> the matching typed
  exception (unknown type names stay `RuntimeError` with the FULL message,
  preserving the pre-typed behavior every existing caller relies on).
- Raising one of these classes server-side and formatting it as
  ``f"{type(e).__name__}: {e}"`` round-trips: the client's `from_wire`
  reconstructs the same type.

All of them subclass `RuntimeError`, so pre-existing ``except RuntimeError``
/ ``pytest.raises(RuntimeError)`` call sites keep working unchanged.

The router classifies these by name (`serving/router.py`):
``Overloaded`` resubmits elsewhere WITHOUT evicting the replica (it is
healthy, just full); ``DeadlineExceeded`` and ``Cancelled`` relay to the
client (the deadline is global and the cancellation was the client's own
doing — another replica would change neither).
"""
from __future__ import annotations

__all__ = ["DeadlineExceeded", "Cancelled", "Overloaded", "HandoffCorrupt",
           "RecurrentStateUnsupported", "PageLayoutUnsupported", "from_wire"]


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before it finished: shed at
    admission, expired in queue, or cut off mid-decode. Retrying without
    a fresh deadline is pointless by definition."""


class Cancelled(RuntimeError):
    """The request was cancelled — an explicit CANCEL op or the client
    disconnecting mid-GENERATE. Nobody is waiting for the answer."""


class Overloaded(RuntimeError):
    """Admission control refused the work: the engine's queue is past its
    configured bound (`EngineConfig.max_queue_depth`/``max_queue_tokens``)
    or every replica behind the router is shedding. Safe to retry
    elsewhere/later — nothing about the request itself is wrong."""


class HandoffCorrupt(RuntimeError):
    """A ``PTKV1``/``PTMG1`` wire blob failed its content checksum (or is
    structurally unparseable past a valid magic): truncated transfer, bit
    flip, or a torn write. The import is REFUSED — a corrupted KV page
    must never decode as garbage context (docs/ROBUSTNESS.md "Wire
    integrity"; the wire mirror of checkpoint `CheckpointCorrupt`). Safe
    to re-ship from the source — nothing about the request is wrong."""


class RecurrentStateUnsupported(RuntimeError):
    """The served model keeps per-sequence state beside the page pool
    (recurrent state, window K/V), and the operation would rebuild or move
    a sequence from pages alone: prefix reuse, speculation, KV hand-off,
    live migration, tier spill. Refused at configuration or call time —
    never served from half of a sequence's state (docs/SERVING.md "What
    refuses"). Retrying elsewhere does not help: it is the model's."""


class PageLayoutUnsupported(RuntimeError):
    """The served model's page row is not K and V heads
    (`ModelFamily.page_rows`: a latent row with no V twin, or two parts of
    different widths), and the operation would ship pages through a blob
    that is laid out as twin K and V pools of ``[.., kv heads, head dim]``
    (``PTKV1`` hand-off, ``PTMG1`` migration, ``PTKT1`` tier frames).
    Refused at configuration or call time — never served from half a page
    row (docs/SERVING.md "What refuses"). Prefix reuse INSIDE one engine
    moves no blob and is served. Retrying elsewhere does not help: it is
    the model's."""


_BY_NAME = {c.__name__: c for c in (DeadlineExceeded, Cancelled,
                                    Overloaded, HandoffCorrupt,
                                    RecurrentStateUnsupported,
                                    PageLayoutUnsupported)}


def from_wire(msg: str) -> Exception:
    """``"<TypeName>: <text>"`` -> the typed exception (message stripped
    of the name, so re-formatting with the type name round-trips), or
    ``RuntimeError(msg)`` verbatim for everything else."""
    head, sep, rest = msg.partition(": ")
    cls = _BY_NAME.get(head) if sep else None
    return cls(rest) if cls is not None else RuntimeError(msg)
