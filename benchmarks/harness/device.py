"""The chip as JAX reports it, the table of published peaks, memory readings.

Peaks of one chip, keyed by ``device_kind``, each with its source. A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no published {what} on record for device kind "
            f"{device_kind!r}: add it to benchmarks/harness/device.py with "
            "its source") from None


def require_chips(n: int):
    """The first ``n`` accelerator devices. Exits non-zero, printing no
    result, when JAX finds no accelerator or fewer chips than asked."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise SystemExit(
            f"benchmark: needs {n} accelerator chip(s); jax reports platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}). Nothing was run.")
    if len(devs) < n:
        raise SystemExit(f"benchmark: the cell asks for {n} chip(s), jax "
                         f"reports {len(devs)}. Nothing was run.")
    return devs[:n]


def memory_reading(devices) -> dict:
    """The runtime's memory counters on the fullest chip (zeros where the
    backend reports none, as the CPU does): ``peak`` and ``live`` are
    ``peak_bytes_in_use`` and ``bytes_in_use``, which count buffers;
    ``reserved_peak`` is ``peak_bytes_reserved``, the most the runtime has
    set aside for a program's scratch while it ran, which the first two do
    not see (GPT-2 small's training step: 2.8 GB of buffers at most, 10.8 GB
    reserved)."""
    def most(key):
        return max((int((d.memory_stats() or {}).get(key, 0))
                    for d in devices), default=0)
    return {"peak": most("peak_bytes_in_use"), "live": most("bytes_in_use"),
            "reserved_peak": most("peak_bytes_reserved"),
            "limit": most("bytes_limit")}


def peak_with_reservation(mem: dict) -> int:
    """The peak on the fullest chip, from a ``memory_reading`` taken at the
    window's close: the larger of the buffers' own peak and the buffers
    live while the step programs ran + the most the runtime reserved for
    one of them. A sum past the chip's memory means the accounting is
    wrong, and that is an error, not a figure to clip."""
    peak = max(mem["peak"], mem["live"] + mem["reserved_peak"])
    if mem["limit"] and peak > mem["limit"]:
        raise SystemExit(f"benchmark: live buffers + the runtime's "
                         f"reservation = {peak} bytes, more than the chip's "
                         f"{mem['limit']}: the memory accounting is wrong")
    return peak


def describe(devices, **more) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    out.update(more)
    return out
