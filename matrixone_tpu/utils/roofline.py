"""Roofline / MFU instrumentation for jitted hot loops.

For any jitted function this module reports XLA's own cost model (FLOPs
+ HBM bytes accessed via `lowered.compile().cost_analysis()`), and —
when the caller also has a measured wall time — the achieved FLOP/s,
bytes/s, and their ratios to the chip's peak (MFU and HBM-bandwidth
utilization).

Peaks come from one table keyed by the `device_kind` jax reports. A
device that is not in the table is an error wherever a share is asked
for, never a default; on the CPU backend there is no peak and the
utilizations are null.

Reference analogue: the reference ships perf *evidence* with its kernels
(cgo/cuvs/blog.md benchmark tables); this is the equivalent
instrumentation for ours.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

#: published single-chip peaks, keyed by `jax.devices()[0].device_kind`
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,            # bf16 MXU
        "bytes_per_s": 819e9,       # HBM
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device=None) -> Optional[dict]:
    """The peak row of `device` (default: the first device jax sees):
    None on the CPU backend, KeyError for an accelerator the table does
    not hold."""
    dev = device if device is not None else jax.devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        return PEAKS[dev.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {dev.device_kind!r}: "
            f"add its row to utils/roofline.py PEAKS") from None


def cost_of(fn: Callable, *args, static_argnames=(), **kwargs) -> dict:
    """XLA cost model of one call: {'flops': N, 'bytes': N} (0 when the
    backend's cost analysis doesn't expose a field). `fn` may already be
    jitted — jit of jit is a no-op wrapper."""
    jitted = jax.jit(fn, static_argnames=static_argnames)
    compiled = jitted.lower(*args, **kwargs).compile()
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:   # noqa: BLE001 — backend without cost model:
        ca = {}         # XLA raises backend-specific types we cannot
                        # enumerate; diagnostics degrade to zeros
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def mfu(flops_per_call: float, bytes_per_call: float,
        calls: float, seconds: float) -> dict:
    """Achieved rates + utilization vs chip peaks for a measured run.

    MFU convention: achieved FLOP/s over the chip's bf16 peak (the
    scaling-book definition) — so an f32 kernel's MFU reads low by
    design; it is comparable across kernels and rounds."""
    if seconds <= 0:
        return {}
    fl = flops_per_call * calls / seconds
    by = bytes_per_call * calls / seconds
    pk = peaks()
    pf, pb = (pk["flops"], pk["bytes_per_s"]) if pk else (None, None)
    out = {
        "achieved_tflops": round(fl / 1e12, 4),
        "achieved_gbps": round(by / 1e9, 2),
        "mfu": round(fl / pf, 4) if pf else None,
        "hbm_util": round(by / pb, 4) if pb else None,
    }
    # arithmetic intensity + the roofline's verdict on what bounds us
    if bytes_per_call > 0 and pf and pb:
        ai = flops_per_call / bytes_per_call
        out["arith_intensity"] = round(ai, 2)
        out["bound"] = "compute" if ai > pf / pb else "memory"
    return out


def report(fn: Callable, args: tuple, calls: float, seconds: float,
           static_argnames=(), **kwargs) -> dict:
    """cost_of + mfu in one shot, safe to call in a bench epilogue: any
    analysis failure degrades to {} rather than killing the bench line."""
    try:
        c = cost_of(fn, *args, static_argnames=static_argnames, **kwargs)
    except Exception:                        # noqa: BLE001
        return {}
    return {**c, **mfu(c["flops"], c["bytes"], calls, seconds)}
