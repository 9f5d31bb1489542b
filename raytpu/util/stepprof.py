"""Step-level chip attribution: live MFU, step-time distributions, and
memory high-water gauges in the cluster TSDB.

The reference's train/serve dashboards read throughput from offline
bench JSONs; the ROADMAP's 40%+ MFU target needs a LIVE measurement.
This module derives per-step FLOPs from the jit ``cost_analysis`` at
compile time (cached per shape bucket — the lowering already happened,
so the question costs one AOT cache hit per bucket, never per step) and
divides by the chip's published peak (:mod:`raytpu.core.chip_specs`) to
emit ``raytpu_train_mfu`` / ``raytpu_infer_decode_mfu`` gauges plus
step-time histograms that ``raytpu top`` and alert rules consume. On a
device the table does not know (the CPU included) the utilization
gauges publish nothing.

Every emission site is behind the ``profiling_enabled()`` flag at the
CALLER (lint rule RTP019) — this module never checks the flag itself,
so a hook pays exactly one boolean read when profiling is off.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from raytpu.core.chip_specs import chip_spec
from raytpu.util.metrics import Gauge, Histogram

_STEP_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 2.5e-2, 5e-2, 0.1,
                 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def device_peak_flops() -> float:
    """Published dense bf16 peak FLOP/s of one local chip; raises
    ``ValueError`` on a device the table does not know."""
    import jax

    return chip_spec(jax.local_devices()[0].device_kind).bf16_flops


def cost_analysis_flops(jitted, *args, **kwargs) -> Optional[float]:
    """FLOPs for one call of ``jitted`` at these arg shapes via the AOT
    ``cost_analysis``; None when the backend doesn't report."""
    try:
        ca = jitted.lower(*args, **kwargs).compile().cost_analysis()
        flops = float((ca or {}).get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


class StepProfiler:
    """One per process and workload kind (``train`` / ``infer``)."""

    def __init__(self, kind: str = "train"):
        if kind == "train":
            self._mfu = Gauge("raytpu_train_mfu",
                              "model FLOPs utilization per train step")
            self._step = Histogram("raytpu_train_step_seconds",
                                   "train step wall time",
                                   boundaries=_STEP_BUCKETS)
        elif kind == "infer":
            self._mfu = Gauge("raytpu_infer_decode_mfu",
                              "model FLOPs utilization per decode step")
            self._step = Histogram("raytpu_infer_step_seconds",
                                   "decode step wall time",
                                   boundaries=_STEP_BUCKETS)
        else:
            raise ValueError(f"unknown StepProfiler kind {kind!r}")
        self.kind = kind
        self._hbm_used = Gauge("raytpu_hbm_used_bytes",
                               "device memory in use",
                               tag_keys=("device",))
        self._hbm_peak = Gauge("raytpu_hbm_peak_bytes",
                               "device memory high-water mark",
                               tag_keys=("device",))
        self._flops: Dict[object, Optional[float]] = {}
        # Peak FLOP/s of the local chip, looked up on first use; 0.0 on
        # a device with no published peak, which silences the gauge.
        self._peak: Optional[float] = None
        self._last_mark: Optional[float] = None
        self._lock = threading.Lock()

    # -- FLOPs accounting --------------------------------------------------

    def ensure_flops(self, key, thunk: Callable[[], Optional[float]]
                     ) -> Optional[float]:
        """Per-bucket cached FLOPs: ``thunk`` (e.g. a
        :func:`cost_analysis_flops` closure) runs once per distinct
        ``key`` — compile-time work stays at compile frequency."""
        with self._lock:
            if key in self._flops:
                return self._flops[key]
        try:
            flops = thunk()
            flops = float(flops) if flops else None
        except Exception:
            flops = None
        with self._lock:
            self._flops[key] = flops
        return flops

    def peak_flops(self) -> float:
        if self._peak is None:
            try:
                self._peak = device_peak_flops()
            except ValueError:
                self._peak = 0.0
        return self._peak

    # -- emission (callers guard with profiling_enabled(); RTP019) ---------

    def observe_step(self, dt_s: float, key=None,
                     flops: Optional[float] = None) -> None:
        """One step took ``dt_s`` seconds; emit the step-time histogram
        and, when per-step FLOPs are known (explicit or cached under
        ``key``), the MFU gauge."""
        dt_s = float(dt_s)
        if dt_s <= 0:
            return
        self._step.observe(dt_s)
        if flops is None and key is not None:
            with self._lock:
                flops = self._flops.get(key)
        if flops and self.peak_flops():
            self._mfu.set(min(1.0, float(flops) / dt_s /
                              self.peak_flops()))

    def mark(self) -> Optional[float]:
        """Interval timing for loops with no explicit step boundary
        (train ``session.report``): returns the seconds since the last
        mark, or None on the first call."""
        now = time.perf_counter()
        with self._lock:
            last, self._last_mark = self._last_mark, now
        return (now - last) if last is not None else None

    def observe_hbm(self) -> None:
        """Device-memory gauges from ``jax.local_devices()`` memory
        stats when the backend reports them (TPU/GPU; CPU reports
        nothing and this is a quiet no-op)."""
        try:
            import jax

            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                used = stats.get("bytes_in_use")
                peak = stats.get("peak_bytes_in_use")
                tag = {"device": f"{d.device_kind}:{d.id}"}
                if used is not None:
                    self._hbm_used.set(float(used), tags=tag)
                if peak is not None:
                    self._hbm_peak.set(float(peak), tags=tag)
        except Exception:
            pass


_profilers: Dict[str, StepProfiler] = {}
_factory_lock = threading.Lock()


def step_profiler(kind: str = "train") -> StepProfiler:
    """Process-wide singleton per kind, so the engine and the train
    session never double-register metric series."""
    with _factory_lock:
        sp = _profilers.get(kind)
        if sp is None:
            sp = _profilers[kind] = StepProfiler(kind)
        return sp
