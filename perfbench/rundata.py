"""What one run recorded, as every per-layer reader receives it."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence


@dataclasses.dataclass
class RunData:
    """What one run recorded; every per-layer reader gets this."""

    cell: Mapping
    cfg: Mapping
    mix: Mapping
    family: Any                     # the module families/<cfg.family>.py
    chips: int
    peaks: Any                      # perfbench.peaks.Peaks, None off a TPU
    window: Sequence[float]         # host clock, seconds
    end_to_end: Dict[str, float]
    memory_peak_bytes: int
    streams: List = dataclasses.field(default_factory=list)
    engine_steps: List = dataclasses.field(default_factory=list)
    # The engine step before the window's first: the baseline of the
    # counters that are running totals (compiles, preemptions).
    step_before: Any = None
    step_ends: List[float] = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    trace: Any = None               # perfbench.trace_reduce.Trace
    traced_steps: List = dataclasses.field(default_factory=list)

    def counted_in_window(self, counter: str) -> Optional[float]:
        """The change of a running total of the engine over the window:
        its value after the window's last step less its value after the
        last step before the window."""
        if not self.engine_steps or self.step_before is None:
            return None
        return float(getattr(self.engine_steps[-1], counter)
                     - getattr(self.step_before, counter))

    def device_idle_pct(self) -> Optional[float]:
        """Share of the traced window in which no operation ran on the
        device, averaged over the chips."""
        from perfbench import trace_reduce

        if self.trace is None or not self.trace.device:
            return None
        lo, hi = trace_reduce.window_of(self.trace)
        return 100.0 * (1.0 - trace_reduce.busy_seconds(self.trace)
                        / (hi - lo))

    def hbm_peak_gb(self) -> Optional[float]:
        return self.memory_peak_bytes / 1e9 if self.memory_peak_bytes \
            else None
