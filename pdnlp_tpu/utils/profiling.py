"""Profiling and step-rate observability.

The reference's only timing is a wall-clock print around the epoch loop
(``/root/reference/single-gpu-cls.py:129,150-151``) plus DeepSpeed's
``wall_clock_breakdown`` (``multi-gpu-deepspeed-cls.py:245``).  Here:

- ``Profiler`` wraps a window of training steps in a ``jax.profiler`` trace
  (viewable in TensorBoard/XProf) when ``--profile_dir`` is set — device
  timelines, HLO cost, HBM usage; the window skips warmup steps so the
  trace shows steady state, not compilation.
- ``StepStats`` turns the epoch wall-clock into the derived rates the
  reference's README table reports informally (steps/s, examples/s).
- ``bf16_peak`` is the chip's bf16 peak by device kind, for a program that
  reports its own MFU (``chip_smoke.py``; the benchmark keeps its own
  ``benchmark/peaks.json`` and reads nothing here).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from pdnlp_tpu.utils.logging import rank0_print

#: per-chip bf16 peak FLOP/s by device kind (prefix-matched); MFU is only
#: reported when the running chip is recognized
BF16_PEAK_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,    # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,    # v6e / Trillium
    "TPU v6e": 918e12,
}


def bf16_peak(device) -> Optional[float]:
    kind = getattr(device, "device_kind", "")
    for prefix, peak in BF16_PEAK_BY_KIND.items():
        if kind.startswith(prefix):
            return peak
    return None


class Profiler:
    """Trace steps [start, start+steps) of training into ``profile_dir``."""

    def __init__(self, profile_dir: Optional[str], start_step: int = 10,
                 num_steps: int = 10):
        self.dir = profile_dir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._active = False
        self._done = False

    def step(self, gstep: int) -> None:
        """Call once per dispatch with the global step index.  Boundary
        crossings (not equality) so K-fused steps that jump over
        ``start_step``/``stop_step`` still open/close the window."""
        if not self.dir or self._done:
            return
        if gstep >= self.start_step and not self._active:
            # Open even when this dispatch already crossed stop_step (one
            # K-fused dispatch can jump the whole window): the window slides
            # forward to trace the NEXT dispatch rather than vanishing.
            import jax

            try:
                jax.profiler.start_trace(self.dir)
                self._active = True
                rank0_print(f"[profiler] tracing from step {gstep} "
                            f"(window {self.start_step}..{self.stop_step}) "
                            f"-> {self.dir}")
            except Exception as e:  # platform without profiler support
                rank0_print(f"[profiler] trace unavailable: {e}")
                self.dir = None
        elif gstep >= self.stop_step and self._active:
            self.close()

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True


@dataclasses.dataclass
class StepStats:
    """Derived rates from the timed epoch (the north-star denominators)."""

    steps: int
    examples: int
    minutes: float

    @property
    def steps_per_second(self) -> float:
        return self.steps / (self.minutes * 60) if self.minutes else 0.0

    @property
    def examples_per_second(self) -> float:
        return self.examples / (self.minutes * 60) if self.minutes else 0.0

    def line(self) -> str:
        return (f"steps/s：{self.steps_per_second:.2f}  "
                f"samples/s：{self.examples_per_second:.1f}")
