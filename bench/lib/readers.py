"""Reductions shared by the per-layer metric readers in
``bench/metrics/``. Each returns ``None`` when the run holds nothing to
read, and the harness then leaves the metric out of the result line."""
from __future__ import annotations

from typing import Optional, Sequence

from bench.lib import flops
from bench.lib import trace as trace_lib


def idle(x) -> Optional[float]:
    """Percent of the traced window in which the device ran nothing."""
    if x.trace is None or not x.trace.devices:
        return None
    return trace_lib.idle_share(x.trace)


def mfu(x) -> Optional[float]:
    """Operations the window's work needs, over the traced window, over
    the bf16 peak, in percent."""
    work, per = x.layer.get("work"), x.layer.get("flops_per_work")
    if x.trace is None or not work or not per:
        return None
    return 100.0 * work * per / (x.trace.window_s * x.peaks["bf16_flops"])


def roofline(x, kernel: str, names: Sequence[str]) -> Optional[float]:
    """The least time the chip could take for a kernel's calls in the
    window, over the time its events took, in percent. ``names`` are the
    substrings that identify the kernel's device events."""
    calls = x.layer.get("kernels", {}).get(kernel)
    if x.trace is None or not calls:
        return None
    n, secs = trace_lib.kernel_time(x.trace, names)
    if n == 0 or secs <= 0:
        return None
    return 100.0 * n * flops.mean_least_time(calls, x.peaks) / secs
