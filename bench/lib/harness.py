"""What every driver shares: the chip check, the compile ledger, the
set-up clock, spans, and the one result line.

A driver is ``bench/drivers/<name>.py`` with ``run(ctx) -> Outcome``.
It builds the system under test from ``ctx.config`` and ``ctx.traffic``
(the cell's two data files), warms every shape its window uses, calls
``ctx.open_window()``, measures for ``ctx.seconds``, calls
``ctx.close_window()``, and then checks what the timed path produced
against the plain reference. The harness turns the outcome into the
result line.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from typing import Any, Dict, List, Optional

import jax


class CompileLedger:
    """Backend compile seconds and persistent-cache hits and misses, read
    from JAX's own monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit. A check
    passes when ``value <= limit`` (and the value is a finite number)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back. ``metrics`` holds end-to-end values by
    name; ``layer`` holds whatever the per-layer readers need (counts,
    FLOPs per unit of work, kernel call shapes)."""
    attempted: int
    failed: int
    metrics: Dict[str, float]
    checks: List[Check]
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """Per-run state a driver sees: the cell's files, the seed, the
    window length, the compile ledger and the window clock."""

    def __init__(self, *, config: dict, traffic: dict, seed: int,
                 seconds: float, t_start: float, ledger: CompileLedger,
                 tracer=None):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.t_start = t_start
        self.ledger = ledger
        self.tracer = tracer
        self.setup_s: Optional[float] = None
        self.window_compiles: Optional[int] = None
        self.memory_peak_bytes: Optional[int] = None
        self._t_open: Optional[float] = None
        self._c_open = 0

    def open_window(self) -> float:
        """Close set-up and open the measured window; returns its start on
        ``time.perf_counter``'s clock."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self._c_open = self.ledger.compiles
        log(f"setup: {self.setup_s:.3f} s, ledger {self.ledger.snapshot()}")
        if self.tracer is not None:
            self.tracer.start()
            self._win_span = span("bench.window")
            self._win_span.__enter__()
        self._t_open = time.perf_counter()
        return self._t_open

    def close_window(self) -> float:
        """Close the window (after the driver blocked on its last
        result); returns the window's length in seconds."""
        window_s = time.perf_counter() - self._t_open
        if self.tracer is not None:
            self._win_span.__exit__(None, None, None)
            self.tracer.stop()
        self.window_compiles = self.ledger.compiles - self._c_open
        log(f"window: {window_s:.3f} s, compiles inside the window: "
            f"{self.window_compiles}")
        self.memory_peak_bytes = peak_bytes()
        return window_s


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def span(name: str):
    """A host span in the profiler's own trace (free when no trace is
    being taken)."""
    return jax.profiler.TraceAnnotation(name)


def device_info(n_used: int) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": int(n_used)}


def result_line(*, correct: bool, outcome: Outcome,
                metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[dict] = None) -> str:
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(outcome.attempted),
        "failed": int(outcome.failed), "metrics": metrics,
        "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in outcome.checks}
    return json.dumps(out)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader sees: the traced window, the
    driver's counts and shapes, the chip's peaks and the cell's files."""
    trace: Any
    layer: Dict[str, Any]
    peaks: Dict[str, float]
    config: dict
    traffic: dict
