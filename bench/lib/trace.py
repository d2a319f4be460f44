"""The reduction from a profiler trace to device metrics.

A run with ``--trace 1`` records the measured window with
``jax.profiler``; :func:`load` reads the ``.xplane.pb`` it wrote
(``jax.profiler.ProfileData``) into plain event lists, and the functions
below reduce those lists. Keeping the reduction on plain lists lets the
tests check it on hand-built events.

- busy time: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices used;
- idle share: 1 minus busy over the window;
- kernel time: the summed device durations of the events whose name (or
  any of whose string stats, such as the HLO op or the op's long name)
  contains one of a kernel's names;
- breakdown: the device operations that took most time, and the idle
  gaps of the device attributed to the innermost ``bench.*`` host span
  that covered the gap's midpoint (what the host was doing meanwhile).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
# device lines that repeat other lines' time (whole programs, steps)
SKIP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")
# control flow whose events enclose the operations they run
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str         # the operation's name, such as "lora_matmul.118"
    start: float      # seconds on the trace's clock
    dur: float        # seconds
    text: str = ""    # the full op text and string stats

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def base(self) -> str:
        """The name without its instance number: "lora_matmul"."""
        head, _, tail = self.name.rpartition(".")
        return head if head and tail.isdigit() else self.name


def short_name(op: str) -> str:
    """"%lora_matmul.118 = bf16[...] custom-call(...)" -> "lora_matmul.118"
    (device events on a TPU are named by their whole HLO text)."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class Trace:
    devices: List[List[Event]]     # one op-event list per device
    host: List[Event]              # bench.* spans
    t0: float
    t1: float
    planes: List[str] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def union(intervals: Iterable[Tuple[float, float]], t0: float,
          t1: float) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [t0, t1], in order."""
    clipped = sorted((max(a, t0), min(b, t1)) for a, b in intervals
                     if b > t0 and a < t1)
    out: List[List[float]] = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    tot = 0.0
    for evs in tr.devices:
        tot += sum(b - a for a, b in union(
            ((e.start, e.end) for e in evs), tr.t0, tr.t1))
    return tot / len(tr.devices)


def idle_share(tr: Trace) -> float:
    """Percent of the window in which no operation ran on a device."""
    return 100.0 * (1.0 - busy_s(tr) / tr.window_s)


def kernel_time(tr: Trace, names: Sequence[str]) -> Tuple[int, float]:
    """(number of events, summed seconds) of the device events, inside
    the window, whose name without its instance number is one of
    ``names``; over all devices."""
    n, s = 0, 0.0
    for evs in tr.devices:
        for e in evs:
            if e.start >= tr.t0 and e.end <= tr.t1 and e.base in names:
                n += 1
                s += e.dur
    return n, s


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` device operations, by name without instance number,
    that took most time (control flow that encloses other operations
    left out)."""
    by: Dict[str, float] = defaultdict(float)
    for evs in tr.devices:
        for e in evs:
            if e.base in CONTAINERS:
                continue
            if e.end > tr.t0 and e.start < tr.t1:
                by[e.base] += min(e.end, tr.t1) - max(e.start, tr.t0)
    n_dev = max(1, len(tr.devices))
    return [[n, s / n_dev] for n, s in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def _innermost(host: List[Event], t: float) -> str:
    best: Optional[Event] = None
    for e in host:
        if e.name == WINDOW_SPAN:
            continue
        if e.start <= t <= e.end and (best is None or e.dur < best.dur):
            best = e
    return best.name if best is not None else "(no span)"


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """Idle time of the device (first device), summed by what the host
    was doing during each gap; the ``k`` largest."""
    if not tr.devices:
        return []
    busy = union(((e.start, e.end) for e in tr.devices[0]), tr.t0, tr.t1)
    gaps, last = [], tr.t0
    for a, b in busy:
        if a > last:
            gaps.append((last, a))
        last = b
    if tr.t1 > last:
        gaps.append((last, tr.t1))
    by: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        by[_innermost(tr.host, 0.5 * (a + b))] += b - a
    return [[n, s] for n, s in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def op_texts(tr: Trace, k: int = 10, width: int = 300) -> List[str]:
    """One full op text of each of the ``k`` most time-consuming device
    operations, for a reader checking what the names match."""
    top = {n for n, _ in top_ops(tr, k)}
    seen, out = set(), []
    for evs in tr.devices[:1]:
        for e in evs:
            if e.base in top and e.base not in seen:
                seen.add(e.base)
                out.append(e.text[:width])
    return out


def breakdown(tr: Trace) -> dict:
    return {"device_ops": top_ops(tr), "idle_gaps": idle_gaps(tr)}


# -- reading jax.profiler's output -------------------------------------

def _text(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(path: str, n_devices: int) -> Trace:
    """Read the ``.xplane.pb`` under ``path`` into a :class:`Trace` over
    the first ``n_devices`` device planes."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    dev_planes, host, planes = [], [], []
    for plane in pd.planes:
        lines = [f"{l.name}:{sum(1 for _ in l.events)}" for l in plane.lines]
        planes.append(f"{plane.name} [{', '.join(lines[:12])}]")
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == OPS_LINE] or \
                [l for l in lines if l.name not in SKIP_LINES]
            evs = [Event(short_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9, _text(e))
                   for l in ops for e in l.events]
            if evs:
                dev_planes.append((plane.name, evs))
        elif plane.name.startswith("/host:"):
            for l in plane.lines:
                for e in l.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    dev_planes.sort(key=lambda p: p[0])
    devices = [evs for _, evs in dev_planes[:n_devices]]
    win = [e for e in host if e.name == WINDOW_SPAN]
    if win:
        t0, t1 = win[0].start, win[0].end
    else:
        allev = [e for evs in devices for e in evs]
        t0 = min(e.start for e in allev)
        t1 = max(e.end for e in allev)
    return Trace(devices=devices, host=host, t0=t0, t1=t1, planes=planes)


class Tracer:
    """Records the measured window into a fresh directory under
    ``$TMPDIR``; :meth:`close` deletes it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)
