"""From a profiler trace to device busy time, idle share, the top
device operations and the idle gaps, labelled by the benchmark's own
host annotations.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain tuples; ``reduce_trace`` works on those alone, so a test can
feed it a small recorded trace.  Busy time is the union of the
intervals in which an operation ran on a device (the ``XLA Ops`` line of
each device plane), clipped to the benchmark's ``bench.window``
annotation; the idle share is one minus busy over that window.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "bench.window"
PREFIX = "bench."
OPS_LINE = "XLA Ops"
CPU_CLIENT = "tf_XLAPjRtCpuClient"

Interval = Tuple[int, int]  # [start, end) in ns


@dataclass
class Trace:
    # device name -> [(op name, start ns, duration ns)]
    device_ops: Dict[str, List[Tuple[str, int, int]]] = field(
        default_factory=dict)
    # [(annotation name, start ns, duration ns)] of the bench.* spans
    annotations: List[Tuple[str, int, int]] = field(default_factory=list)


def _short(name: str) -> str:
    """An XLA op's event name is its whole HLO instruction: keep the
    instruction's name (``%while.3``)."""
    return name.split(" = ", 1)[0]


def load_xplane(path: str, platform: str = "tpu") -> Trace:
    """The device operations and bench.* annotations of a trace.  On the
    ``cpu`` platform (the CPU rehearsal) the XLA CPU client's threads
    stand in for the device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(_short(ev.name), int(ev.start_ns), int(ev.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                out.device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events]
                out.annotations.extend(e for e in events
                                       if e[0].startswith(PREFIX))
                if platform == "cpu" and line.name.startswith(CPU_CLIENT):
                    out.device_ops.setdefault("cpu", []).extend(
                        e for e in events if e[2] > 0)
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(trace: Trace) -> Interval:
    spans = [(s, s + d) for name, s, d in trace.annotations if name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _label(annotations: List[Tuple[str, int, int]], s: int, e: int) -> str:
    """The bench.* span (other than the window) that covers most of
    [s, e); of two that cover as much, the shorter (the inner one)."""
    best, rank = "none", None
    for name, a, d in annotations:
        cover = min(e, a + d) - max(s, a)
        if name != WINDOW and cover > 0 and (rank is None
                                             or (cover, -d) > rank):
            best, rank = name[len(PREFIX):], (cover, -d)
    return best


@dataclass
class Reduced:
    window_s: float
    n_devices: int
    busy_s: Dict[str, float]           # per device that ran an op
    device_ops: List[Tuple[str, float]]  # top ops, seconds per device
    idle_gaps: List[Tuple[str, float]]   # longest gaps, labelled

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def reduce_trace(trace: Trace, n_devices: int, top: int = 10) -> Reduced:
    """``n_devices``: the chips the cell uses; one that ran nothing in
    the window counts as idle throughout."""
    if not trace.device_ops:
        raise ValueError("no device operation in the trace")
    lo, hi = window_of(trace)
    busy, per_op = {}, {}
    all_busy: List[Interval] = []
    for dev, ops in trace.device_ops.items():
        merged = _clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        all_busy.extend(merged)
        for name, s, d in ops:
            cover = min(s + d, hi) - max(s, lo)
            if cover > 0:
                per_op[name] = per_op.get(name, 0) + cover
    ops_top = sorted(((n, ns / 1e9 / n_devices) for n, ns in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
    # idle gaps: stretches of the window in which no device was busy
    gaps, cursor = [], lo
    for s, e in union(all_busy) + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(trace.annotations, s, e), (e - s) / 1e9)
                for s, e in gaps[:top]]
    return Reduced(window_s=(hi - lo) / 1e9, n_devices=n_devices,
                   busy_s=busy,
                   device_ops=ops_top, idle_gaps=labelled)
