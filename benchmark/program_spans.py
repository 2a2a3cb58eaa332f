"""The program's own spans in a profiler trace, and the device's idle
time split by them.

While a profiler session collects, the program marks its spans
(``distpow_tpu/runtime/spans.py``) as host events named
``distpow.<span>``, on the clock of the device operations.  ``load``
reads them beside what ``profile_reduce.load_xplane`` reads;
``split`` divides the device's idle time in the ``bench.window`` into
the part inside some ``worker.solve`` span (the search driver's: the
narrow widths, the drain at each width's end, the poll cadence) and the
rest (the host path's: client, coordinator, RPC, result forward);
``idle_gaps`` names each idle gap after the innermost span, ``bench.*``
or ``distpow.*``, that covers most of it, and ``idle_by_span`` gives
each instant of idle time to the innermost program span open then.

    python3 -m benchmark.program_spans <trace.xplane.pb> [--chips n]

prints both shares and the labelled gaps of a kept trace as JSON.
"""

from __future__ import annotations

import argparse
import json
from dataclasses import dataclass
from typing import Dict, List, Tuple

from . import profile_reduce as pr

PREFIX = "distpow."
SOLVE = PREFIX + "worker.solve"
LABELLED = (pr.PREFIX, PREFIX)

Span = Tuple[str, int, int]  # (name, start ns, duration ns)


def load(path: str, platform: str = "tpu") -> Tuple[pr.Trace, List[Span]]:
    """The trace as ``profile_reduce`` reads it, and the program's
    spans: every host event whose name starts with ``distpow.``."""
    from jax.profiler import ProfileData

    spans = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PREFIX)]
    return pr.load_xplane(path, platform), spans


def _length(intervals: List[pr.Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _intersect(a: List[pr.Interval], b: List[pr.Interval]) -> int:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_intervals(trace: pr.Trace, lo: int, hi: int
                   ) -> Dict[str, List[pr.Interval]]:
    """Per device, the merged intervals in which an operation ran,
    clipped to [lo, hi): the intervals ``reduce_trace`` sums."""
    return {dev: pr._clip(pr.union([(s, s + d) for _, s, d in ops]), lo, hi)
            for dev, ops in trace.device_ops.items()}


@dataclass
class Split:
    idle_share: float         # as profile_reduce.Reduced.idle_share
    search_driver_share: float  # idle inside some worker.solve span
    host_path_share: float      # the rest of the idle time


def split(trace: pr.Trace, spans: List[Span], n_devices: int) -> Split:
    """Each device's idle time in the window, inside and outside the
    union of the ``worker.solve`` spans, averaged over ``n_devices`` as
    ``reduce_trace`` averages busy time (a device that ran nothing is
    idle throughout).  The two shares add up to the idle share."""
    lo, hi = pr.window_of(trace)
    window = hi - lo
    solve = pr._clip(pr.union([(s, s + d) for name, s, d in spans
                               if name == SOLVE]), lo, hi)
    busy = busy_intervals(trace, lo, hi)
    idle = driver = 0
    for dev_busy in busy.values():
        idle += window - _length(dev_busy)
        driver += _length(solve) - _intersect(dev_busy, solve)
    absent = n_devices - len(busy)
    idle += absent * window
    driver += absent * _length(solve)
    idle_share = idle / (n_devices * window)
    driver_share = driver / (n_devices * window)
    return Split(idle_share=idle_share, search_driver_share=driver_share,
                 host_path_share=idle_share - driver_share)


def idle_by_span(trace: pr.Trace, spans: List[Span], n_devices: int
                 ) -> Dict[str, float]:
    """Each device's idle time in the window, each instant given to the
    innermost (shortest) program span open then, ``none`` where none is;
    as shares of the window averaged over ``n_devices`` like ``split``,
    so they add up to the idle share.  Where a gap straddles spans, as
    one between two requests does, this names its pieces where
    ``label`` names the whole."""
    lo, hi = pr.window_of(trace)
    busy = busy_intervals(trace, lo, hi)
    marks = sorted((s, s + d, d, name.split(".", 1)[1])
                   for name, s, d in spans)
    out: Dict[str, float] = {}
    idle_sets = [_complement(b, lo, hi) for b in busy.values()]
    idle_sets += [[(lo, hi)]] * (n_devices - len(busy))
    for idle in idle_sets:
        for s, e in idle:
            open_ = [m for m in marks if m[0] < e and m[1] > s]
            cuts = sorted({s, e} | {c for m in open_ for c in m[:2]
                                    if s < c < e})
            for a, b in zip(cuts, cuts[1:]):
                inner = [m for m in open_ if m[0] <= a and m[1] >= b]
                name = min(inner, key=lambda m: m[2])[3] if inner \
                    else "none"
                out[name] = out.get(name, 0) + (b - a)
    return {k: v / (n_devices * (hi - lo)) for k, v in out.items()}


def _complement(intervals: List[pr.Interval], lo: int, hi: int
                ) -> List[pr.Interval]:
    """[lo, hi) less the merged, sorted ``intervals``."""
    out, cursor = [], lo
    for s, e in intervals:
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        out.append((cursor, hi))
    return out


def label(annotations: List[Span], s: int, e: int) -> str:
    """The ``bench.*`` or ``distpow.*`` span, other than the window,
    that covers most of [s, e); of two that cover as much, the shorter
    (the inner one): ``profile_reduce``'s rule over both prefixes."""
    best, rank = "none", None
    for name, a, d in annotations:
        cover = min(e, a + d) - max(s, a)
        if (name != pr.WINDOW and name.startswith(LABELLED) and cover > 0
                and (rank is None or (cover, -d) > rank)):
            best, rank = name.split(".", 1)[1], (cover, -d)
    return best


def idle_gaps(trace: pr.Trace, spans: List[Span], top: int = 10,
              min_s: float = 0.0) -> List[Tuple[str, float, float]]:
    """The longest stretches of the window in which no device was busy,
    as ``(label, seconds, start seconds into the window)``; ``min_s``
    keeps every gap at least that long, however many."""
    lo, hi = pr.window_of(trace)
    gaps = _complement(pr.union(
        [iv for ivs in busy_intervals(trace, lo, hi).values() for iv in ivs]),
        lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    keep = [g for g in gaps if (g[1] - g[0]) / 1e9 >= min_s] if min_s \
        else gaps[:top]
    marks = trace.annotations + spans
    return [(label(marks, s, e), (e - s) / 1e9, (s - lo) / 1e9)
            for s, e in keep]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--min-gap-s", type=float, default=0.0)
    args = ap.parse_args(argv)
    trace, spans = load(args.xplane, args.platform)
    sp = split(trace, spans, args.chips)
    print(json.dumps({
        "idle_share_pct": 100 * sp.idle_share,
        "search_driver_idle_share_pct": 100 * sp.search_driver_share,
        "host_path_idle_share_pct": 100 * sp.host_path_share,
        "program_spans": len(spans),
        "idle_by_span_pct": {k: 100 * v for k, v in sorted(
            idle_by_span(trace, spans, args.chips).items(),
            key=lambda kv: -kv[1])},
        "idle_gaps": idle_gaps(trace, spans, min_s=args.min_gap_s)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
