"""Scheduler layer: searches packed into each batched launch, the mean
of ``sched.batch_occupancy`` over the window.  Moves ``solve_p95_s``."""


def read(r):
    h = r.histograms.get("sched.batch_occupancy")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
