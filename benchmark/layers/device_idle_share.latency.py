"""Device layer, open-loop cells: the share of the traced window in
which no operation ran on the device (mean over the cell's chips).
Moves ``solve_p95_s``."""


def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share
