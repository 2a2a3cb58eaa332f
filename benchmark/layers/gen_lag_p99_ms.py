"""Load generator layer: the 99th percentile of how late the open-loop
generator fired its arrivals, so a starved generator is not read as a
fast server.  Moves ``solve_p95_s``."""

from benchmark.harness import percentile


def read(r):
    return 1e3 * percentile(r.lags_s, 0.99) if r.lags_s else None
