"""Coordinator layer: the share of Mine requests the coordinator
answered without a search of their own, from its dominance cache or by
joining an identical round in flight: (``cache.hit`` +
``sched.coalesced_requests``) / ``coord.mine_rpcs``, counted over the
window.  Moves ``solve_p95_s``."""


def read(r):
    mines = r.counters.get("coord.mine_rpcs", 0)
    if not mines:
        return None
    served = (r.counters.get("cache.hit", 0)
              + r.counters.get("sched.coalesced_requests", 0))
    return 100.0 * served / mines
