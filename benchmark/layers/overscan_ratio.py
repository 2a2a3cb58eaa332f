"""Search driver layer: candidates the program dispatched
(``search.hashes``) over the candidates credited to its answers; what
it searched beyond each answer.  Moves ``search_rate``."""


def read(r):
    dispatched = r.counters.get("search.hashes", 0)
    if not dispatched or not r.credited_hashes:
        return None
    return dispatched / r.credited_hashes
