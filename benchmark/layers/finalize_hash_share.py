"""Search driver layer: the share of the candidates the program
dispatched (``search.hashes``) whose candidates ran the hash model's
``finalize`` stage (``search.finalize_hashes``: sha256d's second
compression), counted over the window.  None where nothing was
dispatched, or where the program does not count that stage (it then has
no ``search.finalize_hashes`` counter).  Moves ``search_rate``."""


def read(r):
    dispatched = r.counters.get("search.hashes", 0)
    if not dispatched or "search.finalize_hashes" not in r.counters:
        return None
    return 100.0 * r.counters["search.finalize_hashes"] / dispatched
