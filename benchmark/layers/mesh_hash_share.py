"""Search driver layer: the share of the candidates the program
dispatched (``search.hashes``) whose dispatches spanned every device of
the worker's mesh (``search.mesh_hashes``), counted over the window.  A
width served on one device, and the one-chip width-0 probe, lower it.
None where nothing was dispatched, or where the program does not count
mesh work (it then has no ``search.mesh_hashes`` counter).  Moves
``search_rate``."""


def read(r):
    dispatched = r.counters.get("search.hashes", 0)
    if not dispatched or "search.mesh_hashes" not in r.counters:
        return None
    return 100.0 * r.counters["search.mesh_hashes"] / dispatched
