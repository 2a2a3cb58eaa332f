"""Parallel search: partition algebra (jax-free) + the device search driver.

``persistent_search``, ``SearchResult`` and ``make_mesh`` import jax, so
they are exposed lazily (PEP 562 ``__getattr__``) — jax-free consumers
(the native C++ backend, runtime, CLI parsers) can use the partition
algebra without pulling the JAX compute path into their import graph.
None of the lazy names is also a submodule name, so an import of a
submodule cannot shadow one.
"""

from .partition import (  # noqa: F401
    contiguous_bounds,
    remainder_bits,
    split_thread_bytes,
    thread_bytes,
    worker_bits,
)

__all__ = [
    "contiguous_bounds", "remainder_bits", "split_thread_bytes",
    "thread_bytes", "worker_bits",
    "SearchResult", "persistent_search", "make_mesh",
]

_LAZY = {"SearchResult": "search", "persistent_search": "search",
         "make_mesh": "mesh_search"}


def __getattr__(name: str):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{_LAZY[name]}", __name__), name)
