"""The sha256d cell's mix and configuration at the rehearsals' tiny
size: a pool of eight puzzles and two warm ones at
``bench_helpers.SMALL_POOL_NTZ`` nibbles, each with its first solution
under sha256d by a hashlib brute force (``make_pool.brute_first``), and
a 2^13 batch.  At the committed 7 nibbles a CPU search takes minutes a
puzzle.  ``conftest.py`` applies it to every copy ``small_root`` makes."""

from __future__ import annotations

import json
import os

import bench_helpers as bh

MIX = "search-d7-sha256d"
CONFIG = "sha256d-a2-1w"
_POOL = {}


def sha256d_pool() -> dict:
    """The small pool and warm pool, made once a process."""
    from benchmark import make_pool

    if not _POOL:
        made = make_pool.make(
            lambda nonce, ntz: make_pool.brute_first(nonce, ntz, "sha256d"),
            bh.SMALL_POOL_NTZ, 8, 60.0, 4, hash_model="sha256d")
        _POOL.update(pool=made["pool"], warm_pool=made["warm_pool"])
    return _POOL


def shrink_sha256d(root: str) -> None:
    """Shrink the mix and the configuration in the copy at ``root``."""
    bench = os.path.join(root, "benchmark")
    path = os.path.join(bench, "traffic", MIX + ".json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(difficulties=[[bh.SMALL_POOL_NTZ, 1.0]],
               pool_ntz=bh.SMALL_POOL_NTZ, strata=4,
               warm_widths=[0, 1, 2], **sha256d_pool())
    with open(path, "w") as f:
        json.dump(mix, f)
    path = os.path.join(bench, "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["worker"]["BatchSize"] = 1 << 13
    with open(path, "w") as f:
        json.dump(cfg, f)
