"""Make the nonce pool of a closed-loop mix (run once, on the chip).

    python3 benchmark/make_pool.py --mix benchmark/traffic/search-d8.json \
        --count 1024 --seconds 1500 [--hash md5|sha256|sha256d]

A closed-loop cell times some tens of searches per run, and their sizes
(each nonce's first solution in the reference enumeration) are
geometric: drawn fresh per seed, they moved the search rate by ~12%
between seeds on a v5e. So the mix carries a pool of nonces
drawn in order from the ``pool`` domain, none left out, each with the
position of its first solution, found by the benchmark's own search
(``md5_search.py`` or ``sha256_search.py``, not the program's) under the
hash that ``--hash`` names and the mix records as ``pool_hash``.
``traffic.closed_arrivals`` sends the pool stratified by size, so every
seed sends the same distribution of work in another order; and
``reference.credit`` never credits an answer past its recorded first
solution.

Before the pool, the search's compiled program is checked against a
hashlib brute force at two difficulties (``CHECK_NTZ``; the zero mask is
an argument of the same program); every pool secret is checked with
hashlib.  Two ``warm_pool`` nonces, whose first solutions lie just past
the mean (at 8 zeros, 2**32 candidates: into the 4-byte chunks), are the
set-up's warm-up requests: a fixed cost, and off the pool.  Not part of
a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import (md5_search, reference, sha256_search,  # noqa: E402
                       traffic)

# the warm pool's first solutions, in units of the mean 16**ntz: at 8
# zeros just past 2**32 candidates, into the 4-byte chunks
WARM_BAND = (1.0, 1.25)
# the self-check's difficulties: 16 puzzles at the first, 4 at the
# second, lower for sha256d, whose brute force hashes twice a candidate
CHECK_NTZ = {"md5": (4, 5), "sha256": (3, 4), "sha256d": (3, 4)}


def searcher(hash_model: str, batch: int):
    """The benchmark's own first-solution search for ``hash_model``."""
    if hash_model == "md5":
        return md5_search.FirstSolution(batch)
    return sha256_search.FirstSolution(batch, hash_model)


def brute_first(nonce: bytes, ntz: int, hash_model: str = "md5") -> int:
    pos = 1
    while not reference.solves(hash_model, nonce,
                               md5_search.secret_at(pos), ntz):
        pos += 1
    return pos


def self_check(search, hash_model: str = "md5") -> None:
    lo, hi = CHECK_NTZ[hash_model]
    for k, ntz in [(k, lo) for k in range(16)] + [(k, hi) for k in range(4)]:
        nonce = traffic.key_nonce(ntz, k, 4, "pool-check")
        got, want = (search(nonce, ntz),
                     brute_first(nonce, ntz, hash_model))
        if got != want:
            raise RuntimeError(f"{nonce.hex()} at {ntz}: search {got}, "
                               f"hashlib {want}")


def make(search, ntz: int, count: int, seconds: float, strata: int,
         n_warm: int = 2, hash_model: str = "md5") -> dict:
    """Pool and warm pool at ``ntz``; stops drawing at ``count`` or after
    ``seconds``, and keeps a multiple of ``strata``."""
    def first(i: int):
        nonce = traffic.key_nonce(ntz, i, 4, "pool")
        pos = search(nonce, ntz)
        if pos is None or not reference.solves(
                hash_model, nonce, md5_search.secret_at(pos), ntz):
            raise RuntimeError(f"{nonce.hex()}: no checked solution")
        return [nonce.hex(), pos]

    t0 = time.monotonic()
    pool, i = [], 0
    while len(pool) < count and time.monotonic() - t0 < seconds:
        pool.append(first(i))
        i += 1
    pool = pool[:len(pool) - len(pool) % strata]
    warm, lo, hi = [], WARM_BAND[0] * 16 ** ntz, WARM_BAND[1] * 16 ** ntz
    while len(warm) < n_warm:
        entry = first(i)
        i += 1
        if lo < entry[1] <= hi:
            warm.append(entry)
    took = time.monotonic() - t0
    hashes = sum(p for _, p in pool)
    return {"pool": pool, "warm_pool": warm, "drawn": i, "seconds": took,
            "hashes": hashes, "rate_ghs": hashes / took / 1e9,
            "mean_over_16n": hashes / len(pool) / 16 ** ntz}


def dump_mix(mix: dict) -> str:
    """The mix as JSON, one pool entry to a line."""
    lines = []
    for k, v in mix.items():
        if k in ("pool", "warm_pool"):
            rows = ",\n".join("  " + json.dumps(e) for e in v)
            lines.append(f"{json.dumps(k)}: [\n{rows}\n ]")
        else:
            lines.append(f"{json.dumps(k)}: {json.dumps(v)}")
    return "{\n " + ",\n ".join(lines) + "\n}\n"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", required=True)
    ap.add_argument("--hash", choices=sorted(CHECK_NTZ), default="md5")
    ap.add_argument("--count", type=int, default=1024)
    ap.add_argument("--seconds", type=float, default=1500.0)
    ap.add_argument("--batch", type=int, default=1 << 25)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_platforms", args.platform)
    with open(args.mix) as f:
        mix = json.load(f)
    search = searcher(args.hash, args.batch)
    t0 = time.monotonic()
    self_check(search, args.hash)
    checked_s = time.monotonic() - t0
    made = make(search, int(mix["pool_ntz"]), args.count, args.seconds,
                int(mix["strata"]), hash_model=args.hash)
    made["self_check_s"] = checked_s
    mix["pool_hash"] = args.hash
    mix["pool"], mix["warm_pool"] = made.pop("pool"), made.pop("warm_pool")
    made["count"] = len(mix["pool"])
    with open(args.mix, "w") as f:
        f.write(dump_mix(mix))
    print(json.dumps(made))


if __name__ == "__main__":
    main()
