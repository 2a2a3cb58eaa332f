"""The one traffic generator: every mix is a JSON file under
``benchmark/traffic/`` that this module reads.

Copied from ``distpow_tpu/load/loadgen.py`` (``key_nonce``, the Zipf
and blend draws of ``build_schedule``, the open-loop runner), so that no
change to the program moves the load.  What differs:

* a mix is plain data (``load_mix``); its keys are listed in
  ``MIX_KEYS``;
* the runner records when each arrival was due and when it fired, so a
  request is timed from its due time and a late generator shows as lag;
* a closed loop (``closed_arrivals``) sends a stratified pool, or one
  fresh key per request;
* ``domain`` separates the warm-up's nonces from the measured ones.

A mix file holds:

``loop``          ``"open"`` (Poisson arrivals at ``rate_hz``) or
                  ``"closed"`` (one request outstanding: the next is sent
                  when the last is answered)
``rate_hz``       open loop only
``n_keys``        key universe (open loop); ``zipf_s`` its skew
                  (``P(k) ~ 1/(k+1)^s``, 0 = uniform)
``nonce_len``     bytes per nonce
``difficulties``  ``[[ntz, weight], ...]``
``warm_widths``   chunk widths the worker's boot warm-up compiles
``pool``          closed loop only: ``[[nonce hex, first], ...]``, the
                  nonces sent, each with the reference position of its
                  first solution at ``pool_ntz`` zeros (``make_pool.py``).
                  Sent stratified: sorted by ``first`` into ``strata``
                  equal strata, each round sends one nonce of every
                  stratum, all in orders drawn from the seed; so every
                  seed sends the same distribution of work.  The mix
                  ends when the pool does
``pool_hash``     with a ``pool``: the hash model its first solutions
                  were found under; a cell whose configuration runs
                  another model is refused (``manifest.load_cell``)
``warm_pool``     the set-up's warm-up requests, in the pool's form
``warmup``        without a ``warm_pool``: ``{"requests": n}``
                  closed-loop requests, and/or ``{"bursts": [k, ...]}``
                  k concurrent requests at a time, served before the
                  window on ``domain="warmup"`` nonces
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import random
import time
from bisect import bisect_left
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Sequence

MIX_KEYS = {"loop", "rate_hz", "n_keys", "zipf_s", "nonce_len",
            "difficulties", "warm_widths", "warmup", "pool", "pool_ntz",
            "pool_hash", "strata", "warm_pool", "why"}


@dataclass(frozen=True)
class Arrival:
    t: float      # due, seconds from the window's start
    key: int
    nonce: bytes
    ntz: int
    first: Optional[int] = None   # the pool's recorded first solution


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    if mix["loop"] == "open" and not mix.get("rate_hz", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_hz > 0")
    if not mix["difficulties"] or any(w <= 0 for _, w in mix["difficulties"]):
        raise ValueError(f"{path}: difficulties need positive weights")
    if "pool" in mix:
        if [d for d, _ in mix["difficulties"]] != [mix["pool_ntz"]]:
            raise ValueError(f"{path}: a pool is asked at pool_ntz alone")
        if not mix["pool"] or len(mix["pool"]) % mix["strata"]:
            raise ValueError(f"{path}: the pool fills its strata evenly")
        if "pool_hash" not in mix:
            raise ValueError(f"{path}: a pool names its pool_hash")
    return mix


def key_nonce(seed: int, key: int, nonce_len: int,
              domain: str = "loadgen") -> bytes:
    """Per-key nonce: stable for one seed (repeat keys repeat: the
    cache/coalesce point) and disjoint across seeds and domains."""
    digest = hashlib.sha256(f"{domain}:{seed}:{key}".encode()).digest()
    return digest[:nonce_len]


def _cum(weights: Sequence[float]) -> List[float]:
    out, total = [], 0.0
    for w in weights:
        total += float(w)
        out.append(total)
    return out


def _pick(cdf: List[float], rng: random.Random) -> int:
    return bisect_left(cdf, rng.random() * cdf[-1])


class _Keys:
    """Zipf draw over ``n_keys`` (uniform at s = 0 without a table)."""

    def __init__(self, n_keys: int, s: float):
        self.n = n_keys
        self.cdf = _cum(1.0 / (k + 1) ** s for k in range(n_keys)) \
            if s > 0 else None

    def draw(self, rng: random.Random) -> int:
        if self.cdf is None:
            return rng.randrange(self.n)
        return _pick(self.cdf, rng)


def build_schedule(mix: dict, seed: int, seconds: float,
                   domain: str = "loadgen") -> List[Arrival]:
    """Every arrival of an open-loop mix due in ``[0, seconds)``: a
    Poisson process at ``rate_hz`` joined mid-flow, Zipf keys, blended
    difficulties.  Same (mix, seed, seconds): same schedule."""
    rng = random.Random(f"{domain}:{seed}")
    keys = _Keys(mix["n_keys"], mix.get("zipf_s", 0.0))
    diff_cdf = _cum(w for _, w in mix["difficulties"])
    rate = float(mix["rate_hz"])
    out: List[Arrival] = []
    t = rng.expovariate(rate)
    while t < seconds:
        key = keys.draw(rng)
        ntz = mix["difficulties"][_pick(diff_cdf, rng)][0]
        out.append(Arrival(t=t, key=key, ntz=int(ntz), nonce=key_nonce(
            seed, key, mix["nonce_len"], domain)))
        t += rng.expovariate(rate)
    return out


def closed_arrivals(mix: dict, seed: int,
                    domain: str = "loadgen") -> Iterator[Arrival]:
    """Closed-loop requests, each on a key of its own; ``t`` is left 0:
    a closed-loop request is due when it is sent.  With a ``pool``, the
    pool's nonces, stratified by size, until it is spent; else fresh
    nonces without end."""
    rng = random.Random(f"{domain}:{seed}")
    if "pool" in mix:
        for key, (nonce, first) in enumerate(stratified(mix["pool"],
                                                        mix["strata"], rng)):
            yield Arrival(t=0.0, key=key, ntz=int(mix["pool_ntz"]),
                          nonce=bytes.fromhex(nonce), first=int(first))
        return
    diff_cdf = _cum(w for _, w in mix["difficulties"])
    key = 0
    while True:
        ntz = mix["difficulties"][_pick(diff_cdf, rng)][0]
        yield Arrival(t=0.0, key=key, ntz=int(ntz), nonce=key_nonce(
            seed, key, mix["nonce_len"], domain))
        key += 1


def stratified(pool: Sequence, strata: int, rng: random.Random) -> list:
    """``pool`` entries ``[nonce, first]`` sorted by ``first`` into
    ``strata`` equal strata; round j sends the j-th of each stratum's
    shuffled members, the strata in a shuffled order."""
    ranked = sorted(pool, key=lambda e: (e[1], e[0]))
    size = len(ranked) // strata
    groups = [ranked[k * size:(k + 1) * size] for k in range(strata)]
    for g in groups:
        rng.shuffle(g)
    out = []
    for j in range(size):
        order = list(range(strata))
        rng.shuffle(order)
        out.extend(groups[k][j] for k in order)
    return out


class OpenLoopRunner:
    """Fire a schedule on the wall clock without waiting for answers.

    ``submit(arrival, due)`` gets the absolute due time (monotonic
    seconds).  A late arrival fires late, never skipped; ``lags_s``
    holds fire time minus due time for every arrival.  ``span(name)``
    wraps each wait for the next due time (a trace annotation)."""

    def __init__(self, submit: Callable[[Arrival, float], None],
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 span: Callable[[str], object] = contextlib.nullcontext):
        self._submit = submit
        self._clock = clock
        self._sleep = sleep
        self._span = span
        self.lags_s: List[float] = []

    def run(self, schedule: Sequence[Arrival], t0: float) -> None:
        for arr in schedule:
            due = t0 + arr.t
            delay = due - self._clock()
            if delay > 0:
                with self._span("bench.until_due"):
                    self._sleep(delay)
            self.lags_s.append(max(0.0, self._clock() - due))
            self._submit(arr, due)
