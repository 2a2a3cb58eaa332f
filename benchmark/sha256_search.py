"""The benchmark's own SHA-256 and double SHA-256 (``sha256d``) search:
the first solution of a puzzle in the reference enumeration, on the
device, in plain JAX.

It imports nothing of ``distpow_tpu``.  ``make_pool.py --hash sha256d``
runs it to record each pool nonce's first solution, as ``md5_search.py``
does for md5; the enumeration and the driver are md5_search's.

With a 4-byte nonce and ``w`` <= 4 the message ``nonce || t || LE_w(v)``
is one big-endian SHA-256 block: word 0 the nonce, words 1-2 the thread
byte, the chunk's bytes and the 0x80 pad byte, word 15 the length in
bits.  sha256d hashes the 32-byte digest again, as the fixed block
``digest || 0x80 || zeros || 256``.  The hex digest's trailing zeros
are the low nibbles of digest word 7.
"""

from __future__ import annotations

import functools
from typing import Tuple

from . import md5_search
from .md5_search import secret_at  # noqa: F401  (the same surface)
from .roofline import SHA256_INIT, SHA256_K, SHA256D_TAIL

MODELS = ("sha256", "sha256d")
# rounds a step of the schedule loop (``sha256_words``) on the CPU, whose
# compiler takes minutes over the whole unroll; elsewhere all 48 (on a
# v5e: 0.69 GH/s of sha256d, against 0.11 at 16 a step)
CPU_UNROLL = 16


def zero_mask(ntz: int) -> int:
    """Bits of digest word 7 that must be 0 for ``ntz`` trailing hex
    zeros (``ntz`` <= 8)."""
    if not 0 <= ntz <= 8:
        raise ValueError("ntz must be 0..8")
    return (1 << (4 * ntz)) - 1


def width_consts(w: int) -> Tuple[int, int, int]:
    """(pad bits of word 1, pad bits of word 2, word 15) for width w."""
    pad = 5 + w                       # byte index of the 0x80 pad
    p1 = 0x80 << (8 * (7 - pad)) if pad < 8 else 0
    p2 = 0x80 << (8 * (11 - pad)) if pad >= 8 else 0
    return p1, p2, 8 * pad


def _rotr(x, s: int):
    import jax.numpy as jnp

    u = jnp.uint32
    return (x >> u(s)) | (x << u(32 - s))


def _round(st, k, w):
    import jax.numpy as jnp

    a, b, c, d, e, f, g, h = st
    t1 = (h + (_rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25))
          + (g ^ (e & (f ^ g))) + jnp.uint32(k) + w)
    t2 = ((_rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22))
          + (b ^ ((a ^ b) & (b ^ c))))
    return t1 + t2, a, b, c, d + t1, e, f, g


def _schedule(w15, w7, w2, w16):
    import jax.numpy as jnp

    u = jnp.uint32
    s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> u(3))
    s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> u(10))
    return w16 + s0 + w7 + s1


def sha256_words(m, state=SHA256_INIT, unroll=None):
    """One SHA-256 compression of 16 message words (uint32 arrays or
    scalars) from ``state``; returns the eight output words.

    Rounds 0-15 take the message words as given, so constant words
    fold.  Rounds 16-63 run in a ``fori_loop`` whose carry is the state
    and the schedule's last 16 words as tuples, ``unroll`` rounds a step
    (default: ``CPU_UNROLL`` on the CPU, else all): at 16 every carry
    comes back at its own place."""
    import jax
    import jax.numpy as jnp

    if unroll is None:
        unroll = CPU_UNROLL if jax.default_backend() == "cpu" else True
    u = jnp.uint32
    w = [u(x) if isinstance(x, int) else x for x in m]
    st = tuple(u(x) if isinstance(x, int) else x for x in state)
    for i in range(16):
        st = _round(st, SHA256_K[i], w[i])
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in w + list(st)))
    window = tuple(jnp.broadcast_to(x, shape) for x in w)
    st = tuple(jnp.broadcast_to(x, shape) for x in st)
    ks = jnp.asarray(SHA256_K, dtype=u)

    def body(i, carry):
        st, win = carry
        w_new = _schedule(win[1], win[9], win[14], win[0])
        return _round(st, ks[i], w_new), win[1:] + (w_new,)

    st, _ = jax.lax.fori_loop(16, 64, body, (st, window), unroll=unroll)
    return [x + u(s) if isinstance(s, int) else x + s
            for x, s in zip(st, state)]


def _digest(m, double: bool):
    d = sha256_words(m)
    return sha256_words(list(d) + list(SHA256D_TAIL)) if double else d


def _words(nonce_word, v0, p1, p2, m15, n):
    """Message words of candidates 0..n-1 from chunk v0 on."""
    import jax.numpy as jnp

    u = jnp.uint32
    idx = jnp.arange(n, dtype=u)
    t = idx & u(255)
    v = v0 + (idx >> u(8))
    m = [u(0)] * 16
    m[0] = nonce_word
    m[1] = ((t << u(24)) | ((v & u(255)) << u(16))
            | (((v >> u(8)) & u(255)) << u(8)) | ((v >> u(16)) & u(255))
            | p1)
    m[2] = (v & u(0xFF000000)) | p2
    m[15] = m15
    return idx, m


@functools.lru_cache(maxsize=None)
def _first_fn(n: int, double: bool):
    """Compiled: flat index of the first solution among ``n`` candidates
    from chunk v0 on (``n`` if none; only the first ``n_valid`` count)."""
    import jax
    import jax.numpy as jnp

    def first(nonce_word, v0, p1, p2, m15, mask, n_valid):
        idx, m = _words(nonce_word, v0, p1, p2, m15, n)
        h7 = _digest(m, double)[7]
        hit = ((h7 & mask) == 0) & (idx < n_valid)
        return jnp.min(jnp.where(hit, idx, jnp.uint32(n)))

    return jax.jit(first)


@functools.lru_cache(maxsize=None)
def _digests_fn(n: int, double: bool):
    import jax

    def run(nonce_word, v0, p1, p2, m15):
        return _digest(_words(nonce_word, v0, p1, p2, m15, n)[1], double)

    return jax.jit(run)


def digests(nonce: bytes, v0: int, w: int, n: int, double: bool):
    """The digests of the first ``n`` candidates from chunk ``v0`` at
    width ``w``, as eight uint32 arrays (for tests against hashlib)."""
    import numpy as np

    u = np.uint32
    out = _digests_fn(n, double)(u(int.from_bytes(nonce, "big")), u(v0),
                                 *(u(x) for x in width_consts(w)))
    return [np.asarray(x) for x in out]


class FirstSolution(md5_search.FirstSolution):
    """md5_search's driver over SHA-256 (``model`` "sha256") or double
    SHA-256 ("sha256d") candidates."""

    _width_consts = staticmethod(width_consts)
    _zero_mask = staticmethod(zero_mask)

    def __init__(self, batch: int = 1 << 25, model: str = "sha256d"):
        if model not in MODELS:
            raise ValueError(f"model must be one of {MODELS}")
        self.model = model
        super().__init__(batch)

    def _compile(self, n: int):
        return _first_fn(n, self.model == "sha256d")

    @staticmethod
    def _nonce_word(nonce: bytes) -> int:
        return int.from_bytes(nonce, "big")
