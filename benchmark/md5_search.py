"""The benchmark's own md5 search: the first solution of a puzzle in the
reference enumeration, on the device, in plain JAX.

It imports nothing of ``distpow_tpu``.  ``make_pool.py`` runs it to
record each pool nonce's first solution, against which a returned
secret is credited (``reference.credit``), so a search that skips or
reorders candidates is never credited with work it did not do.

The reference enumeration (``reference.py``): the chunk counter ``v``
counts up from 0 in its minimal little-endian encoding (``w`` bytes,
``w`` = 0 for ``v`` = 0), and for each ``v`` the thread byte ``t`` runs
0..255.  The candidate ``(v, t)`` is ``nonce || t || LE_w(v)`` at
position ``v * 256 + t + 1``.  With a 4-byte nonce and ``w`` <= 4 the
message is one md5 block: word 0 the nonce, words 1-2 the thread byte,
the chunk and the 0x80 pad byte, word 14 the length in bits.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Optional, Tuple

_S = [7, 12, 17, 22] * 4 + [5, 9, 14, 20] * 4 + [4, 11, 16, 23] * 4 \
    + [6, 10, 15, 21] * 4
_K = [int(abs(math.sin(i + 1)) * 2 ** 32) & 0xFFFFFFFF for i in range(64)]
_IV = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476)
# the hex digest's nibbles from its end, as shifts into the last word
_NIBBLES = (24, 28, 16, 20, 8, 12, 0, 4)
MAX_WIDTH = 4


def _g(i: int) -> int:
    if i < 16:
        return i
    if i < 32:
        return (5 * i + 1) % 16
    if i < 48:
        return (3 * i + 5) % 16
    return (7 * i) % 16


def zero_mask(ntz: int) -> int:
    """Bits of the digest's last word that must be 0 for ``ntz``
    trailing hex zeros (``ntz`` <= 8)."""
    if not 0 <= ntz <= 8:
        raise ValueError("ntz must be 0..8")
    mask = 0
    for s in _NIBBLES[:ntz]:
        mask |= 0xF << s
    return mask


def width_consts(w: int) -> Tuple[int, int, int]:
    """(pad bits of word 1, pad bits of word 2, word 14) for width w."""
    pad = 5 + w                       # byte index of the 0x80 pad
    p1 = 0x80 << (8 * (pad - 4)) if pad < 8 else 0
    p2 = 0x80 << (8 * (pad - 8)) if pad >= 8 else 0
    return p1, p2, 8 * pad


def segments():
    """(w, first v, end v) of each width, in order."""
    yield 0, 0, 1
    for w in range(1, MAX_WIDTH + 1):
        yield w, 256 ** (w - 1), 256 ** w


def _md5_words(m):
    """The md5 of one block of 16 words (uint32 arrays or ints); returns
    the four output words."""
    import jax.numpy as jnp

    u = jnp.uint32
    a, b, c, d = (u(x) for x in _IV)
    for i in range(64):
        if i < 16:
            f = (b & c) | (~b & d)
        elif i < 32:
            f = (d & b) | (~d & c)
        elif i < 48:
            f = b ^ c ^ d
        else:
            f = c ^ (b | ~d)
        x = a + f + u(_K[i]) + m[_g(i)]
        s = _S[i]
        a, d, c = d, c, b
        b = b + ((x << u(s)) | (x >> u(32 - s)))
    return (a + u(_IV[0]), b + u(_IV[1]), c + u(_IV[2]), d + u(_IV[3]))


def _words(nonce_word, v0, p1, p2, m14, n):
    """Message words of candidates 0..n-1 from chunk v0 on."""
    import jax.numpy as jnp

    u = jnp.uint32
    idx = jnp.arange(n, dtype=u)
    t = idx & u(255)
    v = v0 + (idx >> u(8))
    zero = u(0)
    m = [zero] * 16
    m[0] = nonce_word
    m[1] = t | (v << u(8)) | p1
    m[2] = (v >> u(24)) | p2
    m[14] = m14
    return idx, m


def _first_fn(n: int):
    """Compiled: flat index of the first solution among ``n`` candidates
    from chunk v0 on (``n`` if none; only the first ``n_valid`` count)."""
    import jax
    import jax.numpy as jnp

    def first(nonce_word, v0, p1, p2, m14, mask, n_valid):
        idx, m = _words(nonce_word, v0, p1, p2, m14, n)
        d = _md5_words(m)[3]
        hit = ((d & mask) == 0) & (idx < n_valid)
        return jnp.min(jnp.where(hit, idx, jnp.uint32(n)))

    return jax.jit(first)


def _digests_fn(n: int):
    import jax

    def digests(nonce_word, v0, p1, p2, m14):
        return _md5_words(_words(nonce_word, v0, p1, p2, m14, n)[1])

    return jax.jit(digests)


def digests(nonce: bytes, v0: int, w: int, n: int):
    """md5 of the first ``n`` candidates from chunk ``v0`` at width
    ``w``, as four uint32 arrays (for tests against hashlib)."""
    import numpy as np

    p1, p2, m14 = width_consts(w)
    u = np.uint32
    out = _digests_fn(n)(u(int.from_bytes(nonce, "little")), u(v0), u(p1),
                         u(p2), u(m14))
    return [np.asarray(x) for x in out]


class FirstSolution:
    """Search puzzles in reference order, ``batch`` candidates a call,
    two calls in flight."""

    # the hash's own pieces; sha256_search.FirstSolution replaces them
    _compile = staticmethod(_first_fn)
    _width_consts = staticmethod(width_consts)
    _zero_mask = staticmethod(zero_mask)

    @staticmethod
    def _nonce_word(nonce: bytes) -> int:
        return int.from_bytes(nonce, "little")

    def __init__(self, batch: int = 1 << 25):
        if batch % 256:
            raise ValueError("batch must be a multiple of 256")
        self.batch = batch
        self._fn = self._compile(batch)

    def __call__(self, nonce: bytes, ntz: int,
                 max_position: Optional[int] = None) -> Optional[int]:
        """The position of the first solution (``None`` past
        ``max_position`` or past width 4)."""
        import numpy as np

        if len(nonce) != 4:
            raise ValueError("the message layout assumes a 4-byte nonce")
        u = np.uint32
        word, mask = u(self._nonce_word(nonce)), u(self._zero_mask(ntz))
        per_call = self.batch // 256
        inflight: deque = deque()

        def drain() -> Optional[int]:
            res, v0 = inflight.popleft()
            f = int(res)
            return None if f >= self.batch else v0 * 256 + f + 1

        for w, lo, hi in segments():
            p1, p2, m_len = (u(x) for x in self._width_consts(w))
            for v0 in range(lo, hi, per_call):
                if max_position is not None and v0 * 256 >= max_position:
                    break
                n_valid = min(per_call, hi - v0) * 256
                inflight.append((self._fn(word, u(v0), p1, p2, m_len, mask,
                                          u(n_valid)), v0))
                if len(inflight) > 2:
                    pos = drain()
                    if pos is not None:
                        return pos
        while inflight:
            pos = drain()
            if pos is not None:
                return pos
        return None


def secret_at(position: int) -> bytes:
    """The reference enumeration's candidate secret at ``position``."""
    v, t = divmod(position - 1, 256)
    w = (v.bit_length() + 7) // 8
    return bytes([t]) + v.to_bytes(w, "little")
