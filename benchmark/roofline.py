"""The yardstick of the kernels layer: the operation count per candidate
of md5 and of double SHA-256 (``sha256d``), and the chip's int32 vector
peak.

Both live here, apart from the program, so that no change to the
program can move them.

**Operations per candidate.** ``md5_min_ops`` counts the fewest 32-bit
operations an md5 search needs per candidate by running md5 itself on
symbolic words (``sha256d_min_ops`` does the same for sha256d): a word
that is the same for every candidate of a request (the nonce, the
padding, the length, the state before the first varying word) is a
constant and folds away, an add of several terms costs one op per term
beyond the first plus one for all their constants together, a rotate or
shift by a constant is one op, each round function takes its fewest ops
(``d ^ (b & (c ^ d))`` and the like; for SHA-256 Ch as ``g ^ (e & (f ^
g))`` and Maj as ``b ^ ((a ^ b) & (b ^ c))``, whose ``a ^ b`` is the
next round's ``b ^ c``), a subexpression computed twice is counted once,
and only the work that reaches the digest words the predicate reads is
counted. The predicate then costs a feed-forward add and a masked
compare per word. Every implementation executes at least this many
operations, so a share of the peak built on it cannot pass 100% unless
the peak is wrong.

**Peaks.**  ``PEAKS`` is keyed by ``device_kind``; a kind that is not
there is an error, never a default.
"""

from __future__ import annotations

import math
import struct

MASK = 0xFFFFFFFF
MD5_S = ([7, 12, 17, 22] * 4 + [5, 9, 14, 20] * 4 + [4, 11, 16, 23] * 4
         + [6, 10, 15, 21] * 4)
MD5_K = [int(abs(math.sin(i + 1)) * 2 ** 32) & MASK for i in range(64)]
MD5_INIT = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476)
SHA256_K = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)
SHA256_INIT = (0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
               0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19)
# sha256d's second block after the 8 words of the first digest: the pad
# byte, zeros, and the length, 256 bits
SHA256D_TAIL = (0x80000000, 0, 0, 0, 0, 0, 0, 256)

# TPU v5e (Google Cloud, "TPU v5e"): 197 TFLOP/s of bf16 from four
# 128x128 MXUs, one multiply-add (2 flops) per MXU cell per cycle, gives
# the clock: 197e12 / (4 * 128 * 128 * 2) = 1.503 GHz.  The vector unit
# works on (8 sublanes x 128 lanes) = 1024 int32 lanes per instruction
# and issues 4 vector ALU instructions per VLIW bundle, one bundle per
# cycle: 4 * 1024 * 1.503e9 = 6.157e12 int32 ops/s.
_V5E_CLOCK_HZ = 197e12 / (4 * 128 * 128 * 2)
PEAKS = {
    "TPU v5 lite": {
        "vpu_int32_ops_per_s": 4 * 1024 * _V5E_CLOCK_HZ,
        "derivation": "4 VALU slots x 1024 int32 lanes x 1.503 GHz "
                      "(clock = 197 TFLOP/s bf16 / (4 MXU x 128 x 128 x 2))",
    },
}


def vpu_int32_peak(device_kind: str) -> float:
    """int32 vector ops/s of one chip of ``device_kind``; KeyError when
    the kind is not in the table."""
    if device_kind not in PEAKS:
        raise KeyError(f"no int32 vector peak for device kind "
                       f"{device_kind!r}: add it to benchmark/roofline.py "
                       f"PEAKS with its derivation")
    return PEAKS[device_kind]["vpu_int32_ops_per_s"]


class _Var:
    """A word that differs between candidates of one request."""

    __slots__ = ("key",)

    def __init__(self, key):
        self.key = key


class _Tape:
    """Records the operations on varying words, folding constants and
    sharing repeated subexpressions."""

    def __init__(self):
        self.ids = {}    # (op, argument ids and constants) -> node id
        self.deps = {}   # node id -> ids of the varying arguments

    def _op(self, name, *args):
        if all(isinstance(a, int) for a in args):
            return _FOLD[name](*args) & MASK
        key = (name,) + tuple(a if isinstance(a, int) else ("v", a.key)
                              for a in args)
        if key not in self.ids:
            self.ids[key] = len(self.ids)
            self.deps[self.ids[key]] = [a.key for a in args
                                        if isinstance(a, _Var)]
        return _Var(self.ids[key])

    def xor(self, a, b):
        return self._op("xor", a, b)

    def and_(self, a, b):
        return self._op("and", a, b)

    def or_(self, a, b):
        return self._op("or", a, b)

    def not_(self, a):
        return self._op("not", a)

    def rotl(self, a, s):
        if isinstance(a, int):
            return ((a << s) | (a >> (32 - s))) & MASK
        return self._op("rotl", a, s)

    def rotr(self, a, s):
        return self.rotl(a, 32 - s)

    def shr(self, a, s):
        return self._op("shr", a, s)

    def add(self, *terms):
        """One sum, reassociated: constants fold into one term."""
        const = sum(t for t in terms if isinstance(t, int)) & MASK
        var = [t for t in terms if not isinstance(t, int)]
        if not var:
            return const
        acc = var[0]
        for t in var[1:]:
            acc = self._op("add", acc, t)
        if len(var) < len(terms):
            acc = self._op("add", acc, const)
        return acc

    def count(self, outputs) -> int:
        """Operations that some output depends on."""
        seen, stack = set(), [o.key for o in outputs if isinstance(o, _Var)]
        while stack:
            key = stack.pop()
            if key in seen or key not in self.deps:
                continue
            seen.add(key)
            stack.extend(self.deps[key])
        return len(seen)


_FOLD = {
    "xor": lambda a, b: a ^ b,
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "not": lambda a: ~a,
    "add": lambda a, b: a + b,
    "rotl": lambda a, s: (a << s) | (a >> (32 - s)),
    "shr": lambda a, s: a >> s,
}


def _round_fn(t: _Tape, i: int, b, c, d):
    if i < 16:
        return t.xor(d, t.and_(b, t.xor(c, d)))
    if i < 32:
        return t.xor(c, t.and_(d, t.xor(b, c)))
    if i < 48:
        return t.xor(b, t.xor(c, d))
    return t.xor(c, t.or_(b, t.not_(d)))


def _word_index(i: int) -> int:
    if i < 16:
        return i
    if i < 32:
        return (5 * i + 1) % 16
    if i < 48:
        return (3 * i + 5) % 16
    return (7 * i) % 16


def md5_block(t: _Tape, words):
    """md5 of one 64-byte block of 16 words (ints or _Var); returns the
    four digest words."""
    a, b, c, d = MD5_INIT
    for i in range(64):
        f = _round_fn(t, i, b, c, d)
        x = t.add(a, f, words[_word_index(i)], MD5_K[i])
        a, d, c, b = d, c, b, t.add(b, t.rotl(x, MD5_S[i]))
    return [t.add(v, init) for v, init in zip((a, b, c, d), MD5_INIT)]


def message_words(nonce_len: int, secret_len: int, order: str = "<"):
    """The single md5 block of ``nonce || secret`` (SHA-256's with
    ``order`` ">"): a word holding any secret byte varies, every other
    word (nonce, padding, length) is the same for all candidates of the
    request (here: zero nonce)."""
    n = nonce_len + secret_len
    if n > 55:
        raise ValueError("nonce and secret must fit one block")
    block = bytes(n) + b"\x80" + bytes(55 - n) + struct.pack(
        order + "Q", 8 * n)
    words = list(struct.unpack(order + "16I", block))
    for j in range(16):
        if j * 4 < n and j * 4 + 4 > nonce_len:
            words[j] = _Var(("M", j))  # an input: no node of its own
    return words


def md5_min_ops(nonce_len: int, secret_len: int, ntz: int) -> int:
    """Fewest 32-bit operations per candidate of an md5 search for
    ``ntz`` trailing hex zeros (module docstring)."""
    t = _Tape()
    digest = md5_block(t, message_words(nonce_len, secret_len))
    # hex digest = the four words little-endian in order a, b, c, d:
    # its last 8 nibbles come from d, the 8 before from c, and so on
    words_read = [digest[3 - k] for k in range(min(4, (ntz + 7) // 8))]
    test_ops = sum(1 if (k + 1) * 8 <= ntz else 2
                   for k in range(len(words_read)))
    return t.count(words_read) + test_ops + (len(words_read) - 1)


def md5_search_ops(nonce_len: int, ntz: int) -> int:
    """Operations credited per candidate to a search: the fewest over
    the secret lengths a reference-order search at ``ntz`` runs through
    (a lower bound for every candidate of it)."""
    return min(md5_min_ops(nonce_len, w + 1, ntz) for w in range(5))


def sha256_block(t: _Tape, words, state=SHA256_INIT):
    """SHA-256's compression of one block of 16 words (ints or _Var)
    from ``state``; returns the eight output words."""
    w = list(words)
    for i in range(16, 64):
        x, y = w[i - 15], w[i - 2]
        s0 = t.xor(t.xor(t.rotr(x, 7), t.rotr(x, 18)), t.shr(x, 3))
        s1 = t.xor(t.xor(t.rotr(y, 17), t.rotr(y, 19)), t.shr(y, 10))
        w.append(t.add(w[i - 16], s0, w[i - 7], s1))
    a, b, c, d, e, f, g, h = state
    for i in range(64):
        big_s1 = t.xor(t.xor(t.rotr(e, 6), t.rotr(e, 11)), t.rotr(e, 25))
        ch = t.xor(g, t.and_(e, t.xor(f, g)))
        t1 = t.add(h, big_s1, ch, w[i], SHA256_K[i])
        big_s0 = t.xor(t.xor(t.rotr(a, 2), t.rotr(a, 13)), t.rotr(a, 22))
        maj = t.xor(b, t.and_(t.xor(a, b), t.xor(b, c)))
        a, b, c, d, e, f, g, h = (t.add(t1, big_s0, maj), a, b, c,
                                  t.add(d, t1), e, f, g)
    return [t.add(v, init)
            for v, init in zip((a, b, c, d, e, f, g, h), state)]


def sha256d_min_ops(nonce_len: int, secret_len: int, ntz: int) -> int:
    """Fewest 32-bit operations per candidate of a sha256d search for
    ``ntz`` trailing hex zeros (module docstring).  Stage 1's eight
    words all feed stage 2; of stage 2 only the work that reaches the
    words the predicate reads counts."""
    t = _Tape()
    inner = sha256_block(t, message_words(nonce_len, secret_len, ">"))
    digest = sha256_block(t, list(inner) + list(SHA256D_TAIL))
    # hex digest = the eight words big-endian in order: its last 8
    # nibbles are word 7's low nibbles, the 8 before word 6's, ...
    words_read = [digest[7 - k] for k in range(min(8, (ntz + 7) // 8))]
    test_ops = sum(1 if (k + 1) * 8 <= ntz else 2
                   for k in range(len(words_read)))
    return t.count(words_read) + test_ops + (len(words_read) - 1)


def sha256d_search_ops(nonce_len: int, ntz: int) -> int:
    """``md5_search_ops`` for sha256d: the fewest over the secret
    lengths a reference-order search at ``ntz`` runs through."""
    return min(sha256d_min_ops(nonce_len, w + 1, ntz) for w in range(5))
