"""The plain reference: what a correct answer is, in hashlib alone.

It imports nothing of ``distpow_tpu``.  A puzzle ``(nonce, ntz)`` is
solved by a secret when the hex digest of ``hash(nonce || secret)`` ends
in ``ntz`` zeros (the upstream ``powlib`` predicate).  The reference
enumeration, in which every search is credited, runs the thread byte
fastest and then the chunk counter, little-endian, in its minimal
encoding (the upstream worker's order).
"""

from __future__ import annotations

import hashlib
from typing import Callable, Dict, Optional


def _named(name: str) -> Callable[[bytes], str]:
    def hexdigest(data: bytes) -> str:
        try:
            h = hashlib.new(name)
        except ValueError:
            raise ValueError(f"hash model {name!r}: this host's hashlib "
                             f"has no {name}") from None
        h.update(data)
        return h.hexdigest()

    return hexdigest


def _blake2b_256(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=32).hexdigest()


def _sha256d(data: bytes) -> str:
    """Bitcoin's proof-of-work digest: sha256 of sha256's raw digest."""
    return hashlib.sha256(hashlib.sha256(data).digest()).hexdigest()


# every hash model of the program's registry: name -> hex digest
HASHES: Dict[str, Callable[[bytes], str]] = {
    **{name: _named(name) for name in ("md5", "sha1", "sha256", "sha384",
                                       "sha512", "sha3_256", "ripemd160")},
    "blake2b_256": _blake2b_256,
    "sha256d": _sha256d,
}


def hex_digest(hash_model: str, data: bytes) -> str:
    if hash_model not in HASHES:
        raise KeyError(f"no reference for hash model {hash_model!r}; "
                       f"known: {sorted(HASHES)}")
    return HASHES[hash_model](bytes(data))


def solves(hash_model: str, nonce: bytes, secret: bytes, ntz: int) -> bool:
    digest = hex_digest(hash_model, bytes(nonce) + bytes(secret))
    return digest.endswith("0" * ntz)


def enum_position(secret: bytes) -> int:
    """Candidates a reference-order search covers up to and including
    ``secret``.

    A chunk of ``w`` bytes whose top byte is zero is not a minimal
    encoding: a search reaches it only after the whole of width ``w``
    (a launch that ran past the width's end wrapped into it), so it is
    credited past that width."""
    chunk = secret[1:]
    value = int.from_bytes(chunk, "little")
    if chunk and chunk[-1] == 0:
        value += 256 ** len(chunk)
    return value * 256 + secret[0] + 1


def credit(secret: bytes, first: Optional[int]) -> int:
    """Candidates an answer is credited with: its reference position,
    but never past the puzzle's recorded first solution (``first``,
    from the mix's pool), so a search that skips or reorders
    candidates is not credited with work it did not need."""
    pos = enum_position(secret)
    return pos if first is None else min(pos, first)
