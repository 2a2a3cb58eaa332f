"""The benchmark's own md5 search (benchmark/md5_search.py), against
hashlib, on the CPU: every width's message layout, and the first
solution in the reference enumeration."""

from __future__ import annotations

import hashlib

import bench_helpers  # noqa: F401
import pytest

from benchmark import make_pool, md5_search, reference, traffic


@pytest.mark.parametrize("w,v0", [(0, 0), (1, 1), (1, 254), (2, 256),
                                  (3, 2 ** 24 - 1), (4, 2 ** 24),
                                  (4, 2 ** 32 - 1)])
def test_digests_are_md5_at_every_width(w, v0):
    nonce = bytes.fromhex("a1b2c3d4")
    words = md5_search.digests(nonce, v0, w, 512)
    end = 256 ** w if w else 1
    for i in (0, 1, 255, 256, 511):
        v, t = v0 + i // 256, i % 256
        if v >= end:
            continue
        secret = bytes([t]) + (v.to_bytes(w, "little") if w else b"")
        got = b"".join(int(words[k][i]).to_bytes(4, "little")
                       for k in range(4))
        assert got == hashlib.md5(nonce + secret).digest()
        position = v * 256 + t + 1
        assert reference.enum_position(secret) == position
        assert md5_search.secret_at(position) == secret


@pytest.mark.parametrize("ntz", [1, 2, 3, 4])
def test_first_solution_is_hashlibs_first(ntz):
    search = md5_search.FirstSolution(1 << 14)
    for k in range(6):
        nonce = traffic.key_nonce(ntz, k, 4, "test")
        assert search(nonce, ntz) == make_pool.brute_first(nonce, ntz)


def test_zero_mask_counts_hex_digits_from_the_end():
    digest = bytes(12) + bytes([0x12, 0x34, 0x50, 0x00])
    d = int.from_bytes(digest[12:], "little")
    for ntz in range(9):
        ok = digest.hex().endswith("0" * ntz)
        assert ((d & md5_search.zero_mask(ntz)) == 0) == ok


def test_a_pool_is_whole_strata_and_its_warm_pool_lies_past_the_mean():
    search = md5_search.FirstSolution(1 << 14)
    made = make_pool.make(search, 2, 10, 60.0, 4)
    assert len(made["pool"]) == 8 and made["drawn"] >= 10
    assert all(16 ** 2 < p <= 1.25 * 16 ** 2 for _, p in made["warm_pool"])
    for nonce, first in made["pool"] + made["warm_pool"]:
        n = bytes.fromhex(nonce)
        assert first == make_pool.brute_first(n, 2)
