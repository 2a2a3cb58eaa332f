"""The benchmark's own SHA-256 / sha256d search
(benchmark/sha256_search.py), against hashlib, on the CPU: every
width's message layout, the first solution in the reference
enumeration, and a sha256d pool made by make_pool.py."""

from __future__ import annotations

import hashlib
import json
import os

import bench_helpers  # noqa: F401
import pytest

from benchmark import make_pool, reference, sha256_search, traffic

BATCH = 1 << 14


def _hashlib(nonce: bytes, secret: bytes, double: bool) -> bytes:
    h = hashlib.sha256(nonce + secret).digest()
    return hashlib.sha256(h).digest() if double else h


@pytest.mark.parametrize("double", [False, True])
def test_digests_are_sha256_at_every_width(double):
    nonce = bytes.fromhex("a1b2c3d4")
    for w, v0 in [(0, 0), (1, 1), (1, 254), (2, 256), (3, 2 ** 24 - 1),
                  (4, 2 ** 24), (4, 2 ** 32 - 1)]:
        words = sha256_search.digests(nonce, v0, w, 512, double)
        end = 256 ** w if w else 1
        for i in (0, 1, 255, 256, 511):
            v, t = v0 + i // 256, i % 256
            if v >= end:
                continue
            secret = bytes([t]) + (v.to_bytes(w, "little") if w else b"")
            got = b"".join(int(words[k][i]).to_bytes(4, "big")
                           for k in range(8))
            assert got == _hashlib(nonce, secret, double), (w, v0, i)
            assert sha256_search.secret_at(v * 256 + t + 1) == secret


@pytest.mark.parametrize("model", ["sha256", "sha256d"])
@pytest.mark.parametrize("ntz", [2, 3])
def test_first_solution_is_hashlibs_first(model, ntz):
    search = sha256_search.FirstSolution(BATCH, model)
    for k in range(4):
        nonce = traffic.key_nonce(ntz, k, 4, "test-" + model)
        assert search(nonce, ntz) == make_pool.brute_first(nonce, ntz,
                                                           model)


def test_zero_mask_counts_hex_digits_from_the_end():
    for word in (0x12345000, 0xABCDEF00, 0x10000000, 0):
        digest_hex = (bytes(28) + word.to_bytes(4, "big")).hex()
        for ntz in range(9):
            ok = digest_hex.endswith("0" * ntz)
            assert ((word & sha256_search.zero_mask(ntz)) == 0) == ok


def test_make_pool_writes_a_loadable_sha256d_mix(tmp_path):
    path = str(tmp_path / "tiny-sha256d.json")
    with open(path, "w") as f:
        json.dump({"why": "test", "loop": "closed", "nonce_len": 4,
                   "difficulties": [[2, 1.0]], "warm_widths": [0, 1, 2],
                   "pool_ntz": 2, "strata": 4}, f)
    make_pool.main(["--mix", path, "--hash", "sha256d", "--count", "8",
                    "--batch", str(BATCH), "--platform", "cpu"])
    mix = traffic.load_mix(path)
    assert mix["pool_hash"] == "sha256d"
    assert len(mix["pool"]) == 8 and len(mix["warm_pool"]) == 2
    assert [n for n, _ in mix["pool"]] == [
        traffic.key_nonce(2, i, 4, "pool").hex() for i in range(8)]
    for nonce, first in mix["pool"] + mix["warm_pool"]:
        n = bytes.fromhex(nonce)
        assert first == make_pool.brute_first(n, 2, "sha256d")
        assert reference.solves("sha256d", n,
                                sha256_search.secret_at(first), 2)
    assert os.path.getsize(path) < 4096
