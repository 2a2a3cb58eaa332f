"""The plain reference (benchmark/reference.py) against the program's
own digest, for every hash model of the program's registry."""

from __future__ import annotations

import ast
import os
import random

import bench_helpers as bh
import pytest

from benchmark import reference

MODELS = ["md5", "sha1", "sha256", "sha384", "sha512", "sha3_256",
          "ripemd160", "blake2b_256", "sha256d"]


def test_the_reference_covers_the_registry_and_imports_none_of_it():
    from distpow_tpu.models import registry

    assert sorted(reference.HASHES) == sorted(MODELS) == sorted(
        registry._REGISTRY)
    with open(os.path.join(bh.REPO, "benchmark", "reference.py")) as f:
        tree = ast.parse(f.read())
    imported = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names]
    imported += [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
    assert not [m for m in imported if m.startswith("distpow_tpu")]


@pytest.mark.parametrize("model", MODELS)
def test_reference_agrees_with_the_programs_digest(model):
    from distpow_tpu.models.puzzle import hash_hex

    rng = random.Random(model)
    for size in list(range(65)) + [rng.randrange(65) for _ in range(15)]:
        msg = bytes(rng.randrange(256) for _ in range(size))
        cut = rng.randrange(size + 1)
        nonce, secret = msg[:cut], msg[cut:]
        want = hash_hex(nonce, secret, model)
        assert reference.hex_digest(model, msg) == want
        ntz = len(want) - len(want.rstrip("0"))
        assert reference.solves(model, nonce, secret, ntz)
        assert not reference.solves(model, nonce, secret, ntz + 1)


def test_an_unknown_model_is_an_error_naming_it():
    with pytest.raises(KeyError, match="whirlpool"):
        reference.solves("whirlpool", b"n", b"s", 1)


def test_a_host_without_a_hash_raises_naming_the_model(monkeypatch):
    def new(name):
        raise ValueError(f"unsupported hash type {name}")

    monkeypatch.setattr(reference.hashlib, "new", new)
    with pytest.raises(ValueError, match="ripemd160"):
        reference.solves("ripemd160", b"n", b"s", 1)
