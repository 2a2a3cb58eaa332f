"""Ops-layer tests: packing, difficulty masks, fused search step."""

import hashlib
import random

import jax.numpy as jnp
import numpy as np
import pytest

from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import (
    BLAKE2B_256,
    MD5,
    RIPEMD160,
    SHA1,
    SHA3_256,
    SHA256,
    SHA512,
)
from distpow_tpu.ops.difficulty import meets_difficulty, nibble_masks
from distpow_tpu.ops.packing import build_tail_spec, make_words, pack_reference_bytes
from distpow_tpu.ops.search_step import (
    SENTINEL,
    build_search_step,
    flat_to_candidate,
)


def digest_of(spec, model, tb, chunk):
    state = spec.init_state
    for b in range(spec.n_blocks):
        words = make_words(spec, jnp.uint32(tb), jnp.uint32(chunk))[b]
        state = model.compress(state, words)
    return model.state_to_digest(state)


@pytest.mark.parametrize("model", [
    MD5, SHA256, SHA1,
    # 36 eager loop-form compiles; the fast path keeps sha512 packing
    # covered via test_sha512_jax_vs_hashlib + the search-layer tests
    pytest.param(SHA512, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("nonce_len", [0, 4, 20, 54, 55, 63, 64, 65, 130])
@pytest.mark.parametrize("width", [0, 1, 3, 4])
def test_packing_matches_hashlib(model, nonce_len, width):
    rng = random.Random(nonce_len * 7 + width)
    nonce = bytes(rng.randrange(256) for _ in range(nonce_len))
    spec = build_tail_spec(nonce, width, model)
    for _ in range(3):
        tb = rng.randrange(256)
        chunk = rng.randrange(256 ** width) if width else 0
        msg = pack_reference_bytes(nonce, tb, chunk, width)
        expect = model.hashlib_new()
        expect.update(msg)
        assert digest_of(spec, model, tb, chunk) == expect.digest()


def test_sha256d_tail_spec_identical_to_sha256():
    """Composition must not leak into packing: sha256d's tail spec is
    byte-identical to sha256's at every layout (same padding family,
    block geometry, byte orders, init state) — the finalize stage is
    the ONLY difference between the two models' device programs."""
    from distpow_tpu.models.registry import get_hash_model

    sha256d = get_hash_model("sha256d")
    rng = random.Random(0xD0)
    for nonce_len in (0, 4, 55, 64, 70, 130):
        nonce = bytes(rng.randrange(256) for _ in range(nonce_len))
        for width in (0, 2, 4):
            a = build_tail_spec(nonce, width, SHA256)
            b = build_tail_spec(nonce, width, sha256d)
            assert a.init_state == b.init_state
            assert a.n_blocks == b.n_blocks
            assert a.base_words == b.base_words
            assert a.tb_loc == b.tb_loc and a.chunk_locs == b.chunk_locs
    # ...and the composed digest check itself is pinned elsewhere
    # (test_hash_models.test_sha256d_registry_and_finalize, the fuzz)


def test_packing_extra_const_chunk():
    # width > 4 support: high chunk bytes folded into the constant template
    nonce = b"\x01\x02\x03\x04"
    extra = b"\x09\x02"
    spec = build_tail_spec(nonce, 4, MD5, extra_const_chunk=extra)
    msg = pack_reference_bytes(nonce, 7, 0xDEADBEEF, 4, extra)
    assert digest_of(spec, MD5, 7, 0xDEADBEEF) == hashlib.md5(msg).digest()
    assert len(msg) == 4 + 1 + 4 + 2


@pytest.mark.parametrize("model", [SHA3_256, BLAKE2B_256])
@pytest.mark.parametrize("nonce_len", [0, 4, 130, 135, 260])
@pytest.mark.parametrize("width", [0, 2, 4])
def test_packing_matches_hashlib_sponge_and_params(model, nonce_len, width):
    """The two non-Merkle-Damgard packing families through the full
    template path: sha3's pad10*1 and blake2's zero-fill + baked (t, f)
    parameter words, across host-absorption boundaries (nonce lengths
    bracket the 128/136-byte blocks) and chunk widths — including the
    width>4 extra-const-chunk mechanism, whose bytes must count into
    blake2's byte counter."""
    rng = random.Random(nonce_len * 13 + width)
    nonce = bytes(rng.randrange(256) for _ in range(nonce_len))
    for extra in (b"", b"\x09\x02"):
        spec = build_tail_spec(nonce, width, model, extra_const_chunk=extra)
        for _ in range(2):
            tb = rng.randrange(256)
            chunk = rng.randrange(256 ** width) if width else 0
            msg = pack_reference_bytes(nonce, tb, chunk, width, extra)
            expect = model.hashlib_new()
            expect.update(msg)
            assert digest_of(spec, model, tb, chunk) == expect.digest(), (
                model.name, nonce_len, width, extra
            )


@pytest.mark.parametrize("model", [MD5, SHA256, SHA1, SHA512])
def test_nibble_masks_vs_oracle(model):
    rng = random.Random(42)
    for _ in range(300):
        digest = bytes(
            rng.choice([0, 0, rng.randrange(256)])
            for _ in range(model.digest_bytes)
        )
        words = tuple(
            jnp.uint32(
                int.from_bytes(digest[4 * i : 4 * i + 4], model.word_byteorder)
            )
            for i in range(model.digest_words)
        )
        true_k = puzzle.count_trailing_zero_nibbles(digest)
        for k in (0, 1, true_k, true_k + 1, model.max_difficulty):
            if k > model.max_difficulty:
                with pytest.raises(ValueError):
                    nibble_masks(k, model)
                continue
            ok = bool(meets_difficulty(words, nibble_masks(k, model)))
            assert ok == (true_k >= k), (digest.hex(), k, true_k)


def test_search_step_finds_reference_first_match():
    nonce = b"\x01\x02\x03\x04"
    difficulty = 2
    tbs = list(range(256))
    # oracle: first match in reference enumeration order within width<=2
    oracle = puzzle.python_search(nonce, difficulty, tbs)
    assert oracle is not None

    # width-0 step
    step0 = build_search_step(nonce, 0, difficulty, 0, 256, 1, MD5)
    f0 = int(step0(jnp.uint32(0)))
    # width-1 step covering chunks [1, 256)
    step1 = build_search_step(nonce, 1, difficulty, 0, 256, 255, MD5)
    f1 = int(step1(jnp.uint32(1)))

    if f0 != SENTINEL:
        chunk, tb = flat_to_candidate(f0, 0, 0, 256)
        secret = bytes([tb])
    else:
        assert f1 != SENTINEL
        chunk, tb = flat_to_candidate(f1, 1, 0, 256)
        secret = bytes([tb]) + puzzle.int_to_chunk(chunk)
    assert secret == oracle


def test_search_step_no_false_positives_at_high_difficulty():
    step = build_search_step(b"\x05\x06", 1, 30, 0, 256, 16, MD5)
    assert int(step(jnp.uint32(1))) == SENTINEL


def test_search_step_sha256():
    nonce = b"\xaa"
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 2, tbs, algo="sha256")
    found = None
    step0 = build_search_step(nonce, 0, 2, 0, 256, 1, SHA256)
    f = int(step0(jnp.uint32(0)))
    if f != SENTINEL:
        found = bytes([f % 256])
    else:
        step1 = build_search_step(nonce, 1, 2, 0, 256, 255, SHA256)
        f = int(step1(jnp.uint32(1)))
        assert f != SENTINEL
        chunk, tb = flat_to_candidate(f, 1, 0, 256)
        found = bytes([tb]) + puzzle.int_to_chunk(chunk)
    assert found == oracle


# ---------------------------------------------------------------------------
# Dynamic (serving-path) regime: cached_search_step binds nonce/difficulty/
# partition as runtime operands onto layout-keyed compiled programs.
# ---------------------------------------------------------------------------

from distpow_tpu.ops.search_step import _dyn_search_step, cached_search_step


# Non-md5 parametrizations are `slow` (VERDICT r3 item 8: XLA:CPU
# compiles of their compress forms dominate the default suite); md5
# keeps dyn-vs-static parity in the fast path.  The LONG-nonce cells
# of the two costliest compilers (ripemd160, sha512 — 15-20 s of
# XLA:CPU compile each, r5 durations) sit in the nightly veryslow
# tier: their short-nonce parity still gates every full run, and the
# long-nonce layout class stays covered per full run by the other
# models' (63,1)/(70,2) cells (VERDICT r4 item 6 suite budget).
def _dyn_static_cells():
    cells = []
    for nl, w in ((2, 1), (4, 2), (63, 1), (70, 2)):
        for model in (MD5, SHA256, SHA1, RIPEMD160, SHA512):
            if model is MD5:
                marks = ()
            elif model in (RIPEMD160, SHA512) and nl > 8:
                marks = (pytest.mark.veryslow,)
            else:
                marks = (pytest.mark.slow,)
            cells.append(pytest.param(
                model, nl, w, marks=marks,
                id=f"{model.name}-{nl}-{w}"))
    return cells


@pytest.mark.parametrize("model,nonce_len,width", _dyn_static_cells())
def test_dyn_step_matches_static(model, nonce_len, width):
    rng = random.Random(nonce_len * 31 + width)
    nonce = bytes(rng.randrange(256) for _ in range(nonce_len))
    for difficulty, tb_lo, tbc in ((1, 0, 256), (2, 64, 64)):
        dyn = cached_search_step(
            nonce, width, difficulty, tb_lo, tbc, 8, model.name
        )
        static = build_search_step(
            nonce, width, difficulty, tb_lo, tbc, 8, model
        )
        for c0 in (1, 77, 255):
            assert int(dyn(jnp.uint32(c0))) == int(static(jnp.uint32(c0)))


def test_dyn_step_compile_reuse_across_requests():
    """Different nonces, difficulties, and power-of-two partitions of the
    same (length, width, batch) must share one compiled program."""
    _dyn_search_step.cache_clear()
    cached_search_step(b"\x01\x02\x03\x04", 2, 3, 0, 256, 16, "md5")
    before = _dyn_search_step.cache_info()
    # same length/width/batch, different content/difficulty/partition:
    cached_search_step(b"\xaa\xbb\xcc\xdd", 2, 7, 0, 256, 16, "md5")
    cached_search_step(b"\x01\x02\x03\x04", 2, 5, 64, 64, 64, "md5")  # batch 4096 == 16*256
    after = _dyn_search_step.cache_info()
    assert after.misses == before.misses, "unexpected recompile"
    assert after.hits > before.hits
    # different length => new layout => one new compile
    cached_search_step(b"\x01\x02\x03", 2, 3, 0, 256, 16, "md5")
    assert _dyn_search_step.cache_info().misses == before.misses + 1


def test_dyn_step_difficulty_bucket_sharing():
    """Difficulties 1..8 share one compiled program (one significant mask
    word); difficulty 9+ selects a second bucket and still matches the
    static program."""
    _dyn_search_step.cache_clear()
    cached_search_step(b"\x31\x32\x33\x34", 2, 1, 0, 256, 16, "md5")
    before = _dyn_search_step.cache_info()
    for d in (2, 5, 8):
        cached_search_step(b"\x31\x32\x33\x34", 2, d, 0, 256, 16, "md5")
    assert _dyn_search_step.cache_info().misses == before.misses
    nine = cached_search_step(b"\x31\x32\x33\x34", 2, 9, 0, 256, 16, "md5")
    assert _dyn_search_step.cache_info().misses == before.misses + 1
    static9 = build_search_step(b"\x31\x32\x33\x34", 2, 9, 0, 256, 16, MD5)
    for c0 in (256, 5000):
        assert int(nine(jnp.uint32(c0))) == int(static9(jnp.uint32(c0)))


def test_dyn_step_non_pow2_partition_falls_back():
    nonce = b"\x0e\x0f"
    dyn = cached_search_step(nonce, 1, 1, 10, 96, 4, "md5")
    static = build_search_step(nonce, 1, 1, 10, 96, 4, MD5)
    for c0 in (1, 100):
        assert int(dyn(jnp.uint32(c0))) == int(static(jnp.uint32(c0)))


def test_backend_warmup_smoke():
    from distpow_tpu.backends import JaxBackend

    b = JaxBackend(batch_size=1 << 12)
    b.warmup([3], [0, 1])
    # warmed layouts serve a real request without new dyn compiles
    before = _dyn_search_step.cache_info().misses
    secret = b.search(b"\x09\x08\x07", 2, list(range(256)))
    assert secret is not None
    assert puzzle.check_secret(b"\x09\x08\x07", secret, 2)
    assert _dyn_search_step.cache_info().misses == before


def test_w0_program_partition_independent():
    """Width-0 probes share one layout-keyed program across partitions
    (the first Mine on any worker split is pure dispatch after warmup)."""
    from distpow_tpu.ops.search_step import _dyn_search_step_w0

    _dyn_search_step_w0.cache_clear()
    nonce = b"\x0c\x0d"
    full = cached_search_step(nonce, 0, 1, 0, 256, 1, "md5")
    misses = _dyn_search_step_w0.cache_info().misses
    quarter = cached_search_step(nonce, 0, 1, 64, 64, 1, "md5")
    assert _dyn_search_step_w0.cache_info().misses == misses
    # results agree with the static program on both partitions
    for dyn, (lo, cnt) in ((full, (0, 256)), (quarter, (64, 64))):
        static = build_search_step(nonce, 0, 1, lo, cnt, 1, MD5)
        assert int(dyn(jnp.uint32(0))) == int(static(jnp.uint32(0)))
