"""Hash-model correctness: JAX and pure-Python twins vs hashlib."""

import hashlib
import random
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from distpow_tpu.models import (
    blake2b_jax,
    md5_jax,
    ripemd160_jax,
    sha1_jax,
    sha3_jax,
    sha256_jax,
    sha256d_jax,
    sha384_jax,
    sha512_jax,
)
from distpow_tpu.models.registry import (
    BLAKE2B_256,
    MD5,
    RIPEMD160,
    SHA1,
    SHA3_256,
    SHA256,
    SHA256D,
    SHA384,
    SHA512,
    get_hash_model,
)


def pad_md5(message: bytes) -> bytes:
    tail = message + b"\x80"
    tail += b"\x00" * ((-len(tail) - 8) % 64)
    tail += struct.pack("<Q", len(message) * 8)
    return tail


def pad_sha256(message: bytes) -> bytes:
    tail = message + b"\x80"
    tail += b"\x00" * ((-len(tail) - 8) % 64)
    tail += struct.pack(">Q", len(message) * 8)
    return tail


def blocks_to_words(padded: bytes, order: str):
    fmt = "<16I" if order == "little" else ">16I"
    return [
        list(struct.unpack(fmt, padded[i : i + 64]))
        for i in range(0, len(padded), 64)
    ]


@pytest.mark.parametrize("length", [0, 1, 3, 8, 55, 56, 63, 64, 65, 120, 200])
def test_md5_jax_vs_hashlib(length):
    rng = random.Random(length)
    msg = bytes(rng.randrange(256) for _ in range(length))
    words = blocks_to_words(pad_md5(msg), "little")
    state = md5_jax.md5_digest_words(words)
    digest = b"".join(int(w).to_bytes(4, "little") for w in state)
    assert digest == hashlib.md5(msg).digest()


@pytest.mark.parametrize("length", [0, 1, 8, 55, 56, 64, 65, 130])
def test_sha256_jax_vs_hashlib(length):
    rng = random.Random(1000 + length)
    msg = bytes(rng.randrange(256) for _ in range(length))
    words = blocks_to_words(pad_sha256(msg), "big")
    state = sha256_jax.sha256_digest_words(words)
    digest = b"".join(int(w).to_bytes(4, "big") for w in state)
    assert digest == hashlib.sha256(msg).digest()


@pytest.mark.parametrize("length", [0, 1, 8, 55, 56, 64, 65, 130])
def test_sha1_jax_vs_hashlib(length):
    rng = random.Random(2000 + length)
    msg = bytes(rng.randrange(256) for _ in range(length))
    # same big-endian single-padding scheme as sha256 (FIPS 180-4)
    words = blocks_to_words(pad_sha256(msg), "big")
    state = sha1_jax.sha1_digest_words(words)
    digest = b"".join(int(w).to_bytes(4, "big") for w in state)
    assert digest == hashlib.sha1(msg).digest()


def test_md5_jax_vectorized_batch():
    # the compression must vectorize over batch-shaped message words
    rng = random.Random(7)
    msgs = [bytes(rng.randrange(256) for _ in range(10)) for _ in range(33)]
    word_batches = []
    for m in msgs:
        word_batches.append(blocks_to_words(pad_md5(m), "little")[0])
    arr = np.array(word_batches, dtype=np.uint32)  # (33, 16)
    words = [jnp.asarray(arr[:, i]) for i in range(16)]
    state = md5_jax.md5_digest_words([words])
    for j, m in enumerate(msgs):
        digest = b"".join(int(w[j]).to_bytes(4, "little") for w in state)
        assert digest == hashlib.md5(m).digest()


@pytest.mark.parametrize("model,href", [
    (MD5, hashlib.md5),
    (SHA256, hashlib.sha256),
    (SHA1, hashlib.sha1),
    (RIPEMD160, lambda m: hashlib.new("ripemd160", m)),
    (SHA512, hashlib.sha512),
    (SHA384, hashlib.sha384),
    (SHA3_256, hashlib.sha3_256),
    (BLAKE2B_256, lambda m: hashlib.blake2b(m, digest_size=32)),
    (SHA256D,
     lambda m: hashlib.sha256(hashlib.sha256(m).digest())),
])
@pytest.mark.parametrize("length", [0, 5, 63, 64, 70, 128, 129, 135, 136, 137])
def test_py_twins_vs_hashlib(model, href, length):
    rng = random.Random(length * 31)
    msg = bytes(rng.randrange(256) for _ in range(length))
    mod = {MD5: md5_jax, SHA256: sha256_jax, SHA1: sha1_jax,
           RIPEMD160: ripemd160_jax, SHA512: sha512_jax,
           SHA384: sha384_jax, SHA3_256: sha3_jax,
           BLAKE2B_256: blake2b_jax, SHA256D: sha256d_jax}[model]
    assert mod.py_digest(msg) == href(msg).digest()


@pytest.mark.parametrize("length", [0, 1, 8, 55, 56, 64, 65, 130])
def test_ripemd160_jax_vs_hashlib(length):
    rng = random.Random(3000 + length)
    msg = bytes(rng.randrange(256) for _ in range(length))
    # MD5's little-endian padding scheme (ISO 10118-3)
    words = blocks_to_words(pad_md5(msg), "little")
    state = RIPEMD160.init_state
    for block in words:
        state = ripemd160_jax.ripemd160_compress(
            state, [jnp.uint32(w) for w in block])
    digest = b"".join(int(w).to_bytes(4, "little") for w in state)
    assert digest == hashlib.new("ripemd160", msg).digest()


def test_ripemd160_spec_vectors():
    """Published vectors from the RIPEMD-160 paper (Dobbertin,
    Bosselaers, Preneel — Appendix B), independent of this machine's
    hashlib/OpenSSL build."""
    vectors = {
        b"": "9c1185a5c5e9fc54612808977ee8f548b2258d31",
        b"a": "0bdc9d2d256b3ee9daae347be6f4dc835a467ffe",
        b"abc": "8eb208f7e05d987a9b044a8e98c6b087f15a0bfc",
        b"message digest": "5d0689ef49d2fae572b881b123a85ffa21595f36",
        b"abcdefghijklmnopqrstuvwxyz":
            "f71c27109c692c1b56bbdceb5b9d2865b3708dbc",
        b"1234567890" * 8: "9b752e45573d4b39f4dbd3323cab82bf63326bfb",
    }
    for msg, want in vectors.items():
        assert ripemd160_jax.py_digest(msg).hex() == want, msg


def test_py_absorb_prefix_state():
    # absorbing N full blocks then continuing must equal hashing the whole
    # message — this is what lets long constant nonces run host-side
    msg = bytes(range(256)) * 2  # 512 bytes = 8 blocks
    state, rem, absorbed = md5_jax.py_absorb(msg[:130])
    assert absorbed == 128 and rem == msg[128:130]
    # continue: tail = rem + suffix and padding with total length
    suffix = b"hello"
    total = msg[:130] + suffix
    tail = rem + suffix + b"\x80"
    tail += b"\x00" * ((-len(tail) - 8) % 64)
    tail += struct.pack("<Q", len(total) * 8)
    for i in range(0, len(tail), 64):
        state = md5_jax.py_compress(state, tail[i : i + 64])
    digest = b"".join(w.to_bytes(4, "little") for w in state)
    assert digest == hashlib.md5(total).digest()


def test_registry():
    assert get_hash_model("md5") is MD5
    assert get_hash_model("SHA256") is SHA256
    assert get_hash_model("sha1") is SHA1
    assert get_hash_model("ripemd160") is RIPEMD160
    assert get_hash_model("sha512") is SHA512
    assert SHA512.max_difficulty == 128
    assert SHA512.words_per_block == 32 and SHA512.length_bytes == 16
    assert get_hash_model("sha384") is SHA384
    # the truncating model: digest narrower than the carried state
    assert SHA384.max_difficulty == 96 and SHA384.digest_words == 12
    assert len(SHA384.init_state) == 16
    assert SHA384.state_to_digest(SHA384.init_state) == b"".join(
        w.to_bytes(4, "big") for w in SHA384.init_state[:12])
    assert MD5.max_difficulty == 32
    assert SHA256.max_difficulty == 64
    assert SHA1.max_difficulty == 40
    assert RIPEMD160.max_difficulty == 40
    with pytest.raises(ValueError):
        get_hash_model("sha1024")


def test_ripemd160_fallback_without_openssl_support(monkeypatch):
    """ripemd160 is the only registry model outside hashlib's guaranteed
    set (stock OpenSSL 3 without the legacy provider refuses it); every
    puzzle verification path must fall back to the spec-vector-pinned
    pure-Python implementation (models/ripemd160_py.py) on such hosts."""
    from distpow_tpu.models import puzzle

    real_new = hashlib.new

    def deny(name, *a, **k):
        if name == "ripemd160":
            raise ValueError("unsupported hash type ripemd160")
        return real_new(name, *a, **k)

    monkeypatch.setattr(hashlib, "new", deny)
    h = puzzle.new_hash("ripemd160")
    h.update(b"abc")
    assert h.hexdigest() == "8eb208f7e05d987a9b044a8e98c6b087f15a0bfc"
    assert RIPEMD160.hashlib_new().name == "ripemd160"
    oracle = puzzle.python_search(b"\x0a\x0b", 2, list(range(256)),
                                  algo="ripemd160")
    assert puzzle.check_secret(b"\x0a\x0b", oracle, 2, algo="ripemd160")
    # non-ripemd algos still reject unknown names
    with pytest.raises(ValueError):
        puzzle.new_hash("sha1024")


@pytest.mark.parametrize("length", [0, 1, 8, 111, 112, 127, 128, 129, 260])
def test_sha512_jax_vs_hashlib(length):
    """Fifth model (round 4): 128-byte blocks, 16-byte length field,
    64-bit words emulated as (hi, lo) uint32 pairs.  Lengths straddle
    the 112-mod-128 two-block-padding boundary and the 128-byte block
    boundary."""
    rng = random.Random(4000 + length)
    msg = bytes(rng.randrange(256) for _ in range(length))
    tail = msg + b"\x80"
    tail += b"\x00" * ((-len(tail) - 16) % 128)
    tail += (len(msg) * 8).to_bytes(16, "big")
    state = SHA512.init_state
    for i in range(0, len(tail), 128):
        words = struct.unpack(">32I", tail[i:i + 128])
        state = sha512_jax.sha512_compress(state, [jnp.uint32(w) for w in words])
    digest = b"".join(int(w).to_bytes(4, "big") for w in state)
    assert digest == hashlib.sha512(msg).digest()


def test_sha512_spec_vector():
    """FIPS 180-4 / NIST example vector, independent of hashlib."""
    assert sha512_jax.py_digest(b"abc").hex() == (
        "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
        "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f")


def test_loop_compress_all_constant_block_with_batched_state():
    """Regression (round 4): a tail block can be ALL-constant (every
    message word a scalar) while the incoming state is batch-shaped —
    the fori_loop forms derived their broadcast shape from the words
    alone and crashed in broadcast_to.  Exercises sha256, sha1, and
    sha512 loop forms directly."""
    for model in (SHA256, SHA1, SHA512):
        n = model.words_per_block
        batch_state = tuple(
            jnp.full((7,), s, jnp.uint32) for s in model.init_state)
        out = model.compress(batch_state, [int(i + 1) for i in range(n)])
        # must equal the scalar-state result broadcast
        ref = model.compress(model.init_state,
                             [int(i + 1) for i in range(n)])
        for o, r in zip(out, ref):
            assert o.shape == (7,)
            assert int(o[3]) == int(r)


def test_sha384_spec_vector_and_truncation():
    """FIPS 180-4 vector; the digest is the first 48 bytes of the
    (differently-initialized) sha512 state — the truncating-model case
    (digest_words < state words) no layer may conflate."""
    assert sha384_jax.py_digest(b"abc").hex() == (
        "cb00753f45a35e8bb5a03d699ac65007272c32ab0eded1631a8b605a43ff5bed"
        "8086072ba1e7cc2358baeca134c825a7")
    # mining parity at a difficulty whose masks live in the truncated
    # digest's trailing words
    from distpow_tpu.models import puzzle
    from distpow_tpu.parallel.search import persistent_search

    tbs = list(range(256))
    oracle = puzzle.python_search(b"\x31\x41", 2, tbs, algo="sha384")
    got = persistent_search(b"\x31\x41", 2, tbs, model=SHA384,
                            batch_size=1 << 13)
    assert got is not None and got.secret == oracle


def test_sha3_registry_and_spec_vectors():
    """The sponge model's registry shape + FIPS 202 vectors (the empty
    string and 'abc' are the published SHA3-256 examples)."""
    assert get_hash_model("sha3_256") is SHA3_256
    assert SHA3_256.padding == "sha3" and MD5.padding == "md"
    assert SHA3_256.block_bytes == 136 and SHA3_256.words_per_block == 34
    assert SHA3_256.digest_words == 8 and SHA3_256.max_difficulty == 64
    assert len(SHA3_256.init_state) == 50  # 25 lanes x 2 limbs
    assert sha3_jax.py_digest(b"").hex() == (
        "a7ffc6f8bf1ed76651c14756a061d662f580ff4de43b49fa82d80a4b80f8434a")
    assert sha3_jax.py_digest(b"abc").hex() == (
        "3a985da74fe225b2045c172d6bd390bd855f086e3e9d525b46bfe24511431532")


def test_sha3_jax_compress_batch_vs_hashlib():
    """The limb-pair keccak on batch-shaped words (the serving operand
    shape) matches hashlib lane-for-lane, one-block and two-block."""
    rng = random.Random(99)
    N = 9
    msgs = [bytes(rng.randrange(256) for _ in range(rng.randrange(1, 135)))
            for _ in range(N)]
    blocks = []
    for m in msgs:
        t = bytearray(136)
        t[: len(m)] = m
        t[len(m)] ^= 0x06
        t[-1] ^= 0x80
        blocks.append(struct.unpack("<34I", bytes(t)))
    arr = np.array(blocks, np.uint32)  # (N, 34)
    words = [jnp.asarray(arr[:, j]) for j in range(34)]
    state = sha3_jax.sha3_256_compress(sha3_jax.SHA3_INIT, words)
    for i, m in enumerate(msgs):
        digest = b"".join(
            int(np.asarray(state[w])[i]).to_bytes(4, "little")
            for w in range(8)
        )
        assert digest == hashlib.sha3_256(m).digest(), i
    # two-block path: absorbed prefix -> device continuation
    long_msg = bytes(range(200))
    st, rem, absorbed = sha3_jax.py_absorb(long_msg)
    assert absorbed == 136 and len(rem) == 64
    t = bytearray(136)
    t[: len(rem)] = rem
    t[len(rem)] ^= 0x06
    t[-1] ^= 0x80
    st = sha3_jax.sha3_256_compress(st, struct.unpack("<34I", bytes(t)))
    digest = b"".join(int(w).to_bytes(4, "little") for w in st[:8])
    assert digest == hashlib.sha3_256(long_msg).digest()


def test_sha256d_registry_and_finalize():
    """The composed model's registry shape (r5 ninth model): sha256d
    is plain SHA-256 absorption plus a ``finalize`` composition stage
    — the structural axis no other model exercises.  The vectorized
    finalize and its python twin must agree with hashlib's double
    digest, and the serving path must apply it (a cached step at
    difficulty 1 agrees with the double-hash oracle)."""
    import hashlib

    m = get_hash_model("sha256d")
    assert m is SHA256D
    assert m.finalize is sha256d_jax.sha256d_finalize
    assert m.py_finalize is sha256d_jax.py_finalize
    assert m.compress is sha256_jax.sha256_compress
    assert m.max_difficulty == 64 and m.digest_words == 8

    # vectorized finalize == python twin == hashlib, over a small batch
    msgs = [bytes([i]) * 11 for i in range(4)]
    states = [sha256_jax.py_absorb(b"")[0] for _ in msgs]
    firsts = []
    for msg, st in zip(msgs, states):
        padded = (msg + b"\x80" + bytes((55 - len(msg)) % 64)
                  + (8 * len(msg)).to_bytes(8, "big"))
        for i in range(0, len(padded), 64):
            st = sha256_jax.py_compress(st, padded[i:i + 64])
        firsts.append(st)
    batch = tuple(
        jnp.asarray(np.array([f[w] for f in firsts], np.uint32))
        for w in range(8)
    )
    out = sha256d_jax.sha256d_finalize(batch)
    for i, msg in enumerate(msgs):
        want = hashlib.sha256(hashlib.sha256(msg).digest()).digest()
        got = b"".join(int(w[i]).to_bytes(4, "big") for w in out)
        assert got == want
        assert m.state_to_digest(sha256d_jax.py_finalize(firsts[i])) == want

    # the serving (dyn) path applies finalize: first hit at difficulty
    # 1 matches the double-hash oracle exactly
    from distpow_tpu.models import puzzle
    from distpow_tpu.ops.search_step import SENTINEL, cached_search_step

    nonce = b"\x09\x08\x07"
    step = cached_search_step(nonce, 1, 1, 0, 256, 64, "sha256d")
    got_f = int(step(jnp.uint32(0)))
    assert got_f != SENTINEL
    # brute oracle over the same window
    want_f = None
    for f in range(64 * 256):
        chunk, tb = f // 256, f % 256
        secret = bytes([tb, chunk & 0xFF])
        h = puzzle.new_hash("sha256d")
        h.update(nonce + secret)
        if h.hexdigest().endswith("0"):
            want_f = f
            break
    assert got_f == want_f


def test_blake2b_py_compress_accepts_plain_block():
    """Generic-consumer contract (advisor r4 + review r5): a plain
    BLOCK_BYTES block with an EXPLICIT byte counter must compress
    identically to the template-shaped block carrying the same baked
    parameters; omitting t raises a guiding TypeError instead of
    silently chaining multi-block inputs into a wrong digest (blake2's
    compression is not a pure function of (state, block))."""
    import pytest as _pytest

    from distpow_tpu.models import blake2b_py as b

    state, rem, absorbed = b.py_absorb(b"")
    assert rem == b"" and absorbed == 0
    block = bytes(range(100)) + bytes(28)
    params = (128).to_bytes(8, "little") + (0).to_bytes(8, "little")
    assert (b.py_compress(state, block, t=128)
            == b.py_compress(state, block + params))
    # a final block via explicit kwargs
    final_params = (100).to_bytes(8, "little") + \
        (0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
    assert (b.py_compress(state, block, t=100, last=True)
            == b.py_compress(state, block + final_params))
    # the plain non-final form agrees with py_absorb on real data
    state2, _, absorbed2 = b.py_absorb(block + b"\x99")
    assert absorbed2 == 128
    assert tuple(b.py_compress(state, block, t=128)) == tuple(state2)
    # ...and CHAINING with correct counters matches a 2-block absorb
    block2 = bytes(range(50, 178))
    state3, _, absorbed3 = b.py_absorb(block + block2 + b"\x77")
    assert absorbed3 == 256
    chained = b.py_compress(
        b.py_compress(state, block, t=128), block2, t=256)
    assert tuple(chained) == tuple(state3)
    # omitted counter on a plain block: guided error, not wrong math
    with _pytest.raises(TypeError, match="bytes absorbed"):
        b.py_compress(state, block)
    # the template form still rejects doubled parameters (TypeError,
    # not assert: must survive python -O)
    with _pytest.raises(TypeError, match="baked"):
        b.py_compress(state, block + params, t=1)


def test_blake2b_registry_and_params():
    """The per-block-parameter model's registry shape: blake2's byte
    counter and finalization flag are compression inputs the packing
    layer bakes as extra template words (HashModel.block_param_words) —
    the structural axis no other model exercises."""
    from distpow_tpu.models import blake2b_jax
    from distpow_tpu.models.registry import BLAKE2B_256

    assert get_hash_model("blake2b_256") is BLAKE2B_256
    assert BLAKE2B_256.padding == "blake2"
    assert BLAKE2B_256.param_words == 4
    assert BLAKE2B_256.block_param_words is blake2b_jax.block_param_words
    assert BLAKE2B_256.digest_words == 8 and BLAKE2B_256.max_difficulty == 64
    # param derivation: non-final blocks count full message bytes,
    # the final block the true length, finality all-ones
    assert blake2b_jax.block_param_words(0, 200, 0, 2) == (128, 0, 0, 0)
    assert blake2b_jax.block_param_words(0, 200, 1, 2) == (
        200, 0, 0xFFFFFFFF, 0xFFFFFFFF)
    assert blake2b_jax.block_param_words(256, 10, 0, 1) == (
        266, 0, 0xFFFFFFFF, 0xFFFFFFFF)
    # the template rows carry the params (packing layer)
    from distpow_tpu.ops.packing import build_tail_spec

    spec = build_tail_spec(b"\x01\x02", 2, BLAKE2B_256)
    assert spec.n_blocks == 1
    assert len(spec.base_words[0]) == 32 + 4
    # t = 2 (nonce rem) + 1 (tb) + 2 (width) = 5; final
    assert spec.base_words[0][32:] == (5, 0, 0xFFFFFFFF, 0xFFFFFFFF)


def test_blake2b_search_matches_oracle():
    """Mining parity end-to-end: zero-fill padding, baked per-block
    params, including a host-absorbed full prefix block (the t counter
    must carry across the absorb boundary)."""
    from distpow_tpu.models import puzzle
    from distpow_tpu.models.registry import BLAKE2B_256
    from distpow_tpu.parallel.search import persistent_search

    tbs = list(range(256))
    for nonce in (b"\x27\x18", bytes(range(130))):
        oracle = puzzle.python_search(nonce, 2, tbs, algo="blake2b_256")
        got = persistent_search(nonce, 2, tbs, model=BLAKE2B_256,
                                batch_size=1 << 13)
        assert got is not None and got.secret == oracle
        assert hashlib.blake2b(nonce + got.secret,
                               digest_size=32).hexdigest().endswith("00")


def test_sha3_search_matches_oracle():
    """Mining parity end-to-end through the generic driver — the sponge
    padding hook (ops/packing.py) in its serving configuration."""
    from distpow_tpu.models import puzzle
    from distpow_tpu.parallel.search import persistent_search

    tbs = list(range(256))
    for nonce in (b"\x27\x18", b"\x01\x02\x03\x04"):
        oracle = puzzle.python_search(nonce, 2, tbs, algo="sha3_256")
        got = persistent_search(nonce, 2, tbs, model=SHA3_256,
                                batch_size=1 << 13)
        assert got is not None and got.secret == oracle
        assert hashlib.sha3_256(nonce + got.secret).hexdigest().endswith("00")
