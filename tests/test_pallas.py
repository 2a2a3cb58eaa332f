"""Pallas kernel tests (interpret mode on CPU; the driver/bench exercise
the compiled kernel on real TPU hardware)."""

import inspect

import jax
import jax.numpy as jnp
import pytest

from distpow_tpu.backends import get_backend
from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import MD5, get_hash_model
from distpow_tpu.ops.md5_pallas import build_pallas_search_step
from distpow_tpu.ops.search_step import SENTINEL, build_search_step
from distpow_tpu.parallel.mesh_search import pallas_mesh_persistent_step
from distpow_tpu.parallel.search import RequestOperands


_PALLAS_STEP_SIGNATURE = inspect.signature(pallas_mesh_persistent_step)


def pallas(**kwargs):
    """The ``pallas`` backend, its kernel in interpret mode."""
    return get_backend("pallas", interpret=True, **kwargs)


def _kernel_plan(monkeypatch, model_name, interpret, target_chunks,
                 segments):
    """What the step builder every pallas backend serves through
    (``pallas_mesh_persistent_factory``, one device) hands the chained
    kernel step for one width-1 dispatch of the full partition: the
    step's arguments by name, and the builder's ``(chunks_each,
    chunks_per_step)``."""
    import distpow_tpu.parallel.mesh_search as mesh_search

    sig = _PALLAS_STEP_SIGNATURE
    seen = {}

    def spy(*args):
        seen.update(sig.bind(*args).arguments)
        return lambda *a: None

    monkeypatch.setattr(mesh_search, "pallas_mesh_persistent_step", spy)
    builder = mesh_search.pallas_mesh_persistent_factory(
        RequestOperands(b"\x01", 8, 0, 256, get_hash_model(model_name)),
        mesh_search.make_mesh(jax.devices()[:1]), interpret=interpret)
    _, chunks_each, chunks_per_step = builder(1, b"", target_chunks,
                                              segments)
    return seen, chunks_each, chunks_per_step


def test_pallas_matches_xla_step():
    nonce = b"\x01\x02\x03\x04"
    step_p = build_pallas_search_step(nonce, 1, 2, 0, 256, 128, interpret=True)
    step_x = build_search_step(nonce, 1, 2, 0, 256, 128, MD5)
    for c0 in (1, 129, 200):
        assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0)))


def test_pallas_width2_and_subpartition():
    nonce = b"\x05\x06"
    # 64-thread-byte shard (4-worker partition), width 2
    step_p = build_pallas_search_step(
        nonce, 2, 2, 64, 64, 512, sublanes=8, interpret=True
    )
    step_x = build_search_step(nonce, 2, 2, 64, 64, 512, MD5)
    for c0 in (256, 256 + 512):
        assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0)))


def test_pallas_no_hit_returns_sentinel():
    step = build_pallas_search_step(b"\x07", 1, 30, 0, 256, 128, interpret=True)
    assert int(step(jnp.uint32(1))) == SENTINEL


def test_pallas_rejects_unsupported_configs():
    with pytest.raises(ValueError, match="power-of-two"):
        build_pallas_search_step(b"\x01", 1, 2, 0, 96, 128, interpret=True)
    with pytest.raises(ValueError, match="single-block"):
        build_pallas_search_step(bytes(60), 4, 2, 0, 256, 128, interpret=True)


def test_default_geometry_resolution_at_every_site(monkeypatch):
    """The interpret-mode sublanes cap must hold at every resolution
    site (ops/md5_pallas.py default_geometry): serving gets the swept
    MODEL_GEOMETRY entry, interpret mode is capped at 8 (the serving
    geometry's interpret compile is pathological on XLA:CPU), and an
    explicit override always wins."""
    from distpow_tpu.ops.md5_pallas import MODEL_GEOMETRY, default_geometry

    assert default_geometry("sha256") == MODEL_GEOMETRY["sha256"]
    assert default_geometry("sha256", interpret=True)[0] == 8
    assert default_geometry("md5", interpret=True)[0] == 8
    # ...and so does the step builder both pallas backends serve
    # through (the pallas mesh step factory)
    for interpret, sublanes in ((False, MODEL_GEOMETRY["sha256"][0]),
                                (True, 8)):
        seen, _, _ = _kernel_plan(monkeypatch, "sha256", interpret, 1024, 1)
        assert seen["sublanes"] == sublanes and seen["interpret"] is interpret


def test_pallas_backend_end_to_end():
    backend = pallas(batch_size=1 << 15)
    nonce = b"\x0a\x0b\x0c"
    tbs = list(range(256))
    secret = backend.search(nonce, 2, tbs)
    assert secret is not None
    assert secret == puzzle.python_search(nonce, 2, tbs)


def test_pallas_backend_serves_sha1_with_kernel():
    # sha1 has a _TILE_FNS entry since round 3 — served by the kernel,
    # reference enumeration order
    backend = pallas(hash_model="sha1", batch_size=1 << 14)
    nonce = b"\x11\x22"
    secret = backend.search(nonce, 2, list(range(256)))
    assert secret == puzzle.python_search(nonce, 2, list(range(256)),
                                          algo="sha1")


def test_pallas_backend_serves_ripemd160_with_kernel():
    # fourth model (round 4): the two-line tile serves through the
    # kernel path in reference enumeration order
    backend = pallas(hash_model="ripemd160", batch_size=1 << 14)
    nonce = b"\x33\x44"
    secret = backend.search(nonce, 2, list(range(256)))
    assert secret == puzzle.python_search(nonce, 2, list(range(256)),
                                          algo="ripemd160")


def test_pallas_backend_falls_back_for_sha512():
    # sha512 HAS a kernel tile since round 4, but it is TPU-only
    # (INTERPRET_XLA_FALLBACK: the interpret-mode XLA:CPU compile of
    # the unrolled limb-pair graph is pathological) — under
    # interpret=True the backend must still serve through the
    # transparent XLA fallback
    backend = pallas(hash_model="sha512", batch_size=1 << 13)
    nonce = b"\x55\x66"
    secret = backend.search(nonce, 2, list(range(256)))
    assert secret == puzzle.python_search(nonce, 2, list(range(256)),
                                          algo="sha512")


def test_pallas_backend_falls_back_for_model_without_kernel(monkeypatch):
    # a registry model WITHOUT a kernel entry -> transparent XLA
    # fallback, counted (every registry model has a kernel, so the
    # branch is exercised by deleting one)
    from distpow_tpu.ops import md5_pallas
    from distpow_tpu.runtime.metrics import REGISTRY

    monkeypatch.delitem(md5_pallas._TILE_FNS, "sha1")
    backend = pallas(hash_model="sha1", batch_size=1 << 14)
    before = REGISTRY.get("fallback.pallas_mesh_step")
    nonce = b"\x33\x44"
    secret = backend.search(nonce, 2, list(range(256)))
    assert secret == puzzle.python_search(nonce, 2, list(range(256)),
                                          algo="sha1")
    assert REGISTRY.get("fallback.pallas_mesh_step") > before


def test_pallas_backend_falls_back_for_long_nonce():
    # two-block tail -> transparent XLA fallback inside the builder
    backend = pallas(batch_size=1 << 14)
    nonce = bytes(range(60))
    secret = backend.search(nonce, 1, list(range(256)))
    assert secret is not None
    assert puzzle.check_secret(nonce, secret, 1)


def test_pallas_launch_steps_extends_grid():
    # k sub-batches in one dispatch == k sequential dispatches' minimum
    nonce = b"\x11\x12\x13"
    step_k = build_pallas_search_step(
        nonce, 1, 2, 0, 256, 4, sublanes=8, interpret=True, launch_steps=3
    )
    step_1 = build_search_step(nonce, 1, 2, 0, 256, 4, MD5)
    for c0 in (1, 64):
        got = int(step_k(jnp.uint32(c0)))
        best = SENTINEL
        for i in range(3):
            f = int(step_1(jnp.uint32(c0 + 4 * i)))
            if f != SENTINEL:
                best = min(best, f + i * 4 * 256)
        assert got == best


def test_pallas_launch_bound_enforced():
    with pytest.raises(ValueError, match="2\\^31"):
        build_pallas_search_step(
            b"\x01", 4, 2, 0, 256, 1 << 16, sublanes=8, interpret=True,
            launch_steps=1 << 8,
        )


def test_sha256_tile_matches_hashlib_all_buckets():
    """The DCE'd functional-form SHA-256 tile (ops/md5_pallas.py
    _sha256_tile) must reproduce hashlib's digest words for every
    mask-word bucket, with exactly the dead words elided.  Eager mode:
    the unrolled 64-round graph is too slow for XLA:CPU to compile per
    bucket, but op-by-op eager dispatch is instant."""
    import hashlib
    import struct

    from distpow_tpu.models.sha256_jax import SHA256_INIT
    from distpow_tpu.ops.md5_pallas import _sha256_tile

    msg = b"\x01\x02\x03\x04" + b"\x99\x11\x22\x33\x44"
    tail = (msg + b"\x80" + b"\x00" * (64 - len(msg) - 9)
            + struct.pack(">Q", len(msg) * 8))
    words = [jnp.uint32(w) for w in struct.unpack(">16I", tail)]
    init = [jnp.uint32(s) for s in SHA256_INIT]
    ref_words = struct.unpack(">8I", hashlib.sha256(msg).digest())
    for mw in range(1, 9):
        out = _sha256_tile(words, init, mw)
        for j in range(8):
            if j < 8 - mw:
                assert out[j] is None
            else:
                assert int(out[j]) == ref_words[j], (mw, j)


def test_sha256_tile_randomized_batch_words():
    """Property test: the tile function on BATCH-SHAPED message words
    (the kernel's real operand shape, exercising the non-scalar branch
    of the K+w fold) matches hashlib lane-for-lane across random
    messages and every DCE bucket."""
    import hashlib
    import random
    import struct

    import numpy as np

    from distpow_tpu.models.sha256_jax import SHA256_INIT
    from distpow_tpu.ops.md5_pallas import _sha256_tile

    rng = random.Random(42)
    LANES_N = 16
    msgs = [bytes(rng.randrange(256) for _ in range(rng.randrange(1, 56)))
            for _ in range(LANES_N)]
    # pad each to one block; words[j] becomes a (LANES_N,) array
    blocks = []
    for m in msgs:
        tail = (m + b"\x80" + b"\x00" * (64 - len(m) - 9)
                + struct.pack(">Q", len(m) * 8))
        blocks.append(struct.unpack(">16I", tail))
    words = [jnp.asarray(np.array([b[j] for b in blocks], np.uint32))
             for j in range(16)]
    init = [jnp.uint32(s) for s in SHA256_INIT]
    refs = [struct.unpack(">8I", hashlib.sha256(m).digest()) for m in msgs]
    for mw in (1, 3, 8):
        out = _sha256_tile(words, init, mw)
        for j in range(8 - mw, 8):
            got = np.asarray(out[j])
            for lane in range(LANES_N):
                assert int(got[lane]) == refs[lane][j], (mw, j, lane)


def _one_block_tail_512(msg: bytes) -> tuple:
    """Pad ``msg`` to one 128-byte SHA-512/384 block; 32 uint32 words."""
    import struct

    assert len(msg) <= 128 - 17
    tail = (msg + b"\x80" + b"\x00" * (128 - len(msg) - 17)
            + struct.pack(">QQ", 0, len(msg) * 8))
    return struct.unpack(">32I", tail)


def test_sha512_tile_matches_hashlib_all_buckets():
    """The limb-pair SHA-512 tile (ops/md5_pallas.py _sha512_tile) must
    reproduce hashlib's digest words for every mask-word bucket with
    exactly the dead words elided.  Eager mode, same rationale as the
    sha256 tile test — and doubly so here: the unrolled limb graph is
    the very thing interpret mode refuses to compile
    (INTERPRET_XLA_FALLBACK)."""
    import hashlib
    import struct

    from distpow_tpu.models.sha512_py import SHA512_INIT
    from distpow_tpu.ops.md5_pallas import _sha512_tile

    msg = b"\x01\x02\x03\x04" + b"\x99\x11\x22\x33\x44"
    words = [jnp.uint32(w) for w in _one_block_tail_512(msg)]
    init = [jnp.uint32(s) for s in SHA512_INIT]
    ref_words = struct.unpack(">16I", hashlib.sha512(msg).digest())
    for mw in range(1, 17):
        out = _sha512_tile(words, init, mw)
        for j in range(16):
            if out[j] is None:
                assert j < 16 - mw, (mw, j)
            else:
                assert int(out[j]) == ref_words[j], (mw, j)
        # every masked word must be present (the kernel consumes them)
        for j in range(16 - mw, 16):
            assert out[j] is not None, (mw, j)


def test_sha384_tile_matches_hashlib_all_buckets():
    """SHA-384 shares the compression; digest = first 12 uint32 words
    (6 of 8 64-bit state words) with its own init constants — the
    truncation must hold per bucket."""
    import hashlib
    import struct

    from distpow_tpu.models.sha384_jax import SHA384_INIT
    from distpow_tpu.ops.md5_pallas import _sha384_tile

    msg = b"\xaa\xbb\xcc" + bytes(range(40))
    words = [jnp.uint32(w) for w in _one_block_tail_512(msg)]
    init = [jnp.uint32(s) for s in SHA384_INIT]
    ref_words = struct.unpack(">12I", hashlib.sha384(msg).digest())
    for mw in (1, 2, 3, 7, 12):
        out = _sha384_tile(words, init, mw)
        for j in range(12):
            if out[j] is None:
                assert j < 12 - mw, (mw, j)
            else:
                assert int(out[j]) == ref_words[j], (mw, j)
        for j in range(12 - mw, 12):
            assert out[j] is not None, (mw, j)


def test_sha512_tile_randomized_batch_words():
    """Batch-shaped message words (the kernel's real operand shape)
    match hashlib lane-for-lane across random one-block messages."""
    import hashlib
    import random
    import struct

    import numpy as np

    from distpow_tpu.models.sha512_py import SHA512_INIT
    from distpow_tpu.ops.md5_pallas import _sha512_tile

    rng = random.Random(7)
    LANES_N = 8
    msgs = [bytes(rng.randrange(256) for _ in range(rng.randrange(1, 100)))
            for _ in range(LANES_N)]
    blocks = [_one_block_tail_512(m) for m in msgs]
    words = [jnp.asarray(np.array([b[j] for b in blocks], np.uint32))
             for j in range(32)]
    init = [jnp.uint32(s) for s in SHA512_INIT]
    refs = [struct.unpack(">16I", hashlib.sha512(m).digest()) for m in msgs]
    for mw in (1, 5, 16):
        out = _sha512_tile(words, init, mw)
        for j in range(16 - mw, 16):
            got = np.asarray(out[j])
            for lane in range(LANES_N):
                assert int(got[lane]) == refs[lane][j], (mw, j, lane)


def test_model_geometry_divides_serving_batches():
    """Every shipped MODEL_GEOMETRY tile must divide the power-of-two
    batches serving and the bench dispatch (2^21 and every smaller
    pow2 a backend would round to).  This class of mistake has now been
    caught twice in review — a sweep's absolute best at sublanes=24
    gives a 3072-candidate tile that the kernel builder rejects
    outright at bench shapes and that collapses the swept `inner` to
    unswept territory under the backend's tile rounding — so the
    constraint is pinned here, next to the data it guards."""
    from distpow_tpu.ops.md5_pallas import LANES, MODEL_GEOMETRY

    for mname, (sublanes, inner) in MODEL_GEOMETRY.items():
        tile = sublanes * LANES
        assert (1 << 21) % tile == 0, (
            f"{mname}: tile {tile} (sublanes={sublanes}) does not divide "
            f"the 2^21 serving batch — ship the best power-of-two-"
            f"compatible sweep point instead"
        )
        assert inner & (inner - 1) == 0, (
            f"{mname}: inner {inner} must be a power of two (the "
            f"inner-shrink loop halves it to fit tile counts)"
        )


def test_sha3_tile_matches_hashlib_all_buckets():
    """The unrolled keccak tile (round 4, seventh model — the sponge)
    must reproduce hashlib's digest words for every mask bucket, with
    the final-round chi DCE eliding exactly the dead words.  Eager
    mode, like every limb tile."""
    import hashlib
    import struct

    from distpow_tpu.models.sha3_py import SHA3_INIT
    from distpow_tpu.ops.md5_pallas import _sha3_tile

    msg = b"\x42\x24" + bytes(range(50))
    t = bytearray(136)
    t[: len(msg)] = msg
    t[len(msg)] ^= 0x06
    t[-1] ^= 0x80
    words = [jnp.uint32(w) for w in struct.unpack("<34I", bytes(t))]
    init = [jnp.uint32(s) for s in SHA3_INIT]
    ref_words = struct.unpack("<8I", hashlib.sha3_256(msg).digest())
    for mw in range(1, 9):
        out = _sha3_tile(words, init, mw)
        for j in range(8):
            if out[j] is None:
                assert j < 8 - mw, (mw, j)
            else:
                assert int(out[j]) == ref_words[j], (mw, j)
        for j in range(8 - mw, 8):
            assert out[j] is not None, (mw, j)


def test_sha3_tile_nonzero_absorbed_state():
    """A long nonce host-absorbs a full rate block: the tile's XOR
    absorb must continue from the NONZERO sponge state."""
    import hashlib
    import struct

    from distpow_tpu.models.sha3_py import py_absorb
    from distpow_tpu.ops.md5_pallas import _sha3_tile

    long_msg = bytes(range(170))
    st, rem, absorbed = py_absorb(long_msg)
    assert absorbed == 136
    t = bytearray(136)
    t[: len(rem)] = rem
    t[len(rem)] ^= 0x06
    t[-1] ^= 0x80
    words = [jnp.uint32(w) for w in struct.unpack("<34I", bytes(t))]
    init = [jnp.uint32(s) for s in st]
    ref_words = struct.unpack("<8I", hashlib.sha3_256(long_msg).digest())
    out = _sha3_tile(words, init, 8)
    for j in range(8):
        assert int(out[j]) == ref_words[j], j


def test_blake2b_tile_matches_hashlib_all_buckets():
    """The per-block-parameter tile (round 4, eighth model): the baked
    t/f limbs ride at the end of the 36-word template row, and the
    final-round diagonal DCE elides exactly the dead digest words."""
    import hashlib
    import struct

    from distpow_tpu.models.blake2b_py import BLAKE2B_INIT
    from distpow_tpu.ops.md5_pallas import _blake2b_tile

    msg = b"\x42\x24" + bytes(range(60))
    t = bytearray(128)
    t[: len(msg)] = msg
    words = list(struct.unpack("<32I", bytes(t)))
    words += [len(msg), 0, 0xFFFFFFFF, 0xFFFFFFFF]
    wj = [jnp.uint32(w) for w in words]
    init = [jnp.uint32(s) for s in BLAKE2B_INIT]
    ref = struct.unpack(
        "<8I", hashlib.blake2b(msg, digest_size=32).digest())
    for mw in range(1, 9):
        out = _blake2b_tile(wj, init, mw)
        for j in range(8):
            if out[j] is None:
                assert j < 8 - mw, (mw, j)
            else:
                assert int(out[j]) == ref[j], (mw, j)
        for j in range(8 - mw, 8):
            assert out[j] is not None, (mw, j)


def test_sha512_interpret_mode_falls_back():
    """Both kernel constructors — the single-device builder AND the
    mesh step factory the pallas backends serve through — must refuse
    the limb-pair tiles under interpret=True (ValueError = the
    transparent-fallback signal every caller maps to the XLA step)."""
    from distpow_tpu.ops.md5_pallas import build_pallas_search_step
    from distpow_tpu.parallel.mesh_search import (
        make_mesh,
        pallas_mesh_persistent_factory,
    )

    mesh = make_mesh(jax.devices())
    for mname in ("sha512", "sha384", "sha3_256", "blake2b_256"):
        with pytest.raises(ValueError, match="TPU-only"):
            build_pallas_search_step(
                b"\x01\x02", 1, 3, 0, 256, 8, mname,
                sublanes=8, interpret=True,
            )
        with pytest.raises(ValueError, match="TPU-only"):
            pallas_mesh_persistent_factory(
                RequestOperands(b"\x01\x02", 3, 0, 256,
                                get_hash_model(mname)), mesh,
                interpret=True,
            )


def test_sha256d_tile_matches_hashlib_all_buckets():
    """Composed double-sha256 tile (r5 ninth model): eager tile math
    vs hashlib's double digest at every mask-word bucket; the None-DCE
    contract holds on the SECOND stage's dead words while stage 1 runs
    full-width underneath."""
    import hashlib
    import struct

    import numpy as np

    from distpow_tpu.models.sha256_jax import SHA256_INIT
    from distpow_tpu.ops.md5_pallas import _sha256d_tile

    msgs = [bytes([i, (7 * i) & 0xFF, 3]) + b"abc" for i in range(8)]

    def block_words(m):
        block = (m + b"\x80" + bytes(64 - len(m) - 1 - 8)
                 + (8 * len(m)).to_bytes(8, "big"))
        return struct.unpack(">16I", block)

    cols = [
        jnp.asarray(np.array([block_words(m)[g] for m in msgs], np.uint32))
        for g in range(16)
    ]
    init = tuple(jnp.uint32(c) for c in SHA256_INIT)
    refs = [
        struct.unpack(
            ">8I", hashlib.sha256(hashlib.sha256(m).digest()).digest())
        for m in msgs
    ]
    for mw in (1, 2, 4, 5, 8):
        out = _sha256d_tile(cols, init, mw)
        for j in range(8):
            if j < 8 - mw:
                assert out[j] is None
            else:
                for i, r in enumerate(refs):
                    assert int(out[j][i]) == r[j], (mw, j, i)


def test_sha256d_interpret_falls_back():
    """Off-TPU the composed tile is kernel-unavailable by design (the
    doubled unrolled graph is pathological for XLA:CPU codegen): the
    builder refuses interpret mode and the backend transparently serves
    the fused XLA step instead."""
    with pytest.raises(ValueError, match="TPU-only"):
        build_pallas_search_step(
            b"\x01", 1, 2, 0, 256, 128, model_name="sha256d",
            interpret=True)
    b = pallas(hash_model="sha256d", batch_size=1 << 12)
    secret = b.search(b"\x05\x06\x07", 2, list(range(256)))
    assert secret is not None
    assert puzzle.check_secret(b"\x05\x06\x07", secret, 2, "sha256d")


def test_backend_batch_rounding_keeps_inner_for_24_sublane_tiles(monkeypatch):
    """Serving-side support for the sweep-best sublanes=24 geometries: a
    2^21 batch at tile 3072 is 683 tiles — prime — which would collapse
    the tuned inner to unswept territory.  The step builder the pallas
    backends serve through cuts a dispatch into segments of
    SEGMENT_GRID_STEPS x inner whole tiles, so the inner loop holds at
    serving size, chunk accounting stays exact, a dispatch overshoots
    its plan by under one segment, and a small dispatch grows by under
    one tile."""
    from distpow_tpu.ops import md5_pallas

    monkeypatch.setitem(md5_pallas.MODEL_GEOMETRY, "ripemd160", (24, 1024))
    tile = 24 * 128
    for target_chunks, segments, inner in (((1 << 21) // 256, 128, 1024),
                                           (1024, 1, None)):
        seen, chunks_each, chunks_per_step = _kernel_plan(
            monkeypatch, "ripemd160", False, target_chunks, segments)
        planned = target_chunks * 256 * segments
        seg = seen["seg_local"]
        assert seen["sublanes"] == 24 and seg % tile == 0
        assert seen["grid"] * seen["inner"] * tile == seg
        assert chunks_each * 256 == seg
        assert chunks_per_step == chunks_each * seen["segments"]
        assert 0 <= seen["segments"] * seg - planned < seg
        if inner is not None:
            assert seen["inner"] == inner, "inner would shrink"
        else:
            assert seg - planned < tile, f"small segment overgrown to {seg}"


@pytest.mark.veryslow
def test_sha256_pallas_kernel_matches_xla_step():
    """Full sha256 kernel in interpret mode (one compile ~80-160s on
    XLA:CPU — the single biggest test in the suite, so it carries the
    nightly-style ``veryslow`` marker, VERDICT r4 item 6; per-bucket
    hash correctness is covered by the eager tile test above, the
    scaffold by the md5 tests, and the compiled kernel by the hardware
    parity artifacts under docs/artifacts/).  Run with
    ``pytest -m veryslow`` before shipping kernel-scaffold changes.
    sublanes is pinned to 8: the serving default (16, MODEL_GEOMETRY)
    multiplies the interpret-mode compile severalfold, and tile
    correctness is geometry-independent."""
    from distpow_tpu.models.registry import SHA256

    nonce = b"\x01\x02\x03\x04"
    step_p = build_pallas_search_step(
        nonce, 1, 2, 0, 256, 8, model_name="sha256", sublanes=8,
        interpret=True
    )
    step_x = build_search_step(nonce, 1, 2, 0, 256, 8, SHA256)
    for c0 in (1, 17):
        assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0)))


def test_sha1_tile_matches_hashlib_all_buckets():
    """The SHA-1 tile's single-chain form and its seam handling (rounds
    0-4 draw from raw init words) must reproduce hashlib's digest words
    for every mask-word DCE bucket (1-5)."""
    import hashlib
    import struct

    import numpy as np

    from distpow_tpu.models.sha1_jax import SHA1_INIT
    from distpow_tpu.ops.md5_pallas import _sha1_tile

    rng = np.random.default_rng(11)
    SL, LN = 8, 16
    msgs = [rng.integers(0, 256, 9, dtype=np.uint8).tobytes()
            for _ in range(SL * LN)]
    words = []
    for g in range(16):
        arr = np.zeros((SL, LN), np.uint32)
        for i, m in enumerate(msgs):
            blk = bytearray(64)
            blk[:9] = m
            blk[9] = 0x80
            blk[56:64] = (72).to_bytes(8, "big")
            arr[i // LN, i % LN] = struct.unpack(">16I", bytes(blk))[g]
        words.append(jnp.asarray(arr))
    init = [jnp.uint32(x) for x in SHA1_INIT]
    refs = [struct.unpack(">5I", hashlib.sha1(m).digest()) for m in msgs]
    for mw in range(1, 6):
        out = _sha1_tile(words, init, mw)
        assert sum(o is None for o in out) == 5 - mw
        for j, o in enumerate(out):
            if o is None:
                continue
            o = np.asarray(o)
            for i, r in enumerate(refs):
                assert int(o[i // LN, i % LN]) == r[j], (mw, j, i)


def test_sha1_pallas_kernel_matches_xla_step():
    """Full sha1 kernel in interpret mode vs the XLA step.  Unlike the
    sha256 tile (80-160s interpret compile), the single-chain form
    compiles in seconds, so this is not a slow test."""
    from distpow_tpu.models.registry import SHA1

    nonce = b"\x01\x02\x03\x04"
    step_p = build_pallas_search_step(
        nonce, 1, 2, 0, 256, 8, model_name="sha1", sublanes=8,
        interpret=True
    )
    step_x = build_search_step(nonce, 1, 2, 0, 256, 8, SHA1)
    for c0 in (1, 17):
        assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0)))


def test_ripemd160_pallas_kernel_matches_xla_step():
    """Full ripemd160 kernel in interpret mode vs the XLA step.  Both
    lines in the SHA-1-style functional form compile in seconds (no
    sha256-style schedule expansion), so this is not a slow test."""
    from distpow_tpu.models.registry import RIPEMD160

    nonce = b"\x01\x02\x03\x04"
    step_p = build_pallas_search_step(
        nonce, 1, 2, 0, 256, 8, model_name="ripemd160", sublanes=8,
        interpret=True
    )
    step_x = build_search_step(nonce, 1, 2, 0, 256, 8, RIPEMD160)
    for c0 in (1, 17):
        assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0)))


@pytest.mark.slow
def test_pallas_mesh_matches_jax_mesh_all_partitions():
    """pallas-mesh must be bit-identical to jax-mesh in both sharding
    regimes (tb-split, chunk-split) and on sub-partitions — both return
    the minimal TRUE global flat index, so the decoded secrets match
    exactly (parallel/mesh_search.py pallas_mesh_persistent_step)."""
    from distpow_tpu.backends import JaxMeshBackend, PallasMeshBackend

    b = PallasMeshBackend(batch_size=1 << 14, interpret=True)
    ref = JaxMeshBackend(batch_size=1 << 14)
    for tbs in (list(range(256)),        # tb-split
                list(range(64, 128)),    # tb-split, sub-partition
                list(range(4))):         # chunk-split (tbc < n_dev)
        got = b.search(b"\x01\x02\x03", 2, tbs)
        want = ref.search(b"\x01\x02\x03", 2, tbs)
        assert got == want
        assert puzzle.check_secret(b"\x01\x02\x03", got, 2)


def test_pallas_mesh_falls_back_for_long_nonce():
    from distpow_tpu.backends import PallasMeshBackend

    b = PallasMeshBackend(batch_size=1 << 13, interpret=True)
    nonce = bytes(range(60))  # two-block tail -> XLA mesh fallback
    secret = b.search(nonce, 1, list(range(256)))
    assert secret is not None
    assert puzzle.check_secret(nonce, secret, 1)


def test_pallas_mesh_warmup_covers_serving_compile_keys():
    """After boot warmup, serving any pow2 partition in either sharding
    regime must not build a new chained mesh-kernel program (the same
    layout-keyed discipline the XLA mesh path proves in test_search.py),
    and a solve of a partition warmup stands for compiles nothing.
    Every solve here ends in width 1, so width 2's first dispatch is in
    flight behind it: warmup covers one width past the widest solve."""
    import jax

    from distpow_tpu.backends import PallasMeshBackend
    from distpow_tpu.parallel.mesh_search import pallas_mesh_persistent_step

    b = PallasMeshBackend(batch_size=1 << 14, interpret=True)
    b.warmup([3], [0, 1, 2])
    n_dev = len(jax.devices())
    misses = pallas_mesh_persistent_step.cache_info().misses
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # the partitions warmup represents: tb-split, chunk-split
        for tbs in (list(range(256)), list(range(n_dev // 2))):
            assert b.search(b"\x07\x08\x09", 2, tbs) is not None
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    for tbs in (list(range(128, 192)), [4, 5]):
        assert b.search(b"\x07\x08\x09", 2, tbs) is not None
    assert pallas_mesh_persistent_step.cache_info().misses == misses


@pytest.mark.slow
def test_pallas_mask_word_buckets_match_xla():
    # difficulties spanning all four trailing-word buckets exercise the
    # skipped-final-rounds DCE (mw=1 skips rounds 62-63, mw=2 skips 63)
    nonce = b"\x21\x22\x23"
    for d in (1, 2, 8, 9, 16, 17, 25):
        step_p = build_pallas_search_step(
            nonce, 2, d, 0, 256, 64, sublanes=8, interpret=True, inner=4
        )
        step_x = build_search_step(nonce, 2, d, 0, 256, 64, MD5)
        for c0 in (256, 4096):
            assert int(step_p(jnp.uint32(c0))) == int(step_x(jnp.uint32(c0))), \
                f"divergence at difficulty {d} chunk0 {c0}"


# -- launch-geometry k selection (pure CPU math; ISSUE 8 satellite) ----------

class TestPlanLaunchGeometry:
    """Unit tests for the extracted k-selection logic — in particular
    the advisor-r5 pow2-k fix: the power-of-two rounding only COMMITS
    together with a batch that makes the inner loop effective, and the
    original k is kept otherwise."""

    def test_pow2_tile_is_untouched(self):
        from distpow_tpu.sched.lanes import plan_launch_geometry

        # tile 8x128=1024 is a power of two: the inner-loop fixup never
        # runs and k keeps the driver's requested (odd) multiplier
        batch, chunks, k = plan_launch_geometry(
            2048, 256, 1024, 4, 5, 1 << 24)
        assert (batch, chunks, k) == (2048 * 256, 2048, 5)

    def test_batch_rounds_up_to_whole_tiles(self):
        from distpow_tpu.sched.lanes import plan_launch_geometry

        # 2^21 candidates at a 24-sublane (3072) tile: 682.67 tiles
        # rounds up to a whole grid and chunks re-derive from it
        batch, chunks, k = plan_launch_geometry(
            8192, 256, 3072, 1, 1, 1 << 26)
        assert batch % 3072 == 0
        assert batch >= 8192 * 256
        assert chunks == batch // 256

    def test_pow2_rounding_commits_with_marginal_growth(self):
        from distpow_tpu.sched.lanes import plan_launch_geometry

        # the sweep-best serving shape: 683 (prime) tiles, inner=4 —
        # a whole-tile growth of ~0.15% makes the pow2 k effective
        batch, chunks, k = plan_launch_geometry(
            8192, 256, 3072, 4, 5, 1 << 26)
        assert k & (k - 1) == 0, f"k={k} not a power of two"
        assert batch % 3072 == 0 and batch % 256 == 0
        assert batch <= 8192 * 256 * 1.03  # growth stayed marginal

    def test_growth_rejected_keeps_original_k(self):
        from distpow_tpu.sched.lanes import plan_launch_geometry

        # 53 tiles at tbc=160: the next inner-compatible whole-tile
        # batch is not tbc-aligned within the <=2% cap, so the growth
        # conditions FAIL — the original k=3 must survive (the advisor
        # r5 regression: unconditional rounding silently halved it)
        batch, chunks, k = plan_launch_geometry(
            1000, 160, 3072, 4, 3, 1 << 22)
        assert k == 3
        assert batch == 53 * 3072  # and the batch stayed unrounded

    def test_budget_clamp_holds_through_every_path(self):
        from distpow_tpu.sched.lanes import plan_launch_geometry

        for target_chunks in (1000, 2000, 8192):
            for tbc in (96, 160, 256):
                for launch_steps in (1, 3, 5, 8):
                    for max_launch in (1 << 22, 1 << 24):
                        batch, chunks, k = plan_launch_geometry(
                            target_chunks, tbc, 3072, 4, launch_steps,
                            max_launch)
                        assert batch * k <= max_launch, (
                            target_chunks, tbc, launch_steps, max_launch,
                            batch, k)
                        assert k >= 1 and chunks >= 1
