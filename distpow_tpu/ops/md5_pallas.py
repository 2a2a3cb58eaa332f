"""Pallas TPU kernels for the fused proof-of-work search step — every
registry hash model has a tile (``_TILE_FNS``): MD5, SHA-256, SHA-1,
RIPEMD-160, SHA-512, SHA-384, SHA3-256, BLAKE2b-256.

The hot op of the framework (SURVEY.md section 7 layer 4, the "north
star"): one kernel launch evaluates a dense tile grid of candidates —
index -> message words -> 64 MD5 rounds -> trailing-nibble mask -> argmin
— entirely in VMEM/registers.  Only scalars enter the kernel (the chunk
base, the nonce's packed constant words, the absorbed init state, the
difficulty masks, and the partition descriptor — all in SMEM) and one
uint32 per grid tile leaves it; candidate messages are never materialized
anywhere, not even in HBM.

Compilation is *layout-keyed*: the kernel program depends only on the
tail-byte layout (where the thread byte and chunk bytes land in the
16-word block — a function of nonce length mod 64 and chunk width) and
the batch geometry.  The nonce content, difficulty, and thread-byte
partition are runtime SMEM operands, so a worker compiles each layout
once and serves every subsequent request at any difficulty/partition with
zero recompiles (mirroring ops/search_step.py's dynamic regime).

Layout: each grid step processes a (SUBLANES, 128) tile of flat candidate
indices (uint32 native tile is (8, 128); SUBLANES is a multiple of 8).
The flat index decomposes as ``f = chunk_offset * tb_count + tb_index``
with ``tb_count`` a power of two (the partition algebra only produces
power-of-two runs, worker.go:312-316), so the decomposition is a shift
and a mask — no integer division in the kernel.

The same computation expressed in plain jnp (ops/search_step.py) leaves
fusion decisions to XLA; this kernel pins them.  Both paths share the
packing template and difficulty masks, and tests/test_pallas.py checks
them equal in interpret mode; bench.py compares them on hardware.

SHA-256 shares the whole scaffold (grid, SMEM operands, index
decomposition, min accumulation) with a different tile function and tile
geometry.  Unlike MD5, where the kernel only matched XLA, SHA-256 is
where explicit geometry should PAY: the unrolled XLA step compiles to
one loop fusion but runs at ~77% of the measured VPU roofline
(BENCH round 3) — consistent with register spills from the ~24-value
live set (16-word schedule window + 8 working vars).  And it does pay:
the round-3 hardware sweep measured the kernel at 1.3x the XLA serving
step, ~99% of the measured roofline, at sublanes=16 (see
MODEL_GEOMETRY; the one-vreg-per-live-value sublanes=8 guess lost to
per-tile fixed cost).  The tile
function uses the functional A/E form (a_r/e_r sequences instead of the
8-var shuffle), which makes the difficulty-bucket dead-code elimination
exact: digest word j reads A[63-j] (j<4) or E[67-j] (j>=4), so for the
dominant mask_words=1 bucket the A-chain stops at round 56, the E-chain
at 60, and schedule words 61-63 are never formed.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.md5_jax import MD5_K, MD5_S
from ..models.registry import get_hash_model
from ..models.ripemd160_py import _f as rmd_f
from ..models.ripemd160_py import _KL as RMD_KL
from ..models.ripemd160_py import _KR as RMD_KR
from ..models.ripemd160_py import _RL as RMD_RL
from ..models.ripemd160_py import _RR as RMD_RR
from ..models.ripemd160_py import _SL as RMD_SL
from ..models.ripemd160_py import _SR as RMD_SR
from ..models.sha1_jax import SHA1_K
from ..models.sha256_jax import SHA256_INIT, SHA256_K
from .difficulty import nibble_masks
from .packing import build_tail_spec
from .search_step import SENTINEL, _check_launch, mask_words_for, named_jit

LANES = 128
# Per-model (sublanes, inner) tile geometry — see module docstring.
# MD5: (64, 128) tile x 512 inner fori_loop iterations per grid step:
# the tile height bounds live registers through the unrolled round chain
# (taller tiles spill — 256 sublanes measured ~25% slower), the inner
# loop amortizes per-grid-step fixed cost (TPU v5e sweep, earlier reading:
# ~10.0 GH/s at (64, 512) vs 2.34 GH/s for round 1's flat (256,) grid;
# inner auto-shrinks to divide smaller launches).  SHA-256: the round-3
# hardware sweep (scripts/sweep_sha256_pallas.py, TPU v5e) measured
# Hardware-swept geometries (earlier chip sweeps, TPU v5e,
# 2026-07-31): sha256 (32, 256) at 2025.5 MH/s = 1.35x its XLA serving
# step (the round-3 sweep's (16, 1024) measured 1954; sublanes=32 edged
# it out on the re-sweep); sha1 (32, 2048) at 4335.1 MH/s = 2.28x XLA
# (the old by-analogy (16, 1024) entry measured 3851 — the sweep bought
# +12.5%).  At sublanes=8 the per-tile fixed cost (iota, hit
# accumulation) is amortized over half as many candidates and dominates.
# ripemd160 (32, 512) measured 2840.5 MH/s = 2.25x its XLA serving
# step (r4c sweep).  The sweep's absolute best was (24, 2048) at
# 2895.4, but sublanes=24 gives tile = 24*128 = 3072, which does not
# divide the power-of-two batches serving and the bench dispatch —
# build_pallas_search_step would reject the bench batch outright and
# serving's tile-rounding would leave a prime tile count that the
# inner-shrink loop collapses to unswept territory (review r4) — so the
# best power-of-two-compatible point ships (2% below the sweep max).
# (The r4b bench's 69 MH/s ripemd160-pallas line was a transient
# degraded reading, not the tile: the r4c sweep re-measured the same
# (16, 1024) geometry at 2421 MH/s minutes later, and the degradation
# window also swallowed the sha512 compile right after.)
# sha512 (32, 256) measured 538.9 MH/s = 43.5x the XLA serving step's
# 12.4 MH/s (r4c sweep — the sweep max (24, 256) at 544.7 is again not
# power-of-two-compatible); the geometry surface is nearly flat
# (498-545 across the whole sweep), consistent with Mosaic keeping the
# limb live-set in VMEM at every height.  sha384 shares the tile and
# the geometry (two extra live rounds from its truncation, same
# structure).
MODEL_GEOMETRY = {"md5": (64, 512), "sha256": (32, 256),
                  "sha1": (32, 2048), "ripemd160": (32, 512),
                  "sha512": (32, 256), "sha384": (32, 256),
                  # keccak's ~100-limb live set is the largest of the
                  # tiles and prefers the SHORTEST full-vreg tile:
                  # (8, 2048) measured 560.7 MH/s, monotonically
                  # falling to 425 at sublanes=32 (r4c sweep);
                  # BELOW a vreg's 8-sublane
                  # height the lanes go half-used — sublanes=4 measured
                  # 285, sublanes=2 144 (r4 probe)
                  "sha3_256": (8, 2048),
                  # blake2b (32, 128) measured 974.9 MH/s = 61x the XLA
                  # loop step's 16.0 (r4c sweep; the absolute best
                  # (24, 1024) at 977.4 is again not pow2-compatible).
                  # Unlike keccak it prefers TALLER tiles — the v
                  # working set is half the sponge state's
                  "blake2b_256": (32, 128),
                  # composed double-sha256 (r5 ninth model): starts on
                  # sha256's swept geometry — the live set is one
                  # sha256 chain at a time (stage 2 starts after stage
                  # 1's digest collapses to 8 words), so the same
                  # height should hold; not swept on a chip yet
                  "sha256d": (32, 256)}
# Grid steps in one segment of the chained pallas-mesh step
# (parallel/mesh_search.py pallas_mesh_persistent_step), so a segment is
# SEGMENT_GRID_STEPS * sublanes * LANES * inner candidates a chip: 2^24
# at md5's (64, 512), ~1.6 ms at the kernel's ~10.5 GH/s a chip.  Each
# segment ends in one cross-chip pmin (3-5 us on a v5e 2x2) and a loop
# check, so the overhead stays near 1%, while a solve runs on average
# half a segment past its hit.
SEGMENT_GRID_STEPS = 4
_I32_MISS = 0x7FFFFFFF  # in-kernel miss marker (int32 reduction domain)

# Models whose tile only serves on REAL TPU hardware: interpret mode
# (the off-TPU dev knob) would hand the fully-unrolled 64-bit limb-pair
# graph to XLA:CPU, whose compile on that shape is pathological (>5 min
# vs seconds for everything else).  build_pallas_search_step raises
# ValueError for these under interpret=True and callers fall back to
# the fused XLA step, exactly like a model with no tile at all.
INTERPRET_XLA_FALLBACK = frozenset(
    {"sha512", "sha384", "sha3_256", "blake2b_256",
     # the composed tile doubles sha256's unrolled graph — whose
     # single copy already does not terminate in XLA:CPU codegen at
     # serving height (models/sha256_jax.py platform note)
     "sha256d"})


def default_geometry(model_name: str, interpret: bool = False):
    """Resolve the (sublanes, inner) geometry for a kernel launch.

    Serving uses the model's hardware-swept MODEL_GEOMETRY entry (models
    without one get md5's; the kernel builder rejects unimplemented
    models before geometry matters).  Interpret mode — the off-TPU dev
    knob — caps sublanes at 8: kernel semantics are geometry-
    independent, and XLA:CPU's codegen on the interpreted sha256 tile
    is superlinear in tile height (the (16, 128) serving geometry
    compiles for ~20 min where (8, 128) takes ~3).  Every sublane
    resolution site (the builder, the pallas mesh step factory, the
    scheduler's pallas lane) goes through here so the cap cannot be
    bypassed by a caller resolving geometry itself.
    """
    geom = MODEL_GEOMETRY.get(model_name, MODEL_GEOMETRY["md5"])
    return (min(geom[0], 8), geom[1]) if interpret else geom


def _rotl(x, s: int):
    return (x << s) | (x >> (32 - s))


def _round_key(k: int, m):
    """``K[i] + w[i]`` as one grouped addend, shared by every tile.

    For a CONSTANT message word (python int or 0-d scalar) the round
    constant folds into it on the scalar unit — one scalar-vector add
    in the consuming expression instead of two (XLA's static regime
    gets this from compile-time constant folding; Mosaic cannot, so
    the fold happens here).  For a batch word the grouping is
    op-count-neutral (uint32 wraparound adds are associative), so the
    call sites need no branch."""
    if hasattr(m, "ndim") and m.ndim == 0 or not hasattr(m, "dtype"):
        return jnp.uint32(k) + jnp.uint32(m)
    return jnp.uint32(k) + m


def _md5_tile(words, init, mask_words: int = 4):
    """Unrolled 64-round MD5 on a tile; ``words[g]`` is an array or scalar.

    ``mask_words`` is how many TRAILING digest words the difficulty check
    reads (ops/search_step.py mask_words_for).  Trailing zero nibbles
    live in the last digest words, so for low difficulties only ``d``
    (and then ``c``, ...) matter; the rotation schedule means the last
    rounds' expensive f/rotl chains feed only the leading digest words —
    final ``b`` is produced by round 63, ``c`` by 62, ``a`` by 61 via the
    ``a,d,c = d,c,b`` shuffle — so those rounds are skipped entirely when
    their outputs are dead.  This is the same dead-code elimination XLA
    performs on the fused step (where the unused digest words are simply
    never consumed); Mosaic cannot see through the runtime mask operands,
    so the bucket is a compile key here too.
    """
    a0, b0, c0, d0 = init
    a, b, c, d = a0, b0, c0, d0
    # final digest word <- round whose new-b produces it: b <- 63,
    # c <- 62, d <- 61, a <- 60.  Keeping the last mask_words digest
    # words therefore needs rounds through 61 (mw=1), 62 (mw=2), or all
    # 64 (mw>=3, since final b is round 63's output).
    mw = max(1, min(4, mask_words))
    last_round = 64 - max(0, 3 - mw)
    for i in range(last_round):
        if i < 16:
            f = (b & c) | (~b & d)
            g = i
        elif i < 32:
            f = (d & b) | (~d & c)
            g = (5 * i + 1) % 16
        elif i < 48:
            f = b ^ c ^ d
            g = (3 * i + 5) % 16
        else:
            f = c ^ (b | ~d)
            g = (7 * i) % 16
        f = f + a + _round_key(MD5_K[i], words[g])
        a, d, c = d, c, b
        b = b + _rotl(f, MD5_S[i])
    # un-shuffle the skipped rounds: after round r the registers hold the
    # values that WOULD rotate into place; digest word j (a=0,b=1,c=2,d=3)
    # is live iff j >= 4 - mask_words
    regs = [a, b, c, d]
    for _ in range(64 - last_round):
        # each skipped round performs a,d,c = d,c,b with a new b nobody
        # alive consumes; inverse-rotate the register file instead
        regs = [regs[3], None, regs[1], regs[2]]
    out = []
    for j, (r, r0) in enumerate(zip(regs, (a0, b0, c0, d0))):
        out.append(None if j < 4 - mw else r0 + r)
    return tuple(out)


def _rotr(x, s: int):
    return (x >> s) | (x << (32 - s))


def _sha256_tile(words, init, mask_words: int = 8):
    """DCE'd SHA-256 compression on a tile; ``words[g]`` array or scalar.

    Functional A/E form: with ``A[r]``/``E[r]`` the new ``a``/``e`` after
    round ``r`` (and ``A[-1..-4] = a0..d0``, ``E[-1..-4] = e0..h0``), one
    round is

        t1   = E[r-4] + S1(E[r-1]) + Ch(E[r-1..r-3]) + (K[r] + w[r])
        E[r] = A[r-4] + t1
        A[r] = t1 + S0(A[r-1]) + Maj(A[r-1..r-3])

    and digest word j is ``init[j] + A[63-j]`` (j < 4) or
    ``init[j] + E[67-j]`` (j >= 4).  ``mask_words`` trailing digest words
    are live (ops/search_step.py mask_words_for), so the chains stop at

        maxE = 59 + min(mask_words, 4)      (t1/E needed through there)
        maxA = maxE - 4, or 59 + (mask_words - 4) when mask_words > 4

    — for the dominant difficulty <= 8-nibble bucket that skips 3 full
    rounds, 7 A-side updates, and schedule words 61-63, the same pruning
    XLA's DCE applies to the fused step (2,909 vs 3,165 cost_analysis
    ops/hash).  Returns 8 entries, ``None`` where dead.
    """
    mw = max(1, min(8, mask_words))
    maxE = 59 + min(mw, 4)
    maxA = max(maxE - 4, 59 + (mw - 4) if mw > 4 else -1)

    w = list(words)
    for i in range(16, maxE + 1):
        w15, w7, w2 = w[i - 15], w[i - 7], w[i - 2]
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> 3)
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> 10)
        w.append(w[i - 16] + s0 + w7 + s1)

    A = {-4: init[3], -3: init[2], -2: init[1], -1: init[0]}
    E = {-4: init[7], -3: init[6], -2: init[5], -1: init[4]}
    for r in range(maxE + 1):
        e1, f1, g1, h1 = E[r - 1], E[r - 2], E[r - 3], E[r - 4]
        S1 = _rotr(e1, 6) ^ _rotr(e1, 11) ^ _rotr(e1, 25)
        ch = (e1 & f1) ^ (~e1 & g1)
        t1 = h1 + S1 + ch + _round_key(SHA256_K[r], w[r])
        E[r] = A[r - 4] + t1
        if r <= maxA:
            a1, b1, c1 = A[r - 1], A[r - 2], A[r - 3]
            S0 = _rotr(a1, 2) ^ _rotr(a1, 13) ^ _rotr(a1, 22)
            maj = (a1 & b1) ^ (a1 & c1) ^ (b1 & c1)
            A[r] = t1 + S0 + maj

    out = []
    for j in range(8):
        if j < 8 - mw:
            out.append(None)
        else:
            out.append(init[j] + (A[63 - j] if j < 4 else E[67 - j]))
    return tuple(out)


def _sha256d_tile(words, init, mask_words: int = 8):
    """Composed double-SHA-256 tile: sha256d(m) = sha256(sha256(m)).

    Stage 1 is the plain SHA-256 tile at FULL digest width (every word
    feeds stage 2, so no DCE there); stage 2 hashes the fixed-layout
    second block — digest words ‖ 0x80 marker ‖ zeros ‖ bit-length 256
    (models/sha256d_jax.py SECOND_BLOCK_TAIL_WORDS) — from the constant
    SHA-256 init, with the difficulty-bucket DCE applied to ITS trailing
    chains (mask_words_for semantics compose through unchanged).  The
    word byteorder is big-endian on both sides, so stage 1's digest
    words are stage 2's message words verbatim.
    """
    d = _sha256_tile(words, init, mask_words=8)
    # uint32-wrap the marker word: 0x80000000 as a bare python int
    # overflows int32 argument parsing in the schedule adds
    second = list(d) + [jnp.uint32(c)
                        for c in (0x80000000, 0, 0, 0, 0, 0, 0, 256)]
    init2 = tuple(jnp.uint32(c) for c in SHA256_INIT)
    return _sha256_tile(second, init2, mask_words=mask_words)


def _sha1_tile(words, init, mask_words: int = 5):
    """DCE'd SHA-1 compression on a tile; ``words[g]`` array or scalar.

    Functional single-chain form: with ``X[r]`` the new ``a`` after
    round ``r``, the other four working registers are just delayed,
    rotated copies of the chain — the round inputs are

        a = X[r-1],  b = X[r-2],  c = rotl(X[r-3], 30),
        d = rotl(X[r-4], 30),  e = rotl(X[r-5], 30)

    (with the raw init words standing in at the seam, rounds 0-4), so
    one round computes only

        X[r] = rotl(a, 5) + f(b, c, d) + e + (K[r//20] + w[r])

    and digest word j is ``init[j] + X[79-j]`` for j < 2 or
    ``init[j] + rotl(X[79-j], 30)`` for j >= 2.  ``mask_words``
    trailing digest words are live (ops/search_step.py mask_words_for),
    so the chain stops at round ``74 + mask_words`` — the dominant
    difficulty <= 8-nibble bucket (mw=1) skips 4 rounds and schedule
    words 76-79.  Returns 5 entries, ``None`` where dead.
    """
    mw = max(1, min(5, mask_words))
    last = 74 + mw  # highest X index needed

    w = list(words)
    for i in range(16, last + 1):
        w.append(_rotl(w[i - 3] ^ w[i - 8] ^ w[i - 14] ^ w[i - 16], 1))

    a0, b0, c0, d0, e0 = init
    # seam: rounds 0-4 draw some inputs from the raw init words, which
    # are NOT rotl-related to the chain.  Unrolling the first rounds by
    # hand shows c/d/e all follow the same rule: raw X[idx] for
    # idx <= -3 (c0/d0/e0 are already in final orientation), rotl for
    # idx >= -2 (a0/b0 enter the c/d/e positions via the b->c rotation).
    X = {-1: a0, -2: b0, -3: c0, -4: d0, -5: e0}

    def rot_in(idx):
        return X[idx] if idx <= -3 else _rotl(X[idx], 30)

    for r in range(last + 1):
        a = X[r - 1]
        b = X[r - 2]
        c = rot_in(r - 3)
        d = rot_in(r - 4)
        e = rot_in(r - 5)
        if r < 20:
            f = (b & c) | (~b & d)
        elif r < 40:
            f = b ^ c ^ d
        elif r < 60:
            f = (b & c) | (b & d) | (c & d)
        else:
            f = b ^ c ^ d
        X[r] = _rotl(a, 5) + f + e + _round_key(SHA1_K[r // 20], w[r])

    out = []
    for j in range(5):
        if j < 5 - mw:
            out.append(None)
        else:
            x = X[79 - j]
            out.append(init[j] + (x if j < 2 else _rotl(x, 30)))
    return tuple(out)


def _ripemd160_tile(words, init, mask_words: int = 5):
    """DCE'd RIPEMD-160 compression on a tile (round 4, fourth model).

    Two independent 80-round lines over the same 16 message words, each
    in the SHA-1 functional single-chain form: with ``X[r]`` the value
    written to ``b`` in round ``r`` of a line, the round inputs are

        b = X[r-1],  c = X[r-2],  d = rotl(X[r-3], 10),
        e = rotl(X[r-4], 10),  a = rotl(X[r-5], 10)

    so one round is ``X[r] = rotl(a + f + (K[r//16] + w[R[r]]), S[r])
    + e``.  Seam rule (unrolling rounds 0-4 against the register form):
    chain indices <= -3 enter RAW (d0/e0/a0 are already in final
    orientation), indices >= -2 rotate — identical in shape to the
    SHA-1 tile's seam, with rotl 10 instead of 30.  The two lines are
    explicit ILP: Mosaic can interleave them with no dependence, which
    the single-chain MD5/SHA tiles cannot offer.

    Final combine (spec): digest word j draws on late chain values of
    BOTH lines (e.g. word 3 needs XR[79]), so mask-word DCE saves at
    most one trailing round per line — computed per line from the live
    words rather than assumed.  Returns 5 entries, ``None`` where dead.
    """
    mw = max(1, min(5, mask_words))
    # per digest word j: (left chain index, right chain index) consumed
    need = ((78, 77), (77, 76), (76, 75), (75, 79), (79, 78))
    live = range(5 - mw, 5)
    last_l = max(need[j][0] for j in live)
    last_r = max(need[j][1] for j in live)

    a0, b0, c0, d0, e0 = init

    def line(K, R, S, reverse_f: bool, last: int):
        X = {-1: b0, -2: c0, -3: d0, -4: e0, -5: a0}

        def rot_in(idx):
            return X[idx] if idx <= -3 else _rotl(X[idx], 10)

        for r in range(last + 1):
            b = X[r - 1]
            c = X[r - 2]
            d = rot_in(r - 3)
            e = rot_in(r - 4)
            a = rot_in(r - 5)
            fj = rmd_f(79 - r if reverse_f else r, b, c, d)
            X[r] = _rotl(a + fj + _round_key(K[r // 16], words[R[r]]),
                         S[r]) + e
        return X

    XL = line(RMD_KL, RMD_RL, RMD_SL, False, last_l)
    XR = line(RMD_KR, RMD_RR, RMD_SR, True, last_r)

    # combine: final registers are bl=XL[79], cl=XL[78],
    # dl=rotl(XL[77],10), el=rotl(XL[76],10), al=rotl(XL[75],10) (and
    # the same pattern on the right); h' per the spec's cross-line sum
    h0, h1, h2, h3, h4 = (jnp.uint32(s) for s in init)
    combine = (
        lambda: h1 + XL[78] + _rotl(XR[77], 10),
        lambda: h2 + _rotl(XL[77], 10) + _rotl(XR[76], 10),
        lambda: h3 + _rotl(XL[76], 10) + _rotl(XR[75], 10),
        lambda: h4 + _rotl(XL[75], 10) + XR[79],
        lambda: h0 + XL[79] + XR[78],
    )
    return tuple(combine[j]() if j >= 5 - mw else None for j in range(5))


def _sha512_tile_impl(words, init, mask_words: int, digest_words32: int):
    """DCE'd SHA-512/384 compression on a tile, in uint32 limb pairs.

    The same functional A/E form as ``_sha256_tile`` stretched to 80
    rounds, with every 64-bit quantity carried as a (hi, lo) pair of
    uint32 values (TPU VPUs have no uint64 lanes) using the limb algebra
    from ``models/sha512_jax.py`` — one round is

        t1   = E[r-4] + S1(E[r-1]) + Ch(E[r-1..r-3]) + (K[r] + w[r])
        E[r] = A[r-4] + t1
        A[r] = t1 + S0(A[r-1]) + Maj(A[r-1..r-3])

    with 64-bit digest word j = ``init64[j] + A[79-j]`` (j < 4) or
    ``init64[j] + E[83-j]`` (j >= 4), serialized hi-limb-first into the
    uint32 digest vector.  ``mask_words`` counts trailing *uint32*
    digest words (the shared mask_words_for bucket): the dominant
    difficulty <= 8-nibble bucket keeps only the LOW limb of the last
    64-bit word, so the chains stop at E[76] — three full rounds, every
    A-side update past 72, and schedule words 77-79 are skipped.

    ``words`` is ``2 * words_per_block`` uint32 entries (big-endian
    64-bit message words, hi limb first — exactly the packing
    template's serialization); ``init`` is 16 uint32 entries (8 pairs);
    ``digest_words32`` is 16 (sha512) or 12 (sha384: same state, first
    6 of 8 64-bit words emitted).  Returns ``digest_words32`` entries,
    ``None`` where dead.
    """
    from ..models.sha512_jax import (
        _add64, _add64_many, _k_pair, _rotr64, _shr64, _xor64,
    )

    mw = max(1, min(digest_words32, mask_words))
    n64 = digest_words32 // 2
    first_live = digest_words32 - mw  # first live uint32 digest index
    live64 = [j for j in range(n64) if 2 * j + 1 >= first_live]
    needA = [79 - j for j in live64 if j < 4]
    needE = [83 - j for j in live64 if j >= 4]
    R = max(needE + needA)  # mw >= 1 keeps the last 64-bit word live
    maxA = max(needA + [R - 4])  # E[r] consumes A[r-4]

    W = [(words[2 * i], words[2 * i + 1]) for i in range(16)]
    for i in range(16, R + 1):
        w15, w2 = W[i - 15], W[i - 2]
        s0 = _xor64(_rotr64(w15, 1), _rotr64(w15, 8), _shr64(w15, 7))
        s1 = _xor64(_rotr64(w2, 19), _rotr64(w2, 61), _shr64(w2, 6))
        W.append(_add64_many(W[i - 16], s0, W[i - 7], s1))

    ip = [(init[2 * j], init[2 * j + 1]) for j in range(8)]
    A = {-4: ip[3], -3: ip[2], -2: ip[1], -1: ip[0]}
    E = {-4: ip[7], -3: ip[6], -2: ip[5], -1: ip[4]}
    for r in range(R + 1):
        e1, f1, g1, h1 = E[r - 1], E[r - 2], E[r - 3], E[r - 4]
        S1 = _xor64(_rotr64(e1, 14), _rotr64(e1, 18), _rotr64(e1, 41))
        ch = ((e1[0] & f1[0]) ^ (~e1[0] & g1[0]),
              (e1[1] & f1[1]) ^ (~e1[1] & g1[1]))
        t1 = _add64_many(h1, S1, ch, _k_pair(r), W[r])
        E[r] = _add64(A[r - 4], t1)
        if r <= maxA:
            a1, b1, c1 = A[r - 1], A[r - 2], A[r - 3]
            S0 = _xor64(_rotr64(a1, 28), _rotr64(a1, 34), _rotr64(a1, 39))
            maj = ((a1[0] & b1[0]) ^ (a1[0] & c1[0]) ^ (b1[0] & c1[0]),
                   (a1[1] & b1[1]) ^ (a1[1] & c1[1]) ^ (b1[1] & c1[1]))
            A[r] = _add64(t1, _add64(S0, maj))

    out = [None] * digest_words32
    for j in live64:
        hi, lo = _add64(ip[j], A[79 - j] if j < 4 else E[83 - j])
        out[2 * j], out[2 * j + 1] = hi, lo
    return tuple(out)


def _sha3_tile(words, init, mask_words: int = 8):
    """SHA3-256 sponge absorb on a tile: XOR + unrolled Keccak-f[1600].

    Limb-pair form like the sha512 tile but in little-endian (lo, hi)
    order (models/sha3_py.py).  ``words`` is 34 uint32 entries (one
    136-byte rate block), ``init`` 50 (the sponge state after host
    absorption — all zeros for short nonces).  Keccak admits no
    chain-truncation DCE — theta mixes every lane into every other
    each round — so the only mask-word savings is the FINAL round's
    chi/iota, computed just for the lanes the live digest words read
    (digest = lanes 0-3 of the final state; the dominant <=8-nibble
    bucket needs only lane 3, skipping 24 of 25 final chi lanes).
    Returns 8 entries, ``None`` where dead.
    """
    # the (lo, hi) pair rotation is shared with the fori_loop compress
    # (keccak's little-endian lane convention — the OPPOSITE limb order
    # from the sha512 tile's big-endian (hi, lo) pairs)
    from ..models.sha3_jax import _rotl64 as _rotl64_lohi
    from ..models.sha3_py import KECCAK_RC, KECCAK_ROT

    mw = max(1, min(8, mask_words))
    # digest uint32 word w = limb w%2 of lane w//2; live words w >= 8-mw
    need_lanes = sorted({w // 2 for w in range(8 - mw, 8)})

    A = []
    for i in range(25):
        lo, hi = init[2 * i], init[2 * i + 1]
        if 2 * i < 34:
            lo = lo ^ words[2 * i]
        if 2 * i + 1 < 34:
            hi = hi ^ words[2 * i + 1]
        A.append((lo, hi))

    for r in range(24):
        C = [
            (
                A[x][0] ^ A[x + 5][0] ^ A[x + 10][0] ^ A[x + 15][0]
                ^ A[x + 20][0],
                A[x][1] ^ A[x + 5][1] ^ A[x + 10][1] ^ A[x + 15][1]
                ^ A[x + 20][1],
            )
            for x in range(5)
        ]
        D = []
        for x in range(5):
            rl = _rotl64_lohi(C[(x + 1) % 5], 1)
            D.append((C[(x + 4) % 5][0] ^ rl[0], C[(x + 4) % 5][1] ^ rl[1]))
        A = [(A[i][0] ^ D[i % 5][0], A[i][1] ^ D[i % 5][1])
             for i in range(25)]
        B = [None] * 25
        for x in range(5):
            for y in range(5):
                B[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl64_lohi(
                    A[x + 5 * y], KECCAK_ROT[x][y]
                )
        lanes = range(25) if r < 23 else need_lanes
        A2 = [None] * 25
        for i in lanes:
            x, y = i % 5, i // 5
            b0 = B[x + 5 * y]
            b1 = B[(x + 1) % 5 + 5 * y]
            b2 = B[(x + 2) % 5 + 5 * y]
            A2[i] = (b0[0] ^ (~b1[0] & b2[0]), b0[1] ^ (~b1[1] & b2[1]))
        if A2[0] is not None:
            rc = KECCAK_RC[r]
            A2[0] = (
                A2[0][0] ^ jnp.uint32(rc & 0xFFFFFFFF),
                A2[0][1] ^ jnp.uint32((rc >> 32) & 0xFFFFFFFF),
            )
        A = A2

    out = [None] * 8
    for w in range(8 - mw, 8):
        out[w] = A[w // 2][w % 2]
    return tuple(out)


def _sha512_tile(words, init, mask_words: int = 16):
    return _sha512_tile_impl(words, init, mask_words, 16)


def _sha384_tile(words, init, mask_words: int = 12):
    # same compression and full 16-word state; digest = first 6 of the
    # 8 64-bit state words (models/sha384_jax.py)
    return _sha512_tile_impl(words, init, mask_words, 12)


def _blake2b_tile(words, init, mask_words: int = 8):
    """Unrolled BLAKE2b-256 on a tile, in (lo, hi) uint32 limb pairs.

    ``words`` is 36 entries — 32 message limbs + the 4 baked parameter
    limbs (byte counter t, finalization word f0) the packing layer
    appends per block (``HashModel.block_param_words``); ``init`` is 16
    limbs (8 lanes lo-first).  12 rounds of 8 G mixes with the static
    SIGMA schedule; the limb helpers are shared with the fori_loop
    compress (models/blake2b_jax.py).  Like keccak, every round mixes
    every lane, so the only DCE is the FINAL round's diagonal G calls
    pruned to those writing a v-lane a live digest word reads (the
    dominant ≤8-nibble bucket keeps lane 3: v[3] via G(3,4,9,14) and
    v[11] via G(1,6,11,12) — 2 of 4 diagonals skipped).  Returns 8
    entries, ``None`` where dead.
    """
    from ..models.blake2b_jax import _add64, _rotr64_lohi
    from ..models.blake2b_py import BLAKE2B_IV, BLAKE2B_SIGMA, ROUNDS

    mw = max(1, min(8, mask_words))
    need_lanes = sorted({w // 2 for w in range(8 - mw, 8)})

    v = [(init[2 * i], init[2 * i + 1]) for i in range(8)]
    m = [(words[2 * i], words[2 * i + 1]) for i in range(16)]
    for i in range(8):
        v.append((jnp.uint32(BLAKE2B_IV[i] & 0xFFFFFFFF),
                  jnp.uint32((BLAKE2B_IV[i] >> 32) & 0xFFFFFFFF)))
    v[12] = (v[12][0] ^ words[32], v[12][1] ^ words[33])
    v[14] = (v[14][0] ^ words[34], v[14][1] ^ words[35])

    def G(a, b, c, d, x, y):
        alo, ahi = v[a]
        blo, bhi = v[b]
        clo, chi = v[c]
        dlo, dhi = v[d]
        alo, ahi = _add64(*_add64(alo, ahi, blo, bhi), x[0], x[1])
        dlo, dhi = _rotr64_lohi(dlo ^ alo, dhi ^ ahi, 32)
        clo, chi = _add64(clo, chi, dlo, dhi)
        blo, bhi = _rotr64_lohi(blo ^ clo, bhi ^ chi, 24)
        alo, ahi = _add64(*_add64(alo, ahi, blo, bhi), y[0], y[1])
        dlo, dhi = _rotr64_lohi(dlo ^ alo, dhi ^ ahi, 16)
        clo, chi = _add64(clo, chi, dlo, dhi)
        blo, bhi = _rotr64_lohi(blo ^ clo, bhi ^ chi, 63)
        v[a], v[b], v[c], v[d] = (alo, ahi), (blo, bhi), (clo, chi), \
            (dlo, dhi)

    COLS = ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15))
    DIAGS = ((0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14))
    for r in range(ROUNDS):
        s = BLAKE2B_SIGMA[r]
        for gi, (a, b, c, d) in enumerate(COLS):
            G(a, b, c, d, m[s[2 * gi]], m[s[2 * gi + 1]])
        for gi, (a, b, c, d) in enumerate(DIAGS):
            if r == ROUNDS - 1:
                writes = {a, b, c, d}
                if not any(j in writes or j + 8 in writes
                           for j in need_lanes):
                    continue
            G(a, b, c, d, m[s[8 + 2 * gi]], m[s[8 + 2 * gi + 1]])

    out = [None] * 8
    for w in range(8 - mw, 8):
        j, limb = w // 2, w % 2
        out[w] = init[2 * j + limb] ^ v[j][limb] ^ v[j + 8][limb]
    return tuple(out)


# model -> (tile fn, init-state words, digest words, block words); a
# model has a kernel iff it has an entry here, and MODEL_GEOMETRY above
# is checked against this at import so the two can't drift apart.
_TILE_FNS = {"md5": (_md5_tile, 4, 4, 16), "sha256": (_sha256_tile, 8, 8, 16),
             "sha1": (_sha1_tile, 5, 5, 16),
             "ripemd160": (_ripemd160_tile, 5, 5, 16),
             "sha512": (_sha512_tile, 16, 16, 32),
             "sha384": (_sha384_tile, 16, 12, 32),
             "sha3_256": (_sha3_tile, 50, 8, 34),
             # 36 = 32 message limbs + 4 baked parameter limbs
             "blake2b_256": (_blake2b_tile, 16, 8, 36),
             "sha256d": (_sha256d_tile, 8, 8, 16)}
assert set(_TILE_FNS) == set(MODEL_GEOMETRY), \
    "every pallas kernel model needs a MODEL_GEOMETRY entry and vice versa"


@functools.lru_cache(maxsize=None)
def _dyn_pallas_step(
    tb_word: int,
    tb_shift_in_word: int,
    chunk_word_shifts,  # tuple of (word, shift) per little-endian chunk byte
    grid: int,
    sublanes: int,
    interpret: bool,
    inner: int = 1,
    mask_words: int = 4,
    model_name: str = "md5",
):
    """Layout-keyed pallas program.

    Returned jitted fn: ``(chunk0, init[S], base[16], masks[mask_words],
    part[2]=(tb_lo, log_tbc)) -> uint32`` (flat first-hit index or
    SENTINEL), where ``S`` is the model's state width (md5 4, sha256 8,
    sha1 5).

    Each grid step evaluates ``inner`` consecutive (sublanes, 128) tiles
    in an on-device ``fori_loop``.  The split matters: sublanes bounds
    the live register set of the unrolled 64-round chain (too tall
    spills to VMEM), while inner amortizes the per-grid-step fixed cost
    (index iota, bookkeeping, the cross-lane min) — see MODEL_GEOMETRY
    for the measured TPU v5e sweep.

    ``mask_words`` (the trailing-digest-word bucket of
    ops.search_step.mask_words_for) is a compile key: the final MD5
    rounds whose outputs only feed dead digest words are skipped in
    ``_md5_tile``, matching the DCE XLA applies to the fused step.
    """
    tile = sublanes * LANES
    tile_fn, state_words, digest_words, block_words = _TILE_FNS[model_name]
    mw = max(1, min(digest_words, mask_words))

    def kernel(chunk0_ref, init_ref, base_ref, masks_ref, part_ref, out_ref):
        i = pl.program_id(0)
        chunk0 = chunk0_ref[0]
        tb_lo = part_ref[0]
        log_tbc = part_ref[1]
        row = jax.lax.broadcasted_iota(jnp.uint32, (sublanes, LANES), 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, (sublanes, LANES), 1)
        f0 = (
            jnp.uint32(i) * jnp.uint32(tile * inner)
            + row * jnp.uint32(LANES)
            + col
        )
        init = tuple(init_ref[j] for j in range(state_words))
        consts = [base_ref[w] for w in range(block_words)]

        def tile_candidates(f):
            """Elementwise (sublanes, LANES) array of int32 flat indices:
            the candidate's own index where it hits, _I32_MISS where not.
            Kept elementwise so the inner loop accumulates with ONE
            vector minimum per tile; the expensive cross-lane min runs
            once per grid step, not once per tile.  (Mosaic has no
            unsigned reductions; flat indices are far below 2^31, so the
            int32 domain with int32-max as miss marker is exact.)"""
            chunk = chunk0 + (f >> log_tbc)
            tb = tb_lo + (f & ((jnp.uint32(1) << log_tbc) - jnp.uint32(1)))
            words = list(consts)
            words[tb_word] = words[tb_word] | (tb << tb_shift_in_word)
            for j, (w_i, s_i) in enumerate(chunk_word_shifts):
                byte_j = (chunk >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
                words[w_i] = words[w_i] | (byte_j << s_i)

            state = tile_fn(words, init, mw)
            acc = state[digest_words - mw] & masks_ref[0]
            for j in range(1, mw):
                acc = acc | (state[digest_words - mw + j] & masks_ref[j])
            hit = acc == jnp.uint32(0)
            return jnp.where(hit, f.astype(jnp.int32), jnp.int32(_I32_MISS))

        if inner == 1:
            m = jnp.min(tile_candidates(f0))
        else:
            best = jax.lax.fori_loop(
                0,
                inner,
                lambda j, best: jnp.minimum(
                    best,
                    tile_candidates(
                        f0 + j.astype(jnp.uint32) * jnp.uint32(tile)
                    ),
                ),
                jnp.full((sublanes, LANES), _I32_MISS, jnp.int32),
            )
            m = jnp.min(best)

        # TPU grid steps run sequentially on the core, so a single SMEM
        # cell accumulates the global min across the grid.
        @pl.when(i == 0)
        def _init():
            out_ref[0, 0] = m

        @pl.when(i > 0)
        def _acc():
            out_ref[0, 0] = jnp.minimum(out_ref[0, 0], m)

    call = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 5,
        out_specs=pl.BlockSpec(
            (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
    )

    def step(chunk0, init, base, masks, part):
        chunk0 = jnp.asarray(chunk0, jnp.uint32).reshape((1,))
        m = call(chunk0, init, base, masks, part)[0, 0]
        return jnp.where(
            m == jnp.int32(_I32_MISS), jnp.uint32(SENTINEL), m.astype(jnp.uint32)
        )

    return named_jit(step, f"{model_name}_pallas_step")


def build_pallas_search_step(
    nonce: bytes,
    width: int,
    difficulty: int,
    tb_lo: int,
    tb_count: int,
    chunks_per_step: int,
    model_name: str = "md5",
    extra_const_chunk: bytes = b"",
    sublanes: Optional[int] = None,
    interpret: bool = False,
    launch_steps: int = 1,
    inner: Optional[int] = None,
) -> Callable:
    """Build ``step(chunk0) -> uint32`` backed by the Pallas kernel.

    Same contract as ``ops.search_step.build_search_step``, including the
    ``launch_steps`` multiplier: one dispatch covers ``launch_steps *
    chunks_per_step * tb_count`` candidates.  Where the XLA path amortizes
    the per-dispatch round trip with an on-device ``fori_loop``, the
    kernel simply extends its sequential TPU grid — the flat index
    already spans ``program_id * tile``, so a larger grid IS the
    multi-sub-batch launch, with no extra machinery.  Requires
    ``tb_count`` to be a power of two, an implemented model (one with a
    ``_TILE_FNS`` entry), and a single-block tail (the overwhelmingly
    common configuration); callers fall back to the XLA path otherwise.

    ``sublanes``/``inner`` default to the model's tuned geometry
    (``default_geometry``, which caps interpret-mode sublanes at 8 —
    see its docstring); pass explicitly to sweep.
    """
    model = get_hash_model(model_name)
    if model.name not in _TILE_FNS:
        raise ValueError(
            f"pallas kernel implements {sorted(_TILE_FNS)}, not {model.name}"
        )
    if interpret and model.name in INTERPRET_XLA_FALLBACK:
        # interpret mode runs the traced tile through XLA:CPU, whose
        # compile on the fully-unrolled 64-bit limb-pair graph is
        # pathological (the same blowup as the unrolled fused step —
        # scripts/probe_sha512_forms.py timed out >5 min on CPU where
        # the loop form takes seconds).  Off-TPU dev serving of these
        # models goes through the XLA fallback; the kernel is a
        # TPU-hardware path.  ValueError = the signal every caller
        # (the pallas mesh step factory, the lane planner) maps to a
        # transparent fallback.
        raise ValueError(
            f"{model.name} pallas tile is TPU-only (interpret-mode "
            f"XLA:CPU compile of the limb-pair graph is pathological)"
        )
    geom = default_geometry(model.name, interpret)
    if sublanes is None:
        sublanes = geom[0]
    if inner is None:
        inner = geom[1]
    if tb_count & (tb_count - 1):
        raise ValueError("pallas kernel requires power-of-two tb_count")

    spec = build_tail_spec(bytes(nonce), width, model, extra_const_chunk)
    if spec.n_blocks != 1:
        raise ValueError("pallas kernel requires a single-block tail")
    masks = nibble_masks(difficulty, model)

    batch = chunks_per_step * tb_count
    tile = sublanes * LANES
    if batch % tile:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    _check_launch(batch, launch_steps)
    tiles = batch * launch_steps // tile
    # the inner fori_loop length must divide the tile count; shrink to fit
    inner = max(1, inner)
    while tiles % inner:
        inner //= 2
    grid = tiles // inner

    mw = mask_words_for(difficulty, model)
    _, tb_w, tb_s = spec.tb_loc
    chunk_ws = tuple((w, s) for _, w, s in spec.chunk_locs)
    dyn = _dyn_pallas_step(
        tb_w, tb_s, chunk_ws, grid, sublanes, interpret, inner, mw,
        model.name,
    )

    init = jnp.asarray(spec.init_state, jnp.uint32)
    base = jnp.asarray(spec.base_words[0], jnp.uint32)
    # only the significant trailing mask words enter the kernel (their
    # count is the compile key, same discipline as search_step.pack_operands)
    masks_arr = jnp.asarray(masks[model.digest_words - mw:], jnp.uint32)
    part = jnp.asarray([tb_lo, tb_count.bit_length() - 1], jnp.uint32)

    def step(chunk0):
        return dyn(chunk0, init, base, masks_arr, part)

    return step
