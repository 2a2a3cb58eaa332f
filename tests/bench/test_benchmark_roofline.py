"""The md5 operation count and the peak table (benchmark/roofline.py)."""

from __future__ import annotations

import hashlib
import struct

import bench_helpers  # noqa: F401
import pytest

from benchmark import roofline


def test_symbolic_md5_on_constant_words_is_md5():
    for msg in (b"", b"\x01\x02\x03\x04", b"\xff" * 4 + b"abcde"):
        n = len(msg)
        block = msg + b"\x80" + bytes(55 - n) + struct.pack("<Q", 8 * n)
        digest = roofline.md5_block(roofline._Tape(),
                                    list(struct.unpack("<16I", block)))
        assert struct.pack("<4I", *digest).hex() == hashlib.md5(
            msg).hexdigest()


def test_md5_op_count_is_pinned():
    # 4-byte nonce, secrets of 1-4 bytes: one varying message word;
    # 5 bytes: two.  Pinned: a change here changes every roofline.
    assert [roofline.md5_min_ops(4, n, 8) for n in range(1, 6)] == [
        407, 407, 407, 407, 410]
    assert roofline.md5_search_ops(4, 8) == 407
    assert roofline.md5_search_ops(4, 7) == 408
    # more nibbles than one digest word: the c word is needed too
    assert roofline.md5_min_ops(4, 4, 9) == 418


def test_md5_op_count_is_below_a_plain_implementation():
    # 64 steps of 4 adds, a rotate and the round function (3, 3, 2, 3
    # ops), plus one varying word per round: what an implementation
    # that folds nothing executes
    plain = 16 * (5 + 3) + 16 * (5 + 3) + 16 * (5 + 2) + 16 * (5 + 3) + 4
    assert roofline.md5_search_ops(4, 8) < plain


def test_peak_is_derived_for_v5e_and_missing_kinds_are_errors():
    peak = roofline.vpu_int32_peak("TPU v5 lite")
    assert peak == pytest.approx(4 * 1024 * 197e12 / (4 * 128 * 128 * 2))
    assert "derivation" in roofline.PEAKS["TPU v5 lite"]
    with pytest.raises(KeyError):
        roofline.vpu_int32_peak("TPU v9 imaginary")


def test_symbolic_sha256_and_sha256d_on_constant_words_are_hashlibs():
    for msg in (b"", b"\x01\x02\x03\x04\x05", b"\xff" * 4 + b"abcde"):
        n = len(msg)
        block = msg + b"\x80" + bytes(55 - n) + struct.pack(">Q", 8 * n)
        t = roofline._Tape()
        inner = roofline.sha256_block(t, list(struct.unpack(">16I", block)))
        assert struct.pack(">8I", *inner) == hashlib.sha256(msg).digest()
        outer = roofline.sha256_block(t, inner + list(roofline.SHA256D_TAIL))
        assert struct.pack(">8I", *outer) == hashlib.sha256(
            hashlib.sha256(msg).digest()).digest()
        assert not t.ids  # constants fold: no operation recorded


def test_sha256d_op_count_is_pinned():
    # 4-byte nonce, secrets of 1-4 bytes: one varying message word (1);
    # 5 bytes: two.  Stage 1 1941 ops (schedule 522), stage 2 up to
    # digest word 7 1858, the test 2 (PERF.md section 7).  Pinned: a
    # change here changes a roofline.
    assert [roofline.sha256d_min_ops(4, n, 7) for n in range(1, 6)] == [
        3801, 3801, 3801, 3801, 3809]
    assert roofline.sha256d_search_ops(4, 7) == 3801
    assert roofline.sha256d_search_ops(4, 8) == 3800
    # more nibbles than one digest word: word 6 too
    assert roofline.sha256d_min_ops(4, 4, 9) == 3840


def test_sha256d_op_count_is_below_a_plain_implementation():
    # per round: Sigma1 and Sigma0 (3 rotates, 2 xors each), Ch 3, Maj 4
    # with nothing shared, 4 adds into T1, 3 more into a and e; per
    # schedule word: sigma0 and sigma1 (2 rotates, a shift, 2 xors
    # each) and 3 adds; 8 feed-forward adds; both stages in full
    per_stage = 64 * (5 + 5 + 3 + 4 + 4 + 3) + 48 * (5 + 5 + 3) + 8
    assert roofline.sha256d_search_ops(4, 7) < 2 * per_stage + 2
