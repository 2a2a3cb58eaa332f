"""Per-request operand binding (docs/SERVING.md "Operand binding").

A request's operands for widths 0-4 are packed once on the host and
placed once, before its first dispatch: ``search.operand_binds`` reads 1
a request on one device and on a four-device mesh (where the probe
reads the replicated pack's first shard), and every placement is one
``jax.device_put``.  Each case solves a request of a layout whose
programs a first request compiled, and checks the secret against
hashlib's reference-order first: nonce lengths whose tails cross a
block boundary at some width, difficulties in two mask-word buckets
(the second, 36 bits, under a hash budget: no solution in what was
searched), and partitions of 256, 64 (tb-split) and 2 (chunk-split)
thread bytes.  A width past 4 binds one more pack per 2^32-candidate
segment.
"""

import hashlib

import jax
import pytest

import distpow_tpu.parallel.search as search_mod
from distpow_tpu.backends import JaxMeshBackend, PallasMeshBackend
from distpow_tpu.models.registry import get_hash_model
from distpow_tpu.parallel.search import persistent_search
from distpow_tpu.runtime.metrics import REGISTRY

N_DEV = 4
BATCH = 1 << 12
LAUNCH = 1 << 14
PARTITIONS = {"256": list(range(256)), "64": list(range(64, 128)),
              "2": [6, 7]}
# (nonce length, difficulty, partition): md5 tails are one block up to
# 54 bytes of nonce and width, so 51 crosses at width 4 and 55-60 are
# two blocks at every width; difficulty 9 needs a second mask word
CASES = [(4, 2, "256"), (51, 3, "64"), (55, 2, "2"), (56, 3, "256"),
         (60, 2, "64"), (4, 9, "2"), (55, 9, "256")]
BUDGET = 1 << 15  # hashes for a difficulty-9 request


def _builders():
    return {
        "jax": lambda: None,
        "jax-mesh": lambda: JaxMeshBackend(
            batch_size=BATCH, mesh_devices=N_DEV)._step_builder,
        "pallas-mesh": lambda: PallasMeshBackend(
            batch_size=BATCH, mesh_devices=N_DEV,
            interpret=True)._step_builder,
    }


def _chunks(width):
    """Chunk encodings of one width in reference order (little-endian;
    past 4 bytes, one 2^32 segment a high-byte value)."""
    if width == 0:
        yield b""
    elif width <= 4:
        for c in range(256 ** (width - 1), 256 ** width):
            yield c.to_bytes(width, "little")
    else:
        for hi in range(256 ** (width - 5), 256 ** (width - 4)):
            for c in range(1 << 32):
                yield c.to_bytes(4, "little") + hi.to_bytes(width - 4,
                                                            "little")


def reference_first(nonce, difficulty, tbs, widths=range(5), limit=None):
    """hashlib's first solving secret in reference order (chunk-major,
    thread-byte-minor, widths ascending), or None within ``limit``
    candidates."""
    n = 0
    for width in widths:
        for chunk in _chunks(width):
            for tb in tbs:
                secret = bytes([tb]) + chunk
                digest = hashlib.md5(nonce + secret).hexdigest()
                if digest.endswith("0" * difficulty):
                    return secret
                n += 1
                if limit is not None and n >= limit:
                    return None
    return None


def _nonce(length, seed):
    return bytes((seed * 37 + 11 * i + length) & 0xFF for i in range(length))


def _solve_counted(monkeypatch, nonce, difficulty, tbs, step_builder, **kw):
    """One request; returns ``(result, binds, device_puts, hashes)``."""
    puts = []
    device_put = jax.device_put

    def counting_put(*args, **kwargs):
        puts.append(1)
        return device_put(*args, **kwargs)

    binds0 = REGISTRY.get("search.operand_binds")
    hashes0 = REGISTRY.get("search.hashes")
    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", counting_put)
        res = persistent_search(nonce, difficulty, tbs, batch_size=BATCH,
                                launch_candidates=LAUNCH,
                                step_builder=step_builder, **kw)
    return (res, REGISTRY.get("search.operand_binds") - binds0, len(puts),
            REGISTRY.get("search.hashes") - hashes0)


@pytest.mark.parametrize("case", CASES,
                         ids=[f"len{n}-d{d}-tbc{p}" for n, d, p in CASES])
@pytest.mark.parametrize("backend", list(_builders()))
def test_request_binds_its_operands_once(monkeypatch, backend, case):
    nonce_len, difficulty, part = case
    tbs = PARTITIONS[part]
    builder = _builders()[backend]()
    kw = {"max_hashes": BUDGET} if difficulty > 8 else {}
    # a first request of the layout compiles its programs
    persistent_search(_nonce(nonce_len, 0), difficulty, tbs,
                      batch_size=BATCH, launch_candidates=LAUNCH,
                      step_builder=builder, **kw)
    nonce = _nonce(nonce_len, 1)
    res, binds, puts, hashes = _solve_counted(
        monkeypatch, nonce, difficulty, tbs, builder, **kw)
    if kw:
        assert res is None and hashes >= BUDGET
        assert reference_first(nonce, difficulty, tbs, limit=hashes) is None
    else:
        assert res is not None
        assert res.secret == reference_first(nonce, difficulty, tbs)
    assert binds == 1
    assert puts == binds


@pytest.mark.parametrize("backend", list(_builders()))
def test_width_past_4_binds_a_pack_per_segment(monkeypatch, backend):
    """Widths 1-4 skipped, so the solve reaches width 5: the dense
    pack's bind, then one for the width-5 segment the hit lies in."""
    widths = (0, 5)
    segments = search_mod.width_segments
    monkeypatch.setattr(search_mod, "width_segments",
                        lambda w: segments(w) if w in widths else iter(()))
    tbs = PARTITIONS["256"]
    builder = _builders()[backend]()
    # nonces with no width-0 solution at difficulty 2
    nonces = [n for n in (bytes([s, 0x5A, 0xA5, 0x17]) for s in range(16))
              if reference_first(n, 2, tbs, widths=(0,)) is None][:2]
    persistent_search(nonces[0], 2, tbs, batch_size=BATCH,
                      launch_candidates=LAUNCH, max_width=5,
                      step_builder=builder)
    res, binds, puts, _ = _solve_counted(monkeypatch, nonces[1], 2, tbs,
                                         builder, max_width=5)
    assert res is not None and len(res.secret) == 6
    assert res.secret == reference_first(nonces[1], 2, tbs, widths=widths)
    assert binds == 2
    assert puts == binds


def test_pack_layout_is_layout_keyed():
    """The pack's length and offsets follow the nonce length, the
    mask-word bucket and the model, not the nonce's bytes, the
    difficulty within a bucket or the partition."""
    from distpow_tpu.ops.search_step import pack_operands

    md5 = get_hash_model("md5")
    a = pack_operands(b"\x00" * 51, 1, 0, 256, md5)
    b = pack_operands(bytes(range(51)), 8, 64, 64, md5)
    assert a.words.shape == b.words.shape and a.base_offs == b.base_offs
    assert [s.n_blocks for s in a.specs] == [1, 1, 1, 1, 2]
    c = pack_operands(bytes(range(51)), 9, 6, 2, md5)
    assert c.mask_words == 2 and c.words.shape[0] == a.words.shape[0] + 1
