"""Search-driver tests: the persistent driver on one device and on the
mesh, and the backends that drive it, vs the python oracle."""

import threading

import jax
import pytest

from distpow_tpu.models import puzzle
from distpow_tpu.models.registry import get_hash_model
from distpow_tpu.parallel import partition
from distpow_tpu.parallel.mesh_search import make_mesh, mesh_persistent_factory
from distpow_tpu.parallel.search import persistent_search


NONCES = [b"\x01\x02\x03\x04", b"\x02\x02\x02\x02", b"\xfe\xff"]
# how the driver is handed its steps: the single-device persistent step,
# or the XLA mesh persistent step over every conftest device
ROUTES = ["one-device", "mesh"]


def solve(nonce, difficulty, tbs, route="one-device", model=None, **kw):
    """``persistent_search`` over ``route``.  A mesh width whose segment
    batch does not divide across the devices keeps the single-device
    step, as the mesh backends do."""
    step_builder = None
    if route == "mesh":
        def step_builder(operands):
            mesh_builder = mesh_persistent_factory(
                operands, make_mesh(jax.devices()))

            def builder(*width):
                try:
                    return mesh_builder(*width)
                except ValueError:
                    return None

            return builder

    return persistent_search(nonce, difficulty, tbs, model=model,
                             step_builder=step_builder, **kw)


def test_public_search_name_survives_submodule_import_order():
    """README surface: ``from distpow_tpu.parallel import
    persistent_search`` must yield the FUNCTION whatever imported the
    package's submodules first (backends/__init__ imports
    ``parallel.search``), and importing a submodule must not raise an
    ImportWarning."""
    import subprocess
    import sys as _sys

    code = (
        "import warnings; warnings.simplefilter('error', ImportWarning)\n"
        "import distpow_tpu.backends\n"  # imports parallel.search module
        "import distpow_tpu.parallel.search\n"  # must not ImportWarning
        "from distpow_tpu.parallel import persistent_search, make_mesh\n"
        "assert callable(persistent_search), type(persistent_search)\n"
        "assert callable(make_mesh)\n"
        "print('SURFACE_OK')\n"
    )
    out = subprocess.run(
        [_sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SURFACE_OK" in out.stdout


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("nonce", NONCES)
@pytest.mark.parametrize("difficulty", [1, 2, 3])
def test_search_matches_python_oracle_full_range(nonce, difficulty, route):
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, difficulty, tbs)
    got = solve(nonce, difficulty, tbs, route, batch_size=1 << 14)
    assert got is not None
    assert got.secret == oracle
    assert puzzle.check_secret(nonce, got.secret, difficulty)


@pytest.mark.parametrize("nonce, tbs", [
    # a single worker's shard in a 4-worker config (worker.go:301-316)
    (b"\x05\x06\x07\x08", partition.thread_bytes(2, partition.worker_bits(4))),
    (b"\x21\x22\x23", partition.thread_bytes(2, partition.worker_bits(4))),
    (b"\x05\x06\x07\x08", list(range(64, 128))),  # pow2 sub-partition
    # tb_count == 1 exercises the degenerate lane mapping
    (b"\x09", [7]),
    (b"\x05\x06\x07\x08", [7]),
    (b"\x05\x06\x07\x08", [3, 4, 5]),  # contiguous non-pow2 (static regime)
], ids=["worker-shard", "worker-shard-2", "pow2-sub", "single",
        "single-2", "non-pow2"])
def test_search_partitions(nonce, tbs):
    oracle = puzzle.python_search(nonce, 2, tbs)
    got = solve(nonce, 2, tbs, batch_size=1 << 13)
    assert got is not None and got.secret == oracle
    assert got.secret[0] in tbs


@pytest.mark.parametrize("route", ROUTES)
def test_search_difficulty4_deep(route):
    # difficulty 4 typically needs tens of thousands of candidates; pushes
    # into width >= 2 chunks — the multi-segment loop must preserve
    # enumeration order across segment boundaries and the width cursor
    nonce = b"\x11\x22\x33\x44"
    tbs = list(range(256))
    got = solve(nonce, 4, tbs, route, batch_size=1 << 16)
    assert got is not None
    assert puzzle.check_secret(nonce, got.secret, 4)
    oracle = puzzle.python_search(nonce, 4, tbs)
    assert got.secret == oracle


def test_search_cancellation():
    ev = threading.Event()
    ev.set()
    got = solve(b"\x01", 30, list(range(256)), cancel_check=ev.is_set)
    assert got is None


@pytest.mark.parametrize("route", ROUTES)
def test_search_max_hashes_budget(route):
    got = solve(b"\x01", 30, list(range(256)), route, batch_size=1 << 12,
                max_hashes=1 << 14)
    assert got is None


@pytest.mark.parametrize("gate", ["cancel", "budget", "none"])
def test_search_unsatisfiable_difficulty_gates(gate):
    """An unsatisfiable difficulty returns None on either gate; bare
    library callers with neither get a ValueError instead of an
    un-endable wait (the worker path always passes a cancel_check, so
    serving blocks until cancelled, reference parity with
    worker.go:246-256)."""
    tbs = list(range(256))
    if gate == "cancel":
        assert solve(b"\x01", 33, tbs, cancel_check=lambda: True) is None
    elif gate == "budget":
        assert solve(b"\x01", 33, tbs, max_hashes=100) is None
    else:
        with pytest.raises(ValueError, match="unsatisfiable"):
            solve(b"\x01", 33, tbs)

        from distpow_tpu.backends import native_miner

        backend = native_miner.NativeBackend()
        with pytest.raises(ValueError, match="unsatisfiable"):
            backend.search(b"\x01", 33, tbs)


@pytest.mark.parametrize("algo, nonce", [
    ("sha256", b"\x0a\x0b"),
    ("sha256", b"\x31\x32\x33\x34"),
    # the layers below the registry are hash-agnostic, so
    # enumeration-order parity with the python oracle holds for free
    ("sha1", b"\x0c\x0d"),
    ("sha1", b"\x31\x32\x33\x34"),
    ("ripemd160", b"\x0a\x0b"),
    # 3 blocks: the long-nonce host-absorption path
    ("ripemd160", bytes(range(200))),
    # 128-byte blocks and a 16-byte length field
    ("sha512", b"\x0a\x0b"),
    ("blake2b_256", b"\x31\x32\x33\x34"),
])
def test_search_models(algo, nonce):
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 2, tbs, algo=algo)
    got = solve(nonce, 2, tbs, model=get_hash_model(algo),
                batch_size=1 << 13)
    assert got is not None and got.secret == oracle


@pytest.mark.parametrize("algo", ["sha256d", "md5"])
def test_search_counts_finalize_hashes_and_names_the_hash(algo):
    """Seeded random nonces through the default one-device step, each
    solved past the width-0 probe, by the chained persistent step: each
    secret is the oracle's reference-order first, every candidate
    of a sha256d solve ran its ``finalize`` stage
    (``search.finalize_hashes`` equals ``search.hashes``) and md5's
    none, and every ``search.segment`` and ``search.bind`` span names
    the hash."""
    import random

    from distpow_tpu.runtime.metrics import REGISTRY
    from distpow_tpu.runtime.spans import SPANS

    rng = random.Random(31)
    tbs = list(range(256))
    model = get_hash_model(algo)
    for i in range(3):
        nonce = bytes(rng.randrange(256) for _ in range(4))
        hashes0 = REGISTRY.get("search.hashes")
        final0 = REGISTRY.get("search.finalize_hashes")
        SPANS.reset()
        with SPANS.bind(3100 + i, "w-test"):
            got = solve(nonce, 3, tbs, model=model, batch_size=1 << 13)
        assert got is not None and len(got.secret) > 1
        assert got.secret == puzzle.python_search(nonce, 3, tbs, algo=algo)
        hashes = REGISTRY.get("search.hashes") - hashes0
        final = REGISTRY.get("search.finalize_hashes") - final0
        assert hashes > 256
        assert final == (hashes if algo == "sha256d" else 0)
        spans = [s for s in SPANS.spans_for(3100 + i)
                 if s["name"] in ("search.segment", "search.bind")]
        assert {s["name"] for s in spans} == {"search.segment",
                                              "search.bind"}
        assert all(s["attrs"]["hash"] == algo for s in spans)
    assert "search.finalize_hashes" in REGISTRY.snapshot()["counters"]


@pytest.mark.slow
def test_search_sha512_boundary_layouts():
    """sha512 two-block-tail padding boundary + long-nonce host
    absorption of a full 128-byte block — each length is a fresh layout
    (a fresh loop-form compile), so this lives in the slow set."""
    from distpow_tpu.models.registry import SHA512

    tbs = list(range(256))
    for L in (111, 112, 140):
        nonce = bytes(range(L))
        o = puzzle.python_search(nonce, 1, tbs, algo="sha512")
        g = solve(nonce, 1, tbs, model=SHA512, batch_size=1 << 12)
        assert g is not None and g.secret == o, L


def test_search_all_constant_tail_block():
    """Regression (round 4): nonce lengths where the secret fits block 0
    entirely but padding forces a second, ALL-constant tail block (rem +
    1 + width in [56, 63] for 64-byte-block hashes) crashed the sha
    fori_loop forms on CPU."""
    from distpow_tpu.models.registry import SHA1, SHA256

    tbs = list(range(256))
    for model, algo in ((SHA256, "sha256"), (SHA1, "sha1")):
        nonce = bytes(range(59))  # 59 + 1 + 4 = 64 <= 64 < 64 + 9
        o = puzzle.python_search(nonce, 1, tbs, algo=algo)
        g = solve(nonce, 1, tbs, model=model, batch_size=1 << 12)
        assert g is not None and g.secret == o, algo


def test_mesh_search_sha1_model():
    """sha1 through the shard_map mesh step (the stacked-window vma fix
    in sha1_jax._compress_loop is only exercised under shard_map)."""
    from distpow_tpu.backends import JaxMeshBackend

    nonce = b"\x05\x06"
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 2, tbs, algo="sha1")
    got = JaxMeshBackend(hash_model="sha1", batch_size=1 << 13).search(
        nonce, 2, tbs)
    assert got == oracle


@pytest.mark.slow
def test_mesh_search_new_models():
    """ripemd160, sha512, and blake2b through the shard_map mesh step:
    the stacked-window sha512 loop form must carry cleanly under
    shard_map's varying-axis types, the two-line ripemd compression
    must shard like any other, and blake2b's carry must stay
    vma-uniform although half its initial limbs are replicated IV
    constants (blake2b_jax.py's varying-zero promotion)."""
    tbs = list(range(256))
    for algo in ("ripemd160", "sha512", "blake2b_256",
                 # composed finalize under shard_map: the second
                 # compression's constant init/message words are
                 # varying-promoted (sha256d_jax.sha256d_finalize)
                 "sha256d"):
        oracle = puzzle.python_search(b"\x0a\x0b", 2, tbs, algo=algo)
        got = solve(b"\x0a\x0b", 2, tbs, "mesh",
                    model=get_hash_model(algo), batch_size=1 << 13)
        assert got is not None and got.secret == oracle, algo


def test_search_long_nonce_multi_block():
    # nonce longer than one hash block: constant blocks absorb host-side
    nonce = bytes(range(256))[:100]
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, 2, tbs)
    got = solve(nonce, 2, tbs, batch_size=1 << 13)
    assert got is not None and got.secret == oracle


@pytest.mark.parametrize("difficulty", [2, 3])
def test_mesh_search_matches_single_device(difficulty):
    from distpow_tpu.backends import JaxMeshBackend

    nonce = b"\x01\x02\x03\x04"
    tbs = list(range(256))
    oracle = puzzle.python_search(nonce, difficulty, tbs)
    got = JaxMeshBackend(batch_size=1 << 14).search(nonce, difficulty, tbs)
    assert got == oracle == solve(nonce, difficulty, tbs,
                                  batch_size=1 << 14).secret


def test_mesh_search_sub_partition_and_chunk_split():
    from distpow_tpu.backends import JaxMeshBackend

    backend = JaxMeshBackend(batch_size=1 << 13)
    nonce = b"\x03\x01\x04\x01"
    # 64 tbs over 8 devices; then fewer tbs than devices
    for tbs in (partition.thread_bytes(1, 2), [5, 6, 7]):
        oracle = puzzle.python_search(nonce, 2, tbs)
        assert backend.search(nonce, 2, tbs) == oracle


def test_launch_steps_partition_independent():
    """The launch multiplier enters jit compile keys, so for a fixed
    effective batch it must not depend on which pow2 partition a request
    carries — else boot warmup (tbc=256) couldn't cover serving."""
    from distpow_tpu.parallel.search import effective_batch, launch_steps_for

    for batch_size in (1 << 13, 10_000, 1 << 21):
        E = effective_batch(batch_size)
        for vw in (1, 2, 3, 4):
            ks = {launch_steps_for(vw, E // tbc, tbc) for tbc in (256, 64, 8, 1)}
            assert len(ks) == 1, (batch_size, vw, ks)


@pytest.mark.parametrize("nonce, difficulties, budget", [
    (b"\x0a\x0b\x0c\x0d", (2, 3), dict(batch_size=1 << 13,
                                         launch_candidates=1 << 16)),
    # a tiny per-dispatch budget forces MANY multi-segment dispatches
    # through the pipeline
    (b"\x41\x42", (3,), dict(batch_size=1 << 10,
                              launch_candidates=1 << 12)),
], ids=["segments-16", "segments-4"])
def test_search_small_launch_budget_matches_oracle(nonce, difficulties,
                                                   budget):
    """Multi-segment dispatches preserve reference enumeration order
    across segment boundaries, and the FIFO drain hands back the
    enumeration-order first hit."""
    from distpow_tpu.runtime.metrics import REGISTRY

    steps = REGISTRY.get("search.persistent_steps")
    for d in difficulties:
        oracle = puzzle.python_search(nonce, d, list(range(256)))
        got = solve(nonce, d, list(range(256)), **budget)
        assert got is not None and got.secret == oracle
    assert REGISTRY.get("search.persistent_steps") > steps


def test_warmup_covers_sub_partitions_with_launch_steps():
    """A worker warmed on the full 256-byte partition serves a 4-way
    split (tbc=64) without any new dynamic compiles, launch multiplier
    included."""
    from distpow_tpu.backends import JaxBackend
    from distpow_tpu.ops.search_step import _dyn_search_step

    b = JaxBackend(batch_size=1 << 13)
    b.warmup([4], [0, 1, 2])
    misses = _dyn_search_step.cache_info().misses
    secret = b.search(b"\x01\x01\x02\x03", 2, list(range(64, 128)))
    assert secret is not None
    assert puzzle.check_secret(b"\x01\x01\x02\x03", secret, 2)
    assert _dyn_search_step.cache_info().misses == misses


def test_mesh_warmup_covers_all_pow2_partitions():
    """Boot warmup must pre-compile the mesh programs, and the per-device
    batch must be partition-independent even when the configured batch
    size is not divisible by tbc * n_dev (e.g. 10_000 on 8 devices), so
    every pow2 partition's first Mine is pure dispatch."""
    from distpow_tpu.backends import JaxMeshBackend
    from distpow_tpu.parallel.mesh_search import mesh_persistent_step

    b = JaxMeshBackend(batch_size=10_000)
    b.warmup([4], [0, 1, 2])
    misses = mesh_persistent_step.cache_info().misses
    n_dev = int(b._get_mesh().devices.size)
    for tbs in (list(range(256)),               # tb-split
                list(range(max(1, n_dev // 2))),  # fewer tbs, warmed tbc
                [7],                             # fewer tbs, other tbc
                list(range(4, 6))):
        secret = b.search(b"\x00\x01\x02\x03", 2, tbs)
        assert secret is not None
        assert puzzle.check_secret(b"\x00\x01\x02\x03", secret, 2)
    assert mesh_persistent_step.cache_info().misses == misses, \
        "serving recompiled a program warmup should have covered"


def test_non_pow2_mesh_warns_at_boot_and_request_time(caplog):
    """On a mesh whose device count is not a power of two the segment
    batch does not divide across the devices, so each width is served
    through the single-device persistent step; both the boot warmup and
    the request must SAY so, and the request counts it under
    ``fallback.lane_demotions`` — a 6-device dev mesh should never fall
    back silently."""
    import logging

    from distpow_tpu.backends import JaxMeshBackend
    from distpow_tpu.runtime.metrics import REGISTRY

    b = JaxMeshBackend(batch_size=1 << 13, mesh_devices=6)
    with caplog.at_level(logging.WARNING):
        b.warmup([4], [0, 1])
        assert any("does not divide across 6 devices (not a power of two)"
                   in r.message for r in caplog.records)
        caplog.clear()
        demotions = REGISTRY.get("fallback.lane_demotions")
        secret = b.search(b"\x09\x08", 2, list(range(256)))
        assert any("through the single-device persistent step" in r.message
                   for r in caplog.records)
    assert REGISTRY.get("fallback.lane_demotions") > demotions
    assert secret is not None
    assert puzzle.check_secret(b"\x09\x08", secret, 2)


def test_mesh_search_cancellation():
    from distpow_tpu.backends import JaxMeshBackend

    got = JaxMeshBackend().search(b"\x01", 30, list(range(256)),
                                  cancel_check=lambda: True)
    assert got is None


def _fuzz_configs(rng, n, max_difficulty=3):
    """Random (nonce, difficulty, thread_bytes) mining configs spanning
    the layout space: padding boundaries, multi-block nonces, sub- and
    single-byte partitions."""
    lens = [0, 1, 7, 54, 55, 56, 59, 63, 64, 65, 100, 111, 112, 127, 128,
            140, 200]
    for _ in range(n):
        nonce = bytes(rng.randrange(256) for _ in range(rng.choice(lens)))
        difficulty = rng.randint(1, max_difficulty)
        kind = rng.randrange(3)
        if kind == 0:
            tbs = list(range(256))
        elif kind == 1:
            size = rng.choice([2, 4, 16, 64, 128])
            lo = rng.randrange(0, 256 - size + 1, size)
            tbs = list(range(lo, lo + size))
        else:
            tbs = [rng.randrange(256)]
        yield nonce, difficulty, tbs


def _fuzz_against_oracle(models_algos, seed, n, max_difficulty=3,
                         configs=None):
    import random

    rng = random.Random(seed)
    for model, algo in models_algos:
        for nonce, difficulty, tbs in (
                configs if configs is not None
                else _fuzz_configs(rng, n, max_difficulty)):
            # The oracle generator is infinite, so it gets a candidate
            # budget (an unbounded call could never return None and the
            # exhausted arm would be dead).  The driver's max_hashes is
            # DISPATCH-QUANTIZED (it is checked between dispatches), so
            # an exact shared budget can give up one dispatch earlier
            # than the oracle; the contract
            # tested is therefore budget-aware in each direction:
            # - oracle found after p candidates  => the driver, allowed
            #   p plus generous launch slack, finds the SAME secret;
            # - oracle exhausted the budget => the driver at that exact
            #   budget must also return None (its enumerated prefix
            #   never exceeds its counted hashes).
            budget = 1 << 16
            counted = [0]
            oracle = puzzle.python_search(
                nonce, difficulty, tbs, algo=algo, max_candidates=budget,
                on_progress=lambda k: counted.__setitem__(0, counted[0] + k),
            )
            case = (algo, nonce.hex()[:16], difficulty, tbs[0], len(tbs))
            # A segment-overrun launch may return a valid NON-CANONICAL
            # secret (non-minimal chunk encoding, trailing zero byte)
            # the oracle's minimal-encoding enumeration never visits —
            # legitimate per the puzzle contract (search.py module
            # docstring), so both arms accept it when it verifies.
            def wrapped(res):
                return (res is not None and res.chunk
                        and res.chunk[-1] == 0
                        and puzzle.check_secret(nonce, res.secret,
                                                difficulty, algo))

            if oracle is None:
                got = solve(nonce, difficulty, tbs, model=model,
                            batch_size=1 << 12, max_hashes=budget)
                # pipelined launches legally overshoot max_hashes, so a
                # find PAST the budget is legitimate; a find the driver
                # claims was within it while the oracle saw none is the
                # only real divergence (review r4)
                assert got is None or wrapped(got) or (
                    got.hashes_tried > budget
                    and puzzle.check_secret(nonce, got.secret, difficulty,
                                            algo)
                ), case
            else:
                slack = (1 << 15) + 4 * (1 << 12)
                got = solve(nonce, difficulty, tbs, model=model,
                            batch_size=1 << 12,
                            max_hashes=counted[0] + slack)
                assert got is not None and (
                    got.secret == oracle
                    # a wrapped alias may legitimately pre-empt the
                    # canonical solution, but only from a launch at or
                    # before it — a wrapped find far past the oracle
                    # position would mean a skipped canonical hit
                    # (review r4)
                    or (wrapped(got)
                        and got.hashes_tried <= counted[0] + slack)
                ), case


def test_scaled_launch_budget_tracks_model_cost():
    """Backends' default per-dispatch budget scales inversely with
    HashModel.cost_ops so one launch's wall-clock — the cancellation
    granularity — is roughly model-independent (the fixed 2^30 budget
    quantized sha512/sha3 solves to ~2-4 s steps,
    an earlier chip reading).  An explicit max_launch must
    still win."""
    from distpow_tpu.backends import JaxBackend
    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.parallel.search import (
        DEFAULT_LAUNCH_CANDIDATES,
        scaled_launch_candidates,
    )

    md5 = get_hash_model("md5")
    assert scaled_launch_candidates(md5.cost_ops) == DEFAULT_LAUNCH_CANDIDATES
    prev = DEFAULT_LAUNCH_CANDIDATES + 1
    for mname in ("md5", "sha1", "ripemd160", "sha256", "sha512"):
        got = scaled_launch_candidates(get_hash_model(mname).cost_ops)
        assert 1 << 24 <= got <= DEFAULT_LAUNCH_CANDIDATES
        assert got < prev, (mname, got)  # strictly costlier -> smaller
        prev = got
    # floor holds even for absurd costs
    assert scaled_launch_candidates(10**9) == 1 << 24
    # backends consume the scale; explicit config bypasses it
    assert JaxBackend(hash_model="sha512").max_launch == \
        scaled_launch_candidates(get_hash_model("sha512").cost_ops)
    assert JaxBackend(hash_model="sha512", max_launch=12345).max_launch \
        == 12345


def test_search_differential_fuzz_fast():
    """Seeded differential fuzz: random layouts/partitions vs the
    hashlib oracle (md5 only here — every novel nonce length is a fresh
    layout compile, so the fast path keeps a small n; the slow twin
    covers the full registry).  This family of bugs is real — the
    all-constant-tail-block crash (round 4) lived exactly in a layout
    combination no systematic parametrization covered."""
    from distpow_tpu.models.registry import MD5

    _fuzz_against_oracle([(MD5, "md5")], seed=0xF00D, n=5)


@pytest.mark.slow
def test_search_differential_fuzz_all_models():
    """The full-registry fuzz, budgeted (VERDICT r4 item 6: the old
    shared-stream version was the full suite's dominant item at
    ~300-470 s).  Every model still fuzzes against the hashlib oracle
    on random layouts every full run — the coverage class is intact —
    but each model draws its OWN fixed per-model seed (crc32 of the
    name) and a per-model config count sized to its measured XLA:CPU
    layout-compile cost (``_fuzz_schedules``); the nightly veryslow
    twin below runs the unshrunk n=3-for-all schedule, and the
    md5-only fast fuzz covers the high-frequency layouts on every
    fast-path run."""
    import zlib

    for model, algo, n, maxd in _fuzz_schedules():
        if n > 0:
            _fuzz_against_oracle(
                [(model, algo)], seed=zlib.crc32(algo.encode()) ^ 0x5EED,
                n=n, max_difficulty=maxd)
    # sha3/blake2b: a RANDOM config routinely lands on a layout whose
    # XLA:CPU loop-form compile alone costs 40-70 s (r5 durations), so
    # the slow tier pins their device-vs-oracle coverage with fixed
    # cheap-layout configs (~7-12 s each: short nonce, full partition,
    # one width segment) and leaves the random draws to the nightly
    # twin.
    from distpow_tpu.models.registry import BLAKE2B_256, SHA3_256

    _fuzz_against_oracle([(SHA3_256, "sha3_256")], seed=0, n=0,
                         configs=[(b"\x0c", 2, list(range(256)))])
    _fuzz_against_oracle([(BLAKE2B_256, "blake2b_256")], seed=0, n=0,
                         configs=[(b"", 2, list(range(256)))])


def _fuzz_schedules():
    """(model, algo, n_slow, max_difficulty) per registry model.

    n is budgeted by measured per-config cost on XLA:CPU (r5: a fresh
    layout of the sha3/blake2b loop forms costs ~40-70 s there, vs
    ~2 s for md5 — those two run fixed cheap configs in the slow tier
    instead, n=0 here) so the slow tier stays inside the suite's
    10-min target; the nightly twin below runs n=3 for every model."""
    from distpow_tpu.models.registry import (
        BLAKE2B_256, MD5, RIPEMD160, SHA1, SHA3_256, SHA256, SHA384,
        SHA512,
    )

    from distpow_tpu.models.registry import SHA256D

    return (
        (MD5, "md5", 3, 3), (SHA1, "sha1", 3, 3),
        (SHA256, "sha256", 3, 3), (RIPEMD160, "ripemd160", 3, 3),
        (SHA512, "sha512", 2, 2), (SHA384, "sha384", 1, 2),
        (SHA3_256, "sha3_256", 0, 2), (BLAKE2B_256, "blake2b_256", 0, 2),
        (SHA256D, "sha256d", 2, 3),
    )


@pytest.mark.veryslow
def test_search_differential_fuzz_registry_nightly():
    """The unshrunk registry fuzz for the nightly veryslow tier — n=3
    random configs for every model from the same fixed per-model
    seeds, PLUS the slow tier's fixed cheap-layout sha3/blake2b
    configs, so the nightly is a strict superset of the slow tier's
    schedule and budgeting the slow tier deleted no coverage class
    (VERDICT r4 item 6)."""
    import zlib

    from distpow_tpu.models.registry import BLAKE2B_256, SHA3_256

    for model, algo, _, maxd in _fuzz_schedules():
        _fuzz_against_oracle(
            [(model, algo)], seed=zlib.crc32(algo.encode()) ^ 0x5EED,
            n=3, max_difficulty=maxd)
    _fuzz_against_oracle([(SHA3_256, "sha3_256")], seed=0, n=0,
                         configs=[(b"\x0c", 2, list(range(256)))])
    _fuzz_against_oracle([(BLAKE2B_256, "blake2b_256")], seed=0, n=0,
                         configs=[(b"", 2, list(range(256)))])


def test_early_exits_account_all_dispatched_work():
    """Every exit path — cancel mid-pipeline, found mid-pipeline — must
    leave search.hashes equal to the work the device does: on a cancel
    the dispatches still in flight count their span (the device runs
    them out), and behind a hit the chained dispatches count nothing
    (they halt on the device) and count under
    ``search.halted_dispatches``.  A fake step builder pins dispatch
    sizes so the expected totals are exact."""
    import jax.numpy as jnp

    from distpow_tpu.ops.search_step import HALT, SENTINEL
    from distpow_tpu.runtime.metrics import REGISTRY

    dispatched = [0]

    def make_builder(hit_on_launch=None):
        launches = [0]

        def builder(vw, extra, target_chunks, segments):
            # 5 divides every early segment's chunk count exactly
            # (width1: 255, width2: 65280, width3: 16711680), so no
            # dispatch straddles a segment end and the fake's count
            # matches the driver's in-segment clamp on every dispatch
            def step(chunk0, prev):
                launches[0] += 1
                if hit_on_launch is not None and launches[0] > hit_on_launch:
                    return jnp.asarray([HALT, 0], jnp.uint32)
                dispatched[0] += 5 * 256
                if launches[0] == hit_on_launch:
                    # flat index 0 of this dispatch, in its one segment
                    return jnp.asarray([0, 1], jnp.uint32)
                return jnp.asarray([SENTINEL, 1], jnp.uint32)

            return step, 5, 5

        return builder

    # cancel mid-segment with dispatches in flight
    calls = [0]

    def cancel_after(n):
        def check():
            calls[0] += 1
            return calls[0] > n
        return check

    dispatched[0] = 256  # the width-0 probe
    before = REGISTRY.get("search.hashes")
    got = persistent_search(b"\x01", 30, list(range(256)),
                            batch_size=1 << 10, cancel_check=cancel_after(6),
                            step_builder=lambda _: make_builder())
    assert got is None
    assert REGISTRY.get("search.hashes") - before == dispatched[0] > 256

    # found mid-pipeline: the hit's dispatch counts, the one chained
    # behind it halts.  At 3 nibbles nonce 570400 has no width-0
    # solution and its (tb 0, chunk 16) solves: flat index 0 of the 4th
    # dispatch (chunks 16-20)
    dispatched[0] = 256
    before = REGISTRY.get("search.hashes")
    halted = REGISTRY.get("search.halted_dispatches")
    got = persistent_search(bytes.fromhex("570400"), 3, list(range(256)),
                            batch_size=1 << 10, max_width=1,
                            step_builder=lambda _: make_builder(
                                hit_on_launch=4))
    assert got is not None and got.secret == bytes([0, 16])
    assert got.hashes_tried == dispatched[0]
    assert REGISTRY.get("search.hashes") - before == dispatched[0] > 256
    assert REGISTRY.get("search.halted_dispatches") - halted == 1


@pytest.mark.slow
def test_mesh_search_differential_fuzz():
    """Seeded mesh fuzz: random power-of-two partitions (including
    sub-runs, single bytes, and fewer-tbs-than-devices configs) through
    the mesh persistent step vs the hashlib oracle — the mesh twin of
    test_search_differential_fuzz_*."""
    import random

    rng = random.Random(0xD1CE)
    lens = [0, 2, 55, 63, 64, 100, 112]
    for _ in range(10):
        nonce = bytes(rng.randrange(256) for _ in range(rng.choice(lens)))
        difficulty = rng.randint(1, 2)
        size = rng.choice([1, 2, 4, 8, 64, 256])  # incl. < 8 devices
        lo = rng.randrange(0, 256 - size + 1, size)
        tbs = list(range(lo, lo + size))
        oracle = puzzle.python_search(nonce, difficulty, tbs)
        got = solve(nonce, difficulty, tbs, "mesh", batch_size=1 << 12)
        case = (nonce.hex()[:12], difficulty, lo, size)
        # same wrapped-alias tolerance as _fuzz_against_oracle: a
        # segment-overrun dispatch may legitimately return a verified
        # non-canonical secret (search.py batch-boundary note)
        assert got is not None, case
        assert got.secret == oracle or (
            got.chunk and got.chunk[-1] == 0
            and puzzle.check_secret(nonce, got.secret, difficulty)
        ), case
