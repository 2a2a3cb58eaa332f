"""Worker compute backends.

The reference worker has exactly one compute path — a single-goroutine
byte-at-a-time loop (worker.go:318-400).  Here the miner is a pluggable
backend selected by ``WorkerConfig.Backend``:

* ``python``   — hashlib loop, the CPU behavioral-parity baseline
* ``jax``      — fused XLA search step on the default device (TPU when
                 present), batched + pipelined (parallel/search.py)
* ``jax-mesh`` — shard_map over all local devices, prefix->core
                 (parallel/mesh_search.py)
* ``pallas``   — hand-written TPU kernels for the hot op
                 (ops/md5_pallas.py: every _TILE_FNS model) behind the
                 same driver
* ``pallas-mesh`` — the same kernels spread over the local device mesh
                 (prefix->core + ``lax.pmin``, parallel/mesh_search.py)
* ``native``   — C++ miner via ctypes (backends/native/), the CPU
                 performance path (every ALGO_IDS model)
* ``auto``     — resolve from the hardware at boot: the Pallas kernel
                 backends on TPU (mesh when >1 local device), the XLA
                 backends where the platform was chosen as ``cpu``,
                 an error otherwise — see ``resolve_auto``

Every backend implements ``search(nonce, difficulty, thread_bytes,
cancel_check) -> Optional[bytes]`` returning the first solving secret in
reference enumeration order, or None when cancelled.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

from ..models import puzzle

log = logging.getLogger("distpow.backends")


class PythonBackend:
    """Reference-parity CPU loop (worker.go:318-400 minus string formatting)."""

    name = "python"

    def __init__(self, hash_model: str = "md5", **_):
        self.hash_model = hash_model

    def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
        from ..runtime.metrics import REGISTRY as metrics

        def count_exit(reason: str) -> None:
            # the loop reports why it exited; re-evaluating cancel_check
            # here would misclassify budget exhaustion as a cancel when
            # the condition flipped after the loop stopped (and would
            # re-trigger the check's side effects)
            if reason != "exhausted":
                metrics.inc(f"search.{reason}")

        return puzzle.python_search(
            nonce,
            difficulty,
            thread_bytes,
            algo=self.hash_model,
            cancel_check=cancel_check,
            cancel_poll_interval=1024,
            on_progress=lambda n: metrics.inc("search.hashes", n),
            on_exit=count_exit,
        )


def _resolve_max_launch(max_launch: Optional[int], model) -> int:
    """One home for the backend default budget: explicit config wins;
    otherwise the model's cost-scaled budget (review r4: this
    expression was copy-pasted into three constructors)."""
    from ..parallel.search import scaled_launch_candidates

    return max_launch or scaled_launch_candidates(model.cost_ops)


def _warm_factory(factory, widths, target_chunks, tbc, max_launch) -> None:
    """Compile-and-dispatch each width's step once (tiny real launch)."""
    from ..parallel.search import launch_steps_for
    from ..runtime.watchdog import FIRST_COMPILE_GRACE_S, WATCHDOG

    # one beat per compiled program, and a grace window around each
    # compile+dispatch: a single XLA compile cannot beat, and the
    # largest graphs (sha512's 64-bit limb emulation) have out-waited a
    # 420 s watchdog window on a HEALTHY device (r4 hardware session) —
    # without the grace, arming DeviceHangTimeoutS would kill a sha512
    # worker during its own boot warmup
    with WATCHDOG.active():
        for vw in widths:
            WATCHDOG.beat()
            k = launch_steps_for(int(vw), target_chunks, tbc, max_launch)
            with WATCHDOG.grace(FIRST_COMPILE_GRACE_S):
                step, _ = factory(int(vw), b"", target_chunks, k)
                int(step(1))  # block_until_ready via the int() conversion


# One representative difficulty per mask-word compile bucket
# (ops/search_step.py mask_words_for: difficulties 1..8 share a program,
# 9..16 the next, ...).  Two buckets cover difficulty <= 16 nibbles
# (64 bits) — beyond any feasible puzzle; higher buckets compile on
# demand.
WARMUP_DIFFICULTIES = (1, 9)


def _warm_layouts(build, nonce_lens, widths, batch_size, tbc=256,
                  max_launch=None) -> None:
    """Warm the layout-keyed programs for every (nonce length, width,
    mask-word bucket).

    ``build(nonce, tbc, difficulty) -> StepFactory`` builds the factory
    for the full partition ``[0, tbc)``.  ``target_chunks`` and the
    per-width launch multiplier are derived exactly the way the serving
    path derives them (parallel/search.py: ``effective_batch`` with the
    same ``tbc``, ``launch_steps_for`` with the same budget) — which is
    what makes the warmed compile keys byte-identical to the ones serving
    dispatches.
    """
    from ..parallel.search import DEFAULT_LAUNCH_CANDIDATES, effective_batch

    if max_launch is None:
        max_launch = DEFAULT_LAUNCH_CANDIDATES
    target = max(1, effective_batch(batch_size) // tbc)
    for L in nonce_lens:
        for difficulty in WARMUP_DIFFICULTIES:
            _warm_factory(build(bytes(int(L)), tbc, difficulty), widths,
                          target, tbc, max_launch)


class JaxBackend:
    """Single-device fused-step search (the TPU path).

    ``loop`` selects the serving loop (docs/SERVING.md):
    ``"persistent"`` (default) drives the multi-segment on-device loop
    with the polling drain (parallel/search.py persistent_search);
    ``"serial"`` keeps the pre-PR-6 launch/fetch/relaunch loop — the
    bench baseline (``bench.py --serving-loop``) and the escape hatch.
    """

    name = "jax"

    def __init__(self, hash_model: str = "md5", batch_size: int = 1 << 20,
                 max_launch: Optional[int] = None,
                 loop: str = "persistent", **_):
        from ..models.registry import get_hash_model

        self.model = get_hash_model(hash_model)
        self.batch_size = batch_size
        self.max_launch = _resolve_max_launch(max_launch, self.model)
        if loop not in ("persistent", "serial"):
            raise ValueError(
                f"unknown search loop {loop!r}: expected 'persistent' "
                f"or 'serial'"
            )
        self.loop = loop

    def _persistent_warm_factory(self, nonce: bytes, tbc: int,
                                 difficulty: int):
        """StepFactory-shaped builder over the persistent step, so the
        shared ``_warm_layouts`` derivation (same target/k/mask-bucket
        keys as serving) warms the persistent programs too.  The warmup
        dispatch is chained behind ``CHAIN_HALT``: the on-device loop
        exits at its first condition check, so warming compiles the
        real program at near-zero device cost."""
        import numpy as np

        from ..ops.search_step import (
            CHAIN_HALT,
            cached_persistent_step,
            cached_search_step,
        )

        model_name = self.model.name

        def factory(vw, extra, target_chunks, launch_steps=1):
            if vw == 0:
                step = cached_search_step(
                    nonce, 0, difficulty, 0, tbc, 1, model_name, extra, 1
                )
                return step, 1
            bound = cached_persistent_step(
                nonce, vw, difficulty, 0, tbc, target_chunks, model_name,
                extra, launch_steps,
            )
            return (lambda chunk0: np.asarray(bound(chunk0, CHAIN_HALT))[0]), \
                target_chunks * launch_steps

        return factory

    def warmup(self, nonce_lens: Sequence[int], widths: Sequence[int]) -> None:
        """Pre-compile the layout-keyed programs these nonce lengths hit.

        The dynamic regime (ops/search_step.py) keys compiles on (tail
        layout, batch) only, so warming with a zero nonce of the right
        length and the full 256-byte partition covers every future nonce
        of that length at any difficulty (one program per mask-word
        bucket, WARMUP_DIFFICULTIES) and any power-of-two partition.
        The warmed programs follow the configured loop: the persistent
        step's compile keys differ from the relaunch step's.
        """
        from ..parallel.search import default_step_factory

        if self.loop == "persistent":
            build = self._persistent_warm_factory
        else:
            def build(nonce, tbc, d):
                return default_step_factory(nonce, d, 0, tbc, self.model)

        _warm_layouts(
            build,
            nonce_lens, widths, self.batch_size, max_launch=self.max_launch,
        )

    def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
        from ..parallel.search import persistent_search, search

        kwargs = {}
        if self.loop == "persistent":
            drive = persistent_search
            # launch-lane planning (sched/lanes.py): on a multi-device
            # host the persistent dispatches serve through the mesh
            # persistent step — byte-identical results, n_dev x the
            # per-dispatch coverage.  Single device resolves to None
            # and this stays the classic single-device loop.
            from ..parallel.partition import contiguous_bounds
            from ..sched.lanes import persistent_step_builder

            tb_lo, tbc = contiguous_bounds(thread_bytes)
            kwargs["step_builder"] = persistent_step_builder(
                bytes(nonce), difficulty, tb_lo, tbc, self.model
            )
        else:
            drive = search
        res = drive(
            nonce,
            difficulty,
            thread_bytes,
            model=self.model,
            batch_size=self.batch_size,
            cancel_check=cancel_check,
            launch_candidates=self.max_launch,
            **kwargs,
        )
        return None if res is None else res.secret


class JaxMeshBackend:
    """shard_map over the local device mesh (prefix -> core)."""

    name = "jax-mesh"

    def __init__(
        self,
        hash_model: str = "md5",
        batch_size: int = 1 << 20,
        mesh_devices: int = 0,
        max_launch: Optional[int] = None,
        **_,
    ):
        from ..models.registry import get_hash_model

        self.model = get_hash_model(hash_model)
        self.batch_size = batch_size
        self.mesh_devices = mesh_devices
        self.max_launch = _resolve_max_launch(max_launch, self.model)
        self._mesh = None

    def _get_mesh(self):
        if self._mesh is None:
            import jax

            from ..parallel.mesh_search import make_mesh

            devs = jax.devices()
            if self.mesh_devices:
                devs = devs[: self.mesh_devices]
            self._mesh = make_mesh(devs)
        return self._mesh

    def _step_factory(self, nonce: bytes, difficulty: int, tb_lo: int,
                      tbc: int):
        """Step-factory hook — the ONLY thing kernel-backed mesh
        subclasses override; warmup and search both build through it, so
        compile-key discipline is inherited, not duplicated."""
        from ..parallel.mesh_search import AXIS, _mesh_step_factory

        return _mesh_step_factory(
            nonce, difficulty, tb_lo, tbc, self.model, self._get_mesh(), AXIS
        )

    def warmup(self, nonce_lens: Sequence[int], widths: Sequence[int]) -> None:
        mesh = self._get_mesh()
        n_dev = int(mesh.devices.size)
        if n_dev & (n_dev - 1):
            # non-power-of-two mesh: the factory compiles nonce-content-
            # keyed static programs that cannot be reused by later
            # requests — warming them would burn compile time for nothing.
            # Warn loudly at boot (VERDICT r2 weak #5): every fresh nonce
            # on this mesh will pay a multi-second compile stall at
            # request time (mesh_search.build_static logs again there).
            log.warning(
                "mesh warmup skipped: %d devices is not a power of two, "
                "so search programs are nonce-keyed and compile per "
                "request (multi-second stall per fresh nonce); use a "
                "power-of-two device count for warmed zero-recompile "
                "serving", n_dev)
            return

        def build(nonce, tbc, difficulty):
            return self._step_factory(nonce, difficulty, 0, tbc)

        _warm_layouts(build, nonce_lens, widths, self.batch_size,
                      max_launch=self.max_launch)
        if n_dev > 1:
            # a partition smaller than the device count selects the
            # chunk-split regime (tb_split=False), a distinct compile key;
            # one representative tbc < n_dev warms it for every pow2
            # partition because batch_local is the 256-normalized
            # per-device budget in all of them (mesh_search.py factory)
            _warm_layouts(build, nonce_lens, widths, self.batch_size,
                          tbc=n_dev // 2, max_launch=self.max_launch)

    def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
        from ..parallel.mesh_search import search_mesh
        from ..parallel.partition import contiguous_bounds

        nonce = bytes(nonce)
        tb_lo, tbc = contiguous_bounds(thread_bytes)
        res = search_mesh(
            nonce,
            difficulty,
            thread_bytes,
            mesh=self._get_mesh(),
            model=self.model,
            batch_size=self.batch_size,
            cancel_check=cancel_check,
            launch_candidates=self.max_launch,
            step_factory=self._step_factory(nonce, difficulty, tb_lo, tbc),
        )
        return None if res is None else res.secret


class PallasMeshBackend(JaxMeshBackend):
    """The Pallas kernel spread over the local device mesh.

    Same prefix->core sharding and ``lax.pmin`` found-collective as
    ``jax-mesh``, but each device runs the hand-written kernel
    (ops/md5_pallas.py) instead of the fused XLA step — one compiled
    kernel program serves every device because the partition descriptor
    is a runtime SMEM operand.  ``search`` drives the chained persistent
    loop (parallel/search.py persistent_search) through the chained
    Pallas mesh step (parallel/mesh_search.py
    pallas_mesh_persistent_step), so a solve's dispatches stop on the
    device, on every chip, at the segment of its hit.  Requests and
    widths the kernel cannot express are served through the XLA mesh
    persistent step, counted under ``fallback.pallas_mesh_step``.
    ``_step_factory`` keeps the relaunch form for library callers of
    ``search_mesh``.
    """

    name = "pallas-mesh"

    def __init__(self, *args, interpret: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.interpret = interpret

    def _persistent_builder(self, nonce: bytes, difficulty: int, tb_lo: int,
                            tbc: int):
        """The ``step_builder`` ``search`` serves through: the chained
        Pallas mesh step per width, else the XLA mesh persistent step,
        else (a segment batch that does not divide across the mesh) None,
        the driver's single-device persistent step."""
        from ..parallel.mesh_search import (
            AXIS,
            mesh_persistent_factory,
            pallas_mesh_persistent_factory,
        )
        from ..runtime.metrics import REGISTRY as metrics

        mesh = self._get_mesh()
        xla_builder = mesh_persistent_factory(
            nonce, difficulty, tb_lo, tbc, self.model, mesh, AXIS)
        try:
            pallas_builder = pallas_mesh_persistent_factory(
                nonce, difficulty, tb_lo, tbc, self.model, mesh, AXIS,
                interpret=self.interpret,
            )
        except ValueError as exc:
            metrics.inc("fallback.pallas_mesh_step")
            log.warning("pallas-mesh: %s; serving via the XLA mesh step",
                        exc)
            pallas_builder = None

        def builder(vw, extra, target_chunks, segments):
            if pallas_builder is not None:
                try:
                    return pallas_builder(vw, extra, target_chunks, segments)
                except ValueError as exc:
                    metrics.inc("fallback.pallas_mesh_step")
                    log.warning("pallas-mesh: %s; serving width %d via the "
                                "XLA mesh step", exc, vw)
            try:
                return xla_builder(vw, extra, target_chunks, segments)
            except ValueError:
                return None

        return builder

    def warmup(self, nonce_lens: Sequence[int], widths: Sequence[int]) -> None:
        """Compile the programs ``search`` serves, in both sharding
        regimes and both mask-word buckets, through ``_warm_layouts``'s
        derivation of the served compile keys.  Each warm dispatch runs
        behind ``CHAIN_HALT``: its loop exits at segment 0."""
        import numpy as np

        from ..ops.search_step import (
            CHAIN_HALT,
            cached_persistent_step,
            cached_search_step,
        )

        model_name = self.model.name

        def build(nonce, tbc, difficulty):
            builder = self._persistent_builder(nonce, difficulty, 0, tbc)

            def factory(vw, extra, target_chunks, launch_steps=1):
                if vw == 0:
                    return cached_search_step(
                        nonce, 0, difficulty, 0, tbc, 1, model_name, extra, 1
                    ), 1
                plan = builder(vw, extra, target_chunks, launch_steps)
                if plan is None:
                    bound = cached_persistent_step(
                        nonce, vw, difficulty, 0, tbc, target_chunks,
                        model_name, extra, launch_steps,
                    )
                    chunks = target_chunks * launch_steps
                else:
                    bound, _, chunks = plan
                return (lambda chunk0:
                        np.asarray(bound(chunk0, CHAIN_HALT))[0]), chunks

            return factory

        _warm_layouts(build, nonce_lens, widths, self.batch_size,
                      max_launch=self.max_launch)
        n_dev = int(self._get_mesh().devices.size)
        if n_dev > 1:
            # a partition smaller than the device count selects the
            # chunk-split regime, a compile key of its own; the dispatch
            # size does not depend on the partition, so one
            # representative warms every such partition
            _warm_layouts(build, nonce_lens, widths, self.batch_size,
                          tbc=n_dev // 2, max_launch=self.max_launch)

    def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
        from ..parallel.partition import contiguous_bounds
        from ..parallel.search import persistent_search

        nonce = bytes(nonce)
        tb_lo, tbc = contiguous_bounds(thread_bytes)
        res = persistent_search(
            nonce,
            difficulty,
            thread_bytes,
            model=self.model,
            batch_size=self.batch_size,
            cancel_check=cancel_check,
            launch_candidates=self.max_launch,
            step_builder=self._persistent_builder(nonce, difficulty, tb_lo,
                                                  tbc),
        )
        return None if res is None else res.secret

    def _step_factory(self, nonce: bytes, difficulty: int, tb_lo: int,
                      tbc: int):
        from ..parallel.mesh_search import AXIS, _pallas_mesh_step_factory
        from ..runtime.metrics import REGISTRY as metrics

        xla_factory = super()._step_factory(nonce, difficulty, tb_lo, tbc)
        try:
            pallas_factory = _pallas_mesh_step_factory(
                nonce, difficulty, tb_lo, tbc, self.model, self._get_mesh(),
                AXIS, interpret=self.interpret, max_launch=self.max_launch,
            )
        except ValueError as exc:
            metrics.inc("fallback.pallas_mesh_step")
            log.warning("pallas-mesh: %s; serving via the XLA mesh step",
                        exc)
            return xla_factory

        def factory(vw, extra, target_chunks, launch_steps=1):
            try:
                return pallas_factory(vw, extra, target_chunks, launch_steps)
            except ValueError as exc:
                metrics.inc("fallback.pallas_mesh_step")
                log.warning("pallas-mesh: %s; serving width %d via the "
                            "XLA mesh step", exc, vw)
                return xla_factory(vw, extra, target_chunks, launch_steps)

        return factory


def device_info(backend) -> dict:
    """What the backend serves on, as JAX reports it: ``platform``,
    ``device_kind`` and ``device_count`` (worker Stats and boot log).
    The host-only backends (``python``, ``native``) never import jax,
    so they report no device."""
    if backend.name in ("python", "native"):
        return {"platform": None, "device_kind": None, "device_count": 0}
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def resolve_auto() -> str:
    """The backend ``auto`` names on this host.

    On TPU the Pallas kernel backends serve (docs/KERNELS.md: they win
    for every model, and the 64-bit limb models' fused-XLA serving
    steps are impractical to compile there), the mesh variant when the
    host has more than one device.  Off TPU the XLA backends serve, but
    only when the platform was CHOSEN as ``cpu`` (``JAX_PLATFORMS`` or
    the ``jax_platforms`` config, as the tests do): JAX also lands on
    the CPU when it cannot open the chip — a second process holding it,
    a broken runtime — and a worker must not serve from the CPU then
    without anyone asking for it.  ``jax.devices()`` is the GLOBAL list
    (the worker runs maybe_init_distributed first), which is the right
    mesh-vs-single signal: the mesh backends span the global device
    set.
    """
    import jax

    platform = jax.default_backend()
    multi = len(jax.devices()) > 1
    if platform == "tpu":
        name = "pallas-mesh" if multi else "pallas"
    else:
        chosen = {p for p in (jax.config.jax_platforms or "").split(",")
                  if p}
        if chosen != {"cpu"}:
            raise RuntimeError(
                f"backend auto found no TPU (platform {platform!r}) and "
                f"the platform was not chosen as cpu; JAX falls back to "
                f"the CPU when it cannot open the chip (another process "
                f"may hold it). Set JAX_PLATFORMS=cpu to serve from the "
                f"CPU on purpose.")
        name = "jax-mesh" if multi else "jax"
    log.info("backend auto -> %s (platform=%s, %d global device(s))",
             name, platform, len(jax.devices()))
    return name


def get_backend(name: str, **kwargs):
    name = (name or "jax").lower()
    if name == "auto":
        name = resolve_auto()
    if name == "python":
        return PythonBackend(**kwargs)
    if name == "jax":
        return JaxBackend(**kwargs)
    if name in ("jax-mesh", "mesh"):
        return JaxMeshBackend(**kwargs)
    if name == "pallas-mesh":
        return PallasMeshBackend(**kwargs)
    if name == "pallas":
        from .pallas_backend import PallasBackend

        return PallasBackend(**kwargs)
    if name == "native":
        from .native_miner import NativeBackend

        return NativeBackend(**kwargs)
    raise ValueError(f"unknown worker backend {name!r}")
