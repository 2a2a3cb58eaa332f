"""Worker compute backends.

The reference worker has exactly one compute path — a single-goroutine
byte-at-a-time loop (worker.go:318-400).  Here the miner is a pluggable
backend selected by ``WorkerConfig.Backend``:

* ``python``   — hashlib loop, the CPU behavioral-parity baseline
* ``jax``      — fused XLA search step on the default device (TPU when
                 present)
* ``jax-mesh`` — the XLA step under shard_map over all local devices,
                 prefix->core (parallel/mesh_search.py)
* ``pallas``   — hand-written TPU kernels for the hot op
                 (ops/md5_pallas.py: every _TILE_FNS model) on one device
* ``pallas-mesh`` — the same kernels spread over the local device mesh
                 (prefix->core + ``lax.pmin``, parallel/mesh_search.py)
* ``native``   — C++ miner via ctypes (backends/native/), the CPU
                 performance path (every ALGO_IDS model)
* ``auto``     — resolve from the hardware at boot: the Pallas kernel
                 backends on TPU (mesh when >1 local device), the XLA
                 backends where the platform was chosen as ``cpu``,
                 an error otherwise — see ``resolve_auto``

The four device backends are ``step_builder`` suppliers to one driver,
the chained persistent loop (parallel/search.py ``persistent_search``).
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


# One representative difficulty per mask-word compile bucket
# (ops/search_step.py mask_words_for: difficulties 1..8 share a program,
# 9..16 the next, ...).  Two buckets cover difficulty <= 16 nibbles
# (64 bits) — beyond any feasible puzzle; higher buckets compile on
# demand.
WARMUP_DIFFICULTIES = (1, 9)


def _warm_layouts(make_builder, model, nonce_lens, widths, batch_size,
                  max_launch, tbcs=(256,)) -> None:
    """Warm the programs ``persistent_search`` dispatches for every
    (partition, nonce length, mask-word bucket, width).

    ``make_builder(operands)`` is the backend's ``step_builder``.
    Everything else is derived the way the driver derives it
    (parallel/search.py): the request's operands bound before the probe
    (``RequestOperands``, so the pack has the served shape and
    placement), ``target_chunks`` from ``effective_batch`` with the same
    ``tbc``, each width's segment count from ``launch_steps_for`` with
    the same budget, and the single-device persistent step wherever the
    builder (or its absence) declines a width — which is what makes the
    warmed compile keys byte-identical to the ones serving dispatches.
    Each persistent warm dispatch runs behind ``CHAIN_HALT``: its
    on-device loop exits at its first check, so warming compiles the
    real program at near-zero device cost.
    """
    import numpy as np

    from ..ops.search_step import CHAIN_HALT
    from ..parallel.search import (
        RequestOperands,
        effective_batch,
        launch_steps_for,
    )
    from ..runtime.watchdog import FIRST_COMPILE_GRACE_S, WATCHDOG

    # one beat per compiled program, and a grace window around each
    # compile+dispatch: a single XLA compile cannot beat, and the
    # largest graphs (sha512's 64-bit limb emulation) have out-waited a
    # 420 s watchdog window on a HEALTHY device — without the grace,
    # arming DeviceHangTimeoutS would kill a sha512 worker during its
    # own boot warmup
    with WATCHDOG.active():
        for tbc in tbcs:
            target = max(1, effective_batch(batch_size) // tbc)
            for L in nonce_lens:
                nonce = bytes(int(L))
                for difficulty in WARMUP_DIFFICULTIES:
                    operands = RequestOperands(nonce, difficulty, 0, tbc,
                                               model)
                    builder = make_builder(operands)
                    for vw in map(int, widths):
                        WATCHDOG.beat()
                        k = launch_steps_for(vw, target, tbc, max_launch)
                        with WATCHDOG.grace(FIRST_COMPILE_GRACE_S):
                            if vw == 0:
                                int(operands.probe()(1))
                                continue
                            plan = (builder(vw, b"", target, k)
                                    if builder is not None else None)
                            bound = plan[0] if plan is not None else \
                                operands.persistent_step(vw, b"", target, k)
                            np.asarray(bound(1, CHAIN_HALT))


class JaxBackend:
    """The fused XLA search step behind the persistent driver
    (parallel/search.py ``persistent_search``).

    On one device every dispatch is the single-device persistent step.
    On a multi-device host the lane planner (sched/lanes.py
    ``persistent_step_builder``) serves the dispatches through the mesh
    persistent step; boot warmup covers the single-device programs.
    """

    name = "jax"

    def __init__(self, hash_model: str = "md5", batch_size: int = 1 << 20,
                 max_launch: Optional[int] = None, **_):
        from ..models.registry import get_hash_model

        self.model = get_hash_model(hash_model)
        self.batch_size = batch_size
        self.max_launch = _resolve_max_launch(max_launch, self.model)

    def _step_builder(self, operands):
        """The ``step_builder`` ``search`` hands the driver; None
        selects the single-device persistent step for every width."""
        from ..sched.lanes import persistent_step_builder

        return persistent_step_builder(operands)

    def warmup(self, nonce_lens: Sequence[int], widths: Sequence[int]) -> None:
        """Pre-compile the layout-keyed programs these nonce lengths hit.

        The dynamic regime (ops/search_step.py) keys compiles on (tail
        layout, batch) only, so warming with a zero nonce of the right
        length and the full 256-byte partition covers every future nonce
        of that length at any difficulty (one program per mask-word
        bucket, WARMUP_DIFFICULTIES) and any power-of-two partition.
        """
        _warm_layouts(lambda _: None, self.model, nonce_lens, widths,
                      self.batch_size, self.max_launch)

    def search(self, nonce, difficulty, thread_bytes, cancel_check=None):
        from ..parallel.search import persistent_search

        res = persistent_search(
            nonce,
            difficulty,
            thread_bytes,
            model=self.model,
            batch_size=self.batch_size,
            cancel_check=cancel_check,
            launch_candidates=self.max_launch,
            step_builder=self._step_builder,
        )
        return None if res is None else res.secret


class JaxMeshBackend(JaxBackend):
    """The XLA mesh persistent step over the local device mesh (prefix
    -> core, parallel/mesh_search.py ``mesh_persistent_factory``).

    A width whose segment batch does not divide across the mesh (a
    device count that is not a power of two) is served through the
    single-device persistent step, counted under
    ``fallback.lane_demotions``; boot warmup says so once.
    """

    name = "jax-mesh"

    def __init__(self, hash_model: str = "md5", batch_size: int = 1 << 20,
                 mesh_devices: int = 0, max_launch: Optional[int] = None,
                 **_):
        super().__init__(hash_model, batch_size, max_launch)
        self.mesh_devices = mesh_devices
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

    def _step_builder(self, operands):
        from ..parallel.mesh_search import mesh_persistent_factory
        from ..runtime.metrics import REGISTRY as metrics

        mesh_builder = mesh_persistent_factory(operands, self._get_mesh())

        def builder(vw, extra, target_chunks, segments):
            try:
                return mesh_builder(vw, extra, target_chunks, segments)
            except ValueError as exc:
                metrics.inc("fallback.lane_demotions")
                log.warning("%s: %s; serving width %d through the "
                            "single-device persistent step", self.name,
                            exc, vw)
                return None

        return builder

    def warmup(self, nonce_lens: Sequence[int], widths: Sequence[int]) -> None:
        """Compile the programs ``search`` serves, in both sharding
        regimes: a partition smaller than the device count selects the
        chunk-split regime, a compile key of its own in the Pallas mesh
        step, and one representative warms every such partition (the
        dispatch size does not depend on the partition)."""
        from ..parallel.search import effective_batch

        n_dev = int(self._get_mesh().devices.size)
        batch = effective_batch(self.batch_size)
        if batch % n_dev:
            log.warning(
                "%s: a segment batch of %d candidates does not divide "
                "across %d devices (not a power of two), so requests "
                "serve through the single-device persistent step",
                self.name, batch, n_dev)
        _warm_layouts(self._step_builder, self.model, nonce_lens, widths,
                      self.batch_size, self.max_launch,
                      tbcs=(256, n_dev // 2) if n_dev > 1 else (256,))


class PallasMeshBackend(JaxMeshBackend):
    """The Pallas kernel spread over the local device mesh; ``pallas``
    is this backend over a one-device mesh.

    Same prefix->core sharding and ``lax.pmin`` found-collective as
    ``jax-mesh``, but each device runs the hand-written kernel
    (ops/md5_pallas.py) in the chained Pallas mesh step
    (parallel/mesh_search.py ``pallas_mesh_persistent_step``): one
    compiled kernel program serves every device because the partition
    descriptor is a runtime SMEM operand, and a solve's dispatches stop
    on the device, on every chip, at the segment of its hit.  Requests
    and widths the kernel cannot express are served through the XLA
    mesh persistent step, counted under ``fallback.pallas_mesh_step``.
    """

    name = "pallas-mesh"

    def __init__(self, *args, interpret: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.interpret = interpret

    def _step_builder(self, operands):
        from ..parallel.mesh_search import pallas_mesh_persistent_factory
        from ..runtime.metrics import REGISTRY as metrics

        xla_builder = super()._step_builder(operands)
        try:
            pallas_builder = pallas_mesh_persistent_factory(
                operands, self._get_mesh(), interpret=self.interpret,
            )
        except ValueError as exc:
            metrics.inc("fallback.pallas_mesh_step")
            log.warning("%s: %s; serving via the XLA mesh step", self.name,
                        exc)
            return xla_builder

        def builder(vw, extra, target_chunks, segments):
            try:
                return pallas_builder(vw, extra, target_chunks, segments)
            except ValueError as exc:
                metrics.inc("fallback.pallas_mesh_step")
                log.warning("%s: %s; serving width %d via the XLA mesh "
                            "step", self.name, exc, vw)
                return xla_builder(vw, extra, target_chunks, segments)

        return builder


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
    if name in ("pallas", "pallas-mesh"):
        if name == "pallas":
            # one code path: the kernel over a one-device mesh
            kwargs["mesh_devices"] = 1
        backend = PallasMeshBackend(**kwargs)
        backend.name = name
        return backend
    if name == "native":
        from .native_miner import NativeBackend

        return NativeBackend(**kwargs)
    raise ValueError(f"unknown worker backend {name!r}")
