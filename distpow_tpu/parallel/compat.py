"""The mesh/sharding API surface every mesh call site imports
(parallel/mesh_search.py, the lane planner, bench.py's CPU arms).

Written for the installed JAX: ``jax.shard_map`` (varying-axes checking
spelled ``check_vma``) and the ``jax_num_cpu_devices`` config option.  A JAX upgrade that moves one of
them is a change to this file alone.

``cpu_devices_env`` stays on the pre-init XLA host-device-count flag:
it builds the environment of a CPU-pinned CHILD process, which reads
the flag when its backend initializes.
"""

from __future__ import annotations

import os
import re
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; ``check_vma=False`` where a value's varying
    axes cannot be typed (a pallas_call output)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def request_cpu_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices in this process.  Raises
    RuntimeError when the CPU backend is already initialized (the
    caller's clear_backends discipline, see ``__graft_entry__``)."""
    jax.config.update("jax_num_cpu_devices", int(n))


_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def cpu_devices_env(n: int,
                    base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a SUBPROCESS that must boot on the CPU with ``n``
    virtual devices (bench.py --mesh-serving arms)."""
    env = dict(os.environ if base is None else base)
    flags = re.sub(rf"{_HOST_COUNT_FLAG}=\S+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = f"{flags} {_HOST_COUNT_FLAG}={int(n)}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env
