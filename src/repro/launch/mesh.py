"""Mesh construction — production pods, Gram meshes, and simulated hosts.

Everything here is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.

The simulated-mesh helpers (:func:`host_device_flags`,
:func:`simulated_mesh_env`) exist because XLA's host-platform device count
is fixed at backend initialisation: a process that wants N fake CPU devices
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
jax initialises.  The multidevice tests therefore spawn CPU subprocesses
with the env these helpers build (see ``tests/conftest.py`` — the
``simulated_mesh`` fixture — and the ``multidevice`` CI job).  Nothing that
may hold a TPU starts such a child: the chip belongs to one process.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

#: the XLA flag that fakes N host (CPU) devices in one process
HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 single pod (16, 16) or 2 pods = 512 chips (2, 16, 16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the local device (CPU smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def gram_mesh_shape(n_devices: int) -> Tuple[int, int]:
    """Near-square ``(data, model)`` factorisation of ``n_devices``.

    The Gram engine tiles rows over ``data`` and columns over ``model``; a
    square-ish mesh minimises the replicated stream bytes per device
    (each device holds Bx/nd rows + By/nm columns of prepared streams).
    The larger factor goes to ``data`` — row tiles dominate when the
    symmetric fast path is active.  1 -> (1,1), 4 -> (2,2), 8 -> (4,2),
    12 -> (4,3), primes -> (p, 1).
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    best = 1
    for f in range(1, int(math.isqrt(n_devices)) + 1):
        if n_devices % f == 0:
            best = f
    return (n_devices // best, best)


def make_gram_mesh(n_devices: Optional[int] = None, *,
                   devices: Optional[Sequence] = None,
                   axis_names: Tuple[str, str] = ("data", "model")) -> Mesh:
    """A ``(data, model)`` mesh for the sharded Gram engine.

    Uses the first ``n_devices`` of ``devices`` (default: all local
    devices) arranged by :func:`gram_mesh_shape`.  Built from an explicit
    device array rather than :func:`jax.make_mesh` so *sub*-meshes over a
    device subset work — that is what lets one 8-device process prove
    1-vs-4-vs-8 shard-count invariance (see tests/test_distributed_gram.py).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(
            f"asked for {n_devices} devices, only {len(devices)} available"
            f" — spawn with XLA_FLAGS={HOST_DEVICE_FLAG}={n_devices} to "
            "simulate a host mesh (docs/api/public.md § Distributed Grams)")
    shape = gram_mesh_shape(n_devices)
    return Mesh(np.asarray(devices[:n_devices]).reshape(shape), axis_names)


def host_device_flags(n_devices: int = 8,
                      base: Optional[str] = None) -> str:
    """An ``XLA_FLAGS`` value forcing ``n_devices`` simulated host devices.

    Preserves every other flag already present in ``base`` (default: the
    current ``XLA_FLAGS``), replacing any existing
    ``--xla_force_host_platform_device_count`` — so callers can layer the
    simulated mesh on top of whatever XLA config the environment carries.
    """
    if base is None:
        base = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in base.split()
            if not f.startswith(HOST_DEVICE_FLAG + "=")]
    kept.append(f"{HOST_DEVICE_FLAG}={int(n_devices)}")
    return " ".join(kept)


def simulated_mesh_env(n_devices: int = 8, env=None) -> dict:
    """Environment dict for a subprocess that should see ``n_devices``
    simulated host devices (a copy — the caller's env is never mutated)."""
    out = dict(os.environ if env is None else env)
    out["XLA_FLAGS"] = host_device_flags(n_devices, out.get("XLA_FLAGS", ""))
    return out
