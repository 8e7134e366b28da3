import functools
import os
import subprocess
import sys

import pytest

# NOTE: XLA_FLAGS / device-count overrides are deliberately NOT set here —
# smoke tests and benches must see exactly 1 device.  Multi-device tests
# spawn subprocesses that set their own XLA_FLAGS (see test_distribution.py
# and the `simulated_mesh` fixture below).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROOT = os.path.join(os.path.dirname(__file__), "..")

_PROBE = (
    "import jax; ds = jax.devices(); "
    "assert len(ds) == 8, len(ds); print('MESH-OK')"
)


@functools.lru_cache(maxsize=1)
def _simulated_mesh_available() -> bool:
    """Can a subprocess on this host actually see 8 simulated CPU devices?

    Probes once per session by spawning the same way the tests do.  False
    on exotic jax builds where --xla_force_host_platform_device_count is
    ignored (e.g. a GPU-pinned backend) — the multidevice tier then skips
    gracefully instead of failing on an environment limitation.
    """
    from repro.launch.mesh import simulated_mesh_env
    try:
        r = subprocess.run(
            [sys.executable, "-c", _PROBE], capture_output=True, text=True,
            timeout=300, cwd=ROOT,
            env={**simulated_mesh_env(8), "PYTHONPATH": "src"})
    except (OSError, subprocess.TimeoutExpired):
        return False
    return "MESH-OK" in r.stdout


@pytest.fixture(scope="session")
def simulated_mesh():
    """Runner for programs on a simulated 8-device host mesh.

    XLA's host device count is fixed at backend init, so the program runs
    in a fresh subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (built by
    :func:`repro.launch.mesh.simulated_mesh_env`).  The returned callable
    takes python source, runs it, and asserts it prints ``OK``; the whole
    fixture skips when the host cannot simulate the mesh.
    """
    if not _simulated_mesh_available():
        pytest.skip("host cannot simulate an 8-device mesh "
                    "(--xla_force_host_platform_device_count ignored)")
    from repro.launch.mesh import simulated_mesh_env

    def run(prog: str, n_devices: int = 8, timeout: int = 900):
        r = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            timeout=timeout, cwd=ROOT,
            env={**simulated_mesh_env(n_devices), "PYTHONPATH": "src"})
        assert "OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]
        return r.stdout

    return run


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test module.

    XLA's CPU backend maps the code of every compiled program into the
    process; a test worker that runs several modules full of interpret-mode
    Pallas compiles would otherwise pass the kernel's memory-map limit
    (``vm.max_map_count``) and abort.
    """
    yield
    import jax
    jax.clear_caches()
