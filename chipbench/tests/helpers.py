"""Shared set-up of the benchmark's CPU tests."""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: cells cut to a size a CPU test run holds; limits stay as committed
SMALL = {"mmd_train": {"paths_per_side": 8, "length": 10,
                       "reference_rows": 2},
         "gram2s_4chip": {"paths": 16, "length": 12}}


@contextlib.contextmanager
def no_compile_cache():
    """Keep the tests' programs out of the checkout's compile cache, and
    leave the cache settings (which a run sets) as they were."""
    import jax
    before = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def run_small(workload: str, seed: int, seconds: float = 0.2,
              **overrides) -> dict:
    """A whole run of ``workload`` on the CPU at the tests' size, with the
    harness's look for a chip skipped."""
    from chipbench import run
    with no_compile_cache():
        return run.run(workload, seed, seconds, False, require_chip=False,
                       overrides=dict(SMALL[workload], **overrides))
