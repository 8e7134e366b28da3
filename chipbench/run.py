"""Chip benchmark of the signature library: one run of one cell.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration in ``chipbench/configs/<config>.json``,
the traffic in ``chipbench/traffic/<traffic>.json``, which names its unit
``chipbench/units/<unit>.py``, and each metric's reader in
``chipbench/metrics/<name up to its first dot>.py``.

A run keeps JAX's compile cache at ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), makes its data on the device from the
seed, builds and warms up the cell's own programs, then times whole units,
each ending in ``block_until_ready``, until ``--seconds`` have passed.
With ``--trace 1`` the window is traced and the per-layer metrics are read
from the trace.  After the window the unit is checked against the plain
reference.  The last stdout line is one JSON object; the numbers compared
are the last lines on stderr.  With no TPU, or fewer chips than the cell
asks for, the run exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from chipbench import device  # noqa: E402

#: monitoring events that mean a program was compiled or loaded from cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``metric`` is reported in ``cell``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    bench = read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    e2e = [m for m in bench["end_to_end"] if applies(m, workload, None)]
    names = {m["name"] for m in e2e}
    return {
        "cell": cell,
        "config": read_json(root, "chipbench", "configs",
                            cell["config"] + ".json"),
        "traffic": read_json(root, "chipbench", "traffic",
                             cell["traffic"] + ".json"),
        "end_to_end": e2e,
        "per_layer": [m for m in bench["per_layer"]
                      if applies(m, workload, names)],
    }


def reader(name: str):
    """The ``read(ctx, variant)`` function of metric ``name``."""
    base, _, variant = name.partition(".")
    mod = importlib.import_module(f"chipbench.metrics.{base}")
    return lambda ctx: mod.read(ctx, variant or None)


def enable_compile_cache(root: str) -> str:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded until ``close``."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, name, _secs, **_kw):
        self._event(name)

    def _event(self, name, **_kw):
        if name in COMPILE_EVENTS:
            self.count += 1


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block into a fresh directory; yields a getter for the
    reduced trace, valid once the block has ended."""
    if not enabled:
        yield lambda: None
        return
    from chipbench import trace_reduce
    out = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    result = {}
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        yield lambda: result["trace"]
    finally:
        jax.profiler.stop_trace()
        try:
            paths = [os.path.join(d, f) for d, _, fs in os.walk(out)
                     for f in fs if f.endswith(".xplane.pb")]
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, got {paths}")
            result["trace"] = trace_reduce.load(paths[0])
        finally:
            shutil.rmtree(out, ignore_errors=True)


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_chip: bool = True, overrides=None,
        t_start: float = T_START) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``require_chip=False`` and ``overrides`` (keys replaced in the
    configuration) exist for the tests, which drive a run on the CPU.
    """
    spec = load_cell(root, workload)
    cell, cfg = spec["cell"], dict(spec["config"], **(overrides or {}))
    chips = cell["chips"]
    devices = device.require(chips) if require_chip else jax.devices()[:chips]
    log(f"[device] {device.describe(devices)}, jax {jax.__version__}")
    log(f"[setup] compile cache {enable_compile_cache(root)}")
    from repro.core import dispatch
    for op in ("gram", "sigkernel"):
        log(f"[setup] backend {cfg['backend']!r} resolves to "
            f"{dispatch.resolve(cfg['backend'], op=op)!r} for op {op!r}")
    unit_mod = importlib.import_module(
        f"chipbench.units.{spec['traffic']['unit']}")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        unit = unit_mod.Unit(cfg, spec["traffic"], seed, devices)
        setup_s = time.perf_counter() - t_start
        counter = CompileCounter()
        with traced(trace) as reduced:
            t0 = time.perf_counter()
            n = 0
            while True:
                with (jax.profiler.TraceAnnotation("chipbench.unit")
                      if trace else contextlib.nullcontext()):
                    unit.run()
                n += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        counter.close()
        log(f"[window] {n} units in {window_s:.6f} s, "
            f"{counter.count} programs compiled or loaded in the window")
        memory = device.memory_peak_bytes(devices)
        attempted, failed = unit.outcome()
        t_check = time.perf_counter()
        readings = unit.check()
        log(f"[check] the comparison with the reference took "
            f"{time.perf_counter() - t_check:.3f} s")

    ctx = Context(setup_s=setup_s, window_s=window_s, units=n,
                  work=unit.work, trace=reduced(), devices=devices,
                  peaks=device.peaks(devices[0]) if require_chip else None,
                  log=log)
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
    info = dict(device.describe(devices), memory_peak_bytes=memory)
    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": info}
    if trace:
        t = ctx.trace
        busy = t.busy()
        info.update(busy_s=sum(busy) / len(busy), window_s=t.window_s)
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in sorted(readings.items())}
    result["correct"] = bool(failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except device.NoChip as e:
        log(f"chipbench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
