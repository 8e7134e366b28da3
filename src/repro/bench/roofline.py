"""Roofline attribution for bench entries: achieved vs. peak FLOPs/bandwidth.

The resurrection of the seed's ``benchmarks/roofline.py``, rebuilt around
the bench suite: every timed BENCH entry carries a ``"roofline"`` dict so a
launch-parameter tuning win (or regression) can be attributed to the
compute- vs. memory-bound regime it happened in rather than guessed.

Two FLOP/byte estimators, in preference order:

* :func:`hlo_counts` — lower + compile the actual benched callable and run
  the trip-count-corrected HLO analysis of
  :mod:`repro.launch.hlo_analysis` (dot FLOPs **plus** the new elementwise
  ``arith_flops``, which dominate the scan-heavy Goursat PDE kernels);
  bytes from XLA's cost analysis with an input+output-buffer fallback.
* :func:`analytic_counts` — closed-form per-op estimates from the entry's
  ``meta`` (op, B, L, d, depth), used when no callable is available
  (checks) or when lowering fails.  Documented lower
  bounds, same spirit as the seed's ``sig_model_flops``.

Peaks come from :func:`peaks`: a TPU reads the :data:`TPU_PEAKS` table by
``device_kind`` (an unknown TPU raises); CPU/GPU run two tiny **measured**
probes once per process (a matmul for peak FLOP/s, a copy for bandwidth).

The estimators are fail-open and non-gating: a count that cannot be
computed degrades to fewer keys, and ``compare.py`` only ever *reports*
achieved-fraction deltas.

CLI::

    PYTHONPATH=src python -m repro.bench.roofline BENCH_PR10.json

prints a markdown summary table (the CI perf-smoke artifact) and exits 0
even when entries carry no roofline data (older JSONs).
"""

from __future__ import annotations

import json
import math
import sys
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import timer

#: Per-chip peaks of the TPUs this repo targets, keyed by
#: ``jax.devices()[0].device_kind``.  ``flops`` is the MXU bf16 peak,
#: ``vpu_flops`` the f32 vector-unit peak that bounds the PDE wavefront.
TPU_PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "flops": 197e12,
        "vpu_flops": 8 * 128 * 4 * 1.5e9,
        "bandwidth": 819e9,
        "source": "Google Cloud 'TPU v5e' documentation (197 TFLOP/s bf16, "
                  "819 GB/s HBM); VPU f32 derived as 8x128 lanes x 4 VALU "
                  "slots x 1.5 GHz (clock = 197e12 / (4 MXU x 128^2 x 2); "
                  "the VALU count is not from a datasheet)",
    },
}

#: elementwise VPU flops per refined PDE cell (the 2nd-order Goursat
#: update: two poly evals in the Δ term + 3 multiply-adds)
_PDE_FLOPS_PER_CELL = 10.0

_peaks_memo: Optional[Dict] = None


def _measured_peaks() -> Dict[str, float]:
    """Matmul + copy probes: order-of-magnitude peaks for CPU/GPU hosts."""
    n = 512
    a = jnp.full((n, n), 1.0 / n, jnp.float32)

    @jax.jit
    def mm(x):
        return x @ x

    t_mm = timer.bench(mm, a, repeats=3, warmup=1)
    flops = 2.0 * n ** 3 / max(t_mm, 1e-9)

    big = jnp.zeros((32, 1 << 20), jnp.float32)  # 128 MiB

    @jax.jit
    def cp(x):
        return x + 1.0

    t_cp = timer.bench(cp, big, repeats=3, warmup=1)
    bw = 2.0 * big.size * 4 / max(t_cp, 1e-9)  # read + write
    return {"flops": flops, "vpu_flops": flops, "bandwidth": bw,
            "source": "measured"}


def peaks() -> Dict:
    """Per-device peak FLOP/s + bytes/s (memoised once per process).

    TPUs read :data:`TPU_PEAKS`; a TPU missing from it is an error, not a
    guess.  CPU/GPU hosts get the measured probes.
    """
    global _peaks_memo
    if _peaks_memo is None:
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            if dev.device_kind not in TPU_PEAKS:
                raise KeyError(
                    f"no peak table entry for TPU device_kind "
                    f"{dev.device_kind!r}; add it to TPU_PEAKS with its "
                    f"source")
            _peaks_memo = TPU_PEAKS[dev.device_kind]
        else:
            _peaks_memo = _measured_peaks()
    return _peaks_memo


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

_hlo_memo: Dict = {}


def hlo_counts(fn, *args, key=None) -> Optional[Tuple[float, float]]:
    """(flops, bytes) for ``fn(*args)`` from the compiled HLO, or None.

    FLOPs are the trip-count-corrected dot + elementwise total from
    :func:`repro.launch.hlo_analysis.analyze` — XLA's own cost analysis
    counts while-loop bodies once, which undercounts the scanned Goursat
    wavefront by ~the antidiagonal count.  Bytes prefer XLA's
    ``bytes accessed`` and fall back to input+output buffer sizes.
    Memoised on ``key`` (pass the entry's stable name + shape) because a
    lower+compile per call is the expensive part of the estimate.
    """
    if key is not None and key in _hlo_memo:
        return _hlo_memo[key]
    out: Optional[Tuple[float, float]]
    try:
        from repro.launch.hlo_analysis import analyze
        try:
            lowered = fn.lower(*args)       # already-jitted callable
            jitted = fn
        except AttributeError:
            jitted = jax.jit(fn)
            lowered = jitted.lower(*args)
        compiled = lowered.compile()
        st = analyze(compiled.as_text())
        io_bytes = 0.0
        for a in jax.tree_util.tree_leaves(args):
            if hasattr(a, "size") and hasattr(a, "dtype"):
                io_bytes += float(a.size) * jnp.dtype(a.dtype).itemsize
        for s in jax.tree_util.tree_leaves(jax.eval_shape(jitted, *args)):
            io_bytes += float(math.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        nbytes = io_bytes
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            accessed = float(cost.get("bytes accessed", 0.0))
            nbytes = max(accessed, io_bytes)
        except Exception:
            pass
        out = (float(st.total_flops), float(nbytes))
    except Exception:
        out = None
    if key is not None:
        _hlo_memo[key] = out
    return out


def analytic_counts(name: str, meta: dict) -> Optional[Tuple[float, float]]:
    """Closed-form (flops, bytes) lower bound from an entry's meta, or None.

    Per-op models (f32 bytes; ``grad``/``bwd`` entries pay 3× — forward +
    adjoint sweep + cotangent accumulation):

    * signature / logsignature — Horner touches each of the ``sig_dim``
      signature coordinates ~3× per path step;
    * sigkernel — one Δ matmul per pair (``2·L²·d``) + ~10 VPU flops per
      refined PDE cell; bytes stream Δ three times (write + fwd + solve);
    * gram / gram_reduce — the sigkernel model × ``B²`` pairs.
    """
    op = meta.get("op")
    if not isinstance(op, str):
        if name.startswith("calibration_matmul_scan"):
            return 32 * 2.0 * 256 ** 3, 3 * 256 * 256 * 4.0
        return None
    mult = 3.0 if ("bwd" in name or "grad" in name) else 1.0
    lam = int(meta.get("lam", 0))
    bshape = meta.get("shape")
    if "L" not in meta and isinstance(bshape, (list, tuple)):
        # autotune entries carry the per-op cache-key shape instead of
        # B/L/d: sigkernel (nx, ny, d) at the fixed tuning batch, gram
        # (Bx, By, nx, ny, d) — the grid dims are already refined
        try:
            if op == "sigkernel" and len(bshape) == 3:
                nx, ny, d = bshape
                from .autotune import _TUNE_BATCH
                per = 2.0 * nx * ny * d + _PDE_FLOPS_PER_CELL * nx * ny
                return _TUNE_BATCH * per * mult, \
                    4.0 * _TUNE_BATCH * (2 * nx * d + 3 * nx * ny)
            if op == "gram" and len(bshape) == 5:
                bx, by, nx, ny, d = bshape
                per = 2.0 * nx * ny * d + _PDE_FLOPS_PER_CELL * nx * ny
                return float(bx) * by * per * mult, \
                    4.0 * ((bx + by) * nx * d + bx * by * 3 * nx * ny)
        except (TypeError, ValueError):
            return None
        return None
    try:
        if op in ("signature", "logsignature"):
            from repro.core.tensoralg import sig_dim
            B, L, d = meta["B"], meta["L"], meta["d"]
            sd = sig_dim(d, int(meta["depth"]))
            flops = 3.0 * B * L * sd * mult
            nbytes = 4.0 * B * (L * d + sd)
            return flops, nbytes
        if op in ("sigkernel", "sigkernel_grad"):
            B, L, d = meta.get("B", 4), meta["L"], meta.get("d", 3)
            n = L << lam
            per_pair = 2.0 * L * L * d + _PDE_FLOPS_PER_CELL * n * n
            nbytes = 4.0 * B * (2 * L * d + 3 * L * L)
            return B * per_pair * mult, nbytes
        if op in ("gram", "gram_reduce", "gram_sharded"):
            B, L, d = meta["B"], meta["L"], meta["d"]
            n = L << lam
            pairs = float(B) * B
            per_pair = 2.0 * L * L * d + _PDE_FLOPS_PER_CELL * n * n
            nbytes = 4.0 * (2 * B * L * d + pairs * 3 * L * L)
            return pairs * per_pair * mult, nbytes
    except (KeyError, TypeError, ValueError):
        return None
    return None


def entry_fields(flops: Optional[float], nbytes: Optional[float],
                 seconds: Optional[float], source: str) -> dict:
    """The ``"roofline"`` dict for one bench entry.

    Always contains ``peak_flops`` / ``peak_bandwidth`` / ``source``;
    adds ``flops`` / ``bytes`` / ``bound`` when an estimator produced
    counts and ``achieved_*`` / ``frac_*`` when the entry was timed.
    """
    pk = peaks()
    out: dict = {"peak_flops": pk["flops"], "peak_bandwidth": pk["bandwidth"],
                 "source": source}
    if flops is None or nbytes is None:
        return out
    out["flops"] = float(flops)
    out["bytes"] = float(nbytes)
    t_c = flops / pk["flops"] if pk["flops"] else 0.0
    t_m = nbytes / pk["bandwidth"] if pk["bandwidth"] else 0.0
    out["bound"] = "compute" if t_c >= t_m else "memory"
    if seconds and seconds > 0:
        out["achieved_flops"] = flops / seconds
        out["achieved_bandwidth"] = nbytes / seconds
        if pk["flops"]:
            out["frac_flops"] = out["achieved_flops"] / pk["flops"]
        if pk["bandwidth"]:
            out["frac_bandwidth"] = out["achieved_bandwidth"] / pk["bandwidth"]
    return out


def attach(entry: dict, fn=None, args: tuple = ()) -> dict:
    """Set ``entry["roofline"]`` in place (fail-open) and return the entry.

    With ``fn`` the HLO estimator runs first (memoised on the entry name);
    otherwise — or when lowering fails — the analytic model from the
    entry's meta applies; when even that has nothing, the dict still
    carries the platform peaks so every bench entry has roofline fields.
    A TPU missing from :data:`TPU_PEAKS` raises.
    """
    peaks()
    try:
        seconds = entry.get("seconds")
        counts = None
        source = "analytic"
        if fn is not None:
            counts = hlo_counts(fn, *args, key=entry["name"])
            if counts is not None:
                source = "hlo"
        if counts is None:
            counts = analytic_counts(entry["name"], entry.get("meta", {}))
        if counts is None:
            entry["roofline"] = entry_fields(None, None, seconds, "none")
        else:
            entry["roofline"] = entry_fields(counts[0], counts[1], seconds,
                                             source)
    except Exception:
        entry["roofline"] = {"source": "error"}
    return entry


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def _fmt_rate(x: Optional[float], unit: str) -> str:
    if x is None:
        return "—"
    for scale, prefix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if x >= scale:
            return f"{x / scale:.2f} {prefix}{unit}"
    return f"{x:.2f} {unit}"


def markdown_summary(doc: dict) -> str:
    """Roofline table over a BENCH document's timed entries."""
    fp = doc.get("fingerprint", {})
    head = [
        f"## roofline — mode `{doc.get('mode')}`, "
        f"platform `{fp.get('platform')}` ({fp.get('device_kind')})",
        "",
        "| entry | µs/call | FLOPs | achieved | frac of peak | "
        "bandwidth | frac of peak | bound | src |",
        "|---|---:|---:|---:|---:|---:|---:|---|---|",
    ]
    rows = []
    for e in doc.get("entries", []):
        if e.get("kind") != "time":
            continue
        r = e.get("roofline") or {}
        us = f"{e['seconds'] * 1e6:.1f}"
        rows.append(
            f"| {e['name']} | {us} "
            f"| {_fmt_rate(r.get('flops'), 'F')} "
            f"| {_fmt_rate(r.get('achieved_flops'), 'FLOP/s')} "
            f"| {r.get('frac_flops', 0.0) * 100:.2f}% "
            f"| {_fmt_rate(r.get('achieved_bandwidth'), 'B/s')} "
            f"| {r.get('frac_bandwidth', 0.0) * 100:.2f}% "
            f"| {r.get('bound', '—')} | {r.get('source', '—')} |")
    if not rows:
        rows = ["| (no timed entries with roofline data) | | | | | | | | |"]
    pk = peaks()
    tail = ["", f"peaks: {_fmt_rate(pk['flops'], 'FLOP/s')} compute, "
                f"{_fmt_rate(pk['bandwidth'], 'B/s')} bandwidth "
                f"({pk['source']})"]
    return "\n".join(head + rows + tail)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args or args[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    with open(args[0], encoding="utf-8") as f:
        doc = json.load(f)
    print(markdown_summary(doc))
    if len(args) > 1:
        with open(args[1], "w", encoding="utf-8") as f:
            f.write(markdown_summary(doc) + "\n")
        print(f"\nwrote {args[1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
