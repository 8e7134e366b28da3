"""Readings that set a cell's limits: the program over many seeds, the
control in the nearest lower precision, and planted faults.

    python chipbench/calibrate.py --workload mmd_train \\
        --seeds 11 12 13 --control-seeds 21 22 23 \\
        --faults half_batch answer_altered --fault-seeds 31 32 33

Runs everything in this one process on the chip, each run through the
harness with a window of ``--seconds`` (at least one unit), and prints one
JSON line per run and a summary: per number, the largest program reading
(the lower reading) and the smallest control and fault readings.  The
control is the program with bfloat16 interior cells, its own lower
precision path.  The benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import faults, run  # noqa: E402

CONTROL = {"interior_dtype": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    unit = run.load_cell(run.ROOT, args.workload)["traffic"]["unit"]
    plan = [("program", s, None) for s in args.seeds]
    plan += [("control", s, None) for s in args.control_seeds]
    plan += [(f, s, f) for f in args.faults for s in args.fault_seeds]
    readings = {}
    for kind, seed, fault in plan:
        plant = (faults.planted(unit, fault) if fault
                 else contextlib.nullcontext())
        with plant:
            res = run.run(args.workload, seed, args.seconds, False,
                          overrides=CONTROL if kind == "control" else None)
        values = {k: c["value"] for k, c in res["checks"].items()}
        print(json.dumps({"kind": kind, "seed": seed, "checks": values,
                          "correct": res["correct"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
        for k, v in values.items():
            readings.setdefault(kind, {}).setdefault(k, []).append(v)
    summary = {k: {"lower": max(v)} for k, v in
               readings.get("program", {}).items()}
    for kind, by_name in readings.items():
        if kind != "program":
            for k, v in by_name.items():
                summary.setdefault(k, {})[f"min_{kind}"] = min(v)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
