"""Benchmark suite CLI.

    PYTHONPATH=src python -m repro.bench [--smoke | --quick | --full]
                                         [--repeats N] [--out BENCH_PR10.json]
                                         [--md PATH]

Runs the paper-aligned workloads (signature Table 1, sig-kernel + Gram
Table 2, log-signature Table 3, §3.4 gradient accuracy; ``--smoke`` adds
the all-backend agreement checks and the autotune round-trip), writes the
schema-versioned BENCH JSON, and prints a markdown summary.  Gate a run
against a committed baseline with ``python -m repro.bench.compare``.
"""

from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache

from . import suite


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.bench", description=__doc__.splitlines()[0])
    mode_group = ap.add_mutually_exclusive_group()
    mode_group.add_argument("--smoke", action="store_true",
                            help="tiny CI shapes + backend agreement + "
                                 "autotune round-trip")
    mode_group.add_argument("--quick", action="store_true",
                            help="scaled-down paper cells (the default; "
                                 "the flag exists so cron jobs can say "
                                 "what they mean)")
    mode_group.add_argument("--full", action="store_true",
                            help="the paper's exact cells (slow on CPU)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timing repeats (default: 2 smoke / 3 quick / "
                         "5 full; paper methodology is 50)")
    ap.add_argument("--out", default=None,
                    help="output JSON path, or '-' to skip writing "
                         "(default: BENCH_PR10.json in --smoke mode — the "
                         "committed CI baseline — else BENCH_<mode>.json)")
    ap.add_argument("--md", default=None,
                    help="also write the markdown summary to this path")
    # tolerate (and drop) legacy `benchmarks.run` flags forwarded by the stub
    args, unknown = ap.parse_known_args(argv)
    for flag in unknown:
        print(f"ignoring unknown argument {flag!r}", file=sys.stderr)

    enable_compile_cache()
    mode = "smoke" if args.smoke else ("full" if args.full else "quick")
    if args.out is None:
        # only smoke mode may touch the committed baseline by default —
        # quick/full documents have a different entry set and would poison
        # the CI compare job if committed accidentally
        args.out = "BENCH_PR10.json" if mode == "smoke" \
            else f"BENCH_{mode}.json"
    doc = suite.run_suite(mode, repeats=args.repeats,
                          progress=lambda m: print(m, file=sys.stderr))
    if args.out != "-":
        suite.write_json(doc, args.out)
        print(f"wrote {args.out} ({len(doc['entries'])} entries)",
              file=sys.stderr)
    md = suite.markdown_summary(doc)
    if args.md:
        with open(args.md, "w", encoding="utf-8") as f:
            f.write(md + "\n")
    print(md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
