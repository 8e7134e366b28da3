"""Readings that set the limits of the cells this file's units serve: the
program over many seeds, the control in the nearest lower precision, and
planted faults.

    python chipbench/calibrate_sig.py --workload sigfeat_train \\
        --seeds 11 12 13 --control-seeds 21 22 23 \\
        --faults top_level_dropped --fault-seeds 31 32 33

``calibrate.py``'s runs and summary, with the faults of ``faults_sig.py``
and each unit's control: for ``logsig_sgd_step`` the features computed
from bfloat16-rounded increments, for ``mmd_forward`` ``calibrate.py``'s
own (bfloat16 interior cells).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import calibrate, faults_sig, run  # noqa: E402

CONTROLS = {"logsig_sgd_step": {"increment_dtype": "bfloat16"},
            "mmd_forward": calibrate.CONTROL}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    workload = ap.parse_known_args(argv)[0].workload
    unit = run.load_cell(run.ROOT, workload)["traffic"]["unit"]
    calibrate.CONTROL, calibrate.faults = CONTROLS[unit], faults_sig
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
