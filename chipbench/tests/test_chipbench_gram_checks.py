"""``gram2s_4chip``'s comparison with the reference, driven through a whole
run on four simulated CPU devices at a small size: a sound run is
correct, and the control (bfloat16 interiors) and each planted fault of
a sharded Gram are not."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import faults
from chipbench.tests.helpers import ROOT

CASES = ("sound", "control", *sorted(faults.FAULTS["gram_sharded"]))

SCRIPT = textwrap.dedent('''
    import contextlib, json, sys
    sys.path[:0] = [sys.argv[1]]
    from chipbench import faults
    from chipbench.tests.helpers import run_small
    out = {}
    for case in sys.argv[2:]:
        extra = {"interior_dtype": "bfloat16"} if case == "control" else {}
        plant = (faults.planted("gram_sharded", case)
                 if case in faults.FAULTS["gram_sharded"]
                 else contextlib.nullcontext())
        with plant:
            res = run_small("gram2s_4chip", 2**32 + 3, **extra)
        out[case] = {"correct": res["correct"], "checks": res["checks"],
                     "count": res["device"]["count"]}
    print(json.dumps(out))
''')


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, *CASES],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_gram_check(results, case):
    res = results[case]
    assert res["count"] == 4
    assert res["correct"] == (case == "sound"), res["checks"]
