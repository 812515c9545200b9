"""The training cell on the chip's own path, at a size the CPU holds:
Pallas kernels under the interpreter, gradients rounded to bfloat16 as
they enter the histogram, the reference told the same, and judged by the
limits of the cell's own file (bench/workloads/higgs28-b63.train.json),
not the toy's. It read not correct until PR 34 gave a node's totals one
owner (the root's sums are read off the root histogram of the rounded
operands); a grower that sums them beside the histogram again fails
here before it costs a chip run."""

import json
import os
import subprocess
import sys

from conftest import BENCH, TOY


def test_training_cell_is_correct_on_the_chip_path():
    ov = json.loads(json.dumps(TOY))
    ov["config"]["histogram_operand_dtype"] = "bfloat16"
    del ov["cell"]["limits"]            # the cell file's own
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LIGHTGBM_TPU_PALLAS_INTERPRET="1")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "probe.py"), "--workload",
         "higgs28-b63.train", "--seeds", "77", "--allow-cpu",
         "--overrides", json.dumps(ov)],
        env=env, capture_output=True, text=True, timeout=900, check=True)
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["numbers"]
