"""The held-out training cells on the chip's own path, at a size the CPU
holds: Pallas kernels under the interpreter, gradients rounded to
bfloat16 as they enter the histogram, the reference told the same. It
reads not correct because of the fault set out first under Open
questions in PERF.md (node totals summed in float32 beside histograms of
rounded operands); the day it passes, the cells can come into
BENCHMARK.json."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, TOY


@pytest.mark.xfail(reason="histogram totals at fault, PERF.md section 7",
                   strict=False)
def test_training_cell_is_correct_on_the_chip_path():
    ov = json.loads(json.dumps(TOY))
    ov["config"]["histogram_operand_dtype"] = "bfloat16"
    del ov["cell"]["limits"]            # the held-out cell's own
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LIGHTGBM_TPU_PALLAS_INTERPRET="1")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "probe.py"), "--workload",
         "higgs28-b63.train", "--seeds", "77", "--allow-cpu",
         "--overrides", json.dumps(ov)],
        env=env, capture_output=True, text=True, timeout=900, check=True)
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["numbers"]
