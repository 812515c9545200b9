"""Tests of the benchmark's own yardstick, at a size a CPU holds.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

They are not part of the repo's tier-1 run (that collects tests/ only).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell shrunk to what the CPU holds in seconds; widths, bins, objective
# and the comparison are the cell's own. On the CPU the program takes its
# portable XLA histograms, whose operands are float32, so the reference
# is told float32 too; the chip's path (bfloat16 operands) is driven under
# the Pallas interpreter by test_chip_path.py, in a process of its own
# (the program reads that switch once, when it first traces).
# The limits are the yardstick's own on that sound path, tighter than the
# cell file's, which are set from the chip's readings at 63M rows with
# bfloat16 operands (PERF.md section 2).
TOY = {"cell": {"data": {"rows": 30000},
                "limits": {"bin_edges_bad": 0.0, "bin_mass_gap": 0.3,
                           "bin_mismatch": 0.0, "split_gain_gap": 1e-3,
                           "leaf_value_gap": 1e-3, "score_gap": 1e-3,
                           "loss_gap": 1e-5},
                "job": {"chunk_iters": 2, "auc_rows": 5000,
                        "check": {"hist_trees": 2, "sub": 2048,
                                  "upload_subs": 4}}},
       "config": {"params": {"num_leaves": 15},
                  "histogram_operand_dtype": "float32"}}
