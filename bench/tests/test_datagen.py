"""The one general generator keeps every accepted cell's data bit for
bit: a data spec gives the md5 recorded on commit a059093, at 1 and at 8
threads, so an edit to datagen.py that moves a bit of a cell's table is
seen here and not as a drift of its numbers."""

import hashlib

import pytest

import datagen

SEED = 2147483999            # over 31 bits, as the driver's seeds are
RECORDED = [
    # more than one block of 2**20 rows, so the threads have blocks to share
    ({"kind": "linear_binary", "rows": 2200000, "cols": 4, "noise": 0.5},
     "f529f83042e129c40143537d2a009873"),
    # the cells' 28 columns, generator defaults for the rest
    ({"rows": 30000, "cols": 28}, "23e0d992629bb66bea462c8119a4651f"),
]


@pytest.mark.parametrize("threads", [1, 8])
@pytest.mark.parametrize("spec,md5", RECORDED)
def test_spec_reads_the_recorded_bits(spec, md5, threads):
    X, y = datagen.make(SEED, spec, threads=threads)
    assert hashlib.md5(X.tobytes() + y.tobytes()).hexdigest() == md5
