"""Device ingest as a pipeline of row blocks (ops/bucketize.py
``bin_rows_device``): the binned matrix it writes is byte-equal to the
host ``value_to_bin`` route and to one call of the ingest program over
the whole table, whatever the columns, the row count, the number of
blocks and the row range it writes into."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import bucketize as bz
from lightgbm_tpu.runtime import profiler as P

BLOCK = bz._BLOCK_ROWS
PARAMS = {"verbosity": -1, "max_bin": 63, "min_data_in_leaf": 5}


def _table(n, columns="identity", seed=3):
    """[n, 7] float32 rows with NaNs; ``tiered``: column 0 constant (a
    trivial column, dropped) and column 5 of two values (a narrower bin
    tier, moved to the front), so the kept columns are a take."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 7)).astype(np.float32)
    X[rng.rand(n) < 0.05, 2] = np.nan
    if columns == "tiered":
        X[:, 0] = 1.5
        X[:, 5] = (X[:, 5] > 0).astype(np.float32)
    return X


def _binned(X, impl, reference=None):
    data = X if impl == "device" else np.asarray(X, np.float64)
    ds = lgb.Dataset(data, label=np.asarray(X[:, 1] > 0, np.float64),
                     reference=reference,
                     params=dict(PARAMS, binning_impl=impl))
    ds.construct()
    return ds


def _bucketize_counts():
    return [r["counts"] for r in P.spans()
            if r["name"] == "dataset/bucketize"][-1]


@pytest.mark.parametrize("n, columns", [
    (3 * BLOCK + 3392, "identity"),     # 4 blocks, 3 in flight, a padded last
    (3 * BLOCK + 3392, "tiered"),       # the columns as a take
    (1000, "identity"),                 # under one block
], ids=["identity-4-blocks", "tiered-dropped-4-blocks", "under-one-block"])
def test_pipeline_matches_host_and_one_call(n, columns):
    X = _table(n, columns)
    dev = _binned(X, "device")
    h = dev._handle
    assert h.binned_on == "device"
    counts = _bucketize_counts()
    blocks = -(-n // BLOCK)
    assert counts["blocks"] == blocks
    assert counts["in_flight"] == min(blocks, bz._IN_FLIGHT)
    assert counts["column_slice"] == int(columns == "identity")
    assert (h.real_feature_index == list(range(7))) == (columns == "identity")

    host = _binned(X, "host")._handle
    assert h.X_binned.dtype == host.X_binned.dtype
    assert h.X_binned.tobytes() == host.X_binned.tobytes()

    # one call of the ingest program over all rows, padded to a row tile
    t = bz.pack_bin_table(h.mappers, mode="train")
    rows = np.zeros((bz._round_up(n, bz._ROW_TILE), len(h.mappers)),
                    np.float32)
    rows[:n] = X[:, h.real_feature_index]
    one = np.asarray(bz._bin_rows_jit()(rows, t.table, t.cat_val, t.meta))
    assert h.X_binned.tobytes() == one[:n].astype(h.X_binned.dtype).tobytes()


def test_push_rows_into_a_row_range():
    """Two pushes: the second writes rows [a, N) of a binned matrix whose
    first rows already hold bins, and leaves them as they were."""
    n, a = 2 * BLOCK + 500, BLOCK + 77
    X = _table(n, "tiered")
    ref = _binned(X[:5000], "host")
    whole = _binned(X, "host", reference=ref)._handle.X_binned

    stream = lgb.Dataset(None, reference=ref,
                         params=dict(PARAMS, binning_impl="device"))
    stream.init_streaming(n)
    stream.push_rows(X[:a])
    first = stream._handle.X_binned[:a].copy()
    stream.push_rows(X[a:])
    assert _bucketize_counts()["blocks"] == -(-(n - a) // BLOCK)
    got = stream._handle.X_binned
    assert got[:a].tobytes() == first.tobytes()
    assert got.tobytes() == whole.tobytes()


class _LateBlock:
    """A block whose upload is read only when its bins are fetched: the
    latest an asynchronous transfer may read its staging buffer."""

    def __init__(self, program, rows, table):
        self.program, self.rows, self.table = program, rows, table
        self.nbytes = rows.shape[0] * rows.shape[1]

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.program(self.rows, *self.table))


def test_staging_buffers_reused_across_blocks(monkeypatch):
    """Twelve blocks through four staging buffers, on a device that reads
    each upload as late as it may: every block's own value comes back in
    its own rows, none in another block's."""
    import jax
    program = bz._bin_rows_jit()
    monkeypatch.setattr(bz, "_BLOCK_ROWS", bz._ROW_TILE)
    blocks = 12
    n = bz._ROW_TILE * (blocks - 1) + 17
    X = _table(n)
    X[:, 0] = np.repeat(np.arange(blocks, dtype=np.float32),
                        bz._ROW_TILE)[:n]
    with monkeypatch.context() as late:
        late.setattr(jax, "device_put", lambda x: x)        # no copy
        late.setattr(bz, "_bin_rows_jit", lambda: lambda rows, *table:
                     _LateBlock(program, rows, table))
        h = _binned(X, "device")._handle
    counts = _bucketize_counts()
    assert counts["blocks"] == blocks and counts["in_flight"] == 3
    col = h.X_binned[:, h.real_feature_index.index(0)]
    per_block = [np.unique(col[b * bz._ROW_TILE:(b + 1) * bz._ROW_TILE])
                 for b in range(blocks)]
    assert all(len(u) == 1 for u in per_block)
    assert len({int(u[0]) for u in per_block}) == blocks
    assert h.X_binned.tobytes() == _binned(X, "host")._handle.X_binned.tobytes()
