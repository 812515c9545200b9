"""Every Pallas kernel family through the Mosaic compiler, two ways.

    python scripts/kernel_check.py          # on the chip (one process)
    python scripts/kernel_check.py --aot    # on a CPU host, no chip

On the chip each family is compiled, run, and compared BIT FOR BIT with
the same kernel under the Pallas interpreter on the same device (the
bucketize kernel with its XLA reference): inputs are small multiples of
0.25, so every bf16 product and f32 partial sum is exact and the
comparison is independent of accumulation order. What the interpreter
itself computes is pinned against the XLA lowerings by the CPU suites
(tests/test_histogram_pallas.py and friends).

``--aot`` needs no accelerator: it asks libtpu for a compile-only v5e
topology (``jax.experimental.topologies``) and compiles every family —
plus the smoke's 32-iteration scan chunk at 4M x 28 for both bin widths
and the four-chip ``tree_learner=data`` step — for it. That is the real
Mosaic compiler (scoped-VMEM limits, tiling) without chip time; it cannot
say anything about results or speed.

Families: the slots kernel (legacy, hi/lo wide-bin, F-gridded wide), the
wave megakernel, wave_relabel, wave_apply, take_leaf_values, tiered,
row-wise, pack4, bucketize — float and int8 values, at the smoke width
(F = 28) and one wide shape (F = 128) — and the device predictor's forest
kernel (against the host's float32 walk, not the interpreter).

Writes chiprun_out/kernel_check.json; exits non-zero on any unexpected
outcome.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
AOT = "--aot" in sys.argv[1:]
if AOT:
    # libtpu looks these up when it builds a topology without hardware
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N = 16384                       # rows: 8 grid steps of N_BLK
MIXED = (3, 12, 16, 16, 20, 40, 64, 64, 100, 200, 256, 256)


def _bins(rng, tiers):
    return np.stack([rng.randint(0, t, size=N) for t in tiers]
                    ).astype(np.uint8)


def _vals(rng, int8):
    v = rng.randint(-32, 32, size=(2, N))
    return v.astype(np.int8) if int8 else (v * 0.25).astype(np.float32)


def _wave_table(rng, F, B, K):
    """A consistent semantic [16, 128] wave table: 4 applied splits and K
    candidates over leaves 0..11 (histogram_pallas.py row layout)."""
    t = np.full((16, 128), -1, np.int32)
    for base, leaves in ((0, [0, 3, 5, 7]), (7, list(range(K)))):
        n = len(leaves)
        t[base, :n] = leaves
        t[base + 1, :n] = rng.randint(0, F, size=n)
        t[base + 2, :n] = rng.randint(0, B - 2, size=n)
        t[base + 3, :n] = rng.randint(0, 2, size=n)
        t[base + 4, :n] = rng.choice([0, 1, 2], size=n)
        t[base + 5, :n] = rng.randint(0, B - 1, size=n)
        t[base + 6, :n] = B
    t[14, :K] = rng.randint(0, 2, size=K)
    t[15, :] = 12
    return t


def _slots(F, B, int8, wide_lo, K=8):
    from lightgbm_tpu.ops.histogram_pallas import build_histogram_slots_pallas

    def make(rng):
        return (_bins(rng, (B,) * F), _vals(rng, int8),
                rng.randint(-1, K, size=N).astype(np.int32))
    return make, lambda interp: lambda X, v, s: build_histogram_slots_pallas(
        X, v, s, K, B, interpret=interp, wide_lo=wide_lo)


def _mega(B, int8, wide_lo, K=8):
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas

    def make(rng):
        return (_bins(rng, (B,) * 28).astype(np.int8), _vals(rng, int8),
                rng.randint(0, 12, size=N).astype(np.int32),
                _wave_table(rng, 28, B, K))
    return make, lambda interp: lambda X, v, lor, t: wave_pass_pallas(
        X, v, lor, t, K, B, interpret=interp, wide_lo=wide_lo)


def _relabel(B, int8):
    from lightgbm_tpu.ops.histogram_pallas import wave_relabel_pallas

    def make(rng):
        return (_bins(rng, (B,) * 28).astype(np.int8), _vals(rng, int8),
                rng.randint(0, 12, size=N).astype(np.int32),
                _wave_table(rng, 28, B, 0))
    return make, lambda interp: lambda X, v, lor, t: wave_relabel_pallas(
        X, v, lor, t, B, interpret=interp)


def _apply():
    from lightgbm_tpu.ops.histogram_pallas import wave_apply_pallas

    def make(rng):
        return (rng.randint(0, 4, size=(128, N)).astype(np.int8),
                rng.randint(0, 12, size=N).astype(np.int32),
                _wave_table(rng, 28, 64, 8))
    return make, lambda interp: lambda d, lor, t: wave_apply_pallas(
        d, lor, t, interpret=interp)


def _take_leaf(L):
    from lightgbm_tpu.ops.histogram_pallas import take_leaf_values_pallas

    def make(rng):
        return ((rng.randint(-64, 64, size=L) * 0.25).astype(np.float32),
                rng.randint(0, L, size=N).astype(np.int32))
    return make, lambda interp: lambda v, lor: take_leaf_values_pallas(
        v, lor, interpret=interp)


def _planned(kind, F, int8, K=8):
    from lightgbm_tpu.ops import histogram_rowwise as R
    from lightgbm_tpu.ops.histogram_tiered import (
        build_histogram_slots_tiered_flat, build_tier_plan)
    tiers = tuple(sorted((MIXED * (F // len(MIXED) + 1))[:F]))

    def make(rng):
        return (_bins(rng, tiers), _vals(rng, int8),
                rng.randint(-1, K, size=N).astype(np.int32))
    if kind == "tiered":
        plan = build_tier_plan(tiers)
        return make, lambda interp: lambda X, v, s: \
            build_histogram_slots_tiered_flat(X, v, s, K, plan,
                                              interpret=interp)
    plan = R.build_rowwise_plan(tiers)
    assert R.rowwise_eligible(plan, 2, K), (F, K)
    if kind == "rowwise":
        return make, lambda interp: lambda X, v, s: \
            R.build_histogram_slots_rowwise_flat(X, v, s, K, plan,
                                                 interpret=interp)
    pplan = R.build_pack4_plan(tiers)
    return make, lambda interp: lambda X, v, s: \
        R.build_histogram_slots_rowwise_packed_flat(
            *R.pack4(X, pplan), v, s, K, plan, pplan, interpret=interp)


def _bucketize(F, max_bin):
    """Compiled kernel vs the XLA reference (the bit-identity contract of
    ops/bucketize.py); mappers fitted on NaN/zero-salted columns, the
    last one categorical."""
    from lightgbm_tpu.data.binning import (BIN_TYPE_CATEGORICAL,
                                           BIN_TYPE_NUMERICAL, BinMapper)
    from lightgbm_tpu.ops import bucketize as Bk
    rng = np.random.RandomState(F + max_bin)

    def col(n):
        v = rng.normal(scale=50.0, size=n).astype(np.float32)
        v[rng.rand(n) < 0.05] = np.nan
        v[rng.rand(n) < 0.05] = 0.0
        return v
    S = np.stack([col(4000) for _ in range(F)], axis=1)
    S[:, F - 1] = rng.randint(0, 30, size=4000)
    table = Bk.pack_bin_table([
        BinMapper.find_bin(np.asarray(S[:, f], np.float64), 4000, max_bin,
                           3, 20, bin_type=(BIN_TYPE_CATEGORICAL
                                            if f == F - 1
                                            else BIN_TYPE_NUMERICAL))
        for f in range(F)], mode="train")

    def make(_):
        X = np.stack([col(N) for _ in range(F)], axis=1)
        X[:, F - 1] = rng.randint(-3, 40, size=N)
        return (X,)
    return make, lambda interp: (
        (lambda X: Bk._bucketize_xla(X, table)) if interp
        else (lambda X: Bk._bucketize_pallas(X, table)))


def random_tree(rng, leaves, X, nan=False, cat_feature=None):
    """A random ``leaves``-leaf tree over X's features. Each threshold is
    one of the rows' own values, so a feature value that reaches the
    compare off by one ulp flips a decision; ``nan`` gives every node
    MISSING_NAN and a random default side, ``cat_feature`` makes the
    splits on that feature categorical (bitsets of one or two words)."""
    from lightgbm_tpu.models.tree import MISSING_NAN, Tree
    t = Tree(leaves)
    link = {0: None}                     # leaf -> (parent node, is_left)
    words, bounds = [], [0]
    for i in range(leaves - 1):          # node i splits a leaf of i + 1
        leaf = rng.randint(0, i + 1)
        if link[leaf] is not None:
            node, is_left = link[leaf]
            (t.left_child if is_left else t.right_child)[node] = i
        t.left_child[i], t.right_child[i] = ~leaf, ~(i + 1)
        link[leaf], link[i + 1] = (i, True), (i, False)
        f = t.split_feature[i] = rng.randint(0, X.shape[1])
        if f == cat_feature:
            t.decision_type[i] = 1       # categorical
            t.threshold[i] = t.threshold_in_bin[i] = len(bounds) - 1
            words += [rng.randint(0, 2 ** 32, dtype=np.uint64)
                      for _ in range(rng.randint(1, 3))]
            bounds.append(len(words))
        else:
            t.threshold[i] = np.nan_to_num(X[rng.randint(0, len(X)), f])
            if nan:
                t.decision_type[i] = 2 * rng.randint(0, 2) | MISSING_NAN << 2
    t.leaf_value[:] = rng.normal(size=leaves).astype(np.float32)
    if words:
        t.num_cat = len(bounds) - 1
        t.cat_boundaries = np.asarray(bounds, np.int32)
        t.cat_threshold = np.asarray(words, np.uint32)
    return t


def _predict_forest(leaves, F, K=1, nan=False, cat=False, terms=1):
    """The device predictor (models/predictor.py: the rows coded as
    threshold ranks by ``_layout``, then the kernel) against the HOST's
    float32 walk of the same random forest: per tree the leaf by
    ``Tree.get_leaf_index``, its float32 value added in tree order.
    ``terms`` is the int8 digits a code must take: every threshold is a
    row's own value, so 6 trees of ``leaves`` leaves over few features
    hold hundreds of distinct ones a feature."""
    from lightgbm_tpu.models import predictor as Pm
    rng = np.random.RandomState(leaves + F)
    X = rng.normal(size=(N, F)).astype(np.float32)
    if nan:
        X[rng.rand(N, F) < 0.05] = np.nan
    if cat:
        X[:, 0] = rng.randint(-2, 70, size=N)
    trees = [random_tree(rng, leaves, X, nan, 0 if cat else None)
             for _ in range(6 * K)]
    tb = Pm.build_device_tables(trees, K, F)
    ref = np.zeros((K, N), np.float32)
    for i, t in enumerate(trees):
        ref[i % K] += t.leaf_value.astype(np.float32)[
            t.get_leaf_index(X.astype(np.float64))]
    n = tb.row_tile

    assert tb.terms == terms, (tb.terms, tb.thresholds_max)

    def kernel(X):
        codes = Pm._layout(X, tb.tkeys, tb.ncat, n=n, terms=tb.terms,
                           has_nan=tb.has_nan, dual=tb.dual)
        return Pm._forest_pallas(codes, *tb.arrays, K=K, has_nan=tb.has_nan,
                                 has_zero=tb.has_zero, n=n)[:, :N]
    return (lambda _: (X,)), lambda interp: (
        (lambda X: jnp.asarray(ref)) if interp else kernel)


def cases():
    """(name, builder) — builders are lazy: a family whose import or
    plan fails is reported, not fatal to the rest."""
    out = []
    for F in (28, 128):
        for int8 in (False, True):
            t = "int8" if int8 else "f32"
            out += [
                (f"slots legacy F{F} B64 {t}",
                 lambda F=F, i=int8: _slots(F, 64, i, 128)),
                (f"slots legacy F{F} B256 {t}",
                 lambda F=F, i=int8: _slots(F, 256, i, 128)),
                (f"slots hi/lo F{F} B256 {t}",
                 lambda F=F, i=int8: _slots(F, 256, i, 64)),
                (f"tiered F{F} {t}",
                 lambda F=F, i=int8: _planned("tiered", F, i)),
                (f"row-wise F{F} {t}",
                 lambda F=F, i=int8: _planned("rowwise", F, i)),
                (f"pack4 F{F} {t}",
                 lambda F=F, i=int8: _planned("pack4", F, i)),
            ]
        out += [(f"bucketize F{F} max_bin{mb}",
                 lambda F=F, mb=mb: _bucketize(F, mb)) for mb in (63, 255)]
    for int8 in (False, True):
        t = "int8" if int8 else "f32"
        out += [
            (f"megakernel B64 {t}", lambda i=int8: _mega(64, i, 128)),
            (f"megakernel hi/lo B256 {t}", lambda i=int8: _mega(256, i, 64)),
            (f"wave_relabel B256 {t}", lambda i=int8: _relabel(256, i)),
        ]
    out += [("wave_apply", _apply),
            ("take_leaf_values L255", lambda: _take_leaf(255)),
            ("take_leaf_values L2048", lambda: _take_leaf(2048)),
            ("predict_forest L255 F28", lambda: _predict_forest(255, 28)),
            ("predict_forest L15 F130 K3 nan",
             lambda: _predict_forest(15, 130, K=3, nan=True)),
            ("predict_forest L31 F28 cat",
             lambda: _predict_forest(31, 28, cat=True)),
            ("predict_forest L255 F4 nan c2",
             lambda: _predict_forest(255, 4, nan=True, terms=2))]
    return out


def _short(e: BaseException) -> str:
    lines = [ln.strip() for ln in str(e).strip().splitlines() if ln.strip()]
    return f"{type(e).__name__}: {' | '.join(lines[:3])}"[:400]


def check_on_device(make, fn_of) -> str:
    args = make(np.random.RandomState(0))
    got = jax.jit(fn_of(False))(*args)
    ref = jax.jit(fn_of(True))(*args)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        if not np.array_equal(np.asarray(g), np.asarray(r)):
            bad = int(np.sum(np.asarray(g) != np.asarray(r)))
            raise AssertionError(f"{bad} elements differ from the "
                                 "interpreter / XLA reference")
    return "compiled, ran, bit-identical to reference"


def _topology():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def check_aot(make, fn_of, sharding) -> str:
    specs = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                  sharding=sharding)
             for a in make(np.random.RandomState(0))]
    lowered = jax.jit(fn_of(False)).lower(*specs)
    n = lowered.as_text().count("tpu_custom_call")
    lowered.compile()
    return f"compiled for v5e ({n} Mosaic call(s))"


def aot_train_steps(topo):
    """The smoke's real programs, compiled for the topology: the batched
    scan chunk at 4M x 28 (both bin widths) and the 4-chip data-parallel
    step. The trainer is built on a small CPU dataset with the dispatch
    answering as on the chip; only shapes reach the compiler."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.grow_wave import grow_tree_wave
    from lightgbm_tpu.parallel import (DATA_AXIS,
                                       build_data_parallel_train_fn)
    real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    rows, F, n_pad = 4_000_000, 28, 32
    s0 = SingleDeviceSharding(topo.devices[0])
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), (DATA_AXIS,))
    rng = np.random.RandomState(0)
    X = rng.normal(size=(8192, F)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    def sd(shape, dtype, sh=s0):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def meta_of(g, sh=s0):           # FeatureMeta is an argument: shapes
        return jax.tree.map(lambda a: sd(a.shape, a.dtype, sh), g.meta)
    try:
        for max_bin in (63, 255):
            p = dict(objective="binary", num_leaves=255,
                     min_data_in_leaf=20, max_bin=max_bin, verbose=-1,
                     binning_impl="host")
            g = lgb.Booster(params=p, train_set=lgb.Dataset(
                X, label=y, params=p))._gbdt
            g._host_pad = g.num_data = rows     # closed over by the scan
            yield f"scan chunk 4M x 28 max_bin{max_bin}", lambda g=g: \
                g._get_scan_fn(n_pad, "host").lower(
                    sd((F, rows), jnp.uint8), sd((1, rows), jnp.float32),
                    sd((rows,), jnp.float32), None, sd((rows,), jnp.float32),
                    sd((), jnp.float32), sd((), jnp.int32),
                    sd((), jnp.int32), sd((n_pad, F), bool),
                    meta_of(g),
                    (), (), (), (), (), ())
            if max_bin == 63:
                row = NamedSharding(mesh, P(DATA_AXIS))
                rep = NamedSharding(mesh, P())
                fn = build_data_parallel_train_fn(
                    mesh, g.grow_cfg._replace(n_shards=4),
                    grow_fn=grow_tree_wave)
                yield "4-chip tree_learner=data step", lambda fn=fn, g=g: \
                    fn.lower(
                        sd((F, rows), jnp.uint8,
                           NamedSharding(mesh, P(None, DATA_AXIS))),
                        *(sd((rows,), jnp.float32, row) for _ in range(4)),
                        sd((), jnp.float32, rep), sd((F,), bool, rep),
                        sd((), jnp.int32, rep), meta_of(g, rep))
    finally:
        jax.default_backend = real_backend


def main() -> int:
    if AOT:
        topo = _topology()
        from jax.sharding import SingleDeviceSharding
        sharding = SingleDeviceSharding(topo.devices[0])
        device = {"platform": "tpu (compile-only topology)",
                  "kind": topo.devices[0].device_kind,
                  "count": len(topo.devices)}
    else:
        from lightgbm_tpu.runtime.device import require_tpu
        device = require_tpu()
    print(f"kernel_check: {device}", flush=True)
    results, unexpected = [], 0
    for name, build in cases():
        t0 = time.perf_counter()
        try:
            make, fn_of = build()
            msg = (check_aot(make, fn_of, sharding) if AOT
                   else check_on_device(make, fn_of))
            ok = True
        except Exception as e:                # noqa: BLE001 — reported
            ok, msg = False, _short(e)
        unexpected += not ok
        print(f"{'ok' if ok else 'FAIL':<14s} {name}: {msg} "
              f"[{time.perf_counter() - t0:.1f}s]", flush=True)
        results.append({"family": name, "ok": ok, "detail": msg})
    if AOT:
        for name, lower in aot_train_steps(topo):
            t0 = time.perf_counter()
            lowered = lower()
            n = lowered.as_text().count("tpu_custom_call")
            ma = lowered.compile().memory_analysis()
            msg = (f"compiled for v5e ({n} Mosaic calls; args "
                   f"{ma.argument_size_in_bytes / 1e6:.0f} MB, temp "
                   f"{ma.temp_size_in_bytes / 1e6:.0f} MB)")
            print(f"{'ok':<14s} {name}: {msg} "
                  f"[{time.perf_counter() - t0:.1f}s]", flush=True)
            results.append({"family": name, "ok": True, "detail": msg})
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as f:
        json.dump({"mode": "aot" if AOT else "device", "device": device,
                   "results": results}, f, indent=1)
    good = sum(r["ok"] for r in results)
    print(f"kernel_check: {good}/{len(results)} ok, {unexpected} unexpected")
    return 1 if unexpected else 0


if __name__ == "__main__":
    sys.exit(main())
