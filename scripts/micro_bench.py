"""Microbenchmarks for the wave-grower redesign (run on the real TPU chip).

Measures the primitive costs that decide the histogram/grower architecture:
slot-kernel scaling in K, gather/take throughput, sort, select chains.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

N = 4_000_000
F = 28
B = 256


def timeit(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))          # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.randint(0, 255, size=(F, N), dtype=np.uint8)
                    .astype(np.int8))
    Xr = jnp.asarray(np.ascontiguousarray(
        rng.randint(0, 255, size=(N, 32), dtype=np.uint8).astype(np.int8)))
    g = jnp.asarray(rng.normal(size=(N,)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=(N,)).astype(np.float32))
    vals = jnp.stack([g, h])
    vals8 = jnp.asarray(rng.randint(-127, 127, (2, N), dtype=np.int32)
                        .astype(np.int8))
    slot128 = jnp.asarray(rng.randint(0, 128, size=(N,), dtype=np.int32))

    from lightgbm_tpu.ops.histogram_pallas import build_histogram_slots_pallas

    for K in (1, 8, 32, 128):
        sl = jnp.minimum(slot128, K - 1)
        t = timeit(functools.partial(build_histogram_slots_pallas,
                                     num_slots=K, num_bins=B), X, vals, sl)
        print(f"slots_kernel f32 K={K:3d}: {t*1e3:8.2f} ms")
    for K in (1, 8, 32, 128):
        sl = jnp.minimum(slot128, K - 1)
        t = timeit(functools.partial(build_histogram_slots_pallas,
                                     num_slots=K, num_bins=B), X, vals8, sl)
        print(f"slots_kernel int8 K={K:3d}: {t*1e3:8.2f} ms")

    # gather half the rows (sorted indices), feature-major layout
    idx = jnp.sort(jnp.asarray(
        rng.choice(N, size=N // 2, replace=False).astype(np.int32)))

    @jax.jit
    def take_fmajor(X, idx):
        return jnp.take(X, idx, axis=1)

    t = timeit(take_fmajor, X, idx)
    print(f"take [F,N] axis1 N/2: {t*1e3:8.2f} ms "
          f"({F * N / 2 / t / 1e9:.1f} GB/s)")

    @jax.jit
    def take_rmajor(Xr, idx):
        return jnp.take(Xr, idx, axis=0)

    t = timeit(take_rmajor, Xr, idx)
    print(f"take [N,32] axis0 N/2: {t*1e3:8.2f} ms "
          f"({32 * N / 2 / t / 1e9:.1f} GB/s)")

    @jax.jit
    def take_f32(g, idx):
        return jnp.take(g, idx, axis=0)

    t = timeit(take_f32, g, idx)
    print(f"take f32 [N] N/2:     {t*1e3:8.2f} ms "
          f"({4 * N / 2 / t / 1e9:.1f} GB/s)")

    # scatter: X[:, idx] = vals  (dynamic update at half positions)
    @jax.jit
    def scat_rmajor(Xr, idx, rows):
        return Xr.at[idx].set(rows)

    rows = Xr[:N // 2]
    t = timeit(scat_rmajor, Xr, idx, rows)
    print(f"scatter [N,32] axis0 N/2: {t*1e3:8.2f} ms "
          f"({32 * N / 2 / t / 1e9:.1f} GB/s)")

    # sort: 4M keys + 1 int payload
    keys = jnp.asarray(rng.randint(0, 255, size=(N,), dtype=np.int32))
    payload = jnp.arange(N, dtype=jnp.int32)

    @jax.jit
    def sort2(keys, payload):
        return jax.lax.sort((keys, payload), num_keys=1)

    t = timeit(sort2, keys, payload)
    print(f"sort 4M key+payload:  {t*1e3:8.2f} ms")

    @jax.jit
    def argsortN(keys):
        return jnp.argsort(keys)

    t = timeit(argsortN, keys)
    print(f"argsort 4M:           {t*1e3:8.2f} ms")

    @jax.jit
    def cumsumN(g):
        return jnp.cumsum(g)

    t = timeit(cumsumN, g)
    print(f"cumsum 4M f32:        {t*1e3:8.2f} ms")

    # select chain over F features (table_go_left inner loop shape)
    @jax.jit
    def select_chain(X, feat):
        col = jnp.zeros((N,), jnp.int32)
        for f in range(F):
            col = jnp.where(feat == f, X[f].astype(jnp.int32), col)
        return col

    feat = jnp.asarray(rng.randint(0, F, size=(N,), dtype=np.int32))
    t = timeit(select_chain, X, feat)
    print(f"select chain F=28:    {t*1e3:8.2f} ms")

    # K-length select chain over N (slot -> scalar map)
    @jax.jit
    def slot_chain(slot128, v):
        out = jnp.zeros((N,), jnp.float32)
        for j in range(128):
            out = jnp.where(slot128 == j, v[j], out)
        return out

    v = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
    t = timeit(slot_chain, slot128, v)
    print(f"slot select chain K=128: {t*1e3:8.2f} ms")

    # small-table gather instead of chain
    @jax.jit
    def small_gather(slot128, v):
        return v[jnp.clip(slot128, 0, 127)]

    t = timeit(small_gather, slot128, v)
    print(f"small-table gather [128] by 4M idx: {t*1e3:8.2f} ms")


if __name__ == "__main__":
    main()
