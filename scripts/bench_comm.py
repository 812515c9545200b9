"""Histogram-exchange communication bench: allreduce vs reduce_scatter
vs packed-int payloads (docs/PERF.md §Communication; the measurement
behind ``parallel_hist_mode``).

Per mesh size k this reports, for the representative per-leaf exchange
payload [C, F_pad, B]:

  * analytic byte accounting — bytes RECEIVED per rank per split
    (allreduce materializes the full summed buffer on every rank;
    reduce_scatter only the owned F_pad/k slice → a (k-1)/k reduction)
    and ring-algorithm wire bytes (2(k-1)/k vs (k-1)/k of the payload);
    the packed int32-packed-int16 quantized lane halves both again
    (parallel/packed.py);
  * measured step time of the jitted collective on the actual mesh:
    full-buffer ``psum``, ``psum_scatter`` over the padded feature
    axis, and ``psum_scatter`` of the packed int32 lane.

This is a CPU MOCK: it times XLA:CPU's collectives between virtual
devices of one host, which says nothing about ICI — the byte accounting
is exact, the step times are not device numbers (the result is stamped
``"device": "cpu-virtual"``). The parent never touches JAX; every child
is held to ``JAX_PLATFORMS=cpu``, so no chip is involved even on a TPU
host, and a failed child fails the bench.

A CPU host has one device, and the XLA device-count flag must be set
before the backend initializes — so the driver re-execs itself as one
child interpreter per mesh size with
``--xla_force_host_platform_device_count=k`` (the same virtual-mesh
mechanism as tests/), then merges the children's JSON and writes
``BENCH_COMM.json`` at the repo root (consumed by
scripts/check_stale_claims.py). Also runnable as ``BENCH_COMM=1 python
bench.py``.

Env knobs: COMM_MESH_SIZES (default "2,4"), COMM_FEATURES (64),
COMM_BINS (64), COMM_REPS (5).
"""

import json
import os
import subprocess
import sys
import time

_CHILD_ENV = "_BENCH_COMM_CHILD"


def _child_main() -> None:
    """Runs inside the re-exec'd interpreter: one mesh, three arms."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.context import (DATA_AXIS, DistContext,
                                               make_data_mesh)
    from lightgbm_tpu.parallel.packed import pack_gh, unpack_gh
    from lightgbm_tpu.runtime.profiler import device_barrier
    from lightgbm_tpu.utils import round_up

    F = int(os.environ.get("COMM_FEATURES", "64"))
    B = int(os.environ.get("COMM_BINS", "64"))
    reps = int(os.environ.get("COMM_REPS", "5"))
    C = 2                                    # (grad, hess) lanes

    mesh = make_data_mesh()
    k = int(mesh.devices.size)
    dist = DistContext(DATA_AXIS)
    Fp = round_up(F, k)
    rng = np.random.RandomState(0)
    buf_f32 = jnp.asarray(
        rng.uniform(-1, 1, size=(C, Fp, B)).astype(np.float32))
    buf_i32 = jnp.asarray(
        rng.randint(0, 1 << 10, size=(C, Fp, B)).astype(np.int32))

    def arm_allreduce(x):
        return dist.psum(x)

    def arm_reduce_scatter(x):
        return dist.psum_scatter(x, axis=1)

    def arm_packed(x):
        # the quantized wire path: fold (g, h) int32 lanes into one
        # int32-packed-int16 lane, scatter, unfold
        return unpack_gh(dist.psum_scatter(pack_gh(x, 0), axis=1), 0)

    payload = C * Fp * B * 4
    arms = {
        "allreduce": (arm_allreduce, buf_f32, P(),
                      payload, 2 * (k - 1) / k * payload),
        "reduce_scatter": (arm_reduce_scatter, buf_f32,
                           P(None, DATA_AXIS, None),
                           payload // k, (k - 1) / k * payload),
        "packed": (arm_packed, buf_i32, P(None, DATA_AXIS, None),
                   payload // k // 2, (k - 1) / k * payload / 2),
    }

    out = {"mesh_size": k, "features": F, "features_padded": Fp,
           "num_bins": B, "channels": C, "payload_bytes": payload}
    for name, (fn, buf, out_spec, recv, wire) in arms.items():
        jitted = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),), out_specs=out_spec,
            check_vma=False))
        jax.block_until_ready(jitted(buf))            # compile + warm
        best = float("inf")
        for _ in range(reps):
            device_barrier()
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(buf))
            best = min(best, time.perf_counter() - t0)
        out[name] = {
            "recv_bytes_per_rank": int(recv),
            "wire_bytes_ring": int(wire),
            "step_time_s": round(best, 6),
        }
    ar = out["allreduce"]["recv_bytes_per_rank"]
    rs = out["reduce_scatter"]["recv_bytes_per_rank"]
    pk = out["packed"]["recv_bytes_per_rank"]
    out["byte_reduction_vs_allreduce"] = round(1.0 - rs / ar, 6)
    out["packed_extra_factor"] = round(rs / pk, 4)
    print(json.dumps(out))


def main() -> None:
    if os.environ.get(_CHILD_ENV):
        _child_main()
        return

    sizes = [int(s) for s in
             os.environ.get("COMM_MESH_SIZES", "2,4").split(",") if s]
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    meshes = []
    for k in sizes:
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={k}",
                   PYTHONPATH=repo_root,
                   **{_CHILD_ENV: "1"})
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise SystemExit(f"bench_comm: mesh size {k} failed:\n"
                             + proc.stderr[-2000:])
        meshes.append(json.loads(proc.stdout.strip().splitlines()[-1]))

    result = {"metric": "hist_exchange_allreduce_vs_reduce_scatter",
              "device": "cpu-virtual",
              "meshes": meshes}
    text = json.dumps(result, indent=2)
    out_path = os.path.join(repo_root, "BENCH_COMM.json")
    with open(out_path, "w") as f:
        f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
