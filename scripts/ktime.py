"""Profiler-based kernel timing: device-side durations from the xplane,
immune to host dispatch noise. Import `ktime(fn, *args)` -> dict of
{op_name_prefix: ms_per_call}."""
import collections
import glob
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp


def _barrier(out):
    leaves = jax.tree.leaves(out)
    jax.device_get(jnp.sum(leaves[0].astype(jnp.float32).ravel()[:16]))


def ktime(fn, *args, reps=10, match="custom-call"):
    """Run fn reps times under a device trace; return total device ms/rep
    for events whose name contains `match` (plus a per-op breakdown)."""
    out = fn(*args)
    _barrier(out)
    tmp = tempfile.mkdtemp(prefix="ktime_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(reps):
            out = fn(*args)
        _barrier(out)
        jax.profiler.stop_trace()
        pbs = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
        from jax.profiler import ProfileData
        agg = collections.Counter()
        for pb in pbs:
            pd = ProfileData.from_serialized_xspace(open(pb, "rb").read())
            for plane in pd.planes:
                if "TPU" not in plane.name:
                    continue
                for line in plane.lines:
                    for ev in line.events:
                        agg[ev.name[:60]] += ev.duration_ns
        total = sum(ns for name, ns in agg.items() if match in name)
        return total / reps / 1e6, {
            n: ns / reps / 1e6 for n, ns in agg.most_common(10)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
