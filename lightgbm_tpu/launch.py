"""Multi-process training launcher — the dask.py analog.

The reference's Dask integration (python-package/lightgbm/dask.py:196-260)
finds open ports, builds the `machines` list, and runs `_train_part` (a
plain lgb.train call with machines/num_machines/local_listen_port) once
per worker. Here the transport is the JAX runtime: the launcher spawns N
worker processes wired into one process group via
`jax.distributed.initialize`, and each worker's `lgb.train(params, ...)`
with `num_machines=N, tree_learner="data"` joins the group automatically
(parallel/distributed.py reads the launcher's environment).

Single-machine multi-process (the DistributedMockup pattern,
tests/distributed/_test_distributed.py:53) — a CPU mock: every rank is
one CPU device of this host. A chip belongs to one process, so the
launcher refuses any other ``JAX_PLATFORMS``; one process drives all the
chips of a TPU host (``tree_learner=data`` with no launcher at all):

    python -m lightgbm_tpu.launch -n 4 -- python train_rank.py

Each worker gets LIGHTGBM_TPU_RANK / LIGHTGBM_TPU_NPROC /
LIGHTGBM_TPU_COORDINATOR; `train_rank.py` reads its rank, loads ITS OWN
data shard (params: pre_partition=true), and calls lgb.train. Every rank
produces the identical model (the data-parallel invariant).

On real multi-host TPU pods the pod runtime starts one process per host;
set the same three variables (or pass `machines=` in params) and skip
this launcher.

Metrics note: with pre_partition=true, per-iteration metric printouts are
computed on each rank's local shard (the reference syncs rank sums for
exact global metrics); evaluate the saved model globally for exact
numbers.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
from typing import List, Optional, Sequence


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local(num_machines: int, argv: Sequence[str],
                 coordinator_port: Optional[int] = None,
                 env_extra: Optional[dict] = None,
                 timeout: Optional[float] = None) -> List[int]:
    """Spawn `num_machines` copies of `argv` as one JAX process group of
    CPU ranks on this machine (each with ONE virtual CPU device unless
    the caller's XLA_FLAGS say otherwise). Returns the list of exit
    codes; raises RuntimeError if any worker failed, or if the
    environment asks for an accelerator the ranks would have to share."""
    base_env = dict(os.environ)
    base_env.update(env_extra or {})
    if base_env.setdefault("JAX_PLATFORMS", "cpu") != "cpu":
        raise RuntimeError(
            "launch_local is a CPU mock of a multi-host group: its ranks "
            "cannot share a chip (JAX_PLATFORMS="
            f"{base_env['JAX_PLATFORMS']!r}); run one process per TPU "
            "host, or unset JAX_PLATFORMS")
    port = coordinator_port or _free_port()
    procs = []
    for rank in range(num_machines):
        env = dict(base_env)
        env["LIGHTGBM_TPU_RANK"] = str(rank)
        env["LIGHTGBM_TPU_NPROC"] = str(num_machines)
        env["LIGHTGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
        procs.append(subprocess.Popen(list(argv), env=env))
    import time as _time

    deadline = _time.monotonic() + timeout if timeout else None
    try:
        # poll ALL workers: one crashed rank must bring the group down
        # (the survivors block in collectives waiting for it forever)
        while True:
            codes = [p.poll() for p in procs]
            if any(c not in (0, None) for c in codes):
                break
            if all(c == 0 for c in codes):
                break
            if deadline and _time.monotonic() > deadline:
                raise RuntimeError("launch_local timed out; worker "
                                   f"states: {codes}")
            _time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        codes = [p.wait() for p in procs]
    if any(c != 0 for c in codes):
        raise RuntimeError(f"worker exit codes: {codes}")
    return codes


def main() -> None:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.launch",
        description="Run a training script as N coordinated processes")
    ap.add_argument("-n", "--num-machines", type=int, required=True)
    ap.add_argument("--port", type=int, default=None,
                    help="coordinator port (default: auto)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run, e.g. -- python train.py")
    args = ap.parse_args()
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given")
    launch_local(args.num_machines, cmd, coordinator_port=args.port)


if __name__ == "__main__":
    main()
