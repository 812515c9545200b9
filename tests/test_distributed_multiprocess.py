"""Multi-process distributed training test — the DistributedMockup analog
(reference: tests/distributed/_test_distributed.py:53: N copies of the
trainer as separate localhost processes, each owning a row shard,
tree_learner=data, joint model asserted against single-process training).

Here each process is a separate Python interpreter with ONE virtual CPU
device, wired into a single JAX process group via
parallel/distributed.py (jax.distributed.initialize over loopback). Rank
0 writes the model + training AUC; the test asserts quality and that
every rank produced the identical model (the data-parallel invariant,
SURVEY.md §3.4).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import json, os, sys
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

rank = int(os.environ["LIGHTGBM_TPU_RANK"])
nproc = int(os.environ["LIGHTGBM_TPU_NPROC"])
port = os.environ["LIGHTGBM_TPU_PORT"]
out_dir = os.environ["LIGHTGBM_TPU_OUT"]

from lightgbm_tpu.parallel.distributed import init_distributed
init_distributed(num_machines=nproc, machine_rank=rank,
                 coordinator_address=f"127.0.0.1:{port}")

import jax
assert jax.device_count() == nproc, jax.device_count()

import lightgbm_tpu as lgb

# identical dataset on every rank (pre_partition=false semantics: the
# mockup feeds each process the full file; rows shard over the mesh)
rng = np.random.RandomState(7)
N = 4000
X = rng.normal(size=(N, 10)).astype(np.float32)
w = rng.normal(size=10)
y = (X @ w + rng.normal(scale=0.5, size=N) > 0).astype(np.float32)

params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
              verbose=-1, tree_learner="data", min_data_in_leaf=5)
bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
model = bst.model_to_string()
pred = bst.predict(X)

from sklearn.metrics import roc_auc_score
auc = float(roc_auc_score(y, pred))
import hashlib
with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
    json.dump({"auc": auc,
               "model_hash": hashlib.md5(model.encode()).hexdigest(),
               "model_len": len(model)}, f)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_multiprocess_data_parallel(tmp_path):
    """Two CPU ranks train one data-parallel model over real
    cross-process collectives and must produce the identical model."""
    nproc = 2
    port = _free_port()
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env_base = {k: v for k, v in os.environ.items()}
    env_base.pop("JAX_PLATFORMS", None)
    procs = []
    for rank in range(nproc):
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(env_base,
                   PYTHONPATH=repo_root,
                   LIGHTGBM_TPU_RANK=str(rank),
                   LIGHTGBM_TPU_NPROC=str(nproc),
                   LIGHTGBM_TPU_PORT=str(port),
                   LIGHTGBM_TPU_OUT=str(tmp_path),
                   JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=850)
        outs.append(out.decode())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]

    results = []
    for rank in range(nproc):
        with open(tmp_path / f"rank{rank}.json") as f:
            results.append(json.load(f))
    # every rank must converge to the IDENTICAL model (§3.4 invariant)
    assert len({r["model_hash"] for r in results}) == 1, results
    assert len({r["model_len"] for r in results}) == 1, results
    assert results[0]["auc"] > 0.96, results


_WORKER_PREPART = r"""
import json, os, sys
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

rank = int(os.environ["LIGHTGBM_TPU_RANK"])
nproc = int(os.environ["LIGHTGBM_TPU_NPROC"])
out_dir = os.environ["LIGHTGBM_TPU_OUT"]

import lightgbm_tpu as lgb

# each rank loads ONLY its own shard from its own file (pre-partitioned
# load, dataset_loader.cpp:1162-1213): the file was written by the test
Xy = np.load(os.path.join(out_dir, f"shard{rank}.npz"))
X, y = Xy["X"], Xy["y"]

params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
              verbose=-1, tree_learner="data", min_data_in_leaf=5,
              pre_partition=True, num_machines=nproc)
bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
model = bst.model_to_string()

# local-shard AUC of the joint model
from sklearn.metrics import roc_auc_score
auc = float(roc_auc_score(y, bst.predict(X)))
import hashlib
with open(os.path.join(out_dir, f"pp_rank{rank}.json"), "w") as f:
    json.dump({"auc": auc,
               "model_hash": hashlib.md5(model.encode()).hexdigest()}, f)
if rank == 0:
    bst.save_model(os.path.join(out_dir, "pp_model.txt"))
"""


def test_multiprocess_pre_partitioned(tmp_path):
    """Each rank reads ONLY its own file shard (pre_partition=true with
    distributed feature-sliced binning + mapper allgather); the joint
    model must be rank-identical and match single-process quality."""
    nproc = 2
    rng = np.random.RandomState(11)
    N, F = 6000, 12
    X = rng.normal(size=(N, F)).astype(np.float32)
    w = rng.normal(size=F)
    y = (X @ w + rng.normal(scale=0.5, size=N) > 0).astype(np.float32)
    half = N // nproc
    for rank in range(nproc):
        np.savez(tmp_path / f"shard{rank}.npz",
                 X=X[rank * half:(rank + 1) * half],
                 y=y[rank * half:(rank + 1) * half])

    worker = tmp_path / "worker_pp.py"
    worker.write_text(_WORKER_PREPART)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    env_base = {k: v for k, v in os.environ.items()}
    env_base.pop("JAX_PLATFORMS", None)
    procs = []
    for rank in range(nproc):
        env = dict(env_base,
                   PYTHONPATH=repo_root,
                   LIGHTGBM_TPU_RANK=str(rank),
                   LIGHTGBM_TPU_NPROC=str(nproc),
                   LIGHTGBM_TPU_COORDINATOR=f"127.0.0.1:{port}",
                   LIGHTGBM_TPU_OUT=str(tmp_path),
                   JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=850)
        outs.append(out.decode())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]

    results = []
    for rank in range(nproc):
        with open(tmp_path / f"pp_rank{rank}.json") as f:
            results.append(json.load(f))
    # rank-identical joint model (the §3.4 invariant)
    assert len({r["model_hash"] for r in results}) == 1, results

    # joint model quality ~ single-process full-data training (bin
    # boundaries differ slightly: rank-local samples, as in the
    # reference's pre-partitioned path)
    import lightgbm_tpu as lgb
    from sklearn.metrics import roc_auc_score
    bst_joint = lgb.Booster(model_file=str(tmp_path / "pp_model.txt"))
    auc_joint = roc_auc_score(y, bst_joint.predict(X))
    bst_single = lgb.train(
        dict(objective="binary", num_leaves=15, learning_rate=0.2,
             verbose=-1, min_data_in_leaf=5),
        lgb.Dataset(X, label=y), num_boost_round=10)
    auc_single = roc_auc_score(y, bst_single.predict(X))
    assert auc_joint > auc_single - 0.02, (auc_joint, auc_single)


def test_launcher_cli(tmp_path):
    """python -m lightgbm_tpu.launch spawns a coordinated group."""
    script = tmp_path / "probe.py"
    script.write_text(
        "import os\n"
        "from lightgbm_tpu.parallel.distributed import init_distributed\n"
        "init_distributed(num_machines="
        "int(os.environ['LIGHTGBM_TPU_NPROC']))\n"
        "import jax\n"
        "assert jax.process_count() == 2, jax.process_count()\n"
        "assert jax.device_count() == 2, jax.device_count()\n"
        f"open(os.path.join({str(tmp_path)!r}, "
        "f\"ok{jax.process_index()}\"), 'w').write('1')\n")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()}
    env["PYTHONPATH"] = repo_root
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.launch", "-n", "2", "--",
         sys.executable, str(script)],
        env=env, timeout=600, cwd=repo_root, capture_output=True,
        text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert (tmp_path / "ok0").exists() and (tmp_path / "ok1").exists()
