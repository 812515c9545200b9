"""Every Pallas kernel family still lowers for the TPU, checked from the
CPU host: ``jax.export`` for ``platforms=["tpu"]`` runs the Pallas ->
Mosaic lowering rules (not the Mosaic compiler), so a Pallas symbol the
installed JAX dropped, an unsupported cast or an index that traces to a
gather fails tier-1 instead of waiting for a chip. The family list is
scripts/kernel_check.py's — the one the chip run and the ``--aot``
compile use — so a new family is listed once."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))

import kernel_check  # noqa: E402

CASES = dict(kernel_check.cases())


@pytest.mark.parametrize("name", sorted(CASES))
def test_family_lowers_for_tpu(name):
    make, fn_of = CASES[name]()
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in map(np.asarray, make(np.random.RandomState(0)))]
    export = jax.export.export(jax.jit(fn_of(False)), platforms=["tpu"])
    assert export(*specs).mlir_module().count("tpu_custom_call") >= 1
