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


# A numeric table's wave kernels are the same program with or without
# the categorical bitset operand the categorical tables take: their
# lowering for the TPU, byte for byte, is the one recorded before that
# operand existed (sha256 of the exported module's text; JAX 0.9.0).
# The megakernel's two were recorded again when it began to compact its
# rows before the contraction (ops/histogram_pallas.py _stage_block);
# wave_relabel and the in-kernel wave_apply kept theirs.
NUMERIC_TEXT = {
    "megakernel B64 f32":
        "cf7c0b0d17b9886468bf46448265973b8af4ff502c644a0f71ecd6b19fc84410",
    "megakernel hi/lo B256 int8":
        "0b3184fe4a56d0bbab10987f105e41d879a002575ea9d012d754e193c965b21f",
    "wave_relabel B256 f32":
        "f679d4305e74ffdd79fa6fb96407d6bb68e39550533b387775ce061ac3ca5820",
    "wave_apply in-kernel F137":
        "7e6b411e7337dcd456b7323f239ed83aa2f203fc3eb03d2d0b704d10290ab898",
}


@pytest.mark.parametrize("name", sorted(NUMERIC_TEXT))
def test_numeric_wave_kernels_lower_as_recorded(name):
    import hashlib
    make, fn_of = CASES[name]()
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in map(np.asarray, make(np.random.RandomState(0)))]
    text = jax.export.export(jax.jit(fn_of(False)),
                             platforms=["tpu"])(*specs).mlir_module()
    assert hashlib.sha256(text.encode()).hexdigest() == NUMERIC_TEXT[name]
