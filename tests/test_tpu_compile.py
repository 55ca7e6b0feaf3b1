"""Compile the register-file kernels for a described TPU v5e chip.

No chip is needed: JAX's TPU compiler, installed with libtpu, compiles for
a topology that is described and not attached, and refuses what the chip's
compiler would refuse (unaligned dynamic slices, scalar stores to VMEM,
primitives with no Mosaic lowering) — the faults interpret-mode tests
cannot see. Every kernel of the device-resident register file is compiled
at the real size: a 40,000-slot segment (the SwitchMemory default, paper
§6.1), dense runs of 32,768 slots and the whole segment, reads of 1,024
and 32,768 slots that also clear them, the addTo and the clearing read
of such a run in one program, 4,096-entry scatter streams and
the 65,536-entry int bucket a full-segment run pads to, plus the
(rows, 128)-tiled stream kernels of the ring path.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.constants import DEFAULT_BLOCK_ROWS, LANES
from repro.kernels.dequantize import dequantize_pallas
from repro.kernels.fused_gpv import (fused_addto_pallas,
                                     fused_addto_read_pallas,
                                     fused_fold_pallas,
                                     fused_read_clear_pallas,
                                     fused_read_pallas, fused_scatter_pallas)
from repro.kernels.inc_agg import sat_add_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.sparse_addto import sparse_addto_pallas

SLOTS = 40_000
I32, F32 = jnp.int32, jnp.float32
TILES = (2 * DEFAULT_BLOCK_ROWS, LANES)


def _addto(r, s, f, c):
    return fused_addto_pallas(r, s, f, c, interpret=False)


def _read(n):
    return lambda r, s, c: fused_read_pallas(r, s, n, c, interpret=False)


def _read_clear(n):
    return lambda r, s, live, c: fused_read_clear_pallas(
        r, s, n, live, c, interpret=False)


def _addto_read(n, clear):
    return lambda r, s, f, c: fused_addto_read_pallas(
        r, s, f, c, n, clear, interpret=False)


CASES = [
    ("fused_addto_1024", _addto,
     [((SLOTS,), I32), ((), I32), ((1024,), F32), ((), F32)]),
    ("fused_addto_dense32768", _addto,
     [((SLOTS,), I32), ((), I32), ((32768,), F32), ((), F32)]),
    ("fused_addto_full_segment", _addto,
     [((SLOTS,), I32), ((), I32), ((SLOTS,), F32), ((), F32)]),
    ("fused_read_1024", _read(1024), [((SLOTS,), I32), ((), I32), ((), F32)]),
    ("fused_read_dense32768", _read(32768),
     [((SLOTS,), I32), ((), I32), ((), F32)]),
    ("fused_read_full_segment", _read(SLOTS),
     [((SLOTS,), I32), ((), I32), ((), F32)]),
    ("fused_read_clear_1024", _read_clear(1024),
     [((SLOTS,), I32), ((), I32), ((), I32), ((), F32)]),
    ("fused_read_clear_dense32768", _read_clear(32768),
     [((SLOTS,), I32), ((), I32), ((), I32), ((), F32)]),
    ("fused_addto_read_clear_1024", _addto_read(1024, True),
     [((SLOTS,), I32), ((), I32), ((1024,), F32), ((), F32)]),
    ("fused_addto_read_clear_dense32768", _addto_read(32768, True),
     [((SLOTS,), I32), ((), I32), ((32768,), F32), ((), F32)]),
    ("fused_addto_read_dense32768", _addto_read(32768, False),
     [((SLOTS,), I32), ((), I32), ((32768,), F32), ((), F32)]),
    ("fused_scatter_4096",
     lambda r, i, f, c: fused_scatter_pallas(r, i, f, c, interpret=False),
     [((SLOTS,), I32), ((4096,), I32), ((4096,), F32), ((), F32)]),
    ("sparse_addto_4096",
     lambda r, i, v: sparse_addto_pallas(r, i, v, interpret=False),
     [((SLOTS,), I32), ((4096,), I32), ((4096,), I32)]),
    ("sparse_addto_65536",
     lambda r, i, v: sparse_addto_pallas(r, i, v, interpret=False),
     [((SLOTS,), I32), ((65536,), I32), ((65536,), I32)]),
    ("fused_fold_8x40000",
     lambda x, c: fused_fold_pallas(x, c, interpret=False),
     [((8, SLOTS), F32), ((), F32)]),
    ("fused_fold_8x262144",
     lambda x, c: fused_fold_pallas(x, c, interpret=False),
     [((8, 262144), F32), ((), F32)]),
    ("quantize_tiles",
     lambda x, c: quantize_pallas(x, c, interpret=False),
     [(TILES, F32), ((), F32)]),
    ("dequantize_tiles",
     lambda q, c: dequantize_pallas(q, c, interpret=False),
     [(TILES, I32), ((), F32)]),
    ("sat_add_tiles",
     lambda a, b: sat_add_pallas(a, b, interpret=False),
     [(TILES, I32), (TILES, I32)]),
]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compile
    cache off (a cached entry compiled for a described chip cannot be
    read back without one) and libtpu's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:    # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.mark.parametrize("name,fn,args", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, args):
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
