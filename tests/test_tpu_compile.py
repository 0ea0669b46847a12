"""Compile the BSR kernels for a TPU v5e at yi-6b's projection widths.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for
a described (not attached) ``v5e:2x2`` topology, so a kernel that Mosaic
would refuse - a block not lane-aligned, more SMEM than the chip has -
fails here instead of on the chip. The topology is described inside a
fixture, never at import, so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.yi_6b import CONFIG as YI
from repro.core.deploy import bm_for_rows
from repro.kernels import cim_bsr_matmul as K

# 128x128 is the MXU tile; the search picks 256x128 for yi-6b
TILES = [(128, 128), (256, 128)]

# (d_in, d_out) of every packed yi-6b projection
PROJECTIONS = {
    "q_o": (YI.d_model, YI.n_heads * YI.dh),
    "kv": (YI.d_model, YI.n_kv_heads * YI.dh),
    "gate_up": (YI.d_model, YI.d_ff),
    "down": (YI.d_ff, YI.d_model),
    "head": (YI.d_model, YI.vocab),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packing(d_in, d_out, tile, layers, sharding):
    """Shapes of a packing with every block stored (the largest SMEM and
    grid a projection of this width can need)."""
    bk, bn = tile
    gi, go = d_in // bk, d_out // bn
    lead = (layers,) if layers else ()
    return (_sds(lead + (go, gi, bk, bn), jnp.int8, sharding),
            _sds(lead + (go, gi), jnp.float32, sharding),
            _sds(lead + (go, gi), jnp.int32, sharding),
            _sds(lead + (go,), jnp.int32, sharding))


def _compiled_text(fn, args, m):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"no Mosaic kernel at M={m}"
    return text


# serving quantizes activations to f32 before the kernel (a_bits > 0);
# bf16 is the unquantized activation dtype
XDTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("xdtype", XDTYPES)
@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_bsr_matmul_compiles_for_v5e(one_chip, proj, m, xdtype, tile):
    d_in, d_out = PROJECTIONS[proj]
    x = _sds((m, d_in), xdtype, one_chip)

    def fn(x, blocks, scales, row_idx, nnz):
        return K.bsr_matmul(x, blocks, scales, row_idx, nnz,
                            bm=bm_for_rows(m), interpret=False)

    _compiled_text(fn, (x, *_packing(d_in, d_out, tile, 0, one_chip)), m)


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("xdtype", XDTYPES)
@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_bsr_matmul_stacked_compiles_for_v5e(one_chip, proj, m, xdtype,
                                             tile):
    d_in, d_out = PROJECTIONS[proj]
    x = _sds((m, d_in), xdtype, one_chip)
    layer = _sds((), jnp.int32, one_chip)

    def fn(x, blocks, scales, row_idx, nnz, layer):
        return K.bsr_matmul_stacked(x, blocks, scales, row_idx, nnz, layer,
                                    bm=bm_for_rows(m), interpret=False)

    _compiled_text(
        fn, (x, *_packing(d_in, d_out, tile, YI.n_layers, one_chip), layer),
        m)


def test_kernel_keeps_its_name_in_the_compiled_program(one_chip):
    """The device trace names the kernel's calls by the custom call's HLO
    name, which the kernel sets itself, whatever function wraps it."""
    d_in, d_out = PROJECTIONS["kv"]
    x = _sds((8, d_in), jnp.float32, one_chip)

    def some_wrapper(x, blocks, scales, row_idx, nnz):
        return K.bsr_matmul(x, blocks, scales, row_idx, nnz, interpret=False)

    text = _compiled_text(
        some_wrapper, (x, *_packing(d_in, d_out, (256, 128), 0, one_chip)), 8)
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(", text)
    assert calls and all(re.fullmatch(r"bsr_matmul_stacked(\.\d+)?", c)
                         for c in calls), calls


def test_unaligned_tile_is_refused_by_name():
    """A tile the compiled kernel cannot take fails before Mosaic does,
    and the error names the tile."""
    with pytest.raises(ValueError, match="64x128"):
        K.check_tile(64, 128, interpret=False)
    K.check_tile(64, 128, interpret=True)
    K.check_tile(128, 256, interpret=False)
