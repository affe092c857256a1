"""Compile the main path's Pallas kernels for a described TPU v5e, no chip.

The TPU compiler ships with jaxlib and compiles for a topology that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes off the (8, 128) tiling, operand forms Mosaic cannot lower,
HBM refs read without a DMA, more VMEM than a kernel may use. Shapes are
smollm-135m's: its embedding leaf (49152 x 576 elements, 110592 blocks) for
the spectral-lossy codec, and its heads (9 query, 3 kv, head_dim 64) for
paged decode attention. Nothing runs; each test asserts that the compiled
program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and a
test worker that described it at import would keep the others from it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import paged_attention as PK
from repro.kernels import spectral_lossy as K
from repro.kernels.ref import BLOCK

LEAF_BLOCKS = 49152 * 576 // BLOCK          # smollm-135m embedding leaf
TUNER_BLOCKS = 4096                         # a pow2 bucket >= every tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (kernel, argument shapes/dtypes) at the smollm-135m leaf size
_CODEC = {
    "dct_hist_coarse": (K.dct_hist_coarse, [((LEAF_BLOCKS, BLOCK), "f32")]),
    "hist_refine": (K.hist_refine, [((LEAF_BLOCKS, BLOCK), "f32"),
                                    ((LEAF_BLOCKS,), "i32")]),
    "threshold_quant": (K.threshold_quant, [((LEAF_BLOCKS, BLOCK), "f32"),
                                            ((LEAF_BLOCKS,), "f32")]),
    "dequant_idct": (K.dequant_idct, [((LEAF_BLOCKS, BLOCK), "i8"),
                                      ((LEAF_BLOCKS,), "f32")]),
    "dct_hist_coarse_tiled": (K.dct_hist_coarse_tiled,
                              [((LEAF_BLOCKS, BLOCK), "f32")]),
    "hist_refine_tiled": (K.hist_refine_tiled,
                          [((LEAF_BLOCKS, BLOCK), "f32"),
                           ((LEAF_BLOCKS,), "i32")]),
}
_DT = {"f32": jnp.float32, "i32": jnp.int32, "i8": jnp.int8}


@pytest.mark.parametrize("name", sorted(_CODEC))
def test_codec_kernel_compiles_for_v5e(one_chip, name):
    kernel, arg_specs = _CODEC[name]
    args = [_spec(one_chip, shape, _DT[dt]) for shape, dt in arg_specs]
    text = _compiled_text(functools.partial(kernel, interpret=False), *args)
    assert "tpu_custom_call" in text


# every tile the autotuner may time on the chip must compile: the tuner
# compiles them inside the first compress, where a refusal is fatal
_TUNED_KERNELS = {"hist": ("dct_hist_coarse", "hist_refine",
                           "dct_hist_coarse_tiled", "hist_refine_tiled"),
                  "quant": ("threshold_quant", "dequant_idct")}


@pytest.mark.parametrize("kind,tile", [
    (kind, tile) for kind, tiles in sorted(ops._TILE_CANDIDATES.items())
    for tile in tiles])
def test_tuner_candidate_tiles_compile_for_v5e(one_chip, kind, tile):
    for name in _TUNED_KERNELS[kind]:
        kernel, arg_specs = _CODEC[name]
        args = [_spec(one_chip, (TUNER_BLOCKS,) + shape[1:], _DT[dt])
                for shape, dt in arg_specs]
        fn = functools.partial(kernel, interpret=False, tile=tile)
        assert "tpu_custom_call" in _compiled_text(fn, *args), name


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_decode_attention_compiles_for_v5e(one_chip, dtype):
    b, pages_per_seq, num_pages, page_size = 8, 16, 65, 16
    hq, n_kv, d = 9, 3, 64                      # smollm-135m
    dt = jnp.dtype(dtype)
    args = (_spec(one_chip, (b, 1, hq, d), dt),
            _spec(one_chip, (num_pages, page_size, n_kv, d), dt),
            _spec(one_chip, (num_pages, page_size, n_kv, d), dt),
            _spec(one_chip, (b, pages_per_seq), jnp.int32),
            _spec(one_chip, (b,), jnp.int32))
    fn = functools.partial(PK.paged_decode_attention, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_grouped_expert_matmuls_compile_for_v5e(one_chip):
    """Moonlight-16B-A3B's dropless expert layer at its widths (16,384
    tokens, top-6, 8 held experts of d 2048 x 1408), forward and backward:
    the grouped matmuls lower to the TPU's ragged-dot kernels."""
    from repro.models import moe as moe_lib
    t, k, e, d, f = 16384, 6, 8, 2048, 1408
    p = {"w_gate": _spec(one_chip, (e, d, f), jnp.bfloat16),
         "w_up": _spec(one_chip, (e, d, f), jnp.bfloat16),
         "w_down": _spec(one_chip, (e, f, d), jnp.bfloat16)}
    args = (p, _spec(one_chip, (t, d), jnp.bfloat16),
            _spec(one_chip, (t, k), jnp.int32),
            _spec(one_chip, (t, k), jnp.float32))

    def loss(p, x, top_e, top_w):
        y, _ = moe_lib.routed_experts(p, x, top_e, top_w)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), *args)
    assert "tpu_custom_call" in text and "ragged-dot" in text
