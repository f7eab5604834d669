"""The main-path qpack kernels compile for a described TPU v5e chip.

Interpret-mode tests (tests/test_kernels.py, tests/test_qpack_fused.py)
cannot see what Mosaic refuses: unaligned or strided lane slices, 8-bit
shifts, shape casts. These compiles run the chip's own compiler against a
``v5e:2x2`` topology that is described, not attached, at the sizes the
main path uses: 4096 pool blocks of 512 values for the fused demote and
promote kernels (4096 blocks of 2048 in 4 KB-block mode), and the
server's head_dim-128 KV blocks for the fixed-rate encode and decode.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import qpack as qp

BLOCKS = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("vals", [128, 512, 2048])
def test_fused_encode_compiles(one_chip, vals):
    _compile(lambda x: qp.qpack_fused_encode_2d(x, interpret=False),
             one_chip, ((BLOCKS, vals), jnp.bfloat16))


@pytest.mark.parametrize("vals", [512, 2048])
def test_fused_decode_compiles(one_chip, vals):
    _compile(lambda d, r: qp.qpack_fused_decode_2d(d, r, interpret=False),
             one_chip, ((BLOCKS, 2 * vals), jnp.uint8),
             ((BLOCKS,), jnp.int32))


@pytest.mark.parametrize("bits", [4, 8])
def test_encode_head_dim_blocks_compiles(one_chip, bits):
    _compile(lambda x: qp.qpack_encode_2d(x, bits=bits, interpret=False),
             one_chip, ((BLOCKS, 128), jnp.float32))


@pytest.mark.parametrize("bits", [4, 8])
def test_decode_head_dim_blocks_compiles(one_chip, bits):
    _compile(lambda c, s: qp.qpack_decode_2d(c, s, bits=bits,
                                             interpret=False),
             one_chip, ((BLOCKS, 128 * bits // 8), jnp.uint8),
             ((BLOCKS,), jnp.float32))
