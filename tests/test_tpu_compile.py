"""The serving-path Pallas kernels compile for a TPU v5e at published widths.

Nothing runs: each kernel is lowered and compiled for a chip that is
described, not attached (jax.experimental.topologies), so the TPU compiler
refuses here what it would refuse on the chip — illegal block shapes, ops
Mosaic cannot lower, VMEM overruns. Interpret-mode tests
(tests/test_kernels.py) hold the numerics; these hold the lowering.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and the test workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_decode.ops import (paged_flash_decode,
                                            paged_flash_decode_quant)
from repro.kernels.sampling.ops import topk_topp_mask

WIDTHS = ["qwen2-1.5b", "paper-demo"]
BLOCK_SIZE = 16
ROWS = 16  # decode rows per step (slots + prefill lane rows)
MAX_BLOCKS = 34  # (prompt 512 + gen 32) / block 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


# (arch, rows, table width, pool blocks): the small step at both widths,
# and the benchmark's lane step: 64 slots + 512 lane rows over a
# (2048 + 1024) / 16 = 192-block table, a pool of 12 288 blocks + null
PAGED_SHAPES = [pytest.param(a, ROWS, MAX_BLOCKS, 1 + ROWS * MAX_BLOCKS,
                             id=a) for a in WIDTHS] + [
    pytest.param("qwen2-1.5b", 576, 192, 12289, id="qwen2-1.5b-576x192")]


def _paged_args(arch, rows, width, nb, sh, dtype):
    cfg = get_config(arch)
    pool = (nb, cfg.n_kv_heads, BLOCK_SIZE, cfg.head_dim)
    return (cfg, _sds((rows, cfg.n_heads, cfg.head_dim), jnp.bfloat16, sh),
            _sds(pool, dtype, sh), _sds(pool, dtype, sh),
            _sds((rows, width), jnp.int32, sh),
            _sds((rows,), jnp.int32, sh))


@pytest.mark.parametrize("arch,rows,width,nb", PAGED_SHAPES)
def test_paged_decode_compiles(arch, rows, width, nb, one_chip):
    _, q, kp, vp, tbl, lens = _paged_args(arch, rows, width, nb, one_chip,
                                          jnp.bfloat16)
    _compile(paged_flash_decode, q, kp, vp, tbl, lens)


@pytest.mark.parametrize("arch,rows,width,nb", PAGED_SHAPES)
def test_paged_decode_quant_compiles(arch, rows, width, nb, one_chip):
    cfg, q, kp, vp, tbl, lens = _paged_args(arch, rows, width, nb, one_chip,
                                            jnp.int8)
    scales = _sds(kp.shape[:3], jnp.float32, one_chip)
    _compile(paged_flash_decode_quant, q, kp, vp, scales, scales, tbl, lens)


@pytest.mark.parametrize("arch", WIDTHS)
def test_sampling_mask_compiles(arch, one_chip):
    V = get_config(arch).vocab_size
    fn = jax.jit(lambda x, k, p: topk_topp_mask(x, k, p, impl="pallas"))
    _compile(fn, _sds((ROWS, V), jnp.float32, one_chip),
             _sds((ROWS,), jnp.int32, one_chip),
             _sds((ROWS,), jnp.float32, one_chip))


@pytest.mark.parametrize("arch", WIDTHS)
def test_flash_attention_prefill_compiles(arch, one_chip):
    cfg = get_config(arch)
    S = 512
    q = _sds((1, S, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((1, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, n_kv_heads=cfg.n_kv_heads))
    _compile(fn, q, kv, kv)
