"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the installed TPU compiler lowers each kernel for one
device of a described ``v5e:2x2`` topology, so a block shape or VMEM use that
Mosaic refuses fails here instead of on the first chip run.  Shapes are
minitron-4b's published widths (24 query / 8 KV heads, head dim 128,
d_model 3072, d_ff 9216) at RSI rank fraction 0.3 and the chip smoke's page
size of 128.  Nothing runs, so these tests say nothing about results or
times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.lowrank_matmul import (
    lowrank_matmul_batched_pallas,
    lowrank_matmul_pallas,
)

# minitron-4b attention widths and the smoke's decode batch / page size
B, H, KV, HD = 8, 24, 8, 128
PAGE = 128
D_MODEL, D_FF = 3072, 9216
RANK_SQ, RANK_KV = 922, 308  # ceil(0.3 * 3072), ceil(0.3 * 1024)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip, so keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


@pytest.mark.parametrize("S", [1024, 1152])  # 1152: blocks shrink to 128
def test_flat_decode_compiles(one_chip, S):
    args = (
        _sds(one_chip, (B, 1, H, HD)),
        _sds(one_chip, (B, S, KV, HD)),
        _sds(one_chip, (B, S, KV, HD)),
        _sds(one_chip, (B, S), jnp.bool_),
    )
    assert _compile_has_kernel(decode_attention_pallas, *args)


@pytest.mark.parametrize("n_tbl", [1, 9])  # one page, and the smoke's 1152-token table
def test_paged_decode_compiles(one_chip, n_tbl):
    P = B * n_tbl + 1
    args = (
        _sds(one_chip, (B, 1, H, HD)),
        _sds(one_chip, (P, PAGE, KV, HD)),
        _sds(one_chip, (P, PAGE, KV, HD)),
        _sds(one_chip, (B, n_tbl), jnp.int32),
        _sds(one_chip, (B,), jnp.int32),
    )
    assert _compile_has_kernel(paged_decode_attention_pallas, *args)


@pytest.mark.parametrize(
    "M,K,r,N",
    [
        (8, D_MODEL, RANK_SQ, D_MODEL),  # decode: attention q / o
        (8, D_MODEL, RANK_KV, KV * HD),  # decode: attention k / v
        (8, D_FF, RANK_SQ, D_MODEL),  # decode: MLP down
        (256, D_MODEL, RANK_SQ, D_MODEL),  # one 256-token prefill chunk
        (2048, D_FF, RANK_SQ, D_MODEL),  # grouped prefill, 8 x 256 tokens
    ],
)
def test_fused_lowrank_compiles(one_chip, M, K, r, N):
    args = (
        _sds(one_chip, (M, K)),
        _sds(one_chip, (K, r)),
        _sds(one_chip, (r, N)),
    )
    assert _compile_has_kernel(lowrank_matmul_pallas, *args)


def test_batched_fused_lowrank_compiles(one_chip):
    L = 16  # the smoke's layer count
    args = (
        _sds(one_chip, (L, B, D_MODEL)),
        _sds(one_chip, (L, D_MODEL, RANK_SQ)),
        _sds(one_chip, (L, RANK_SQ, D_MODEL)),
    )
    assert _compile_has_kernel(lowrank_matmul_batched_pallas, *args)
