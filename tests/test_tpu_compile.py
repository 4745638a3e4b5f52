"""Main-path Pallas kernels compile for a TPU v5e at nllb600m widths.

Interpret mode (every other kernel test) accepts block shapes and VMEM
footprints that the chip's compiler refuses. These tests compile each
kernel with ``interpret=False`` for a described ``v5e:2x2`` topology —
no chip needed, only the TPU compiler that ships with jax — and check
that the executable holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attn, fasst, ops, paged_attn, qmm

# nllb600m: d_model 1024, d_ff 8192, 16 heads = 16 KV heads of 64
D_MODEL, D_FF, HKV, HEAD_DIM = 1024, 8192, 16, 64
SLOTS, MAX_PAGES = 8, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_hlo(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_paged_attn_compiles(topo, pages, page_size):
    P = 1 + SLOTS * MAX_PAGES
    call = functools.partial(paged_attn.paged_attn_call, sm_scale=0.125,
                             out_dtype=jnp.float32, interpret=False)
    q = ((SLOTS, HKV, 8, HEAD_DIM), jnp.float32)   # G=1 padded to 8
    tables = ((SLOTS, MAX_PAGES), jnp.int32)
    lens = ((SLOTS,), jnp.int32)
    if pages == "bf16":
        kv = ((P, HKV, page_size, HEAD_DIM), jnp.bfloat16)
        hlo = _compiled_hlo(
            topo, lambda q, k, v, t, n: call(q, k, None, v, None, t, n),
            q, kv, kv, tables, lens)
    else:
        kv = ((P, HKV, page_size, HEAD_DIM), jnp.int8)
        sc = ((P, HKV, page_size), jnp.float32)
        hlo = _compiled_hlo(topo, call, q, kv, sc, kv, sc, tables, lens)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
@pytest.mark.parametrize("fmt", ["int4", "fp4"])
def test_qmm_compiles(topo, fmt, k, n, m):
    call = functools.partial(qmm.qmm_kernel_call, fmt_name=fmt, sub_block=64,
                             bm=min(m, 128), bn=256, bk=512, interpret=False)
    hlo = _compiled_hlo(topo, call, ((m, k), jnp.bfloat16),
                        ((k // 2, n), jnp.uint8), ((k // 64, n), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_decode_attn_compiles(topo):
    S = 256
    call = functools.partial(decode_attn.decode_attn_call, bs=128,
                             sm_scale=0.125, interpret=False)
    codes = ((SLOTS, HKV, S, HEAD_DIM), jnp.int8)
    scales = ((SLOTS, HKV, S), jnp.float32)
    hlo = _compiled_hlo(topo, call, ((SLOTS, HKV, 8, HEAD_DIM), jnp.float32),
                        codes, scales, codes, scales, ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_fasst_compiles_at_ffn_width(topo):
    """The NAF block over nllb600m's FFN width fits VMEM."""
    M = 2048
    bm = ops._pick_tile(M, min(256, ops._fasst_rows(D_FF, jnp.float32,
                                                    jnp.float32)))
    call = functools.partial(fasst.fasst_act_call, mode="relu", bm=bm,
                             interpret=False)
    hlo = _compiled_hlo(topo, call, ((M, D_FF), jnp.float32))
    assert "tpu_custom_call" in hlo
