"""The Pallas kernels of the serving path, compiled for a described TPU v5e
at chatglm3-6b widths (32 query heads over 2 KV heads, head_dim 128, bf16).

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip's compiler would
(unaligned block shapes, too much VMEM). Interpret-mode tests cannot see
those faults. The topology is described inside a fixture, so importing this
file loads no TPU library."""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa

B, HQ, HKV, HD = 8, 32, 2, 128
BLOCK, N_BLOCKS, N_TBL = 16, 512, 32


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("sq,ragged", [(1, False), (16, True)],
                         ids=["decode", "mixed"])
def test_paged_kernel_compiles_for_v5e(one_chip, sq, ragged):
    """The blockspec variant over the head-major pool: decode (sq=1) and a
    ragged mixed wave (sq=16 with per-row q_lens)."""
    i32 = jnp.int32

    def step(q, k_pool, v_pool, tables, off, kv_len, q_lens):
        return pa.paged_attention_pool(
            q, k_pool, v_pool, tables, off, kv_len, causal=True,
            interpret=False, variant="blockspec",
            q_lens=q_lens if ragged else None)

    pool = _spec((N_BLOCKS, HKV, BLOCK, HD), jnp.bfloat16, one_chip)
    _compile(step, _spec((B, sq, HQ, HD), jnp.bfloat16, one_chip), pool,
             pool, _spec((B, N_TBL), i32, one_chip),
             _spec((B,), i32, one_chip), _spec((B,), i32, one_chip),
             _spec((B,), i32, one_chip))


def test_flash_forward_compiles_for_v5e(one_chip):
    """Causal GQA flash forward at seq 2048 (the prefill kernel)."""
    seq, blk = 2048, 512

    def fwd(q, k, v):
        return fa.flash_attention_bhsd(
            q, k, v, causal=True, n_q_heads_per_kv=HQ // HKV, block_q=blk,
            block_k=blk, interpret=False)

    kv = _spec((HKV, seq, HD), jnp.bfloat16, one_chip)
    compiled = _compile(fwd, _spec((HQ, seq, HD), jnp.bfloat16, one_chip),
                        kv, kv)
    # the fp32 (m, l, acc) scratch and double-buffered q/k/v/o blocks fit
    # VMEM with room to spare; the program's HBM is its operands
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == (HQ + 2 * HKV) * seq * HD * 2
    assert math.isfinite(mem.temp_size_in_bytes)
