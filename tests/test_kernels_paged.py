"""Paged-attention kernel (attend straight from the block pool) vs the
block-table-native XLA mirror, the gather path, and the serve oracle — plus
the paged-scatter overflow regression.

Kernel variants run in interpret mode (kernel body executed on CPU); the
``REPRO_PAGED_ATTN`` env flips the engine-facing lowering per test.

(Multi-device setup comes from tests/conftest.py — pytest-only module.)"""
import dataclasses  # noqa: E402
import os  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.configs import ASSIGNED_ARCHS  # noqa: E402
from repro.kernels import ops, paged_attention as pa, ref  # noqa: E402
from repro.models import blocks  # noqa: E402
from repro.models.layers import ModelOptions  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402

RNG = np.random.default_rng(7)


def make_paged_case(b, sq, hq, hkv, hd, nb, bs, n_tbl, kv_lens, dt):
    """Random pool + ragged per-row tables. Each row r holds ``kv_lens[r]``
    live tokens (the new sq arrive at the end); live blocks are a random
    disjoint subset of the pool, remaining table entries are -1."""
    q = jnp.asarray(RNG.normal(size=(b, sq, hq, hd)), dt)
    k_pool = jnp.asarray(RNG.normal(size=(nb, hkv, bs, hd)), dt)
    v_pool = jnp.asarray(RNG.normal(size=(nb, hkv, bs, hd)), dt)
    tables = np.full((b, n_tbl), -1, np.int32)
    free = list(RNG.permutation(nb))
    for r, ln in enumerate(kv_lens):
        need = -(-max(ln, 1) // bs)
        for j in range(need):
            tables[r, j] = free.pop()
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    kv_offset = kv_len - sq  # the sq new tokens sit at the row's tail
    return q, k_pool, v_pool, jnp.asarray(tables), kv_offset, kv_len


SWEEP = [
    # b, sq, hq, hkv, hd, nb, bs, n_tbl, kv_lens, window, dtype
    (2, 1, 4, 2, 16, 12, 4, 4, [9, 16], 0, jnp.float32),       # decode GQA
    (2, 1, 4, 4, 16, 12, 4, 4, [1, 13], 0, jnp.float32),       # MHA ragged
    (3, 1, 8, 2, 16, 16, 8, 3, [24, 5, 17], 0, jnp.float32),   # g=4, bs=8
    (2, 1, 4, 2, 16, 12, 4, 4, [9, 16], 3, jnp.float32),       # window
    (2, 4, 4, 2, 16, 14, 4, 5, [11, 20], 0, jnp.float32),      # append
    (2, 5, 4, 2, 16, 14, 4, 6, [5, 21], 5, jnp.float32),       # append+win
    (2, 1, 4, 2, 16, 12, 16, 2, [9, 30], 0, jnp.bfloat16),     # bf16, bs=16
    (2, 3, 2, 2, 32, 10, 8, 3, [19, 8], 0, jnp.bfloat16),      # bf16 append
]


@pytest.mark.parametrize("variant", ["loop", "blockspec"])
@pytest.mark.parametrize("b,sq,hq,hkv,hd,nb,bs,n_tbl,kv_lens,window,dt",
                         SWEEP)
def test_kernel_vs_ref(variant, b, sq, hq, hkv, hd, nb, bs, n_tbl, kv_lens,
                       window, dt):
    case = make_paged_case(b, sq, hq, hkv, hd, nb, bs, n_tbl, kv_lens, dt)
    q, k_pool, v_pool, tables, kv_offset, kv_len = case
    r = ref.paged_attention_ref(q, k_pool, v_pool, tables, kv_offset, kv_len,
                                causal=True, window=window)
    o = pa.paged_attention_pool(q, k_pool, v_pool, tables, kv_offset, kv_len,
                                causal=True, window=window, interpret=True,
                                variant=variant)
    tol = 3e-2 if dt == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(r.astype(jnp.float32)
                                - o.astype(jnp.float32))))
    assert err < tol, err


@pytest.mark.parametrize("variant", ["loop", "blockspec"])
@pytest.mark.parametrize("q_lens", [[4, 1], [3, 0], [1, 4]])
def test_kernel_ragged_q_lens(variant, q_lens):
    """Mixed-tick waves: rows carry ragged per-row query counts (chunk
    width prefilling, 1 decoding, 0 idle) padded to the wave max. Padded
    query positions must come out exactly zero and real positions must
    match the reference attending only kv_offset + q_len_r tokens."""
    b, sq, hq, hkv, hd, nb, bs, n_tbl = 2, 4, 4, 2, 16, 14, 4, 5
    kv_off = [7, 9]
    kv_lens = [o + q for o, q in zip(kv_off, q_lens)]
    # capacity must cover each row's real tokens; build at the padded tail
    case = make_paged_case(b, sq, hq, hkv, hd, nb, bs, n_tbl,
                           [o + sq for o in kv_off], jnp.float32)
    q, k_pool, v_pool, tables, _, _ = case
    kv_offset = jnp.asarray(kv_off, jnp.int32)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    ql = jnp.asarray(q_lens, jnp.int32)
    r = ref.paged_attention_ref(q, k_pool, v_pool, tables, kv_offset, kv_len,
                                causal=True, window=0, q_lens=ql)
    o = pa.paged_attention_pool(q, k_pool, v_pool, tables, kv_offset, kv_len,
                                causal=True, window=0, interpret=True,
                                variant=variant, q_lens=ql)
    err = float(jnp.max(jnp.abs(r - o)))
    assert err < 2e-5, err
    # padded rows really are zeros (a fully-masked row never contributes)
    on = np.asarray(o)
    for row, n in enumerate(q_lens):
        np.testing.assert_array_equal(on[row, n:], 0.0)


def test_kernel_vs_gathered_dense():
    """The pool path must equal plain masked attention over each row's
    gathered logical view — the end-to-end gather-path equivalence."""
    from repro.models.layers import attention
    b, sq, hq, hkv, hd, nb, bs, n_tbl = 2, 1, 4, 2, 16, 12, 4, 4
    kv_lens = [9, 15]
    case = make_paged_case(b, sq, hq, hkv, hd, nb, bs, n_tbl, kv_lens,
                           jnp.float32)
    q, k_pool, v_pool, tables, kv_offset, kv_len = case
    span = (jnp.clip(tables, 0, nb - 1)[:, :, None] * bs
            + jnp.arange(bs)[None, None, :]).reshape(b, n_tbl * bs)
    k_rows = jnp.take(k_pool.transpose(0, 2, 1, 3).reshape(nb * bs, hkv, hd),
                      span, axis=0)
    v_rows = jnp.take(v_pool.transpose(0, 2, 1, 3).reshape(nb * bs, hkv, hd),
                      span, axis=0)
    want = attention(q, k_rows, v_rows, causal=False, window=0,
                     kv_offset=0, kv_len=kv_len, opts=ModelOptions())
    for variant in ("loop", "blockspec"):
        got = pa.paged_attention_pool(q, k_pool, v_pool, tables, kv_offset,
                                      kv_len, causal=True, window=0,
                                      interpret=True, variant=variant)
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   atol=2e-5)


def test_scatter_overflow_leaves_last_block_untouched():
    """Regression: tokens past table capacity must be DROPPED. Clipping the
    block index routed them into the row's last allocated block (a valid
    physical id passes the ``phys >= 0`` check) and silently overwrote its
    cached K/V."""
    nb, bs, hkv, hd, n_tbl = 4, 4, 2, 8, 2  # capacity 2 blocks = 8 tokens
    cache = {
        "k": jnp.asarray(RNG.normal(size=(nb, hkv, bs, hd)), jnp.float32),
        "v": jnp.asarray(RNG.normal(size=(nb, hkv, bs, hd)), jnp.float32),
    }
    tables = jnp.asarray([[2, 1]], jnp.int32)  # full table, last block = 1
    k = jnp.asarray(RNG.normal(size=(1, 1, hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 1, hkv, hd)), jnp.float32)
    # row sits AT capacity: the write would land at pos 8 -> block index 2,
    # one past the table; clipped-to-last it would corrupt block 1 slot 0
    new = blocks.paged_kv_scatter(cache, k, v, tables,
                                  jnp.asarray([8], jnp.int32))
    np.testing.assert_array_equal(np.asarray(new["k"]),
                                  np.asarray(cache["k"]))
    np.testing.assert_array_equal(np.asarray(new["v"]),
                                  np.asarray(cache["v"]))
    # in-capacity writes still land: pos 5 -> block 1 slot 1
    new = blocks.paged_kv_scatter(cache, k, v, tables,
                                  jnp.asarray([5], jnp.int32))
    np.testing.assert_array_equal(np.asarray(new["k"][1, :, 1]),
                                  np.asarray(k[0, 0]))
    assert not np.array_equal(np.asarray(new["k"]), np.asarray(cache["k"]))


def _attn_case(cfg, mode, s, kv_lens, window=0):
    nb, bs, n_tbl = 16, 4, 6
    d = cfg.d_model
    p = {w: jnp.asarray(RNG.normal(size=(d, cfg.n_heads * cfg.head_dim))
                        / np.sqrt(d), jnp.float32) for w in ("wq", "wo")}
    for w in ("wk", "wv"):
        p[w] = jnp.asarray(RNG.normal(size=(d, cfg.n_kv_heads * cfg.head_dim))
                           / np.sqrt(d), jnp.float32)
    b = len(kv_lens)
    x = jnp.asarray(RNG.normal(size=(b, s, d)), jnp.float32)
    cache = {
        "k": jnp.asarray(RNG.normal(size=(nb, cfg.n_kv_heads, bs,
                                          cfg.head_dim)), jnp.float32),
        "v": jnp.asarray(RNG.normal(size=(nb, cfg.n_kv_heads, bs,
                                          cfg.head_dim)), jnp.float32),
    }
    tables = np.full((b, n_tbl), -1, np.int32)
    free = list(RNG.permutation(nb))
    for r, ln in enumerate(kv_lens):
        for j in range(-(-(ln + s) // bs)):
            tables[r, j] = free.pop()
    kv_offset = jnp.asarray(kv_lens, jnp.int32)
    pos = kv_offset[:, None] + jnp.arange(s)[None, :]
    return dict(p=p, x=x, pos=pos, cache=cache, kv_offset=kv_offset,
                mode=mode, window=window,
                block_tables=jnp.asarray(tables))


@pytest.mark.parametrize("mode,s,kv_lens,window", [
    ("decode", 1, [7, 12], 0),
    ("decode", 1, [7, 12], 3),
    ("append", 4, [5, 9], 0),
])
def test_attn_apply_kernel_matches_gather(monkeypatch, mode, s, kv_lens,
                                          window):
    """blocks.attn_apply with use_paged_kernel must match the gather path
    bit-for-bit on out AND cache, under both engine lowerings."""
    cfg = ASSIGNED_ARCHS["chatglm3-6b"].reduced()
    case = _attn_case(cfg, mode, s, kv_lens, window)
    kw = dict(case)
    p, x, pos = kw.pop("p"), kw.pop("x"), kw.pop("pos")
    out_g, cache_g = blocks.attn_apply(cfg, ModelOptions(), p, x, pos=pos,
                                       **kw)
    opts_k = ModelOptions(use_paged_kernel=True)
    for lowering in ("jnp", "interpret"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", lowering)
        ops.paged_attention.clear_cache()  # env is read at trace time
        out_k, cache_k = blocks.attn_apply(cfg, opts_k, p, x, pos=pos, **kw)
        err = float(jnp.max(jnp.abs(out_g - out_k)))
        assert err < 2e-5, (lowering, err)
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(cache_g[leaf]),
                                          np.asarray(cache_k[leaf]),
                                          err_msg=f"{lowering}/{leaf}")
    ops.paged_attention.clear_cache()


def _engine_build(**over):
    from repro.core import pipeline as pl
    from repro.core.partitioner import plan_stages
    from repro.launch.mesh import make_test_mesh
    cfg = ASSIGNED_ARCHS["chatglm3-6b"].reduced()
    mesh = make_test_mesh(1, 2)
    eng = pl.EngineConfig(n_trials=1, n_microbatches=2, microbatch=2,
                          n_stages=2, data_size=1, max_seq=24,
                          cache_dtype=jnp.float32, prefill_chunks=2,
                          paged=True, block_size=4, n_blocks=24, **over)
    plan = plan_stages(cfg, eng.n_stages)
    params = pl.init_trial_params(cfg, eng, plan, jax.random.PRNGKey(0),
                                  max_pos=24)
    return cfg, mesh, eng, params


@pytest.mark.parametrize("lowering", ["jnp", "interpret"])
def test_engine_kernel_matches_gather_and_oracle(monkeypatch, lowering):
    """Full serve engine: the kernel path's greedy tokens must be
    bit-identical to the gather path and the single-device oracle."""
    from test_serve_engine import oracle_tokens
    monkeypatch.setenv("REPRO_PAGED_ATTN", lowering)
    ops.paged_attention.clear_cache()
    cfg, mesh, eng, params = _engine_build()
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32),
                    g, arrival=0.5 * i)
            for i, (p, g) in enumerate([(9, 4), (12, 3), (7, 5), (5, 2)])]
    e_g = ServeEngine(cfg, eng, mesh, params, ModelOptions())
    comp_g = e_g.run([r.clone() for r in reqs])
    e_k = ServeEngine(cfg, eng, mesh, params,
                      ModelOptions(use_paged_kernel=True))
    comp_k = e_k.run([r.clone() for r in reqs])
    for r, a, b in zip(reqs, comp_g, comp_k):
        assert a.tokens == b.tokens, f"request {r.rid}: kernel != gather"
        assert b.tokens == oracle_tokens(cfg, ModelOptions(), params, r), \
            f"request {r.rid}: kernel diverged from the oracle"
    assert e_k.allocator.all_free()
    ops.paged_attention.clear_cache()


@pytest.mark.parametrize("lowering", ["jnp", "interpret"])
def test_engine_fused_kernel_matches_split(monkeypatch, lowering):
    """Fused mixed-tick admission through the paged-attention kernel: the
    per-row q-length masking must keep greedy tokens and tick latencies
    bit-identical to the split schedule on the same lowering."""
    monkeypatch.setenv("REPRO_PAGED_ATTN", lowering)
    ops.paged_attention.clear_cache()
    cfg, mesh, eng, params = _engine_build()
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32),
                    g, arrival=0.5 * i)
            for i, (p, g) in enumerate([(9, 4), (12, 3), (7, 5), (5, 2)])]
    opts = ModelOptions(use_paged_kernel=True)
    e_s = ServeEngine(cfg, eng, mesh, params, opts)
    comp_s = e_s.run([r.clone() for r in reqs])
    e_f = ServeEngine(cfg, eng, mesh, params, opts, fused=True)
    comp_f = e_f.run([r.clone() for r in reqs])
    for a, b in zip(comp_s, comp_f):
        assert a.tokens == b.tokens, f"request {a.rid}: fused != split"
        assert a.ttft_ticks == b.ttft_ticks
        assert a.finished_tick == b.finished_tick
    assert e_f.stats.calls < e_s.stats.calls
    assert e_f.allocator.all_free()
    ops.paged_attention.clear_cache()


def test_engine_kernel_requires_paged():
    cfg, mesh, eng, params = _engine_build()
    dense = dataclasses.replace(eng, paged=False, n_blocks=0)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, dense, mesh, params,
                    ModelOptions(use_paged_kernel=True))


def test_paged_mode_default_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
    assert ops._paged_mode() == ("pallas" if jax.default_backend() == "tpu"
                                 else "jnp")
    for m in ("pallas", "interpret", "jnp"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", m)
        assert ops._paged_mode() == m
