"""Substrate tests: optimizer, data pipeline, checkpointing, fault-tolerant
loop, HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo as hlo_lib
from repro.checkpoint import ckpt
from repro.core.pipeline import EngineConfig
from repro.data.pipeline import HostShard, SyntheticTokenSource, TrainBatches
from repro.optim.adamw import AdamW, SGD, warmup_cosine_schedule
from repro.runtime.fault_tolerance import LoopConfig, run_with_restarts


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------


def test_adamw_per_trial_lrs_differ():
    params = {"w": jnp.ones((2, 4))}  # K=2 trials
    grads = {"w": jnp.ones((2, 4))}
    opt = AdamW()
    state = opt.init(params)
    hp = {"lr": jnp.array([1e-1, 1e-3])}
    new, _ = opt.update(params, grads, state, hp, jnp.int32(0))
    d0 = float(jnp.abs(params["w"][0] - new["w"][0]).max())
    d1 = float(jnp.abs(params["w"][1] - new["w"][1]).max())
    assert d0 > d1 * 50  # lr ratio reflected (Adam normalizes magnitude)


def test_adamw_first_step_is_lr_sized():
    params = {"w": jnp.zeros((1, 3))}
    grads = {"w": jnp.full((1, 3), 0.5)}
    opt = AdamW()
    st = opt.init(params)
    new, st = opt.update(params, grads, st, {"lr": jnp.array([0.01])},
                         jnp.int32(0))
    # bias-corrected adam first step = -lr * g/|g| = -lr
    np.testing.assert_allclose(np.asarray(new["w"]), -0.01, rtol=1e-4)


def test_adamw_clip_scales_update():
    params = {"w": jnp.zeros((1, 4))}
    g_small = {"w": jnp.full((1, 4), 0.1)}
    g_big = {"w": jnp.full((1, 4), 100.0)}
    opt = AdamW(grad_clip=1.0)
    hp = {"lr": jnp.array([0.01])}
    st = opt.init(params)
    n1, _ = opt.update(params, g_small, st, hp, jnp.int32(0),
                       grad_norm=jnp.array([0.2]))
    st = opt.init(params)
    n2, _ = opt.update(params, g_big, st, hp, jnp.int32(0),
                       grad_norm=jnp.array([200.0]))
    # both end up at -lr after adam normalization; clip must not NaN/blow up
    assert jnp.all(jnp.isfinite(n1["w"])) and jnp.all(jnp.isfinite(n2["w"]))


def test_schedule_warmup_cosine():
    f = warmup_cosine_schedule(warmup=10, total=100)
    assert float(f(jnp.int32(0))) == 0.0
    assert abs(float(f(jnp.int32(10))) - 1.0) < 1e-6
    assert float(f(jnp.int32(100))) < 0.11


def test_sgd_momentum():
    params = {"w": jnp.zeros((1, 2))}
    opt = SGD(momentum=0.9)
    st = opt.init(params)
    hp = {"lr": jnp.array([1.0])}
    g = {"w": jnp.ones((1, 2))}
    p1, st = opt.update(params, g, st, hp, jnp.int32(0))
    p2, st = opt.update(p1, g, st, hp, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(p2["w"]), -1.0 - 1.9, rtol=1e-6)


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------


def test_data_determinism_and_shift():
    cfg = __import__("repro.configs", fromlist=["x"]).get_config(
        "chatglm3-6b").reduced()
    eng = EngineConfig(n_trials=2, n_microbatches=2, microbatch=2,
                       n_stages=2, data_size=2)
    d1 = TrainBatches(cfg, eng, seq_len=16, seed=7)
    d2 = TrainBatches(cfg, eng, seq_len=16, seed=7)
    b1, b2 = d1.batch_for_step(3), d2.batch_for_step(3)
    d1.close(), d2.close()
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shift
    np.testing.assert_array_equal(b1["tokens"][..., 1:],
                                  b1["labels"][..., :-1])
    assert b1["tokens"].shape == (2, 2, 4, 16)  # (K, M, mb*data, seq)
    assert b1["tokens"].max() < cfg.vocab_size


def test_data_distinct_across_coordinates():
    src = SyntheticTokenSource(vocab_size=1000, seq_len=32, seed=0)
    a = src.sequence(0, 0, 0, 0)
    assert not np.array_equal(a, src.sequence(1, 0, 0, 0))
    assert not np.array_equal(a, src.sequence(0, 1, 0, 0))
    assert not np.array_equal(a, src.sequence(0, 0, 1, 0))
    np.testing.assert_array_equal(a, SyntheticTokenSource(
        1000, 32, 0).sequence(0, 0, 0, 0))


def test_host_sharding_partitions_rows():
    rows = [list(HostShard(i, 4).rows(26)) for i in range(4)]
    flat = [r for rs in rows for r in rs]
    assert sorted(flat) == list(range(26))


def test_prefetch_iterator():
    cfg = __import__("repro.configs", fromlist=["x"]).get_config(
        "chatglm3-6b").reduced()
    eng = EngineConfig(n_trials=1, n_microbatches=1, microbatch=2,
                       n_stages=1, data_size=1)
    data = TrainBatches(cfg, eng, seq_len=8, seed=0, prefetch=2)
    b0 = next(data)
    b1 = next(data)
    data.close()
    assert b0["tokens"].shape == b1["tokens"].shape
    assert not np.array_equal(b0["tokens"], b1["tokens"])


# --------------------------------------------------------------------------
# checkpointing + fault tolerance
# --------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
            "count": jnp.int32(7)}
    ckpt.save(str(tmp_path), 42, tree, extra={"note": "x"})
    assert ckpt.latest_step(str(tmp_path)) == 42
    back = ckpt.restore(str(tmp_path), 42, tree)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == np.dtype("bfloat16") or \
        np.asarray(back["b"]["c"]).dtype.name == "bfloat16"
    assert ckpt.manifest(str(tmp_path), 42)["extra"]["note"] == "x"


def test_checkpoint_cleanup_keeps_latest(tmp_path):
    tree = {"a": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree)
    ckpt.cleanup(str(tmp_path), keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert ckpt.restore(str(tmp_path), 4, tree) is not None
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path), 1, tree)


def test_async_checkpointer(tmp_path):
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    saver.save(10, {"w": jnp.ones((8, 8))})
    saver.wait()
    assert ckpt.latest_step(str(tmp_path)) == 10


def test_restart_resumes_and_matches_uninterrupted(tmp_path):
    """Injected failure at step 7: the restarted run must produce the exact
    same final state as an uninterrupted run (determinism contract)."""

    def step_fn(state, step):
        return {"x": state["x"] + (step + 1)}, {"step": step}

    init = {"x": jnp.zeros(())}
    clean = run_with_restarts(step_fn, init,
                              LoopConfig(n_steps=10, checkpoint_every=2,
                                         ckpt_dir=str(tmp_path / "clean")))

    boom = {"armed": True}

    def injector(step):
        if step == 7 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("simulated chip failure")

    faulty = run_with_restarts(step_fn, init,
                               LoopConfig(n_steps=10, checkpoint_every=2,
                                          ckpt_dir=str(tmp_path / "faulty")),
                               failure_injector=injector)
    assert faulty.restarts == 1
    assert float(faulty.final_state["x"]) == float(clean.final_state["x"])


def test_restart_exhaustion_raises(tmp_path):
    def step_fn(state, step):
        raise RuntimeError("always fails")

    with pytest.raises(RuntimeError):
        run_with_restarts(step_fn, {"x": jnp.zeros(())},
                          LoopConfig(n_steps=3, checkpoint_every=1,
                                     ckpt_dir=str(tmp_path),
                                     max_restarts=2))


# --------------------------------------------------------------------------
# HLO analyzer (roofline input)
# --------------------------------------------------------------------------


def test_hlo_analyzer_counts_loops_and_collectives():
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    mesh = make_mesh((2,), ("x",))

    def inner(w, x):
        def body(c, _):
            y = jnp.dot(c, w)
            y = lax.psum(y, "x")
            return y, ()
        out, _ = lax.scan(body, x, None, length=5)
        return out

    f = jax.shard_map(inner, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                      check_vma=False)
    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
        jax.ShapeDtypeStruct((4, 16), jnp.float32))
    costs = hlo_lib.analyze(lowered.compile().as_text())
    assert costs.trip_counts == [5]
    np.testing.assert_allclose(costs.flops, 2 * 4 * 16 * 16 * 5, rtol=0.05)
    # ring all-reduce bytes: 2 * B * (n-1)/n per execution
    want = 5 * 2 * (4 * 16 * 4) * (2 - 1) / 2
    np.testing.assert_allclose(costs.collective_bytes, want, rtol=0.05)
