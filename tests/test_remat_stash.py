"""The gang step's remat stash (``pipeline.train_stash``): the layer-boundary
stash gives the tick stash's losses and gradients, is chosen from shapes and
the chip's HBM, and makes the backward replay each layer's forward once."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.extend import core as jcore
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import pipeline as pl
from repro.core import scheduler as sched
from repro.core.partitioner import plan_stages
from repro.data.pipeline import TrainBatches
from repro.launch.mesh import make_test_mesh
from repro.models.layers import ModelOptions

OPTS = ModelOptions(remat=True)
SEQ = 16
BUDGET = sched.HBM_BYTES_PER_CHIP * sched.HBM_BUDGET_FRACTION


def _eng(n_stages=1, data_size=1, **kw):
    return pl.EngineConfig(n_trials=2, n_microbatches=2, microbatch=2,
                           n_stages=n_stages, data_size=data_size, **kw)


def _setup(cfg, eng, mesh):
    params = pl.init_trial_params(cfg, eng, plan_stages(cfg, eng.n_stages),
                                  jax.random.PRNGKey(0), max_pos=SEQ,
                                  mesh=mesh)
    data = TrainBatches(cfg, eng, SEQ, seed=1)
    try:
        batch = data.batch_for_step(0)
    finally:
        data.close()
    return params, batch


def _loss_and_grads(cfg, eng, mesh, params, batch):
    """Per-trial losses and reduced gradients of one gang step."""
    def step(p, b):
        def local(p):
            loss_vec, _ = pl.pipeline_train_loss(cfg, OPTS, eng, p, b)
            return loss_vec.sum(), loss_vec

        g, loss_vec = jax.grad(local, has_aux=True)(p)
        g, _ = pl.reduce_grads(cfg, eng, g)
        for ax in eng.dp_axes:
            loss_vec = lax.pmean(loss_vec, ax)
        return loss_vec, g

    pspecs = pl.param_pspecs(cfg, eng)
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, pl.batch_pspecs(cfg, eng, train=True)),
        out_specs=(P(), pspecs), check_vma=False))
    return jax.device_get(fn(params, batch))


def _keep(monkeypatch, kept):
    """Steps built from here on keep ``kept`` layer inputs per tick."""
    monkeypatch.setattr(pl, "train_stash", lambda *a: (kept, 0))


@pytest.mark.parametrize("n_data,n_stages,fsdp", [(1, 1, False),
                                                  (2, 4, True)])
def test_layer_stash_matches_tick_stash(monkeypatch, n_data, n_stages, fsdp):
    cfg = get_config("bert-large").reduced()
    eng = _eng(n_stages, n_data, fsdp=fsdp)
    mesh = make_test_mesh(n_data, n_stages)
    params, batch = _setup(cfg, eng, mesh)
    layers = plan_stages(cfg, n_stages).layers_per_stage
    assert pl.train_stash(cfg, OPTS, eng, SEQ, jnp.float32)[0] == layers
    loss_l, grads_l = _loss_and_grads(cfg, eng, mesh, params, batch)
    # no gang fits a chip without HBM: the step falls back to the tick stash
    monkeypatch.setattr(sched, "HBM_BYTES_PER_CHIP", 0)
    assert pl.train_stash(cfg, OPTS, eng, SEQ, jnp.float32)[0] == 0
    loss_t, grads_t = _loss_and_grads(cfg, eng, mesh, params, batch)
    runs = [(loss_t, grads_t)]
    if layers > 2:  # and some layers kept, some not
        _keep(monkeypatch, 2)
        runs.append(_loss_and_grads(cfg, eng, mesh, params, batch))
    for loss, grads in runs:
        np.testing.assert_allclose(loss, loss_l, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_l)):
            np.testing.assert_allclose(a, b, rtol=1e-6)


def test_stash_is_sized_from_shapes_and_hbm():
    # the benchmark's gang: BERT-Large, K=2, M=4 x 4 x 512, one stage, fp32
    bert = get_config("bert-large")
    eng = pl.EngineConfig(n_trials=2, n_microbatches=4, microbatch=4,
                          n_stages=1)
    act = 4 * 512 * 1024 * 4  # one (mb, seq, d) fp32 activation
    # one stage: one tick is live, and it keeps all 24 layer inputs and
    # its stage output
    assert eng.stash_ticks == 1
    assert pl.train_stash(bert, OPTS, eng, 512, jnp.float32) == (
        24, 25 * act)
    assert sched.stash_layers(bert, eng, 512, param_bytes=4,
                              act_bytes=4) == 24
    # the same gang as 4 stages holds all 11 ticks: 11 x (6 + 1)
    four = dataclasses.replace(eng, n_stages=4)
    assert four.stash_ticks == 11
    assert pl.train_stash(bert, OPTS, four, 512, jnp.float32) == (
        6, 11 * 7 * act)
    # only the nested checkpoints keep layer inputs
    no_inner = dataclasses.replace(eng, layer_remat=False)
    assert pl.train_stash(bert, OPTS, no_inner, 512, jnp.float32) == (
        0, act)
    assert pl.train_stash(bert, ModelOptions(), eng, 512,
                          jnp.float32) == (0, 0)

    # chatglm3-6b at 4096 over 4 stages x 4 FSDP rows, bf16: 4 of 7
    glm = get_config("chatglm3-6b")
    bf16 = ModelOptions(remat=True, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    rows = pl.EngineConfig(n_trials=1, n_microbatches=4, microbatch=1,
                           n_stages=4, data_size=4, fsdp=True)
    kept, n_bytes = pl.train_stash(glm, bf16, rows, 4096, jnp.bfloat16)
    act16 = 4096 * 4096 * 6  # a bf16 activation, with its fp32 convert
    # 7 ticks x (4 layer inputs + stage output + stage input)
    assert (kept, n_bytes) == (4, 7 * 6 * act16)
    # the gang, counted once (K trials' params, optimizer state and
    # gradients, and the transients), fits beside this stash and not
    # beside one more layer input a tick
    one = sched.per_chip_bytes(glm, rows, 4096, train=True)
    gang = (2 * one.params_bytes + one.opt_bytes + one.act_bytes
            - sched.stash_bytes(glm, rows, 4096, 0))
    assert gang + n_bytes <= BUDGET < gang + n_bytes + 7 * act16

    # yi-34b at 4096 over 4 stages x 16 FSDP rows, bf16: not one layer
    # input per tick fits beside the gang
    yi = get_config("yi-34b")
    opts = ModelOptions(remat=True, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    big = pl.EngineConfig(n_trials=1, n_microbatches=4, microbatch=1,
                          n_stages=4, data_size=16, fsdp=True)
    assert pl.train_stash(yi, opts, big, 4096, jnp.bfloat16) == (
        0, sched.stash_bytes(yi, big, 4096, 0))
    assert sched.stash_bytes(yi, big, 4096, 0) == 7 * 4096 * 7168 * 6
    one = sched.per_chip_bytes(yi, big, 4096, train=True)
    gang = (2 * one.params_bytes + one.opt_bytes + one.act_bytes
            - sched.stash_bytes(yi, big, 4096, 0))
    assert gang + sched.stash_bytes(yi, big, 4096, 1) > BUDGET


# the planner's gangs (K trials, M microbatches each) as they were before
# the step could keep layer inputs: plans count the tick stash alone
@pytest.mark.parametrize("arch,base,n_trials,seq,plans", [
    ("bert-large", pl.EngineConfig(n_trials=1, n_microbatches=1,
                                   microbatch=4, n_stages=4),
     6, 512, [(6, 5)]),
    ("bert-large", pl.EngineConfig(n_trials=1, n_microbatches=1,
                                   microbatch=4, n_stages=1),
     6, 512, [(2, 1)] * 3),
    ("chatglm3-6b", pl.EngineConfig(n_trials=1, n_microbatches=16,
                                    microbatch=1, n_stages=16,
                                    data_size=16, fsdp=True),
     8, 4096, [(2, 11)] * 4),
])
def test_gangs_are_planned_with_the_tick_stash(arch, base, n_trials, seq,
                                               plans):
    cfg = get_config(arch)
    trials = [sched.TrialSpec(arch=arch, lr=1e-4 * (i + 1), tag=str(i))
              for i in range(n_trials)]
    gangs = sched.plan_gangs(trials, base, {arch: cfg}, seq,
                             target_bubble=0.10)
    assert [(g.engine.n_trials, g.engine.n_microbatches)
            for g in gangs] == plans
    assert min(sched.max_concurrent_trials(cfg, base, seq),
               n_trials) == plans[0][0]
    for g in gangs:
        est = sched.per_chip_bytes(cfg, g.engine, seq, train=True)
        assert est.total * g.engine.n_trials <= BUDGET


def _dots(jaxpr) -> int:
    """dot_generals a run of ``jaxpr`` executes: sub-jaxprs counted with
    their scans' lengths, a cond by its costliest branch."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += 1
            continue
        subs = [x.jaxpr if isinstance(x, jcore.ClosedJaxpr) else x
                for v in eqn.params.values()
                for x in (v if isinstance(v, (list, tuple)) else (v,))
                if isinstance(x, (jcore.ClosedJaxpr, jcore.Jaxpr))]
        if not subs:
            continue
        counts = [_dots(s) for s in subs]
        if eqn.primitive.name == "cond":
            n += max(counts)
        else:
            n += eqn.params.get("length", 1) * sum(counts)
    return n


def _grad_dots(cfg, eng, mesh) -> int:
    params, batch = _setup(cfg, eng, mesh)

    def loss(p):
        return pl.pipeline_train_loss(cfg, OPTS, eng, p, batch)[0].sum()

    pspecs = pl.param_pspecs(cfg, eng)
    fn = jax.shard_map(jax.grad(loss), mesh=mesh, in_specs=(pspecs,),
                       out_specs=pspecs, check_vma=False)
    return _dots(jax.make_jaxpr(fn)(params).jaxpr)


def _objective(loss, aux):
    return loss.sum() + aux.sum()


@pytest.mark.parametrize("arch,n_data", [("bert-large", 1),
                                         ("bert-large", 2),
                                         ("granite-moe-3b-a800m", 1)])
def test_tickwise_grads_match_the_whole_step(arch, n_data):
    """One stage: each tick's backward right after its forward gives the
    losses and gradients of the step differentiated whole."""
    cfg = get_config(arch).reduced()
    eng = _eng(1, n_data, fsdp=n_data > 1)
    mesh = make_test_mesh(n_data, 1)
    params, batch = _setup(cfg, eng, mesh)
    pspecs = pl.param_pspecs(cfg, eng)
    bspecs = pl.batch_pspecs(cfg, eng, train=True)

    def whole(p, b):
        def f(p):
            loss, aux = pl.pipeline_train_loss(cfg, OPTS, eng, p, b)
            return _objective(loss, aux), loss
        return jax.grad(f, has_aux=True)(p)

    def ticks(p, b):
        return pl.tickwise_train_grads(cfg, OPTS, eng, p, b, _objective)

    outs = [jax.device_get(jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(pspecs, bspecs), out_specs=(pspecs, P()),
        check_vma=False))(params, batch)) for fn in (whole, ticks)]
    (g_w, loss_w), (g_t, loss_t) = outs
    np.testing.assert_allclose(loss_t, loss_w, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_t), jax.tree.leaves(g_w)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_backward_replays_each_kept_layer_forward_once(monkeypatch):
    eng = _eng()
    mesh = make_test_mesh(1, 1)
    cfg = get_config("bert-large").reduced()
    params, batch = _setup(cfg, eng, mesh)
    fwd = jax.shard_map(
        lambda p: pl.pipeline_train_loss(cfg, OPTS, eng, p, batch)[0].sum(),
        mesh=mesh, in_specs=(pl.param_pspecs(cfg, eng),), out_specs=P(),
        check_vma=False)
    # F: products in one layer's forward (q, k, v, scores, mix, out, up,
    # down); the head adds one per tick
    f = 8
    per_tick = cfg.n_layers * f + 1
    assert _dots(jax.make_jaxpr(fwd)(params).jaxpr) == eng.n_ticks * per_tick

    # every layer kept: forward F, its checkpoint's replay F - 1 (the last
    # product's output is not needed by the backward), backward 2F; the
    # head's forward is replayed with its backward (1 + 2)
    assert pl.train_stash(cfg, OPTS, eng, SEQ, jnp.float32)[0] == 4
    every = _grad_dots(cfg, eng, mesh)
    assert every == eng.n_ticks * (cfg.n_layers * (4 * f - 1) + 1 + 3)
    # the tick stash replays every layer's forward once more
    monkeypatch.setattr(sched, "HBM_BYTES_PER_CHIP", 0)
    ticks = _grad_dots(cfg, eng, mesh)
    assert ticks == every + eng.n_ticks * cfg.n_layers * f
    # each layer kept spares its replay
    for kept in (1, 3):
        _keep(monkeypatch, kept)
        assert _grad_dots(cfg, eng, mesh) == ticks - eng.n_ticks * kept * f

    # the one-stage step runs each tick's backward after its forward and
    # keeps every layer input: as many products as every layer kept
    monkeypatch.undo()
    step = jax.shard_map(
        lambda p: pl.tickwise_train_grads(cfg, OPTS, eng, p, batch,
                                          _objective)[0],
        mesh=mesh, in_specs=(pl.param_pspecs(cfg, eng),),
        out_specs=pl.param_pspecs(cfg, eng), check_vma=False)
    assert _dots(jax.make_jaxpr(step)(params).jaxpr) == every
