"""Hydra orchestrator: search space → gangs → shard-parallel training →
model selection. The end-to-end system of the paper (Fig. 3) with Cerebro's
role played by ``core.trials``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import pipeline as pl
from repro.core.partitioner import plan_stages
from repro.core.scheduler import GangPlan, TrialSpec, plan_gangs
from repro.core.trials import TrialResult
from repro.data.pipeline import TrainBatches
from repro.models.layers import ModelOptions
from repro.obs.tracer import resolve
from repro.optim.adamw import AdamW
from repro.runtime.fault_tolerance import LoopConfig, run_with_restarts


@dataclasses.dataclass
class HydraConfig:
    seq_len: int
    steps: int
    eval_every: int = 0  # 0 = only at end
    checkpoint_every: int = 50
    ckpt_dir: Optional[str] = None
    seed: int = 0
    param_dtype: jnp.dtype = jnp.float32


def with_dispatch_span(step_fn, tracer):
    """``step_fn`` with each call inside a ``step.dispatch`` span of
    ``tracer``; with the tracer off, ``step_fn`` itself."""
    if not tracer.enabled:
        return step_fn

    def dispatch(params, opt_state, batch, hparams, step):
        with tracer.span("step.dispatch"):
            return step_fn(params, opt_state, batch, hparams, step)

    return dispatch


class HydraRunner:
    """Runs one gang (same-arch trials) as a single shard-parallel program."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions, mesh,
                 hydra_cfg: HydraConfig, optimizer: Optional[AdamW] = None,
                 tracer=None):
        self.cfg, self.opts, self.mesh = cfg, opts, mesh
        self.hc = hydra_cfg
        self.optimizer = optimizer or AdamW(grad_clip=1.0)
        # gang/rung, set-up, data and dispatch spans for the obs timeline
        # and the profiler trace (NULL_TRACER when off)
        self.trace = resolve(tracer)

    def _build(self, gang: GangPlan):
        """The gang's parameters, AdamW state, per-trial hyperparameters and
        jitted train step. With the tracer on, the step is wrapped in a
        ``step.dispatch`` span; with it off, it is the bare jitted step.
        The ``build.step`` span carries the step's remat stash
        (``pipeline.train_stash``): its scheme, ``"layers"`` or ``"ticks"``
        (``None`` without remat), the layer inputs it keeps per tick and its
        estimated bytes."""
        eng = gang.engine
        tr = self.trace
        with tr.span("build.params"):
            plan = plan_stages(self.cfg, eng.n_stages)
            key = jax.random.PRNGKey(self.hc.seed)
            max_pos = self.hc.seq_len if self.cfg.rope == "learned" else 0
            params = pl.init_trial_params(self.cfg, eng, plan, key,
                                          dtype=self.hc.param_dtype,
                                          max_pos=max_pos, mesh=self.mesh)
        with tr.span("build.optimizer"):
            opt_state = self.optimizer.init(params)
        hparams = {
            "lr": jnp.asarray([t.lr for t in gang.trials], jnp.float32),
            "wd": jnp.asarray([t.weight_decay for t in gang.trials],
                              jnp.float32),
        }
        kept, stash_bytes = pl.train_stash(
            self.cfg, self.opts, eng, self.hc.seq_len, self.hc.param_dtype)
        stash = ("layers" if kept else "ticks") if self.opts.remat else None
        with tr.span("build.step", stash=stash, stash_layers=kept,
                     stash_bytes=stash_bytes):
            step_fn = pl.make_train_step(self.cfg, self.opts, eng, self.mesh,
                                         self.optimizer)
        return params, opt_state, hparams, with_dispatch_span(step_fn, tr)

    def run_gang(self, gang: GangPlan, n_steps: Optional[int] = None
                 ) -> list[TrialResult]:
        eng = gang.engine
        n_steps = n_steps or self.hc.steps
        with self.trace.span("gang", arch=gang.arch, n_trials=eng.n_trials,
                             steps=n_steps):
            params, opt_state, hparams, step_fn = self._build(gang)
            data = TrainBatches(self.cfg, eng, self.hc.seq_len,
                                seed=self.hc.seed, tracer=self.trace)
            losses = np.zeros((eng.n_trials,), np.float64)

            def one_step(state, step):
                p, o = state
                batch = data.batch_for_step(step)
                p, o, metrics = step_fn(p, o, batch, hparams,
                                        jnp.asarray(step, jnp.int32))
                return (p, o), metrics

            # each gang owns a checkpoint subdirectory: restarts within one
            # gang resume exactly, but a later gang (another rung of
            # successive halving, a different K) can never restore a stale
            # checkpoint whose trial axis doesn't match its own parameter
            # shapes
            ckpt_dir = self.hc.ckpt_dir
            if ckpt_dir is not None:
                tag = "|".join(
                    t.tag or f"lr{t.lr:g}wd{t.weight_decay:g}s{t.seed}"
                    for t in gang.trials)
                digest = hashlib.md5(tag.encode()).hexdigest()[:8]
                ckpt_dir = os.path.join(
                    ckpt_dir,
                    f"{gang.arch}-k{eng.n_trials}-n{n_steps}-{digest}")
            report = run_with_restarts(
                one_step, (params, opt_state),
                LoopConfig(n_steps=n_steps,
                           checkpoint_every=self.hc.checkpoint_every,
                           ckpt_dir=ckpt_dir))
            data.close()
            params, opt_state = report.final_state
            if report.step_metrics:
                losses = np.asarray(report.step_metrics[-1]["loss"])
            # held-out evaluation: a fresh deterministic batch beyond the
            # train steps
            val = self.evaluate(gang, params, hparams, step=10_000_000)
            return [TrialResult(spec=t, steps=n_steps,
                                train_loss=float(losses[i]),
                                val_loss=float(val[i]),
                                restarts=report.restarts)
                    for i, t in enumerate(gang.trials)]

    def evaluate(self, gang: GangPlan, params, hparams, step: int):
        """Per-trial validation loss on a held-out deterministic batch."""
        eng = gang.engine
        data = TrainBatches(self.cfg, eng, self.hc.seq_len,
                            seed=self.hc.seed + 999)
        batch = data.batch_for_step(step)
        data.close()
        pspecs = pl.param_pspecs(self.cfg, eng)
        bspecs = pl.batch_pspecs(self.cfg, eng, train=True)
        from jax.sharding import PartitionSpec as P

        def eval_loss(p, b):
            loss_vec, _ = pl.pipeline_train_loss(self.cfg, self.opts, eng,
                                                 p, b)
            for ax in eng.dp_axes:
                loss_vec = jax.lax.pmean(loss_vec, ax)
            return loss_vec

        fn = jax.jit(jax.shard_map(eval_loss, mesh=self.mesh,
                                   in_specs=(pspecs, bspecs),
                                   out_specs=P(), check_vma=False))
        return np.asarray(fn(params, batch))


def run_model_selection(cfg: ArchConfig, opts: ModelOptions, mesh,
                        hydra_cfg: HydraConfig, trials: Sequence[TrialSpec],
                        base_eng: pl.EngineConfig,
                        strategy=None, tracer=None) -> dict:
    """Full Hydra workflow: plan gangs, train them shard-parallel, select.

    ``tracer`` (``repro.obs.Tracer``) wraps each successive-halving rung —
    every ``train_fn`` invocation — and each gang in spans, with the
    gang's set-up, data and dispatch spans and its XLA compiles inside, so
    a search run exports the same Perfetto timeline as a serve run and,
    under a profiler, shows its spans in the device trace.

    Returns {"best": TrialResult, "all": [TrialResult...], "gangs": int}.
    """
    trace = resolve(tracer)
    runner = HydraRunner(cfg, opts, mesh, hydra_cfg, tracer=tracer)
    all_results: list[TrialResult] = []
    rung = [0]  # train_fn call index (a halving strategy calls it per rung)

    def train_fn(specs, n_steps):
        with trace.span("rung", label=rung[0], n_trials=len(specs),
                        steps=n_steps):
            gangs = plan_gangs(specs, base_eng, {cfg.name: cfg},
                               hydra_cfg.seq_len)
            out = []
            for g in gangs:
                out.extend(runner.run_gang(g, n_steps))
        all_results.extend(out)
        rung[0] += 1
        return out

    if strategy is None:
        results = train_fn(list(trials), hydra_cfg.steps)
        best = min(results, key=lambda r: r.val_loss)
    else:
        best = strategy.run(list(trials), train_fn)
    return {"best": best, "all": all_results}
