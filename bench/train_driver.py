"""Training cells: K trials of one architecture trained as one gang
through the program's own ``HydraRunner`` build (params, AdamW state,
per-trial learning rates, the jitted pipelined train step) and its data
pipeline (``TrainBatches.batch_for_step``), as ``run_gang`` drives them.

Set-up builds that one step, gives it the benchmark's weights and a fresh
optimizer state for them, and drives it from the seed through its first
four steps, reading what the comparison needs on the way. The window then
drives the same objects on. After the window the program's state is freed
and the plain reference follows the first three steps of every trial.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

import common
import flops
import weights
from common import now

SETUP_STEPS = 4  # the first compiles; the reference follows three
TRACE_S = 10.0  # a traced run traces the first this many seconds


def _units(name: str, leaf) -> int:
    """How many compared units a leaf holds per trial: each layer of a
    stacked layer leaf is one, any other leaf is one."""
    return leaf.shape[1] if name.startswith("layers/") else 1


@jax.jit
def _unit_norms(tree):
    """Per (trial, unit) norms of every leaf, in tree order."""
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = a.astype(jnp.float32)
        if weights.leaf_name(path).startswith("layers/"):
            out.append(jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(
                range(2, a.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(
                range(1, a.ndim))))[:, None])
    return out


@functools.partial(jax.jit, static_argnames=("leaves", "vocab"))
def _delta_norms_jit(p3, key, leaves, vocab):
    out = []
    for i, ((name, shape, dtype), a) in enumerate(zip(leaves,
                                                      jax.tree.leaves(p3))):
        w0 = weights._leaf(name, jax.ShapeDtypeStruct(shape, dtype),
                           jax.random.fold_in(key, i), vocab)
        d = a.astype(jnp.float32) - w0.astype(jnp.float32)
        ax = tuple(range(2 if name.startswith("layers/") else 1, a.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(d), axis=ax))
        out.append(n if name.startswith("layers/") else n[:, None])
    return out


def _delta_norms(p3, struct, seed, vocab):
    """Per (trial, unit) norms of the weights' change since the seed's
    weights, which are made again leaf by leaf inside the program that
    subtracts them, so they are never held whole beside ``p3``."""
    leaves = tuple((weights.leaf_name(path), s.shape, s.dtype) for path, s
                   in jax.tree_util.tree_flatten_with_path(struct)[0])
    return [np.asarray(x) for x in _delta_norms_jit(
        p3, common.jax_key(jax, seed, 1), leaves, vocab)]


def build(spec, devices) -> dict:
    """The gang as ``HydraRunner._build`` makes it: the jitted pipelined
    train step, the per-trial learning rates and the optimizer. The
    program's own weights and optimizer state are dropped; ``start`` puts
    the benchmark's in their place."""
    from repro.configs import get_config
    from repro.core import pipeline as pl
    from repro.core.hydra import HydraConfig, HydraRunner
    from repro.core.scheduler import GangPlan
    from repro.core.trials import grid_search
    from repro.launch.mesh import make_test_mesh
    from repro.models.layers import ModelOptions

    c, t = spec["config"], spec["traffic"]
    cfg = get_config(c["model"])
    if t.get("smoke"):
        cfg = cfg.reduced()
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab_size", "rope", "norm_eps", "act",
                "tie_embeddings"):
        if getattr(cfg, key) != c[key]:
            raise SystemExit(f"configuration file disagrees with the "
                             f"program's {cfg.name}: {key} = {c[key]!r} "
                             f"against {getattr(cfg, key)!r}")
    eng = pl.EngineConfig(n_trials=len(t["lrs"]),
                          n_microbatches=t["n_microbatches"],
                          microbatch=t["microbatch"], n_stages=t["n_stages"],
                          data_size=1)
    trials = tuple(grid_search(cfg.name, t["lrs"], (t["weight_decay"],)))
    mesh = make_test_mesh(1, t["n_stages"])
    runner = HydraRunner(cfg, ModelOptions(remat=c["remat"]), mesh,
                         HydraConfig(seq_len=t["seq_len"], steps=1))
    prog, opt_state, hparams, step_fn = runner._build(
        GangPlan(cfg.name, trials, eng))
    struct = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          prog)
    shard = jax.tree.map(lambda a: a.sharding, prog)
    del prog, opt_state
    gc.collect()
    return {"cfg": cfg, "eng": eng, "runner": runner, "hparams": hparams,
            "step_fn": step_fn, "struct": struct, "shard": shard}


def start(gang: dict, spec: dict, seed: int, phases: dict):
    """The seed's weights and a fresh optimizer state for them, then the
    set-up steps, which read what the comparison needs. Returns the state
    after them, the readings and the data pipeline."""
    from repro.data.pipeline import TrainBatches
    c, t = spec["config"], spec["traffic"]
    t0 = now()
    p = weights.make(gang["struct"], gang["shard"], seed, c["vocab_size"])
    o = gang["runner"].optimizer.init(p)
    jax.block_until_ready((p, o))
    phases["weights"] = now() - t0
    data = TrainBatches(gang["cfg"], gang["eng"], t["seq_len"], seed=seed)
    b1 = c["optimizer"]["b1"]
    readings = {"losses": [], "batches": []}
    for step in range(SETUP_STEPS):
        t0 = now()
        if step == SETUP_STEPS - 1:
            readings["delta"] = _delta_norms(p, gang["struct"], seed,
                                             c["vocab_size"])
            phases["delta_norms"] = now() - t0
            t0 = now()
        p, o, m, batch = one_step(gang, data, p, o, step)
        if step < SETUP_STEPS - 1:
            readings["losses"].append(np.asarray(m["loss"]))
            readings["batches"].append(batch)
        if step == 0:
            readings["grad1"] = [np.asarray(x) / (1 - b1)
                                 for x in _unit_norms(o["m"])]
            # AdamW's first moment after one step: (1 - b1) x the first
            # clipped gradient, kept whole on the host
            readings["m1"] = jax.tree.map(np.asarray, o["m"])
        jax.block_until_ready((p, o))
        phases[f"step{step}"] = now() - t0
    return p, o, readings, data


def one_step(gang, data, p, o, step):
    with jax.profiler.TraceAnnotation("bench.batch"):
        batch = data.batch_for_step(step)
    with jax.profiler.TraceAnnotation("bench.train_step"):
        p, o, m = gang["step_fn"](p, o, batch, gang["hparams"],
                                  jnp.asarray(step, jnp.int32))
    return p, o, m, batch


def run(spec: dict, seed: int, seconds: float, trace_dir, devices,
        clock) -> dict:
    c, t = spec["config"], spec["traffic"]
    phases = {}
    if "process_start" in clock:
        phases["to_build"] = now() - clock["process_start"]
    t0 = now()
    gang = build(spec, devices)
    phases["build"] = now() - t0
    eng = gang["eng"]
    p, o, readings, data = start(gang, spec, seed, phases)
    common.log("setup phases " + " ".join(f"{k}={v:.3f}"
                                          for k, v in phases.items()))
    try:
        tokens_per_step = (eng.n_trials * eng.n_microbatches
                           * eng.microbatch * t["seq_len"])
        prof = None
        n_comp0 = clock["compiles"].n
        if trace_dir:
            from serve_driver import _start_trace, _stop_trace
            prof = _start_trace(trace_dir)
        w0 = now()
        w1 = w0 + seconds
        clock["window_start"] = w0
        step = SETUP_STEPS
        pending = None
        done_t = []
        losses = []
        traced_end = None
        while True:
            p, o, m, _ = one_step(gang, data, p, o, step)
            step += 1
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    loss = np.asarray(pending["loss"])
                done_t.append(now())
                losses.append(loss)
            pending = m
            if prof is not None and now() >= w0 + TRACE_S:
                traced_end = now()
                prof = _stop_trace(prof)
            if done_t and done_t[-1] >= w1:
                break
        jax.block_until_ready((p, o))
        if prof is not None:
            traced_end = now()
            _stop_trace(prof)
        in_win = [x for x in done_t if x < w1]
        # the window's rate: its whole steps over the time they took, from
        # the window's start (the set-up's last step has ended) to the end
        # of the last step that ended inside it
        span = in_win[-1] - w0
        traced = [x for x in done_t if x < (traced_end or w1)]
        dev = common.device_info(devices)
        record = {
            "kind": "train", "config": c, "traffic": t,
            "peaks": spec.get("peaks")
            or common.peaks_for(devices[0].device_kind),
            "window_s": seconds, "steps": len(traced),
            "tokens": len(traced) * tokens_per_step,
            "n_chips": len(devices),
            "flops_per_token": flops.train_flops_per_token(c, t["seq_len"]),
        }
        out = {"counters": {"steps": len(in_win),
                            "tokens_per_step": tokens_per_step,
                            "window_s": seconds, "span_s": span,
                            "steps_total": step,
                            "compiles_in_run": clock["compiles"].n - n_comp0,
                            "nonfinite": int(sum(
                                not np.all(np.isfinite(x)) for x in losses))},
               "device": dev, "record": record, "readings": readings,
               "struct": gang["struct"], "shard": gang["shard"],
               "hparams": {"lr": list(t["lrs"]), "wd": t["weight_decay"]}}
    finally:
        data.close()
    del p, o, gang
    gc.collect()
    return out


def metrics(out: dict, spec: dict) -> tuple:
    cnt = out["counters"]
    vals = {"train_tok_s": cnt["steps"] * cnt["tokens_per_step"]
            / cnt["span_s"]}
    return vals, cnt["steps"], cnt["nonfinite"], dict(cnt)


def _flat(units) -> np.ndarray:
    return np.concatenate([np.asarray(x).reshape(len(x), -1) for x in units],
                          axis=1)


def _worst(num, ref, mask=None) -> float:
    """Worst unit's ``num`` over the larger of that unit's reference norm
    and the median unit's."""
    num, ref = _flat(num), _flat(ref)
    med = np.median(ref, axis=1, keepdims=True)
    g = num / np.maximum(ref, med)
    if mask is not None:
        g = np.where(mask, g, 0.0)
    return float(g.max())


def _gap(prog, ref, mask=None) -> float:
    """Worst unit's gap between the program's norm and the reference's."""
    return _worst([np.abs(np.asarray(a) - np.asarray(b))
                   for a, b in zip(prog, ref)], ref, mask)


def reference_readings(spec, out, seed, precision="fp32", keep=1.0,
                       against=None, keep_g1=False):
    """The reference's losses, first-gradient unit norms and three-step
    change unit norms for every trial, from the seed's weights. With
    ``against`` = (tree, scale), a first gradient stacked over the trials
    (times ``scale``), also the unit norms of its difference from this
    reference's; with ``keep_g1``, this reference's whole first gradient,
    stacked over the trials, on the host."""
    c = spec["config"]
    ref = common.config_reference(spec["workload"]["config"])
    p0 = weights.make(out["struct"], out["shard"], seed, c["vocab_size"])
    # one device, float32, one trial at a time
    p0 = jax.tree.map(lambda a: np.asarray(a), p0)
    losses, g1n, dn, en, full = [], [], [], [], []
    k_n = len(out["hparams"]["lr"])
    for k in range(k_n):
        pk = jax.tree.map(lambda a: jnp.asarray(a[k], jnp.float32), p0)
        bt = [{"tokens": b["tokens"][k], "labels": b["labels"][k]}
              for b in out["readings"]["batches"]]
        r = ref.train(c, pk, bt, out["hparams"]["lr"][k],
                      out["hparams"]["wd"], precision=precision, keep=keep)
        losses.append(r["losses"])
        g = jax.tree.map(lambda a: a[None], r["grad1"])
        g1n.append([np.asarray(x)[0] for x in _unit_norms(g)])
        if against is not None:
            tree, scale = against
            e = jax.tree.map(lambda a, b: (scale * jnp.asarray(b[k]) - a)[None],
                             r["grad1"], tree)
            en.append([np.asarray(x)[0] for x in _unit_norms(e)])
            del e
        if keep_g1:
            full.append(jax.tree.map(np.asarray, r["grad1"]))
        d = jax.tree.map(lambda a, b: (a - jnp.asarray(b[k]))[None],
                         r["params"], p0)
        dn.append([np.asarray(x)[0] for x in _unit_norms(d)])
        del pk, r, g, d
        gc.collect()
    stack = lambda per_k: [np.stack([per_k[k][i] for k in range(k_n)])
                           for i in range(len(per_k[0]))]
    res = {"losses": np.asarray(losses).T, "grad1": stack(g1n),
           "delta": stack(dn)}
    if en:
        res["grad1_err"] = stack(en)
    if full:
        res["g1_full"] = jax.tree.map(lambda *xs: np.stack(xs), *full)
    return res


def readings_gaps(prog: dict, ref: dict, err=None) -> dict:
    """The numbers of a training run (or of the control, or of a fault)
    compared with a reading of the reference; with ``err``, the unit norms
    of the difference of their first gradients, also that one."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g_ref = _flat(ref["grad1"])
    med = np.median(g_ref, axis=1, keepdims=True)
    moved = g_ref >= 1e-3 * med  # leaves the gradient moves at all
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad1_gap": _gap(prog["grad1"], ref["grad1"]),
           "change_gap": _gap(prog["delta"], ref["delta"], moved)}
    if err is not None:
        out["grad1_diff"] = _worst(err, ref["grad1"])
    return out


def compare(out: dict, spec: dict, seed: int) -> dict:
    lim = spec["traffic"]["check"]
    b1 = spec["config"]["optimizer"]["b1"]
    ref = reference_readings(spec, out, seed,
                             against=(out["readings"]["m1"], 1 / (1 - b1)))
    gaps = readings_gaps(out["readings"], ref, ref["grad1_err"])
    res = {k: common.check(v, lim[k], v <= lim[k]) for k, v in gaps.items()}
    nf = out["counters"]["nonfinite"]
    res["nonfinite_losses"] = common.check(nf, 0, nf == 0)
    return res
