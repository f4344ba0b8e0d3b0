"""Bring-up smoke run: the system's main paths on a TPU, at published widths.

    python3 chip_smoke.py               # one chip: train, then serve
    python3 chip_smoke.py --four-chips  # four chips: the cross-chip gang only

One chip (the default) runs two phases in this one process:

* **train** — BERT-Large (``configs/bert_large.py``, the paper's workload)
  at its published widths and depth (24 layers), K=2 trials through
  ``run_model_selection`` on a 1x1 mesh for a few steps. Checks: every
  loss is finite and no gang restarted (a restart would hide a failed
  step).
* **serve** — chatglm3-6b at its published widths and depth, bf16 weights
  and KV cache, 8 requests (384-token prompts, 32 new tokens each) through
  ``ServeEngine`` on a paged pool, built by the same code as
  ``python -m repro.launch.serve`` (``build_serving``). It serves the
  requests once through the compiled Pallas paged-attention kernel and once
  through the gather path. Checks: every request gets its whole budget of
  in-vocab tokens, and one kernel call on the engine's live pool and block
  tables matches ``ref.paged_attention_ref``.

Train runs first so that each phase's ``peak_bytes_in_use`` (a process
lifetime high-water mark that cannot be reset) is read before the larger
serve phase raises it; the train phase's arrays are freed before serving.

``--four-chips`` runs only what exists across chips: the same K=2
BERT-Large gang as 4 pipeline stages over 4 chips (M=4 microbatches) and as
1 stage on one of those chips, in this process. Checks: per-trial losses
agree, and each chip's peak memory is about a quarter of the 1-stage one.

Each phase prints one JSON line (device kind, compile seconds, steady
seconds, peak bytes). The last line is ``{"ok": true, "device": {...}}``.
Any failed check raises, and the script exits non-zero without that line;
it also refuses to run when JAX finds no TPU. The numbers are bring-up
readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import pipeline as pl  # noqa: E402
from repro.core.hydra import (HydraConfig, HydraRunner,  # noqa: E402
                              run_model_selection)
from repro.core.scheduler import GangPlan  # noqa: E402
from repro.core.trials import grid_search  # noqa: E402
from repro.kernels import paged_attention as pa  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models.layers import ModelOptions  # noqa: E402
from repro.serve import Request  # noqa: E402

# serve phase: chatglm3-6b at published widths; 8 requests fill one slot's
# 8 batch rows, so every engine call is one 8-row pass over the weights
SERVE_FLAGS = ("--arch", "chatglm3-6b", "--paged", "--slots", "1",
               "--microbatch", "8", "--prompt-len", "384", "--gen-len", "32")
N_REQUESTS = 8
# train phases: BERT-Large (max 512 positions), K=2 learning rates
TRAIN_ARCH = "bert-large"
TRAIN_STEPS = 3
TRAIN_SEQ = 512
TRAIN_MICROBATCH = 4
TRAIN_LRS = (1e-4, 3e-5)
# one kernel call vs the fp32 reference, both rounded to bf16 at the end:
# the outputs can differ by one bf16 ulp (2^-8 relative) plus, if the
# kernel's fp32 p@v runs as one bf16 MXU pass, 2^-8 of the largest |v|
# summed over. 2^-6 * max|v| is twice that bound; a wrong block, head or
# mask moves the output by O(max|v|).
KERNEL_TOL_REL = 2.0 ** -6
# 1 stage vs 4 stages: the same fp32 math summed in another order (per-stage
# programs, vocab-parallel psums over 4 chips); after a few AdamW steps that
# stays at rounding level, far below what a dropped microbatch or a
# misordered stage moves a loss (>1e-2 relative)
LOSS_RTOL = 1e-3
# each of 4 stages holds a quarter of the layers, vocab tables and optimizer
# state; the pipeline stash and per-device scratch add a little
STAGE_PEAK_SHARE = (0.15, 0.40)

_COMPILE_S = [0.0]
_N_COMPILES = [0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration
        _N_COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def peak_bytes(device):
    """The device's high-water mark so far, or None where the backend
    keeps no allocator statistics (the CPU)."""
    stats = device.memory_stats() or {}
    if device.platform == "tpu":
        check("peak_bytes_in_use" in stats, "TPU reports peak_bytes_in_use")
    return stats.get("peak_bytes_in_use")


def _phase_line(phase: str, t_compile0: float, **fields) -> dict:
    dev = jax.devices()[0]
    line = {"phase": phase, "device_kind": dev.device_kind,
            "compile_s": round(_COMPILE_S[0] - t_compile0, 3), **fields}
    print(json.dumps(line), flush=True)
    return line


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _serve_once(engine, requests, on_step=None) -> tuple:
    """All ``requests`` through ``engine``; returns (completions by rid,
    wall seconds ending when the cache is ready, programs compiled)."""
    n0, c0 = len(engine.completions), _N_COMPILES[0]
    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r.clone())
    while not engine.done():
        engine.step()
        if on_step is not None:
            on_step(engine)
    jax.block_until_ready(engine.cache)
    wall = time.perf_counter() - t0
    return ({c.rid: c for c in engine.completions[n0:]}, wall,
            _N_COMPILES[0] - c0)


def _check_completions(comps, requests, vocab: int, what: str) -> None:
    check(sorted(comps) == [r.rid for r in requests],
          f"{what}: every request completes")
    for r in requests:
        toks = comps[r.rid].tokens
        check(len(toks) == r.max_new_tokens,
              f"{what}: request {r.rid} got {len(toks)} of "
              f"{r.max_new_tokens} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"{what}: request {r.rid} tokens in [0, {vocab})")


def kernel_vs_ref(engine, slots, seed: int = 0) -> dict:
    """One paged-kernel call on the engine's live pool and block tables
    (trial 0, layer 0) against ``ref.paged_attention_ref``. Compiled for
    the chip on a TPU; interpreted elsewhere."""
    cfg = engine.cfg
    pool_k = engine.cache["layers"]["k"][0, 0]
    pool_v = engine.cache["layers"]["v"][0, 0]
    width = max(len(s.table.blocks) for s in slots)
    tables = np.stack([s.table.as_row(width) for s in slots])
    kv_len = np.asarray([s.pos for s in slots], np.int32)
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (len(slots), 1, cfg.n_heads, cfg.head_dim),
                          pool_k.dtype)
    args = (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(kv_len - 1), jnp.asarray(kv_len))
    got = pa.paged_attention_pool(
        *args, causal=True, interpret=jax.default_backend() != "tpu")
    with jax.default_matmul_precision("float32"):
        want = ref.paged_attention_ref(*args, causal=True)
    live = np.unique(tables[tables >= 0])
    vmax = float(np.abs(np.asarray(pool_v, np.float32)[live]).max())
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    tol = KERNEL_TOL_REL * vmax
    check(err <= tol, f"paged kernel vs reference: max err {err} > {tol}")
    return {"rows": len(slots), "table_width": width,
            "kv_len": kv_len.tolist(), "max_abs_err": err, "tol": tol}


def serve_phase(flags=SERVE_FLAGS, n_requests: int = N_REQUESTS,
                seed: int = 0) -> dict:
    t_c0 = _COMPILE_S[0]
    args = serve.build_args().parse_args(list(flags) + ["--paged-kernel"])
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    rng = np.random.default_rng(seed)
    requests = [Request(i, rng.integers(0, cfg.vocab_size, args.prompt_len),
                        args.gen_len) for i in range(n_requests)]
    t0 = time.perf_counter()
    setup = serve.build_serving(args, requests)
    jax.block_until_ready(setup.params)
    init_s = time.perf_counter() - t0
    n_params = sum(x.size // setup.eng.n_trials
                   for x in jax.tree.leaves(setup.params))

    out = {"arch": cfg.name, "n_layers": cfg.n_layers,
           "d_model": cfg.d_model, "n_params": int(n_params),
           "weight_dtype": str(jnp.dtype(setup.opts.param_dtype)),
           "requests": n_requests, "prompt_len": args.prompt_len,
           "gen_len": args.gen_len, "init_s": round(init_s, 3)}
    runs = {}
    for name, use_kernel in (("kernel", True), ("gather", False)):
        opts = dataclasses.replace(setup.opts, use_paged_kernel=use_kernel)
        engine = serve.make_engine(args, setup, opts=opts)
        probe = {}

        def on_step(eng):
            # once every row is decoding halfway through its budget
            dec = eng.batcher.decode_slots()
            if (use_kernel and not probe and len(dec) == n_requests
                    and len(dec[0].generated) >= args.gen_len // 2):
                probe.update(kernel_vs_ref(eng, dec, seed=seed))

        comps, cold_s, _ = _serve_once(engine, requests, on_step)
        _check_completions(comps, requests, cfg.vocab_size, f"{name} cold")
        if use_kernel:
            check(bool(probe), "kernel check ran on a live decode step")
            out["kernel_vs_ref"] = probe
        calls = engine.stats.calls
        warm, steady_s, n_compiled = _serve_once(engine, requests)
        _check_completions(warm, requests, cfg.vocab_size, f"{name} steady")
        check(n_compiled == 0, f"{name}: the steady pass compiled "
              f"{n_compiled} programs; the cold pass warms every shape")
        check(all(warm[r].tokens == comps[r].tokens for r in comps),
              f"{name}: the same requests served twice give the same tokens")
        runs[name] = comps
        out[name] = {"cold_s": round(cold_s, 3),
                     "steady_s": round(steady_s, 3),
                     "engine_calls": engine.stats.calls - calls,
                     "tokens": n_requests * args.gen_len}
        del engine
    agree = sum(a == b for r in runs["kernel"]
                for a, b in zip(runs["kernel"][r].tokens,
                                runs["gather"][r].tokens))
    out["kernel_gather_token_agreement"] = round(
        agree / (n_requests * args.gen_len), 4)
    out["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
    del setup, runs
    gc.collect()
    return _phase_line("serve", t_c0, **out)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _bert(smoke: bool):
    cfg = get_config(TRAIN_ARCH)
    return cfg.reduced() if smoke else cfg


def train_phase(smoke: bool = False, steps: int = TRAIN_STEPS,
                seq_len: int = TRAIN_SEQ,
                microbatch: int = TRAIN_MICROBATCH) -> dict:
    """K=2 trials through ``run_model_selection`` on a 1x1 mesh (the
    planner picks the gang's microbatch count)."""
    t_c0 = _COMPILE_S[0]
    cfg = _bert(smoke)
    eng = pl.EngineConfig(n_trials=2, n_microbatches=1,
                          microbatch=microbatch, n_stages=1, data_size=1)
    trials = grid_search(cfg.name, TRAIN_LRS)
    t0 = time.perf_counter()
    res = run_model_selection(cfg, ModelOptions(remat=True),
                              make_test_mesh(1, 1),
                              HydraConfig(seq_len=seq_len, steps=steps),
                              trials, eng)
    wall = time.perf_counter() - t0
    results = res["all"]
    check(len(results) == len(trials), "every trial trained")
    for r in results:
        check(math.isfinite(r.train_loss) and math.isfinite(r.val_loss),
              f"trial {r.spec.tag}: finite losses "
              f"({r.train_loss}, {r.val_loss})")
        check(r.restarts == 0, f"trial {r.spec.tag}: {r.restarts} restarts")
    compile_s = _COMPILE_S[0] - t_c0
    gc.collect()
    return _phase_line(
        "train", t_c0, arch=cfg.name, n_layers=cfg.n_layers,
        d_model=cfg.d_model,
        trials=len(results), steps=steps,
        seq_len=seq_len, microbatch=microbatch, wall_s=round(wall, 3),
        # run wall outside XLA compilation (init, data, steps, evaluation;
        # the evaluation's host readback ends it)
        steady_s=round(wall - compile_s, 3),
        losses=[[r.train_loss, r.val_loss] for r in results],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def gang_phase(smoke: bool = False, steps: int = TRAIN_STEPS,
               seq_len: int = TRAIN_SEQ,
               microbatch: int = TRAIN_MICROBATCH) -> dict:
    """One K=2 gang as 4 pipeline stages over 4 chips, then as 1 stage on
    the first of them; compares losses and per-chip peaks."""
    n_stages, n_microbatches = 4, 4
    t_c0 = _COMPILE_S[0]
    cfg = _bert(smoke)
    devices = jax.devices()[:n_stages]
    check(len(devices) == n_stages, f"{n_stages} devices")
    trials = tuple(grid_search(cfg.name, TRAIN_LRS))
    hc = HydraConfig(seq_len=seq_len, steps=steps)
    opts = ModelOptions(remat=True)
    eng = pl.EngineConfig(n_trials=len(trials),
                          n_microbatches=n_microbatches,
                          microbatch=microbatch, n_stages=n_stages)
    out = {"arch": cfg.name, "n_layers": cfg.n_layers,
           "trials": len(trials), "steps": steps,
           "seq_len": seq_len, "microbatch": microbatch,
           "n_microbatches": n_microbatches}
    res = {}
    for s in (n_stages, 1):
        runner = HydraRunner(cfg, opts, make_test_mesh(1, s), hc)
        t0 = time.perf_counter()
        res[s] = runner.run_gang(GangPlan(
            cfg.name, trials, dataclasses.replace(eng, n_stages=s)))
        out[f"wall_s_{s}stage"] = round(time.perf_counter() - t0, 3)
        out[f"peak_bytes_{s}stage"] = [peak_bytes(d)
                                       for d in devices[:s]]
        del runner
        gc.collect()
    gaps = []
    for a, b in zip(res[n_stages], res[1]):
        check(a.restarts == 0 and b.restarts == 0, "no restarts")
        for la, lb in ((a.train_loss, b.train_loss),
                       (a.val_loss, b.val_loss)):
            check(math.isfinite(la) and math.isfinite(lb), "finite losses")
            gaps.append(abs(la - lb) / abs(lb))
    out["losses"] = {s: [[r.train_loss, r.val_loss] for r in res[s]]
                     for s in res}
    out["max_loss_rel_gap"] = max(gaps)
    check(max(gaps) <= LOSS_RTOL,
          f"{n_stages}-stage vs 1-stage losses: rel gap {max(gaps)} > "
          f"{LOSS_RTOL}")
    one = out["peak_bytes_1stage"][0]
    if one is not None:
        shares = [p / one for p in out[f"peak_bytes_{n_stages}stage"]]
        out["stage_peak_share"] = [round(x, 4) for x in shares]
        lo, hi = STAGE_PEAK_SHARE
        check(all(lo <= x <= hi for x in shares),
              f"per-chip peak shares {shares} outside [{lo}, {hi}] of the "
              f"1-stage peak")
    return _phase_line("gang", t_c0, **out)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage BERT-Large gang over four "
                    "chips against its 1-stage run")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
              f"{len(devices)}")
        gang_phase()
    else:
        train_phase()
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
