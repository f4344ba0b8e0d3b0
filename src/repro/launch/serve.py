"""Serving driver: continuous-batching engine over the Hydra pipeline.

Default mode streams a dynamic request trace (Poisson arrivals or a JSONL
replay) through :class:`repro.serve.ServeEngine` — slots are recycled the
round a request finishes and queued requests are admitted via chunked
prefill. ``--arches K`` co-serves K model variants from one gang: the slot
grid grows a trial axis, each request's ``arch`` id routes it to its own
variant's rows, and one SPMD program advances all K streams per tick.
``--static`` runs the old lockstep baseline on the same trace for
comparison.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --n-data 2 --n-model 4 --slots 3 --n-requests 12 --rate 2.0

    # co-serve two variants from one gang, traffic skewed 3:1 toward arch 0
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --arches 2 --arch-weights 3,1 --n-requests 16 --rate 2.0

    # paged multi-arch gang with shortest-prompt-first admission
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --arches 2 --paged --policy sjf --n-requests 16

    # paged + radix prefix cache (cross-request KV sharing; plan the grid
    # for the traffic's expected prefix redundancy)
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --paged --prefix-cache --expected-hit-rate 0.5 --n-requests 16

    # overcommit past the pool (preemptive retraction) with a host spill
    # tier absorbing retract payloads and evicted prefix blocks
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --paged --prefix-cache --overcommit 1.5 --host-blocks 32

    # sliding-window serving (attention archs; window < prompt+gen)
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --window 8 --n-requests 12

    # replay a recorded request stream (JSONL rows may carry arch/deadline)
    ... python -m repro.launch.serve --arch chatglm3-6b --smoke \
        --trace /tmp/stream.jsonl
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import pipeline as pl
from repro.core import scheduler as sched
from repro.core.partitioner import plan_stages
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.layers import ModelOptions
from repro.obs import (Tracer, report, write_events, write_metrics,
                       write_perfetto)
from repro.serve import (POLICIES, Request, ServeEngine, blocks_for,
                         load_trace, poisson_trace, static_serve)


def build_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-data", type=int, default=1)
    ap.add_argument("--n-model", type=int, default=1)
    ap.add_argument("--arches", type=int, default=1,
                    help="model variants K co-served by one gang (trial "
                    "rows); requests are routed by their arch id")
    ap.add_argument("--arch-weights", default="",
                    help="comma arrival weights per arch for the synthetic "
                    "trace and capacity planning (default uniform)")
    ap.add_argument("--slots", type=int, default=0,
                    help="microbatch slots M per trial (0 = capacity-planned,"
                    " capped by --max-slots)")
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=1,
                    help="requests per (slot × data replica)")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max prompt length for the synthetic trace")
    ap.add_argument("--gen-len", type=int, default=8,
                    help="max generation budget for the synthetic trace")
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrivals per engine tick")
    ap.add_argument("--trace", default="",
                    help="JSONL request-stream to replay instead of the "
                    "synthetic Poisson trace")
    ap.add_argument("--prefill-chunks", type=int, default=2)
    ap.add_argument("--policy", choices=POLICIES, default="fcfs",
                    help="per-arch admission order: fcfs | sjf (shortest "
                    "prompt first) | deadline (earliest Request.deadline)")
    ap.add_argument("--deadline-slack", type=float, default=0.0,
                    help=">0: stamp synthetic requests with arrival + slack "
                    "* total_len deadlines (for --policy deadline)")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding attention window in tokens (0 = full "
                    "attention; attention-family archs only)")
    ap.add_argument("--static", action="store_true",
                    help="run the lockstep static-batch baseline instead")
    cache = ap.add_mutually_exclusive_group()
    cache.add_argument("--paged", action="store_true",
                       help="paged KV-cache: per-trial block pools + "
                       "per-request block tables (admit by expected length)")
    cache.add_argument("--dense", action="store_true",
                       help="dense per-slot cache strips (the default)")
    adm = ap.add_mutually_exclusive_group()
    adm.add_argument("--fused-admission", action="store_true",
                     help="fold each round's prefill waves + decode step "
                     "into ONE mixed-tick pipeline call: prefilling rows "
                     "ride at their chunk width, decoding rows at qlen 1, "
                     "idle rows at 0 (attention-family archs; greedy tokens "
                     "stay bit-identical to the split schedule)")
    adm.add_argument("--split-admission", action="store_true",
                     help="one append call per chunk-length group plus a "
                     "separate decode call per round (the default)")
    attn = ap.add_mutually_exclusive_group()
    attn.add_argument("--paged-kernel", action="store_true",
                      help="paged decode/append attends straight from the "
                      "block pool via the Pallas paged-attention kernel "
                      "(trimmed block tables, O(live) work; interpret-mode/"
                      "jnp lowering on CPU — requires --paged)")
    attn.add_argument("--paged-gather", action="store_true",
                      help="paged decode/append gathers each row's full "
                      "max_seq logical K/V view before attending (the "
                      "default)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (--paged)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="per-trial block-pool size (--paged with explicit "
                    "--slots; 0 = back every cell at max_seq)")
    ap.add_argument("--expected-seq", type=int, default=0,
                    help="expected request length for paged capacity "
                    "planning (0 = max_seq/2)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="paged admission headroom: commit up to this "
                    "fraction of each pool partition (1.0 = preemption-free; "
                    "> 1.0 enables retraction — on pool exhaustion the "
                    "youngest running request is preempted, swapped to the "
                    "host tier or replayed, and restored later)")
    ap.add_argument("--host-blocks", type=int, default=0,
                    help="host-memory spill tier capacity per pool partition "
                    "(--paged): evicted prefix-cache blocks spill to host "
                    "instead of being destroyed, and retraction swaps KV out "
                    "instead of recomputing (0 = no host tier)")
    ap.add_argument("--no-spill", action="store_true",
                    help="keep the host tier for retraction payloads only: "
                    "prefix-cache eviction destroys blocks instead of "
                    "spilling them")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="radix prefix cache over the paged block pool: "
                    "completed prompts stay cached and new requests reuse "
                    "shared-prefix KV blocks (requires --paged)")
    ap.add_argument("--expected-hit-rate", type=float, default=0.0,
                    help="expected prefix-cache hit fraction for paged "
                    "capacity planning (shrinks per-row expected demand)")
    ap.add_argument("--spec-draft", default="",
                    help="gang-speculative decoding: pair every target arch "
                    "with a drafter trial row holding THIS ArchConfig's "
                    "weights (must share the target's parameter skeleton and "
                    "vocab — heterogeneous drafter archs need ragged param "
                    "packing, see ROADMAP). Drafter rows autoregressively "
                    "propose --spec-gamma tokens; the target verifies them "
                    "in one append-mode call. Greedy tokens stay "
                    "bit-identical; drafter quality only moves the "
                    "acceptance rate")
    ap.add_argument("--spec-gamma", type=int, default=3,
                    help="draft tokens proposed per speculation round")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event / Perfetto JSON "
                    "timeline of the run here (one track per (k,m,b) slot "
                    "cell + pool/host-tier/queue counter tracks; open at "
                    "https://ui.perfetto.dev). Enables tracing")
    ap.add_argument("--events-out", default="",
                    help="write the raw structured event log (JSONL, one "
                    "event per line) here. Enables tracing")
    ap.add_argument("--metrics-out", default="",
                    help="write the run's metric registry snapshot (JSONL, "
                    "one metric per line) here")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def parse_weights(spec: str, k: int):
    if not spec:
        return None
    w = [float(x) for x in spec.split(",")]
    if len(w) != k:
        raise SystemExit(f"--arch-weights needs {k} comma-separated values, "
                         f"got {len(w)}")
    return w


def check_args(args) -> None:
    """Reject flag combinations no engine can run (before any compile)."""
    if args.paged and args.static:
        raise SystemExit("--static is the dense lockstep baseline; "
                         "drop --paged")
    if args.prefix_cache and not args.paged:
        raise SystemExit("--prefix-cache shares paged KV blocks; add --paged")
    if args.overcommit > 1.0 and not args.paged:
        raise SystemExit(
            f"--overcommit {args.overcommit} > 1.0 admits past the block "
            f"pool and relies on retracting paged block commitments; dense "
            f"cache strips cannot be retracted — add --paged")
    if args.paged_kernel and not args.paged:
        raise SystemExit("--paged-kernel attends through block tables; "
                         "add --paged")
    if args.host_blocks < 0:
        raise SystemExit(f"--host-blocks must be >= 0, got {args.host_blocks}")
    if (args.host_blocks > 0 or args.no_spill) and not args.paged:
        raise SystemExit("--host-blocks/--no-spill manage the paged block "
                         "store's host tier; add --paged")
    if args.static and args.arches > 1:
        raise SystemExit("--static is single-arch lockstep batching; "
                         "multi-arch routing needs the continuous engine")
    if args.fused_admission and args.static:
        raise SystemExit("--fused-admission fuses the continuous engine's "
                         "round; drop --static")
    if args.spec_draft and args.static:
        raise SystemExit("--spec-draft speculates inside the continuous "
                         "engine's rounds; drop --static")
    if args.spec_draft and args.fused_admission:
        raise SystemExit("--spec-draft and --fused-admission both own the "
                         "round's ragged call structure; pick one")
    if args.spec_draft and args.spec_gamma < 1:
        raise SystemExit(f"--spec-gamma must be >= 1, got {args.spec_gamma}")


@dataclasses.dataclass
class ServeSetup:
    """Everything one serving run is built from (see :func:`build_serving`)."""

    cfg: ArchConfig
    eng: pl.EngineConfig
    mesh: Mesh
    opts: ModelOptions
    params: dict
    requests: list
    spec_pairs: Optional[dict]


def build_serving(args, requests=None) -> ServeSetup:
    """Parsed CLI args -> mesh, config, engine config (capacity-planned when
    ``--slots 0``), request stream and trial-stacked weights.

    ``requests`` (optional) replaces the ``--trace`` / synthetic stream; it
    is checked against ``max_seq`` the same way. Published-width models
    hold weights, KV cache and activations in bf16, their published dtype;
    ``--smoke`` keeps fp32, which the CPU oracle tests compare bit-exactly.
    Weights are made after every check, so a bad trace fails before any
    compile.
    """
    check_args(args)
    weights = parse_weights(args.arch_weights, args.arches)
    mesh = make_test_mesh(args.n_data, args.n_model)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    max_seq = args.prompt_len + args.gen_len
    opts = ModelOptions(use_paged_kernel=args.paged_kernel,
                        param_dtype=dtype, compute_dtype=dtype)
    base = pl.EngineConfig(
        n_trials=args.arches, n_microbatches=max(args.slots, 1),
        microbatch=args.microbatch, n_stages=args.n_model,
        data_size=args.n_data, max_seq=max_seq, cache_dtype=dtype,
        prefill_chunks=args.prefill_chunks, paged=args.paged,
        block_size=args.block_size, window=args.window)
    if args.slots <= 0:
        exp = args.expected_seq or None
        mix = None
        if args.arches > 1:
            w = weights or [1.0] * args.arches
            mix = [(wi, exp or max_seq // 2) for wi in w]
        planned = sched.plan_serve_capacity(
            cfg, base, max_seq, paged=args.paged, expected_seq=exp,
            block_size=args.block_size, max_slots=args.max_slots, mix=mix,
            hit_rate=args.expected_hit_rate if args.paged else 0.0,
            overcommit=args.overcommit if args.paged else 1.0,
            host_blocks=args.host_blocks)
        slots = min(planned.n_microbatches, args.max_slots)
        for line in report.render_capacity_plan(planned, slots, args.paged):
            print(line)
        base = dataclasses.replace(base, n_microbatches=slots,
                                   n_blocks=planned.n_blocks,
                                   host_blocks=planned.host_blocks)
    elif args.paged:
        n_blocks = args.n_blocks
        if n_blocks <= 0:
            # default pool: back every cell at max_seq (worst case — still
            # paged mechanics; shrink with --n-blocks to see backpressure)
            dp = args.n_data
            per_row = blocks_for(max_seq, args.block_size)
            n_blocks = args.microbatch * args.slots * per_row * dp
        base = dataclasses.replace(base, n_blocks=n_blocks,
                                   host_blocks=args.host_blocks)
    eng = base
    spec_pairs = None
    if args.spec_draft:
        dcfg = get_config(args.spec_draft)
        if args.smoke:
            dcfg = dcfg.reduced()
        # drafter rows ride the same stacked param pytree (leading K axis),
        # so the drafter arch must share the target's parameter skeleton —
        # heterogeneous drafter archs need ragged param packing (ROADMAP)
        e1 = dataclasses.replace(eng, n_trials=1)

        def skeleton(c):
            shapes = jax.eval_shape(lambda: pl.init_trial_params(
                c, e1, plan_stages(c, eng.n_stages), jax.random.PRNGKey(0),
                dtype=dtype, max_pos=max_seq))
            return jax.tree.map(lambda x: (x.shape, x.dtype), shapes)

        if dcfg.vocab_size != cfg.vocab_size or skeleton(dcfg) != skeleton(cfg):
            raise SystemExit(
                f"--spec-draft {args.spec_draft}: drafter parameter skeleton "
                f"(or vocab) differs from {args.arch} — the trial axis "
                f"stacks rows of one shape, so a smaller drafter arch needs "
                f"ragged per-row param packing (tracked in ROADMAP.md); "
                f"pick an arch variant with an identical skeleton")
        # drafter rows mirror the target rows: target k drafts on row K + k
        spec_pairs = {k: args.arches + k for k in range(args.arches)}
        eng = dataclasses.replace(eng, n_trials=2 * args.arches)

    if requests is None and args.trace:
        requests = load_trace(args.trace)
    if requests is not None:
        too_long = [r.rid for r in requests if r.total_len > max_seq]
        if too_long:
            raise SystemExit(f"trace requests {too_long} exceed max_seq="
                             f"{max_seq}; raise --prompt-len/--gen-len")
        bad_arch = [r.rid for r in requests if r.arch >= args.arches]
        if bad_arch:
            raise SystemExit(f"trace requests {bad_arch} target arch ids >= "
                             f"--arches={args.arches}; raise --arches")
        if args.static:
            # fail before params/compile: lockstep groups need one length
            n_cells = eng.n_microbatches * eng.microbatch * eng.data_size
            for g0 in range(0, len(requests), n_cells):
                plens = {r.prompt_len for r in requests[g0:g0 + n_cells]}
                if len(plens) > 1:
                    raise SystemExit(
                        f"--static needs uniform prompt lengths per batch "
                        f"group; group at {g0} has {sorted(plens)} — drop "
                        f"--static or bucket the trace")
    elif args.static:
        # lockstep baseline needs uniform prompts; stagger the budgets
        rng = np.random.default_rng(args.seed)
        requests = [
            Request(i, rng.integers(0, cfg.vocab_size,
                                    (args.prompt_len,)).astype(np.int32),
                    int(rng.integers(max(1, args.gen_len // 2),
                                     args.gen_len + 1)))
            for i in range(args.n_requests)]
    else:
        requests = poisson_trace(
            args.n_requests, args.rate, cfg.vocab_size,
            prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
            gen_lens=(max(args.gen_len // 2, 1), args.gen_len),
            seed=args.seed, n_arches=args.arches, arch_weights=weights,
            deadline_slack=args.deadline_slack)

    plan = plan_stages(cfg, eng.n_stages)
    params = pl.init_trial_params(cfg, eng, plan,
                                  jax.random.PRNGKey(args.seed),
                                  dtype=dtype, max_pos=max_seq, mesh=mesh)
    return ServeSetup(cfg, eng, mesh, opts, params, requests, spec_pairs)


def make_engine(args, setup: ServeSetup, opts: Optional[ModelOptions] = None,
                tracer=None) -> ServeEngine:
    """The continuous engine the CLI flags describe, over ``setup``'s
    weights; ``opts`` (optional) replaces ``setup.opts``."""
    return ServeEngine(setup.cfg, setup.eng, setup.mesh, setup.params,
                       opts or setup.opts, overcommit=args.overcommit,
                       policy=args.policy, prefix_cache=args.prefix_cache,
                       spill=not args.no_spill, fused=args.fused_admission,
                       spec_gamma=args.spec_gamma if args.spec_draft else 0,
                       spec_pairs=setup.spec_pairs, tracer=tracer)


def main():
    args = build_args().parse_args()
    tracing = bool(args.trace_out or args.events_out)
    if tracing and args.static:
        raise SystemExit("--trace-out/--events-out trace the continuous "
                         "engine's rounds; drop --static")
    enable_compile_cache()
    setup = build_serving(args)
    cfg, eng, requests = setup.cfg, setup.eng, setup.requests
    tracer = Tracer() if tracing else None

    if args.static:
        completions, stats = static_serve(cfg, eng, setup.mesh, setup.params,
                                          requests, setup.opts)
        mode = "static"
    else:
        engine = make_engine(args, setup, tracer=tracer)
        completions = engine.run(requests)
        stats = engine.stats
        mode = "continuous/paged" if args.paged else "continuous"
        if args.paged_kernel:
            mode += "+kernel"
        if args.fused_admission:
            mode += "+fused"
        if args.spec_draft:
            mode += f"+spec(gamma={args.spec_gamma})"
        if args.prefix_cache:
            mode += "+prefix-cache"
        if args.arches > 1:
            mode += f" x{args.arches}-arch gang"

    s = stats.summary()
    lines = report.render_completions(completions, multi_arch=args.arches > 1)
    lines += report.render_summary(mode, len(completions), s,
                                   policy=args.policy)
    if args.paged:
        lines += report.render_paged(s, eng.n_blocks, eng.block_size,
                                     eng.host_blocks, args.overcommit)
    if args.spec_draft and not args.static:
        lines += report.render_spec(s, engine.spec_stats.summary())
    if args.prefix_cache:
        lines += report.render_prefix(s)
    for line in lines:
        print(line)

    if tracer is not None:
        if args.trace_out:
            n = write_perfetto(tracer.events, args.trace_out)
            print(f"wrote {n} trace records -> {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")
        if args.events_out:
            n = write_events(tracer.events, args.events_out)
            print(f"wrote {n} events -> {args.events_out}")
    if args.metrics_out:
        n = write_metrics(stats.snapshot(), args.metrics_out)
        print(f"wrote {n} metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
