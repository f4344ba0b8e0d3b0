"""Serving cells: chatglm3-6b (or any decoder the program serves) through
the program's own launcher path, ``build_serving`` and ``make_engine``,
driven by an open or a closed loop of requests.

A run: build the engine; swap in the benchmark's weights; warm every shape
the traffic uses; pre-roll; measure ``seconds``; follow the window's
requests to their end; free the engine; compare a sample of the served
tokens with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np

import common
import traffic as gen
import weights
from common import now

# the launcher's own weight seed; its weights are replaced by the benchmark's
ENGINE_FLAGS_SEED = ["--seed", "0"]
# a traced run traces the first this many seconds of its window: a longer
# trace only costs time to write and read
TRACE_S = 20.0


@dataclasses.dataclass
class Tracked:
    """What the loop saw of one request."""

    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    in_window: bool
    emit_t: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    prefilled: int = 0
    done: bool = False


class CallLog:
    """Wraps the engine's append and decode steps to record, for every call,
    when it was dispatched and the rows it carried: (start, new tokens,
    emits a token). The rows come from the batcher's own view at the call
    (``prefill_groups``, ``decode_slots``), so no device array is read."""

    def __init__(self, engine):
        self.calls = []
        self.engine = engine
        self._append = engine.append_step
        self._decode = engine.decode_step
        engine.append_step = self.append
        engine.decode_step = self.decode

    def append(self, params, cache, batch):
        qlen = int(batch["tokens"].shape[-1])
        group = self.engine.batcher.prefill_groups().get(qlen, [])
        rows = [(s.pos, qlen, len(s.chunks) == 1) for s in group]
        self.calls.append((now(), "append", rows))
        with jax.profiler.TraceAnnotation("bench.append"):
            return self._append(params, cache, batch)

    def decode(self, params, cache, batch):
        rows = [(s.pos, 1, True) for s in self.engine.batcher.decode_slots()]
        self.calls.append((now(), "decode", rows))
        with jax.profiler.TraceAnnotation("bench.decode"):
            return self._decode(params, cache, batch)


def _observe(engine, tracked: dict, n_comp: int, t: float) -> tuple:
    """After a step: new tokens and prefill progress of every live request.
    Returns (prompt tokens prefilled, tokens emitted, completions seen)."""
    pre = out = 0
    seen = []
    for s in engine.batcher.slots:
        r = s.request
        if r is not None:
            seen.append((r.rid, min(s.pos, r.prompt_len), s.generated))
    for c in engine.completions[n_comp:]:
        seen.append((c.rid, c.prompt_len, c.tokens))
    for rid, prefilled, toks in seen:
        tr = tracked[rid]
        pre += prefilled - tr.prefilled
        tr.prefilled = prefilled
        new = min(len(toks), tr.max_new) - len(tr.tokens)
        if new > 0:
            tr.tokens.extend(int(x) for x in toks[len(tr.tokens):
                                                   len(tr.tokens) + new])
            tr.emit_t.extend([t] * new)
            out += new
    for c in engine.completions[n_comp:]:
        tracked[c.rid].done = True
    return pre, out, len(engine.completions)


def build(spec: dict, seed: int, devices):
    """The engine as the program's launcher builds it for the traffic
    file's layout, holding the benchmark's weights."""
    from repro.configs import get_config
    from repro.launch import serve

    c, t = spec["config"], spec["traffic"]
    args = serve.build_args().parse_args(t["engine"] + ENGINE_FLAGS_SEED)
    pcfg = get_config(args.arch)
    if args.smoke:
        pcfg = pcfg.reduced()
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab_size", "rope", "rope_theta", "norm_eps",
                "act", "tie_embeddings"):
        if getattr(pcfg, key) != c[key]:
            raise SystemExit(f"configuration file disagrees with the "
                             f"program's {args.arch}: {key} = {c[key]!r} "
                             f"against {getattr(pcfg, key)!r}")
    setup = serve.build_serving(args, requests=[])
    program_params = setup.params
    setup = dataclasses.replace(setup, params=None)
    struct = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          program_params)
    shard = jax.tree.map(lambda a: a.sharding, program_params)
    del program_params
    gc.collect()
    params = weights.make(struct, shard, seed, c["vocab_size"])
    setup = dataclasses.replace(setup, params=params)
    engine = serve.make_engine(args, setup)
    return args, setup, engine, (struct, shard)


def warm(engine, spec: dict) -> None:
    """Every append width and the decode step, once, through the engine:
    one request per prompt length the traffic can send."""
    from repro.serve import Request
    t = spec["traffic"]
    lens = gen.distinct_prompt_lengths(t["prompt"])
    rng = np.random.default_rng(0)
    for i, n in enumerate(lens):
        engine.submit(Request(-1 - i, rng.integers(0, 100, n, np.int32), 2))
    while not engine.done():
        engine.step()
    jax.block_until_ready(engine.cache)
    engine.completions.clear()


def _run_open(engine, spec, seed, seconds, trace_dir, counters):
    from repro.serve import Request
    t = spec["traffic"]
    vocab = spec["config"]["vocab_size"]
    load = gen.open_loop(t, vocab, seconds, common.np_rng(seed, 2))
    n = len(load["due"])
    tracked = {}
    i = 0
    n_comp = len(engine.completions)
    w0, w1 = load["window"]
    t0 = now()
    w0a, w1a = t0 + w0, t0 + w1
    stat0 = stat1 = None
    win_pre = win_out = 0
    late = []
    prof = None
    window_reqs = set(np.nonzero(load["in_window"])[0].tolist())
    drain_from = w1a
    while True:
        tn = now()
        if stat0 is None and tn >= w0a:
            stat0 = (dict(engine.stats.summary()), tn)
            if trace_dir:
                prof = _start_trace(trace_dir)
                counters["traced"] = [now(), None]
                counters["traced_stats"] = [stat0[0], None]
        if prof is not None and tn >= min(w1a, w0a + TRACE_S):
            counters["traced"][1] = now()
            counters["traced_stats"][1] = dict(engine.stats.summary())
            prof = _stop_trace(prof)
            # writing the trace stalls the loop; the window's requests
            # still get the whole drain after it
            drain_from = max(w1a, now())
        if stat1 is None and tn >= w1a:
            stat1 = (dict(engine.stats.summary()), tn)
            queued = engine.batcher.queued()
        while i < n and t0 + load["due"][i] <= tn:
            tracked[i] = Tracked(i, t0 + load["due"][i], load["prompts"][i],
                                 int(load["max_new"][i]),
                                 bool(load["in_window"][i]))
            late.append(tn - tracked[i].due)
            engine.submit(Request(i, load["prompts"][i],
                                  int(load["max_new"][i])))
            i += 1
        if stat1 is not None and all(tracked[r].done for r in window_reqs
                                     if r in tracked) \
                and i > max(window_reqs):
            break
        if tn > drain_from + t["drain_s"]:
            break
        if engine.done():
            if i >= n:
                break
            with jax.profiler.TraceAnnotation("bench.wait"):
                _sleep_until(t0 + load["due"][i])
            continue
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        te = now()
        pre, out, n_comp = _observe(engine, tracked, n_comp, te)
        if w0a <= te < w1a:
            win_pre += pre
            win_out += out
    if prof is not None:
        _stop_trace(prof)
    counters.update(window_s=w1a - w0a, prompt_tokens=win_pre,
                    output_tokens=win_out, stats0=stat0[0], stats1=stat1[0],
                    window=(w0a, w1a), queued_at_close=queued,
                    late_p50_ms=1e3 * common.percentile(late, 50),
                    late_max_ms=1e3 * max(late))
    return [tracked[r] for r in sorted(window_reqs)]


def _run_closed(engine, spec, seed, seconds, trace_dir, counters):
    from repro.serve import Request
    t = spec["traffic"]
    vocab = spec["config"]["vocab_size"]
    load = gen.closed_loop(t, vocab, t["pool"], common.np_rng(seed, 2))
    tracked = {}
    nxt = 0
    n_comp = len(engine.completions)
    t0 = now()
    w0a, w1a = t0 + t["preroll_s"], t0 + t["preroll_s"] + seconds
    stat0 = stat1 = None
    win_pre = win_out = 0
    prof = None

    def send():
        nonlocal nxt
        i = nxt % t["pool"]
        tracked[nxt] = Tracked(nxt, now(), load["prompts"][i],
                               int(load["max_new"][i]), False)
        engine.submit(Request(nxt, load["prompts"][i],
                              int(load["max_new"][i])))
        nxt += 1

    for _ in range(t["outstanding"]):
        send()
    while True:
        tn = now()
        if stat0 is None and tn >= w0a:
            stat0 = (dict(engine.stats.summary()), tn)
            if trace_dir:
                prof = _start_trace(trace_dir)
                counters["traced"] = [now(), None]
        if tn >= w1a:
            stat1 = (dict(engine.stats.summary()), tn)
            if prof is not None:
                counters["traced"][1] = now()
                _stop_trace(prof)
            break
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        te = now()
        before = n_comp
        pre, out, n_comp = _observe(engine, tracked, n_comp, te)
        if w0a <= te < w1a:
            win_pre += pre
            win_out += out
            for c in engine.completions[before:n_comp]:
                tracked[c.rid].in_window = True
        for _ in range(n_comp - before):
            send()
    counters.update(window_s=w1a - w0a, prompt_tokens=win_pre,
                    output_tokens=win_out, stats0=stat0[0], stats1=stat1[0],
                    window=(w0a, w1a))
    return [r for r in tracked.values() if r.in_window]


def _sleep_until(t: float) -> None:
    import time
    d = t - now()
    if d > 0:
        time.sleep(d)


def _start_trace(trace_dir):
    jax.profiler.start_trace(trace_dir)
    ann = jax.profiler.TraceAnnotation("bench.window")
    ann.__enter__()
    return ann


def _stop_trace(ann):
    ann.__exit__(None, None, None)
    jax.profiler.stop_trace()
    return None


def loop(engine, spec, seed, seconds, trace_dir, counters):
    """Drive ``engine`` with the cell's load from the seed: pre-roll, the
    window of ``seconds``, and (open loop) the window's requests to their
    end. Returns the window's requests."""
    drive = _run_open if spec["traffic"]["kind"] == "serve_open" \
        else _run_closed
    return drive(engine, spec, seed, seconds, trace_dir, counters)


def run(spec: dict, seed: int, seconds: float, trace_dir, devices,
        clock) -> dict:
    c, t = spec["config"], spec["traffic"]
    args, setup, engine, _ = build(spec, seed, devices)
    warm(engine, spec)
    calls = CallLog(engine)
    counters = {}
    n_compiled0 = clock["compiles"].n
    reqs = loop(engine, spec, seed, seconds, trace_dir, counters)
    clock["window_start"] = counters["window"][0]
    counters["compiles_in_run"] = clock["compiles"].n - n_compiled0
    dev = common.device_info(devices)
    w0, w1 = counters.get("traced") or counters["window"]
    stats0, stats1 = counters.get("traced_stats") or (counters["stats0"],
                                                      counters["stats1"])
    rec = {
        "kind": "serve", "config": c, "traffic": t,
        "peaks": spec.get("peaks")
        or common.peaks_for(devices[0].device_kind),
        "window_s": counters["window_s"],
        "n_cells": engine.batcher.n_cells,
        "stats0": stats0, "stats1": stats1,
        "calls": [cl for cl in calls.calls if w0 <= cl[0] < w1],
        "weight_bytes": common.DTYPE_BYTES[c["weight_dtype"]],
        "cache_bytes": common.DTYPE_BYTES[c["cache_dtype"]],
    }
    params = setup.params
    engine.cache = None
    del engine, calls, setup
    gc.collect()
    return {"requests": reqs, "counters": counters, "device": dev,
            "record": rec, "params": params}


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def metrics(out: dict, spec: dict) -> tuple:
    """(metrics, attempted, failed, counts) of a serving run."""
    reqs = out["requests"]
    cnt = out["counters"]
    failed = [r for r in reqs if not r.done or len(r.tokens) != r.max_new]
    ttft, itl = [], []
    for r in reqs:
        if r.emit_t:
            ttft.append(r.emit_t[0] - r.due)
            itl.extend(np.diff(r.emit_t).tolist())
    vals = {
        "ttft_p90_ms": 1e3 * common.percentile(ttft, 90) if ttft else None,
        "itl_p95_ms": 1e3 * common.percentile(itl, 95) if itl else None,
        "serve_tok_s": (cnt["prompt_tokens"] + cnt["output_tokens"])
        / cnt["window_s"],
    }
    counts = {"requests": len(reqs), "failed": len(failed),
              "ttft_samples": len(ttft), "itl_samples": len(itl),
              "ttft_p50_ms": 1e3 * common.percentile(ttft, 50)
              if ttft else None,
              "itl_p50_ms": 1e3 * common.percentile(itl, 50) if itl else None,
              "window_prompt_tokens": cnt["prompt_tokens"],
              "window_output_tokens": cnt["output_tokens"],
              "window_s": cnt["window_s"],
              "compiles_in_run": cnt["compiles_in_run"]}
    for k in ("late_p50_ms", "late_max_ms"):
        if k in cnt:
            counts["arrivals_" + k] = cnt[k]
    return vals, len(reqs), len(failed), counts


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample(reqs: list, n: int, seed: int) -> list:
    """The longest served request and n-1 others drawn from the seed."""
    ok = [r for r in reqs if r.done and len(r.tokens) == r.max_new]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in ok if r is not longest]
    rng = common.np_rng(seed, 3)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def teacher_batch(picked: list, max_seq: int):
    """Token rows (prompt ++ served tokens but the last) and, per served
    token, the (row, position) whose logits predicted it."""
    toks = np.zeros((len(picked), max_seq), np.int32)
    rows, cols, served = [], [], []
    for i, r in enumerate(picked):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        p = len(r.prompt)
        for j, tok in enumerate(r.tokens):
            rows.append(i)
            cols.append(p - 1 + j)
            served.append(tok)
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(served)


def logit_gap(ref_logits, chosen) -> float:
    """Widest gap by which a chosen token's reference logit lies below the
    reference's best at that position."""
    import jax.numpy as jnp
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[:, None],
                              axis=-1)[:, 0]
    return float(jnp.max(best - got))


def compare(out: dict, spec: dict, seed: int, control: bool = False) -> dict:
    """The run's checks: every request of the window finished with its whole
    budget of in-vocabulary tokens, and the widest logit gap of a sample of
    served tokens against the plain reference is within the limit.
    ``control`` also reads the gap of the tokens that the reference in
    float8 puts first (the control), at the same positions."""
    c, t = spec["config"], spec["traffic"]
    chk = t["check"]
    reqs = out["requests"]
    bad = [r.rid for r in reqs if not r.done or len(r.tokens) != r.max_new
           or any(not 0 <= x < c["vocab_size"] for x in r.tokens)]
    res = {"unfinished_or_malformed": common.check(len(bad), 0, not bad)}
    picked = sample(reqs, chk["requests"], seed)
    max_seq = t["max_seq"]
    if not picked:
        res["logit_gap"] = common.check("no request to compare",
                                        chk["max_logit_gap"], False)
        return res
    toks, rows, cols, served = teacher_batch(picked, max_seq)
    ref = common.config_reference(spec["workload"]["config"])
    want = ref.logits(c, out["params"], toks, rows, cols, precision="fp32")
    gap = logit_gap(want, served)
    res["logit_gap"] = common.check(gap, chk["max_logit_gap"],
                                    gap <= chk["max_logit_gap"])
    res["compared_tokens"] = common.check(int(len(served)),
                                          chk["min_tokens"],
                                          len(served) >= chk["min_tokens"])
    if control:
        low = ref.logits(c, out["params"], toks, rows, cols, precision="fp8")
        import jax.numpy as jnp
        cgap = logit_gap(want, np.asarray(jnp.argmax(low, axis=-1)))
        res["control_logit_gap"] = common.check(
            cgap, chk["max_logit_gap"], cgap <= chk["max_logit_gap"])
    return res

