"""The program's own spans and the gang step's parts, on the chip.

    python bench/program_trace.py --workload bert-large.k2-gang \
        --seeds 7,2147487402 [--trace-seconds 10] [--cost-seconds 20] \
        [--out program_trace.json]

Builds the cell's gang as the benchmark does (``train_driver.build``) and
wraps its step in the program's ``step.dispatch`` span
(``hydra.with_dispatch_span``) with a ``repro.obs.Tracer``, which also
records the XLA compiles. For each seed it gives the gang the seed's
weights, runs the benchmark's set-up steps, then a window of the cell's
``run_seconds`` with the benchmark's own loop and a traced
``TrainBatches`` (``data.batch`` spans). The first seed's window runs
under the profiler for its first ``--trace-seconds`` (stopping it stalls
the loop for tens of seconds, so what follows is not a steady window).
Each window reports its steps and rate; every step that took ``SLOW_S``
over the median, with the program spans inside it (and, traced, the
device's busy time there); and, traced, the device time per step by the
step's part (``phase_time``), the median ``data.batch`` and
``step.dispatch`` spans and the busy time as ``trace_reduce`` reads it.
The set-up reports its XLA compiles by program, the seconds JAX spent
tracing and lowering the train step, and each set-up step's
``step.dispatch``. Windows of ``--cost-seconds`` then alternate between
the bare step and the wrapped one, ``COST_RUNS`` each, with the profiler
off: the tracer's cost.

The chip's trace gives an operation's HLO name (``fusion.180``) but not
its name stack, so that comes from the step's compiled text. Device time
is counted as ``trace_reduce`` counts it for the breakdown: in leaf
operations (those that hold no other).

The reductions (``phase_of``, ``hlo_op_names``, ``phase_time``,
``spans_in``) are what a benchmark reader of these parts would reuse.
The rest (``_loop``, ``_window``, ``_traced``, the cost windows) stands
in for the benchmark's own traced run until ``train_driver`` passes a
``Tracer`` in traced runs and ``trace_reduce`` keeps the program's spans;
it is to be deleted then, not kept as a second harness. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import numpy as np  # noqa: E402

import common  # noqa: E402
import trace_reduce  # noqa: E402
from common import now  # noqa: E402

PROGRAM_SPANS = ("build.", "data.", "step.", "gang", "rung", "obs.clock")
PARTS = ("fwd", "bwd", "opt", "none")
SLOW_S = 0.3  # a step this much over the median is reported as slow
COST_RUNS = 3  # windows with the tracer off, and as many with it on
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*'
                      r'op_name="([^"]*)"')


def phase_of(op_name: str) -> str:
    """The step's part an operation belongs to, from its name stack:
    under a ``transpose(...)`` the backward pass (remat's recompute
    included), else under ``forward`` the forward pass, else under
    ``optimizer`` or ``grad_reduce`` the update; else none."""
    parts = op_name.split("/")
    if any(p.startswith("transpose(") for p in parts):
        return "bwd"
    if any(p == "forward" or p.endswith("(forward)") for p in parts):
        return "fwd"
    if "optimizer" in parts or "grad_reduce" in parts:
        return "opt"
    return "none"


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} for every instruction of a
    compiled module's text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def read_program_trace(path: str) -> dict:
    """What ``trace_reduce.simplify`` keeps, plus the program's spans
    among the host events: [name, start_ns, dur_ns, stats]."""
    from jax.profiler import ProfileData
    tr = trace_reduce.simplify(path)
    tr["spans"] = [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(PROGRAM_SPANS)]
    return tr


def leaf_time(tr: dict, w0: float, w1: float, chip: int = 0) -> np.ndarray:
    """Device time (ns) of one chip inside [w0, w1) in leaf operations, by
    name id, as ``trace_reduce`` sums it for its breakdown."""
    nid, st, du = (np.asarray(x, np.float64) for x in tr["devices"][chip])
    t = trace_reduce._leaf_time(nid.astype(np.int64), st, st + du, w0, w1)
    return np.pad(t, (0, len(tr["names"]) - len(t)))


def phase_time(tr: dict, op_names: dict, w0: float, w1: float,
               chip: int = 0) -> dict:
    """Device time (ns) of one chip inside [w0, w1) by the step's part:
    leaf operations by their name stack (``op_names``, HLO name -> stack);
    ``outer`` the busy time in which only an operation that holds others
    runs (a loop between its iterations), so that the parts and ``outer``
    sum to the busy time; ``remat`` the part of ``bwd`` under
    ``rematted_computation``."""
    out = dict.fromkeys(PARTS + ("outer", "remat"), 0.0)
    for name, ns in zip(tr["names"], leaf_time(tr, w0, w1, chip)):
        stack = op_names.get(name, "")
        part = phase_of(stack)
        out[part] += float(ns)
        if part == "bwd" and "rematted_computation" in stack:
            out["remat"] += float(ns)
    nid, st, du = (np.asarray(x, np.float64) for x in tr["devices"][chip])
    busy, _, _ = trace_reduce._busy_and_gaps(st, st + du, w0, w1)
    out["outer"] = busy - sum(out[p] for p in PARTS)
    return out


def spans_in(tr: dict, name: str, w0: float, w1: float) -> list:
    """Durations (ns) of the trace's program spans called ``name`` that
    start inside [w0, w1)."""
    return [d for n, s, d, _ in tr["spans"] if n == name and w0 <= s < w1]


def window(tr: dict):
    wins = [h for h in tr["host"] if h[0] == trace_reduce.WINDOW]
    _, w0, wd = max(wins, key=lambda h: h[2])
    return w0, w0 + wd


# ---------------------------------------------------------------------------
# on the chip
# ---------------------------------------------------------------------------


def _loop(gang, data, p, o, step, seconds, prof_s, trace_dir, tracer):
    """The benchmark's window (``train_driver.run``'s loop), with the
    profiler over its first ``prof_s`` seconds. Returns the state, the next
    step, the window's start, its steps' end times and the traced end."""
    import jax
    import train_driver
    from serve_driver import _start_trace, _stop_trace
    prof = None
    if prof_s > 0:
        prof = _start_trace(trace_dir)
        tracer.anchor()
    w0 = now()
    w1 = w0 + seconds
    pending, done_t, traced_end = None, [], None
    while True:
        p, o, m, _ = train_driver.one_step(gang, data, p, o, step)
        step += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench.wait"):
                np.asarray(pending["loss"])
            done_t.append(now())
        pending = m
        if prof is not None and now() >= w0 + prof_s:
            traced_end = now()
            prof = _stop_trace(prof)
        if done_t and done_t[-1] >= w1:
            break
    jax.block_until_ready((p, o))
    if prof is not None:
        traced_end = now()
        _stop_trace(prof)
    return p, o, step, w0, done_t, traced_end


def _compiles(tracer, since: int) -> list:
    return [[e["program"], round(e["seconds"], 3), e["cache_hit"], e["span"]]
            for e in tracer.events[since:] if e["ev"] == "xla_compile"]


def _spans(tracer, since: int, name: str) -> list:
    """(begin wall, end wall) of the tracer's spans called ``name``."""
    ev = tracer.events[since:]
    begin = {e["id"]: e["wall"] for e in ev
             if e["ev"] == "span_begin" and e["name"] == name}
    return [(begin[e["id"]], e["wall"]) for e in ev
            if e["ev"] == "span_end" and e["id"] in begin]


def measure(args, spec, devices, seconds: float) -> dict:
    import gc

    import jax
    import train_driver
    import weights
    from repro.core.hydra import with_dispatch_span
    from repro.data.pipeline import TrainBatches
    from repro.obs import Tracer
    c, t = spec["config"], spec["traffic"]
    staged = []  # JAX's tracing and lowering of the train step

    def on_stage(event, secs, fun_name="", **_):
        if "train_step" in fun_name and event in (
                "/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            staged.append([event.rsplit("/", 1)[1], fun_name,
                           round(secs, 3)])

    jax.monitoring.register_event_duration_secs_listener(on_stage)
    tracer = Tracer()
    t0 = now()
    bare = train_driver.build(spec, devices)
    traced = dict(bare, step_fn=with_dispatch_span(bare["step_fn"], tracer))
    eng = bare["eng"]
    tokens_per_step = (eng.n_trials * eng.n_microbatches * eng.microbatch
                       * t["seq_len"])
    out = {"device": common.device_info(devices), "build_s": now() - t0,
           "windows": [], "cost": []}
    p = o = None
    step = 0
    for i, seed in enumerate(args.seeds):
        del p, o
        gc.collect()
        mark = len(tracer.events)
        staged.clear()
        p = weights.make(bare["struct"], bare["shard"], seed,
                         c["vocab_size"])
        o = bare["runner"].optimizer.init(p)
        data = TrainBatches(bare["cfg"], eng, t["seq_len"], seed=seed,
                            tracer=tracer)
        setup_t = now()
        for step in range(train_driver.SETUP_STEPS):
            p, o, m, _ = train_driver.one_step(traced, data, p, o, step)
            np.asarray(m["loss"])
        res = {"seed": seed, "setup_s": now() - setup_t,
               "setup_compiles": _compiles(tracer, mark),
               "setup_staging": list(staged),
               "setup_dispatch_s": [round(b - a, 6) for a, b in
                                    _spans(tracer, mark, "step.dispatch")]}
        mark = len(tracer.events)
        prof_s = args.trace_seconds if i == 0 else 0.0
        trace_dir = tempfile.mkdtemp(prefix="program_trace_")
        try:
            p, o, step, w0, done_t, traced_end = _loop(
                traced, data, p, o, train_driver.SETUP_STEPS, seconds,
                prof_s, trace_dir, tracer)
            res.update(_window(seconds, tracer, mark, w0, done_t,
                               tokens_per_step))
            if prof_s > 0:
                try:
                    _traced(res, trace_dir, tracer, done_t, traced_end,
                            bare, p, o, data, len(devices))
                except Exception:  # keep the window's other numbers
                    import traceback
                    res["trace_error"] = traceback.format_exc()
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
            data.close()
        res["window_compiles"] = _compiles(tracer, mark)
        common.log(json.dumps(res))
        out["windows"].append(res)
        _save(args, out)
    if args.cost_seconds > 0:
        seed = args.seeds[-1]
        off = TrainBatches(bare["cfg"], eng, t["seq_len"], seed=seed)
        on = TrainBatches(bare["cfg"], eng, t["seq_len"], seed=seed,
                          tracer=tracer)
        try:
            for k in range(2 * COST_RUNS):
                gang, data = (bare, off) if k % 2 == 0 else (traced, on)
                p, o, step, w0, done_t, _ = _loop(
                    gang, data, p, o, step, args.cost_seconds, 0, None,
                    tracer)
                in_win = [x for x in done_t if x < w0 + args.cost_seconds]
                rate = len(in_win) * tokens_per_step / (in_win[-1] - w0)
                out["cost"].append(["off" if k % 2 == 0 else "on", rate])
                common.log(f"cost {out['cost'][-1]}")
                _save(args, out)
        finally:
            off.close()
            on.close()
    return out


def _window(seconds, tracer, mark, w0, done_t, tokens_per_step) -> dict:
    """A window's steps and rate, as ``train_driver`` counts them, and its
    slow steps with the program's spans inside each (seconds)."""
    in_win = [x for x in done_t if x < w0 + seconds]
    steps = np.diff([w0] + done_t)
    med = float(np.median(steps))
    spans = [(n, a, b) for n in ("data.batch", "step.dispatch")
             for a, b in _spans(tracer, mark, n)]
    wall0 = tracer._t0 / 1e9  # the tracer's wall 0 on the perf_counter
    slow = []
    for k, dt in enumerate(steps):
        if dt <= med + SLOW_S:
            continue
        a, b = done_t[k] - dt - wall0, done_t[k] - wall0
        inside = {}
        for n, s, e in spans:
            if e > a and s < b:
                inside.setdefault(n, []).append(round(min(e, b)
                                                      - max(s, a), 6))
        slow.append({"step": k, "s": float(dt), "spans": inside})
    return {"steps": len(in_win),
            "train_tok_s": len(in_win) * tokens_per_step
            / (in_win[-1] - w0),
            "step_s_median": med, "step_s_max": float(steps.max()),
            "step_s": [round(float(x), 4) for x in steps], "slow": slow}


def _traced(res, trace_dir, tracer, done_t, traced_end, gang, p, o, data,
            n_chips) -> None:
    """A traced window's numbers (see the module docstring)."""
    import jax.numpy as jnp
    tr = read_program_trace(trace_reduce.find_trace(trace_dir))
    red = trace_reduce.reduce(tr, n_chips)
    n = len([x for x in done_t if x < traced_end])
    tw0, tw1 = window(tr)
    # the name stacks of the steady step's operations, from the compiled
    # text of the same program (lowered after the window, with no span
    # open, so its compile is counted nowhere)
    hlo = gang["step_fn"].lower(p, o, data._batch(0), gang["hparams"],
                                jnp.asarray(0, jnp.int32)).compile()
    names = hlo_op_names(hlo.as_text())
    parts = phase_time(tr, names, tw0, tw1)
    res.update(traced_steps=n, busy_s=red["busy_s"],
               window_s=red["window_s"],
               busy_ms_per_step=red["busy_s"] * 1e3 / n,
               parts_ms_per_step={k: v / 1e6 / n for k, v in parts.items()})
    t = leaf_time(tr, tw0, tw1)
    res["top_ops"] = [[tr["names"][i], float(t[i]) / 1e6 / n,
                       phase_of(names.get(tr["names"][i], "")),
                       names.get(tr["names"][i], "")[-120:]]
                      for i in np.argsort(-t, kind="stable")[:24]]
    for name in ("data.batch", "step.dispatch"):
        d = spans_in(tr, name, tw0, tw1)
        if d:
            res[f"{name}_ms_median"] = float(np.median(d)) / 1e6
            res[f"{name}_n"] = len(d)
    # the device's busy time inside each slow step that was traced, placed
    # on the trace's clock through the tracer's anchor
    clock = next(([s, st_["wall"]] for nm, s, _, st_ in tr["spans"]
                  if nm == "obs.clock"), None)
    wall0 = tracer._t0 / 1e9
    _, st, du = (np.asarray(x, np.float64) for x in tr["devices"][0])
    for s in res["slow"]:
        end = done_t[s["step"]]
        if clock is None or end > traced_end:
            continue
        a_ns, b_ns = (clock[0] + (x - wall0 - clock[1]) * 1e9
                      for x in (end - s["s"], end))
        busy, lo, hi = trace_reduce._busy_and_gaps(st, st + du, a_ns, b_ns)
        s["device_busy_s"] = busy / 1e9
        s["longest_gap_s"] = float(np.max(hi - lo)) / 1e9 if len(lo) else 0


def _save(args, out: dict) -> None:
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--trace-seconds", type=float, default=10.0)
    ap.add_argument("--cost-seconds", type=float, default=0.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    spec = common.cell_spec(args.workload)
    common.enable_cache(jax)
    try:
        devices = common.require_chips(jax, spec["workload"]["chips"])
    except common.NoChip as e:
        common.log(f"program_trace: {e}")
        return 3
    res = measure(args, spec, devices, common.manifest()["run_seconds"])
    _save(args, res)
    print(json.dumps({k: v for k, v in res.items() if k != "windows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
