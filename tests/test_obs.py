"""Observability layer: bounded reservoirs, the metric registry behind
``ServeStats``, tracer on/off semantics, spans and their place on the
profiler's clock, XLA compile events, JSONL + Perfetto export
round-trips, the span validator, a traced-vs-untraced engine parity
check (tracing must never change the schedule or the tokens), and the
same for the gang's train path (scopes and spans change no instruction
and no loss).

(Multi-device setup comes from tests/conftest.py — pytest-only module.)"""
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import tracemalloc  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.core import pipeline as pl  # noqa: E402
from repro.core.hydra import (HydraConfig, HydraRunner,  # noqa: E402
                              run_model_selection)
from repro.core.partitioner import plan_stages  # noqa: E402
from repro.core import scheduler as sched  # noqa: E402
from repro.core.scheduler import GangPlan  # noqa: E402
from repro.core.trials import grid_search  # noqa: E402
from repro.data.pipeline import TrainBatches  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models.layers import ModelOptions  # noqa: E402
from repro.obs import (NULL_TRACER, TraceInvariantError,  # noqa: E402
                       Tracer, read_events, resolve, to_chrome_trace,
                       validate_spans, write_events, write_metrics,
                       write_perfetto)
from repro.obs.metrics import (DEFAULT_RESERVOIR_CAP, MetricRegistry,
                               Reservoir)  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.serve.engine import ServeStats  # noqa: E402


# ---------------------------------------------------------------- metrics --

def test_reservoir_exact_below_cap():
    r = Reservoir("x", cap=100)
    for v in [3.0, 1.0, 2.0]:
        r.append(v)
    assert len(r) == 3 and list(r) == [3.0, 1.0, 2.0]
    assert r.mean_value == 2.0 and r.min_value == 1.0 and r.max_value == 3.0
    assert float(np.mean(r)) == 2.0  # numpy protocol goes via __array__
    assert r.percentile(50) == 2.0
    snap = r.snapshot()
    assert snap["count"] == 3 and snap["sum"] == 6.0
    assert {"min", "max", "mean", "p50", "p95", "p99"} <= set(snap)


def test_reservoir_bounded_above_cap_with_exact_aggregates():
    r = Reservoir("y", cap=64)
    n = 10_000
    for v in range(n):
        r.append(float(v))
    # the sample buffer is bounded; count/sum/min/max stay exact
    assert len(r) == n and len(r.samples) == 64
    assert r.min_value == 0.0 and r.max_value == float(n - 1)
    assert r.snapshot()["sum"] == float(n * (n - 1) // 2)
    # sampled percentiles land inside the true support
    assert 0.0 <= r.percentile(50) <= float(n - 1)


def test_reservoir_deterministic_per_name():
    a, b = Reservoir("det", cap=8), Reservoir("det", cap=8)
    for v in range(1000):
        a.append(float(v))
        b.append(float(v))
    assert list(a) == list(b)  # seeded by name, not global RNG state


def test_registry_idempotent_and_typed():
    reg = MetricRegistry()
    c = reg.counter("ticks")
    assert reg.counter("ticks") is c
    c.value += 3
    assert reg.value("ticks") == 3
    reg.gauge("wall_s")
    reg.set_value("wall_s", 1.5)
    assert reg.value("wall_s") == 1.5
    h = reg.histogram("ttft", cap=4)
    h.append(2.0)
    with pytest.raises(TypeError):
        reg.set_value("ttft", [1.0])  # histograms append, never assign
    snap = reg.snapshot()
    assert snap["ticks"] == 3 and snap["ttft"]["count"] == 1


def test_servestats_facade_routes_through_registry():
    s = ServeStats()
    s.ticks += 4
    s.tokens_generated += 10
    s.wall_s = 2.0
    s.ttft_samples.append(1.0)
    s.ttft_samples.append(3.0)
    s.tpot_samples.append(0.5)
    s.block_usage_samples.append(7)
    assert s.registry.value("ticks") == 4
    assert s.ticks == 4 and s.wall_s == 2.0
    summ = s.summary()
    assert summ["tokens_generated"] == 10
    assert summ["ttft_p50"] == 2.0
    assert "ttft_p99" in summ and "tpot_p99" in summ
    assert summ["peak_blocks_in_use"] == 7
    assert s.ttft_samples.cap == DEFAULT_RESERVOIR_CAP
    with pytest.raises(AttributeError):
        s.not_a_metric  # noqa: B018


# ----------------------------------------------------------------- tracer --

def test_disabled_tracer_emits_nothing():
    for tr in (NULL_TRACER, resolve(None)):
        assert not tr.enabled
        tr.begin_tick(3)
        tr.emit("x", a=1)
        tr.req("admit", 0, k=0)
        tr.round(modes=["decode"])
        with tr.span("gang"):
            tr.anchor()
        assert len(tr.events) == 0 and len(tr) == 0


def test_tracer_stamps_tick_and_wall():
    tr = Tracer()
    assert resolve(tr) is tr
    tr.begin_tick(5)
    tr.req("admit", 7, k=0, m=1, b=0)
    tr.round(modes=["decode"], occupied=1)
    [admit, rnd] = tr.events
    assert admit["ev"] == "admit" and admit["rid"] == 7
    assert admit["tick"] == 5 and admit["wall"] >= 0.0
    assert rnd["ev"] == "round" and rnd["modes"] == ["decode"]
    tr.clear()
    assert len(tr) == 0


def test_span_nesting_and_parent_ids():
    tr = Tracer()
    with tr.span("gang", arch="a") as gang:
        with tr.span("data.batch"):
            pass
        with tr.span("step.dispatch") as dispatch:
            with tr.span("inner"):
                pass
    with pytest.raises(ValueError):
        with tr.span("failed"):
            raise ValueError("the span still closes")
    begins = [e for e in tr.events if e["ev"] == "span_begin"]
    ends = [e for e in tr.events if e["ev"] == "span_end"]
    assert [(e["name"], e["parent"]) for e in begins] == [
        ("gang", None), ("data.batch", gang), ("step.dispatch", gang),
        ("inner", dispatch), ("failed", None)]
    assert len({e["id"] for e in begins}) == 5
    assert begins[0]["arch"] == "a" and "arch" not in begins[1]
    assert [e["name"] for e in ends] == ["data.batch", "inner",
                                         "step.dispatch", "gang", "failed"]
    end_of = {e["id"]: e["wall"] for e in ends}
    assert all(e["wall"] <= end_of[e["id"]] for e in begins)
    # Perfetto: one track per nesting depth
    recs = to_chrome_trace(tr.events)["traceEvents"]
    tid = {r["name"]: r["tid"] for r in recs if r["ph"] == "X"}
    assert (tid["gang a"], tid["data.batch"], tid["inner"]) == (0, 1, 2)


def test_null_tracer_span_allocates_nothing():
    span = NULL_TRACER.span
    assert span("data.batch") is span("step.dispatch")

    def calls(n):
        for _ in range(n):
            with span("data.batch"):
                pass

    calls(100)
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        calls(10_000)
        after, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # nothing survives a call, and nothing piles up across 10,000 of them
    assert after <= before and peak - before < 1024


def test_clock_anchor_places_a_span_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    with tr.span("obs.test.before"):  # stamped before the profiler ran
        time.sleep(0.002)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.anchor()
        time.sleep(0.05)
        with tr.span("obs.test.span"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs."):
                    found[e.name] = e
    assert "obs.test.before" not in found
    clock = found["obs.clock"]
    begin = next(e for e in tr.events if e["ev"] == "span_begin"
                 and e["name"] == "obs.test.span")
    anchor_wall = next(v for k, v in clock.stats if k == "wall")
    placed = clock.start_ns + (begin["wall"] - anchor_wall) * 1e9
    assert abs(placed - found["obs.test.span"].start_ns) < 1e6


# ------------------------------------------------------ the gang's train path


def _tiny_gang(tracer=None, n_stages=1):
    cfg = get_config("bert-large").reduced()
    eng = pl.EngineConfig(n_trials=2, n_microbatches=2, microbatch=2,
                          n_stages=n_stages, data_size=1)
    trials = tuple(grid_search(cfg.name, [1e-3, 3e-4], (0.01,)))
    runner = HydraRunner(cfg, ModelOptions(remat=True),
                         make_test_mesh(1, n_stages),
                         HydraConfig(seq_len=16, steps=2), tracer=tracer)
    return runner, GangPlan(cfg.name, trials, eng)


def _setup_losses(tracer, steps=3):
    runner, gang = _tiny_gang(tracer)
    p, o, hp, step_fn = runner._build(gang)
    data = TrainBatches(runner.cfg, gang.engine, 16, seed=3, tracer=tracer)
    losses = []
    try:
        for t in range(steps):
            p, o, m = step_fn(p, o, data.batch_for_step(t), hp,
                              jnp.asarray(t, jnp.int32))
            losses.append(np.asarray(m["loss"]))
    finally:
        data.close()
    return np.stack(losses)


def test_gang_losses_identical_with_tracer_on_and_off():
    off = _setup_losses(None)
    tr = Tracer()
    on = _setup_losses(tr)
    assert np.array_equal(on, off)
    begins = [e for e in tr.events if e["ev"] == "span_begin"]
    assert [e["name"] for e in begins] == (
        ["build.params", "build.optimizer", "build.step"]
        + ["data.batch", "step.dispatch"] * 3)
    assert all(e["parent"] is None for e in begins)
    # the off run compiled everything else; this one makes its own step
    compiles = [e for e in tr.events if e["ev"] == "xla_compile"]
    assert {e["program"] for e in compiles} == {"jit(train_step)"}
    assert all(e["span"] == "step.dispatch" and e["seconds"] > 0
               and e["cache_hit"] is False for e in compiles)


@pytest.mark.parametrize("hbm,stash,kept", [
    (sched.HBM_BYTES_PER_CHIP, "layers", 4), (0, "ticks", 0)])
def test_build_step_span_names_the_remat_stash(monkeypatch, hbm, stash,
                                               kept):
    monkeypatch.setattr(sched, "HBM_BYTES_PER_CHIP", hbm)
    tr = Tracer()
    runner, gang = _tiny_gang(tr)
    runner._build(gang)
    begin = next(e for e in tr.events if e["ev"] == "span_begin"
                 and e["name"] == "build.step")
    assert (begin["stash"], begin["stash_layers"]) == (stash, kept)
    assert begin["stash_bytes"] == sched.stash_bytes(
        runner.cfg, gang.engine, 16, kept, act_bytes=4) > 0


def test_model_selection_spans_nest_rung_gang_build():
    runner, gang = _tiny_gang()
    tr = Tracer()
    out = run_model_selection(runner.cfg, runner.opts, runner.mesh,
                              HydraConfig(seq_len=16, steps=1),
                              gang.trials, gang.engine, tracer=tr)
    assert len(out["all"]) == 2
    begins = {e["id"]: e for e in tr.events if e["ev"] == "span_begin"}

    def path(e):
        return (path(begins[e["parent"]]) + "/" if e["parent"] is not None
                else "") + e["name"]

    paths = {path(e) for e in begins.values()}
    assert {"rung", "rung/gang", "rung/gang/build.params",
            "rung/gang/data.batch", "rung/gang/step.dispatch"} <= paths
    programs = {e["program"] for e in tr.events if e["ev"] == "xla_compile"}
    assert {"jit(train_step)", "jit(eval_loss)"} <= programs


@contextlib.contextmanager
def _persistent_cache(path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = {"jax_compilation_cache_dir": str(path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_xla_compile_names_train_step_and_marks_a_cache_hit(tmp_path):
    runner, gang = _tiny_gang()
    p, o, hp, step_fn = runner._build(gang)
    data = TrainBatches(runner.cfg, gang.engine, 16, seed=3)
    batch = data.batch_for_step(0)
    data.close()
    args = (hp, jnp.asarray(0, jnp.int32))
    lowered = step_fn.lower(p, o, batch, *args)
    tr = Tracer()
    with _persistent_cache(tmp_path):
        with tr.span("first"):
            lowered.compile()
        jax.clear_caches()  # the next compile has to find it on disk
        with tr.span("again"):
            step_fn.lower(p, o, batch, *args).compile()
    [first, again] = [e for e in tr.events if e["ev"] == "xla_compile"]
    assert first["program"] == again["program"] == "jit(train_step)"
    assert (first["span"], first["cache_hit"]) == ("first", False)
    assert (again["span"], again["cache_hit"]) == ("again", True)
    # no span open: nothing is recorded
    n = len(tr)
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    assert len(tr) == n


def _instructions(hlo: str) -> list:
    """A compiled module's instructions without their metadata, leaving
    out the module's name and its source-location tables."""
    out = []
    for line in hlo.splitlines():
        line = line.strip()
        if line.startswith(("%", "ROOT %", "ENTRY")):
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return out


def test_train_step_scopes_change_no_instruction(monkeypatch):
    runner, gang = _tiny_gang(n_stages=2)
    p, o, hp, _ = runner._build(gang)
    eng = gang.engine
    batch = {k: np.zeros((2, 2, 2, 16), np.int32) for k in ("tokens",
                                                              "labels")}

    def compiled():
        fn = pl.make_train_step(runner.cfg, runner.opts, eng, runner.mesh,
                                runner.optimizer)
        return fn.lower(p, o, batch, hp,
                        jnp.asarray(0, jnp.int32)).compile().as_text()

    scoped = compiled()
    names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in ("/jvp(forward)/", "/transpose(jvp(forward))/",
                  "/grad_reduce/", "/optimizer/", "/rematted_computation/"):
        assert any(scope in n for n in names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "forward" not in bare
    assert _instructions(scoped) == _instructions(bare)
    assert len(_instructions(scoped)) > 1000


# ----------------------------------------------------------------- export --

def _lifecycle_events():
    tr = Tracer()
    tr.begin_tick(0)
    tr.req("enqueue", 1, arch=0, plen=8)
    tr.begin_tick(1)
    tr.req("admit", 1, k=0, m=0, b=0, plen=8)
    tr.req("prefill_chunk", 1, k=0, m=0, b=0, qlen=4, pos=0)
    tr.round(modes=["append:4"], occupied=1, occupancy=1.0, queues=[0],
             pool_blocks=2, host_depth=[1], inflight=0)
    tr.begin_tick(2)
    tr.req("first_token", 1, k=0, m=0, b=0)
    tr.begin_tick(3)
    tr.req("swap_out", 1, blocks=2)
    tr.req("retract", 1, via="swap", pos=9)
    tr.begin_tick(4)
    tr.req("restore", 1, k=0, m=0, b=0, via="swap")
    tr.begin_tick(5)
    tr.req("complete", 1, tokens=3, ttft=1.0)
    tr.compile("decode", qlen=1, table_width=0)
    with tr.span("gang", arch="a", n_trials=2, steps=4):
        tr.emit("xla_compile", program="jit(train_step)", seconds=0.25,
                cache_hit=False, span="gang")
    return tr.events


def test_jsonl_round_trip(tmp_path):
    events = _lifecycle_events()
    path = str(tmp_path / "events.jsonl")
    assert write_events(events, path) == len(events)
    assert read_events(path) == events


def test_metrics_jsonl(tmp_path):
    s = ServeStats()
    s.ticks += 2
    s.ttft_samples.append(1.0)
    path = str(tmp_path / "metrics.jsonl")
    n = write_metrics(s.snapshot(), path)
    recs = [json.loads(x) for x in open(path)]
    assert len(recs) == n
    by_name = {r["metric"]: r for r in recs}
    assert by_name["ticks"]["value"] == 2
    assert by_name["ttft_samples"]["hist"]["count"] == 1


def test_perfetto_trace_structure(tmp_path):
    trace = to_chrome_trace(_lifecycle_events())
    recs = trace["traceEvents"]
    names = [r["name"] for r in recs]
    # one residency slice per (admit|restore)->(retract|complete) interval
    res = [r for r in recs if r["ph"] == "X" and r["name"] == "req 1"]
    assert len(res) == 2
    assert {r["args"]["closed_by"] for r in res} == {"retract", "complete"}
    assert any(r["ph"] == "X" and r["name"].startswith("prefill q4")
               for r in recs)
    for counter in ("device blocks in use", "host tier p0", "arch 0 queue",
                    "occupied cells", "transfer in-flight"):
        assert counter in names
    assert any(r["ph"] == "i" and r["name"] == "first_token" for r in recs)
    assert any(r["name"] == "compile decode" for r in recs)
    gang = [r for r in recs if r["ph"] == "X" and r["name"] == "gang a"]
    assert len(gang) == 1 and gang[0]["dur"] >= 1
    [xla] = [r for r in recs if r["name"] == "xla jit(train_step)"]
    assert xla["ph"] == "X" and xla["dur"] == 250_000
    path = str(tmp_path / "t.json")
    assert write_perfetto(_lifecycle_events(), path) == len(recs)
    assert json.load(open(path))["traceEvents"]


def test_perfetto_closes_truncated_residency():
    keep = ("enqueue", "admit", "prefill_chunk", "first_token")
    events = [e for e in _lifecycle_events() if e["ev"] in keep]
    res = [r for r in to_chrome_trace(events)["traceEvents"]
           if r["ph"] == "X" and r["name"] == "req 1"]
    assert len(res) == 1 and res[0]["args"]["closed_by"] == "open"


# -------------------------------------------------------------- validator --

def test_validator_accepts_legal_lifecycle():
    rep = validate_spans(_lifecycle_events())
    assert rep == {"requests": 1, "completed": 1, "retracted_terminal": 0,
                   "violations": 0}


def _drop(events, name):
    return [e for e in events if e["ev"] != name]


@pytest.mark.parametrize("mutate,needle", [
    (lambda evs: _drop(evs, "enqueue"), "'admit' before 'enqueue'"),
    # in-flight events only while resident: queued rid prefilling is illegal
    (lambda evs: _drop(evs, "admit"), "expected 'running'"),
    (lambda evs: _drop(evs, "swap_out"), "without a preceding 'swap_out'"),
    (lambda evs: _drop(evs, "restore"), "state 'retracted'"),
    (lambda evs: evs + [dict(next(e for e in evs if e["ev"] == "complete"),
                             tick=0)], "backwards"),
])
def test_validator_rejects_illegal_traces(mutate, needle):
    with pytest.raises(TraceInvariantError) as err:
        validate_spans(mutate(_lifecycle_events()))
    assert needle in str(err.value).lower()


def test_validator_open_requests_need_allow_open():
    events = _drop(_lifecycle_events(), "complete")
    with pytest.raises(TraceInvariantError):
        validate_spans(events)
    rep = validate_spans(events, allow_open=True)
    assert rep["requests"] == 1 and rep["completed"] == 0


def test_validator_property_interleavings():
    hyp = pytest.importorskip(
        "hypothesis",
        reason="property tests need the optional hypothesis package")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=60, deadline=None)
    @hyp.given(plans=st.lists(
        st.tuples(st.integers(0, 3),           # retract/restore cycles
                  st.booleans(),               # ends retracted (truncated)
                  st.sampled_from(["swap", "recompute", "requeue"])),
        min_size=1, max_size=6),
        seed=st.integers(0, 2**16))
    def run(plans, seed):
        # interleave legal per-request lifecycles across shuffled rounds:
        # any schedule the engine could emit must satisfy the validator
        rng = np.random.default_rng(seed)
        tr = Tracer()
        script = []  # (rid, step) in per-request order
        for rid, (cycles, trunc, via) in enumerate(plans):
            steps = [("enqueue", {}), ("admit", {"k": 0, "m": 0, "b": rid})]
            for _ in range(cycles):
                if via == "swap":
                    steps.append(("swap_out", {"blocks": 1}))
                steps.append(("retract", {"via": via}))
                steps.append(("restore", {"via": via, "b": rid}))
            if trunc and cycles:
                steps = steps[:-1]  # ends retracted — terminal is legal
            else:
                steps.append(("complete", {"tokens": 1}))
            script.append([(rid, s) for s in steps])
        tick = 0
        while any(script):
            live = [q for q in script if q]
            order = rng.permutation(len(live))
            tr.begin_tick(tick)
            for i in order:
                if live[i] and rng.random() < 0.7:
                    rid, (name, fields) = live[i].pop(0)
                    tr.req(name, rid, **fields)
            tick += 1
        rep = validate_spans(tr.events, allow_open=True)
        assert rep["requests"] == len(plans) and rep["violations"] == 0
        done = sum(1 for c, trunc, _ in plans if not (trunc and c))
        assert rep["completed"] == done
        assert rep["retracted_terminal"] == len(plans) - done

    run()


# ------------------------------------------------- engine trace integration

MAX_SEQ = 20


def _traced_pair():
    cfg = ASSIGNED_ARCHS["chatglm3-6b"].reduced()
    opts = ModelOptions()
    mesh = make_test_mesh(1, 2)
    eng = pl.EngineConfig(n_trials=1, n_microbatches=2, microbatch=1,
                          n_stages=2, data_size=1, max_seq=MAX_SEQ,
                          cache_dtype=jnp.float32, prefill_chunks=2,
                          paged=True, block_size=4, n_blocks=10)
    plan = plan_stages(cfg, eng.n_stages)
    params = pl.init_trial_params(cfg, eng, plan, jax.random.PRNGKey(0),
                                  max_pos=MAX_SEQ)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    (8 + 4 * (i % 2),)).astype(np.int32),
                    3 + i % 3, arrival=0.7 * i) for i in range(6)]
    return cfg, eng, mesh, params, opts, reqs


@pytest.mark.parametrize("build, name", [
    (lambda cfg, eng, mesh: pl._trial_params_init(
        cfg, eng, plan_stages(cfg, eng.n_stages), jnp.dtype(jnp.float32),
        MAX_SEQ, mesh), "init_params"),
    (lambda cfg, eng, mesh: pl.make_train_step(
        cfg, ModelOptions(), dataclasses.replace(eng, paged=False), mesh,
        AdamW()), "train_step"),
    (lambda cfg, eng, mesh: pl.make_serve_step(
        cfg, ModelOptions(), eng, mesh, "decode"), "serve_decode"),
    (lambda cfg, eng, mesh: pl.make_serve_step(
        cfg, ModelOptions(), eng, mesh, "mixed"), "serve_mixed"),
    (lambda cfg, eng, mesh: pl.make_slot_reset(
        cfg, dataclasses.replace(eng, paged=False), mesh), "slot_reset"),
    (lambda cfg, eng, mesh: pl.make_transfer_kernels(cfg, eng, mesh).copy,
     "transfer_copy"),
])
def test_jitted_programs_have_stable_names(build, name):
    """``xla_compile`` events and the trace's module names come from
    these: each program is ``jit(<name>)``, module ``jit_<name>``."""
    cfg, eng, mesh, *_ = _traced_pair()
    assert build(cfg, eng, mesh).__name__ == name


def test_engine_trace_matches_untraced_run():
    cfg, eng, mesh, params, opts, reqs = _traced_pair()
    e0 = ServeEngine(cfg, eng, mesh, params, opts)
    comp0 = e0.run([r.clone() for r in reqs])
    assert len(e0.trace.events) == 0  # off = no event churn at all
    tr = Tracer()
    e1 = ServeEngine(cfg, eng, mesh, params, opts, tracer=tr)
    comp1 = e1.run([r.clone() for r in reqs])
    assert [c.tokens for c in comp0] == [c.tokens for c in comp1]
    assert e0.stats.ticks == e1.stats.ticks
    rep = validate_spans(tr.events)
    assert rep["requests"] == len(reqs) == rep["completed"]
    by_ev = {e["ev"] for e in tr.events}
    assert {"enqueue", "admit", "prefill_chunk", "first_token", "complete",
            "round", "compile"} <= by_ev
    rounds = [e for e in tr.events if e["ev"] == "round"]
    assert len(rounds) == e1.stats.ticks
    assert all("pool_blocks" in r for r in rounds)
    assert len(to_chrome_trace(tr.events)["traceEvents"]) > len(reqs)


def test_engine_trace_retraction_lifecycle():
    cfg, eng, mesh, params, opts, _ = _traced_pair()
    tight = dataclasses.replace(eng, n_blocks=6)
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    (10,)).astype(np.int32), 5, arrival=0.0)
            for i in range(4)]
    tr = Tracer()
    e = ServeEngine(cfg, tight, mesh, params, opts, overcommit=1.5,
                    host_blocks=8, tracer=tr)
    comps = e.run([r.clone() for r in reqs], max_ticks=5000)
    assert len(comps) == len(reqs)
    assert e.stats.retractions > 0  # the tight pool must actually preempt
    rep = validate_spans(tr.events)
    assert rep["completed"] == len(reqs) and rep["violations"] == 0
    retracts = [ev for ev in tr.events if ev["ev"] == "retract"]
    restores = [ev for ev in tr.events if ev["ev"] == "restore"]
    assert len(retracts) == e.stats.retractions
    assert len(restores) == len(retracts)  # all drained => all came back
    assert all(ev["via"] in ("swap", "recompute", "requeue")
               for ev in retracts + restores)
