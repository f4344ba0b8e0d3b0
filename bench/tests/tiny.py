"""A cell of the benchmark at a size the CPU runs in seconds: the
program's reduced model (``--smoke``: 4 layers, width 64, vocabulary 128,
float32) under a short load. Tests drive the harness through it."""
from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import common  # noqa: E402


def serve_spec(kind: str = "chat") -> dict:
    """A serving cell of chatglm3-6b at the reduced size, open loop
    (``chat``) or closed loop (``docs``), with the serving metrics."""
    from repro.configs import get_config
    spec = {
        "workload": {"name": "chatglm3-6b.tiny", "config": "chatglm3-6b",
                     "traffic": "chat", "chips": 1},
        "config": common.load_json(
            os.path.join(BENCH, "configs", "chatglm3-6b.json")),
        "traffic": common.load_json(os.path.join(HERE, "data", "chat.json")),
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
            ("serve_tok_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [{"name": n, "unit": "%"} for n in (
            "mfu.chat", "idle.chat", "append_fill.chat")],
    }
    r = get_config("chatglm3-6b").reduced()
    c = dict(spec["config"], n_layers=r.n_layers, d_model=r.d_model,
             n_heads=r.n_heads, n_kv_heads=r.n_kv_heads, head_dim=r.head_dim,
             d_ff=r.d_ff, vocab_size=r.vocab_size, weight_dtype="float32",
             cache_dtype="float32", compute_dtype="float32")
    t = copy.deepcopy(spec["traffic"])
    eng = t["engine"]
    eng[eng.index("--microbatch") + 1] = "4"
    eng[eng.index("--prompt-len") + 1] = "24"
    eng[eng.index("--gen-len") + 1] = "8"
    t["engine"] = eng + ["--smoke"]
    t["max_seq"] = 32
    t["prompt"] = {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "multiple": 8, "min": 8, "max": 24}
    t["output"] = {"dist": "uniform", "min": 2, "max": 8}
    t["preroll_s"] = 1.0
    t["drain_s"] = 20.0
    if kind == "chat":
        t["rate_per_s"] = 4.0
    else:  # a closed loop (offline jobs) on the same layout
        t["kind"] = "serve_closed"
        t["outstanding"] = 6
        t["pool"] = 64
    t["check"] = dict(t["check"], requests=3, min_tokens=4)
    # the CPU has no entry in the table of peaks; these stand in for it
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    return dict(spec, config=c, traffic=t, peaks=peaks)


def run_serve(spec: dict, seed: int, seconds: float, trace: int = 0,
              break_engine=None) -> dict:
    """One run of ``spec`` on the CPU (the harness's look for a chip is
    skipped); ``break_engine(engine)`` may break the timed path first."""
    import jax
    import run
    import serve_driver
    build = serve_driver.build
    if break_engine is not None:
        def broken(*a, **k):
            out = build(*a, **k)
            break_engine(out[2])
            return out
        serve_driver.build = broken
    try:
        args = run.parse(["--workload", spec["workload"]["name"], "--seed",
                          str(seed), "--seconds", str(seconds), "--trace",
                          str(trace)])
        return run.run(args, spec, jax.devices()[:1], jax)
    finally:
        serve_driver.build = build


def train_spec() -> dict:
    from repro.configs import get_config
    spec = common.cell_spec("bert-large.k2-gang")
    r = get_config("bert-large").reduced()
    seq = 16
    c = dict(spec["config"], n_layers=r.n_layers, d_model=r.d_model,
             n_heads=r.n_heads, n_kv_heads=r.n_kv_heads, head_dim=r.head_dim,
             d_ff=r.d_ff, vocab_size=r.vocab_size, max_positions=seq)
    t = dict(spec["traffic"], smoke=True, lrs=[1e-3, 3e-4],
             n_microbatches=2, microbatch=2, seq_len=seq)
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    return dict(spec, config=c, traffic=t, peaks=peaks)


def run_cell(spec: dict, seed: int, seconds: float, trace: int = 0,
             patch=None) -> dict:
    """One run of ``spec`` on the CPU (the harness's look for a chip is
    skipped); ``patch()`` may break the timed path first and returns a
    function that mends it."""
    import jax
    import run
    mend = patch() if patch is not None else None
    try:
        args = run.parse(["--workload", spec["workload"]["name"], "--seed",
                          str(seed), "--seconds", str(seconds), "--trace",
                          str(trace)])
        return run.run(args, spec, jax.devices()[:1], jax)
    finally:
        if mend is not None:
            mend()
