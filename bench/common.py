"""Pieces every driver of the benchmark shares: the manifest, the device
check, the compile counter, seeds, percentiles and the result line.

Nothing here imports the program; the drivers do.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_spec(name: str, man: dict | None = None) -> dict:
    """Everything one cell is made of, found by the names in the manifest:
    its entry, its configuration file, its traffic file, and the metrics it
    reports with and without the trace."""
    man = man or manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; the manifest has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = next(c for c in man["configs"] if c["name"] == w["config"])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "workload": w,
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "traffic": load_json(os.path.join(BENCH_DIR, "traffic",
                                          w["traffic"] + ".json")),
        "end_to_end": [m for m in man["end_to_end"] if applies(m)],
        "per_layer": [m for m in man["per_layer"] if applies(m)],
    }


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def config_reference(config_name: str):
    return load_module(os.path.join(BENCH_DIR, "configs",
                                    config_name + ".ref.py"),
                       "bench_ref_" + config_name.replace("-", "_")
                       .replace(".", "_"))


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# device and compilation
# ---------------------------------------------------------------------------


def require_chips(jax, n: int):
    """The first ``n`` accelerator devices, or NoChip."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU, only {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts XLA backend compilations (``jax.monitoring``), so a run can
    show that nothing compiles inside its window."""

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def enable_cache(jax) -> str:
    """The program's persistent compile cache (``<checkout>/.jax_cache`` or
    ``$JAX_COMPILATION_CACHE_DIR``), keeping every program however fast it
    compiled, so a run after the first compiles nothing."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


# ---------------------------------------------------------------------------
# seeds and statistics
# ---------------------------------------------------------------------------


def seed_words(seed: int) -> list:
    """A run's seed (any whole number) as 32-bit words for numpy and JAX."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def np_rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng(seed_words(seed) + [stream])


def jax_key(jax, seed: int, stream: int):
    import numpy as np
    key = jax.random.PRNGKey(stream)
    for w in seed_words(seed):
        key = jax.random.fold_in(key, np.uint32(w))
    return key


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def now() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# output
# ---------------------------------------------------------------------------


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def finish(result: dict) -> None:
    """Print the compared numbers as the last lines of stderr, then the
    result as the last line of stdout (``checks`` is its last key)."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
            f"{'ok' if c['ok'] else 'FAILED'})")
    log(f"correct: {result['correct']}")
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = result["checks"]
    print(json.dumps(out), flush=True)


def check(value, limit, ok: bool) -> dict:
    if isinstance(value, float) and not math.isfinite(value):
        value, ok = str(value), False
    return {"value": value, "limit": limit, "ok": bool(ok)}
