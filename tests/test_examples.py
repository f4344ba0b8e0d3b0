"""Smoke-run every script under examples/ with tiny configs.

The examples are documentation that executes — they rot silently unless CI
runs them (this suite already caught a stale-checkpoint crash in
model_selection.py between successive-halving rungs). Each script runs in a
subprocess on a single forced host device with its smallest configuration;
the assertion is just "exits 0" — correctness of the underlying machinery is
covered by the unit/integration tiers.

A new example script is picked up automatically (parametrized over the
directory listing); give it a tiny-args entry below if its defaults are too
slow for CI.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES_DIR = os.path.join(ROOT, "examples")
EXAMPLES = sorted(f for f in os.listdir(EXAMPLES_DIR) if f.endswith(".py"))

# per-script tiny-config args (defaults are used when absent)
TINY_ARGS = {
    "model_selection.py": ["--tiny", "--steps", "2"],
    "serve_decode.py": ["--slots", "2", "--n-requests", "6",
                        "--prompt-len", "8", "--gen-len", "4"],
}
TIMEOUT_S = 420


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script, tmp_path):
    env = {**os.environ,
           "PYTHONPATH": os.path.join(ROOT, "src"),
           # one host device: the examples degrade to their single-device
           # paths (smallest compiles); the forced-8 flag from conftest.py
           # must not leak into the subprocess
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           # serve_decode.py runs the serve launcher, which turns on JAX's
           # persistent compile cache; tests don't
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    args = list(TINY_ARGS.get(script, []))
    if script == "model_selection.py":
        args += ["--ckpt-dir", str(tmp_path / "ckpt")]  # hermetic
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, script), *args],
        env=env, capture_output=True, text=True, timeout=TIMEOUT_S, cwd=ROOT)
    assert proc.returncode == 0, (
        f"examples/{script} exited {proc.returncode}\n"
        f"--- stdout ---\n{proc.stdout[-1500:]}\n"
        f"--- stderr ---\n{proc.stderr[-1500:]}")
