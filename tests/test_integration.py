"""Driver end-to-end tests, each run in a subprocess (the launchers own
their process: argv parsing, env setup, stdout reporting).

The pipeline/serve exactness checks that used to hide behind subprocess
wrappers here are now ordinary pytest modules under ``tests/integration/``
(collected in-process — tests/conftest.py provides the fake devices).
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.path.join(ROOT, "src"),
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       # the launchers turn on JAX's persistent compile cache; tests don't
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


def _run(args, timeout=540):
    proc = subprocess.run([sys.executable] + args, env=ENV, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_train_driver_end_to_end(tmp_path):
    out = _run(["-m", "repro.launch.train", "--arch", "chatglm3-6b",
                "--smoke", "--trials", "2", "--steps", "4",
                "--n-data", "2", "--n-model", "4",
                "--n-microbatches", "2", "--seq-len", "16",
                "--ckpt-dir", str(tmp_path)])
    assert "best_trial" in out


def test_serve_driver_continuous_end_to_end():
    out = _run(["-m", "repro.launch.serve", "--arch", "chatglm3-6b",
                "--smoke", "--n-data", "2", "--n-model", "4",
                "--slots", "3", "--prompt-len", "8", "--gen-len", "4",
                "--n-requests", "8", "--rate", "2.0"])
    assert "continuous:" in out and "slot occupancy" in out


def test_serve_driver_trace_replay(tmp_path):
    """--trace replays a recorded JSONL request stream."""
    trace = tmp_path / "stream.jsonl"
    gen = _run(["-c", (
        "from repro.serve import poisson_trace, save_trace; "
        "save_trace(%r, poisson_trace(5, 1.0, 128, prompt_lens=(4, 8), "
        "gen_lens=(2, 4), seed=3))") % str(trace)])
    assert trace.exists(), gen
    out = _run(["-m", "repro.launch.serve", "--arch", "chatglm3-6b",
                "--smoke", "--n-data", "1", "--n-model", "2",
                "--slots", "2", "--prompt-len", "8", "--gen-len", "4",
                "--trace", str(trace)])
    assert "5 requests" in out and "slot occupancy" in out
