"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusal to
run without a TPU (the published-width run itself needs the chip).

The phases run in-process on the fake host devices from conftest.py, with
the reduced configs; nothing here turns the persistent compile cache on."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# 40-token prompts prefill in two chunks whose kernel-path block tables
# differ in width (2 and 4 blocks of 16), so the first chunk's program runs
# once per pass — the shape a cache made off the mesh would recompile
TINY_SERVE = ("--arch", "chatglm3-6b", "--smoke", "--paged", "--slots", "1",
              "--microbatch", "4", "--prompt-len", "40", "--gen-len", "4")
TINY_TRAIN = dict(smoke=True, steps=2, seq_len=16, microbatch=2)


def test_serve_phase_tiny():
    line = chip_smoke.serve_phase(flags=TINY_SERVE, n_requests=4)
    probe = line["kernel_vs_ref"]
    assert probe["rows"] == 4 and probe["max_abs_err"] <= probe["tol"]
    for run in ("kernel", "gather"):
        assert line[run]["tokens"] == 16 and line[run]["engine_calls"] > 0
    # fp32 smoke weights: the jnp mirror and the gather path agree exactly
    assert line["kernel_gather_token_agreement"] == 1.0


def test_train_phase_tiny():
    line = chip_smoke.train_phase(**TINY_TRAIN)
    assert line["trials"] == 2 and len(line["losses"]) == 2


def test_gang_phase_tiny():
    line = chip_smoke.gang_phase(**TINY_TRAIN)
    assert set(line["losses"]) == {1, 4}
    assert line["max_loss_rel_gap"] <= chip_smoke.LOSS_RTOL


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_tpu(tmp_path, alone):
    """No TPU (JAX held to the CPU), or the script copied away from the
    repo: non-zero exit and no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
