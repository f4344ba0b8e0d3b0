"""The control comes out as not correct: the plain reference put in the
program's place at the next precision down (float8 for the served model's
bfloat16, bfloat16 for the gang's float32), at the program's reduced size
on the CPU, against limits of that size. The readings at the cells' own
sizes are taken on the chip (``bench/readings.py``; ``PERF.md``)."""
import serve_driver
import tiny
import train_driver

# at the reduced size in float32 the program reads zero up to rounding;
# these stand where the cells' limits stand between their two readings
TINY_LOGIT_GAP = 1e-3
TINY_TRAIN = {"loss_gap": 1e-4, "grad1_gap": 1e-3, "change_gap": 1e-3,
              "grad1_diff": 1e-3}


def test_serving_control_fails_where_the_program_passes():
    spec = tiny.serve_spec("chat")
    spec["traffic"]["check"]["max_logit_gap"] = TINY_LOGIT_GAP
    # every request of the window, so that enough tokens are compared
    spec["traffic"]["check"]["requests"] = 64
    compare = serve_driver.compare

    def with_control(out, spec, seed, control=False):
        return compare(out, spec, seed, control=True)
    serve_driver.compare = with_control
    try:
        res = tiny.run_serve(spec, 2**31 + 21, 4.0)
    finally:
        serve_driver.compare = compare
    assert res["checks"]["logit_gap"]["ok"]
    assert not res["checks"]["control_logit_gap"]["ok"]


def test_training_control_fails_where_the_program_passes():
    spec = tiny.train_spec()
    spec["traffic"]["check"] = dict(TINY_TRAIN)
    got = {}
    compare = train_driver.compare

    def keep(out, spec, seed):
        got["out"] = out
        return compare(out, spec, seed)
    train_driver.compare = keep
    try:
        res = tiny.run_cell(spec, 2**31 + 23, 1.0)
    finally:
        train_driver.compare = compare
    assert res["correct"], res["checks"]
    out = got["out"]
    seed = 2**31 + 23
    base = train_driver.reference_readings(spec, out, seed, keep_g1=True)
    ctrl = train_driver.reference_readings(
        spec, out, seed, precision="bf16", against=(base["g1_full"], 1.0))
    gaps = train_driver.readings_gaps(ctrl, base, ctrl["grad1_err"])
    assert any(gaps[k] > TINY_TRAIN[k] for k in TINY_TRAIN), gaps
