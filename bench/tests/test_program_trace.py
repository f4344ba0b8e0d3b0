"""The reductions of ``program_trace`` on small traces worked out by hand:
the step's parts from name stacks, device time by part, program spans."""
import pytest

import program_trace as pt


@pytest.mark.parametrize("stack, part", [
    ("jit(train_step)/jvp(forward)/while/body/dot_general", "fwd"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/add", "bwd"),
    ("jit(train_step)/transpose(jvp(forward))/checkpoint/"
     "rematted_computation/dot_general", "bwd"),
    ("jit(train_step)/optimizer/mul", "opt"),
    ("jit(train_step)/grad_reduce/reduce_sum", "opt"),
    ("jit(train_step)/jit(clip)/min", "none"),
    ("jit(train_step)/feedforward/add", "none"),
    ("", "none"),
])
def test_part_of_a_name_stack(stack, part):
    assert pt.phase_of(stack) == part


def test_op_names_from_compiled_text():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0)',
        "}",
        "ENTRY %main.9 (a: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  %fusion.180 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_'
        'computation.1, metadata={op_type="mul" op_name="jit(train_step)/'
        'optimizer/mul" source_file="a.py" source_line=3}',
        '  ROOT %copy.2 = f32[4]{0} copy(%fusion.180), metadata={op_name='
        '"jit(train_step)/jvp(forward)/copy"}',
        "}",
    ])
    assert pt.hlo_op_names(text) == {
        "a": "x", "fusion.180": "jit(train_step)/optimizer/mul",
        "copy.2": "jit(train_step)/jvp(forward)/copy"}


def _trace():
    # window [100, 1100) on chip 0: "loop" [100, 600) holds f [100, 200)
    # and b [300, 500); o [600, 700); r [700, 900) is recompute; u
    # [900, 1000); f again [1050, 1200), half of it inside
    return {"names": ["loop", "f", "b", "o", "r", "u"],
            "devices": [[[0, 1, 2, 3, 4, 5, 1],
                         [100, 100, 300, 600, 700, 900, 1050],
                         [500, 100, 200, 100, 200, 100, 150]]],
            "host": [["bench.window", 100, 1000]],
            "spans": [["data.batch", 150, 5, {}],
                      ["data.batch", 650, 7, {}],
                      ["step.dispatch", 160, 3, {}],
                      ["data.batch", 1150, 9, {}]]}


def test_leaf_time_is_what_trace_reduce_counts():
    import trace_reduce
    tr = _trace()
    # the loop holds others, so it has none; f's second run is cut
    assert pt.leaf_time(tr, 100, 1100).tolist() == [0, 150, 200, 100, 200,
                                                    100]
    red = trace_reduce.reduce(tr, 1)
    assert dict(red["device_ops"]) == {
        n: t / 1e9 for n, t in zip(tr["names"],
                                   pt.leaf_time(tr, 100, 1100)) if t}
    assert red["busy_s"] * 1e9 == 950  # [100, 1000) and [1050, 1100)


def test_device_time_by_part_in_the_window():
    stacks = {"loop": "jit(train_step)/transpose(jvp(forward))/while",
              "f": "jit(train_step)/jvp(forward)/a",
              "b": "jit(train_step)/transpose(jvp(forward))/b",
              "o": "jit(train_step)/optimizer/c",
              "r": "jit(train_step)/transpose(jvp(forward))/checkpoint/"
                   "rematted_computation/d",
              "u": "jit(train_step)/jit(clip)/e"}
    w0, w1 = pt.window(_trace())
    assert (w0, w1) == (100, 1100)
    got = pt.phase_time(_trace(), stacks, w0, w1)
    # leaf operations by their stack; the 200 in which only the loop runs
    # is outer, whatever the loop's stack; f runs 100 + 50 inside the
    # window
    assert got == {"fwd": 150.0, "bwd": 400.0, "opt": 100.0, "none": 100.0,
                   "outer": 200.0, "remat": 200.0}
    assert sum(got[k] for k in pt.PARTS + ("outer",)) == 950  # busy
    # an operation with no name stack counts as none
    del stacks["u"]
    assert pt.phase_time(_trace(), stacks, w0, w1)["none"] == 100.0


def test_program_spans_inside_the_window():
    assert pt.spans_in(_trace(), "data.batch", 100, 1100) == [5, 7]
    assert pt.spans_in(_trace(), "step.dispatch", 100, 1100) == [3]


def test_train_readings_of_the_recorded_chip_trace_stay_as_they_were():
    """``mfu.train`` and ``idle.train`` on the recorded window, pinned to
    what ``trace_reduce`` read there when the program's spans came in."""
    import json
    import os

    import common
    import trace_reduce
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "chip_trace.json")
    with open(path) as f:
        red = trace_reduce.reduce(json.load(f)["trace"], 1)
    rec = {"tokens": 1024, "flops_per_token": 2.15e9, "n_chips": 1,
           "peaks": {"flops_bf16": 197e12}}
    assert (red["busy_s"], red["window_s"]) == (0.058044735, 0.065667729)
    assert common.metric_reader("idle.train").read(rec, red) \
        == 11.608432507236532
    assert common.metric_reader("mfu.train").read(rec, red) \
        == 19.253485295034075
