"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload bert-large.k2-gang --seed 7 \
        --seconds 40 --trace 0

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``, with the plain reference beside it in
``<config>.ref.py``) and its traffic (``bench/traffic/<traffic>.json``);
the traffic file names the driver module under ``bench/`` that runs it. ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` runs the same load under the profiler and
prints its per-layer metrics, each read by ``bench/metrics/<metric>.py``
from the run's record and the reduced trace, with the device's busy time
and the breakdown.

The run fails, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for. The last stdout line is the result; the numbers
compared with the reference, each beside its limit, are the last lines of
stderr and the result's last key, ``checks``.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

T_START = __import__("time").perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import common  # noqa: E402

def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also write a few steps of the trace (JSON) here")
    return ap.parse_args(argv)


def run(args, spec: dict, devices, jax) -> dict:
    """One run of the cell on ``devices``: the result line's fields."""
    import importlib
    drv = importlib.import_module(spec["traffic"]["driver"])
    clock = {"compiles": common.CompileCounter(jax), "process_start": T_START}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    try:
        out = drv.run(spec, args.seed, args.seconds, trace_dir, devices,
                      clock)
        setup_s = clock["window_start"] - T_START
        vals, attempted, failed, counts = drv.metrics(out, spec)
        common.log("counts " + " ".join(f"{k}={v}" for k, v in
                                         counts.items()))
        result = {"attempted": attempted, "failed": failed,
                  "device": out["device"]}
        if args.trace:
            import trace_reduce
            simple = trace_reduce.simplify(trace_reduce.find_trace(trace_dir))
            if args.keep_trace:
                import json
                with open(args.keep_trace, "w") as f:
                    json.dump(trace_reduce.cut(simple), f)
            red = trace_reduce.reduce(simple, len(devices))
            result["device"]["busy_s"] = red["busy_s"]
            result["device"]["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            mets = {}
            for m in spec["per_layer"]:
                v = common.metric_reader(m["name"]).read(out["record"], red)
                if v is not None:
                    mets[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            vals["setup_s"] = setup_s
            mets = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                    for m in spec["end_to_end"]
                    if vals.get(m["name"]) is not None}
        common.log(f"setup_s={setup_s}")
        result["metrics"] = mets
        checks = drv.compare(out, spec, args.seed)
        result["checks"] = checks
        result["correct"] = all(c["ok"] for c in checks.values()) \
            and failed == 0
        return result
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = parse(argv)
    spec = common.cell_spec(args.workload)
    import jax
    common.enable_cache(jax)
    try:
        devices = common.require_chips(jax, spec["workload"]["chips"])
    except common.NoChip as e:
        common.log(f"bench: {e}")
        return 3
    common.peaks_for(devices[0].device_kind)
    common.finish(run(args, spec, devices, jax))
    return 0


if __name__ == "__main__":
    sys.exit(main())
