"""Readings that set a cell's limits (and a serving cell's rate), on the
chip, in one process: the engine or the gang is built and warmed once.

    # a serving cell: the program's widest logit gap and the control's
    # (the reference in float8), one line per seed, at the cell's own load
    python bench/readings.py --workload <cell> --seconds 15 \
        --seeds 11,12,13 control

    # a training cell: the program's three numbers against the reference,
    # the control's (the reference in bfloat16) and a planted fault's (half
    # of each batch left out), one line per seed, the gang built once
    python bench/readings.py --workload bert-large.k2-gang --seconds 0 \
        --seeds 11,12,13 train

    # the open-loop rate sweep that places a serving cell below its knee
    python bench/readings.py --workload <cell> --seconds 25 \
        --seeds 5 sweep --rates 1,1.5,2,2.5,3

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import common  # noqa: E402
import numpy as np  # noqa: E402


def _drain(engine):
    while not engine.done():
        engine.step()


def _fresh(engine, cfg, eng, mesh):
    from repro.core import pipeline as pl
    engine.cache = pl.serve_cache_struct(cfg, eng, dry_run=False, mesh=mesh)


def _train(spec, devices, seeds, n_control, n_fault) -> int:
    """The gang is built once; every seed gets its own weights, optimizer
    state and set-up steps on it, then the readings of the reference, of
    the control (the reference in bfloat16) and of the fault (half of each
    batch left out, planted in the reference), each against the
    reference."""
    import train_driver as td
    gang = td.build(spec, devices)
    for i, seed in enumerate(seeds):
        phases = {}
        p, o, readings, data = td.start(gang, spec, seed, phases)
        data.close()
        del p, o
        gc.collect()
        out = {"readings": readings, "struct": gang["struct"],
               "shard": gang["shard"], "hparams": {
                   "lr": list(spec["traffic"]["lrs"]),
                   "wd": spec["traffic"]["weight_decay"]}}
        row = {"seed": seed, "peak": common.device_info(devices)[
            "memory_peak_bytes"], **{f"t.{k}": round(v, 3)
                                     for k, v in phases.items()}}
        b1 = spec["config"]["optimizer"]["b1"]
        base = td.reference_readings(
            spec, out, seed, "fp32", against=(readings["m1"], 1 / (1 - b1)),
            keep_g1=i < max(n_control, n_fault))
        row.update({f"ref.{k}": v for k, v in td.readings_gaps(
            readings, base, base["grad1_err"]).items()})
        for tag, prec, keep, n in (("control", "bf16", 1.0, n_control),
                                   ("half_batch", "fp32", 0.5, n_fault)):
            if i < n:
                # the control and the fault stand in the program's place
                ref = td.reference_readings(spec, out, seed, prec, keep,
                                            against=(base["g1_full"], 1.0))
                row.update({f"{tag}.{k}": v for k, v in td.readings_gaps(
                    ref, base, ref["grad1_err"]).items()})
                del ref
        row["losses_prog"] = np.asarray(readings["losses"]).tolist()
        row["losses_ref"] = base["losses"].tolist()
        print(json.dumps(row), flush=True)
        del out, readings, base
        gc.collect()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("what", choices=("control", "sweep", "train"))
    ap.add_argument("--rates", default="")
    ap.add_argument("--control-seeds", type=int, default=4,
                    help="train: how many of the seeds also read the control")
    ap.add_argument("--fault-seeds", type=int, default=3,
                    help="train: how many also read the half-batch fault")
    a = ap.parse_args(argv)
    import jax
    import serve_driver as sd
    import weights
    common.enable_cache(jax)
    spec = common.cell_spec(a.workload)
    devices = common.require_chips(jax, spec["workload"]["chips"])
    seeds = [int(x) for x in a.seeds.split(",")]
    if a.what == "train":
        return _train(spec, devices, seeds, a.control_seeds, a.fault_seeds)
    args, setup, engine, (struct, shard) = sd.build(spec, seeds[0], devices)
    sd.warm(engine, spec)
    vocab = spec["config"]["vocab_size"]
    if a.what == "sweep":
        for rate in [float(x) for x in a.rates.split(",")]:
            sp = copy.deepcopy(spec)
            sp["traffic"]["rate_per_s"] = rate
            sp["traffic"]["drain_s"] = 30.0
            cnt = {}
            reqs = sd.loop(engine, sp, seeds[0], a.seconds, None, cnt)
            cnt["compiles_in_run"] = 0
            vals, att, failed, counts = sd.metrics(
                {"requests": reqs, "counters": cnt}, sp)
            print(json.dumps({"rate": rate, **vals, **counts,
                              "queued_at_close": cnt["queued_at_close"]}),
                  flush=True)
            _drain(engine)
        return 0
    for seed in seeds:
        if seed != seeds[0]:
            engine.params = None
            setup = None
            gc.collect()
            engine.params = weights.make(struct, shard, seed, vocab)
        cnt = {}
        reqs = sd.loop(engine, spec, seed, a.seconds, None, cnt)
        _drain(engine)
        engine.cache = None
        gc.collect()
        chk = sd.compare({"requests": reqs, "params": engine.params}, spec,
                         seed, control=True)
        print(json.dumps({"seed": seed, **{k: v["value"]
                                           for k, v in chk.items()}}),
              flush=True)
        _fresh(engine, engine.cfg, engine.eng, engine.mesh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
