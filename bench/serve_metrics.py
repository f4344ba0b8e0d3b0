"""Arithmetic of the serving cells' per-layer metrics (``bench/metrics``)."""
from __future__ import annotations

import flops


def mfu(rec: dict, red: dict):
    if red["busy_s"] <= 0 or not rec["calls"]:
        return None
    roof = sum(flops.serve_call_roofline_s(
        rec["config"], rows, rec["peaks"], rec["weight_bytes"],
        rec["cache_bytes"]) for _, _, rows in rec["calls"] if rows)
    return 100.0 * roof / red["busy_s"]


def idle(red: dict):
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def append_fill(rec: dict):
    d_calls = rec["stats1"]["prefill_calls"] - rec["stats0"]["prefill_calls"]
    if d_calls <= 0:
        return None
    d_ticks = (rec["stats1"]["prefill_slot_ticks"]
               - rec["stats0"]["prefill_slot_ticks"])
    return 100.0 * d_ticks / (d_calls * rec["n_cells"])
