"""Reduce one ``jax.profiler`` trace to what the benchmark reports.

The traced window is the host span ``bench.window`` that the drivers open
around the measured window. Within it, for each chip: busy time is the
union of the intervals in which an operation ran (the device plane's
``XLA Ops`` line), idle time the rest. Busy time is averaged over the
chips. The breakdown lists the device operations that took the most time
(leaf operations only, so that a loop and the operations inside it are not
counted twice; mean per chip) and the longest idle gaps of the first chip,
each labelled by the innermost ``bench.*`` host span over its midpoint.

``simplify`` turns the profiler's file into plain arrays, and ``reduce``
works on those alone, so a small recorded trace checks the arithmetic.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
TOP = 10


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def simplify(path: str) -> dict:
    """{"names": [...], "devices": [[name_ids, starts_ns, durs_ns] per chip
    in plane order], "host": [[name, start_ns, dur_ns], ...] of the
    benchmark's spans}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ids: dict = {}
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            nid, st, du = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    n = short_name(e.name)
                    nid.append(ids.setdefault(n, len(ids)))
                    st.append(e.start_ns)
                    du.append(e.duration_ns)
            devices.append((int(plane.name.rsplit(":", 1)[1]),
                            [nid, st, du]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    devices.sort(key=lambda d: d[0])
    names = [None] * len(ids)
    for n, i in ids.items():
        names[i] = n
    return {"names": names, "devices": [d[1] for d in devices],
            "host": host}


def _busy_and_gaps(starts, ends, w0, w1):
    """Union of [start, end) clipped to [w0, w1): busy length and the idle
    gaps as (start, end) arrays."""
    keep = (ends > w0) & (starts < w1)
    s = np.clip(starts[keep], w0, w1)
    e = np.clip(ends[keep], w0, w1)
    if not len(s):
        return 0.0, np.array([w0]), np.array([w1])
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    gap_lo = np.concatenate([[w0], reach])
    gap_hi = np.concatenate([s, [w1]])
    idle = gap_hi > gap_lo
    gap_lo, gap_hi = gap_lo[idle], gap_hi[idle]
    busy = (w1 - w0) - float(np.sum(gap_hi - gap_lo))
    return busy, gap_lo, gap_hi


def _leaf_time(nid, starts, ends, w0, w1):
    """Time of each operation that contains no other, clipped to the
    window, summed by name id."""
    order = np.lexsort((-ends, starts))
    s, e, n = starts[order], ends[order], nid[order]
    leaf = np.ones(len(s), bool)
    leaf[:-1] = s[1:] >= e[:-1]
    t = np.clip(e, w0, w1) - np.clip(s, w0, w1)
    keep = leaf & (t > 0)
    return np.bincount(n[keep], weights=t[keep])


def reduce(tr: dict, n_devices: int) -> dict:
    wins = [h for h in tr["host"] if h[0] == WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    _, w0, wd = max(wins, key=lambda h: h[2])
    w1 = w0 + wd
    devs = tr["devices"][:n_devices]
    if len(devs) < n_devices or not all(len(d[1]) for d in devs):
        raise ValueError(f"the trace holds {len(devs)} device planes with "
                         f"operations; the run used {n_devices}")
    names = tr["names"]
    busy, op_time, gaps = [], np.zeros(len(names)), None
    for i, (nid, st, du) in enumerate(devs):
        nid = np.asarray(nid, np.int64)
        st = np.asarray(st, np.float64)
        en = st + np.asarray(du, np.float64)
        b, lo, hi = _busy_and_gaps(st, en, w0, w1)
        busy.append(b)
        lt = _leaf_time(nid, st, en, w0, w1)
        op_time[:len(lt)] += lt
        if i == 0:
            gaps = (lo, hi)
    spans = [(s, s + d, n) for n, s, d in tr["host"] if n != WINDOW]

    def label(a, b):
        mid = (a + b) / 2
        cover = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        return min(cover)[1] if cover else "none"

    lo, hi = gaps
    top_gaps = np.argsort(lo - hi, kind="stable")[:TOP]
    n = len(devs)
    top_ops = np.argsort(-op_time, kind="stable")[:TOP]
    return {
        "window_s": wd / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "device_ops": [[names[i], float(op_time[i]) / n / 1e9]
                       for i in top_ops if op_time[i] > 0],
        "idle_gaps": [[label(lo[i], hi[i]), float(hi[i] - lo[i]) / 1e9]
                      for i in top_gaps],
    }


def cut(tr: dict, n_steps: int = 3) -> dict:
    """A few steps of the first chip out of a simplified trace, with a
    window span of their own: a fixture for the tests."""
    steps = sorted((h for h in tr["host"]
                    if h[0] in ("bench.step", "bench.train_step")),
                   key=lambda h: h[1])
    mid = len(steps) // 2
    w0 = steps[mid][1]
    last = steps[min(mid + n_steps, len(steps) - 1)]
    w1 = last[1] + last[2]
    nid, st, du = (np.asarray(x) for x in tr["devices"][0])
    keep = (st < w1) & (st + du > w0)
    used = sorted(set(nid[keep].tolist()))
    remap = {o: i for i, o in enumerate(used)}
    host = [h for h in tr["host"] if h[1] < w1 and h[1] + h[2] > w0
            and h[0] != WINDOW]
    return {"names": [tr["names"][i] for i in used],
            "devices": [[[remap[int(x)] for x in nid[keep]],
                         st[keep].tolist(), du[keep].tolist()]],
            "host": host + [[WINDOW, w0, w1 - w0]]}


def find_trace(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{found}")
    return found[0]
