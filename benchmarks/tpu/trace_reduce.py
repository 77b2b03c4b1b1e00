"""From a profiler trace of the window to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
flat list of events; ``summarize`` reduces them: the window (the host
annotation ``bench.window``), each device's busy time (the union of its
op intervals inside the window), time per op name, the longest device ops
and the longest idle gaps named by the host work under them. Metric
readers (``metrics/*.py``) take their numbers from the summary, and the
tests check the reduction on a small recorded trace
(``tests/fixtures/``).

An event is a dict: ``plane``, ``line``, ``name``, ``text`` (the name
and the event's string stats, which carry the HLO op and kernel names),
``start_ns``, ``end_ns``.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no xplane trace under {trace_dir}")
    events = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            dev = DEVICE_PLANE.match(plane.name)
            host = plane.name.startswith("/host:")
            if not dev and not host:
                continue
            for line in plane.lines:
                if dev and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = [ev.name]
                    for _, v in ev.stats:
                        if isinstance(v, str):
                            text.append(v)
                    events.append({
                        "plane": plane.name, "line": line.name,
                        "name": short_name(ev.name), "text": " ".join(text),
                        "start_ns": float(ev.start_ns),
                        "end_ns": float(ev.start_ns) + float(ev.duration_ns)})
    return events


def short_name(name: str) -> str:
    """A device op's event name is its whole HLO instruction; keep the
    instruction's name (``%fusion.3 = ...`` -> ``fusion.3``)."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def union_ns(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(evs, t0, t1):
    return [(max(e["start_ns"], t0), min(e["end_ns"], t1)) for e in evs
            if e["end_ns"] > t0 and e["start_ns"] < t1]


def _gaps(intervals, t0, t1):
    gaps, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def _host_label(events, s, e) -> str:
    """What the host was doing in the gap [s, e]: the host event that
    overlaps it the most, the shortest of those that tie (the innermost
    frame); the window annotation itself is left out."""
    best, key = "idle host", (0.0, 0.0)
    for ev in events:
        if ev["name"] == WINDOW:
            continue
        ov = min(e, ev["end_ns"]) - max(s, ev["start_ns"])
        k = (ov, -(ev["end_ns"] - ev["start_ns"]))
        if ov > 0 and k > key:
            best, key = ev["name"], k
    return best


def summarize(events: list, chips: int) -> dict:
    wins = [e for e in events if e["name"] == WINDOW]
    if not wins:
        raise RuntimeError("the trace has no window annotation")
    t0, t1 = wins[0]["start_ns"], wins[0]["end_ns"]
    window_ns = t1 - t0
    devices = sorted({e["plane"] for e in events
                      if DEVICE_PLANE.match(e["plane"])},
                     key=lambda p: int(DEVICE_PLANE.match(p).group(1)))[:chips]
    host = [e for e in events if e["plane"].startswith("/host:")]
    per_dev, op_ns, busy = {}, {}, []
    for d in devices:
        ops = [e for e in events if e["plane"] == d
               and e["end_ns"] > t0 and e["start_ns"] < t1]
        iv = _clip(ops, t0, t1)
        busy.append(union_ns(iv))
        for e in ops:
            dur = min(e["end_ns"], t1) - max(e["start_ns"], t0)
            op_ns[e["name"]] = op_ns.get(e["name"], 0.0) + dur / len(devices)
        per_dev[d] = {"ops": ops, "intervals": iv}
    n = max(1, len(devices))
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    if devices:
        gaps = sorted(_gaps(per_dev[devices[0]]["intervals"], t0, t1),
                      key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "window_ns": (t0, t1),
        "busy_s": sum(busy) / n / 1e9,
        "devices": len(devices),
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "per_device": per_dev,
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": [[_host_label(host, s, e), (e - s) / 1e9]
                          for s, e in gaps]},
    }


def op_time_s(summary: dict, pattern: str) -> float:
    """Seconds (averaged over the chips) of device ops whose name or
    stats match ``pattern``, inside the window."""
    rx = re.compile(pattern)
    t0, t1 = summary["window_ns"]
    total = 0.0
    for dev in summary["per_device"].values():
        for e in dev["ops"]:
            if rx.search(e["text"]):
                total += min(e["end_ns"], t1) - max(e["start_ns"], t0)
    return total / max(1, summary["devices"]) / 1e9
