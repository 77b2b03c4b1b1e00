"""The program's own spans, for the readers in ``metrics/``.

The program records a span (``repro.utils.timing``) only while a
profiler session collects, and ``run.py --trace 1`` collects around the
window alone, so after the window the buffer holds the spans the window
ran. They are timed on ``time.perf_counter_ns()``, which the written
trace's clock follows by one constant, so one offset a run puts them on
the device trace's clock:

- training: the window is one ``fit`` call, whose ``train.fit`` span
  opens microseconds after the window's annotation;
- serving: ``serve_open_loop``'s ``t0 - 0.05`` is its window start on the
  ``perf_counter`` clock, a few statements after the annotation opens.

Every function returns None where it finds nothing to read: a program
without the recorder, an empty buffer, a buffer that dropped records
(a part of the window would be missing), or a missing anchor.

``BENCHMARK.json`` lists none of these readers' metrics yet:
``run.per_layer`` fails a traced run on a listed metric's None, and a
program without the recorder gives None for all of them. They join the
benchmark once ``run.per_layer`` can leave such a metric out.
"""
from __future__ import annotations

import bisect


def records():
    """The program's span records, or None (see the module doc)."""
    try:
        from repro.utils import timing
    except ImportError:
        return None
    if not hasattr(timing, "recorded"):
        return None
    if timing.dropped() > 0:
        return None
    return timing.recorded() or None


def window(ctx, kind: str):
    """``(records, offset, summary)`` of a traced window, the offset
    from the ``kind`` ("train" or "serve") cell's anchor; or None."""
    recs, summary = records(), ctx["trace"]
    if recs is None or not summary:
        return None
    off = (train_offset(recs, summary) if kind == "train"
           else serve_offset(ctx["driver"], summary))
    if off is None:
        return None
    return recs, off, summary


def train_offset(recs, summary):
    """Nanoseconds from the span clock to the trace's, from the window's
    single ``train.fit`` span."""
    fits = named(recs, "train.fit")
    if len(fits) != 1:
        return None
    return summary["window_ns"][0] - fits[0].start_ns


def serve_offset(driver, summary):
    """Nanoseconds from the span clock to the trace's, from the window
    start of ``serve_open_loop``."""
    t0 = getattr(driver, "t0", None)
    if t0 is None:
        return None
    return summary["window_ns"][0] - (t0 - 0.05) * 1e9


def named(recs, name: str) -> list:
    return [r for r in recs if r.name == name]


def in_window(recs, name: str, offset: float, summary) -> list:
    """The records called ``name`` that start inside the window."""
    t0, t1 = summary["window_ns"]
    return [r for r in named(recs, name)
            if t0 <= r.start_ns + offset < t1]


def duration_ns(r) -> int:
    return r.end_ns - r.start_ns


def descendant_ns(recs, root, name: str) -> int:
    """Time in spans called ``name`` under ``root`` (the outermost such
    span on each path)."""
    kids = {}
    for r in recs:
        kids.setdefault(r.parent, []).append(r)
    total, todo = 0, list(kids.get(root.id, ()))
    while todo:
        r = todo.pop()
        if r.name == name:
            total += duration_ns(r)
        else:
            todo.extend(kids.get(r.id, ()))
    return total


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _covered(merged, starts, a, b) -> float:
    """Length of [a, b] that the merged intervals cover."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


def idle_inside_ns(recs, offset: float, summary) -> float:
    """Device idle time inside the given spans, clipped to the window
    and averaged over the chips: how much of the device's idle share
    those spans hold."""
    t0, t1 = summary["window_ns"]
    inside = merge((max(t0, r.start_ns + offset), min(t1, r.end_ns + offset))
                   for r in recs)
    devices = list(summary["per_device"].values())
    if not devices:
        return 0.0
    idle = 0.0
    for dev in devices:
        busy = merge(dev["intervals"])
        starts = [b[0] for b in busy]
        for a, b in inside:
            idle += (b - a) - _covered(busy, starts, a, b)
    return idle / len(devices)


def idle_share(recs, offset: float, summary):
    """Device idle inside the given spans, as a share (%) of the window;
    None without a device in the trace."""
    if summary["devices"] == 0:
        return None
    t0, t1 = summary["window_ns"]
    return 100.0 * idle_inside_ns(recs, offset, summary) / (t1 - t0)


def lanes_per_edge(stages):
    """Plan lanes over live edges of ``view.stage`` records; None where
    no plan was built (a backend without the Sum-stage kernels)."""
    lanes = sum(r.attrs.get("plan_lanes", 0) for r in stages)
    edges = sum(r.attrs.get("live_edges", 0) for r in stages)
    if lanes <= 0 or edges <= 0:
        return None
    return lanes / edges
