"""Profiler traces reduced to the numbers the per-layer metrics read.

A trace is first normalised to plain data (so a small recorded trace can be
kept as a test fixture):

    {"window_ns": [0, length], "start_unix_ns": window start (unix ns),
     "planes": [{"name": ..., "lines": [{"name": ...,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

with every time relative to the window's start: the span in which the
profiler recorded events (:func:`recorded_span`).  Device
planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per
operation run on the chip and their ``XLA Modules`` line one event per
program execution.  Host threads are the lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str) -> dict:
    """Normalise the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    planes, start = [], None
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
        lines = [{"name": line.name,
                  "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    if start is None:
        raise RuntimeError("the trace has no profile start time")
    return recorded_span({"start_unix_ns": start, "planes": planes})


def recorded_span(trace: dict) -> dict:
    """Set the window to the span in which the profiler recorded events,
    on any plane.  The session's own start and stop times lie outside it
    (on a TPU v5e some 50 ms before the first event and some 230 ms after
    the last, with nothing recorded on host or device there), so they would
    count untraced time as idle."""
    events = [(e[1], e[1] + e[2]) for p in trace["planes"]
              for line in p["lines"] for e in line["events"]]
    if not events:
        raise RuntimeError("the trace recorded no event")
    lo = min(s for s, _ in events)
    hi = max(e for _, e in events)
    for p in trace["planes"]:
        for line in p["lines"]:
            for e in line["events"]:
                e[1] -= lo
    trace["window_ns"] = [0, hi - lo]
    trace["start_unix_ns"] += lo
    return trace


def device_planes(trace: dict) -> list:
    return sorted((p for p in trace["planes"]
                   if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, duration)`` intervals within
    ``[lo, hi)``."""
    spans = sorted((max(s, lo), min(s + d, hi)) for s, d in intervals
                   if s < hi and s + d > lo)
    total, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: int, hi: int) -> list:
    """``[(start, length)]`` of the stretches of ``[lo, hi)`` that no
    interval covers."""
    gaps, cur = [], lo
    for s, d in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, hi) - cur))
        cur = max(cur, s + d)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi - cur))
    return [g for g in gaps if g[1] > 0]


def busy_ns(trace: dict) -> list:
    """Busy time of each chip: the union of its operations' intervals."""
    lo, hi = trace["window_ns"]
    return [union_ns([(e[1], e[2]) for e in line_events(p, OPS_LINE)], lo, hi)
            for p in device_planes(trace)]


_OPCODE = re.compile(r"[\]\})] ([a-z][\w\-]*)\(")


def op_name(event_name: str) -> str:
    """An operation's own name: ``fusion.37`` of the event named
    ``%fusion.37 = s32[...] fusion(...), ...`` (the whole HLO line)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_label(event_name: str) -> str:
    """``fusion.37 (fusion)``: the name and the HLO opcode."""
    rest = event_name.split(" = ", 1)
    if len(rest) < 2:
        return event_name
    m = _OPCODE.search(rest[1])
    return f"{op_name(event_name)} ({m.group(1)})" if m else op_name(
        event_name)


def op_totals(trace: dict, line_name: str = OPS_LINE) -> dict:
    """{operation name: (total ns, count)} over every chip."""
    out: dict = {}
    for p in device_planes(trace):
        for name, _s, d in line_events(p, line_name):
            key = op_name(name)
            tot, cnt = out.get(key, (0, 0))
            out[key] = (tot + d, cnt + 1)
    return out


def matching(totals: dict, pattern: str) -> tuple:
    """(total ns, count) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    tot = cnt = 0
    for name, (t, c) in totals.items():
        if rx.search(name):
            tot += t
            cnt += c
    return tot, cnt


def host_events(trace: dict) -> list:
    """``[(name, start, duration)]`` of every host-thread event."""
    out = []
    for p in trace["planes"]:
        if p["name"] != "/host:CPU":
            continue
        for line in p["lines"]:
            out.extend((e[0], e[1], e[2]) for e in line["events"] if e[2] > 0)
    return out


def label_gaps(trace: dict, gaps: list, pending=None, top: int = 10) -> list:
    """The ``top`` longest gaps, each ``[label, seconds]``.  A gap in which
    no request waited (``pending(start, end)`` is False) is "no request
    waiting"; otherwise it takes the name of the host event that overlaps
    it most, or "host: no traced event"."""
    events = host_events(trace)
    out = []
    for s, d in sorted(gaps, key=lambda g: -g[1])[:top]:
        e = s + d
        if pending is not None and not pending(s, e):
            out.append(["no request waiting", d / 1e9])
            continue
        best, best_ov = None, 0
        for name, hs, hd in events:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([f"host: {best}" if best else "host: no traced event",
                    d / 1e9])
    return out
