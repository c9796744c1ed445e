"""From a profiler trace to busy time, idle gaps and time per operation.

`load` reads the newest `.xplane.pb` under a directory with jax's own
`ProfileData` and keeps plain tuples, which is also what the recorded trace
in `tests/data/` holds; `reduce` works on those tuples alone, so the same
code runs on the chip's trace and in the test.

A device plane's operation line nests: a `while` or a fusion's parent spans
the operations inside it.  Time is counted on leaves only (events that
contain no other event of their line), so nothing is counted twice, and busy
time is the union of the leaves.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start seconds, duration seconds

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SLICE_START = "bench:slice_start"      # marks the harness writes on the host
SLICE_STOP = "bench:slice_stop"
MOSAIC_MARK = "tpu_custom_call"     # a Pallas kernel's custom_call_target


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> Dict:
    """{"device": {plane: [Event]}, "host": [Event], "mosaic": [names]}.
    Device events are the operation line's; an operation is a Mosaic
    (Pallas) call where its HLO text names that custom-call target."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(newest_xplane(trace_dir))
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    mosaic = set()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                evs = device.setdefault(plane.name, [])
                for ev in line.events:
                    name = short_name(ev.name)
                    evs.append((name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9))
                    if MOSAIC_MARK in ev.name:
                        mosaic.add(name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9))
    return {"device": device, "host": host, "mosaic": sorted(mosaic)}


def short_name(name: str) -> str:
    """An operation line's event is named by its whole HLO text,
    `%sort.60 = (s32[...]) sort(...)`: the instruction's name is enough."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event of the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[Event] = []
    for i, (name, start, dur) in enumerate(evs):
        end = start + dur
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        # sorted by start, longer first: the next event is inside this one
        # exactly when it starts before this one ends
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end + 1e-12:
            continue
        out.append((name, start, dur))
    return out


def union_and_gaps(evs: Sequence[Event], t0: float, t1: float):
    """Busy seconds (union of the events inside [t0, t1]) and the idle gaps
    between them as (start, seconds)."""
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    cursor = t0
    for _, start, dur in sorted(evs, key=lambda e: e[1]):
        s, e = max(start, t0), min(start + dur, t1)
        if e <= s:
            continue
        if s > cursor:
            gaps.append((cursor, s - cursor))
        if e > cursor:
            busy += e - max(s, cursor)
            cursor = e
    if t1 > cursor:
        gaps.append((cursor, t1 - cursor))
    return busy, gaps


def host_doing(host: Sequence[Event], at: float) -> str:
    """The innermost host span open at time `at`: what the host was doing."""
    best = None
    for name, start, dur in host:
        if start <= at <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "host blocked on the device (no span)"


def reduce(trace: Dict, top: int = 10) -> Dict:
    """Busy and window seconds averaged over the device planes, time of
    Mosaic calls and of other operations, the longest idle gaps by what the
    host was doing, and the operations that took most time."""
    planes = trace["device"]
    if not planes:
        raise ValueError("the trace holds no device plane: nothing ran on "
                         "the device while it was traced")
    mosaic = set(trace["mosaic"])
    busy_sum = window_sum = mosaic_sum = other_sum = 0.0
    per_op: Dict[str, float] = {}
    all_gaps: List[Tuple[float, float]] = []
    starts = [s for name, s, _ in trace["host"] if name == SLICE_START]
    stops = [s for name, s, _ in trace["host"] if name == SLICE_STOP]
    for evs in planes.values():
        lv = leaves(evs)
        if not lv:
            continue
        # the slice runs from the harness's start mark to its stop mark: the
        # device is idle at the start mark, and that gap belongs to the slice
        t0 = min(e[1] for e in lv)
        t1 = max(e[1] + e[2] for e in lv)
        if starts and starts[0] < t0:
            t0 = starts[0]
        if stops and t0 < stops[-1] < t1:
            t1 = stops[-1]
        busy, gaps = union_and_gaps(lv, t0, t1)
        busy_sum += busy
        window_sum += t1 - t0
        all_gaps += gaps
        for name, _, dur in lv:
            per_op[name] = per_op.get(name, 0.0) + dur
            if name in mosaic:
                mosaic_sum += dur
            else:
                other_sum += dur
    n = len([1 for evs in planes.values() if evs])
    all_gaps.sort(key=lambda g: -g[1])
    idle = [[host_doing(trace["host"], s + d / 2), d]
            for s, d in all_gaps[:top]]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_sum / n, "window_s": window_sum / n,
            "mosaic_s": mosaic_sum / n, "other_s": other_sum / n,
            "longest_gap_s": all_gaps[0][1] if all_gaps else 0.0,
            "device_ops": [[k, v / n] for k, v in ops],
            "idle_gaps": idle}
