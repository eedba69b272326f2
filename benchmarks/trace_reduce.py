"""From a profiler trace (.xplane.pb) to the device's busy and idle time, the
time of the check program per launch, the operations that took most time and
the longest idle gaps.

The arithmetic is pure functions over lists of (start, duration, name) in
seconds; `read_trace` in front of them is the only code that knows the
trace's format. On a TPU each chip is a plane `/device:TPU:<n>`, whose line
`XLA Ops` holds every operation the chip ran and whose line `XLA Modules`
holds one event per launched program.

The traced window is marked inside the trace: run.py holds one
`TraceAnnotation` named `bench.window` open while it sleeps, and the event
lands in a `/host:*` plane of the same file, on the clock the device planes
use. `reduce` works on that window alone: operations are clipped to its
edges, so busy time cannot pass it, and a launch that an edge cuts is
counted apart and left out of the programs' times.
"""

from __future__ import annotations

import glob
import math
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE_PREFIX = "/host:"
WINDOW_EVENT = "bench.window"


def merge(intervals):
    """Sorted disjoint (start, end) covering the same time as `intervals`
    of (start, duration, ...)."""
    out: list[list[float]] = []
    for start, end in sorted((e[0], e[0] + e[1]) for e in intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(events, w0: float, w1: float):
    """The part of each (start, duration, name) that lies inside [w0, w1];
    an event with nothing inside goes."""
    out = []
    for start, duration, name in events:
        s, e = max(start, w0), min(start + duration, w1)
        if e > s:
            out.append((s, e - s, name))
    return out


def whole_launches(modules, w0: float, w1: float):
    """(the events of a modules line that lie wholly inside [w0, w1], how
    many more an edge of it cuts). A cut launch would read as a short one."""
    touching = [e for e in modules if e[0] < w1 and e[0] + e[1] > w0]
    whole = [e for e in touching if w0 <= e[0] and e[0] + e[1] <= w1]
    return whole, len(touching) - len(whole)


def busy_seconds(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def idle_share(intervals, window_s: float) -> float:
    return 1.0 - busy_seconds(intervals) / window_s


def self_times(events):
    """The same events with the time of the events nested inside each taken
    out of it: a loop or a conditional encloses the operations of its body,
    and keeps only the time that none of them covers."""
    out: list[list] = []
    open_: list[tuple[float, int]] = []  # (end, index in out), innermost last
    for start, duration, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][0] <= start:
            open_.pop()
        if open_:
            out[open_[-1][1]][1] -= duration
        out.append([start, duration, name])
        open_.append((start + duration, len(out) - 1))
    return [(s, max(d, 0.0), n) for s, d, n in out]


def top_ops(events, k: int = 10, name_chars: int = 96):
    """[name, seconds] of the k names with most summed time. A name in the
    trace is the operation's whole HLO text: its head is kept."""
    total: dict[str, float] = {}
    for _, duration, name in events:
        name = name[:name_chars]
        total[name] = total.get(name, 0.0) + duration
    return [[n, s] for n, s in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(intervals, start: float, end: float, k: int = 5):
    """(start, seconds) of the k longest stretches of [start, end] in which
    nothing ran, longest first."""
    gaps, at = [], start
    for s, e in merge(intervals):
        if s > at:
            gaps.append((at, min(s, end) - at))
        at = max(at, e)
    if end > at:
        gaps.append((at, end - at))
    return sorted((g for g in gaps if g[1] > 0), key=lambda g: -g[1])[:k]


def programs(modules) -> dict:
    """{program name: [launches, seconds]} of the events of a modules line."""
    out: dict[str, list] = {}
    for _, duration, name in modules:
        entry = out.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += duration
    return out


def seconds_per_launch(programs: dict, match: str) -> float | None:
    """Mean device time of one launch of the programs whose name matches."""
    pattern = re.compile(match)
    hits = [v for name, v in programs.items() if pattern.search(name)]
    launches = sum(v[0] for v in hits)
    return sum(v[1] for v in hits) / launches if launches else None


def read_trace(trace_dir: str) -> tuple[dict, list]:
    """({plane: {line: [(start_s, duration_s, name)]}} of the device planes,
    [(start_s, end_s)] of every `bench.window` event of the host planes) of
    the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return {}, []
    planes, windows = {}, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith(HOST_PLANE_PREFIX):
            windows += [
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for line in plane.lines
                for ev in line.events
                if ev.name == WINDOW_EVENT
            ]
        if not DEVICE_PLANE.match(plane.name):
            continue
        planes[plane.name] = {
            line.name: [
                (ev.start_ns * 1e-9, ev.duration_ns * 1e-9, ev.name)
                for ev in line.events
            ]
            for line in plane.lines
            if line.name in (OPS_LINE, MODULES_LINE)
        }
    return planes, windows


def reduce(planes: dict, window: tuple[float, float] | None = None) -> dict | None:
    """The summary the readers and the result line use, averaged over the
    chips that ran anything in the window. `window` is (start, end) on the
    events' clock: operations are clipped to it, idle time at either end of
    it is a gap like any other, and a launch that an edge cuts counts in
    `launches_cut` and not in `programs`. `event_lead_s` and `event_tail_s`
    say how far the device's events reach beyond it: on a device that is
    never idle both are above 0, or the session did not cover the window.
    Without a window it is from the first to the last device event, which
    leaves out idle time at either end. None if no operation ran on any
    device inside the window."""
    chips = [p for p in planes.values() if p.get(OPS_LINE)]
    if not chips:
        return None
    first = min(e[0] for p in chips for e in p[OPS_LINE])
    last = max(e[0] + e[1] for p in chips for e in p[OPS_LINE])
    w0, w1 = window or (first, last)
    l0, l1 = window or (-math.inf, math.inf)  # no window cuts no launch
    chips = [
        (clip(p[OPS_LINE], w0, w1), *whole_launches(p.get(MODULES_LINE, []), l0, l1))
        for p in chips
    ]
    chips = [chip for chip in chips if chip[0]]
    if not chips:
        return None
    # min: the clipped intervals lie inside the window, and their summed
    # lengths can pass its length by a rounding of the last digit
    busy = [min(busy_seconds(ops), w1 - w0) for ops, _, _ in chips]
    busy_s = sum(busy) / len(chips)
    fullest = chips[busy.index(max(busy))][0]
    return {
        "window_s": w1 - w0,
        "event_span_s": last - first,
        "event_lead_s": w0 - first,
        "event_tail_s": last - w1,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (w1 - w0),
        "programs": programs([e for _, whole, _ in chips for e in whole]),
        "launches_cut": sum(cut for _, _, cut in chips),
        "device_ops": top_ops(
            [e for ops, _, _ in chips for e in self_times(ops)], 10
        ),
        "idle_gaps": [
            [f"unattributed@{s - w0:.3f}s", d]
            for s, d in idle_gaps(fullest, w0, w1, 5)
        ],
    }
