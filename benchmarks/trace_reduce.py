"""From a profiler trace (.xplane.pb) to the device's busy and idle time, the
time of the check program per launch, the operations that took most time and
the longest idle gaps.

The arithmetic is pure functions over lists of (start, duration, name) in
seconds; `read_device_events` in front of them is the only code that knows
the trace's format. On a TPU each chip is a plane `/device:TPU:<n>`, whose
line `XLA Ops` holds every operation the chip ran and whose line
`XLA Modules` holds one event per launched program.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


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


def read_device_events(trace_dir: str) -> dict:
    """{plane: {line: [(start_s, duration_s, name)]}} of the device planes of
    the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return {}
    planes = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
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
    return planes


def reduce(planes: dict, window_s: float | None = None) -> dict | None:
    """The summary the readers and the result line use, averaged over the
    chips that ran anything. `window_s` is the traced window by the host's
    clock; without it the window is from the first to the last device
    event, which leaves out idle time at either end. None if no operation
    ran on any device."""
    chips = [p for p in planes.values() if p.get(OPS_LINE)]
    if not chips:
        return None
    starts = [e[0] for p in chips for e in p[OPS_LINE]]
    ends = [e[0] + e[1] for p in chips for e in p[OPS_LINE]]
    t0, t1 = min(starts), max(ends)
    event_span_s = t1 - t0
    window_s = window_s or event_span_s
    busy = [busy_seconds(p[OPS_LINE]) for p in chips]
    busy_s = sum(busy) / len(chips)
    fullest = max(chips, key=lambda p: busy_seconds(p[OPS_LINE]))
    return {
        "window_s": window_s,
        "event_span_s": event_span_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": programs(
            [e for p in chips for e in p.get(MODULES_LINE, [])]
        ),
        "device_ops": top_ops(
            [e for p in chips for e in self_times(p[OPS_LINE])], 10
        ),
        "idle_gaps": [
            [f"unattributed@{s - t0:.3f}s", d]
            for s, d in idle_gaps(fullest[OPS_LINE], t0, t1, 5)
        ],
    }
