"""The device-feed account: what the host was doing whenever the chip had
nothing queued.

A profiler trace says how long the device sat idle, not why, and only for
the few seconds it covers. This account is kept by the engine itself, over
every check launch (the batcher's and the direct BatchCheck's alike), from
clock reads the submit and resolve phases already take:

  - `dispatched()` at the end of a launch's dispatch. If nothing was in
    flight the device queue was empty since `mark`; that starved interval
    is charged walking back along THIS launch's own timeline: its
    dispatch, its assemble, its earliest rider's wait in the batcher, that
    rider's time in the handler before it, and whatever lies before the
    request arrived to `starved_no_request`.
  - `ready()` right after a launch's readback. Launches run in order on
    one device queue, so a readback also proves done every launch whose
    dispatch had ENDED before this one's began (a resolver thread that
    wakes late, or a launch the watchdog abandoned, cannot wedge the
    count; two launches dispatched at once from two threads are in an
    order the host cannot know, and each waits for its own readback), and
    `ready - max(dispatched, latest ready before it)` is the program's
    own time without the wait behind earlier launches.

Every second since the first launch lands in exactly one of
observability.DEVICE_FEED_STATES. Host clocks only: no device contact, no
readback of its own. Launches of other verbs (expand, list, filter) are
not counted yet: while one of those runs alone the account reads starved.
"""

from __future__ import annotations

import threading

from ..observability import DEVICE_FEED_STATES


class DeviceFeed:
    """One engine's account. Thread-safe; times are time.perf_counter()
    seconds handed in by the caller, so a test scripts them."""

    def __init__(self, metrics=None):
        self.metrics = metrics
        self.seconds = dict.fromkeys(DEVICE_FEED_STATES, 0.0)
        self._lock = threading.Lock()
        self._seq = 0
        self._in_flight: dict[int, float] = {}  # launch -> its dispatch's end
        self._mark = None  # everything before it is charged
        self._last_ready = 0.0

    def _charge(self, state: str, seconds: float) -> None:
        if seconds > 0.0:
            self.seconds[state] += seconds
            if self.metrics is not None:
                self.metrics.device_feed_state[state].inc(seconds)

    def dispatched(self, t_submit: float, t_launch: float, t_done: float,
                   riders=()) -> tuple[tuple, float]:
        """A launch whose dispatch ran from `t_launch` to `t_done` is in
        the device queue. Returns (its token for `ready`, the starved
        seconds it ended). `riders` are the launch's RequestTraces: read
        only when the queue was empty."""
        with self._lock:
            self._seq += 1
            token = (self._seq, t_launch, t_done)
            was_empty = not self._in_flight
            self._in_flight[self._seq] = t_done
            if self._mark is None:
                self._mark = t_done
                return token, 0.0
            mark = self._mark
            self._mark = max(mark, t_done)
            if not was_empty:
                self._charge("busy", t_done - mark)
                return token, 0.0
            queued = min(
                (rt.enqueued for rt in riders if rt.enqueued is not None),
                default=None,
            )
            arrived = min((rt.arrived for rt in riders), default=None)
            upper = t_done
            for state, lower in (
                ("starved_dispatch", t_launch),
                ("starved_assemble", t_submit),
                ("starved_queue", queued),
                ("starved_decode", arrived),
            ):
                if lower is not None:
                    lower = max(min(lower, upper), mark)
                    self._charge(state, upper - lower)
                    upper = lower
            self._charge("starved_no_request", upper - mark)
            return token, max(0.0, t_done - mark)

    def ready(self, token, t_ready: float) -> float:
        """The launch `token` stands for was read back at `t_ready`.
        Returns its estimated device service seconds."""
        seq, t_launch, t_done = token
        with self._lock:
            service = max(0.0, t_ready - max(t_done, self._last_ready))
            self._last_ready = max(self._last_ready, t_ready)
            if self._in_flight:
                # busy until now, whichever launches this readback ends
                self._charge("busy", t_ready - self._mark)
                self._mark = max(self._mark, t_ready)
            self._in_flight = {
                k: done
                for k, done in self._in_flight.items()
                if k != seq and done > t_launch
            }
        if self.metrics is not None:
            self.metrics.launch_device_seconds.observe(service)
        return service
