#!/usr/bin/env python3
"""The load generator: a child of run.py that offers the cell's traffic to
the daemon over gRPC and never imports JAX, so that the serving process is
the only one that touches the chip and does not share its interpreter lock
with the callers.

A closed loop: each caller thread has a channel of its own, sends one RPC,
waits for the answer, thinks for the time the workload draws, and sends the
next. Caller t sends RPCs number t,
t + callers, t + 2 callers, ... of the workload, warm-up and window alike.
The warm-up runs unmeasured until it has lasted `warmup_s` and every caller
has had an answer; then `{"event": "window_start"}` is printed, the window
runs for `--seconds`, and one JSON line reports every RPC that was answered
inside it. Latency is from send to answer at the caller; an RPC that fails
counts as the timeout.

Beside the window's totals the line says where in the window they fell: the
window cut in halves and in quarters by the time of the answer, and the
longest time in which no answer came. They are for reading a run's spread
(run.py prints them on its `window` phase line); no metric reads them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from workload import Workload, read_json  # noqa: E402


def caller(t, callers, workload, port, timeout, stop, done, records):
    from keto_tpu.api.client import ReadClient, open_channel

    client = ReadClient(open_channel(f"127.0.0.1:{port}"))
    items = workload.items
    rpc = t
    try:
        while not stop.is_set():
            queries, expected, think_s = workload.request(rpc)
            sent = time.perf_counter()
            try:
                got = np.asarray(workload.send(client, queries, timeout), bool)
                error = None if len(got) == items else f"{len(got)} answers"
            except Exception as e:  # an RPC error is a failed RPC, not a crash
                error = f"{type(e).__name__}: {e}"
            answered = time.perf_counter()
            if error is not None:
                got = np.zeros(items, bool)
            records.append((rpc, sent, answered, error, got,
                            int((got != expected).sum())))
            done[t] += 1
            rpc += callers
            if think_s:
                stop.wait(think_s)
    finally:
        client.close()


def window_parts(answered_s, window_s: float, latency, good, n: int) -> list[dict]:
    """The window cut into `n` parts of equal length by the time of each
    RPC's answer, `answered_s` seconds into it; `latency` is the RPC's
    seconds and `good` its checks answered right. Every RPC falls into one
    part, so the parts' counts add up to the window's."""
    latency, good = np.asarray(latency), np.asarray(good)
    part = np.minimum((np.asarray(answered_s) * n / window_s).astype(int), n - 1)
    parts = []
    for k in range(n):
        here = part == k
        parts.append({
            "rpcs": int(here.sum()),
            "good_checks": int(good[here].sum()),
            "p50_ms": 1e3 * float(np.median(latency[here])) if here.any() else None,
        })
    return parts


def longest_gap(answered_s, window_s: float) -> tuple[float, float]:
    """(seconds, start) of the longest time without an answer, the window's
    two ends counted as answers: a stall shows here, wherever it falls."""
    edges = np.concatenate(([0.0], np.sort(answered_s), [window_s]))
    gaps = np.diff(edges)
    k = int(np.argmax(gaps))
    return float(gaps[k]), float(edges[k])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tuples", type=int, default=None)
    ap.add_argument("--answers", required=True,
                    help="where the window's answers are written (.npz)")
    args = ap.parse_args(argv)

    traffic = read_json(args.traffic)
    if traffic["loop"] != "closed":
        raise SystemExit(f"loop {traffic['loop']!r} is not built yet")
    workload = Workload(read_json(args.config), traffic, args.seed, args.tuples)
    callers, timeout = int(traffic["callers"]), float(traffic["timeout_s"])

    stop = threading.Event()
    done = [0] * callers
    records: list = []  # list.append is atomic; read only after the joins
    threads = [
        threading.Thread(
            target=caller, daemon=True,
            args=(t, callers, workload, args.port, timeout, stop, done, records),
        )
        for t in range(callers)
    ]
    for th in threads:
        th.start()
    warm_until = time.perf_counter() + float(traffic["warmup_s"])
    while time.perf_counter() < warm_until or not all(done):
        if not any(th.is_alive() for th in threads):
            raise SystemExit("every caller died in the warm-up")
        time.sleep(0.05)

    t0, cpu0 = time.perf_counter(), time.process_time()
    print(json.dumps({"event": "window_start"}), flush=True)
    time.sleep(args.seconds)
    t1, cpu1 = time.perf_counter(), time.process_time()
    stop.set()
    for th in threads:
        th.join(timeout + 5)
    stuck = sum(th.is_alive() for th in threads)

    window = sorted(r for r in records if t0 <= r[2] <= t1)
    latency = [timeout if r[3] else r[2] - r[1] for r in window]
    errors = [r[3] for r in window if r[3]]
    wrong_rpcs = sum(1 for r in window if not r[3] and r[5])
    answered_s = [r[2] - t0 for r in window]
    good = [0 if r[3] else workload.items - r[5] for r in window]
    gap_s, gap_at_s = longest_gap(answered_s, t1 - t0)
    np.savez(
        args.answers,
        rpc=np.array([r[0] for r in window], np.int64),
        answers=np.array([r[4] for r in window], bool).reshape(len(window), workload.items),
    )
    print(json.dumps({
        "event": "result",
        "window_s": t1 - t0,
        "cpu_s": cpu1 - cpu0,
        "attempted": len(window),
        "errors": len(errors),
        "first_error": errors[0] if errors else None,
        "wrong_rpcs": wrong_rpcs,
        "wrong_checks": sum(r[5] for r in window if not r[3]),
        "good_checks": sum(good),
        "latency_s": latency,
        "halves": window_parts(answered_s, t1 - t0, latency, good, 2),
        "quarters": window_parts(answered_s, t1 - t0, latency, good, 4),
        "longest_gap_s": gap_s,
        "longest_gap_at_s": gap_at_s,
        "callers_stuck": stuck,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
