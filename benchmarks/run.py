#!/usr/bin/env python3
"""benchmarks/run.py: one cell of the benchmark, served from the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip and serves: it makes the cell's data set from
the seed, loads it behind Registry + Daemon as `keto-tpu serve` composes
them (the way chip_smoke.phase_load does), and starts benchmarks/loadgen.py
as a child, which offers the traffic over gRPC and stays off JAX. It reads
the daemon's counters before and after the child's window, with --trace 1
takes a profiler trace inside it, compares answers with the plain reference,
and prints phase lines and, last, the one result line of the contract.

Everything that belongs to one cell is data found by name: BENCHMARK.json
names the cell's configuration and traffic mix and the metrics;
configs/<config>.json, traffic/<traffic>.json, metrics/<metric>.json,
generators/<name>.py and readers/<name>.py hold them. This file and
loadgen.py name none of them.

The command starts that process as a child and starts it once more if it
had to compile: a process that has compiled its check program serves
2 to 3% slower than one that read it from the compile cache (PERF.md, PR 28),
so every window is served by a process that read all its programs. Both
set-ups count in `setup_s`.

Without a TPU it refuses to run. The one exception is the rehearsal of the
on-chip-measurement guide: JAX_PLATFORMS=cpu AND --tuples <a small size>.
A rehearsal prints counts and host clocks of the CPU under the same names,
marked by the device it reports, and takes no trace.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

OUT = os.path.join(HERE, "out")
TRACE_START_S = 3.0  # into the window
TRACE_LENGTH_S = 3.0  # longer is too large to reduce inside a run
TRACE_MARGIN_S = 0.05  # of trace before and after the marked window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"  # compiled, and kept
AGAIN = 75  # exit code of a serving process that compiled: start it again


class BenchFailure(Exception):
    """The run cannot give a result."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchFailure(f"BENCHMARK.json has no {what} named {name!r}")


def require_device(chips: int, rehearsal_ok: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not rehearsal_ok:
        raise BenchFailure(
            f"no TPU: jax.devices() reports {device}. A CPU run is only a "
            "rehearsal: set JAX_PLATFORMS=cpu and pass --tuples"
        )
    if device["count"] < chips:
        raise BenchFailure(
            f"the cell needs {chips} chip(s), jax.devices() has {device['count']}"
        )
    return device


def device_peak(kind: str) -> dict:
    """The device's row of peaks.json. An unknown device is an error, not a
    default."""
    from workload import read_json

    peak = read_json(os.path.join(HERE, "peaks.json")).get(kind)
    if peak is None:
        raise BenchFailure(f"peaks.json has no device kind {kind!r}")
    return peak


def serve(config: dict, cols):
    """Registry -> bulk_load -> Daemon.start() -> the device mirror, every
    default plane on. Returns the daemon, the engine and the phase clocks."""
    from keto_tpu.api.daemon import Daemon
    from keto_tpu.config import Config
    from keto_tpu.registry import Registry

    t0 = time.perf_counter()
    registry = Registry(Config(config["serve_config"]))
    registry.relation_tuple_manager().bulk_load(cols)
    t1 = time.perf_counter()
    daemon = Daemon(registry)
    daemon.start()
    t2 = time.perf_counter()
    engine = registry.check_engine()
    state = engine._ensure_state()
    t3 = time.perf_counter()
    if state.snapshot.n_tuples != len(cols):
        daemon.stop()
        raise BenchFailure("the device mirror does not hold every loaded tuple")
    return daemon, engine, {"bulk_load_s": t1 - t0, "snapshot_build_s": t3 - t2}


class Tracer:
    """A profiler trace that holds a window of TRACE_LENGTH_S seconds,
    TRACE_START_S into the run's window (both cut to fit a short one), taken
    by a timer thread. The window is marked inside the trace, by an
    annotation held open while the thread sleeps: it lands in a host plane
    on the clock of the device planes, and trace_reduce clips the device's
    events to it. The session is TRACE_MARGIN_S longer at either end: on
    the chip the device's events begin and end up to 5 ms inside the host's
    session, and the profiler cuts the event of a launch in flight at the
    session's edge, so without the margin the window's ends read as idle
    and a cut launch as a whole, short one (PERF.md, PR 28). The host's own
    clock around the same sleep is printed beside it and decides nothing."""

    def __init__(self, seconds: float):
        self.dir = os.path.join(OUT, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.start_after = min(TRACE_START_S, seconds / 4)
        self.length = min(TRACE_LENGTH_S, seconds / 2)
        self.host_window_s = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def begin(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax
        import trace_reduce

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the device planes are what is read,
        options.host_tracer_level = 1  # and the host plane's one annotation
        time.sleep(self.start_after)
        jax.profiler.start_trace(self.dir, profiler_options=options)
        try:
            time.sleep(TRACE_MARGIN_S)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
                time.sleep(self.length)
            self.host_window_s = time.perf_counter() - t0
            time.sleep(TRACE_MARGIN_S)
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> dict:
        self._thread.join()
        try:
            summary = window_summary(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return {**summary, "host_window_s": self.host_window_s}


def window_summary(trace_dir: str) -> dict:
    """trace_reduce's summary of the one marked window of the trace under
    `trace_dir`. A trace without the mark, or with no operation inside it,
    gives no device time, and the host's clock is no stand-in for it."""
    import trace_reduce

    planes, windows = trace_reduce.read_trace(trace_dir)
    if len(windows) != 1:
        raise BenchFailure(
            f"the trace holds {len(windows)} {trace_reduce.WINDOW_EVENT} "
            "events, not the one that marks the traced window"
        )
    summary = trace_reduce.reduce(planes, windows[0])
    if summary is None:
        raise BenchFailure(
            "no operation ran on the device inside the traced window"
        )
    return summary


def device_times(trace: dict) -> dict:
    """`busy_s` and `window_s` of the result line's `device`, refused where
    the contract refuses them."""
    busy_s, window_s = trace["busy_s"], trace["window_s"]
    if not 0 < busy_s <= window_s:
        raise BenchFailure(
            f"device.busy_s {busy_s!r} is not above 0 and at most "
            f"device.window_s {window_s!r}"
        )
    return {"busy_s": busy_s, "window_s": window_s}


class Collections:
    """The collector's full collections in this process since this object
    was made, and the seconds they held the interpreter, counted through
    `gc.callbacks`. It observes: it neither starts a collection nor changes
    a threshold, which are the program's to choose. A full collection of
    the serving process stops every handler at once (PERF.md section 5), so
    a run's count belongs beside its rate."""

    def __init__(self):
        self.count, self.seconds, self._began = 0, 0.0, None
        gc.callbacks.append(self._on_collection)

    def _on_collection(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._began
            self._began = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on_collection)
        return {"full_collections": self.count, "full_collection_s": self.seconds}


def run_child(args, config_path, traffic_path, port, answers, on_window):
    """Start the load generator, call `on_window()` when its window opens,
    and return its result line."""
    cmd = [
        sys.executable, os.path.join(HERE, "loadgen.py"),
        "--config", config_path, "--traffic", traffic_path,
        "--port", str(port), "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--answers", answers,
    ]
    if args.tuples is not None:
        cmd += ["--tuples", str(args.tuples)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # it never imports JAX anyway
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    result = None
    try:
        for line in child.stdout:
            event = json.loads(line)
            if event.get("event") == "window_start":
                on_window()
            elif event.get("event") == "result":
                result = event
    finally:
        if child.poll() is None and result is None:
            child.kill()
        rc = child.wait()
    if rc != 0 or result is None:
        raise BenchFailure(f"the load generator ended with code {rc}")
    return result


def reference_differs(engine, workload, answers_path: str, seed: int,
                      samples: int) -> int:
    """How many of `samples` of the window's checks, drawn by seed, the
    daemon answered otherwise than the plain reference does."""
    import numpy as np

    with np.load(answers_path) as saved:
        rpcs, answers = saved["rpc"], saved["answers"]
    if not len(rpcs):
        return 0
    rng = np.random.default_rng([seed, 1])
    differ = 0
    rows = rng.integers(0, len(rpcs), samples)
    for row, item in zip(rows, rng.integers(0, answers.shape[1], len(rows))):
        query = workload.request(int(rpcs[row]))[0][item]
        want = engine.reference.check_relation_tuple(query, 0).allowed
        differ += bool(answers[row, item]) != bool(want)
    return differ


def compared(child: dict, ref_differs: int, failed_batches: dict,
             breaker: float) -> dict:
    """Every number that decides `correct`, as [number, limit]; each may be
    at most its limit, and every limit is 0: an answer is exact or wrong."""
    return {
        "wrong_checks": [child["wrong_checks"], 0],
        "reference_differs": [ref_differs, 0],
        "failed_device_batches": [sum(failed_batches.values()), 0],
        "breaker_state": [breaker, 0],
    }


def verdict(child: dict, ref_differs: int, failed_batches: dict,
            breaker: float) -> tuple[bool, int]:
    """(`correct`, `failed`) of the result line. Wrong answers, failed device
    batches and an open breaker make a run incorrect; an RPC that erred is a
    failed RPC."""
    failed = int(child["errors"] + child["wrong_rpcs"] + child["callers_stuck"])
    numbers = compared(child, ref_differs, failed_batches, breaker)
    correct = (
        all(number <= limit for number, limit in numbers.values())
        and child["attempted"] > 0
    )
    return bool(correct), failed


def read_metrics(metrics: list, cell: str, run) -> dict:
    """Every metric of one of BENCHMARK.json's lists that this cell reports
    and whose reader (metrics/<name>.json) found something to read."""
    from workload import load_module, read_json

    out = {}
    for metric in metrics:
        if cell not in metric.get("workloads", [cell]):
            continue
        spec = read_json(os.path.join(HERE, "metrics", metric["name"] + ".json"))
        if run.rehearsal and spec.get("needs_chip"):
            continue  # no CPU number under the name of a device metric
        reader = load_module("readers", spec["reader"])
        reads = getattr(reader, "names", lambda args: ())(spec["args"])
        unknown = [n for n in reads if not run.after.knows(n)]
        if unknown:
            raise BenchFailure(
                f"metric {metric['name']} reads {unknown}, which the daemon "
                "does not expose: a counter was renamed or removed"
            )
        value = reader.read(run, **spec["args"])
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    """The serving process. Returns AGAIN, before any traffic, where it had
    to compile a program that the compile cache now holds."""
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tuples", type=int, default=None,
                    help="rehearsal size; only with JAX_PLATFORMS=cpu")
    ap.add_argument("--started", type=float, default=None,
                    help="set by supervise(): its clock when the command began")
    ap.add_argument("--again", action="store_true",
                    help="set by supervise(): the process before this one compiled")
    args = ap.parse_args(argv)
    if args.started is not None:
        t_start = args.started  # perf_counter is one clock for every process

    from workload import WARM_STREAM, Workload, read_json

    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = named(bench["workloads"], args.workload, "workload")
    config_path = os.path.join(
        ROOT, named(bench["configs"], cell["config"], "configuration")["file"]
    )
    traffic_path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    config, traffic = read_json(config_path), read_json(traffic_path)

    # before any work: without the program and the chip there is no run
    from keto_tpu.compile_cache import ensure_compile_cache

    device = require_device(
        cell["chips"],
        os.environ.get("JAX_PLATFORMS") == "cpu" and args.tuples is not None,
    )
    rehearsal = device["platform"] != "tpu"
    if args.tuples is not None and not rehearsal:
        raise BenchFailure("--tuples is for the CPU rehearsal only")
    peak = None if rehearsal else device_peak(device["kind"])
    import jax

    compiles: list[str] = []  # one entry for every program built or loaded
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_: name == COMPILE_EVENT and compiles.append(name)
    )
    kept: list[str] = []  # one entry for every program compiled here and cached
    jax.monitoring.register_event_listener(
        lambda name, **_: name == CACHE_WRITE_EVENT and kept.append(name)
    )
    cache_dir = ensure_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    emit("device", rehearsal=rehearsal, compile_cache=cache_dir, **device)

    t0 = time.perf_counter()
    workload = Workload(config, traffic, args.seed, args.tuples)
    cols = workload.columns()
    synth_s = time.perf_counter() - t0
    daemon, engine, clocks = serve(config, cols)
    try:
        t0 = time.perf_counter()
        for k, n in enumerate(traffic["warm_launch_sizes"]):
            # every launch shape the traffic can form, before any caller
            engine.check_batch(workload.draw(WARM_STREAM + k, n)[0])
        warm_launch_s = time.perf_counter() - t0
        emit("serving", tuples=len(cols), synth_s=synth_s,
             warm_launch_s=warm_launch_s, programs=len(compiles),
             programs_compiled=len(kept), again=args.again, **clocks)
        if kept and not args.again:
            return AGAIN  # the finally below stops the daemon

        from scrape import Scrape

        marks = {}
        tracer = Tracer(args.seconds) if args.trace and not rehearsal else None

        def on_window():
            marks["setup_s"] = time.perf_counter() - t_start
            marks["programs"] = len(compiles)
            marks["before"] = Scrape.of(daemon.metrics_port)
            marks["collections"] = Collections()
            if tracer is not None:
                tracer.begin()

        answers = os.path.join(OUT, "answers.npz")
        child = run_child(args, config_path, traffic_path, daemon.read_port,
                          answers, on_window)
        collected = marks["collections"].close()
        after = Scrape.of(daemon.metrics_port)
        emit("window", **{k: child[k] for k in (
            "window_s", "attempted", "good_checks", "halves", "quarters",
            "longest_gap_s", "longest_gap_at_s")})
        compiles_in_window = len(compiles) - marks["programs"]
        trace = tracer.reduce() if tracer else None

        t0 = time.perf_counter()
        samples = int(config["reference_samples"])
        ref_differs = reference_differs(engine, workload, answers, args.seed, samples)
        os.remove(answers)
        failed_batches = after.by_label("keto_tpu_check_batch_failed_total", "cause")
        breaker = after.value("keto_tpu_breaker_state")
        correct, failed = verdict(child, ref_differs, failed_batches, breaker)
        emit(
            "judged", reference_s=time.perf_counter() - t0,
            reference_samples=samples, reference_differs=ref_differs,
            wrong_checks=child["wrong_checks"], rpc_errors=child["errors"],
            first_error=child["first_error"], callers_stuck=child["callers_stuck"],
            check_batch_failed_total=failed_batches, breaker_state=breaker,
            host_fallback_total=after.by_label("keto_tpu_host_fallback_total", "cause"),
            compiles_in_window=compiles_in_window,
            window_s=child["window_s"], **collected,
            cpu_count=os.cpu_count(), cpus_allowed=sorted(os.sched_getaffinity(0)),
        )
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()
        )
    finally:
        daemon.stop()

    run = SimpleNamespace(
        before=marks["before"], after=after, child=child, trace=trace, peak=peak,
        rehearsal=rehearsal,
        values={
            "setup_s": marks["setup_s"],
            "gen_cpu_share": child["cpu_s"] / child["window_s"],
            "compiles_in_window": compiles_in_window,
            **clocks,
        },
    )
    line = {
        "correct": correct, "attempted": child["attempted"], "failed": failed,
        "metrics": read_metrics(
            bench["per_layer" if args.trace else "end_to_end"], args.workload, run
        ),
    }
    if trace is not None:
        emit("trace", **{k: v for k, v in trace.items()
                         if k not in ("device_ops", "idle_gaps")})
        device.update(device_times(trace))
        line["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    line["device"] = device
    line["compared"] = compared(child, ref_differs, failed_batches, breaker)
    for name, (number, limit) in line["compared"].items():
        print(f"compared {name}: {number} (limit {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def supervise(argv: list, program=None) -> int:
    """Run the serving process (`program`: this file) as a child of this
    one, which stays off JAX and so off the chip, and once more if it ends
    with AGAIN. The children write to this process's output; the last line
    is the second's."""
    started = time.perf_counter()
    program = program or [sys.executable, os.path.abspath(__file__)]
    code = AGAIN
    for more in ([], ["--again"]):
        if code != AGAIN:
            break
        child = subprocess.Popen(
            [*program, *argv, "--started", repr(started), *more]
        )
        try:
            code = child.wait()
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
    return 1 if code == AGAIN else code


def serve_once(argv=None) -> int:
    try:
        return main(argv)
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    if "--started" in sys.argv:
        sys.exit(serve_once())
    # ended from outside, this process takes its child along (the finally)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(supervise(sys.argv[1:]))
