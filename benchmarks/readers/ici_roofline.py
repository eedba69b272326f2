"""Share of the ICI roofline that the sharded check program reaches: the
least time one chip's interconnect could take to receive what a launch's
collectives bring it (collectives.launch_bytes, from the launch counters
over the window: steps, frontier and bucket slots a launch, and the
mesh's devices) over the device time of one launch's program in the
trace, per chip. The chip's bandwidth is its row of ici_peaks.json; a
device kind missing there is an error, not a default.

Every check launch of a cell served from a mesh is a sharded one, so the
launch counters are the sharded launches'. Nothing read where the window
held no sharded launch (one device, or a program older than the mesh's
counters, which run.py is not asked to refuse: see prom_ratio_optional) or
the run took no trace."""

import os

import collectives
import trace_reduce
from workload import read_json

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCHES = "keto_tpu_mesh_launches_total"
DEVICES = "keto_tpu_mesh_devices"
STEPS = "keto_tpu_launch_iterations_sum"
FRONTIER = "keto_tpu_launch_frontier_slots_total"
BUCKET = "keto_tpu_launch_bucket_slots_total"


def reads(args: dict) -> set[str]:
    return {LAUNCHES, DEVICES, STEPS, FRONTIER, BUCKET}


def ici_bytes_per_s(kind: str) -> float:
    peak = read_json(os.path.join(BENCH, "ici_peaks.json")).get(kind)
    if peak is None:
        raise KeyError(f"ici_peaks.json has no device kind {kind!r}")
    return float(peak["ici_bytes_per_s"])


def read(run, match):
    import jax

    def grew(name):
        return run.after.value(name) - run.before.value(name)

    launches = grew(LAUNCHES)
    program_s = run.trace and trace_reduce.seconds_per_launch(
        run.trace["programs"], match
    )
    if launches <= 0 or not program_s:
        return None
    per_launch = collectives.launch_bytes(
        grew(STEPS) / launches, grew(FRONTIER) / launches,
        grew(BUCKET) / launches, int(run.after.value(DEVICES)),
    )
    bandwidth = ici_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * per_launch / bandwidth / program_s
