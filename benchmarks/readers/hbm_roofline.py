"""Share of the HBM roofline that the check program reaches: the least time
the chip could take to move the bytes a launch gathers (bytes over the peak
of peaks.json) over the device time of one launch's program in the trace.
The bound is bytes, not operations: the kernel is gathers and compares.

The bytes come from the program's own estimate for now
(keto_tpu_launch_gather_bytes); PERF.md lists moving that arithmetic into
the benchmark for the tracing issue."""

import trace_reduce


def names(args: dict) -> set[str]:
    return {args["bytes"] + "_sum", args["bytes"] + "_count"}


def read(run, bytes, match):
    launches = (run.after.value(bytes + "_count")
                - run.before.value(bytes + "_count"))
    program_s = run.trace and trace_reduce.seconds_per_launch(
        run.trace["programs"], match
    )
    if launches <= 0 or not program_s:
        return None
    per_launch = (run.after.value(bytes + "_sum")
                  - run.before.value(bytes + "_sum")) / launches
    return 100.0 * per_launch / run.peak["hbm_bytes_per_s"] / program_s
