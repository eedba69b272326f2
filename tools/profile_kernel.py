"""Per-phase TPU profiling harness for the check kernel.

Times each BFS step phase as a standalone jitted function on the bench
dataset's real tables/shapes, so a regression in one phase is visible
without reading an XLA trace. Run on the bench machine:

    python tools/profile_kernel.py [--platform cpu] [--frontier 16384]

Prints one JSON line per phase: {"phase", "ms", "shapes"} plus a
"step_total" line and the table/probe stats that drive the costs
(dh_probes / rh_probes multiply every probe gather's width).

Timing discipline: a blocked call includes the launch-to-readback
latency, so per-call blocking measures that, not the phase. Phases are
timed with a DEEP async-dispatch loop (block once at the end) and the
amortized per-call cost reported; the blocked one-shot latency is
reported separately for the full kernel.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, n=100, window=8, **kw):
    """Amortized per-call ms with a BOUNDED in-flight window: an
    unbounded dispatch queue holds n result buffers on-device."""
    out = fn(*args, **kw)
    jax_block(out)
    t0 = time.perf_counter()
    pending = []
    for _ in range(n):
        pending.append(fn(*args, **kw))
        if len(pending) > window:
            jax_block(pending.pop(0))
    jax_block(pending)
    return (time.perf_counter() - t0) / n * 1e3, out


def jax_block(out):
    import jax

    jax.block_until_ready(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", choices=("auto", "cpu"), default="auto")
    ap.add_argument("--frontier", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=4096)
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import functools

    import jax.numpy as jnp
    import numpy as np

    import bench
    from keto_tpu.config import Config
    from keto_tpu.engine.snapshot import build_snapshot
    from keto_tpu.engine.kernel import (
        check_kernel,
        dedupe_phase,
        expand_phase,
        flag_phase,
        kernel_static_config,
        probe_phase,
        seed_state,
        snapshot_tables,
    )

    namespaces, tuples, queries = bench.build_dataset()
    cfg = Config({"limit": {"max_read_depth": 5}})
    cfg.set_namespaces(namespaces)
    snap = build_snapshot(tuples, namespaces)
    tables = snapshot_tables(snap)
    statics = kernel_static_config(snap, 5, args.frontier)
    print(
        json.dumps(
            {
                "phase": "table_stats",
                "dh_probes": statics["dh_probes"],
                "rh_probes": statics["rh_probes"],
                "K": statics["K"],
                "max_steps": statics["max_steps"],
                "dh_cap": int(snap.dh_obj.shape[0]),
                "rh_cap": int(snap.rh_obj.shape[0]),
                "n_edges": int(tables["e_pack"].shape[0]),
                "device": str(jax.devices()[0]),
            }
        )
    )

    B, F = args.batch, args.frontier
    # encode the bench queries exactly as the engine does
    from keto_tpu.engine.delta import SnapshotView

    view = SnapshotView(snap)
    q_obj = np.zeros(B, dtype=np.int32)
    q_rel = np.zeros(B, dtype=np.int32)
    q_skind = np.zeros(B, dtype=np.int32)
    q_sa = np.full(B, -2, dtype=np.int32)
    q_sb = np.zeros(B, dtype=np.int32)
    q_valid = np.zeros(B, dtype=bool)
    for i, t in enumerate(queries[:B]):
        node = view.encode_node(t.namespace, t.object, t.relation)
        q_obj[i], q_rel[i] = node
        s = view.encode_subject(t)
        if s is not None:
            q_skind[i], q_sa[i], q_sb[i] = s
        q_valid[i] = True
    q_depth = np.full(B, 5, dtype=np.int32)
    qd = {k: jnp.asarray(v) for k, v in dict(
        q_obj=q_obj, q_rel=q_rel, q_depth=q_depth, q_skind=q_skind,
        q_sa=q_sa, q_sb=q_sb, q_valid=q_valid,
    ).items()}

    st = seed_state(qd["q_obj"], qd["q_rel"], qd["q_depth"], qd["q_valid"], F)
    live = jnp.arange(F) < st.n_tasks
    obj, rel, depth, q = st.t_obj, st.t_rel, st.t_depth, st.t_q
    ctx = st.t_ctx
    isl_state = (st.isl_parent, st.isl_pid, st.n_isl)

    n_cr = statics["n_config_rels"]

    f_flag = jax.jit(functools.partial(flag_phase, n_config_rels=n_cr))
    ms, _ = timed(f_flag, tables, obj, rel, live)
    print(json.dumps({"phase": "flag", "ms": round(ms, 3)}))

    f_probe = jax.jit(
        functools.partial(
            probe_phase,
            dh_probes=statics["dh_probes"], has_delta=statics["has_delta"],
        )
    )
    ms, _ = timed(
        f_probe, tables, obj, rel, qd["q_skind"][q], qd["q_sa"][q],
        qd["q_sb"][q], depth, live,
    )
    print(json.dumps({"phase": "probe", "ms": round(ms, 3)}))

    f_expand = jax.jit(
        functools.partial(
            expand_phase,
            K=statics["K"], rh_probes=statics["rh_probes"],
            n_config_rels=n_cr, wildcard_rel=statics["wildcard_rel"],
            n_queries=B, n_island_cap=statics["n_island_cap"],
            has_delta=statics["has_delta"],
        )
    )
    ms, (children, _, _) = timed(
        f_expand, tables, q, ctx, obj, rel, depth, live, isl_state
    )
    print(json.dumps({"phase": "expand", "ms": round(ms, 3)}))

    f_dedupe = jax.jit(functools.partial(dedupe_phase, F=F, n_queries=B))
    ms, _ = timed(f_dedupe, children)
    print(json.dumps({"phase": "dedupe", "ms": round(ms, 3)}))

    # full kernel: pipelined steady state with a BOUNDED window
    full = functools.partial(check_kernel, **statics)
    fargs = (
        tables, qd["q_obj"], qd["q_rel"], qd["q_depth"],
        qd["q_skind"], qd["q_sa"], qd["q_sb"], qd["q_valid"],
    )
    out = full(*fargs)
    jax_block(out)
    n, window = 20, 6
    t0 = time.perf_counter()
    pending = []
    for _ in range(n):
        pending.append(full(*fargs))
        if len(pending) > window:
            jax_block(pending.pop(0))
    jax_block(pending)
    ms = (time.perf_counter() - t0) / n * 1e3
    # blocked one-shot latency (launch to readback)
    t0 = time.perf_counter()
    jax_block(full(*fargs))
    one_ms = (time.perf_counter() - t0) * 1e3
    print(
        json.dumps(
            {
                "phase": "full_kernel",
                "ms": round(ms, 3),
                "blocked_one_shot_ms": round(one_ms, 3),
                "per_step_ms": round(ms / statics["max_steps"], 3),
                "max_steps": statics["max_steps"],
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
