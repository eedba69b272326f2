"""Phase-level ablation timing for the check-kernel BFS step.

Standalone per-phase jits measure mostly the fixed cost of a launch,
and the HLO op census turned out not to predict cost (the round-5
row-compare rewrite REDUCED relayout copies but the step got 6%
slower). This harness gets per-phase numbers that a launch's fixed
cost cannot drown: run ONE phase N times inside a
fori_loop in ONE launch, so the fixed launch cost amortizes to noise
and the phase's steady-state cost is (t_N - t_0) / N.

DCE discipline: each variant threads a data-dependent-but-identity
term (sink >> 31, always 0 at runtime for nonnegative sinks, never
provably so) into the next iteration's inputs, so XLA cannot hoist the
phase out of the loop or fold iterations.

    python tools/ablate_step.py [--frontier 16384] [--iters 50]

Prints one JSON line per variant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frontier", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument(
        "--only", default=None, help="comma-separated variant names"
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from keto_tpu.engine import kernel as kmod
    from keto_tpu.engine.kernel import (
        Expansion,
        dedupe_phase,
        expand_phase,
        flag_phase,
        kernel_static_config,
        probe_phase,
        program_lookup,
        snapshot_tables,
    )
    from keto_tpu.engine.snapshot import build_snapshot

    namespaces, tuples, queries = bench.build_dataset()
    snap = build_snapshot(tuples, namespaces)
    tables = snapshot_tables(snap)
    statics = kernel_static_config(snap, 5, args.frontier)
    B, F, N = args.batch, args.frontier, args.iters
    S = statics["K"] + 1

    rng = np.random.default_rng(0)
    n_slots = int(tables["objslot_ns"].shape[0])
    obj0 = jnp.asarray(
        rng.integers(0, max(n_slots, 2), F, dtype=np.int32)
    )
    rel0 = jnp.asarray(rng.integers(0, 3, F, dtype=np.int32))
    depth0 = jnp.full(F, 5, jnp.int32)
    skind0 = jnp.zeros(F, jnp.int32)
    sa0 = jnp.asarray(rng.integers(0, 1000, F, dtype=np.int32))
    sb0 = jnp.zeros(F, jnp.int32)
    live0 = jnp.ones(F, bool)
    q0 = jnp.asarray(rng.integers(0, B, F, dtype=np.int32))

    def dep(sink):
        # 0 at runtime (every body keeps sink bounded by masking its
        # contribution to one bit, so int32 overflow can never flip the
        # sign), never provably 0 to the compiler
        return (sink >> jnp.int32(31)).astype(jnp.int32)

    def bit(x):
        # data-dependent single bit: keeps the sink accumulation bounded
        # (<= iters), so dep(sink) stays 0 even though full sums of
        # [F]-sized int32 arrays would overflow the sink negative and
        # silently perturb the benchmarked inputs by -1 per iteration
        return jnp.asarray(x, jnp.int32).sum() & jnp.int32(1)

    def loopify(body):
        """body(carry_obj, sink) -> new_sink ; returns jitted N-iter fn."""

        def run(n):
            def it(i, st):
                o, sink = st
                o2 = o + dep(sink)
                return (o2, body(o2, sink))

            return jax.lax.fori_loop(
                0, n, it, (obj0, jnp.int32(0))
            )[1]

        return jax.jit(run, static_argnums=0)

    variants: dict = {}

    variants["empty"] = loopify(lambda o, sink: sink + (o[0] & 1))

    # calibration: k standalone bucket-row gathers from the big packed table
    # (dh_pack is stored as its [cap/8, 64] bucket rows)
    def gather_k(k):
        def body(o, sink):
            acc = sink
            for i in range(k):
                rows = kmod._isolate(
                    tables["dh_pack"][(o + i) & (tables["dh_pack"].shape[0] - 1)]
                )
                acc = acc + (rows[0, 0] & 1)
            return acc

        return body

    variants["gather_x4"] = loopify(gather_k(4))

    # calibration: one scatter-max of F updates into a 2F table
    def scatter_body(o, sink):
        tgt = jnp.zeros(2 * F, jnp.int32).at[o & (2 * F - 1)].max(o)
        return sink + (tgt[0] & 1)

    variants["scatter_x1"] = loopify(scatter_body)

    # calibration: cumsum / cummax over [F*S]
    def cumsum_body(o, sink):
        c = jnp.cumsum(jnp.broadcast_to(o[:, None], (F, S)).reshape(-1))
        return sink + (c[-1] & 1)

    variants["cumsum_FS"] = loopify(cumsum_body)

    def cummax_body(o, sink):
        c = jax.lax.cummax(jnp.broadcast_to(o[:, None], (F, S)).reshape(-1))
        return sink + (c[-1] & 1)

    variants["cummax_FS"] = loopify(cummax_body)

    # phases
    def flag_body(o, sink):
        f = flag_phase(
            tables, o, rel0, live0,
            n_config_rels=statics["n_config_rels"], island_is_host=True,
        )
        return sink + bit(f)

    variants["flag"] = loopify(flag_body)

    def probe_body(o, sink):
        h = probe_phase(
            tables, o, rel0, skind0, sa0, sb0, depth0, live0,
            dh_probes=statics["dh_probes"], has_delta=statics["has_delta"],
        )
        return sink + bit(h)

    variants["probe"] = loopify(probe_body)

    def probe_nodelta_body(o, sink):
        h = probe_phase(
            tables, o, rel0, skind0, sa0, sb0, depth0, live0,
            dh_probes=statics["dh_probes"], has_delta=False,
        )
        return sink + bit(h)

    variants["probe_nodelta"] = loopify(probe_nodelta_body)

    isl0 = (jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32), jnp.int32(0))

    def expand_body(o, sink):
        ch, oq, _ = expand_phase(
            tables, q0, q0, o, rel0, depth0, live0, isl0,
            K=statics["K"], rh_probes=statics["rh_probes"],
            n_config_rels=statics["n_config_rels"],
            wildcard_rel=statics["wildcard_rel"], n_queries=B,
            n_island_cap=0, has_delta=statics["has_delta"],
        )
        return sink + bit(ch.obj.sum() + oq.sum() + ch.ctx.sum() + ch.depth.sum())

    variants["expand"] = loopify(expand_body)

    def dedupe_body(o, sink):
        ch = Expansion(q0, q0, o, rel0, depth0, live0)
        nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new, oq = dedupe_phase(
            ch, F, B
        )
        return sink + bit(nt_obj.sum() + n_new + oq.sum() + nt_rel.sum())

    variants["dedupe"] = loopify(dedupe_body)

    def full_body(o, sink):
        prog = program_lookup(
            tables, o, rel0, live0, n_config_rels=statics["n_config_rels"]
        )
        f = flag_phase(
            tables, o, rel0, live0,
            n_config_rels=statics["n_config_rels"], island_is_host=True,
            prog=prog,
        )
        h = probe_phase(
            tables, o, rel0, skind0, sa0, sb0, depth0, live0,
            dh_probes=statics["dh_probes"], has_delta=statics["has_delta"],
        )
        ch, oq, _ = expand_phase(
            tables, q0, q0, o, rel0, depth0, live0, isl0,
            K=statics["K"], rh_probes=statics["rh_probes"],
            n_config_rels=statics["n_config_rels"],
            wildcard_rel=statics["wildcard_rel"], n_queries=B,
            n_island_cap=0, has_delta=statics["has_delta"], prog=prog,
        )
        nt_q, nt_ctx, nt_obj, nt_rel, nt_depth, n_new, oq2 = dedupe_phase(
            ch, F, B
        )
        return sink + bit(
            f.sum() + h.sum().astype(jnp.int32) + nt_obj.sum()
            + n_new + oq.sum() + oq2.sum()
        )

    variants["full_step"] = loopify(full_body)

    only = set(args.only.split(",")) if args.only else None
    print(json.dumps({
        "device": str(jax.devices()[0]), "F": F, "B": B, "iters": N,
    }), flush=True)
    for name, fn in variants.items():
        if only and name not in only:
            continue
        # warm both trip counts, then time: per-iter = (tN - t1) / (N - 1)
        jax.block_until_ready(fn(1))
        jax.block_until_ready(fn(N))
        t1 = []
        tN = []
        for _ in range(3):
            s = time.perf_counter()
            jax.block_until_ready(fn(1))
            t1.append(time.perf_counter() - s)
            s = time.perf_counter()
            jax.block_until_ready(fn(N))
            tN.append(time.perf_counter() - s)
        per = (min(tN) - min(t1)) / (N - 1) * 1e3
        print(json.dumps({
            "variant": name, "per_iter_ms": round(per, 4),
            "t1_ms": round(min(t1) * 1e3, 2), "tN_ms": round(min(tN) * 1e3, 2),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
