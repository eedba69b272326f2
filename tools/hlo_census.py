"""Optimized-HLO op census for the check kernel's while-loop body.

The round-4 profile showed the BFS step is op-overhead bound (~3.5 ms
fixed per step at F=4k, +40% at 8x F). Before building any Pallas
replacement, this tool answers: WHICH ops make up the step? It AOT
lowers+compiles check_kernel for the current backend, extracts the
while-loop body computation from the optimized HLO, and prints a census
of op counts grouped by opcode (fusions counted as one boundary each,
with their root op noted).

    python tools/hlo_census.py [--frontier 16384] [--batch 4096] [--out f]

Compiles for the attached TPU (as_text returns the optimized module),
or under JAX_PLATFORMS=cpu for a rough look.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frontier", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--out", default=None, help="also dump full HLO text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from keto_tpu.engine.kernel import (
        check_kernel,
        kernel_static_config,
        snapshot_tables,
    )
    from keto_tpu.engine.snapshot import build_snapshot

    namespaces, tuples, _ = bench.build_dataset()
    snap = build_snapshot(tuples, namespaces)
    tables = snapshot_tables(snap)
    statics = kernel_static_config(snap, 5, args.frontier)

    B = args.batch
    qz = jnp.zeros(B, jnp.int32)
    lowered = check_kernel.lower(
        tables, qz, qz, qz + 5, qz, qz, qz, jnp.ones(B, bool), **statics
    )
    compiled = lowered.compile()
    txt = compiled.as_text()
    if args.out:
        with open(args.out, "w") as f:
            f.write(txt)

    # find the while body computation: the body referenced by the while op
    m = re.search(r"while\(.*\), condition=.*, body=([%\w.-]+)", txt)
    body_name = m.group(1).lstrip("%") if m else None
    # split computations
    comps = {}
    cur = None
    for line in txt.splitlines():
        cm = re.match(r"^[%]?([\w.-]+) \([\w.]*: ", line) or re.match(
            r"^(?:ENTRY )?[%]?([\w.-]+) \(", line
        )
        if cm and ("{" in line or line.rstrip().endswith("{")):
            cur = cm.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)

    def census(name):
        ops = collections.Counter()
        fusion_roots = collections.Counter()
        lines = comps.get(name, [])
        for line in lines:
            om = re.match(r"\s+(?:ROOT )?[%]?[\w.-]+ = [^ ]+ ([\w-]+)\(", line)
            if not om:
                continue
            op = om.group(1)
            if op in ("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"):
                continue
            ops[op] += 1
            if op == "fusion":
                rm = re.search(r"calls=([%\w.-]+)", line)
                if rm:
                    # root op of the called fusion computation
                    fl = comps.get(rm.group(1).lstrip("%"), [])
                    for l in fl:
                        if "ROOT" in l:
                            r = re.match(
                                r"\s+ROOT [%]?[\w.-]+ = [^ ]+ ([\w-]+)\(", l
                            )
                            if r:
                                fusion_roots[r.group(1)] += 1
        return ops, fusion_roots

    if body_name is None:
        # fall back: largest computation
        body_name = max(comps, key=lambda k: len(comps[k]))
    ops, roots = census(body_name)
    total = sum(ops.values())
    print(json.dumps({
        "body": body_name,
        "total_boundaries": total,
        "ops": dict(ops.most_common()),
        "fusion_roots": dict(roots.most_common()),
        "device": str(jax.devices()[0]),
        "frontier": args.frontier,
        "batch": B,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
