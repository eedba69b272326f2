"""Row-gather cost vs row width on the attached device.

Decides the bucketized-hash-table question: the check kernel's probe
phase gathers [F, P] packed rows of 8 int32 lanes (32 B) where P is the
table's worst-case probe chain (~10 at 1e8 scale). A bucketized layout
(4 key-slots per 32-lane row) would cut P to ~3 but quadruple the row
width. Worth it only if a row-gather's cost is per-ROW, not per-byte,
at 128 B rows — which this measures directly:

  for width in {8, 16, 32, 64} lanes: gather [F, P] rows, report ms and
  ns/row at F=32768 for P in {2, 3, 10}.

Run: python tools/microbench_rowwidth.py [--cap 26] [--f 32768]
One JSON line per (width, P) with amortized per-call cost (bounded
in-flight window — see tools/profile_kernel.py).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def timed(fn, *args, n=40, window=8):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    pending = []
    for _ in range(n):
        pending.append(fn(*args))
        if len(pending) >= window:
            jax.block_until_ready(pending.pop(0))
    for p in pending:
        jax.block_until_ready(p)
    return (time.perf_counter() - t0) * 1e3 / n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cap", type=int, default=26,
                    help="log2 of total table lanes (26 -> 256 MiB)")
    ap.add_argument("--f", type=int, default=32768)
    args = ap.parse_args()

    import numpy as np

    global jax
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "f": args.f}), flush=True)

    rng = np.random.default_rng(7)
    total_lanes = 1 << args.cap
    for width in (8, 16, 32, 64):
        n_rows = total_lanes // width
        pack = jax.device_put(
            rng.integers(0, 1 << 30, (n_rows, width), dtype=np.int32)
        )

        for P in (2, 3, 10):
            idx = jax.device_put(
                rng.integers(0, n_rows, (args.f, P), dtype=np.int32)
            )

            @jax.jit
            def probe(ix, pk):
                # pk is a jit OPERAND: a closure/default-arg would embed
                # the table as a compile-time constant: a huge program
                # and a slow compile
                (rows,) = jax.lax.optimization_barrier((pk[ix],))
                # reduce like the probe's match+max so the gather is used
                return jnp.max(rows, axis=(1, 2))

            ms = timed(probe, idx, pack)
            rows_per_call = args.f * P
            print(json.dumps({
                "width_lanes": width,
                "row_bytes": width * 4,
                "P": P,
                "table_rows": n_rows,
                "ms": round(ms, 3),
                "ns_per_row": round(ms * 1e6 / rows_per_call, 2),
                "gb_per_s": round(
                    rows_per_call * width * 4 / ms / 1e6, 2
                ),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
