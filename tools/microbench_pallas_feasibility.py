"""Pallas feasibility probes for a hand-written probe kernel (ROADMAP S8).

Round 4 concluded that the check kernel is per-op-overhead bound and
that the remaining single-chip lever is collapsing the BFS step into a
Pallas mega-kernel. Before anyone commits days to that, three facts
need to be true on the chip and toolchain at hand — this script measures
them in ~1 minute (record: PALLAS_FEASIBILITY_r04.json):

1. does a basic Pallas kernel compile and run at all?
2. vectorized dynamic indexing (`tab_ref[idx_vec, :]`) — the naive
   shape of a hash-probe gather — is NOT lowered on TPU ("Cannot do
   int indexing on TPU"); confirm the failure mode is still that.
3. the supported alternative is scalar-prefetched BLOCK gathers
   (PrefetchScalarGridSpec, one (8, 128) block per grid step — the
   minimum TPU block shape). A mega-step therefore implies a
   bucket-of-8-slots table layout so a probe's block IS its bucket.

Run: python tools/microbench_pallas_feasibility.py
Prints one JSON line per probe.
"""

from __future__ import annotations

import json
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev)}), flush=True)

    # 1. basic kernel
    def add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    t0 = time.perf_counter()
    out = jax.jit(
        lambda a, b: pl.pallas_call(
            add_kernel, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype)
        )(a, b)
    )(x, jnp.ones_like(x))
    jax.block_until_ready(out)
    ok = bool(np.allclose(np.asarray(out), np.asarray(x) + 1.0))
    print(json.dumps({"probe": "basic_kernel", "ok": ok,
                      "compile_s": round(time.perf_counter() - t0, 1)}),
          flush=True)

    # 2. vectorized dynamic indexing (expected: lowering error)
    def vgather_kernel(idx_ref, tab_ref, o_ref):
        o_ref[...] = tab_ref[idx_ref[...], :]

    tab = jnp.arange(256 * 128, dtype=jnp.int32).reshape(256, 128)
    idx = jnp.array([3, 7, 0, 200, 12, 9, 1, 255], dtype=jnp.int32)
    try:
        jax.jit(
            lambda i, t: pl.pallas_call(
                vgather_kernel,
                out_shape=jax.ShapeDtypeStruct((i.shape[0], t.shape[1]),
                                               t.dtype),
            )(i, t)
        )(idx, tab)
        print(json.dumps({"probe": "vector_int_indexing", "ok": True,
                          "note": "now supported?! revisit mega-step plan"}),
              flush=True)
    except Exception as e:
        print(json.dumps({"probe": "vector_int_indexing", "ok": False,
                          "error": (str(e).splitlines() or [""])[-1][:120]}),
              flush=True)

    # 3. scalar-prefetch block gather ((8, 128) minimum block)
    def gkern(idx_ref, tab_ref, o_ref):
        o_ref[...] = tab_ref[...]

    def gather_blocks(bidx, t):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bidx.shape[0],),
            in_specs=[pl.BlockSpec((8, 128), lambda i, r: (r[i], 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, r: (i, 0)),
        )
        return pl.pallas_call(
            gkern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bidx.shape[0] * 8, 128),
                                           t.dtype),
        )(bidx, t)

    bidx = jnp.array([3, 7, 0, 30, 12], dtype=jnp.int32)
    got = jax.jit(gather_blocks)(bidx, tab)
    want = np.asarray(tab).reshape(32, 8, 128)[np.asarray(bidx)].reshape(
        -1, 128
    )
    print(json.dumps({
        "probe": "scalar_prefetch_block_gather",
        "ok": bool(np.array_equal(np.asarray(got), want)),
        "block": [8, 128],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
