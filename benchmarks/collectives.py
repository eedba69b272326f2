"""The bytes one chip receives over ICI in a launch of the sharded check
program (keto_tpu/parallel/kernel.py), by arithmetic on what the program
reports of its launches: steps, frontier F, bucket B and devices n.

Every BFS step of that program merges its shards three ways:
- `all_gather` of the step's candidate children, one [F] vector a field
  (engine/kernel.py `Expansion`: q, ctx, obj, rel and depth as int32,
  valid as a bool): a chip receives every other chip's F of each;
- `psum` of the [F] int32 probe hits and `pmax` of the [B] int32
  host-replay causes: all-reduces, which a ring makes in two passes that
  each bring a chip (n - 1) / n of the vector.

The arithmetic lives here, with the benchmark, so that a change to the
program cannot move what `mesh_ici_roofline` divides.
"""

from __future__ import annotations

CANDIDATE_FIELD_BYTES = (4, 4, 4, 4, 4, 1)
INT32_BYTES = 4


def step_bytes(frontier: float, bucket: float, devices: int) -> float:
    """Bytes one chip of a mesh of `devices` receives in one BFS step."""
    if devices < 2:
        return 0.0
    peers = devices - 1
    gathered = peers * frontier * sum(CANDIDATE_FIELD_BYTES)
    reduced = 2 * peers / devices * INT32_BYTES * (frontier + bucket)
    return gathered + reduced


def launch_bytes(steps: float, frontier: float, bucket: float,
                 devices: int) -> float:
    """Bytes one chip receives in a launch of `steps` BFS steps."""
    return steps * step_bytes(frontier, bucket, devices)
