"""The one traffic generator. A cell is a configuration (a data set made by
a generator module from the seed) under a traffic mix (a data file of
parameters). Both the serving process and the load generator build the same
`Workload` from the same files and seed, so the child needs no data from
the parent and the parent can rebuild any request the child sent.

This module and everything it imports stay off JAX: the load generator
imports it.

RPC number i of a run carries `items` checks drawn from
`numpy.random.default_rng([seed, i])`: a target drawn by the mix's `draw`
over all of the data set's targets, asked for by its owner (allowed) with
probability `allowed_share` and otherwise by a subject that owns nothing.
After its answer the caller thinks for a time drawn from the same stream,
exponential with mean `think_ms` (0: it sends again at once). So the work
is a function of the seed and the RPC's number alone, and every seed draws
from the same distribution.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WARM_STREAM = 1 << 40  # RPC numbers no window reaches: the warm-up draws


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by name."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"{kind}.{name}")


def _send_check(client, queries, timeout):
    return [client.check(queries[0], timeout=timeout)]


def _send_check_batch(client, queries, timeout):
    out = client.check_batch(queries, timeout=timeout)
    errors = [e for _, e in out if e]
    if errors:
        raise RuntimeError(f"{len(errors)} items failed: {errors[0]}")
    return [allowed for allowed, _ in out]


RPCS = {"check": _send_check, "check_batch": _send_check_batch}


def _draw_uniform(rng, n_targets, n):
    return rng.integers(0, n_targets, n)


DRAWS = {"uniform": _draw_uniform}


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 tuples: int | None = None):
        self.seed = seed
        self.generator = load_module("generators", config["generator"])
        self.truth = self.generator.Truth(
            config["generator_params"], seed, tuples or config["tuples"]
        )
        self.items = int(traffic["items"])
        self._send = RPCS[traffic["rpc"]]
        self._draw = DRAWS[traffic["draw"]]
        self._allowed_share = float(traffic["allowed_share"])
        self._think_s = float(traffic["think_ms"]) / 1e3

    def columns(self):
        return self.generator.columns(self.truth)

    def draw(self, rpc: int, n: int):
        """(queries, expected answers, think time in seconds) of RPC number
        `rpc`, were it to carry n checks."""
        from keto_tpu.ketoapi import RelationTuple

        rng = np.random.default_rng([self.seed, rpc])
        targets = self._draw(rng, self.truth.n_targets, n)
        allowed = rng.random(n) < self._allowed_share
        queries = [
            RelationTuple.make(
                *self.truth.query(int(t), bool(a), f"{rpc}-{k}")
            )
            for k, (t, a) in enumerate(zip(targets, allowed))
        ]
        return queries, allowed, rng.exponential(self._think_s)

    def request(self, rpc: int):
        return self.draw(rpc, self.items)

    def send(self, client, queries, timeout: float) -> list[bool]:
        return self._send(client, queries, timeout)
