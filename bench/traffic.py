"""The one request generator: reads a traffic mix (``bench/traffic/<mix>.json``)
and yields the requests of one run from its seed.

A mix states ``batch`` (images per request), ``distinct_inputs`` (how many
different batches the run cycles through), ``loop``/``clients`` (closed,
one client: the next request leaves when the previous one has answered)
and ``fault``: ``{"kind": "none"}``, or ``{"kind": "straggler",
"slowdown": s}``, one worker per request slowed s times for that whole
forward.  Every seed gives the same set of inputs and stragglers, in
another order: the inputs in a fresh permutation every ``distinct_inputs``
requests, the straggling worker in one every ``n_workers`` requests.
"""
from __future__ import annotations

from typing import Iterator, NamedTuple

import numpy as np

FAULT_KINDS = ("none", "straggler")


class Request(NamedTuple):
    input: int                # index into the run's distinct input batches
    straggler: int | None     # worker slowed for this request, if any


def validate(mix: dict) -> None:
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError("only a closed loop of one client is generated")
    if int(mix["batch"]) < 1 or int(mix["distinct_inputs"]) < 1:
        raise ValueError("batch and distinct_inputs must be positive")
    if mix["fault"]["kind"] not in FAULT_KINDS:
        raise ValueError(f"fault kind {mix['fault']['kind']!r} not in "
                         f"{FAULT_KINDS}")


def requests(mix: dict, seed: int, n_workers: int) -> Iterator[Request]:
    """The run's endless request stream."""
    validate(mix)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    n_inputs = int(mix["distinct_inputs"])
    straggle = mix["fault"]["kind"] == "straggler"
    inputs: list[int] = []
    workers: list[int] = []
    while True:
        if not inputs:
            inputs = rng.permutation(n_inputs).tolist()
        if straggle and not workers:
            workers = rng.permutation(n_workers).tolist()
        yield Request(inputs.pop(), workers.pop() if straggle else None)


def slowdown(mix: dict) -> float:
    return float(mix["fault"].get("slowdown", 1.0))
