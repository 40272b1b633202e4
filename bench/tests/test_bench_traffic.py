"""The request generator: the same seed gives the same traffic, another
seed another order of the same requests."""
from __future__ import annotations

import itertools
import json
from collections import Counter

import pytest

from bench import traffic
from bench.tests.conftest import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _take(mix, seed, n=96):
    return list(itertools.islice(traffic.requests(mix, seed, 4), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = _mix(name)
    assert _take(mix, BIG_SEED) == _take(mix, BIG_SEED)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_of_the_same_requests(name):
    mix = _mix(name)
    a, b = _take(mix, BIG_SEED), _take(mix, BIG_SEED + 1)
    assert a != b
    # every seed draws the same set of inputs and stragglers
    assert Counter(r.input for r in a) == Counter(r.input for r in b)
    assert Counter(r.straggler for r in a) == Counter(r.straggler for r in b)


@pytest.mark.parametrize("name", MIXES)
def test_every_input_is_used_and_faults_follow_the_mix(name):
    mix = _mix(name)
    reqs = _take(mix, 7)
    assert {r.input for r in reqs} == set(range(mix["distinct_inputs"]))
    if mix["fault"]["kind"] == "straggler":
        assert {r.straggler for r in reqs} == {0, 1, 2, 3}
        assert traffic.slowdown(mix) == 10.0
    else:
        assert {r.straggler for r in reqs} == {None}


def test_an_unknown_loop_or_fault_is_refused():
    mix = _mix(MIXES[0])
    with pytest.raises(ValueError):
        next(traffic.requests({**mix, "loop": "open"}, 1, 4))
    with pytest.raises(ValueError):
        next(traffic.requests({**mix, "fault": {"kind": "meteor"}}, 1, 4))
