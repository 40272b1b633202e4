"""The trace reduction: on synthetic intervals, and on a short trace of
the vgg16-224.b1.straggler cell recorded on a TPU v5e chip."""
from __future__ import annotations

import gzip
import shutil

import pytest

from bench import harness, tracing
from bench.tests.conftest import DATA

CHIP_TRACE = DATA / "vgg16-224.b1.straggler.xplane.pb.gz"


def test_union_merges_overlaps_and_clips_to_the_window():
    got = tracing._union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 10)
    assert got == [(1, 3), (5, 10)]


def test_gaps_are_labelled_by_the_innermost_open_span():
    thread = [("bench.window", 0, 100), ("bench.request", 0, 50),
              ("bench.forward", 1, 49), ("bench.executor_run", 10, 20),
              ("bench.request", 50, 100), ("bench.forward", 51, 99)]
    idle = tracing._label_gaps(thread, [(11, 13), (30, 34), (60, 61),
                                        (99.2, 99.8)])
    assert idle == {"bench.executor_run": 2, "bench.forward": 5,
                    "bench.request": pytest.approx(0.6)}


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(CHIP_TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce(tracing.load(path), harness.CATEGORIES)


def test_chip_trace_busy_and_idle(chip):
    assert 0 < chip["busy_s"] < chip["window_s"]
    assert chip["devices"] == 1
    idle = sum(t for _, t in chip["idle_gaps"])
    # the listed gaps are the largest part of the idle time
    assert 0 < idle <= chip["window_s"] - chip["busy_s"] + 1e-9


def test_chip_trace_categories_and_top_ops(chip):
    cats = chip["category_s"]
    assert cats["conv"] > 0 and cats["coding"] > 0
    assert cats["conv"] + cats["coding"] <= chip["busy_s"] * 1.0001
    ops = chip["device_ops"]
    assert 0 < len(ops) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(name and t > 0 for name, t in ops)


def test_a_trace_without_a_window_reads_nothing(tmp_path):
    assert tracing.find_trace(tmp_path) is None
