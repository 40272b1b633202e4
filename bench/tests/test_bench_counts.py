"""The FLOP and byte counts against hand counts, and the statistics
against a window with an injected stall."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench import counts, harness
from bench.tests.conftest import REPO


def test_conv_count_matches_a_hand_count_of_vgg16_conv1_1():
    # 224x224 output, 64 filters of 3x3x3: 2 * 224*224*64 * 27 FLOPs
    x, w = (1, 3, 226, 226), (64, 3, 3, 3)
    assert counts.conv_flops(x, w, 1) == 2 * 224 * 224 * 64 * 27
    assert counts.conv_flops(x, w, 1) == 173_408_256
    assert counts.conv_bytes(x, w, 1) == 4 * (3 * 226 * 226 + 64 * 27
                                              + 64 * 224 * 224)


def test_strided_conv_count():
    # ResNet stem: 7x7 stride 2 over a 230x230 padded input -> 112x112
    assert counts.conv_flops((2, 3, 230, 230), (64, 3, 7, 7), 2) == \
        2 * 2 * 64 * 112 * 112 * 3 * 49


def test_coding_gemm_count():
    # encode G (4, 3) @ X (3, F): 4*F outputs, 3 multiply-adds each
    assert counts.gemm_flops(4, 3, 1000) == 24_000
    assert counts.gemm_bytes(4, 3, 1000) == 4 * (12 + 3000 + 4000)


@pytest.mark.parametrize("config, gflops", [("vgg16-224", 30.7),
                                            ("resnet18-224", 3.63)])
def test_model_flops_match_the_published_counts(config, gflops):
    cell = harness.find_cell(REPO, next(
        w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())
        ["workloads"] if w["config"] == config))
    ref, cfg = cell.reference, cell.cfg
    total = counts.model_flops(ref.conv_layers(cfg), 1,
                               ref.head_features(cfg), cfg["classes"])
    head = 2 * 25088 * 1000
    assert ref.head_features(cfg) == 25088
    assert total - head == pytest.approx(gflops * 1e9, rel=0.01)


def _rec(latencies, images_per_request=1):
    reqs = [SimpleNamespace(latency_s=t, forward_s=t, run_wall_s=t / 2,
                            runs=2) for t in latencies]
    return SimpleNamespace(requests=reqs, window_s=sum(latencies),
                           images=images_per_request * len(reqs),
                           runs=[(4, 3, t / 4) for t in latencies
                                 for _ in range(2)])


@pytest.mark.parametrize("metric", ["latency_p50_ms", "latency_p90_ms",
                                    "images_per_s", "model.master_ms",
                                    "backend.run_ms"])
def test_an_injected_stall_moves_window_statistics(metric):
    read = harness.metric_reader(REPO, metric)
    steady = [0.2] * 100
    # the later part of the window stalls: a statistic of the whole window
    # sees it, one of its first requests would not
    stalled = [0.2] * 40 + [0.5] * 60
    assert read(_rec(stalled)) != pytest.approx(read(_rec(steady)))
    assert read(_rec(steady)) == pytest.approx(
        {"latency_p50_ms": 200, "latency_p90_ms": 200, "images_per_s": 5,
         "model.master_ms": 100, "backend.run_ms": 50}[metric])


def test_rate_is_all_work_over_all_time():
    read = harness.metric_reader(REPO, "images_per_s")
    rec = _rec([0.1] * 50 + [5.0], images_per_request=32)
    assert read(rec) == pytest.approx(32 * 51 / 10.0)
