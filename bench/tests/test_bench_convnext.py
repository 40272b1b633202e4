"""ConvNeXt in the benchmark, on the CPU at a small size (32 px, widths
16-128, depths 1, 1, 2, 1): the plain reference against the program's
uncoded and coded forwards, the control, a whole run of the harness, the
adapter's count of grouped convs, and the readers of ``model.norm`` and
``model.dwconv``."""
from __future__ import annotations

import ast
import functools
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.telemetry
from bench import counts, harness
from bench.tests.conftest import BENCH, REPO, make_checkout
from repro.telemetry import RequestLog

CFG = json.loads((BENCH / "configs" / "convnext-b-224.json").read_text())
SMALL = dict(CFG, name="convnext-b-32", image=32, widths=[16, 32, 64, 128],
             depths=[1, 1, 2, 1], classes=10)
CELL = "convnext-b-32.b1.straggler"
NEW = {"model.norm_ms": "model.norm", "model.dwconv_ms": "model.dwconv"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose BENCHMARK.json also holds the small ConvNeXt cell
    and the new per-layer metrics."""
    root = make_checkout(tmp_path_factory.mktemp("convnext"))
    path = root / "bench" / "tests" / "data" / "convnext-b-32.json"
    path.write_text(json.dumps(SMALL))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": SMALL["name"], "source": CFG["source"],
                            "file": str(path.relative_to(root)),
                            "reduced": ["image", "widths", "depths",
                                        "classes"], "why": "CPU test size"})
    spec["workloads"].append({"name": CELL, "config": SMALL["name"],
                              "traffic": "b1.straggler", "chips": 1,
                              "why": "CPU test"})
    spec["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "model",
         "moves": "latency_p50_ms", "workloads": [CELL]} for name in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def cell(root):
    return harness.find_cell(root, CELL)


@pytest.fixture(scope="module")
def program(cell):
    params = harness.make_params(cell, 2**31 + 3)
    x = harness.make_inputs(cell, 2**31 + 3)[0]
    sut = cell.system.build(cell.cfg, params)
    sut.close()
    return params, x, sut


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _pooled(entry, params, x):
    """On a real-clock pool whose worker 1 is 10x slow: decoded from the
    first arrivals."""
    from repro.dist import CodedExecutor, FaultPlan, RealClock
    from repro.models.cnn import SMALL_CNN_PARAMS

    with CodedExecutor(4, clock=RealClock(),
                       fault_plan=FaultPlan(straggler={1: 10.0})) as ex:
        return entry(params, x, scheme="mds", n=4,
                     sys_params=SMALL_CNN_PARAMS, executor=ex)


def _fixed(entry, params, x, scheme, subset):
    """One (4, 2) code of ``scheme`` for every segment, decoded from
    ``subset``."""
    from repro.core.schemes import get_scheme
    from repro.models.cnn import SMALL_CNN_PARAMS

    return entry(params, x, get_scheme(scheme).make(4, 2), subset,
                 sys_params=SMALL_CNN_PARAMS)


def _small(**kw):
    from repro.models.cnn import SMALL_CNN_PARAMS

    return lambda entry, params, x: entry(params, x,
                                          sys_params=SMALL_CNN_PARAMS, **kw)


FORWARDS = {
    "uncoded": lambda entry, params, x: entry(params, x),
    "mds_default_params": lambda entry, params, x: entry(params, x,
                                                         scheme="mds", n=4),
    "mds": _small(scheme="mds", n=4),
    "replication": _small(scheme="replication", n=4),
    "mds_default_subset": functools.partial(_fixed, scheme="mds",
                                            subset=None),
    "mds_straggler_subset": functools.partial(_fixed, scheme="mds",
                                              subset=(1, 2)),
    "replication_straggler_subset": functools.partial(
        _fixed, scheme="replication", subset=(2, 3)),
    "mds_pool_straggler": _pooled,
}


@pytest.mark.parametrize("forward", sorted(FORWARDS))
def test_reference_matches_the_program(cell, program, forward):
    params, x, sut = program
    ref = cell.reference.forward(cell.cfg, params, x)
    assert ref.shape == (1, SMALL["classes"])
    out = FORWARDS[forward](sut._entry, sut.params, x)
    assert _rel(out, ref) < 1e-5


def test_the_small_cell_codes_depthwise_and_pointwise_convs(program):
    """The coded forwards above do code: under ``SMALL_CNN_PARAMS`` every
    pointwise conv and stage 1-2's depthwise convs are segments."""
    from repro.models.cnn import SMALL_CNN_PARAMS, convnext_conv_specs

    layers = convnext_conv_specs(32, SMALL["widths"], SMALL["depths"],
                                 SMALL_CNN_PARAMS)
    coded = {li.name for li in layers if li.type1}
    assert {"s1b0dw", "s2b0dw", "s1b0pw1", "s4b0pw2"} <= coded


def test_three_pass_control_departs_from_the_reference(cell, program):
    params, x, _ = program
    fwd = functools.partial(cell.reference.forward, cell.cfg, params, x)
    exact, low = fwd(mode="highest"), fwd(mode="bf16_3x")
    assert 0 < _rel(low, exact) < 1e-3
    with pytest.raises(ValueError):
        fwd(mode="int8")


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse((BENCH / "references" / "convnext.py").read_text())
    mods = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    assert mods and not any(m.startswith("repro") for m in mods)


@pytest.mark.parametrize("control, correct", [(None, True),
                                              ("reference:bf16_3x", False)])
def test_a_whole_run(cell, control, correct):
    out = harness.run_cell(cell, 2**31 + 5, 0.5, False,
                           t0=time.perf_counter(), devices=jax.devices(),
                           control=control, log=lambda *a: None)
    assert out["correct"] is correct and out["attempted"] >= 1
    assert out["window_compiles"] == 0 or control is not None
    err = out["checks"]["logit_rel_err"]
    assert (err["value"] <= err["limit"]) is correct


# ---------------------------------------------------------------------------
# the adapter's count of grouped convs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("x_shape, w_shape, stride", [
    ((1, 512, 20, 20), (512, 1, 7, 7), 1),
    ((2, 128, 62, 62), (128, 1, 7, 7), 1),
    ((1, 64, 9, 9), (128, 8, 3, 3), 2)])
def test_a_grouped_conv_is_recorded_as_its_groups(cell, x_shape, w_shape,
                                                  stride):
    b, c_in, h, w = x_shape
    c_out, per, k, _ = w_shape
    h_out, w_out = (h - k) // stride + 1, (w - k) // stride + 1
    parts = cell.system.per_group((x_shape, w_shape, stride))
    assert len(parts) == c_in // per
    assert sum(counts.conv_flops(*p) for p in parts) == \
        2 * b * c_out * h_out * w_out * per * k * k
    # input, weight and output, each counted once
    assert sum(counts.conv_bytes(*p) for p in parts) == \
        4 * (b * c_in * h * w + c_out * per * k * k + b * c_out * h_out * w_out)


def test_dense_convs_are_recorded_as_they_are(cell):
    conv = ((1, 3, 226, 226), (64, 3, 3, 3), 1)
    assert cell.system.per_group(conv) == [conv]


def test_counted_work_of_the_uncoded_forward_is_the_model(cell, program):
    params, x, sut = program
    with cell.system.count_work() as seen:
        sut._entry(sut.params, x).block_until_ready()
    work = harness._work(seen)
    ref, cfg = cell.reference, cell.cfg
    model = counts.model_flops(ref.conv_layers(cfg), 1,
                               ref.head_features(cfg), cfg["classes"])
    assert work["conv_flops"] == model - 2 * 128 * SMALL["classes"]
    assert work["convs"] == 1 + 3 + sum(
        16 * 2 ** (s - 1) * d + 2 * d for s, d in
        enumerate(SMALL["depths"], start=1))


def test_convnext_b_flops_and_parameters_match_the_published_counts():
    """15.4 GMACs and 89M parameters (arXiv:2201.03545, Table 1)."""
    ref = harness.load_module(BENCH / "references" / "convnext.py")
    flops = counts.model_flops(ref.conv_layers(CFG), 1,
                               ref.head_features(CFG), CFG["classes"])
    assert flops / 2 == pytest.approx(15.4e9, rel=0.01)
    shapes = jax.eval_shape(functools.partial(ref.init, CFG),
                            jax.random.PRNGKey(0))
    size = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert size == pytest.approx(89e6, rel=0.01)


# ---------------------------------------------------------------------------
# the readers of model.norm and model.dwconv
# ---------------------------------------------------------------------------

def _log(monkeypatch, n):
    """Request i holds i+1 ms of each new span."""
    log = RequestLog(maxlen=8)
    for i in range(n):
        rec = log.open(batch=1)
        for span in NEW.values():
            rec.spans[span] = [i + 1, (i + 1) * 1_000_000, 0]
    monkeypatch.setattr(repro.telemetry, "request_log", log)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_reads_the_last_records_or_nothing(monkeypatch, name):
    read = harness.metric_reader(REPO, name)
    _log(monkeypatch, 5)
    assert read(SimpleNamespace(requests=[0, 0])) == pytest.approx(4.5)
    assert read(SimpleNamespace(requests=[0] * 6)) is None
    monkeypatch.delattr(repro.telemetry, "request_log")
    assert read(SimpleNamespace(requests=[0])) is None


def test_the_readers_read_a_window_of_the_program(cell):
    params = harness.make_params(cell, 2**31 + 7)
    xs = harness.make_inputs(cell, 2**31 + 7)
    sut = cell.system.build(cell.cfg, params)
    try:
        harness.warm_up(cell, sut, xs, lambda *a: None)
        _, _, reqs, _ = harness.measure(cell, sut, xs, 2**31 + 7, 0.3, False)
    finally:
        sut.close()
    window = SimpleNamespace(requests=reqs)
    got = {name: harness.metric_reader(REPO, name)(window) for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    forward_ms = sum(r.forward_s for r in reqs) / len(reqs) * 1e3
    assert sum(got.values()) < forward_ms


def test_the_cell_finds_its_files_and_only_it_reads_the_new_metrics():
    found = harness.find_cell(REPO, "convnext-b-224.b1.straggler")
    assert found.cfg["widths"] == [128, 256, 512, 1024]
    assert found.cfg["depths"] == [3, 3, 27, 3]
    assert found.workload["chips"] == 1
    assert set(NEW) <= {m["name"] for m in found.per_layer}
    assert "model.encode_compiled_pct" not in {
        m["name"] for m in found.per_layer}
    other = harness.find_cell(REPO, "vgg16-224.b1.straggler")
    assert not set(NEW) & {m["name"] for m in other.per_layer}
