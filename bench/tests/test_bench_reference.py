"""The plain references against the program's CNNs at a small size on the
CPU: the uncoded forward, and the functional coded forward."""
from __future__ import annotations

import ast
import functools

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import BENCH, make_checkout

CELLS = ["vgg16-64.b1.straggler", "resnet18-64.b1.straggler"]


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    root = make_checkout(tmp_path_factory.mktemp("ref"))
    return {c: harness.find_cell(root, c) for c in CELLS}


def _program(cell, seed=3):
    params = harness.make_params(cell, seed)
    x = harness.make_inputs(cell, seed)[0]
    sut = cell.system.build(cell.cfg, params)
    sut.close()
    return params, x, sut


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_the_uncoded_and_the_coded_forward(cells, name):
    cell = cells[name]
    params, x, sut = _program(cell)
    ref = cell.reference.forward(cell.cfg, params, x)
    assert ref.shape == (1, cell.cfg["classes"])
    uncoded = sut._entry(sut.params, x)
    coded = sut._entry(sut.params, x, scheme="mds", n=4)
    assert _rel(uncoded, ref) < 1e-5
    assert _rel(coded, ref) < 2e-5


@pytest.mark.parametrize("name", CELLS)
def test_three_pass_control_departs_from_the_reference(cells, name):
    cell = cells[name]
    params, x, _ = _program(cell)
    fwd = functools.partial(cell.reference.forward, cell.cfg, params, x)
    exact, low = fwd(mode="highest"), fwd(mode="bf16_3x")
    assert 0 < _rel(low, exact) < 1e-3
    with pytest.raises(ValueError):
        fwd(mode="int8")


@pytest.mark.parametrize("path", sorted((BENCH / "references").glob("*.py")),
                         ids=lambda p: p.name)
def test_references_import_nothing_of_the_program(path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        for m in mods:
            assert not m.startswith("repro"), (path.name, m)
            assert m.split(".")[0] in ("jax", "bench", "functools", "__future__"), m


def test_weights_and_inputs_follow_the_seed(cells):
    cell = cells[CELLS[0]]
    big = 2**31 + 99
    a, b = harness.make_params(cell, big), harness.make_params(cell, big)
    c = harness.make_params(cell, big + 2**32)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(u, v) for u, v in zip(leaves(a), leaves(b)))
    assert not np.array_equal(a["head"], c["head"])
    xa, xb = harness.make_inputs(cell, big), harness.make_inputs(cell, big + 1)
    assert not np.array_equal(xa[0], xb[0])
