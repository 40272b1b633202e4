"""Shared helpers of the benchmark's own tests: a throw-away checkout that
holds the benchmark and the CPU-sized test cells of ``data/``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"


def make_checkout(tmp: Path) -> Path:
    """A directory with a copy of ``bench/``, the test BENCHMARK.json and
    the test-only traffic mix: a root ``harness.find_cell`` can read."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "bench" / "tests" / "data").mkdir(parents=True)
    for f in DATA.glob("*.json"):
        cfg = json.loads(f.read_text())
        if "reference" in cfg:
            # a test size of a benchmark configuration keeps its limits
            full = BENCH / "configs" / f"{cfg['name'].rsplit('-', 1)[0]}-224.json"
            cfg["limits"] = json.loads(full.read_text())["limits"]
        (tmp / "bench" / "tests" / "data" / f.name).write_text(json.dumps(cfg))
    shutil.copy(DATA / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copy(DATA / "b2.straggler.json", tmp / "bench" / "traffic")
    return tmp


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> Path:
    return make_checkout(tmp_path_factory.mktemp("checkout"))
