"""The persistent compile cache rule (launch/compile_cache.py)."""
from pathlib import Path

import pytest

from repro.launch.compile_cache import CACHE_ENV, compile_cache_dir

REPO = Path(__file__).resolve().parents[1]


def test_environment_variable_wins(tmp_path):
    assert compile_cache_dir({CACHE_ENV: str(tmp_path)}) == tmp_path


@pytest.mark.parametrize("env", [{}, {CACHE_ENV: ""}], ids=["unset", "empty"])
def test_default_is_fixed_inside_the_checkout(env):
    first, second = compile_cache_dir(env), compile_cache_dir(dict(env))
    assert first == second == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
