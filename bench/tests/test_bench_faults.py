"""A whole run of the harness on the CPU at a small size (the look for a
chip skipped): correct as it stands, and not correct when the timed path
is broken underneath or the control stands in for the program."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

B1, B2 = "vgg16-64.b1.straggler", "vgg16-64.b2.straggler"
R1 = "resnet18-64.b1.straggler"


def _run(root, name, seed=2**31 + 5, control=None):
    cell = harness.find_cell(root, name)
    return harness.run_cell(cell, seed, 0.5, False, t0=time.perf_counter(),
                            devices=jax.devices(), control=control,
                            log=lambda *a: None)


def _refused(out):
    err = out["checks"]["logit_rel_err"]
    assert out["correct"] is False
    assert out["failed"] > 0
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("name", [B1, B2])
def test_a_sound_run_is_correct(checkout, name):
    out = _run(checkout, name)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p90_ms",
                                   "images_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"   # never named a TPU


@pytest.mark.parametrize("name", [B1, R1])
def test_the_three_pass_control_is_not_correct(checkout, name):
    _refused(_run(checkout, name, control="reference:bf16_3x"))


def test_an_answer_altered_where_it_is_produced(checkout, monkeypatch):
    import repro.models.cnn as cnn

    forward = cnn.vgg16_forward

    def altered(*a, **kw):
        y = forward(*a, **kw)
        return y.at[0, 0].add(1e-3 * jnp.max(jnp.abs(y)))
    monkeypatch.setattr(cnn, "vgg16_forward", altered)
    _refused(_run(checkout, B1))


def test_the_exchange_between_workers_left_out(checkout, monkeypatch):
    """The first arrived piece of every coded run never reaches the master
    (its rows read as zeros)."""
    import repro.dist.executor as executor

    decode = executor.decode_blocks

    def lossy(scheme, subset, stacked, chunks=1):
        return decode(scheme, subset, stacked.at[0].set(0.0), chunks=chunks)
    monkeypatch.setattr(executor, "decode_blocks", lossy)
    _refused(_run(checkout, B1))


def test_half_of_the_batch_left_out(checkout, monkeypatch):
    """The conv stack runs on the first half of the batch only; the second
    half's rows repeat it."""
    import repro.models.cnn as cnn

    plan = cnn.forward_plan

    def half(p, convs, x, **kw):
        y = plan(p, convs, x[: x.shape[0] // 2], **kw)
        return jnp.concatenate([y, y], axis=0)
    monkeypatch.setattr(cnn, "forward_plan", half)
    _refused(_run(checkout, B2))
