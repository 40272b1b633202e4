"""The entry encode of a coded segment as compiled programs
(``coded_conv._entry_pieces``): the k partitions' slices, their stack and
flatten in one program, the scheme's own encode GEMM, the split into the
n pieces in another; a selection scheme's pieces in one slicing program.

* the pieces are bit-identical to the eager pipeline they replace
  (slices, stack, the scheme's encode, per-piece indexing);
* a warmed encode transfers nothing from the host (slice bounds are
  static, the encode matrix stays on the device);
* every segment encode of a coded forward counts ``encodes_compiled``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coded_conv import _entry_pieces, _piece_chains, run_segment
from repro.core.schemes import commutes_elementwise, get_scheme
from repro.core.splitting import ConvSpec, plan_segment_split
from repro.models import cnn
from repro.telemetry import trace

SCHEMES = {"mds43": ("mds", 4, 3), "mds44": ("mds", 4, 4),
           "lt": ("lt", 5, 3), "replication": ("replication", 4, None),
           "uncoded": ("uncoded", 4, None)}


def _scheme(name):
    kind, n, k = SCHEMES[name]
    return get_scheme(kind).make(n, k)


def _segment(scheme, batch, remainder):
    """A one-layer 3x3 segment whose output width leaves ``remainder``
    columns to the master (or none), and its padded entry input."""
    k = scheme.k
    w_out = 5 * k + (1 if remainder else 0)
    spec = ConvSpec(c_in=6, c_out=4, h_in=9, w_in=w_out + 2, kernel=3)
    split = plan_segment_split([spec], [1], k)
    assert (split.remainder is not None) == remainder
    x = jax.random.normal(jax.random.PRNGKey(k + batch),
                          (batch, 6, 9, w_out + 2))
    return x, spec, split


def _eager_pieces(x, scheme, split):
    """The pipeline before compilation, op by op."""
    if commutes_elementwise(scheme):
        return [x[..., cp.entry.a_i:cp.entry.b_i]
                for cp in _piece_chains(scheme, split)]
    parts = jnp.stack([x[..., cp.entry.a_i:cp.entry.b_i]
                       for cp in split.parts])
    coded = scheme.encode(parts.reshape(scheme.k, -1))
    coded = coded.reshape((scheme.n,) + parts.shape[1:])
    return [coded[i] for i in range(scheme.n)]


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


@pytest.mark.parametrize("remainder", [False, True],
                         ids=["even", "remainder"])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("name", sorted(SCHEMES))
def test_pieces_are_bit_identical_to_the_eager_encode(name, batch, remainder):
    scheme = _scheme(name)
    x, _, split = _segment(scheme, batch, remainder)
    got = _entry_pieces(x, scheme, split)
    want = _eager_pieces(x, scheme, split)
    assert isinstance(got, tuple) and len(got) == scheme.n
    assert [_bits(g) for g in got] == [_bits(w) for w in want]


@pytest.mark.parametrize("name", ["mds43", "lt", "replication"])
def test_a_warmed_encode_transfers_nothing(name):
    scheme = _scheme(name)
    x, _, split = _segment(scheme, 1, True)
    jax.block_until_ready(_entry_pieces(x, scheme, split))
    with jax.transfer_guard_host_to_device("disallow"):
        jax.block_until_ready(_entry_pieces(x, scheme, split))


def test_the_encode_matrix_is_made_once():
    scheme = _scheme("mds43")
    x, _, split = _segment(scheme, 1, False)
    from repro.core.coding import device_matrix

    device_matrix.cache_clear()
    for _ in range(3):
        _entry_pieces(x, scheme, split)
    info = device_matrix.cache_info()
    assert (info.misses, info.hits) == (1, 2)


def test_every_segment_encode_of_a_forward_is_compiled():
    params = cnn.init_vgg16(jax.random.PRNGKey(0), image=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 64, 64))
    names = {"encodes": "encodes", "encodes_compiled": "encodes_compiled"}
    with trace.counting(names) as ops:
        y = cnn.vgg16_forward(params, x, scheme="mds", n=4)
    rec = trace.request_log.last(1)[0]
    assert ops["encodes"] >= 2
    assert ops["encodes_compiled"] == ops["encodes"]
    assert rec.count("encodes_compiled") == rec.count("encodes") \
        == ops["encodes"]
    ref = cnn.vgg16_forward(params, x)
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) < 1e-5


def test_run_segment_encodes_through_the_compiled_pieces(monkeypatch):
    """``run_segment`` takes its pieces from ``_entry_pieces``: its
    output is the plain conv's, and the encode ran once."""
    from repro.core import coded_conv

    calls = []
    real = coded_conv._entry_pieces
    monkeypatch.setattr(coded_conv, "_entry_pieces",
                        lambda *a: calls.append(a) or real(*a))
    scheme = _scheme("mds43")
    x, spec, split = _segment(scheme, 2, True)
    w = jax.random.normal(jax.random.PRNGKey(7), (4, 6, 3, 3))
    y = run_segment(x, [w], scheme, [spec], [1], [None], split=split)
    ref = coded_conv.conv2d(x, w)
    assert len(calls) == 1
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) < 1e-5
