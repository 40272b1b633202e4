"""The exit decode of a coded segment as compiled programs
(``schemes.decode_pieces`` and ``coded_conv._join_parts``): the arrived
pieces' stack and flatten in one program, the scheme's own decode GEMM
(``skinny_gemm``), the unflatten in another, the join along W in a
fourth; a selection scheme keeps its pieces by static position inside the
stack program and runs no GEMM.

* the segment output is bit-identical to the eager decode it replaces
  (stack, flatten, a decode matrix uploaded per call, unflatten, unstack
  and concatenate), on the functional path and on a real-clock pool
  whose arrival order is not sorted;
* a warmed decode transfers nothing from the host;
* every segment decode of a coded forward counts ``decodes_compiled``;
* the decode matrix has one device copy per ordered subset and dtype;
* the decode GEMM stays its own ``skinny_gemm`` program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coded_conv import (_chain, _entry_pieces, _join_parts,
                                   _piece_chains, run_segment)
from repro.core.coding import device_matrix
from repro.core.schemes import (_lt_decode_matrix, decode_pieces, get_scheme,
                                resolve_subset)
from repro.core.splitting import ConvSpec, plan_segment_split
from repro.dist import CodedExecutor, DeterministicDelay, RealClock
from repro.kernels.ops import mds_decode
from repro.models import cnn
from repro.telemetry import trace

SCHEMES = {"mds43": ("mds", 4, 3), "mds44": ("mds", 4, 4),
           "lt": ("lt", 5, 3), "replication": ("replication", 4, None),
           "uncoded": ("uncoded", 4, None)}
# per-worker piece seconds on the real-clock pool: arrivals 1, 3, 0, 2, 4
DELAYS = (0.04, 0.0, 0.06, 0.02, 0.08)


def _scheme(name):
    kind, n, k = SCHEMES[name]
    return get_scheme(kind).make(n, k)


def _segment(scheme, batch, remainder):
    """A one-layer 3x3 segment whose output width leaves ``remainder``
    columns to the master (or none), its padded entry input and weight."""
    k = scheme.k
    w_out = 5 * k + (1 if remainder else 0)
    spec = ConvSpec(c_in=6, c_out=4, h_in=9, w_in=w_out + 2, kernel=3)
    split = plan_segment_split([spec], [1], k)
    assert (split.remainder is not None) == remainder
    x = jax.random.normal(jax.random.PRNGKey(k + batch),
                          (batch, 6, 9, w_out + 2))
    w = jax.random.normal(jax.random.PRNGKey(7), (4, 6, 3, 3))
    return x, w, spec, split


def _eager_decode(scheme, subset, outs):
    """The decode before compilation, op by op: an eager stack and
    flatten, the decode matrix uploaded on the call (or the selection's
    index array), the GEMM, an eager unflatten."""
    stacked = jnp.stack(outs)
    flat = stacked.reshape(len(outs), -1)
    if hasattr(scheme, "decode_positions"):
        dec = flat[jnp.asarray(scheme.decode_positions(subset))]
    else:
        D = (scheme.decode_matrix(subset) if hasattr(scheme, "decode_matrix")
             else _lt_decode_matrix(scheme.n, scheme.k, scheme.seed,
                                    scheme.c, scheme.delta, tuple(subset)))
        dec = mds_decode(jnp.asarray(D, dtype=flat.dtype), flat)
    return dec.reshape((scheme.k,) + stacked.shape[1:])


def _eager_segment(x, w, scheme, spec, split, subset):
    """The whole segment with the eager exit: the same entry encode and
    chains, the eager decode, unstack and concatenate, the remainder."""
    piece_in = _entry_pieces(x, scheme, split)
    chains = _piece_chains(scheme, split)
    args = ([w], [spec], [1], [None], [None])
    outs = [_chain(piece_in[i], chains[i], *args, apply_acts=False)
            for i in subset]
    y = jnp.concatenate(list(_eager_decode(scheme, subset, outs)), axis=-1)
    if split.remainder is not None:
        r = split.remainder
        y_rem = _chain(x[..., r.entry.a_i:r.entry.b_i], r, *args,
                       apply_acts=True)
        y = jnp.concatenate([y, y_rem], axis=-1)
    return y


def _pool(scheme):
    return CodedExecutor(scheme.n, clock=RealClock(),
                         delay_model=DeterministicDelay(DELAYS[:scheme.n]))


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


@pytest.mark.parametrize("path", ["functional", "executor"])
@pytest.mark.parametrize("remainder", [False, True],
                         ids=["even", "remainder"])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("name", sorted(SCHEMES))
def test_segment_is_bit_identical_to_the_eager_decode(name, batch, remainder,
                                                      path):
    scheme = _scheme(name)
    x, w, spec, split = _segment(scheme, batch, remainder)
    run = lambda ex: run_segment(x, [w], scheme, [spec], [1], [None],
                                 split=split, executor=ex)
    if path == "functional":
        got, subset = run(None), resolve_subset(scheme, None)
    else:
        with _pool(scheme) as ex:
            got = run(ex)
            subset = list(ex.last_report.subset)
        assert subset != sorted(subset)  # arrival order, not worker order
    want = _eager_segment(x, w, scheme, spec, split, subset)
    assert _bits(got) == _bits(want)


@pytest.mark.parametrize("name", sorted(SCHEMES))
def test_a_warmed_decode_transfers_nothing(name):
    """The functional decode and join, and the executor's decode at the
    accepting arrival, once warmed, run with host-to-device transfers
    disallowed."""
    scheme = _scheme(name)
    x, w, _, split = _segment(scheme, 2, True)
    piece_in = _entry_pieces(x, scheme, split)
    subset = resolve_subset(scheme, None)
    outs = [jax.lax.conv(piece_in[i], w, (1, 1), "VALID") for i in subset]
    decode = lambda: _join_parts(decode_pieces(scheme, subset, outs))
    jax.block_until_ready(decode())
    with jax.transfer_guard_host_to_device("disallow"):
        jax.block_until_ready(decode())
    with _pool(scheme) as ex:
        for guard in ("allow", "disallow"):
            h = ex.run_async(scheme, [lambda p=p: p for p in piece_in])
            h.wait()
            with jax.transfer_guard_host_to_device(guard):
                jax.block_until_ready(_join_parts(h.result()))


@pytest.mark.parametrize("net", ["vgg16", "resnet18"])
def test_every_segment_decode_of_a_forward_is_compiled(net):
    init, fwd = {"vgg16": (cnn.init_vgg16, cnn.vgg16_forward),
                 "resnet18": (cnn.init_resnet18, cnn.resnet18_forward)}[net]
    params = init(jax.random.PRNGKey(0), image=32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 32, 32))
    names = {"decodes": "decodes", "decodes_compiled": "decodes_compiled"}
    with trace.counting(names) as ops:
        y = fwd(params, x, scheme="mds", n=4)
    rec = trace.request_log.last(1)[0]
    assert ops["decodes"] >= 2
    assert ops["decodes_compiled"] == ops["decodes"]
    assert rec.count("decodes_compiled") == rec.count("decodes") \
        == ops["decodes"]
    ref = fwd(params, x)
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) < 1e-5


@pytest.mark.parametrize("name", ["mds43", "lt"])
def test_one_device_matrix_per_ordered_subset_and_dtype(name):
    """Equal schemes share the copy; a reordered subset is a key of its
    own (its matrix is the columns permuted), as is another dtype."""
    kind, n, k = SCHEMES[name]
    rows = jnp.asarray(np.random.default_rng(3).normal(size=(k, 16)),
                       jnp.float32)
    device_matrix.cache_clear()
    for subset in ([0, 1, 2], [2, 0, 1], [0, 1, 2]):
        get_scheme(kind).make(n, k).decode_from(subset, rows)
    get_scheme(kind).make(n, k).decode_from([0, 1, 2],
                                            rows.astype(jnp.bfloat16))
    info = device_matrix.cache_info()
    assert (info.currsize, info.misses, info.hits) == (3, 3, 1)


@pytest.mark.parametrize("name", ["mds43", "lt"])
def test_the_decode_gemm_stays_its_own_skinny_gemm_program(name):
    """The decode and join trace to four programs at top level: stack and
    flatten, the ``skinny_gemm`` GEMM, unflatten, join; no new program
    takes the GEMM's or the conv's name."""
    scheme = _scheme(name)
    subset = resolve_subset(scheme, None)
    outs = [jnp.ones((2, 4, 7, 5), jnp.float32) * i for i in subset]
    jaxpr = jax.make_jaxpr(
        lambda *o: _join_parts(decode_pieces(scheme, subset, o)))(*outs)
    names = [e.params.get("name") for e in jaxpr.jaxpr.eqns]
    assert names == ["_piece_rows", "skinny_gemm_pallas", "_source_blocks",
                     "_join_parts"]
    assert not any("skinny_gemm" in n or "conv_general_dilated" in n
                   for n in names if n != "skinny_gemm_pallas")


def test_a_selection_decode_keeps_its_pieces_in_the_stack_program():
    scheme = _scheme("replication")
    subset = [1, 3, 0]
    outs = [jnp.full((2, 3), i, jnp.float32) for i in subset]
    jaxpr = jax.make_jaxpr(
        lambda *o: decode_pieces(scheme, subset, o))(*outs)
    names = [e.params.get("name") for e in jaxpr.jaxpr.eqns]
    assert names == ["_piece_rows", "_source_blocks"]
    got = decode_pieces(scheme, subset, outs)
    assert _bits(got) == _bits(_eager_decode(scheme, subset, outs))
