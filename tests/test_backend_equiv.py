"""Backend equivalence: threaded CodedExecutor vs shard_map MeshExecutor.

ISSUE 8 tentpole contract (DESIGN.md §13): both implementations of the
``dist/backend.py`` seam must decode BITWISE-identically for every
registered scheme under every modeled fault pattern — no fault, a dead
worker (its piece redispatched, arriving last), a straggler (arriving
after every healthy piece).  The threaded backend derives the decodable
subset from k-th-arrival order on its virtual clock; the mesh backend
derives the same subset ahead of dispatch from its configured pattern and
masks the rest — if either side drifts, the byte comparison here fails.

Runs on forced 8-way CPU devices (conftest) so the mesh is real SPMD.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.coded_conv import coded_conv2d, conv2d
from repro.core.coded_linear import coded_matmul
from repro.core.schemes import get_scheme, scheme_names
from repro.core.splitting import ConvSpec
from repro.dist import (CodedExecutor, DeterministicDelay, FakeClock,
                        FaultPlan, MeshExecutor)
from repro.dist.backend import CodedOp, ExecBackend
from repro.launch.mesh import PiecePlacementError, make_local_mesh
from repro.models.model import ModelConfig
from repro.serving import Engine, Request, ServingScheduler

N = 5  # pieces per coded op in the equivalence matrix (<= 8 devices)

# (label, threaded FaultPlan kwargs, mesh fault kwargs) — the same fault,
# expressed in each backend's native vocabulary
FAULTS = [
    ("none", {}, {}),
    ("dead", dict(fault_plan=FaultPlan(dead=frozenset({1}))),
     dict(dead=(1,))),
    ("straggler", dict(fault_plan=FaultPlan(straggler={2: 50.0})),
     dict(stragglers=(2,))),
]
FAULT_IDS = [f[0] for f in FAULTS]


def _scheme(name, n=N):
    cls = get_scheme(name)
    if name in ("mds", "lt"):
        return cls.make(n, 3)
    return cls.make(n)  # structural k: replication floor(n/2), uncoded n


def _pair(n, fp_kw, mesh_kw):
    ex_t = CodedExecutor(n, clock=FakeClock(),
                         delay_model=DeterministicDelay(1.0), **fp_kw)
    ex_m = MeshExecutor(**mesh_kw)
    return ex_t, ex_m


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("fault,fp_kw,mesh_kw", FAULTS, ids=FAULT_IDS)
@pytest.mark.parametrize("name", scheme_names())
class TestCrossBackendBitwise:
    def test_matmul(self, name, fault, fp_kw, mesh_kw, rng):
        code = _scheme(name)
        x = jnp.asarray(rng.normal(size=(13, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        ex_t, ex_m = _pair(code.n, fp_kw, mesh_kw)
        try:
            y_t = coded_matmul(x, w, code, executor=ex_t)
            y_m = coded_matmul(x, w, code, executor=ex_m)
            # both masters consumed the SAME decodable subset...
            assert (list(ex_t.last_report.subset)
                    == list(ex_m.last_report.subset))
        finally:
            ex_t.close()
            ex_m.close()
        # ...and decoded to the SAME bytes (-0.0 included)
        assert _bitwise(y_t, y_m)
        assert np.allclose(y_t, x @ w, rtol=1e-3, atol=2e-3)

    def test_conv2d(self, name, fault, fp_kw, mesh_kw, rng):
        code = _scheme(name)
        spec = ConvSpec(c_in=3, c_out=4, h_in=12, w_in=26, kernel=3,
                        stride=1, batch=2)
        x = jnp.asarray(rng.normal(size=(2, 3, 12, 26)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(4, 3, 3, 3)), jnp.float32)
        ex_t, ex_m = _pair(code.n, fp_kw, mesh_kw)
        try:
            y_t = coded_conv2d(x, w, code, spec, executor=ex_t)
            y_m = coded_conv2d(x, w, code, spec, executor=ex_m)
            assert (list(ex_t.last_report.subset)
                    == list(ex_m.last_report.subset))
        finally:
            ex_t.close()
            ex_m.close()
        assert _bitwise(y_t, y_m)
        assert np.allclose(y_t, conv2d(x, w, spec.stride),
                           rtol=1e-3, atol=2e-3)


class TestCrossBackendDecodePaths:
    def test_replicated_decode_fallback_matches(self, rng):
        # d_out NOT a multiple of the device count: the mesh decode cannot
        # column-shard and must fall back to the replicated decode — the
        # bytes still match the threaded backend
        code = _scheme("mds")
        x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
        ex_t, ex_m = _pair(code.n, {}, {})
        try:
            y_t = coded_matmul(x, w, code, executor=ex_t)
            y_m = coded_matmul(x, w, code, executor=ex_m)
        finally:
            ex_t.close()
            ex_m.close()
        assert _bitwise(y_t, y_m)


# ---------------------------------------------------------------------------
# the seam itself: protocol conformance, CodedOp validation
# ---------------------------------------------------------------------------

class TestBackendSeam:
    def test_both_backends_satisfy_protocol(self):
        ex_t = CodedExecutor(3, clock=FakeClock(),
                             delay_model=DeterministicDelay(1.0))
        ex_m = MeshExecutor()
        try:
            assert isinstance(ex_t, ExecBackend)
            assert isinstance(ex_m, ExecBackend)
        finally:
            ex_t.close()
            ex_m.close()

    def test_coded_op_validates(self):
        code = _scheme("mds")
        x = jnp.zeros((3, 4, 8), jnp.float32)
        w = jnp.zeros((8, 4), jnp.float32)
        with pytest.raises(ValueError, match="kind"):
            CodedOp("solve", code, x, w)
        with pytest.raises(ValueError, match="ConvSpec"):
            CodedOp("conv2d", code, x, w)


# ---------------------------------------------------------------------------
# MeshExecutor specifics: compile-once, placement errors, report surface
# ---------------------------------------------------------------------------

class TestMeshExecutor:
    def test_compile_once_per_shape(self, rng):
        code = _scheme("mds")
        w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        xa = jnp.asarray(rng.normal(size=(code.k * 4, 8)), jnp.float32)
        xb = jnp.asarray(rng.normal(size=(code.k * 6, 8)), jnp.float32)
        with MeshExecutor() as ex:
            ex.run_op(CodedOp("matmul", code, xa, w))
            ex.run_op(CodedOp("matmul", code, xa, w))
            assert ex.compile_count == 1  # same (scheme, shapes): cached
            ex.run_op(CodedOp("matmul", code, xb, w))
            assert ex.compile_count == 2  # new token count: one more build
            assert ex.run_count == 3

    def test_too_many_pieces_is_typed(self, rng):
        code = get_scheme("mds").make(9, 3)  # 9 pieces > 8 device slices
        x = jnp.asarray(rng.normal(size=(3 * 4, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        with MeshExecutor() as ex:
            with pytest.raises(PiecePlacementError, match="extent"):
                ex.run_op(CodedOp("matmul", code, x, w))

    def test_make_local_mesh_model_override(self):
        mesh = make_local_mesh(model=4)
        assert int(mesh.shape["model"]) == 4
        assert int(mesh.shape["data"]) == 2
        with pytest.raises(PiecePlacementError, match="1 <= model"):
            make_local_mesh(model=0)
        with pytest.raises(PiecePlacementError, match="divide"):
            make_local_mesh(model=3)
        with pytest.raises(PiecePlacementError, match="1 <= model"):
            make_local_mesh(model=16)

    def test_bad_axis_and_bad_order_are_typed(self, rng):
        with pytest.raises(PiecePlacementError, match="no 'nope' axis"):
            MeshExecutor(axis="nope")
        code = _scheme("mds")
        x = jnp.asarray(rng.normal(size=(3 * 4, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        with MeshExecutor(order=(0, 0, 1, 2, 3)) as ex:
            with pytest.raises(ValueError, match="permutation"):
                ex.run_op(CodedOp("matmul", code, x, w))

    def test_thunk_surface_is_refused(self):
        with MeshExecutor() as ex:
            with pytest.raises(NotImplementedError, match="thunk"):
                ex.run(_scheme("mds"), [lambda: None])

    def test_report_surface(self, rng):
        code = _scheme("mds")
        x = jnp.asarray(rng.normal(size=(code.k * 4, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        seen = []
        with MeshExecutor(dead=(1,)) as ex:
            ex.on_report = seen.append
            ex.run_op(CodedOp("matmul", code, x, w))
            rep = ex.last_report
        assert seen == [rep]
        assert rep.wall_s > 0.0 and rep.t_complete == rep.wall_s
        assert all(isinstance(p, int) for p in rep.subset)
        assert rep.failures == [(1, 0.0)]
        assert 1 not in rep.subset  # mds(5,3) never needs the dead piece
        # dispatch bookkeeping: n pieces, no redispatch consumed
        assert ex.pool.dispatch_count == code.n
        assert sorted(ex.pool.alive_workers()) == list(range(8))


# ---------------------------------------------------------------------------
# engine + scheduler on the mesh backend
# ---------------------------------------------------------------------------

def _eng_cfg(scheme="mds", n=4, k=3):
    return ModelConfig(name="mesh-t", n_layers=1, d_model=16, n_heads=2,
                       n_kv_heads=2, d_ff=32, vocab=32, gated=False,
                       dtype=jnp.float32, coded_n=n, coded_k=k,
                       coded_scheme=scheme)


def _eng_reqs(n=3):
    return [Request(i, ((np.arange(4) + 2 * i) % 32).astype(np.int32),
                    max_new=2, arrival_s=0.0) for i in range(n)]


class TestMeshServing:
    def test_engine_string_shorthand_and_token_parity(self):
        # the SAME weights + coded math on both backends: generated tokens
        # must match token-for-token (the GEMMs are bitwise identical)
        eng_m = Engine(_eng_cfg(), seed=0, executor="mesh")
        with CodedExecutor(4, clock=FakeClock(),
                           delay_model=DeterministicDelay(1.0)) as ex:
            eng_t = Engine(_eng_cfg(), seed=0, executor=ex)
            out_t = eng_t.generate(_eng_reqs())
        out_m = eng_m.generate(_eng_reqs())
        assert eng_m.executor.run_count > 0
        for a, b in zip(out_t, out_m):
            assert a.rid == b.rid
            assert a.tokens.tolist() == b.tokens.tolist()

    def test_engine_rejects_unknown_backend_string(self):
        with pytest.raises(ValueError, match="unknown executor backend"):
            Engine(_eng_cfg(), seed=0, executor="bogus")

    def test_engine_rejects_segment_on_mesh(self):
        with pytest.raises(ValueError, match="threaded backend"):
            Engine(_eng_cfg("replication", n=4, k=2), seed=0,
                   executor=MeshExecutor(), segment=True)

    def test_engine_rejects_adaptive_on_mesh(self):
        with pytest.raises(ValueError, match="threaded pool backend"):
            Engine(_eng_cfg(), seed=0, executor=MeshExecutor(),
                   adaptive=True)

    def test_scheduler_serves_on_mesh(self):
        eng = Engine(_eng_cfg(), seed=0, executor="mesh")
        sched = ServingScheduler(eng, max_seq=16, max_batch=2,
                                 master_call_s=1e-3)
        res = sched.serve(_eng_reqs())
        assert len(res.completions) == 3
        assert all(len(c.tokens) > 0 for c in res.completions)
        assert eng.executor.run_count > 0
        assert all(s.coded_n == 4 and s.coded_k == 3 for s in res.steps)


# ---------------------------------------------------------------------------
# REPRO_BACKEND switch: the same test body runs on whichever backend CI picks
# ---------------------------------------------------------------------------

class TestBackendSwitch:
    def test_coded_matmul_on_session_backend(self, make_executor,
                                             backend_name, rng):
        code = _scheme("mds")
        x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        ex = make_executor(code.n)
        y = coded_matmul(x, w, code, executor=ex)
        assert np.allclose(y, x @ w, rtol=1e-3, atol=2e-3)
        assert ex.run_count == 1
        if backend_name == "mesh":
            assert ex.compile_count == 1

    def test_fault_tolerant_on_session_backend(self, make_executor, rng):
        code = _scheme("mds")
        x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        ex = make_executor(code.n, dead=(0,), stragglers=(3,))
        y = coded_matmul(x, w, code, executor=ex)
        assert np.allclose(y, x @ w, rtol=1e-3, atol=2e-3)
        assert 0 not in ex.last_report.subset
        assert 3 not in ex.last_report.subset
