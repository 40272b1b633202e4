"""Compiles for a described TPU v5e: the main path's kernels at real widths.

Nothing here needs a chip.  The TPU compiler that ships with jax compiles
for a topology it is told about (``v5e:2x2``), and refuses what the chip
would refuse — a kernel whose blocks overflow the scoped VMEM, a block not
aligned to the tiling, a program that cannot be partitioned.  Interpret
mode on the CPU sees none of that.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every xdist worker
imports this file.  These compiles all live in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.schemes import get_scheme
from repro.core.splitting import plan_width_split
from repro.dist.backend import CodedOp
from repro.dist.mesh_exec import MeshExecutor
from repro.kernels.mds_decode import mds_decode_pallas
from repro.kernels.mds_encode import mds_encode_pallas, skinny_gemm_pallas
from repro.models.cnn import vgg16_conv_specs

V5E_HBM = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_gemm(sharding, m, b, f):
    gemm = jax.jit(functools.partial(skinny_gemm_pallas, interpret=False))
    return gemm.lower(_sds((m, b), sharding), _sds((b, f), sharding)).compile()


# (tokens per piece, d_in, d_out): gemma-2b gate/up (2048 -> 16384) and
# down (16384 -> 2048) at a decode step (4 lanes -> 1 token per piece) and
# at a packed prefill (512 tokens -> 171 per piece), plus the shapes the
# untiled kernel was refused at (minicpm-2b's down projection among them)
PIECE_GEMMS = [
    (1, 2048, 16384), (1, 16384, 2048),
    (171, 2048, 16384), (171, 16384, 2048),
    (8, 16384, 2048), (8, 8192, 2048), (8, 5760, 2304),
    (1024, 2048, 16384), (8, 2048, 16384),
]


@pytest.mark.parametrize("m,b,f", PIECE_GEMMS,
                         ids=[f"{m}x{b}x{f}" for m, b, f in PIECE_GEMMS])
def test_piece_gemm_compiles(one_chip, m, b, f):
    compiled = _compile_gemm(one_chip, m, b, f)
    assert "tpu_custom_call" in compiled.as_text()   # a kernel, not XLA


def test_mds_encode_decode_compile_at_vgg16_conv1_2(one_chip):
    """The encode (G @ X) and decode (D @ Y) GEMMs over conv1_2's
    flattened width partitions at 224x224, mds(4, 3)."""
    spec = vgg16_conv_specs(224)[1].spec
    plan = plan_width_split(spec, 3)
    f_in = spec.batch * spec.c_in * spec.h_in * plan.w_in_p
    f_out = spec.batch * spec.c_out * spec.h_out * plan.w_out_p
    for kernel, rows, f in ((mds_encode_pallas, 4, f_in),
                            (mds_decode_pallas, 3, f_out)):
        compiled = jax.jit(functools.partial(kernel, interpret=False)).lower(
            _sds((rows, 3), one_chip), _sds((3, f), one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_mesh_program_compiles_on_four_chips(topo):
    """MeshExecutor's whole coded GEMM — per-slice encode, piece GEMM,
    masked gather, column-parallel decode — as one program on a 2x2 v5e,
    one mds(4, 3) piece per chip, at gemma-2b's up projection."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    replicated = NamedSharding(mesh, P())
    scheme = get_scheme("mds").make(4, 3)
    x = _sds((3 * 171, 2048), replicated)
    w = _sds((2048, 16384), replicated)
    ex = MeshExecutor(mesh, dead=(2,), interpret=False)
    program = ex._build(CodedOp("matmul", scheme, x, w), ex._subset(scheme))
    compiled = program.lower(x, w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "num_partitions=4" in text
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < V5E_HBM
