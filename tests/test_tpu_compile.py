"""The main path's Pallas kernel compiles for a TPU v5e, without a chip.

The TPU compiler is installed with jax and compiles for a v5e that is
described, not attached.  These tests compile the int8 GEMM through
Mosaic (``interpret=False``) at every shape and block the MLPerf-Tiny
mappings hand it, and check that ``lower()`` only picks blocks from that
list.  The topology is described inside a module fixture (never at
import): only one process may load the TPU library at a time.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backend import lower
from repro.cnn import mlperf_tiny_networks
from repro.core import apply_transforms, dispatch
from repro.core.graph import dead_node_elimination, integerize, layout_to
from repro.kernels.matmul_requant import matmul_requant
from repro.kernels.tpu import interpret_mode, tpu_block

# (M, K, N) with blocks (bm, bk, bn): every pallas_gemm segment lower()
# builds for the four nets on gap9, diana and ne16_octa
GEMM_CASES = [
    ((1, 256, 2), (1, 256, 2)),  # MobileNet head
    ((1, 64, 10), (1, 64, 10)),  # ResNet head
    ((1, 64, 12), (1, 64, 12)),  # DS-CNN head
    ((1, 640, 128), (1, 640, 128)),  # DAE encoder in
    ((1, 128, 128), (1, 128, 128)),
    ((1, 128, 8), (1, 128, 8)),
    ((1, 8, 128), (1, 8, 128)),
    ((1, 128, 640), (1, 128, 128)),  # DAE decoder out, gap9/diana tile
    ((1, 128, 640), (1, 128, 640)),  # DAE decoder out, ne16_octa tile
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gemm(bm, bk, bn):
    def f(a, w, mult, bias):
        return matmul_requant(
            a, w, mult, bias, shift=5, relu=True, rounding="even",
            block_m=bm, block_k=bk, block_n=bn, interpret=False,
        )

    return f


def _operands(one_chip, m, k, n, slots=None):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    a_shape = (m, k) if slots is None else (slots, m, k)
    return s(a_shape, jnp.int8), s((k, n), jnp.int8), s((n,), jnp.int32), s((n,), jnp.int32)


@pytest.mark.parametrize("shape,blocks", GEMM_CASES, ids=lambda v: "x".join(map(str, v)))
def test_gemm_compiles_for_v5e(one_chip, shape, blocks):
    compiled = jax.jit(_gemm(*blocks)).lower(*_operands(one_chip, *shape)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vmapped_gemm_compiles_for_v5e(one_chip):
    """The 8-slot batch ``BatchedModel`` builds over DAE's 640->128 layer."""
    batched = jax.vmap(_gemm(1, 640, 128), in_axes=(0, None, None, None))
    args = _operands(one_chip, 1, 640, 128, slots=8)
    compiled = jax.jit(batched).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_int8_gemm_takes_sublane_blocks_of_8(one_chip):
    """Mosaic accepts int8 row blocks of 8, so SUBLANE needs no int8 case."""
    compiled = jax.jit(_gemm(8, 128, 128)).lower(*_operands(one_chip, 64, 128, 128)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("target", ["gap9", "diana", "ne16_octa"])
@pytest.mark.parametrize("net", ["MobileNet", "ResNet", "DSCNN", "DAE"])
def test_lower_picks_compiled_blocks(net, target):
    g = apply_transforms(
        mlperf_tiny_networks()[net], [dead_node_elimination, integerize(1), layout_to("NHWC")]
    )
    gemms = [ls for ls in lower(dispatch(g, target)).segments if ls.route == "pallas_gemm"]
    assert gemms, (net, target)
    for ls in gemms:
        shape = tuple(int(ls.segment.anchor.attr(a)) for a in ("B", "C", "K"))
        assert (shape, ls.meta["blocks"]) in GEMM_CASES, (net, target, shape, ls.meta["blocks"])


@pytest.mark.parametrize(
    "block,dim,quantum,want",
    [
        (64, 128, 128, 128),  # below one lane: the whole dim
        (320, 640, 128, 128),  # 256 does not divide 640
        (640, 640, 128, 640),
        (2, 2, 128, 2),  # a dim narrower than a lane stays whole
        (64, 1024, 128, 128),  # raised to one lane, not the whole dim
        (100, 200, 128, 200),  # no lane multiple divides 200
        (1, 64, 8, 8),
        (24, 64, 8, 16),
    ],
)
def test_tpu_block(block, dim, quantum, want):
    got = tpu_block(block, dim, quantum)
    assert got == want
    assert dim % got == 0


def test_pallas_runs_interpreted_on_cpu():
    assert jax.default_backend() == "cpu"
    assert interpret_mode()
    a = jnp.ones((1, 128), jnp.int8)
    w = jnp.ones((128, 128), jnp.int8)
    n = jnp.zeros((128,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda a, w: matmul_requant(a, w, n, n))(a, w))
    assert "interpret=True" in jaxpr
