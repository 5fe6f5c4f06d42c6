"""repro.kernels — Pallas TPU kernels for the compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
with its jnp oracle in ref.py and the DSE-scheduled jit wrapper in ops.py.
The kernels run under the Pallas interpreter on the CPU backend and
compile through Mosaic on a TPU (tpu.py derives the mode and the legal
block dims); tests check them against the oracles on the CPU and compile
the main path's GEMM for a described v5e.
"""

from . import ops, ref
from .flash_attention import flash_attention
from .matmul_requant import matmul_requant
from .moe_gmm import moe_gmm
from .rglru_scan import rglru_scan
from .ssd_scan import ssd_scan
from .tiled_conv import tiled_conv2d

__all__ = [
    "ops",
    "ref",
    "flash_attention",
    "matmul_requant",
    "moe_gmm",
    "rglru_scan",
    "ssd_scan",
    "tiled_conv2d",
]
