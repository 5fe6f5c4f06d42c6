"""jit'd kernel wrappers with DSE-selected BlockSpecs.

This is the MATCH "specialized codegen branch" for TPU: before a kernel
runs, its workload is scheduled by the LOMA DSE against the TPU v5e
MatchTarget; the winning tile sizes become the kernel's BlockSpecs
(aligned to MXU/VPU quanta via ``tpu_align``, then snapped to dims that
tile the array exactly via :func:`repro.kernels.tpu.tpu_block`).  The
mapping is cached exactly like the paper caches DSE results per layer
geometry.  Whether a kernel runs interpreted is derived from the backend
(:func:`repro.kernels.tpu.interpret_mode`), not passed.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.core import attention_workload, matmul_workload, scan_workload, schedule_for_kernel
from repro.core.workload import Workload, LoopDim, Operand
from repro.targets.tpu_v5e import make_tpu_v5e_target

from . import ref
from .flash_attention import flash_attention
from .matmul_requant import matmul_requant
from .moe_gmm import moe_gmm
from .rglru_scan import rglru_scan
from .ssd_scan import ssd_scan
from .tpu import LANE, SUBLANE, tpu_block

__all__ = [
    "scheduled_matmul_requant",
    "scheduled_flash_attention",
    "scheduled_moe_gmm",
    "scheduled_rglru_scan",
    "scheduled_ssd_scan",
    "kernel_schedule_table",
]

_TARGET = None


def _tpu():
    global _TARGET
    if _TARGET is None:
        _TARGET = make_tpu_v5e_target()
    return _TARGET


# ---------------------------------------------------------------------------


def scheduled_matmul_requant(a, w, mult, bias, *, shift=8, relu=False):
    M, K = a.shape
    N = w.shape[1]
    wl = matmul_workload(name=f"mmrq_{M}x{N}x{K}", M=M, N=N, KD=K, a_bytes=1, b_bytes=1, out_bytes=1)
    sched = schedule_for_kernel(
        wl, _tpu().module("mxu"), align={"M": "sublane", "N": "lane", "KD": "lane"}
    )
    bm = tpu_block(sched.block_of("M", M), M, SUBLANE)
    bn = tpu_block(sched.block_of("N", N), N, LANE)
    bk = tpu_block(sched.block_of("KD", K), K, LANE)
    return matmul_requant(
        a, w, mult, bias, shift=shift, relu=relu, block_m=bm, block_n=bn, block_k=bk
    )


def scheduled_flash_attention(q, k, v, *, causal=True):
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    wl = attention_workload(name=f"fa_{B}x{H}x{Sq}x{Sk}x{D}", B=B, H=H, SQ=Sq, SK=Sk, D=D, causal=causal)
    sched = schedule_for_kernel(
        wl, _tpu().module("mxu"), align={"SQ": "sublane", "SK": "lane"}
    )
    bq = tpu_block(sched.block_of("SQ", Sq), Sq, SUBLANE)
    bk = tpu_block(sched.block_of("SK", Sk), Sk, SUBLANE)
    return flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)


def scheduled_moe_gmm(x, w):
    E, C, D = x.shape
    F = w.shape[-1]
    wl = matmul_workload(name=f"gmm_{E}x{C}x{D}x{F}", M=C, N=F, KD=D)
    sched = schedule_for_kernel(
        wl, _tpu().module("mxu"), align={"M": "sublane", "N": "lane", "KD": "lane"}
    )
    bc = tpu_block(sched.block_of("M", C), C, SUBLANE)
    bf = tpu_block(sched.block_of("N", F), F, LANE)
    bd = tpu_block(sched.block_of("KD", D), D, LANE)
    return moe_gmm(x, w, block_c=bc, block_f=bf, block_d=bd)


def scheduled_rglru_scan(a, b):
    B, T, W = a.shape
    wl = scan_workload(name=f"lru_{B}x{T}x{W}", B=B, T=T, D=W)
    sched = schedule_for_kernel(wl, _tpu().module("vpu"), align={"D": "lane"})
    bw = tpu_block(sched.block_of("D", W), W, LANE)
    bt = tpu_block(sched.block_of("T", T), T, SUBLANE)
    return rglru_scan(a, b, block_w=bw, block_t=bt)


def scheduled_ssd_scan(xb, a, Bm, Cm):
    B, H, T, P = xb.shape
    N = Bm.shape[-1]
    wl = scan_workload(name=f"ssd_{B}x{H}x{T}", B=B * H, T=T, D=P * N, state=1)
    sched = schedule_for_kernel(wl, _tpu().module("vpu"), align={"T": "sublane"})
    # T is the last dim of the (1, 1, bt) decay block: a lane dim there
    bt = tpu_block(sched.block_of("T", T), T, LANE)
    return ssd_scan(xb, a, Bm, Cm, block_t=bt)


def kernel_schedule_table() -> list[dict]:
    """Inspection helper: DSE decisions for representative kernel shapes
    (surfaced by benchmarks/tpu_kernel_schedules.py)."""
    rows = []
    shapes = [
        ("matmul_requant", dict(M=4096, N=6144, KD=6144)),
        ("matmul_requant", dict(M=512, N=512, KD=512)),
        ("flash_attention", dict(B=8, H=16, SQ=4096, SK=4096, D=128)),
        ("moe_gmm", dict(M=1280, N=10752, KD=6144)),
        ("rglru_scan", dict(B=8, T=4096, D=2560)),
    ]
    for name, dims in shapes:
        if name == "flash_attention":
            wl = attention_workload(name=name, **dims)
            mod = _tpu().module("mxu")
            align = {"SQ": "sublane", "SK": "lane"}
        elif name == "rglru_scan":
            wl = scan_workload(name=name, **dims)
            mod = _tpu().module("vpu")
            align = {"D": "lane"}
        else:
            wl = matmul_workload(name=name, **dims)
            mod = _tpu().module("mxu")
            align = {"M": "sublane", "N": "lane", "KD": "lane"}
        s = schedule_for_kernel(wl, mod, align=align)
        rows.append(
            {
                "kernel": name,
                "dims": dims,
                "block": dict(s.block),
                "grid_order": s.grid_order,
                "predicted_cycles": s.predicted_cycles,
            }
        )
    return rows
