"""GQA attention with RoPE / M-RoPE / local windows / encoder mode.

The training/prefill path uses a **chunked online-softmax** formulation
(pure jnp `lax.scan` over key blocks) — the same algorithm as the Pallas
flash kernel in ``repro.kernels.flash_attention`` (its oracle), with
O(S·block) memory so 32k-token prefill compiles and fits.  The kernel and
this reference are interchangeable through ``repro.kernels.ops``.

GQA: ``n_kv_heads`` K/V heads shared by groups of query heads (kv=1 is
MQA, e.g. granite-34b).  M-RoPE (qwen2-vl): head-dim sections rotate with
separate (t, h, w) position streams.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec

__all__ = [
    "attention_params",
    "attention",
    "rope_tables",
    "mrope_tables",
    "apply_rope",
]


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions (..., S) -> sin/cos (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def mrope_tables(
    positions3: jax.Array, sections: tuple[int, ...], head_dim: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """M-RoPE (qwen2-vl): positions3 (3, B, S); head-dim halves split into
    ``sections`` (t, h, w), each rotated by its own position stream."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang_all = positions3.astype(jnp.float32)[..., None] * freqs  # (3, B, S, half)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[i, ..., start : start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x (B, S, H, D); sin/cos (B, S, D/2) or (S, D/2)."""
    if sin.ndim == 2:
        sin, cos = sin[None], cos[None]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attention_params(cfg: ModelConfig) -> dict:
    d, hd, nh, nkv = cfg.d_model, cfg.head_dim_, cfg.n_heads, cfg.kv_heads
    p = {
        "wq": ParamSpec((d, nh * hd), ("embed", "heads"), cfg.dtype),
        "wk": ParamSpec((d, nkv * hd), ("embed", "kv_heads"), cfg.dtype),
        "wv": ParamSpec((d, nkv * hd), ("embed", "kv_heads"), cfg.dtype),
        "wo": ParamSpec((nh * hd, d), ("heads", "embed"), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((nh * hd,), ("heads",), cfg.dtype, init="zeros")
        p["bk"] = ParamSpec((nkv * hd,), ("kv_heads",), cfg.dtype, init="zeros")
        p["bv"] = ParamSpec((nkv * hd,), ("kv_heads",), cfg.dtype, init="zeros")
    return p


# ---------------------------------------------------------------------------
# Core attention math (chunked online softmax)
# ---------------------------------------------------------------------------


def _qkv(params: dict, x: jax.Array, cfg: ModelConfig):
    B, S, _ = x.shape
    hd, nh, nkv = cfg.head_dim_, cfg.n_heads, cfg.kv_heads
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _chunked_attention(
    q: jax.Array,  # (B, Sq, H, D) — rope applied
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    window: int | None = None,
    chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax attention scanning key chunks; fp32 accumulators.
    ``scale`` multiplies the scores (default ``1/sqrt(head_dim)``)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    g = H // KV
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, g, D)

    chunk = min(chunk, Sk)
    n_chunks = Sk // chunk
    assert Sk % chunk == 0, f"Sk={Sk} % chunk={chunk}"
    kc = k.reshape(B, n_chunks, chunk, KV, D).astype(jnp.float32)
    vc = v.reshape(B, n_chunks, chunk, KV, D).astype(jnp.float32)
    kc = jnp.moveaxis(kc, 1, 0)  # (n, B, chunk, KV, D)
    vc = jnp.moveaxis(vc, 1, 0)

    q_pos = q_offset + jnp.arange(Sq)  # (Sq,)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, idx = inp
        k_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kb)  # (B,Sq,KV,g,chunk)
        mask = jnp.ones((Sq, chunk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bqkgc,bckd->bqkgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, g), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, g, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, D)


def attention(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    sin: jax.Array | None,
    cos: jax.Array | None,
    causal: bool | None = None,
    window: int | None = None,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    if sin is not None:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    causal = cfg.causal if causal is None else causal
    out = _chunked_attention(
        q, k, v, causal=causal, window=window, chunk=min(kv_chunk, S), scale=cfg.attn_scale
    )
    out = constrain(out.astype(x.dtype), "batch", "seq", "heads", None)
    y = out.reshape(B, S, -1) @ params["wo"]
    return constrain(y, "batch", "seq", None)
