"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

Implements the chunked SSD algorithm (the paper's "minimal SSD"
listing, ported to jnp): intra-chunk quadratic attention-like term +
inter-chunk state recurrence — O(T) in sequence length with
MXU-friendly chunk matmuls.  ``repro.kernels.ssd_scan`` provides the
Pallas version; this module is its oracle.

Block layout follows mamba2: in_proj -> (z | x | B | C | dt),
causal depthwise conv on (x,B,C), SSD core, gated RMSNorm, out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec, rmsnorm

__all__ = ["ssd_params", "ssd_block", "ssd_decode_step", "ssd_chunked_ref", "ssd_state_init"]


def _dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    return d_in, n_heads, cfg.ssm_head_dim, cfg.ssm_state


def ssd_params(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in, H, P, N = _dims(cfg)
    cw = cfg.ssm_conv
    p = {
        "in_z": ParamSpec((d, d_in), ("embed", "ffn"), cfg.dtype),
        "in_x": ParamSpec((d, d_in), ("embed", "ffn"), cfg.dtype),
        "in_B": ParamSpec((d, N), ("embed", None), cfg.dtype),
        "in_C": ParamSpec((d, N), ("embed", None), cfg.dtype),
        "in_dt": ParamSpec((d, H), ("embed", "heads"), cfg.dtype, scale=0.1),
        "dt_bias": ParamSpec((H,), ("heads",), "float32", init="zeros"),
        "A_log": ParamSpec((H,), ("heads",), "float32", init="ones"),
        "D": ParamSpec((H,), ("heads",), "float32", init="ones"),
        "conv_x": ParamSpec((cw, d_in), (None, "ffn"), cfg.dtype, scale=0.5),
        "conv_B": ParamSpec((cw, N), (None, None), cfg.dtype, scale=0.5),
        "conv_C": ParamSpec((cw, N), (None, None), cfg.dtype, scale=0.5),
        "norm": ParamSpec((d_in,), ("ffn",), "float32", init="zeros"),
        "out": ParamSpec((d_in, d), ("ffn", "embed"), cfg.dtype),
    }
    if cfg.ssm_conv_bias:
        p["conv_bias_x"] = ParamSpec((d_in,), ("ffn",), cfg.dtype, init="zeros")
        p["conv_bias_B"] = ParamSpec((N,), (None,), cfg.dtype, init="zeros")
        p["conv_bias_C"] = ParamSpec((N,), (None,), cfg.dtype, init="zeros")
    return p


def _conv(params: dict, name: str, x: jax.Array, state: jax.Array | None = None):
    """Causal depthwise conv of one of x, B, C (plus its bias, where the
    block has one), then SiLU; returns (activation, new conv state)."""
    from repro.models.rglru import _causal_conv1d  # shared depthwise conv

    y, st = _causal_conv1d(x, params[f"conv_{name}"], state)
    if f"conv_bias_{name}" in params:
        y = y + params[f"conv_bias_{name}"]
    return jax.nn.silu(y), st


def _segsum(x: jax.Array) -> jax.Array:
    """(..., L) -> (..., L, L) with out[i,j] = sum_{j<k<=i} x[k], -inf above diag."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked_ref(
    x: jax.Array,  # (B, T, H, P)
    dt: jax.Array,  # (B, T, H)  (post-softplus, >0)
    A: jax.Array,  # (H,)       (negative)
    Bm: jax.Array,  # (B, T, N)
    Cm: jax.Array,  # (B, T, N)
    chunk: int = 128,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD; returns (y (B,T,H,P), final_state (B,H,P,N))."""
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk

    xb = (x * dt[..., None]).reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    a = (dt * A[None, None, :]).reshape(Bsz, nc, chunk, H)  # (B,c,l,H) <= 0
    a = jnp.moveaxis(a, -1, 2).astype(jnp.float32)  # (B, c, H, l)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)

    a_cs = jnp.cumsum(a, axis=-1)  # (B,c,H,l)
    L = jnp.exp(_segsum(a))  # (B,c,H,l,l)

    # 1) intra-chunk (diagonal blocks)
    y_diag = jnp.einsum("bcln,bcsn,bchls,bcshp->bclhp", Cc, Bc, L, xb)

    # 2) chunk-final states
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)  # (B,c,H,l)
    states = jnp.einsum("bcln,bchl,bclhp->bchpn", Bc, decay_states, xb)

    # 3) inter-chunk recurrence over chunk states
    if init_state is None:
        init_state = jnp.zeros_like(states[:, 0])
    states = jnp.concatenate([init_state[:, None], states], axis=1)  # (B,c+1,H,P,N)
    chunk_decay = a_cs[..., -1]  # (B,c,H)
    pad = jnp.pad(chunk_decay, ((0, 0), (1, 0), (0, 0)))  # (B,c+1,H)
    dc = jnp.exp(_segsum(jnp.moveaxis(pad, 1, -1)))  # (B,H,c+1,c+1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", dc, states)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]

    # 4) inter-chunk contribution to outputs
    state_decay = jnp.exp(a_cs)  # (B,c,H,l)
    y_off = jnp.einsum("bcln,bchpn,bchl->bclhp", Cc, prev_states, state_decay)

    y = (y_diag + y_off).reshape(Bsz, T, H, P)
    return y, final_state


def ssd_block(params: dict, x: jax.Array, cfg: ModelConfig, *, return_state: bool = False):
    """Full mamba2 block: (B,T,D) -> (B,T,D) [, final state dict].  The SSD
    runs in chunks of ``cfg.ssm_chunk``; a sequence longer than one chunk
    and not a multiple of it is padded at its end with steps of ``dt = 0``,
    which neither decay nor feed the state."""
    B_, T, D = x.shape
    d_in, H, P, N = _dims(cfg)
    z = x @ params["in_z"]
    xs = x @ params["in_x"]
    xs = constrain(xs, "batch", "seq", "ffn")
    Bm = x @ params["in_B"]
    Cm = x @ params["in_C"]
    dt_raw = (x @ params["in_dt"]).astype(jnp.float32) + params["dt_bias"]
    dt = jax.nn.softplus(dt_raw)  # (B,T,H)

    xs, cx = _conv(params, "x", xs)
    Bm, cb = _conv(params, "B", Bm)
    Cm, cc = _conv(params, "C", Cm)

    A = -jnp.exp(params["A_log"])  # (H,) negative
    xh = xs.reshape(B_, T, H, P)
    chunk = min(cfg.ssm_chunk, T)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, -T % chunk)) + ((0, 0),) * (a.ndim - 2))
    y, final_state = ssd_chunked_ref(pad(xh), pad(dt), A, pad(Bm), pad(Cm), chunk=chunk)
    y = y[:, :T] + xh.astype(jnp.float32) * params["D"][None, None, :, None] * 1.0
    y = y.reshape(B_, T, d_in).astype(x.dtype)

    y = y * jax.nn.silu(z)  # gated
    y = rmsnorm(y, params["norm"], cfg.norm_eps)
    y = y @ params["out"]
    y = constrain(y, "batch", "seq", None)
    if return_state:
        return y, {"h": final_state, "conv_x": cx, "conv_B": cb, "conv_C": cc}
    return y


def ssd_state_init(cfg: ModelConfig, batch: int) -> dict:
    d_in, H, P, N = _dims(cfg)
    cw = cfg.ssm_conv
    return {
        "h": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, cw - 1, d_in), jnp.dtype(cfg.dtype)),
        "conv_B": jnp.zeros((batch, cw - 1, N), jnp.dtype(cfg.dtype)),
        "conv_C": jnp.zeros((batch, cw - 1, N), jnp.dtype(cfg.dtype)),
    }


def ssd_decode_step(
    params: dict,
    x: jax.Array,  # (B, 1, D)
    state: dict,
    cfg: ModelConfig,
) -> tuple[jax.Array, dict]:
    B_, _, D = x.shape
    d_in, H, P, N = _dims(cfg)
    z = x @ params["in_z"]
    xs = x @ params["in_x"]
    Bm = x @ params["in_B"]
    Cm = x @ params["in_C"]
    dt = jax.nn.softplus((x @ params["in_dt"]).astype(jnp.float32) + params["dt_bias"])

    xs, cx = _conv(params, "x", xs, state["conv_x"])
    Bm, cb = _conv(params, "B", Bm, state["conv_B"])
    Cm, cc = _conv(params, "C", Cm, state["conv_C"])
    xs = xs[:, 0].reshape(B_, H, P).astype(jnp.float32)
    Bm = Bm[:, 0].astype(jnp.float32)  # (B,N)
    Cm = Cm[:, 0].astype(jnp.float32)
    dt = dt[:, 0]  # (B,H)

    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A[None, :])  # (B,H)
    h = state["h"] * decay[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", xs * dt[..., None], Bm
    )
    y = jnp.einsum("bhpn,bn->bhp", h, Cm) + xs * params["D"][None, :, None]
    y = y.reshape(B_, 1, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, params["norm"], cfg.norm_eps)
    y = y @ params["out"]
    return y, {"h": h, "conv_x": cx, "conv_B": cb, "conv_C": cc}
