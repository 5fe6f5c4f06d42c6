"""Plain float32 reference of the Granite 4.0 hybrid decoder
(``model_type`` ``granitemoehybrid``): Mamba-2 and attention layers, each
followed by routed experts and a shared expert.

It is the yardstick the serving path is compared with, so it shares no
algorithm with it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one layer after another (no
scan over stacked layers), no cache, no chunking and no kernels.  The SSD
is the sequential recurrence ``h <- exp(dt*A)*h + dt*x (x) B``,
``y = C.h + D*x``; attention is the full causal softmax; the expert layer
loops over the experts it holds.  The file imports nothing but ``jax``,
so a copy of it can stand outside the package.

``config`` is a dict with the keys of the published ``config.json``:
``hidden_size``, ``num_hidden_layers``, ``layer_types``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_d_conv``,
``num_attention_heads``, ``num_key_value_heads``, ``num_experts_per_tok``,
``rms_norm_eps``, ``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``; and ``expert_offset``, the
first expert held, where a chip holds a share (default 0).  The router's
width and the number of experts held are read from the weights' shapes.

``weights`` is ``{"embed": (V, D), "final_norm": (D,), "layers": [...]}``,
where ``layers[i]`` (any sequence, so each layer can be made when it is
read) is one layer's dict: ``norm1``, ``ssd`` (``in_z``, ``in_x``,
``in_B``, ``in_C``, ``in_dt``, ``dt_bias``, ``A_log``, ``D``, ``conv_x``,
``conv_B``, ``conv_C``, ``conv_bias_x``, ``conv_bias_B``, ``conv_bias_C``,
``norm``, ``out``) or ``attn`` (``wq``, ``wk``, ``wv``, ``wo``), then
``norm2`` and ``moe`` (``router`` (D, E), ``wi_gate``, ``wi_up`` (held, D,
F), ``wo`` (held, F, D), ``shared`` with ``wi_gate``, ``wi_up``, ``wo``).
Matrices are stored (in, out), as ``x @ w`` reads them.

Departures from the published model:
- RMSNorm weights are stored as ``w`` and applied as ``(1 + w)``; the
  published model stores ``1 + w``.  The two are the same function.
- The Mamba input projection is kept as five matrices (z, x, B, C, dt)
  and its depthwise conv as three (x, B, C): the published ``in_proj`` and
  ``conv1d`` split along their outputs.
- With ``expert_offset`` and fewer experts than the router scores, the
  layer is one chip's share of expert parallelism: the other experts'
  part of the result is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["forward"]

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])) @ p["wo"]


def _conv(x, w, b):
    """Causal depthwise conv over time: x (B, T, C), w (K, C), b (C,)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i : i + T] * w[i] for i in range(K)) + b


def _mamba(cfg, p, x):
    Bsz, T, _ = x.shape
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    z = x @ p["in_z"]
    xs = jax.nn.silu(_conv(x @ p["in_x"], p["conv_x"], p["conv_bias_x"]))
    Bm = jax.nn.silu(_conv(x @ p["in_B"], p["conv_B"], p["conv_bias_B"]))
    Cm = jax.nn.silu(_conv(x @ p["in_C"], p["conv_C"], p["conv_bias_C"]))
    dt = jax.nn.softplus(x @ p["in_dt"] + p["dt_bias"])  # (B, T, H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(Bsz, T, H, P)

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t * A)[:, :, None, None] * h + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t)

    h0 = jnp.zeros((Bsz, H, P, Bm.shape[-1]), F32)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xh, dt, Bm, Cm))
    _, y = jax.lax.scan(step, h0, seq)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xh
    y = y.reshape(Bsz, T, H * P) * jax.nn.silu(z)
    return _rmsnorm(y, p["norm"], cfg["rms_norm_eps"]) @ p["out"]


def _attention(cfg, p, x):
    """Causal GQA attention without positions, one sequence at a time."""
    Bsz, T, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = p["wq"].shape[1] // nh
    causal = jnp.tril(jnp.ones((T, T), bool))
    out = []
    for b in range(Bsz):
        q = (x[b] @ p["wq"]).reshape(T, nh, hd)
        k = jnp.repeat((x[b] @ p["wk"]).reshape(T, nkv, hd), nh // nkv, axis=1)
        v = jnp.repeat((x[b] @ p["wv"]).reshape(T, nkv, hd), nh // nkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
        s = jnp.where(causal, s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(T, nh * hd))
    return jnp.stack(out) @ p["wo"]


def _experts(cfg, p, x):
    """Top-k of the router's logits, a softmax over those k; the held
    experts' gated outputs, one expert at a time; plus the shared expert."""
    top, idx = jax.lax.top_k(x @ p["router"], cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    y = _swiglu(p["shared"], x)
    first = cfg.get("expert_offset", 0)
    for e in range(p["wi_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        mine = {k: p[k][e] for k in ("wi_gate", "wi_up", "wo")}
        y = y + gate[..., None] * _swiglu(mine, x)
    return y


def _layer(cfg, kind, p, x):
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = _rmsnorm(x, p["norm1"], eps)
    h = _mamba(cfg, p["ssd"], h) if kind == "mamba" else _attention(cfg, p["attn"], h)
    x = x + m * h
    return x + m * _experts(cfg, p["moe"], _rmsnorm(x, p["norm2"], eps))


def forward(config: dict, weights: dict, tokens, positions=None):
    """Logits (float32) of ``tokens`` (B, T) int32: (B, T, V), or with
    ``positions`` (B, P) only at those positions, (B, P, V)."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, F32), t)
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    with jax.default_matmul_precision("highest"):
        layer = {k: jax.jit(lambda p, x, k=k: _layer(config, k, p, x)) for k in set(kinds)}
        embed = f32(weights["embed"])
        x = embed[jnp.asarray(tokens)] * config["embedding_multiplier"]
        for i, kind in enumerate(kinds):
            x = layer[kind](f32(weights["layers"][i]), x)
        x = _rmsnorm(x, f32(weights["final_norm"]), config["rms_norm_eps"])
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        return (x @ embed.T) / config["logits_scaling"]
