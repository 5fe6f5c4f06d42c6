"""Token-choice top-k MoE with capacity, gather-based dispatch.

Two sharding strategies are registered with the pod-level MATCH
dispatcher (repro.distributed.autoshard):

* **EP** — expert axis sharded over "model" (dbrx: 16 experts / 16-way
  axis is exact).  Resharding token-major -> expert-major activations
  makes GSPMD emit all-to-all/collective traffic on the "model" axis.
* **TP-experts** — expert axis replicated, per-expert hidden ("moe_ffn")
  sharded over "model" (granite-moe: 40 experts do not divide 16; its
  per-expert d_ff=512 does).

Dispatch is FLOP-free (argsort/scatter/gather slot assignment rather
than the GShard one-hot einsum), so MODEL_FLOPS/HLO_FLOPs stays honest;
dropped tokens (capacity overflow) contribute zero, standard
capacity-factor semantics.

:func:`moe_share_ffn` (``cfg.moe_dropless``) is the expert layer at one
chip's share of expert parallelism (``cfg.expert_share``): it routes every
token over all
``n_experts`` (top-k logits, softmax over those k), drops none, and
computes the gated sum of the outputs of the experts it holds, plus the
shared expert that every chip computes alike.  The exchange that would
bring the other chips' parts is absent on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec, mlp, mlp_params

__all__ = ["moe_params", "moe_ffn", "moe_capacity", "moe_share_ffn"]


def moe_params(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = len(cfg.experts_held)
    p = {
        "router": ParamSpec((d, e), ("embed", None), "float32", scale=0.1),
        "wi_gate": ParamSpec((held, d, f), ("experts", "embed", "moe_ffn"), cfg.dtype),
        "wi_up": ParamSpec((held, d, f), ("experts", "embed", "moe_ffn"), cfg.dtype),
        "wo": ParamSpec((held, f, d), ("experts", "moe_ffn", "embed"), cfg.dtype),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = mlp_params(d, cfg.shared_expert_d_ff, "swiglu", cfg.dtype)
    return p


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # pad to sublane multiple


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).  Group = batch row (standard)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = moe_capacity(cfg, S)

    logits = (x.astype(jnp.float32)) @ params["router"]  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # ---- top-k routing with per-expert capacity ------------------------
    remaining = probs
    counts = jnp.zeros((B, E), jnp.int32)
    slot_for_token = []  # k x (B, S) slot index in [0, E*C) or -1
    gate_for_token = []  # k x (B, S)
    for _ in range(K):
        gate = jnp.max(remaining, axis=-1)  # (B, S)
        idx = jnp.argmax(remaining, axis=-1)  # (B, S)
        oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (B, S, E)
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]  # (B, S, E)
        counts = counts + jnp.sum(oh, axis=1)
        my_pos = jnp.sum(pos * oh, axis=-1)  # (B, S)
        keep = my_pos < C
        slot = jnp.where(keep, idx * C + my_pos, -1)
        slot_for_token.append(slot)
        gate_for_token.append(jnp.where(keep, gate, 0.0))
        remaining = remaining * (1 - oh.astype(remaining.dtype))

    slots = jnp.stack(slot_for_token, axis=-1)  # (B, S, K)
    gates = jnp.stack(gate_for_token, axis=-1)  # (B, S, K)
    # renormalize kept gates (standard for top-k routing)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # ---- dispatch: scatter (token,k) ids into (E*C) slots, then gather --
    # every index in these scatters is UNIQUE (slot = expert*C + position),
    # so both the forward scatters and their transposes (gathers) lower
    # cleanly — a duplicate-index scatter-add here costs ~10x HBM traffic
    # through XLA's collision-safe lowering.
    token_ids = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, K))
    k_ids = jnp.broadcast_to(jnp.arange(K)[None, None, :], (B, S, K))
    tok_k = token_ids * K + k_ids  # (B,S,K) unique per (token, k)
    flat_slots = slots.reshape(B, S * K)
    flat_tok_k = tok_k.reshape(B, S * K)
    safe_slots = jnp.where(flat_slots >= 0, flat_slots, E * C)  # drop bin
    bidx = jnp.arange(B)[:, None]
    # unfilled slots default to S*K (out of range -> their combine write
    # is dropped, never clobbering token 0)
    tok_k_for_slot = jnp.full((B, E * C + 1), S * K, jnp.int32)
    tok_k_for_slot = tok_k_for_slot.at[bidx, safe_slots].set(flat_tok_k, mode="drop")
    gate_for_slot = jnp.zeros((B, E * C + 1), jnp.float32)
    gate_for_slot = gate_for_slot.at[bidx, safe_slots].set(gates.reshape(B, S * K), mode="drop")
    tok_k_for_slot = tok_k_for_slot[:, : E * C]
    gate_for_slot = gate_for_slot[:, : E * C]

    if cfg.moe_dispatch == "unique_k":
        # dispatch gather over the (token, k) EXPANDED view: indices are
        # unique (tok_k), so the transpose is a unique-index scatter into
        # (B, S*K, D) followed by a dense sum over K — no duplicate-index
        # scatter-add (whose collision-safe lowering costs ~10x HBM bytes).
        # The expanded view is a broadcast, free in fwd.  Refuted as a
        # speed-up (config.py); the tests compare it with "token".
        xk = jnp.broadcast_to(x[:, :, None, :], (B, S, K, D)).reshape(B, S * K, D)
        # one zero pad row: unfilled slots (index S*K) stay unique and
        # their (zero) cotangents land on the discarded pad row
        xk = jnp.concatenate([xk, jnp.zeros((B, 1, D), x.dtype)], axis=1)

        def _row_gather_x(arr, idx):
            return arr.at[idx].get(unique_indices=True, mode="promise_in_bounds")

        dispatched = jax.vmap(_row_gather_x)(xk, tok_k_for_slot)
    else:
        tok_for_slot = jnp.clip(tok_k_for_slot // K, 0, S - 1)
        dispatched = jnp.take_along_axis(x, tok_for_slot[..., None], axis=1)
    dispatched = dispatched.reshape(B, E, C, D)
    dispatched = constrain(dispatched, "batch", "experts", None, None)

    # ---- expert computation (the only FLOP-heavy part) ------------------
    g = jnp.einsum("becd,edf->becf", dispatched, params["wi_gate"])
    u = jnp.einsum("becd,edf->becf", dispatched, params["wi_up"])
    g = constrain(g, "batch", "experts", None, "moe_ffn")
    h = jax.nn.silu(g) * u
    eo = jnp.einsum("becf,efd->becd", h, params["wo"])
    eo = constrain(eo, "batch", "experts", None, None)
    eo = eo.reshape(B, E * C, D)

    # ---- combine ---------------------------------------------------------
    if cfg.moe_combine == "scatter":
        # REFUTED alternative (kept as a tested formulation): scatter-SET back to
        # (token, k) space with unique indices.  Under GSPMD the sharded
        # scatter lowers to an all-gather/select storm: granite-moe train
        # collective term 1.3 s -> 133 s.  Default stays "gather".
        eo_scaled = eo * gate_for_slot[..., None].astype(eo.dtype)
        tok_out = jnp.zeros((B, S * K, D), eo.dtype)
        tok_out = tok_out.at[bidx, tok_k_for_slot].set(eo_scaled, mode="drop")
        y = jnp.sum(tok_out.reshape(B, S, K, D), axis=2)
    else:
        # gather each token's k slots back.  Indices are made UNIQUE by
        # routing dropped tokens to a dedicated zero pad row (instead of
        # clip-to-0 collisions), so the transpose is a unique-index
        # scatter — XLA's collision-safe scatter-add lowering cost ~10x
        # HBM bytes on this layer.  Cotangents of
        # the pad row are all zero (gate=0), so uniqueness is sound.
        eo_pad = jnp.concatenate([eo, jnp.zeros((B, 1, D), eo.dtype)], axis=1)
        gather_slots = jnp.where(slots >= 0, slots, E * C).reshape(B, S * K)

        def _row_gather(arr, idx):  # (EC+1, D), (SK,) -> (SK, D)
            return arr.at[idx].get(unique_indices=True, mode="promise_in_bounds")

        tok_out = jax.vmap(_row_gather)(eo_pad, gather_slots)
        tok_out = tok_out.reshape(B, S, K, D)
        y = jnp.sum(tok_out * gates[..., None].astype(tok_out.dtype), axis=2)
    if "shared" in params:
        y = y + mlp(params["shared"], x, "swiglu")
    y = constrain(y.astype(x.dtype), "batch", "seq", None)

    # ---- load-balancing aux loss (Switch/GShard) ------------------------
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    top1 = jax.nn.one_hot(jnp.argmax(logits, -1), E, dtype=jnp.float32)
    ce = jnp.mean(top1, axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return y, aux


def moe_share_ffn(params: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """The expert layer at this chip's share: x (B, S, D) -> (y, rows).

    Routing is over all ``cfg.n_experts``: the top-``k`` router logits and
    a softmax over those k (float32).  No token is dropped.  The held
    experts are computed densely over every token and weighted by each
    token's gate for them (zero where the token did not pick them): at
    decode's few tokens a step is bound by reading the experts' weights,
    not by their FLOPs.  ``rows`` counts the routed (token, expert) pairs
    that the held experts computed.  The shared expert is added on every
    chip alike."""
    held = cfg.experts_held
    logits = x.astype(jnp.float32) @ params["router"]  # (B, S, E)
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top, axis=-1)  # (B, S, k)
    # gate of each held expert for each token, (B, S, held); one_hot gives
    # a zero row for an expert that another chip holds
    local = jax.nn.one_hot(idx - held.start, len(held), dtype=jnp.float32)
    rows = jnp.sum(local, dtype=jnp.int32)
    w = jnp.einsum("bsk,bske->bse", gates, local)
    g = jnp.einsum("bsd,edf->bsef", x, params["wi_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, params["wi_up"])
    h = jax.nn.silu(g) * u * w[..., None].astype(x.dtype)
    y = jnp.einsum("bsef,efd->bsd", h, params["wo"])
    if "shared" in params:
        y = y + mlp(params["shared"], x, "swiglu")
    return constrain(y.astype(x.dtype), "batch", "seq", None), rows
