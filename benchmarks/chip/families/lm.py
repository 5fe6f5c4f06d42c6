"""The LM family: a Granite 4.0 hybrid (``granitemoehybrid``) decoder on
``repro.models.LM`` served by ``repro.serving.ServeEngine``.

* ``build``: the model's configuration from the published ``config.json``
  keys, seeded bf16 weights made on the device (``layer_weights``, one
  layer at a time, as the reference makes each again when it reads it),
  and the engine.  Set-up parts: ``weights_s``; the driver adds
  ``prefill_s``.
* ``inputs``: the batch's seeded prompts and the rows the check compares.
* ``check``: the engine's logits at the compared rows and positions
  against the plain float32 reference (``lm_reference``, a copy of
  ``repro.models.reference_granite``) over the prompt and the tokens the
  engine generated, computed on the device layer by layer.
* ``work``: bytes and FLOPs of one decode step.
"""

from __future__ import annotations

import sys
import time
from types import SimpleNamespace

import numpy as np

from benchmarks.chip import lm_reference

# The limits of ``check``, on the relative L2 error ||got - want|| /
# ||want|| of each compared logits row (readings on one v5e, PERF.md §6).
# The median over the 32 rows: the program (bf16 weights and activations)
# read 0.0142-0.0186 in 21 runs, the weights rounded to float8's 3
# mantissa bits 0.170-0.192 in five, the shared expert left out 0.98-0.99;
# 0.05 lies 2.7x above the first and 3.4x below the second.  The largest
# row, which sees a fault confined to one sequence or one step: it is
# heavy-tailed at bf16, where rounding can swap which of two near-equal
# router logits enters a token's top 10 and so change an expert; the
# program read 0.0196-0.0668 in 27 runs, float8 0.197-0.363, no shared
# expert 1.03-1.09; 0.12 lies 1.8x above the first and 1.6x below the
# second.
MEDIAN_LIMIT = 0.05
MAX_LIMIT = 0.12
# a greedy token is the argmax of its row's logits up to one rounding of
# the stated bf16 (XLA may take the argmax before the logits are rounded):
# its logit lies within 2**-7 of the row's largest, relative to that
TIE = 2.0**-7


def model_config(config: dict):
    """``repro``'s ModelConfig for a configuration of published keys."""
    from repro.models import ModelConfig

    if config["position_embedding_type"] != "nope" or config["mamba_n_groups"] != 1:
        raise ValueError("the LM family runs Granite hybrids without positions and with one SSD group")
    if config["attention_bias"] or config["mamba_proj_bias"] or config["hidden_act"] != "silu":
        raise ValueError("the LM family runs Granite hybrids without projection biases, with SiLU")
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["mamba_n_heads"] * config["mamba_d_head"] != config["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand x hidden_size")
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    return ModelConfig(
        name=config["name"],
        family="hybrid",
        n_layers=len(kinds),
        d_model=d,
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=d // heads,
        vocab=config["vocab_size"],
        block_types=tuple("attn" if k == "attention" else "ssd" for k in kinds),
        pos_kind="none",
        n_experts=config["experts_routed"],
        top_k=config["num_experts_per_tok"],
        moe_d_ff=config["intermediate_size"],
        moe_dropless=True,
        expert_share=(config["expert_offset"], config["num_local_experts"]),
        shared_expert_d_ff=config["shared_intermediate_size"],
        ssm_state=config["mamba_d_state"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_expand=config["mamba_expand"],
        ssm_conv=config["mamba_d_conv"],
        ssm_conv_bias=config["mamba_conv_bias"],
        ssm_chunk=config["mamba_chunk_size"],
        ssm_ffn=True,
        embedding_multiplier=config["embedding_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
    )


def _normal(key, shape, std, dtype):
    """Uniform values of standard deviation ``std`` (cheaper to make than
    normal ones, and as good for speed and agreement)."""
    import jax
    import jax.numpy as jnp

    a = std * 3**0.5
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def layer_weights(config: dict, i, kind: str) -> dict:
    """Layer ``i``'s seeded weights (``kind`` ``mamba`` or ``attention``),
    in the layout of ``lm_reference`` and of the program's blocks."""
    import jax
    import jax.numpy as jnp

    bf, f32 = jnp.bfloat16, jnp.float32
    d, f, fs = config["hidden_size"], config["intermediate_size"], config["shared_intermediate_size"]
    held, E = config["num_local_experts"], config["experts_routed"]
    base = jax.random.fold_in(jax.random.key(config["weights"]["seed"], impl="rbg"), i)
    names = iter(range(1 << 10))
    key = lambda: jax.random.fold_in(base, next(names))
    mat = lambda shape, dtype=bf: _normal(key(), shape, shape[-2] ** -0.5, dtype)
    swiglu = lambda n, w: {"wi_gate": mat(n + (d, w)), "wi_up": mat(n + (d, w)), "wo": mat(n + (w, d))}
    out = {"norm1": jnp.zeros((d,), f32), "norm2": jnp.zeros((d,), f32)}
    if kind == "mamba":
        H, P, N, K = (config[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv"))
        d_in = H * P
        dt = jnp.exp(jax.random.uniform(key(), (H,), f32, np.log(1e-3), np.log(1e-1)))
        out["ssd"] = {
            "in_z": mat((d, d_in)), "in_x": mat((d, d_in)), "in_B": mat((d, N)), "in_C": mat((d, N)),
            "in_dt": _normal(key(), (d, H), 0.1 * d**-0.5, bf),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
            "A_log": jnp.log(jax.random.uniform(key(), (H,), f32, 1.0, 16.0)),
            "D": jnp.ones((H,), f32),
            "conv_x": _normal(key(), (K, d_in), K**-0.5, bf),
            "conv_B": _normal(key(), (K, N), K**-0.5, bf),
            "conv_C": _normal(key(), (K, N), K**-0.5, bf),
            "norm": jnp.zeros((d_in,), f32),
            "out": mat((d_in, d)),
            "conv_bias_x": _normal(key(), (d_in,), 0.1, bf),
            "conv_bias_B": _normal(key(), (N,), 0.1, bf),
            "conv_bias_C": _normal(key(), (N,), 0.1, bf),
        }
    else:
        nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
        hd = d // nh
        out["attn"] = {"wq": mat((d, nh * hd)), "wk": mat((d, nkv * hd)), "wv": mat((d, nkv * hd)), "wo": mat((nh * hd, d))}
    out["moe"] = {"router": mat((d, E), f32), **swiglu((held,), f), "shared": swiglu((), fs)}
    return out


def embed_weights(config: dict):
    import jax
    import jax.numpy as jnp

    d = config["hidden_size"]
    key = jax.random.fold_in(jax.random.key(config["weights"]["seed"], impl="rbg"), 1 << 16)  # past any layer index
    return _normal(key, (config["vocab_size"], d), d**-0.5, jnp.bfloat16), jnp.zeros((d,), jnp.float32)


def _kinds(config: dict) -> list[str]:
    return config["layer_types"][: config["num_hidden_layers"]]


def program_weights(config: dict, model):
    """The seeded weights in ``model``'s (a ``repro.models.LM``) tree, made
    layer by layer (``_Layers``, as the reference reads them) and stacked
    with the layers given up, so that they are not held twice."""
    import jax

    embed, final = embed_weights(config)
    layers = _Layers(config)
    stack = jax.jit(model.params_from_layers, donate_argnums=(0, 1, 2))
    return stack(embed, [layers[i] for i in range(len(layers.kinds))], final)


def build(config: dict, transform=None):
    """The engine on seeded weights (``transform``, where given, maps the
    program's weight tree before the engine gets it: the controls)."""
    import jax

    from repro.models import LM
    from repro.serving import ServeEngine

    model = LM(model_config(config))

    t0 = time.perf_counter()
    params = program_weights(config, model)
    params = jax.block_until_ready(jax.jit(transform, donate_argnums=0)(params) if transform else params)
    parts = {"weights_s": time.perf_counter() - t0}

    def engine(traffic: dict):
        return ServeEngine(
            model, params, batch_slots=traffic["batch"], max_len=traffic["max_len"], prefill_rows=traffic["prefill_rows"]
        )

    return SimpleNamespace(engine=engine, parts=parts), parts


def inputs(config: dict, traffic: dict, rng: np.random.Generator):
    """The batch's prompts and the rows whose logits the check compares;
    the driver takes the same dict."""
    pool = {
        "prompts": rng.integers(0, config["vocab_size"], (traffic["batch"], traffic["prompt_len"]), dtype=np.int32),
        "check_seqs": np.sort(rng.choice(traffic["batch"], traffic["check_seqs"], replace=False)).astype(np.int32),
    }
    return pool, pool


class _Layers:
    """The seeded layers, each made on the device when it is read."""

    def __init__(self, config: dict):
        import jax

        self.kinds = _kinds(config)
        self.make = {k: jax.jit(lambda i, k=k: layer_weights(config, i, k)) for k in set(self.kinds)}

    def __getitem__(self, i):
        return self.make[self.kinds[i]](np.int32(i))


def reference_logits(config: dict, tokens: np.ndarray, positions: np.ndarray) -> np.ndarray:
    """The plain reference's float32 logits of ``tokens`` (B, T) at
    ``positions`` (B, P), on the seeded weights."""
    embed, final = embed_weights(config)
    weights = {"embed": embed, "final_norm": final, "layers": _Layers(config)}
    return np.asarray(lm_reference.forward(config, weights, tokens, positions), np.float64)


def rel_l2(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """||got - want|| / ||want|| of each logits row."""
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def row_errors(config: dict, answers: dict) -> np.ndarray:
    """The relative L2 error of each compared logits row, (rows, positions)."""
    got = answers["logits"].astype(np.float64)
    return rel_l2(got, reference_logits(config, answers["tokens"], answers["positions"]))


def wrong_tokens(answers: dict) -> int:
    """The compared positions whose next token, as the engine chose it, is
    not the greedy pick of the engine's own logits there: its logit lies
    more than a tie below the row's largest."""
    lg = answers["logits"].astype(np.float64)
    top = lg.max(axis=-1)
    chosen = np.take_along_axis(lg, answers["chosen"][..., None].astype(np.int64), axis=-1)[..., 0]
    return int(np.sum(chosen < top - TIE * np.abs(top)))


def check(config: dict, pool: dict, answers: dict, unanswered: int, rng, rows: int) -> tuple[bool, dict]:
    """The engine's logits at ``answers["positions"]`` of the compared rows
    against the reference's over the prompt and the generated tokens (the
    median and the largest relative L2 error of a row), and the tokens the
    engine chose there against its own logits.  ``unanswered`` takes no
    part: in lock step every step that reached the host answered all its
    rows."""
    err = row_errors(config, answers)
    checks = {
        "logits_rel_l2_median": {"value": float(np.median(err)), "limit": MEDIAN_LIMIT},
        "logits_rel_l2_max": {"value": float(err.max()), "limit": MAX_LIMIT},
        "wrong_tokens": {"value": wrong_tokens(answers), "limit": 0},
    }
    print(f"benchmark: compared {err.size} logits rows (of {rows}) with the reference", file=sys.stderr)
    return err.size == rows, checks


def work(config: dict) -> dict:
    """Bytes and FLOPs of one decode step, by hand from the configuration.

    Bytes: every weight once a step (the tied embedding is the LM head);
    per sequence, the SSD state (float32) and conv state (bf16) read and
    written; per sequence and cached position, one attention layer's K and
    V (bf16) read.  FLOPs per token: 2 x the multiply-adds of every
    projection, of the shared expert, of the routed experts at their
    expected share (top_k x held / routed), of the router and the LM head;
    the SSD state update and readout; attention scores and values per
    cached position."""
    kinds = _kinds(config)
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    d, V = config["hidden_size"], config["vocab_size"]
    H, P, N, K = (config[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv"))
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, d_in = d // nh, H * P
    f, fs, held, E, k = (config[x] for x in ("intermediate_size", "shared_intermediate_size", "num_local_experts", "experts_routed", "num_experts_per_tok"))
    L = n_m + n_a
    mamba_mats = d * (2 * d_in + 2 * N + H) + d_in * d + (K + 1) * (d_in + 2 * N)  # bf16: projections, conv and its bias
    attn_mats = d * (nh + 2 * nkv) * hd + nh * hd * d
    expert = 3 * d * f
    bf16 = n_m * mamba_mats + n_a * attn_mats + L * (held * expert + 3 * d * fs) + V * d
    f32 = n_m * (3 * H + d_in) + L * (2 * d + d * E) + d  # A_log, dt_bias, D, gated norm; norms, router; final norm
    macs = n_m * (d * (2 * d_in + 2 * N + H) + d_in * d) + n_a * attn_mats
    macs += L * (k * held / E * expert + 3 * d * fs + d * E) + V * d
    return {
        "layers": L,
        "experts_held": held,
        "weight_bytes": 2 * bf16 + 4 * f32,
        "expert_weight_bytes": 2 * L * held * expert,
        "ssd_state_bytes": n_m * H * P * N * 4,  # one sequence
        "conv_state_bytes": n_m * (K - 1) * (d_in + 2 * N) * 2,
        "kv_bytes_per_position": n_a * 2 * nkv * hd * 2,
        "flops_per_token": 2 * macs + n_m * 4 * H * P * N,
        "attn_flops_per_position": n_a * 4 * nh * hd,
    }


def step_bytes(w: dict, batch: int, pos: float) -> float:
    """The least bytes one decode step of ``batch`` sequences at position
    ``pos`` moves: the weights, the recurrent state read and written, K and
    V read over ``pos`` cached positions and written at one."""
    return w["weight_bytes"] + batch * (2 * (w["ssd_state_bytes"] + w["conv_state_bytes"]) + w["kv_bytes_per_position"] * (pos + 1))


def token_flops(w: dict, pos: float) -> float:
    return w["flops_per_token"] + w["attn_flops_per_position"] * (pos + 1)
