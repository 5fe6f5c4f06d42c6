"""granite-4.0-h-small on the LM path: the configuration reproduces the
published layer order and size, and the program agrees with the plain
float32 reference (``repro.models.reference_granite``) at ``SMOKE`` size on
seeded weights: the full forward, the serving engine's prefill and greedy
decode through its cache, and the expert layer cut into chip shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.models import LM
from repro.models import reference_granite as ref
from repro.models.layers import init_from_specs
from repro.models.moe import moe_ffn, moe_params, moe_share_ffn
from repro.serving import ServeEngine

# published layer_types (config.json): attention at 5, 15, 25, 35
PUBLISHED = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
# float32 program against the float32 reference: the chunked SSD, the
# online softmax and the dense expert sum add in other orders than the
# sequential recurrence, the full softmax and the per-expert loop, which
# moves logits by ~1e-6 of their scale over 10 layers; 1e-4 leaves that
# room, while a dropped term or a wrong multiplier moves them by >1e-2
TOL = 1e-4


def _hf(cfg) -> dict:
    """The published config.json keys the reference reads, for ``cfg``."""
    return {
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "layer_types": ["mamba" if t == "ssd" else "attention" for t in cfg.layer_pattern()],
        "mamba_n_heads": cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
        "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.ssm_conv,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "num_experts_per_tok": cfg.top_k,
        "rms_norm_eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embedding_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "expert_offset": cfg.experts_held.start,
    }


def _seeded(model: LM, seed: int):
    """Seeded weights with every zero- or one-initialised vector drawn too:
    norms and conv biases around 0, A in [1, 16], dt in [1e-3, 1e-1]."""
    params = model.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)

    def draw(path, a):
        name = jax.tree_util.keystr(path[-1:])
        if name == "['A_log']":
            return jnp.asarray(np.log(rng.uniform(1, 16, a.shape)), a.dtype)
        if name == "['dt_bias']":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), a.shape))
            return jnp.asarray(dt + np.log(-np.expm1(-dt)), a.dtype)
        if "norm" in name or "conv_bias" in name or name == "['D']":
            return a + jnp.asarray(0.2 * rng.standard_normal(a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(draw, params)


def _weights(model: LM, params) -> dict:
    return {"embed": params["embed"], "final_norm": params["final_norm"], "layers": model.layers_of(params)}


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke("granite_4_0_h_small").replace(dtype="float32")
    model = LM(cfg)
    return cfg, model, _seeded(model, 0)


def test_config_reproduces_the_published_layer_types():
    cfg = get_config("granite_4_0_h_small")
    assert _hf(cfg)["layer_types"] == PUBLISHED
    assert get_smoke("granite_4_0_h_small").layer_pattern() == cfg.layer_pattern()[:10]


def test_config_counts_the_published_parameters():
    """32.2 B in all (IBM: 32B total), 2.414 B at one chip's share of the
    first 10 layers (9 of 72 experts held)."""
    cfg = get_config("granite_4_0_h_small")
    assert abs(cfg.n_params() / 32.2e9 - 1) < 2e-3
    cut = cfg.replace(n_layers=10, moe_dropless=True, expert_share=(0, 9))
    assert abs(cut.n_params() / 2.414e9 - 1) < 1e-3
    # the shapes the model builds hold exactly what n_params counts
    shapes = jax.eval_shape(lambda: LM(cut).init(jax.random.key(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == cut.n_params()


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_agrees_with_the_reference(seed):
    cfg = get_smoke("granite_4_0_h_small").replace(dtype="float32")
    model = LM(cfg)
    params = _seeded(model, seed)
    # 20 tokens: two whole SSD chunks of 8 and a padded third
    toks = jax.random.randint(jax.random.key(seed + 10), (2, 20), 0, cfg.vocab)
    got, _ = model.forward(params, toks)
    want = ref.forward(_hf(cfg), _weights(model, params), toks)
    assert got.shape == want.shape
    assert _rel(got, want) < TOL


@pytest.mark.parametrize("prefill_rows", [0, 2])
def test_engine_prefill_then_greedy_decode_agrees_with_the_reference(smoke, prefill_rows):
    """Prefill (in row groups) then greedy decode through the cache: the
    logits of every step agree with the reference's full forward over the
    prompt and the generated tokens."""
    cfg, model, params = smoke
    B, S, steps = 3, 12, 6
    prompts = np.asarray(jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab), np.int32)
    eng = ServeEngine(model, params, max_len=32, prefill_rows=prefill_rows)
    ls = eng.prefill(prompts, keep=range(B))
    first = np.asarray(ls.tokens)
    logits, toks = [ls.logits], [first]
    for _, (tok, lg) in zip(range(steps), eng.steps(ls)):
        toks.append(tok)
        logits.append(lg)
    assert eng.decode_steps == steps + 1  # the step dispatched ahead
    seq = np.concatenate([prompts, np.stack(toks[:-1], axis=1)], axis=1)
    want = ref.forward(_hf(cfg), _weights(model, params), seq)[:, S - 1 :]
    got = np.stack([np.asarray(g) for g in logits], axis=1)
    assert _rel(got, want) < TOL
    # the device's greedy picks are the argmax of the logits it returned
    np.testing.assert_array_equal(np.stack(toks[1:], axis=1), got[:, 1:].argmax(-1))
    np.testing.assert_array_equal(toks[0], got[:, 0].argmax(-1))


def _share_cfg(first, count):
    return get_smoke("granite_4_0_h_small").replace(
        dtype="float32", n_experts=8, top_k=2, expert_share=(first, count)
    )


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the shares' outputs, with the shared
    expert counted once, add up to the layer holding all 8, which agrees
    with the reference's per-expert loop."""
    whole = _share_cfg(0, 8)
    p = init_from_specs(jax.random.key(3), moe_params(whole))
    x = jax.random.normal(jax.random.key(4), (2, 16, whole.d_model), jnp.float32)
    y_all, rows_all = moe_share_ffn(p, x, whole)
    shared, _ = moe_share_ffn({**p, "wi_gate": p["wi_gate"][:0], "wi_up": p["wi_up"][:0], "wo": p["wo"][:0]}, x, _share_cfg(0, 0))
    parts, rows = [], 0
    for s in range(4):
        cut = {**p, **{k: p[k][2 * s : 2 * s + 2] for k in ("wi_gate", "wi_up", "wo")}}
        y, r = moe_share_ffn(cut, x, _share_cfg(2 * s, 2))
        parts.append(np.asarray(y) - np.asarray(shared))
        rows += int(r)
    assert rows == int(rows_all) == 2 * 16 * whole.top_k  # every routed pair, once
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(y_all), atol=1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(_hf(whole), p, x)
    assert _rel(y_all, want) < TOL


def test_a_share_drops_no_token_under_skewed_routing():
    """Every token routes to the held experts 2 and 3: all 2 x 32 routed
    pairs are computed, where a capacity of 1.25 x the even load would drop
    most of them."""
    cfg = _share_cfg(2, 2)
    p = init_from_specs(jax.random.key(3), moe_params(cfg))
    p["router"] = p["router"].at[:, 2:4].add(100.0)
    x = jnp.abs(jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model), jnp.float32))
    y, rows = moe_share_ffn(p, x, cfg)
    assert int(rows) == 2 * 16 * cfg.top_k
    with jax.default_matmul_precision("highest"):
        want = ref._experts(_hf(cfg), p, x)
    assert _rel(y, want) < TOL


def test_the_capacity_layer_adds_the_shared_expert():
    """``CONFIG``'s expert layer (``moe_ffn`` over all experts), given room
    for every token, computes what the dropless layer holding all of them
    does, the shared expert included."""
    dropless = _share_cfg(0, 8)
    capacity = dropless.replace(moe_dropless=False, expert_share=(), capacity_factor=8 / 2)
    p = init_from_specs(jax.random.key(3), moe_params(capacity))
    assert "shared" in p
    x = jax.random.normal(jax.random.key(4), (2, 16, capacity.d_model), jnp.float32)
    y, _ = moe_ffn(p, x, capacity)
    want, _ = moe_share_ffn(p, x, dropless)
    assert _rel(y, want) < TOL


def test_a_share_needs_the_dropless_layer():
    with pytest.raises(ValueError, match="moe_dropless"):
        get_config("granite_4_0_h_small").replace(expert_share=(0, 9))


def test_existing_configs_keep_their_math():
    """The new fields' defaults leave a config without them as it was:
    no shared expert, the 1/sqrt(head_dim) softmax scale, sqrt(d) on the
    embedding, no residual or logits scaling."""
    cfg = get_smoke("granite_moe_3b_a800m")
    assert not cfg.moe_dropless and not cfg.expert_share and not cfg.shared_expert_d_ff and not cfg.has_ffn("ssd")
    assert cfg.attn_scale == 1 / np.sqrt(cfg.head_dim_)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (0.0, 1.0, 1.0)
    assert moe_params(cfg)["wi_gate"].shape[0] == cfg.n_experts
