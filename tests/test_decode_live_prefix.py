"""A decode step's global attention reads K and V only over the live
prefix of its cache (``transformer.kv_prefixes``, picked on the device by
``lax.switch``): the same logits and cache as the masked read of the whole
buffer, one executable across prefix boundaries, the engine's ``kv_read``
counter equal to what the step read, and the local-attention ring as
before."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.models import LM, ModelConfig, kv_read
from repro.models import transformer
from repro.serving import ServeEngine

CFG = ModelConfig(
    name="live_prefix", family="dense", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, vocab=64,
    d_ff=64, dtype="float32",
)
B = 2


def _cache(model: LM, max_len: int, position: int, seed: int) -> dict:
    """A cache decoded up to ``position``: K and V everywhere, valid
    positions in slots [0, position), and past them empty slots (-1) mixed
    with stale positions of a longer sequence."""
    rng = np.random.default_rng(seed)
    slot = np.arange(max_len)
    stale = np.where(slot % 3 == 0, slot, -1)

    def fill(path, a):
        if jax.tree_util.keystr(path[-1:]) == "['pos']":
            return jnp.asarray(np.broadcast_to(np.where(slot < position, slot, stale), a.shape), a.dtype)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    return jax.tree_util.tree_map_with_path(fill, model.init_cache(B, max_len))


def _decode(model, params, cache, position):
    tokens = jnp.arange(B, dtype=jnp.int32) + 3
    return jax.jit(model.decode_step)(params, cache, tokens, jnp.int32(position))


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("max_len", [1024, 1100])
def test_live_prefix_matches_the_masked_whole_buffer(monkeypatch, max_len, scan_layers):
    """Positions at and around a prefix boundary, and the cache's last;
    1100 is no multiple of the bucket, so its last prefix is the whole
    cache.  With the bucket past the cache length the step reads the whole
    buffer under the mask, as before the prefixes."""
    model = LM(dataclasses.replace(CFG, scan_layers=scan_layers))
    params = model.init(jax.random.key(0))
    for position in (0, 511, 512, 513, max_len - 1):
        cache = _cache(model, max_len, position, seed=position)
        got_logits, got_cache = _decode(model, params, cache, position)
        with monkeypatch.context() as m:
            m.setattr(transformer, "KV_BUCKET", 1 << 30)
            want_logits, want_cache = _decode(model, params, cache, position)
        close = dict(rtol=1e-5, atol=1e-5, err_msg=f"position {position}")
        np.testing.assert_allclose(got_logits, want_logits, **close)
        # the second layer's new K and V come from the first layer's output
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, **close), got_cache, want_cache)


@pytest.mark.parametrize("length", [300, 512, 1024, 1100, 4096])
def test_kv_read_is_the_prefix_the_switch_picks(length):
    prefixes = transformer.kv_prefixes(length)
    assert prefixes[-1] == length and all(n % transformer.KV_BUCKET == 0 for n in prefixes[:-1])
    pick = jax.jit(lambda p: jax.lax.switch(p // transformer.KV_BUCKET, [lambda n=n: n for n in prefixes]))
    for p in sorted({0, 1, 511, 512, 513, length // 2, length - 2, length - 1} & set(range(length))):
        n = kv_read(p, length)
        assert int(pick(jnp.int32(p))) == n
        assert n == min(m for m in prefixes if m > p)


def test_local_attention_ring_decodes_as_the_forward_across_its_wrap():
    """recurrentgemma's local attention keeps a ring of ``local_window``
    slots and reads all of it: decoding past the wrap agrees with the
    teacher-forced forward."""
    cfg = dataclasses.replace(get_smoke("recurrentgemma_2b"), local_window=8, dtype="float32")
    model = LM(cfg)
    params = model.init(jax.random.key(1))
    S, steps = 8, 12
    toks = jax.random.randint(jax.random.key(2), (B, S + steps), 0, cfg.vocab)
    full, _ = model.forward(params, toks)
    lg, cache = model.prefill(params, toks[:, :S], max_len=S + steps)
    assert cache["stack0"]["b2_local_attn"]["k"].shape[3] == 8
    step = jax.jit(model.decode_step)
    for t in range(steps):
        lg, cache = step(params, cache, toks[:, S + t], jnp.int32(S + t))
        np.testing.assert_allclose(lg, full[:, S + t], rtol=1e-4, atol=1e-4, err_msg=f"step {t}")


def test_engine_decode_compiles_once_across_a_prefix_boundary():
    """The engine's decode step crosses from the first prefix into the
    second without a new executable, and its ``lm.decode`` spans record the
    prefix each step read."""
    model = LM(CFG)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, batch_slots=B, max_len=1100)
    prompts = np.asarray(np.random.default_rng(0).integers(0, CFG.vocab, (B, 509)), np.int32)
    tr = obs.get_tracer()
    was = tr.enabled, list(tr._events)
    tr.clear()
    tr.enabled = True
    try:
        ls = eng.prefill(prompts)
        stream = eng.steps(ls, ahead=False)
        for _ in range(6):  # positions 509 .. 514
            next(stream)
        stream.close()
        events = [e for e in tr.chrome_trace()["traceEvents"] if e["name"] == "lm.decode"]
    finally:
        tr.clear()
        tr.enabled = was[0]
        tr._events.extend(was[1])
    assert eng._step._cache_size() == 1
    assert [(e["args"]["pos"], e["args"]["kv_read"]) for e in events] == [
        (509, 512), (510, 512), (511, 512), (512, 1024), (513, 1024), (514, 1024)
    ]
