"""Composable LM: embeds + scanned block stacks + heads + caches.

One model class serves all ten assigned architectures; the block mix is
driven by ``ModelConfig.block_types``:

* ``attn`` / ``local_attn``  — GQA attention (+MLP or MoE)
* ``rglru``                  — Griffin recurrent block (+MLP)
* ``ssd``                    — Mamba-2 block (self-contained, or followed
                               by the FFN / expert layer: ``cfg.ssm_ffn``)

Each block adds ``residual_multiplier`` x its mixer's output, then x its
FFN's (Granite 4.0 scales both branches; 1 elsewhere).  The expert layer
is ``moe_ffn`` (capacity factor, all experts) or, with
``cfg.moe_dropless``, ``moe_share_ffn`` (dropless, over the held experts
of ``cfg.expert_share``, plus a shared one).

Layer stacks are executed with ``jax.lax.scan`` over *stacked* per-layer
parameters; heterogeneous repeating patterns (recurrentgemma R,R,A) scan
over super-blocks.  HLO size is therefore O(#distinct block kinds), not
O(depth) — granite-34b's 88 layers compile as one scan body.

Entry points:
  forward(params, tokens | embeds)      -> logits (training/encoder)
  loss(params, batch)                   -> scalar (+ MoE aux)
  prefill(params, tokens, cache_len)    -> (last_logits, cache)
  decode_step(params, cache, token, pos)-> (logits, cache)
  decode(params, cache, token, pos)     -> (logits, cache, expert_rows)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models.attention import attention, mrope_tables, rope_tables
from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec, embed_params, init_from_specs, mlp, mlp_params, rmsnorm, spec_shapes
from repro.models.moe import moe_ffn, moe_params, moe_share_ffn
from repro.models.rglru import rglru_block, rglru_decode_step, rglru_params, rglru_state_init
from repro.models.ssd import ssd_block, ssd_decode_step, ssd_params, ssd_state_init

__all__ = ["KV_BUCKET", "LM", "StackSpec", "kv_prefixes", "kv_read"]

# a decode step's global attention reads K and V over a prefix of the
# cache that is a multiple of this many positions (the last, the whole
# cache): one static shape per bucket, picked on the device
KV_BUCKET = 512


def kv_prefixes(length: int) -> tuple[int, ...]:
    """The prefixes of a global-attention cache of ``length`` positions
    that a decode step may read: each multiple of :data:`KV_BUCKET` below
    ``length``, then ``length``."""
    return tuple(range(KV_BUCKET, length, KV_BUCKET)) + (length,)


def kv_read(position: int, length: int) -> int:
    """The prefix of :func:`kv_prefixes` that a decode step at ``position``
    reads: the shortest that holds ``position``.  The step picks it with
    ``lax.switch(position // KV_BUCKET, ...)``, whose index clamps to the
    last prefix as the ``min`` here does."""
    return min(length, (position // KV_BUCKET + 1) * KV_BUCKET)


@dataclass(frozen=True)
class StackSpec:
    """One scanned stack: a block pattern repeated ``repeats`` times."""

    pattern: tuple[str, ...]  # e.g. ("attn",) or ("rglru","rglru","attn")
    repeats: int


def _plan_stacks(cfg: ModelConfig) -> list[StackSpec]:
    pat = cfg.layer_pattern()
    period = len(cfg.block_types)
    if period > 1:
        reps = len(pat) // period
        rem = len(pat) % period
        stacks = [StackSpec(tuple(cfg.block_types), reps)]
        if rem:
            stacks.append(StackSpec(tuple(pat[-rem:]), 1))
        return stacks
    return [StackSpec((pat[0],), len(pat))]


def _stack_specs(specs, n: int):
    """Add a leading 'layers' axis of size n to every ParamSpec."""

    def add(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.scale)

    return jax.tree.map(add, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.stacks = _plan_stacks(cfg)

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def _block_specs(self, btype: str) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        out: dict[str, Any] = {"norm1": ParamSpec((d,), ("embed",), "float32", init="zeros")}
        if btype in ("attn", "local_attn"):
            out["attn"] = attn_mod.attention_params(cfg)
        elif btype == "rglru":
            out["rglru"] = rglru_params(cfg)
        elif btype == "ssd":
            out["ssd"] = ssd_params(cfg)
            if not cfg.has_ffn(btype):
                return out  # mamba2 blocks carry no separate MLP
        out["norm2"] = ParamSpec((d,), ("embed",), "float32", init="zeros")
        if cfg.is_moe:
            out["moe"] = moe_params(cfg)
        else:
            out["mlp"] = mlp_params(d, cfg.d_ff, cfg.activation, cfg.dtype)
        return out

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {"embed": embed_params(cfg.vocab, cfg.d_model, cfg.dtype)}
        if cfg.frontend_stub:
            # modality frontend stub: a single projection from precomputed
            # frame/patch embeddings (input_specs provide those)
            specs["frontend"] = ParamSpec((cfg.d_model, cfg.d_model), ("embed", None), cfg.dtype)
        for i, st in enumerate(self.stacks):
            blk = {f"b{j}_{bt}": self._block_specs(bt) for j, bt in enumerate(st.pattern)}
            specs[f"stack{i}"] = _stack_specs(blk, st.repeats)
        specs["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), "float32", init="zeros")
        if not cfg.tie_embeddings:
            specs["lm_head"] = ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), cfg.dtype)
        return specs

    def init(self, rng: jax.Array):
        return init_from_specs(rng, self.param_specs())

    def _layer_slots(self):
        """(stack key, repeat, block key) of each layer, in layer order."""
        for i, st in enumerate(self.stacks):
            for r in range(st.repeats):
                for j, bt in enumerate(st.pattern):
                    yield f"stack{i}", r, f"b{j}_{bt}"

    def layers_of(self, params: dict) -> list[dict]:
        """Each layer's block parameters, unstacked, in layer order."""
        return [jax.tree.map(lambda a: a[r], params[s][b]) for s, r, b in self._layer_slots()]

    def params_from_layers(self, embed, layers: list[dict], final_norm) -> dict:
        """The model's parameters from per-layer block dicts in layer order
        (the inverse of :meth:`layers_of`; tied embeddings only)."""
        if not self.cfg.tie_embeddings or self.cfg.frontend_stub:
            raise ValueError("params_from_layers builds tied-embedding token models only")
        stacks: dict[str, dict[str, list]] = {}
        for layer, (s, _, b) in zip(layers, self._layer_slots(), strict=True):
            stacks.setdefault(s, {}).setdefault(b, []).append(layer)
        params: dict[str, Any] = {"embed": embed, "final_norm": final_norm}
        for s, blocks in stacks.items():
            params[s] = {b: jax.tree.map(lambda *a: jnp.stack(a), *reps) for b, reps in blocks.items()}
        return params

    def param_shapes(self):
        return spec_shapes(self.param_specs())

    # ------------------------------------------------------------------
    # Block application
    # ------------------------------------------------------------------
    def _residual(self, x: jax.Array, h: jax.Array) -> jax.Array:
        m = self.cfg.residual_multiplier
        return x + h if m == 1.0 else x + h * jnp.asarray(m, h.dtype)

    def _ffn(self, btype: str, bp: dict, x: jax.Array):
        """The block's FFN or expert layer on its residual stream; returns
        (x, MoE aux loss, routed rows its held experts computed)."""
        cfg = self.cfg
        zero = jnp.zeros((), jnp.int32)
        if not cfg.has_ffn(btype):
            return x, jnp.zeros((), jnp.float32), zero
        h2 = rmsnorm(x, bp["norm2"], cfg.norm_eps)
        aux, rows = jnp.zeros((), jnp.float32), zero
        if cfg.moe_dropless:
            y, rows = moe_share_ffn(bp["moe"], h2, cfg)
        elif cfg.is_moe:
            y, aux = moe_ffn(bp["moe"], h2, cfg)
        else:
            y = mlp(bp["mlp"], h2, cfg.activation)
        return self._residual(x, y), aux, rows

    def _apply_block(self, btype: str, bp: dict, x: jax.Array, rope, aux):
        cfg = self.cfg
        h = rmsnorm(x, bp["norm1"], cfg.norm_eps)
        if btype in ("attn", "local_attn"):
            sin, cos = rope
            window = cfg.local_window if btype == "local_attn" else None
            h = attention(bp["attn"], h, cfg, sin=sin, cos=cos, window=window)
        elif btype == "rglru":
            h = rglru_block(bp["rglru"], h, cfg)
        elif btype == "ssd":
            h = ssd_block(bp["ssd"], h, cfg)
        x, a, _ = self._ffn(btype, bp, self._residual(x, h))
        return x, aux + a

    def _maybe_remat(self, fn):
        cfg = self.cfg
        if cfg.remat == "none":
            return fn
        if cfg.remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)  # "full"

    @staticmethod
    def _scan_or_loop(body, carry, xs, repeats: int, scan: bool):
        """lax.scan over stacked layer params, or an unrolled python loop
        (scan_layers=False — used by the roofline depth-extrapolation
        protocol, where while-loop bodies must appear per-layer in HLO)."""
        if scan:
            return jax.lax.scan(body, carry, xs)
        ys = []
        for r in range(repeats):
            sl = jax.tree.map(lambda p: p[r], xs)
            carry, y = body(carry, sl)
            ys.append(y)
        if ys and ys[0] is not None:
            ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
        else:
            ys = None
        return carry, ys

    def _run_stacks(self, params: dict, x: jax.Array, rope):
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)

        def scan_stack(i: int, st: StackSpec, x, aux):
            stack_params = params[f"stack{i}"]

            def body(carry, layer_params):
                x, aux = carry
                for j, bt in enumerate(st.pattern):
                    x, aux = self._apply_block(bt, layer_params[f"b{j}_{bt}"], x, rope, aux)
                return (x, aux), None

            body = self._maybe_remat(body)
            (x, aux), _ = self._scan_or_loop(body, (x, aux), stack_params, st.repeats, cfg.scan_layers)
            return x, aux

        aux = aux0
        for i, st in enumerate(self.stacks):
            x, aux = scan_stack(i, st, x, aux)
        return x, aux

    def _embed_in(self, params: dict, tokens: jax.Array | None, embeds: jax.Array | None):
        cfg = self.cfg
        if embeds is not None:
            x = embeds.astype(jnp.dtype(cfg.dtype))
            if cfg.frontend_stub:
                x = x @ params["frontend"]
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
            x = x * self._embed_scale(x.dtype)
        return constrain(x, "batch", "seq", None)

    def _embed_scale(self, dtype) -> jax.Array:
        """The embedding's multiplier: the config's, else gemma-style sqrt(d)."""
        cfg = self.cfg
        return jnp.asarray(cfg.embedding_multiplier or jnp.sqrt(cfg.d_model), dtype)

    def _logits(self, params: dict, x: jax.Array) -> jax.Array:
        """LM head on normed hidden states (B, S, D) -> (B, S, V)."""
        head = params["embed"] if self.cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,vd->bsv", x, head)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(self.cfg.logits_scaling, logits.dtype)
        return logits

    def _rope_for(self, positions: jax.Array | None, B: int, S: int):
        cfg = self.cfg
        if cfg.pos_kind == "none":
            return (None, None)
        if positions is None:
            positions = jnp.arange(S)
        if cfg.pos_kind == "mrope":
            if positions.ndim == 1:
                positions = jnp.broadcast_to(positions, (3, B, S))
            return mrope_tables(positions, cfg.mrope_sections, cfg.head_dim_, cfg.rope_theta)
        return rope_tables(positions, cfg.head_dim_, cfg.rope_theta)

    # ------------------------------------------------------------------
    # Training / encoder forward
    # ------------------------------------------------------------------
    def forward(
        self,
        params: dict,
        tokens: jax.Array | None = None,
        *,
        embeds: jax.Array | None = None,
        positions: jax.Array | None = None,
        last_only: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward; returns (logits (B,S,V), moe_aux).

        ``last_only`` slices to the final position *before* the LM head —
        prefill only needs next-token logits, saving the (B,S,V) product.
        """
        x = self._embed_in(params, tokens, embeds)
        B, S, _ = x.shape
        rope = self._rope_for(positions, B, S)
        x, aux = self._run_stacks(params, x, rope)
        x = rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
        if last_only:
            x = x[:, -1:]
        logits = constrain(self._logits(params, x), "batch", "seq", "vocab")
        return logits, aux

    def loss(self, params: dict, batch: dict) -> jax.Array:
        """Mean next-token (or frame-label) cross-entropy + MoE aux."""
        logits, aux = self.forward(
            params,
            batch.get("tokens"),
            embeds=batch.get("embeds"),
            positions=batch.get("positions"),
        )
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask")
        nll = logz - gold
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            denom = nll.size
        return jnp.sum(nll) / denom + 0.01 * aux

    # ------------------------------------------------------------------
    # Serving: cache init / prefill / decode
    # ------------------------------------------------------------------
    def _layer_cache_spec(self, btype: str, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if btype in ("attn", "local_attn"):
            length = min(max_len, cfg.local_window) if btype == "local_attn" else max_len
            kv, hd = cfg.kv_heads, cfg.head_dim_
            # heads before positions, the layout the decode step's
            # contractions read: a prefix of positions is then read in
            # place, where with positions first XLA lays the whole buffer
            # out anew in each branch of the live-prefix switch
            return {
                "k": jnp.zeros((batch, kv, length, hd), dt),
                "v": jnp.zeros((batch, kv, length, hd), dt),
                "pos": jnp.full((length,), -1, jnp.int32),
            }
        if btype == "rglru":
            return rglru_state_init(cfg, batch)
        if btype == "ssd":
            return ssd_state_init(cfg, batch)
        raise ValueError(btype)

    def init_cache(self, batch: int, max_len: int) -> dict:
        cache: dict[str, Any] = {}
        for i, st in enumerate(self.stacks):
            per_layer = {
                f"b{j}_{bt}": self._layer_cache_spec(bt, batch, max_len)
                for j, bt in enumerate(st.pattern)
            }
            cache[f"stack{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (st.repeats,) + a.shape).copy(), per_layer
            )
        return cache

    def _layer_cache_axes(self, btype: str) -> dict:
        """Logical sharding axes mirroring _layer_cache_spec leaves."""
        if btype in ("attn", "local_attn"):
            return {
                "k": ("layers", "batch", "kv_heads", None, None),
                "v": ("layers", "batch", "kv_heads", None, None),
                "pos": ("layers", None),
            }
        if btype == "rglru":
            return {
                "h": ("layers", "batch", "ffn"),
                "conv": ("layers", "batch", None, "ffn"),
            }
        if btype == "ssd":
            return {
                "h": ("layers", "batch", "heads", None, None),
                "conv_x": ("layers", "batch", None, "ffn"),
                "conv_B": ("layers", "batch", None, None),
                "conv_C": ("layers", "batch", None, None),
            }
        raise ValueError(btype)

    def cache_axes(self) -> dict:
        """Pytree of logical-axes tuples parallel to init_cache output."""
        out: dict[str, Any] = {}
        for i, st in enumerate(self.stacks):
            out[f"stack{i}"] = {
                f"b{j}_{bt}": self._layer_cache_axes(bt) for j, bt in enumerate(st.pattern)
            }
        return out

    def _decode_block(self, btype: str, bp: dict, lc: dict, x, position):
        """One block of one decode step; returns (x, its cache, routed rows)."""
        cfg = self.cfg
        h = rmsnorm(x, bp["norm1"], cfg.norm_eps)
        if btype in ("attn", "local_attn"):
            window = cfg.local_window if btype == "local_attn" else None
            length = lc["k"].shape[2]
            slot = position % length if btype == "local_attn" else position
            out, lc = self._decode_attn(bp["attn"], h, lc, slot, position, window)
        elif btype == "rglru":
            out, lc = rglru_decode_step(bp["rglru"], h, lc, cfg)
        elif btype == "ssd":
            out, lc = ssd_decode_step(bp["ssd"], h, lc, cfg)
        x, _, rows = self._ffn(btype, bp, self._residual(x, out))
        return x, lc, rows

    def _decode_attn(self, ap: dict, x, lc: dict, slot, position, window):
        """Single-token attention: a global layer reads the live prefix of
        its cache (:func:`kv_read`), a local layer its whole ring."""
        cfg = self.cfg
        B = x.shape[0]
        hd, nh, nkv = cfg.head_dim_, cfg.n_heads, cfg.kv_heads
        q, k_new, v_new = attn_mod._qkv(ap, x, cfg)
        pos_arr = jnp.asarray(position, jnp.int32)[None]
        if cfg.pos_kind != "none":
            sin, cos = rope_tables(pos_arr, hd, cfg.rope_theta)
            q = attn_mod.apply_rope(q, sin, cos)
            k_new = attn_mod.apply_rope(k_new, sin, cos)
        k_new, v_new = (jnp.swapaxes(a, 1, 2).astype(lc["k"].dtype) for a in (k_new, v_new))
        k = jax.lax.dynamic_update_slice(lc["k"], k_new, (0, 0, slot, 0))
        v = jax.lax.dynamic_update_slice(lc["v"], v_new, (0, 0, slot, 0))
        posbuf = jax.lax.dynamic_update_slice(lc["pos"], pos_arr, (slot,))
        k = constrain(k, "batch", "kv_heads", None, None)
        v = constrain(v, "batch", "kv_heads", None, None)

        g = nh // nkv
        qf = (q.astype(jnp.float32) * cfg.attn_scale).reshape(B, 1, nkv, g, hd)

        def attend(k, v, posbuf):
            s = jnp.einsum("bqkgd,bksd->bqkgs", qf, k.astype(jnp.float32))
            valid = (posbuf >= 0) & (posbuf <= position)
            if window is not None:
                valid &= posbuf > position - window
            s = jnp.where(valid[None, None, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bqkgs,bksd->bqkgd", p, v.astype(jnp.float32))

        if window is None:
            # global attention writes position p at slot p, so no slot past
            # ``position`` is valid yet: read only the live prefix (kv_read)
            branches = [
                lambda k, v, pb, n=n: attend(k[:, :, :n], v[:, :, :n], pb[:n]) for n in kv_prefixes(k.shape[2])
            ]
            out = jax.lax.switch(position // KV_BUCKET, branches, k, v, posbuf)
        else:  # a ring: once it wraps, every slot is live
            out = attend(k, v, posbuf)
        out = out.reshape(B, 1, nh * hd).astype(x.dtype)
        y = out @ ap["wo"]
        return constrain(y, "batch", "seq", None), {"k": k, "v": v, "pos": posbuf}

    def decode_step(
        self,
        params: dict,
        cache: dict,
        tokens: jax.Array,  # (B,) int32
        position: jax.Array,  # scalar int32
    ) -> tuple[jax.Array, dict]:
        """One autoregressive step: logits for the next token + new cache."""
        logits, cache, _ = self.decode(params, cache, tokens, position)
        return logits, cache

    def decode(self, params: dict, cache: dict, tokens: jax.Array, position: jax.Array):
        """:meth:`decode_step`, also returning the routed (token, expert)
        pairs the held experts computed in each layer with an FFN, in layer
        order: (logits, cache, rows (n_ffn_layers,) int32), or rows of
        length 0 for a model without a share of experts."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens[:, None], axis=0)
        x = x * self._embed_scale(x.dtype)
        x = constrain(x, "batch", None, None)

        new_cache: dict[str, Any] = {}
        rows = []
        for i, st in enumerate(self.stacks):
            sp = params[f"stack{i}"]
            sc = cache[f"stack{i}"]

            def body(x, inp):
                lp, lc = inp
                lc_out, r = {}, []
                for j, bt in enumerate(st.pattern):
                    key = f"b{j}_{bt}"
                    x, lc_out[key], n = self._decode_block(bt, lp[key], lc[key], x, position)
                    if cfg.moe_dropless and cfg.has_ffn(bt):
                        r.append(n)
                return x, (lc_out, jnp.stack(r) if r else jnp.zeros((0,), jnp.int32))

            x, (nc, r) = self._scan_or_loop(body, x, (sp, sc), st.repeats, cfg.scan_layers)
            new_cache[f"stack{i}"] = nc
            rows.append(r.reshape(-1))

        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return constrain(logits, "batch", "vocab"), new_cache, jnp.concatenate(rows)

    def prefill(
        self, params: dict, tokens: jax.Array, max_len: int | None = None
    ) -> tuple[jax.Array, dict]:
        """Prefill: one pass over the prompt filling the cache; returns
        (last-token logits (B,V), cache).  The pass both computes the
        residual stream and captures per-layer K/V (attention) or final
        recurrent states (rglru/ssd).  ``max_len`` reserves decode head
        room (default: prompt length + 1 step granularity handled by the
        serving engine)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        assert max_len >= S
        x = self._embed_in(params, tokens, None)
        rope = self._rope_for(None, B, S)
        cache = self.init_cache(B, max_len)
        x, cache = self._forward_filling(params, x, rope, cache)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)[:, -1:]
        logits = self._logits(params, x)[:, 0]
        return constrain(logits, "batch", "vocab"), cache

    def _forward_filling(self, params, x, rope, cache):
        """Forward pass that also captures each layer's cache entry."""
        cfg = self.cfg
        S = x.shape[1]

        def fill_block(bt, bp, lc, x):
            h = rmsnorm(x, bp["norm1"], cfg.norm_eps)
            if bt in ("attn", "local_attn"):
                _, k, v = attn_mod._qkv(bp["attn"], h, cfg)
                sin, cos = rope
                if sin is not None:
                    k = attn_mod.apply_rope(k, sin, cos)
                k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # the cache's (B, KV, S, hd)
                L = lc["k"].shape[2]
                if L <= S:
                    # ring-buffer (local) or exactly-sized cache: keep the
                    # last L entries (requires S % L == 0 for the ring
                    # slot mapping; checked by the serving engine)
                    kk, vv = k[:, :, -L:], v[:, :, -L:]
                    pp = jnp.arange(S)[-L:].astype(jnp.int32)
                else:
                    # head-room for decode: prompt in slots [0, S)
                    pad = ((0, 0), (0, 0), (0, L - S), (0, 0))
                    kk = jnp.pad(k, pad)
                    vv = jnp.pad(v, pad)
                    pp = jnp.pad(jnp.arange(S, dtype=jnp.int32), (0, L - S), constant_values=-1)
                lc_new = {
                    "k": kk.astype(lc["k"].dtype),
                    "v": vv.astype(lc["v"].dtype),
                    "pos": pp,
                }
                window = cfg.local_window if bt == "local_attn" else None
                y = attention(bp["attn"], h, cfg, sin=sin, cos=cos, window=window)
            elif bt == "rglru":
                y, lc_new = rglru_block(bp["rglru"], h, cfg, return_state=True)
            elif bt == "ssd":
                y, lc_new = ssd_block(bp["ssd"], h, cfg, return_state=True)
            x, _, _ = self._ffn(bt, bp, self._residual(x, y))
            return x, lc_new

        for i, st in enumerate(self.stacks):
            sp = params[f"stack{i}"]
            sc = cache[f"stack{i}"]

            def body(x, inp):
                lp, lc = inp
                lc_out = {}
                for j, bt in enumerate(st.pattern):
                    key = f"b{j}_{bt}"
                    x, lc_out[key] = fill_block(bt, lp[key], lc[key], x)
                return x, lc_out

            x, nc = self._scan_or_loop(body, x, (sp, sc), st.repeats, cfg.scan_layers)
            cache[f"stack{i}"] = nc
        return x, cache
