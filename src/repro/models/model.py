"""Unified model API over all assigned architecture families.

Every family exposes the same functional surface, which the trainer, serving
engine, dry-run, and smoke tests consume uniformly:

  init(rng) -> params
  loss_fn(params, batch) -> (scalar loss, metrics)        [train shapes]
  prefill(params, batch, cache_len) -> (last_logits, cache)
  decode_step(params, cache, tokens, positions) -> (logits, cache)
  init_cache(batch, cache_len) -> cache pytree

Layer stacks are ``lax.scan``-ed (bounded HLO at 100 layers); the per-layer
body is ``jax.checkpoint``-ed in the training path (remat).  The hybrid
family (heterogeneous layer types) uses a python loop instead — it is the
smallest assigned model.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops

from . import layers as L
from . import mamba as M
from . import moe as MOE
from . import rwkv as R

_AUX_COEF = 0.01


def build_model(cfg: ArchConfig, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, kv_quant: bool = False):
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        m = TransformerLM(cfg, dtype, param_dtype)
        m.kv_quant = kv_quant  # int8 KV cache (§Perf; transformer family)
        return m
    if fam == "hybrid":
        return HymbaLM(cfg, dtype, param_dtype)
    if fam == "ssm":
        return RWKV6LM(cfg, dtype, param_dtype)
    if fam == "audio":
        return EncDecLM(cfg, dtype, param_dtype)
    raise ValueError(f"unknown family {fam!r}")


@dataclasses.dataclass
class BaseModel:
    cfg: ArchConfig
    dtype: object = jnp.bfloat16
    param_dtype: object = jnp.bfloat16

    # shared helpers ------------------------------------------------------
    def _positions(self, b: int, s: int) -> jax.Array:
        return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def _kv_cache_zeros(self, b: int, t: int, n: int) -> dict:
        c = self.cfg
        shape = (n, b, t, c.n_kv_heads, c.head_dim)
        return {"k": jnp.zeros(shape, self.dtype), "v": jnp.zeros(shape, self.dtype)}


# ===========================================================================
# dense / moe / vlm decoder-only transformer
# ===========================================================================
class TransformerLM(BaseModel):
    """Decoder-only LM; MoE FFN if cfg.moe; interleaved cross-attn if vlm."""

    def __init__(self, cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16):
        super().__init__(cfg, dtype, param_dtype)
        self.is_moe = cfg.moe is not None
        self.is_vlm = cfg.family == "vlm"
        self.kv_quant = False  # int8 KV cache (set via build_model)
        if self.is_vlm:
            assert cfg.n_layers % cfg.cross_every == 0
            self.n_groups = cfg.n_layers // cfg.cross_every
            self.self_per_group = cfg.cross_every - 1  # last layer of group is cross

    # -- params -----------------------------------------------------------
    def init(self, rng) -> dict:
        c, pd = self.cfg, self.param_dtype
        ks = jax.random.split(rng, 6)
        n_self = c.n_layers if not self.is_vlm else self.n_groups * self.self_per_group
        blocks = {
            "attn": L.init_attention(ks[0], c, pd, n_layers=n_self),
            "ln1": jnp.ones((n_self, c.d_model), pd),
            "ln2": jnp.ones((n_self, c.d_model), pd),
        }
        if self.is_moe:
            blocks["moe"] = MOE.init_moe(ks[1], c, pd)
        else:
            blocks["ffn"] = L.init_mlp(ks[1], c, pd, n_layers=n_self)
        params = {
            "emb": L.init_embedding(ks[2], c, pd),
            "final_norm": jnp.ones((c.d_model,), pd),
            "blocks": blocks,
        }
        if self.is_vlm:
            params["cross"] = {
                "attn": L.init_attention(ks[3], c, pd, n_layers=self.n_groups),
                "ffn": L.init_mlp(ks[4], c, pd, n_layers=self.n_groups),
                "ln1": jnp.ones((self.n_groups, c.d_model), pd),
                "ln2": jnp.ones((self.n_groups, c.d_model), pd),
                "ln_img": jnp.ones((self.n_groups, c.d_model), pd),
            }
        if self.is_moe:
            # MoE FFN applies to every layer; vlm never combines with moe here.
            assert not self.is_vlm
        return params

    # -- one transformer block (self-attn + ffn) ---------------------------
    def _self_block(self, blk: dict, x, positions, *, cache=None, cache_positions=None, window=0, chunk_start=None,
                    layer=None):
        c = self.cfg
        h, new_cache = L.attention_layer(
            blk["attn"],
            L.rms_norm(x, blk["ln1"], c.norm_eps),
            c,
            positions,
            cache=cache,
            cache_positions=cache_positions,
            window=window,
            chunk_start=chunk_start,
        )
        x = x + h
        xn = L.rms_norm(x, blk["ln2"], c.norm_eps)
        if self.is_moe and cache is not None:  # serving: dropless, held experts
            out, aux = MOE.moe_serve(blk["moe"], xn, c, layer)  # aux: the layer's counters
        elif self.is_moe:
            out, aux = MOE.moe_ffn(blk["moe"], xn, c)
        else:
            out, aux = L.mlp_layer(blk["ffn"], xn), 0.0
        return x + out, aux, new_cache

    def _serve_layers(self, params, x, cache, **block_kw):
        """Scan the self-attention blocks over ``x`` and their cache leaves
        (a serving program); a MoE adds each layer's counters to the cache's
        ``counters``, summed on the device across programs.

        A MoE's expert stacks stay out of the scanned inputs: the scan carries
        the layer index, and the expert GEMMs read that layer of each whole
        stack in place, so no program copies a layer's experts out."""
        keys = self._cache_keys
        blocks, stacks, layers = params["blocks"], {}, ()
        if self.is_moe:
            stacks = {n: blocks["moe"][n] for n in MOE.EXPERTS}
            blocks = dict(blocks, moe={"router": blocks["moe"]["router"]})
            layers = (jnp.arange(blocks["ln1"].shape[0]),)

        def self_body(x, inp):
            blk, *kv = inp
            layer = None
            if self.is_moe:
                layer, *kv = kv
                blk = dict(blk, moe=dict(blk["moe"], **stacks))
            xc, aux, kv = self._self_block(blk, x, cache=tuple(kv), layer=layer, **block_kw)
            return L.shard_act(xc), ((kv, aux) if self.is_moe else kv)

        x, ys = jax.lax.scan(self_body, x, (blocks, *layers, *[cache[k] for k in keys]))
        kv, counts = ys if self.is_moe else (ys, None)
        cache = dict(cache, **dict(zip(keys, kv)))
        if counts is not None:
            cache["counters"] = {name: cache["counters"][name] + counts[:, i]
                                 for i, name in enumerate(MOE.COUNTERS)}
        return x, cache

    def _cross_block(self, blk: dict, x, image_embs):
        c = self.cfg
        h, _ = L.attention_layer(
            blk["attn"],
            L.rms_norm(x, blk["ln1"], c.norm_eps),
            c,
            None,
            kv_input=L.rms_norm(image_embs, blk["ln_img"], c.norm_eps),
            causal=False,
            use_rope=False,
        )
        x = x + h
        return x + L.mlp_layer(blk["ffn"], L.rms_norm(x, blk["ln2"], c.norm_eps))

    # -- forward over the stack --------------------------------------------
    def _forward(self, params, tokens, *, image_embs=None, remat=False):
        c = self.cfg
        b, s = tokens.shape
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))

        def self_body(carry, blk):
            x, aux = carry
            x, aux_i, _ = self._self_block(blk, x, positions)
            return (L.shard_act(x), aux + aux_i), None

        body = L.ckpt(self_body) if remat else self_body
        aux0 = jnp.zeros((), jnp.float32)

        if not self.is_vlm:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
        else:
            g, spg = self.n_groups, self.self_per_group
            grouped = jax.tree.map(lambda a: a.reshape(g, spg, *a.shape[1:]), params["blocks"])

            def group_body(carry, blks):
                self_blks, cross_blk = blks
                (x, aux), _ = jax.lax.scan(body, carry, self_blks)
                x = self._cross_block(cross_blk, x, image_embs)
                return (x, aux), None

            gbody = L.ckpt(group_body) if remat else group_body
            (x, aux), _ = jax.lax.scan(gbody, (x, aux0), (grouped, params["cross"]))

        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return x, aux

    # -- public API ---------------------------------------------------------
    def loss_fn(self, params, batch):
        c = self.cfg
        x, aux = self._forward(
            params, batch["tokens"], image_embs=batch.get("image_embs"), remat=True
        )
        logits = L.logits_from_hidden(params["emb"], x, c)
        loss = L.cross_entropy_loss(logits, batch["targets"], c.vocab)
        total = loss + _AUX_COEF * aux if self.is_moe else loss
        return total, {"ce_loss": loss, "aux_loss": aux}

    # -- caches / decode ----------------------------------------------------
    def init_cache(self, b: int, cache_len: int) -> dict:
        c = self.cfg
        n_self = c.n_layers if not self.is_vlm else self.n_groups * self.self_per_group
        if self.kv_quant:
            shape = (n_self, b, cache_len, c.n_kv_heads, c.head_dim)
            cache = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32),
            }
        else:
            cache = self._kv_cache_zeros(b, cache_len, n_self)
        if self.is_moe:
            cache["counters"] = {name: jnp.zeros((n_self,), jnp.int32) for name in MOE.COUNTERS}
        if self.is_vlm:
            cache["cross_k"] = jnp.zeros(
                (self.n_groups, b, c.n_image_tokens, c.n_kv_heads, c.head_dim), self.dtype
            )
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        return cache

    @property
    def _cache_keys(self) -> tuple[str, ...]:
        return ("k", "v", "k_scale", "v_scale") if self.kv_quant else ("k", "v")

    def prefill(self, params, batch, cache_len: int):
        c = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache = self.init_cache(b, cache_len)
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        image_embs = batch.get("image_embs")
        keys = self._cache_keys

        def self_body(x, inp):
            blk, *kv = inp
            xc, _, kv = self._self_block(blk, x, positions, cache=tuple(kv))
            return L.shard_act(xc), kv

        if not self.is_vlm:
            x, cache = self._serve_layers(params, x, cache, positions=positions)
        else:
            g, spg = self.n_groups, self.self_per_group
            grouped = jax.tree.map(lambda a: a.reshape(g, spg, *a.shape[1:]), params["blocks"])
            kvg = [cache[k].reshape(g, spg, *cache[k].shape[1:]) for k in keys]

            def group_body(x, inp):
                self_blks, cross_blk, *kv = inp
                x, kv = jax.lax.scan(self_body, x, (self_blks, *kv))
                x = self._cross_block(cross_blk, x, image_embs)
                # Cross K/V are static per request: computed once here.
                imn = L.rms_norm(image_embs, cross_blk["ln_img"], c.norm_eps)
                ck = ops.matmul(imn, cross_blk["attn"]["wk"]).reshape(b, -1, c.n_kv_heads, c.head_dim)
                cv = ops.matmul(imn, cross_blk["attn"]["wv"]).reshape(b, -1, c.n_kv_heads, c.head_dim)
                return x, (*kv, ck, cv)

            x, (*kvg, cks, cvs) = jax.lax.scan(group_body, x, (grouped, params["cross"], *kvg))
            for key, arr in zip(keys, kvg):
                cache[key] = arr.reshape(g * spg, *arr.shape[2:])
            cache["cross_k"], cache["cross_v"] = cks, cvs

        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.logits_from_hidden(params["emb"], x[:, -1:], c)
        return logits, cache

    def supports_chunked_prefill(self) -> bool:
        """Chunk-append prefill works on the plain (non-vlm, non-int8) k/v
        layout; other layouts fall back to monolithic prefill."""
        return not self.is_vlm and not self.kv_quant

    def prefill_chunk(self, params, cache, tokens, start, last_row=None):
        """Append a prompt chunk at absolute positions ``[start, start+S)``.

        ``tokens``: (B, S); ``start``: scalar, may be traced — one compiled
        program per chunk *length* serves every chunk offset, which is what
        makes scheduler-granular chunked prefill affordable.  Returns
        ``(logits, cache)`` like :meth:`prefill`; ``last_row`` (scalar, may
        be traced, defaults to ``S-1``) selects the row whose logits are
        returned, so a padded final chunk can ask for its last *real* row —
        the first-token logits — without a separate decode program.  Rows
        past the real prompt (a padded final chunk) are causally dead; later
        chunks or decode steps overwrite them.
        """
        if not self.supports_chunked_prefill():
            raise NotImplementedError(
                f"chunked prefill unsupported for this layout "
                f"(vlm={self.is_vlm}, kv_quant={self.kv_quant})"
            )
        c = self.cfg
        b, s = tokens.shape
        start = jnp.asarray(start, jnp.int32)
        positions = start + self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        x, cache = self._serve_layers(params, x, cache, positions=positions, chunk_start=start)
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        if last_row is None:
            x_last = x[:, -1:]
        else:
            x_last = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(last_row, jnp.int32), 1, axis=1
            )
        return L.logits_from_hidden(params["emb"], x_last, c), cache

    def decode_step(self, params, cache, tokens, positions):
        """tokens: (B, 1); positions: (B,) — index of the new token."""
        c = self.cfg
        b = tokens.shape[0]
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        pos2d = positions[:, None]
        keys = self._cache_keys

        def self_body(x, inp):
            blk, *kv = inp
            xc, _, kv = self._self_block(blk, x, pos2d, cache=tuple(kv), cache_positions=positions)
            return L.shard_act(xc), kv

        if not self.is_vlm:
            x, cache = self._serve_layers(params, x, cache, positions=pos2d,
                                          cache_positions=positions)
        else:
            g, spg = self.n_groups, self.self_per_group
            grouped = jax.tree.map(lambda a: a.reshape(g, spg, *a.shape[1:]), params["blocks"])
            kvg = [cache[k].reshape(g, spg, *cache[k].shape[1:]) for k in keys]

            def group_body(x, inp):
                self_blks, cross_blk, ck, cv, *kv = inp
                x, kv = jax.lax.scan(self_body, x, (self_blks, *kv))
                q = ops.matmul(L.rms_norm(x, cross_blk["ln1"], c.norm_eps), cross_blk["attn"]["wq"])
                h = L.decode_attention_jnp(
                    q.reshape(b, 1, c.n_heads, c.head_dim),
                    ck,
                    cv,
                    jnp.full((b,), ck.shape[1], jnp.int32),  # attend over all image tokens
                )
                x = x + ops.matmul(h.reshape(b, 1, c.q_dim), cross_blk["attn"]["wo"])
                x = x + L.mlp_layer(cross_blk["ffn"], L.rms_norm(x, cross_blk["ln2"], c.norm_eps))
                return x, kv

            x, kvg = jax.lax.scan(
                group_body,
                x,
                (grouped, params["cross"], cache["cross_k"], cache["cross_v"], *kvg),
            )
            cache = dict(
                cache,
                **{key: arr.reshape(g * spg, *arr.shape[2:]) for key, arr in zip(keys, kvg)},
            )

        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x, c), cache


# ===========================================================================
# hymba: parallel attention + mamba heads, SWA + 3 global layers
# ===========================================================================
class HymbaLM(BaseModel):
    def _layer_kinds(self) -> list[str]:
        n = self.cfg.n_layers
        glob = {0, n // 2, n - 1}
        return ["global" if i in glob else "swa" for i in range(n)]

    def init(self, rng) -> dict:
        c, pd = self.cfg, self.param_dtype
        ks = jax.random.split(rng, 4)
        return {
            "emb": L.init_embedding(ks[0], c, pd),
            "final_norm": jnp.ones((c.d_model,), pd),
            "blocks": {
                "attn": L.init_attention(ks[1], c, pd),
                "mamba": M.init_mamba(ks[2], c, pd),
                "ffn": L.init_mlp(ks[3], c, pd),
                "ln1": jnp.ones((c.n_layers, c.d_model), pd),
                "ln2": jnp.ones((c.n_layers, c.d_model), pd),
            },
        }

    def _windows(self):
        """Per-layer window sizes (0 = global) as a scannable array."""
        return jnp.array(
            [0 if k == "global" else self.cfg.window for k in self._layer_kinds()], jnp.int32
        )

    def _layer(self, blk, x, positions, kind, *, cache=None, cache_positions=None, cache_valid=None, window=None):
        """Parallel attn + mamba on the same normalized input (Hymba fusion).

        ``kind`` picks the static window ('global'/'swa'); pass ``window``
        (possibly traced, 0 = global) instead when scanning over layers.
        """
        c = self.cfg
        xn = L.rms_norm(x, blk["ln1"], c.norm_eps)
        if window is None:
            window = 0 if kind == "global" else c.window
        attn_cache = mamba_state = None
        if cache is not None:
            attn_cache, mamba_state = cache
        h_attn, new_attn_cache = L.attention_layer(
            blk["attn"],
            xn,
            c,
            positions,
            window=window,
            cache=attn_cache,
            cache_positions=cache_positions,
            cache_valid=cache_valid,
        )
        if cache is not None and x.shape[1] == 1:
            h_mamba, new_mamba_state = M.mamba_decode_step(blk["mamba"], xn, mamba_state, c)
        else:
            h_mamba, new_mamba_state = M.mamba_layer(blk["mamba"], xn, c)
        x = x + 0.5 * (h_attn + h_mamba)
        x = x + L.mlp_layer(blk["ffn"], L.rms_norm(x, blk["ln2"], c.norm_eps))
        return x, (new_attn_cache, new_mamba_state)

    def _slice_blocks(self, params, i):
        return jax.tree.map(lambda a: a[i], params["blocks"])

    def loss_fn(self, params, batch):
        c = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))

        # Layers are structurally homogeneous — only the window differs
        # (0 = global) — so the stack scans with a traced per-layer window,
        # keeping the HLO bounded like every other family.
        def body(x, inp):
            blk, w = inp
            x, _ = self._layer(blk, x, positions, None, window=w)
            return L.shard_act(x), None

        x, _ = jax.lax.scan(L.ckpt(body), x, (params["blocks"], self._windows()))
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.logits_from_hidden(params["emb"], x, c)
        loss = L.cross_entropy_loss(logits, batch["targets"], c.vocab)
        return loss, {"ce_loss": loss}

    def init_cache(self, b: int, cache_len: int) -> dict:
        c = self.cfg
        kinds = self._layer_kinds()
        cache = {}
        for i, kind in enumerate(kinds):
            t = cache_len if kind == "global" else min(c.window, cache_len)
            cache[f"layer{i}"] = {
                "k": jnp.zeros((b, t, c.n_kv_heads, c.head_dim), self.dtype),
                "v": jnp.zeros((b, t, c.n_kv_heads, c.head_dim), self.dtype),
                "ssm": jnp.zeros((b, c.d_model, c.ssm_state), jnp.float32),
            }
        return cache

    def prefill(self, params, batch, cache_len: int):
        c = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        cache = self.init_cache(b, cache_len)
        for i, kind in enumerate(self._layer_kinds()):
            blk = self._slice_blocks(params, i)
            entry = cache[f"layer{i}"]
            x, ((kc, vc), ssm) = self._layer(
                blk, x, positions, kind, cache=((entry["k"], entry["v"]), entry["ssm"])
            )
            cache[f"layer{i}"] = {"k": kc, "v": vc, "ssm": ssm}
            x = L.shard_act(x)
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x[:, -1:], c), cache

    def decode_step(self, params, cache, tokens, positions):
        c = self.cfg
        b = tokens.shape[0]
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        new_cache = dict(cache)
        for i, kind in enumerate(self._layer_kinds()):
            blk = self._slice_blocks(params, i)
            entry = cache[f"layer{i}"]
            t = entry["k"].shape[1]
            # Ring-buffer slots + valid-count for SWA layers.
            cpos = positions if kind == "global" else positions % t
            cvalid = positions + 1 if kind == "global" else jnp.minimum(positions + 1, t)
            x, ((kc, vc), ssm) = self._layer(
                blk,
                x,
                positions[:, None],
                kind,
                cache=((entry["k"], entry["v"]), entry["ssm"]),
                cache_positions=cpos,
                cache_valid=cvalid,
            )
            new_cache[f"layer{i}"] = {"k": kc, "v": vc, "ssm": ssm}
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x, c), new_cache


# ===========================================================================
# rwkv6
# ===========================================================================
class RWKV6LM(BaseModel):
    def init(self, rng) -> dict:
        c, pd = self.cfg, self.param_dtype
        ks = jax.random.split(rng, 2)
        return {
            "emb": L.init_embedding(ks[0], c, pd),
            "final_norm": jnp.ones((c.d_model,), pd),
            "blocks": {
                "rwkv": R.init_rwkv(ks[1], c, pd),
                "ln1": jnp.ones((c.n_layers, c.d_model), pd),
                "ln2": jnp.ones((c.n_layers, c.d_model), pd),
            },
        }

    def _layer(self, blk, x, *, state=None):
        """state: (wkv (B,H,hd,hd), x1 (B,d), x2 (B,d)) or None."""
        c = self.cfg
        wkv_state = x1 = x2 = None
        if state is not None:
            wkv_state, x1, x2 = state
        xn = L.rms_norm(x, blk["ln1"], c.norm_eps)
        h, (new_wkv, last1) = R.time_mix_layer(blk["rwkv"], xn, c, state=wkv_state, x_prev=x1)
        x = x + h
        xn2 = L.rms_norm(x, blk["ln2"], c.norm_eps)
        h2, last2 = R.channel_mix_layer(blk["rwkv"], xn2, c, x_prev=x2)
        x = x + h2
        return x, (new_wkv, last1, last2)

    def loss_fn(self, params, batch):
        c = self.cfg
        tokens = batch["tokens"]
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))

        def body(x, blk):
            x, _ = self._layer(blk, x)
            return L.shard_act(x), None

        x, _ = jax.lax.scan(L.ckpt(body), x, params["blocks"])
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.logits_from_hidden(params["emb"], x, c)
        loss = L.cross_entropy_loss(logits, batch["targets"], c.vocab)
        return loss, {"ce_loss": loss}

    def init_cache(self, b: int, cache_len: int) -> dict:
        c = self.cfg
        n, h, hd, d = c.n_layers, c.n_heads, c.head_dim, c.d_model
        return {
            "wkv": jnp.zeros((n, b, h, hd, hd), jnp.float32),
            "x1": jnp.zeros((n, b, d), self.dtype),
            "x2": jnp.zeros((n, b, d), self.dtype),
        }

    def _run(self, params, x, cache):
        def body(x, inp):
            blk, wkv, x1, x2 = inp
            x, (wkv, x1, x2) = self._layer(blk, x, state=(wkv, x1, x2))
            return L.shard_act(x), (wkv, x1, x2)

        x, (wkv, x1, x2) = jax.lax.scan(body, x, (params["blocks"], cache["wkv"], cache["x1"], cache["x2"]))
        return x, {"wkv": wkv, "x1": x1, "x2": x2}

    def prefill(self, params, batch, cache_len: int):
        c = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        x = L.embed_tokens(params["emb"], tokens).astype(self.dtype)
        x, cache = self._run(params, x, self.init_cache(b, cache_len))
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x[:, -1:], c), cache

    def decode_step(self, params, cache, tokens, positions):
        del positions  # recurrent state carries all history
        c = self.cfg
        x = L.embed_tokens(params["emb"], tokens).astype(self.dtype)
        x, cache = self._run(params, x, cache)
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x, c), cache


# ===========================================================================
# seamless (enc-dec)
# ===========================================================================
class EncDecLM(BaseModel):
    def init(self, rng) -> dict:
        c, pd = self.cfg, self.param_dtype
        ks = jax.random.split(rng, 6)
        ne = c.n_enc_layers
        return {
            "emb": L.init_embedding(ks[0], c, pd),
            "final_norm": jnp.ones((c.d_model,), pd),
            "enc_norm": jnp.ones((c.d_model,), pd),
            "encoder": {
                "attn": L.init_attention(ks[1], c, pd, n_layers=ne),
                "ffn": L.init_mlp(ks[2], c, pd, n_layers=ne),
                "ln1": jnp.ones((ne, c.d_model), pd),
                "ln2": jnp.ones((ne, c.d_model), pd),
            },
            "decoder": {
                "attn": L.init_attention(ks[3], c, pd),
                "cross": L.init_attention(ks[4], c, pd),
                "ffn": L.init_mlp(ks[5], c, pd),
                "ln1": jnp.ones((c.n_layers, c.d_model), pd),
                "ln_x": jnp.ones((c.n_layers, c.d_model), pd),
                "ln2": jnp.ones((c.n_layers, c.d_model), pd),
            },
        }

    def encode(self, params, frames):
        """frames: (B, S, d) stubbed audio-frontend embeddings."""
        c = self.cfg
        b, s, _ = frames.shape
        positions = self._positions(b, s)
        x = L.shard_act(frames.astype(self.dtype))

        def body(x, blk):
            h, _ = L.attention_layer(
                blk["attn"], L.rms_norm(x, blk["ln1"], c.norm_eps), c, positions, causal=False
            )
            x = x + h
            x = x + L.mlp_layer(blk["ffn"], L.rms_norm(x, blk["ln2"], c.norm_eps))
            return L.shard_act(x), None

        x, _ = jax.lax.scan(L.ckpt(body), x, params["encoder"])
        return L.rms_norm(x, params["enc_norm"], c.norm_eps)

    def _dec_layer(self, blk, x, positions, memory, *, cache=None, cache_positions=None):
        c = self.cfg
        h, new_cache = L.attention_layer(
            blk["attn"],
            L.rms_norm(x, blk["ln1"], c.norm_eps),
            c,
            positions,
            cache=cache,
            cache_positions=cache_positions,
        )
        x = x + h
        h, _ = L.attention_layer(
            blk["cross"],
            L.rms_norm(x, blk["ln_x"], c.norm_eps),
            c,
            None,
            kv_input=memory,
            causal=False,
            use_rope=False,
        )
        x = x + h
        x = x + L.mlp_layer(blk["ffn"], L.rms_norm(x, blk["ln2"], c.norm_eps))
        return x, new_cache

    def loss_fn(self, params, batch):
        c = self.cfg
        memory = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))

        def body(x, blk):
            x, _ = self._dec_layer(blk, x, positions, memory)
            return L.shard_act(x), None

        x, _ = jax.lax.scan(L.ckpt(body), x, params["decoder"])
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        logits = L.logits_from_hidden(params["emb"], x, c)
        loss = L.cross_entropy_loss(logits, batch["targets"], c.vocab)
        return loss, {"ce_loss": loss}

    def init_cache(self, b: int, cache_len: int) -> dict:
        cache = self._kv_cache_zeros(b, cache_len, self.cfg.n_layers)
        return cache

    def prefill(self, params, batch, cache_len: int):
        """Encode frames + prefill the decoder with its token prefix."""
        c = self.cfg
        memory = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = self._positions(b, s)
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))
        cache = self.init_cache(b, cache_len)

        def body(x, inp):
            blk, kc, vc = inp
            x, (kc, vc) = self._dec_layer(blk, x, positions, memory, cache=(kc, vc))
            return L.shard_act(x), (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (params["decoder"], cache["k"], cache["v"]))
        cache.update(k=ks, v=vs)
        cache["memory"] = memory
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x[:, -1:], c), cache

    def decode_step(self, params, cache, tokens, positions):
        c = self.cfg
        b = tokens.shape[0]
        memory = cache["memory"]
        x = L.shard_act(L.embed_tokens(params["emb"], tokens).astype(self.dtype))

        def body(x, inp):
            blk, kc, vc = inp
            x, (kc, vc) = self._dec_layer(
                blk, x, positions[:, None], memory, cache=(kc, vc), cache_positions=positions
            )
            return L.shard_act(x), (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (params["decoder"], cache["k"], cache["v"]))
        cache = dict(cache, k=ks, v=vs)
        x = L.rms_norm(x, params["final_norm"], c.norm_eps)
        return L.logits_from_hidden(params["emb"], x, c), cache
