"""Unified decoder LM: dense GQA, Mixtral-style MoE+SWA, DeepSeek MLA+MoE, VLM.

Scan-over-layers everywhere (HLO size O(1) in depth — required for the
512-device CPU dry-run compile and the remat-friendly layout on TPU).
Heterogeneous stacks (DeepSeek's dense first layer) become [unrolled prefix +
scanned homogeneous body].

Decode uses either GQA KV caches (B, Hkv, S, hd) or the MLA latent cache
(B, S, kv_lora + rope) — the paper-pool's MLA arch caches 576 floats/position
instead of 2*H*hd, and decode uses the absorbed-projection trick so scores and
values are computed directly against the latent.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import params as PM
from .layers import (
    FLASH_RESIDUALS,
    attention,
    decode_attention,
    moe_block,
    rms_norm,
    rope,
    swiglu,
)

DP = ("pod", "data")          # batch axes (pod present only multi-pod)
TP = "model"



def _vocab_specs(vocab: int, d_model: int, model_axis: int):
    """Shard embeddings on vocab when divisible, else on d_model, else replicate."""
    from jax.sharding import PartitionSpec as _P
    if vocab % model_axis == 0:
        return _P(TP, None), _P(None, TP)
    if d_model % model_axis == 0:
        return _P(None, TP), _P(TP, None)
    return _P(None, None), _P(None, None)

def _expert_specs(cfg: ModelConfig, model_axis: int):
    """Expert parallelism when E divides the model axis; else tensor-shard
    inside each expert (mixtral: 8 experts on a 16-way axis)."""
    E = cfg.moe.n_experts
    if E % model_axis == 0:
        return P(TP, None, None), P(TP, None, None)
    return P(None, None, TP), P(None, TP, None)


def _attn_layout(cfg: ModelConfig) -> dict:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        lay = {
            "ln": PM.ParamInfo((D,), P(None), "ones"),
            "wq": PM.ParamInfo((D, H * qk), P(None, TP)),
            "w_dkv": PM.ParamInfo((D, m.kv_lora_rank + m.qk_rope_dim), P(None, None)),
            "kv_ln": PM.ParamInfo((m.kv_lora_rank,), P(None), "ones"),
            "w_uk": PM.ParamInfo((m.kv_lora_rank, H * m.qk_nope_dim), P(None, TP)),
            "w_uv": PM.ParamInfo((m.kv_lora_rank, H * m.v_head_dim), P(None, TP)),
            "wo": PM.ParamInfo((H * m.v_head_dim, D), P(TP, None)),
        }
        return lay
    lay = {
        "ln": PM.ParamInfo((D,), P(None), "ones"),
        "wq": PM.ParamInfo((D, H * hd), P(None, TP)),
        "wk": PM.ParamInfo((D, Hkv * hd), P(None, TP)),
        "wv": PM.ParamInfo((D, Hkv * hd), P(None, TP)),
        "wo": PM.ParamInfo((H * hd, D), P(TP, None)),
    }
    if cfg.qkv_bias:
        lay["bq"] = PM.ParamInfo((H * hd,), P(TP), "zeros")
        lay["bk"] = PM.ParamInfo((Hkv * hd,), P(TP), "zeros")
        lay["bv"] = PM.ParamInfo((Hkv * hd,), P(TP), "zeros")
    if cfg.qk_norm:
        lay["q_norm"] = PM.ParamInfo((hd,), P(None), "ones")
        lay["k_norm"] = PM.ParamInfo((hd,), P(None), "ones")
    return lay


def _mlp_layout(cfg: ModelConfig, d_ff: int) -> dict:
    D = cfg.d_model
    return {
        "ln": PM.ParamInfo((D,), P(None), "ones"),
        "w_gate": PM.ParamInfo((D, d_ff), P(None, TP)),
        "w_up": PM.ParamInfo((D, d_ff), P(None, TP)),
        "w_down": PM.ParamInfo((d_ff, D), P(TP, None)),
    }


def _moe_layout(cfg: ModelConfig, model_axis: int) -> dict:
    D, E, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_expert
    up_spec, down_spec = _expert_specs(cfg, model_axis)
    lay = {
        "ln": PM.ParamInfo((D,), P(None), "ones"),
        "router": PM.ParamInfo((D, E), P(None, None), scale=0.02),
        "w_gate": PM.ParamInfo((E, D, F), up_spec),
        "w_up": PM.ParamInfo((E, D, F), up_spec),
        "w_down": PM.ParamInfo((E, F, D), down_spec),
    }
    if cfg.moe.n_shared:
        S = cfg.moe.n_shared * F
        lay["shared_gate"] = PM.ParamInfo((D, S), P(None, TP))
        lay["shared_up"] = PM.ParamInfo((D, S), P(None, TP))
        lay["shared_down"] = PM.ParamInfo((S, D), P(TP, None))
    return lay


class DecoderLM:
    """Dense / MoE / MLA / VLM decoder with a registry-facing API."""

    def __init__(self, cfg: ModelConfig, *, model_axis: int = 16, mesh=None):
        self.cfg = cfg
        self.model_axis = model_axis
        self.mesh = mesh

    # -------------------------------------------------------------- layout
    def layer_layout(self, *, moe: bool) -> dict:
        cfg = self.cfg
        lay = {"attn": _attn_layout(cfg)}
        if moe:
            lay["mlp"] = _moe_layout(cfg, self.model_axis)
        else:
            d_ff = cfg.moe.first_dense_ff if (cfg.moe and cfg.moe.first_dense) else cfg.d_ff
            lay["mlp"] = _mlp_layout(cfg, d_ff)
        return lay

    def layout(self) -> dict:
        cfg = self.cfg
        emb_spec, head_spec = _vocab_specs(cfg.vocab, cfg.d_model, self.model_axis)
        lay: dict[str, Any] = {
            "embed": PM.ParamInfo((cfg.vocab, cfg.d_model), emb_spec, scale=0.02),
            "final_ln": PM.ParamInfo((cfg.d_model,), P(None), "ones"),
        }
        if not cfg.tie_embeddings:
            lay["lm_head"] = PM.ParamInfo((cfg.d_model, cfg.vocab), head_spec, scale=0.02)
        is_moe = cfg.moe is not None
        n = cfg.n_layers
        if is_moe and cfg.moe.first_dense:
            lay["layer0"] = self.layer_layout(moe=False)
            lay["layers"] = PM.stack(n - 1, self.layer_layout(moe=True))
        else:
            lay["layers"] = PM.stack(n, self.layer_layout(moe=is_moe))
        return lay

    # ------------------------------------------------------------ sharding
    def _shard(self, x, *spec):
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, P(*spec))
        )

    def _dp(self):
        if self.mesh is None:
            return DP
        return tuple(a for a in DP if a in self.mesh.axis_names) or None

    # ------------------------------------------------------------- forward
    def _attention(self, p, x, positions, *, window: int, pairs: bool):
        cfg = self.cfg
        B, S, D = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        if cfg.mla is not None:
            m = cfg.mla
            qk = m.qk_nope_dim + m.qk_rope_dim
            q = (h @ p["wq"]).reshape(B, S, H, qk).transpose(0, 2, 1, 3)
            q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
            dkv = h @ p["w_dkv"]
            c_kv = rms_norm(dkv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
            k_rope = dkv[..., m.kv_lora_rank :][:, None]                   # (B,1,S,r)
            k_rope = rope(k_rope, positions, cfg.rope_theta)
            q_rope = rope(q_rope, positions, cfg.rope_theta)
            k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, m.qk_nope_dim).transpose(0, 2, 1, 3)
            v = (c_kv @ p["w_uv"]).reshape(B, S, H, m.v_head_dim).transpose(0, 2, 1, 3)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, H, S, m.qk_rope_dim))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            out = attention(
                q, k, v, causal=True, window=window, mesh=self.mesh,
                q_block=cfg.q_block, kv_block=cfg.kv_block, pairs=pairs,
                mask_mode=cfg.mask_mode,
            )
            out = out.transpose(0, 2, 1, 3).reshape(B, S, H * m.v_head_dim)
            return x + out @ p["wo"]
        q = h @ p["wq"] + (p["bq"] if cfg.qkv_bias else 0)
        k = h @ p["wk"] + (p["bk"] if cfg.qkv_bias else 0)
        v = h @ p["wv"] + (p["bv"] if cfg.qkv_bias else 0)
        q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.rope_theta:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        out = attention(
            q, k, v, causal=True, window=window, mesh=self.mesh,
            q_block=cfg.q_block, kv_block=cfg.kv_block, pairs=pairs,
            mask_mode=cfg.mask_mode,
        )
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        return x + out @ p["wo"]

    def _mlp(self, p, x, *, moe: bool):
        cfg = self.cfg
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        if not moe:
            return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), 0.0
        B, S, D = h.shape
        shared = (
            (p["shared_gate"], p["shared_up"], p["shared_down"])
            if "shared_gate" in p
            else None
        )
        shard_fn = None
        if cfg.moe_token_shard and self.mesh is not None:
            # keep dispatch capacity data-sharded.  Measured §Perf: a big win
            # for tensor-parallel experts (mixtral: GSPMD otherwise
            # replicates the buffer), a REGRESSION for expert-parallel
            # layouts (deepseek) where forcing either C- or E-major sharding
            # fights the partitioner — EP dispatch wants explicit shard_map
            # all_to_all (recorded future work); leave the flag off there.
            shard_fn = lambda t: self._shard(t, None, self._dp(), None)
        y, aux = moe_block(
            h.reshape(B * S, D),
            p["router"],
            p["w_gate"],
            p["w_up"],
            p["w_down"],
            top_k=cfg.moe.top_k,
            capacity_factor=cfg.moe.capacity_factor,
            shared=shared,
            shard_fn=shard_fn,
        )
        return x + y.reshape(B, S, D), aux

    def _layer(self, p, x, positions, *, moe: bool):
        cfg = self.cfg
        window = cfg.sliding_window
        with jax.named_scope("attention"):
            x = self._attention(p["attn"], x, positions, window=window, pairs=cfg.causal_pairs)
        with jax.named_scope("mlp"):
            x, aux = self._mlp(p["mlp"], x, moe=moe)
        x = self._shard(x, self._dp(), None, None)
        return x, aux

    def _remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        if self.cfg.remat == "full":
            return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
        cp = jax.checkpoint_policies
        # the attention kernel's residuals too: its forward would otherwise
        # rerun in the backward pass (about 12 ms a step at 2 x 2048, PERF.md)
        return jax.checkpoint(fn, policy=cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, cp.save_only_these_names(FLASH_RESIDUALS)))

    def backbone(self, params, x, positions):
        """Embedding-space input -> final hidden states (+ MoE aux loss)."""
        cfg = self.cfg
        is_moe = cfg.moe is not None
        aux_total = 0.0
        if "layer0" in params:
            x, aux = self._remat(partial(self._layer, moe=False))(params["layer0"], x, positions)
            aux_total += aux

        body = self._remat(partial(self._layer, moe=is_moe))

        def scan_step(carry, layer_p):
            h, aux = carry
            h, a = body(layer_p, h, positions)
            return (h, aux + a), None

        (x, aux_total), _ = lax.scan(scan_step, (x, aux_total), params["layers"])
        return rms_norm(x, params["final_ln"], cfg.norm_eps), aux_total

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"][tokens].astype(jnp.dtype(self.cfg.dtype))

    def unembed(self, params, h):
        if self.cfg.tie_embeddings:
            return h @ params["embed"].T
        return h @ params["lm_head"]

    # ---------------------------------------------------------------- train
    def loss(self, params, batch):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        x = self.embed(params, tokens)
        n_img = 0
        if cfg.vlm is not None:
            img = batch["img_emb"].astype(x.dtype)
            x = jnp.concatenate([img, x], axis=1)
            n_img = img.shape[1]
        x = self._shard(x, self._dp(), None, None)
        B, S, _ = x.shape
        positions = jnp.arange(S)
        h, aux = self.backbone(params, x, positions)
        if n_img:
            h = h[:, n_img:]
        with jax.named_scope("logits_loss"):
            logits = self.unembed(params, h).astype(jnp.float32)
            logits = self._shard(logits, self._dp(), None, TP)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
            nll = (lse - gold).mean()
        total = nll + 0.01 * aux
        return total, {"nll": nll, "aux": aux}

    # ------------------------------------------------------------ serving
    def prefill(self, params, batch):
        """Full-sequence forward returning last-position logits.

        (The serving engine's cache is produced by ``decode``-compatible
        projections; prefill here returns hidden states for scoring.)
        """
        tokens = batch["tokens"]
        x = self.embed(params, tokens)
        if self.cfg.vlm is not None:
            x = jnp.concatenate([batch["img_emb"].astype(x.dtype), x], axis=1)
        x = self._shard(x, self._dp(), None, None)
        positions = jnp.arange(x.shape[1])
        h, _ = self.backbone(params, x, positions)
        return self.unembed(params, h[:, -1:]).astype(jnp.float32)

    # -------------------------------------------------------------- decode
    def cache_layout(self, batch: int, seq: int) -> dict:
        cfg = self.cfg
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        n = cfg.n_layers
        window = cfg.sliding_window
        S_eff = min(seq, window) if window else seq
        if cfg.mla is not None:
            m = cfg.mla
            per = {
                "c_kv": PM.ParamInfo(
                    (batch, seq, m.kv_lora_rank), P(self._dp(), TP, None), "zeros"
                ),
                "k_rope": PM.ParamInfo(
                    (batch, seq, m.qk_rope_dim), P(self._dp(), TP, None), "zeros"
                ),
            }
        else:
            per = {
                "k": PM.ParamInfo((batch, Hkv, S_eff, hd), P(self._dp(), None, TP, None), "zeros"),
                "v": PM.ParamInfo((batch, Hkv, S_eff, hd), P(self._dp(), None, TP, None), "zeros"),
            }
        if cfg.moe is not None and cfg.moe.first_dense:
            return {"layer0": per, "layers": PM.stack(n - 1, per)}
        return {"layers": PM.stack(n, per)}

    def _decode_attn(self, p, x, cache, index):
        """One-token attention against the cache; returns (out, new cache)."""
        cfg = self.cfg
        B = x.shape[0]
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        pos = jnp.asarray([index])
        if cfg.mla is not None:
            m = cfg.mla
            qk = m.qk_nope_dim + m.qk_rope_dim
            q = (h @ p["wq"]).reshape(B, 1, H, qk).transpose(0, 2, 1, 3)
            q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
            q_rope = rope(q_rope, pos, cfg.rope_theta)
            dkv = h @ p["w_dkv"]
            c_new = rms_norm(dkv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
            kr_new = rope(dkv[..., m.kv_lora_rank :][:, None], pos, cfg.rope_theta)[:, 0]
            c_kv = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new, index, axis=1)
            k_rope = lax.dynamic_update_slice_in_dim(cache["k_rope"], kr_new, index, axis=1)
            # absorbed decode: score against the latent directly
            w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
            q_eff = jnp.einsum("bhqd,rhd->bhqr", q_nope, w_uk)             # (B,H,1,r)
            s = jnp.einsum("bhqr,bsr->bhqs", q_eff, c_kv, preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bhqd,bsd->bhqs", q_rope, k_rope, preferred_element_type=jnp.float32)
            s = s / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
            mask = jnp.arange(c_kv.shape[1]) <= index
            s = jnp.where(mask[None, None, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqs,bsr->bhqr", pr.astype(c_kv.dtype), c_kv)  # (B,H,1,r)
            w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
            out = jnp.einsum("bhqr,rhd->bhqd", ctx, w_uv)
            out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * m.v_head_dim)
            return x + out @ p["wo"], {"c_kv": c_kv, "k_rope": k_rope}
        q = h @ p["wq"] + (p["bq"] if cfg.qkv_bias else 0)
        k = h @ p["wk"] + (p["bk"] if cfg.qkv_bias else 0)
        v = h @ p["wv"] + (p["bv"] if cfg.qkv_bias else 0)
        q = q.reshape(B, 1, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, 1, Hkv, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, 1, Hkv, hd).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.rope_theta:
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)
        S_cache = cache["k"].shape[2]
        window = cfg.sliding_window
        slot = index % S_cache if window else index
        kc = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=2)
        vc = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=2)
        if window:
            # ring buffer: all S_eff slots valid once warm; positions rotate
            valid = jnp.minimum(index + 1, S_cache)
            out = decode_attention(q, kc, vc, valid, window=0)
        else:
            out = decode_attention(q, kc, vc, index + 1, window=0)
        out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * hd)
        return x + out @ p["wo"], {"k": kc, "v": vc}

    def decode_step(self, params, batch):
        """serve_step: one new token given a warm cache.

        batch: tokens (B,1) int32, cache pytree, index scalar int32.
        """
        cfg = self.cfg
        tokens, cache, index = batch["tokens"], batch["cache"], batch["index"]
        x = self.embed(params, tokens)
        x = self._shard(x, self._dp(), None, None)
        is_moe = cfg.moe is not None
        new_cache: dict[str, Any] = {}
        if "layer0" in params:
            x, c0 = self._decode_attn(params["layer0"]["attn"], x, cache["layer0"], index)
            x, _ = self._mlp(params["layer0"]["mlp"], x, moe=False)
            new_cache["layer0"] = c0

        def scan_step(h, pc):
            layer_p, layer_c = pc
            h, c = self._decode_attn(layer_p["attn"], h, layer_c, index)
            h, _ = self._mlp(layer_p["mlp"], h, moe=is_moe)
            return h, c

        x, stacked = lax.scan(scan_step, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = stacked
        h = rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = self.unembed(params, h).astype(jnp.float32)
        return logits, new_cache
