"""Decoder-only LM assembly covering the dense / moe / hybrid (zamba2) /
ssm (rwkv6) / vlm families.

Layer stack runs as a two-level lax.scan over stacked parameters
(groups x layers-per-group) with configurable activation checkpointing:
the outer scan saves one residual per *group*, the inner scan is rematted,
giving O(L/G + G) live residuals instead of O(L) — the knob that makes
mistral-large-123b train_4k fit (DESIGN.md §5).

Decode paths carry per-layer caches stacked on a leading layer axis and
advance them through the same scan machinery (no remat).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import common, mamba2, mlp, rwkv6
from repro.models.attention import AttnSpec
from repro.models.mamba2 import Mamba2Spec
from repro.models.mlp import MoESpec
from repro.models.rwkv6 import Rwkv6Spec
from repro.parallel.sharding import constrain

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# Specs from config
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig, *, causal=True, sliding=False) -> AttnSpec:
    return AttnSpec(
        d_model=cfg.d_model, head_dim=cfg.head_dim_, plan=cfg.head_plan(),
        qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta, causal=causal,
        sliding_window=cfg.sliding_window if sliding else 0)


def moe_spec(cfg: ModelConfig) -> MoESpec:
    return MoESpec(d_model=cfg.d_model, d_ff=cfg.d_ff,
                   n_experts=cfg.n_experts, k=cfg.experts_per_token)


def mamba_spec(cfg: ModelConfig) -> Mamba2Spec:
    return Mamba2Spec(d_model=cfg.d_model, d_state=cfg.ssm_state)


def rwkv_spec(cfg: ModelConfig) -> Rwkv6Spec:
    return Rwkv6Spec(d_model=cfg.d_model, d_ff=cfg.d_ff)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    if cfg.family in ("dense", "vlm"):
        return {"ln1_w": jnp.ones((cfg.d_model,), dtype),
                "attn": attn.init_attention(k1, attn_spec(cfg), dtype),
                "ln2_w": jnp.ones((cfg.d_model,), dtype),
                "mlp": mlp.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)}
    if cfg.family == "moe":
        return {"ln1_w": jnp.ones((cfg.d_model,), dtype),
                "attn": attn.init_attention(k1, attn_spec(cfg), dtype),
                "ln2_w": jnp.ones((cfg.d_model,), dtype),
                "moe": mlp.init_moe(k2, moe_spec(cfg), dtype)}
    if cfg.family == "hybrid":
        return {"ln1_w": jnp.ones((cfg.d_model,), dtype),
                "mamba": mamba2.init_mamba2(k1, mamba_spec(cfg), dtype)}
    if cfg.family == "ssm":
        return {"ln1_w": jnp.ones((cfg.d_model,), dtype),
                "ln1_b": jnp.zeros((cfg.d_model,), dtype),
                "rwkv_tm": rwkv6.init_rwkv6(k1, rwkv_spec(cfg), dtype),
                "ln2_w": jnp.ones((cfg.d_model,), dtype),
                "ln2_b": jnp.zeros((cfg.d_model,), dtype)}
    raise ValueError(f"family {cfg.family} not handled by lm.py")


def init_lm(key, cfg: ModelConfig):
    dtype = common.default_dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 4)
    Vp, D = cfg.vocab_padded, cfg.d_model
    params: dict[str, Any] = {
        "embed": common.embed_init(keys[0], (Vp, D), dtype),
        "final_norm_w": jnp.ones((D,), dtype),
        "lm_head": common.dense_init(keys[1], (D, Vp), D, dtype),
        "blocks": jax.vmap(lambda k: _init_block(k, cfg, dtype))(
            jnp.stack(keys[4:4 + cfg.n_layers])),
    }
    if cfg.family == "hybrid":
        # zamba2: one *shared* attention+mlp block reused every attn_every
        # mamba layers (arXiv:2411.15242)
        params["shared"] = {
            "ln1_w": jnp.ones((D,), dtype),
            "attn": attn.init_attention(keys[2], attn_spec(cfg, sliding=True), dtype),
            "ln2_w": jnp.ones((D,), dtype),
            "mlp": mlp.init_swiglu(keys[3], D, cfg.d_ff, dtype),
        }
    if cfg.family == "vlm":
        params["img_proj"] = common.dense_init(keys[2], (D, D), D, dtype)
    return params


# ---------------------------------------------------------------------------
# Per-layer forward (train/prefill)
# ---------------------------------------------------------------------------

def _apply_layer(cfg: ModelConfig, p, shared, x, positions, aux, layer_idx):
    if cfg.family in ("dense", "vlm", "moe"):
        h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
        a, _ = attn.attention_full(p["attn"], h, attn_spec(cfg), positions)
        x = x + a
        x = constrain(x, "batch", "seq", "embed")
        h = common.rms_norm(x, p["ln2_w"], cfg.norm_eps)
        if cfg.family == "moe":
            m, am = mlp.moe_apply(p["moe"], h, moe_spec(cfg))
            aux = aux + am["moe_aux"]
        else:
            m = mlp.swiglu(p["mlp"], h)
        x = x + m
    elif cfg.family == "hybrid":
        h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
        m, _ = mamba2.mamba2_forward(p["mamba"], h, mamba_spec(cfg))
        x = x + m

        def with_shared(x):
            h = common.rms_norm(x, shared["ln1_w"], cfg.norm_eps)
            a, _ = attn.attention_full(shared["attn"], h,
                                       attn_spec(cfg, sliding=True), positions)
            x = x + a
            h = common.rms_norm(x, shared["ln2_w"], cfg.norm_eps)
            return x + mlp.swiglu(shared["mlp"], h)

        x = jax.lax.cond((layer_idx + 1) % cfg.attn_every == 0,
                         with_shared, lambda y: y, x)
    elif cfg.family == "ssm":
        h = common.layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        a, _ = rwkv6.rwkv6_time_mix(p["rwkv_tm"], h, rwkv_spec(cfg))
        x = x + a
        h = common.layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
        c, _ = rwkv6.rwkv6_channel_mix(p["rwkv_tm"], h)
        x = x + c
    else:
        raise ValueError(cfg.family)
    x = constrain(x, "batch", "seq", "embed")
    return x, aux


def _run_blocks(params, x, cfg: ModelConfig, positions, *, remat: str = "full"):
    """Two-level scan over stacked layers (see module docstring)."""
    L, G = cfg.n_layers, cfg.remat_group_
    n_groups = L // G
    shared = params.get("shared")
    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups, G) + a.shape[1:]), params["blocks"])
    idx = jnp.arange(L, dtype=jnp.int32).reshape(n_groups, G)

    def layer_body(carry, xs):
        x, aux = carry
        p, i = xs
        x, aux = _apply_layer(cfg, p, shared, x, positions, aux, i)
        return (x, aux), None

    if remat == "full":
        layer_body = jax.checkpoint(layer_body)

    def group_body(carry, xs):
        new_carry, _ = jax.lax.scan(layer_body, carry, xs)
        return new_carry, None

    if remat in ("full", "group"):
        group_body = jax.checkpoint(group_body)

    (x, aux), _ = jax.lax.scan(group_body, (x, jnp.zeros((), jnp.float32)),
                               (stacked, idx))
    return x, aux


# ---------------------------------------------------------------------------
# Full forward + loss
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, "batch", "seq", "embed")


def logits_from(params, x, cfg: ModelConfig):
    x = common.rms_norm(x, params["final_norm_w"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    logits = constrain(logits, "batch", "seq", "vocab")
    # mask padded vocab slots out of the softmax
    if cfg.vocab_padded != cfg.vocab_size:
        neg = jnp.float32(-1e9).astype(logits.dtype)
        mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
        logits = jnp.where(mask[None, None, :], logits, neg)
    return logits


def forward_train(params, batch, cfg: ModelConfig, *, remat: str = "full"):
    """batch: {'tokens': [B,T] i32, 'labels': [B,T] i32 (-1 = masked),
    optional 'img_embeds': [B,Ti,D]} -> (loss, metrics)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg)
    labels = batch["labels"]
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = jnp.einsum("btd,de->bte", batch["img_embeds"].astype(x.dtype),
                         params["img_proj"])
        x = jnp.concatenate([img, x], axis=1)
        labels = jnp.concatenate(
            [jnp.full(img.shape[:2], -1, labels.dtype), labels], axis=1)
    B, T = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x, aux = _run_blocks(params, x, cfg, positions, remat=remat)
    logits = logits_from(params, x, cfg)
    loss = common.softmax_cross_entropy(logits, labels)
    total = loss + MOE_AUX_COEF * aux / max(cfg.n_layers, 1)
    return total, {"ce_loss": loss, "moe_aux": aux}


def forward_logits(params, tokens, cfg: ModelConfig):
    """No-cache forward over a whole token sequence: tokens [B,T] -> logits
    [B,T,V]. The reference that prefill followed by cached decode steps must
    reproduce."""
    x = embed_tokens(params, tokens, cfg)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x, _ = _run_blocks(params, x, cfg, positions, remat="none")
    return logits_from(params, x, cfg)


# ---------------------------------------------------------------------------
# Decode: caches + single-token step
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked per-layer cache matching the family."""
    dtype = common.default_dtype(cfg.dtype)
    L = cfg.n_layers

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), tree)

    if cfg.family in ("dense", "vlm", "moe"):
        return stack(attn.init_kv_cache(batch, max_len, attn_spec(cfg), dtype))
    if cfg.family == "hybrid":
        n_occ = cfg.n_layers // cfg.attn_every
        mamba_state = mamba2.init_mamba2_state(batch, mamba_spec(cfg), dtype)
        mamba_stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), mamba_state)
        kv = attn.init_kv_cache(batch, max_len, attn_spec(cfg, sliding=True), dtype)
        kv_stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_occ,) + a.shape).copy(), kv)
        return {"mamba": mamba_stacked, "shared_kv": kv_stacked}
    if cfg.family == "ssm":
        st = rwkv6.init_rwkv6_state(batch, rwkv_spec(cfg), dtype)
        return stack({"wkv": st[0], "tm_last": st[1], "cm_last": st[2]})
    raise ValueError(cfg.family)


def decode_step(params, cache, tokens, cur_index, cfg: ModelConfig):
    """One serving step: tokens [B,1] -> (logits [B,1,V], new_cache)."""
    x = embed_tokens(params, tokens, cfg)
    B = x.shape[0]
    shared = params.get("shared")

    if cfg.family in ("dense", "vlm", "moe"):
        def body(x, xs):
            p, c = xs
            h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
            a, c = attn.attention_decode(p["attn"], h, c, cur_index, attn_spec(cfg))
            x = x + a
            h = common.rms_norm(x, p["ln2_w"], cfg.norm_eps)
            if cfg.family == "moe":
                m, _ = mlp.moe_apply(p["moe"], h, moe_spec(cfg))
            else:
                m = mlp.swiglu(p["mlp"], h)
            return x + m, c

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))

    elif cfg.family == "hybrid":
        def body(carry, xs):
            x, kv_all = carry
            p, ms, i = xs
            h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
            m, ms = mamba2.mamba2_decode(p["mamba"], h, ms, mamba_spec(cfg))
            x = x + m

            occ = (i + 1) // cfg.attn_every - 1

            def with_shared(op):
                x, kv_all = op
                c = jax.tree_util.tree_map(lambda a: a[occ], kv_all)
                h = common.rms_norm(x, shared["ln1_w"], cfg.norm_eps)
                a, c = attn.attention_decode(shared["attn"], h, c, cur_index,
                                             attn_spec(cfg, sliding=True))
                x = x + a
                h = common.rms_norm(x, shared["ln2_w"], cfg.norm_eps)
                x = x + mlp.swiglu(shared["mlp"], h)
                kv_all = jax.tree_util.tree_map(
                    lambda buf, v: jax.lax.dynamic_update_index_in_dim(buf, v, occ, 0),
                    kv_all, c)
                return (x, kv_all)

            x, kv_all = jax.lax.cond((i + 1) % cfg.attn_every == 0,
                                     with_shared, lambda op: op, (x, kv_all))
            return (x, kv_all), ms

        idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, kv_new), mamba_new = jax.lax.scan(
            body, (x, cache["shared_kv"]), (params["blocks"], cache["mamba"], idx))
        new_cache = {"mamba": mamba_new, "shared_kv": kv_new}

    elif cfg.family == "ssm":
        def body(x, xs):
            p, c = xs
            h = common.layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
            a, (wkv, tm_last) = rwkv6.rwkv6_time_mix(
                p["rwkv_tm"], h, rwkv_spec(cfg),
                init_state=c["wkv"], last_x=c["tm_last"])
            x = x + a
            h = common.layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
            cmix, cm_last = rwkv6.rwkv6_channel_mix(p["rwkv_tm"], h,
                                                    last_x=c["cm_last"])
            x = x + cmix
            return x, {"wkv": wkv, "tm_last": tm_last, "cm_last": cm_last}

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    else:
        raise ValueError(cfg.family)

    return logits_from(params, x, cfg), new_cache


def prefill(params, tokens, cfg: ModelConfig, max_len: int):
    """Prefill pass: run the full prompt, return (last_logits, cache, T).
    Uses the train forward plus per-layer cache collection (no remat)."""
    B, T = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    shared = params.get("shared")
    dtype = common.default_dtype(cfg.dtype)

    if cfg.family in ("dense", "vlm", "moe"):
        spec = attn_spec(cfg)

        def body(x, p):
            h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
            a, (k, v) = attn.attention_full(p["attn"], h, spec, positions)
            x = x + a
            h = common.rms_norm(x, p["ln2_w"], cfg.norm_eps)
            if cfg.family == "moe":
                m, _ = mlp.moe_apply(p["moe"], h, moe_spec(cfg))
            else:
                m = mlp.swiglu(p["mlp"], h)
            # write prompt K/V into a max_len cache buffer
            c = attn.init_kv_cache(B, max_len, spec, dtype)
            c["k"] = jax.lax.dynamic_update_slice(
                c["k"], k.astype(dtype), (0, 0, 0, 0))
            c["v"] = jax.lax.dynamic_update_slice(
                c["v"], v.astype(dtype), (0, 0, 0, 0))
            return x + m, c

        x, cache = jax.lax.scan(body, x, params["blocks"])
    elif cfg.family == "ssm":
        def body(x, p):
            h = common.layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
            a, (wkv, tm_last) = rwkv6.rwkv6_time_mix(p["rwkv_tm"], h, rwkv_spec(cfg))
            x = x + a
            h = common.layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
            cmix, cm_last = rwkv6.rwkv6_channel_mix(p["rwkv_tm"], h)
            x = x + cmix
            return x, {"wkv": wkv, "tm_last": tm_last, "cm_last": cm_last}

        x, cache = jax.lax.scan(body, x, params["blocks"])
    elif cfg.family == "hybrid":
        # interleaved mamba + shared attn: unrolled python loop (38 small
        # layers; prefill has no remat so HLO stays manageable)
        spec = attn_spec(cfg, sliding=True)
        mamba_states, kv_caches = [], []
        for i in range(cfg.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            h = common.rms_norm(x, p["ln1_w"], cfg.norm_eps)
            m, ms = mamba2.mamba2_forward(p["mamba"], h, mamba_spec(cfg))
            x = x + m
            mamba_states.append(ms)
            if (i + 1) % cfg.attn_every == 0:
                h = common.rms_norm(x, shared["ln1_w"], cfg.norm_eps)
                a, (k, v) = attn.attention_full(shared["attn"], h, spec, positions)
                x = x + a
                h = common.rms_norm(x, shared["ln2_w"], cfg.norm_eps)
                x = x + mlp.swiglu(shared["mlp"], h)
                c = attn.init_kv_cache(B, max_len, spec, dtype)
                W = c["k"].shape[1]
                if T <= W:
                    c["k"] = jax.lax.dynamic_update_slice(
                        c["k"], k.astype(dtype), (0, 0, 0, 0))
                    c["v"] = jax.lax.dynamic_update_slice(
                        c["v"], v.astype(dtype), (0, 0, 0, 0))
                else:
                    # rolling window: position p lives at slot p % W
                    c["k"] = jnp.roll(k[:, -W:].astype(dtype), T % W, axis=1)
                    c["v"] = jnp.roll(v[:, -W:].astype(dtype), T % W, axis=1)
                kv_caches.append(c)

        def stack_trees(trees):
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)

        cache = {"mamba": stack_trees(mamba_states),
                 "shared_kv": stack_trees(kv_caches)}
    else:
        raise ValueError(cfg.family)

    logits = logits_from(params, x[:, -1:], cfg)
    return logits, cache, T
