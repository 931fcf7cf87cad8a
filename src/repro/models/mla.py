"""Multi-head latent attention (DeepSeek-V3 MLA, without a query LoRA).

Per token: q = x W_q, split per head into a no-rope part (qk_nope) and a
rope part (qk_rope); [c_kv | k_rope] = x W_kv_a, with c_kv RMS-normed
(kv_lora_rank wide) and k_rope rotated and shared by all heads. W_kv_b maps
c_kv to each head's no-rope key (K half) and value (V half). The softmax
scale is 1/sqrt(qk_nope + qk_rope).

Prefill runs the expanded form: per-head K and V through W_kv_b, causal
flash attention with V zero-padded to the query-key width, then W_o. Decode
runs the absorbed form over a latent cache of c_kv [B,S,R] and k_rope
[B,S,Dr] per layer, stacked over the layers: each head's no-rope query is
folded through W_kv_b's K half into the latent space, attends with its rope
part over [c_kv | k_rope] with c_kv as the values
(kernels/mla_decode_attention.py, which reads its layer of the stack in
place), and the latent output maps back through W_kv_b's V half and W_o.

Rotary embeddings use the halves convention of `common.apply_rotary`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return float(self.qk_head_dim) ** -0.5


def init_mla(key, spec: MlaSpec, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    D, H, R = spec.d_model, spec.n_heads, spec.kv_lora_rank
    N, Dr, Dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    return {
        "wq": common.dense_init(ks[0], (D, H, N + Dr), D, dtype),
        "wkv_a": common.dense_init(ks[1], (D, R + Dr), D, dtype),
        "kv_norm_w": jnp.ones((R,), dtype),
        "wk_b": common.dense_init(ks[2], (R, H, N), R, dtype),
        "wv_b": common.dense_init(ks[3], (R, H, Dv), R, dtype),
        "wo": common.dense_init(ks[4], (H, Dv, D), H * Dv, dtype),
    }


def init_latent_cache(batch: int, max_len: int, spec: MlaSpec,
                      dtype=jnp.bfloat16):
    return {"c_kv": jnp.zeros((batch, max_len, spec.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, spec.qk_rope_head_dim),
                                dtype)}


def _project(params, x, spec: MlaSpec, positions):
    """x [B,T,D] -> (q_nope [B,T,H,N], q_rope [B,T,H,Dr], c_kv [B,T,R],
    k_rope [B,T,Dr]), rope applied at `positions` [B,T]."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    kv = jnp.einsum("btd,dc->btc", x, params["wkv_a"])
    R, N = spec.kv_lora_rank, spec.qk_nope_head_dim
    c_kv = common.rms_norm(kv[..., :R], params["kv_norm_w"], spec.norm_eps)
    sin, cos = common.rotary_angles(positions, spec.qk_rope_head_dim,
                                    spec.rope_theta)
    q_rope = common.apply_rotary(q[..., N:], sin, cos)
    k_rope = common.apply_rotary(kv[..., None, R:], sin, cos)[..., 0, :]
    return q[..., :N], q_rope, c_kv, k_rope


def mla_full(params, x, spec: MlaSpec, positions):
    """Prefill / training attention, expanded form. x [B,T,D] -> ([B,T,D],
    (c_kv [B,T,R], k_rope [B,T,Dr]))."""
    q_nope, q_rope, c_kv, k_rope = _project(params, x, spec, positions)
    H = spec.n_heads
    k_nope = jnp.einsum("btc,chk->bthk", c_kv, params["wk_b"])
    v = jnp.einsum("btc,chk->bthk", c_kv, params["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_rope.shape[:2] + (H,) + k_rope.shape[2:])],
        -1)
    # flash attention takes one width for q, k and v: V rides zero-padded
    # to the query-key width (the kernel's 1/sqrt(width) is MLA's scale)
    Dv = spec.v_head_dim
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, spec.qk_head_dim - Dv)))

    from repro.kernels import ops as kops
    out = kops.flash_attention(q, k, v, causal=True)[..., :Dv]
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, (c_kv, k_rope)


def mla_decode(params, x, cache, layer, cur_index, spec: MlaSpec):
    """Single-token decode, absorbed form. x [B,1,D]; cache holds the
    stacked c_kv [L,B,S,R] and k_rope [L,B,S,Dr], of which this is layer
    `layer` [] int32; cur_index [] int32, the number of positions already
    cached. Returns (y [B,1,D], the cache with this layer's new position
    written)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), cur_index, jnp.int32)
    q_nope, q_rope, c_new, kr_new = _project(params, x, spec, positions)
    at = (layer, 0, cur_index, 0)
    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new[None].astype(cache["c_kv"].dtype), at)
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr_new[None].astype(cache["k_rope"].dtype), at)
    q_lat = jnp.einsum("bhk,chk->bhc", q_nope[:, 0], params["wk_b"])
    lengths = jnp.full((B,), cur_index + 1, jnp.int32)

    from repro.kernels import ops as kops
    o_lat = kops.mla_decode_attention(q_lat, q_rope[:, 0], c_kv, k_rope,
                                      layer, lengths, scale=spec.scale)
    o = jnp.einsum("bhc,chk->bhk", o_lat.astype(x.dtype), params["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", o, params["wo"])[:, None]
    return y, {"c_kv": c_kv, "k_rope": k_rope}
