"""Feed-forward layers: SwiGLU (LLaMA-family), GELU (whisper), the MoE
layer (top-k routing, capacity-based dispatch, expert-TP sharding), and
the held-expert MoE layer of one expert-parallel chip's share (sigmoid
routing over all experts, the held experts' part computed for every routed
pair, shared experts)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": common.dense_init(k1, (d_model, d_ff), d_model, dtype),
        "w_in": common.dense_init(k2, (d_model, d_ff), d_model, dtype),
        "w_out": common.dense_init(k3, (d_ff, d_model), d_ff, dtype),
    }


def swiglu(params, x):
    g = jnp.einsum("btd,df->btf", x, params["w_gate"])
    h = jnp.einsum("btd,df->btf", x, params["w_in"])
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    return jnp.einsum("btf,fd->btd", act, params["w_out"])


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_in": common.dense_init(k1, (d_model, d_ff), d_model, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": common.dense_init(k2, (d_ff, d_model), d_ff, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x):
    h = jnp.einsum("btd,df->btf", x, params["w_in"]) + params["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("btf,fd->btd", h, params["w_out"]) + params["b_out"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int           # per-expert hidden size
    n_experts: int
    k: int              # experts per token
    capacity_factor: float = 2.0


def init_moe(key, spec: MoESpec, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, D, F = spec.n_experts, spec.d_model, spec.d_ff
    return {
        "router": common.dense_init(k1, (D, E), D, jnp.float32),
        "w_gate": common.dense_init(k2, (E, D, F), D, dtype),
        "w_in": common.dense_init(k3, (E, D, F), D, dtype),
        "w_out": common.dense_init(k4, (E, F, D), F, dtype),
    }


def moe_capacity(n_tokens: int, spec: MoESpec) -> int:
    cap = max(1, int(spec.capacity_factor * n_tokens * spec.k
                     / spec.n_experts))
    # round to 8 for TPU-friendly shapes, but never inflate tiny decode caps
    # (T=1: top-k experts are distinct, so rank-within-expert is always 0
    # and cap=1 suffices — a floor of 8 would cost 8x expert FLOPs)
    return -(-cap // 8) * 8 if cap >= 8 else cap


def moe_apply(params, x, spec: MoESpec):
    """Capacity-based top-k MoE with PER-BATCH-ROW routing.

    x [B,T,D] -> (y [B,T,D], aux).

    Routing ranks (position-within-expert) are computed with a cumsum over T
    *within each batch row only*, never across rows. This keeps the batch
    dim of every intermediate — including the [B, E, cap, D] dispatch
    buffer — shardable over the data axes under SPMD. (§Perf iteration 1:
    the original flat formulation cumsum'd across the whole global batch,
    which forced XLA to replicate a [E, cap_global, D] buffer on every
    device — 53 GB temp and ~20x FLOPs on grok-1-314b train_4k.)

    Tokens over capacity are dropped (the residual path carries them) —
    standard for capacity-based TPU MoE deployments.
    """
    Bsz, T, D = x.shape
    E, K = spec.n_experts, spec.k
    cap = moe_capacity(T, spec)                                # per row

    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)                   # [B,T,K]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # load-balance auxiliary loss (Switch Transformer eq. 4)
    me = jnp.mean(probs, axis=(0, 1))                          # [E]
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # rank of each (t, slot) within its expert, per batch row
    flat_e = idx.reshape(Bsz, T * K)                           # [B,TK]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # [B,TK,E]
    ranks = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.sum(ranks * onehot, axis=-1)                    # [B,TK]
    keep = rank < cap

    # dispatch: per-row scatter into [B, E, cap, D]. vmap over the batch row
    # emits a scatter with *batching dims*, which SPMD partitions along B —
    # a raw 3-index .at[] scatter would replicate the buffer on every device.
    from repro.parallel.sharding import constrain
    xr = jnp.repeat(x, K, axis=1)                              # [B,TK,D]
    safe_rank = jnp.where(keep, rank, 0)
    contrib = jnp.where(keep[..., None], xr, 0).astype(x.dtype)

    def row_scatter(row_x, row_e, row_r):
        return jnp.zeros((E, cap, D), x.dtype).at[row_e, row_r].add(row_x)

    buf = jax.vmap(row_scatter)(contrib, flat_e, safe_rank)    # [B,E,cap,D]
    buf = constrain(buf, "batch", "experts", None, "embed")

    # expert computation (batched SwiGLU over E; F is TP-sharded)
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    h = jnp.einsum("becd,edf->becf", buf, params["w_in"])
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    act = constrain(act, "batch", "experts", None, "ff")
    out = jnp.einsum("becf,efd->becd", act, params["w_out"])
    out = constrain(out, "batch", "experts", None, "embed")

    # combine: per-row gather back and weight by gates
    def row_gather(row_out, row_e, row_r):
        return row_out[row_e, row_r]

    y_slots = jax.vmap(row_gather)(out, flat_e, safe_rank)     # [B,TK,D]
    y_slots = jnp.where(keep[..., None], y_slots, 0)
    w = gate_vals.reshape(Bsz, T * K)[..., None].astype(x.dtype)
    y = jnp.sum((y_slots * w).reshape(Bsz, T, K, D), axis=2)
    return y, {"moe_aux": aux}


# ---------------------------------------------------------------------------
# Held-expert MoE (DeepSeek-V3 routing, one expert-parallel chip's share)
# ---------------------------------------------------------------------------

HELD_TILE = 4096   # (token, held expert) pairs per grouped-matmul pass


@dataclasses.dataclass(frozen=True)
class HeldMoESpec:
    d_model: int
    d_ff: int           # per-expert hidden size
    n_experts: int      # routed experts the router scores
    k: int              # experts per token
    first_held: int     # this chip holds experts [first_held,
    n_held: int         #                          first_held + n_held)
    n_shared: int       # shared experts, run as one SwiGLU of n_shared*d_ff
    scaling: float      # routed_scaling_factor


def init_held_moe(key, spec: HeldMoESpec, dtype=jnp.float32):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    E, H, D, F = spec.n_experts, spec.n_held, spec.d_model, spec.d_ff
    return {
        "router": common.dense_init(k1, (D, E), D, dtype),
        "e_bias": jnp.zeros((E,), jnp.float32),
        "w_gate": common.dense_init(k2, (H, D, F), D, dtype),
        "w_in": common.dense_init(k3, (H, D, F), D, dtype),
        "w_out": common.dense_init(k4, (H, F, D), F, dtype),
        "shared": init_swiglu(k5, D, spec.n_shared * F, dtype),
    }


def route_sigmoid(params, x, spec: HeldMoESpec):
    """x [N,D] -> (experts [N,k] i32, weights [N,k] f32): sigmoid scores of
    all experts in f32; the top k of score + correction bias (the bias
    picks, it does not weigh); each pick weighted by its score over the k
    picks' sum, times the scaling factor."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"].astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + params["e_bias"], spec.k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * spec.scaling
    return idx, w


def held_moe_apply(params, x, spec: HeldMoESpec):
    """x [B,T,D] -> (y [B,T,D], counts): the held experts' part for every
    (token, held expert) pair the router picks, none dropped, plus the
    shared experts. What the other chips' experts would add is left out.

    Few tokens (every pair fits one pass of HELD_TILE, as in a decode
    step): each held expert runs over every row, weighted by the row's
    routing weight to it (zero where not picked), so each held expert's
    weights are read once, in place. Many tokens (a prefill): the pairs are
    sorted by held expert (pairs to experts held elsewhere sort last) and
    run through grouped matmuls (`jax.lax.ragged_dot`) in passes of
    HELD_TILE pairs, as many as the held pairs need. counts:
    `moe_pairs_held`, the pairs routed to held experts, whose part is
    computed, and `moe_experts_hit`, the held experts that got one or
    more."""
    D, K, H = spec.d_model, spec.k, spec.n_held
    x2 = x.reshape(-1, D)
    idx, w = route_sigmoid(params, x2, spec)
    local = idx - spec.first_held               # [N,K]; held: 0 <= local < H
    counts = jnp.sum(jax.nn.one_hot(local, H, dtype=jnp.int32), axis=(0, 1))
    n_pairs = jnp.sum(counts)
    if x2.shape[0] * K <= HELD_TILE:
        gate = jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(local, H))
        g = jnp.einsum("nd,edf->enf", x2, params["w_gate"])
        h = jnp.einsum("nd,edf->enf", x2, params["w_in"])
        act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
        o = jnp.einsum("enf,efd->end", act, params["w_out"])
        y = jnp.einsum("end,ne->nd", o.astype(jnp.float32), gate)
    else:
        y = _held_grouped(params, x2, local.reshape(-1), w.reshape(-1),
                          counts, n_pairs, K, H)
    y = y.astype(x.dtype).reshape(x.shape) + swiglu(params["shared"], x)
    return y, {"moe_pairs_held": n_pairs,
               "moe_experts_hit": jnp.sum(counts > 0).astype(jnp.int32)}


def _held_grouped(params, x2, local, w, counts, n_pairs, K, H):
    """The held experts' part [N,D] f32 of many tokens: their (token, held
    expert) pairs sorted by expert, in passes of HELD_TILE pairs through
    grouped matmuls; `local` [N*K] each pair's held-expert index (out of
    [0, H) for experts held elsewhere), `w` [N*K] its routing weight."""
    rows = HELD_TILE
    order = jnp.argsort(jnp.where((local >= 0) & (local < H), local, H),
                        stable=True)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    # padded so that a pass starting before n_pairs never reads past the end
    tok = jnp.pad(order // K, (0, rows))
    wt = jnp.pad(w[order], (0, rows))

    def one_pass(i, y):
        start = i * rows
        t = jax.lax.dynamic_slice_in_dim(tok, start, rows)
        gs = jnp.maximum(jnp.minimum(ends, start + rows)
                         - jnp.maximum(starts, start), 0)
        xt = x2[t]
        g = jax.lax.ragged_dot(xt, params["w_gate"], gs)
        h = jax.lax.ragged_dot(xt, params["w_in"], gs)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(x2.dtype) * h
        o = jax.lax.ragged_dot(act, params["w_out"], gs).astype(jnp.float32)
        # rows past the pass's groups hold no defined value: masked, not
        # multiplied, so that nothing of them reaches y
        w_t = jax.lax.dynamic_slice_in_dim(wt, start, rows)[:, None]
        mine = (start + jnp.arange(rows) < n_pairs)[:, None]
        return y.at[t].add(jnp.where(mine, o * w_t, 0.0))

    return jax.lax.fori_loop(0, (n_pairs + rows - 1) // rows, one_pass,
                             jnp.zeros(x2.shape, jnp.float32))
