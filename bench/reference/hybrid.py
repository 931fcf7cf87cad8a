"""Plain f32 training reference for a Mamba2 backbone with one shared
attention block (Zamba2-1.2B as this repo builds it).

Forward: token embedding; per layer RMSNorm, Mamba2 mixer (separate
projections to z, x, B, C, dt; depthwise causal convolutions with bias and
SiLU on x, B and C; dt = softplus(dt + dt_bias); A = -exp(A_log); the SSD
recurrence s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t, y_t = C_t s_t + D x_t;
y * SiLU(z) through an RMSNorm; output projection), residual; after every
`attn_every`-th layer the shared block (RMSNorm, causal softmax attention
with rotary embeddings, residual, RMSNorm, SwiGLU, residual); final RMSNorm,
untied head, mean token cross-entropy plus a 1e-4 z-loss on the
log-partition.

Training: AdamW (decoupled weight decay on every tensor of two or more
dimensions in the stacked layout, global gradient clipping), run layer by
layer: each pass keeps one layer's f32 gradients at a time, so weights,
both moments and the activations fit one chip. A step makes two passes,
the first for the global gradient norm, the second to update.

Weights come in `bench.weights.hybrid_weights`' layout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import F32, causal_attention, mm, rms_norm, rope, swiglu

CHUNK = 64          # the SSD recurrence is checkpointed every CHUNK steps
Z_LOSS = 1e-4


def _silu(x):
    return x * jnp.reciprocal(1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _conv(u, w, b):
    """Depthwise causal convolution: u [B, T, C], w [W, C], b [C]."""
    W, T = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(up[:, i:i + T] * w[i].astype(F32) for i in range(W)) + \
        b.astype(F32)


def _ssd(x, dt, A, Bm, Cm, D):
    """x [B, T, H, P], dt [B, T, H], A [H], Bm/Cm [B, T, N] -> y [B, T, H, P]
    by the recurrence, one step at a time."""
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * A)[:, :, None, None] * s + \
            (dtt[:, :, None, None] * bt[:, None, :, None] * xt[:, :, None, :])
        return s, jnp.einsum("bn,bhnp->bhp", ct, s)

    @jax.checkpoint
    def chunk(s, inp):
        return jax.lax.scan(step, s, inp)

    seq = [jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)]
    seq = [a.reshape((T // CHUNK, CHUNK) + a.shape[1:]) for a in seq]
    s0 = jnp.zeros((Bsz, H, N, P), F32)
    _, y = jax.lax.scan(chunk, s0, tuple(seq))
    y = jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)
    return y + D[None, None, :, None] * x


def mamba_layer(x, p, s: dict, quant: bool):
    """x + Mamba2(RMSNorm(x)) for one layer's weights p."""
    eps, P = s["norm_eps"], s["ssm_head_dim"]
    h = rms_norm(x, p["ln"], eps)
    z = mm("btd,de->bte", h, p["w_z"], quant)
    xs = _silu(_conv(mm("btd,de->bte", h, p["w_x"], quant), p["conv_x_w"],
                     p["conv_x_b"]))
    Bm = _silu(_conv(mm("btd,de->bte", h, p["w_B"], quant), p["conv_B_w"],
                     p["conv_B_b"]))
    Cm = _silu(_conv(mm("btd,de->bte", h, p["w_C"], quant), p["conv_C_w"],
                     p["conv_C_b"]))
    dt = _softplus(mm("btd,dh->bth", h, p["w_dt"], quant) + p["dt_bias"])
    Bsz, T, Din = xs.shape
    y = _ssd(xs.reshape(Bsz, T, Din // P, P), dt, -jnp.exp(p["A_log"]),
             Bm, Cm, p["D"].astype(F32)).reshape(Bsz, T, Din)
    y = rms_norm(y * _silu(z), p["norm_w"], eps)
    return x + mm("bte,ed->btd", y, p["w_out"], quant)


def shared_block(x, p, s: dict, quant: bool):
    eps, theta = s["norm_eps"], s["rope_theta"]
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    h = rms_norm(x, p["ln1"], eps)
    q = rope(mm("btd,dhk->bthk", h, p["wq"], quant), pos, theta)
    k = rope(mm("btd,dhk->bthk", h, p["wk"], quant), pos, theta)
    v = mm("btd,dhk->bthk", h, p["wv"], quant)
    x = x + mm("bthk,hkd->btd", causal_attention(q, k, v, quant), p["wo"],
               quant)
    h = rms_norm(x, p["ln2"], eps)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)


def head_loss(x, final_norm, lm_head, labels, s: dict, quant: bool):
    logits = mm("btd,dv->btv", rms_norm(x, final_norm, s["norm_eps"]),
                lm_head, quant)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll + Z_LOSS * lse * lse)


def _block(x, p, shared, s, quant, with_shared):
    x = mamba_layer(x, p, s, quant)
    return shared_block(x, shared, s, quant) if with_shared else x


_STATIC = ("s", "quant", "with_shared")


def _hashable(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_fwd(x, p, shared, *, s, quant, with_shared):
    with jax.default_matmul_precision("highest"):
        return _block(x, p, shared, dict(s), quant, with_shared)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_vjp(x, p, shared, dy, *, s, quant, with_shared):
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(lambda x, p, sh: _block(x, p, sh, dict(s), quant,
                                                  with_shared), x, p, shared)
        return pull(dy)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _head(x, fn, head, labels, *, s, quant):
    with jax.default_matmul_precision("highest"):
        loss, pull = jax.vjp(lambda x, fn, hd: head_loss(
            x, fn, hd, labels, dict(s), quant), x, fn, head)
        return (loss,) + pull(jnp.ones((), F32))


@functools.partial(jax.jit, static_argnums=2)
def _embed_grad(tokens, dx, vocab_rows):
    g = jnp.zeros((vocab_rows, dx.shape[-1]), F32)
    return g.at[tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))


def grads_by_layer(w, tokens, labels, s: dict, quant: bool = False,
                   rows=None):
    """Yields (name, layer or None, f32 gradient) for every leaf of `w`,
    one layer at a time, after yielding ("loss", None, loss). `rows`
    restricts the batch to those rows (the mean is over them)."""
    if rows is not None:
        tokens, labels = tokens[rows], labels[rows]
    sh = _hashable(s)
    L, every = w["layers"]["ln"].shape[0], s["attn_every"]
    x = w["embed"][tokens].astype(F32)
    xs = []
    for i in range(L):
        xs.append(x)
        p = jax.tree_util.tree_map(lambda a: a[i], w["layers"])
        x = _block_fwd(x, p, w["shared"], s=sh, quant=quant,
                       with_shared=(i + 1) % every == 0)
    loss, dx, dfn, dhead = _head(x, w["final_norm"], w["lm_head"], labels,
                                 s=sh, quant=quant)
    yield "loss", None, loss
    yield "final_norm", None, dfn
    yield "lm_head", None, dhead
    dshared = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32),
                                     w["shared"])
    for i in reversed(range(L)):
        p = jax.tree_util.tree_map(lambda a: a[i], w["layers"])
        dx, dp, dsh = _block_vjp(xs[i], p, w["shared"], dx, s=sh,
                                 quant=quant,
                                 with_shared=(i + 1) % every == 0)
        xs[i] = None
        if (i + 1) % every == 0:
            dshared = jax.tree_util.tree_map(jnp.add, dshared, dsh)
        for name, g in dp.items():
            yield f"layers/{name}", i, g
    for name, g in dshared.items():
        yield f"shared/{name}", None, g
    yield "embed", None, _embed_grad(tokens, dx, w["embed"].shape[0])


def leaf(tree, name):
    for part in name.split("/"):
        tree = tree[part]
    return tree


def set_leaf(tree, name, value):
    *path, last = name.split("/")
    for part in path:
        tree = tree[part]
    tree[last] = value


def leaf_names(w) -> list:
    out = []
    for k, v in w.items():
        out += [f"{k}/{n}" for n in v] if isinstance(v, dict) else [k]
    return out


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, i, lr, clip, step, b1, b2, eps, wd):
    """AdamW on p (or on its row i, when i >= 0 and p is stacked)."""
    if i is None:
        sel = lambda a: a
    else:
        sel = lambda a: a[i]
    pf, mf, vf = sel(p).astype(F32), sel(m), sel(v)
    g = g * clip
    mf = b1 * mf + (1 - b1) * g
    vf = b2 * vf + (1 - b2) * g * g
    upd = (mf / (1 - b1 ** step)) / (jnp.sqrt(vf / (1 - b2 ** step)) + eps)
    pn = (pf - lr * (upd + wd * pf)).astype(p.dtype)
    if i is None:
        return pn, mf, vf
    return p.at[i].set(pn), m.at[i].set(mf), v.at[i].set(vf)


def train(w, batches, lrs, s: dict, opt: dict, quant: bool = False,
          rows=None):
    """AdamW steps from weights `w` (a dict of device arrays, updated in
    place: pass a copy you may lose), one per (tokens, labels) in `batches`
    at the rates `lrs`. Returns (losses, first-step gradient norm per
    leaf) and leaves the stepped weights in `w`."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    names = leaf_names(w)
    m = {n: jnp.zeros(leaf(w, n).shape, F32) for n in names}
    v = {n: jnp.zeros(leaf(w, n).shape, F32) for n in names}
    losses, first_norms = [], None
    for t, ((tokens, labels), lr) in enumerate(zip(batches, lrs), start=1):
        sumsq = {n: 0.0 for n in names}
        for name, _, g in grads_by_layer(w, tokens, labels, s, quant, rows):
            if name == "loss":
                losses.append(float(g))
            else:
                sumsq[name] = sumsq[name] + jnp.sum(g * g)
        sumsq = {n: float(x) for n, x in sumsq.items()}
        gnorm = math.sqrt(sum(sumsq.values()))
        if first_norms is None:
            first_norms = {n: math.sqrt(x) for n, x in sumsq.items()}
        clip = min(1.0, opt["grad_clip_norm"] / (gnorm + 1e-9))
        for name, i, g in grads_by_layer(w, tokens, labels, s, quant, rows):
            if name == "loss":
                continue
            p = leaf(w, name)
            decay = wd if p.ndim >= 2 else 0.0
            idx = None if i is None else jnp.int32(i)
            pn, mn, vn = _adam(p, m[name], v[name], g, idx, lr, clip,
                               float(t), b1, b2, eps, decay)
            set_leaf(w, name, pn)
            m[name], v[name] = mn, vn
    return losses, first_norms
