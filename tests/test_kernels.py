"""Per-kernel correctness: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracles in kernels/ref.py (the required assert_allclose gates)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.quant_codec import quantize_int8
from repro.kernels.rwkv6_scan import rwkv6_scan

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("T,Hq,Hkv,Dh", [
    (128, 4, 4, 64),    # MHA
    (256, 4, 2, 64),    # GQA group 2
    (128, 8, 1, 32),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(T, Hq, Hkv, Dh, dtype, causal):
    ks = jax.random.split(KEY, 3)
    B = 2
    q = jax.random.normal(ks[0], (B, T, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, Dh), dtype)
    g = Hq // Hkv
    out = flash_attention(q, k, v, causal=causal, group=g, bq=64, bk=64,
                          interpret=True)
    exp = ref.mha_reference(q, k, v, causal=causal, group=g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_sliding_window():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = flash_attention(q, k, v, causal=True, group=1, sliding_window=64,
                          bq=64, bk=64, interpret=True)
    exp = ref.mha_reference(q, k, v, causal=True, group=1, sliding_window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_grads_match_reference():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, group=2,
                                       bq=64, bk=64, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.mha_reference(q, k, v, causal=True, group=2) ** 2)

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("S,bk", [(512, 256), (1024, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(S, bk, dtype):
    ks = jax.random.split(KEY, 3)
    B, Hq, Hkv, Dh = 2, 4, 2, 64
    q = jax.random.normal(ks[0], (B, 1, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    lengths = jnp.array([S // 3, S], jnp.int32)
    out = decode_attention(q, k, v, lengths, group=2, bk=bk, interpret=True)
    exp = ref.mha_reference(q, k, v, causal=False, group=2, lengths=lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("T,H,P,G,N,chunk", [
    (128, 4, 32, 1, 16, 64),
    (256, 4, 64, 2, 32, 128),
    (64, 2, 16, 2, 16, 64),
])
def test_mamba2_ssd_sweep(T, H, P, G, N, chunk):
    ks = jax.random.split(KEY, 6)
    Bt = 2
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bt, T, G, N))
    Cm = jax.random.normal(ks[4], (Bt, T, G, N))
    D = jax.random.normal(ks[5], (H,))
    y1, s1 = mamba2_ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y2, s2 = ref.mamba2_scan_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=2e-4)


def _mamba2_inputs(Bt, T, H, P, G, N, *, with_state=False, decay=1.0):
    """Random scan inputs; `decay` scales dt."""
    ks = jax.random.split(KEY, 7)
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = decay * jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bt, T, G, N))
    Cm = jax.random.normal(ks[4], (Bt, T, G, N))
    D = jax.random.normal(ks[5], (H,))
    s0 = jax.random.normal(ks[6], (Bt, H, N, P)) if with_state else None
    return (x, dt, A, Bm, Cm, D), s0


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("T", [128, 100])
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("with_state", [False, True])
def test_mamba2_chunked_reference_matches_scan(chunk, T, G, with_state):
    """The chunked (SSD dual) form equals the sequential scan in y and the
    final state, at lengths that tile by the chunk and do not."""
    args, s0 = _mamba2_inputs(2, T, 4, 16, G, 16, with_state=with_state)
    y1, s1 = ref.mamba2_chunked_reference(*args, chunk=chunk, init_state=s0)
    y2, s2 = ref.mamba2_scan_reference(*args, init_state=s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_mamba2_chunked_reference_strong_decay_grads_finite():
    """|A| dt summing to hundreds within a chunk underflows the decays to
    0; the masked segment sum keeps every gradient finite."""
    args, s0 = _mamba2_inputs(1, 100, 2, 16, 1, 16, with_state=True,
                              decay=10.0)
    assert float(jnp.min(jnp.sum(-args[1][:, :64] * args[2], axis=1))) > 200

    def loss(*a):
        y, s = ref.mamba2_chunked_reference(*a[:6], chunk=64, init_state=a[6])
        return jnp.sum(y) + jnp.sum(s)

    grads = jax.grad(loss, argnums=tuple(range(7)))(*args, s0)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
    y1, s1 = ref.mamba2_chunked_reference(*args, chunk=64, init_state=s0)
    y2, s2 = ref.mamba2_scan_reference(*args, init_state=s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("with_state", [False, True])
def test_mamba2_kernel_grads_match_reference(chunk, G, with_state):
    """The kernel's custom vjp (the chunked reference, differentiated) gives
    the sequential reference's gradients, at a length that does not tile
    by the chunk."""
    from repro.kernels import ops
    args, s0 = _mamba2_inputs(1, 100, 2, 16, G, 16, with_state=with_state)
    gy = jax.random.normal(jax.random.fold_in(KEY, 1), args[0].shape)

    def loss(scan):
        def f(*a):
            y, s = scan(*a)
            return jnp.sum(y * gy) + jnp.sum(s)
        return f

    args = args + ((s0,) if with_state else ())
    n = len(args)
    g1 = jax.grad(loss(lambda *a: ops._mamba2_kernel_vjp(
        *a[:6], chunk, True, a[6] if with_state else None)),
        argnums=tuple(range(n)))(*args)
    g2 = jax.grad(loss(lambda *a: ref.mamba2_scan_reference(
        *a[:6], init_state=a[6] if with_state else None)),
        argnums=tuple(range(n)))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_mamba2_ssd_initial_state_continuation():
    """Scanning [0:T] must equal scanning [0:T/2] then [T/2:T] with the
    carried state — the decode/prefill contract."""
    ks = jax.random.split(KEY, 6)
    Bt, T, H, P, G, N = 1, 128, 2, 32, 1, 16
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bt, T, G, N))
    Cm = jax.random.normal(ks[4], (Bt, T, G, N))
    D = jnp.zeros((H,))
    y_full, s_full = ref.mamba2_scan_reference(x, dt, A, Bm, Cm, D)
    h = T // 2
    y1, s1 = mamba2_ssd(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], D,
                        chunk=64, interpret=True)
    y2, s2 = mamba2_ssd(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], D,
                        chunk=64, init_state=s1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("T,H,Dh,chunk", [(64, 2, 32, 32), (128, 4, 64, 64)])
def test_rwkv6_scan_sweep(T, H, Dh, chunk):
    ks = jax.random.split(KEY, 5)
    B = 2
    r = jax.random.normal(ks[0], (B, T, H, Dh))
    k = jax.random.normal(ks[1], (B, T, H, Dh))
    v = jax.random.normal(ks[2], (B, T, H, Dh))
    w = -jnp.exp(jax.random.normal(ks[3], (B, T, H, Dh)))
    u = jax.random.normal(ks[4], (H, Dh))
    y1, s1 = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    y2, s2 = ref.rwkv6_scan_reference(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("n,block", [(1000, 256), (4096, 256), (65, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_codec_sweep(n, block, dtype):
    x = jax.random.normal(KEY, (n,), dtype)
    q1, s1 = quantize_int8(x, block=block, interpret=True)
    q2, s2 = ref.quantize_int8_reference(x, block=block)
    assert bool(jnp.all(q1 == q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


# ---------------------------------------------------------------------------
# lengths that do not tile: the wrappers pad, and padding changes nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,causal", [(100, True), (200, True), (200, False)])
def test_flash_attention_untiled_lengths(T, causal):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, T, 4, 32))
    k = jax.random.normal(ks[1], (1, T, 2, 32))
    v = jax.random.normal(ks[2], (1, T, 2, 32))
    g = jax.random.normal(ks[3], (1, T, 4, 32))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, group=2,
                                       interpret=True) * g)

    def f_ref(q, k, v):
        return jnp.sum(ref.mha_reference(q, k, v, causal=causal, group=2) * g)

    out = flash_attention(q, k, v, causal=causal, group=2, interpret=True)
    exp = ref.mha_reference(q, k, v, causal=causal, group=2)
    assert out.shape == exp.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-5)
    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_decode_attention_untiled_cache():
    ks = jax.random.split(KEY, 3)
    B, S, Hq, Hkv, Dh = 2, 300, 4, 2, 64
    q = jax.random.normal(ks[0], (B, 1, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    lengths = jnp.array([S // 3, S], jnp.int32)
    out = decode_attention(q, k, v, lengths, group=2, bk=128, interpret=True)
    exp = ref.mha_reference(q, k, v, causal=False, group=2, lengths=lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-5)


def test_scans_untiled_lengths():
    """Padded scan steps leave the carried state untouched, so outputs and
    final states match the oracles at a length that does not tile."""
    ks = jax.random.split(KEY, 6)
    Bt, T, H, P, G, N = 1, 200, 2, 32, 1, 16
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bt, T, G, N))
    Cm = jax.random.normal(ks[4], (Bt, T, G, N))
    D = jax.random.normal(ks[5], (H,))
    got = mamba2_ssd(x, dt, A, Bm, Cm, D, interpret=True)
    exp = ref.mamba2_scan_reference(x, dt, A, Bm, Cm, D)
    for a, b in zip(got, exp):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-4)

    T, Dh = 50, 32
    r, k, v = (jax.random.normal(kk, (Bt, T, H, Dh)) for kk in ks[:3])
    w = -jnp.exp(jax.random.normal(ks[3], (Bt, T, H, Dh)))
    u = jax.random.normal(ks[4], (H, Dh))
    got = rwkv6_scan(r, k, v, w, u, chunk=32, interpret=True)
    exp = ref.rwkv6_scan_reference(r, k, v, w, u)
    for a, b in zip(got, exp):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-4)
