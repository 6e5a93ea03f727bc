"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, Hkv, L, S, D, window, dtype)
    (2, 4, 2, 128, 128, 64, None, jnp.float32),
    (1, 8, 8, 256, 256, 128, None, jnp.float32),
    (1, 4, 1, 256, 256, 64, 64, jnp.float32),
    (2, 2, 2, 96, 96, 32, None, jnp.float32),      # unaligned -> padding
    (1, 4, 2, 128, 128, 64, None, jnp.bfloat16),
    (1, 2, 1, 64, 64, 128, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,Hkv,L,S,D,window,dtype", FLASH_CASES)
def test_flash_attention_matches_ref(B, H, Hkv, L, S, D, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, L, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              bq=64, bk=64)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_flash_attention_rows_sum_to_one_property():
    """Online softmax must renormalise exactly: attention of constant V
    returns that constant."""
    B, H, L, D = 1, 2, 128, 64
    q = jax.random.normal(KEY, (B, L, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, L, H, D))
    v = jnp.ones((B, L, H, D))
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, L, H, P, G, N, chunk)
    (2, 64, 4, 16, 1, 16, 16),
    (1, 128, 8, 32, 2, 64, 32),
    (2, 40, 4, 8, 2, 16, 16),      # L not divisible by chunk -> padding
    (1, 256, 2, 64, 1, 128, 128),
]


@pytest.mark.parametrize("B,L,H,P,G,N,chunk", SSD_CASES)
def test_ssd_scan_matches_ref(B, L, H, P, G, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, G, N))
    Cm = jax.random.normal(ks[4], (B, L, G, N))
    D = jnp.ones((H,))
    y, s = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk)
    yr, sr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=2e-4, atol=2e-4)


def test_ssd_matches_stepwise_recurrence():
    """The chunked SSD (any chunking) must equal the sequential SSM."""
    B, L, H, P, G, N = 1, 24, 2, 4, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, G, N))
    Cm = jax.random.normal(ks[4], (B, L, G, N))
    D = jnp.zeros((H,))
    y, _ = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=8)
    S = np.zeros((B, H, P, N))
    Bf = np.repeat(np.asarray(Bm), H // G, 2)
    Cf = np.repeat(np.asarray(Cm), H // G, 2)
    for t in range(L):
        dA = np.exp(np.asarray(dt)[:, t] * np.asarray(A)[None])
        S = S * dA[:, :, None, None] + np.einsum(
            "bh,bhn,bhp->bhpn", np.asarray(dt)[:, t], Bf[:, t],
            np.asarray(x)[:, t])
        yt = np.einsum("bhn,bhpn->bhp", Cf[:, t], S)
        np.testing.assert_allclose(np.asarray(y)[:, t], yt,
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ddpm step
# ---------------------------------------------------------------------------

DDPM_CASES = [
    ((4, 20), jnp.float32, 0), ((4, 20), jnp.float32, 3),
    ((2, 3, 40), jnp.float32, 1), ((8, 256), jnp.bfloat16, 2),
    ((1, 7), jnp.float32, 0),
]


@pytest.mark.parametrize("shape,dtype,l_rev", DDPM_CASES)
def test_ddpm_step_matches_ref(shape, dtype, l_rev):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], shape, dtype)
    e = jax.random.normal(ks[1], shape, dtype)
    n = jax.random.normal(ks[2], shape, dtype)
    alpha, abar, btilde = 0.9, 0.5, 0.04
    out = ops.ddpm_step(x, e, n, jnp.float32(alpha), jnp.float32(abar),
                        jnp.float32(btilde), jnp.int32(l_rev))
    expect = ref.ddpm_step_ref(x, e, n, alpha, abar, btilde, l_rev)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_ddpm_step_last_step_is_deterministic():
    x = jax.random.normal(KEY, (4, 16))
    e = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16))
    n1 = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 16))
    n2 = jax.random.normal(jax.random.fold_in(KEY, 3), (4, 16))
    a = [jnp.float32(0.9), jnp.float32(0.5), jnp.float32(0.04)]
    o1 = ops.ddpm_step(x, e, n1, *a, jnp.int32(0))
    o2 = ops.ddpm_step(x, e, n2, *a, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# absorbed-MLA decode
# ---------------------------------------------------------------------------

# S = 48 positions give the kernel blocks of 16 rows; the positions hit an
# empty cache, one row, each side of a block's edge and the last row
MLA_S, MLA_BK = 48, 16
MLA_POS = (0, 1, MLA_BK - 1, MLA_BK, MLA_BK + 1, MLA_S - 1)


def _mla_inputs(dtype, R=1, B=len(MLA_POS), H=4, W=128, seed=0):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    q = jax.random.normal(ks[0], (B, H, W), jnp.bfloat16)
    own = jax.random.normal(ks[1], (B, W), dtype)
    kv = jax.random.normal(ks[2], (R, B, MLA_S, W), dtype)
    return q, own, kv


MLA_CASES = {
    # id: (positions, cache dtype, window, layout)
    "mixed-bf16": (MLA_POS, jnp.bfloat16, None, "plain"),
    "mixed-f32": (MLA_POS, jnp.float32, None, "plain"),
    "scalar-pos": (MLA_BK + 1, jnp.bfloat16, None, "plain"),
    "window": (MLA_POS, jnp.bfloat16, 20, "plain"),
    # a token that attends only itself: slots with no block to read amid
    # slots with one
    "window-own-only": (MLA_POS, jnp.bfloat16, 1, "plain"),
    "stacked-scan": (MLA_POS, jnp.bfloat16, None, "scan"),
    "stacked-unrolled": (MLA_POS, jnp.float32, 20, "unrolled"),
    # the split form on three shards of the sequence, combined by their
    # log-sum-exps: positions fall before, inside and past each shard
    "split-bf16": (MLA_POS, jnp.bfloat16, None, "split"),
    "split-window": (MLA_POS, jnp.float32, 20, "split"),
}


def _mla_split(q, own, kv, pos, n, **kw):
    """The kernel's split form on n shards of kv's sequence, and the own
    row, combined as a mesh's devices combine them."""
    Sl = kv.shape[2] // n
    o, lse = zip(*[ops.mla_decode(q, None, kv[:, :, k * Sl:(k + 1) * Sl],
                                  pos - k * Sl, 0, **kw) for k in range(n)])
    for k in range(n):      # a shard with no row to read gives 0, NEG_INF
        lo = jnp.maximum(pos - kw["window"] + 1, 0) if kw["window"] else 0
        empty = np.asarray((pos <= k * Sl) | (lo >= (k + 1) * Sl))
        assert np.all(np.asarray(o[k])[empty] == 0)
        assert np.all(np.asarray(lse[k])[empty] == -1e30)
    o, lse = jnp.stack(o), jnp.stack(lse)
    own_lse = jnp.einsum("bhc,bc->bh", q.astype(jnp.float32),
                         own.astype(jnp.float32))[..., None] * kw["scale"]
    top = jnp.maximum(lse.max(0), own_lse)
    w, w_own = jnp.exp(lse - top), jnp.exp(own_lse - top)
    ctx = ((w * o).sum(0) + w_own * own[:, None, :kw["width"]]) \
        / (w.sum(0) + w_own)
    return ctx.astype(q.dtype)


@pytest.mark.parametrize("case", list(MLA_CASES))
def test_mla_decode_matches_ref(case):
    from repro.kernels.mla_decode import block_rows
    assert block_rows(MLA_S) == MLA_BK
    pos, dtype, window, layout = MLA_CASES[case]
    R = 1 if layout in ("plain", "split") else 3
    q, own, kv = _mla_inputs(dtype, R)
    pos = jnp.asarray(pos, jnp.int32)
    kw = dict(scale=0.125, width=96, window=window)
    if layout in ("plain", "split"):
        out = [ops.mla_decode(q, own, kv, pos, 0, **kw) if layout == "plain"
               else _mla_split(q, own, kv, pos, 3, **kw)]
        expect = [ref.mla_decode_ref(q, own, kv, pos, 0, **kw)]
    elif layout == "scan":      # the layer index is traced
        _, out = jax.lax.scan(
            lambda c, r: (c, ops.mla_decode(q, own, kv, pos, r, **kw)),
            0, jnp.arange(R))
        expect = [ref.mla_decode_ref(q, own, kv, pos, r, **kw) for r in range(R)]
    else:
        out = [ops.mla_decode(q, own, kv, pos, r, **kw) for r in range(R)]
        expect = [ref.mla_decode_ref(q, own, kv, pos, r, **kw) for r in range(R)]
    for o, e in zip(out, expect):
        assert o.shape == (q.shape[0], q.shape[1], 96) and o.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(e, np.float32),
                                   **_tol(jnp.bfloat16))


@pytest.mark.parametrize("window", [None, 20])
def test_mla_decode_ignores_rows_the_mask_excludes_property(window):
    """Rows at or past a slot's position, below its window, and in the
    other layers of a stacked cache may hold anything finite: the output
    is bit for bit that of a cache with zeros there."""
    q, own, kv = _mla_inputs(jnp.bfloat16, R=2, seed=1)
    pos = jnp.asarray(MLA_POS, jnp.int32)
    s = jnp.arange(MLA_S)[None, :, None]
    live = s < pos[:, None, None]
    if window is not None:
        live &= s > pos[:, None, None] - window
    junk = jnp.full_like(kv, 3e4)
    clean = kv.at[0].set(0.0).at[1].set(jnp.where(live, kv[1], 0.0))
    dirty = junk.at[1].set(jnp.where(live, kv[1], junk[1]))
    kw = dict(scale=0.125, width=96, window=window)
    a = ops.mla_decode(q, own, clean, pos, 1, **kw)
    b = ops.mla_decode(q, own, dirty, pos, 1, **kw)
    assert np.all(np.isfinite(np.asarray(a, np.float32)))
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


MLA_STREAM_CASES = {
    # id: (query rows a grid step holds, buffers, positions, window); one
    # grid step, then groups of one and two slots past idle ones
    "one-step": (None, None, MLA_POS, 20),
    "groups-of-1": (4, 2, (5, 0, 0, MLA_BK * 2 + 1, 0, 2), None),
    "groups-of-2": (8, 3, (5, 0, 0, MLA_BK * 2 + 1, 0, 2), None),
}


@pytest.mark.parametrize("case", list(MLA_STREAM_CASES))
def test_mla_decode_reads_each_block_after_its_copy(monkeypatch, case):
    """Under the TPU interpreter, a copy lands only when it is waited for
    and unwritten VMEM reads NaN: a block used before its wait, a buffer
    refilled while in use, or a copy stream that breaks where a grid step
    ends (copies started in one step are waited for in the next) would
    show in the output."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import mla_decode as K
    group_rows, buffers, pos, window = MLA_STREAM_CASES[case]
    if group_rows is not None:
        monkeypatch.setattr(K, "GROUP_ROWS", group_rows)
        monkeypatch.setattr(K, "BUFFERS", buffers)
    q, own, kv = _mla_inputs(jnp.bfloat16, R=2, seed=2)
    pos = jnp.asarray(pos, jnp.int32)
    steps = q.shape[0] // K._group(*q.shape[:2])
    assert (steps == 1) == (group_rows is None)
    kw = dict(scale=0.125, width=96, window=window)
    out = K.mla_decode_bhw(q, own[:, None], kv, pos, jnp.array([1], jnp.int32),
                           interpret=pltpu.InterpretParams(
                               dma_execution_mode="on_wait",
                               uninitialized_memory="nan"), **kw)
    expect = ref.mla_decode_ref(q, own, kv, pos, 1, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               **_tol(jnp.bfloat16))
