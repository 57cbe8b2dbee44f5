"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:  # optional test dep: falls back to fixed deterministic examples
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_decode.ref import decode_ref
from repro.kernels.paged_decode.kernel import chunk_blocks
from repro.kernels.paged_decode.ops import (paged_flash_decode,
                                            paged_flash_decode_quant,
                                            paged_gather_decode,
                                            paged_gather_decode_quant,
                                            quantize_kv)
from repro.kernels.paged_decode.ref import (paged_decode_quant_ref,
                                            paged_decode_ref)
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_ref_loop
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref


def _mk(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,causal,window,bq,bk",
    [
        (1, 128, 4, 4, 32, True, 0, 64, 64),
        (2, 256, 4, 2, 64, True, 0, 128, 128),
        (1, 256, 8, 1, 64, True, 64, 64, 64),  # MQA + sliding window
        (2, 128, 4, 4, 128, False, 0, 128, 128),  # bidirectional
        (1, 192, 6, 2, 32, True, 0, 64, 64),  # non-pow2 seq
    ])
def test_flash_attention_sweep(dtype, B, S, Hq, Hkv, hd, causal, window,
                               bq, bk, rng):
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, S, Hq, hd), dtype)
    k = _mk(ks[1], (B, S, Hkv, hd), dtype)
    v = _mk(ks[2], (B, S, Hkv, hd), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        n_kv_heads=Hkv, block_q=bq, block_k=bk,
                        interpret=True)
    r = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal, window=window)
    r = r.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                 - r.astype(jnp.float32)))) < tol


@settings(max_examples=8, deadline=None)
@given(
    S=st.sampled_from([64, 128, 256]),
    Hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    causal=st.booleans(),
)
def test_flash_attention_property(S, Hkv, g, causal):
    rng = jax.random.PRNGKey(S * 31 + Hkv * 7 + g)
    ks = jax.random.split(rng, 3)
    Hq, hd, B = Hkv * g, 32, 1
    q = _mk(ks[0], (B, S, Hq, hd), jnp.float32)
    k = _mk(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = _mk(ks[2], (B, S, Hkv, hd), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, n_kv_heads=Hkv,
                        block_q=64, block_k=64, interpret=True)
    r = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal)
    r = r.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


@pytest.mark.parametrize("cur", [0, 17, 255, 511])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(cur, dtype, rng):
    B, Hq, Hkv, S, hd = 2, 8, 2, 512, 64
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, Hq, hd), dtype)
    k = _mk(ks[1], (B, Hkv, S, hd), dtype)
    v = _mk(ks[2], (B, Hkv, S, hd), dtype)
    o = flash_decode(q, k, v, cur, block_k=128, interpret=True)
    r = decode_ref(q, k, v, cur)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - r))) < tol


def test_flash_decode_merge_identity(rng):
    """Merging two half-cache partials == attention over the full cache."""
    from repro.kernels.flash_decode.kernel import flash_decode_kernel
    B, Hq, Hkv, S, hd = 1, 4, 2, 256, 32
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, Hq, hd), jnp.float32)
    k = _mk(ks[1], (B, Hkv, S, hd), jnp.float32)
    v = _mk(ks[2], (B, Hkv, S, hd), jnp.float32)
    o1, m1, l1 = flash_decode_kernel(q, k[:, :, :128], v[:, :, :128], 127,
                                     block_k=64, interpret=True)
    o2, m2, l2 = flash_decode_kernel(q, k[:, :, 128:], v[:, :, 128:], 127,
                                     block_k=64, interpret=True)
    mg = jnp.maximum(m1, m2)
    w1, w2 = l1 * jnp.exp(m1 - mg), l2 * jnp.exp(m2 - mg)
    merged = (o1 * w1 + o2 * w2) / (w1 + w2)
    ref = decode_ref(q, k, v, 255)
    assert float(jnp.max(jnp.abs(merged - ref))) < 1e-3


def _paged_setup(rng, B, Hq, Hkv, hd, bs, MB, dtype, extra_blocks=3):
    """Random pool + per-row tables drawing *disjoint, shuffled* physical
    blocks (block 0 reserved as the null block, like serve/blocks.py)."""
    NB = 1 + B * MB + extra_blocks
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, Hq, hd), dtype)
    kp = _mk(ks[1], (NB, Hkv, bs, hd), dtype)
    vp = _mk(ks[2], (NB, Hkv, bs, hd), dtype)
    ids = np.random.default_rng(int(jax.random.randint(rng, (), 0, 1 << 30))
                                ).permutation(np.arange(1, NB))
    tables = jnp.asarray(ids[:B * MB].reshape(B, MB), jnp.int32)
    return q, kp, vp, tables


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,hd,bs,MB", [
    (2, 4, 2, 32, 16, 4),
    (3, 8, 8, 64, 8, 3),   # MHA
    (1, 4, 1, 128, 32, 2),  # MQA, wide blocks
])
def test_paged_decode_kernel_sweep(B, Hq, Hkv, hd, bs, MB, dtype, rng):
    q, kp, vp, tables = _paged_setup(rng, B, Hq, Hkv, hd, bs, MB, dtype)
    lengths = jnp.asarray([(i * 7) % (MB * bs) for i in range(B)], jnp.int32)
    o = paged_flash_decode(q, kp, vp, tables, lengths, interpret=True)
    r = paged_decode_ref(q, kp, vp, tables, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(o - r))) < tol
    # the XLA gather fallback agrees too (it's what CPU serving runs)
    g = paged_gather_decode(q, kp, vp, tables, lengths)
    assert float(jnp.max(jnp.abs(g - r))) < tol


def test_paged_decode_masks_fully_and_partially(rng):
    B, Hq, Hkv, hd, bs, MB = 3, 4, 2, 32, 16, 3
    q, kp, vp, tables = _paged_setup(rng, B, Hq, Hkv, hd, bs, MB,
                                     jnp.float32)
    lengths = jnp.asarray([-1, 0, MB * bs - 1], jnp.int32)
    o = paged_flash_decode(q, kp, vp, tables, lengths, interpret=True)
    r = paged_decode_ref(q, kp, vp, tables, lengths)
    assert float(jnp.max(jnp.abs(o[0]))) == 0.0, "masked row must be zero"
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


def test_paged_decode_matches_contiguous_cache(rng):
    """Paging a contiguous cache into shuffled physical blocks must not
    change the attention output (table order == logical order)."""
    B, Hq, Hkv, hd, bs, MB = 2, 8, 2, 64, 16, 4
    S = MB * bs
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, Hq, hd), jnp.float32)
    k = _mk(ks[1], (B, Hkv, S, hd), jnp.float32)
    v = _mk(ks[2], (B, Hkv, S, hd), jnp.float32)
    NB = 1 + B * MB
    perm = np.random.default_rng(0).permutation(np.arange(1, NB))
    tables = jnp.asarray(perm.reshape(B, MB), jnp.int32)
    kp = jnp.zeros((NB, Hkv, bs, hd), jnp.float32)
    vp = jnp.zeros((NB, Hkv, bs, hd), jnp.float32)
    for b in range(B):
        for j in range(MB):
            blk = slice(j * bs, (j + 1) * bs)
            kp = kp.at[tables[b, j]].set(k[b, :, blk])
            vp = vp.at[tables[b, j]].set(v[b, :, blk])
    cur = 37
    o = paged_flash_decode(q, kp, vp, tables,
                           jnp.full((B,), cur, jnp.int32), interpret=True)
    r = decode_ref(q, k, v, cur)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


def _quantize_pool(kp, vp):
    """int8 + per-(block, head, token) scale over the head dim."""
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    return kq, vq, ks, vs


@pytest.mark.parametrize("B,Hq,Hkv,hd,bs,MB", [
    (2, 4, 2, 32, 16, 4),
    (3, 8, 8, 64, 8, 3),   # MHA
    (1, 4, 1, 128, 32, 2),  # MQA, wide blocks
])
def test_paged_decode_quant_kernel_sweep(B, Hq, Hkv, hd, bs, MB, rng):
    """The quant kernel (dequant folded into scores and probabilities,
    scales in VMEM blocks) must match the dequantize-everything reference exactly-ish,
    and the whole int8 scheme must stay within the divergence bound of
    the fp pool it quantized."""
    q, kp, vp, tables = _paged_setup(rng, B, Hq, Hkv, hd, bs, MB,
                                     jnp.float32)
    kq, vq, ks, vs = _quantize_pool(kp, vp)
    lengths = jnp.asarray([(i * 7) % (MB * bs) for i in range(B)], jnp.int32)
    o = paged_flash_decode_quant(q, kq, vq, ks, vs, tables, lengths,
                                 interpret=True)
    r = paged_decode_quant_ref(q, kq, vq, ks, vs, tables, lengths)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3
    # the XLA gather fallback agrees too (it's what CPU serving runs)
    g = paged_gather_decode_quant(q, kq, vq, ks, vs, tables, lengths)
    assert float(jnp.max(jnp.abs(g - r))) < 2e-3
    # bounded divergence vs the fp pool: int8-over-head-dim keeps the
    # attention output within a small relative RMS of the unquantized one
    fp = paged_decode_ref(q, kp, vp, tables, lengths)
    rmse = float(jnp.sqrt(jnp.mean((o - fp) ** 2)
                          / jnp.maximum(jnp.mean(fp ** 2), 1e-12)))
    assert rmse < 0.05, f"quant divergence {rmse} out of bound"


def test_paged_decode_quant_masks_fully_and_partially(rng):
    B, Hq, Hkv, hd, bs, MB = 3, 4, 2, 32, 16, 3
    q, kp, vp, tables = _paged_setup(rng, B, Hq, Hkv, hd, bs, MB,
                                     jnp.float32)
    kq, vq, ks, vs = _quantize_pool(kp, vp)
    lengths = jnp.asarray([-1, 0, MB * bs - 1], jnp.int32)
    o = paged_flash_decode_quant(q, kq, vq, ks, vs, tables, lengths,
                                 interpret=True)
    r = paged_decode_quant_ref(q, kq, vq, ks, vs, tables, lengths)
    assert float(jnp.max(jnp.abs(o[0]))) == 0.0, "masked row must be zero"
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


def _paged_run(quant, q, kp, vp, tables, lengths):
    """The bf16 or int8 (pool quantized here) kernel beside its oracle."""
    if not quant:
        return (paged_flash_decode(q, kp, vp, tables, lengths, interpret=True),
                paged_decode_ref(q, kp, vp, tables, lengths))
    kq, vq, ks, vs = _quantize_pool(kp, vp)
    return (paged_flash_decode_quant(q, kq, vq, ks, vs, tables, lengths,
                                     interpret=True),
            paged_decode_quant_ref(q, kq, vq, ks, vs, tables, lengths))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("Hq,Hkv,hd,bs,MB", [
    (12, 2, 128, 16, 12),  # g 6 (qwen2-1.5b widths); 12 = 8 + a short chunk
    (16, 2, 64, 16, 10),   # g 8; a width that is not a multiple of C = 8
    (8, 2, 32, 8, 20),     # block 8: C = 16 over a 20-block table
])
def test_paged_decode_lengths_at_chunk_edges(Hq, Hkv, hd, bs, MB, quant,
                                             rng):
    """Rows at -1, 0, the last position of the first chunk, the first of
    the second, and the table's last position: the chunk loop runs 0, 1,
    1, 2 and every chunk, and each row matches the oracle."""
    C = chunk_blocks(bs, MB)
    lengths = jnp.asarray([-1, 0, C * bs - 1, C * bs, MB * bs - 1], jnp.int32)
    q, kp, vp, tables = _paged_setup(rng, 5, Hq, Hkv, hd, bs, MB,
                                     jnp.bfloat16)
    o, r = _paged_run(quant, q, kp, vp, tables, lengths)
    assert float(jnp.max(jnp.abs(o[0]))) == 0.0, "masked row must be zero"
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_never_reads_dead_blocks(quant, rng):
    """Every table entry past a row's live blocks names a block filled
    with NaN (int8: its scales): the output is finite and matches the
    oracle over the real tables, so no dead block reaches the math."""
    B, Hq, Hkv, hd, bs, MB = 5, 12, 2, 128, 16, 12
    q, kp, vp, tables = _paged_setup(rng, B, Hq, Hkv, hd, bs, MB,
                                     jnp.float32, extra_blocks=1)
    nan_blk, = set(range(1, kp.shape[0])) - set(np.asarray(tables).flat)
    lengths = jnp.asarray([0, 8 * bs - 1, 8 * bs, 3 * bs + 5, MB * bs - 1],
                          jnp.int32)
    dead = jnp.arange(MB)[None, :] > lengths[:, None] // bs
    poisoned = jnp.where(dead, nan_blk, tables)
    if not quant:
        kp, vp = kp.at[nan_blk].set(jnp.nan), vp.at[nan_blk].set(jnp.nan)
        o = paged_flash_decode(q, kp, vp, poisoned, lengths, interpret=True)
        r = paged_decode_ref(q, kp, vp, tables, lengths)
    else:
        kq, vq, ks, vs = _quantize_pool(kp, vp)
        ks, vs = ks.at[nan_blk].set(jnp.nan), vs.at[nan_blk].set(jnp.nan)
        o = paged_flash_decode_quant(q, kq, vq, ks, vs, poisoned, lengths,
                                     interpret=True)
        r = paged_decode_quant_ref(q, kq, vq, ks, vs, tables, lengths)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert float(jnp.max(jnp.abs(o - r))) < 2e-3


@settings(max_examples=8, deadline=None)
@given(Hkv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 4]),
       bs=st.sampled_from([4, 8, 16]), seed=st.integers(0, 1 << 16))
def test_paged_gather_fallback_property(Hkv, g, bs, seed):
    """Property sweep of the XLA gather fallback against the reference on
    adversarial tables: a fully-masked null-block row (all-zero table),
    a mid-truncate row (suffix entries back at the null block), and rows
    at arbitrary partial depths — the block-table states serving actually
    produces around admission, truncate, and retirement."""
    rng = np.random.default_rng(seed)
    B, MB, hd = 4, 3, 32
    Hq = Hkv * g
    NB = 1 + B * MB + 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _mk(ks[0], (B, Hq, hd), jnp.float32)
    kp = _mk(ks[1], (NB, Hkv, bs, hd), jnp.float32)
    vp = _mk(ks[2], (NB, Hkv, bs, hd), jnp.float32)
    perm = list(rng.permutation(np.arange(1, NB)))
    tables = np.zeros((B, MB), np.int32)
    lengths = np.zeros((B,), np.int32)
    lengths[0] = -1  # masked row: null table, no valid positions
    tables[1, 0] = perm.pop()  # truncated back to one block
    lengths[1] = int(rng.integers(0, bs))
    for b in (2, 3):
        for j in range(MB):
            tables[b, j] = perm.pop()
        lengths[b] = int(rng.integers(0, MB * bs))
    t, L = jnp.asarray(tables), jnp.asarray(lengths)
    out = paged_gather_decode(q, kp, vp, t, L)
    ref = paged_decode_ref(q, kp, vp, t, L)
    assert float(jnp.max(jnp.abs(out[0]))) == 0.0
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


def test_paged_gather_truncate_regrow_invariance(rng):
    """Speculative rollback then regrowth rewrites a row's table suffix
    onto different physical blocks. Same logical KV -> bit-identical
    output, even with the freed blocks poisoned: the gather path must
    depend only on (table, pool content at named blocks, length)."""
    B, Hq, Hkv, hd, bs, MB = 1, 4, 2, 32, 8, 4
    S = MB * bs
    ks = jax.random.split(rng, 3)
    q = _mk(ks[0], (B, Hq, hd), jnp.float32)
    k = _mk(ks[1], (B, Hkv, S, hd), jnp.float32)
    v = _mk(ks[2], (B, Hkv, S, hd), jnp.float32)
    NB = 1 + 2 * MB  # room for the original AND the regrown suffix
    kp = jnp.zeros((NB, Hkv, bs, hd), jnp.float32)
    vp = jnp.zeros((NB, Hkv, bs, hd), jnp.float32)
    first = np.arange(1, MB + 1)
    for j, bid in enumerate(first):
        blk = slice(j * bs, (j + 1) * bs)
        kp = kp.at[bid].set(k[0, :, blk])
        vp = vp.at[bid].set(v[0, :, blk])
    tables = jnp.asarray(first[None, :], jnp.int32)
    cur = S - 1
    out1 = paged_gather_decode(q, kp, vp, tables,
                               jnp.asarray([cur], jnp.int32))
    # truncate the last 2 blocks, regrow onto fresh physical ids with the
    # same logical KV, and poison the old blocks with garbage
    keep = MB - 2
    regrown = np.arange(MB + 1, MB + 3)
    for j, bid in enumerate(regrown, start=keep):
        blk = slice(j * bs, (j + 1) * bs)
        kp = kp.at[bid].set(k[0, :, blk])
        vp = vp.at[bid].set(v[0, :, blk])
    for bid in first[keep:]:
        kp = kp.at[bid].set(1e6)
        vp = vp.at[bid].set(-1e6)
    tables2 = jnp.asarray(
        np.concatenate([first[:keep], regrown])[None, :], jnp.int32)
    out2 = paged_gather_decode(q, kp, vp, tables2,
                               jnp.asarray([cur], jnp.int32))
    assert jnp.array_equal(out1, out2), \
        "physical block placement leaked into the attention output"


@pytest.mark.parametrize("B,S,W,bt,bw", [
    (2, 128, 64, 32, 64), (1, 256, 128, 64, 128), (2, 64, 256, 16, 128)])
def test_rglru_kernel_sweep(B, S, W, bt, bw, rng):
    ks = jax.random.split(rng, 3)
    a = jax.random.uniform(ks[0], (B, S, W), jnp.float32, 0.05, 0.999)
    b = jax.random.normal(ks[1], (B, S, W), jnp.float32)
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    hk = rglru_scan(a, b, h0, block_t=bt, block_w=bw, interpret=True)
    hr = rglru_ref_loop(a, b, h0)
    assert float(jnp.max(jnp.abs(hk - hr))) < 2e-3


@settings(max_examples=6, deadline=None)
@given(decay=st.floats(0.01, 6.0), S=st.sampled_from([64, 128]))
def test_rglru_kernel_extreme_decay_property(decay, S):
    """Stability under strong decay (the log-space clip must not blow up)."""
    rng = jax.random.PRNGKey(int(decay * 1000) + S)
    ks = jax.random.split(rng, 2)
    a = jnp.exp(-decay * jax.random.uniform(ks[0], (1, S, 64), minval=0.5,
                                            maxval=1.0))
    b = jax.random.normal(ks[1], (1, S, 64), jnp.float32)
    hk = rglru_scan(a, b, None, block_t=32, block_w=64, interpret=True)
    hr = rglru_ref_loop(a, b, None)
    assert bool(jnp.all(jnp.isfinite(hk)))
    assert float(jnp.max(jnp.abs(hk - hr))) < 2e-3


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 128, 2, 32, 32), (1, 64, 4, 64, 16), (1, 96, 1, 16, 32)])
def test_wkv6_kernel_sweep(B, S, H, hd, chunk, rng):
    ks = jax.random.split(rng, 5)
    mk = lambda k: jax.random.normal(k, (B, S, H, hd), jnp.float32) * 0.5
    r, k_, v = mk(ks[0]), mk(ks[1]), mk(ks[2])
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    o, sf = wkv6(r, k_, v, logw, u, chunk=chunk, interpret=True)
    orf, sr = wkv6_ref(*(a.transpose(0, 2, 1, 3) for a in (r, k_, v, logw)),
                       u)
    assert float(jnp.max(jnp.abs(o - orf.transpose(0, 2, 1, 3)))) < 2e-3
    assert float(jnp.max(jnp.abs(sf - sr))) < 2e-3


# ---------------------------------------------------------------------------
# sampling: fused top-k/top-p mask (bisection kernel vs sort-based oracle)
# ---------------------------------------------------------------------------

from repro.kernels.sampling.ops import topk_topp_mask  # noqa: E402
from repro.kernels.sampling.ref import NEG_INF, topk_topp_mask_ref  # noqa: E402


@pytest.mark.parametrize("T,V", [(4, 128), (3, 200), (1, 512)])
def test_sampling_mask_kernel_matches_oracle(T, V, rng):
    """The bisection kernel (interpret mode) must produce the oracle's
    keep-set: same survivors, same NEG_INF drops — including a non-128
    vocab that exercises the lane padding."""
    ks = jax.random.split(rng, 2)
    logits = jax.random.normal(ks[0], (T, V), jnp.float32) * 3.0
    top_k = jnp.asarray([0, 5, 1, 40][:T], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9, 0.5, 0.25][:T], jnp.float32)
    got = topk_topp_mask(logits, top_k, top_p, impl="interpret")
    want = topk_topp_mask_ref(logits, top_k, top_p)
    keep_g, keep_w = got > NEG_INF / 2, want > NEG_INF / 2
    assert bool(jnp.all(keep_g == keep_w)), "keep-sets differ"
    assert bool(jnp.all(jnp.where(keep_w, got == want, True))), \
        "kept logits must pass through unchanged"


def test_sampling_mask_semantics(rng):
    """Unit semantics on a hand-checkable distribution."""
    logits = jnp.log(jnp.asarray([[0.4, 0.3, 0.2, 0.05, 0.05]],
                                 jnp.float32))
    # top_k=2 keeps exactly the two largest
    out = topk_topp_mask_ref(logits, jnp.asarray([2]), jnp.asarray([1.0]))
    assert [bool(b) for b in (out[0] > NEG_INF / 2)] == \
        [True, True, False, False, False]
    # top_p=0.65 needs {0.4, 0.3} (cumsum crosses at the second token;
    # 0.65 sits safely between 0.4 and 0.7 so fp roundoff can't flip it)
    out = topk_topp_mask_ref(logits, jnp.asarray([0]), jnp.asarray([0.65]))
    assert [bool(b) for b in (out[0] > NEG_INF / 2)] == \
        [True, True, False, False, False]
    # disabled filters keep everything
    out = topk_topp_mask_ref(logits, jnp.asarray([0]), jnp.asarray([1.0]))
    assert bool(jnp.all(out[0] > NEG_INF / 2))
    # the argmax always survives even the harshest settings
    out = topk_topp_mask_ref(logits, jnp.asarray([1]), jnp.asarray([1e-3]))
    assert [bool(b) for b in (out[0] > NEG_INF / 2)] == \
        [True, False, False, False, False]


def test_sampling_mask_kernel_tie_values(rng):
    """Value ties at the top-k boundary are all kept (both impls)."""
    logits = jnp.asarray([[1.0, 2.0, 2.0, 0.0, -1.0] + [-9.0] * 123],
                         jnp.float32)
    for impl in ("xla", "interpret"):
        out = topk_topp_mask(logits, jnp.asarray([2]), jnp.asarray([1.0]),
                             impl=impl)
        keep = out[0] > NEG_INF / 2
        assert [bool(b) for b in keep[:5]] == [False, True, True, False,
                                               False], impl


@pytest.mark.parametrize("op", ["paged", "paged_int8", "flash", "mask"])
def test_pallas_ops_refuse_to_interpret_implicitly(op, rng):
    """Off the TPU a Pallas op runs only when the caller asks for the
    interpreter: the default lowers through Mosaic and fails loudly instead
    of silently interpreting (or silently taking an XLA path)."""
    q, kp, vp, tables = _paged_setup(rng, 2, 4, 2, 32, 8, 2, jnp.float32)
    lengths = jnp.asarray([3, 9], jnp.int32)
    kq, vq, ks, vs = _quantize_pool(kp, vp)
    x = _mk(rng, (1, 128, 4, 32), jnp.float32)
    calls = {
        "paged": lambda: paged_flash_decode(q, kp, vp, tables, lengths),
        "paged_int8": lambda: paged_flash_decode_quant(q, kq, vq, ks, vs,
                                                       tables, lengths),
        "flash": lambda: flash_attention(x, x, x),
        "mask": lambda: topk_topp_mask(kp[0, 0], jnp.zeros((8,), jnp.int32),
                                       jnp.ones((8,), jnp.float32),
                                       impl="pallas"),
    }
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(calls[op]())
