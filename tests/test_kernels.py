"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU; same call path targets TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.gemm.ops import gemm
from repro.kernels.gemm.ref import gemm_ref
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_mla_attention)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_mla_attention_ref)
from repro.kernels.tree_reduce.ops import (coded_tree_reduce, decode_add,
                                           encode_rows, tree_reduce)
from repro.kernels.tree_reduce.ref import linear_reduce_ref, tree_reduce_ref
from repro.models.layers import gqa_attention, paged_gather
from repro.optim.compression import CODECS

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- GEMM ----

GEMM_SHAPES = [(128, 128, 128), (256, 128, 384), (200, 300, 150),
               (64, 512, 64), (1, 128, 1), (130, 257, 129)]


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_sweep(m, k, n, dtype):
    x = jnp.asarray(RNG.normal(size=(m, k)), dtype=dtype)
    y = jnp.asarray(RNG.normal(size=(k, n)), dtype=dtype)
    out = gemm(x, y)
    ref = gemm_ref(x, y)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_gemm_blocks():
    x = jnp.asarray(RNG.normal(size=(256, 256)), dtype=jnp.float32)
    y = jnp.asarray(RNG.normal(size=(256, 256)), dtype=jnp.float32)
    ref = gemm_ref(x, y)
    for bm, bn, bk in [(128, 128, 128), (64, 128, 256), (128, 64, 64)]:
        out = gemm(x, y, block_m=bm, block_n=bn, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- flash attention --

ATTN_CASES = [
    # (B, Tq, Tk, Hq, Hkv, D, causal, window, softcap)
    (2, 128, 128, 4, 4, 64, True, None, None),
    (1, 256, 256, 8, 2, 64, True, None, None),        # GQA
    (1, 256, 256, 4, 1, 128, True, 64, None),         # MQA + window
    (1, 128, 128, 2, 2, 64, True, None, 50.0),        # softcap
    (2, 200, 200, 4, 2, 32, True, None, None),        # unaligned T
    (1, 128, 128, 4, 4, 64, False, None, None),       # bidirectional
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Tq, Tk, Hq, Hkv, D, causal, window, cap = case
    if not causal and Tq % 128:
        pytest.skip("non-causal padding needs exact blocks (documented)")
    q = jnp.asarray(RNG.normal(size=(B, Tq, Hq, D)), dtype=dtype)
    k = jnp.asarray(RNG.normal(size=(B, Tk, Hkv, D)), dtype=dtype)
    v = jnp.asarray(RNG.normal(size=(B, Tk, Hkv, D)), dtype=dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap)
    pos = jnp.arange(Tq)
    ref = gqa_attention(q, k, v, pos_q=pos, pos_k=pos, causal=causal,
                        window=window, attn_cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_grad():
    B, T, H, D = 1, 128, 2, 32
    q = jnp.asarray(RNG.normal(size=(B, T, H, D)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, H, D)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, H, D)), dtype=jnp.float32)
    pos = jnp.arange(T)
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        gqa_attention(q, k, v, pos_q=pos, pos_k=pos) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-3)


def test_flash_matches_singlehead_ref():
    bh, T, D = 3, 128, 64
    q = jnp.asarray(RNG.normal(size=(bh, T, D)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(size=(bh, T, D)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(size=(bh, T, D)), dtype=jnp.float32)
    out = flash_attention(q[:, :, None], k[:, :, None], v[:, :, None])
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------- tree reduce --

@pytest.mark.parametrize("n,d", [(2, 128), (8, 512), (13, 700), (16, 1024),
                                 (32, 64), (1, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_reduce_sweep(n, d, dtype):
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype=dtype)
    out = tree_reduce(x)
    ref = jnp.sum(x.astype(jnp.float32), axis=0).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_tree_reduce_bitwise_deterministic_order():
    """The kernel's sum is bitwise-equal to the H-tree-order oracle — the
    determinism property linear accumulation does not have."""
    x = jnp.asarray(RNG.normal(size=(16, 512)) * 1e3, dtype=jnp.float32)
    out = np.asarray(tree_reduce(x))
    ref_tree = np.asarray(tree_reduce_ref(x))
    assert np.array_equal(out, ref_tree)
    # and the tree order genuinely differs from linear order somewhere
    ref_lin = np.asarray(linear_reduce_ref(x))
    assert not np.array_equal(ref_tree, ref_lin) or np.allclose(ref_tree,
                                                                ref_lin)


# ------------------------------------------------------- paged attention --
#
# The fused decode kernel walks block tables directly; its oracle is the
# gather-then-attend reference (the paged_kernel="ref" lowering).  Cases pin
# ragged per-row lengths, sentinel-padded table tails, lengths that stop
# mid-block (block-edge straddles), GQA grouping, and softcap/window.


def _paged_case(dtype, seed=0, B=3, n=4, N=9, bs=4, Hkv=2, G=3, d=16, dv=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, d)), dtype=dtype)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv, d)), dtype=dtype)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv, dv)), dtype=dtype)
    tables = jnp.asarray(rng.integers(1, N, size=(B, n)), dtype=jnp.int32)
    # sentinel-padded tails + ragged lengths: row 0 full-ish and straddling
    # a block edge (13 % bs != 0), row 1 short with a sentinel tail, row 2
    # minimal (single cached token)
    tables = tables.at[1, 2:].set(0)
    offset = jnp.asarray([13, 6, 0], jnp.int32)
    return q, kp, vp, tables, offset


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap",
                         [(None, None), (5, None), (None, 8.0), (6, 4.0)])
def test_paged_attention_parity(dtype, window, softcap):
    q, kp, vp, tables, offset = _paged_case(dtype)
    out = paged_attention(q, kp, vp, tables, offset, window=window,
                          softcap=softcap)
    B, _, Hq, d = q.shape
    Hkv = kp.shape[2]
    qh = q[:, 0].reshape(B, Hkv, Hq // Hkv, d)
    ref = paged_attention_ref(qh, kp, vp, tables, offset + 1,
                              scale=1.0 / np.sqrt(d), window=window,
                              softcap=softcap).reshape(out.shape)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_attention_matches_gather_then_gqa():
    """Against the PRODUCTION ref lowering: paged_gather materializes the
    virtual view, gqa_attention masks causally by per-row positions."""
    q, kp, vp, tables, offset = _paged_case(jnp.float32, seed=1)
    out = paged_attention(q, kp, vp, tables, offset)
    k_all = paged_gather(kp, tables)
    v_all = paged_gather(vp, tables)
    S = k_all.shape[1]
    pos_k = jnp.arange(S, dtype=jnp.int32)[None, :]
    ref = gqa_attention(q, k_all, v_all, pos_q=offset[:, None], pos_k=pos_k,
                        causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_sentinel_and_unreferenced_blocks():
    """Poisoning the sentinel block and every unreferenced pool block must
    not move the output by a single bit — the masking (and the kernel's
    block walk) never lets those values in."""
    q, kp, vp, tables, offset = _paged_case(jnp.float32, seed=2)
    out = paged_attention(q, kp, vp, tables, offset)
    live = set()
    for b in range(tables.shape[0]):
        nblk = -(-int(offset[b] + 1) // kp.shape[1])
        live |= set(np.asarray(tables[b, :nblk]).tolist())
    poison = [i for i in range(kp.shape[0]) if i not in (live - {0})]
    kp2 = kp.at[jnp.asarray(poison)].set(1e9)
    vp2 = vp.at[jnp.asarray(poison)].set(1e9)
    out2 = paged_attention(q, kp2, vp2, tables, offset)
    assert np.array_equal(np.asarray(out), np.asarray(out2))
    # table entries past each row's length name poisoned blocks too
    out3 = paged_attention(q, kp2, vp2, _poison_tails(tables, offset,
                                                     kp.shape[1], poison),
                           offset)
    assert np.array_equal(np.asarray(out), np.asarray(out3))


def _poison_tails(tables, offset, bs, poison):
    """``tables`` with every entry past each row's live pages replaced by
    a (non-sentinel) poisoned block."""
    t = np.asarray(tables).copy()
    fill = [i for i in poison if i != 0]
    for b in range(t.shape[0]):
        live = -(-int(offset[b] + 1) // bs)
        t[b, live:] = [fill[j % len(fill)] for j in range(t.shape[1] - live)]
    return jnp.asarray(t)


def test_paged_attention_invariant_to_block_placement():
    """The same logical KV content scattered to different physical blocks
    (scrambled tables) must attend identically."""
    q, kp, vp, tables, offset = _paged_case(jnp.float32, seed=3)
    N, n = kp.shape[0], tables.shape[1]
    out = paged_attention(q, kp, vp, tables, offset)
    perm = np.concatenate([[0], 1 + np.random.default_rng(9).permutation(
        N - 1)]).astype(np.int32)          # sentinel block 0 stays put
    inv = np.argsort(perm).astype(np.int32)
    kp2 = kp[jnp.asarray(inv)]
    vp2 = vp[jnp.asarray(inv)]
    tables2 = jnp.asarray(perm)[tables]
    out2 = paged_attention(q, kp2, vp2, tables2, offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_paged_attention_rejects_multi_token():
    q, kp, vp, tables, offset = _paged_case(jnp.float32)
    q2 = jnp.concatenate([q, q], axis=1)
    with pytest.raises(ValueError, match="decode-only"):
        paged_attention(q2, kp, vp, tables, offset)


# Rows whose live pages span several of the kernel's page groups: the group
# is held to 3 pages (12 tokens), so a 40-page table is 14 groups, the last
# one partial.  Rows: one token; ending on a page edge (5 pages); 4 pages,
# straddling the first group edge; the whole table; and a masked row (all
# sentinel, the engine's placeholder length), which must stream nothing.
_MG_OFFSETS = (0, 19, 13, 159, 159)
_MG_MASKED = 4


def _multi_group_case(dtype, seed=0, n=40, N=120, bs=4, Hkv=2, G=3, d=16):
    rng = np.random.default_rng(seed)
    B = len(_MG_OFFSETS)
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, d)), dtype=dtype)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv, d)), dtype=dtype)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv, d)), dtype=dtype)
    tables = np.zeros((B, n), np.int32)
    offset = np.asarray(_MG_OFFSETS, np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b in range(B):
        if b != _MG_MASKED:
            live = -(-int(offset[b] + 1) // bs)
            tables[b, :live] = [free.pop() for _ in range(live)]
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(offset)


@pytest.fixture
def three_page_groups(monkeypatch):
    """Hold the GQA kernel's group to 3 pages at the tiny test widths."""
    def use(kp, vp):
        page = kp.shape[1] * kp.shape[2] * (kp.shape[3] + vp.shape[3]) \
            * kp.dtype.itemsize
        monkeypatch.setattr(paged_kernel, "_GROUP_BYTES", 3 * page)
    return use


def _ref_rows(q, kp, vp, tables, offset, **kw):
    B, _, Hq, d = q.shape
    Hkv = kp.shape[2]
    qh = q[:, 0].reshape(B, Hkv, Hq // Hkv, d)
    return paged_attention_ref(qh, kp, vp, tables, offset + 1,
                               scale=1.0 / np.sqrt(d),
                               **kw).reshape(B, 1, Hq, -1)


def test_pages_per_group_follows_page_bytes():
    """~1 MiB of K+V pages a group, at least one, at most the table."""
    phi4 = 16 * 8 * (128 + 128) * 2          # 64 KiB a page
    qwen = 16 * 2 * (128 + 128) * 2          # 16 KiB a page
    assert paged_kernel.pages_per_group(phi4, 256) == 16
    assert paged_kernel.pages_per_group(qwen, 128) == 64
    assert paged_kernel.pages_per_group(qwen, 40) == 40
    assert paged_kernel.pages_per_group(4 << 20, 256) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap",
                         [(None, None), (5, None), (None, 8.0), (6, 4.0)])
def test_paged_attention_multi_group_parity(three_page_groups, dtype,
                                            window, softcap):
    q, kp, vp, tables, offset = _multi_group_case(dtype)
    three_page_groups(kp, vp)
    out = paged_attention(q, kp, vp, tables, offset, window=window,
                          softcap=softcap)
    ref = _ref_rows(q, kp, vp, tables, offset, window=window,
                    softcap=softcap)
    live = [b for b in range(q.shape[0]) if b != _MG_MASKED]
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               **_tol(dtype))
    # the masked row streams nothing and reads zeros, not NaN
    assert not np.asarray(out, np.float32)[_MG_MASKED].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_masked_rows_leave_active_rows_bit_identical(
        three_page_groups, dtype):
    """Active rows read the same bits whether masked rows sit between
    them (which change the order of the kernel's cross-row prefetches and
    what the VMEM buffers held before) or not."""
    q, kp, vp, tables, offset = _multi_group_case(dtype, seed=4)
    three_page_groups(kp, vp)
    out = np.asarray(paged_attention(q, kp, vp, tables, offset), np.float32)
    live = [b for b in range(q.shape[0]) if b != _MG_MASKED]
    idx = jnp.asarray(live)
    alone = paged_attention(q[idx], kp, vp, tables[idx], offset[idx])
    assert np.array_equal(out[live], np.asarray(alone, np.float32))
    # masked rows first, between and last
    order = [_MG_MASKED, live[0], _MG_MASKED, live[1], live[2], _MG_MASKED,
             live[3], _MG_MASKED]
    idx = jnp.asarray(order)
    mixed = np.asarray(paged_attention(q[idx], kp, vp, tables[idx],
                                       offset[idx]), np.float32)
    got = [mixed[i] for i, b in enumerate(order) if b != _MG_MASKED]
    assert np.array_equal(out[live], np.stack(got))


def test_paged_attention_multi_group_ignores_blocks_past_length(
        three_page_groups):
    """Poisoned sentinel, unreferenced blocks and table entries past each
    row's length, across page groups: not one bit moves."""
    q, kp, vp, tables, offset = _multi_group_case(jnp.float32, seed=2)
    three_page_groups(kp, vp)
    out = paged_attention(q, kp, vp, tables, offset)
    live = {int(t) for t in np.asarray(tables).ravel()} - {0}
    poison = [i for i in range(kp.shape[0]) if i not in live]
    kp2 = kp.at[jnp.asarray(poison)].set(1e9)
    vp2 = vp.at[jnp.asarray(poison)].set(1e9)
    out2 = paged_attention(q, kp2, vp2, _poison_tails(tables, offset,
                                                      kp.shape[1], poison),
                           offset)
    assert np.array_equal(np.asarray(out), np.asarray(out2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_attention_parity(dtype):
    rng = np.random.default_rng(5)
    B, n, N, bs, H, r, dr = 3, 4, 9, 4, 4, 24, 8
    qe = jnp.asarray(rng.normal(size=(B, 1, H, r)), dtype=dtype)
    qr = jnp.asarray(rng.normal(size=(B, 1, H, dr)), dtype=dtype)
    ckv = jnp.asarray(rng.normal(size=(N, bs, r)), dtype=dtype)
    krp = jnp.asarray(rng.normal(size=(N, bs, dr)), dtype=dtype)
    tables = jnp.asarray(rng.integers(1, N, size=(B, n)), dtype=jnp.int32)
    tables = tables.at[2, 1:].set(0)
    offset = jnp.asarray([13, 6, 2], jnp.int32)
    scale = 1.0 / np.sqrt(32 + dr)
    out = paged_mla_attention(qe, qr, ckv, krp, tables, offset, scale=scale)
    ref = paged_mla_attention_ref(qe[:, 0], qr[:, 0], ckv, krp,
                                  tables, offset + 1, scale=scale)[:, None]
    assert out.shape == (B, 1, H, r)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    # sentinel poisoning is invisible through the latent pools too
    live = {int(t) for b in range(B)
            for t in np.asarray(tables[b, :-(-int(offset[b] + 1) // bs)])}
    poison = [i for i in range(N) if i not in (live - {0})]
    out2 = paged_mla_attention(qe, qr, ckv.at[jnp.asarray(poison)].set(1e9),
                               krp.at[jnp.asarray(poison)].set(1e9),
                               tables, offset, scale=scale)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(out2, np.float32))


def _mla_multi_group_case(dtype, seed=0, n=40, N=120, bs=4, H=4, r=24,
                          dr=8):
    """The GQA multi-group rows (``_MG_OFFSETS``) over latent pools."""
    rng = np.random.default_rng(seed)
    B = len(_MG_OFFSETS)
    qe = jnp.asarray(rng.normal(size=(B, 1, H, r)), dtype=dtype)
    qr = jnp.asarray(rng.normal(size=(B, 1, H, dr)), dtype=dtype)
    ckv = jnp.asarray(rng.normal(size=(N, bs, r)), dtype=dtype)
    kr = jnp.asarray(rng.normal(size=(N, bs, dr)), dtype=dtype)
    tables = np.zeros((B, n), np.int32)
    offset = np.asarray(_MG_OFFSETS, np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b in range(B):
        if b != _MG_MASKED:
            live = -(-int(offset[b] + 1) // bs)
            tables[b, :live] = [free.pop() for _ in range(live)]
    return qe, qr, ckv, kr, jnp.asarray(tables), jnp.asarray(offset)


@pytest.fixture
def three_mla_page_groups(monkeypatch):
    """Hold the MLA kernel's group to 3 pages at the tiny test widths."""
    def use(ckv, kr):
        page = ckv.shape[1] * (ckv.shape[2] + kr.shape[2]) \
            * ckv.dtype.itemsize
        monkeypatch.setattr(paged_kernel, "_GROUP_BYTES", 3 * page)
    return use


_MLA_SCALE = 1.0 / np.sqrt(32 + 8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_attention_multi_group_parity(three_mla_page_groups,
                                                dtype):
    """Rows of one token, ending on a page edge, straddling a group edge,
    filling the whole table (14 groups, the last partial), and an
    all-sentinel masked row, which streams nothing and reads zeros."""
    qe, qr, ckv, kr, tables, offset = _mla_multi_group_case(dtype)
    three_mla_page_groups(ckv, kr)
    out = paged_mla_attention(qe, qr, ckv, kr, tables, offset,
                              scale=_MLA_SCALE)
    ref = paged_mla_attention_ref(qe[:, 0], qr[:, 0], ckv, kr, tables,
                                  offset + 1, scale=_MLA_SCALE)[:, None]
    live = [b for b in range(qe.shape[0]) if b != _MG_MASKED]
    assert out.dtype == qe.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               **_tol(dtype))
    assert not np.asarray(out, np.float32)[_MG_MASKED].any()


def test_paged_mla_attention_multi_group_ignores_blocks_past_length(
        three_mla_page_groups):
    """Poisoned sentinel, unreferenced blocks and table entries past each
    row's length, across page groups: not one bit moves, and masked rows
    between live ones change nothing either."""
    qe, qr, ckv, kr, tables, offset = _mla_multi_group_case(jnp.float32,
                                                            seed=2)
    three_mla_page_groups(ckv, kr)
    out = paged_mla_attention(qe, qr, ckv, kr, tables, offset,
                              scale=_MLA_SCALE)
    live = {int(t) for t in np.asarray(tables).ravel()} - {0}
    poison = [i for i in range(ckv.shape[0]) if i not in live]
    ckv2 = ckv.at[jnp.asarray(poison)].set(1e9)
    kr2 = kr.at[jnp.asarray(poison)].set(1e9)
    out2 = paged_mla_attention(qe, qr, ckv2, kr2,
                               _poison_tails(tables, offset, ckv.shape[1],
                                             poison),
                               offset, scale=_MLA_SCALE)
    assert np.array_equal(np.asarray(out), np.asarray(out2))
    order = [_MG_MASKED, 0, _MG_MASKED, 1, 2, 3, _MG_MASKED]
    idx = jnp.asarray(order)
    mixed = np.asarray(paged_mla_attention(
        qe[idx], qr[idx], ckv, kr, tables[idx], offset[idx],
        scale=_MLA_SCALE))
    got = np.stack([mixed[i] for i, b in enumerate(order)
                    if b != _MG_MASKED])
    assert np.array_equal(np.asarray(out)[:4], got)


def test_mla_pages_per_group_follows_page_bytes():
    """DeepSeek-V3's latent page: 16 tokens of c_kv (512) and the rotary
    key (64, kept in a 128-lane tile) in bf16, 20 KiB, so 51 pages (816
    tokens) a 1 MiB group."""
    page = 16 * (512 + 128) * 2
    assert paged_kernel.pages_per_group(page, 512) == 51


# ------------------------------------------------- codec-fused tree sum --


@pytest.mark.parametrize("codec", ["none", "bf16", "int8"])
@pytest.mark.parametrize("n,d", [(2, 128), (6, 384), (16, 512)])
def test_coded_tree_reduce_parity(codec, n, d):
    """Fused dequant+reduce == decode rows, then the plain tree_reduce
    (same H-tree order; int8 may differ by an FMA ulp)."""
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype=jnp.float32)
    wire = encode_rows(x, codec)
    out = coded_tree_reduce(wire, codec)
    if codec == "int8":
        rows = (wire["q"].astype(jnp.float32)
                * wire["scale"]).reshape(n, d)
    else:
        rows = wire["x"].astype(jnp.float32)
    ref = tree_reduce(rows)
    assert out.dtype == jnp.float32 and out.shape == (d,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("codec", ["bf16", "int8"])
@pytest.mark.parametrize("m", [1024, 640])
def test_decode_add_fused_matches_unfused(codec, m):
    """The fused receive-side accumulate == keep + codec.decode(wire), and
    with default dispatch (off-TPU) it IS that expression bit for bit.
    m=640 is neither a multiple of the bf16 block nor of the int8 row tile,
    so the kernels' padding is exercised too."""
    rng = np.random.default_rng(11)
    keep = jnp.asarray(rng.normal(size=(m,)), dtype=jnp.float32)
    send = jnp.asarray(rng.normal(size=(m,)), dtype=jnp.float32)
    c = CODECS[codec]
    wire = c.encode(send)
    plain = keep + c.decode(wire, keep.shape, keep.dtype)
    fused = decode_add(keep, wire, c, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                               rtol=1e-6, atol=1e-6)
    if jax.default_backend() != "tpu":
        assert np.array_equal(np.asarray(decode_add(keep, wire, c)),
                              np.asarray(plain))
