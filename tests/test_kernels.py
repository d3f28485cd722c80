"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles,
executed in interpret mode (kernel body runs in Python on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as KV
from repro.core.continuous import page_work
from repro.kernels import decode_attention as DA
from repro.kernels import flash_attention as FA
from repro.kernels import ops as KOPS
from repro.kernels import ref as R
from repro.kernels import rmsnorm as RN
from repro.models import layers as LY


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype != jnp.float32 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,D,window,cap",
    [
        (1, 128, 128, 4, 4, 64, None, None),        # MHA
        (2, 256, 256, 8, 2, 64, None, None),        # GQA 4:1
        (2, 128, 256, 4, 1, 128, None, None),       # MQA, Sq != Sk
        (1, 256, 256, 4, 2, 64, 64, None),          # sliding window
        (1, 128, 128, 2, 2, 64, None, 50.0),        # softcap (gemma2)
        (2, 128, 128, 6, 2, 32, 32, 30.0),          # window + cap
        (1, 384, 384, 4, 4, 96, None, None),        # phi3 head dim
    ])
def test_flash_attention_sweep(rng, B, Sq, Sk, Hq, Hkv, D, window, cap,
                               dtype):
    q = _rand(rng, (B, Sq, Hq, D), dtype)
    k = _rand(rng, (B, Sk, Hkv, D), dtype)
    v = _rand(rng, (B, Sk, Hkv, D), dtype)
    qp = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
    assert FA.shape_supported(q, k)
    out = FA.flash_attention(q, k, v, qp, kp, window=window, scale=D ** -0.5,
                             attn_softcap=cap, interpret=True)
    ref = R.flash_attention_ref(q, k, v, qp, kp, window=window,
                                scale=D ** -0.5, attn_softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_ragged_positions(rng):
    """Invalid (-1) key positions — ragged batches / ring caches."""
    B, S, H, D = 2, 256, 4, 64
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, H, D), jnp.float32)
    v = _rand(rng, (B, S, H, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kp = jnp.where(pos % 5 == 2, -1, pos)
    out = FA.flash_attention(q, k, v, pos, kp, window=None, scale=D ** -0.5,
                             interpret=True)
    ref = R.flash_attention_ref(q, k, v, pos, kp, window=None,
                                scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sk,Hq,Hkv,D,Dv,window,cap",
    [
        (2, 256, 4, 4, 64, 64, None, None),
        (3, 512, 8, 2, 64, 64, None, None),
        (1, 256, 16, 4, 128, 128, 128, None),
        (2, 256, 4, 2, 64, 64, None, 50.0),
        (1, 512, 8, 8, 192, 128, None, None),        # MLA-ish Dv != D
    ])
def test_decode_attention_sweep(rng, B, Sk, Hq, Hkv, D, Dv, window, cap,
                                dtype):
    q = _rand(rng, (B, 1, Hq, D), dtype)
    k = _rand(rng, (B, Sk, Hkv, D), dtype)
    v = _rand(rng, (B, Sk, Hkv, Dv), dtype)
    kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
    kp = jnp.where(kp % 9 == 5, -1, kp)               # holes (ring dump)
    qp = jnp.asarray(np.stack([np.full(1, Sk - 1 - 7 * b) for b in range(B)]),
                     jnp.int32)
    assert DA.shape_supported(q, k)
    out = DA.decode_attention(q, k, v, kp, qp, window=window,
                              scale=D ** -0.5, attn_softcap=cap,
                              interpret=True)
    ref = R.decode_attention_ref(q, k, v, kp, qp, window=window,
                                 scale=D ** -0.5, attn_softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,P,page,npages,Hq,Hkv,D,Dv,window,cap",
    [
        (2, 9, 16, 4, 4, 4, 64, 64, None, None),      # MHA
        (3, 13, 32, 3, 8, 2, 64, 64, None, None),     # GQA 4:1
        (2, 9, 16, 4, 16, 4, 128, 128, 24, None),     # GQA + window
        (2, 9, 16, 4, 4, 2, 64, 64, None, 50.0),      # softcap (gemma2)
        (1, 7, 16, 4, 6, 2, 32, 32, 20, 30.0),        # window + cap
        (1, 9, 32, 3, 8, 8, 192, 128, None, None),    # MLA-ish Dv != D
    ])
def test_paged_decode_attention_sweep(rng, B, P, page, npages, Hq, Hkv, D,
                                      Dv, window, cap, dtype):
    """Paged kernel vs the dense-gather oracle: random block tables with
    unallocated holes, ring-style partial pages, per-slot query positions."""
    kpool = _rand(rng, (P, page, Hkv, D), dtype)
    vpool = _rand(rng, (P, page, Hkv, Dv), dtype)
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    perm = rng.permutation(P - 1)           # page P-1 stays the dump page
    q_pos = np.zeros((B, 1), np.int32)
    next_page = 0
    for b in range(B):
        ctx = int(rng.integers(1, npages * page))
        q_pos[b, 0] = ctx - 1
        used = -(-ctx // page)
        bt[b, :used] = perm[next_page:next_page + used]
        next_page += used
        for t in range(ctx):
            ppos[bt[b, t // page], t % page] = t
    q = _rand(rng, (B, 1, Hq, D), dtype)
    assert DA.paged_shape_supported(q, kpool, jnp.asarray(bt))
    out = DA.paged_decode_attention(q, kpool, vpool, jnp.asarray(ppos),
                                    jnp.asarray(bt), jnp.asarray(q_pos),
                                    window=window, scale=D ** -0.5,
                                    attn_softcap=cap, interpret=True)
    ref = R.paged_decode_attention_ref(q, kpool, vpool, jnp.asarray(ppos),
                                       jnp.asarray(bt), jnp.asarray(q_pos),
                                       window=window, scale=D ** -0.5,
                                       attn_softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    assert np.abs(np.asarray(out, np.float32)
                  - np.asarray(ref, np.float32)).max() <= 1e-2


def test_paged_decode_matches_dense_decode(rng):
    """The paged kernel over a scattered pool == the dense decode kernel
    over the equivalent contiguous cache."""
    B, P, page, npages, H, D = 2, 9, 32, 4, 4, 64
    kpool = _rand(rng, (P, page, H, D), jnp.float32)
    vpool = _rand(rng, (P, page, H, D), jnp.float32)
    ppos = np.full((P, page), -1, np.int32)
    bt = np.asarray([[3, 0, 6, -1], [5, 2, -1, -1]], np.int32)
    q_pos = np.asarray([[100], [50]], np.int32)
    for b in range(B):
        for t in range(int(q_pos[b, 0]) + 1):
            if t // page < npages and bt[b, t // page] >= 0:
                ppos[bt[b, t // page], t % page] = t
    q = _rand(rng, (B, 1, H, D), jnp.float32)
    out = DA.paged_decode_attention(q, kpool, vpool, jnp.asarray(ppos),
                                    jnp.asarray(bt), jnp.asarray(q_pos),
                                    window=None, scale=D ** -0.5,
                                    interpret=True)
    # densify: gather pages into (B, npages*page, H, D)
    safe = np.where(bt >= 0, bt, P - 1)
    kd = jnp.asarray(np.asarray(kpool)[safe].reshape(B, npages * page, H, D))
    vd = jnp.asarray(np.asarray(vpool)[safe].reshape(B, npages * page, H, D))
    kp = np.where(bt[..., None] >= 0, np.asarray(ppos)[safe], -1)
    kp = jnp.asarray(kp.reshape(B, npages * page))
    ref = DA.decode_attention(q, kd, vd, kp, jnp.asarray(q_pos),
                              window=None, scale=D ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Live-page bound: each item streams only the pages its queries can see
# ---------------------------------------------------------------------------

EDGE_PAGE, EDGE_NPAGES = 16, 4           # max_len 64


def _edge_layout(rng, window):
    """Block tables, stored positions and query positions that put the
    live-page bound at its edges.  Without a window: contexts of 1, 15,
    16, 17 and max_len tokens; an empty slot (no pages); an inactive
    fused-decode lane (pages allocated, its query position ``lens`` never
    written); and a slot whose allocated pages past the bound, and the
    rest of its last live page, hold stale entries beyond its query.
    With a window: ring layers past and short of their ring length,
    beside unwritten pages the full-attention layers would fill."""
    P = 40
    ppos = np.full((P, EDGE_PAGE), -1, np.int32)
    free = iter(rng.permutation(P - 1))   # page P-1 stays the dump page
    rows, q_pos = [], []

    def row(n_pages):
        bt = np.full(EDGE_NPAGES, -1, np.int32)
        bt[:n_pages] = [next(free) for _ in range(n_pages)]
        rows.append(bt)
        return bt

    def store(bt, positions, ring_len=EDGE_NPAGES * EDGE_PAGE):
        for p in positions:
            rp = p % ring_len
            ppos[bt[rp // EDGE_PAGE], rp % EDGE_PAGE] = p

    max_len = EDGE_NPAGES * EDGE_PAGE
    if window is None:
        for ctx in (1, 15, 16, 17, max_len):
            store(row(-(-ctx // EDGE_PAGE)), range(ctx))
            q_pos.append(ctx - 1)
        row(0)                                  # empty slot
        q_pos.append(5)
        store(row(2), range(21))                # inactive lane at lens 21
        q_pos.append(21)
        bt = row(EDGE_NPAGES)                   # stale entries past q_pos
        store(bt, range(20))
        ppos[bt[1], 4:] = np.arange(28, 40)
        ppos[bt[2]] = np.arange(40, 56)
        ppos[bt[3]] = np.arange(56, 72)
        q_pos.append(19)
    else:
        ring_len = KV.paged_ring_len(window, EDGE_PAGE, EDGE_NPAGES)
        for q in (70, 25, ring_len, 3):
            store(row(EDGE_NPAGES), range(max(0, q - ring_len + 1), q + 1),
                  ring_len)
            q_pos.append(q)
    return P, np.stack(rows), np.asarray(q_pos, np.int32), ppos


@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("W", [1, 3])
@pytest.mark.parametrize("pool", ["fp", "q8"])
def test_paged_kernels_live_bound_vs_oracle(rng, pool, W, window):
    """The decode (W == 1) and mixed (W == 3: each row's query window
    ends at its query position) kernels against the dense-gather oracle
    on the live-bound edge layouts, in both pool dtypes."""
    Hq, Hkv, D = 4, 2, 32
    P, bt, q_last, ppos = _edge_layout(rng, window)
    B = bt.shape[0]
    k = jnp.asarray(rng.normal(size=(P, EDGE_PAGE, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, EDGE_PAGE, Hkv, D)), jnp.float32)
    q_pos = q_last[:, None] - np.arange(W)[::-1][None, :]
    q_pos = np.where(q_pos >= 0, q_pos, -1).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, W, Hq, D)), jnp.float32)
    args = (jnp.asarray(ppos), jnp.asarray(bt), jnp.asarray(q_pos))
    kw = dict(window=window, scale=D ** -0.5)
    if pool == "q8":
        (kq, ks), (vq, vs) = KV.quantize_kv(k), KV.quantize_kv(v)
        fn = DA.paged_decode_attention_q8 if W == 1 \
            else DA.paged_mixed_attention_q8
        out = fn(q, kq, ks, vq, vs, *args, interpret=True, **kw)
        ref = R.paged_mixed_attention_ref(q, kq, vq, *args, k_scale=ks,
                                          v_scale=vs, **kw)
    else:
        fn = DA.paged_decode_attention if W == 1 \
            else DA.paged_mixed_attention
        out = fn(q, k, v, *args, interpret=True, **kw)
        ref = R.paged_mixed_attention_ref(q, k, v, *args, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if window is None:                   # the empty slot comes back zero
        assert not np.asarray(out[5]).any()


def test_live_pages_hand_count():
    """n_live against a hand count: decode rows at page edges, a row past
    max_len, a query-less row, an empty slot; packed windows of
    packed_meta_table, padding rows included."""
    page, npages = 16, 4
    q = jnp.asarray([0, 14, 15, 16, 17, 31, 32, 63, 64, 200, -1],
                    jnp.int32)
    want = [1, 1, 1, 2, 2, 2, 3, 4, 4, 4, 0]
    assert DA.live_pages(q, page, npages).tolist() == want
    # decode rows: the query position, none for a row with no first page
    bt = np.zeros((4, npages), np.int32)
    bt[2] = -1
    assert DA._row_live_pages(jnp.asarray([[15], [16], [40], [-1]]),
                              jnp.asarray(bt), page).tolist() \
        == [1, 2, 0, 0]
    # mixed rows: the window's largest query position (-1 = padding)
    assert DA._row_live_pages(jnp.asarray([[30, 31, 32], [5, -1, -1]]),
                              jnp.asarray(bt[:2]), page).tolist() == [3, 1]
    # packed: a decode lane at 15, a 10-token chunk from 12 (windows of
    # 8 and 2 lanes, ending at 19 and 21), a decode lane at 16; T = 24
    # and 8 work rows, the last 4 unused
    meta = DA.packed_meta_table([0, 1, 11], [1, 10, 1], [0, 1, 2], 24, 8)
    qp = np.full((1, 24), -1, np.int32)
    qp[0, 0] = 15
    qp[0, 1:11] = np.arange(12, 22)
    qp[0, 11] = 16
    got = DA.packed_live_pages(jnp.asarray(meta), jnp.asarray(qp), page,
                               npages).tolist()
    assert got == [1, 2, 2, 2, 0, 0, 0, 0]
    # the serve loop's host count agrees with the kernels' bound; the
    # packed kernel steps once per block of live pages (at 3 pages a
    # block, the three 4-page rows take 2, the query-less row none)
    assert page_work(np.asarray(q), page, npages) == (sum(want),
                                                      q.size * npages)
    assert page_work(np.asarray(q), page, npages, 3) == (
        sum(want), sum(-(-n // 3) for n in want))
    assert page_work(np.asarray(q), page, npages, 3)[1] == 13


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("shape", [(4, 256), (2, 64, 512), (1, 8, 128)])
def test_rmsnorm_sweep(rng, shape, dtype):
    x = _rand(rng, shape, dtype)
    w = _rand(rng, shape[-1:], jnp.float32) * 0.1
    assert RN.shape_supported(x)
    out = RN.fused_rmsnorm(x, w, interpret=True)
    ref = R.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,dh,chunk", [
    (1, 128, 2, 16, 128),
    (2, 256, 2, 32, 128),
    (1, 64, 4, 8, 64),        # single chunk
    (2, 512, 1, 64, 128),
])
def test_mlstm_chunk_kernel_sweep(rng, B, S, H, dh, chunk, dtype):
    """4th kernel: chunkwise mLSTM vs the jnp chunked oracle, incl.
    nonzero initial state (prefix continuation)."""
    import jax
    from repro.kernels.mlstm_chunk import mlstm_chunked_kernel
    from repro.models.ssm import mlstm_chunked
    q = _rand(rng, (B, S, H, dh), dtype)
    k = _rand(rng, (B, S, H, dh), dtype)
    v = _rand(rng, (B, S, H, dh), dtype)
    i_pre = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    logf = jax.nn.log_sigmoid(
        jnp.asarray(rng.normal(size=(B, S, H)) + 2, jnp.float32))
    st = {"C": jnp.asarray(rng.normal(size=(B, H, dh, dh)) * 0.1,
                           jnp.float32),
          "n": jnp.asarray(np.abs(rng.normal(size=(B, H, dh))),
                           jnp.float32),
          "m": jnp.zeros((B, H), jnp.float32)}
    h_ref, st_ref = mlstm_chunked(q, k, v, i_pre, logf, st)
    h_k, st_k = mlstm_chunked_kernel(q, k, v, i_pre, logf, st,
                                     chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_ref),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(st_k["m"]),
                               np.asarray(st_ref["m"]), rtol=1e-5,
                               atol=1e-5)


def test_kernel_mode_dispatch(rng):
    """Model attention dispatches to the Pallas kernel in interpret mode
    and produces the same result as the jnp path."""
    from repro.models import layers as L
    B, S, H, D = 1, 128, 4, 64
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, H, D), jnp.float32)
    v = _rand(rng, (B, S, H, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    off = L.mha_attention(q, k, v, pos, pos, window=None, scale=D ** -0.5)
    with KOPS.kernel_mode_ctx("interpret"):
        on = L.mha_attention(q, k, v, pos, pos, window=None, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               rtol=2e-5, atol=2e-5)


def test_kernel_mode_follows_backend():
    """Unpinned, the mode follows JAX's backend: off on the CPU."""
    with KOPS.kernel_mode_ctx(None):
        assert KOPS.kernel_mode() == "off"
    with KOPS.kernel_mode_ctx("interpret"):
        assert KOPS.kernel_mode() == "interpret"
    with pytest.raises(ValueError):
        KOPS.set_kernel_mode("gpu")


def test_kernel_fallback_counted_and_warned_once():
    """With kernels on, a shape the kernel cannot take runs the jnp
    reference, counts one fallback per traced call, and warns once per
    kernel and shape."""
    import warnings
    x = jnp.ones((1, 1), jnp.float32)
    q = jnp.ones((1, 1), jnp.int8)
    s = jnp.ones((1,), jnp.float32)
    KOPS.reset_counts()
    with KOPS.kernel_mode_ctx("tpu"), warnings.catch_warnings(record=True) \
            as caught:
        warnings.simplefilter("always")
        assert KOPS.maybe_quant_matmul(x, q, s) is None
        assert KOPS.maybe_quant_matmul(x, q, s) is None
    assert KOPS.fallback_counts() == {"quant_matmul": 2}
    assert KOPS.kernel_counts() == {}
    assert sum("quant_matmul" in str(w.message) for w in caught) == 1
    KOPS.reset_counts()
    assert KOPS.fallback_counts() == {}


def test_mlstm_dispatch_refuses_tpu_mode(rng):
    """The mLSTM kernel has no TPU lowering: in "tpu" mode its
    dispatcher raises instead of falling back."""
    q = _rand(rng, (1, 128, 2, 16), jnp.float32)
    g = jnp.zeros((1, 128, 2), jnp.float32)
    st = {"C": jnp.zeros((1, 2, 16, 16)), "n": jnp.zeros((1, 2, 16)),
          "m": jnp.zeros((1, 2))}
    with KOPS.kernel_mode_ctx("tpu"), pytest.raises(NotImplementedError):
        KOPS.maybe_mlstm_chunked(q, q, q, g, g, st)


# ---------------------------------------------------------------------------
# Stacked pools: each kernel reads its layer of the whole stack in place
# ---------------------------------------------------------------------------

STACK_LAYERS, STACK_PAGE, STACK_NPAGES = 3, 8, 4


def _stack_setup(rng, pool):
    """Four slots' block tables over a 3-layer stacked pool whose layers
    differ: each holds its own K/V (codes and scales for ``pool`` q8)
    and its own stored positions, a slot's context cut at a different
    length in each layer.  Returns the pool dict of stacked leaves, the
    same pool with its rows widened to whole lanes as the engine makes
    it on a TPU (zeros past the head dim and the heads), the block
    tables and each slot's last query position."""
    L, page, npages = STACK_LAYERS, STACK_PAGE, STACK_NPAGES
    B, P, Hkv, D = 4, 21, 2, 32
    ctx = np.asarray([1, 12, 19, npages * page])
    bt = np.full((B, npages), -1, np.int32)
    free = iter(rng.permutation(P - 1))      # page P-1 stays the dump page
    ppos = np.full((L, P, page), -1, np.int32)
    for b in range(B):
        bt[b, :-(-ctx[b] // page)] = [next(free)
                                      for _ in range(-(-ctx[b] // page))]
        for layer in range(L):
            for t in range(max(ctx[b] - 3 * layer, 0)):
                ppos[layer, bt[b, t // page], t % page] = t
    k = jnp.asarray(rng.normal(size=(L, P, page, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, page, Hkv, D)), jnp.float32)
    stack = {"ppos": jnp.asarray(ppos)}
    if pool == "q8":
        (stack["pk"], stack["pk_scale"]), (stack["pv"], stack["pv_scale"]) \
            = KV.quantize_kv(k), KV.quantize_kv(v)
    else:
        stack["pk"], stack["pv"] = k, v
    wide = {n: a if n == "ppos" else jnp.pad(
        a, [(0, 0)] * (a.ndim - 1) + [(0, 128 - a.shape[-1])])
        for n, a in stack.items()}
    return stack, wide, jnp.asarray(bt), ctx - 1


@pytest.mark.parametrize("layer", range(STACK_LAYERS))
@pytest.mark.parametrize("pool", ["fp", "q8"])
@pytest.mark.parametrize("kind", ["decode", "mixed", "packed"])
def test_paged_kernels_read_their_layer_of_a_stack(rng, kind, pool, layer):
    """Each paged kernel given the whole stacked pool, its rows widened
    to whole lanes, and a layer index, and its jnp fallback
    (``layers.mha_attention_paged[_packed]`` with kernels off), equals
    the oracle run on that layer's slice; the kernel equals itself run
    on the slice, and on the unwidened stack, bit for bit."""
    Hq, D = 4, 32
    narrow, stack, bt, q_last = _stack_setup(rng, pool)
    one = {n: a[layer] for n, a in narrow.items()}
    kw = dict(window=None, scale=D ** -0.5)
    names = ("pk", "pk_scale", "pv", "pv_scale") if pool == "q8" \
        else ("pk", "pv")

    def pools(p):
        return [p[n] for n in names] + [p["ppos"]]

    def ref_kw(p):
        return (dict(k_scale=p["pk_scale"], v_scale=p["pv_scale"])
                if pool == "q8" else {})

    suffix = "_q8" if pool == "q8" else ""
    if kind == "packed":
        seg_len = np.asarray([1, 3, 2, 4])
        T_ = 16
        slot_ids = np.full(T_, -1, np.int32)
        q_pos = np.full(T_, -1, np.int32)
        seg_start = np.concatenate([[0], np.cumsum(seg_len)[:-1]])
        for b, (s, n) in enumerate(zip(seg_start, seg_len)):
            slot_ids[s:s + n] = b
            q_pos[s:s + n] = q_last[b] - n + 1 + np.arange(n)
        meta = jnp.asarray(DA.packed_meta_table(
            seg_start, seg_len, np.arange(4, dtype=np.int32), T_,
            T_ // DA.PACKED_BLOCK_Q + 4))
        q = jnp.asarray(rng.normal(size=(1, T_, Hq, D)), jnp.float32)
        q_pos, slot_ids = jnp.asarray(q_pos[None]), jnp.asarray(slot_ids)
        fn = getattr(DA, "paged_packed_attention" + suffix)

        def run(p, lyr):
            return fn(q, *pools(p), bt, q_pos, meta, lyr, interpret=True,
                      **kw)

        ref = R.paged_packed_attention_ref(
            q, one["pk"], one["pv"], one["ppos"], bt, q_pos, slot_ids,
            **ref_kw(one), **kw)
        with KOPS.kernel_mode_ctx("off"):
            fallback = LY.mha_attention_paged_packed(
                q, stack, bt, q_pos, slot_ids, meta, layer=layer, **kw)
    else:
        W = 1 if kind == "decode" else 3
        q_pos = q_last[:, None] - np.arange(W)[::-1][None, :]
        q_pos = jnp.asarray(np.where(q_pos >= 0, q_pos, -1), jnp.int32)
        q = jnp.asarray(rng.normal(size=(4, W, Hq, D)), jnp.float32)
        fn = getattr(DA, f"paged_{kind}_attention" + suffix)

        def run(p, lyr):
            return fn(q, *pools(p), bt, q_pos, lyr, interpret=True, **kw)

        ref = R.paged_mixed_attention_ref(
            q, one["pk"], one["pv"], one["ppos"], bt, q_pos,
            **ref_kw(one), **kw)
        with KOPS.kernel_mode_ctx("off"):
            fallback = LY.mha_attention_paged(q, stack, bt, q_pos,
                                              layer=layer, **kw)
    out = run(stack, layer)[..., :D]      # V's widened lanes read zero
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(fallback), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for p, lyr in (({n: a[layer] for n, a in stack.items()}, None),
                   (narrow, layer)):
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(run(p, lyr)[..., :D]))
