"""flash_attention + decode_attention kernels: sweeps vs full-softmax oracle,
plus model-level blockwise path (_flash_sdpa) vs plain sdpa equivalence."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.RandomState(3)
TOL = dict(rtol=5e-2, atol=5e-2)


def _qkv(B, S, T, H, KV, hd, dt):
    q = jnp.asarray(RNG.randn(B, S, H, hd), dt)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), dt)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), dt)
    return q, k, v


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 128, 8, 1, 128),     # MQA
])
@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.float32])
def test_flash_attention_sweep(B, S, H, KV, hd, dt):
    q, k, v = _qkv(B, S, S, H, KV, hd, dt)
    y = ops.flash_attention(q, k, v, causal=True, block_q=128,
                            block_kv=128)
    yr = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL)


def test_flash_noncausal():
    q, k, v = _qkv(2, 128, 128, 4, 4, 32, jnp.float32)
    y = ops.flash_attention(q, k, v, causal=False, block_q=128,
                            block_kv=128)
    yr = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


def test_flash_block_shape_invariance():
    """Result must not depend on the BlockSpec tiling."""
    q, k, v = _qkv(1, 256, 256, 4, 4, 64, jnp.float32)
    y1 = ops.flash_attention(q, k, v, block_q=128, block_kv=128)
    y2 = ops.flash_attention(q, k, v, block_q=256, block_kv=128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("kv_len", [1, 65, 128, 255])
def test_decode_attention_kv_len(kv_len):
    B, T, H, KV, hd = 2, 256, 8, 2, 64
    q = jnp.asarray(RNG.randn(B, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    y = ops.decode_attention(q, k, v, jnp.int32(kv_len), block_kv=128)
    yr = ref.decode_attention_ref(q, k, v, jnp.int32(kv_len))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


def test_decode_ignores_stale_cache():
    """Positions >= kv_len must not affect the result (cache garbage)."""
    B, T, H, KV, hd = 1, 128, 4, 4, 32
    q = jnp.asarray(RNG.randn(B, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    y1 = ops.decode_attention(q, k, v, jnp.int32(64), block_kv=128)
    k2 = k.at[:, 64:].set(1e4)
    v2 = v.at[:, 64:].set(-1e4)
    y2 = ops.decode_attention(q, k2, v2, jnp.int32(64), block_kv=128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)


def test_model_flash_vs_plain_sdpa():
    """The model's XLA blockwise path == plain softmax attention."""
    from repro.models.attention import _flash_sdpa, sdpa
    B, S, H, hd = 2, 256, 4, 32
    q = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    yf = _flash_sdpa(q, k, v, causal=True, scale=0.17, block=64)
    yp = sdpa(q, k, v, causal=True, scale=0.17)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yp), rtol=1e-4,
                               atol=1e-4)


def test_model_flash_ragged_tail():
    """T not a multiple of the block: padding + kv_len mask path."""
    from repro.models.attention import _flash_sdpa, sdpa
    B, S, H, hd = 1, 100, 2, 16
    q = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, H, hd), jnp.float32)
    yf = _flash_sdpa(q, k, v, causal=False, scale=0.25, block=64)
    yp = sdpa(q, k, v, causal=False, scale=0.25)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yp), rtol=1e-4,
                               atol=1e-4)


def test_kernel_matches_model_path():
    """Pallas kernel == the model's XLA formulation (same contract)."""
    from repro.models.attention import attention
    B, S, H, KV, hd = 1, 128, 4, 2, 64
    q, k, v = _qkv(B, S, S, H, KV, hd, jnp.float32)
    y_kernel = ops.flash_attention(q, k, v, causal=True, block_q=128,
                                   block_kv=128)
    y_model = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Ragged tails: pad=True pads q/k/v, masks padded keys via kv_len, and
# slices padded query rows back off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,causal", [(100, True), (100, False), (64, True)])
def test_flash_ragged_pad(S, causal):
    q, k, v = _qkv(1, S, S, 4, 2, 32, jnp.float32)
    y = ops.flash_attention(q, k, v, causal=causal, pad=True)
    assert y.shape == q.shape
    yr = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


def test_flash_kv_len_masks_tail():
    """An explicit kv_len < T (prefill against a longer cache) masks."""
    q, k, v = _qkv(1, 128, 256, 4, 4, 32, jnp.float32)
    y = ops.flash_attention(q, k, v, causal=False, kv_len=jnp.int32(200),
                            block_q=128, block_kv=128)
    yr = ref.flash_attention_ref(q, k[:, :200], v[:, :200], causal=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


def test_decode_ragged_cache_pad():
    """A 100-slot (non-128-multiple) cache pads; kv_len masks the tail."""
    B, T, H, KV, hd = 2, 100, 4, 2, 32
    q = jnp.asarray(RNG.randn(B, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    y = ops.decode_attention(q, k, v, jnp.int32(77), pad=True)
    yr = ref.decode_attention_ref(q, k, v, jnp.int32(77))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


# ---------------------------------------------------------------------------
# Per-request kv_len vectors + the block-paged decode variant
# ---------------------------------------------------------------------------

def test_decode_vector_kv_len():
    """A (B,) per-request length vector: each row masks independently."""
    B, T, H, KV, hd = 3, 256, 8, 2, 64
    q = jnp.asarray(RNG.randn(B, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    lens = jnp.asarray([65, 128, 255], jnp.int32)
    y = ops.decode_attention(q, k, v, lens, block_kv=128)
    yr = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    # backward compat: a scalar is every-row broadcast of the vector form
    ys = ops.decode_attention(q, k, v, jnp.int32(65), block_kv=128)
    yv = ops.decode_attention(q, k, v, jnp.full((B,), 65, jnp.int32),
                              block_kv=128)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yv), rtol=1e-6,
                               atol=1e-6)


def test_decode_vector_kv_len_bad_shape_raises():
    import pytest
    B, T, H, KV, hd = 2, 128, 4, 2, 32
    q = jnp.zeros((B, H, hd), jnp.float32)
    k = jnp.zeros((B, T, KV, hd), jnp.float32)
    v = jnp.zeros((B, T, KV, hd), jnp.float32)
    with pytest.raises(ValueError, match="kv_len"):
        ops.decode_attention(q, k, v, jnp.zeros((B, 2), jnp.int32),
                             block_kv=128)


def _paged_case(B, n_prompt_blocks, page, KV, hd, H, dt, seed=11):
    """Pools + shuffled per-request block tables + ragged kv_lens."""
    rng = np.random.RandomState(seed)
    P = B * n_prompt_blocks + 1                  # + the null block 0
    q = jnp.asarray(rng.randn(B, H, hd), dt)
    k_pool = jnp.asarray(rng.randn(P, KV, page, hd), dt)
    v_pool = jnp.asarray(rng.randn(P, KV, page, hd), dt)
    perm = rng.permutation(np.arange(1, P))      # blocks land anywhere
    tables = jnp.asarray(perm.reshape(B, n_prompt_blocks), jnp.int32)
    return q, k_pool, v_pool, tables


@pytest.mark.parametrize("lens", [
    [256, 256],            # aligned full blocks
    [129, 200],            # partial last block
    [1, 255],              # single-key edge + almost-full
])
def test_paged_decode_vs_contiguous(lens):
    """Gathering the table into a contiguous cache and running plain
    decode_attention must match the paged kernel bit-for-tolerance."""
    B, NB, page, H, KV, hd = 2, 2, 128, 4, 2, 32
    q, k_pool, v_pool, tables = _paged_case(B, NB, page, KV, hd, H,
                                            jnp.float32)
    kv_len = jnp.asarray(lens, jnp.int32)
    y = ops.paged_decode_attention(q, k_pool, v_pool, tables, kv_len)
    k = k_pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KV, hd)
    v = v_pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KV, hd)
    yc = ops.decode_attention(q, k, v, kv_len, block_kv=page)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yc), rtol=1e-5,
                               atol=1e-5)
    yr = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, kv_len)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


def test_paged_decode_ignores_unmapped_blocks():
    """Junk in pool blocks outside every table (incl. the null block)
    must never leak into results."""
    B, NB, page, H, KV, hd = 2, 2, 128, 4, 2, 32
    q, k_pool, v_pool, tables = _paged_case(B, NB, page, KV, hd, H,
                                            jnp.float32)
    kv_len = jnp.asarray([200, 129], jnp.int32)
    y1 = ops.paged_decode_attention(q, k_pool, v_pool, tables, kv_len)
    k2 = k_pool.at[0].set(1e4)                   # poison the null block
    v2 = v_pool.at[0].set(-1e4)
    y2 = ops.paged_decode_attention(q, k2, v2, tables, kv_len)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_paged_decode_reads_a_layer_of_the_stack_in_place(dt):
    """The stacked (L, P, KV, page, hd) call at layer i equals the 4-D
    call on pool[i] bit for bit: ragged lengths, tables whose tails
    point at the null block, and other layers' pages never read."""
    L, B, NB, page, H, KV, hd = 3, 3, 3, 128, 4, 2, 32
    rng = np.random.RandomState(5)
    P = B * NB + 1
    q = jnp.asarray(rng.randn(B, H, hd), dt)
    k = jnp.asarray(rng.randn(L, P, KV, page, hd), dt)
    v = jnp.asarray(rng.randn(L, P, KV, page, hd), dt)
    lens = np.asarray([1, 200, 3 * page], np.int32)
    blocks = iter(rng.permutation(np.arange(1, P)))
    tables = np.zeros((B, NB), np.int32)         # tails: the null block
    for b in range(B):
        for j in range(-(-int(lens[b]) // page)):
            tables[b, j] = next(blocks)
    tables, kv_len = jnp.asarray(tables), jnp.asarray(lens)
    for i in range(L):
        y = ops.paged_decode_attention(q, k, v, tables, kv_len,
                                       jnp.int32(i))
        y4 = ops.paged_decode_attention(q, k[i], v[i], tables, kv_len)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y4))
        yr = ref.paged_decode_attention_ref(q, k[i], v[i], tables, kv_len)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_paged_decode_latent_mode(dt):
    """Latent mode (absorbed multi-head latent attention): one KV head
    whose keys are 128-wide latent rows followed by a 64-wide rope key
    kept transposed in a pool of its own, the values the latent rows
    themselves; 8 query heads, a custom scale; ragged last pages, a
    stacked pool read at a layer, null-block tails — against the gather
    oracle."""
    L, B, NB, page, H, R, rope = 2, 3, 3, 128, 8, 128, 64
    rng = np.random.RandomState(7)
    P = B * NB + 1
    q = jnp.asarray(rng.randn(B, H, R + rope), dt)
    ckv = jnp.asarray(rng.randn(L, P, 1, page, R), dt)
    kpe = jnp.asarray(rng.randn(L, P, 1, rope, page), dt)
    lens = np.asarray([1, 130, 3 * page - 5], np.int32)
    blocks = iter(rng.permutation(np.arange(1, P)))
    tables = np.zeros((B, NB), np.int32)         # tails: the null block
    for b in range(B):
        for j in range(-(-int(lens[b]) // page)):
            tables[b, j] = next(blocks)
    tables, kv_len = jnp.asarray(tables), jnp.asarray(lens)
    scale = 1.59 / np.sqrt(R + rope)
    tol = 1e-4 if dt == jnp.float32 else 2e-2
    for i in range(L):
        y = ops.paged_decode_attention(q, ckv, None, tables, kv_len,
                                       jnp.int32(i), k_rope_pool=kpe,
                                       scale=scale)
        assert y.shape == (B, H, R)
        yr = ref.paged_decode_attention_ref(q, ckv[i], None, tables, kv_len,
                                            k_rope_pool=kpe[i], scale=scale)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=tol, atol=tol)
    # the rope keys count: zeroing them moves the result as the oracle
    # says, so the kernel neither ignores nor misplaces them
    y0 = ops.paged_decode_attention(q, ckv, None, tables, kv_len,
                                    jnp.int32(1), k_rope_pool=kpe * 0,
                                    scale=scale)
    yr0 = ref.paged_decode_attention_ref(q, ckv[1], None, tables, kv_len,
                                         k_rope_pool=kpe[1] * 0, scale=scale)
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(yr0, np.float32), rtol=tol,
                               atol=tol)
    assert np.abs(np.asarray(y0, np.float32) - np.asarray(y, np.float32)
                  ).max() > 10 * tol


def _nan_tailed_case(B, NB, page, lens, seed):
    """Pools whose live pages are random and whose every other block is
    NaN, and two tables: ``tables`` points each request's tail past its
    last live page at NaN blocks, ``clean`` at the finite null block 0."""
    rng = np.random.RandomState(seed)
    n_live = [-(-n // page) for n in lens]
    P = 1 + sum(n_live) + B * NB                 # null + live + NaN tails
    live = rng.permutation(np.arange(1, 1 + sum(n_live)))
    tables = np.zeros((B, NB), np.int32)
    clean = np.zeros((B, NB), np.int32)
    nan_blocks = iter(range(1 + sum(n_live), P))
    at = 0
    for b in range(B):
        clean[b, :n_live[b]] = tables[b, :n_live[b]] = \
            live[at:at + n_live[b]]
        at += n_live[b]
        for j in range(n_live[b], NB):
            tables[b, j] = next(nan_blocks)
    is_nan = np.zeros(P, bool)
    is_nan[1 + sum(n_live):] = True
    return rng, P, is_nan, jnp.asarray(tables), jnp.asarray(clean)


#: lengths of 1, a page edge, mid-page and the full table (3 pages of 128)
_EDGE_LENS = [1, 128, 200, 384]


@pytest.mark.parametrize("kv_step", ["planned", 1])
@pytest.mark.parametrize("KV,G", [(1, 8), (2, 4), (4, 7), (8, 4)])
def test_paged_decode_reads_no_dead_page(KV, G, kv_step):
    """Table slots past each request's last live page point at blocks of
    NaN: the kernel — all KV heads of a page a grid step as planned, or
    one — equals the reference over a table whose tails are finite, and
    is finite, so no dead page enters the result."""
    B, NB, page, hd = len(_EDGE_LENS), 3, 128, 32
    rng, P, is_nan, tables, clean = _nan_tailed_case(
        B, NB, page, _EDGE_LENS, seed=KV)
    q = jnp.asarray(rng.randn(B, KV * G, hd), jnp.float32)
    shape = (P, KV, page, hd)
    k = np.where(is_nan[:, None, None, None], np.nan, rng.randn(*shape))
    v = np.where(is_nan[:, None, None, None], np.nan, rng.randn(*shape))
    k, v = jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32)
    kv_len = jnp.asarray(_EDGE_LENS, jnp.int32)
    from repro.kernels import plan_for
    pin = {} if kv_step == "planned" else {"kv_step": kv_step}
    plan = plan_for("paged_decode_attention",
                    {"B": B, "T": NB * page, "H": KV * G, "KV": KV,
                     "hd": hd, "page": page}, dtype=q.dtype, **pin)
    assert plan.blocks["kv_step"] == (KV if kv_step == "planned"
                                      else kv_step)
    y = ops.paged_decode_attention(q, k, v, tables, kv_len, plan=plan)
    yr = ref.paged_decode_attention_ref(q, k, v, clean, kv_len)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


def test_paged_decode_latent_mode_reads_no_dead_page():
    """The latent mode takes the same clamp: NaN pages in both pools'
    table tails stay out of the result."""
    B, NB, page, H, R, rope = len(_EDGE_LENS), 3, 128, 8, 128, 64
    rng, P, is_nan, tables, clean = _nan_tailed_case(
        B, NB, page, _EDGE_LENS, seed=9)
    q = jnp.asarray(rng.randn(B, H, R + rope), jnp.float32)
    ckv = np.where(is_nan[:, None, None, None], np.nan,
                   rng.randn(P, 1, page, R))
    kpe = np.where(is_nan[:, None, None, None], np.nan,
                   rng.randn(P, 1, rope, page))
    ckv, kpe = jnp.asarray(ckv, jnp.float32), jnp.asarray(kpe, jnp.float32)
    kv_len = jnp.asarray(_EDGE_LENS, jnp.int32)
    scale = 1.0 / np.sqrt(R + rope)
    y = ops.paged_decode_attention(q, ckv, None, tables, kv_len,
                                   k_rope_pool=kpe, scale=scale)
    yr = ref.paged_decode_attention_ref(q, ckv, None, clean, kv_len,
                                        k_rope_pool=kpe, scale=scale)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("latent", [False, True])
def test_paged_decode_table_repeats_the_last_live_page(latent,
                                                       monkeypatch):
    """The table the kernel's index maps read sends every grid step
    past a request's last live page to that page, and leaves the live
    pages as they are: dead steps repeat a block index, so the TPU
    pipeline copies nothing for them.  Interpret mode cannot show a copy
    that is never made, so this reads the table operand the kernel is
    handed."""
    import jax
    from repro.kernels import decode_attention
    seen = []
    real = decode_attention.pl.pallas_call

    def spy(*a, **kw):
        call = real(*a, **kw)

        def run(tables, *operands):
            seen.append(np.asarray(tables))
            return call(tables, *operands)
        return run

    monkeypatch.setattr(decode_attention.pl, "pallas_call", spy)
    page, NB, H, hd = 128, 4, 4, 128
    lens = np.asarray([0, 1, 128, 129, 300, 512], np.int32)
    B = len(lens)
    tables = np.arange(1, 1 + B * NB, dtype=np.int32).reshape(B, NB)
    P = 1 + B * NB
    q = jnp.zeros((B, H, hd + 64 * latent), jnp.float32)
    if latent:
        pools = (jnp.zeros((P, 1, page, hd)), None)
        kw = {"k_rope_pool": jnp.zeros((P, 1, 64, page))}
    else:
        pools, kw = (jnp.zeros((P, 2, page, hd)),) * 2, {}
    with jax.disable_jit():                  # concrete operands
        ops.paged_decode_attention(q, *pools, jnp.asarray(tables),
                                   jnp.asarray(lens), **kw)
    got, = seen
    for b, n in enumerate(lens):
        last = max(-(-int(n) // page) - 1, 0)
        assert list(got[b]) == [tables[b, min(j, last)] for j in range(NB)]


def test_paged_decode_page_block_mismatch_raises():
    """A plan whose block_kv != the pool page is a geometry bug: raise."""
    import pytest
    from repro.kernels import plan_for
    B, NB, page, H, KV, hd = 1, 1, 128, 4, 2, 32
    q, k_pool, v_pool, tables = _paged_case(B, NB, page, KV, hd, H,
                                            jnp.float32)
    plan = plan_for("paged_decode_attention",
                    {"B": B, "T": 256, "H": H, "KV": KV, "hd": hd,
                     "page": 256})
    with pytest.raises(ValueError, match="page"):
        ops.paged_decode_attention(q, k_pool, v_pool, tables,
                                   jnp.asarray([100], jnp.int32), plan=plan)


# ---------------------------------------------------------------------------
# Tiling contract: misalignment raises instead of silently clamping
# ---------------------------------------------------------------------------

def test_flash_sub128_block_raises():
    """block_q=64 used to be clamp-accepted; now a non-MXU block raises."""
    q, k, v = _qkv(1, 128, 128, 4, 4, 64, jnp.float32)
    with pytest.raises(ValueError, match="block_q=64"):
        ops.flash_attention(q, k, v, block_q=64, block_kv=128)


def test_flash_sub128_seq_raises():
    q, k, v = _qkv(1, 64, 64, 4, 4, 64, jnp.float32)
    with pytest.raises(ValueError, match="S=64"):
        ops.flash_attention(q, k, v)


def test_decode_non_divisible_block_raises():
    B, T, H, KV, hd = 1, 256, 4, 4, 32
    q = jnp.zeros((B, H, hd), jnp.float32)
    k = jnp.zeros((B, T, KV, hd), jnp.float32)
    v = jnp.zeros((B, T, KV, hd), jnp.float32)
    with pytest.raises(ValueError, match="T=256"):
        ops.decode_attention(q, k, v, jnp.int32(7), block_kv=384)
