"""Op parity tests: flash attention / rmsnorm / rope vs reference math.

The pallas kernels compile only on TPU; on the CPU test platform the
dispatcher uses the blockwise-jnp path, which shares the exact online-softmax
math with the kernel — these tests pin that math (and gradients) against the
O(S^2) oracle. The kernel itself is additionally exercised in interpret mode
for one small case.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops.attention import (
    flash_attention, reference_attention, _blockwise_forward, _pallas_forward,
)
from tony_tpu.ops.rmsnorm import rms_norm, _rms_reference
from tony_tpu.ops.rope import apply_rope, rope_frequencies


def _qkv(b=2, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    q, k, v = _qkv(s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


def test_flash_non_divisible_uses_small_blocks():
    # seq shorter than the default block: block size clamps to seq
    q, k, v = _qkv(s=64)
    out = flash_attention(q, k, v, True)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_non_divisible_long_length_pads(causal):
    """Lengths > block that don't divide it go through the pad+mask path,
    including gradients."""
    q, k, v = _qkv(b=1, s=192)
    out = flash_attention(q, k, v, causal)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal) ** 2))(q)
    g2 = jax.grad(
        lambda q: jnp.sum(reference_attention(q, k, v, causal) ** 2))(q)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


def test_pallas_kernel_interpret_mode():
    """Run the actual pallas kernel (interpreted on CPU) against the oracle."""
    q, k, v = _qkv(b=1, h=2, s=128, d=64)
    out, lse = _pallas_forward(q, k, v, causal=True, sm_scale=64 ** -0.5,
                               block_q=64, block_k=64, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # lse finite and ordered sanely
    assert np.isfinite(np.asarray(lse)).all()


def test_blockwise_forward_lse():
    q, k, v = _qkv(s=128)
    out, lse = _blockwise_forward(q, k, v, False, 64 ** -0.5, 64)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, v * 0 + k) * 64 ** -0.5
    ref_lse = jax.nn.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(lse, ref_lse, atol=1e-4, rtol=1e-4)


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True)
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=3e-2,
                               rtol=3e-2)


def test_rms_norm_matches_reference_and_grads():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0
    np.testing.assert_allclose(rms_norm(x, w), _rms_reference(x, w, 1e-6),
                               atol=1e-6, rtol=1e-5)

    def loss(x, w):
        return jnp.sum(rms_norm(x, w) ** 2)

    def loss_ref(x, w):
        return jnp.sum(_rms_reference(x, w, 1e-6) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gx, gx_r, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(gw, gw_r, atol=1e-4, rtol=1e-4)


def test_rope_properties():
    cos, sin = rope_frequencies(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 128, 64))
    y = apply_rope(x, cos, sin)
    # norm-preserving per pair
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
        atol=1e-4, rtol=1e-4)
    # position 0 is identity
    np.testing.assert_allclose(y[:, :, 0], x[:, :, 0], atol=1e-5)
    # explicit positions reproduce the default
    pos = jnp.arange(128)
    y2 = apply_rope(x, cos, sin, positions=pos)
    np.testing.assert_allclose(y, y2, atol=1e-6)
    # batched (B, S) positions align with the batch dim, not heads
    xb = x[:2]
    pos_b = jnp.stack([jnp.arange(128), jnp.arange(10, 138)])
    yb = apply_rope(xb, cos[:256] if cos.shape[0] >= 138 else
                    rope_frequencies(64, 256)[0],
                    rope_frequencies(64, 256)[1], positions=pos_b)
    y_row0 = apply_rope(xb[:1], *rope_frequencies(64, 256),
                        positions=jnp.arange(128))
    np.testing.assert_allclose(yb[0], y_row0[0], atol=1e-6)
    # relative-position property: dot(q_m, k_n) depends only on m - n
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 64))
    qk = []
    for m, n in [(5, 3), (105, 103)]:
        qm = apply_rope(q, cos, sin, positions=jnp.array([m]))
        kn = apply_rope(k, cos, sin, positions=jnp.array([n]))
        qk.append(float(jnp.sum(qm * kn)))
    assert abs(qk[0] - qk[1]) < 1e-3


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_kernels_match_blockwise(causal):
    """The TPU backward kernels (interpret mode here) must match the
    blockwise-jnp backward, including padded kv_len masking."""
    from tony_tpu.ops import attention as A

    s, d, kv_len = 256, 32, 200   # kv_len < s exercises the pad mask
    ks = jax.random.split(jax.random.PRNGKey(7 + causal), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2, s, d)) for kk in ks)
    out, lse = A._blockwise_forward(q, k, v, causal, d ** -0.5, 128,
                                    kv_len=kv_len)
    want = A._blockwise_backward(q, k, v, out, lse, g, causal, d ** -0.5,
                                 128, kv_len=kv_len)
    got = A._pallas_backward(q, k, v, out, lse, g, causal, d ** -0.5,
                             128, 128, kv_len, interpret=True)
    for name, w, got_g in zip(("dq", "dk", "dv"), want, got):
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4, err_msg=name)

# ---------------------------------------------------------------------------
# GQA-native paths: narrow (B, Hkv, S, D) K/V through every branch
# ---------------------------------------------------------------------------

def _gqa_qkv(b=1, h=4, hk=2, s=128, d=32, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_reference(causal):
    """Blockwise path with narrow K/V vs the broadcast oracle, incl. all
    three gradients (dK/dV come back group-reduced to the narrow layout)."""
    q, k, v = _gqa_qkv()
    out = flash_attention(q, k, v, causal)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for name, gf, gr in zip(("dq", "dk", "dv"), g_flash, g_ref):
        assert gf.shape == gr.shape, name
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


def test_pallas_gqa_kernels_interpret_mode():
    """The actual pallas kernels with the GQA K/V row map (interpreted on
    CPU): forward vs oracle, backward vs the blockwise backward."""
    from tony_tpu.ops import attention as A

    q, k, v = _gqa_qkv(b=2, h=4, hk=2, s=128, d=32)
    sm = 32 ** -0.5
    out, lse = A._pallas_forward(q, k, v, causal=True, sm_scale=sm,
                                 block_q=64, block_k=64, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    want = A._blockwise_backward(q, k, v, out, lse, g, True, sm, 64)
    got = A._pallas_backward(q, k, v, out, lse, g, True, sm, 64, 64,
                             None, interpret=True)
    for name, w, got_g in zip(("dq", "dk", "dv"), want, got):
        assert got_g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


def test_rope_long_context_scaling():
    """Llama-3.1 rescale: high-frequency components untouched, fully
    low-frequency ones slowed by exactly `factor`, band in between
    monotonic — and the scaled tables match unscaled inside the original
    context for local-geometry dims."""
    import numpy as np

    from tony_tpu.ops.rope import rope_frequencies, scale_rope_frequencies
    import jax.numpy as jnp

    head_dim, orig, factor = 128, 512, 8.0
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, head_dim, 2,
                                         dtype=jnp.float32) / head_dim))
    scaled = scale_rope_frequencies(inv, factor, orig)
    wavelen = np.asarray(2.0 * np.pi / inv)
    s, i = np.asarray(scaled), np.asarray(inv)
    hi = wavelen < orig / 4.0          # clearly-local dims
    lo = wavelen > orig / 1.0          # never completed a period
    assert hi.any() and lo.any()
    np.testing.assert_array_equal(s[hi], i[hi])
    np.testing.assert_allclose(s[lo], i[lo] / factor, rtol=1e-6)
    mid = ~(hi | lo)
    if mid.any():                       # band interpolates within bounds
        assert (s[mid] <= i[mid] + 1e-9).all()
        assert (s[mid] >= i[mid] / factor - 1e-9).all()

    # table-level: the rescale flows into rope_frequencies — the slowest
    # component's accumulated phase at the last position shrinks by ~factor
    # (acos of its cos row recovers phase while phase < pi)
    cos_u, _ = rope_frequencies(64, 256, scaling_factor=0.0)
    cos_s, _ = rope_frequencies(64, 256, scaling_factor=8.0,
                                orig_max_seq=128)
    assert cos_u.shape == cos_s.shape
    phase_u = float(np.arccos(np.clip(np.asarray(cos_u)[255, -1], -1, 1)))
    phase_s = float(np.arccos(np.clip(np.asarray(cos_s)[255, -1], -1, 1)))
    assert 0 < phase_s < phase_u
    np.testing.assert_allclose(phase_s, phase_u / 8.0, rtol=1e-2)


def test_segmented_long_seq_flash_matches_reference(monkeypatch):
    """Sequences longer than LONG_SEQ_CHUNK split into VMEM-sized
    segments merged by the exact lse rule — forward AND gradients must
    match the unsegmented path (threshold shrunk so the segmented code
    runs at test sizes); causal, non-causal, GQA, and padded-kv cases."""
    import tony_tpu.ops.attention as att

    monkeypatch.setattr(att, "LONG_SEQ_CHUNK", 64)
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    b, h, hk, s, d = 2, 4, 2, 256, 16   # 4 segments of 64
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, hk, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, hk, s, d), jnp.float32)
    g = jax.random.normal(kg, (b, h, s, d), jnp.float32)

    for causal in (True, False):
        def loss(q, k, v, causal=causal):
            return jnp.sum(att.flash_attention(q, k, v, causal,
                                               block_q=64, block_k=64) * g)

        want_out = att.reference_attention(q, k, v, causal)
        got_out = att.flash_attention(q, k, v, causal, block_q=64,
                                      block_k=64)
        np.testing.assert_allclose(np.asarray(got_out),
                                   np.asarray(want_out), atol=2e-5,
                                   rtol=2e-5, err_msg=f"causal={causal}")
        got_grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref_loss(q, k, v, causal=causal):
            return jnp.sum(att.reference_attention(q, k, v, causal) * g)

        want_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for got, want, name in zip(got_grads, want_grads, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=5e-5, rtol=5e-4,
                err_msg=f"d{name} causal={causal}")

    # padded tail: a 224-length sequence pads to 256 inside
    # flash_attention, so the last segment runs with a partial kv_len
    s2 = 224
    q2, k2, v2 = q[:, :, :s2], k[:, :, :s2], v[:, :, :s2]
    got = att.flash_attention(q2, k2, v2, True, block_q=64, block_k=64)
    want = att.reference_attention(q2, k2, v2, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_segmented_pallas_kernels_interpret_mode(monkeypatch):
    """The REAL pallas kernels (interpret mode), forced through the full
    dispatch stack WITH segmentation: proves the segmented path composes
    with the kernels themselves, not only the blockwise fallback."""
    import tony_tpu.ops.attention as att

    monkeypatch.setattr(att, "LONG_SEQ_CHUNK", 64)
    monkeypatch.setattr(att, "_FORCE", "pallas")
    monkeypatch.setattr(att, "_INTERPRET", True)
    key = jax.random.PRNGKey(7)
    kq, kk, kv, kg = jax.random.split(key, 4)
    b, h, hk, s, d = 1, 2, 1, 128, 16    # 2 segments, GQA
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, hk, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, hk, s, d), jnp.float32)
    g = jax.random.normal(kg, (b, h, s, d), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(att.flash_attention(q, k, v, True, block_q=32,
                                           block_k=32) * g)

    got = att.flash_attention(q, k, v, True, block_q=32, block_k=32)
    want = att.reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    got_dq, got_dk, got_dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        return jnp.sum(att.reference_attention(q, k, v, True) * g)

    want_g = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got_, want_, name in zip((got_dq, got_dk, got_dv), want_g, "qkv"):
        np.testing.assert_allclose(np.asarray(got_), np.asarray(want_),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("rows,d", [(3, 256), (40, 256), (2100, 128)],
                         ids=["decode-3", "one-block", "padded-blocks"])
def test_rms_pallas_kernel_interpret_matches_reference(rows, d):
    """The RMSNorm kernel itself (interpreted on the CPU) at the three
    row regimes of `_rms_pallas`: fewer rows than a tile, one block equal
    to the array, and fixed blocks with the last one padded."""
    from tony_tpu.ops import rmsnorm as R

    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,)) + 1.0
    block_rows = max(8, R.BLOCK_BYTES // (4 * d) // 8 * 8)
    assert (rows > block_rows) == (rows == 2100)
    got = R._rms_pallas(x, w, 1e-5, interpret=True)
    np.testing.assert_allclose(got, _rms_reference(x, w, 1e-5),
                               atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("s", [3, 37, 129])
def test_pallas_kernels_pad_a_short_sequence_to_whole_tiles(s, monkeypatch):
    """Prompt lengths Mosaic refused as they were (3, 37, 129 rows): the
    REAL kernels (interpret mode), through the whole dispatch, round a
    one-block sequence up to whole tiles, mask the padded K columns and
    slice the padded Q rows — forward and all three gradients against the
    O(S^2) oracle. That they then COMPILE is tests/test_tpu_compile.py's."""
    import tony_tpu.ops.attention as att

    monkeypatch.setattr(att, "_FORCE", "pallas")
    monkeypatch.setattr(att, "_INTERPRET", True)
    assert att._tile_pad(s, 512, 512) == (-s) % 128
    q, k, v = _gqa_qkv(b=1, h=4, hk=2, s=s, d=32, seed=s)
    g = jax.random.normal(jax.random.PRNGKey(s + 1), q.shape)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, True) * g)

    np.testing.assert_allclose(
        np.asarray(att.flash_attention(q, k, v, True)),
        np.asarray(reference_attention(q, k, v, True)),
        atol=2e-5, rtol=2e-5)
    got = jax.grad(partial(loss, att.flash_attention),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(partial(loss, reference_attention),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-4, err_msg=f"d{name}")


# -- ops/cache_attention.py: the decode step's length-aware cache read ------

@pytest.mark.parametrize("budget,dtype,want", [
    (2048, jnp.bfloat16, 256), (2048, jnp.int8, 256), (640, jnp.bfloat16, 128),
    (48, jnp.bfloat16, 16), (48, jnp.int8, 0), (80, jnp.float32, 16),
    (37, jnp.bfloat16, 0)])
def test_cache_read_chunk_is_whole_tiles_of_the_stored_type(budget, dtype,
                                                            want):
    from tony_tpu.ops.cache_attention import read_chunk_rows

    assert read_chunk_rows(budget, dtype) == want


@pytest.mark.parametrize("beyond", ["noise", "huge"])
@pytest.mark.parametrize("layout", ["bf16", "int8"])
@pytest.mark.parametrize("heads", [(32, 8), (4, 4)], ids=["gqa32-8", "mha4"])
@pytest.mark.parametrize("window", [1, 4])
def test_decode_read_kernel_matches_the_jnp_body_and_reads_no_row_past_a_length(
        window, heads, layout, beyond, monkeypatch):
    """`tony_decode_read` (interpret mode, chunks of 32 rows) through the
    dispatcher, against the jnp body on a cache whose rows past each
    slot's length are zero. The kernel's cache holds noise there, or
    values that would swamp any softmax: neither reaches the result.
    Lengths 0, 1, chunk - 1, chunk, chunk + 1 and budget - 1 in one batch;
    a decode step's window and a speculative one's; grouped and plain
    heads; both row formats (the int8 one in float32, where the kernel's
    arithmetic is the body's to rounding)."""
    from tony_tpu.ops import cache_attention as ca

    monkeypatch.setattr(ca, "READ_CHUNK_ROWS", 32)
    monkeypatch.setattr(ca, "_INTERPRET", True)
    (h, g), d, s, n_layers = heads, 32, 128, 2
    lens = jnp.asarray([0, 1, 31, 32, 33, s - 1], jnp.int32)
    b = lens.shape[0]
    quant = layout == "int8"
    dtype = jnp.float32 if quant else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(window * 10 + h), 9)
    q = jax.random.normal(ks[0], (b, h, window, d), dtype)
    k_new = jax.random.normal(ks[1], (b, g, window, d), dtype)
    v_new = jax.random.normal(ks[2], (b, g, window, d), dtype)
    shape = (n_layers, b, g, s, d)
    held = (jnp.arange(s)[None, :] < lens[:, None])[None, :, None, :, None]
    if quant:
        clean = {"k": jax.random.randint(ks[3], shape, -127, 128, jnp.int8),
                 "v": jax.random.randint(ks[4], shape, -127, 128, jnp.int8),
                 "k_scale": jax.random.uniform(ks[5], shape[:-1] + (1,),
                                               jnp.float32, 0.002, 0.02),
                 "v_scale": jax.random.uniform(ks[6], shape[:-1] + (1,),
                                               jnp.float32, 0.002, 0.02)}
        junk = {"k": jax.random.randint(ks[7], shape, -127, 128, jnp.int8),
                "v": jax.random.randint(ks[8], shape, -127, 128, jnp.int8),
                "k_scale": clean["k_scale"], "v_scale": clean["v_scale"]}
        if beyond == "huge":
            junk = {"k": jnp.full(shape, 127, jnp.int8),
                    "v": jnp.full(shape, -127, jnp.int8),
                    "k_scale": jnp.full(shape[:-1] + (1,), 1e3),
                    "v_scale": jnp.full(shape[:-1] + (1,), 1e3)}
    else:
        clean = {"k": jax.random.normal(ks[3], shape, dtype),
                 "v": jax.random.normal(ks[4], shape, dtype)}
        junk = {"k": jax.random.normal(ks[7], shape, dtype),
                "v": jax.random.normal(ks[8], shape, dtype)}
        if beyond == "huge":
            junk = {"k": jnp.full(shape, 3e4, dtype),
                    "v": jnp.full(shape, -3e4, dtype)}
    zero = {name: jnp.where(held, leaf, jnp.zeros((), leaf.dtype))
            for name, leaf in clean.items()}
    dirty = {name: jnp.where(held, clean[name], junk[name]) for name in clean}
    layer = jnp.asarray([1], jnp.int32)
    got = ca.cache_attention(layer, lens, q, k_new, v_new, dirty)
    scales = tuple(zero[n][..., 0] for n in ("k_scale", "v_scale")
                   if n in zero)
    want = ca._attend_jnp(layer, lens, q.reshape(b, g, h // g * window, d),
                          k_new, v_new, zero["k"], zero["v"], *scales,
                          window=window).reshape(q.shape)
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = 2e-5 if quant else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # and the body reads none of it either (masked before the softmax)
    again = ca._attend_jnp(
        layer, lens, q.reshape(b, g, h // g * window, d), k_new, v_new,
        dirty["k"], dirty["v"],
        *(dirty[n][..., 0] for n in ("k_scale", "v_scale") if n in dirty),
        window=window).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(again, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


RIDING = {"all-ride": [1, 1, 1, 1, 1], "one-rides": [0, 0, 1, 0, 0],
          "first-parked": [0, 1, 1, 1, 1], "last-parked": [1, 1, 1, 1, 0],
          "alternating": [1, 0, 1, 0, 1], "none-rides": [0, 0, 0, 0, 0]}


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32-state", "bf16-state"])
@pytest.mark.parametrize("mask", list(RIDING))
def test_lightning_step_moves_the_state_of_riding_slots_only(mask,
                                                             state_dtype):
    """`tony_lightning_step` (interpret mode) against the jnp body under a
    riding mask: the riders' output rows and state slabs are the body's,
    a slot that does not ride keeps its slabs BIT-equal (noise, and huge
    values that a decay would have moved) and gets a row of zeros, and no
    other layer's slice is touched."""
    from tony_tpu.ops import lightning as L

    b, h, d, n_layers = 5, 4, 16, 3
    riding = np.asarray(RIDING[mask], bool)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(key, (b, h, d), jnp.float32)
               for key in ks[:3])
    state = jax.random.normal(ks[3], (n_layers, b, h, d, d), jnp.float32)
    state = state.at[:, 1].multiply(1e30).astype(state_dtype)
    decay = jnp.asarray([0.9, 0.5, 0.99, 0.3], jnp.float32)
    layer = jnp.asarray([1], jnp.int32)
    args = (layer, *L.compact_riders(jnp.asarray(riding)), decay, q, k, v,
            state)
    o, new = L._step_pallas(*args, scale=0.25, interpret=True)
    want_o, want = L._step_jnp(*args, scale=0.25)
    assert new.dtype == state.dtype and o.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(o[~riding]))) and bool(
        jnp.all(o[~riding] == 0))
    assert bool(jnp.all(new[:, ~riding] == state[:, ~riding]))
    assert bool(jnp.all(new[(0, 2), :] == state[(0, 2), :]))
    assert bool(jnp.all(want[:, ~riding] == state[:, ~riding]))
    calm = riding & (np.arange(b) != 1)         # slot 1 holds 1e30s
    np.testing.assert_allclose(o[calm], want_o[calm], atol=1e-5, rtol=1e-5)
    tol = 1e-5 if state_dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(new[1, calm], np.float32),
                               np.asarray(want[1, calm], np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(new[1, 1], np.float32),
                               np.asarray(want[1, 1], np.float32),
                               atol=1e20, rtol=tol)
    if riding.any():
        assert not bool(jnp.all(new[1, riding] == state[1, riding]))
    # the mask absent is every slot riding
    o_all, new_all = L.lightning_step(layer, decay, q, k, v, state, 0.25)
    full = L._step_jnp(layer, *L.compact_riders(jnp.ones((b,), bool)),
                       *args[3:], scale=0.25)
    assert bool(jnp.all(o_all == full[0])) and bool(
        jnp.all(new_all == full[1]))


def test_sparse_read_kernel_dereferences_no_block_of_a_slot_that_reads_none():
    """`tony_sparse_read` (interpret mode) with a row of `counts` 0, as
    models/sala.py hands it a slot that does not ride: that slot's token
    attends to its own row alone (its output is its V row, finite) though
    its block ids point anywhere and its cache rows are NaN; the other
    slot's result is the jnp body's."""
    from tony_tpu.ops import sparse_attention as sa

    b, g, r, d, block, nb, sm, n_layers = 2, 2, 2, 16, 8, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (b, g, r, d), jnp.float32)
    k_new = jax.random.normal(ks[1], (b, g, d), jnp.float32)
    v_new = jax.random.normal(ks[2], (b, g, d), jnp.float32)
    shape = (n_layers, b, g, nb * block, d)
    k_cache = jax.random.normal(ks[3], shape).at[:, 1].set(jnp.nan)
    v_cache = jax.random.normal(ks[4], shape).at[:, 1].set(jnp.nan)
    ids = jnp.stack([jnp.broadcast_to(jnp.arange(sm) * 2, (g, sm)),
                     jnp.full((g, sm), nb - 1)]).astype(jnp.int32)
    counts = jnp.asarray([[5, 3], [0, 0]], jnp.int32)
    lens = jnp.asarray([75, nb * block - 1], jnp.int32)
    args = (jnp.asarray([1], jnp.int32), ids, counts, lens, q, k_new, v_new,
            k_cache, v_cache)
    got = sa._decode_attend_pallas(*args, block=block, interpret=True)
    want = sa._decode_attend_jnp(*args, block=block)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        got[1], jnp.broadcast_to(v_new[1][:, None, :], (g, r, d)), atol=1e-6)


# -- tony_sparse_select: the decode step's block selection, sorting nothing --

# the sala-longdoc cell's selection rule and budget (36 864 tokens: 2 304
# compressed keys, 576 blocks, 64 of them read), at narrow heads
SELECT_BUDGET = 36864


def _select_args(lens, riding, seed=0, q=None, ck=None, heads=2, d=32,
                 n_layers=2, layer=1):
    """The arguments of one selection call of every slot: random queries,
    compressed keys and completed rows unless given."""
    from tony_tpu.ops import lightning as L
    from tony_tpu.ops import sparse_attention as sa

    spec = sa.SparseSpec()
    b, g, nc = len(lens), 2, SELECT_BUDGET // spec.kernel_stride
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    if q is None:
        q = jax.random.normal(ks[0], (b, g, heads, d)) * 2
    if ck is None:
        ck = jax.random.normal(ks[1], (n_layers, b, g, nc, d))
    row = jax.random.normal(ks[2], (b, g, d)).astype(jnp.bfloat16)
    lens = jnp.asarray(lens, jnp.int32)
    at = lens + 1 - spec.kernel_size
    flag = (at >= 0) & (at % spec.kernel_stride == 0)
    return spec, (jnp.asarray([layer], jnp.int32),
                  *L.compact_riders(jnp.asarray(riding, bool)), lens,
                  jnp.maximum(at, 0) // spec.kernel_stride,
                  flag.astype(jnp.int32), q.astype(jnp.bfloat16), row,
                  ck.astype(jnp.bfloat16))


def _select_both(spec, args):
    from tony_tpu.ops import sparse_attention as sa

    want = jax.jit(partial(sa._select_decode_jnp, spec=spec))(*args)
    got = jax.jit(partial(sa._select_decode_pallas, spec=spec,
                          interpret=True))(*args)
    assert got[0].dtype == got[1].dtype == jnp.int32
    return tuple(map(np.asarray, got)), tuple(map(np.asarray, want))


SELECT_CONTEXTS = {
    "one-block-short-of-the-budget": 36863, "long": 30001,
    "first-past-dense-len": 8192, "last-dense": 8191,
    "dense-under-64-blocks": 4000, "dense-no-complete-window": 20,
    "empty-slot": 0, "completes-a-window": 12303, "completes-none": 12304,
    "window-covers-the-whole-context": 9000}


@pytest.mark.parametrize("context", list(SELECT_CONTEXTS))
def test_sparse_select_kernel_picks_the_jnp_bodys_blocks(context):
    """`tony_sparse_select` (interpret mode) against the jnp body — the
    one that sorts twice — at the cell's rule and budget: the ids and the
    counts are the same integers, for contexts past `dense_len` up to one
    block short of the budget (the 64 best of up to 576 blocks, ascending),
    for contexts the dense branch reads whole, and whether or not the
    token completes a compressed key of its own."""
    n = SELECT_CONTEXTS[context]
    spec, args = _select_args([n, max(n - 1, 0)], [1, 1],
                              seed=len(context))
    if "completes" in context:
        assert bool(args[5][0]) == (context == "completes-a-window")
    (ids, counts), (want_ids, want) = _select_both(spec, args)
    assert (counts == want).all() and (ids == want_ids).all()
    attended, _ = spec.read_blocks(n + 1)
    assert counts[0].tolist() == [min(attended, -(-n // 64))] * 2
    for row, c in zip(ids[0], counts[0]):
        assert (np.diff(row[:c]) > 0).all() and (row[c:] == 0).all()


@pytest.mark.parametrize("mask", list(RIDING))
def test_sparse_select_kernel_selects_for_riding_slots_only(mask):
    """Under a riding mask: a slot that does not ride gets count 0 and ids
    0 from the kernel and from the jnp body, and a rider's row is what it
    is when every slot rides, whoever else rides."""
    riding = np.asarray(RIDING[mask], bool)
    lens = [36000, 8191, 20015, 12303, 500]
    spec, args = _select_args(lens, riding, seed=11)
    (ids, counts), (want_ids, want) = _select_both(spec, args)
    assert (counts == want).all() and (ids == want_ids).all()
    assert (counts[~riding] == 0).all() and (ids[~riding] == 0).all()
    _, every = _select_args(lens, np.ones(5, bool), seed=11)
    (all_ids, all_counts), _ = _select_both(spec, every)
    assert (ids[riding] == all_ids[riding]).all()
    assert (counts[riding] == all_counts[riding]).all()
    assert (all_counts > 0).all()


def _keys_along_one_axis(levels, b=1, g=2, d=32, n_layers=2):
    """Compressed keys whose score against a query along the first axis is
    the given level (exact in bfloat16), the same in every layer, slot and
    group: levels (NC,)."""
    ck = jnp.zeros((n_layers, b, g, levels.shape[0], d))
    return ck.at[..., 0].set(jnp.asarray(levels, jnp.float32))


@pytest.mark.parametrize("tie", ["equal-pooled-scores-at-the-64th-place",
                                 "a-softmax-of-exact-zeros"])
def test_sparse_select_kernel_breaks_ties_toward_the_lower_block(tie):
    """Ties, constructed: of two blocks whose pooled scores are the same
    float at the 64th place the lower one is read, and where one key holds
    the whole softmax (every other probability underflows to an exact 0)
    the free places go to the lowest blocks — `lax.top_k`'s rule, which
    the kernel's rank keeps. Position 20 000: blocks 0 and 280..312 are
    forced (34 of the 64), 30 are free."""
    n, nc, d = 20000, SELECT_BUDGET // 16, 32
    low, high = 150, 200                            # the two that tie
    levels = np.zeros(nc, np.float32)
    if tie.startswith("equal"):
        best = np.arange(10, 10 + 29 * 3, 3)        # 29 blocks well ahead
        levels[4 * best + 1] = 3.0
        levels[[4 * low + 1, 4 * high + 1]] = 2.0
        expect = sorted([0, *best, low, *range(280, 313)])
    else:
        levels[4 * 100 + 1] = 64.0      # a score of 256: the rest read 0.0
        expect = sorted([0, *range(1, 30), 100, *range(280, 313)])
    q = jnp.zeros((1, 2, 2, d)).at[..., 0].set(32.0 ** 0.5 * 4)
    spec, args = _select_args([n], [1], q=q, ck=_keys_along_one_axis(levels))
    (ids, counts), (want_ids, want) = _select_both(spec, args)
    assert (counts == want).all() and (ids == want_ids).all()
    assert counts.tolist() == [[64, 64]]
    assert ids[0, 0, :64].tolist() == expect
    if tie.startswith("equal"):
        assert high not in ids[0, 0]


def test_select_decode_without_a_mask_is_every_slot_riding(monkeypatch):
    """`select_decode`, the model's entry: `riders` absent is every slot
    riding, and with the kernels interpreted (TONY_FLASH_INTERPRET) it
    returns what the plain path does."""
    from tony_tpu.ops import sparse_attention as sa

    spec, args = _select_args([36000, 9000, 100], [1, 1, 1], seed=5)
    layer, _, _, lens, j, flag, q, row, ck = args
    plain = sa.select_decode(layer, q, ck, lens, spec, (j, flag, row))
    monkeypatch.setattr(sa, "_INTERPRET", True)
    kernel = sa.select_decode(layer, q, ck, lens, spec, (j, flag, row))
    want = sa._select_decode_jnp(*args, spec=spec)
    for a, b, c in zip(plain, kernel, want):
        assert bool(jnp.all(a == c)) and bool(jnp.all(b == c))
    assert np.asarray(want[1]).tolist() == [[64, 64], [64, 64], [2, 2]]
