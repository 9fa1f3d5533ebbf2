"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernels target TPU; interpret=True executes the kernel body on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import flash_attention as _fa
from repro.kernels import ops, ref

RNG = np.random.default_rng(3)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 512, 128), (64, 64, 192),
    (100, 40, 88),          # BConv-like irregular
    (33, 17, 65),           # fully ragged
])
def test_nest_gemm(m, k, n, dtype, tol):
    x, w = _rand((m, k), dtype), _rand((k, n), dtype)
    out = ops.nest_gemm(x, w, interpret=True)
    expect = ref.nest_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol * k)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (96, 64, 160)])
def test_nest_gemm_block_transposed_output(m, k, n):
    """BIRRD-style free output re-layout."""
    x, w = _rand((m, k), jnp.float32), _rand((k, n), jnp.float32)
    out = ops.nest_gemm(x, w, interpret=True, out_block_t=True)
    expect = ref.nest_gemm_ref(x, w, out_block_t=True)
    assert out.shape == (n, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-2)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,s,h,d,causal", [
    (2, 128, 2, 64, True), (2, 128, 2, 64, False),
    (1, 256, 4, 32, True), (2, 192, 1, 128, True),
    (1, 320, 2, 64, False),     # ragged seq
])
def test_flash_attention(b, s, h, d, causal, dtype, tol):
    q = _rand((b, s, h, d), dtype) * 0.3
    k = _rand((b, s, h, d), dtype) * 0.3
    v = _rand((b, s, h, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, s, d)
    kf = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * h, s, d)
    vf = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * h, s, d)
    expect = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    expect = expect.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("b,l,d,n", [(2, 64, 32, 16), (1, 128, 64, 8),
                                     (2, 256, 16, 4)])
def test_mamba_scan(b, l, d, n):
    da = jnp.asarray(RNG.uniform(0.7, 0.999, (b, l, d, n)), jnp.float32)
    dbx = _rand((b, l, d, n), jnp.float32) * 0.1
    c = _rand((b, l, n), jnp.float32)
    h0 = _rand((b, d, n), jnp.float32) * 0.1
    y, h = ops.mamba_scan(da, dbx, c, h0, interpret=True)
    yr, hr = ref.mamba_scan_ref(da, dbx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Randomized oracle sweeps (ragged shapes the parametrized grids miss)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 150), k=st.integers(1, 150), n=st.integers(1, 150))
def test_nest_gemm_randomized_ragged(m, k, n):
    """Arbitrary non-block-multiple shapes vs the einsum oracle (the
    zero-pad path of ops.nest_gemm must be exact, not approximate)."""
    x, w = _rand((m, k), jnp.float32), _rand((k, n), jnp.float32)
    out = ops.nest_gemm(x, w, interpret=True)
    expect = ref.nest_gemm_ref(x, w)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4 * max(k, 1))


@settings(max_examples=8, deadline=None)
@given(m=st.integers(2, 140), k=st.integers(2, 100), n=st.integers(2, 140))
def test_nest_gemm_out_block_t_randomized_ragged(m, k, n):
    """The BIRRD-style block-transposed output map on ragged shapes:
    per-block transposition at swapped block coordinates must equal the
    global transpose after the pad-slice round trip."""
    x, w = _rand((m, k), jnp.float32), _rand((k, n), jnp.float32)
    out = ops.nest_gemm(x, w, interpret=True, out_block_t=True)
    expect = ref.nest_gemm_ref(x, w, out_block_t=True)
    assert out.shape == (n, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4 * max(k, 1))


@pytest.mark.parametrize("act", ["relu", "gelu", "silu"])
def test_nest_gemm_fused_activation(act):
    """Activation fused at the final-K store == oracle + host activation
    (the PallasBackend's lowering of elementwise Activation drains)."""
    import jax
    x, w = _rand((96, 72), jnp.float32), _rand((72, 80), jnp.float32)
    out = ops.nest_gemm(x, w, interpret=True, act=act)
    fn = {"relu": lambda v: jnp.maximum(v, 0.0), "gelu": jax.nn.gelu,
          "silu": jax.nn.silu}[act]
    expect = fn(ref.nest_gemm_ref(x, w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-2)


@settings(max_examples=6, deadline=None)
@given(s=st.integers(2, 96), sk=st.integers(2, 200),
       d=st.sampled_from([16, 32, 64]))
def test_flash_attention_noncausal_padded_kv_randomized(s, sk, d):
    """Non-causal cross-attention with ragged (padded) KV: the docstring
    promises padded KV rows are masked -- randomized regression."""
    b, h = 1, 2
    q = _rand((b, s, h, d), jnp.float32) * 0.3
    k = _rand((b, sk, h, d), jnp.float32) * 0.3
    v = _rand((b, sk, h, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, interpret=True)
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, s, d)
    kf = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * h, sk, d)
    vf = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * h, sk, d)
    expect = ref.flash_attention_ref(qf, kf, vf, causal=False)
    expect = expect.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_blockaligned_padded_kv_does_not_leak():
    """Regression for the raw kernel's pad guard: a block-aligned kv_len
    shorter than the padded buffer (kv_len % bkv == 0) used to skip the
    mask entirely, letting whole padding blocks contribute.  Poison the
    pad region to make any leak loud."""
    bh, s, d, real_kv = 2, 64, 32, 64
    q = _rand((bh, s, d), jnp.float32) * 0.3
    k = _rand((bh, real_kv, d), jnp.float32) * 0.3
    v = _rand((bh, real_kv, d), jnp.float32)
    poison = jnp.full((bh, 64, d), 100.0, jnp.float32)
    k_pad = jnp.concatenate([k, poison], axis=1)     # padded to 128
    v_pad = jnp.concatenate([v, poison], axis=1)
    out = _fa.flash_attention(q, k_pad, v_pad, causal=False, bq=32, bkv=64,
                              kv_len=real_kv, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_mamba_scan_matches_model_recurrence():
    """Kernel semantics == the model's chunked associative scan."""
    from repro.models.ssm import _ssm_scan_chunked
    b, l, d, n = 2, 64, 8, 4
    da = jnp.asarray(RNG.uniform(0.5, 0.99, (b, l, d, n)), jnp.float32)
    dbx = _rand((b, l, d, n), jnp.float32)
    h0 = _rand((b, d, n), jnp.float32)
    h_seq, h_last = _ssm_scan_chunked(da, dbx, h0, chunk=16)
    c = _rand((b, l, n), jnp.float32)
    y_model = jnp.einsum("bldn,bln->bld", h_seq, c)
    y_kernel, h_kernel = ops.mamba_scan(da, dbx, c, h0, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_last),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# TPU legality of the launch geometry (the block legaliser, the in-kernel
# adapt, the interpret decision)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(tile=st.integers(1, 3000), dim=st.integers(1, 3000),
       align=st.sampled_from([8, 128]))
def test_tpu_block_is_mosaic_legal(tile, dim, align):
    """A legal block is the whole axis or an ``align`` multiple no
    smaller than the tile it came from (clipped to the axis)."""
    b = ops.tpu_block(tile, dim, align)
    assert b == dim or (b % align == 0 and min(tile, dim) <= b < dim)


@pytest.mark.parametrize("m_l,n_l,m_next,k_next", [
    (4, 32, 4, 16), (4, 16, 4, 32), (3, 5, 7, 4), (1, 1024, 1, 128),
    (256, 1024, 256, 128), (256, 128, 256, 3072), (5, 7, 3, 11),
    (2, 6, 9, 4)])
def test_adapt_block_matches_runtime_adapt(m_l, n_l, m_next, k_next):
    """The in-kernel adapt (one-hot matmuls, no reshape) is bit-identical
    to the runtime's host glue, with zeros outside the adapted region."""
    from repro.kernels.fused_chain import adapt_block
    from repro.runtime.executable import adapt
    rows, width = -(-m_next // 8) * 8, -(-k_next // 128) * 128
    src = RNG.standard_normal((-(-m_l // 8) * 8,
                               -(-n_l // 128) * 128)).astype(np.float32)
    out = np.asarray(adapt_block(jnp.asarray(src), m_l, n_l, m_next, k_next,
                                 rows, width))
    np.testing.assert_array_equal(out[:m_next, :k_next],
                                  adapt(src[:m_l, :n_l], m_next, k_next))
    assert not out[m_next:].any() and not out[:, k_next:].any()


def test_interpret_mode_only_on_cpu():
    import jax
    assert ops.interpret_mode(True) is True
    assert ops.interpret_mode(False) is False
    assert ops.interpret_mode() == (jax.default_backend() == "cpu")
