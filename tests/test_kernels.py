"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""
import numpy as np
import pytest

import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_ref
from repro.kernels.filter_project.kernel import filter_scan, parse_i32
from repro.kernels.filter_project.ops import filter_mask
from repro.kernels.filter_project.ref import filter_scan_ref, parse_i32_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# filter_project
# ---------------------------------------------------------------------------
FILTER_SHAPES = [(2048, 2048), (8192, 2048), (16384, 1024), (4096, 512)]
PROGRAMS = [
    (("gt", 0, 50),),
    (("gt", 0, 50), ("lt", 1, 0.25), ("and",)),
    (("gt", 0, 80), ("le", 0, 10), ("or",), ("ne", 1, 0.5), ("and",)),
    (("eq", 0, 3), ("not",)),
]


class TestFilterScan:
    @pytest.mark.parametrize("n,block", FILTER_SHAPES)
    @pytest.mark.parametrize("prog", PROGRAMS)
    def test_mask_and_counts_match_ref(self, n, block, prog):
        a = jnp.asarray(RNG.integers(0, 100, n).astype(np.int32))
        b = jnp.asarray(RNG.random(n).astype(np.float32))
        nrows = n - 17
        m1 = filter_scan((a, b), prog, nrows, block=block, interpret=True)
        m2 = filter_scan_ref((a, b), prog, nrows)
        assert bool((m1 == m2).all())
        # the count is the mask's reduction on both routes
        _, c1 = filter_mask((a, b), prog, nrows, block=block,
                            interpret=True)
        _, c2 = filter_mask((a, b), prog, nrows, block=block,
                            use_pallas=False)
        assert int(c1) == int(c2) == int(m2.sum())

    def test_rows_beyond_nrows_never_match(self):
        n, block = 4096, 1024
        a = jnp.ones((n,), jnp.int32) * 99
        m = filter_scan((a,), (("gt", 0, 0),), 100, block=block,
                        interpret=True)
        assert int(m.sum()) == 100

    @settings(max_examples=20, deadline=None)
    @given(nrows=st.integers(0, 4096), thr=st.integers(-5, 105))
    def test_property_count_matches_numpy(self, nrows, thr):
        n, block = 4096, 1024
        a_np = RNG.integers(0, 100, n).astype(np.int32)
        m, c = filter_mask((jnp.asarray(a_np),), (("gt", 0, thr),), nrows,
                           block=block, interpret=True)
        expect = int((a_np[:nrows] > thr).sum())
        assert int(m.sum()) == expect == int(c.sum())


class TestParseI32:
    @pytest.mark.parametrize("n,block", [(2048, 2048), (8192, 2048)])
    def test_digits_roundtrip(self, n, block):
        vals = np.concatenate([
            np.array([0, 1, 999_999_999, 123_456_789], np.int64),
            RNG.integers(0, 10**9, n - 4)]).astype(np.int64)
        digits = np.zeros((n, 10), np.uint8)
        v = vals.copy()
        for k in range(9, -1, -1):
            digits[:, k] = (v % 10) + 48
            v //= 10
        d = jnp.asarray(digits)
        out = parse_i32(d, block=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), vals.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(parse_i32_ref(d)))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # (B, Hq, Hkv, T, S, D, causal, window)
    (1, 4, 4, 256, 256, 64, True, None),
    (2, 8, 2, 128, 256, 64, True, None),     # GQA + offset (decode-style)
    (1, 4, 2, 256, 256, 128, True, 128),     # sliding window
    (1, 2, 2, 256, 256, 64, False, None),    # bidirectional
    (1, 16, 1, 128, 128, 64, True, None),    # MQA
]


class TestFlashAttention:
    @pytest.mark.parametrize("case", ATTN_CASES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, case, dtype):
        b, hq, hkv, t, s, d, causal, window = case
        q = jnp.asarray(RNG.standard_normal((b, hq, t, d)), dtype)
        k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
        v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
        ref = mha_ref(q, k, v, causal=causal, window=window)
        atol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=atol)

    def test_block_sizes(self):
        b, hq, hkv, t, s, d = 1, 2, 2, 256, 256, 64
        q = jnp.asarray(RNG.standard_normal((b, hq, t, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
        ref = mha_ref(q, k, v)
        for bq, bk in [(64, 64), (128, 256), (256, 128)]:
            out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)

    def test_vjp_path_runs(self):
        import jax

        from repro.kernels.flash_attention.ops import attention

        q = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.float32)

        def loss(q, k, v):
            return attention(q, k, v, True, None, None, "pallas").sum()

        g = jax.grad(loss)(q, k, v)
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
DECODE_CASES = [
    (2, 8, 2, 512, 64, None),
    (1, 4, 4, 256, 128, None),
    (3, 8, 4, 384, 64, 128),
    (1, 32, 8, 1024, 128, None),
]


class TestDecodeAttention:
    @pytest.mark.parametrize("case", DECODE_CASES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, case, dtype):
        b, hq, hkv, s, d, window = case
        q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
        k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
        v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
        kv_len = jnp.asarray(RNG.integers(1, s + 1, b).astype(np.int32))
        out = decode_attention(q, k, v, kv_len, window=window,
                               interpret=True)
        ref = decode_ref(q, k, v, kv_len, window=window)
        atol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=atol)

    def test_len_one_cache(self):
        b, hq, hkv, s, d = 1, 4, 2, 128, 64
        q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
        kv_len = jnp.asarray([1], jnp.int32)
        out = decode_attention(q, k, v, kv_len, interpret=True)
        ref = decode_ref(q, k, v, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
