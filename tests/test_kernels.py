"""Device programs vs. their oracles: the exact stream-scoring program
against the numpy oracle, and the Pallas flash-attention kernel against
its pure-jnp reference (interpret mode on CPU).

For each, sweep shapes/dtypes and compare against the oracle.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.random_factor import (
    random_factor_sum,
    random_percentage,
    seek_distance_np,
    stream_stats_batch64,
    stream_stats_batch_np,
)
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref

pytestmark = pytest.mark.slow  # interpret-mode Pallas runs, seconds per case


def _assert_bit_equal(offs, szs):
    """Seek counts and distances bit-equal to the oracle; the percentage,
    which the program divides by a constant, within one float64 ulp."""

    got = [np.asarray(x) for x in stream_stats_batch64(offs, szs)]
    rf, pct, dist = stream_stats_batch_np(offs, szs)
    for name, g, w in zip(("rf", "pct", "dist"), got, (rf, pct, dist)):
        assert g.dtype == w.dtype, name
    np.testing.assert_array_equal(got[0], rf, err_msg="rf")
    np.testing.assert_array_equal(got[2], dist, err_msg="dist")
    np.testing.assert_array_max_ulp(got[1], pct, maxulp=1)
    return got


class TestStreamRF:
    """``stream_stats_batch64``, the scoring program the device backend
    runs, is bit-equal to ``stream_stats_batch_np``."""

    @pytest.mark.parametrize("m", [1, 3, 8, 37, 300])
    @pytest.mark.parametrize("n", [8, 64, 128])
    def test_shapes_vs_ref(self, m, n):
        rng = np.random.default_rng(m * 1000 + n)
        # a grid of 4 KiB slots above 2**40: merges, seeks, overlaps and
        # tied offsets of different sizes all occur
        offs = (1 << 40) + rng.integers(0, 2 * n, size=(m, n)) * 4096
        szs = rng.choice([4096, 8192, 12288], size=(m, n))
        _assert_bit_equal(offs, szs)

    def test_agrees_with_core_detector(self):
        """The program == the scalar per-request definitions, row by row."""

        rng = np.random.default_rng(7)
        offs = rng.integers(0, 1 << 20, size=(16, 128)) * 256
        szs = np.full((16, 128), 256, np.int64)
        rf, pct, dist = _assert_bit_equal(offs, szs)
        for i in range(16):
            assert rf[i] == random_factor_sum(offs[i], szs[i])
            assert pct[i] == random_percentage(offs[i], szs[i])
            assert dist[i] == seek_distance_np(offs[i], szs[i])

    def test_contiguous_and_reversed(self):
        offs = (np.arange(128, dtype=np.int64) * 64)[None]
        szs = np.full((1, 128), 64, np.int64)
        assert int(_assert_bit_equal(offs, szs)[0][0]) == 0
        assert int(_assert_bit_equal(offs[:, ::-1].copy(), szs)[0][0]) == 0  # sorted away

    def test_fully_random(self):
        offs = (np.arange(128, dtype=np.int64) * 1000)[None]
        szs = np.full((1, 128), 64, np.int64)
        assert int(_assert_bit_equal(offs, szs)[0][0]) == 127

    def test_percentage(self):
        offs = (np.arange(128, dtype=np.int64) * 1000)[None]
        szs = np.full((1, 128), 64, np.int64)
        assert float(_assert_bit_equal(offs, szs)[1][0]) == 1.0

    def test_block_boundary_padding(self):
        """All-zero rows appended to a matrix (the neutral rows that round
        a job's stacked matrix up to its fixed shape) score nothing and
        leave the real rows' scores as they are."""

        rng = np.random.default_rng(9)
        offs = rng.integers(0, 1 << 20, size=(5, 128)) * 17
        szs = np.full((5, 128), 17, np.int64)
        alone = _assert_bit_equal(offs, szs)
        zeros = np.zeros((3, 128), np.int64)
        padded = _assert_bit_equal(np.vstack([offs, zeros]), np.vstack([szs, zeros]))
        for a, p in zip(alone, padded):
            np.testing.assert_array_equal(p[:5], a)
            assert not p[5:].any()


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,h,kv,sq,sk,hd,causal",
        [
            (1, 2, 2, 128, 128, 64, True),
            (2, 4, 2, 128, 128, 64, True),   # GQA n_rep=2
            (1, 6, 1, 128, 128, 32, True),   # MQA-ish n_rep=6
            (1, 2, 2, 256, 256, 128, False),
            (1, 2, 2, 64, 192, 64, False),   # sq != sk (cross-ish)
        ],
    )
    def test_vs_ref(self, b, h, kv, sq, sk, hd, causal, dtype):
        rng = np.random.default_rng(hash((b, h, sq, sk, hd)) % 2**31)
        q = jnp.asarray(rng.normal(size=(b, h, sq, hd)), dtype)
        k = jnp.asarray(rng.normal(size=(b, kv, sk, hd)), dtype)
        v = jnp.asarray(rng.normal(size=(b, kv, sk, hd)), dtype)
        got = flash_attention_op(q, k, v, causal=causal,
                                 block_q=64, block_k=64)
        want = flash_attention_ref(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol)

    def test_block_shape_independence(self):
        """Different tilings must give identical math (within fp error)."""

        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
        a = flash_attention_op(q, k, v, causal=True, block_q=64, block_k=64)
        b = flash_attention_op(q, k, v, causal=True, block_q=128, block_k=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_model_attention_layer(self):
        """The kernel agrees with the XLA path used by the model trunk."""

        from repro.models.layers import _attend_direct

        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(2, 128, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.float32)
        xla = _attend_direct(q, k, v, n_rep=2, scale=0.125, causal=True)
        from repro.kernels.flash_attention.ops import flash_attention_bshd

        pal = flash_attention_bshd(q, k, v, causal=True, scale=0.125)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(pal),
                                   atol=2e-5, rtol=2e-5)
