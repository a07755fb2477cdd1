"""Pallas kernel sweeps vs. pure-jnp oracles (interpret mode on CPU).

Per the assignment: for each kernel, sweep shapes/dtypes and
assert_allclose against ref.py.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.random_factor import random_factor_batch
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.stream_rf.ops import random_percentage_op, stream_rf_op
from repro.kernels.stream_rf.ref import stream_rf_ref

pytestmark = pytest.mark.slow  # interpret-mode Pallas runs, seconds per case


class TestStreamRF:
    @pytest.mark.parametrize("m", [1, 3, 8, 37, 300])
    @pytest.mark.parametrize("n", [8, 64, 128])
    def test_shapes_vs_ref(self, m, n):
        rng = np.random.default_rng(m * 1000 + n)
        offs = rng.integers(0, 1 << 24, size=(m, n)).astype(np.int32)
        szs = rng.integers(1, 1 << 10, size=(m, n)).astype(np.int32)
        got = np.asarray(stream_rf_op(offs, szs, interpret=True))
        want = np.asarray(stream_rf_ref(offs, szs))
        np.testing.assert_array_equal(got, want)

    def test_agrees_with_core_detector(self):
        """Kernel == the host control-plane's batched scorer (same Eq. 1)."""

        rng = np.random.default_rng(7)
        offs = rng.integers(0, 1 << 20, size=(16, 128)).astype(np.int32)
        szs = np.full((16, 128), 256, np.int32)
        got = np.asarray(stream_rf_op(offs, szs, interpret=True))
        want = np.asarray(random_factor_batch(offs, szs))
        np.testing.assert_array_equal(got, want)

    def test_contiguous_and_reversed(self):
        offs = (np.arange(128, dtype=np.int32) * 64)[None]
        szs = np.full((1, 128), 64, np.int32)
        assert int(stream_rf_op(offs, szs, interpret=True)[0]) == 0
        assert int(stream_rf_op(offs[:, ::-1].copy(), szs, interpret=True)[0]) == 0  # sorted away

    def test_fully_random(self):
        offs = (np.arange(128, dtype=np.int32) * 1000)[None]
        szs = np.full((1, 128), 64, np.int32)
        assert int(stream_rf_op(offs, szs, interpret=True)[0]) == 127

    def test_percentage(self):
        offs = (np.arange(128, dtype=np.int32) * 1000)[None]
        szs = np.full((1, 128), 64, np.int32)
        assert float(random_percentage_op(offs, szs, interpret=True)[0]) == pytest.approx(1.0)

    def test_block_boundary_padding(self):
        """M not divisible by the stream block: padded rows must not leak."""

        rng = np.random.default_rng(9)
        offs = rng.integers(0, 1 << 20, size=(5, 128)).astype(np.int32)
        szs = np.full((5, 128), 17, np.int32)
        got = np.asarray(stream_rf_op(offs, szs, interpret=True, block_streams=4))
        want = np.asarray(stream_rf_ref(offs, szs))
        np.testing.assert_array_equal(got, want)


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
