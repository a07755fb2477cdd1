"""Kernel micro-benchmarks (interpret mode on CPU — correctness-grade
timing only; the real perf story is the §Roofline analysis).

Reports per-call wall time for the Pallas paths and the derived work:
streams/s for stream_rf, attention FLOPs for flash_attention, plus the
jnp-oracle comparison so the CSV captures the overhead of interpret mode
honestly.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import Row, emit, timeit
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.stream_rf.ops import stream_rf_op
from repro.kernels.stream_rf.ref import stream_rf_ref


def run() -> list[Row]:
    rows: list[Row] = []
    print("\n== kernel micro (interpret mode; correctness-grade timing) ==")
    rng = np.random.default_rng(0)

    offs = rng.integers(0, 1 << 24, size=(512, 128)).astype(np.int32)
    szs = np.full((512, 128), 256 * 1024, np.int32)
    interpret = jax.default_backend() != "tpu"

    def pallas(o, s):
        return stream_rf_op(o, s, interpret=interpret)

    for name, fn in (("stream_rf_pallas", pallas),
                     ("stream_rf_ref", stream_rf_ref)):
        out = fn(offs, szs)  # warmup/compile
        us, _ = timeit(lambda: jax.block_until_ready(fn(offs, szs)), repeat=3)
        sps = 512 / (us / 1e6)
        print(f"{name:22s} {us:10.1f} us/call  {sps:12.0f} streams/s")
        rows.append(Row(name, us, f"streams_per_s={sps:.0f}"))

    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    flops = 4 * 1 * 4 * 256 * 256 * 64  # qk + pv
    for name, fn in (
        ("flash_attn_pallas", lambda: flash_attention_op(
            q, k, v, causal=True, block_q=64, block_k=64)),
        ("flash_attn_ref", lambda: flash_attention_ref(q, k, v, causal=True)),
    ):
        jax.block_until_ready(fn())
        us, _ = timeit(lambda: jax.block_until_ready(fn()), repeat=3)
        print(f"{name:22s} {us:10.1f} us/call  {flops/(us/1e6)/1e9:8.2f} GFLOP/s")
        rows.append(Row(name, us, f"gflops={flops/(us/1e6)/1e9:.2f}"))
    return rows


if __name__ == "__main__":
    emit(run())
