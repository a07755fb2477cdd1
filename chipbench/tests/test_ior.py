"""The vectorized IOR generators against ``repro.core.workloads``."""

import numpy as np
import pytest

from chipbench import ior
from repro.core import workloads


@pytest.mark.parametrize("nproc,segments,transfer", [(8, 64, 47008), (16, 33, 4096)])
def test_strided_offsets_match(nproc, segments, transfer):
    got = ior.strided_offsets(nproc, segments, transfer)
    want = workloads._strided_offsets(nproc, nproc * segments * transfer, transfer)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("nproc,segments,transfer", [(8, 64, 2 << 20), (5, 17, 4096)])
def test_file_per_process_offsets_match(nproc, segments, transfer):
    got = ior.file_per_process_offsets(nproc, segments, transfer)
    want = workloads._segmented_contiguous_offsets(nproc, nproc * segments * transfer, transfer)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("skew", [0.0, 1.0, 14.0])
def test_merge_is_the_original_merge(skew):
    per_proc = ior.strided_offsets(12, 40, 47008)
    offs, procs = ior.merge_arrivals(per_proc, np.random.default_rng(5), skew)
    want = workloads.merge_arrivals(per_proc, 47008, np.random.default_rng(5), skew=skew)
    assert offs.tolist() == [r.offset for r in want]
    assert sorted(offs.tolist()) == sorted(np.concatenate(per_proc).tolist())
    assert procs.tolist() == [int(o // 47008) % 12 for o in offs]


@pytest.mark.parametrize("name", ["ior_hard", "ior_easy", "ior_easy_ssdup"])
def test_trace_per_seed_and_job(name):
    from .conftest import config_cell, shrink

    cfg = shrink(config_cell(name)).cfg
    a = ior.ior_trace(cfg, 2**31 + 7, 3)
    b = ior.ior_trace(cfg, 2**31 + 7, 3)
    c = ior.ior_trace(cfg, 2**31 + 7, 4)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["offsets"], c["offsets"])
    # the same offsets whatever the draw: shard sizes never change
    assert np.array_equal(np.sort(a["offsets"]), np.sort(c["offsets"]))
    if cfg["file_per_process"]:
        assert np.array_equal(a["file_ids"], a["offsets"] // (cfg["segments"] * cfg["transfer_bytes"]))
