"""Unit tests for the config + stats layers (SURVEY §1 L1/L11)."""

import pytest

from deneva_tpu.config import Config, CCAlg, WorkloadKind
from deneva_tpu.stats import Stats, StatsArr, parse_summary


def test_config_defaults_validate():
    cfg = Config().validate()
    assert cfg.cc_alg == CCAlg.TPU_BATCH
    assert cfg.workload == WorkloadKind.YCSB


def test_config_from_args_roundtrip():
    cfg = Config.from_args([
        "--cc-alg=OCC", "--zipf-theta", "0.9", "--epoch_batch=1024",
        "--node_cnt=4", "--backoff=false", "--mesh_shape=(8,)",
    ])
    assert cfg.cc_alg == CCAlg.OCC
    assert cfg.zipf_theta == 0.9
    assert cfg.epoch_batch == 1024
    assert cfg.node_cnt == 4
    assert cfg.backoff is False
    assert cfg.mesh_shape == (8,)


def test_config_rejects_unknown_and_bad():
    with pytest.raises(ValueError):
        Config.from_args(["--nonsense=1"])
    # a field that went with its only reader (the host codec pool) fails
    # like any unknown one, never silently ignored
    with pytest.raises(ValueError, match="unknown config field"):
        Config.from_args(["--thread_cnt=2"])
    with pytest.raises(ValueError):
        Config(epoch_batch=1000).validate()  # not a power of two
    with pytest.raises(ValueError):
        Config().validate().replace(epoch_batch=1000)  # replace re-validates


def test_stats_arr_percentiles():
    # weighted nearest-rank, matching the reference's sorted-array
    # indexing (stats_array.cpp:127-146 get_idx)
    a = StatsArr(cap=4)
    a.extend(range(1, 101))
    assert a.percentile(50) == pytest.approx(50.0)
    assert a.percentile(99) == pytest.approx(99.0)
    assert len(a) == 100


def test_stats_arr_weighted_equals_expanded():
    """extend_weighted(values, counts) is exactly the expanded multiset —
    the driver feeds whole latency histograms through this path with no
    sample cap (round-1 weakness #7 fixed)."""
    import numpy as np
    vals = np.array([0.5, 1.5, 2.5, 3.5])
    counts = np.array([500_000, 300_000, 150_000, 50_000])
    w = StatsArr()
    w.extend_weighted(vals, counts)
    e = StatsArr()
    e.extend(np.repeat(vals, counts))
    assert len(w) == counts.sum() == len(e)
    for p in (50, 90, 95, 99):
        assert w.percentile(p) == e.percentile(p)
    assert w.mean() == pytest.approx(e.mean())


def test_stats_merge_and_summary_roundtrip():
    s1, s2 = Stats(), Stats()
    s1.incr("total_txn_commit_cnt", 100)
    s1.incr("total_txn_abort_cnt", 7)
    s2.incr("total_txn_commit_cnt", 50)
    s2.arr("client_client_latency").extend([1.0, 2.0, 3.0])
    s1.merge(s2)
    s1.set("total_runtime", 2.0)

    line = s1.summary_line()
    assert line.startswith("[summary] total_runtime=2,tput=75,txn_cnt=150")
    fields = parse_summary(line)
    assert fields["total_txn_commit_cnt"] == 150
    assert fields["total_txn_abort_cnt"] == 7
    assert fields["client_client_latency_p50"] == 2.0


def test_prog_line_and_proc_utilization():
    """[prog] tick parity (system/thread.cpp:86-105 + stats.h:311-316
    mem/cpu utilization from /proc/self)."""
    import sys

    from deneva_tpu.stats import proc_utilization

    u = proc_utilization()
    if sys.platform == "linux":     # zeros are the documented non-/proc fallback
        assert u["mem_util"] > 1.0  # this process surely exceeds 1 MiB RSS
        assert u["cpu_util"] > 0.0
    assert u["cpu_util"] >= 0.0
    s = Stats()
    s.incr("total_txn_commit_cnt", 40)
    s.set("total_runtime", 2.0)
    line = s.prog_line({"epoch_cnt": 9})
    assert line.startswith("[prog] total_runtime=2,tput=20,txn_cnt=40")
    assert "mem_util=" in line and "cpu_util=" in line
    assert line.endswith("epoch_cnt=9")


def test_stats_arr_boundary_ranks():
    """Weighted nearest-rank at the boundary ranks: p0 is the min, p100
    the max, a single bucket answers every percentile with its value,
    and huge weights neither overflow nor skew the rank arithmetic."""
    from deneva_tpu.stats import StatsArr, weighted_nearest_rank

    a = StatsArr()
    a.extend([5.0, 1.0, 9.0])
    assert a.percentile(0) == 1.0
    assert a.percentile(100) == 9.0
    # single bucket: every rank answers the one value
    b = StatsArr()
    b.extend_weighted([42.0], [7])
    for p in (0, 1, 50, 99, 100):
        assert b.percentile(p) == 42.0
    assert len(b) == 7
    # huge weights: 1e12 copies of 1.0 vs one copy of 100.0 — p99 must
    # stay at the heavy value (float64 cumsum holds the exact rank)
    c = StatsArr()
    c.extend_weighted([1.0, 100.0], [1e12, 1.0])
    assert c.percentile(99) == 1.0
    assert c.percentile(100) == 100.0
    # empty / zero-weight input answers 0 by contract
    assert StatsArr().percentile(50) == 0.0
    assert weighted_nearest_rank([], None, 50) == 0.0
    assert weighted_nearest_rank([3.0], [0.0], 50) == 0.0
    # the shared helper agrees with the array path (one definition)
    assert weighted_nearest_rank([5.0, 1.0, 9.0], None, 0) == 1.0
    assert weighted_nearest_rank([5.0, 1.0, 9.0], None, 100) == 9.0


def test_stats_arr_merge_from_grown_buffers():
    """merge_from on arrays that outgrew their initial capacity: the
    splice must copy only the LIVE prefix (amortized growth leaves
    np.resize garbage past _n) and weighted entries merge exactly."""
    import numpy as np

    from deneva_tpu.stats import StatsArr

    a = StatsArr(cap=4)
    a.extend(np.arange(100, dtype=np.float64))     # grows 4 -> 128
    assert len(a) == 100
    b = StatsArr(cap=4)
    b.extend_weighted([1000.0, 2000.0], [50, 50])  # weighted source
    b.extend(np.arange(100, 170, dtype=np.float64))  # grown + mixed
    a.merge_from(b)
    assert len(a) == 100 + 100 + 70
    # the merged multiset ranks exactly: 170 unit samples 0..169 below
    # the 100 heavy samples at 1000/2000
    assert a.percentile(100) == 2000.0
    assert a.percentile(0) == 0.0
    # 170/270 ~ 63% of mass below 170: p50 lands inside the unit ramp,
    # p75 inside the heavy tail
    assert a.percentile(50) < 170.0
    assert a.percentile(75) == 1000.0
    # view() expands weights for small series — the oracle the
    # percentile path must match
    v = np.sort(a.view())
    assert len(v) == 270
    assert v[-1] == 2000.0 and (v[:170] == np.arange(170)).all()
    # merging an EMPTY grown array is a no-op
    c = StatsArr(cap=4)
    n0 = len(a)
    a.merge_from(c)
    assert len(a) == n0
