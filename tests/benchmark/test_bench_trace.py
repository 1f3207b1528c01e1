"""`benchmark/trace_reduce.py`: the interval arithmetic on hand-made
events, and the whole reduction on a small trace recorded on the chip
(`benchmark/testdata/tiny.xplane.pb`: a toy-sized cell, 0.05 s of a
served TPU_BATCH run on one v5e, my chip run, PR 24)."""

import json
import os

import pytest

from conftest import BENCH

TINY = os.path.join(BENCH, "testdata", "tiny.xplane.pb")


def test_union_and_gaps(trace_reduce):
    ev = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (32, 35, "d")]
    busy, gaps = trace_reduce.union_s(ev)
    assert busy == pytest.approx(30e-9) and gaps == [(20, 30)]
    assert trace_reduce.union_s([]) == (0.0, [])


def test_self_time_charges_a_loop_only_what_its_body_leaves(trace_reduce):
    # a while of 100 ns around two fusions of 30 ns and one nested pair
    ev = [(0, 100, "while"), (10, 40, "fusion.1"), (50, 80, "fusion.2"),
          (55, 60, "inner"), (200, 210, "fusion.1")]
    st = trace_reduce.self_times(ev)
    assert st["while"] == pytest.approx(40e-9)
    assert st["fusion.1"] == pytest.approx(40e-9)
    assert st["fusion.2"] == pytest.approx(25e-9)
    assert st["inner"] == pytest.approx(5e-9)
    assert sum(st.values()) == pytest.approx(110e-9)    # == the union


def test_gaps_are_named_by_the_host_event_that_covers_them(trace_reduce):
    host = [(0, 50, "np.asarray(jax.Array)"), (40, 45, "Execute")]
    out = trace_reduce.name_gaps([(10, 30), (41, 44), (100, 101)], host)
    assert out[0] == ["np.asarray(jax.Array)", pytest.approx(20e-9)]
    assert out[2] == ["no host event", pytest.approx(1e-9)]


def test_a_trace_without_a_device_plane_is_refused(trace_reduce):
    class Plane:
        name, lines = "/host:CPU", []

    class Prof:
        planes = [Plane()]
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(Prof(), 1.0, 4)


def _profile(ops):
    """A one-chip profile whose "XLA Ops" line holds ``ops``."""
    class Ev:
        def __init__(self, a, b, n):
            self.start_ns, self.duration_ns, self.name = a, b - a, n

    class Line:
        name = "XLA Ops"
        events = [Ev(*o) for o in ops]

    class Plane:
        name, lines = "/device:TPU:0", [Line()]

    class Prof:
        planes = [Plane()]
    return Prof()


@pytest.mark.parametrize("host_window_s, want_window_s", [
    (2.0, 2.0),         # the host's reading encloses the operations
    (0.9, 1.0),         # the profiler recorded from before the reading
])
def test_busy_time_never_passes_the_window(trace_reduce, host_window_s,
                                           want_window_s):
    ops = [(0, 600_000_000, "a"), (600_000_000, 1_000_000_000, "b")]
    got = trace_reduce.reduce(_profile(ops), host_window_s, 4)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["window_s"] == pytest.approx(want_window_s)
    assert got["busy_s"] <= got["window_s"]


def test_the_recorded_chip_trace_reduces_to_its_known_numbers(trace_reduce):
    want = json.load(open(os.path.join(BENCH, "testdata",
                                       "tiny_expected.json")))
    got = trace_reduce.reduce(trace_reduce.load(TINY), want["window_s"],
                              want["epochs_per_group"])
    assert got["chips"] == 1 and got["window_s"] == want["window_s"]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["groups"] == want["groups"] > 0
    assert got["epochs"] == want["groups"] * want["epochs_per_group"]
    assert got["group_busy_s"] == pytest.approx(want["group_busy_s"])
    ops = got["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and ops[0][0] == want["top_op"]
    assert ops == sorted(ops, key=lambda r: -r[1])
    # self times never add up to more than the device was busy
    assert sum(s for _, s in ops) <= got["busy_s"] * (1 + 1e-9)
    assert len(got["breakdown"]["idle_gaps"]) <= 10
