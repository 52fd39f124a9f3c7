"""(a) The trace reduction: interval arithmetic on made-up planes, and exact
numbers from one small recorded device trace (``fixture.xplane.pb``, captured
on a TPU v5e from this benchmark's own rehearsal-size training run)."""
import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture.xplane.pb")
EXPECTED = os.path.join(HERE, "fixture.expected.json")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 3)]) == [[0, 3], [5, 7]]
    assert tr.length([[0, 3], [5, 7]]) == 5
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == \
        [[0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def _planes():
    s = 1e9   # seconds to ns
    dev0 = [("fusion.1", 0.0 * s, 1.0 * s), ("all-reduce.2", 1.0 * s, 1.5 * s),
            ("fusion.3", 1.25 * s, 2.0 * s), ("fusion.1", 3.0 * s, 3.5 * s),
            ("copy.9", 9.0 * s, 12.0 * s)]          # runs past the window
    dev1 = [("fusion.1", 0.0 * s, 1.0 * s)]
    host = [("bench.window", 0.0, 4.0 * s), ("bench.step", 0.0, 0.1 * s),
            ("bench.wait", 2.0 * s, 2.9 * s), ("bench.step", 3.6 * s, 3.7 * s)]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host, "lines": {}}


def test_reduction_of_made_up_planes():
    out = tr.reduce_planes(_planes(), default_gap_label="engine", top=3)
    assert out["window_s"] == 4.0
    d0, d1 = out["devices"]["/device:TPU:0"], out["devices"]["/device:TPU:1"]
    assert d0["busy_s"] == 2.5 and d1["busy_s"] == 1.0
    assert out["busy_s"] == 1.75                      # mean over devices
    assert out["idle_fraction_max"] == 0.75           # the idler device
    assert d0["collective_s"] == 0.5
    assert d0["collective_exposed_s"] == 0.25         # 1.0-1.25 alone
    assert out["collective_exposed_s"] == 0.25
    assert out["device_ops"] == [["fusion.1", 1.5], ["fusion.3", 0.75],
                                 ["all-reduce.2", 0.5]]
    # gaps of device 0: 2.0-3.0 (mostly bench.wait) and 3.5-4.0 (nothing
    # of the benchmark's covers half of it: the default label)
    assert out["idle_gaps"] == [["bench.wait", 1.0], ["engine", 0.5]]
    assert out["spans"] == {"bench.step": 0.2, "bench.wait": 0.9}


def test_device_plane_names():
    assert tr._is_device_plane("/device:TPU:0")
    assert not tr._is_device_plane("/device:TPU:0 SparseCore 1")
    assert not tr._is_device_plane("/host:CPU")


def test_no_device_plane_is_an_error():
    with pytest.raises(tr.TraceError):
        tr.reduce_planes({"devices": {}, "host": [], "lines": {}})


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_trace_reproduces_exact_numbers():
    with open(EXPECTED) as f:
        want = json.load(f)
    got = tr.reduce_file(FIXTURE, default_gap_label="host")
    assert got["window_s"] == want["window_s"]
    assert got["busy_s"] == want["busy_s"]
    assert got["idle_fraction_max"] == want["idle_fraction_max"]
    assert got["device_ops"][:5] == want["device_ops"][:5]
    assert got["idle_gaps"][:3] == want["idle_gaps"][:3]
    assert 0.0 < got["busy_s"] < got["window_s"]
    assert abs(1.0 - got["busy_s"] / got["window_s"]
               - got["idle_fraction_max"]) < 1e-12
