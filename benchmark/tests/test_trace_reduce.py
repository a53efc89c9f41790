"""The trace reduction, against the one real TPU trace the repo holds."""
import glob
import os

import pytest

import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = glob.glob(os.path.join(ROOT, "tpu_profile_r05", "plugins",
                                 "profile", "*", "*.xplane.pb"))


@pytest.mark.skipif(not FIXTURE, reason="tpu_profile_r05 is gone")
def test_reduction_of_a_real_trace():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    red = trace_reduce.reduce(FIXTURE[0])
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = 100.0 * (1 - red["busy_s"] / red["window_s"])
    assert 0.0 <= idle <= 100.0
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert all(sec > 0 for _n, sec in red["device_ops"])
    assert red["idle_gaps"] and len(red["idle_gaps"]) <= 10
    assert all(name and sec > 0 for name, sec in red["idle_gaps"])
    secs, count = trace_reduce.time_matching(red, r"copy")
    assert count > 0 and 0 < secs <= red["busy_s"] * 1.0001
    name, runs, mod_s = trace_reduce.dominant_module(red)
    assert runs >= 1 and mod_s > 0


def test_union_and_gaps():
    assert trace_reduce._union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce._gaps([(0, 10), (5, 20), (30, 40)], 0, 50) \
        == [(20, 30), (40, 50)]
    assert trace_reduce.short_name("%fusion.1 = f32[2] fusion(x)") \
        == "%fusion.1"
