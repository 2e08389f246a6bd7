"""The trace reduction against a small trace recorded on a v5e
(``record_trace.py``): one ``bench.window`` holding 3 ``bench.tick``
spans of 2 ``jit_step`` executions each, each tick followed by a 50 ms
``bench.wait`` with the device idle."""
import pathlib

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data" / \
    "tpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(DATA)


def test_window_and_device(reduced):
    assert reduced.devices == 1
    assert 0.17 < reduced.window_s < 1.0
    assert reduced.spans["bench.tick"] and len(reduced.spans["bench.tick"]) == 3
    assert len(reduced.spans["bench.wait"]) == 3


def test_program_time(reduced):
    assert reduced.program_n == {"jit_step": 6}
    busy = reduced.busy_s
    # a program's span also holds the slivers between its ops
    assert 0.9 * busy < reduced.program_s["jit_step"] <= 1.05 * busy
    assert busy < 0.05 * reduced.window_s


def test_idle_gaps_are_named_by_the_span_they_fall_in(reduced):
    gaps = dict(reduced.breakdown()["idle_gaps"])
    idle = reduced.window_s - reduced.busy_s
    assert abs(sum(s for _, s in reduced.gaps) - idle) < 1e-6
    assert gaps["bench.wait"] > 0.14          # 3 x 50 ms with no work
    assert gaps["other"] < 0.03               # the 20 ms before the ticks
    tick_idle, n = reduced.idle_in("bench.tick")
    assert n == 3
    # the device's clock reads about 1 ms early against the host's in
    # this trace, so the 12 us of work per tick falls just before its span
    assert 0 <= tick_idle <= sum(b - a for a, b in reduced.spans["bench.tick"])


def test_device_ops_are_named_by_program_and_op(reduced):
    ops = reduced.breakdown()["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(name.startswith("jit_step/") for name, _ in ops)
    assert not any(name.split("/")[1] in trace_reduce._CONTAINERS
                   for name, _ in ops)


def test_base_name():
    assert trace_reduce.base_name("jit_branch_segment(123)") == \
        "jit_branch_segment"
    assert trace_reduce.base_name(
        "%multiply_add_fusion.5 = bf16[48,256,768]{2,1,0} fusion(x)") == \
        "multiply_add_fusion"
    assert trace_reduce.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
