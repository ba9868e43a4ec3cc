"""The trace reducer on a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``: three decode-sized steps through the
program's fused LoRA kernel, each followed by a 2 ms host sleep)."""
from pathlib import Path

import pytest

from lib import trace_reduce

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(str(TRACE),
                                     host_spans=("decode", "host-wait"),
                                     kernels=("lora_matmul",))


def test_window_and_busy(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(9.84769e-3, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(5.2826e-5, abs=1e-10)
    assert reduced["clock_offset_ns"] == [1708396.0]


def test_ops_and_kernel(reduced):
    assert reduced["kernel_calls"] == {"lora_matmul": 3}
    assert reduced["kernel_s"]["lora_matmul"] == pytest.approx(4.0518e-5,
                                                                abs=1e-10)
    assert set(reduced["ops"]) == {"lora_matmul.1", "fusion"}
    assert sum(reduced["ops"].values()) == pytest.approx(reduced["busy_s"])


def test_idle_gaps_named_by_host_span(reduced):
    idle = reduced["idle_by_span"]
    assert set(idle) == {"decode", "host-wait"}
    assert idle["host-wait"] > 8e-3 > idle["decode"]
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_top_orders_by_time():
    assert trace_reduce.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [
        ["b", 3.0], ["c", 2.0]]


def test_op_name_is_the_hlo_name():
    assert trace_reduce.op_name(
        "%fusion.3 = f32[8,768]{1,0} fusion(f32[8,768] %lora_matmul.1)"
    ) == "fusion.3"


def test_self_time_excludes_nested_ops():
    # a while op spanning two body ops, then a lone op
    events = [(0.0, 100.0, "while.1"), (10.0, 30.0, "fusion.1"),
              (40.0, 90.0, "fusion.2"), (120.0, 130.0, "copy.1")]
    got = trace_reduce._self_times(events)
    assert got == pytest.approx({"while.1": 30e-9, "fusion.1": 20e-9,
                                 "fusion.2": 50e-9, "copy.1": 10e-9})
