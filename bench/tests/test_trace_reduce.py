"""The trace reduction on a small trace recorded on a TPU v5e.

``data/small_trace/small.xplane.pb`` comes from ``bench/tools/record_trace.py``:
the paged decode-attention kernel jitted as ``small_step``, run three times
under ``host:step`` spans, with a 50 ms ``host:wait`` span before the third.
"""
from pathlib import Path

import numpy as np
import pytest

from bench.trace_reduce import _union, clip, reduce_trace, self_times, short_op

TRACE = Path(__file__).parent / "data" / "small_trace" / "small.xplane.pb"


def test_union_merges_overlaps():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [7.0, 8.0]])
    np.testing.assert_array_equal(_union(iv), [[0.0, 3.0], [5.0, 8.0]])


def test_clip_keeps_only_the_part_inside_the_window():
    iv = np.array([[0.0, 3.0], [5.0, 8.0], [9.0, 12.0], [13.0, 14.0]])
    np.testing.assert_array_equal(clip(iv, 2.0, 10.0), [[2.0, 3.0], [5.0, 8.0], [9.0, 10.0]])


def test_self_time_leaves_out_the_ops_inside():
    # a loop op of 10 holding two ops of 3 and 4; a lone op after it
    ev = [("loop", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 5.0, 4.0), ("c", 12.0, 2.0)]
    assert [round(d, 9) for _, _, d in self_times(ev)] == [3.0, 3.0, 4.0, 2.0]


def test_short_op_names():
    name = ("%copy.110 = bf16[36,2,5153,16,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[36,2,5153,16,"
            "128]{4,3,2,1,0:T(8,128)(2,1)} %get-tuple-element.793)")
    assert short_op(name) == "%copy.110 bf16[36,2,5153,16,128] copy"
    loop = "%while.5 = (s32[]{:T(128)}, bf16[32,2048]{1,0}) while((s32[], bf16[32,2048]) %t)"
    assert short_op(loop) == "%while.5 (tuple) while"


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE)


def test_one_device_busy_less_than_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < 0.05


def test_programs_counted_by_name(reduced):
    assert reduced["module_n"]["jit_small_step"] == 3
    # every op ran inside its program's span, a little shorter than it
    assert 0.99 * reduced["module_s"]["jit_small_step"] <= reduced["busy_s"]
    assert reduced["busy_s"] <= reduced["module_s"]["jit_small_step"]


def test_the_longest_gap_is_the_hosts_wait(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert name == "host:wait"
    assert 0.045 < seconds < 0.2


def test_the_kernel_is_found_among_the_ops(reduced):
    from bench.serving import NAMES

    kernel = [k for k in reduced["op_s"] if NAMES["kernel"].search(k)]
    assert kernel
    assert 0 < sum(reduced["op_s"][k] for k in kernel) <= reduced["busy_s"]
