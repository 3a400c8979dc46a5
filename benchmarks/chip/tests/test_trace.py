"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_sample_trace.py``): three rounds of a bf16 matmul, the fused
round-fold kernels and the graph-combine kernel, each round inside a
``bench.round`` host span."""
import os

import pytest

import trace_reduce

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(SAMPLE)


def test_finds_the_chip_and_its_ops(summary):
    assert summary.n_devices == 1
    assert len(summary.ops) == 33
    assert 0 < summary.busy_s < summary.window_s


def test_names_and_opcodes(summary):
    kinds = {(o.name, o.opcode) for o in summary.ops}
    assert ("copy-start", "copy-start") in kinds
    assert ("convolution_reduce_fusion", "fusion") in kinds
    fold = [o for o in summary.ops
            if o.opcode == "custom-call" and o.name.startswith("round_fold")]
    combine = [o for o in summary.ops if o.opcode == "custom-call"
               and o.name.startswith("graph_combine")]
    assert len(fold) == 6 and len(combine) == 3


def test_kernel_seconds_sum_their_events(summary):
    s = summary.seconds(lambda o: o.name.startswith("graph_combine"))
    assert s == pytest.approx((2992 + 3078 + 3052) * 1e-9)


def test_breakdown_lists(summary):
    top = summary.top_ops(10)
    assert len(top) == 10
    assert top[0][0] == "round_fold"
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    gaps = summary.idle_gaps()
    assert {name for name, _ in gaps} <= {"bench.round", "host:none"}
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-9)


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
