"""The reducer against traces whose answers are known: interval arithmetic
by hand, a tiny hand-written xspace through the profiler's own reader, and
a slice of a trace recorded on the chip (``data/recorded_trace.json``)."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_subtract():
    busy = tr.union([(0, 5), (3, 8), (10, 12), (12, 13), (20, 20)])
    assert busy == [(0, 8), (10, 13)]
    assert tr.total(busy) == 11
    assert tr.gaps(busy, (0, 16)) == [(8, 10), (13, 16)]
    assert tr.clip(busy, (4, 11)) == [(4, 8), (10, 11)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]


def test_self_times_of_nested_operations():
    # a while of 8 holds a fusion of 5 and a kernel of 2: 1 is its own
    own = tr.self_times([("while.1", 0, 8), ("fusion.1", 0, 5),
                         ("custom-call.2", 6, 2), ("all-reduce.7", 12, 3)])
    assert own == {"while.1": 1, "fusion.1": 5, "custom-call.2": 2,
                   "all-reduce.7": 3}


def test_op_label():
    text = ('%checkpoint.19 = (bf16[512,1024,64]{2,1,0:T(8,128)(2,1)}, '
            'bf16[512,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[512,1024,64]'
            '{2,1,0:T(8,128)(2,1)} %bitcast.384), custom_call_target="tpu_custom_call"')
    assert tr.op_label(text) == "checkpoint.19 [custom-call]"
    assert tr.op_label("%fusion.3 = f32[8]{0:T(128)S(1)} fusion(f32[8]{0} %p), "
                       "kind=kLoop") == "fusion.3 [fusion]"
    assert tr.op_label("bench.feed") == "bench.feed"


@pytest.fixture
def tiny(tmp_path):
    from jax.profiler import ProfileData

    text = open(os.path.join(DATA, "tiny.xspace.txt")).read()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_tiny_xspace_through_the_profilers_reader(tiny):
    # chip 0: busy [0, 8) and [12, 15) of a 16 us window; chip 1 all of it
    t = tr.read_xplane(tiny, chips=2)
    assert t.window == (1000, 17000) and t.window_s == pytest.approx(16e-6)
    assert tr.total(t.busy(0)) == 11000 and tr.total(t.busy(1)) == 16000
    assert t.busy_s() == pytest.approx((11e-6 + 16e-6) / 2)
    assert t.idle_share() == pytest.approx(1 - 13.5 / 16)
    # one chip only: the second plane is not read
    assert tr.read_xplane(tiny, chips=1).idle_share() == pytest.approx(5 / 16)
    # the kernel is found by its HLO text, its time is the sum of its calls
    assert t.kernels == ["closed_call.2 [custom-call]"]
    assert t.leaf_time_s(t.is_kernel) == pytest.approx(2e-6)
    assert t.count(t.is_kernel) == 1
    # the collective ran with nothing beside it (the while had ended)
    assert t.collective_s(0) == pytest.approx((3e-6, 3e-6))
    # the step's module: two executions, whole inside the window
    assert t.main_module() == "jit_train_step(1)"
    assert sorted(t.module_durations_s("jit_train_step(1)")) == \
        pytest.approx([3e-6, 8e-6])
    # gaps [8, 12) and [15, 16): feed covers 3.5 of the first, dispatch 0.5;
    # nothing of the benchmark's is open in the second but the window span
    assert t.idle_by_span(0) == pytest.approx({"feed": 4e-6, "(no span)": 1e-6})
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1 [fusion]", pytest.approx(5e-6)]
    assert dict(map(tuple, b["device_ops"]))["while.1 [while]"] == pytest.approx(1e-6)
    assert b["idle_gaps"][0] == ["feed", pytest.approx(4e-6)]
    # foreign host events are not spans of the benchmark
    assert all(n in ("window", "feed", "dispatch") for n, _, _ in t.host)


def test_json_round_trip(tiny):
    t = tr.read_xplane(tiny, chips=2)
    again = tr.Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert again == t


def test_recorded_chip_trace_gives_known_values():
    doc = json.load(open(os.path.join(DATA, "recorded_trace.json")))
    t = tr.Trace.from_json(doc["trace"])
    want = doc["expected"]
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert t.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    assert t.leaf_time_s(t.is_kernel) == pytest.approx(want["kernel_s"], rel=1e-9)
    assert t.count(t.is_kernel) == want["kernel_calls"]
    assert t.idle_by_span(0) == pytest.approx(want["idle_by_span"], rel=1e-9)
    # and against a 10 ns grid marked operation by operation when the slice
    # was cut, which shares no code with the interval arithmetic
    assert t.busy_s() == pytest.approx(want["brute_force_busy_s_10ns_grid"],
                                       abs=2e-6)
    assert t.leaf_time_s(t.is_kernel) == pytest.approx(
        want["brute_force_kernel_s_10ns_grid"], abs=2e-6)
    assert sum(t.idle_by_span(0).values()) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-9)
