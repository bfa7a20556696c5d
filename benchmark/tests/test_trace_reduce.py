"""The reduction from a trace to metrics, pinned on a small recording
from the chip (``tiny.xplane.pb``: the test-size LSTM cell, a 20 ms
window on one TPU v5e, my chip run, PR 24) and, for what one chip cannot
record, on hand-made intervals."""

import os

import pytest

import json

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDING = os.path.join(HERE, "tiny.xplane.pb")
SCOPES = os.path.join(HERE, "tiny.scopes.json")


@pytest.fixture(scope="module")
def recorded():
    with open(SCOPES) as f:
        scopes = json.load(f)
    return tr.reduce_file(RECORDING, chips=1, scopes=scopes)


def test_recording_busy_share_and_scope_times(recorded):
    # five steps of the test-size LSTM (hidden 128, batch 8, 12 tokens)
    assert len(recorded.devices) == 1
    assert len(recorded.devices[0].ops) == 2670
    assert recorded.window_s == pytest.approx(0.0213935, rel=1e-4)
    assert recorded.busy() == [pytest.approx(3.0748e-4, rel=1e-4)]
    assert recorded.busy_s == pytest.approx(3.0748e-4, rel=1e-4)
    assert recorded.idle_share() == pytest.approx(0.985627, rel=1e-5)
    # forward and backward of both recurrences, as a union of intervals
    assert recorded.scope_seconds(r"jvp\(lstm\d+\)") == \
        pytest.approx(1.26738e-4, rel=1e-4)
    assert recorded.scope_seconds(r"transpose\(jvp\(lstm0\)\)") == \
        pytest.approx(5.3725e-5, rel=1e-4)
    # one chip: no collective ran, so none is exposed
    assert recorded.exposed_collective_s() == 0.0


def test_recording_breakdown(recorded):
    ops = recorded.top_ops()
    assert [name for name, _ in ops[:3]] == \
        ["no_layer_scope", "lstm0.bwd", "lstm1.bwd"]
    assert ops[1][1] == pytest.approx(5.2207e-5, rel=1e-4)
    gaps = recorded.idle_gaps()
    assert len(gaps) == 10 and gaps[0][0] == "bench_feeder"
    assert gaps[0][1] == pytest.approx(2.98752e-3, rel=1e-4)
    assert {k: len(v) for k, v in recorded.host.items()} == {
        "bench_reader": 5, "bench_feeder": 5, "bench_event_handler": 4}


def test_union_total_subtract():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []


def test_exposed_collective_time_on_hand_made_intervals():
    # an all-reduce of 4 ms, 1.5 ms of it while a fusion still runs
    collectives = [(10.0, 14.0)]
    others = [(8.0, 11.5), (20.0, 21.0)]
    assert tr.exposed(collectives, others) == pytest.approx(2.5)
    ops = [tr.Op("fusion.1", "", 8.0, 11.5),
           tr.Op("all-reduce.3", "", 10.0, 14.0),
           tr.Op("fusion.2", "", 20.0, 21.0)]
    reduced = tr.Reduced([tr.Device(0, ops)], {}, 13.0)
    assert reduced.exposed_collective_s() == pytest.approx(2.5)
    assert reduced.busy() == [pytest.approx(7.0)]
    assert reduced.idle_share() == pytest.approx(1 - 7.0 / 13.0)


def test_an_enclosing_while_is_not_counted_twice():
    ops = [tr.Op("while.1", "jit(f)/transpose(jvp(lstm0))/while", 0.0, 10.0),
           tr.Op("fusion.7", "jit(f)/transpose(jvp(lstm0))/while/body/dot",
                 1.0, 4.0),
           tr.Op("fusion.9", "jit(f)/jvp(embed)/gather", 12.0, 13.0)]
    reduced = tr.Reduced([tr.Device(0, ops)], {}, 13.0)
    assert reduced.busy() == [pytest.approx(11.0)]
    assert reduced.scope_seconds(r"jvp\(lstm\d+\)") == pytest.approx(10.0)
    assert dict(reduced.top_ops())["lstm0.bwd"] == pytest.approx(3.0)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [tr.Op("fusion.1", "", 0.0, 1.0), tr.Op("fusion.2", "", 3.0, 4.0),
           tr.Op("fusion.3", "", 4.5, 5.0)]
    host = {"bench_reader": [(1.1, 2.9)], "bench_event_handler": []}
    reduced = tr.Reduced([tr.Device(0, ops)], host, 5.0)
    assert reduced.idle_gaps() == [["bench_reader", 2.0], ["trainer", 0.5]]


def test_scope_labels():
    assert tr.scope_label(
        "jit(traced)/jit(main)/transpose(jvp(lstm1))/while/body/dot") == \
        "lstm1.bwd"
    assert tr.scope_label("jit(traced)/jit(main)/jvp(stem_conv)/conv") == \
        "conv.fwd"
    assert tr.scope_label("jit(step)/transpose(jvp(lstm1_proj))/dot") == \
        "proj.bwd"
    assert tr.scope_label("jit(step)/jit(main)/mul") == "no_layer_scope"
    assert tr.scope_label("") == ""
    hlo = '''
  %fusion.9 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jit(main)/jvp(lstm0)/while/body/add" source_file="x.py" source_line=3}
  ROOT %while.2 = (s32[], f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/transpose(jvp(lstm0))/while"}
'''
    assert tr.scopes_from_hlo(hlo) == {
        "fusion.9": "jit(step)/jit(main)/jvp(lstm0)/while/body/add",
        "while.2": "jit(step)/jit(main)/transpose(jvp(lstm0))/while"}
    assert tr.instruction("%fusion.92 = f32[100]{0} fusion(f32[5] %x)") == \
        "fusion.92"
