"""``benchmark/probes/gaps.py``: idle gaps named by the program's spans,
on the recording from the chip (PR 24's, from before the program wrote
any span: every gap falls back to "no program span") and on hand-made
intervals."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.probes import gaps

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDING = os.path.join(HERE, "tiny.xplane.pb")


def test_a_trace_without_program_spans_names_no_gap():
    spans = gaps.host_spans(RECORDING)
    assert spans == []
    busy = tr.reduce_file(RECORDING, chips=1).devices[0].intervals()
    out = gaps.report(busy, spans)
    assert out["steps"] == 0 and len(out["longest"]) == 10
    # the same gaps trace_reduce finds, longest first
    assert out["longest"][0]["ms"] == pytest.approx(2.98752, rel=1e-4)
    for row in out["longest"]:
        assert row["train"] == row["prefetch"] == gaps.NO_SPAN
        assert row["step"] is None and row["shares"] == {}
    assert list(out["idle_ms_a_step_by_span"]) == [gaps.NO_SPAN]
    assert gaps.main([RECORDING]) == 0


def _spans():
    S = gaps.Span
    return [
        # step 7 on the trainer's thread: 10.0 .. 10.1
        S("train.step", 7, 10.000, 10.100),
        S("train.data_wait", 7, 10.001, 10.002),
        S("train.dispatch", 7, 10.002, 10.005),
        S("train.device_wait", 7, 10.005, 10.090),
        S("train.callback", 7, 10.091, 10.099),
        S("train.step", 8, 10.100, 10.200),
        S("train.data_wait", 8, 10.100, 10.140),
        S("train.dispatch", 8, 10.140, 10.143),
        # the prefetch thread works on batch 8, then 9, beside them
        S("prefetch.decode", 8, 10.050, 10.120),
        S("prefetch.h2d", 8, 10.120, 10.139),
        S("prefetch.read", 9, 10.1395, 10.1400),
    ]


def test_gaps_on_hand_made_intervals():
    # the device runs step 7 from 10.004 to 10.088, step 8 from 10.142
    busy = [(9.900, 9.999), (10.004, 10.050), (10.050, 10.088),
            (10.142, 10.180)]
    out = gaps.report(busy, _spans())
    assert out["steps"] == 2
    long, short = out["longest"][0], out["longest"][1]
    # 54 ms between the steps: the trainer waits for its batch while the
    # worker decodes and copies it
    assert long["ms"] == pytest.approx(54.0)
    assert (long["train"], long["step"]) == ("train.data_wait", 8)
    assert (long["prefetch"], long["prefetch_step"]) == \
        ("prefetch.decode", 8)
    # it begins in step 7 and ends in step 8
    assert long["shares"]["train.device_wait"] == pytest.approx(2 / 54)
    assert long["shares"]["train.callback"] == pytest.approx(8 / 54)
    assert long["shares"]["train.data_wait+1"] == pytest.approx(40 / 54)
    assert long["shares"]["train.dispatch+1"] == pytest.approx(2 / 54)
    # between two brackets: step 7's own, 1 ms before its callback and 1
    # after
    assert long["shares"]["train.step"] == pytest.approx(2 / 54)
    assert "train.step+1" not in long["shares"]
    assert long["shares"]["prefetch.decode"] == pytest.approx(32 / 54)
    assert long["shares"]["prefetch.h2d"] == pytest.approx(19 / 54)
    assert sum(v for k, v in long["shares"].items()
               if k.startswith("train.")) == pytest.approx(1.0)
    # 5 ms before step 7's first kernel: 1 of it before the step began
    assert short["ms"] == pytest.approx(5.0)
    assert (short["train"], short["step"]) == ("train.dispatch", 7)
    assert short["prefetch"] == gaps.NO_SPAN
    by_span = out["idle_ms_a_step_by_span"]
    assert by_span["train.data_wait+1"] == pytest.approx(40 / 2)
    assert by_span["train.data_wait"] == pytest.approx(1 / 2)
    # 8 whole-step brackets' seconds over the two steps' 200 ms
    assert out["coverage"] == pytest.approx(
        (1 + 3 + 85 + 8 + 40 + 3) / 200)
    assert by_span[gaps.NO_SPAN] == pytest.approx(1 / 2)
    assert sum(v for k, v in by_span.items()
               if not k.startswith("prefetch.")) == \
        pytest.approx(out["idle_ms_a_step_in_long_gaps"])


def test_a_gap_between_two_brackets_is_the_steps_own():
    spans = [gaps.Span("train.step", 3, 0.0, 1.0),
             gaps.Span("train.dispatch", 3, 0.0, 0.2)]
    row = gaps.name_gap((0.5, 0.6), spans)
    assert (row["train"], row["step"]) == ("train.step", 3)
    assert row["shares"] == {"train.step": pytest.approx(1.0)}
