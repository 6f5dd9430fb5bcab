"""The reader of the compile layer's traces, on small hand-made traces."""

import pytest

from test_program_trace import CALL, _reading, _readers, host

READER = "compile_traces_per_call.arith"


def test_a_trace_in_one_of_two_calls_reads_one_half():
    calls = CALL[:3] + [("pud/compile.trace", 120, 200),
                        ("pud/compile.sync", 150, 50)] + CALL[6:]
    r = _reading([host(main=calls)])
    assert _readers()[READER].read(r) == pytest.approx(0.5)


def test_a_trace_after_the_window_is_not_counted():
    calls = CALL + [("pud/compile.trace", 1105, 10)]
    r = _reading([host(main=calls)])
    assert _readers()[READER].read(r) == 0.0


def test_an_instrumented_trace_without_the_span_reads_zero():
    r = _reading([host(main=CALL)])
    assert _readers()[READER].read(r) == 0.0


def test_a_trace_without_program_spans_reads_nothing():
    r = _reading([host(main=[("bench/window", 0, 1000),
                             ("bench/session.elementwise", 100, 800)])])
    assert _readers()[READER].read(r) is None
