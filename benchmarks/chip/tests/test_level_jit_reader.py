"""The reader of the level executor's jitted dispatches, on small
hand-made traces."""

import pytest

from test_program_trace import CALL, _reading, _readers, host

READER = "level_jit_per_call.arith"


def test_one_jitted_dispatch_per_call_reads_one():
    # Two calls inside the window, each with one jitted dispatch.
    calls = CALL[:9] + [("pud/backend.levels_jit", 510, 100),
                        ("pud/backend.run_fused", 910, 40),
                        ("pud/backend.levels_jit", 915, 20)]
    r = _reading([host(main=calls)])
    assert _readers()[READER].read(r) == pytest.approx(1.0)


def test_a_build_is_not_a_jitted_dispatch():
    calls = CALL[:9] + [("pud/backend.levels_build", 510, 100)]
    r = _reading([host(main=calls)])
    assert _readers()[READER].read(r) == 0.0


def test_an_instrumented_trace_without_the_span_reads_zero():
    r = _reading([host(main=CALL)])
    assert _readers()[READER].read(r) == 0.0


def test_a_trace_without_program_spans_reads_nothing():
    r = _reading([host(main=[("bench/window", 0, 1000),
                             ("bench/session.elementwise", 100, 800)])])
    assert _readers()[READER].read(r) is None
