"""The reader of the scrub's tile steps, on small hand-made traces."""

import pytest

from test_program_trace import host
from test_tmr_scrub_cell import SPANS, _reading, _readers

READER = "steps_per_tile.scrub"

#: One scrub call of two tiles, each tile one step.
STEPS = [("bench/window", 0, 1000),
         ("pud/service.scrub", 100, 800),
         ("pud/scrub.tile", 110, 390),
         ("pud/scrub.step", 120, 300),
         ("pud/scrub.tile", 500, 390),
         ("pud/scrub.step", 510, 300)]


def test_a_step_in_every_tile_reads_one():
    r = _reading([host(main=STEPS)])
    assert _readers()[READER].read(r) == pytest.approx(1.0)


def test_tiles_without_a_step_read_zero():
    r = _reading([host(main=SPANS)])
    assert _readers()[READER].read(r) == 0.0


def test_a_trace_without_the_scrub_span_reads_nothing():
    no_scrub = [("bench/window", 0, 1000), ("pud/elementwise", 100, 800),
                ("pud/scrub.step", 150, 150)]
    r = _reading([host(main=no_scrub)])
    assert _readers()[READER].read(r) is None
