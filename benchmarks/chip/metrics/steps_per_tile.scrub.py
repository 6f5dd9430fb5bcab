"""Tile steps per tile voted: the count of the program's
``pud/scrub.step`` spans over that of its ``pud/scrub.tile`` spans
(profiler trace; see ``scrub_trace.py``).  1 where every tile runs as
one jitted step; 0 where the tile loop issues its dispatches one by
one."""

from scrub_trace import span_per_call


def read(reading):
    tiles = span_per_call(reading, "scrub.tile", "count")
    if not tiles:
        return None
    return span_per_call(reading, "scrub.step", "count") / tiles
