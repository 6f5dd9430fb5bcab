"""Dispatches of a jitted level walk per call: the count of the
program's ``pud/backend.levels_jit`` spans over the window's calls
(profiler trace; see ``program_trace.py``).  1 where every call of the
window runs its schedule as one compiled program, 0 where the level
executor walks it op by op."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "backend.levels_jit", "count")
