"""The program's host spans (``repro.obs``), read back from a profile.

``DramSession("pallas").elementwise("add", ...)`` at 64 lanes runs under
``jax.profiler`` twice, each call in its own profile: the first under a
fresh compile cache (a miss, which traces), the second on other operands
(a hit).  Each ``.xplane.pb`` is read with ``jax.profiler.ProfileData``.
"""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.compile import trace
from repro.session import DramSession

ONCE = ("elementwise", "compile", "session.run_fused", "backend.run_fused")

#: What a miss opens besides: the trace, and its one read of the
#: operands' origin IDs.
MISS = ("compile.trace", "compile.sync")

#: Each span and the span it opens inside.
PARENT = {"compile": "elementwise", "session.run_fused": "elementwise",
          "backend.run_fused": "session.run_fused",
          "compile.trace": "compile", "compile.sync": "compile.trace"}


def _pud_events(log_dir):
    """(name under the prefix, start, end, thread line) of every
    ``pud/`` span of the one trace under ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.SPAN_PREFIX):
                    out.append((ev.name[len(obs.SPAN_PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                (plane.name, line.name)))
    return out


@pytest.fixture(scope="module")
def traced_add(tmp_path_factory):
    """(miss events, hit events, program) of two profiled calls."""
    rng = np.random.default_rng(0)
    events, program = [], None
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "_compiled", collections.OrderedDict())
        for _ in ("miss", "hit"):
            log_dir = str(tmp_path_factory.mktemp("profile"))
            a, b = (rng.integers(0, 2**32, 64, dtype=np.uint32)
                    for _ in range(2))
            # A session of its own: each call walks its schedule eagerly.
            session = DramSession("pallas")
            jax.profiler.start_trace(log_dir)
            try:
                out, program = session.elementwise("add", a, b)
                jax.block_until_ready(out)
            finally:
                jax.profiler.stop_trace()
            np.testing.assert_array_equal(np.asarray(out), a + b)
            events.append(_pud_events(log_dir))
    return events[0], events[1], program


def test_each_layer_span_opens_once_per_call(traced_add):
    miss, hit, _ = traced_add
    for events, extra in ((miss, MISS), (hit, ())):
        names = [e[0] for e in events]
        for name in ONCE + extra:
            assert names.count(name) == 1, name
        assert set(names) == set(ONCE + extra)


def test_spans_nest_on_the_calling_thread(traced_add):
    events, _, _ = traced_add
    assert len({e[3] for e in events}) == 1
    first = {e[0]: e for e in events}
    for name, start, end, _ in events:
        if name == "elementwise":
            continue
        _, p_start, p_end, _ = first[PARENT[name]]
        assert p_start <= start and end <= p_end, (name, PARENT[name])
    # The compile finishes before the fused run starts.
    assert first["compile"][2] <= first["session.run_fused"][1]


def test_every_traced_gate_reads_its_planes_back(traced_add):
    """A miss traces the program and reads every gate's operand planes
    back in one read, of their origin IDs; a hit opens ``pud/compile``
    and neither ``compile.trace`` nor ``compile.sync``."""
    miss, hit, program = traced_add
    assert len(program.ops) == 96
    assert [e[0] for e in miss].count("compile.sync") == 1
    names = [e[0] for e in hit]
    assert "compile" in names
    assert "compile.trace" not in names and "compile.sync" not in names


def test_a_span_without_a_profiler_keeps_nothing(tmp_path):
    for _ in range(1000):
        with obs.span("elementwise"):
            pass
    with pytest.raises(KeyError):
        with obs.span("compile"):
            raise KeyError("passes through")
    # A profile started afterwards holds none of those spans.
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    assert _pud_events(str(tmp_path)) == []


def test_level_walk_spans_open_inside_the_backend(tmp_path):
    """The same program three times: the first call walks eagerly and
    opens no ``backend.levels_*`` span, the second builds the jitted
    walk, the third dispatches it; each inside ``backend.run_fused``."""
    rng = np.random.default_rng(1)
    a, b = (rng.integers(0, 2**32, 64, dtype=np.uint32) for _ in range(2))
    session = DramSession("pallas")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            out, _ = session.elementwise("add", a, b)
            jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(out), a + b)
    events = sorted(_pud_events(str(tmp_path)), key=lambda e: e[1])
    backend = [e for e in events if e[0] == "backend.run_fused"]
    levels = [e for e in events if e[0].startswith("backend.levels")]
    assert [e[0] for e in levels] == ["backend.levels_build",
                                      "backend.levels_jit"]
    for (_, start, end, _), (_, p_start, p_end, _) in zip(levels,
                                                          backend[1:]):
        assert p_start <= start and end <= p_end
