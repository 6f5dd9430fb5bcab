"""The program's host spans (``repro.obs``), read back from a profile.

One ``DramSession("pallas").elementwise("add", ...)`` at 64 lanes runs
under ``jax.profiler``; the ``.xplane.pb`` it writes is read with
``jax.profiler.ProfileData``.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.compile import compile_elementwise
from repro.session import DramSession

ONCE = ("elementwise", "compile", "session.run_fused", "backend.run_fused")

#: Each span and the span it opens inside.
PARENT = {"compile": "elementwise", "session.run_fused": "elementwise",
          "backend.run_fused": "session.run_fused",
          "compile.sync": "compile"}


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
    log_dir = str(tmp_path_factory.mktemp("profile"))
    rng = np.random.default_rng(0)
    a, b = (rng.integers(0, 2**32, 64, dtype=np.uint32) for _ in range(2))
    session = DramSession("pallas")
    jax.profiler.start_trace(log_dir)
    try:
        out, program = session.elementwise("add", a, b)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(out), a + b)
    return _pud_events(log_dir), program


def test_each_layer_span_opens_once_per_call(traced_add):
    events, _ = traced_add
    names = [e[0] for e in events]
    for name in ONCE:
        assert names.count(name) == 1, name
    assert set(names) == set(ONCE) | {"compile.sync"}


def test_spans_nest_on_the_calling_thread(traced_add):
    events, _ = traced_add
    assert len({e[3] for e in events}) == 1
    once = {e[0]: e for e in events if e[0] in ONCE}
    for name, start, end, _ in events:
        if name == "elementwise":
            continue
        _, p_start, p_end, _ = once[PARENT[name]]
        assert p_start <= start and end <= p_end, (name, PARENT[name])
    # The compile finishes before the fused run starts.
    assert once["compile"][2] <= once["session.run_fused"][1]


def test_every_traced_gate_reads_its_planes_back(traced_add):
    events, program = traced_add
    syncs = sum(1 for e in events if e[0] == "compile.sync")
    assert len(program.ops) == 96
    assert syncs >= len(program.ops)


def test_the_megakernel_route_opens_the_backend_span(tmp_path):
    rng = np.random.default_rng(1)
    a, b = (rng.integers(0, 2**32, 64, dtype=np.uint32) for _ in range(2))
    cp = compile_elementwise("add", a, b)
    session = DramSession("pallas")
    jax.profiler.start_trace(str(tmp_path))
    try:
        final = jax.block_until_ready(
            session.run_fused(cp.program, cp.state, mode="megakernel"))
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(cp.outputs(final)), a + b)
    names = sorted(e[0] for e in _pud_events(str(tmp_path)))
    assert names == ["backend.run_fused", "session.run_fused"]


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
