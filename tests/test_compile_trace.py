"""The elementwise trace (``repro.compile.trace``): rows keyed by plane
origin, one trace per (op, lanes, tier, n_act), and a program that is
exact for every operand, the ones whose planes coincide included."""

import collections
import json
import os

import numpy as np
import pytest

from _proptest import rand_u32
from repro import obs
from repro.backends import ExecutionContext
from repro.compile import compile_elementwise, trace, trace_planes
from repro.core import bitplanes as bp
from repro.session import DramSession

IDEAL = ExecutionContext(ideal=True)
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture
def opened(monkeypatch):
    """The names of the spans opened, under a fresh compile cache."""
    monkeypatch.setattr(trace, "_compiled", collections.OrderedDict())
    names = []
    span = obs.span
    monkeypatch.setattr(obs, "span", lambda n: names.append(n) or span(n))
    return names


def _ops(program):
    return [(op.kind, op.x, op.n_act, list(op.srcs), list(op.dsts))
            for op in program.ops]


def _golden(nbits):
    with open(os.path.join(GOLDEN_DIR, f"add{nbits}.json")) as f:
        doc = json.load(f)
    return doc, [(r["kind"], r["x"], r["n_act"], r["srcs"], r["dsts"])
                 for r in doc["ops"]]


# ------------------------------------------------------------ the trace


@pytest.mark.parametrize("nbits", [8, 16, 32])
def test_origin_trace_reproduces_the_golden_adder(nbits):
    doc, want = _golden(nbits)
    tr = trace_planes(lambda bs, A, B: list(bs.add(A, B)[0]), nbits,
                      tier=5, n_act=32)
    assert _ops(tr.program) == want
    assert tr.program.n_rows() == len(tr.sources) == doc["rows"]
    # Rows 0-2 are the first gate's operands: A[0], B[0], the carry-in 0.
    assert tr.sources[:3] == (0, nbits, 2 * nbits)


def test_compiled_add_is_the_golden_add32(opened):
    _, want = _golden(32)
    rng = np.random.default_rng(32)
    cp = compile_elementwise("add", rand_u32(rng, 64), rand_u32(rng, 64),
                             tier=5, n_act=32)
    assert _ops(cp.program) == want


def test_the_image_places_each_input_row(opened):
    rng = np.random.default_rng(5)
    a, b = rand_u32(rng, 96), rand_u32(rng, 96)
    cp = compile_elementwise("sub", a, b, tier=5, n_act=32)
    tr = trace._compiled[("sub", 96, 5, 32)].trace
    planes = np.concatenate([
        np.asarray(bp.pack_uint_elements(a)),
        np.asarray(bp.pack_uint_elements(b)),
        np.zeros((1, 3), np.uint32), np.full((1, 3), 0xFFFFFFFF, np.uint32)])
    state = np.asarray(cp.state)
    assert state.shape == (cp.program.n_rows(), 3)
    np.testing.assert_array_equal(state, planes[list(tr.sources)])
    gate_rows = {d for op in cp.program.ops for d in op.dsts}
    assert not state[sorted(gate_rows)].any()
    # sub reads the constant 1 (the carry-in) as an input row.
    assert 2 * 32 + 1 in tr.sources


# ------------------------------------------------------------ the cache


def test_one_trace_serves_every_operand_pair(opened):
    rng = np.random.default_rng(1)
    cps = [compile_elementwise("add", rand_u32(rng, 64), rand_u32(rng, 64),
                               tier=5, n_act=32) for _ in range(2)]
    assert opened.count("compile") == 2
    assert opened.count("compile.trace") == 1
    assert cps[0].program is cps[1].program
    assert not np.array_equal(np.asarray(cps[0].state),
                              np.asarray(cps[1].state))


@pytest.mark.parametrize("change", [{"lanes": 96}, {"op": "sub"},
                                    {"tier": 7}, {"n_act": 16}],
                         ids=lambda c: next(iter(c)))
def test_a_new_shape_traces_again(opened, change):
    rng = np.random.default_rng(2)

    def compile_(op="add", lanes=64, tier=5, n_act=32):
        return compile_elementwise(op, rand_u32(rng, lanes),
                                   rand_u32(rng, lanes), tier=tier,
                                   n_act=n_act)

    compile_()
    compile_()
    assert opened.count("compile.trace") == 1
    compile_(**change)
    assert opened.count("compile.trace") == 2


def test_the_cache_keeps_its_most_recent_shapes(opened, monkeypatch):
    monkeypatch.setattr(trace, "COMPILE_CACHE_SIZE", 2)
    rng = np.random.default_rng(3)
    for lanes in (32, 64, 32, 96, 32, 64):
        compile_elementwise("and", rand_u32(rng, lanes),
                            rand_u32(rng, lanes), tier=5, n_act=32)
    # 32, 64 miss; 32 hits; 96 evicts 64; 32 hits; 64 misses again.
    assert opened.count("compile.trace") == 4
    assert list(trace._compiled) == [("and", 32, 5, 32),
                                     ("and", 64, 5, 32)]


def test_operands_of_different_lanes_are_refused():
    with pytest.raises(ValueError, match="lanes"):
        compile_elementwise("add", np.zeros(64, np.uint32),
                            np.zeros(32, np.uint32))


# ----------------------------------------- exact where values coincide


#: Operand pairs whose planes the value-keyed trace merged into one row.
CASES = {
    "a_eq_b": lambda a, b: (b, b),
    "a_zero": lambda a, b: (np.zeros_like(a), b),
    "b_zero": lambda a, b: (a, np.zeros_like(b)),
    "below_2_16": lambda a, b: (a >> 16, b >> 16),
    "a_not_b": lambda a, b: (~b, b),
}

NUMPY = {"add": np.add, "sub": np.subtract, "and": np.bitwise_and,
         "xor": np.bitwise_xor}


@pytest.mark.parametrize("op", sorted(NUMPY))
def test_one_trace_is_exact_where_planes_coincide(op, opened):
    rng = np.random.default_rng(16)
    a0, b0 = rand_u32(rng, 64), rand_u32(rng, 64)
    pallas = DramSession("pallas", IDEAL)
    oracle = DramSession("oracle", IDEAL)
    pairs = [("random", (a0, b0))] + [(n, f(a0, b0))
                                      for n, f in CASES.items()]
    for name, (a, b) in pairs:
        want = NUMPY[op](a, b)
        for session in (pallas, oracle):
            got, _ = session.elementwise(op, a, b)
            np.testing.assert_array_equal(
                np.asarray(got), want,
                err_msg=f"{op} {name} on {session.backend.name}")
    assert opened.count("compile.trace") == 1


@pytest.mark.parametrize("op, numpy_op", [("mul", np.multiply),
                                          ("div", np.floor_divide)])
def test_deep_ops_are_exact(op, numpy_op):
    rng = np.random.default_rng(8)
    a = rng.integers(0, 256, 32, dtype=np.uint32)
    b = rng.integers(1, 256, 32, dtype=np.uint32)
    a[:4] = b[:4]
    a[4:8] = 0
    got, _ = DramSession("pallas", IDEAL).elementwise(op, a, b)
    np.testing.assert_array_equal(np.asarray(got), numpy_op(a, b))
