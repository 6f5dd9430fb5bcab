"""Static analyzer tests: races, liveness, equivalence, certification.

Positive direction: every golden fixture and every differential-suite
random program must certify against its fused schedule — the analyzer
may not reject artifacts the compiler legitimately emits (aliasing,
dead stores, input replication, mixed arities, cost-only ops
included).  Negative direction: every seeded schedule mutation
(:mod:`repro.analyze.mutate`) and every hand-built hazard (dependent
ops forced into one level, malformed groups, use-after-free row
references) must be caught with its stable finding code.
"""

import glob
import json
import os

import numpy as np
import pytest

from _proptest import sweep
from repro.analyze import (Certificate, CertificationError, MUTATIONS,
                           allocator_findings, analyze, apply_mutation,
                           certify, check_ops, equivalence_findings,
                           lifetimes, liveness_findings, schedule_findings)
from repro.analyze.cert import schedule_digest
from repro.backends import ExecutionContext
from repro.compile import build_schedule
from repro.compile.schedule import FusedGroup, Schedule
from repro.pud.isa import Program, PUDOp
from repro.session import DramSession
from repro.session.cache import CompileCache, program_key
from repro.session.rows import RowAllocator

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_FILES = sorted(glob.glob(os.path.join(GOLDEN_DIR, "*.json")))
GOLDEN_IDS = [os.path.basename(p)[:-5] for p in GOLDEN_FILES]


def _load_golden(path):
    with open(path) as f:
        doc = json.load(f)
    return doc, Program.from_json(json.dumps(doc["ops"]))


def _codes(findings):
    return {f.code for f in findings}


def _dedup_dsts(prog: Program) -> Program:
    """Differential programs draw dsts with replacement; a duplicate
    destination inside one op is a validation error (matching
    ``check_program``), so certification tests run the semantically
    identical dedup'd form."""
    out = Program()
    for op in prog.ops:
        out.emit(op.kind, x=op.x, n_act=op.n_act, tag=op.tag,
                 srcs=op.srcs, dsts=tuple(dict.fromkeys(op.dsts)))
    return out


# ------------------------------------------------------------ race pass


def test_check_ops_clean_program():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("NOT", srcs=(3,), dsts=(4,))
    assert check_ops(prog, 5) == []


def test_check_ops_row_range_and_dup_dst():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 9), dsts=(2,))
    prog.emit("COPY", srcs=(0,), dsts=(1, 1))
    codes = _codes(check_ops(prog, 5))
    assert {"OP_ROW_RANGE", "OP_DUP_DST"} <= codes


def test_check_ops_maj_shape_errors():
    prog = Program()
    prog.emit("MAJ", x=4, n_act=8, srcs=(0, 1, 2, 3), dsts=(4,))
    prog.emit("MAJ", x=5, n_act=8, srcs=(0, 1, 2), dsts=(5,))
    prog.emit("MRC", n_act=8, srcs=(0, 1), dsts=(6,))
    codes = _codes(check_ops(prog, 8))
    assert {"OP_MAJ_ARITY", "OP_MAJ_OPERANDS", "OP_SRC_COUNT"} <= codes


def test_check_ops_underpowered_maj_is_warning_only():
    prog = Program()
    prog.emit("MAJ", x=5, n_act=2, srcs=(0, 1, 2, 3, 4), dsts=(5,))
    findings = check_ops(prog, 6)
    assert _codes(findings) == {"OP_NACT_UNDER_ARITY"}
    assert all(f.severity == "warning" for f in findings)


def test_check_ops_unknown_kind():
    prog = Program()
    prog.emit("XOR", srcs=(0,), dsts=(1,))
    assert _codes(check_ops(prog, 4)) == {"OP_UNKNOWN_KIND"}


def test_check_ops_duplicate_maj_operands_legal():
    # Input replication (paper identity): MAJ reading one row thrice.
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 0, 1), dsts=(2,))
    assert check_ops(prog, 3) == []


def _dependent_pair() -> Program:
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("NOT", srcs=(3,), dsts=(4,))
    return prog


def test_schedule_findings_clean_on_compiler_output():
    prog = _dependent_pair()
    assert schedule_findings(build_schedule(prog), prog) == []


def test_schedule_findings_intra_level_raw():
    # Force both dependent ops into ONE level: the fused executor would
    # feed the NOT stale level-entry state.
    prog = _dependent_pair()
    maj, not_ = (op for op in prog.ops)
    bad = Schedule(levels=((FusedGroup("MAJ", 3, (maj,)),
                            FusedGroup("NOT", 0, (not_,))),))
    codes = _codes(schedule_findings(bad, prog))
    assert "RACE_RAW_LEVEL" in codes


def test_schedule_findings_intra_level_waw():
    prog = Program()
    prog.emit("COPY", srcs=(0,), dsts=(2,))
    prog.emit("COPY", srcs=(1,), dsts=(2,))
    a, b = prog.ops
    bad = Schedule(levels=((FusedGroup("COPY", 0, (a, b)),),))
    assert "RACE_WAW_LEVEL" in _codes(schedule_findings(bad, prog))


def test_schedule_findings_identical_redundant_writes_benign():
    # Two content-equal writers of one row commit the same value:
    # legal under unspecified level-exit commit order.
    prog = Program()
    prog.emit("COPY", srcs=(0,), dsts=(2,))
    prog.emit("COPY", srcs=(0,), dsts=(2,))
    a, b = prog.ops
    sched = Schedule(levels=((FusedGroup("COPY", 0, (a, b)),),))
    assert schedule_findings(sched, prog) == []


def test_schedule_findings_dropped_op():
    prog = _dependent_pair()
    maj = prog.ops[0]
    bad = Schedule(levels=((FusedGroup("MAJ", 3, (maj,)),),))
    assert "SCHED_OP_SET" in _codes(schedule_findings(bad, prog))


def _group_malformations() -> dict[str, tuple[Program, Schedule]]:
    """Programs beside a hand-built schedule whose one group the walk
    could not run: a stale MAJ arity, a MAJ arity of the wrong parity,
    an MRC fan-out too narrow, and an op in a group of another kind."""
    maj5 = PUDOp("MAJ", x=5, n_act=8, srcs=(0, 1, 2, 3, 4), dsts=(5,))
    mrc = PUDOp("MRC", n_act=4, srcs=(0,), dsts=(1, 2, 3))
    not_ = PUDOp("NOT", srcs=(0,), dsts=(1,))
    cases = {
        "maj_stale": (maj5, FusedGroup("MAJ", 3, (maj5,))),
        "maj_parity": (maj5, FusedGroup("MAJ", 6, (maj5,))),
        "mrc_narrow": (mrc, FusedGroup("MRC", 2, (mrc,))),
        "kind": (not_, FusedGroup("COPY", 0, (not_,))),
    }
    return {name: (Program([op]), Schedule(((g,),)))
            for name, (op, g) in cases.items()}


@pytest.mark.parametrize("case", sorted(_group_malformations()))
def test_schedule_findings_malformed_group(case):
    prog, bad = _group_malformations()[case]
    assert "SCHED_GROUP" in _codes(schedule_findings(bad, prog))
    with pytest.raises(CertificationError):
        certify(prog, sched=bad)
    # The compiler's own grouping of the same program is clean.
    assert schedule_findings(build_schedule(prog), prog) == []


# -------------------------------------------------------- liveness pass


def test_lifetimes_intervals():
    prog = Program()
    prog.emit("COPY", srcs=(0,), dsts=(1,))      # op 0
    prog.emit("NOT", srcs=(1,), dsts=(2,))       # op 1
    prog.emit("FRAC", dsts=(2,))                 # value-neutral: ignored
    lt = lifetimes(prog)
    assert lt[0].read_before_write and lt[0].first_read == 0
    assert lt[1].first_write == 0 and lt[1].last_read == 1
    assert lt[2].first_write == 1 and lt[2].last_write == 1


def test_dead_op_warning_and_outputs():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("NOT", srcs=(0,), dsts=(4,))
    # Without explicit outputs every last write counts as live.
    assert liveness_findings(prog) == []
    # With outputs={3}, the NOT's write to row 4 is dead (warning).
    findings = liveness_findings(prog, outputs=(3,))
    assert _codes(findings) == {"LIVE_DEAD_OP"}
    assert all(f.severity == "warning" for f in findings)


def test_undeclared_input_error():
    prog = Program()
    prog.emit("NOT", srcs=(7,), dsts=(0,))
    assert liveness_findings(prog) == []  # inputs inferred silently
    findings = liveness_findings(prog, inputs=(1, 2))
    assert _codes(findings) == {"LIVE_UNDECLARED_INPUT"}


def test_allocator_use_after_free_and_leak():
    alloc = RowAllocator(capacity=8, name="arena")
    keep = alloc.alloc(2, tag="keep")
    stale = alloc.alloc(2, tag="stale")
    alloc.free(stale)
    assert set(alloc.free_rows) == set(stale.indices)

    prog = Program()
    prog.emit("COPY", srcs=(keep.indices[0],),
              dsts=(stale.indices[0],))        # write to a freed row
    codes = _codes(allocator_findings(prog, alloc))
    assert "LIVE_USE_AFTER_FREE" in codes
    # keep[1] is reserved but never referenced -> leak warning.
    assert "LIVE_LEAKED_ROWS" in codes

    prog2 = Program()
    prog2.emit("COPY", srcs=(0,), dsts=(99,))
    assert "LIVE_UNALLOCATED" in _codes(allocator_findings(prog2, alloc))


# ----------------------------------------------------- equivalence pass


def test_equivalence_clean_across_artifacts():
    for path in GOLDEN_FILES:
        _, prog = _load_golden(path)
        assert equivalence_findings(prog, build_schedule(prog)) == [], path


def test_equivalence_catches_forced_same_level_dependency():
    # The race pass sees the RAW; equivalence independently proves the
    # stale-entry read computes a different dataflow.
    prog = _dependent_pair()
    maj, not_ = prog.ops
    bad = Schedule(levels=((FusedGroup("MAJ", 3, (maj,)),
                            FusedGroup("NOT", 0, (not_,))),))
    assert any(f.code == "EQ_SCHEDULE_ROW"
               for f in equivalence_findings(prog, bad))


def test_equivalence_padding_and_expansion_identities():
    # Mixed arities (forces constant padding), MRC fan-outs, NOT in one
    # program: the schedule certifies only because the symbolic domain
    # proves MAJ_k == MAJ_{k+2m}(.., 0*m, 1*m) for the padded MAJ3.
    prog = Program()
    prog.emit("MAJ", x=7, n_act=8,
              srcs=(0, 1, 2, 3, 4, 5, 6), dsts=(7,))
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(8,))
    prog.emit("NOT", srcs=(7,), dsts=(9,))
    prog.emit("MRC", n_act=32, srcs=(8,), dsts=(10, 11, 12))
    sched = build_schedule(prog)
    assert sched.histogram()[("MAJ", 7)] == 1  # the MAJ3 really is padded
    assert equivalence_findings(prog, sched) == []


# -------------------------------------------------- certification driver


@pytest.mark.parametrize("path", GOLDEN_FILES, ids=GOLDEN_IDS)
def test_golden_certifies_and_matches_frozen_certificate(path):
    doc, prog = _load_golden(path)
    sched = build_schedule(prog)
    cert = certify(prog, sched=sched)
    frozen = doc["certificate"]
    assert cert.digest == frozen["digest"]
    assert cert.program_key == frozen["program_key"]
    assert cert.schedule_digest == frozen["schedule_digest"] \
        == schedule_digest(sched)
    assert {name: {"errors": e, "warnings": w}
            for name, e, w in cert.summary} == frozen["passes"]


def test_certificate_deterministic():
    _, prog = _load_golden(GOLDEN_FILES[0])
    sched = build_schedule(prog)
    a = certify(prog, sched=sched)
    b = certify(prog, sched=sched)
    assert a == b and a.digest == b.digest
    assert isinstance(a, Certificate) and a.n_levels == sched.n_levels


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_seeded_mutations_rejected(mutation):
    applied = 0
    for path in GOLDEN_FILES:
        _, prog = _load_golden(path)
        sched = build_schedule(prog)
        bad = apply_mutation(sched, mutation)
        if bad is None:
            continue  # no site on this fixture (e.g. no MAJ group)
        applied += 1
        assert bad != sched
        assert apply_mutation(sched, mutation) == bad  # deterministic
        with pytest.raises(CertificationError) as err:
            certify(prog, sched=bad)
        assert err.value.report.errors, (path, mutation)
        assert certify(prog, sched=sched)  # the input was not modified
    assert applied >= 1, f"mutation {mutation} never applicable"


@sweep(n_cases=20, seed=0x5C4E)
def test_schedule_mutations_rejected_on_random_programs(rng):
    """Beyond the fixtures: on random programs (aliasing, dead stores,
    mixed arities), some mutation applies, and certify rejects every
    one that does."""
    from test_compile_differential import rand_program

    prog = _dedup_dsts(rand_program(rng, n_ops=12))
    sched = build_schedule(prog)
    certify(prog, sched=sched)
    applied = []
    for name in MUTATIONS:
        bad = apply_mutation(sched, name)
        if bad is None:
            continue
        applied.append(name)
        with pytest.raises(CertificationError):
            certify(prog, sched=bad)
    assert applied


def test_analyze_report_never_raises():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 99), dsts=(1,))
    report = analyze(prog, n_rows=4)
    assert not report.ok
    assert "OP_ROW_RANGE" in _codes(report.errors)
    # Summary is canonical: all three passes present even when clean.
    assert [s[0] for s in report.summary()] == \
        ["race", "liveness", "equivalence"]


# ----------------------------------- differential-suite certification


def test_differential_programs_certify():
    from test_compile_differential import rand_program

    rng = np.random.default_rng(0xA11A)
    for trial in range(25):
        prog = _dedup_dsts(rand_program(rng, n_ops=12))
        sched = build_schedule(prog)
        cert = certify(prog, sched=sched)
        assert cert.schedule_digest == schedule_digest(sched), trial


def test_traced_adder_certifies_with_dead_gate():
    from repro.compile import trace_planes

    def f(bs, A, B):
        s, carry = bs.add(A, B)
        bs.not_(carry)          # dead gate: complement nothing reads
        return list(s)

    prog = trace_planes(f, 4, tier=5, n_act=32).program
    sched = build_schedule(prog)
    cert = certify(prog, sched=sched)
    assert cert.summary[0] == ("race", 0, 0)


# ------------------------------------------------ cache + session wiring


def test_certificate_cache_hit_and_upgrade():
    _, prog = _load_golden(GOLDEN_FILES[0])
    cache = CompileCache()
    sched = cache.schedule_for(prog)

    first = cache.certificate_for(prog, sched=sched)
    assert first.schedule_digest == schedule_digest(sched)
    assert (cache.certificate_stats.misses,
            cache.certificate_stats.hits) == (1, 0)

    again = cache.certificate_for(prog, sched=sched)
    assert again is first
    assert cache.certificate_stats.hits == 1  # zero re-analysis

    # Without a schedule in hand the lookup resolves the cached one.
    assert cache.certificate_for(prog) is first
    assert (cache.certificate_stats.misses, cache.certificate_stats.hits,
            cache.stats.misses) == (1, 2, 1)


def test_certificate_cache_rejects_uncertifiable():
    _, prog = _load_golden(GOLDEN_FILES[0])
    cache = CompileCache()
    sched = cache.schedule_for(prog)
    bad = apply_mutation(sched, "drop_op")
    with pytest.raises(CertificationError):
        cache.certificate_for(prog, sched=bad)
    # Nothing admitted: a later good lookup is a fresh miss, not a hit.
    cache.certificate_for(prog, sched=sched)
    assert cache.certificate_stats.hits == 0


def test_session_certifies_run_fused():
    session = DramSession("oracle", ExecutionContext(ideal=True))
    prog = _dependent_pair()
    state = np.zeros((5, 4), np.uint32)
    session.run_fused(prog, state)
    assert session.cache.certificate_stats.lookups == 1
    session.run_fused(prog, state)
    assert session.cache.certificate_stats.hits == 1


def test_session_certify_opt_out():
    session = DramSession("oracle",
                          ExecutionContext(ideal=True, certify=False))
    prog = _dependent_pair()
    session.run_fused(prog, np.zeros((5, 4), np.uint32))
    assert session.cache.certificate_stats.lookups == 0


def test_validate_carries_findings():
    from repro.session.validate import (ProgramValidationError,
                                        check_program)

    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 7), dsts=(1, 1))
    with pytest.raises(ProgramValidationError) as err:
        check_program(prog, 4)
    codes = {f.code for f in err.value.findings}
    assert {"OP_ROW_RANGE", "OP_DUP_DST"} <= codes


def test_program_key_matches_cert_key():
    _, prog = _load_golden(GOLDEN_FILES[0])
    cert = certify(prog)
    assert cert.program_key == program_key(prog)
