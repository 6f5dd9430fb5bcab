"""``benchmarks.bench``: per-op vs fused vs megakernel execution harness.

Times the same addressed :class:`~repro.pud.isa.Program` through all
three execution paths of a :class:`~repro.session.DramSession` — per-op
interpretation (``run``, one kernel launch per MAJ/MRC op),
compile-cached fused execution (``run_fused``, one launch per schedule
dispatch group, see :mod:`repro.compile`), and megakernel execution
(``run_fused(mode="megakernel")``, ONE launch for the whole schedule
via lowered level tables, see :mod:`repro.compile.megakernel`) — for
the paper-motivated workloads: bit-serial adder / multiplier (§8.1)
and the Multi-RowCopy secure-erase wave (§8.2).  Results land in a
machine-readable ``BENCH_fused.json`` so the perf trajectory of the
fusion layer is recorded run over run (schema ``repro-bench/fused-v4``
in ``docs/BENCH.md``).

Usage::

    python -m benchmarks.bench --smoke            # CI-size, ~seconds
    python -m benchmarks.bench                    # full sizes
    python -m benchmarks.bench --backends oracle pallas sim

Every row carries wall-clock timings, *structural* dispatch counts and
CostModel-priced energy (both measured in a scoped ``count_dispatches``
window per run, so workloads never leak counts into each other), the
modelled launch overhead (dispatches x
:data:`repro.core.costmodel.KERNEL_LAUNCH_NS` — the command-stream cost
the megakernel collapses), the session compile-cache hits/misses of the
fused paths, and an ``offload`` block pricing the same program on the
PUD side (time and joules for both, via
:func:`repro.pud.offload.plan_program`); the CI gate asserts on the
structural columns (megakernel <= 2 dispatches for add32/mul8, fused <
per-op, megakernel energy <= fused <= per-op, >= 1 cache hit), which
needs no timing stability.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

from _bench_io import default_out, execution_stamp, write_bench_json

SCHEMA = "repro-bench/fused-v4"
DEFAULT_OUT = default_out("BENCH_fused.json")


# --------------------------------------------------------------- workloads
def _adder(nbits: int, lanes: int):
    """Traced §8.1 ripple-carry adder over ``lanes`` bit-serial lanes."""
    import numpy as np

    from repro.compile import compile_elementwise

    rng = np.random.default_rng(7)
    a = rng.integers(0, 2 ** 32, lanes, dtype=np.uint32)
    b = rng.integers(0, 2 ** 32, lanes, dtype=np.uint32)
    if nbits < 32:
        mask = np.uint32((1 << nbits) - 1)
        a, b = a & mask, b & mask
    cp = compile_elementwise("add", a, b, tier=5, n_act=32)
    return cp.program, cp.state


def _multiplier(nbits: int, lanes: int):
    """Traced shift-and-add multiplier restricted to ``nbits`` planes."""
    import numpy as np

    from repro.compile import trace_planes
    from repro.core import bitplanes as bp

    rng = np.random.default_rng(11)
    bits_a = rng.integers(0, 2, (nbits, lanes)).astype(bool)
    bits_b = rng.integers(0, 2, (nbits, lanes)).astype(bool)
    tr = trace_planes(lambda bs, A, B: list(bs.mul(A, B)), nbits, tier=5,
                      n_act=32)
    return tr.program, tr.image(bp.pack(bits_a), bp.pack(bits_b))


def _erase(waves: int, fanout: int, words: int):
    """§8.2 Multi-RowCopy bank wipe: one WR'd pattern row fans out to
    ``waves`` disjoint ``fanout``-row groups (all independent — a
    single dependency level, so the fused path is one dispatch)."""
    import numpy as np

    from repro.pud.isa import Program

    prog = Program()
    prog.emit("WR", tag="erase/pattern")
    row = 1
    for w in range(waves):
        prog.emit("MRC", n_act=fanout + 1, tag=f"erase/wave[{w}]",
                  srcs=(0,), dsts=tuple(range(row, row + fanout)))
        row += fanout
    state = np.zeros((row, words), np.uint32)
    state[0] = 0xDEADBEEF  # the predetermined wipe pattern
    return prog, state


def _workloads(smoke: bool):
    if smoke:
        return {
            "add32": lambda: _adder(32, 64),
            "mul8": lambda: _multiplier(8, 64),
            "erase_mrc31": lambda: _erase(waves=8, fanout=31, words=64),
        }
    return {
        "add32": lambda: _adder(32, 4096),
        "mul16": lambda: _multiplier(16, 4096),
        "erase_mrc31": lambda: _erase(waves=64, fanout=31, words=2048),
    }


# ----------------------------------------------------------------- driver
def _timed(fn, session, reps: int):
    """(wall_s per rep, final output, frozen dispatch/energy scope).

    The warm-up run (jit/pallas compile paths) executes inside its own
    ``count_dispatches`` scope, so the launch count — and the
    CostModel-priced energy — is exact for one run: no dividing a
    shared counter across reps, no leakage from whatever ran before.
    """
    import jax

    with session.count_dispatches() as scope:
        out = fn()
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out, scope


def bench_program(name: str, prog, state, sessions, ref, reps: int):
    import numpy as np

    from repro.core.costmodel import KERNEL_LAUNCH_NS
    from repro.pud.offload import plan_program

    want = np.asarray(ref.run(prog, state))
    rows = []
    for be_name, sess in sessions.items():
        modes = {}
        runners = (
            ("per_op", lambda: sess.run(prog, state)),
            ("fused", lambda: sess.run_fused(prog, state)),
            ("megakernel",
             lambda: sess.run_fused(prog, state, mode="megakernel")),
        )
        for mode, runner in runners:
            if mode != "per_op":  # per-op never touches the caches
                cache0 = sess.cache.stats.snapshot()
                low0 = sess.cache.lowering_stats.snapshot()
            wall, out, scope = _timed(runner, sess, reps)
            modes[mode] = {
                "wall_s": wall,
                "dispatches": scope.count,
                "launch_overhead_ns": scope.count * KERNEL_LAUNCH_NS,
                "energy_nj": scope.energy_nj,
                "parity": bool((np.asarray(out) == want).all()),
            }
            if mode != "per_op":
                d = sess.cache.stats.delta(cache0)
                modes[mode]["cache"] = {"hits": d.hits, "misses": d.misses}
            if mode == "megakernel":
                dl = sess.cache.lowering_stats.delta(low0)
                modes[mode]["lowering_cache"] = {"hits": dl.hits,
                                                 "misses": dl.misses}
                modes[mode]["vmem"] = _vmem_plan(sess, prog, state)
        # The fused warm-up built (and cached) the schedule; reading the
        # level count back is a hit, never a second scheduling pass.
        # The offload decision reuses the same cached schedule: the row
        # records where this program would run, in ns AND nJ.
        decision = plan_program(prog, state.shape[1] * 4, ctx=sess.ctx,
                                sched=sess.schedule_for(prog))
        rows.append({
            "name": name,
            "backend": be_name,
            "n_ops": len(prog.ops),
            "n_levels": sess.schedule_for(prog).n_levels,
            "per_op": modes["per_op"],
            "fused": modes["fused"],
            "megakernel": modes["megakernel"],
            "speedup": modes["per_op"]["wall_s"]
            / max(modes["fused"]["wall_s"], 1e-12),
            "dispatch_reduction": modes["per_op"]["dispatches"]
            / max(modes["fused"]["dispatches"], 1),
            "megakernel_dispatch_reduction":
            modes["per_op"]["dispatches"]
            / max(modes["megakernel"]["dispatches"], 1),
            "energy_reduction": modes["per_op"]["energy_nj"]
            / max(modes["fused"]["energy_nj"], 1e-12),
            "megakernel_energy_reduction":
            modes["per_op"]["energy_nj"]
            / max(modes["megakernel"]["energy_nj"], 1e-12),
            "offload": {
                "tpu_ns": decision.tpu_ns,
                "pud_ns": decision.pud_ns,
                "tpu_energy_nj": decision.tpu_energy_nj,
                "pud_energy_nj": decision.pud_energy_nj,
                "winner": decision.winner,
                "winner_energy": decision.winner_energy,
            },
        })
    return rows


def _vmem_plan(sess, prog, state):
    """The megakernel column-blocking decision for this (program, image),
    or None on backends without the capability (their megakernel rows
    measure the exact fallback path)."""
    caps = sess.capabilities()
    if not caps.megakernel:
        return None
    from repro.compile import plan_vmem

    low = sess.cache.lowering_for(prog)
    rows, words = state.shape
    return plan_vmem(low, rows, words, caps.vmem_budget_bytes).as_dict()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size workloads, 1 timing rep")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="output JSON path (default results/BENCH_fused.json)")
    ap.add_argument("--backends", nargs="+", default=["oracle", "pallas"],
                    help="executors to time (sim is slow: opt in)")
    ap.add_argument("--reps", type=int, default=None,
                    help="timing repetitions (default: 1 smoke, 3 full)")
    args = ap.parse_args(argv)
    reps = args.reps or (1 if args.smoke else 3)

    from repro.backends import ExecutionContext
    from repro.session import DramSession

    # One session per backend for the whole run: repeated programs hit
    # the compile cache exactly as they would in a serving deployment.
    ideal = ExecutionContext(ideal=True)
    sessions = {n: DramSession(n, ideal, name=f"bench-{n}")
                for n in args.backends}
    ref = (sessions.get("oracle")
           or DramSession("oracle", ideal, name="bench-oracle-ref"))

    rows = []
    for name, build in _workloads(args.smoke).items():
        prog, state = build()
        print(f"[bench] {name}: {len(prog.ops)} ops ...", flush=True)
        rows.extend(bench_program(name, prog, state, sessions, ref, reps))

    hits = sum(s.cache.stats.hits for s in sessions.values())
    misses = sum(s.cache.stats.misses for s in sessions.values())
    lhits = sum(s.cache.lowering_stats.hits for s in sessions.values())
    lmisses = sum(s.cache.lowering_stats.misses for s in sessions.values())
    doc = {
        "schema": SCHEMA,
        "smoke": args.smoke,
        "reps": reps,
        **execution_stamp(),
        "compile_cache": {
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / max(hits + misses, 1),
        },
        "lowering_cache": {
            "hits": lhits,
            "misses": lmisses,
            "hit_rate": lhits / max(lhits + lmisses, 1),
        },
        "workloads": rows,
    }
    write_bench_json(args.out, doc)

    for r in rows:
        ok = (r["per_op"]["parity"] and r["fused"]["parity"]
              and r["megakernel"]["parity"])
        flag = "" if ok else "  !! PARITY MISMATCH"
        print(f"  {r['name']:12s} [{r['backend']:7s}] "
              f"per-op {r['per_op']['wall_s']*1e3:8.1f} ms "
              f"/{r['per_op']['dispatches']:5d} disp "
              f"/{r['per_op']['energy_nj']/1e3:9.1f} uJ | fused "
              f"{r['fused']['wall_s']*1e3:8.1f} ms "
              f"/{r['fused']['dispatches']:5d} disp | mega "
              f"{r['megakernel']['wall_s']*1e3:8.1f} ms "
              f"/{r['megakernel']['dispatches']:5d} disp "
              f"/{r['megakernel']['energy_nj']/1e3:9.1f} uJ | "
              f"{r['speedup']:5.2f}x wall, "
              f"{r['megakernel_dispatch_reduction']:5.1f}x mega "
              f"dispatch, {r['megakernel_energy_reduction']:5.1f}x mega "
              f"energy{flag}")
    cc, lc = doc["compile_cache"], doc["lowering_cache"]
    print(f"[bench] compile cache: {cc['hits']} hits / {cc['misses']} "
          f"misses ({cc['hit_rate']*100:.0f}% hit rate); lowering cache: "
          f"{lc['hits']} hits / {lc['misses']} misses")
    bad = [r for r in rows
           if not (r["per_op"]["parity"] and r["fused"]["parity"]
                   and r["megakernel"]["parity"])]
    return 1 if bad else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
