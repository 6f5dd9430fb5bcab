"""Service-layer tests: typed requests, admission control, continuous
batching, SLO accounting, and the engine's thin-client integrity hooks.

The load-bearing claims: (1) coalesced execution is bit-exact with
per-request execution on every backend — batching is purely a
throughput/dispatch optimization; (2) N sessions sharing one compile
cache resolve a same-shape program concurrently as exactly 1 miss +
N-1 hits; (3) admission control actually bounds the two scarce
resources (queue depth, tenant arena rows) and load-shedding only
drops past-deadline work; (4) the SLO snapshot is structured,
JSON-serializable, and reuses the trainer's straggler detector per
pooled session.
"""

import asyncio
import json
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from _proptest import rand_u32
from repro.backends import ExecutionContext, get_backend
from repro.ft.straggler import StragglerDetector
from repro.serve import (ArenaExhaustedError, DeadlineExceededError,
                         EraseRequest, HealRequest, IntegrityRequest,
                         Priority, QueueFullError, RequestQueue,
                         ServeError, SloMonitor)
from repro.session import CompileCache, DramSession
from test_session import valid_rand_program

IDEAL = ExecutionContext(ideal=True)
BACKENDS = ("oracle", "sim", "pallas")


def heal_req(rng, rows=2, words=8, flips=3, tenant="default", **kw):
    """A heal request whose replicas agree except for ``flips`` bits."""
    base = rand_u32(rng, rows, words)
    replicas = np.stack([base, base, base])
    flat = replicas[0].reshape(-1)
    for i in rng.choice(flat.size, size=flips, replace=False):
        flat[i] ^= np.uint32(1) << np.uint32(rng.integers(32))
    return HealRequest(replicas=replicas, tenant=tenant, **kw)


def mixed_requests(seed, n_heal=3, n_erase=2, rows=2, words=8):
    """Deterministic mixed workload; fresh objects every call (requests
    are stamped at admission, so they cannot be served twice)."""
    rng = np.random.default_rng(seed)
    reqs = [heal_req(rng, rows, words, tenant=f"t{i}")
            for i in range(n_heal)]
    reqs += [EraseRequest(rows=5, words=words, pattern=0xDEADBEEF,
                          fanout=4, tenant=f"t{i}") for i in range(n_erase)]
    return reqs


# ------------------------------------------- coalescing is bit-exact


@pytest.mark.parametrize("backend", BACKENDS)
def test_coalesced_bit_exact_with_per_request(make_pud_service, backend):
    """Same deterministic workload, coalescing on vs off, every backend:
    per-request results must be bit-identical (and match the oracle)."""
    ref = make_pud_service(backend="oracle", coalesce=True)
    want = ref.serve(mixed_requests(seed=42))
    for coalesce in (True, False):
        svc = make_pud_service(backend=backend, coalesce=coalesce)
        got = svc.serve(mixed_requests(seed=42))
        for w, g in zip(want, got):
            if hasattr(w, "healed"):
                assert (np.asarray(g.healed) == np.asarray(w.healed)).all()
                assert g.fixed_bits == w.fixed_bits == 3
            else:
                assert (np.asarray(g.wiped) == np.asarray(w.wiped)).all()
                assert (np.asarray(g.wiped) == 0xDEADBEEF).all()


def test_coalescing_cuts_dispatches_not_results(make_pud_service):
    """pallas, structural: batching the same tick's heals+erases into
    fused groups must strictly reduce kernel launches."""
    counts = {}
    for coalesce in (True, False):
        svc = make_pud_service(backend="pallas", coalesce=coalesce)
        svc.serve(mixed_requests(seed=7, n_heal=4, n_erase=4))
        snap = svc.snapshot()
        counts[coalesce] = snap.dispatches
        assert snap.completed == 8
    assert counts[True] < counts[False], counts


def test_heal_through_service_equals_backend_majx(make_pud_service):
    """A single heal is exactly the backend's majority vote."""
    rng = np.random.default_rng(3)
    replicas = rand_u32(rng, 3, 2, 8)
    svc = make_pud_service(backend="pallas")
    [res] = svc.serve([HealRequest(replicas=replicas)])
    want = np.asarray(get_backend("oracle", IDEAL).majx(replicas))
    assert (np.asarray(res.healed) == want).all()
    assert res.decision is not None  # offload verdict rides along


def test_verify_request_counts_bits(make_pud_service):
    rng = np.random.default_rng(4)
    live = rand_u32(rng, 2, 8)
    ref = live.copy()
    ref[0, 0] ^= 0b101  # 2 flipped bits
    svc = make_pud_service(backend="oracle")
    [res] = svc.serve([IntegrityRequest(live=live, reference=ref)])
    assert res.mismatch_bits == 2
    assert res.total_bits == live.size * 32
    assert 0.0 < res.success_rate < 1.0


# ------------------------------------------- shared-cache concurrency


@pytest.mark.parametrize("backend", BACKENDS)
def test_concurrent_sessions_one_miss_rest_hits(backend):
    """N sessions over ONE cache resolve the same program concurrently:
    exactly 1 miss + N-1 hits, results bit-exact with the oracle."""
    n = 4
    rng = np.random.default_rng(0)
    prog = valid_rand_program(rng, rows=8, n_ops=6)
    state = rand_u32(rng, 8, 8)
    want = np.asarray(get_backend("oracle", IDEAL).run(prog, state))
    cache = CompileCache()
    sessions = [DramSession(backend, IDEAL, cache=cache, name=f"s{i}")
                for i in range(n)]
    with ThreadPoolExecutor(max_workers=n) as pool:
        outs = list(pool.map(
            lambda s: np.asarray(s.run_fused(prog, state)), sessions))
    assert (cache.stats.hits, cache.stats.misses) == (n - 1, 1)
    for out in outs:
        assert (out == want).all()


def test_service_pool_shares_one_cache(make_pud_service):
    """Every pooled session holds the service's cache; a steady request
    shape is 1 miss + hits thereafter across the whole pool."""
    svc = make_pud_service(backend="pallas", pool_size=3)
    assert all(s.cache is svc.cache for s in svc.sessions)
    for r in range(3):
        svc.serve(mixed_requests(seed=r, n_heal=2, n_erase=0))
    # 2 lookups per heal batch (run_fused + the offload verdict's
    # schedule_for): 3 rounds = 6 lookups, only the first ever builds.
    assert svc.cache.stats.misses == 1
    assert svc.cache.stats.hits == 5
    assert svc.snapshot().cache["hit_rate"] == pytest.approx(5 / 6)


# ------------------------------------------- admission & backpressure


def test_queue_full_backpressure(make_pud_service):
    svc = make_pud_service(backend="oracle", queue_depth=2)
    rng = np.random.default_rng(1)
    with pytest.raises(QueueFullError):
        svc.serve([heal_req(rng) for _ in range(3)])
    assert svc.backlog == 2      # the two admitted requests still queue
    assert svc.snapshot().rejected == 1
    while svc.backlog:
        svc.tick()               # and remain servable after the rejection


def test_tenant_queue_depth_cap(make_pud_service):
    svc = make_pud_service(backend="oracle", tenant_queue_depth=1)
    rng = np.random.default_rng(2)
    with pytest.raises(QueueFullError, match="tenant 'a'"):
        svc.serve([heal_req(rng, tenant="a"), heal_req(rng, tenant="a")])


def test_arena_exhausted_and_released(make_pud_service):
    # a (3, 2, words) heal needs (3+1)*2 = 8 arena rows
    svc = make_pud_service(backend="oracle", tenant_rows=8)
    rng = np.random.default_rng(3)
    svc.serve([heal_req(rng, tenant="a")])
    arena = svc.admission.arena("a")
    assert arena.rows_in_use == 0          # reservation freed on completion
    with pytest.raises(ArenaExhaustedError, match="tenant 'a'"):
        svc.serve([heal_req(rng, tenant="a"), heal_req(rng, tenant="a")])
    snap = svc.snapshot().tenants["a"]
    assert snap["completed"] == 1 and snap["rejected"] == 1
    assert snap["row_budget"] == 8


def test_deadline_shedding(make_pud_service):
    """A past-deadline request is load-shed at its tick: its slot holds
    the DeadlineExceededError, its arena rows are released, live work
    in the same tick completes normally."""
    svc = make_pud_service(backend="oracle")
    rng = np.random.default_rng(4)
    late = heal_req(rng, tenant="late", deadline_s=-0.001)
    ok = heal_req(rng, tenant="ok")
    res_late, res_ok = svc.serve([late, ok])
    assert isinstance(res_late, DeadlineExceededError)
    assert res_ok.fixed_bits == 3
    snap = svc.snapshot()
    assert snap.shed == 1 and snap.completed == 1
    assert snap.tenants["late"]["shed"] == 1
    assert svc.admission.arena("late").rows_in_use == 0


def test_shedding_disabled_runs_late_work(make_pud_service):
    svc = make_pud_service(backend="oracle", shed_late=False)
    rng = np.random.default_rng(5)
    [res] = svc.serve([heal_req(rng, deadline_s=-0.001)])
    assert res.fixed_bits == 3


def test_priority_order_and_fifo():
    q = RequestQueue(max_depth=8)
    rng = np.random.default_rng(6)
    lo = heal_req(rng, tenant="lo", priority=Priority.LOW)
    n1 = heal_req(rng, tenant="n1")
    n2 = heal_req(rng, tenant="n2")
    hi = heal_req(rng, tenant="hi", priority=Priority.HIGH)
    for r in (lo, n1, n2, hi):
        q.push(r)
    assert [r.tenant for r in q.drain()] == ["hi", "n1", "n2", "lo"]
    assert len(q) == 0 and q.tenant_depth("lo") == 0


def test_request_validation():
    rng = np.random.default_rng(7)
    with pytest.raises(ServeError, match="odd replica count"):
        HealRequest(replicas=rand_u32(rng, 4, 2, 8))
    with pytest.raises(ServeError, match="required"):
        HealRequest()
    with pytest.raises(ServeError, match="rank-2"):
        IntegrityRequest(live=rand_u32(rng, 8), reference=rand_u32(rng, 8))
    with pytest.raises(ServeError, match="fanout"):
        EraseRequest(rows=4, words=8, fanout=32)
    with pytest.raises(ServeError, match="rows >= 1"):
        EraseRequest(rows=0, words=8)


# --------------------------------------------------- async client API


def test_async_submit_and_stop(make_pud_service):
    async def drive():
        svc = make_pud_service(backend="oracle")
        await svc.start()
        rng = np.random.default_rng(8)
        results = await asyncio.gather(
            *(svc.submit(heal_req(rng, tenant=f"t{i}")) for i in range(4)))
        await svc.stop()
        return svc, results

    svc, results = asyncio.run(drive())
    assert [r.fixed_bits for r in results] == [3, 3, 3, 3]
    assert svc.snapshot().completed == 4 and svc.backlog == 0


def test_async_submit_shed_raises(make_pud_service):
    async def drive():
        svc = make_pud_service(backend="oracle")
        await svc.start()
        rng = np.random.default_rng(9)
        try:
            with pytest.raises(DeadlineExceededError):
                await svc.submit(heal_req(rng, deadline_s=-0.001))
        finally:
            await svc.stop()

    asyncio.run(drive())


# ------------------------------------------------------- SLO snapshot


def test_slo_snapshot_structure(make_pud_service):
    svc = make_pud_service(backend="pallas", pool_size=2)
    for r in range(2):
        svc.serve(mixed_requests(seed=r, n_heal=4, n_erase=2))
    snap = svc.snapshot()
    assert snap.completed == 12
    assert snap.p50_latency_s is not None
    assert snap.p99_latency_s is not None
    assert snap.p99_latency_s >= snap.p50_latency_s
    assert snap.batch_occupancy > 1.0       # heals coalesced
    assert snap.batches >= 2 and snap.dispatches > 0
    assert snap.throughput_rps > 0
    assert len(snap.session_ema_s) == 2
    assert set(snap.tenants) == {"t0", "t1", "t2", "t3"}
    json.dumps(snap.to_dict())              # schema is JSON-serializable


def test_reset_slo_rebases_cache_window(make_pud_service):
    svc = make_pud_service(backend="oracle")
    svc.serve(mixed_requests(seed=0, n_heal=2, n_erase=0))  # the miss
    svc.reset_slo()
    assert svc.snapshot().completed == 0
    svc.serve(mixed_requests(seed=1, n_heal=2, n_erase=0))
    cache = svc.snapshot().cache
    assert cache == {"hits": 2, "misses": 0, "hit_rate": 1.0}


def test_slo_monitor_flags_straggler_session():
    mon = SloMonitor(n_sessions=2)
    for _ in range(6):
        mon.record_batch(1, 0.001, 1, session_idx=0)
        mon.record_batch(1, 0.100, 1, session_idx=1)
    snap = mon.snapshot(CompileCache().stats)
    assert snap.slow_sessions == [1]
    assert snap.session_ema_s[1] > snap.session_ema_s[0]


# ------------------------------------- straggler detector contract


def test_straggler_post_init_contract():
    """The ema field is never None after construction (the old
    ``ema: np.ndarray = None`` type-lie is gone)."""
    det = StragglerDetector(n_workers=3)
    assert isinstance(det.ema, np.ndarray) and det.ema.shape == (3,)
    seeded = StragglerDetector(n_workers=2, ema=[0.5, 1.0])
    assert seeded.ema.dtype == float and seeded.ema[1] == 1.0
    with pytest.raises(ValueError, match="n_workers"):
        StragglerDetector(n_workers=0)
    with pytest.raises(ValueError, match="alpha"):
        StragglerDetector(n_workers=2, alpha=0.0)
    with pytest.raises(ValueError, match="shape"):
        StragglerDetector(n_workers=2, ema=np.zeros(3))


# ------------------------------------- engine as a service client
# (the tiny 2-tensor engine factory lives in conftest.py, shared with
# the system suite)


def test_engine_heal_and_verify_through_service(make_tiny_pud_engine):
    eng, params = make_tiny_pud_engine(pud_backend="pallas")
    bad = {k: v.copy() for k, v in params.items()}
    bad["w"][0, 0] = np.float32(99.0)  # silent corruption in one replica
    fixed = eng.heal_params([bad, params, params])
    assert fixed > 0
    assert eng.verify_params(params) == 1.0
    assert (np.asarray(eng.params["w"]) == params["w"]).all()
    assert eng.pud_decisions[-1] is not None
    assert eng.service.snapshot().tenants["engine"]["completed"] == 2


def test_engine_heals_params_beyond_default_arena():
    """An engine-owned service sizes its row budget from the params: a
    17 MiB tree packs into 1,088 rows of 4,096 words, and a 3-replica
    heal needs 4 x 1,088 rows, past the default 4,096-row budget."""
    from repro.configs.registry import get_config
    from repro.serve import ServiceConfig
    from repro.serve.engine import Engine

    rng = np.random.default_rng(17)
    clean = {"w": rng.integers(0, 2**32, 17 * 2**20 // 4, dtype=np.uint32)}
    bad = {"w": clean["w"].copy()}
    bad["w"][::100_003] ^= np.uint32(1 << 7)
    flipped = int(np.bitwise_count(bad["w"] ^ clean["w"]).sum())
    eng = Engine(clean, get_config("xlstm-125m", smoke=True),
                 pud_backend="oracle")
    assert 4 * 1088 > ServiceConfig.tenant_rows
    assert eng.heal_params([bad, clean, dict(clean)]) == flipped
    assert (np.asarray(eng.params["w"]) == clean["w"]).all()
    assert eng.verify_params(clean) == 1.0


def test_engine_warns_on_non_ideal_context(make_tiny_pud_engine):
    from repro.serve.engine import IntegrityContextWarning

    eng, params = make_tiny_pud_engine(pud_backend="oracle",
                               pud_ctx=ExecutionContext(ideal=False))
    with pytest.warns(IntegrityContextWarning, match="non-ideal"):
        eng.heal_params([params, params, params])


def test_engine_strict_integrity_raises(make_tiny_pud_engine):
    from repro.serve.engine import IntegrityContextError

    eng, params = make_tiny_pud_engine(pud_backend="oracle",
                               pud_ctx=ExecutionContext(ideal=False),
                               strict_integrity=True)
    with pytest.raises(IntegrityContextError, match="fidelity studies"):
        eng.heal_params([params, params, params])


def test_engine_ideal_context_is_silent(make_tiny_pud_engine):
    eng, params = make_tiny_pud_engine(pud_backend="oracle")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng.heal_params([params, params, params])


def test_engines_can_share_one_service(make_pud_service, make_tiny_pud_engine):
    svc = make_pud_service(backend="pallas")
    a, params = make_tiny_pud_engine(pud_service=svc, tenant="engine-a")
    b, _ = make_tiny_pud_engine(pud_service=svc, tenant="engine-b")
    assert a.service is svc and b.service is svc
    a.heal_params([params, params, params])
    b.heal_params([params, params, params])
    tenants = svc.snapshot().tenants
    assert tenants["engine-a"]["completed"] == 1
    assert tenants["engine-b"]["completed"] == 1
    assert svc.cache.stats.hits >= 1       # second vote reused the schedule
