"""Backend parity: oracle / sim (ideal) / pallas (interpret) must agree
bit-exactly on every op class, and sim's calibrated error model must
reproduce the paper's success-rate ordering."""

import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import rand_u32
from repro.backends import (ExecutionContext, available_backends,
                            get_backend, register_backend)
from repro.backends.base import Backend
from repro.pud.isa import Program

BACKENDS = ("oracle", "sim", "pallas")
IDEAL = ExecutionContext(ideal=True)


def _all(ctx=IDEAL):
    return {name: get_backend(name, ctx) for name in BACKENDS}


# ---------------------------------------------------------------- registry


def test_pallas_interprets_on_cpu_only():
    """One rule decides how Pallas kernels run: the interpreter off a
    TPU (the only way they run on a CPU), compiled on one."""
    import jax

    from repro.backends.pallas import interpret_mode

    on_tpu = jax.default_backend() == "tpu"
    assert interpret_mode() is not on_tpu
    assert get_backend("pallas", IDEAL).interpret is not on_tpu


def test_registry_lists_all_three():
    assert set(BACKENDS) <= set(available_backends())


def test_registry_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown backend"):
        get_backend("cuda")


def test_registry_accepts_new_backend():
    from repro.backends import _REGISTRY

    before = dict(_REGISTRY)
    try:
        @register_backend("oracle2")
        class Oracle2(get_backend("oracle").__class__):
            pass

        assert "oracle2" in available_backends()
        assert isinstance(get_backend("oracle2"), Backend)
    finally:  # don't leak the test backend into the process registry
        _REGISTRY.clear()
        _REGISTRY.update(before)


def test_capabilities_shape():
    caps = {n: get_backend(n, IDEAL).capabilities() for n in BACKENDS}
    assert caps["sim"].device_model and not caps["oracle"].device_model
    assert caps["pallas"].accelerated and caps["pallas"].native_batch
    assert not caps["sim"].stochastic  # ideal ctx
    assert get_backend("sim").capabilities().stochastic


# ------------------------------------------------------------- MAJX parity


@pytest.mark.parametrize("x", [3, 5, 7, 9])
def test_majx_parity(x):
    rng = np.random.default_rng(x)
    planes = jnp.asarray(rand_u32(rng, x, 4, 40))
    outs = {n: np.asarray(be.majx(planes, n_act=32))
            for n, be in _all().items()}
    assert (outs["oracle"] == outs["sim"]).all()
    assert (outs["oracle"] == outs["pallas"]).all()


def test_majx_minimum_activation_parity():
    """n_act at the minimum reachable level (no replication)."""
    rng = np.random.default_rng(0)
    planes = jnp.asarray(rand_u32(rng, 3, 16))
    outs = {n: np.asarray(be.majx(planes, n_act=4))
            for n, be in _all().items()}
    assert (outs["oracle"] == outs["sim"]).all()
    assert (outs["oracle"] == outs["pallas"]).all()


def test_majx_batch_matches_loop():
    rng = np.random.default_rng(1)
    batch = jnp.asarray(rand_u32(rng, 3, 5, 8, 128))
    pal = get_backend("pallas", IDEAL)
    ora = get_backend("oracle", IDEAL)
    got = np.asarray(pal.majx_batch(batch))
    want = np.stack([np.asarray(ora.majx(p)) for p in batch])
    assert (got == want).all()


# ----------------------------------------------------- Multi-RowCopy parity


@pytest.mark.parametrize("n_dst", [1, 7, 15, 31])
def test_rowcopy_parity(n_dst):
    rng = np.random.default_rng(n_dst)
    src = jnp.asarray(rand_u32(rng, 24))
    outs = {n: np.asarray(be.rowcopy(src, n_dst))
            for n, be in _all().items()}
    assert outs["oracle"].shape == (n_dst, 24)
    assert (outs["oracle"] == outs["sim"]).all()
    assert (outs["oracle"] == outs["pallas"]).all()


def test_rowcopy_2d_parity():
    rng = np.random.default_rng(9)
    src = jnp.asarray(rand_u32(rng, 3, 40))
    outs = {n: np.asarray(be.rowcopy(src, 7)) for n, be in _all().items()}
    assert outs["oracle"].shape == (7, 3, 40)
    assert (outs["oracle"] == outs["sim"]).all()
    assert (outs["oracle"] == outs["pallas"]).all()


# ------------------------------------------------------------ mismatch parity


def test_mismatch_parity():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rand_u32(rng, 700))
    b = jnp.asarray(rand_u32(rng, 700))
    counts = {n: int(be.mismatch(a, b)) for n, be in _all().items()}
    assert counts["oracle"] == counts["sim"] == counts["pallas"]
    for be in _all().values():
        assert int(be.mismatch(a, a)) == 0
        assert be.success_rate(a, a) == 1.0


# ----------------------------------------------------- program execution


def _demo_program() -> Program:
    p = Program()
    p.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    p.emit("NOT", srcs=(3,), dsts=(4,))
    p.emit("COPY", srcs=(4,), dsts=(5,))
    p.emit("MRC", n_act=8, srcs=(5,), dsts=tuple(range(6, 13)))
    p.emit("MAJ", x=5, n_act=32, srcs=(0, 1, 2, 3, 4), dsts=(13, 14))
    p.emit("FRAC", dsts=(15,))
    return p


def test_program_execution_parity():
    rng = np.random.default_rng(3)
    prog = _demo_program()
    state = jnp.asarray(rand_u32(rng, prog.n_rows(), 8))
    finals = {n: np.asarray(be.run(prog, state)) for n, be in _all().items()}
    assert (finals["oracle"] == finals["sim"]).all()
    assert (finals["oracle"] == finals["pallas"]).all()


def test_program_semantics_against_closed_form():
    rng = np.random.default_rng(4)
    prog = _demo_program()
    state0 = np.asarray(rand_u32(rng, prog.n_rows(), 8))
    out = np.asarray(get_backend("oracle").run(prog, jnp.asarray(state0)))
    maj3 = ((state0[0] & state0[1]) | (state0[1] & state0[2])
            | (state0[0] & state0[2]))
    assert (out[3] == maj3).all()
    assert (out[4] == ~maj3).all()
    assert (out[5] == ~maj3).all()
    for d in range(6, 13):
        assert (out[d] == ~maj3).all()
    assert (out[15] == state0[15]).all()  # FRAC: value-wise untouched


def test_cost_only_program_is_noop():
    """Programs recorded purely for costing execute as identity."""
    rng = np.random.default_rng(5)
    be = get_backend("oracle")
    _, prog = be.elementwise("xor", rand_u32(rng, 8), rand_u32(rng, 8))
    state = jnp.asarray(rand_u32(rng, 4, 4))
    assert (np.asarray(be.run(prog, state)) == np.asarray(state)).all()


# ---------------------------------------------- compiled §8.1 arithmetic


@pytest.mark.parametrize("op,ref", [
    ("add", lambda a, b: (a + b).astype(np.uint32)),
    ("xor", lambda a, b: a ^ b),
    ("and", lambda a, b: a & b),
])
def test_elementwise_parity(op, ref):
    rng = np.random.default_rng(6)
    a = rand_u32(rng, 16)
    b = rand_u32(rng, 16)
    progs = {}
    for name, be in _all().items():
        out, prog = be.elementwise(op, a, b, tier=5, n_act=32)
        assert (np.asarray(out) == ref(a, b)).all(), name
        progs[name] = prog.histogram()
    # the recorded Program is backend-invariant
    assert progs["oracle"] == progs["sim"] == progs["pallas"]


# -------------------------------------------------- calibrated error model


def test_sim_error_model_replication_ordering():
    """Obs 6: 32-row MAJ3 success > 4-row MAJ3 success (input replication
    strengthens the charge-share margin)."""
    rng = np.random.default_rng(7)
    planes = jnp.asarray(rand_u32(rng, 3, 256))
    want = get_backend("oracle").majx(planes)
    rates = {}
    for n_act in (4, 32):
        sim = get_backend("sim", ExecutionContext(seed=11))
        rates[n_act] = sim.success_rate(sim.majx(planes, n_act=n_act), want)
    assert rates[32] > rates[4]
    em = ExecutionContext().error_model
    assert rates[4] == pytest.approx(em.majx_success(3, 4), abs=0.05)
    assert rates[32] == pytest.approx(em.majx_success(3, 32), abs=0.05)


def test_sim_ideal_vs_stochastic():
    rng = np.random.default_rng(8)
    planes = jnp.asarray(rand_u32(rng, 7, 256))
    want = get_backend("oracle").majx(planes)
    ideal = get_backend("sim", IDEAL)
    assert ideal.success_rate(ideal.majx(planes, n_act=32), want) == 1.0
    noisy = get_backend("sim", ExecutionContext(seed=3))
    s = noisy.success_rate(noisy.majx(planes, n_act=32), want)
    assert s < 1.0  # MAJ7@32: ~34% success (Obs 8)


def test_shared_context_threads_regime():
    """One ExecutionContext declares the regime for any backend."""
    ctx = ExecutionContext(mfr="M", temp_c=90.0, vpp_v=2.1, tier=7,
                           ideal=True)
    for name in BACKENDS:
        be = get_backend(name, ctx)
        assert be.ctx.mfr == "M"
        assert be.ctx.error_model.mfr == "M"
    # Mfr M caps MAJX arity at 7 (fn 11)
    assert get_backend("sim", ctx.replace(ideal=False)
                       ).capabilities().max_majx == 7
