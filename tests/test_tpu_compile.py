"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses (a
vector load from SMEM, a scalar store to VMEM, a table that overflows
SMEM).  These compile each kernel entry point with ``interpret=False``
at one 65,536-bit DRAM row (2,048 ``uint32`` words) for a ``v5e:2x2``
topology that is described, not attached.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends import ExecutionContext
from repro.compile import (build_schedule, compile_elementwise,
                           lower_schedule, plan_vmem)
from repro.kernels.bitserial.ops import bitserial_add
from repro.kernels.majx.ops import majx
from repro.kernels.megakernel.ops import run_lowering
from repro.kernels.mismatch.ops import mismatch_count
from repro.kernels.rowcopy.ops import fanout

WORDS = 2048  # one 65,536-bit DRAM row


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _add32_case():
    """The §8.1 add32 program over one row of lanes, lowered to the
    megakernel's level tables, and its column plan."""
    ctx = ExecutionContext(ideal=True)
    rng = np.random.default_rng(0)
    a, b = (rng.integers(0, 2**32, WORDS * 32, dtype=np.uint32)
            for _ in range(2))
    cp = compile_elementwise("add", a, b, tier=ctx.tier, n_act=ctx.n_act)
    low = lower_schedule(build_schedule(cp.program))
    rows, words = cp.state.shape
    plan = plan_vmem(low, rows, words, ctx.vmem_budget_bytes,
                     block_r=ctx.block_r)
    return (functools.partial(run_lowering, low, block_c=plan.block_c,
                              interpret=False), [(rows, words)])


CASES = {
    "majx9": lambda: (functools.partial(majx, interpret=False),
                      [(9, 8, WORDS)]),
    "majx_batch": lambda: (jax.vmap(functools.partial(majx,
                                                      interpret=False)),
                           [(4, 9, 8, WORDS)]),
    "fanout31": lambda: (functools.partial(fanout, fanout_n=31,
                                           interpret=False), [(8, WORDS)]),
    "bitserial_add": lambda: (functools.partial(bitserial_add,
                                                interpret=False),
                              [(32, 8, WORDS)] * 2),
    "mismatch": lambda: (functools.partial(mismatch_count, interpret=False),
                         [(8, WORDS)] * 2),
    "megakernel_add32": _add32_case,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
