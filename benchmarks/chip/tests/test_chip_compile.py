"""Every kernel shape the cells run, compiled for a described TPU v5e.

Nothing runs: this finds what the chip's compiler would refuse (tiling,
fast-memory limits) before any chip time is spent.  The shapes come
from the cells' own configuration and traffic files at their real sizes.
"""

import os
from functools import partial

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import harness
from repro.kernels.majx.ops import majx

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def majx_shapes(config, traffic):
    """The MAJ groups of the traced program, at the cell's lane count."""
    from repro.compile import compile_elementwise
    from repro.compile.schedule import build_schedule

    lanes = 256
    a = np.arange(lanes, dtype=np.uint32) * 2654435761
    cp = compile_elementwise(config["op"], a, a[::-1], tier=config["ctx"][
        "tier"], n_act=config["ctx"]["n_act"])
    words = traffic["lanes"] // 32
    return {(g.param, len(g.ops), words)
            for level in build_schedule(cp.program).levels for g in level
            if g.kind == "MAJ"}


def all_shapes():
    spec = harness.load_spec()
    shapes = set()
    for w in spec["workloads"]:
        cell = harness.resolve(spec, w["name"], trace=False)
        shapes |= majx_shapes(cell.config, cell.traffic)
    return sorted(shapes)


def test_every_cell_kernel_compiles_for_the_chip(one_chip):
    shapes = all_shapes()
    assert shapes
    fn = jax.jit(partial(majx, interpret=False))
    for shape in shapes:
        arg = jax.ShapeDtypeStruct(shape, np.uint32, sharding=one_chip)
        text = fn.lower(arg).compile().as_text()
        assert "tpu_custom_call" in text or "custom-call" in text, shape
