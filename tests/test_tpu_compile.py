"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses (a
vector load from SMEM, a scalar store to VMEM).  These compile each
kernel entry point with ``interpret=False`` at one 65,536-bit DRAM row
(2,048 ``uint32`` words) for a ``v5e:2x2`` topology that is described,
not attached.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compile import build_schedule
from repro.kernels.bitserial.ops import bitserial_add
from repro.kernels.majx.ops import majx
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
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scrub_tile_compiles_for_v5e(one_chip):
    """The scrub's tile at its real size (1,024 rows of 4,096 words):
    the jitted level walk of the tile program holds a kernel and keeps
    its working set well under 1 GB; the tile step (image, walk,
    write-back and mismatch counts in one program) over three replicas
    of four tiles holds both kernels, updates the replicas in place and
    keeps its temporaries under 256 MiB."""
    import copy

    from repro.backends.pallas import PallasBackend, _LevelWalk
    from repro.serve import scrub

    backend = PallasBackend()
    backend.interpret = False
    tile = scrub.TILE_ROWS
    walk = _LevelWalk(build_schedule(scrub.tile_program(3, tile)))
    image = jax.ShapeDtypeStruct((4 * tile, scrub.ROW_WORDS), jnp.uint32,
                                 sharding=one_chip)
    fn = functools.partial(copy.copy(backend)._walk, walk)
    compiled = jax.jit(fn).lower(image).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + \
        mem.output_size_in_bytes < 2**28
    replicas = tuple(jax.ShapeDtypeStruct((4 * tile, scrub.ROW_WORDS),
                                          jnp.uint32, sharding=one_chip)
                     for _ in range(3))
    counts = jax.ShapeDtypeStruct((4, 3), jnp.uint32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    twin, _ = backend.tracer()
    step = jax.jit(functools.partial(scrub._step, fn, twin.mismatch, tile),
                   donate_argnums=(0, 1)).lower(replicas, counts, t).compile()
    text = step.as_text()
    assert "majx_csa" in text and "mismatch_popcount" in text
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * tile * scrub.ROW_WORDS * 4
    assert mem.temp_size_in_bytes < 2**28


def test_region_erase_step_compiles_for_v5e(one_chip):
    """The rank wipe's step at its real size: a bank of 65,536 rows of
    2,048 words, in a region of 16 banks.  It holds ``rowcopy_fanout``,
    aliases the whole region (wiped in place, 8.59 GB) and keeps its
    temporaries (the bank's image, its copy with the pattern row, the
    kernel's 31 copies) under 1.5 GiB: 1.07 GB when written."""
    import copy

    from repro.backends.pallas import PallasBackend, _LevelWalk
    from repro.pud.secure_erase import BANK_ROWS, erase_program
    from repro.serve import region

    backend = PallasBackend()
    backend.interpret = False
    walk = _LevelWalk(build_schedule(erase_program(BANK_ROWS)))
    fn = functools.partial(copy.copy(backend)._walk, walk)
    rows = jax.ShapeDtypeStruct((16, BANK_ROWS, WORDS), jnp.uint32,
                                sharding=one_chip)
    pattern = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(region._step, fn),
                   donate_argnums=0).lower(rows, pattern, t).compile()
    assert "rowcopy_fanout" in step.as_text()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == 16 * BANK_ROWS * WORDS * 4
    assert mem.temp_size_in_bytes < 3 * 2**29


def test_elementwise_image_and_unpack_compile_for_v5e(one_chip):
    """The add32 arith cell's per-call device work around the walk, at
    its 2^20 lanes: packing both operands into the image, and unpacking
    the result rows, each one jitted program that holds no temporary
    beyond its operands and its result."""
    from repro.compile import trace

    lanes = 2**20
    compiled = trace._compile("add", lanes, 5, 32)
    rows, words = len(compiled.trace.sources), lanes // 32
    operand = jax.ShapeDtypeStruct((lanes,), jnp.uint32, sharding=one_chip)
    image = compiled.image.lower(operand, operand).compile()
    # Rows pad to the chip's 8 sublanes.
    assert image.memory_analysis().output_size_in_bytes == \
        -(-rows // 8) * 8 * words * 4
    assert image.memory_analysis().temp_size_in_bytes < 2**24
    state = jax.ShapeDtypeStruct((rows, words), jnp.uint32,
                                 sharding=one_chip)
    unpack = compiled.unpack.lower(state).compile()
    assert unpack.memory_analysis().output_size_in_bytes == lanes * 4
    assert unpack.memory_analysis().temp_size_in_bytes < 2**24
