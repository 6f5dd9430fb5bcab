"""Dry-run machinery on a small faked-device mesh (subprocess: the device
count is locked at first jax init, so tests exercise it out of process)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from repro.configs.registry import get_config, SHAPES
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import lower_cell
from repro.launch import roofline as rl

cfg = get_config("xlstm-125m", smoke=True)
import dataclasses
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
mesh = make_mesh((4, 2), ("data", "model"))
lowered, compiled = lower_cell(cfg, shape, mesh)
mem = compiled.memory_analysis()
coll = rl.collective_bytes(compiled.as_text(), loop_multiplier=cfg.n_layers)
ca = compiled.cost_analysis()
print(json.dumps({
    "temp_gb": mem.temp_size_in_bytes / 2**30,
    "flops": ca.get("flops", 0.0),
    "coll_ops": coll.n_ops,
    "coll_bytes": coll.total_bytes,
}))
"""


@pytest.mark.slow
def test_dryrun_lowers_on_8_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["flops"] > 0
    assert data["coll_ops"] > 0          # mesh collectives present
    assert data["temp_gb"] < 64          # smoke-size memory


@pytest.mark.slow
def test_decode_cell_lowers():
    script = SCRIPT.replace('SHAPES["train_4k"], seq_len=64, global_batch=8',
                            'SHAPES["decode_32k"], seq_len=128, global_batch=8')
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
