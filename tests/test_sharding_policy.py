"""Sharding policy rules (pspec correctness, divisibility degradation) and
a real (small-mesh) dry-run through the CLI in a subprocess."""

import functools
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.models import Model
from repro.sharding.policy import param_pspecs

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(arch, mesh=MESH, mode="train"):
    cfg = get_config(arch)
    params = jax.eval_shape(
        functools.partial(Model(cfg).init, max_seq=4096), jax.random.PRNGKey(0))
    return cfg, params, param_pspecs(cfg, params, mesh, mode)


def test_dense_tp_fsdp_rules():
    cfg, params, specs = _specs("internvl2-76b")
    blk = specs["blocks"]["layer0"]
    # stacked group dim first, then (D, X): fsdp x model
    assert blk["mixer"]["wq"] == P(None, "data", "model")
    assert blk["mixer"]["wo"] == P(None, "model", "data")
    assert blk["ffn"]["w_up"] == P(None, "data", "model")
    assert blk["ffn"]["w_down"] == P(None, "model", "data")
    assert blk["mixer_norm"] == P(None, None)
    # untied input embedding: vocab over fsdp
    assert specs["embed"] == P("data", None)
    assert specs["lm_head"] == P("data", "model")


def test_serve_mode_has_no_fsdp():
    cfg, params, specs = _specs("internvl2-76b", mode="serve")
    blk = specs["blocks"]["layer0"]
    assert blk["mixer"]["wq"] == P(None, None, "model")
    assert blk["ffn"]["w_down"] == P(None, "model", None)


def test_moe_expert_parallel_rules():
    cfg, params, specs = _specs("dbrx-132b")
    moe = specs["blocks"]["layer0"]["ffn"]
    assert moe["w_gate"] == P(None, "data", None, "model")   # (G, E, D, F)
    assert moe["w_down"] == P(None, "data", "model", None)   # (G, E, F, D)
    assert moe["router"] == P(None, None, None)


def test_divisibility_degrades_to_replication():
    # granite vocab 49155 isn't divisible by 16 anywhere
    cfg, params, specs = _specs("granite-3-8b")
    assert specs["embed"] == P(None, "data")   # tied: vocab/model unfit ->None
    # mamba2 vocab 50280 % 16 != 0, tied embedding
    cfg, params, specs = _specs("mamba2-370m")
    assert specs["embed"][0] is None


def test_multipod_fsdp_spans_pod_and_data():
    cfg, params, specs = _specs("internvl2-76b", mesh=MESH_MP)
    blk = specs["blocks"]["layer0"]
    assert blk["mixer"]["wq"] == P(None, ("pod", "data"), "model")


def test_ssm_rules():
    cfg, params, specs = _specs("mamba2-370m")
    blk = specs["blocks"]["layer0"]["mixer"]
    assert blk["wx"] == P(None, "data", "model")
    assert blk["out"] == P(None, "model", "data")
    assert blk["conv_w"] == P(None, None, "model")
    assert blk["A_log"] == P(None, None)


ALL_CELLS_SUBPROC = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs.base import cells
from repro.launch.inputs import build_cell
mesh = jax.make_mesh((2, 4), ("data", "model"))
n = 0
for arch, shape, skip in cells():
    cell = build_cell(arch, shape, mesh)      # constructs every abstract
    assert cell.args, (arch, shape)           # input tree + sharding
    n += 1
print("BUILT", n)
"""


@pytest.mark.slow
def test_every_cell_constructs_on_small_mesh_subprocess():
    """All 32 runnable cells must build their abstract sharded inputs on an
    arbitrary (2,4) mesh — catches shape/divisibility bugs without the
    cost of compiling (the full compile proof is the dry-run)."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", ALL_CELLS_SUBPROC],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=560)
    assert "BUILT 32" in out.stdout, out.stdout + out.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_cli_one_cell_subprocess():
    """The actual dry-run entry point must pass for a representative cell
    (cheapest full cell: mamba2 long_500k) on the production mesh."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-370m", "--shape", "long_500k"],
        capture_output=True, text=True, env=env, cwd=root, timeout=560)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "1 cells compiled OK, 0 failed" in out.stdout
    assert "roofline:" in out.stdout
