"""Ahead-of-time compiles of the main path for one described TPU v5e chip.

Nothing runs: each program is lowered and compiled by the TPU compiler
for a chip that is described, not attached, which catches tiling, VMEM
and memory refusals before any chip time is spent.  The topology and
everything built from it live in module-scoped fixtures, so that only
the worker given this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import Model
from repro.runtime import (
    FleetConfig,
    FleetGrid,
    SimRunConfig,
    SweepGrid,
    simulate_batch,
    simulate_fleet,
)

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def on_chip(one_chip):
    """Shapes (a pytree of arrays or ShapeDtypeStructs) placed on the chip."""
    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    return place


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


class _Captured(Exception):
    pass


def _capture(monkeypatch, module, attr, build):
    """Swap a module's compile cache for one that hands back the jitted
    kernel and the arguments the entry point prepared, without running."""
    seen = {}

    def cache(*static):
        fn = build(*static)

        def call(*args):
            seen["fn"], seen["args"] = fn, args
            raise _Captured
        return call

    monkeypatch.setattr(module, attr, cache)
    return seen


def _lattice():
    from benchmarks.sweep_frontier import lattice

    lat = lattice(quick=False)
    cfg = SimRunConfig(duration_us=lat["duration_us"])
    grid = SweepGrid.product(
        t_s_us=lat["t_s_grid"], t_l_us=lat["t_l_grid"], m=lat["m_grid"],
        rate_mpps=lat["rhos"] * cfg.service_rate_mpps, seeds=lat["seeds"])
    assert len(grid) == 2016
    return grid, cfg, lat["slot_us"]


def test_fixed_sweep_kernel_compiles(monkeypatch, on_chip):
    from repro.runtime import batched

    seen = _capture(monkeypatch, batched, "_compiled_sweep",
                    batched._build_sweep)
    grid, cfg, slot_us = _lattice()
    with pytest.raises(_Captured):
        simulate_batch(grid, cfg, slot_us=slot_us)
    _compile(seen["fn"], *on_chip(seen["args"]))


def test_adaptive_sweep_kernel_compiles(monkeypatch, on_chip):
    from repro.runtime import batched_adaptive

    seen = _capture(monkeypatch, batched_adaptive,
                    "_compiled_adaptive_sweep",
                    batched_adaptive._build_adaptive_sweep)
    grid, cfg, slot_us = _lattice()
    with pytest.raises(_Captured):
        simulate_batch(grid, cfg, slot_us=slot_us, stepping="adaptive")
    _compile(seen["fn"], *on_chip(seen["args"]))


def test_fleet_kernel_compiles_1000_hosts(monkeypatch, on_chip):
    from benchmarks.fleet import scale_sweep
    from repro.runtime import fleet

    seen = _capture(monkeypatch, fleet, "_compiled_fleet_sweep",
                    fleet._build_fleet_sweep)
    fgrid, cfg, slot_us = scale_sweep(quick=False)
    assert isinstance(fgrid, FleetGrid)
    assert fgrid.fleet == FleetConfig(n_hosts=1000) and len(fgrid) == 8
    with pytest.raises(_Captured):
        simulate_fleet(fgrid, cfg, slot_us=slot_us, shard=False)
    _compile(seen["fn"], *on_chip(seen["args"]))


@pytest.fixture(scope="module")
def gemma(on_chip):
    """gemma-2b at published widths: the model and its parameter shapes."""
    model = Model(get_config("gemma-2b"))
    shapes = jax.eval_shape(functools.partial(model.init, max_seq=1024),
                            jax.random.PRNGKey(0))
    return model, on_chip(shapes)


def test_gemma_prefill_compiles(gemma, on_chip):
    model, params = gemma
    tokens = on_chip(jax.ShapeDtypeStruct((1, 1024), jnp.int32))
    _compile(jax.jit(model.prefill), params, {"tokens": tokens})


def test_gemma_decode_step_compiles(gemma, on_chip):
    model, params = gemma
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(4, 1024)))
    vec = on_chip(jax.ShapeDtypeStruct((4,), jnp.int32))
    _compile(jax.jit(model.decode_step, donate_argnums=(2,)),
             params, vec, cache, vec)


def _kernel_compiles(fn, *args):
    assert "tpu_custom_call" in _compile(jax.jit(fn), *args).as_text()


def test_flash_attention_compiles_gemma_shapes(on_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("gemma-2b")
    hd, s = cfg.resolved_head_dim, 1024
    q = jax.ShapeDtypeStruct((1, s, cfg.n_heads, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, cfg.n_kv_heads, hd), jnp.bfloat16)
    _kernel_compiles(functools.partial(flash_attention, interpret=False),
                     *on_chip((q, kv, kv)))


@pytest.mark.parametrize("n_kv", [None, 2])
def test_decode_attention_compiles_gemma_shapes(on_chip, n_kv):
    """gemma-2b's MQA cache, and a bf16 GQA cache whose head pairs share
    a packed sublane."""
    from repro.kernels.decode_attention import decode_attention

    cfg = get_config("gemma-2b")
    hd, t, b = cfg.resolved_head_dim, 1024, 4
    q = jax.ShapeDtypeStruct((b, cfg.n_heads, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, n_kv or cfg.n_kv_heads, hd),
                              jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    _kernel_compiles(functools.partial(decode_attention, interpret=False),
                     *on_chip((q, kv, kv, pos)))


def test_ssd_scan_compiles_mamba2_shapes(on_chip):
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.mamba2 import ssm_dims

    cfg = get_config("mamba2-370m")
    _, nh, _ = ssm_dims(cfg)
    length = 4 * cfg.ssm_chunk
    args = (jax.ShapeDtypeStruct((1, length, nh, cfg.ssm_head_dim),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((1, length, nh), jnp.float32),
            jax.ShapeDtypeStruct((nh,), jnp.float32),
            jax.ShapeDtypeStruct((1, length, cfg.ssm_state), jnp.float32),
            jax.ShapeDtypeStruct((1, length, cfg.ssm_state), jnp.float32))
    _kernel_compiles(functools.partial(ssd_scan, chunk=cfg.ssm_chunk,
                                       interpret=False), *on_chip(args))


def test_every_kernel_is_compiled_here():
    """A new Pallas kernel under src/repro/kernels/ needs a compile test
    in this file."""
    import repro.kernels as k

    root = os.path.dirname(k.__file__)
    kernels = [d for d in os.listdir(root)
               if os.path.isfile(os.path.join(root, d, "kernel.py"))]
    tests = [n for n in globals() if n.startswith("test_")]
    assert len(kernels) == 3
    for name in kernels:
        assert any(t.startswith(f"test_{name}_compiles") for t in tests), name
