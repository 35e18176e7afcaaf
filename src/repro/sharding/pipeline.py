"""GPipe-style pipeline parallelism over a "pipe" mesh axis (DESIGN.md §4).

The production mesh for this assignment is DP×TP (16×16 / 2×16×16), so PP
ships as an optional substrate: ``gpipe`` runs a layer stack split into
S = |pipe| stages over M microbatches using shard_map + lax.ppermute —
the schedule is the classic (M + S - 1)-step ramp/drain with bubbles
masked.  Stage i holds layers [i·L/S, (i+1)·L/S); activations stream
stage→stage over collective-permute (ICI-neighbor traffic only, the reason
PP is the cross-pod axis of choice at 1000+ nodes).

Validated against the sequential reference in an 8-device subprocess
(tests/test_pipeline.py), including grads through the pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe"]


def gpipe(block_fn, stacked_params, x, mesh, *, pipe_axis: str = "pipe",
          n_microbatches: int):
    """Run ``y = block_fn(params_l, y)`` for every layer l, pipelined.

    stacked_params: pytree with leading layer dim L on every leaf
                    (L % n_stages == 0);
    x: (B, ...) with B % n_microbatches == 0.
    Returns y with x's shape.  Differentiable (jax.grad streams the
    backward pipeline in reverse automatically).
    """
    s = mesh.shape[pipe_axis]
    m = n_microbatches
    b = x.shape[0]
    assert b % m == 0, (b, m)
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    assert lead % s == 0, (lead, s)
    per_stage = lead // s

    # (L, ...) -> (S, L/S, ...): dim 0 shards over the pipe axis.
    staged = jax.tree.map(
        lambda p: p.reshape((s, per_stage) + p.shape[1:]), stacked_params)
    xmb = x.reshape((m, b // m) + x.shape[1:])

    def stage_fn(params, mb):
        # params: (1, L/S, ...) local stage slice;  mb: (M, mbs, ...) full.
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(pipe_axis)
        carry = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)
        for t in range(m + s - 1):
            mb_idx = t - stage                      # microbatch at this stage
            active = (mb_idx >= 0) & (mb_idx < m)
            inp = jnp.where(stage == 0,
                            mb[jnp.clip(jnp.asarray(t), 0, m - 1)], carry)
            y = inp
            for l in range(per_stage):
                y = block_fn(jax.tree.map(lambda p: p[l], params), y)
            y = jnp.where(active, y, inp)
            idx = jnp.clip(mb_idx, 0, m - 1)
            store = active & (stage == s - 1)
            outs = outs.at[idx].set(jnp.where(store, y, outs[idx]))
            carry = jax.lax.ppermute(
                y, pipe_axis, [(i, (i + 1) % s) for i in range(s)])
        # broadcast final outputs from the last stage to all stages
        outs = jax.lax.psum(
            jnp.where(stage == s - 1, outs, jnp.zeros_like(outs)), pipe_axis)
        return outs

    other = tuple(a for a in mesh.axis_names if a != pipe_axis)
    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(pipe_axis), staged), P()),
        out_specs=P(),
        check_vma=False)
    del other
    outs = fn(staged, xmb)
    return outs.reshape(x.shape)
