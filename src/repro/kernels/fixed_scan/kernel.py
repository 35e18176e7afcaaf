"""The fixed-slot calibration scan's slots, K at a time, in one Pallas kernel.

The batched fixed-slot engine (``repro.runtime.batched``) advances every
operating point one slot at a time.  Under XLA a slot is a chain of
some thirty small fusions, each launched on its own, so a slot costs
far more than its work.  This kernel runs K slots of the engine's slot
body in one call and keeps the state on the core between them.

Layout: the points lie on the lane axis, padded to whole ``(8, 128)``
tiles, and every quantity (a thread's timer, a queue's backlog, a
counter) is its own row of such tiles.  Operands are stacks of rows::

    consts (n_c, R, 128) f32      point constants, read once
    fstate (n_f, R, 128) f32      float state, aliased to the output
    istate (n_i, R, 128) i32      integer state, aliased to the output
    draws  (K, n_d, R, 128) f32   each slot's random draws

The grid walks the points eight lane-rows (one vreg per row) at a time;
points never interact, so a block's K slots run start to end before the
next block's, with the state in vregs and VMEM.  ``slot(c, f, i, d, t)``
is the engine's slot body on lists of ``(8, 128)`` tiles: it takes the
constants, the state's float and integer rows, the slot's draws and the
slot index, and returns the new float and integer rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret

LANES = 128
ROWS = 8      # lane-rows per grid step: one f32 vreg per quantity


def _kernel(t0_ref, c_ref, f_ref, i_ref, d_ref, f_out, i_out, *, slot,
            n_slots):
    c = [c_ref[j] for j in range(c_ref.shape[0])]
    t0 = t0_ref[0]

    def body(k, carry):
        f, i = carry
        d = [d_ref[k, j] for j in range(d_ref.shape[1])]
        f, i = slot(c, list(f), list(i), d, t0 + k)
        return tuple(f), tuple(i)

    # a loop, not an unroll: Mosaic compiles the slot body once
    f, i = jax.lax.fori_loop(
        0, n_slots, body,
        (tuple(f_ref[j] for j in range(f_ref.shape[0])),
         tuple(i_ref[j] for j in range(i_ref.shape[0]))))
    for j, x in enumerate(f):
        f_out[j] = x
    for j, x in enumerate(i):
        i_out[j] = x


def advance_slots(slot, consts, fstate, istate, draws, t0, *,
                  interpret: bool | None = None):
    """``(fstate, istate)`` after ``draws.shape[0]`` slots of ``slot``,
    the first of them slot ``t0`` (an int32 scalar)."""
    n_slots, n_d, r, lanes = draws.shape
    assert lanes == LANES and r % ROWS == 0, draws.shape

    def rows(n):
        return pl.BlockSpec((n, ROWS, LANES), lambda b, t0: (0, b, 0))

    specs = [rows(consts.shape[0]), rows(fstate.shape[0]),
             rows(istate.shape[0]),
             pl.BlockSpec((n_slots, n_d, ROWS, LANES),
                          lambda b, t0: (0, 0, b, 0))]
    tile = ROWS * LANES * 4
    # every block twice (the pipeline's two buffers), and room to spill
    vmem = 2 * tile * (consts.shape[0] + 2 * fstate.shape[0]
                       + 2 * istate.shape[0] + n_slots * n_d) + (4 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, slot=slot, n_slots=n_slots),
        out_shape=(jax.ShapeDtypeStruct(fstate.shape, fstate.dtype),
                   jax.ShapeDtypeStruct(istate.shape, istate.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r // ROWS,), in_specs=specs,
            out_specs=(specs[1], specs[2])),
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=resolve_interpret(interpret),
        name="fixed_scan",
    )(jnp.reshape(t0, (1,)).astype(jnp.int32), consts, fstate, istate,
      draws)
