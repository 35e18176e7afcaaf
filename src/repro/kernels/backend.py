"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` picks from the default backend: the compiled kernel on a
    TPU (the only path there), the Pallas interpreter anywhere else.
    An explicit ``False`` compiles for a TPU described off-chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
