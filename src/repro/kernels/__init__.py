"""Pallas TPU kernels: the serving/training compute hot spots, and the
fixed-slot calibration scan's slots.

The model kernels ship as <name>/{kernel.py, ops.py, ref.py}: the
pallas_call + BlockSpec tiling, the jit'd public wrapper, and the
pure-jnp oracle it is validated against (interpret mode on CPU; the TPU
target is declared in the BlockSpecs).  ``fixed_scan/kernel.py`` runs
the batched engine's slot body (``repro.runtime.batched``); its oracle
is that engine's ``lax.scan`` driver over the same body.
"""

from .decode_attention import decode_attention, reference_decode_attention  # noqa: F401
from .flash_attention import flash_attention, reference_attention  # noqa: F401
from .ssd_scan import reference_ssd_scan, ssd_scan  # noqa: F401
