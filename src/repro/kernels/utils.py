"""Shared wrapper plumbing for the Pallas kernels: padding to block
multiples, block-size fitting, and the interpret-default resolution.

The model-kernel wrappers in ``ops.py`` pad their inputs up to the
kernel's block multiple, run the kernel, and slice the pad back off — this
module is the ONE copy of that arithmetic, which the row-blocked DP
forward (``hosting.dp_minplus_rows``) also uses to pad its rows and
slots.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pad_to(x, axis: int, mult: int, value=0):
    """Pad ``x`` along ``axis`` up to the next multiple of ``mult`` with
    ``value`` (0/False by default).  Returns ``(padded, pad)``."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def fit_block(block: int, n: int, floor: int = 16) -> int:
    """Shrink a requested block size to the next power of two covering
    ``n`` (never below ``floor``): tiny inputs then run as one block
    instead of padding up to the full requested block."""
    return min(block, max(floor, 1 << (n - 1).bit_length()))


def default_interpret() -> bool:
    """Resolve ``interpret=None``: True on CPU (no Mosaic backend — the
    kernel body runs through the Pallas interpreter, bit-identical to the
    compiled lowering), False on TPU.  See ``kernels.__init__``."""
    return jax.default_backend() == "cpu"
