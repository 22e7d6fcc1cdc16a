"""Pallas TPU kernels for the fused transformer path (SURVEY.md §7 step 8)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


class DoesNotTile(Exception):
    """A kernel's own statement, made from shapes before anything is traced
    into a pallas_call, that it does not tile this call.  It is the one
    thing that lets ops/fused.py take the XLA composite: whatever tracing,
    lowering or compiling raises is something else and propagates."""


# float32 elements per block of a row-tiled kernel: such a kernel keeps
# several block-sized float32 temporaries live, and Mosaic's scoped VMEM
# limit on a v5e is 16 MiB
_BLOCK_ELEMS = 256 * 1024


def pick_block_rows(r: int, n: int) -> int:
    """Rows per block for a kernel tiling an [r, n] array by rows: the
    largest power of two from 256 down to 8 that divides r and keeps the
    block within _BLOCK_ELEMS (8 rows at any width); 0 if none divides."""
    for cand in (256, 128, 64, 32, 16, 8):
        if r % cand == 0 and (cand * n <= _BLOCK_ELEMS or cand == 8):
            return cand
    return 0


def interpret_default() -> bool:
    """Pallas kernels interpret on CPU (tests), compile via Mosaic on TPU."""
    return jax.default_backend() == "cpu"


def im(f):
    """Index-map wrapper forcing literal ints to i32 (the framework enables
    jax_enable_x64 for float64 API parity; Mosaic rejects i64 block indices)."""
    def g(*idx):
        return tuple(jnp.int32(v) if isinstance(v, int) else v
                     for v in f(*idx))
    return g
