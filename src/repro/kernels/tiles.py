"""Layout helpers shared by the Pallas kernels.

Two rules of the TPU compiler shape every kernel here: a block's last two
dimensions must divide by (8, 128) or span the whole array, and a kernel
value cannot be a 1-D vector. So sequence axes are padded up to their tile
in the wrappers (the kernels mask or neutralise the padding), and per-row
vectors travel as `(n, 1)` columns inside a kernel and as `(1, n)` rows in
HBM, converted with a masked reduction instead of a transpose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def seq_tile(n: int, block: int) -> tuple[int, int]:
    """(block, padded length) for a sequence axis of length `n`.

    A sequence that fits one block is one full-length block, unpadded.
    A longer one pads to a multiple of 128 lanes, and the block halves
    (down to 128) until it divides that length, so padding stays under
    128 slots whatever `n` is."""
    if n <= block:
        return n, n
    padded = -(-n // LANES) * LANES
    while block > LANES and padded % block:
        block //= 2
    return block, -(-padded // block) * block


def pad_to(x, axis: int, n: int):
    """Zero-pad `x` along `axis` up to length `n` (no-op when equal)."""
    extra = n - x.shape[axis]
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def row_to_col(row):
    """(1, n) -> (n, 1), exactly: keep the diagonal, reduce over lanes."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def col_to_row(col):
    """(n, 1) -> (1, n), exactly: keep the diagonal, reduce over sublanes."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)
