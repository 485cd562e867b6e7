"""Shared TPU tile-shape helpers for the Pallas kernels in this package."""

from __future__ import annotations

#: float32 VMEM tile shape (sublane x lane)
SUBLANE = 8
LANE = 128


def round_up(n: int, k: int) -> int:
    """Smallest multiple of k that is >= max(n, k)."""
    return max(k, (n + k - 1) // k * k)


def pow2_bucket(n: int) -> int:
    """Next power of two >= max(n, 1) — the padding granule shared by every
    cross-problem batching scheme in this repo (batched annealing, batched
    cycle simulation).

    Padding each problem to bucket sizes (instead of group-max) makes a
    problem's batched result independent of which other problems share its
    dispatch, so batched artifacts are reproducible and cacheable per
    problem, and the compiled program is reused across explorations."""
    return 1 << max(0, (n - 1)).bit_length()


def pad2d(x, fill=0, rows=SUBLANE):
    """Zero-copy-where-possible pad of a 2-D array to the float32 VMEM tile
    grid (rows to a multiple of ``rows`` — SUBLANE, or a kernel's row block
    — and cols to a LANE multiple).

    Returns the padded array; ``fill`` seeds the padding region (0 for data
    whose pad rows must reduce to the masked identity).
    """
    import jax.numpy as jnp

    r, c = x.shape
    rp, cp = round_up(r, rows), round_up(c, LANE)
    if (rp, cp) == (r, c):
        return x
    out = jnp.full((rp, cp), fill, dtype=x.dtype)
    return out.at[:r, :c].set(x)
