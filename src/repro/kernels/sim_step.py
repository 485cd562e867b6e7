"""Tile-step ALU dispatch kernels for the cycle-accurate fabric simulator.

Every simulated cycle, all PE tiles execute one micro-op of their configured
datapath in lockstep: gather operands, apply the tile's opcode, write the
result.  That inner step — a batched, opcode-indexed elementwise dispatch —
is the hot loop of :mod:`repro.sim.cycle`, and it is exactly VPU-shaped:
same instruction stream across lanes, divergence resolved by select.

Three implementations behind the same backend-switch pattern as
:mod:`repro.kernels.pnr_cost`:

* :func:`alu_step_reference` — pure NumPy loop, the oracle;
* :func:`alu_step_jnp` — ``jax.vmap`` of ``lax.switch`` over the flattened
  (batch x tile) lanes, jitted per static op table;
* :func:`alu_step_pallas` — Pallas kernel computing every op of the static
  table and masking by opcode (compute-all-select, the way a SIMD machine
  actually retires divergent lanes).  Interpret mode on CPU hosts;
  compiles to VMEM tiles on TPU.

Opcode 0 is always ``nop`` (padding lanes).  Semantics mirror
:data:`repro.graphir.interp.SEMANTICS` in float32: predicates are encoded
as 1.0/0.0 and consumed as ``x != 0``, so a schedule simulated here
bit-matches the NumPy interpreter on IEEE-exact op sets (the whole paper
suite: add/sub/mul/min/max/shift/compare/select).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .ops import default_interpret

#: (a, b, c) -> result, all float32; must mirror graphir.interp.SEMANTICS
ALU_IMPLS: Dict[str, Callable] = {
    "nop": lambda a, b, c: jnp.zeros_like(a),
    "add": lambda a, b, c: a + b,
    "sub": lambda a, b, c: a - b,
    "neg": lambda a, b, c: -a,
    "abs": lambda a, b, c: jnp.abs(a),
    "mul": lambda a, b, c: a * b,
    "mac": lambda a, b, c: a * b + c,
    "div": lambda a, b, c: a / b,
    "recip": lambda a, b, c: 1.0 / a,
    "shl": lambda a, b, c: a * (2.0 ** b),
    "shr": lambda a, b, c: a / (2.0 ** b),
    "ashr": lambda a, b, c: a / (2.0 ** b),
    "eq": lambda a, b, c: (a == b).astype(a.dtype),
    "neq": lambda a, b, c: (a != b).astype(a.dtype),
    "lt": lambda a, b, c: (a < b).astype(a.dtype),
    "lte": lambda a, b, c: (a <= b).astype(a.dtype),
    "gt": lambda a, b, c: (a > b).astype(a.dtype),
    "gte": lambda a, b, c: (a >= b).astype(a.dtype),
    "min": lambda a, b, c: jnp.minimum(a, b),
    "max": lambda a, b, c: jnp.maximum(a, b),
    "and": lambda a, b, c: ((a != 0) & (b != 0)).astype(a.dtype),
    "or": lambda a, b, c: ((a != 0) | (b != 0)).astype(a.dtype),
    "xor": lambda a, b, c: ((a != 0) ^ (b != 0)).astype(a.dtype),
    "not": lambda a, b, c: (a == 0).astype(a.dtype),
    "sign": lambda a, b, c: jnp.sign(a),
    "sel": lambda a, b, c: jnp.where(a != 0, c, b),   # ports: cond,false,true
    "floor": lambda a, b, c: jnp.floor(a),
    "round": lambda a, b, c: jnp.round(a),
    "exp": lambda a, b, c: jnp.exp(a),
    "log": lambda a, b, c: jnp.log(a),
    "tanh": lambda a, b, c: jnp.tanh(a),
    "sigmoid": lambda a, b, c: 1.0 / (1.0 + jnp.exp(-a)),
    "rsqrt": lambda a, b, c: jax.lax.rsqrt(a),
    "sqrt": lambda a, b, c: jnp.sqrt(a),
    "pow": lambda a, b, c: a ** b,
}


def op_table(used_ops: Sequence[str]) -> Tuple[str, ...]:
    """Static opcode table for a design: nop first, then sorted used ops."""
    missing = sorted(set(used_ops) - set(ALU_IMPLS))
    if missing:
        raise NotImplementedError(f"no ALU dispatch for ops {missing}")
    return ("nop",) + tuple(sorted(set(used_ops) - {"nop"}))


def alu_step_reference(codes: np.ndarray, a: np.ndarray, b: np.ndarray,
                       c: np.ndarray, ops: Tuple[str, ...]) -> np.ndarray:
    """NumPy oracle built on the interpreter's SEMANTICS table (independent
    of the jnp implementations above); codes (N,), operands (..., N)."""
    from ..graphir.interp import SEMANTICS
    from ..graphir.ops import OPS

    out = np.zeros_like(a, dtype=np.float32)
    for k, name in enumerate(ops):
        m = codes == k
        if not m.any() or name == "nop":
            continue
        args = [x[..., m].astype(np.float32) for x in (a, b, c)]
        if name == "sel":
            r = SEMANTICS[name](args[0] != 0, args[1], args[2])
        else:
            r = SEMANTICS[name](*args[:OPS[name].arity])
        out[..., m] = np.asarray(r, dtype=np.float32)
    return out


@functools.partial(jax.jit, static_argnames=("ops",))
def alu_step_jnp(codes: jax.Array, a: jax.Array, b: jax.Array,
                 c: jax.Array, ops: Tuple[str, ...]) -> jax.Array:
    """Batched dispatch: ``lax.switch`` vmapped over every (batch, tile)
    lane.  codes (N,), operands (N,) or (B, N)."""
    branches = [ALU_IMPLS[name] for name in ops]
    flat_codes = jnp.broadcast_to(codes, a.shape).reshape(-1)
    fa, fb, fc = (x.reshape(-1) for x in (a, b, c))
    out = jax.vmap(
        lambda k, x, y, z: jax.lax.switch(k, branches, x, y, z)
    )(flat_codes, fa, fb, fc)
    return out.reshape(a.shape)


@functools.partial(jax.jit, static_argnames=("ops",))
def alu_step_masked(codes: jax.Array, a: jax.Array, b: jax.Array,
                    c: jax.Array, ops: Tuple[str, ...],
                    active: jax.Array) -> jax.Array:
    """:func:`alu_step_jnp` with a dynamic activity mask.

    ``active`` (broadcastable to ``a``'s shape) carries dynamic program
    structure as *data*: the batched cycle simulator pads every tile to the
    bucket's micro-op count and instance count, then masks the padding with
    ``(step < n_steps) & (lane < n_inst)`` instead of baking each program's
    real lengths into the compiled code.  Inactive lanes retire 0.0 — the
    same value the nop padding computes — so one jitted program serves
    every program in a bucket and results are bit-identical to the
    per-program dispatch on the real lanes.
    """
    out = alu_step_jnp(codes, a, b, c, ops)
    return jnp.where(jnp.broadcast_to(active, out.shape), out,
                     jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _build_step_kernel(ops: Tuple[str, ...]):
    def kernel(codes_ref, a_ref, b_ref, c_ref, o_ref):
        codes = codes_ref[...]
        a, b, c = a_ref[...], b_ref[...], c_ref[...]
        out = jnp.zeros_like(a)
        for k, name in enumerate(ops):
            if name == "nop":
                continue
            out = jnp.where(codes == k, ALU_IMPLS[name](a, b, c), out)
        o_ref[...] = out
    return kernel


@functools.partial(jax.jit, static_argnames=("ops", "interpret"))
def alu_step_pallas(codes: jax.Array, a: jax.Array, b: jax.Array,
                    c: jax.Array, ops: Tuple[str, ...],
                    *, interpret: Optional[bool] = None) -> jax.Array:
    """Compute-all-select dispatch as a Pallas VPU kernel.

    Operands are padded to float32 tile multiples (8 x 128); the batch axis
    maps onto sublanes, tiles onto lanes.  Division/transcendental branches
    run on every lane and are masked out by the opcode select — standard
    SIMD divergence handling, no flow control in the kernel.  ``interpret``
    left unset follows :func:`~repro.kernels.default_interpret`.
    """
    from .tiling import LANE, SUBLANE, round_up

    if interpret is None:
        interpret = default_interpret()
    shape = a.shape
    a2 = a.reshape(-1, shape[-1]).astype(jnp.float32)
    b2 = b.reshape(-1, shape[-1]).astype(jnp.float32)
    c2 = c.reshape(-1, shape[-1]).astype(jnp.float32)
    rows, cols = a2.shape
    rp, cp = round_up(rows, SUBLANE), round_up(cols, LANE)
    pad = lambda x: jnp.zeros((rp, cp), jnp.float32).at[:rows, :cols].set(x)
    codes2 = jnp.zeros((rp, cp), jnp.int32).at[:rows, :cols].set(
        jnp.broadcast_to(codes.astype(jnp.int32), (rows, cols)))
    out = pl.pallas_call(
        _build_step_kernel(ops),
        out_shape=jax.ShapeDtypeStruct((rp, cp), jnp.float32),
        interpret=interpret,
    )(codes2, pad(a2), pad(b2), pad(c2))
    return out[:rows, :cols].reshape(shape)
