"""Jitted public wrappers around the Pallas kernels (padding + dispatch).

``interpret`` left unset resolves through :func:`default_interpret`: the
kernels compile for the TPU, run in the Pallas interpreter on the ``cpu``
platform (the test suite's path), and refuse any other platform.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..graphir.graph import Graph
from .flash_attention import flash_attention
from .gemm import gemm_pe
from .mamba_scan import mamba_scan
from .pe_fused import kernel_from_config, make_pe_kernel


def default_interpret() -> bool:
    """Interpret mode for a Pallas call whose caller left it unset.

    The one place that decides it: False on ``tpu`` (compile the kernel),
    True on ``cpu`` (the interpreter, for tests), and an error on any other
    platform, so a kernel never silently runs interpreted on a host that
    was meant to compile it.  Callers that pass ``interpret=`` explicitly
    keep their choice.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU only and interpret on CPU; "
        f"platform {platform!r} has neither path")


def fused_pe_apply(pattern: Graph, *inputs, block=(256, 256),
                   interpret: Optional[bool] = None):
    """Apply a mined/merged PE pattern elementwise over the inputs."""
    interp = default_interpret() if interpret is None else interpret
    fn = make_pe_kernel(pattern, block=block, interpret=interp)
    return fn(*inputs)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=0.0,
              bq=128, bk=128, interpret: Optional[bool] = None):
    """Padded flash attention; q (B,Hq,S,D), k/v (B,Hkv,S,D)."""
    interp = default_interpret() if interpret is None else interpret
    b, hq, s, d = q.shape
    blk = max(min(bq, s), min(bk, s))
    pad = (-s) % blk
    if pad:
        zq = jnp.zeros((b, hq, pad, d), q.dtype)
        zk = jnp.zeros((b, k.shape[1], pad, d), k.dtype)
        q = jnp.concatenate([q, zq], axis=2)
        k = jnp.concatenate([k, zk], axis=2)
        v = jnp.concatenate([v, zk.astype(v.dtype)], axis=2)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, scale=scale, bq=bq, bk=bk,
                          interpret=interp)
    return out[:, :, :s] if pad else out


def selective_scan(a, bx, c, *, bs=128, bd=128,
                   interpret: Optional[bool] = None):
    """Padded chunked mamba scan; a/bx (B,S,D,N), c (B,S,N) -> y (B,S,D)."""
    interp = default_interpret() if interpret is None else interpret
    b, s, d, n = a.shape
    pad_s = (-s) % min(bs, max(s, 1))
    pad_d = (-d) % min(bd, max(d, 1))
    if pad_s or pad_d:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d), (0, 0)),
                    constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad_s), (0, pad_d), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad_s), (0, 0)))
    y = mamba_scan(a, bx, c, bs=bs, bd=bd, interpret=interp)
    return y[:, :s, :d]


def matmul_fused(x, w, *extras, epilogue=None, extra_kinds=(),
                 bm=128, bn=128, bk=128, out_dtype=None,
                 interpret: Optional[bool] = None):
    """Padded MXU matmul with fused PE epilogue."""
    interp = default_interpret() if interpret is None else interpret
    m, k = x.shape
    _, n = w.shape
    pm, pk, pn = (-m) % min(bm, m), (-k) % min(bk, k), (-n) % min(bn, n)
    if pm or pk or pn:
        x = jnp.pad(x, ((0, pm), (0, pk)))
        w = jnp.pad(w, ((0, pk), (0, pn)))
        extras = tuple(
            jnp.pad(e, ((0, pn),)) if e.ndim == 1
            else jnp.pad(e, ((0, pm), (0, pn))) for e in extras)
    out = gemm_pe(x, w, *extras, epilogue=epilogue, extra_kinds=extra_kinds,
                  bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                  interpret=interp)
    return out[:m, :n] if (pm or pn) else out
