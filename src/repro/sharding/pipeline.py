"""GPipe-style pipeline parallelism over the ``pod`` axis (optional).

The default multi-pod config treats ``pod`` as an extra DP axis; this module
provides the alternative: layers are partitioned into ``n_stages``
contiguous stages (stage s owns layers [s*L/S, (s+1)*L/S)), microbatches
stream through the stages with ``lax.ppermute`` handing activations across
pods, in the classic GPipe schedule (bubble fraction (S-1)/(M+S-1)).

Implemented with ``shard_map`` so it composes with the in-stage TP sharding;
``jax.grad`` differentiates straight through (ppermute is differentiable),
giving 1F1B-equivalent memory behavior under remat.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          mesh: Mesh, axis: str = "pod"):
    """Build pipeline_apply(stage_params, x_micro) -> y_micro.

    stage_params: pytree whose leaves have a leading ``n_stages`` dim,
    sharded over `axis` (each pod holds its stage's slice).
    x_micro: (n_micro, mb, ...) microbatched inputs (replicated over `axis`).
    Returns (n_micro, mb, ...) outputs of the LAST stage (replicated).
    """
    n_stages = mesh.shape[axis]

    def _inner(params_local, x):
        # params_local: leaves (1, ...) — this pod's stage
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage_id = lax.axis_index(axis)
        n_micro = x.shape[0]
        mb_shape = x.shape[1:]
        n_ticks = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf_in, outputs = carry
            # stage 0 injects microbatch t (when valid)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_t = x[inject]
            my_in = jnp.where(stage_id == 0, x_t, buf_in)
            y = stage_fn(params_here, my_in)
            # last stage records its result at slot t - (n_stages - 1)
            slot = t - (n_stages - 1)
            valid = (slot >= 0) & (stage_id == n_stages - 1)
            write = jnp.where(slot >= 0, slot, 0)
            outputs = lax.cond(
                valid,
                lambda o: o.at[write].set(y),
                lambda o: o,
                outputs)
            nxt = lax.ppermute(y, axis, fwd_perm)
            return (nxt, outputs), None

        buf0 = jnp.zeros(mb_shape, x.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
        (_, outputs), _ = lax.scan(tick, (buf0, outs0),
                                   jnp.arange(n_ticks))
        # broadcast the last stage's outputs to every pod (masked psum —
        # ppermute can't fan out one source to all destinations)
        outputs = jnp.where(stage_id == n_stages - 1, outputs,
                            jnp.zeros_like(outputs))
        outputs = lax.psum(outputs, axis)
        return outputs

    other_axes = [a for a in mesh.axis_names if a != axis]

    def apply(stage_params, x_micro):
        p_spec = jax.tree.map(lambda _: P(axis), stage_params)
        return shard_map(
            _inner, mesh=mesh,
            in_specs=(p_spec, P()),
            out_specs=P(),
            check_vma=False,
        )(stage_params, x_micro)

    return apply


def stage_split(tree: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def split(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])
    return jax.tree.map(split, tree)
