"""GPipe-style pipeline-parallel forward over a mesh axis.

``gpipe_forward(stage_fn, mesh, axis_name)`` partitions a stack of stage
params over ``axis_name`` and runs the classic rotation schedule under
``shard_map``: at step ``t`` stage ``s`` processes microbatch ``t - s``,
activations hop one stage per step via ``ppermute``, and the bubble is the
usual ``S - 1`` steps at each end. Every device runs the same program; only
its stage slice of the params is resident (the point of pipeline parallelism
— per-device param memory is ``1/S``).

The forward is numerically identical to applying the stages sequentially to
each microbatch, which is what the substrate test asserts.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(stage_fn: Callable, mesh: Mesh, axis_name: str) -> Callable:
    """Build the pipelined forward.

    ``stage_fn(stage_params, x) -> y`` is one stage (y.shape == x.shape —
    the inter-stage activation must be shape-stable to ride the rotation).
    The returned callable takes ``(params, xs)`` where every params leaf has
    a leading stage axis of size ``mesh.shape[axis_name]`` and
    ``xs: (M, microbatch, ...)`` stacks the microbatches; it returns the
    ``(M, microbatch, ...)`` outputs after all stages.
    """
    n_stages = mesh.shape[axis_name]

    def run(params, xs):
        M = xs.shape[0]

        def local(params_l, xs_l):
            # params_l leaves: (1, ...) — this device's stage; xs_l replicated
            p = jax.tree.map(lambda w: w[0], params_l)
            s = jax.lax.axis_index(axis_name)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            zero = jnp.zeros_like(xs_l[0])

            def step(t, carry):
                state, out = carry
                # stage 0 ingests microbatch t; drain steps (t >= M) re-feed
                # the clamped last microbatch, whose stale results never
                # reach the live output-write window below
                feed = jax.lax.dynamic_index_in_dim(
                    xs_l, jnp.minimum(t, M - 1), axis=0, keepdims=False)
                cur = jnp.where(s == 0, feed, state)
                y = stage_fn(p, cur)
                # the last stage writes microbatch t-(S-1) when it is live;
                # touch only that row (a masked whole-buffer update would
                # cost O(M) HBM traffic per rotation step, O(M^2) overall)
                oidx = t - (n_stages - 1)
                live = (s == n_stages - 1) & (oidx >= 0) & (oidx < M)
                idx = jnp.clip(oidx, 0, M - 1)
                row = jax.lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(live, y, row), idx, 0)
                state = jax.lax.ppermute(y, axis_name, perm)
                return state, out

            _, out = jax.lax.fori_loop(
                0, M + n_stages - 1, step, (zero, jnp.zeros_like(xs_l)))
            # only the last stage holds real outputs; psum replicates them
            return jax.lax.psum(out, axis_name)

        pspecs = jax.tree.map(
            lambda w: P(axis_name, *([None] * (w.ndim - 1))), params)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(pspecs, P(*([None] * xs.ndim))),
            out_specs=P(*([None] * xs.ndim)),
            check_vma=False,
        )(params, xs)

    return run
