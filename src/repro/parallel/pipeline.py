"""GPipe-style pipeline parallelism over a mesh axis via shard_map+ppermute.

Each shard of the ``stage`` axis owns one stage's parameters; microbatches
stream through with the classic (M + S - 1)-step schedule. Activations move
stage i -> i+1 with ``lax.ppermute`` — on the optical fabric this is a ring
traffic matrix, i.e. exactly the pattern Vermilion serves at full
throughput (paper Fig 3; ``core.collectives.pipeline_traffic``).

Not used by the 40-cell dry-run grid (DP-over-pods is the deployment
default); tested on a fake 4-device mesh (tests/test_pipeline.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, stage_params, x_microbatches, mesh: Mesh,
                   axis: str = "stage"):
    """Run ``y = stage_S-1(...stage_0(x))`` for each microbatch.

    stage_params: pytree with leading stage axis (S, ...), sharded over
    ``axis``.  x_microbatches: (M, mb, d) replicated.  Returns (M, mb, d).
    """
    s = mesh.devices.shape[list(mesh.axis_names).index(axis)]
    m = x_microbatches.shape[0]

    def body(params, xs):
        # params: (1, ...) local stage slice; xs: (M, mb, d) replicated
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)          # current activation
        outs = jnp.zeros((m,) + mb_shape, xs.dtype)
        fwd = [(i, (i + 1) % s) for i in range(s)]

        def step(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (when in range)
            inject = jnp.where(t < m, t, 0)
            buf = jnp.where(jax.lax.axis_index(axis) == 0,
                            jnp.where(t < m, xs[inject], buf), buf)
            y = stage_fn(params, buf)
            # last stage emits microbatch t - (S - 1)
            emit = t - (s - 1)
            take = jnp.logical_and(emit >= 0, emit < m)
            outs = jax.lax.cond(
                take,
                lambda o: o.at[jnp.maximum(emit, 0)].set(y),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, fwd)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, m + s - 1, step, (buf, outs))
        # only the last stage's outs are real; broadcast via masked psum
        mask = (jax.lax.axis_index(axis) == s - 1).astype(outs.dtype)
        last = jax.lax.psum(outs * mask, axis)
        return last[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    out = f(stage_params, x_microbatches)
    return out[0]
