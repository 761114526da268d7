"""Pipeline parallelism: GPipe-style microbatch pipelining over the mesh
"stage" axis (net-new; the reference's only PP is forwarding
`pipeline_parallel_size` to vLLM — SURVEY §2.7).

TPU-first design: one `shard_map` program; stage s holds slice s of the
stacked stage parameters, every step all stages compute simultaneously on
their activation buffer, and `ppermute` rotates activations one stage
forward over ICI. The schedule is a `lax.scan` over M + S - 1 ticks (fill +
drain), so the whole pipeline is a single compiled XLA program — no
per-microbatch host involvement."""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Apply S stages as a pipeline over M microbatches.

    stage_fn(params_for_one_stage, x) -> y with y.shape == x.shape;
    stage_params: pytree whose leaves have a leading stage axis of size S
    (sharded over `axis`); microbatches: [M, mb, ...]. Returns [M, mb, ...]
    = stage_{S-1}(...stage_0(x)...), replicated."""
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    def per_device(params, xs):
        # params leaves: [1, ...] (this device's stage); xs: [M, mb, ...].
        p = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def body(buf, t):
            y = stage_fn(p, buf)
            from_prev = jax.lax.ppermute(y, axis, perm)
            nxt = jnp.take(xs, jnp.clip(t + 1, 0, M - 1), axis=0)
            new_buf = jnp.where(idx == 0, nxt, from_prev)
            return new_buf, y

        _, ys = jax.lax.scan(body, xs[0], jnp.arange(M + S - 1))
        # Stage S-1 produced microbatch m's output at tick m + S - 1.
        outs = ys[S - 1:S - 1 + M]
        is_last = (idx == S - 1).astype(outs.dtype)
        return jax.lax.psum(outs * is_last, axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)
