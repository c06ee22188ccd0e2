"""Pipeline parallelism: GPipe-style microbatched stage execution.

An alternative use of the inter-pod axis (DESIGN.md §5): instead of DP,
the layer stack splits into ``n_stages`` contiguous stages; microbatches
stream through with ``jax.lax.ppermute`` hops between stage neighbours
inside ``shard_map``.  Fill+drain bubble = (n_stages-1)/(n_micro+n_stages-1);
the schedule is the classic GPipe one (all-forward, all-backward via jax
autodiff through the permutes).

Works on any 1-D mesh axis; exercised at smoke scale in
tests/test_distributed.py::test_pipeline_parallel_matches_serial.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis="pp", n_micro=None):
    """Run ``x`` through ``n_stages`` pipeline stages.

    Args:
      stage_fn: ``(params_for_stage, h) -> h`` — one stage's computation.
      stage_params: pytree with leading axis ``n_stages`` (stage-sharded).
      x: global batch ``(B, ...)``; B must divide into microbatches.
      mesh: mesh containing ``axis`` of size n_stages.
      n_micro: number of microbatches (default: n_stages).

    Returns the pipeline output ``(B, ...)`` (resident on the last stage,
    replicated back through the collective at the end).
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    micro = x.reshape(n_micro, B // n_micro, *x.shape[1:])

    def run(params, micro):
        # params: this stage's slice (leading axis removed by shard_map)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_steps = n_micro + n_stages - 1
        # mark carries as axis-varying (they depend on the stage index)
        buf = jax.lax.pvary(jnp.zeros_like(micro[0]), (axis,))
        outs = jax.lax.pvary(jnp.zeros_like(micro), (axis,))
        micro = jax.lax.pvary(micro, (axis,))

        def step(i, carry):
            buf, outs = carry
            # stage 0 injects microbatch i (when in range)
            inject = jnp.where(i < n_micro, i, 0)
            buf = jnp.where(stage == 0,
                            jnp.where(i < n_micro, micro[inject], buf), buf)
            buf = stage_fn(params, buf)
            # emit from the last stage: microbatch index i - (n_stages - 1)
            out_ix = i - (n_stages - 1)
            valid = (out_ix >= 0) & (out_ix < n_micro)
            outs = jnp.where(
                (stage == n_stages - 1) & valid,
                outs.at[jnp.clip(out_ix, 0, n_micro - 1)].set(buf), outs)
            # shift activations to the next stage
            buf = jax.lax.ppermute(
                buf, axis, [(j, (j + 1) % n_stages) for j in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_steps, step, (buf, outs))
        # bring the result (held by the last stage) to every stage
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    shard = jax.shard_map(run, mesh=mesh, in_specs=(PS(axis), PS()),
                          out_specs=PS())
    out = shard(stage_params, micro)
    return out.reshape(B, *x.shape[1:])
