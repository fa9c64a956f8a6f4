"""GPipe-style pipeline parallelism over the ``pod`` axis (multi-pod mesh).

The default multi-pod configuration treats ``pod`` as extra data parallelism;
this module provides the alternative: each pod owns half the layer stack and
microbatches stream through a collective-permute ring.  A 1F1B-ish schedule
is emulated with a scan over (microbatches + stages - 1) ticks; bubbles =
(stages-1)/(ticks) as usual.  Exercised by tests and by
``launch/dryrun.py --pipeline`` for one config to prove the lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(fn_stage, params_stages, x_micro, mesh, *, stages: int):
    """Run `x_micro` [M, ...] microbatches through `stages` pipeline stages.

    fn_stage(stage_params, x) -> x.  params_stages has a leading [stages] dim
    sharded over "pod"; each pod applies its local stage and permutes
    activations to the next pod between ticks.
    """
    M = x_micro.shape[0]
    ticks = M + stages - 1

    def body(h, params, x_m):
        """One shard (pod) tick: receive, compute local stage, hand off."""
        return fn_stage(params, h)

    def sharded(x_micro, params_stages):
        ax = jax.lax.axis_index("pod")
        out = jnp.zeros_like(x_micro)
        state = jnp.zeros_like(x_micro[0])

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (if in range) — other stages use
            # what arrived over the ring last tick
            m_in = jnp.clip(t, 0, M - 1)
            inject = jnp.where(ax == 0,
                               x_micro[m_in],
                               state)
            y = fn_stage(jax.tree.map(lambda p: p[0], params_stages), inject)
            # last stage emits microbatch t-(stages-1)
            m_out = jnp.clip(t - (stages - 1), 0, M - 1)
            emit = (ax == stages - 1) & (t >= stages - 1)
            out = jnp.where(emit, out.at[m_out].set(y), out)
            # ring hand-off to the next stage
            y_next = jax.lax.ppermute(
                y, "pod", [(i, (i + 1) % stages) for i in range(stages)])
            return (y_next, out), None

        (_, out), _ = jax.lax.scan(tick, (state, out), jnp.arange(ticks))
        # the final outputs live on the last pod; share them
        out = jax.lax.psum(out, "pod") / 1.0  # all pods but last contribute 0
        return out

    return jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(), P("pod")),
        out_specs=P(),
        check_vma=False,
    )(x_micro, params_stages)
