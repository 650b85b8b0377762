"""Jaxpr-audit fixture: a sharded step that spends TWO collectives where
the serving budget allows one (the split-stats shape PR-4 replaced with
the packed single-all_gather merge).

Works on a 1-device mesh: shard_map still lowers real stablehlo
collective ops, so the audit counts them without multi-device state.
"""

import jax
from jax.sharding import PartitionSpec as P


def build_two_collective_step(mesh, axis="x"):
    def step(x):
        s = jax.lax.psum(x, axis)     # collective 1
        m = jax.lax.pmax(x, axis)     # collective 2
        return s + m

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=P(axis), out_specs=P()))


def build_one_collective_step(mesh, axis="x"):
    def step(x):
        return jax.lax.psum(x, axis)  # exactly one collective

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=P(axis), out_specs=P()))
