"""The life of a cache member that is a STATE addressed by lane (the
family contract in models/__init__.py): what every family with such a
member does around its own recurrence, written once.

A member is [layers of the kind, lanes, ...].  No program clears a lane
and the engine never touches one, so the family's programs keep the
state's life with these four:

    rows_start   a prefill row starts from its lane's entry, or from
                 ZEROS where its first position is 0 (whatever the lane
                 held); chunk n + 1 of a prompt thus starts from what
                 chunk n left
    rows_target  the lane a prefill row writes back to; a row of no
                 tokens (a bucket's filler) gets a lane outside the
                 member, and its write is dropped
    rows_put     the write itself
    lanes_keep   a decode step's new entry for the live lanes, the old
                 one bit for bit for the idle ones

What a bucket's padding does to the state is the recurrence's own
business (a padded token must be a no-op of the rule), as is the replay
after a preemption (it starts at position 0, so `rows_start` zeroes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _over(mask: jax.Array, like: jax.Array) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


def rows_start(member: jax.Array, pli: int, lanes: jax.Array,
               fresh: jax.Array) -> jax.Array:
    """member[pli, lanes] with zeros for the rows that are `fresh`."""
    got = member[pli, lanes]
    return jnp.where(_over(fresh, got), 0, got)


def rows_target(lanes: jax.Array, true_lens: jax.Array,
                n_lanes: int) -> jax.Array:
    return jnp.where(true_lens > 0, lanes, n_lanes)


def rows_put(member: jax.Array, pli: int, target: jax.Array,
             value: jax.Array) -> jax.Array:
    return member.at[pli, target].set(value.astype(member.dtype),
                                      mode="drop")


def lanes_keep(live: jax.Array, new: jax.Array, old: jax.Array
               ) -> jax.Array:
    return jnp.where(_over(live, new), new, old)
