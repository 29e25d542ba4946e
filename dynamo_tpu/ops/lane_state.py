"""The life of a cache member that is a STATE addressed by lane (the
family contract in models/__init__.py): what every family with such a
member does around its own recurrence, written once.

A member is [layers of the kind, lanes, ...].  No program clears a lane
and the engine never touches one, so the family's programs keep the
state's life with these five:

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
    lanes_step   a decode step of the family's recurrence over one
                 layer of the member: the live lanes' entries read and
                 written, the idle ones bit for bit what they were.
                 Where `resolve_state_impl` says so the member goes
                 WHOLE into ops/pallas_lane_state.py's kernel, which
                 moves the busy lanes' entries once in and once out, in
                 place (`lanes_plan` compacts the busy lanes, once a
                 burst); elsewhere the jnp step runs over every lane of
                 `member[pli]` and a select keeps the idle ones

The small members (a convolution's tail: KB a lane) take `lanes_keep`;
the float32 state (MB a lane and layer) takes `lanes_step`.

What a bucket's padding does to the state is the recurrence's own
business (a padded token must be a no-op of the rule), as is the replay
after a preemption (it starts at position 0, so `rows_start` zeroes).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .paged_attention import PALLAS_IMPLS
from .pallas_lane_state import LanePlan


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


def resolve_state_impl(impl: str, platform: str, dk: int, dv: int,
                       state_dtype) -> str:
    """What the family's `attn_impl` means for the decode step of a
    state member whose entry is [heads, dk, dv] a lane:
    `resolve_decode_impl`'s twin, with the STATE's conditions (the
    attention read's are about the paged pool's blocks).  -> "pallas" |
    "pallas_interpret" | "jnp".

    The kernel runs where Mosaic can tile a head's [dk, dv] entry whole:
    a float32 member, dk a multiple of 8 sublanes and dv of 128 lanes.
    "auto" takes it on a TPU.  "pallas" is what the engine has made of
    "auto" by the time a program is traced (it names the impl that runs
    in the MDC) or what a caller asked for: the kernel under the same
    conditions, whatever the backend (a compile for a described chip).
    "pallas_interpret" is the kernel under the interpreter, which tiles
    anything (CPU tests).  "jnp" (and "jnp_bf16", the GQA read's other
    jnp form) keeps the jnp step and with it the parent's program: the
    A/B on the chip.  On `nemotron-twotower.chat` (64 lanes, 13.7 busy)
    the jnp step moves 4.8 GB of state a decode step and the kernel
    0.7 GB (PERF.md section 6, PR 41)."""
    if jnp.dtype(state_dtype) != jnp.dtype(jnp.float32):
        return "jnp"
    if impl == "pallas_interpret":
        return impl
    if (impl == "pallas" or (impl == "auto" and platform == "tpu")) \
            and dk % 8 == 0 and dv % 128 == 0:
        return "pallas"
    return "jnp"


def lanes_plan(valid: jax.Array, impl: str) -> LanePlan:
    """The busy lanes of a burst: `valid` and, where `impl`
    (`resolve_state_impl`'s answer) is the kernel, the busy lanes
    compacted for its scalar prefetch: busy lanes first in their order,
    the tail repeating the last busy one (lane 0 where none is busy),
    and their number.  The jnp step needs `valid` alone.  `valid` is
    constant over a burst's steps: decode_multi makes the plan once,
    outside its scan."""
    if impl not in PALLAS_IMPLS:
        return LanePlan(valid, None, None)
    n = jnp.sum(valid, dtype=jnp.int32)
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    last = jnp.maximum(n - 1, 0)
    return LanePlan(valid, order[jnp.minimum(jnp.arange(valid.shape[0]),
                                             last)], n.reshape(1))


def lanes_step(member: jax.Array, pli: int, plan: LanePlan,
               jnp_step: Callable, kernel_step: Callable, impl: str):
    """One decode step of a recurrence over `member[pli]` -> (the read
    [lanes, ...] float32, member).  `impl` is `resolve_state_impl`'s
    answer and `plan` `lanes_plan`'s for it.  `kernel_step(member, pli,
    plan, interpret=...)` is one of ops/pallas_lane_state.py's;
    `jnp_step(state [lanes, ...] float32, valid=...)` -> (read, new
    state) is the family's jnp recurrence, which keeps a lane that is
    not valid itself."""
    if impl in PALLAS_IMPLS:
        return kernel_step(member, pli, plan,
                           interpret=impl == "pallas_interpret")
    read, new = jnp_step(member[pli].astype(jnp.float32), valid=plan.valid)
    return read, member.at[pli].set(new.astype(member.dtype))


def resolve_chunk_impl(impl: str, platform: str, tokens: int, chunk: int,
                       dk: int, dv: int, state_dtype, *, unit: int,
                       sub: int) -> str:
    """`resolve_state_impl`'s twin for the PREFILL half: what the
    family's `attn_impl` means for the chunked form of its recurrence
    over a row of `tokens` tokens (a program's bucket) in chunks of
    `chunk`, between `rows_start` and `rows_put`.  `dk` and `dv` are the
    widths of a head's planes ([T, dk] and [T, dv]), `unit` the tokens
    the kernel's body works on at a time (two of the delta rule's
    chunks), `sub` a sub-chunk.  -> "pallas" | "pallas_interpret" |
    "jnp".

    ops/pallas_chunk_state.py's kernel (ONE call a layer over every row,
    a chunk's operands and the carried state in VMEM) runs where a row
    is whole chunks (at least one) of a float32 state, and where Mosaic
    tiles what the body makes: planes of whole 128-lane tiles, a unit of
    whole tiles (its [unit, unit] masks and transposes), and a sub-chunk
    whose sum is a tree (a power of two).  "auto" takes it on a TPU,
    "pallas" whatever the backend (what the engine has made of "auto" by
    the time a program is traced; a compile for a described chip),
    "pallas_interpret" runs it under the interpreter, which tiles
    anything (CPU tests).  The family's jnp form a row (`jax.vmap`)
    stays for everything else: the CPU, a bucket under one chunk (Ling's
    32-token bucket), an explicit "jnp" (the parent's program: the A/B
    on the chip).  On a v5e a 2048-token row of 32 heads x 128 x 128
    takes 3.54 ms in the jnp form and 2.4 in the kernel; Ling's
    2048-token program goes 94.97 -> 78.33 ms, its 64- to 1024-token
    programs gain 0 to 11 % (PERF.md section 6, PR 45)."""
    if jnp.dtype(state_dtype) != jnp.dtype(jnp.float32) \
            or tokens < chunk or tokens % chunk or sub & (sub - 1):
        return "jnp"
    if impl == "pallas_interpret":
        return impl
    if (impl == "pallas" or (impl == "auto" and platform == "tpu")) \
            and dk % 128 == 0 and dv % 128 == 0 and unit % 128 == 0:
        return "pallas"
    return "jnp"
