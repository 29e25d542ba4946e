"""The block scores of a prompt-sized input as one Pallas kernel
(ops/block_sparse_attention.py `block_scores`' mathematics: the module
docstring there): a (KV group, query tile) grid step holds the group's
compressed keys and the tile's scores in VMEM and writes P, the score of
every block, alone.  The jnp form writes the [queries, heads, slots]
float32 scores to HBM and reads them back for the mask, the max, the
exponential, the sum, the division, the sum over a group's heads and
the pool: at 2048 queries x 32 heads x 3128 slots that is 820 MB a
pass, 12.8 MB of P in the end.

Layout.  The pool over a block's `per` windows and the neighbour's first
`pad` is a max over ADJACENT slots; in lanes that is a shuffle.  The
keys are laid so that window p of block j is lane j of slab p
([nkv, per, tiles, hd, 128 blocks]; the gather that reads the pages
lays them so): a slab's scores are one matmul, the pool is an
elementwise max of `per` slabs and the neighbour term one lane roll
(the last lane takes the next tile's first).

A grid step, one head of the group at a time (16 heads x [tq, slots]
float32 at once would be 26 MB): the scores of the VISIBLE key tiles
into scratch with the row max, the exponentials in place with the row
sum, the normalised row added to the group's sum A; after the last
head the pool over A.  Key tiles past the tile's frontier (the last
visible slot of its last query that is read, a scalar prefetched a
query tile) are not visited, and a tile none of whose queries is read
(all at or under `dense_len`, or padding) runs nothing and writes
zeros.  The group's keys are one block whose index moves with the group
alone: they are copied in once a group, not once a step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import NEG_INF

# blocks a key tile: the lanes of a vector register
_LANES = 128
# the fewest queries the kernel is worth a call for (a decode step's rows
# score [8, 32, 3128] = 3 MB through the jnp form: nothing to win)
TILE_QUERIES = 128
# queries a grid step: a head's matmul has this many rows a key tile it
# loads.  2048 queries over 3128 slots at a context of 40 k on a v5e:
# 2.13 ms at 64, 1.48 at 128, 1.15 at 256 (my chip runs, PR 56); code and
# scratch grow with it
_STEP_QUERIES = 256
# what a kernel may ask of a v5e's 128 MiB beside its neighbours
_VMEM_BUDGET = 48 * 1024 * 1024


def _tiles(n_blocks: int) -> int:
    return -(-n_blocks // _LANES)


def vmem_bytes(tq: int, group: int, hd: int, per: int, n_blocks: int,
               itemsize: int) -> int:
    """What a grid step holds: the two scratch planes, and two buffers
    each of the group's keys, the query tile, the limits (a lane-padded
    column) and the output tile."""
    nt = _tiles(n_blocks)
    plane = tq * _LANES * 4
    return (per * nt * plane + per * (nt + 1) * plane
            + 2 * (per * nt * hd * _LANES * itemsize
                   + group * tq * hd * itemsize + plane + nt * plane))


def choice_tile(rows: int, group: int, hd: int, per: int, n_blocks: int,
                itemsize: int) -> int:
    """The queries a grid step for an input of `rows` queries: a step's
    worth or the input's own tiles, halved while a table is so wide that
    the step's buffers pass the budget."""
    tq = min(_STEP_QUERIES, -(-rows // TILE_QUERIES) * TILE_QUERIES)
    while tq > 8 and vmem_bytes(tq, group, hd, per, n_blocks,
                                itemsize) > _VMEM_BUDGET:
        tq //= 2
    return tq


def frontier(t, read, sizes, tq: int, n_tiles: int):
    """t [T] positions, read [T] bool, T whole tiles of `tq` queries ->
    (lim [T]: a query's last visible slot, under `slot0` where it sees
    no whole window; visit [T / tq]: the key tiles a query tile visits,
    up to the last visible slot of its last read query, 0 where it has
    none)."""
    lim = sizes.slot0 + (t - (sizes.kernel - 1)) // sizes.stride
    hi = jnp.max(jnp.where(read, lim, -1).reshape(-1, tq), axis=1)
    per = sizes.block // sizes.stride
    visit = jnp.where(hi >= sizes.slot0,
                      jnp.minimum(hi // per // _LANES + 1, n_tiles), 0)
    return lim, visit.astype(jnp.int32)


def block_scores_pallas(q, ck_seq, t, read, sizes, *, tq: int = 0,
                        interpret: bool = False):
    """`block_scores` for one sequence: q [T, nh, hd] at positions t
    [T], ck_seq [NC, nkv, hd] -> P [T, nkv, NB] float32.  `read` [T]
    bool marks the queries whose scores anyone reads: a query tile with
    none is not scored (zeros), and no tile visits keys past the last
    visible slot of its last read query.  Rows that are not read hold
    whatever the visited tiles gave them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    NC, nkv, _ = ck_seq.shape
    G = nh // nkv
    per = sizes.block // sizes.stride
    pad = min(sizes.kernel // sizes.stride - 1, per)
    slot0, L = sizes.slot0, _LANES
    NB = NC // per
    nt = _tiles(NB)
    tq = tq or choice_tile(T, G, hd, per, NB, q.dtype.itemsize)
    nq = -(-T // tq)
    pad_q = nq * tq - T

    # slot per j + p -> [nkv, p, tile, hd, lane j]
    keys = jnp.pad(ck_seq[:NB * per].reshape(NB, per, nkv, hd),
                   ((0, nt * L - NB), (0, 0), (0, 0), (0, 0)))
    keys = keys.reshape(nt, L, per, nkv, hd).transpose(3, 2, 0, 4, 1)
    qg = jnp.pad(q.reshape(T, nkv, G, hd).transpose(1, 2, 0, 3),
                 ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    lim, visit = frontier(jnp.pad(t.astype(jnp.int32), (0, pad_q)),
                          jnp.pad(read, (0, pad_q)), sizes, tq, nt)
    root = float(hd) ** 0.5

    def scores_of(qh, k):
        if interpret:       # the CPU has no bf16 x bf16 -> float32 dot
            qh, k = qh.astype(jnp.float32), k.astype(jnp.float32)
        return jnp.dot(qh, k, preferred_element_type=jnp.float32) / root

    def kernel(visit_ref, q_ref, k_ref, lim_ref, o_ref, s_sc, a_sc):
        nv = visit_ref[pl.program_id(1)]

        @pl.when(nv == 0)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        @pl.when(nv > 0)
        def _():
            row_lim = lim_ref[...]                               # [tq, 1]
            # the slot of window 0 of each lane's block in tile 0
            lane = per * jax.lax.broadcasted_iota(jnp.int32, (tq, L), 1)
            a_sc[...] = jnp.zeros(a_sc.shape, jnp.float32)

            def head(h, carry):
                qh = q_ref[0, h]                                 # [tq, hd]

                def score(jt, m):
                    for p in range(per):
                        f = lane + (per * L * jt + p)
                        s = jnp.where((f >= slot0) & (f <= row_lim),
                                      scores_of(qh, k_ref[0, p, jt]),
                                      NEG_INF)
                        s_sc[p, jt] = s
                        m = jnp.maximum(m, s)
                    return m

                m = jax.lax.fori_loop(
                    0, nv, score, jnp.full((tq, L), NEG_INF, jnp.float32)
                ).max(axis=1, keepdims=True)

                def exps(jt, l):
                    for p in range(per):
                        # a slot that is out is NEG_INF under a finite
                        # max: exactly 0
                        e = jnp.exp(s_sc[p, jt] - m)
                        s_sc[p, jt] = e
                        l = l + e
                    return l

                l = jax.lax.fori_loop(
                    0, nv, exps, jnp.zeros((tq, L), jnp.float32)
                ).sum(axis=1, keepdims=True)
                # a row that sees nothing has m = NEG_INF and e = 1
                # everywhere: its share is 0, as the jnp form's
                r = jnp.where(row_lim >= slot0,
                              1.0 / jnp.maximum(l, 1e-30), 0.0)

                def add(jt, c):
                    for p in range(per):
                        a_sc[p, jt] = a_sc[p, jt] + s_sc[p, jt] * r
                    return c

                jax.lax.fori_loop(0, nv, add, 0)
                return carry

            jax.lax.fori_loop(0, G, head, 0)
            last = lane == per * (L - 1)

            def pool(jt, c):
                best = a_sc[0, jt]
                for p in range(1, per):
                    best = jnp.maximum(best, a_sc[p, jt])
                for p in range(pad):
                    # block j + 1's first windows touch block j too:
                    # lane j takes lane j + 1, the last the next tile's
                    nxt = jnp.where(
                        last, pltpu.roll(a_sc[p, jt + 1], L - 1, 1),
                        pltpu.roll(a_sc[p, jt], L - 1, 1))
                    best = jnp.maximum(best, nxt)
                o_ref[0, jt] = best
                return c

            jax.lax.fori_loop(0, nt, pool, 0)

    need = vmem_bytes(tq, G, hd, per, NB, q.dtype.itemsize)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nkv, nq),
            in_specs=[
                pl.BlockSpec((1, G, tq, hd), lambda g, i, v: (g, 0, i, 0)),
                pl.BlockSpec((1, per, nt, hd, L),
                             lambda g, i, v: (g, 0, 0, 0, 0)),
                pl.BlockSpec((tq, 1), lambda g, i, v: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, nt, tq, L),
                                   lambda g, i, v: (g, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((per, nt, tq, L), jnp.float32),
                            pltpu.VMEM((per, nt + 1, tq, L), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nkv, nt, nq * tq, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the step's buffers and as much again for what the body
            # keeps between them
            vmem_limit_bytes=2 * need),
        interpret=interpret,
    )(visit, qg, keys, lim[:, None])
    return out.transpose(2, 0, 1, 3).reshape(nq * tq, nkv, nt * L)[:T, :, :NB]
