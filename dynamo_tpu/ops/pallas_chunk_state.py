"""Pallas TPU kernel for the PREFILL of a cache member that is a STATE
addressed by lane (ops/lane_state.py): the chunked form of the family's
recurrence over a row of tokens, one shell and one body a recurrence.
The twin of ops/pallas_lane_state.py, which is the decode step.  The
delta rule's body is here (models/ling.py); Mamba-2's is not yet
(`ssd_chunked` of ops/ssm.py stays models/nemotron_h.py's only form:
PERF.md section 7 says what a body for it measured and what it needs).

The jnp form (`kda_chunked` of ops/delta_attention.py) hands every
intermediate of a chunk through HBM in float32 (`k_seen` [N, H, 4, C,
dk] is 134 MB a layer and 2048 tokens, A, B, the solve's operands and
the scan's six inputs beside it) and its matmuls are 16 x 64 x 128 tiles
one einsum at a time.  Here HBM sees the projections in, the reads out
and the row's final state; a grid step's operands, everything made of
them and the carried state stay in VMEM.

The shell (`_chunk_state_call`) owns everything that is not the
recurrence:

  * the grid is (rows, head blocks, chunk groups); rows and head blocks
    are parallel, the chunk groups of a row come last and in order
    ("arbitrary"), because each starts from the state the one before
    left;
  * the state of a head block lives in the OUTPUT state's block, whose
    index does not change along the chunk axis: it is resident in VMEM
    from the row's first chunk group, where it is loaded from the row's
    start state, to its last, after which the pipeline writes it to HBM
    once;
  * operands arrive in the layout the projections leave them in, [rows,
    T, heads x width] (a free reshape of [rows, T, heads, width]): the
    index map picks the chunk group's tokens and the head block's lanes,
    a head is a static lane slice of the block, and nothing is
    transposed through HBM.  The reads leave the same way;
  * a factor a token and head (beta) meets a [tokens, width] tile as a
    COLUMN [tokens, 1].  An array [.., 1] is not the way to hand one
    over (the TPU's tiled layout pads the minor axis to 128 lanes,
    PERF.md's PR 41 lesson): the heads of a block go on the minor axis,
    [rows, head blocks, T, hb], made by XLA outside (KB a row), and
    head h's column is the static lane slice [:, h:h+1];
  * `chunk_tiling` picks head block and chunks a grid step from the
    shapes alone, under `_VMEM_BUDGET`.

The body (`_kda_chunk_body`) works on 128 tokens at a time so that every
tile, mask and transpose is a whole [128, 128] float32 tile: the delta
rule's chunk of 64 goes two chunks side by side as ONE block-diagonal
[128, 128] problem, the cross terms masked away, which also fills the
MXU's rows.

The arithmetic is the configuration's: float32 throughout and every
product `Precision.HIGHEST`, the jnp form's operations (the cumulative
sums inside a sub-chunk and over the sub-chunks' totals, every exponent
a difference through the sub-chunk's middle decay, forward substitution
inside the diagonal blocks and block rows after).  What differs is the
order inside a reduction (the MXU's; a tree over a sub-chunk's lanes),
products with masked zeros where the jnp form slices, and one
re-association in the block rows (below).
tests/test_chunk_state_kernel.py holds the body to the jnp form and to
the token recurrence under the interpreter, tests/test_tpu_compile.py
compiles it inside the family's prefill program for a described v5e,
benchmarks/bench_chunk_state.py times it against the jnp form on the
chip.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_attention import F32, HI

# a grid step's operand and read blocks, double-buffered, and what the
# body makes of them
_VMEM_BUDGET = 32 << 20
_VMEM_LIMIT = 64 << 20
# (head, unit) problems a grid step, and heads of them
_STEP_PROBLEMS, _STEP_HEADS = 8, 8


class _Blocked(NamedTuple):
    """A VMEM operand (or the read): the array, its block, and the
    block's index from the step's (row, head block, chunk group)."""
    array: jax.Array | jax.ShapeDtypeStruct
    block: Tuple
    index: Callable


def chunk_tiling(tokens: int, unit: int, heads: int, token_bytes: int,
                 live_bytes: int) -> Tuple[int, int]:
    """-> (head block, units of `unit` tokens a grid step) for a row of
    `tokens` tokens (a multiple of `unit`) and `heads` heads.
    `token_bytes`: what one token of one head is in the operand and read
    blocks; `live_bytes`: what the body keeps of one unit of one head
    while it works (its operands' products).  A grid step takes up to
    `_STEP_PROBLEMS` (head, unit) problems, heads first: the problems of
    a step are independent until each head walks its own chunks, and
    their matmuls fill each other's waits.  The delta rule on a v5e,
    2048 tokens x 32 heads (my chip runs, PR 45): 2.89 ms at 1 head x 4
    units, 2.42 at 4 x 2, 2.38 at 8 x 1, 2.20 at 8 x 2; but the body is
    unrolled, and a call site of 16 problems compiles for 9.8 s where 8
    take 3.6 (six bucket programs x ten layers, cold once a
    deployment), so a step takes 8.  The head block divides the heads,
    the units divide the row, and blocks (double-buffered) and products
    stay under `_VMEM_BUDGET`."""
    n = tokens // unit
    per_problem = unit * 2 * token_bytes + live_bytes
    fits = lambda k: k * per_problem <= _VMEM_BUDGET
    hb = max(h for h in range(1, heads + 1)
             if heads % h == 0 and (h == 1 or h <= _STEP_HEADS and fits(h)))
    units = max(g for g in range(1, n + 1)
                if n % g == 0 and (g == 1 or hb * g <= _STEP_PROBLEMS
                                   and fits(hb * g)))
    return hb, units


def _chunk_state_call(
    body: Callable,
    state: jax.Array,                # [rows, H, dk, dv] float32
    blocked: Sequence[_Blocked],
    read: _Blocked,
    *,
    groups: int,                     # chunk groups a row
    head_block: int,
    flops: int,
    interpret: bool,
):
    """body(operand refs, read ref, state ref) for every (row, head
    block, chunk group); the state ref [hb, dk, dv] is the carried state:
    the body reads what the group before left and leaves its own.
    -> (read, state after the row's last token)."""
    R, H, dk, dv = state.shape
    hb, nj = head_block, H // head_block
    if nj * hb != H or state.dtype != F32:
        raise ValueError(f"{state.dtype} state of {H} heads in blocks of "
                         f"{hb}: the kernel takes a float32 state in whole "
                         "head blocks (resolve_chunk_impl)")
    n_in = len(blocked)

    spec = lambda b: pl.BlockSpec(b.block, b.index)
    state_spec = pl.BlockSpec((None, hb, dk, dv), lambda r, j, g: (r, j, 0, 0))

    def kernel(*refs):
        ins, s_in = refs[:n_in], refs[n_in]
        r_out, s_out = refs[n_in + 1:]

        @pl.when(pl.program_id(2) == 0)
        def _():
            s_out[...] = s_in[...]

        body(ins, r_out, s_out)

    moved = sum(b.array.size * b.array.dtype.itemsize for b in blocked) \
        + read.array.size * 4 + 2 * state.size * 4
    return pl.pallas_call(
        kernel,
        grid=(R, nj, groups),
        in_specs=[spec(b) for b in blocked] + [state_spec],
        out_specs=[spec(read), state_spec],
        out_shape=[read.array, jax.ShapeDtypeStruct(state.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(flops=int(flops), bytes_accessed=moved,
                                      transcendentals=0),
        interpret=interpret,
    )(*[b.array for b in blocked], state)


def _mm(a, b):
    return jnp.dot(a, b, precision=HI, preferred_element_type=F32)


def _mm_nt(a, b):
    """a [m, k] x b [n, k] -> [m, n]."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=HI,
                               preferred_element_type=F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _head_columns(x: jax.Array, nj: int) -> jax.Array:
    """[rows, T, H] -> [rows, nj, T, hb]: each head's factor a token as a
    COLUMN of its head block's plane (heads on the minor axis)."""
    R, T, H = x.shape
    return jnp.swapaxes(x.reshape(R, T, nj, H // nj), 1, 2)


def _rows_of(x: jax.Array, tokens: int) -> jax.Array:
    """[rows, T, H, w] -> [rows, tokens, H x w] float32, zero-padded."""
    R, T = x.shape[:2]
    x = x.astype(F32).reshape(R, T, -1)
    return jnp.pad(x, ((0, 0), (0, tokens - T), (0, 0))) if tokens > T else x


# ---------------------------------------------------------------------------
# the delta rule (ops/delta_attention.py kda_chunked)
# ---------------------------------------------------------------------------


def _kda_chunk_body(ins, o_ref, s_ref, *, scale: float, chunk: int, sub: int,
                    dk: int, dv: int):
    """A grid step: `hb` heads x `units` pairs of chunks.  q, k, log a
    [Tg, hb dk], v [Tg, hb dv], beta [Tg, hb] columns; o [Tg, hb dv];
    the carried state [hb, dk, dv].

    Everything that does not depend on the state is made for all of the
    step's pairs first (A, B, the inverse, the solve, q_in, k_out); the
    16 row steps of the substitution run ONCE over all their diagonal
    blocks side by side; then each head walks its chunks with the state,
    three matmuls a chunk."""
    q_ref, k_ref, v_ref, la_ref, beta_ref = ins
    hb = s_ref.shape[0]
    C, U = chunk, 2 * chunk                   # a unit: two chunks
    n = C // sub                              # sub-chunks a chunk
    units = q_ref.shape[0] // U
    rr, cc = _iota((U, U), 0), _iota((U, U), 1)
    same_chunk = rr // C == cc // C
    same_sub = rr // sub == cc // sub
    # cumulative sums inside a sub-chunk, as a product with ones
    tri_sub = (same_sub & (cc <= rr)).astype(F32)
    strict = same_chunk & (rr > cc)
    lower = same_chunk & (rr >= cc)
    below = same_chunk & (rr // sub > cc // sub)     # earlier sub-chunks
    sub_of_row = _iota((U, 1), 0) // sub             # [U, 1]: 0 .. 2n - 1
    fold_col = _iota((sub, U), 1) // sub
    mid = (sub - 1) // 2

    def spread(rows):
        """2n rows [1, w], one a sub-chunk -> [U, w]."""
        return jnp.concatenate(
            [jnp.broadcast_to(r, (sub, r.shape[-1])) for r in rows], axis=0)

    pre = []
    for h in range(hb):
        lk, lv = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        for u in range(units):
            rows = slice(u * U, (u + 1) * U)
            q, k, v = q_ref[rows, lk], k_ref[rows, lk], v_ref[rows, lv]
            beta = beta_ref[rows, h:h + 1]                     # [U, 1]
            since = _mm(tri_sub, la_ref[rows, lk])             # [U, dk]
            total = [since[(i + 1) * sub - 1:(i + 1) * sub] for i
                     in range(2 * n)]
            mids = [since[i * sub + mid:i * sub + mid + 1] for i
                    in range(2 * n)]
            before = []            # the decay up to the sub-chunk's start
            for i in range(2 * n):
                run = total[i] if i % n == 0 else run + total[i]
                before.append(run - total[i])
            G = spread(before) + since
            rel = jnp.exp(since - spread(mids))
            k_rel, q_rel = k * rel, q * rel
            # sub-chunk i of both chunks against its own chunk's keys as
            # it sees them; a later key's factor is exp(0)
            a_rows, b_rows = [[None] * (2 * n) for _ in range(2)]
            for i in range(n):
                i0, i1 = i, n + i
                top = jnp.concatenate(
                    [jnp.broadcast_to(before[i0] + mids[i0], (C, dk)),
                     jnp.broadcast_to(before[i1] + mids[i1], (C, dk))], 0)
                later = sub_of_row % n > i
                k_seen = k * jnp.exp(jnp.where(later, 0.0, top - G))
                lhs = jnp.concatenate(
                    [x[j * sub:(j + 1) * sub] for j in (i0, i1)
                     for x in (k_rel, q_rel)], axis=0)         # [4 sub, dk]
                p = _mm_nt(lhs, k_seen)                        # [4 sub, U]
                a_rows[i0], b_rows[i0] = p[:sub], p[sub:2 * sub]
                a_rows[i1], b_rows[i1] = p[2 * sub:3 * sub], p[3 * sub:]
            A = jnp.where(strict, jnp.concatenate(a_rows, 0), 0.0) * beta
            B = jnp.where(lower, jnp.concatenate(b_rows, 0), 0.0)
            # the diagonal blocks side by side: D[t, (i, s)] = A_ii[t, s]
            D = sum(jnp.where(fold_col == i, A[i * sub:(i + 1) * sub], 0.0)
                    for i in range(2 * n))
            g_end = [G[(c + 1) * C - 1:(c + 1) * C] for c in range(2)]
            ends = jnp.concatenate(
                [jnp.broadcast_to(g, (C, dk)) for g in g_end], 0)
            grown = jnp.exp(G)
            pre.append(dict(
                A=A, B=B, D=D,
                rhs=beta * jnp.concatenate([v, k * grown], -1),
                q_in=q * grown, k_out_t=(k * jnp.exp(ends - G)).T,
                # the chunk's decay a ROW of the state: [dk, dv] tiles
                decay=[jnp.broadcast_to(jnp.exp(g), (dv, dk)).T
                       for g in g_end]))

    # (I + A_ii)^-1 by forward substitution, a row at a time, every
    # diagonal block of the step at once.  Held transposed: Tt[c, (i,
    # t)] = T_ii[t, c], so that row t of every A_ii is ONE row over the
    # lanes; the sum over s is a sum over a block's `sub` lanes (log2
    # steps of roll and add: lane l then holds the sum of lanes l .. l +
    # sub - 1, which at a block's first lane is the block's).  All the
    # step's problems in ONE chain: a chain a problem is two vector
    # registers wide, waits for every operation and takes 4.96 ms where
    # this takes 2.38 (2048 tokens x 32 heads; my chip runs, PR 45).
    # (lax, not jnp: 16 x 12 operations, and a jnp operator costs ten
    # times a primitive's bind to trace.)
    D = jnp.concatenate([p["D"] for p in pre], axis=1)         # [sub, L]
    L = D.shape[1]
    at, unit_row = _iota((sub, L), 1) % sub, _iota((sub, L), 0)
    Tt = jnp.where((at == 0) & (unit_row == 0), 1.0, 0.0)     # row 0: e_0
    for t in range(1, sub):
        w = lax.mul(jnp.broadcast_to(D[t:t + 1], (sub, L)), Tt)
        step = sub // 2
        while step:
            w = lax.add(w, pltpu.roll(w, L - step, 1))
            step //= 2
        e_t = (unit_row == t).astype(F32)
        Tt = lax.select(at == t, lax.sub(e_t, pltpu.roll(w, t, 1)), Tt)

    for i, p in enumerate(pre):
        tt = Tt[:, i * U:(i + 1) * U]                          # [sub, U]
        inv = jnp.where(same_sub, jnp.concatenate([tt] * (2 * n), 0),
                        0.0).T                       # blockdiag(T_ii)
        # block rows: T_i = T_ii (E_i - A_i,<i T_<i) = T_ii E_i - (T_ii
        # A_i,<i) T_<i, one more block row of each chunk right after
        # each turn (the product T_ii A_i,<i once for all turns: four
        # matmuls where the other order takes six, and the MXU is what
        # this kernel waits for)
        below_t = _mm(inv, jnp.where(below, p["A"], 0.0))
        T = inv
        for _ in range(n - 1):
            T = inv - _mm(below_t, T)
        p["sol"] = _mm(T, p["rhs"])                            # [U, dv + dk]

    zeros = jnp.zeros((C, dv), F32)
    for h in range(hb):
        S = s_ref[h]
        for u in range(units):
            p = pre[h * units + u]
            for c in range(2):
                rows = slice(c * C, (c + 1) * C)
                U0, W = p["sol"][rows, :dv], p["sol"][rows, dv:]
                both = _mm(jnp.concatenate([W, p["q_in"][rows]], 0), S)
                Uc = U0 - both[:C]
                Upad = jnp.concatenate([Uc, zeros] if c == 0
                                       else [zeros, Uc], 0)    # [U, dv]
                o = both[C:] + _mm(p["B"][rows], Upad)
                S = p["decay"][c] * S + _mm(p["k_out_t"], Upad)
                o_ref[u * U + c * C:u * U + (c + 1) * C,
                      h * dv:(h + 1) * dv] = o * scale
        s_ref[h] = S


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("scale", "chunk", "sub", "units", "interpret"))
@jax.named_scope("dyn.attn_delta")
def kda_chunk_rows(q: jax.Array, k: jax.Array, v: jax.Array,
                   log_a: jax.Array, beta: jax.Array, state: jax.Array, *,
                   scale: float, chunk: int = 64, sub: int = 16,
                   units: int | None = None, interpret: bool = False):
    """`kda_chunked` for every row at once.  q, k, log_a [rows, T, H,
    dk], v [rows, T, H, dv], beta [rows, T, H], state [rows, H, dk, dv]
    float32 -> (o [rows, T, H, dv] float32, state after each row's last
    token).  T a multiple of `chunk` (resolve_chunk_impl); a token with
    beta 0 and log a 0 (padding) leaves the state as it was.  `units`:
    pairs of chunks a grid step where not `chunk_tiling`'s (the tests'
    way to several chunk groups in a short row)."""
    R, T, H, dk = k.shape
    dv = v.shape[-1]
    if T % chunk or chunk % sub or sub & (sub - 1):
        raise ValueError(f"a row of {T} tokens in chunks of {chunk} and "
                         f"sub-chunks of {sub}: the kernel takes whole "
                         "chunks and a power of two a sub-chunk")
    U = 2 * chunk
    Tp = -(-T // U) * U                 # an odd chunk: one of padding
    hb, per_step = chunk_tiling(
        Tp, U, H, 4 * (3 * dk + 2 * dv),
        4 * U * (3 * U + 4 * dk + 2 * (dk + dv) + 2 * dv))
    per_step = units or per_step
    if (Tp // U) % per_step:
        raise ValueError(f"{Tp // U} units do not split into steps of "
                         f"{per_step}")
    nj, Tg = H // hb, per_step * U
    at = lambda r, j, g: (r, g, j)
    wide = lambda x, w: _Blocked(_rows_of(x, Tp), (None, Tg, hb * w), at)
    beta = _head_columns(jnp.pad(beta.astype(F32),
                                 ((0, 0), (0, Tp - T), (0, 0))), nj)
    n = chunk // sub
    flops = R * H * (Tp // U) * 2 * U * (
        U * dk + 4 * n * sub * dk + 2 * (n - 1) * U * U + U * (dk + dv)
        + 2 * (2 * dk * dv + chunk * dv))
    o, state = _chunk_state_call(
        functools.partial(_kda_chunk_body, scale=scale, chunk=chunk, sub=sub,
                          dk=dk, dv=dv),
        state,
        [wide(q, dk), wide(k, dk), wide(v, dv), wide(log_a, dk),
         _Blocked(beta, (None, None, Tg, hb), lambda r, j, g: (r, j, g, 0))],
        _Blocked(jax.ShapeDtypeStruct((R, Tp, H * dv), F32),
                 (None, Tg, hb * dv), at),
        groups=Tp // Tg, head_block=hb, flops=flops, interpret=interpret)
    return o[:, :T].reshape(R, T, H, dv), state
