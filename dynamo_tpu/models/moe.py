"""The expert layer every family with experts shares: the two routers,
the dropless dispatch in its three forms and the rule that picks among
them.  It belongs to no family and imports none: a config is read by
its field names (`experts_per_token`, `n_experts`, `expert_shards`,
`dtype`; where present `experts_held`, `expert_gated`, `expert_act`;
`ds_router`'s `moe_scoring`, `n_group`, `topk_group`, `norm_topk_prob`,
`routed_scaling_factor`), never by its type.

A layer holds `moe_gate` (and `moe_gate_bias`) for a router and the
`moe_w_*` stacks for the dispatch.  Experts shard over the "tp" mesh
axis (EP reuses tp, parallel/mesh.py moe_w_* rules).

ONE mathematics: the dispatch is DROPLESS and batch-invariant (same
token -> same output regardless of chunking/co-batch), which prefix
caching, replay after a preemption and greedy determinism rely on.  The
FORM is the program's choice by shape (`moe_dispatch_form`), which no
caller and no config can set: few tokens (every decode step, the
prefill buckets to 256 tokens) multiply every token with every VISITED
expert and mask the combine, in one kernel that reads no expert nobody
picked -- the weights' read is the cost there; prompt-sized inputs sort
their picks by expert and multiply each token with its own experts
only; what lies between multiplies every token with every held expert.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("dyn.moe_router")
def softmax_router(layer, cfg, x: jax.Array):
    """Top-k routing: returns (weights [T,k] softmaxed, expert ids [T,k]).

    topk-then-softmax == HF Mixtral's softmax-topk-renormalize (softmax of
    the selected logits), verified against transformers in
    tests/test_loader.py."""
    router = (x.astype(jnp.float32) @ layer["moe_gate"].astype(jnp.float32))
    top_w, top_e = jax.lax.top_k(router, cfg.experts_per_token)
    return jax.nn.softmax(top_w, axis=-1), top_e


@jax.named_scope("dyn.moe_router")
def ds_router(layer, cfg, x: jax.Array):
    """DeepSeek routing -> (weights [T, k], ids [T, k]).

    Mirrors HF DeepseekV3TopkRouter exactly: scores are sigmoid (V3) or
    softmax (V2); expert CHOICE adds e_score_correction_bias and applies
    group-limited top-k (per-group score = sum of that group's top-2),
    but combine WEIGHTS are the raw scores of the chosen experts,
    optionally renormalized, then scaled by routed_scaling_factor."""
    T = x.shape[0]
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ layer["moe_gate"].astype(jnp.float32)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + layer["moe_gate_bias"] if "moe_gate_bias" in layer \
        else scores
    if cfg.n_group > 1:
        g = choice.reshape(T, cfg.n_group, E // cfg.n_group)
        if cfg.moe_scoring == "sigmoid":
            # V3 lineage: group score = sum of the group's top-2
            group_scores = jax.lax.top_k(g, 2)[0].sum(-1)    # [T, n_group]
        else:
            # V2 lineage (group_limited_greedy): group score = group max
            group_scores = g.max(-1)
        _, keep = jax.lax.top_k(group_scores, cfg.topk_group)
        gmask = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        choice = jnp.where(
            jnp.repeat(gmask, E // cfg.n_group, axis=1), choice, 0.0)
    _, top_e = jax.lax.top_k(choice, k)                      # [T, k]
    if "moe_forced_picks" in layer:
        # a witness's, never a served program's: the float32 reference's
        # choice [T, k] in place of the program's own, the weights still
        # from the program's scores (PERF.md section 7t: with random
        # weights a pick that flips at bf16-level noise hides the
        # arithmetic from a comparison of logits)
        top_e = layer["moe_forced_picks"]
    top_w = jnp.take_along_axis(scores, top_e, axis=1)
    if cfg.norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    return top_w * cfg.routed_scaling_factor, top_e


def experts_held(cfg) -> Tuple[int, int]:
    """(first, count) of the routed experts whose weights this program
    holds, out of the `cfg.n_experts` the router scores: all of them
    unless the config says otherwise (`experts_held`, a chip's share of
    an expert-parallel deployment)."""
    return getattr(cfg, "experts_held", None) or (0, cfg.n_experts)


def relu2(h: jax.Array) -> jax.Array:
    """relu(h)^2, the activation of a plain (non-gated) expert."""
    return jnp.square(jax.nn.relu(h))


def _expert_hidden(layer, cfg, mm) -> jax.Array:
    """A routed expert's hidden activations in the form the family's
    configuration gives it, for all three dispatches: `mm(w)` multiplies
    the dispatch's rows with an expert matrix stack.  GATED (the default:
    `cfg.expert_gated` absent or True) is act(x Wgate) * (x Wup), three
    matrices an expert; PLAIN is act(x Wup), two, and the layer has no
    `moe_w_gate`.  `cfg.expert_act` is the activation, a function (SiLU
    where absent: SwiGLU)."""
    act = getattr(cfg, "expert_act", jax.nn.silu)
    if getattr(cfg, "expert_gated", True):
        return act(mm(layer["moe_w_gate"])) * mm(layer["moe_w_up"])
    return act(mm(layer["moe_w_up"]))


def _held_picks(cfg, top_e: jax.Array, valid: Optional[jax.Array]):
    """(on [T, k] bool: the picks of valid rows that fall on a held
    expert; seen [held] bool: the held experts such a pick visits)."""
    first, count = experts_held(cfg)
    on = (top_e >= first) & (top_e < first + count)
    if valid is not None:
        on = on & valid[:, None]
    seen = jnp.zeros((count,), bool).at[
        jnp.where(on, top_e - first, count)].set(True, mode="drop")
    return on, seen


def moe_held_counts(cfg, top_e: jax.Array, valid: Optional[jax.Array]):
    """(picks that fell on a held expert, held experts with a token), two
    int32 scalars over the valid rows of top_e [T, k]: what a family
    that holds a share of its experts counts on the device
    (`KV_COUNTERS`)."""
    on, seen = _held_picks(cfg, top_e, valid)
    return jnp.sum(on, dtype=jnp.int32), jnp.sum(seen, dtype=jnp.int32)


def _combine_weights(cfg, top_w: jax.Array, top_e: jax.Array,
                     valid: Optional[jax.Array]) -> jax.Array:
    """[T, held] in cfg.dtype: a row's routing weight for each held
    expert, 0 where it did not pick it and for a row `valid` masks."""
    T, E = top_e.shape[0], cfg.n_experts
    wmat = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], top_e
    ].set(top_w)                                       # [T, E]
    if valid is not None:
        wmat = wmat * valid.astype(jnp.float32)[:, None]
    first, count = experts_held(cfg)
    if count != E:
        wmat = wmat[:, first:first + count]            # the held columns
    return wmat.astype(cfg.dtype)


@jax.named_scope("dyn.moe_dispatch")
def moe_dispatch_dense(layer, cfg, x: jax.Array,
                       top_w: jax.Array, top_e: jax.Array,
                       valid: Optional[jax.Array] = None) -> jax.Array:
    """Dropless masked-dense MoE dispatch for precomputed routing
    (top_w/top_e [T, k]): all experts compute all tokens, the router
    matrix masks the combine.  Batch-invariant by construction.

    With experts sharded over tp, the expert einsums run local to each
    shard and the final combine reduces over the expert axis (one psum on
    the way out) — no dispatch tensors, no all-to-all.

    `cfg.n_experts` is the ROUTER's width; the `moe_w_*` stacks hold the
    experts `experts_held(cfg)` names.  A chip's share of a wider
    deployment computes the held experts' part for the tokens routed to
    them and adds nothing for the rest; with all experts held this is
    the one code path there was."""
    wmat = _combine_weights(cfg, top_w, top_e, valid)
    h = _expert_hidden(layer, cfg,
                       lambda w: jnp.einsum("td,edf->etf", x, w))
    eout = jnp.einsum("etf,efd->etd", h, layer["moe_w_down"])
    return jnp.einsum("etd,te->td", eout, wmat)


# rows of one m-tile of the grouped matmul: a group that is not empty
# pays up to one tile of rows it does not have (moe_dispatch_form)
_GMM_TILE_M = 128


# rows up to which a program's experts take the visited form: the
# weights' read is the cost there (moe_dispatch_form)
_VISITED_MAX_ROWS = 256


def moe_dispatch_form(tokens: int, k: int, held: int, routed: int,
                      shards: int = 1) -> str:
    """Which form the dropless dispatch takes for a program of `tokens`
    rows: "visited", "dense" or "grouped".  They differ in the experts
    whose weights they read and in the rows they multiply: dense reads
    every HELD expert's weights and multiplies tokens x held rows;
    grouped reads the visited experts' and multiplies the picks that
    fall on a held expert (about tokens x k x held / routed) plus up to
    one m-tile a group; visited reads the visited experts' and
    multiplies tokens x visited rows.  Visited up to
    `_VISITED_MAX_ROWS` rows, where the weights' read is the cost
    (every decode step, the prefill buckets to 256 tokens); grouped
    where dense would multiply at least twice as many rows; dense
    between.

    One expert layer on a TPU v5e, ms, dense / grouped (my chip runs,
    PR 32): Moonlight's widths (64 experts of 2048 x 1408, top 6) T 512:
    3.51 / 1.94, 2048: 16.88 / 3.06; MiMo's (16 of 256 held, 4096 x
    2048, top 8) 512: 2.48 / 1.48, 2048: 10.92 / 3.20.

    The same at each expert cell's decode rows by the held experts
    visited, ms, dense / grouped / visited, and the visited form's share
    of 819 GB/s on the visited experts' bytes (my chip runs, PR 44,
    benchmarks/bench_moe_decode.py; the kernel at `f_tile`'s width):
      Moonlight, 16 rows, 64 held:  6: 1.485 / 0.182 / 0.160 (79 %)
        16: 1.474 / 0.410 / 0.381 (89 %)  32: 1.479 / 0.772 / 0.749
        (90 %)  64: 1.485 / 1.518 / 1.488 (91 %)
      MiMo, 32 rows, 16 held:  1: 1.102 / 0.112 / 0.080 (77 %)
        4: 1.105 / 0.316 / 0.289 (85 %)  8: 1.105 / 0.598 / 0.560 (88 %)
        16: 1.108 / 1.146 / 1.110 (89 %)
      Keye, 8 rows, 16 of 2048 x 768:  1: 0.211 / 0.149 / 0.018 (66 %)
        4: 0.205 / 0.169 / 0.054 (85 %)  8: 0.204 / 0.191 / 0.103 (90 %)
        16: 0.212 / 0.243 / 0.212 (87 %)
      Ling, 64 rows, 16 of 2560 x 768:  1: 0.250 / 0.137 / 0.016 (88 %)
        4: 0.261 / 0.188 / 0.076 (76 %)  8: 0.261 / 0.243 / 0.143 (80 %)
        16: 0.266 / 0.348 / 0.272 (85 %)
      Nemotron, 64 rows, 16 of 2688 x 1856, two matrices:  1: 0.437 /
        0.115 / 0.041 (59 %)  4: 0.432 / 0.211 / 0.117 (83 %)  8: 0.433 /
        0.382 / 0.225 (87 %)  16: 0.433 / 0.645 / 0.441 (88 %)
      Command A+, 8 rows, 16 of 4096 x 4096:  1: 2.162 / 0.168 / 0.157
        (78 %)  4: 2.156 / 0.601 / 0.553 (89 %)  8: 2.165 / 1.166 / 1.090
        (90 %)  16: 2.166 / 2.285 / 2.150 (91 %)
    The dense form's time does not depend on what was visited; the
    grouped form reads the visited experts too but pays three calls and
    a sort a layer (Keye 27 %, Ling 31 %, Nemotron 46 % of the bound at 4
    of 16), so it is not decode's form.  With every expert visited the
    visited form is the dense form's time to 2.5 %, and at 128 and 256
    rows, picks drawn evenly, it is the faster one: Moonlight 128: 1.537
    / 1.613 / 1.489, 256: 1.724 / 1.719 / 1.524; MiMo 128: 1.141 / 1.068
    / 0.996, 256: 1.343 / 1.323 / 1.177; Nemotron 128: 0.429 / 0.619 /
    0.441, 256: 0.507 / 0.680 / 0.479.  Hence the bound.

    Stacks split over devices (`shards` > 1) keep the dense form: its
    einsums run local to each shard under GSPMD, the kernels would have
    the stacks gathered to every device first."""
    if shards > 1:
        return "dense"
    if tokens <= _VISITED_MAX_ROWS:
        return "visited"
    dense_rows = tokens * held
    grouped_rows = tokens * k * held // routed + held * _GMM_TILE_M
    return "grouped" if dense_rows >= 2 * grouped_rows else "dense"


def _gmm_tiling(kdim: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the Pallas grouped matmul for an expert matrix
    [kdim, n]: the whole contraction in one step where a row tile and
    a weight tile of 6 MiB together allow (the kernel holds two of each
    in 16 MiB of scoped VMEM beside the output tile and its fp32
    accumulator), n halved until they do.  Timed on the chip (PR 32):
    Moonlight's (128, 2048, 1408) / (128, 1408, 2048) and MiMo's
    (128, 4096, 512) / (128, 2048, 1024) are within 3 % of the best of
    six a shape; tm 64 or 256 changes nothing."""
    tm, tk, tn = _GMM_TILE_M, kdim, n

    def over():
        return (tm + tn) * tk * itemsize > 6 << 20

    while over() and tn > 128:
        tn = max(128, tn // 2 // 128 * 128)
    while over() and tk > 128:
        tk = max(128, tk // 2 // 128 * 128)
    return tm, tk, tn


def _grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
    """lhs [m, k] with its rows sorted by group, rhs [g, k, n],
    group_sizes [g] -> [m, n]: group i's rows times rhs[i], fp32
    accumulation, an empty group costs nothing.  Rows past the last
    group are undefined; the caller masks them.

    A platform rule, not a choice: on the TPU the Pallas kernel (megablox
    `gmm`, which visits only the m-tiles that hold rows), elsewhere
    `jax.lax.ragged_dot`, its twin for the CPU.  What XLA makes of
    `ragged_dot` on the chip was timed too: 2.2-2.8 x the kernel's time
    at 2048 tokens and slower than the dense form under 1024 (PR 32)."""
    def tpu(lhs, rhs, group_sizes):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        m = lhs.shape[0]
        lhs = jnp.pad(lhs, ((0, -m % _GMM_TILE_M), (0, 0)))
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                   tiling=_gmm_tiling(rhs.shape[1], rhs.shape[2],
                                      rhs.dtype.itemsize))[:m]

    def xla(lhs, rhs, group_sizes):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(lhs.dtype)

    return jax.lax.platform_dependent(lhs, rhs, group_sizes,
                                      tpu=tpu, default=xla)


@jax.named_scope("dyn.moe_dispatch")
def moe_dispatch_grouped(layer, cfg, x: jax.Array,
                         top_w: jax.Array, top_e: jax.Array,
                         valid: Optional[jax.Array] = None) -> jax.Array:
    """The dropless dispatch's form for prompt-sized inputs
    (moe_dispatch_dense's contract and mathematics): the (token, pick)
    pairs sorted by expert, the expert's matmuls (three, or two where it
    is plain: `_expert_hidden`) grouped over the sorted rows (a row
    meets its own expert's matrices only), the k results of a token
    gathered back and summed in pick order.

    A pick of an expert held elsewhere (`experts_held`) and every pick
    of a row `valid` masks out sort behind the held groups, belong to no
    group and are never multiplied.  A row's result depends on no other
    row: a matmul row by row, and a sum over its own k picks in a fixed
    order."""
    T, d = x.shape
    k = top_e.shape[1]
    first, count = experts_held(cfg)
    local = top_e.reshape(-1) - first                  # [T*k]
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & jnp.repeat(valid, k)
    group = jnp.where(held, local, count)              # count = no group
    order = jnp.argsort(group, stable=True)
    place = jnp.argsort(order)                         # pair -> sorted row
    sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    xs = x[order // k]                                 # [T*k, d]
    h = _expert_hidden(layer, cfg,
                       lambda w: _grouped_matmul(xs, w, sizes))
    ys = _grouped_matmul(h, layer["moe_w_down"], sizes)
    held = held.reshape(T, k)
    y = jnp.where(held[..., None], ys[place].reshape(T, k, d), 0)
    w = jnp.where(held, top_w, 0).astype(cfg.dtype)
    return jnp.einsum("tkd,tk->td", y, w)


@partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("cfg", "tile", "interpret"))
@jax.named_scope("dyn.moe_dispatch")
def _visited(stacks, cfg, x, top_w, top_e, valid, tile, interpret):
    """moe_dispatch_visited over a layer's expert stacks alone, jitted
    with the (hashable) config static: a program traces and lowers the
    form ONCE for its expert layers and not once a layer.  A Pallas
    call site costs about 60 ms of tracing: 11 layers x 10 programs
    were 6 s of `setup_s` on the state-space cell (my chip runs,
    PR 44)."""
    from ..ops.pallas_moe_visited import moe_visited, visited_plan

    def tpu(stacks, x, top_w, top_e, valid):
        ids, n = visited_plan(_held_picks(cfg, top_e, valid)[1])
        return moe_visited(
            stacks, lambda refs, mm: _expert_hidden(refs, cfg, mm), x,
            _combine_weights(cfg, top_w, top_e, valid), ids, n,
            tile=tile, interpret=interpret)

    def xla(stacks, x, top_w, top_e, valid):
        return moe_dispatch_dense(stacks, cfg, x, top_w, top_e, valid)

    if interpret:
        return tpu(stacks, x, top_w, top_e, valid)
    return jax.lax.platform_dependent(stacks, x, top_w, top_e, valid,
                                      tpu=tpu, default=xla)


def moe_dispatch_visited(layer, cfg, x: jax.Array,
                         top_w: jax.Array, top_e: jax.Array,
                         valid: Optional[jax.Array] = None, *,
                         tile: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """The dropless dispatch's form for decode-sized inputs
    (moe_dispatch_dense's contract and mathematics, its rounding points
    too): every row through every VISITED expert, the combine weight
    deciding, and an expert no valid row picked is never read.  One
    Pallas call a layer (ops/pallas_moe_visited.py) that walks the
    visited experts' ids; batch-invariant as the dense form is.

    A platform rule, not a choice: the kernel on the TPU, the dense
    einsums elsewhere (the same results over every held expert).
    `interpret` runs the kernel under the interpreter, for the tests;
    `tile` is the kernel's hidden tile where it is not its own choice
    (benchmarks/bench_moe_decode.py)."""
    stacks = {k: w for k, w in layer.items() if k.startswith("moe_w_")}
    return _visited(stacks, cfg, x, top_w, top_e, valid, tile, interpret)


def moe_form(cfg, tokens: int) -> str:
    """What a program of `tokens` rows runs for its routed experts: the
    dropless dispatch's "visited", "dense" or "grouped" form.  The one
    rule, asked by the traced code and by the engine's counters."""
    return moe_dispatch_form(tokens, cfg.experts_per_token,
                             experts_held(cfg)[1], cfg.n_experts,
                             cfg.expert_shards)


def moe_dispatch(layer, cfg, x: jax.Array, top_w: jax.Array,
                 top_e: jax.Array,
                 valid: Optional[jax.Array] = None) -> jax.Array:
    """Routed experts for precomputed routing, x [T, d] -> [T, d]: the
    one entry point of every family with experts.  The FORM follows the
    program's shape (moe_form), which the caller cannot set."""
    dispatch = {"grouped": moe_dispatch_grouped,
                "visited": moe_dispatch_visited,
                "dense": moe_dispatch_dense}[moe_form(cfg, x.shape[0])]
    return dispatch(layer, cfg, x, top_w, top_e, valid)


def moe_rows(fn, h: jax.Array, valid: jax.Array):
    """A family's routed FFN `fn(x [T, d], valid [T])` over co-batched
    prefill rows h [Bp, T, d].  A dropless dispatch has no pools to keep
    apart and a row's result depends on no other row: the rows run
    flattened, as one program-sized input."""
    Bp, T = h.shape[:2]
    out = fn(h.reshape(Bp * T, -1), valid.reshape(Bp * T))
    return jax.tree_util.tree_map(
        lambda o: o.reshape(Bp, T, *o.shape[1:]) if o.ndim else o, out)
