"""Draft-model proposer: a second, smaller model on the target's mesh.

The draft holds its own params and its own KV cache arrays, but the
cache is ADDRESSED BY THE TARGET'S BLOCK TABLES: same block_size, same
num_blocks, same garbage block 0.  That makes the whole proposer
allocator-free — wherever the engine's allocator put a sequence's
target KV, the draft KV for the same positions lives at the same block
ids in the draft arrays.  Shared prefix blocks are safe by the same
hash argument as the target cache (one hash = one token run = one KV
content), and a block id recycled to a new sequence is overwritten by
that sequence's catch-up prefill before it is ever read.

Per speculation round for one slot:

  1. catch-up: prefill the draft over tokens[draft_pos:ctx] (bucketed
     B=1 chunks).  draft_pos is engine bookkeeping on the slot — after a
     verify it equals the new ctx, so steady-state catch-up is EMPTY
     (the accepted drafts' KV was already written by step 2, and the
     rejected tail is overwritten by the next round's step 2).
  2. propose: ONE fused decode_multi program runs k greedy draft steps
     from last_token at position ctx, chaining sampled ids on device —
     k tokens for one dispatch, exactly the program shape the target
     engine uses for its own fused decode.

v1 scope: greedy drafts (the proposal is a point mass, which is what
engine/sampler.py spec_accept_tokens assumes), single-host slices only
(draft programs do not ride the multihost step stream; engine/core.py
rejects the combination at init).
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import get_family


class DraftModelProposer:
    name = "draft"

    def __init__(self, model_cfg, mesh, *, num_blocks: int,
                 block_size: int, prefill_buckets, model_path: str = "",
                 max_k: int = 4, seed: int = 0,
                 kv_cache_dtype: str = "bf16", compile_watch=None):
        from ..parallel.mesh import shard_params

        self.cfg = model_cfg
        self.family = get_family(model_cfg)
        self.mesh = mesh
        self.block_size = block_size
        self.buckets = tuple(prefill_buckets)
        self.max_k = max_k
        # int8 draft cache (quant/kv.py): same fallback rule as the
        # engine — a family without the quantized path stays bf16
        quantized = (kv_cache_dtype == "int8"
                     and hasattr(self.family, "kv_cache_scale_shapes"))
        with mesh:
            if model_path:
                from ..models.loader import load_params

                self.params = load_params(model_path, model_cfg, mesh=mesh)
            else:
                self.params = shard_params(
                    self.family.init_params(model_cfg,
                                            jax.random.PRNGKey(seed)),
                    mesh)
            k_shape, v_shape = self.family.kv_cache_shapes(
                model_cfg, num_blocks, block_size)
            k_spec, v_spec = self.family.kv_cache_specs()
            from jax.sharding import NamedSharding

            dtype = jnp.int8 if quantized else model_cfg.dtype
            kv = [
                # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
                jax.jit(partial(jnp.zeros, k_shape, dtype),
                        out_shardings=NamedSharding(mesh, k_spec))(),
                # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
                jax.jit(partial(jnp.zeros, v_shape, dtype),
                        out_shardings=NamedSharding(mesh, v_spec))(),
            ]
            if quantized:
                scale_shapes = self.family.kv_cache_scale_shapes(
                    model_cfg, num_blocks, block_size)
                scale_specs = self.family.kv_cache_scale_specs()
                kv += [
                    # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
                    jax.jit(partial(jnp.zeros, shape, jnp.float32),
                            out_shardings=NamedSharding(mesh, spec))()
                    for shape, spec in zip(scale_shapes, scale_specs)
                ]
            self.kv = tuple(kv)
        # the draft's prefill/propose programs dispatch during serving
        # exactly like the target's: under the engine's compile watchdog
        # (obs/compile_watch.py) a draft recompile mid-serving is
        # observed too.  A standalone proposer (tests, benches) wraps
        # with a local watch so the call syntax never branches.
        if compile_watch is None:
            from ..obs.compile_watch import CompileWatch

            compile_watch = CompileWatch()
        self._watch = compile_watch
        self._jit_prefill = compile_watch.wrap(jax.jit(
            compile_watch.named(
                partial(self._prefill_impl, self.family, self.cfg),
                "draft_prefill"),
            donate_argnums=(1,)), "draft_prefill", lambda a: a[2].shape[-1])
        self._jit_propose = {}  # k -> jitted k-step greedy draft program

    @staticmethod
    def _prefill_impl(family, cfg, params, kv, toks, positions, table,
                      ctx_len, true_len):
        _, kv = family.prefill(params, cfg, kv, toks, positions, table,
                               ctx_len, true_len)
        return kv

    @staticmethod
    def _propose_impl(family, cfg, mesh, k, params, kv, token, position,
                      table, ctx_len):
        toks, kv = family.decode_multi(
            params, cfg, kv, token[None], position[None], table[None],
            ctx_len[None], k, None, valid=jnp.ones((1,), bool), mesh=mesh,
        )
        return toks[:, 0], kv

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def propose(self, tokens: Sequence[int], k: int, *, ctx: int,
                draft_pos: int, block_table) -> List[int]:
        """k greedy draft tokens continuing tokens[:ctx+1] (last_token is
        tokens[ctx]).  Catch-up prefill covers [draft_pos, ctx); the
        caller advances draft_pos to the new ctx after verification."""
        table = jnp.asarray(block_table)
        pos = draft_pos
        while pos < ctx:
            chunk = min(ctx - pos, self.buckets[-1])
            bucket = self._bucket_for(chunk)
            toks = np.zeros(bucket, np.int32)
            toks[:chunk] = tokens[pos:pos + chunk]
            positions = pos + np.arange(bucket, dtype=np.int32)
            self.kv = self._jit_prefill(
                self.params, self.kv, jnp.asarray(toks),
                jnp.asarray(positions), table, jnp.int32(pos),
                jnp.int32(chunk))
            pos += chunk
        k = min(k, self.max_k)
        jit = self._jit_propose.get(k)
        if jit is None:
            jit = self._jit_propose[k] = self._watch.wrap(jax.jit(
                self._watch.named(
                    partial(self._propose_impl, self.family, self.cfg,
                            self.mesh, k), "draft_propose"),
                donate_argnums=(1,)), "draft_propose",
                lambda a, _k=k: _k)
        burst, self.kv = jit(
            self.params, self.kv, jnp.int32(tokens[ctx]), jnp.int32(ctx),
            table, jnp.int32(ctx))
        return [int(t) for t in np.asarray(burst)]
