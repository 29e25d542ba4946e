"""Speculative decoding subsystem: proposers + packed verification.

Decode is memory-bandwidth-bound (the raw loop ran at 0.76 of the HBM
roofline on an earlier set-up; not measured on today's code), so the only way left to raise tokens/s/chip is to emit
MORE THAN ONE accepted token per weight/KV pass.  Speculative decoding
(Leviathan et al. 2023; Chen et al. 2023) does that: a cheap proposer
drafts k continuation tokens, the target model scores all of them in one
pass, and rejection sampling accepts the longest prefix that preserves
the target distribution exactly (greedy mode = exact argmax-prefix
match, so served output is token-identical to plain decode).

Pieces:

  * ngram.py  — NgramProposer: zero-weight prompt-lookup.  The tail of
    the generated sequence is matched against its own history
    (prompt + output); on a hit the tokens that followed the previous
    occurrence become the draft.  Free to run, surprisingly effective on
    repetitive serving workloads (extraction, code, templated JSON), and
    CPU-only — the tier-1 test proposer.
  * draft.py  — DraftModelProposer: a second, smaller model on the SAME
    mesh, with its own KV cache ADDRESSED BY THE TARGET'S block tables
    (same block_size/num_blocks geometry, separate arrays) — no second
    allocator, no second scheduler.  Greedy k-step drafts via the
    family's fused decode_multi program.
  * verify.py — the packing planner: speculating slots' rows
    [last_token, d1..dk] concatenate into ONE padding-free stream with
    segment ids, verified by the engine's `spec_verify` program
    (models/*.spec_verify_packed over ops/packed_prefill.py segment-id
    causal attention).  Rejection sampling itself lives in
    engine/sampler.py (spec_accept_tokens) next to the distribution it
    must preserve.

The engine side (engine/core.py _spec_step) owns adaptivity — a
per-sequence acceptance-rate EMA shrinks the draft length down to 0
(plain decode) and probes periodically to re-engage — and KV rollback:
blocks grown for rejected draft positions return to the allocator
(block_allocator.trim_blocks), so accounting matches plain decode.
"""

from .draft import DraftModelProposer
from .ngram import NgramProposer
from .verify import SpecPlan, plan_spec_verify


def make_proposer(config, mesh, compile_watch=None):
    """Build the proposer an EngineConfig asks for (engine/core.py).

    `config.spec_decode`: "ngram" (zero-weight prompt lookup) or "draft"
    (second model on the same mesh; resolved from spec_draft_config >
    spec_draft_model_path > spec_draft_model preset, vocab-checked
    against the target)."""
    if config.spec_decode == "ngram":
        return NgramProposer(max_ngram=config.spec_ngram_max,
                             min_ngram=config.spec_ngram_min)
    if config.spec_decode == "draft":
        from ..models import PRESETS, get_family  # noqa: F401

        if config.spec_draft_config is not None:
            draft_cfg = config.spec_draft_config
        elif config.spec_draft_model_path:
            from ..engine.loader_cache import cached_hf_config

            draft_cfg = cached_hf_config(config.spec_draft_model_path)
        elif config.spec_draft_model:
            if config.spec_draft_model not in PRESETS:
                raise ValueError(
                    f"unknown draft preset {config.spec_draft_model!r}; "
                    f"have {sorted(PRESETS)}")
            draft_cfg = PRESETS[config.spec_draft_model]
        else:
            raise ValueError(
                "spec_decode='draft' needs spec_draft_config, "
                "spec_draft_model_path, or spec_draft_model")
        target_cfg = config.resolve_model()
        if draft_cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{target_cfg.vocab_size}: draft tokens must be valid "
                "target tokens")
        return DraftModelProposer(
            draft_cfg, mesh,
            num_blocks=config.num_blocks, block_size=config.block_size,
            prefill_buckets=config.prefill_buckets,
            model_path=config.spec_draft_model_path,
            max_k=config.spec_k, seed=config.seed,
            # the draft cache matches the target's quantization policy:
            # its writes (catch-up prefill + propose bursts) are KV write
            # sites like any other, and its HBM footprint halves too
            kv_cache_dtype=config.kv_cache_dtype,
            # the engine threads its compile watchdog through so draft
            # compiles are observed on the same FPM/metric plane
            compile_watch=compile_watch,
        )
    raise ValueError(
        f"spec_decode must be 'off' | 'ngram' | 'draft', "
        f"got {config.spec_decode!r}")


__all__ = [
    "DraftModelProposer",
    "NgramProposer",
    "SpecPlan",
    "make_proposer",
    "plan_spec_verify",
]
