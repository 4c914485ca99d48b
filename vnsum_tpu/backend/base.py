"""The Backend protocol — the seam the whole framework hangs on.

The reference's equivalent is the OllamaLLM langchain wrapper duplicated five
times (SURVEY.md §2 C2). Here there is ONE interface, and it is batched:
`generate` takes a *list* of prompts so strategies can submit every LLM call
of a round (across chunks and across documents) as one unit. TpuBackend turns
that into sharded device batches; OllamaBackend loops over HTTP for parity;
FakeBackend is the deterministic hermetic test double (SURVEY.md §4).

Optional observability contract (vnsum_tpu.obs): backends MAY publish phase
telemetry from inside generate() via ``obs.trace.emit(name, t0, dur, ...)``
— host timestamps around already-dispatched device calls, never extra
device syncs. emit() no-ops on a single contextvar read unless a caller
(the serving scheduler) installed a collector, so backends wrap
their hot paths unconditionally. Recognized phase names: "tokenize",
"prefill"/"spec_prefill" (their end is the TTFT anchor), "decode",
"decode_seg", "spec_step", "dispatch" (fused one-shot program, pack to
detokenize), "detokenize". TpuBackend publishes them through
``core.profiling.host_span`` — one interval for the profiler, this collector
and an always-on aggregate — so the collector also sees that primitive's
other bare names ("pack", "enqueue", "wait", "count", "cache_lookup",
"cache_gather", "cache_insert", "adopt", "harvest"); FakeBackend emits
directly; HTTP parity backends (ollama/hf) simply emit nothing.

Optional prefix-cache contract (vnsum_tpu.cache): backends with a prefix KV
cache additionally expose ``cached_prefix_tokens(text, cache_hint=None)``
(thread-safe read-only probe — the serving queue bills only uncached tokens
against its admission budget), ``take_cache_report()`` (per-prompt cached
token counts of the last generate, cleared on read — scheduler attribution
into ServeRequestRecord), and ``prefix_cache_stats()`` (pool gauges for
/metrics). The scheduler discovers all three via getattr, so plain backends
need none of them. TpuBackend implements the real thing; FakeBackend mirrors
it synthetically (a real radix index over whitespace words, no device pool).
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

from ..core.config import GenerationConfig


@runtime_checkable
class Backend(Protocol):
    name: str

    def generate(
        self,
        prompts: list[str],
        *,
        max_new_tokens: int | None = None,
        config: GenerationConfig | None = None,
        references: list[str | None] | None = None,
        cache_hints: list[str | None] | None = None,
    ) -> list[str]:
        """Generate one completion per prompt, order-preserving.

        ``references`` optionally carries one source text per prompt (None
        entries allowed) for reference-guided speculative decoding
        (vnsum_tpu.spec): strategies pass the chunk being summarized, and a
        backend with ``config.spec_k > 0`` drafts from it.

        ``cache_hints`` optionally carries one string per prompt naming the
        prompt PREFIX the caller expects to recur (template headers,
        carried-forward summaries) for the radix prefix KV cache
        (vnsum_tpu.cache): a backend with the cache enabled bounds its block
        insertion to the hinted prefix so unique content tails don't churn
        the pool. Both are advisory metadata, never semantic inputs —
        backends without the feature accept and ignore them, and greedy
        outputs are identical either way."""
        ...

    def count_tokens(self, text: str) -> int:
        ...

    def count_tokens_batch(self, texts: list[str]) -> list[int]:
        """Batched count — the splitter issues one call per split
        level instead of one per sentence piece."""
        ...


# -- shared device-batch helpers (TpuBackend + LongContextBackend) ----------
# Greedy parity between the one-chip engine and the seq-sharded long-context
# engine depends on identical packing / seed / detokenize semantics — keep
# ONE copy of each here.


def fold_seed(gen_seed: int, backend_seed: int, dispatch: int) -> int:
    """Per-batch PRNG seed folded from (config seed, backend seed, dispatch
    index): sampled batches draw fresh randomness, same-seed reruns over the
    same call sequence replay bit-exactly, greedy ignores the key."""
    return (
        gen_seed * 0x9E3779B1 + backend_seed * 0x85EBCA77 + dispatch
    ) & 0x7FFFFFFF


def left_pad_batch(encoded_group, B: int, S: int, pad_id: int):
    """Pack encoded prompts into a fixed-shape left-padded [B, S] batch;
    rows beyond the group are all-pad filler. Returns (tokens, pad_lens)."""
    import numpy as np

    tokens = np.full((B, S), pad_id, dtype=np.int32)
    pad_lens = np.full((B,), S, dtype=np.int32)
    for row, ids in enumerate(encoded_group):
        tokens[row, S - len(ids):] = ids
        pad_lens[row] = S - len(ids)
    return tokens, pad_lens


def trim_to_eos(
    ids, eos_id: int, pad_id: int, extra_eos: tuple[int, ...] = ()
) -> list[int]:
    """Cut a generated id row at its first EOS/pad slot. ``extra_eos`` carries
    the active GenerationConfig.eos_ids — custom stop tokens are emitted
    before the done check fires, so they must be stripped like native EOS."""
    stops = {eos_id, pad_id, *extra_eos}
    out: list[int] = []
    for t in ids:
        if t in stops:
            break
        out.append(t)
    return out


def decodable_vocab_limit(tok, model_vocab_size: int) -> int:
    """Sampling range that can actually become text: the model head may be
    larger than the tokenizer (random-init 128k-vocab model + byte tokenizer
    in benches/tests), and a tokenizer may carry padded/special ids its
    decode() drops (ByteTokenizer ids >= 256). Sampling outside this range
    yields silently-vanishing tokens and empty summaries. Real HF
    tokenizers set decodable == vocab == model head, making this a no-op."""
    tok_limit = getattr(
        tok, "decodable_vocab_size", getattr(tok, "vocab_size", None)
    )
    return min(model_vocab_size, tok_limit or model_vocab_size)


_warned_unsampleable: set = set()


def sampling_vocab(tok, model_vocab_size: int, terminators=()):
    """(limit, allowed-or-None) restriction the engines apply to logits
    before sampling.

    ``limit`` extends :func:`decodable_vocab_limit` just far enough to cover
    every terminator id (EOS must stay *sampleable*, or a model trained to
    emit it — e.g. a ByteTokenizer fixture where eos_id=257 sits above the
    256 decodable bytes — can never stop early and always burns the full
    max_new budget). ``allowed`` is a bool [limit] numpy mask, or None when
    every id below ``limit`` is fair game (the common HF case); ids in
    [decodable, limit) that are not terminators stay blocked so sampling
    cannot emit text-invisible filler tokens.

    Terminators at or above the model head (e.g. a special id above a
    padded-head Qwen3's 151936 logits) are physically unsampleable —
    warn loudly instead of silently never terminating.
    """
    import numpy as np

    decodable = decodable_vocab_limit(tok, model_vocab_size)
    terms = sorted({int(t) for t in terminators})
    dropped = [t for t in terms if not 0 <= t < model_vocab_size]
    # the engines rebuild programs per (B, S, max_new) bucket; the condition
    # is a per-backend constant, so warn once per distinct case, not per
    # compile (the key is the condition itself, not the tok object)
    warn_key = (model_vocab_size, decodable, tuple(dropped))
    if dropped and warn_key not in _warned_unsampleable:
        _warned_unsampleable.add(warn_key)
        from ..core.logging import get_logger

        get_logger("vnsum.backend").warning(
            "terminator ids %s lie outside the model head (vocab %d) and "
            "can never be sampled; generation will run to max_new unless "
            "another terminator fires",
            dropped, model_vocab_size,
        )
    terms = [t for t in terms if 0 <= t < model_vocab_size]
    limit = max([decodable] + [t + 1 for t in terms])
    if limit == decodable:
        return limit, None
    allowed = np.zeros((limit,), dtype=bool)
    allowed[:decodable] = True
    allowed[terms] = True
    return limit, allowed


def terminator_ids(tok, gen) -> tuple[int, ...]:
    """The ONE effective stop-token set both engines use for done detection,
    sampleability (sampling_vocab), and detok stripping: the tokenizer's
    native EOS is always a terminator, custom GenerationConfig.eos_ids add
    to it rather than replace it. A token in only one of those three roles
    would either leak into text or burn the batch budget on thrown-away
    tokens — keep the policy in this single place."""
    return tuple(sorted({tok.eos_id, *gen.eos_ids}))


def mask_unsampleable(row_logits, allowed):
    """Apply a :func:`sampling_vocab` mask to a [B, limit] logits slice —
    blocked ids get float32 min so neither argmax nor categorical can pick
    them. ``allowed=None`` (everything decodable) is the identity. ONE copy
    shared by the one-chip and long-context engines so the masking semantics
    cannot drift between them."""
    if allowed is None:
        return row_logits
    import jax.numpy as jnp

    return jnp.where(allowed, row_logits, jnp.finfo(jnp.float32).min)


def resolve_max_new(
    max_new_tokens: int | None, config, backend_default: int
) -> int:
    """Decode-budget resolution shared by every backend: explicit argument >
    explicit config override > the backend's constructor default. A config
    passed only for temperature/eos (max_new_tokens=None) keeps the
    constructor budget."""
    if max_new_tokens is not None:
        return max_new_tokens
    if config is not None and config.max_new_tokens is not None:
        return config.max_new_tokens
    return backend_default


def get_backend(spec: str, **kwargs) -> Backend:
    """Factory: "fake", "ollama", "tpu", or "hf"."""
    if spec == "fake":
        from .fake import FakeBackend

        return FakeBackend(**kwargs)
    if spec == "ollama":
        from .ollama import OllamaBackend

        return OllamaBackend(**kwargs)
    if spec == "tpu":
        from .engine import TpuBackend

        return TpuBackend(**kwargs)
    if spec == "hf":
        from .hf import HFBackend

        return HFBackend(**kwargs)
    raise ValueError(f"unknown backend {spec!r} (use tpu|ollama|hf|fake)")
