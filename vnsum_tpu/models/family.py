"""The seam between the one-shot generation program and a model family.

``backend/engine.py`` owns the program: chunked prefill, left padding,
sampling by (seed, uid, t), the early-exit decode loop, the statistics. What
differs between families is the layer stack and what it keeps between
steps, and that is what a ``Family`` supplies: ``forward``, the constructor
of the state a program carries (the KV cache; for latent attention the
latent cache and the expert counters; for a recurrence its state beside
the keys and values), the parameters' init, and the two attention
functions over the stacked cache, one per phase, and each layer's sliding
window where it has layers of that kind.

``family_of(cfg)`` resolves a family from the config's type: the module a
config class lives in names its family as ``FAMILY``. Entries of the engine
that a family cannot run yet are named in ``missing`` with what they lack,
and refuse it by that text; nothing falls back silently.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Callable


@dataclass(frozen=True)
class Family:
    name: str
    # (params, cfg, tokens, positions, cache, write_index, mask, *,
    #  last_only, stacked_attention_fn, **forward_kwargs) -> (logits, cache)
    forward: Callable
    # (cfg, batch, cache_len, *, quantized) -> the state a program carries
    init_cache: Callable
    init_params: Callable
    # (cfg, interpret) -> can the Pallas kernels take this config at all
    kernels_supported: Callable
    # (cfg, S, C) -> (prefill kernel usable, decode kernel usable)
    attention_supported: Callable
    # (cfg, mesh, interpret, pad_lens, layer_window, q_offset) -> stacked fn
    prefill_attention: Callable
    # (cfg, mesh, interpret, pad_lens, S, t, layer_window) -> stacked fn of
    # decode step t after a prompt bucket of S (its token at slot S + t)
    decode_attention: Callable
    # whether the cache has an int8 form (quantize_kv)
    int8_cache: bool = True
    # whether its prefill kernel is ops/flash_attention.py's, whose grid
    # cells EngineStats.prefill_blocks counts by class
    counts_prefill_blocks: bool = False
    # cfg -> each layer's sliding window in cache slots (0 = the layer
    # attends globally), or None where no layer has a window: what the
    # kernels clamp their key range with, layer by layer
    layer_windows: Callable = lambda cfg: None
    # cfg -> each layer's query heads a KV head where the layers differ in
    # them, or None where ``cfg.q_per_kv`` holds for every layer
    layer_groups: Callable = lambda cfg: None
    # cfg -> how many layers of keys and values the cache holds (a family
    # that mixes attention with other layers holds them for those alone; a
    # stack looped over its weights holds a layer a (pass, layer))
    attention_layers: Callable = lambda cfg: cfg.n_layers
    # (cfg, pad_lens, prefill spans, cache slots) -> {name: count} a
    # dispatch's prefill adds to ``EngineStats.prefill_blocks`` beside the
    # attention's cells, from the pads it was packed with (a scan's tokens,
    # the keys a latent kernel expands, the scores a looped stack's kernel
    # computed), or None; counted whether or not ``counts_prefill_blocks``
    prefill_counts: Callable | None = None
    # the fewest tokens a ROW PIECE of this family's prefill may hold
    # without its layers losing their pace, or None. Where it is set the
    # engine runs a prefill chunk a piece of the batch's rows at a time —
    # as few rows as hold this many tokens — and does not run a piece whose
    # rows hold nothing but left pad in that chunk
    # (``TpuBackend._prefill_forward``); ``forward`` and
    # ``prefill_attention`` then take ``cache_rows``, the piece's rows of the
    # batch's cache, which they read and write in place. None is the whole
    # batch a chunk: a family sets it after measuring its own layers
    prefill_piece_tokens: int | None = None
    # (cfg, kernels on, interpret) -> further keywords of ``forward``
    forward_kwargs: Callable = lambda cfg, kernels, interpret: {}
    # final state -> {name: device array} returned with a program's output,
    # or None when the family counts nothing
    counters: Callable | None = None
    # state after a forward -> what a parity check may see of the position
    # just scored beside its logits (``prefill_then_decode_logits`` collects
    # it after the prefill and after each step), or None
    row_record: Callable | None = None
    # engine entry -> what this family lacks for it
    missing: dict = field(default_factory=dict)
    # cfg -> the same for ONE configuration of a family that runs the entry
    # otherwise (a stack looped over its weights)
    config_missing: Callable = lambda cfg: {}

    def refuse(self, entry: str, cfg=None) -> None:
        """Raise if this family, or this configuration of it where ``cfg``
        is given, cannot run ``entry`` of the engine."""
        if entry in self.missing:
            raise NotImplementedError(
                f"the {self.name} family cannot run the engine's {entry} "
                f"yet: {self.missing[entry]}")
        lacks = self.config_missing(cfg) if cfg is not None else {}
        if entry in lacks:
            raise NotImplementedError(
                f"this {self.name} configuration cannot run the engine's "
                f"{entry} yet: {lacks[entry]}")


def family_of(cfg) -> Family:
    return importlib.import_module(type(cfg).__module__).FAMILY
