"""Long-context generation: ring-attention prefill + seq-sharded decode.

The reference cannot run a 54k-token document through its model at all — its
truncated strategy cuts inputs to 16384−2048 tokens
(runners/run_summarization_ollama.py:8-13, config
run_full_evaluation_pipeline.py:1004-1007), and the engine's one-chip path
(`backend.engine`) clips the same way because a single chip can't hold the KV
cache. This module removes that ceiling with sequence parallelism:

- **Prefill** runs the full prompt as ONE forward with the sequence dim
  sharded over the mesh `seq` axis: blockwise ring attention
  (`parallel.ring`, K/V blocks rotating via `ppermute`) so no device ever
  holds the full [S, S] scores or the full KV cache — an N-way seq axis
  multiplies the maximum prompt length by N.
- **Decode** keeps the prefill KV cache frozen and seq-sharded. Each step,
  every device computes an online-softmax partial over its local cache shard;
  partials merge over the seq axis with `pmax`/`psum` (log-sum-exp
  renormalization), then merge again with the attention over the small
  replicated cache of freshly generated tokens. New-token KV is appended only
  to that replicated decode cache — the sharded prefill cache is never
  touched again, so there is no resharding traffic in the loop.

The decode step reuses `models.llama.forward` via its `stacked_attention_fn`
seam (the decode-side cache write, RoPE, and MLP are the same code the
one-chip engine runs); the merge math is the only new device code.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.config import GenerationConfig
from ..core.logging import get_logger
from .base import (
    fold_seed,
    left_pad_batch,
    mask_unsampleable,
    resolve_max_new,
    sampling_vocab,
    terminator_ids,
    trim_to_eos,
)
from ..models.llama import (
    LlamaConfig,
    _embed_lookup,
    _lm_head_logits,
    _rmsnorm,
    _rope_cos_sin,
    cache_free_block,
    dequantize_cache_layer,
    forward,
    init_kv_cache,
    prefill_positions,
)
from ..models.sampling import sample_logits
from ..parallel.mesh import AXES
from ..parallel.ring import ring_attention
from ..text.tokenizer import Tokenizer, get_tokenizer

logger = get_logger("vnsum.long")

_NEG = jnp.float32(-1e30)


# -- prefill -----------------------------------------------------------------


def long_prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,     # [B, S] int32, left-padded; S sharded over `seq`
    pad_lens: jax.Array,   # [B] int32
    mesh: Mesh,
    *,
    remat: bool = True,
):
    """One ring-attention forward over the full (sharded) prompt.

    Returns (last_logits [B, V] f32, prefill_cache {"k","v": [L, B, KV, S,
    hd]}) — the ENGINE-NATIVE stacked layout, S sharded over the seq axis.
    Remat is on by default: prefill is one giant forward, and recomputing
    block activations is far cheaper than holding S-long intermediates for
    XLA's scheduler."""
    B, S = tokens.shape
    x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    positions = prefill_positions(pad_lens, S)
    cos, sin = _rope_cos_sin(cfg, positions)
    attention = partial(ring_attention, mesh=mesh, pad_lens=pad_lens)

    def block(x, lp):
        # ONE copy of the decoder math (models.llama.cache_free_block, the
        # same block forward_train scans) — here the k/v become the cache,
        # transposed PER LAYER to the engine-native [B, KV, S, hd] order
        # (ops/decode_attention's axis order) so the scan stacks the final
        # layout directly — a post-scan whole-cache transpose would hold
        # two full copies at the exact moment of peak HBM use
        x, (k, v) = cache_free_block(x, lp, cos, sin, cfg, attention)
        return x, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    if remat:
        block = jax.checkpoint(block)

    x, (ks, vs) = jax.lax.scan(block, x, params["layers"])
    cache_spec = NamedSharding(
        mesh, P(None, AXES.data, AXES.model, AXES.seq, None)
    )
    ks = jax.lax.with_sharding_constraint(ks, cache_spec)
    vs = jax.lax.with_sharding_constraint(vs, cache_spec)

    x = _rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = _lm_head_logits(x, params, cfg)
    return logits[:, 0], {"k": ks, "v": vs}


def quantize_prefill_cache(cache: dict) -> dict:
    """[L, B, KV, S, hd] bf16 cache -> int8 values + per-(layer, head,
    token) f32 scales. Decode streams every shard's cache each step, so this
    halves long-context decode HBM traffic (the engine's per-vector scheme,
    models.llama._quantize_kv — axis-agnostic over leading dims)."""
    from ..models.llama import _quantize_kv

    k8, ks = _quantize_kv(cache["k"])
    v8, vs = _quantize_kv(cache["v"])
    return {"k": k8, "v": v8, "ks": ks, "vs": vs}


# -- decode over the sharded prefill cache -----------------------------------


def _prefill_partial_local(
    q, k_loc, v_loc, pad_lens, k_scale=None, v_scale=None, *,
    q_per_kv, axis_name,
):
    """Per-device online-softmax partial over the local prefill-cache shard,
    merged across the seq axis inside (pmax/psum). q [B, H, hd];
    k_loc/v_loc [B, KV, S_loc, hd] (int8 when k_scale/v_scale [B, KV, S_loc]
    are given). Returns (o [B, H, hd] f32, m, l [B, H]). Dense fallback for
    head dims the Pallas kernel can't take (see _kernel_partial_local)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, hd = q.shape
    KV = k_loc.shape[1]
    S_loc = k_loc.shape[2]
    G = q_per_kv

    qg = q.reshape(B, KV, G, hd)
    if k_scale is not None:
        # int8 cache stays int8 into the MXU (the dtype convert fuses into
        # the tile load); the per-(head, token) scale is constant over the
        # contracted hd dim, so it factors out of the dot EXACTLY and
        # multiplies the scores instead — the f32-dequantized shard copy
        # never materializes.
        scores = (
            jnp.einsum("bkgh,bksh->bkgs", qg, k_loc.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
            * k_scale[:, :, None, :]
            / jnp.sqrt(jnp.float32(hd))
        )
    else:
        scores = (
            jnp.einsum("bkgh,bksh->bkgs", qg, k_loc,
                       preferred_element_type=jnp.float32)
            / jnp.sqrt(jnp.float32(hd))
        )
    k_pos = idx * S_loc + jnp.arange(S_loc)
    valid = k_pos[None, :] >= pad_lens[:, None]  # [B, S_loc]
    scores = jnp.where(valid[:, None, None], scores, _NEG)

    m = jnp.max(scores, axis=-1)                      # [B, KV, G]
    p = jnp.where(valid[:, None, None], jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    if v_scale is not None:
        # same trick on the value side: scale the probabilities along s
        # (constant over hd), keep v int8 in the matmul
        pv = p * v_scale[:, :, None, :]
        o = jnp.einsum("bkgs,bksh->bkgh", pv, v_loc.astype(jnp.float32))
    else:
        o = jnp.einsum("bkgs,bksh->bkgh", p, v_loc.astype(jnp.float32))

    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    o_g = jax.lax.psum(o * corr[..., None], axis_name)
    return (
        o_g.reshape(B, H, hd),
        m_g.reshape(B, H),
        l_g.reshape(B, H),
    )


def _kernel_partial_local(
    q, k_all, v_all, pad_lens, layer_idx, k_scale=None, v_scale=None, *,
    q_per_kv, axis_name, interpret,
):
    """Kernelized shard-local partial (VERDICT r3 #5): the stacked-cache
    decode kernel runs on each device's cache shard — layer selection via
    scalar prefetch (no per-layer extraction copy), int8 K/V streamed with
    in-kernel dequant — and its unnormalized (o, m, l) state LSE-merges
    across the seq axis exactly like the dense partial's.

    q [B, H, hd]; k_all/v_all the WHOLE local stacked shard
    [L, B, KV, S_loc, hd] (+ scales [L, B, KV, S_loc])."""
    idx = jax.lax.axis_index(axis_name)
    S_loc = k_all.shape[3]
    # left-pad boundary in this shard's local coordinates: rows whose global
    # pad falls past the shard mask out entirely (the kernel then emits
    # m=-inf, l=0 — inert in the merge)
    pads_local = jnp.clip(pad_lens - idx * S_loc, 0, S_loc)
    cache = {"k": k_all, "v": v_all}
    if k_scale is not None:
        cache.update(ks=k_scale, vs=v_scale)
    from ..ops.decode_attention import flash_decode_attention

    o, m, l = flash_decode_attention(
        q[:, None], cache, layer_idx, pads_local, S_loc - 1, q_per_kv,
        return_partials=True, interpret=interpret,
    )
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    o_g = jax.lax.psum(o * corr[..., None], axis_name)
    return o_g, m_g, l_g


def make_long_decode_attention(
    mesh: Mesh, prefill_cache: dict, pad_lens: jax.Array, q_per_kv: int,
    *, decode_kernel: str | bool = "auto", interpret: bool = False,
):
    """Build the merged attention for models.llama.forward's
    ``stacked_attention_fn`` seam: the returned ``attention(q, cache,
    layer_idx, t)`` attends over BOTH the frozen seq-sharded prefill cache
    (closure) and the small replicated decode cache, valid slots 0..t; the
    decode loop binds ``t`` per step via a lambda.

    ``decode_kernel`` "auto" runs the Pallas stacked-cache kernel on each
    shard when the head dim is lane-aligned (or under interpret), else the
    dense einsum partial — the kernel consumes the whole stacked shard with
    the layer chosen by scalar prefetch, so the per-step per-layer
    extraction copy of the shard never materializes."""
    quantized = "ks" in prefill_cache
    hd = prefill_cache["k"].shape[-1]
    if decode_kernel == "auto":
        # real kernels need Mosaic on the mesh's devices AND a lane-aligned
        # head dim (supports_decode — ONE copy of that rule); interpret mode
        # simulates them anywhere
        from ..ops.decode_attention import supports_decode

        S_total = prefill_cache["k"].shape[3]
        mesh_platform = next(iter(mesh.devices.flat)).platform
        decode_kernel = interpret or (
            mesh_platform == "tpu" and supports_decode(S_total, hd)
        )
    logger.info(
        "long-context decode attention path: %s",
        "kernel" if decode_kernel else "dense",
    )
    out_specs = (
        P(AXES.data, AXES.model, None),
        P(AXES.data, AXES.model),
        P(AXES.data, AXES.model),
    )
    if decode_kernel:
        kv_spec = P(None, AXES.data, AXES.model, AXES.seq, None)
        scale_spec = P(None, AXES.data, AXES.model, AXES.seq)
        in_specs = [
            P(AXES.data, AXES.model, None), kv_spec, kv_spec, P(AXES.data),
            P(),
        ]
        if quantized:
            in_specs += [scale_spec, scale_spec]
        partial_fn = shard_map(
            partial(
                _kernel_partial_local, q_per_kv=q_per_kv,
                axis_name=AXES.seq, interpret=interpret,
            ),
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            check_vma=False,
        )
    else:
        kv_spec = P(AXES.data, AXES.model, AXES.seq, None)
        scale_spec = P(AXES.data, AXES.model, AXES.seq)
        in_specs = [
            P(AXES.data, AXES.model, None), kv_spec, kv_spec, P(AXES.data),
        ]
        if quantized:
            in_specs += [scale_spec, scale_spec]
        partial_fn = shard_map(
            partial(
                _prefill_partial_local, q_per_kv=q_per_kv, axis_name=AXES.seq
            ),
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
        )

    def attention(q, cache, layer_idx, t):
        """q [B, 1, H, hd]; cache = small decode cache [L, B, KV, C, hd];
        attends prefill shards + decode slots 0..t."""
        B, _, H, hd = q.shape
        q1 = q[:, 0]

        if decode_kernel:
            args = [
                q1, prefill_cache["k"], prefill_cache["v"], pad_lens,
                jnp.asarray(layer_idx, jnp.int32),
            ]
            if quantized:
                args += [prefill_cache["ks"], prefill_cache["vs"]]
        else:

            def layer(name):
                return jax.lax.dynamic_index_in_dim(
                    prefill_cache[name], layer_idx, 0, keepdims=False
                )

            args = [q1, layer("k"), layer("v"), pad_lens]
            if quantized:
                args += [layer("ks"), layer("vs")]
        o1, m1, l1 = partial_fn(*args)

        # decode-cache partial (replicated math; C = max_new is small)
        # [B, KV, C, hd], a head apiece however the cache tiles them
        k_dec, v_dec = dequantize_cache_layer(cache, layer_idx, hd)
        KV = k_dec.shape[1]
        C = k_dec.shape[2]
        qg = q1.reshape(B, KV, q_per_kv, hd)
        scores = (
            jnp.einsum("bkgh,bkch->bkgc", qg, k_dec.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
            / jnp.sqrt(jnp.float32(hd))
        )
        valid = (jnp.arange(C) <= t)[None, None, None, :]
        scores = jnp.where(valid, scores, _NEG)
        m2 = jnp.max(scores, axis=-1)
        p = jnp.where(valid, jnp.exp(scores - m2[..., None]), 0.0)
        l2 = jnp.sum(p, axis=-1)
        o2 = jnp.einsum("bkgc,bkch->bkgh", p, v_dec.astype(jnp.float32))
        m2 = m2.reshape(B, H)
        l2 = l2.reshape(B, H)
        o2 = o2.reshape(B, H, hd)

        # log-sum-exp merge of the two partials
        m = jnp.maximum(m1, m2)
        c1 = jnp.exp(m1 - m)
        c2 = jnp.exp(m2 - m)
        l = l1 * c1 + l2 * c2
        o = o1 * c1[..., None] + o2 * c2[..., None]
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out[:, None].astype(q.dtype)  # [B, 1, H, hd]

    return attention


# -- full generation program -------------------------------------------------


def generate_long_tokens(
    params: dict,
    cfg: LlamaConfig,
    mesh: Mesh,
    tokens: jax.Array,     # [B, S] left-padded, S % seq_axis == 0
    pad_lens: jax.Array,   # [B]
    max_new: int,
    *,
    eos_ids,
    pad_id: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int = 0,
    quantize_kv: bool = False,
    vocab_limit: int = 0,
    vocab_allowed=None,
    decode_kernel: str | bool = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Traceable end-to-end long-context generation; returns [B, max_new].

    jit this with params/tokens shardings; the prompt may exceed single-chip
    memory by the seq-axis factor. ``quantize_kv`` stores the frozen prefill
    cache int8 (decode streams every shard per step — traffic halves, and
    the freed HBM doubles the context that fits)."""
    B, S = tokens.shape
    eos = jnp.asarray(list(eos_ids), dtype=jnp.int32)
    # 0 = full model vocab; a smaller tokenizer vocab restricts sampling to
    # decodable ids (same rationale as engine.py's vocab_limit). The bool
    # ``vocab_allowed`` mask keeps terminators above the decodable range
    # sampleable while blocking text-invisible filler ids (base.sampling_vocab)
    V = vocab_limit or None
    allowed = None if vocab_allowed is None else jnp.asarray(vocab_allowed)

    def restrict(row_logits):  # [B, V]
        return mask_unsampleable(row_logits, allowed)

    last_logits, prefill_cache = long_prefill(
        params, cfg, tokens, pad_lens, mesh
    )
    if quantize_kv:
        prefill_cache = quantize_prefill_cache(prefill_cache)
    key = jax.random.key(seed)
    key, sub = jax.random.split(key)
    first = sample_logits(
        restrict(last_logits[:, :V]), sub, temperature, top_k, top_p
    )
    done0 = pad_lens == S  # all-pad filler rows start done

    attention = make_long_decode_attention(
        mesh, prefill_cache, pad_lens, cfg.q_per_kv,
        decode_kernel=decode_kernel, interpret=interpret,
    )
    decode_cache0 = init_kv_cache(cfg, B, max_new)
    out0 = jnp.full((B, max_new), pad_id, dtype=jnp.int32)

    def cond(carry):
        t, _cur, _cache, done, _key, _out = carry
        return (t < max_new) & ~jnp.all(done)

    def body(carry):
        t, cur, cache, done, key, out = carry
        emit = jnp.where(done, pad_id, cur)
        out = jax.lax.dynamic_update_slice(out, emit[:, None], (0, t))
        done = done | jnp.isin(cur, eos)
        pos = (S - pad_lens) + t
        # decode-cache mask is handled inside the attention (slots 0..t);
        # forward()'s own mask argument covers only dense fallbacks — pass
        # the same slot validity for shape consistency
        mask_t = (jnp.arange(max_new) <= t)[None, None, :].repeat(B, axis=0)
        logits, cache = forward(
            params, cfg, cur[:, None], pos[:, None], cache, t, mask_t,
            stacked_attention_fn=lambda q, c, li: attention(q, c, li, t),
        )
        key, sub = jax.random.split(key)
        nxt = sample_logits(
            restrict(logits[:, -1, :V]), sub, temperature, top_k, top_p
        )
        return (t + 1, nxt, cache, done, key, out)

    *_, out = jax.lax.while_loop(
        cond, body, (jnp.int32(0), first, decode_cache0, done0, key, out0)
    )
    return out


class LongContextBackend:
    """Backend-protocol generation over a seq-sharded mesh: prompts up to
    (seq_axis × single-chip limit) tokens run UN-truncated. Pair with
    strategies.truncated (max_context set to the long limit) to summarize
    VN-LongSum's 54k-token docs in one shot — a capability the reference's
    16k context fundamentally cannot match."""

    name = "tpu"
    label = "tpu+long-context"

    def __init__(
        self,
        model_config: LlamaConfig | None = None,
        mesh: Mesh | None = None,
        tokenizer: str | Tokenizer = "byte",
        params=None,
        batch_size: int = 1,
        max_new_tokens: int = 1024,
        max_total_tokens: int | None = None,
        generation: GenerationConfig | None = None,
        seed: int = 0,
        quantize: bool = False,
        quantize_kv: bool = False,
        decode_kernel: str | bool = "auto",
        interpret: bool = False,
    ) -> None:
        from ..models.llama import init_params, llama32_3b

        from ..core.jax_cache import enable_compilation_cache

        enable_compilation_cache()
        if model_config is not None:
            from ..models.family import family_of

            family_of(model_config).refuse(
                "long-context backend", model_config)
        if (model_config is not None) and model_config.sliding_window:
            raise NotImplementedError(
                "LongContextBackend runs ring attention (global K/V "
                "streaming); sliding-window (Gemma local) configs are "
                "one-chip-engine only"
            )
        if mesh is None or AXES.seq not in mesh.shape:
            raise ValueError(
                "LongContextBackend needs a mesh with a 'seq' axis — that "
                "axis is what multiplies the context ceiling"
            )
        # same rule as TpuBackend: no quiet dense path on whatever platform
        # is there. Off-chip callers pass interpret=True (emulated kernel)
        # or decode_kernel=False (the dense partial, by name)
        self.platform = next(iter(mesh.devices.flat)).platform
        if decode_kernel is not False and not interpret and self.platform != "tpu":
            raise RuntimeError(
                f"LongContextBackend found platform {self.platform!r}, not "
                "'tpu': its decode kernel needs the chip. Pass "
                "interpret=True to emulate it, or decode_kernel=False for "
                "the dense path."
            )
        self.cfg = model_config or llama32_3b()
        self.mesh = mesh
        self.tok = get_tokenizer(tokenizer) if isinstance(tokenizer, str) else tokenizer
        # prompts here are near the memory ceiling by definition — default to
        # one row at a time; raise only when the per-row cache share allows.
        # Rounded DOWN to a data-axis multiple (the value is the caller's
        # HBM high-water mark) — except that at least data_size rows must
        # exist to shard over the data axis at all, so a smaller request is
        # floored up. Either adjustment is loud: memory budgets depend on it.
        data_size = mesh.shape.get(AXES.data, 1)
        self.batch_size = max(
            data_size, (max(batch_size, 1) // data_size) * data_size
        )
        if self.batch_size != batch_size:
            logger.warning(
                "batch_size adjusted %d -> %d (mesh data axis %d needs a "
                "divisible row count); per-dispatch memory scales with it",
                batch_size, self.batch_size, data_size,
            )
        self.max_new_tokens = max_new_tokens
        # the long path deliberately ignores cfg.max_seq_len (that is the
        # ONE-CHIP ceiling); the real limit is RoPE numerical range + HBM
        self.max_total_tokens = max_total_tokens or (
            self.cfg.max_seq_len * mesh.shape[AXES.seq]
        )
        if max_new_tokens >= self.max_total_tokens:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} must be < "
                f"max_total_tokens={self.max_total_tokens}"
            )
        self.gen_cfg = generation or GenerationConfig()
        self._seed = seed
        self._dispatch = 0
        self._fns: dict = {}
        self.quantize_kv = bool(quantize_kv)
        self.decode_kernel = decode_kernel
        self.interpret = bool(interpret)
        if params is None:
            from ..models import jitted_init

            params = jitted_init(init_params, self.cfg, seed)
        if quantize:
            from ..models.quant import is_quantized, quantize_params

            if not is_quantized(params):
                params = jax.jit(quantize_params)(params)
        from ..parallel.sharding import shard_params

        self.params = shard_params(params, mesh, self.cfg.tie_embeddings)

    def _bucket(self, n: int) -> int:
        """Round S up to a multiple of (seq_axis × 128) with pow2-ish steps
        to bound recompiles."""
        step = self.mesh.shape[AXES.seq] * 128
        b = step
        while b < n:
            b *= 2
        return min(b, ((self.max_total_tokens + step - 1) // step) * step)

    def _next_seed(self, gen: GenerationConfig) -> int:
        s = fold_seed(gen.seed, self._seed, self._dispatch)
        self._dispatch += 1
        return s

    def generate(
        self,
        prompts: list[str],
        *,
        max_new_tokens: int | None = None,
        config: GenerationConfig | None = None,
        references: list[str | None] | None = None,  # spec metadata; unused
        cache_hints: list[str | None] | None = None,  # cache metadata; unused
    ) -> list[str]:
        gen = config or self.gen_cfg
        max_new = resolve_max_new(max_new_tokens, gen, self.max_new_tokens)
        if max_new >= self.max_total_tokens:
            raise ValueError(
                f"max_new_tokens={max_new} must be < "
                f"max_total_tokens={self.max_total_tokens}"
            )
        if not prompts:
            return []
        data_size = self.mesh.shape.get(AXES.data, 1)

        encoded = []
        for p in prompts:
            ids = self.tok.encode(p, add_bos=True)
            if len(ids) > self.max_total_tokens - max_new:
                ids = ids[: self.max_total_tokens - max_new]
            encoded.append(ids)

        # length-sorted groups of at most batch_size rows, each bucketed for
        # ITS longest member: prompts at this scale sit near the HBM ceiling,
        # so one giant longest-prompt batch would OOM and make every short
        # prompt pay the longest prefill
        order = sorted(range(len(encoded)), key=lambda i: len(encoded[i]))
        results: list[str | None] = [None] * len(encoded)
        for start in range(0, len(order), self.batch_size):
            group = order[start : start + self.batch_size]
            S = self._bucket(max(len(encoded[i]) for i in group))
            B = data_size
            while B < len(group):
                B *= 2
            # batch_size is the caller's HBM high-water mark — never exceed
            # it just to reach a power of two (batch_size % data == 0 is
            # checked at construction, so the clamp stays shardable)
            B = min(B, self.batch_size)
            tokens, pad_lens = left_pad_batch(
                [encoded[i] for i in group], B, S, self.tok.pad_id
            )

            fn = self._get_fn(B, S, max_new, gen)
            t0 = time.time()
            out = np.asarray(
                fn(self.params, tokens, pad_lens, self._next_seed(gen))
            )
            logger.info(
                "long generate: B=%d S=%d new=%d in %.1fs",
                B, S, max_new, time.time() - t0,
            )
            for row, i in enumerate(group):
                ids = trim_to_eos(
                    out[row].tolist(), self.tok.eos_id, self.tok.pad_id,
                    tuple(gen.eos_ids),
                )
                results[i] = self.tok.decode(ids).strip()
        return results  # type: ignore[return-value]

    def _get_fn(self, B: int, S: int, max_new: int, gen: GenerationConfig):
        key = (B, S, max_new, gen.with_(seed=0))
        if key not in self._fns:
            from ..models.quant import is_quantized
            from ..parallel.sharding import param_shardings

            ns = lambda spec: NamedSharding(self.mesh, spec)
            eos_ids = terminator_ids(self.tok, gen)
            vocab_limit, vocab_allowed = sampling_vocab(
                self.tok, self.cfg.vocab_size, eos_ids
            )

            def program(params, tokens, pad_lens, seed):
                return generate_long_tokens(
                    params, self.cfg, self.mesh, tokens, pad_lens, max_new,
                    eos_ids=eos_ids, pad_id=self.tok.pad_id,
                    temperature=gen.temperature, top_k=gen.top_k,
                    top_p=gen.top_p, seed=seed,
                    quantize_kv=self.quantize_kv,
                    vocab_limit=vocab_limit,
                    vocab_allowed=vocab_allowed,
                    decode_kernel=self.decode_kernel,
                    interpret=self.interpret,
                )

            self._fns[key] = jax.jit(
                program,
                in_shardings=(
                    param_shardings(
                        self.mesh, self.cfg.tie_embeddings,
                        is_quantized(self.params),
                        qk_norm=self.cfg.qk_norm,
                        sandwich_norms=self.cfg.sandwich_norms,
                    ),
                    ns(P(AXES.data, AXES.seq)),
                    ns(P(AXES.data)),
                    None,
                ),
                out_shardings=ns(P(AXES.data, None)),
            )
            logger.info("built long-context fn B=%d S=%d new=%d", B, S, max_new)
        return self._fns[key]

    def count_tokens(self, text: str) -> int:
        return self.tok.count(text)

    def count_tokens_batch(self, texts: list[str]) -> list[int]:
        return self.tok.count_batch(texts)
