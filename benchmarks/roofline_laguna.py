"""Operations and bytes of the Laguna family's kernels and of a whole
one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket), each layer kind with its OWN query heads
(``num_attention_heads_per_layer``), causal attention clipped to the window
on sliding layers, ``num_experts_per_tok`` experts and the shared one a
token on sparse layers and the dense feed-forward on dense ones, a decode
step that reads each weight it uses once, each expert it TOUCHES once and
each row's cache up to ``min(fill, window)`` on sliding layers. Keys of
``sizes`` are the published ``config.json`` names as
``engine_setup_laguna.sizes_of`` gives them, the per-layer lists cut to the
depth that runs. ``experts`` are the counters of the dispatch itself
(``slots_routed``, ``slots_held``, ``decode_touched``,
``decode_layer_steps``): the distinct experts a decode step read are
counted on the device, not expected from a load.
"""
from __future__ import annotations


def layers(sizes: dict) -> list[tuple[int, bool, bool]]:
    """(query heads, sliding, sparse) of each layer that runs."""
    return [(h, kind == "sliding_attention", ffn == "sparse")
            for h, kind, ffn in zip(sizes["num_attention_heads_per_layer"],
                                    sizes["layer_types"],
                                    sizes["mlp_layer_types"])]


def sparse_layers(sizes: dict) -> int:
    return sum(sparse for _, _, sparse in layers(sizes))


def attention_params(sizes: dict, heads: int) -> int:
    """Weights of the attention projections of a layer with ``heads`` query
    heads: q, k, v, o and the head gate."""
    d, hd, kv = (sizes["hidden_size"], sizes["head_dim"],
                 sizes["num_key_value_heads"])
    return d * (heads + 2 * kv) * hd + heads * hd * d + d * heads


def expert_params(sizes: dict) -> int:
    """Weights of one routed expert (a SwiGLU)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def shared_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["shared_expert_intermediate_size"]


def dense_ffn_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["num_experts"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes, all layers: each layer's attention at
    its own heads; on a sparse layer the router, the shared expert and the
    experts its picks hit here (``share`` of them); on a dense one its
    feed-forward."""
    sparse = (router_params(sizes) + shared_params(sizes)
              + sizes["num_experts_per_tok"] * share * expert_params(sizes))
    return sum(attention_params(sizes, h)
               + (sparse if is_sparse else dense_ffn_params(sizes))
               for h, _, is_sparse in layers(sizes))


def fixed_params(sizes: dict) -> int:
    """Weights every decode step reads whatever the routers pick: all but
    the routed experts."""
    return sum(attention_params(sizes, h)
               + (router_params(sizes) + shared_params(sizes) if is_sparse
                  else dense_ffn_params(sizes))
               for h, _, is_sparse in layers(sizes))


def causal_pairs(n: int, window: int = 0) -> int:
    """(query, key) pairs of a causal sequence of n tokens: query i sees
    keys j <= i, with a window only the last ``window`` of them."""
    if not window or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length, every layer with its
    own heads, clipped to the window on sliding layers: 2 operations a
    pair and head over the head's width, for the scores and again for the
    values."""
    w = sizes["sliding_window"]
    return 4 * sizes["head_dim"] * sum(
        h * causal_pairs(n, w if sliding else 0)
        for h, sliding, _ in layers(sizes) for n in prompt_lens)


def context(n: int, steps: int, window: int = 0) -> int:
    """Cache slots one row that started at n tokens reads over ``steps``
    steps in one layer: step t reads n + t + 1, at most the window."""
    if not window:
        return steps * (n + 1) + steps * (steps - 1) // 2
    below = max(0, min(steps, window - n - 1))   # steps still inside it
    return (below * (n + 1) + below * (below - 1) // 2
            + (steps - below) * window)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps: every head of a layer
    against the slots the layer lets it see, each slot's keys and values
    (and, in an int8 cache, their two float32 scales a KV head) read
    once."""
    kv, hd, w = (sizes["num_key_value_heads"], sizes["head_dim"],
                 sizes["sliding_window"])
    scales = 8 if kv_bytes == 1 else 0
    ops = slots = 0
    for h, sliding, _ in layers(sizes):
        ctx = sum(context(n, steps, w if sliding else 0)
                  for n in context_lens)
        ops += 4 * h * hd * ctx
        slots += ctx
    return {"ops": ops, "bytes": kv * (2 * hd * kv_bytes + scales) * slots}


def touched(sizes: dict, experts: dict, steps: int) -> float:
    """Experts read over a dispatch's ``steps`` decode steps, all sparse
    layers: the device's count, scaled to these steps where it counted
    others."""
    if not experts.get("decode_layer_steps"):
        return 0.0
    return (experts["decode_touched"] / experts["decode_layer_steps"]
            * steps * sparse_layers(sizes))


def expert_matmul(sizes: dict, experts: dict, prompt_tokens: int, rows: int,
                  steps: int, weight_bytes: float) -> dict:
    """The grouped expert product over one dispatch: operations of the
    prefill's slots, and for decode its operations and the bytes of the
    experts its steps touched, each read once a step."""
    per_expert = expert_params(sizes)
    slots = (sizes["num_experts_per_tok"] * held_share(experts)
             * sparse_layers(sizes))               # a token, all layers
    return {"prefill_ops": 2 * per_expert * slots * prompt_tokens,
            "decode_ops": 2 * per_expert * slots * rows * steps,
            "decode_bytes": per_expert * weight_bytes
            * touched(sizes, experts, steps)}


def _matmul_peak(precision: dict, peaks: dict) -> float:
    return peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts: dict, prompt_lens: list[int],
                         steps: int) -> dict:
    """The least time of each of the family's three kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it."""
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    dec_ops = dec["ops"] / peaks["flops_bf16"]
    dec_mem = dec["bytes"] / peaks["hbm_bytes_per_s"]
    ex = expert_matmul(sizes, experts, sum(prompt_lens), len(prompt_lens),
                       steps, precision["weights"])
    peak = _matmul_peak(precision, peaks)
    ex_dec_ops = ex["decode_ops"] / peak
    ex_dec_mem = ex["decode_bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "flash_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens)
            / peaks["flops_bf16"], "bound": "compute"},
        "flash_decode_attention": {
            "seconds": max(dec_ops, dec_mem),
            "bound": "compute" if dec_ops >= dec_mem else "memory"},
        "expert_grouped_matmul": {
            "seconds": ex["prefill_ops"] / peak + max(ex_dec_ops, ex_dec_mem),
            "bound": "compute, then "
            + ("compute" if ex_dec_ops >= ex_dec_mem else "memory")},
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts: dict,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    matmuls over every real token (the head once a row), the kernels above,
    and decode steps that read every weight they use once."""
    token_params = params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["flash_prefill_attention"]["seconds"])
    # a decode step reads everything but the routed experts whole, the
    # head, and the experts it touches
    dec_attn = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    decode_bytes = ((fixed_params(sizes) + head) * precision["weights"] * steps
                    + ex["decode_bytes"] + dec_attn["bytes"])
    decode_ops = 2 * (token_params + head) * rows * steps + dec_attn["ops"]
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
