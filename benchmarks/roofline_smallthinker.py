"""Operations and bytes of the SmallThinker family's kernels and of a whole
one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket), causal attention clipped to the window on
window layers, ``num_experts_per_tok`` experts a token, a decode step that
reads each weight it uses once, each expert it TOUCHES once and each row's
cache up to ``min(fill, window)`` on window layers. Keys of ``sizes`` are
the published ``config.json`` names as ``engine_setup_smallthinker.sizes_of``
gives them. ``experts`` are the counters of the dispatch itself
(``slots_routed``, ``slots_held``, ``decode_touched``,
``decode_layer_steps``): the distinct experts a decode step read are
counted on the device, not expected from a load.
"""
from __future__ import annotations


def attention_params(sizes: dict) -> int:
    """Weights of one layer's attention projections: q, k, v and o."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def expert_params(sizes: dict) -> int:
    """Weights of one expert (a ReGLU)."""
    return 3 * sizes["hidden_size"] * sizes["moe_ffn_hidden_size"]


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["moe_num_primary_experts"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def layer_params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes in one layer: attention, the router,
    and the experts its picks hit here (``share`` of its picks)."""
    return (attention_params(sizes) + router_params(sizes)
            + sizes["moe_num_active_primary_experts"] * share
            * expert_params(sizes))


def window_layers(sizes: dict) -> int:
    return sum(map(bool, sizes["sliding_window_layout"]))


def causal_pairs(n: int, window: int = 0) -> int:
    """(query, key) pairs of a causal sequence of n tokens: query i sees
    keys j <= i, with a window only the last ``window`` of them."""
    if not window or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length, every layer, clipped to
    the window on window layers: 2 operations a pair and head over the
    head's width, for the scores and again for the values."""
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    n_window = window_layers(sizes)
    n_global = sizes["num_hidden_layers"] - n_window
    w = sizes["sliding_window_size"]
    return per_pair * sum(n_global * causal_pairs(n) + n_window
                          * causal_pairs(n, w) for n in prompt_lens)


def decode_context(sizes: dict, context_lens: list[int], steps: int) -> int:
    """Cache slots read over ``steps`` steps, summed over rows and layers:
    step t of a row that started at n tokens reads n + t + 1 slots on a
    global layer and at most the window on a window layer."""
    n_window = window_layers(sizes)
    n_global = sizes["num_hidden_layers"] - n_window
    w = sizes["sliding_window_size"]
    total = 0
    for n in context_lens:
        # sum over t of (n + t + 1), and of min(n + t + 1, w)
        total += n_global * (steps * (n + 1) + steps * (steps - 1) // 2)
        below = max(0, min(steps, w - n - 1))   # steps still inside w
        total += n_window * (below * (n + 1) + below * (below - 1) // 2
                             + (steps - below) * w)
    return total


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps: every head against the slots
    its layer lets it see, each slot's keys and values (and, in an int8
    cache, their two float32 scales a KV head) read once."""
    ctx = decode_context(sizes, context_lens, steps)
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    scales = 8 if kv_bytes == 1 else 0
    return {"ops": 4 * sizes["num_attention_heads"] * hd * ctx,
            "bytes": kv * (2 * hd * kv_bytes + scales) * ctx}


def touched(sizes: dict, experts: dict, steps: int) -> float:
    """Experts read over a dispatch's ``steps`` decode steps, all layers:
    the device's count, scaled to these steps where it counted others."""
    if not experts.get("decode_layer_steps"):
        return 0.0
    return (experts["decode_touched"] / experts["decode_layer_steps"]
            * steps * sizes["num_hidden_layers"])


def expert_matmul(sizes: dict, experts: dict, prompt_tokens: int, rows: int,
                  steps: int, weight_bytes: float) -> dict:
    """The grouped expert product over one dispatch: operations of the
    prefill's slots, and for decode its operations and the bytes of the
    experts its steps touched, each read once a step."""
    per_expert = expert_params(sizes)
    slots = (sizes["moe_num_active_primary_experts"] * held_share(experts)
             * sizes["num_hidden_layers"])          # a token, all layers
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
    layers = sizes["num_hidden_layers"]
    token_params = layers * layer_params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["flash_prefill_attention"]["seconds"])
    # a decode step reads attention and router whole, the head, and the
    # experts it touches
    fixed = layers * (attention_params(sizes) + router_params(sizes)) + head
    dec_attn = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    decode_bytes = (fixed * precision["weights"] * steps
                    + ex["decode_bytes"] + dec_attn["bytes"])
    decode_ops = 2 * (token_params + head) * rows * steps + dec_attn["ops"]
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
