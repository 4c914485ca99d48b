"""Operations and bytes of the Keye family's kernels and of a whole one-shot
dispatch, from its shapes, the configuration and the engine's expert
counters, and the least time a chip could take for them.

Counts the WORK, whatever implements it: real prompt tokens (not the padded
bucket); for a real query and layer the index scores of its VISIBLE keys
(2 x indexer_head_dim x indexer_num_heads operations a pair, at the bf16
peak), the selection by its bytes (a query's visible scores, float32, read
once), attention over ``min(visible, topk)`` keys (4 x head_dim x heads a
pair); a decode step that reads each weight it uses once, each expert it
TOUCHES once, a row's selected slots of keys and values and the row's
indexer keys up to its fill. A masked form that scores every causal key
reads LOW against this, and should. Keys of ``sizes`` are the published
``config.json`` names as ``engine_setup_keye.sizes_of`` gives them;
``experts`` the counters of the dispatch itself.
"""
from __future__ import annotations


def attention_params(sizes: dict) -> int:
    """Weights of one layer's attention projections: q, k, v and o."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def indexer_params(sizes: dict) -> int:
    """Weights of one layer's indexer: its queries, its key, the heads'
    weights."""
    sa = sizes["sa_config"]
    return sizes["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def expert_params(sizes: dict) -> int:
    """Weights of one expert (a SwiGLU)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["num_experts"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def layer_params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes in one layer."""
    return (attention_params(sizes) + indexer_params(sizes)
            + router_params(sizes)
            + sizes["num_experts_per_tok"] * share * expert_params(sizes))


def visible_pairs(n: int) -> int:
    """(query, key) pairs of a causal sequence of n tokens."""
    return n * (n + 1) // 2


def selected_pairs(n: int, topk: int) -> int:
    """... of which a query keeps at most ``topk``."""
    if n <= topk:
        return visible_pairs(n)
    return visible_pairs(topk) + (n - topk) * topk


def decode_pairs(context_lens: list[int], steps: int, topk: int = 0) -> int:
    """Slots over ``steps`` steps, summed over rows: step t of a row that
    started at n tokens sees n + t + 1 slots (with ``topk``: keeps at most
    that many)."""
    total = 0
    for n in context_lens:
        for t in range(steps):
            seen = n + t + 1
            total += min(seen, topk) if topk else seen
    return total


def index_select(sizes: dict, prompt_lens: list[int], steps: int) -> dict:
    """The selection kernel over one dispatch, all layers: the index
    scores' operations and the bytes of a query's visible scores and of the
    indexer keys a decode step reads (bf16)."""
    sa = sizes["sa_config"]
    layers = sizes["num_hidden_layers"]
    per_pair = 2 * sa["indexer_head_dim"] * sa["indexer_num_heads"]
    pre = sum(visible_pairs(n) for n in prompt_lens)
    dec = decode_pairs(prompt_lens, steps)
    return {"prefill_ops": per_pair * pre * layers,
            "prefill_bytes": 4 * pre * layers,
            "decode_ops": per_pair * dec * layers,
            "decode_bytes": (2 * sa["indexer_head_dim"] + 4) * dec * layers}


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    topk = sizes["sa_config"]["topk"]
    return per_pair * sizes["num_hidden_layers"] * sum(
        selected_pairs(n, topk) for n in prompt_lens)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode attention over ``steps`` steps: every head against the
    row's SELECTED slots, each slot's keys and values (and, in an int8
    cache, their two float32 scales a KV head) read once."""
    ctx = decode_pairs(context_lens, steps, sizes["sa_config"]["topk"]) \
        * sizes["num_hidden_layers"]
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
    """The grouped expert product over one dispatch, as
    ``roofline_smallthinker.expert_matmul`` counts it."""
    per_expert = expert_params(sizes)
    slots = (sizes["num_experts_per_tok"] * held_share(experts)
             * sizes["num_hidden_layers"])          # a token, all layers
    return {"prefill_ops": 2 * per_expert * slots * prompt_tokens,
            "decode_ops": 2 * per_expert * slots * rows * steps,
            "decode_bytes": per_expert * weight_bytes
            * touched(sizes, experts, steps)}


def _matmul_peak(precision: dict, peaks: dict) -> float:
    return peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]


def _bound(ops_s: float, mem_s: float) -> dict:
    return {"seconds": max(ops_s, mem_s),
            "bound": "compute" if ops_s >= mem_s else "memory"}


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts: dict, prompt_lens: list[int],
                         steps: int) -> dict:
    """The least time of each of the family's four kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row."""
    hbm = peaks["hbm_bytes_per_s"]
    sel = index_select(sizes, prompt_lens, steps)
    sel_pre = _bound(sel["prefill_ops"] / peaks["flops_bf16"],
                     sel["prefill_bytes"] / hbm)
    sel_dec = _bound(sel["decode_ops"] / peaks["flops_bf16"],
                     sel["decode_bytes"] / hbm)
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    ex = expert_matmul(sizes, experts, sum(prompt_lens), len(prompt_lens),
                       steps, precision["weights"])
    peak = _matmul_peak(precision, peaks)
    ex_dec = _bound(ex["decode_ops"] / peak, ex["decode_bytes"] / hbm)
    return {
        "dsa_index_select": {
            "seconds": sel_pre["seconds"] + sel_dec["seconds"],
            "bound": f"{sel_pre['bound']}, then {sel_dec['bound']}"},
        "dsa_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens)
            / peaks["flops_bf16"], "bound": "compute"},
        "dsa_decode_attention": _bound(dec["ops"] / peaks["flops_bf16"],
                                       dec["bytes"] / hbm),
        "expert_grouped_matmul": {
            "seconds": ex["prefill_ops"] / peak + ex_dec["seconds"],
            "bound": f"compute, then {ex_dec['bound']}"},
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts: dict,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    matmuls over every real token (the head once a row), the index scores
    and the attention over the selection, and decode steps that read every
    weight they use once, the experts they touch, the selected slots and
    the rows' indexer keys."""
    layers = sizes["num_hidden_layers"]
    token_params = layers * layer_params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    sel = index_select(sizes, prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + max(sel["prefill_ops"] / peaks["flops_bf16"],
                       sel["prefill_bytes"] / peaks["hbm_bytes_per_s"])
                 + kernels["dsa_prefill_attention"]["seconds"])
    fixed = layers * (attention_params(sizes) + indexer_params(sizes)
                      + router_params(sizes)) + head
    dec_attn = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    decode_bytes = (fixed * precision["weights"] * steps + ex["decode_bytes"]
                    + dec_attn["bytes"] + sel["decode_bytes"])
    decode_ops = (2 * (token_params + head) * rows * steps + dec_attn["ops"]
                  + sel["decode_ops"])
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_index_ops": sel["prefill_ops"],
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
