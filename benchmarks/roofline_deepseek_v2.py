"""Operations and bytes of the DeepSeek-V2 family's kernels and of a whole
one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket), causal attention (half the square), a decode
step that reads each expert it touches once and each row's latent cache
once. Keys of ``sizes`` are the published ``config.json`` names as
``engine_setup_deepseek_v2.sizes_of`` gives them. What the routed experts
do is taken from the counters (``experts``: ``slots_routed``,
``slots_held``, ``tokens`` per expert layer and held expert), not from an
assumed share: a token's picks hit the experts held here as often as they
did.
"""
from __future__ import annotations


def expert_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def attention_params(sizes: dict) -> int:
    """Weights of one layer's attention projections: q down and up, kv
    down, the key and value halves of kv up, and the output."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rq, rk = sizes["v_head_dim"], sizes["q_lora_rank"], sizes["kv_lora_rank"]
    return (d * rq + rq * h * (dn + dr) + d * (rk + dr)
            + rk * h * (dn + dv) + h * dv * d)


def expert_params(sizes: dict) -> int:
    """Weights of one routed expert (a SwiGLU)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def layer_params_a_token(sizes: dict, held_share: float) -> dict:
    """Matmul weights a token passes in one layer of each kind: a dense
    layer; an expert layer = attention + shared experts + router + the
    experts its picks hit HERE (``held_share`` of its picks, from the
    counters)."""
    d = sizes["hidden_size"]
    attn = attention_params(sizes)
    return {
        "dense": attn + 3 * d * sizes["intermediate_size"],
        "expert": attn + sizes["n_shared_experts"] * expert_params(sizes)
        + d * sizes["experts_total"]
        + sizes["num_experts_per_tok"] * held_share * expert_params(sizes),
    }


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length, every layer: per head
    n^2 / 2 pairs, 2 operations each over the query/key width and over the
    value width."""
    h = sizes["num_attention_heads"]
    width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    return sum(h * width * n * n for n in prompt_lens) \
        * sizes["num_hidden_layers"]


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     cache_bytes: float) -> dict:
    """The absorbed decode kernel over ``steps`` steps: every head against
    each row's latent rows (scores over rank + rope, values over rank), the
    latent cache read once a row and step."""
    rank, dr = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    rows = len(context_lens)
    ctx = sum(context_lens) * steps + rows * steps * (steps - 1) // 2
    layers = sizes["num_hidden_layers"]
    return {"ops": 2 * sizes["num_attention_heads"] * (2 * rank + dr)
                   * ctx * layers,
            "bytes": (rank + dr) * cache_bytes * ctx * layers}


def expected_touched(tokens: list[int], slots: float) -> float:
    """Distinct experts that ``slots`` picks touch when they fall on the
    experts as the counted ``tokens`` did (independent draws)."""
    total = sum(tokens)
    if not total:
        return 0.0
    return sum(1.0 - (1.0 - t / total) ** slots for t in tokens)


def expert_matmul(sizes: dict, experts: dict, prompt_tokens: int, rows: int,
                  steps: int, weight_bytes: float) -> dict:
    """The grouped expert product over one dispatch: operations of the
    prefill's held slots, and for decode the bytes of the experts a step
    touches (each once) and its operations. The split of the counters
    between the phases follows the tokens: prefill saw ``prompt_tokens`` of
    the dispatch's ``prompt_tokens + rows * steps``."""
    share = held_share(experts)
    per_expert = expert_params(sizes)
    k, layers = sizes["num_experts_per_tok"], expert_layers(sizes)
    prefill_slots = prompt_tokens * k * share * layers
    step_slots = rows * k * share            # a layer and step
    touched = sum(expected_touched(layer, step_slots)
                  for layer in experts["tokens"])    # all layers, one step
    return {"prefill_ops": 2 * per_expert * prefill_slots,
            "decode_ops": 2 * per_expert * step_slots * layers * steps,
            "decode_bytes": per_expert * weight_bytes * touched * steps}


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
    matmul_peak = peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]
    ex_dec_ops = ex["decode_ops"] / matmul_peak
    ex_dec_mem = ex["decode_bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "mla_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens)
            / peaks["flops_bf16"], "bound": "compute"},
        "mla_decode_attention": {
            "seconds": max(dec_ops, dec_mem),
            "bound": "compute" if dec_ops >= dec_mem else "memory"},
        "expert_grouped_matmul": {
            "seconds": ex["prefill_ops"] / matmul_peak
            + max(ex_dec_ops, ex_dec_mem),
            "bound": "compute, then "
            + ("compute" if ex_dec_ops >= ex_dec_mem else "memory")},
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts: dict,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    matmuls over every real token (the head once a row), the kernels above,
    and decode steps that read every weight they use once."""
    share = held_share(experts)
    per = layer_params_a_token(sizes, share)
    n_dense, n_expert = sizes["first_k_dense_replace"], expert_layers(sizes)
    token_params = n_dense * per["dense"] + n_expert * per["expert"]
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    matmul_peak = peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / matmul_peak
                 + kernels["mla_prefill_attention"]["seconds"])
    # a decode step reads attention, shared experts, router and the dense
    # layers whole, the head, and the experts it touches
    d = sizes["hidden_size"]
    fixed = (n_dense * per["dense"] + head + n_expert * (
        attention_params(sizes)
        + sizes["n_shared_experts"] * expert_params(sizes)
        + d * sizes["experts_total"]))
    dec_attn = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    decode_bytes = (fixed * precision["weights"] * steps
                    + ex["decode_bytes"] + dec_attn["bytes"])
    decode_ops = (2 * (token_params + head) * rows * steps + dec_attn["ops"])
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
