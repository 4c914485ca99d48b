"""Operations and bytes of the LFM2-MoE family's kernels and of a whole
one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket, and no piece of pads); each layer an operator
by ``layer_types`` — the convolution operator's four products and, at the
memory peak, its element-wise pass (``b``, ``c`` and ``x`` read, the gated
result written: the three taps and the two gates are arithmetic on the way)
— and a feed-forward, dense on the first ``num_dense_layers`` and
``num_experts_per_tok`` experts a token after them; causal attention over
the attention layers alone at the heads' own width; a decode step that
reads each weight it uses once, each expert it TOUCHES once, reads and
writes each row's tails once and reads each row's keys and values up to its
fill. Keys of ``sizes`` are the published ``config.json`` names as
``engine_setup_lfm2.sizes_of`` gives them. ``experts`` are the counters of
the dispatch itself (``slots_routed``, ``slots_held``, ``decode_touched``,
``decode_layer_steps``): the distinct experts a decode step read are
counted on the device, not expected from a load.
"""
from __future__ import annotations

from benchmarks.roofline_granite_h import (  # noqa: F401  (shapes alone)
    _larger,
    _matmul_peak,
    decode_context,
)


def layers_of(sizes: dict, kind: str) -> int:
    """Layers of one operator (``conv`` or ``full_attention``)."""
    return sum(k == kind for k in sizes["layer_types"])


def sparse_layers(sizes: dict) -> int:
    return len(sizes["layer_types"]) - sizes["num_dense_layers"]


def conv_params(sizes: dict) -> int:
    """Matmul weights of one convolution operator: in_proj (B | C | x) and
    out_proj."""
    return 4 * sizes["hidden_size"] ** 2


def attention_params(sizes: dict) -> int:
    """Weights of one attention operator: q, k, v and o."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def dense_ffn_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_params(sizes: dict) -> int:
    """Weights of one routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["num_experts"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def fixed_params(sizes: dict) -> int:
    """Weights every decode step reads whatever the routers pick: all but
    the routed experts (and the head, counted where it is used)."""
    return (layers_of(sizes, "conv") * conv_params(sizes)
            + layers_of(sizes, "full_attention") * attention_params(sizes)
            + sizes["num_dense_layers"] * dense_ffn_params(sizes)
            + sparse_layers(sizes) * router_params(sizes))


def params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes, all layers: ``fixed_params`` and on a
    sparse layer the experts its picks hit here (``share`` of them)."""
    return fixed_params(sizes) + (
        sparse_layers(sizes) * sizes["num_experts_per_tok"] * share
        * expert_params(sizes))


def shortconv_bytes_a_token(sizes: dict, act_bytes: float = 2) -> float:
    """The convolution operators' element-wise pass for one token, all
    convolution layers: b, c and x read, the gated result written."""
    return 4 * sizes["hidden_size"] * act_bytes * layers_of(sizes, "conv")


def tail_bytes_a_row(sizes: dict, act_bytes: float = 2) -> float:
    """One row's tails, every convolution layer."""
    return (layers_of(sizes, "conv") * (sizes["conv_L_cache"] - 1)
            * sizes["hidden_size"] * act_bytes)


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length on the attention layers:
    2 operations a pair and head over the head's width, for the scores and
    again for the values."""
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    return per_pair * layers_of(sizes, "full_attention") * sum(
        n * (n + 1) // 2 for n in prompt_lens)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps on the attention layers: each
    slot's keys and values (and, in an int8 cache, their two float32 scales
    a KV head) read once a step."""
    ctx = decode_context(context_lens, steps) * layers_of(
        sizes, "full_attention")
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    scales = 8 if kv_bytes == 1 else 0
    return {"ops": 4 * sizes["num_attention_heads"] * hd * ctx,
            "bytes": kv * (2 * hd * kv_bytes + scales) * ctx}


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


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts: dict, prompt_lens: list[int],
                         steps: int) -> dict:
    """The least time of each of the family's three kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it."""
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    peak = _matmul_peak(precision, peaks)
    ex_dec = _larger(ex["decode_ops"] / peak, ex["decode_bytes"] / hbm)
    return {
        "flash_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens) / bf16,
            "bound": "compute"},
        "flash_decode_attention": _larger(dec["ops"] / bf16,
                                          dec["bytes"] / hbm),
        "expert_grouped_matmul": {
            "seconds": ex["prefill_ops"] / peak + ex_dec["seconds"],
            "bound": "compute, then " + ex_dec["bound"]},
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts: dict,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    products over every real token at the matmul peak (the head once a
    row), the convolution operators' element-wise pass at the memory peak,
    the causal attention at the bf16 peak, and decode steps each the larger
    of its operations and its bytes — every weight but the routed experts
    once, the experts it touched, each row's tails read and written, each
    row's keys and values up to its fill."""
    token_params = params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    hbm = peaks["hbm_bytes_per_s"]
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    shortconv_bytes = shortconv_bytes_a_token(sizes) * tokens
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + shortconv_bytes / hbm
                 + kernels["flash_prefill_attention"]["seconds"])
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    tail_bytes = 2 * tail_bytes_a_row(sizes) * rows * steps
    decode_bytes = ((fixed_params(sizes) + head) * precision["weights"] * steps
                    + ex["decode_bytes"] + tail_bytes + dec["bytes"])
    decode_ops = 2 * (token_params + head) * rows * steps + dec["ops"]
    decode_s = max(decode_bytes / hbm, decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "shortconv_bytes": shortconv_bytes,
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_tail_bytes": tail_bytes,
            "decode_expert_bytes": ex["decode_bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
