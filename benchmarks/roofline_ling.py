"""Operations and bytes of the Ling-3.0-flash family's kernels and of a
whole one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts the WORK, whatever implements it: real prompt tokens (not the padded
bucket, and no piece of pads); each layer a mixer — Kimi Delta Attention or,
the last of each ``layer_group_size``, latent attention — and a
feed-forward, dense on the first ``first_k_dense_replace`` layers and
``num_experts_per_tok`` routed experts a token (the share of them held here
by the device's own count) plus the shared expert after them. The delta
rule's prefill is counted in its CHUNKED form at the configuration's
``kda_chunk_size`` C, a head of ``d`` channels and token: the two triangles
of decayed products ``A`` and ``B`` (``C d`` each), the triangular solve for
``U`` and ``W`` (``2 C d``), ``B U`` (``C d``) and the three products with
the ``d x d`` state (``2 d^2`` each) — ``5 C d + 6 d^2`` — beside the bytes
it has to move: q, k, v read and the output written in the activations'
type, the float32 gate a key channel and beta read, a row's state read and
written once a layer. Its decode update reads and writes a row's float32
matrix state once a step and layer, and is bound by that. Latent attention
is ``roofline_deepseek_v2``'s count at this family's heads over the MLA
layers alone. Keys of ``sizes`` are the published ``config.json`` names as
``engine_setup_ling.sizes_of`` gives them. ``experts`` are the counters of
the dispatch itself (``slots_routed``, ``slots_held``, ``decode_touched``,
``decode_layer_steps``): the distinct experts a decode step read are counted
on the device, not expected from a load.
"""
from __future__ import annotations

from benchmarks.roofline_granite_h import (  # noqa: F401  (shapes alone)
    _larger,
    _matmul_peak,
    decode_context,
)


def mla_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] // sizes["layer_group_size"]


def kda_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - mla_layers(sizes)


def sparse_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def kda_width(sizes: dict) -> int:
    return sizes["num_attention_heads"] * sizes["head_dim"]


def kda_params(sizes: dict) -> int:
    """Matmul weights of one KDA mixer: q, k, v, the decay gate's matrix
    and the output projection, beta's and the head-wise gate's."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    return 5 * d * kda_width(sizes) + 2 * d * h


def mla_params(sizes: dict) -> int:
    """Weights of one MLA mixer: the whole query projection, the latent's
    down projection, both halves of its up projection, the head-wise gate
    and the output projection."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    rank, dn = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    dr, dv = sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    return (d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv)
            + d * h + h * dv * d)


def dense_ffn_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_params(sizes: dict) -> int:
    """Weights of one routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def shared_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes[
        "moe_shared_expert_intermediate_size"]


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["experts_total"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts and experts["slots_routed"] else 0.0)


def fixed_params(sizes: dict) -> int:
    """Weights every decode step reads whatever the routers pick: all but
    the routed experts (and the head, counted where it is used)."""
    return (kda_layers(sizes) * kda_params(sizes)
            + mla_layers(sizes) * mla_params(sizes)
            + sizes["first_k_dense_replace"] * dense_ffn_params(sizes)
            + sparse_layers(sizes) * (router_params(sizes)
                                      + shared_params(sizes)))


def params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes, all layers: ``fixed_params`` and on a
    sparse layer the experts its picks hit here (``share`` of them)."""
    return fixed_params(sizes) + (
        sparse_layers(sizes) * sizes["num_experts_per_tok"] * share
        * expert_params(sizes))


def kda_scan_a_token(sizes: dict, act_bytes: float = 2) -> dict:
    """The chunked delta rule for one token of one KDA layer, all heads:
    operations (``5 C d + 6 d^2`` a head) and the bytes that have to move
    (q, k, v in and the output out in the activations' type, the float32
    gate and beta in)."""
    h, d = sizes["num_attention_heads"], sizes["head_dim"]
    c = sizes["kda_chunk_size"]
    return {"ops": h * (5 * c * d + 6 * d * d),
            "bytes": 4 * h * d * act_bytes + 4 * h * d + 4 * h}


def kda_state_bytes_a_row(sizes: dict) -> int:
    """One row's float32 matrix state of ONE KDA layer."""
    return 4 * sizes["num_attention_heads"] * sizes["head_dim"] ** 2


def tail_bytes_a_row(sizes: dict, act_bytes: float = 2) -> float:
    """One row's convolution tails (q | k | v), every KDA layer."""
    return (kda_layers(sizes) * (sizes["short_conv_kernel_size"] - 1)
            * 3 * kda_width(sizes) * act_bytes)


def mla_prefill_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length on the MLA layers: per
    head n^2 / 2 pairs, 2 operations each over the query/key width and over
    the value width."""
    width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    return sizes["num_attention_heads"] * width * mla_layers(sizes) * sum(
        n * n for n in prompt_lens)


def mla_decode(sizes: dict, context_lens: list[int], steps: int,
               cache_bytes: float = 2) -> dict:
    """The absorbed decode kernel over ``steps`` steps on the MLA layers:
    every head against each row's latent rows (scores over rank + rope,
    values over rank), the latent cache read once a row and step."""
    rank, dr = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    ctx = decode_context(context_lens, steps) * mla_layers(sizes)
    return {"ops": 2 * sizes["num_attention_heads"] * (2 * rank + dr) * ctx,
            "bytes": (rank + dr) * cache_bytes * ctx}


def touched(sizes: dict, experts: dict, steps: int) -> float:
    """Experts read over a dispatch's ``steps`` decode steps, all sparse
    layers: the device's count, scaled to these steps where it counted
    others."""
    if not experts or not experts.get("decode_layer_steps"):
        return 0.0
    return (experts["decode_touched"] / experts["decode_layer_steps"]
            * steps * sparse_layers(sizes))


def expert_matmul(sizes: dict, experts: dict, prompt_tokens: int, rows: int,
                  steps: int, weight_bytes: float) -> dict:
    """The grouped expert product over one dispatch: operations of the
    prefill's slots held here, and for decode its operations and the bytes
    of the experts its steps touched, each read once a step."""
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
    """The least time of each of the family's five kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it."""
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    peak = _matmul_peak(precision, peaks)
    scan, layers = kda_scan_a_token(sizes), kda_layers(sizes)
    state = kda_state_bytes_a_row(sizes) * layers * rows
    dec = mla_decode(sizes, prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    ex_dec = _larger(ex["decode_ops"] / peak, ex["decode_bytes"] / hbm)
    return {
        "kda_prefill_scan": _larger(
            scan["ops"] * tokens * layers / bf16,
            (scan["bytes"] * tokens * layers + 2 * state) / hbm),
        # the state read and written once a row, layer and step
        "kda_decode_update": {"seconds": 2 * state * steps / hbm,
                              "bound": "memory"},
        "mla_prefill_attention": {
            "seconds": mla_prefill_ops(sizes, prompt_lens) / bf16,
            "bound": "compute"},
        "mla_decode_attention": _larger(dec["ops"] / bf16,
                                        dec["bytes"] / hbm),
        "expert_grouped_matmul": {
            "seconds": ex["prefill_ops"] / peak + ex_dec["seconds"],
            "bound": "compute, then " + ex_dec["bound"]},
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts: dict,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    products over every real token at the matmul peak (the head once a
    row), the delta rule's chunked scan and the causal latent attention at
    their own bounds, and decode steps each the larger of its operations
    and its bytes — every weight but the routed experts once, the experts
    it touched, each row's matrix states and tails read and written, each
    row's latent rows up to its fill."""
    token_params = params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    hbm = peaks["hbm_bytes_per_s"]
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["kda_prefill_scan"]["seconds"]
                 + kernels["mla_prefill_attention"]["seconds"])
    dec = mla_decode(sizes, prompt_lens, steps)
    state_bytes = (2 * kda_state_bytes_a_row(sizes) * kda_layers(sizes)
                   + 2 * tail_bytes_a_row(sizes)) * rows * steps
    decode_bytes = ((fixed_params(sizes) + head) * precision["weights"] * steps
                    + ex["decode_bytes"] + state_bytes + dec["bytes"])
    decode_ops = 2 * (token_params + head) * rows * steps + dec["ops"]
    decode_s = max(decode_bytes / hbm, decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "kda_scan_ops": kda_scan_a_token(sizes)["ops"] * tokens
            * kda_layers(sizes),
            "mla_prefill_ops": mla_prefill_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_state_bytes": state_bytes,
            "decode_expert_bytes": ex["decode_bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
