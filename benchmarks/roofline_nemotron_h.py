"""Operations and bytes of the Nemotron-H family's kernels and of a whole
one-shot dispatch, from its shapes, the configuration and the engine's
expert counters, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket, and no chunk of pads); each layer ONE mixer
by ``hybrid_override_pattern``; the chunked scan as published (chunks of
``chunk_size``, ``C B^T`` once a GROUP and chunk) whatever chunk the kernel
runs; causal attention over the attention layers alone; the routed experts
at the PUBLISHED width (1,856) whatever width they are stored at,
``num_experts_per_tok`` of them and the shared one a token on sparse layers;
a decode step that reads each weight it uses once, each expert it TOUCHES
once, reads and writes each row's recurrent state once and reads each row's
keys and values up to its fill. Keys of ``sizes`` are the published
``config.json`` names as ``engine_setup_nemotron_h.sizes_of`` gives them,
the pattern cut to the depth that runs. ``experts`` are the counters of the
dispatch itself (``slots_routed``, ``slots_held``, ``decode_touched``,
``decode_layer_steps``): the distinct experts a decode step read are counted
on the device, not expected from a load.
"""
from __future__ import annotations

from benchmarks.roofline_granite_h import (  # noqa: F401  (shapes alone)
    _larger,
    _matmul_peak,
    decode_context,
)


def layers_of(sizes: dict, kind: str) -> int:
    """Layers of one kind (``M``, ``E`` or ``*``) among those that run."""
    return sizes["hybrid_override_pattern"].count(kind)


def inner(sizes: dict) -> int:
    return sizes["mamba_num_heads"] * sizes["mamba_head_dim"]


def mamba_params(sizes: dict) -> int:
    """Matmul weights of one Mamba-2 mixer: in_proj (z | xBC | dt, with B
    and C of every group) and out_proj."""
    d = sizes["hidden_size"]
    bc = 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return (d * (2 * inner(sizes) + bc + sizes["mamba_num_heads"])
            + inner(sizes) * d)


def attention_params(sizes: dict) -> int:
    """Weights of one attention mixer: q, k, v and o."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def expert_params(sizes: dict) -> int:
    """Weights of one routed expert: two matrices, no gate."""
    return 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def shared_params(sizes: dict) -> int:
    return (2 * sizes["hidden_size"]
            * sizes["moe_shared_expert_intermediate_size"])


def router_params(sizes: dict) -> int:
    return sizes["hidden_size"] * sizes["n_routed_experts"]


def held_share(experts: dict) -> float:
    return (experts["slots_held"] / experts["slots_routed"]
            if experts["slots_routed"] else 0.0)


def fixed_params(sizes: dict) -> int:
    """Weights every decode step reads whatever the routers pick: all but
    the routed experts."""
    return (layers_of(sizes, "M") * mamba_params(sizes)
            + layers_of(sizes, "*") * attention_params(sizes)
            + layers_of(sizes, "E") * (router_params(sizes)
                                       + shared_params(sizes)))


def params_a_token(sizes: dict, share: float) -> float:
    """Matmul weights a token passes, all layers: ``fixed_params`` and on a
    sparse layer the experts its picks hit here (``share`` of them)."""
    return fixed_params(sizes) + (
        layers_of(sizes, "E") * sizes["num_experts_per_tok"] * share
        * expert_params(sizes))


def scan_a_token(sizes: dict, act_bytes: float = 2) -> dict:
    """The chunked scan for one token in one layer. Operations: the masked
    product over the chunk (2 Q inner), the state's readout and its update
    (2 N inner each) and C B^T once a group (2 Q N G). Bytes: X read and Y
    written, every group's B and C, dt and its running sum."""
    q, n, g = sizes["chunk_size"], sizes["ssm_state_size"], sizes["n_groups"]
    return {"ops": 2 * q * inner(sizes) + 4 * n * inner(sizes)
            + 2 * q * n * g,
            "bytes": (2 * inner(sizes) + 2 * g * n) * act_bytes
            + 3 * 4 * sizes["mamba_num_heads"]}


def state_bytes_a_row(sizes: dict) -> int:
    """One row's recurrent state, every Mamba layer, float32."""
    return (layers_of(sizes, "M") * sizes["ssm_state_size"]
            * inner(sizes) * 4)


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length on the attention layers:
    2 operations a pair and head over the head's width, for the scores and
    again for the values."""
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    return per_pair * layers_of(sizes, "*") * sum(
        n * (n + 1) // 2 for n in prompt_lens)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps on the attention layers: each
    slot's keys and values (and, in an int8 cache, their two float32 scales
    a KV head) read once a step."""
    ctx = decode_context(context_lens, steps) * layers_of(sizes, "*")
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    scales = 8 if kv_bytes == 1 else 0
    return {"ops": 4 * sizes["num_attention_heads"] * hd * ctx,
            "bytes": kv * (2 * hd * kv_bytes + scales) * ctx}


def decode_state(sizes: dict, rows: int, steps: int) -> dict:
    """The state update over ``steps`` steps: every row's state of every
    Mamba layer read and written once a step; a decay, an outer product and
    a readout an element."""
    elements = state_bytes_a_row(sizes) // 4 * rows * steps
    return {"ops": 5 * elements, "bytes": 2 * 4 * elements}


def touched(sizes: dict, experts: dict, steps: int) -> float:
    """Experts read over a dispatch's ``steps`` decode steps, all sparse
    layers: the device's count, scaled to these steps where it counted
    others."""
    if not experts.get("decode_layer_steps"):
        return 0.0
    return (experts["decode_touched"] / experts["decode_layer_steps"]
            * steps * layers_of(sizes, "E"))


def expert_matmul(sizes: dict, experts: dict, prompt_tokens: int, rows: int,
                  steps: int, weight_bytes: float) -> dict:
    """The grouped expert product over one dispatch: operations of the
    prefill's slots, and for decode its operations and the bytes of the
    experts its steps touched, each read once a step."""
    per_expert = expert_params(sizes)
    slots = (sizes["num_experts_per_tok"] * held_share(experts)
             * layers_of(sizes, "E"))              # a token, all layers
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
    scan = scan_a_token(sizes)
    scanned = tokens * layers_of(sizes, "M")
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    upd = decode_state(sizes, rows, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    peak = _matmul_peak(precision, peaks)
    ex_dec = _larger(ex["decode_ops"] / peak, ex["decode_bytes"] / hbm)
    return {
        "ssd_prefill_scan": _larger(scan["ops"] * scanned / bf16,
                                    scan["bytes"] * scanned / hbm),
        "ssm_decode_update": _larger(upd["ops"] / bf16, upd["bytes"] / hbm),
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
    row), the scan and the causal attention at the bf16 peak, and decode
    steps each the larger of its operations and its bytes — every weight
    but the routed experts once, the experts it touched, the state read and
    written, each row's keys and values up to its fill."""
    token_params = params_a_token(sizes, held_share(experts))
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    ex = expert_matmul(sizes, experts, tokens, rows, steps,
                       precision["weights"])
    prefill_matmul_ops = 2 * token_params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["ssd_prefill_scan"]["seconds"]
                 + kernels["flash_prefill_attention"]["seconds"])
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    upd = decode_state(sizes, rows, steps)
    decode_bytes = ((fixed_params(sizes) + head) * precision["weights"] * steps
                    + ex["decode_bytes"] + upd["bytes"] + dec["bytes"])
    decode_ops = (2 * (token_params + head) * rows * steps + upd["ops"]
                  + dec["ops"])
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "scan_ops": scan_a_token(sizes)["ops"] * tokens
            * layers_of(sizes, "M"),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_state_bytes": upd["bytes"],
            "decode_expert_bytes": ex["decode_bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
