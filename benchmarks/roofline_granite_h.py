"""Operations and bytes of the Granite-4.0-H family's kernels and of a whole
one-shot dispatch, from its shapes and the configuration, and the least
time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket, and no chunk of pads), causal attention over
the attention layers alone at the heads' own width (64: lanes the kernels
pad are no work), the chunked scan as published (whole chunk tiles), a
decode step that reads each weight once, reads and writes each row's
recurrent state once and reads each row's keys and values up to its fill.
Keys of ``sizes`` are the published ``config.json`` names as
``engine_setup_granite_h.sizes_of`` gives them. This family routes nothing:
the ``experts`` argument the readers' signature has is taken and not read.
"""
from __future__ import annotations


def layers_of(sizes: dict, kind: str) -> int:
    return sum(k == kind for k in sizes["layer_types"])


def inner(sizes: dict) -> int:
    return sizes["mamba_n_heads"] * sizes["mamba_d_head"]


def mamba_params(sizes: dict) -> int:
    """Matmul weights of one Mamba-2 mixer: in_proj (z | xBC | dt) and
    out_proj."""
    d, n = sizes["hidden_size"], sizes["mamba_d_state"]
    return (d * (2 * inner(sizes) + 2 * n + sizes["mamba_n_heads"])
            + inner(sizes) * d)


def attention_params(sizes: dict) -> int:
    """Weights of one attention mixer: q, k, v and o."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def ffn_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def token_params(sizes: dict) -> int:
    """Matmul weights a token passes through, all layers (the head is
    counted per sampled position, the embedding is a gather)."""
    return (layers_of(sizes, "mamba") * mamba_params(sizes)
            + layers_of(sizes, "attention") * attention_params(sizes)
            + sizes["num_hidden_layers"] * ffn_params(sizes))


def scan_a_token(sizes: dict, act_bytes: float = 2) -> dict:
    """The chunked scan for one token in one layer. Operations: the masked
    product over the chunk (2 Q inner), the state's readout and its update
    (2 N inner each) and C B^T (2 Q N). Bytes: X read and Y written, B and
    C, dt and its running sum."""
    q, n = sizes["mamba_chunk_size"], sizes["mamba_d_state"]
    return {"ops": 2 * q * inner(sizes) + 4 * n * inner(sizes) + 2 * q * n,
            "bytes": (2 * inner(sizes) + 2 * n) * act_bytes
            + 3 * 4 * sizes["mamba_n_heads"]}


def state_bytes_a_row(sizes: dict) -> int:
    """One row's recurrent state, every Mamba layer, float32."""
    return (layers_of(sizes, "mamba") * sizes["mamba_d_state"]
            * inner(sizes) * 4)


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length on the attention layers:
    2 operations a pair and head over the head's width, for the scores and
    again for the values."""
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    return per_pair * layers_of(sizes, "attention") * sum(
        n * (n + 1) // 2 for n in prompt_lens)


def decode_context(context_lens: list[int], steps: int) -> int:
    """Cache slots read over ``steps`` steps in one layer, summed over
    rows: step t of a row that started at n tokens reads n + t + 1."""
    return sum(steps * (n + 1) + steps * (steps - 1) // 2
               for n in context_lens)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps on the attention layers: each
    slot's keys and values (and, in an int8 cache, their two float32 scales
    a KV head) read once a step."""
    ctx = decode_context(context_lens, steps) * layers_of(sizes, "attention")
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


def _matmul_peak(precision: dict, peaks: dict) -> float:
    return peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]


def _larger(ops_s: float, mem_s: float) -> dict:
    return {"seconds": max(ops_s, mem_s),
            "bound": "compute" if ops_s >= mem_s else "memory"}


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts, prompt_lens: list[int], steps: int) -> dict:
    """The least time of each of the family's four kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it."""
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    scan = scan_a_token(sizes)
    scanned = tokens * layers_of(sizes, "mamba")
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    upd = decode_state(sizes, rows, steps)
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    return {
        "ssd_prefill_scan": _larger(scan["ops"] * scanned / bf16,
                                    scan["bytes"] * scanned / hbm),
        "ssm_decode_update": _larger(upd["ops"] / bf16, upd["bytes"] / hbm),
        "flash_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens) / bf16,
            "bound": "compute"},
        "flash_decode_attention": _larger(dec["ops"] / bf16,
                                          dec["bytes"] / hbm),
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    products over every real token at the matmul peak (the head once a
    row), the scan and the causal attention at the bf16 peak, and decode
    steps each the larger of its operations and its bytes — weights once,
    state read and written, each row's keys and values up to its fill."""
    params = token_params(sizes)
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    prefill_matmul_ops = 2 * params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["ssd_prefill_scan"]["seconds"]
                 + kernels["flash_prefill_attention"]["seconds"])
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    upd = decode_state(sizes, rows, steps)
    decode_bytes = ((params + head) * precision["weights"] * steps
                    + upd["bytes"] + dec["bytes"])
    decode_ops = (2 * (params + head) * rows * steps + upd["ops"]
                  + dec["ops"])
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "scan_ops": scan_a_token(sizes)["ops"] * tokens
            * layers_of(sizes, "mamba"),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_state_bytes": upd["bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
