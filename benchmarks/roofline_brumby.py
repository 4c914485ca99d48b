"""Operations and bytes of the Brumby family's two kernels and of a whole
one-shot dispatch, from its shapes and the configuration, and the least
time a chip could take for them.

Counts the WORK, whatever implements it: real prompt tokens (not the padded
bucket, and no chunk of pads); power retention of degree 2 as the state
form defines it — ``phi`` at its 8,256 distinct products (``d (d + 1) / 2``
at ``d`` = 128; lanes a kernel pads are no work), the state's 128 value
channels and the normaliser's one beside them (129; how the normaliser is
stored is the program's business) —; a decode step that reads each weight
once and reads and writes each row's state and normaliser once a layer.
Keys of ``sizes`` are the published ``config.json`` names and the assumed
``retention_*`` ones as ``engine_setup_brumby.sizes_of`` gives them. This
family routes nothing: the ``experts`` argument the readers' signature has
is taken and not read.
"""
from __future__ import annotations


def phi_width(sizes: dict) -> int:
    """Distinct products of a head's channels: d (d + 1) / 2."""
    d = sizes["head_dim"]
    return d * (d + 1) // 2


def mixer_params(sizes: dict) -> int:
    """Matmul weights of one retention mixer: q, k, v and o (the gate's
    [hidden, KV heads] float32 projection is 0.07% of them and left out)."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def layer_params(sizes: dict) -> int:
    return mixer_params(sizes) + 3 * sizes["hidden_size"] * sizes[
        "intermediate_size"]


def token_params(sizes: dict) -> int:
    """Matmul weights a token passes through, all layers (the head is
    counted per sampled position, the embedding is a gather)."""
    return sizes["num_hidden_layers"] * layer_params(sizes)


def state_bytes_a_row_and_layer(sizes: dict) -> int:
    """A row's state and normaliser of one layer, float32: KV heads x 8,256
    x (128 value channels + 1)."""
    return (sizes["num_key_value_heads"] * phi_width(sizes)
            * (sizes["head_dim"] + 1) * 4)


def scan_a_token(sizes: dict, act_bytes: float = 2) -> dict:
    """The chunked retention for one real token in one layer. Operations: a
    query head's read of state and normaliser (2 n (dv + 1)) and a KV
    head's write (the same); the pairs inside the chunk, (C + 1) / 2 a token
    under the causal mask, each a score (2 d) and its weight against v and
    the normaliser (2 (dv + 1)) a query head; ``phi``, n products a head of
    either kind. Bytes: q, k, v and the output, and the float32 gate a KV
    head."""
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, n = sizes["head_dim"], phi_width(sizes)
    pairs = (sizes["retention_chunk_size"] + 1) / 2
    return {"ops": (2 * n * (d + 1) * (h + kv)
                    + pairs * h * (2 * d + 2 * (d + 1))
                    + n * (h + kv)),
            "bytes": (2 * h + 2 * kv) * d * act_bytes + 4 * kv}


def decode_state(sizes: dict, rows: int, steps: int) -> dict:
    """The one-token update over ``steps`` steps: every row's state and
    normaliser of every layer read and written once a step; a decay and a
    rank-one write an element, and each of its query heads' reads."""
    per = state_bytes_a_row_and_layer(sizes) // 4
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    elements = per * sizes["num_hidden_layers"] * rows * steps
    return {"ops": (3 + 2 * group) * elements, "bytes": 2 * 4 * elements}


def _matmul_peak(precision: dict, peaks: dict) -> float:
    return peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]


def _larger(ops_s: float, mem_s: float) -> dict:
    return {"seconds": max(ops_s, mem_s),
            "bound": "compute" if ops_s >= mem_s else "memory"}


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts, prompt_lens: list[int], steps: int) -> dict:
    """The least time of each of the family's two kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it. The scan also writes a row's state once a
    layer and dispatch."""
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    layers = sizes["num_hidden_layers"]
    scan = scan_a_token(sizes)
    upd = decode_state(sizes, rows, steps)
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    scan_bytes = (scan["bytes"] * tokens
                  + state_bytes_a_row_and_layer(sizes) * rows) * layers
    return {
        "retention_prefill_scan": _larger(
            scan["ops"] * tokens * layers / bf16, scan_bytes / hbm),
        "retention_decode_update": _larger(upd["ops"] / bf16,
                                           upd["bytes"] / hbm),
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    products over every real token at the matmul peak (the head once a
    row), the chunked retention at the larger of its operations at the bf16
    peak and its bytes, and decode steps each the larger of its operations
    and its bytes — weights once, every row's state and normaliser read and
    written."""
    params = token_params(sizes)
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    prefill_matmul_ops = 2 * params * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["retention_prefill_scan"]["seconds"])
    upd = decode_state(sizes, rows, steps)
    decode_bytes = (params + head) * precision["weights"] * steps \
        + upd["bytes"]
    decode_ops = 2 * (params + head) * rows * steps + upd["ops"]
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "scan_ops": scan_a_token(sizes)["ops"] * tokens
            * sizes["num_hidden_layers"],
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_state_bytes": upd["bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
