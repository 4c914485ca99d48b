"""Operations and bytes a dispatch needs, from its shapes and the
configuration, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket), causal attention (half the square), weights
read once per decode step. Keys of ``sizes`` are the published
``config.json`` names a configuration file holds.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            f"{path.name} with its source")
    return table[device_kind]


def layer_matmul_params(sizes: dict) -> int:
    """Weights of one decoder layer's matrix multiplications."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * sizes["intermediate_size"]


def matmul_params(sizes: dict) -> int:
    """Weights every token passes through: the layers (the output head is
    counted per sampled position, the embedding is a gather)."""
    return sizes["num_hidden_layers"] * layer_matmul_params(sizes)


def kv_bytes_per_token(sizes: dict, kv_bytes: float) -> float:
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * sizes["head_dim"] * kv_bytes)


def prefill(sizes: dict, prompt_lens: list[int]) -> dict:
    """Operations of prefilling these prompts: matmuls over every token, the
    output head once per row, causal attention over each row's own length."""
    tokens = sum(prompt_lens)
    head = sizes["hidden_size"] * sizes["vocab_size"]
    attn = sum(2 * sizes["num_attention_heads"] * sizes["head_dim"] * n * n
               for n in prompt_lens) * sizes["num_hidden_layers"]
    return {"matmul_ops": 2 * matmul_params(sizes) * tokens
                          + 2 * head * len(prompt_lens),
            "attention_ops": attn}


def decode(sizes: dict, context_lens: list[int], steps: int,
           weight_bytes: float, kv_bytes: float) -> dict:
    """Bytes and operations of ``steps`` decode steps over rows that start
    at these context lengths: every weight read once per step, each row's
    cache read once per step."""
    params = matmul_params(sizes) + sizes["hidden_size"] * sizes["vocab_size"]
    rows = len(context_lens)
    ctx = sum(context_lens) * steps + rows * steps * (steps - 1) // 2
    return {"bytes": params * weight_bytes * steps
                     + kv_bytes_per_token(sizes, kv_bytes) * ctx,
            "ops": 2 * params * rows * steps
                   + 4 * sizes["num_attention_heads"] * sizes["head_dim"]
                   * sizes["num_hidden_layers"] * ctx}


def least_seconds(sizes: dict, precision: dict, peaks: dict,
                  prompt_lens: list[int], steps: int) -> dict:
    """The least time one chip could take for a dispatch that prefills these
    prompts and then decodes ``steps`` tokens per row, with the bound of
    each phase. ``precision``: ``weights`` and ``kv`` in bytes, and
    ``prefill_matmul`` naming the peak its matmuls run at."""
    pre = prefill(sizes, prompt_lens)
    matmul_peak = peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]
    pre_s = (pre["matmul_ops"] / matmul_peak
             + pre["attention_ops"] / peaks["flops_bf16"])
    dec = decode(sizes, prompt_lens, steps, precision["weights"],
                 precision["kv"])
    dec_mem = dec["bytes"] / peaks["hbm_bytes_per_s"]
    dec_ops = dec["ops"] / peaks["flops_bf16"]
    return {"prefill_s": pre_s, "prefill_bound": "compute",
            "decode_s": max(dec_mem, dec_ops),
            "decode_bound": "memory" if dec_mem >= dec_ops else "compute",
            "total_s": pre_s + max(dec_mem, dec_ops), **pre, **dec}
