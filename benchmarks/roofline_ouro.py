"""Operations and bytes of a dense stack LOOPED over its weights (Ouro), of
its two attention kernels and of a whole one-shot dispatch, from its shapes
and the configuration, and the least time a chip could take for them.

Counts what the algorithm needs, not what the program does: real prompt
tokens (not the padded bucket), causal attention over each row's own
length, a decode step that reads each row's keys and values up to its fill.
What the loop changes, against ``benchmarks/roofline.py``'s one pass:

- every product of the layers ``T`` = ``total_ut_steps`` times a token (the
  head once: only the last pass's stream is projected);
- keys and values of ``T * L`` cache layers — a (pass, layer) has its own —
  in the causal pairs of the prefill and in every decode step's reads;
- **the layers' weights ``T`` times a decode step**, the head's once. A
  step's least bytes are not one reading of the weights: pass ``t + 1`` of
  a token cannot start before pass ``t`` has ended (its input is pass
  ``t``'s normed output, through every layer), and the layers' weights of
  one pass (617 MB at 12 layers of the published widths in int8, 2.5 GB at
  48) are far more than the chip's on-chip memory holds until the next
  pass comes round. So each pass streams them from HBM again.

Keys of ``sizes`` are the published ``config.json`` names as
``engine_setup_ouro.sizes_of`` gives them. The model routes nothing: the
``experts`` argument the readers' signature has is taken and not read.
"""
from __future__ import annotations


def cache_layers(sizes: dict) -> int:
    """Layers of keys and values: one a (pass, layer)."""
    return sizes["total_ut_steps"] * sizes["num_hidden_layers"]


def layer_params(sizes: dict) -> int:
    """Matmul weights of one layer: q, k, v, o and the SwiGLU's three."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return (d * (h + 2 * kv) * hd + h * hd * d
            + 3 * d * sizes["intermediate_size"])


def stack_params(sizes: dict) -> int:
    """Matmul weights of the stack, ONCE: what the chip holds."""
    return sizes["num_hidden_layers"] * layer_params(sizes)


def token_params(sizes: dict) -> int:
    """Matmul weights a token passes THROUGH: the stack once a pass (the
    head is counted per sampled position, the embedding is a gather)."""
    return sizes["total_ut_steps"] * stack_params(sizes)


def prefill_attention_ops(sizes: dict, prompt_lens: list[int]) -> float:
    """Causal attention over each row's own length on every (pass, layer):
    2 operations a pair and head over the head's width, for the scores and
    again for the values."""
    per_pair = 4 * sizes["num_attention_heads"] * sizes["head_dim"]
    return per_pair * cache_layers(sizes) * sum(
        n * (n + 1) // 2 for n in prompt_lens)


def decode_context(context_lens: list[int], steps: int) -> int:
    """Cache slots read over ``steps`` steps in one cache layer, summed over
    rows: step t of a row that started at n tokens reads n + t + 1."""
    return sum(steps * (n + 1) + steps * (steps - 1) // 2
               for n in context_lens)


def decode_attention(sizes: dict, context_lens: list[int], steps: int,
                     kv_bytes: float) -> dict:
    """The decode kernel over ``steps`` steps on all ``T * L`` cache layers:
    each slot's keys and values (and, in an int8 cache, their two float32
    scales a KV head) read once a step and (pass, layer)."""
    ctx = decode_context(context_lens, steps) * cache_layers(sizes)
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    scales = 8 if kv_bytes == 1 else 0
    return {"ops": 4 * sizes["num_attention_heads"] * hd * ctx,
            "bytes": kv * (2 * hd * kv_bytes + scales) * ctx}


def _matmul_peak(precision: dict, peaks: dict) -> float:
    return peaks[{"int8": "ops_int8", "bf16": "flops_bf16"}[
        precision["prefill_matmul"]]]


def _larger(ops_s: float, mem_s: float) -> dict:
    return {"seconds": max(ops_s, mem_s),
            "bound": "compute" if ops_s >= mem_s else "memory"}


def kernel_least_seconds(sizes: dict, precision: dict, peaks: dict,
                         experts, prompt_lens: list[int], steps: int) -> dict:
    """The least time of each of the two attention kernels in a dispatch
    that prefills these prompts and decodes ``steps`` tokens a row, each
    with the bound that sets it."""
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    hbm, bf16 = peaks["hbm_bytes_per_s"], peaks["flops_bf16"]
    return {
        "flash_prefill_attention": {
            "seconds": prefill_attention_ops(sizes, prompt_lens) / bf16,
            "bound": "compute"},
        "flash_decode_attention": _larger(dec["ops"] / bf16,
                                          dec["bytes"] / hbm),
    }


def dispatch(sizes: dict, precision: dict, peaks: dict, experts,
             prompt_lens: list[int], steps: int) -> dict:
    """Operations, bytes and least time of a whole dispatch: prefill
    products over every real token, ``T`` times the stack, at the matmul
    peak (the head once a row), causal attention of ``T * L`` cache layers
    at the bf16 peak, and decode steps each the larger of its operations
    and the bytes it reads — the stack's weights ``T`` times and the head's
    once (the module's docstring says why), each row's keys and values of
    ``T * L`` cache layers up to its fill."""
    through = token_params(sizes)
    head = sizes["hidden_size"] * sizes["vocab_size"]
    tokens, rows = sum(prompt_lens), len(prompt_lens)
    kernels = kernel_least_seconds(sizes, precision, peaks, experts,
                                   prompt_lens, steps)
    prefill_matmul_ops = 2 * through * tokens + 2 * head * rows
    prefill_s = (prefill_matmul_ops / _matmul_peak(precision, peaks)
                 + kernels["flash_prefill_attention"]["seconds"])
    dec = decode_attention(sizes, prompt_lens, steps, precision["kv"])
    decode_weight_bytes = (through + head) * precision["weights"] * steps
    decode_bytes = decode_weight_bytes + dec["bytes"]
    decode_ops = 2 * (through + head) * rows * steps + dec["ops"]
    decode_s = max(decode_bytes / peaks["hbm_bytes_per_s"],
                   decode_ops / peaks["flops_bf16"])
    return {"prefill_matmul_ops": prefill_matmul_ops,
            "prefill_attention_ops": prefill_attention_ops(sizes, prompt_lens),
            "decode_bytes": decode_bytes, "decode_ops": decode_ops,
            "decode_weight_bytes": decode_weight_bytes,
            "decode_kv_bytes": dec["bytes"],
            "prefill_s": prefill_s, "decode_s": decode_s,
            "total_s": prefill_s + decode_s, "kernels": kernels}
