"""Decompose the decode roofline gap (VERDICT r4 next #5).

BENCH_r04 decode runs at 0.72-0.74 of the HBM roofline at the e2e shape
(llama32-3b int8 + int8 KV, B=8, S=8192, C=8448, max_new=128) and nothing
attributed the missing ~26%. This script measures the SAME engine programs
with one knob changed per arm, all instrument=True (decode as one dispatch,
fetch-synced), so each delta isolates one term:

  A  baseline       — e2e_engine_kwargs exact (temperature 1.0, BPE-4096)
  B  greedy         — temperature 0.0: categorical-sampling cost = A - B
  C  vocab-8k       — model vocab_size 8192: lm_head/embed width cost
  D  window-256     — all layers sliding_window=256: decode attention now
                      reads ~256 cache positions instead of ~8300, so
                      cache-stream cost = A - D (weights+overheads remain)
  E  kernel-direct  — flash_decode_attention standalone on the full-size
                      int8 cache, 32 steps in one jit: the kernel's own
                      achieved HBM bandwidth, no model around it

Roofline bookkeeping per arm: mandatory decode bytes/step = int8 weight
bytes + K/V bytes up to fill + scale bytes. v5e numbers from bench.py
(819 GB/s, PERF.md measurement hygiene).

Writes artifacts/decode_gap_r5.json.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))



def hbm_bytes_per_s() -> float:
    import bench

    return bench.device_peaks()["hbm_bytes_per_s"]


def weight_bytes(params) -> int:
    import jax

    return sum(int(l.nbytes) for l in jax.tree.leaves(params))


def cache_bytes(cfg, B: int, fill: int, quantized: bool) -> int:
    # decode attention streams K and V up to the fill point each step
    kv = cfg.n_layers * B * cfg.n_kv_heads * fill * cfg.head_dim * 2
    if not quantized:
        return kv * 2  # bf16
    return kv + cfg.n_layers * B * cfg.n_kv_heads * fill * 4 * 2  # int8+f32 scales


def run_arm(label: str, cfg, tok_spec, gen_cfg, prompts, max_new: int) -> dict:
    import numpy as np

    from vnsum_tpu.backend.engine import EngineStats, TpuBackend

    be = TpuBackend(
        model_config=cfg, tokenizer=tok_spec, batch_size=8,
        max_new_tokens=max_new, quantize=True, instrument=True,
    )
    t0 = time.time()
    be.generate(prompts, config=gen_cfg)  # compile + warm
    compile_s = time.time() - t0
    be.stats = EngineStats()
    t1 = time.time()
    be.generate(prompts, config=gen_cfg)
    wall = time.time() - t1
    st = be.stats
    steps = sum(d["steps"] for d in st.dispatches)
    dec = st.phase_seconds.get("decode", 0.0)
    pre = st.phase_seconds.get("prefill", 0.0)
    ms_per_step = dec / steps * 1e3 if steps else 0.0
    wb = weight_bytes(be.params)
    # average fill across the decode: S + max_new/2 — clamped to the sliding
    # window when every layer is windowed (arm D), since the kernel's DMA
    # clamp means positions beyond the window are never read
    S = st.dispatches[0]["S"] if st.dispatches else 0
    fill = S + max_new // 2
    if cfg.sliding_window and not any(cfg.layer_is_global):
        fill = min(fill, cfg.sliding_window)
    cb = cache_bytes(cfg, st.dispatches[0]["B"] if st.dispatches else 8,
                     fill, be.quantize_kv)
    mandatory = wb + cb
    roofline_ms = mandatory / hbm_bytes_per_s() * 1e3
    row = {
        "label": label,
        "compile_and_warm_s": round(compile_s, 1),
        "wall_s": round(wall, 2),
        "prefill_s": round(pre, 2),
        "decode_s": round(dec, 3),
        "decode_steps": steps,
        "ms_per_step": round(ms_per_step, 3),
        "weight_bytes": wb,
        "cache_bytes_at_mid_fill": cb,
        "roofline_ms_per_step": round(roofline_ms, 3),
        "roofline_frac": round(roofline_ms / ms_per_step, 4) if ms_per_step else 0,
        "dispatches": st.dispatches,
    }
    print(f"{label}: {json.dumps({k: row[k] for k in ('decode_s','ms_per_step','roofline_frac')})}",
          file=sys.stderr)
    del be
    gc.collect()
    return row


def run_kernel_direct(cfg, B: int, C: int, steps: int = 32) -> dict:
    """flash_decode_attention alone on a full int8 cache: the kernel's own
    achieved bandwidth at the e2e cache shape."""
    import jax
    import jax.numpy as jnp

    from vnsum_tpu.models.llama import init_kv_cache
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    cache = init_kv_cache(cfg, B, C, quantized=True)
    # nonzero fill (values AND scales at 1.0) keeps the dequantized math
    # finite; bandwidth is layout-determined, not value-determined. The
    # cache is an ARGUMENT of the jitted loop — captured as a closure
    # constant it gets baked into the program (4 GB of lowering constants)
    # and the measurement stops being a pure HBM-stream read
    cache = {k: jnp.ones_like(v) for k, v in cache.items()}
    pad_lens = jnp.zeros((B,), jnp.int32)
    fill = jnp.int32(C - 1)
    H, hd = cfg.n_heads, cfg.head_dim
    L = cfg.n_layers

    def loop_fn(q, cache):
        def body(q, i):
            # cycle through the layers like the model does (i % L), so the
            # stream touches the whole stacked cache; q depends on the
            # previous output so steps serialize (no CSE)
            o = flash_decode_attention(
                q, cache, (i % L).astype(jnp.int32), pad_lens, fill,
                cfg.q_per_kv, None,
            )
            return o * 1e-3 + q, None

        return jax.lax.scan(body, q, jnp.arange(steps), length=steps)[0]

    q0 = jnp.ones((B, 1, H, hd), jnp.bfloat16)
    loop = jax.jit(loop_fn)
    import numpy as np

    np.asarray(loop(q0, cache))  # compile + warm
    t0 = time.time()
    out = loop(q0, cache)
    np.asarray(out)
    dt = time.time() - t0
    # one layer per step: bytes = B*KV*C*hd*2 int8 + scales
    per_step = B * cfg.n_kv_heads * C * cfg.head_dim * 2 + B * cfg.n_kv_heads * C * 4 * 2
    bw = per_step * steps / dt
    return {
        "label": "kernel_direct_layer0",
        "steps": steps,
        "seconds": round(dt, 3),
        "bytes_per_step_one_layer": per_step,
        "achieved_gb_per_s": round(bw / 1e9, 1),
        "frac_of_819": round(bw / hbm_bytes_per_s(), 4),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="artifacts/decode_gap_r5.json")
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--arms", default="A,B,C,D,E")
    args = ap.parse_args()
    arms = set(args.arms.split(","))

    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.data.synthesize import synthesize_corpus
    from vnsum_tpu.models.fixtures import train_bpe_tokenizer
    from vnsum_tpu.models.llama import llama32_3b

    enable_compilation_cache()
    prompts: list[str] = []
    tok_spec = "byte"
    if arms & set("ABCD"):  # the kernel-direct arm needs none of this
        root = tempfile.mkdtemp(prefix="vnsum_decgap_")
        synthesize_corpus(
            f"{root}/corpus", n_docs=4, tokens_per_doc=9_000,
            summary_tokens=200, seed=7, ragged=0.0,
        )
        doc_paths = sorted(Path(f"{root}/corpus/doc").glob("*.txt"))
        hf_tok = train_bpe_tokenizer(
            (p.read_text(encoding="utf-8") for p in doc_paths),
            vocab_size=4096,
        )
        hf_tok.save_pretrained(f"{root}/tok")
        tok_spec = f"hf:{root}/tok"

        # 8 prompts that land in the S=8192 bucket (the e2e dominant shape)
        words = " ".join(
            p.read_text(encoding="utf-8") for p in doc_paths
        ).split()
        for i in range(8):
            seg = " ".join(words[i * 7000 : i * 7000 + 7400])
            prompts.append("Tóm tắt văn bản sau: " + seg)

    cfg = llama32_3b(max_seq_len=8448)
    sampled = GenerationConfig(temperature=1.0, seed=11)
    greedy = GenerationConfig(temperature=0.0)

    rows = []
    if "A" in arms:
        rows.append(run_arm("A_baseline", cfg, tok_spec, sampled, prompts,
                            args.max_new))
    if "B" in arms:
        rows.append(run_arm("B_greedy", cfg, tok_spec, greedy, prompts,
                            args.max_new))
    if "C" in arms:
        small_head = dataclasses.replace(cfg, vocab_size=8192)
        rows.append(run_arm("C_vocab8k", small_head, tok_spec, sampled,
                            prompts, args.max_new))
    if "D" in arms:
        windowed = dataclasses.replace(
            cfg, sliding_window=256,
            layer_is_global=(False,) * cfg.n_layers,
        )
        rows.append(run_arm("D_window256", windowed, tok_spec, sampled,
                            prompts, args.max_new))
    kernel_row = None
    if "E" in arms:
        kernel_row = run_kernel_direct(cfg, B=8, C=8448, steps=112)
        print(f"E: {json.dumps(kernel_row)}", file=sys.stderr)

    out_path = Path(args.out)
    if out_path.exists() and arms != set("ABCDE"):
        # partial rerun (e.g. --arms E after a fixed kernel-direct): keep
        # the measured rows that were not re-run
        prev = json.loads(out_path.read_text())
        have = {r["label"] for r in rows}
        rows = rows + [r for r in prev.get("arms", []) if r["label"] not in have]
        if kernel_row is None:
            kernel_row = prev.get("kernel_direct")
    rec = {
        "what": "decode roofline gap decomposition at the e2e shape",
        "hbm_bytes_per_s_assumed": hbm_bytes_per_s(),
        "arms": rows,
        "kernel_direct": kernel_row,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    by = {r["label"].split("_")[0]: r for r in rows}
    if {"A", "B", "C", "D"} <= set(by):
        a = by["A"]["ms_per_step"]
        rec["attribution_ms_per_step"] = {
            "total": a,
            "sampling_categorical": round(a - by["B"]["ms_per_step"], 3),
            "vocab_width_head": round(a - by["C"]["ms_per_step"], 3),
            "cache_stream_attention": round(a - by["D"]["ms_per_step"], 3),
            "weights_plus_residue": round(by["D"]["ms_per_step"], 3),
        }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({"ok": True, "arms": [r["label"] for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
