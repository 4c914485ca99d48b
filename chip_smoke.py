#!/usr/bin/env python3
"""chip_smoke.py — does today's code still start, compile and answer on the chip?

Drives the system's main paths once, at the full width of Llama-3.2-3B with
random weights made from a seed, through the entry points a user calls:

    device   jax.devices()[0].platform must be "tpu"; versions, cache dir
    kernels  the GQA Pallas kernels, compiled (never interpreted), at the
             head geometries of 128 and 256 lanes, against the dense
             reference attention of models/llama.py (NOT the paired 64-wide
             heads, which the Granite and LFM2 cells' parity checks hold,
             nor the latent, scan, delta-rule and expert kernels, which
             their families' phases below run)
    offline  PipelineRunner (what `python -m vnsum_tpu.pipeline.cli
             --backend tpu` runs): VN-LongSum-length documents,
             mapreduce, a full-batch S=8192 dispatch, a reduce, evaluation
    serve    `python -m vnsum_tpu.serve.server --backend tpu --inflight
             --journal-dir ...` on a cold program cache:
             /v1/generate (shared prefix, stream), /v1/summarize, /metrics,
             SIGTERM drain
    mesh     the generate step under TpuBackend(mesh=) at model=4 and
             data=4 — only with >= 4 devices, otherwise reported as skipped
    experts, moe, laguna, nemotron_h, ouro, lfm2, ling, brumby, keye
             one family each on the one-shot path at its published widths
             and a few layers, against its plain reference
             (``--phases device,<family>``; each phase's docstring)

One process holds the chip at a time: this parent never imports JAX and runs
each phase in its own child. It exits non-zero if any phase failed or ran on
a platform other than tpu, writes the full report to
chiprun_out/chip_smoke.json, and prints as the last line of its standard
output one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.

`--rehearsal` is the debugging aid, never what the plain command does: tiny
model, CPU, interpret-mode kernels, report marked "rehearsal": true.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent
PHASES = ("device", "kernels", "offline", "serve", "mesh", "experts", "moe",
          "laguna", "nemotron_h", "ouro", "lfm2", "ling", "brumby", "keye")
# the whole run, compilation included, must end inside 1200 s
TOTAL_BUDGET_S = 1140
PHASE_TIMEOUT_S = {
    "device": 120, "kernels": 360, "offline": 600, "serve": 600, "mesh": 600,
    "experts": 420, "moe": 420, "laguna": 480, "nemotron_h": 480,
    "ouro": 360, "lfm2": 420, "ling": 480, "brumby": 480, "keye": 600,
}


# the ouro phase's three limits on the chip: logits a row, the first pass's
# cache rows, the last pass's (benchmarks/configs/ouro-2.6b-l12-int8.json)
OURO_LIMITS = (0.125, 0.011, 0.095)


def sizes(rehearsal: bool) -> dict:
    """Every size the phases use, in one place: the full-width run and the
    tiny CPU rehearsal differ here and nowhere else."""
    if rehearsal:
        return dict(
            kernel_geometries=[("tiny-g2", 4, 2, 16, 24), ("tiny-g3", 6, 2, 16, 24)],
            kernel_S=64, kernel_off=128, kernel_C=232, kernel_B=8,
            docs=2, words_per_doc=2400, bpe_vocab=512, chunk_size=420,
            token_max=300, offline_batch=4, offline_max_new=8,  # rehearsal engine
            offline_seq=640, offline_prefill_chunk=128, probe_tokens=380,
            serve_model="tiny", serve_slots=2, serve_slot_tokens=192,
            serve_max_new=24, serve_block_tokens=16,
            serve_prefix_bytes=120, serve_doc_bytes=26_000,
            mesh_prompt_bytes=200, mesh_batch=8, mesh_max_new=4,
            mesh_seq=512,
            experts_seq=328, experts_batch=4, experts_max_new=8,
            experts_prefill_chunk=128, experts_prompt_bytes=250,
            experts_parity=(150, 256, 4),
            moe_layers=8, moe_seq=328, moe_batch=4, moe_max_new=8,
            moe_prefill_chunk=128, moe_prompt_bytes=250,
            moe_parity=(150, 256, 4), moe_tolerance=0.05, moe_tie_band=0.1,
            laguna_layers=9, laguna_seq=328, laguna_batch=4, laguna_max_new=8,
            laguna_prefill_chunk=128, laguna_prompt_bytes=250,
            laguna_parity=(150, 256, 4), laguna_tolerance=0.12,
            laguna_tie_band=0.1,
            nemotron_h_layers=9, nemotron_h_seq=328, nemotron_h_batch=4,
            nemotron_h_max_new=8, nemotron_h_prefill_chunk=128,
            nemotron_h_prompt_bytes=250, nemotron_h_parity=(150, 256, 4),
            nemotron_h_tolerance=0.05, nemotron_h_tie_band=0.02,
            nemotron_h_state_tolerance=0.01,
            lfm2_layers=10, lfm2_seq=328, lfm2_batch=4, lfm2_max_new=8,
            lfm2_prefill_chunk=128, lfm2_prompt_bytes=250,
            lfm2_parity=(150, 256, 4), lfm2_tolerance=0.15,
            lfm2_tie_band=0.02, lfm2_state_tolerance=0.01,
            ling_layers=6, ling_held=8, ling_seq=328, ling_batch=4,
            ling_max_new=8, ling_prefill_chunk=128, ling_prompt_bytes=250,
            ling_kernel=(2, 96, 4, 16), ling_kernel_tolerance=1e-4,
            brumby_layers=3, brumby_seq=328, brumby_batch=4,
            brumby_max_new=8, brumby_prefill_chunk=128,
            brumby_prompt_bytes=250, brumby_kernel=(2, 40, 4, 2, 16),
            brumby_kernel_tolerance=1e-4,
            keye_layers=3, keye_seq=328, keye_batch=4, keye_max_new=8,
            keye_prefill_chunk=128, keye_prompt_bytes=250,
            keye_kernel=(32, 64, 200, 4, 2, 16, 4, 8, 24, 16, 32),
            keye_kernel_tolerance=1e-4,
            ouro_layers=2, ouro_seq=328, ouro_batch=4, ouro_max_new=8,
            ouro_prefill_chunk=128, ouro_prompt_bytes=250,
            ouro_parity=(150, 256, 4), ouro_tolerance=0.05,
            ouro_kv_tolerance=0.03, ouro_kv_last_pass_tolerance=0.05,
        )
    return dict(
        kernel_geometries=None,  # derived from MODEL_REGISTRY
        # C leaves a partial tail block in every kernel (3208 % 128 == 8): past
        # the cache's end a block holds stale VMEM, NaN patterns included
        kernel_S=1024, kernel_off=2048, kernel_C=3208, kernel_B=8,
        # offline: chunk_size 7800 BPE tokens lands map prompts in the
        # S=8192 bucket at B=16
        docs=4, words_per_doc=37_000, bpe_vocab=4096, chunk_size=7_800,
        token_max=6_000,  # batch and decode budget: e2e_engine_kwargs
        offline_seq=8448, offline_prefill_chunk=2048, probe_tokens=7_300,
        # serve: bf16 weights (6.4 GB, the server has no --quantize) leave
        # room for two slots whose prompt bucket holds a 12k-token map chunk
        serve_model="llama3.2:3b", serve_slots=2, serve_slot_tokens=12_800,
        serve_max_new=640, serve_block_tokens=64,
        serve_prefix_bytes=6_000, serve_doc_bytes=26_000,
        mesh_prompt_bytes=3_500, mesh_batch=8, mesh_max_new=32,
        mesh_seq=4352,
        # experts: one dispatch of 8 rows in the S=2048 bucket, two chunks
        experts_seq=2112, experts_batch=8, experts_max_new=64,
        experts_prefill_chunk=1024, experts_prompt_bytes=5_500,
        experts_parity=(1500, 2048, 4),   # prompt tokens, bucket, steps
        # moe: one period of [global, window, window, window] at the
        # published widths; prompts past the 4096 window in the S=8192 bucket
        moe_layers=4, moe_seq=8256, moe_batch=4, moe_max_new=64,
        moe_prefill_chunk=2048, moe_prompt_bytes=6_000,
        moe_parity=(4500, 8192, 4), moe_tolerance=0.05, moe_tie_band=0.1,
        # laguna: the dense layer and one period of [sliding, sliding,
        # sliding, full] at the published widths (10.7 GB of int8 weights);
        # prompts three 512-windows long in the S=2048 bucket
        laguna_layers=5, laguna_seq=2304, laguna_batch=4, laguna_max_new=32,
        laguna_prefill_chunk=1024, laguna_prompt_bytes=1_900,
        # the cell's own limits (benchmarks/configs/laguna-s-2.1-l5-int8.json)
        laguna_parity=(1500, 2048, 4), laguna_tolerance=0.115,
        laguna_tie_band=0.3,
        # the first six layers MEMEM* at the published widths: 3 Mamba-2 at
        # 8 groups, 2 sparse of 128 experts each, 1 attention at 32/2 heads
        # (3.5 GB of int8 weights)
        nemotron_h_layers=6, nemotron_h_seq=2304, nemotron_h_batch=4,
        nemotron_h_max_new=32, nemotron_h_prefill_chunk=1024,
        nemotron_h_prompt_bytes=1_900, nemotron_h_parity=(1500, 2048, 4),
        nemotron_h_tolerance=0.25, nemotron_h_tie_band=0.1,
        nemotron_h_state_tolerance=0.006,
        # lfm2: the first seven layers c c A c c c A at the published widths
        # (both dense layers, five sparse ones, both kinds of operator)
        lfm2_layers=7, lfm2_seq=2304, lfm2_batch=4, lfm2_max_new=32,
        lfm2_prefill_chunk=1024, lfm2_prompt_bytes=1_900,
        lfm2_parity=(1500, 2048, 4), lfm2_tolerance=0.25,
        lfm2_tie_band=0.1, lfm2_state_tolerance=0.012,
        # ling: the first period K K K K K M of layers 0-5 at the published
        # widths, 128 of 512 experts held; the two delta-rule kernels at a
        # row piece's shape (rows, tokens, heads, head width) in bfloat16
        ling_layers=6, ling_held=128, ling_seq=2304, ling_batch=4,
        ling_max_new=32, ling_prefill_chunk=1024, ling_prompt_bytes=1_900,
        ling_kernel=(4, 2048, 32, 128), ling_kernel_tolerance=3e-2,
        # brumby: two power-retention layers at the published widths; the
        # two retention kernels at a row piece's shape (rows, tokens, query
        # heads, KV heads, head width) in bfloat16
        brumby_layers=2, brumby_seq=2304, brumby_batch=4, brumby_max_new=32,
        brumby_prefill_chunk=1024, brumby_prompt_bytes=1_900,
        brumby_kernel=(2, 2048, 40, 8, 128), brumby_kernel_tolerance=3e-2,
        # keye: two layers at the published widths (all 128 experts), prompts
        # past the top-k of 2,048; the sparse-attention kernels at (queries
        # compared with the XLA forms, queries timed, cache slots, query
        # heads, KV heads, head width, indexer heads, indexer width, top-k,
        # the blocks of the tests: none here)
        keye_layers=2, keye_seq=4352, keye_batch=4, keye_max_new=32,
        keye_prefill_chunk=1024, keye_prompt_bytes=2_900,
        keye_kernel=(512, 2048, 16640, 32, 4, 128, 16, 64, 2048, None, None),
        keye_kernel_tolerance=3e-2,
        # ouro: two layers at the published widths run FOUR times (8 cache
        # layers), 16 / 16 heads; prompts of two 2,048-token chunks in the
        # S=4096 bucket; the cell's own limits
        # (benchmarks/configs/ouro-2.6b-l12-int8.json)
        ouro_layers=2, ouro_seq=4352, ouro_batch=4, ouro_max_new=32,
        ouro_prefill_chunk=2048, ouro_prompt_bytes=3_800,
        ouro_parity=(3000, 4096, 4), ouro_tolerance=OURO_LIMITS[0],
        ouro_kv_tolerance=OURO_LIMITS[1],
        ouro_kv_last_pass_tolerance=OURO_LIMITS[2],
    )


# ---------------------------------------------------------------------------
# children (these import JAX; the parent below never does)
# ---------------------------------------------------------------------------


class Checks:
    """Named pass/fail checks; a phase is ok when every check is."""

    def __init__(self) -> None:
        self.results: dict[str, bool] = {}
        self.details: dict[str, object] = {}

    def check(self, name: str, ok, detail=None) -> bool:
        self.results[name] = bool(ok)
        if detail is not None:
            self.details[name] = detail
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail is not None and not ok else ""),
              flush=True)
        return bool(ok)

    def report(self) -> dict:
        return {"ok": all(self.results.values()), "checks": self.results,
                "check_details": self.details}


def _device_report() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "count": len(d)}


def _memory() -> list[dict]:
    import jax

    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return [
        {"id": d.id, **{k: int(v) for k, v in (d.memory_stats() or {}).items()
                        if k in keep}}
        for d in jax.local_devices()
    ]


def _watch_compiles() -> dict:
    """Sum XLA backend-compile seconds and count persistent-cache hits and
    misses, from JAX's own monitoring events."""
    import jax.monitoring as mon

    seen = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["backend_compile_s"] += seconds

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return seen


def phase_device(args) -> dict:
    import importlib.metadata as md

    import jax

    from vnsum_tpu import native
    from vnsum_tpu.core.jax_cache import enable_compilation_cache

    c = Checks()
    dev = _device_report()
    c.check("platform_is_tpu", dev["platform"] == "tpu" or args.rehearsal,
            dev["platform"])
    cache_dir = enable_compilation_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    def version(pkg: str) -> str:
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    return {
        **c.report(),
        "versions": {"python": sys.version.split()[0], "jax": jax.__version__,
                     "jaxlib": version("jaxlib"), "libtpu": version("libtpu")},
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "compile_cache_entries_at_start": entries,
        # the C++ host core is built with `make` on first use; without it
        # ROUGE and the byte splitter run their slower Python twins
        "native_available": bool(native.available()),
    }


def phase_kernels(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.models import MODEL_REGISTRY
    from vnsum_tpu.models.llama import (
        _attention,
        _quantize_kv,
        decode_attention_mask,
        dequantize_cache_layer,
        prefill_attention_mask,
        verify_attention_mask,
    )
    from vnsum_tpu.ops.decode_attention import (
        flash_decode_attention,
        flash_spec_verify_attention,
    )
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    enable_compilation_cache()
    sz = sizes(args.rehearsal)
    interpret = args.rehearsal
    c = Checks()
    geoms = sz["kernel_geometries"]
    if geoms is None:
        # one entry per distinct head geometry among the lane-aligned
        # families (head_dim % 128 — the others take the dense path by rule)
        seen: dict = {}
        for name, factory in MODEL_REGISTRY.items():
            cfg = factory()
            key = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
            if cfg.head_dim % 128 == 0 and key not in seen:
                seen[key] = (name, *key, cfg.sliding_window or 1024)
        geoms = list(seen.values())
    S, OFF, C, B = (sz["kernel_S"], sz["kernel_off"], sz["kernel_C"],
                    sz["kernel_B"])
    L, LAYER, BP = 2, 1, 2  # two-layer cache, read layer 1; prefill rows
    rng = np.random.default_rng(0)
    pads_np = rng.integers(1, S // 2, size=B).astype(np.int32)
    pads_np[0] = 0                                  # one row with no padding
    pads = jnp.asarray(pads_np)
    fill = C - 9                                    # decode: last valid slot
    fills = jnp.asarray(
        rng.integers(OFF, C - 8, size=B).astype(np.int32), jnp.int32)
    TOL = 2e-2  # max abs error over the reference's max abs value
    rows: list[dict] = []

    # the reference: models.llama's dense attention over the dequantized
    # layer, with the slot-space window of models.llama._block. One program
    # per query shape — the bf16 and int8 caches share it, as do decode and
    # the Sq=1 verify — so compiling references does not dwarf the kernels
    @jax.jit
    def dense(q, k, v, mask, q_slots, win):
        k_slot = jnp.arange(C)[None, None, :]
        mask = mask & ((win == 0) | (k_slot > q_slots[:, :, None] - win))
        return _attention(q, k, v, mask, q.shape[2] // k.shape[1])

    @jax.jit
    def measure(got, want, valid):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        got = jnp.where(valid, got, 0.0)
        return (jnp.max(jnp.abs(got - jnp.where(valid, want, 0.0))),
                jnp.max(jnp.abs(want)), jnp.all(jnp.isfinite(got)))

    def compare(label, kernel, want, valid):
        t0 = time.perf_counter()
        try:
            got = jax.block_until_ready(kernel())
        except Exception as e:  # a compiler refusal is that case's finding
            c.check(label, False, f"{type(e).__name__}: {str(e)[:1500]}")
            return
        first_call_s = time.perf_counter() - t0
        err, scale, finite = (
            x.item() for x in measure(got, want, jnp.asarray(valid)))
        rows.append({"case": label, "max_abs_err": round(err, 5),
                     "ref_max": round(scale, 3),
                     "first_call_s": round(first_call_s, 2)})
        c.check(label, finite and err <= TOL * scale,
                {"err": err, "ref_max": scale})

    for name, H, KV, hd, win in geoms:
        G = H // KV
        for quantized in (False, True):
            tag = f"{name}/G{G}hd{hd}/{'int8' if quantized else 'bf16'}"
            t0 = time.time()
            kk, kv_, kq = jax.random.split(jax.random.key(H * 1000 + hd), 3)
            k = jax.random.normal(kk, (L, B, KV, C, hd), jnp.bfloat16)
            v = jax.random.normal(kv_, (L, B, KV, C, hd), jnp.bfloat16)
            if quantized:
                k8, ks = _quantize_kv(k)
                v8, vs = _quantize_kv(v)
                cache = {"k": k8, "v": v8, "ks": ks, "vs": vs}
            else:
                cache = {"k": k, "v": v}
            cache_p = {n: a[:, :BP] for n, a in cache.items()}
            pads_p = pads[:BP]
            kd, vd = (a.astype(jnp.bfloat16)
                      for a in dequantize_cache_layer(cache, LAYER))
            # peaked softmax (score std ~3) so a masking slip moves outputs
            # by far more than bf16 rounding does
            qs = jax.random.normal(kq, (B, S, H, hd), jnp.bfloat16) * 3.0
            qp, q1, q5 = qs[:BP], qs[:, :1], qs[:, :5]
            for wname, w in (("global", 0), (f"win{win}", win)):
                wj = jnp.int32(w)
                # prefill, whole prompt and at a q_offset chunk (one
                # compiled kernel: the offset is a runtime scalar)
                for off in (0, OFF):
                    q_slots = off + jnp.broadcast_to(
                        jnp.arange(S)[None, :], (BP, S))
                    mask = prefill_attention_mask(pads_p, off + S, C)[:, off:]
                    want = dense(qp, kd[:BP], vd[:BP], mask, q_slots, wj)
                    valid = (q_slots >= pads_p[:, None])[:, :, None, None]
                    compare(
                        f"{tag}/{wname}/prefill@{off}",
                        lambda: flash_prefill_attention(
                            qp, cache_p, LAYER, pads_p, G, wj,
                            jnp.int32(off), interpret=interpret),
                        want, valid)
                # decode (one query at a shared fill)
                mask = decode_attention_mask(pads, fill, C)
                q_slots = jnp.full((B, 1), fill)
                want = dense(q1, kd, vd, mask, q_slots, wj)
                compare(
                    f"{tag}/{wname}/decode",
                    lambda: flash_decode_attention(
                        q1, cache, LAYER, pads, fill, G, wj,
                        interpret=interpret),
                    want, True)
                # verify: Sq=1 is the slot loop's every decode step,
                # Sq=5 the speculative step (k=4), per-row fills
                for Sq, q in ((1, q1), (5, q5)):
                    mask = verify_attention_mask(pads, fills, Sq, C)
                    q_slots = fills[:, None] + jnp.arange(Sq)[None, :]
                    want = dense(q, kd, vd, mask, q_slots, wj)
                    compare(
                        f"{tag}/{wname}/verify_sq{Sq}",
                        lambda q=q: flash_spec_verify_attention(
                            q, cache, LAYER, pads, fills, G, wj,
                            interpret=interpret),
                        want, True)
            print(f"  {tag}: {time.time() - t0:.1f}s", flush=True)

    # does block_until_ready wait for the device? (dispatch returns early;
    # a fetch after the block must find the work already done)
    n, reps = (256, 4) if args.rehearsal else (8192, 200)
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(a):
        return jax.lax.fori_loop(0, reps, lambda _i, y: (y @ a) * 1.0, a)

    float(chain(x)[0, 0])  # compile + warm, the fetch's slice program too
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    sync = {"dispatch_s": round(t1 - t0, 4), "block_s": round(t2 - t1, 4),
            "fetch_after_block_s": round(t3 - t2, 4),
            "flops": 2.0 * n ** 3 * reps}
    c.check("block_until_ready_waits",
            args.rehearsal or (t3 - t2) < max(0.05, 0.2 * (t2 - t1)), sync)
    return {**c.report(), "cases": rows, "tolerance": TOL,
            "geometries": [list(g) for g in geoms], "sync_probe": sync}


def e2e_engine_kwargs(tok_spec) -> dict:
    """The offline phase's engine configuration: chunk_size 7800 lands map
    prompts in the S=8192 bucket, int8 weights, W8A8 prefill (lossy; its
    quality cost is bounded in artifacts/quality_lossy_ab.json), and
    prefill in 2048-token chunks, which caps the prefill transients so that
    B=16 fits beside the int8 KV cache."""
    from vnsum_tpu.models import llama32_3b

    return dict(
        model_config=llama32_3b(max_seq_len=8448),
        tokenizer=tok_spec,
        batch_size=16,
        max_new_tokens=128,
        quantize=True,
        quantize_act=True,
        prefill_chunk_tokens=2048,
    )


def _pick_ragged_eos(outs: list[str], tok, budget: int = 128) -> tuple[int, ...]:
    """Pick the token id whose per-row frequency makes the EXPECTED
    termination step ~budget/3 under sampled decode: with ~f occurrences per
    ``budget``-token row, per-step hit probability is ~f/budget, so
    E[termination] ~ budget/f. f~3 puts the average stop around step 40 of
    128 — most rows finish well before the budget at scattered depths, the
    shape real summaries produce."""
    from collections import Counter

    rows = [tok.encode(o) for o in outs if o]
    rows = [r for r in rows if r]
    if not rows:
        return (10,)
    counts: Counter = Counter()
    for r in rows:
        counts.update(r)
    target = 3.0 * len(rows)  # ~3 occurrences per row on average
    best = min(counts, key=lambda b: (abs(counts[b] - target), b))
    return (int(best),)


def _offline_engine(args, sz, tok_spec):
    """The offline phase's engine (e2e_engine_kwargs), or its tiny
    interpret-mode stand-in for the rehearsal."""
    from vnsum_tpu.backend.engine import TpuBackend

    if not args.rehearsal:
        return TpuBackend(**e2e_engine_kwargs(tok_spec))
    from vnsum_tpu.models import tiny_llama

    return TpuBackend(
        model_config=tiny_llama(
            vocab_size=sz["bpe_vocab"] + 64, max_seq_len=sz["offline_seq"]),
        tokenizer=tok_spec, batch_size=sz["offline_batch"],
        max_new_tokens=sz["offline_max_new"], quantize=True,
        quantize_act=True, prefill_chunk_tokens=sz["offline_prefill_chunk"],
        interpret=True,
    )


def phase_offline(args) -> dict:
    import jax

    from vnsum_tpu.core.config import GenerationConfig, PipelineConfig
    from vnsum_tpu.data.synthesize import synthesize_corpus
    from vnsum_tpu.models.fixtures import train_bpe_tokenizer
    from vnsum_tpu.pipeline.cli import failures
    from vnsum_tpu.pipeline.runner import PipelineRunner

    sz = sizes(args.rehearsal)
    c = Checks()
    root = Path(args.work) / "offline"
    corpus = synthesize_corpus(
        root / "corpus", n_docs=sz["docs"], tokens_per_doc=sz["words_per_doc"],
        summary_tokens=714, seed=7, ragged=0.2,
    )
    doc_paths = sorted((root / "corpus/doc").glob("*.txt"))
    # the quality-run configuration tokenizes with the checkpoint's BPE, not
    # raw bytes; train one on the corpus
    hf_tok = train_bpe_tokenizer(
        (p.read_text(encoding="utf-8") for p in doc_paths),
        vocab_size=sz["bpe_vocab"],
    )
    hf_tok.save_pretrained(str(root / "tok"))
    tok_spec = f"hf:{root / 'tok'}"
    sample = doc_paths[0].read_text(encoding="utf-8")
    bytes_per_tok = len(sample.encode()) / len(hf_tok.encode(sample))

    t0 = time.time()
    backend = _offline_engine(args, sz, tok_spec)
    init_s = time.time() - t0
    c.check("engine_platform_is_tpu",
            backend.platform == "tpu" or args.rehearsal, backend.platform)

    # random-init weights under greedy decode can emit EOS at step 0; use
    # the sampled ragged-EOS recipe, at full batch so the dominant
    # (B, S=8192) program is the one the probe compiles
    raw = b" ".join(p.read_text(encoding="utf-8").encode() for p in doc_paths)
    step = int(sz["probe_tokens"] * bytes_per_tok)
    nb = backend.batch_size
    if len(raw) < nb * step:
        raise RuntimeError(f"corpus too small for the probe: {len(raw)} < "
                           f"{nb * step}")
    probe = backend.generate(
        ["Tóm tắt: " + raw[i * step:(i + 1) * step].decode("utf-8", "ignore")
         for i in range(nb)],
        config=GenerationConfig(temperature=1.0, seed=11),
    )
    max_new = backend.max_new_tokens
    eos = _pick_ragged_eos(probe, backend.tok, max_new)
    backend.gen_cfg = GenerationConfig(
        max_new_tokens=max_new, temperature=1.0, seed=11,
        eos_ids=eos,
    )

    model = "llama3.2-3b"
    cfg = PipelineConfig(
        approach="mapreduce", models=[model], backend="tpu",
        docs_dir=str(root / "corpus/doc"),
        summary_dir=str(root / "corpus/summary"),
        generated_summaries_dir=str(root / "gen"),
        results_dir=str(root / "results"), logs_dir=str(root / "logs"),
        chunk_size=sz["chunk_size"], chunk_overlap=sz["chunk_size"] // 39,
        token_max=sz["token_max"], max_new_tokens=max_new,
        batch_size=backend.batch_size, tokenizer=tok_spec,
    )
    t0 = time.time()
    results = PipelineRunner(cfg, backend_factory=lambda _m: backend).run()
    pipeline_s = time.time() - t0

    rec = results.summarization[model]
    details = rec["processing_details"]
    st = backend.stats
    c.check("no_pipeline_failures", not failures(results), failures(results))
    c.check("every_document_success",
            rec["successful"] == sz["docs"] and rec["failed"] == 0
            and all(d["status"] == "success" for d in details),
            {"successful": rec["successful"], "failed": rec["failed"]})
    c.check("generated_tokens_positive", st.generated_tokens > 0,
            st.generated_tokens)
    S_big = 8192 if not args.rehearsal else max(s for _b, s in st.by_bucket)
    full = {f"B={b},S={s}": n for (b, s), n in st.by_bucket.items()}
    c.check("full_batch_dispatch_in_top_bucket",
            args.rehearsal
            or st.by_bucket.get((backend.batch_size, S_big), 0) >= 1, full)
    c.check("reduce_dispatch_ran",
            sum(d["llm_calls"] for d in details) > rec["total_chunks"],
            {"llm_calls": sum(d["llm_calls"] for d in details),
             "chunks": rec["total_chunks"]})
    ev = results.evaluation.get(model, {})
    c.check("evaluation_present",
            "rouge_scores" in ev and "bert_scores" in ev
            and "semantic_similarity" in ev, sorted(ev))
    paths = st.attention_paths
    c.check("attention_path_kernel",
            bool(paths) and all(p == "kernel" for prog in paths.values()
                                for p in prog.values()), paths)
    if not args.rehearsal:
        # flash resolved on: the generate program carries Mosaic custom calls
        B, S = backend.batch_size, 8192
        lowered = backend._make_fn(
            B, S, max_new, backend.gen_cfg
        ).lower(
            backend.params, jax.ShapeDtypeStruct((B, S), "int32"),
            jax.ShapeDtypeStruct((B,), "int32"), 0,
        ).as_text()
        c.check("mosaic_custom_calls_in_generate_program",
                "tpu_custom_call" in lowered,
                lowered.count("tpu_custom_call"))
    return {
        **c.report(),
        "corpus": {"docs": sz["docs"],
                   "avg_words": corpus["documents"]["avg_tokens_per_file"],
                   "bytes_per_bpe_token": round(bytes_per_tok, 2)},
        "engine_init_s": round(init_s, 1),
        "pipeline_s": round(pipeline_s, 1),
        "engine_first_call_s": round(st.compile_seconds, 1),
        "dispatches": full, "attention_paths": paths,
        "documents": [{k: d[k] for k in ("filename", "num_chunks", "llm_calls",
                                         "summary_length_chars", "status")}
                      for d in details],
        "generated_tokens": st.generated_tokens,
        "prompt_tokens": st.prompt_tokens,
        "eos_ids": list(eos),
        "evaluation_keys": sorted(ev),
    }


def phase_mesh(args) -> dict:
    import jax

    n = jax.device_count()
    if n < 4:
        return {"ok": True, "skipped": f"{n} devices", "checks": {}}
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import llama32_3b, tiny_llama
    from vnsum_tpu.parallel import make_mesh

    sz = sizes(args.rehearsal)
    c = Checks()
    runs = {}
    filler = "Quốc hội đã thông qua nghị quyết về phát triển kinh tế xã hội. "
    prompts = [
        (filler * (sz["mesh_prompt_bytes"] // len(filler.encode()) + 1))
        + f"(tài liệu {i})" for i in range(sz["mesh_batch"])
    ]
    for spec in ({"model": 4}, {"data": 4}):
        tag = ",".join(f"{k}={v}" for k, v in spec.items())
        mesh = make_mesh(spec)
        model_cfg = (
            tiny_llama(max_seq_len=sz["mesh_seq"], n_heads=8, n_kv_heads=4)
            if args.rehearsal else llama32_3b(max_seq_len=sz["mesh_seq"])
        )
        t0 = time.time()
        be = TpuBackend(
            model_config=model_cfg, mesh=mesh, batch_size=sz["mesh_batch"],
            max_new_tokens=sz["mesh_max_new"], quantize=True,
            quantize_act=True, interpret=args.rehearsal,
            generation=GenerationConfig(temperature=1.0, seed=5),
            # the prefix pool keeps KV blocks after the call: cache shards
            # that can be inspected, not only a program's temporaries
            cache_blocks=128,
        )
        before = _memory()
        outs = be.generate(prompts)
        wall = time.time() - t0
        c.check(f"{tag}/outputs", len(outs) == len(prompts)
                and be.stats.generated_tokens > 0, be.stats.generated_tokens)
        c.check(f"{tag}/attention_path_kernel",
                all(p == "kernel" for prog in be.stats.attention_paths.values()
                    for p in prog.values()), be.stats.attention_paths)
        # every device holds parameter shards, not device 0 alone
        leaves = jax.tree.leaves(be.params)
        holders = {s.device.id for leaf in leaves
                   for s in leaf.addressable_shards}
        c.check(f"{tag}/params_on_every_device", len(holders) == 4,
                sorted(holders))
        if "model" in spec:
            wq = be.params["layers"]["wq"]
            wq = wq["q"] if isinstance(wq, dict) else wq
            shard = wq.addressable_shards[0].data.shape
            c.check(f"{tag}/wq_is_sharded", shard != wq.shape,
                    {"global": list(wq.shape), "shard": list(shard)})
        pool_k = be.prefix_cache.store.pool["k"]  # [N, L, KV, BLK, hd]
        pool_holders = {s.device.id for s in pool_k.addressable_shards}
        kv_per_shard = pool_k.addressable_shards[0].data.shape[2]
        c.check(f"{tag}/cache_blocks_on_every_device",
                len(pool_holders) == 4
                and be.prefix_cache.stats_dict()["blocks_used"] > 0
                and kv_per_shard == pool_k.shape[2] // spec.get("model", 1),
                {"holders": sorted(pool_holders),
                 "kv_heads_per_shard": kv_per_shard,
                 "stats": be.prefix_cache.stats_dict()})
        mem = _memory()
        held = [m.get("bytes_in_use", 0) for m in mem]
        # resident bytes are parameter + pool shards: even, not device 0's
        c.check(f"{tag}/resident_bytes_balanced",
                args.rehearsal or min(held) > 0.5 * max(held), held)
        runs[tag] = {"wall_s": round(wall, 1),
                     "first_call_s": round(be.stats.compile_seconds, 1),
                     "memory_before_generate": before, "memory": mem,
                     "attention_paths": be.stats.attention_paths}
        del be
    return {**c.report(), "runs": runs}


def _generate_twice(backend, prompts, c: "Checks"):
    """Two calls of ``backend.generate`` (the first compiles), with the
    checks every one-shot family phase makes of them: (outputs, seconds of
    the first call, of the second)."""
    t0 = time.time()
    backend.generate(prompts)
    first_s = time.time() - t0
    t0 = time.time()
    outs = backend.generate(prompts)
    second_s = time.time() - t0
    paths = backend.stats.attention_paths
    c.check("attention paths are the kernels", bool(paths) and all(
        p == "kernel" for prog in paths.values() for p in prog.values()),
        paths)
    c.check("every row answered", len(outs) == len(prompts)
            and sum(bool(o) for o in outs) >= len(outs) - 1)
    return outs, first_s, second_s


def phase_experts(args) -> dict:
    """The DeepSeek-V2 family on the one-shot path: latent attention with
    an absorbed decode, sparse experts of which this chip holds 40 of 160,
    shared experts — at the published widths with one dense and two expert
    layers, int8, through ``TpuBackend.generate``; and its logits against
    the plain reference (prefill and decode steps)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_deepseek_v2 as reference
    from benchmarks.engine_setup_deepseek_v2 import sizes_from
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.deepseek import deepseek_v2, tiny_deepseek
    from vnsum_tpu.models.quant import init_params_quantized

    sz = sizes(args.rehearsal)
    c = Checks()
    if args.rehearsal:
        cfg = tiny_deepseek(experts_held=8, max_seq_len=sz["experts_seq"])
    else:
        cfg = deepseek_v2(n_layers=3, experts_held=40,
                          max_seq_len=sz["experts_seq"])
    params = jitted_init(init_params_quantized, cfg, 3)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", params=params,
        batch_size=sz["experts_batch"], max_new_tokens=sz["experts_max_new"],
        quantize=True, quantize_act=True, quantize_kv=False,
        prefill_chunk_tokens=sz["experts_prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=3),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["experts_prompt_bytes"] - 300 * i, f"e{i}")
               for i in range(sz["experts_batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    st = backend.stats
    k, layers = cfg.num_experts_per_tok, cfg.n_expert_layers
    # both calls count: prompt tokens go through prefill, and every row runs
    # every decode step of the budget (no extra EOS here, but a sampled EOS
    # ends nothing early for the others), so at least the prompts' tokens
    c.check("slots routed cover the prompts' tokens",
            st.expert_slots_routed >= st.prompt_tokens * k * layers,
            (st.expert_slots_routed, st.prompt_tokens * k * layers))
    share = st.expert_slots_held / max(st.expert_slots_routed, 1)
    want = cfg.n_held / cfg.n_routed_experts
    c.check("held share near experts held / experts routed",
            0.4 * want <= share <= 1.8 * want, (share, want))
    tokens = np.asarray(st.expert_tokens)
    c.check("expert_tokens add up to slots held",
            tokens.shape == (layers, cfg.n_held)
            and int(tokens.sum()) == st.expert_slots_held, tokens.shape)

    n, bucket, steps = sz["experts_parity"]
    ids = backend.tok.encode(_vn_text((n + steps) * 3, "parity"))[:n + steps]
    got = np.asarray(backend.prefill_then_decode_logits(
        ids[:n], ids[n:], bucket=bucket), np.float64)
    sizes_ref = sizes_from(cfg)
    want_l = np.asarray(jax.jit(lambda p, t: reference.logits(
        p, t, sizes_ref, expert_offset=cfg.expert_offset, last=steps + 1))(
        backend.params, jnp.asarray(ids, jnp.int32)), np.float64)
    errors = (np.linalg.norm(got - want_l, axis=-1)
              / np.linalg.norm(want_l, axis=-1))
    c.check("logits within 0.05 of the plain reference, prefill and decode",
            bool(np.all(np.isfinite(errors)) and errors.max() <= 0.05),
            errors.tolist())
    rep = c.report()
    rep.update(first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2),
               parity_errors=errors.tolist(), held_share=share,
               expert_tokens=tokens.tolist(),
               engine=backend.describe())
    return rep


def _expert_family(args, key: str, cfg, expert_layers: int, reference,
                   sizes_ref: dict, *, window: bool = True,
                   more=None) -> dict:
    """What the phases ``moe``, ``laguna``, ``nemotron_h`` and ``lfm2`` share: a
    family with every expert held, at the published widths and int8,
    through ``TpuBackend.generate`` (with ``window``: GQA window layers and
    prompts longer than the window); its counters; and its logits against
    its plain reference (prefill and decode steps). ``key`` prefixes the
    phase's entries of ``sizes``; ``more(c, sz, backend, state, ids)`` adds
    the family's own checks of the parity run's state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.quant import init_params_quantized

    sz = {k[len(key) + 1:]: v for k, v in sizes(args.rehearsal).items()
          if k.startswith(key + "_")}
    c = Checks()
    params = jitted_init(init_params_quantized, cfg, 3)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", params=params,
        batch_size=sz["batch"], max_new_tokens=sz["max_new"],
        quantize=True, quantize_act=True, quantize_kv=True,
        prefill_chunk_tokens=sz["prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=3),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["prompt_bytes"] - 300 * i // 4, f"{key[0]}{i}")
               for i in range(sz["batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    st = backend.stats
    k, layers = cfg.num_experts_per_tok, expert_layers
    c.check("slots routed cover the prompts' tokens",
            st.expert_slots_routed >= st.prompt_tokens * k * layers,
            (st.expert_slots_routed, st.prompt_tokens * k * layers))
    c.check("every expert is held: slots held == slots routed",
            st.expert_slots_held == st.expert_slots_routed,
            (st.expert_slots_held, st.expert_slots_routed))
    tokens = np.asarray(st.expert_tokens)
    c.check("expert_tokens add up to slots held",
            tokens.shape == (layers, cfg.n_held)
            and int(tokens.sum()) == st.expert_slots_held, tokens.shape)
    steps = st.expert_decode_layer_steps
    per_step = st.expert_decode_touched / max(steps, 1)
    c.check("a decode step touches between k and rows x k experts a layer",
            steps == 2 * layers * sz["max_new"]
            and k <= per_step <= min(cfg.n_held, sz["batch"] * k),
            (steps, per_step))
    blocks = st.prefill_blocks
    if window:
        c.check("prompts leave the window: window layers skip cells below "
                "it and compute more scores than the window needs",
                # (the rehearsal's window is narrower than one grid cell)
                (args.rehearsal or blocks.get("dead_causal", 0) > 0)
                and blocks.get("edge", 0) > 0
                and blocks.get("window_scores_computed", 0)
                > blocks.get("window_scores_needed", 0) > 0, blocks)

    n, bucket, n_steps = sz["parity"]
    ids = backend.tok.encode(_vn_text((n + n_steps) * 3, "parity"))[:n + n_steps]
    got, state = backend.prefill_then_decode_logits(
        ids[:n], ids[n:], bucket=bucket, return_state=True)
    got = np.asarray(got, np.float64)
    # the routers' picks of the scored positions: the reference takes them
    # where they are the top-k of its own logits inside a tie band
    rows = state["rows"]
    picks = jnp.asarray((rows["picks"] if isinstance(rows, dict) else rows)[
        :, :, 0].swapaxes(0, 1))
    want_l = np.asarray(jax.jit(lambda p, t, theirs: reference.forward(
        p, t, sizes_ref, last=n_steps + 1, theirs=theirs,
        tie_band=sz["tie_band"])["logits"])(
        backend.params, jnp.asarray(ids, jnp.int32), picks), np.float64)
    errors = (np.linalg.norm(got - want_l, axis=-1)
              / np.linalg.norm(want_l, axis=-1))
    c.check(f"logits within {sz['tolerance']} of the plain reference, "
            "prefill and decode",
            bool(np.all(np.isfinite(errors))
                 and errors.max() <= sz["tolerance"]), errors.tolist())
    extra = more(c, sz, backend, state, ids) if more else {}
    rep = c.report()
    rep.update(extra)
    rep.update(first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2),
               parity_errors=errors.tolist(),
               distinct_experts_a_decode_step=per_step,
               prefill_blocks=blocks, expert_tokens=tokens.tolist(),
               engine=backend.describe())
    return rep


def phase_moe(args) -> dict:
    """The SmallThinker family on the one-shot path: GQA at 28/4 heads with
    rotary 4096-window layers and position-free global layers, 64 ReGLU
    experts all held, routed on the layer's input — one period of four
    layers (``_expert_family``)."""
    from benchmarks import reference_smallthinker as reference
    from benchmarks.engine_setup_smallthinker import sizes_from
    from vnsum_tpu.models.smallthinker import (
        smallthinker_21b_a3b,
        tiny_smallthinker,
    )

    sz = sizes(args.rehearsal)
    make = tiny_smallthinker if args.rehearsal else smallthinker_21b_a3b
    cfg = make(n_layers=sz["moe_layers"], max_seq_len=sz["moe_seq"])
    return _expert_family(args, "moe", cfg, cfg.n_layers, reference,
                          sizes_from(cfg))


def phase_laguna(args) -> dict:
    """The Laguna family on the one-shot path: GQA at 48/8 heads on full
    layers and 72/8 in a 512 window (both kernels at G = 6 and 9 in one
    program), the per-head gate, YaRN partial and plain rotary, the leading
    dense layer, 256 SwiGLU experts top-10 and the shared one all held — the
    dense layer and one period of four (``_expert_family``)."""
    from benchmarks import reference_laguna as reference
    from benchmarks.engine_setup_laguna import sizes_from
    from vnsum_tpu.models.laguna import laguna_s_2_1, tiny_laguna

    sz = sizes(args.rehearsal)
    make = tiny_laguna if args.rehearsal else laguna_s_2_1
    cfg = make(n_layers=sz["laguna_layers"], max_seq_len=sz["laguna_seq"])
    return _expert_family(args, "laguna", cfg, cfg.n_sparse_layers,
                          reference, sizes_from(cfg))


def phase_nemotron_h(args) -> dict:
    """The Nemotron-H family on the one-shot path, which is also the
    state-space phase: the first six layers ``MEMEM*`` at the published
    widths — Mamba-2 at 8 groups of B and C through both scan kernels, 128
    non-gated relu2 experts top-6 by sigmoid scores and the shared one all
    held, GQA at 16 query heads a KV head — a recurrent state, expert
    counters and keys and values in one carry (``_expert_family``), and
    the first Mamba layer's recurrent state against the reference's after
    the prompt and after each forced token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_nemotron_h as reference
    from benchmarks.engine_setup_nemotron_h import sizes_from
    from vnsum_tpu.models.nemotron_h import (
        nemotron_3_nano_30b_a3b,
        tiny_nemotron_h,
    )

    sz = sizes(args.rehearsal)
    make = tiny_nemotron_h if args.rehearsal else nemotron_3_nano_30b_a3b
    cfg = make(n_layers=sz["nemotron_h_layers"],
               max_seq_len=sz["nemotron_h_seq"])
    sizes_ref = sizes_from(cfg)

    def state_check(c, sz, backend, state, ids):
        n_rows = sz["parity"][2] + 1
        want = np.asarray(jax.jit(
            lambda p, t: reference.state_as_the_program_lays_it(
                reference.forward(p, t, sizes_ref, last=n_rows)["ssm_rows"])
        )(backend.params, jnp.asarray(ids, jnp.int32)), np.float64)
        # [rows, first | last, 1, N, HP] against [first | last, rows, N, HP]
        mine = np.asarray(state["rows"]["ssm"], np.float64)[:, 0, 0]
        errors = [float(np.linalg.norm(mine[r] - want[0, r])
                        / np.linalg.norm(want[0, r])) for r in range(n_rows)]
        c.check(f"the first Mamba layer's state within "
                f"{sz['state_tolerance']} of the reference's, after the "
                "prompt and after each forced token",
                max(errors) <= sz["state_tolerance"], errors)
        blocks = backend.stats.prefill_blocks
        c.check("the scan's tokens are counted beside the attention's cells",
                0 < blocks.get("scan_tokens_real", 0)
                <= blocks.get("scan_tokens_computed", 0)
                and blocks.get("edge", 0) > 0, blocks)
        return {"state_errors": errors,
                "state_dtype": str(state["cache"]["ssm"].dtype)}

    return _expert_family(args, "nemotron_h", cfg, cfg.n_sparse, reference,
                          sizes_ref, window=False, more=state_check)


def phase_lfm2(args) -> dict:
    """The LFM2-MoE family on the one-shot path: the first seven layers
    ``c c A c c c A`` at the published widths — the gated short convolution
    at three taps with its two-token tail, rotary QK-normed GQA at 32/8
    heads of 64, the two dense layers and five layers of 32 gated experts
    top-4 by sigmoid score + bias, all held — tails, expert counters and
    keys and values in one carry (``_expert_family``), and the first
    convolution layer's tail against the reference's after the prompt and
    after each forced token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_lfm2 as reference
    from benchmarks.engine_setup_lfm2 import sizes_from
    from vnsum_tpu.models.lfm2 import lfm2_8b_a1b, tiny_lfm2

    sz = sizes(args.rehearsal)
    make = tiny_lfm2 if args.rehearsal else lfm2_8b_a1b
    cfg = make(n_layers=sz["lfm2_layers"], max_seq_len=sz["lfm2_seq"])
    sizes_ref = sizes_from(cfg)

    def tail_check(c, sz, backend, state, ids):
        n_rows = sz["parity"][2] + 1
        want = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, sizes_ref,
                                           last=n_rows)["tail_rows"]
        )(backend.params, jnp.asarray(ids, jnp.int32)), np.float64)
        # [rows, first | last, 1, K - 1, D] against [first | last, rows, ..]
        mine = np.asarray(state["rows"]["tail"].astype(np.float32),
                          np.float64)[:, 0, 0]
        errors = [float(np.linalg.norm(mine[r] - want[0, r])
                        / np.linalg.norm(want[0, r])) for r in range(n_rows)]
        c.check(f"the first convolution layer's tail within "
                f"{sz['state_tolerance']} of the reference's, after the "
                "prompt and after each forced token",
                max(errors) <= sz["state_tolerance"], errors)
        blocks = backend.stats.prefill_blocks
        c.check("the convolution's tokens are counted beside the "
                "attention's cells",
                0 < blocks.get("conv_tokens_real", 0)
                <= blocks.get("conv_tokens_computed", 0)
                and blocks.get("edge", 0) > 0, blocks)
        return {"tail_errors": errors,
                "tail_dtype": str(state["cache"]["conv"].dtype)}

    return _expert_family(args, "lfm2", cfg, cfg.n_sparse, reference,
                          sizes_ref, window=False, more=tail_check)


def phase_ling(args) -> dict:
    """The Ling-3.0-flash family on the one-shot path. First its two
    delta-rule kernels (``ops/kda_scan.py``) compiled at the published
    widths — a row piece of the prefill scan under ragged pads, in place at
    rows of a larger state, and the one-token update — against their XLA
    forms, each timed inside a jitted loop (the scan, as the layer calls it
    — the gate's projection, ``A_log`` and ``dt_bias`` in, no prologue
    around the kernel —, also at the cell's call: four rows of a
    2,048-token chunk into the map dispatch's stacked state of 10 layers x
    24 rows). Then one small generate: the
    first period ``K K K K K M`` at the published widths (five KDA layers,
    the latent attention at 32 heads with no compressed query, both dense
    layers and four sparse layers of which this chip holds 128 of 512
    experts), int8 and W8A8, twice through ``TpuBackend.generate``, with
    its counters. The logits against ``benchmarks/reference_ling.py`` are
    the cell's own set-up (``--workload
    ling-3.0-flash-ep4-l12-int8.offline-mapreduce-8k-kda-ep``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.ling import ling_3_0_flash, tiny_ling
    from vnsum_tpu.models.quant import init_params_quantized
    from vnsum_tpu.ops import kda_scan

    sz = {k[5:]: v for k, v in sizes(args.rehearsal).items()
          if k.startswith("ling_")}
    c = Checks()
    R, S, H, d = sz["kernel"]
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    chunk = 32 if args.rehearsal else 64
    ks = jax.random.split(jax.random.key(5), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(ks[0], (R, S, H, d))) * d ** -0.5)
    k = unit(jax.random.normal(ks[1], (R, S, H, d)))
    v = jax.random.normal(ks[2], (R, S, H, d))
    # a layer's gate: the projection in the inputs' type, A_log and dt_bias
    # as ``models/ling.py::float_leaves`` draws them, and a projection wide
    # enough that the log-decays run from the bound of -5 to none
    a = jax.random.uniform(ks[3], (R, S, H, d), minval=-4.0,
                           maxval=10.0).astype(dtype)
    gate = dict(
        A_log=jnp.log(jax.random.uniform(ks[6], (H,), minval=0.5, maxval=2.0)),
        dt_bias=jax.random.uniform(ks[7], (H, d), minval=-8.0, maxval=-1.0),
        lower_bound=-5.0)
    g = kda_scan.kda_gate(a, **gate)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, S, H)))
    pads = jnp.asarray([0, 1, S // 3, S - S // 4][:R], jnp.int32)
    real = jnp.arange(S)[None, :] >= pads[:, None]
    q, k, v = (jnp.where(real[:, :, None, None], x, 0).astype(dtype)
               for x in (q, k, v))
    beta = jnp.where(real[:, :, None], beta, 0.0)
    rows = jnp.arange(R, dtype=jnp.int32)[::-1] * 2
    state = jnp.zeros((2, 2 * R, H, d, d), jnp.float32)
    interpret = bool(args.rehearsal)
    o, new = jax.jit(lambda *x: kda_scan.kda_prefill_scan(
        *x, **gate, chunk=chunk, interpret=interpret))(
        q, k, v, a, beta, state, 1, pads, rows)
    want_o, want = jax.jit(lambda *a: kda_scan.kda_chunked_xla(
        *a, chunk))(q, k, v, g, beta, state[1, rows])

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errs = {"prefill_out": rel(o, want_o), "prefill_state": rel(new[1, rows],
                                                                want)}
    c.check("kda_prefill_scan is its XLA form (a row piece under ragged "
            "pads, in place)", max(errs.values()) <= sz["kernel_tolerance"]
            and not np.asarray(new[0]).any()
            and not np.asarray(new[1, rows + 1]).any(), errs)
    step = (q[:, -1], k[:, -1], v[:, -1], g[:, -1], beta[:, -1])
    o1, new1 = jax.jit(lambda *a: kda_scan.kda_decode_update(
        *a, interpret=interpret))(*step, new[:, rows], 1)
    want_o1, want1 = jax.jit(kda_scan.kda_step_xla)(*step, new[1, rows])
    errs.update(decode_out=rel(o1, want_o1), decode_state=rel(new1[1], want1))
    c.check("kda_decode_update is the one-token step",
            max(errs["decode_out"], errs["decode_state"]) <= 1e-5, errs)

    def timed(fn, *a, n=8):
        """Seconds a call inside a jitted loop of ``n`` (a lone call costs
        the host as much as a small kernel)."""
        loop = jax.jit(lambda *a: jax.lax.fori_loop(
            0, n, lambda _, carry: fn(*a[:-1], carry)[1], a[-1]))
        jax.block_until_ready(loop(*a))
        t0 = time.time()
        jax.block_until_ready(loop(*a))
        return (time.time() - t0) / n

    # as the cell calls it: a piece's rows of the map dispatch's stacked
    # state (10 KDA layers x 24 rows), every token live
    cell_rows = jnp.arange(R, dtype=jnp.int32) + R
    times = {
        "kda_prefill_scan_s": timed(
            lambda q, k, v, a, b, st: kda_scan.kda_prefill_scan(
                q, k, v, a, b, st, 1, pads * 0, rows, **gate, chunk=chunk,
                interpret=interpret), q, k, v, a, beta, state),
        "kda_prefill_scan_cell_s": timed(
            lambda q, k, v, a, b, st: kda_scan.kda_prefill_scan(
                q, k, v, a, b, st, 7, pads * 0, cell_rows, **gate,
                chunk=chunk, interpret=interpret), q, k, v, a, beta,
            jnp.zeros((10, 24, H, d, d), jnp.float32)),
        "kda_decode_update_s": timed(
            lambda *a: kda_scan.kda_decode_update(
                *a[:5], a[5], 1, interpret=interpret),
            *step, new[:, rows])}

    make = tiny_ling if args.rehearsal else ling_3_0_flash
    cfg = make(n_layers=sz["layers"], experts_held=sz["held"],
               max_seq_len=sz["seq"])
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte",
        params=jitted_init(init_params_quantized, cfg, 3),
        batch_size=sz["batch"], max_new_tokens=sz["max_new"], quantize=True,
        quantize_act=True, quantize_kv=False,
        prefill_chunk_tokens=sz["prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=3),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["prompt_bytes"] - 300 * i // 4, f"g{i}")
               for i in range(sz["batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    st = backend.stats
    layers, top = cfg.n_sparse, cfg.num_experts_per_tok
    c.check("slots routed cover the prompts' tokens, a share of them held",
            st.expert_slots_routed >= st.prompt_tokens * top * layers
            and 0 < st.expert_slots_held < st.expert_slots_routed,
            (st.expert_slots_routed, st.expert_slots_held))
    tokens = np.asarray(st.expert_tokens)
    c.check("expert_tokens add up to slots held",
            tokens.shape == (layers, cfg.n_held)
            and int(tokens.sum()) == st.expert_slots_held, tokens.shape)
    blocks = st.prefill_blocks
    c.check("the scan's tokens and the latent kernel's keys are counted",
            0 < blocks.get("kda_tokens_real", 0)
            <= blocks.get("kda_tokens_computed", 0)
            and 0 < blocks.get("latent_keys_real", 0)
            <= blocks.get("latent_keys_expanded", 0), blocks)
    rep = c.report()
    rep.update(kernel_errors=errs, kernel_seconds=times,
               first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2), prefill_blocks=blocks,
               held_share=st.expert_slots_held / max(st.expert_slots_routed,
                                                     1),
               engine=backend.describe())
    return rep


def phase_brumby(args) -> dict:
    """The Brumby family on the one-shot path. First its two power-retention
    kernels (``ops/power_retention.py``) compiled at the published widths —
    a row piece of the chunked scan under ragged pads, in place at rows of a
    larger state, and the one-token update — against their XLA forms, each
    timed in one jitted program of eight calls (the scan also at the cell's
    call: one row of a 2,048-token chunk into the map dispatch's stacked
    state of 10 layers x 12 rows). The update ALONE at the cell's 12 and 4
    rows (``retention_decode_update_alone``): ms a call and GB/s of the
    bytes the kernel moves, by the host's clock, the state donated; that no
    copy stands beside it was checked twice — the compiled program's
    temporaries are under one layer's block (a check of this phase), and
    the device trace of the same eight calls shows the eight kernel events,
    the operands' pads, casts and transposes (a few us a call) and nothing
    of the state's size: the host's clock reads ~0.13 ms a call over the
    kernel's own events (1.36 for 1.22 ms at 12 rows; ``PERF.md`` section
    5, PR 61). Then one small generate: two
    layers at the published widths, int8 and W8A8, twice through
    ``TpuBackend.generate``, with its counters. The logits, the state and
    the normaliser against ``benchmarks/reference_brumby.py`` are the cell's
    own set-up (``--workload
    brumby-14b-l10-int8.offline-mapreduce-8k-retention``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.brumby import brumby_14b, tiny_brumby
    from vnsum_tpu.models.quant import init_params_quantized
    from vnsum_tpu.ops import power_retention as pr

    sz = {k[7:]: v for k, v in sizes(args.rehearsal).items()
          if k.startswith("brumby_")}
    c = Checks()
    R, S, H, KV, d = sz["kernel"]
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    chunk = 8 if args.rehearsal else 256
    how = dict(scale=d ** -0.5, eps=1e-6)
    interpret = bool(args.rehearsal)
    ks = jax.random.split(jax.random.key(5), 5)
    # q and k as a QK-norm leaves them (unit mean square), a layer's gates
    # from a head that forgets in ~8 tokens to one that keeps ~8,000
    q = jax.random.normal(ks[0], (R, S, H, d))
    k = jax.random.normal(ks[1], (R, S, KV, d))
    v = jax.random.normal(ks[2], (R, S, KV, d))
    gamma = jax.nn.log_sigmoid(
        jnp.linspace(2.0, 9.0, KV) + jax.random.normal(ks[3], (R, S, KV)))
    pads = jnp.asarray([1, S - S // 4, 0, S // 3][:R], jnp.int32)
    real = jnp.arange(S)[None, :] >= pads[:, None]
    q = q.astype(dtype)
    k, v = (jnp.where(real[:, :, None, None], x, 0).astype(dtype)
            for x in (k, v))
    T = pr.n_tiles(d)
    rows = jnp.arange(R, dtype=jnp.int32)[::-1] * 2
    state = jnp.zeros((2, 2 * R, KV, T, d, d), jnp.float32)
    norm = jnp.zeros((2, 2 * R, KV, d, d), jnp.float32)
    o, new, new_z = jax.jit(lambda *x: pr.retention_prefill_scan(
        *x, chunk=chunk, interpret=interpret, **how))(
        q, k, v, gamma, state, norm, 1, pads, rows)
    want_o, want, want_z = jax.jit(lambda *a: pr.retention_chunked_xla(
        *a, chunk, **how))(q, k, v, gamma, state[1, rows], norm[1, rows])

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errs = {"prefill_out": rel(o, want_o),
            "prefill_state": rel(new[1, rows], want),
            "prefill_normaliser": rel(new_z[1, rows], want_z)}
    c.check("retention_prefill_scan is its XLA form (a row piece under "
            "ragged pads, in place)",
            max(errs.values()) <= sz["kernel_tolerance"]
            and not np.asarray(new[0]).any()
            and not np.asarray(new[1, rows + 1]).any()
            and not np.asarray(o)[1, :int(pads[1])].any(), errs)
    step = (q[:, -1], k[:, -1], v[:, -1], gamma[:, -1])
    o1, new1, new_z1 = jax.jit(lambda *a: pr.retention_decode_update(
        *a, interpret=interpret, **how))(*step, new[:, rows],
                                         new_z[:, rows], 1)
    want_o1, want1, want_z1 = jax.jit(lambda *a: pr.retention_step_xla(
        *a, **how))(*step, new[1, rows], new_z[1, rows])
    errs.update(decode_out=rel(o1, want_o1), decode_state=rel(new1[1], want1),
                decode_normaliser=rel(new_z1[1], want_z1))
    # the write is float32 on the vector unit; the read's products run in
    # the inputs' type, as the scan's
    c.check("retention_decode_update is the one-token step",
            max(errs["decode_state"], errs["decode_normaliser"]) <= 1e-5
            and errs["decode_out"] <= sz["kernel_tolerance"], errs)

    def timed(fn, *a, n=8):
        """(seconds a call, the program's temporary bytes) of ``n`` calls
        chained in ONE jitted program (a lone call costs the host as much
        as a small kernel); the last two arguments are the state and the
        normaliser (their shapes: zeros are made here and donated), handed
        from call to call. The calls are written out, not a ``fori_loop``:
        carried through a loop the stacked state cost every call one
        layer's copy beside the kernel (3.05 ms a 12-row update where the
        cell's trace reads 1.3). The zeros are on the device BEFORE the
        clock starts: filling 4.15 GB takes ~5 ms, which the clock used to
        count (1.92 ms a call for the trace's 1.27)."""
        def chain(*a):
            carry = a[-2:]
            for _ in range(n):
                carry = fn(*a[:-2], *carry)[1:]
            return carry

        chained = jax.jit(
            chain, donate_argnums=(len(a) - 2, len(a) - 1)).lower(*a).compile()
        fresh = lambda: jax.block_until_ready(tuple(  # noqa: E731
            jnp.zeros(x.shape, x.dtype) for x in a[-2:]))
        jax.block_until_ready(chained(*a[:-2], *fresh()))
        carry = fresh()
        t0 = time.time()
        jax.block_until_ready(chained(*a[:-2], *carry))
        return ((time.time() - t0) / n,
                chained.memory_analysis().temp_size_in_bytes)

    # as the cell calls it: one row of the map dispatch's stacked state (10
    # layers x 12 rows), every token live; the update at all 12 rows
    B, L = (4, 3) if args.rehearsal else (12, 10)
    big = (jax.ShapeDtypeStruct((L, B, KV, T, d, d), jnp.float32),
           jax.ShapeDtypeStruct((L, B, KV, d, d), jnp.float32))
    times = {
        "retention_prefill_scan_s": timed(
            lambda q, k, v, g, st, z: pr.retention_prefill_scan(
                q, k, v, g, st, z, 1, pads * 0, rows, chunk=chunk,
                interpret=interpret, **how), q, k, v, gamma, state, norm)[0],
        "retention_prefill_scan_cell_s": timed(
            lambda q, k, v, g, st, z: pr.retention_prefill_scan(
                q, k, v, g, st, z, L - 1, pads[:1] * 0,
                jnp.asarray([B - 1], jnp.int32), chunk=chunk,
                interpret=interpret, **how),
            q[:1], k[:1], v[:1], gamma[:1], *big)[0]}

    def update_alone(n_rows: int) -> dict:
        """The update ALONE at ``n_rows`` rows of a stacked state of L
        layers: ms a call, GB/s of the bytes the kernel moves (a row's and
        layer's state and normaliser read and written once), and the
        program's temporary bytes — under one layer's block means no copy
        of the state stands beside the kernel."""
        stacked = tuple(jax.ShapeDtypeStruct((L, n_rows) + x.shape[2:],
                                             x.dtype) for x in big)
        s, temp = timed(
            lambda q, k, v, g, st, z: pr.retention_decode_update(
                q, k, v, g, st, z, L - 1, interpret=interpret, **how),
            *(jnp.concatenate([x] * (n_rows // R), 0) for x in step),
            *stacked)
        moved = 2 * 4 * n_rows * KV * (T * d * d + d * d)
        layer = 4 * n_rows * KV * T * d * d
        c.check(f"the update at {n_rows} rows runs in place: no copy of a "
                "layer's state beside it (a kernel interpreted keeps some)",
                interpret or temp < layer, (temp, layer))
        return {"ms_a_call": s * 1e3, "gb_s": moved / s / 1e9,
                "temp_bytes": temp}

    alone = {f"rows_{n}": update_alone(n)
             for n in ((4, 2) if args.rehearsal else (12, 4))}

    make = tiny_brumby if args.rehearsal else brumby_14b
    cfg = make(n_layers=sz["layers"], max_seq_len=sz["seq"])
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte",
        params=jitted_init(init_params_quantized, cfg, 3),
        batch_size=sz["batch"], max_new_tokens=sz["max_new"], quantize=True,
        quantize_act=True, quantize_kv=False,
        prefill_chunk_tokens=sz["prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=3),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["prompt_bytes"] - 300 * i // 4, f"g{i}")
               for i in range(sz["batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    blocks = backend.stats.prefill_blocks
    c.check("the scan's tokens are counted",
            0 < blocks.get("retention_tokens_real", 0)
            <= blocks.get("retention_tokens_computed", 0), blocks)
    state_leaves = backend.describe()["state_bytes_per_row"]
    c.check("the program carries a state and no keys and values",
            sorted(state_leaves) == ["norm", "ret"], state_leaves)
    rep = c.report()
    rep.update(kernel_errors=errs, kernel_seconds=times,
               retention_decode_update_alone=alone,
               first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2), prefill_blocks=blocks,
               engine=backend.describe())
    return rep


def phase_keye(args) -> dict:
    """The Keye family on the one-shot path. First the kernels of
    ``ops/sparse_attention.py`` compiled at the published widths over a
    stacked int8 cache of 16,640 slots — a row piece's selection
    (``dsa_index_select``) and masked attention (``dsa_prefill_attention``)
    under a left pad at a chunk's offset, in place at a row of a larger
    state, and a decode step's selection and masked walk
    (``dsa_decode_attention``) in the form the cell's dispatch runs them,
    EIGHT rows under ragged pads — against their XLA forms: every query
    keeps exactly min(visible, top-k) slots, the slots the two forms do not
    share are a few per mille (two roundings of one bfloat16 product), the
    attention agrees on the SAME set. The eight-row decode step also meets
    ``benchmarks/reference_keye.py`` (the cell's parity check runs ONE
    row): its sets against ``top_by_sort`` of the equations' scores in
    float32, its walk against a plain softmax over the kept slots of the
    multiplied-out cache. Each is timed in one jitted program
    of eight calls, the decode attention in BOTH its forms (the masked walk
    and ``decode_attention_gathered``: PERF.md keeps both readings). Then
    one small generate: two layers at the published widths, all 128
    experts, int8 and W8A8, prompts past the top-k, twice through
    ``TpuBackend.generate``, with its counters. The logits, the caches'
    rows and the selection against ``benchmarks/reference_keye.py`` are the
    cell's own set-up (``--workload
    keye-vl-2.0-l12-int8.offline-mapreduce-12k-dsa``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.keye import keye_vl_2_0_30b_a3b, tiny_keye
    from vnsum_tpu.models.llama import _attention, dequantize_cache_layer
    from vnsum_tpu.models.quant import init_params_quantized
    from vnsum_tpu.ops import sparse_attention as sa

    sz = {k[5:]: v for k, v in sizes(args.rehearsal).items()
          if k.startswith("keye_")}
    c = Checks()
    S, S_timed, C, H, KV, hd, Hi, di, topk, bq, bk = sz["kernel"]
    interpret = bool(args.rehearsal)
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    blocks = dict(block_k=bk, interpret=interpret)
    L, B = 2, 8
    ks = iter(jax.random.split(jax.random.key(63), 12))
    cache = {
        "k": jax.random.randint(next(ks), (L, B, KV, C, hd), -127, 128,
                                jnp.int8),
        "v": jax.random.randint(next(ks), (L, B, KV, C, hd), -127, 128,
                                jnp.int8),
        "ks": jax.random.uniform(next(ks), (L, B, KV, C), jnp.float32,
                                 0.005, 0.02),
        "vs": jax.random.uniform(next(ks), (L, B, KV, C), jnp.float32,
                                 0.005, 0.02),
        "ki": jax.random.normal(next(ks), (L, B, di, C)).astype(dtype),
    }
    # a row piece: one row of the state (row 2) whose pad ends inside the
    # cache, its chunk's queries ending at the cache's last prompt slot
    off = (C - S_timed) // 128 * 128 if not interpret else C - 2 * S_timed
    pad = jnp.asarray([off // 3 + 5], jnp.int32)
    rows = jnp.asarray([2], jnp.int32)

    def queries(n):
        kq = jax.random.split(jax.random.key(n), 3)
        return (jax.random.normal(kq[0], (1, n, H, hd)).astype(dtype),
                jax.random.normal(kq[1], (1, n, Hi, di)).astype(dtype),
                jax.random.normal(kq[2], (1, n, Hi), jnp.float32) * 0.03)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    q, q_idx, w_idx = queries(S)
    mask, last = jax.jit(lambda q, w, cache: sa.dsa_index_select(
        q, w, cache, 1, pad, off, rows, topk=topk, block_q=bq, **blocks))(
        q_idx, w_idx, cache)
    Cp = mask.shape[2]
    slot = jnp.arange(C)[None, None, :]
    visible = (slot >= pad[:, None, None]) & (
        slot <= off + jnp.arange(S)[None, :, None])
    scores = jax.jit(sa.index_scores_xla)(q_idx, w_idx, cache["ki"][1, rows])
    want_sel = jax.jit(lambda s, v: sa.select_xla(s, v, topk))(scores, visible)
    got_sel = np.asarray(mask[:, :, :C]) != 0
    kept = got_sel.sum(-1)
    want_kept = np.minimum(np.asarray(visible).sum(-1), topk)
    unshared = int((got_sel != np.asarray(want_sel)).sum())
    errs = {"select_unshared_share": unshared / max(int(want_kept.sum()), 1),
            "select_last_scores": rel(
                np.where(np.isfinite(last[:, :C]), last[:, :C], 0.0),
                np.where(np.asarray(visible)[:, -1], scores[:, -1], 0.0))}
    c.check("dsa_index_select keeps exactly min(visible, top-k) slots a "
            "query, all visible, and its set is the XLA form's but for "
            "near-ties (a row piece under a left pad, in place)",
            (kept == want_kept).all() and not (got_sel & ~np.asarray(
                visible)).any() and not np.asarray(mask[:, :, C:]).any()
            and errs["select_unshared_share"] <= 0.01
            and errs["select_last_scores"] <= sz["kernel_tolerance"], errs)

    def dense(q, sel, fill_mask):
        k, v = dequantize_cache_layer(cache, 1, hd)
        return _attention(q, k[rows].astype(dtype), v[rows].astype(dtype),
                          sel & fill_mask, H // KV)

    out = jax.jit(lambda q, cache, m: sa.dsa_prefill_attention(
        q, cache, 1, m, pad, off, rows, block_q=bq, **blocks))(q, cache, mask)
    want = jax.jit(dense)(q, jnp.asarray(got_sel), visible)
    real = np.asarray(visible).any(-1)[0]
    errs["prefill_attention"] = rel(np.asarray(out)[0][real],
                                    np.asarray(want)[0][real])
    c.check("dsa_prefill_attention is dense attention over the same sets",
            errs["prefill_attention"] <= sz["kernel_tolerance"]
            and not np.asarray(out)[0][~real].any(), errs)

    # a decode step: every row of the state (the cell's eight), ragged
    # pads — rows that see more slots than the top-k and rows that see
    # fewer —, one fill
    fill = C - 3
    pads = jnp.asarray([0, C // 2, 130, C - 40, 1, C // 3, C - 5 - topk,
                        C - 4], jnp.int32)
    kq = jax.random.split(jax.random.key(7), 3)
    q1 = jax.random.normal(kq[0], (B, H, hd)).astype(dtype)
    q1_idx = jax.random.normal(kq[1], (B, Hi, di)).astype(dtype)
    w1_idx = jax.random.normal(kq[2], (B, Hi), jnp.float32) * 0.03
    mask1, scores1 = jax.jit(lambda q, w, cache: sa.dsa_index_select_decode(
        q, w, cache, 1, pads, fill, topk=topk, **blocks))(
        q1_idx, w1_idx, cache)
    visible1 = (slot[0] >= pads[:, None]) & (slot[0] <= fill)
    want_scores1 = jax.jit(sa.index_scores_xla)(
        q1_idx[:, None], w1_idx[:, None], cache["ki"][1])[:, 0]
    want_sel1 = jax.jit(lambda s, v: sa.select_xla(s[:, None], v[:, None],
                                                   topk))(
        want_scores1, visible1)[:, 0]
    got_sel1 = np.asarray(mask1[:, :C]) != 0
    errs["decode_select_unshared"] = int(
        (got_sel1 != np.asarray(want_sel1)).sum())
    errs["decode_select_scores"] = rel(
        np.where(np.asarray(visible1), scores1[:, :C], 0.0),
        np.where(np.asarray(visible1), want_scores1, 0.0))
    c.check("the decode step's selection keeps exactly min(visible, top-k) "
            "slots a row and is the XLA form's but for near-ties",
            (got_sel1.sum(-1) == np.minimum(
                np.asarray(visible1).sum(-1), topk)).all()
            and errs["decode_select_unshared"] <= 0.01 * B * topk
            and errs["decode_select_scores"] <= sz["kernel_tolerance"], errs)
    walk = jax.jit(lambda q, cache, m: sa.dsa_decode_attention(
        q, cache, 1, m, pads, fill, **blocks))
    got1 = walk(q1, cache, mask1)
    k_of = min(topk, C)
    order = jnp.argsort(~jnp.asarray(got_sel1), axis=-1, stable=True)[:, :k_of]
    valid = jnp.take_along_axis(jnp.asarray(got_sel1), order, axis=-1)
    gathered = jax.jit(lambda q, cache, i, v: sa.decode_attention_gathered(
        q, cache, 1, i, v))
    want1 = gathered(q1, cache, order, valid)
    errs["decode_attention_walk_vs_gathered"] = rel(got1, want1)
    c.check("dsa_decode_attention (the masked walk) and the gathered form "
            "give the same rows",
            errs["decode_attention_walk_vs_gathered"]
            <= sz["kernel_tolerance"], errs)

    # the same eight rows against the reference: the equations in float32
    # at the highest precision, the top-k by a full sort
    from benchmarks import reference_keye as reference

    def plain(q_idx, w_idx, q, cache, kept):
        f32 = jnp.float32
        with jax.default_matmul_precision("highest"):
            scores = jnp.einsum("bh,bhc->bc", w_idx, jnp.maximum(jnp.einsum(
                "bhd,bdc->bhc", q_idx.astype(f32),
                cache["ki"][1].astype(f32)), 0.0))
            own = reference.top_by_sort(scores, visible1, topk)
            k = cache["k"][1].astype(f32) * cache["ks"][1][..., None]
            v = cache["v"][1].astype(f32) * cache["vs"][1][..., None]
            qg = q.astype(f32).reshape(B, KV, H // KV, hd)
            s = jnp.einsum("bkgd,bkcd->bkgc", qg, k) / jnp.sqrt(f32(hd))
            p = jax.nn.softmax(
                jnp.where(kept[:, None, None, :], s, -jnp.inf), -1)
            return scores, own, jnp.einsum(
                "bkgc,bkcd->bkgd", p, v).reshape(B, H, hd)

    ref_scores, ref_sel, ref_rows = jax.jit(plain)(
        q1_idx, w1_idx, q1, cache, jnp.asarray(got_sel1))
    ref_sel, seen1 = np.asarray(ref_sel), np.asarray(visible1)
    # a slot the two sets do not share: how far, in the reference's
    # scores, from the reference's cut, over the visible scores' spread
    far = [float(np.abs(s[u] - s[own].min()).max() / s[v].std())
           if u.any() else 0.0
           for s, own, v, u in zip(np.asarray(ref_scores, np.float64),
                                   ref_sel, seen1, got_sel1 != ref_sel)]
    errs["decode_select_vs_reference_unshared"] = int(
        (got_sel1 != ref_sel).sum())
    errs["decode_select_vs_reference_from_cut"] = max(far)
    errs["decode_scores_vs_reference"] = rel(
        np.where(seen1, scores1[:, :C], 0.0),
        np.where(seen1, ref_scores, 0.0))
    errs["decode_attention_vs_reference"] = rel(got1, ref_rows)
    c.check("the eight-row decode step meets the reference: its sets are "
            "top_by_sort's but for slots at the cut, its walk a plain "
            "softmax over the kept slots",
            (got_sel1.sum(-1) == ref_sel.sum(-1)).all()
            and errs["decode_select_vs_reference_unshared"]
            <= 0.01 * B * topk
            and errs["decode_select_vs_reference_from_cut"] <= 0.05
            and errs["decode_scores_vs_reference"] <= sz["kernel_tolerance"]
            and errs["decode_attention_vs_reference"]
            <= sz["kernel_tolerance"], errs)

    def timed(fn, *a, n=8):
        """Seconds a call of ``n`` calls in ONE jitted program, each
        call's first argument nudged by the call before (so that none is
        merged with another)."""
        def chain(x, *rest):
            for _ in range(n):
                y = fn(x, *rest)
                y = y[0] if isinstance(y, tuple) else y
                x = x + (jnp.sum(y.astype(jnp.float32)) * 0).astype(x.dtype)
            return x

        chained = jax.jit(chain)
        jax.block_until_ready(chained(*a))
        t0 = time.time()
        jax.block_until_ready(chained(*a))
        return (time.time() - t0) / n

    qt, qt_idx, wt_idx = queries(S_timed)
    mask_t = jax.jit(lambda q, w, cache: sa.dsa_index_select(
        q, w, cache, 1, pad, off, rows, topk=topk, block_q=bq, **blocks)[0])(
        qt_idx, wt_idx, cache)
    times = {
        "dsa_index_select_s": timed(
            lambda q, w, cache: sa.dsa_index_select(
                q, w, cache, 1, pad, off, rows, topk=topk, block_q=bq,
                **blocks), qt_idx, wt_idx, cache),
        "dsa_prefill_attention_s": timed(
            lambda q, cache, m: sa.dsa_prefill_attention(
                q, cache, 1, m, pad, off, rows, block_q=bq, **blocks),
            qt, cache, mask_t),
        "dsa_index_select_decode_s": timed(
            lambda q, w, cache: sa.dsa_index_select_decode(
                q, w, cache, 1, pads, fill, topk=topk, **blocks),
            q1_idx, w1_idx, cache),
        "dsa_decode_attention_walk_s": timed(
            lambda q, cache, m: sa.dsa_decode_attention(
                q, cache, 1, m, pads, fill, **blocks), q1, cache, mask1),
        "dsa_decode_attention_gathered_s": timed(
            lambda q, cache, i, v: sa.decode_attention_gathered(
                q, cache, 1, i, v), q1, cache, order, valid),
        # what the gathered form needs first: the index list from the scores
        "decode_top_k_indices_s": timed(
            lambda s: jax.lax.top_k(s, k_of)[1].astype(jnp.float32),
            jnp.where(jnp.asarray(visible1), want_scores1, -jnp.inf)),
    }
    times["shape"] = {"prefill_queries": S_timed, "slots": C,
                      "visible_to_last_query": int(off + S_timed - pad[0]),
                      "decode_rows": B, "decode_fill": fill}

    make = tiny_keye if args.rehearsal else keye_vl_2_0_30b_a3b
    cfg = make(n_layers=sz["layers"], max_seq_len=sz["seq"])
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte",
        params=jitted_init(init_params_quantized, cfg, 63),
        batch_size=sz["batch"], max_new_tokens=sz["max_new"], quantize=True,
        quantize_act=True, prefill_chunk_tokens=sz["prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=63),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["prompt_bytes"] - 300 * i // 4, f"y{i}")
               for i in range(sz["batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    counted = backend.stats.prefill_blocks
    c.check("the selection's scores are counted: computed >= needed, "
            "selected < visible x heads (the prompts pass the top-k)",
            0 < counted.get("dsa_index_scores_needed", 0)
            <= counted.get("dsa_index_scores_computed", 0)
            and 0 < counted.get("dsa_attention_scores_selected", 0)
            < counted.get("dsa_keys_visible", 0) * cfg.n_heads
            and counted["dsa_attention_scores_selected"]
            <= counted.get("dsa_attention_scores_computed", 0), counted)
    c.check("experts were routed and held",
            backend.stats.expert_slots_routed > 0
            and backend.stats.expert_slots_held
            == backend.stats.expert_slots_routed,
            (backend.stats.expert_slots_routed,
             backend.stats.expert_slots_held))
    state_leaves = backend.describe()["state_bytes_per_row"]
    c.check("the program carries indexer keys beside keys and values",
            {"k", "v", "ks", "vs", "ki"} <= set(state_leaves), state_leaves)
    rep = c.report()
    rep.update(kernel_errors=errs, kernel_seconds=times,
               first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2), prefill_blocks=counted,
               engine=backend.describe())
    return rep


def phase_ouro(args) -> dict:
    """The dense family LOOPED over its weights (``LlamaConfig.loop_passes``,
    Ouro-2.6B) on the one-shot path: two layers at the published widths run
    four times — sandwich norms, the final norm after every pass, 16 query
    heads on 16 KV heads, 8 cache layers — int8 and W8A8, through
    ``TpuBackend.generate`` (both GQA kernels at one query head a KV head,
    the prefill in two chunks of row pieces); its counters over every
    (pass, layer); and its logits and the first layer's FIRST and LAST
    pass's cache rows against ``benchmarks/reference_ouro.py`` (prefill in
    two chunks behind a left pad, then decode steps)."""
    from benchmarks import engine_setup_ouro as setup
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models import jitted_init, ouro_2p6b, tiny_ouro
    from vnsum_tpu.models.llama import cache_layers
    from vnsum_tpu.models.quant import init_params_quantized

    sz = sizes(args.rehearsal)
    c = Checks()
    make = tiny_ouro if args.rehearsal else ouro_2p6b
    cfg = make(n_layers=sz["ouro_layers"], max_seq_len=sz["ouro_seq"])
    params = jitted_init(init_params_quantized, cfg, 50)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", params=params,
        batch_size=sz["ouro_batch"], max_new_tokens=sz["ouro_max_new"],
        quantize=True, quantize_act=True,
        prefill_chunk_tokens=sz["ouro_prefill_chunk"],
        generation=GenerationConfig(temperature=1.0, seed=50),
        interpret=args.rehearsal)
    prompts = [_vn_text(sz["ouro_prompt_bytes"] - 300 * i // 4, f"o{i}")
               for i in range(sz["ouro_batch"])]
    _outs, first_s, second_s = _generate_twice(backend, prompts, c)
    st = backend.stats
    n_cache = cache_layers(cfg)
    c.check("a cache layer a (pass, layer)",
            n_cache == cfg.loop_passes * cfg.n_layers
            and backend.family.attention_layers(cfg) == n_cache, n_cache)
    c.check("both kernels at one query head a KV head",
            cfg.q_per_kv == 1 and all(
                set(p.values()) == {"kernel"}
                for p in st.attention_paths.values()), st.attention_paths)
    blocks = dict(st.prefill_blocks)
    c.check("prefill scores counted over every pass and layer",
            blocks.get("scores_needed", 0) > 0
            and blocks["scores_computed"] >= blocks["scores_needed"]
            and blocks["scores_needed"] % (cfg.n_heads * n_cache) == 0,
            blocks)
    c.check("decode key blocks walked over every pass and layer",
            st.decode_kv_blocks_total > 0
            and st.decode_kv_blocks_total % n_cache == 0,
            (st.decode_kv_blocks_total, st.decode_kv_blocks_skipped))

    n, bucket, steps = sz["ouro_parity"]
    config = {"reference": {"parity": {
        "prompt_tokens": n, "bucket": bucket, "decode_steps": steps,
        "tolerance": sz["ouro_tolerance"],
        "kv_tolerance": sz["ouro_kv_tolerance"],
        "kv_last_pass_tolerance": sz["ouro_kv_last_pass_tolerance"]}},
        "rehearsal": {}, **setup.MECHANISMS, **setup.sizes_from(cfg),
        "layer_types": ["full_attention"] * cfg.n_layers}
    parity = setup.parity_with_reference(backend, config, 50, False)
    c.check("logits, first-pass and last-pass cache rows within the cell's "
            "limits of the plain reference, prefill and decode",
            parity["ok"], {k: parity[k] for k in (
                "errors", "kv_error", "kv_last_pass_error",
                "kv_decode_errors")})
    rep = c.report()
    rep.update(first_call_s=round(first_s, 2),
               second_call_s=round(second_s, 2), parity=parity,
               prefill_blocks=blocks, engine=backend.describe())
    return rep


def _rehearsal_server(argv: list[str]) -> int:
    """The rehearsal's server child: the real serve.server.main, with the
    engine's kernels emulated (the product has no such flag, on purpose)."""
    from vnsum_tpu.backend import engine

    class InterpretedBackend(engine.TpuBackend):
        def __init__(self, *a, **kw):
            kw.setdefault("interpret", True)
            super().__init__(*a, **kw)

    engine.TpuBackend = InterpretedBackend
    from vnsum_tpu.serve.server import main as server_main

    return server_main(argv)


def _child(args) -> int:
    phase = args.phase
    if phase == "rehearsal-server":
        return _rehearsal_server(args.rest)
    t0 = time.time()
    rep: dict = {"phase": phase, "ok": False}
    try:
        compiles = _watch_compiles()
        rep.update({"device": phase_device, "kernels": phase_kernels,
                    "offline": phase_offline, "mesh": phase_mesh,
                    "experts": phase_experts,
                    "moe": phase_moe,
                    "laguna": phase_laguna,
                    "nemotron_h": phase_nemotron_h,
                    "ouro": phase_ouro,
                    "lfm2": phase_lfm2,
                    "ling": phase_ling,
                    "brumby": phase_brumby,
                    "keye": phase_keye}[phase](args))
        rep["device"] = _device_report()
        rep["memory"] = _memory()
        rep["compile"] = {k: round(v, 2) if isinstance(v, float) else v
                          for k, v in compiles.items()}
        if rep["device"]["platform"] != "tpu" and not args.rehearsal:
            rep["ok"] = False
            rep["error"] = f"ran on platform {rep['device']['platform']!r}"
    except Exception:
        rep["ok"] = False
        rep["error"] = traceback.format_exc()[-6000:]
        traceback.print_exc()
    rep["wall_s"] = round(time.time() - t0, 1)
    Path(args.report).write_text(json.dumps(rep, indent=1, default=str))
    return 0 if rep["ok"] else 1


# ---------------------------------------------------------------------------
# parent: no JAX from here down
# ---------------------------------------------------------------------------


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _tail(path: Path, n: int = 4000) -> str:
    try:
        return path.read_text(errors="replace")[-n:]
    except OSError:
        return ""


def run_child(phase: str, env: dict, work: Path, logs: Path,
              timeout_s: float, rehearsal: bool) -> dict:
    out = work / f"{phase}.json"
    log = logs / f"chip_smoke_{phase}.log"
    cmd = [sys.executable, str(HERE / "chip_smoke.py"), "--phase", phase,
           "--report", str(out), "--work", str(work)]
    if rehearsal:
        cmd.append("--rehearsal")
    with open(log, "w") as fh:
        proc = subprocess.Popen(cmd, env=env, cwd=HERE, stdout=fh,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            _kill(proc)
            return {"phase": phase, "ok": False,
                    "error": f"timed out after {timeout_s:.0f}s",
                    "log_tail": _tail(log)}
        finally:
            _kill(proc)
    if out.is_file():
        rep = json.loads(out.read_text())
    else:
        rep = {"phase": phase, "ok": False,
               "error": f"child exited {rc} without a report"}
    if not rep.get("ok"):
        rep["log_tail"] = _tail(log)
    return rep


def _http(port: int, method: str, path: str, body: dict | None = None,
          timeout: float = 600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw.decode("utf-8", "replace")
    finally:
        conn.close()


def _sse(port: int, path: str, body: dict, timeout: float = 600.0):
    """POST and read a text/event-stream to its end: (status, events)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        text = resp.read().decode("utf-8", "replace")
    finally:
        conn.close()
    events = []
    for frame in text.split("\n\n"):
        name, data = None, None
        for line in frame.splitlines():
            if line.startswith("event: "):
                name = line[7:]
            elif line.startswith("data: "):
                data = line[6:]
        if name and data is not None:
            events.append((name, json.loads(data)))
    return resp.status, events


def _metric(text: str, name: str) -> float | None:
    """Sum of one Prometheus family's samples (labels folded)."""
    total, found = 0.0, False
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, val = line.rpartition(" ")
        if head == name or head.startswith(name + "{"):
            total += float(val)
            found = True
    return total if found else None


def _vn_text(n_bytes: int, salt: str) -> str:
    base = ("Quốc hội đã thông qua nghị quyết về phát triển kinh tế xã hội "
            f"giai đoạn mới ({salt}). Các đại biểu đề nghị tiếp tục hoàn "
            "thiện thể chế, nâng cao chất lượng nguồn nhân lực và bảo vệ "
            "môi trường.\n\n")
    out = base * (n_bytes // len(base.encode()) + 1)
    return out.encode()[:n_bytes].decode("utf-8", "ignore")


def phase_serve(env: dict, work: Path, logs: Path, timeout_s: float,
                rehearsal: bool) -> dict:
    sz = sizes(rehearsal)
    c = Checks()
    t_start = time.time()
    deadline = t_start + timeout_s
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    journal, flight = work / "journal", work / "flight"
    server_args = [
        "--backend", "tpu", "--model", sz["serve_model"], "--inflight",
        "--slots", str(sz["serve_slots"]),
        "--max-batch", str(sz["serve_slots"]),
        "--slot-prompt-tokens", str(sz["serve_slot_tokens"]),
        "--max-new-tokens", str(sz["serve_max_new"]),
        "--cache-block-tokens", str(sz["serve_block_tokens"]),
        "--journal-dir", str(journal), "--flight-dir", str(flight),
        "--port", str(port),
    ]
    if rehearsal:
        cmd = [sys.executable, str(HERE / "chip_smoke.py"), "--phase",
               "rehearsal-server", "--", *server_args]
    else:
        cmd = [sys.executable, "-m", "vnsum_tpu.serve.server", *server_args]
    log = logs / "chip_smoke_serve.log"
    rep: dict = {"phase": "serve", "command": " ".join(cmd[1:]),
                 "sanitizers": env.get("VNSUM_SANITIZERS", "")}
    MN = sz["serve_max_new"]
    fh = open(log, "w")
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, stdout=fh,
                            stderr=subprocess.STDOUT, start_new_session=True)
    try:
        # -- wait for /readyz ------------------------------------------------
        ready = False
        while time.time() < deadline and proc.poll() is None:
            try:
                status, _ = _http(port, "GET", "/readyz", timeout=2.0)
                if status == 200:
                    ready = True
                    break
            except OSError:
                pass
            time.sleep(0.5)
        rep["ready_s"] = round(time.time() - t_start, 1)
        if not c.check("server_ready", ready,
                       f"exit={proc.poll()} after {rep['ready_s']}s"):
            return {**rep, **c.report(), "log_tail": _tail(log)}
        left = lambda: max(deadline - time.time(), 5.0)  # noqa: E731

        def generate(prompt: str, rid: str) -> dict:
            status, body = _http(port, "POST", "/v1/generate", {
                "prompt": prompt, "max_new_tokens": MN, "request_id": rid,
            }, timeout=left())
            out = {"rid": rid, "status": status}
            if status == 200:
                rec = json.loads(body)["completions"][0]["record"]
                out.update(generated_tokens=rec["generated_tokens"],
                           cached_prompt_tokens=rec["cached_prompt_tokens"],
                           total_s=round(rec["total_s"], 2),
                           ttft_s=round(rec["ttft_s"], 2))
            else:
                out["body"] = body[:400]
            return out

        prefix = _vn_text(sz["serve_prefix_bytes"], "phần chung")
        # -- the first request, on a cold program cache ----------------------
        t0 = time.time()
        first = generate(prefix + " Hãy tóm tắt ý chính thứ nhất.", "smoke-a")
        first["wall_s"] = round(time.time() - t0, 1)
        c.check("first_request_200", first["status"] == 200, first)
        # -- concurrent: one sharing smoke-a's prefix, one plain, one stream -
        replies: dict = {}

        def run(name, fn):
            try:
                replies[name] = fn()
            except Exception as e:
                replies[name] = {"status": -1, "error": repr(e)}

        def stream() -> dict:
            status, events = _sse(port, "/v1/generate", {
                "prompt": "Viết một câu ngắn về biến đổi khí hậu.",
                "max_new_tokens": MN, "request_id": "smoke-stream",
                "stream": True,
            }, timeout=left())
            deltas = "".join(p.get("text", "") for n, p in events
                             if n == "delta")
            done = [p for n, p in events if n == "done"]
            final = done[0]["completions"][0] if done else None
            return {
                "status": status, "events": len(events),
                "delta_events": sum(n == "delta" for n, _ in events),
                "deltas_equal_done": final is not None
                and deltas == final["text"],
                "generated_tokens": final["record"]["generated_tokens"]
                if final else 0,
            }

        threads = [
            threading.Thread(target=run, args=("shared", lambda: generate(
                prefix + " Hãy nêu ý chính thứ hai, thật ngắn gọn.",
                "smoke-b"))),
            threading.Thread(target=run, args=("plain", lambda: generate(
                "Tóm tắt: giáo dục phổ thông đang đổi mới.", "smoke-c"))),
            threading.Thread(target=run, args=("stream", stream)),
        ]
        for t in threads:
            t.start()
            time.sleep(0.05)  # arrival order: shared, plain, stream
        for t in threads:
            t.join()
        rep["requests"] = {"first": first, **replies}
        gens = [first, replies["shared"], replies["plain"]]
        c.check("generate_all_200", all(r.get("status") == 200 for r in gens),
                gens)
        c.check("generate_tokens_positive",
                all(r.get("generated_tokens", 0) > 0 for r in gens),
                [r.get("generated_tokens") for r in gens])
        c.check("stream_200_and_deltas_equal_done",
                replies["stream"].get("status") == 200
                and replies["stream"].get("deltas_equal_done")
                and replies["stream"].get("generated_tokens", 0) > 0,
                replies["stream"])
        # -- /v1/summarize, mapreduce over a multi-chunk text ----------------
        t0 = time.time()
        status, body = _http(port, "POST", "/v1/summarize", {
            "text": _vn_text(sz["serve_doc_bytes"], "văn bản dài"),
            "approach": "mapreduce", "max_new_tokens": MN,
            "request_id": "smoke-sum",
        }, timeout=left())
        summ = {"status": status, "wall_s": round(time.time() - t0, 1)}
        if status == 200:
            payload = json.loads(body)
            summ.update(num_chunks=payload["num_chunks"],
                        llm_calls=payload["llm_calls"],
                        generated_tokens=payload["serving"]["generated_tokens"])
        else:
            summ["body"] = body[:400]
        rep["summarize"] = summ
        c.check("summarize_200_multi_chunk",
                status == 200 and summ.get("num_chunks", 0) >= 2
                and summ.get("generated_tokens", 0) > 0, summ)
        # -- /metrics and /healthz -------------------------------------------
        _, metrics = _http(port, "GET", "/metrics", timeout=30.0)
        m = {name: _metric(metrics, f"vnsum_serve_{name}") for name in (
            "watchdog_stalls_total", "watchdog_hung_dispatches_total",
            "fault_failures_total", "fault_retries_total",
            "degraded_steps_total", "degraded_rung",
            "inflight_segments_total",
            "cache_hit_tokens_total", "requests_total",
        )}
        rep["metrics"] = m
        for name in ("watchdog_stalls_total", "watchdog_hung_dispatches_total",
                     "fault_failures_total", "fault_retries_total",
                     "degraded_steps_total", "degraded_rung"):
            c.check(f"{name}_is_0", not m[name], m[name])
        c.check("inflight_segments_positive",
                (m["inflight_segments_total"] or 0) > 0,
                m["inflight_segments_total"])
        c.check("cache_hit_tokens_positive",
                (m["cache_hit_tokens_total"] or 0) > 0,
                m["cache_hit_tokens_total"])
        _, health = _http(port, "GET", "/healthz", timeout=30.0)
        engine = json.loads(health).get("engine", {})
        rep["engine"] = engine
        rep["device"] = {"platform": engine.get("platform"),
                         "device_kind": engine.get("device_kind"),
                         "count": engine.get("device_count")}
        rep["memory"] = engine.get("memory")
        c.check("engine_platform_is_tpu",
                engine.get("platform") == "tpu" or rehearsal,
                engine.get("platform"))
        paths = engine.get("attention_paths", {})
        c.check("attention_path_kernel",
                bool(paths) and all(p == "kernel" for prog in paths.values()
                                    for p in prog.values()), paths)
        # -- SIGTERM drains, seals and exits 0 -------------------------------
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            rc = None
        c.check("sigterm_exit_0", rc == 0, rc)
        ledger = subprocess.run(
            [sys.executable, "-m", "vnsum_tpu.serve.journal", str(journal)],
            env={**env, "JAX_PLATFORMS": "cpu"}, cwd=HERE,
            capture_output=True, text=True, timeout=120,
        )
        try:
            led = json.loads(ledger.stdout)
            rep["journal"] = {k: led[k] for k in (
                "sealed", "torn_records", "entries", "live", "by_status")}
            c.check("journal_sealed_nothing_owed",
                    led["sealed"] and led["live"] == 0, rep["journal"])
        except (ValueError, KeyError):
            c.check("journal_sealed_nothing_owed", False,
                    (ledger.stdout + ledger.stderr)[-400:])
    finally:
        _kill(proc)
        fh.close()
    rep.update(c.report())
    rep["wall_s"] = round(time.time() - t_start, 1)
    if not rep["ok"]:
        rep["log_tail"] = _tail(log)
    return rep


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: one JSON object with exactly the keys "ok"
    and "device", the device with exactly "platform", "kind" and "count" as
    the device phase read them from jax.devices()."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device.get("platform")),
                   "kind": str(device.get("device_kind")),
                   "count": int(device.get("count") or 0)},
    })


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny model on the CPU with interpret-mode kernels; "
                         "for debugging this script, never a result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    ap.add_argument("--out", default=str(HERE / "chiprun_out/chip_smoke.json"))
    # child plumbing
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    ap.add_argument("rest", nargs="*", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if not (HERE / "vnsum_tpu").is_dir():
        print("chip_smoke: no vnsum_tpu package next to this script — it "
              "drives the repository, it is not the program", file=sys.stderr)
        return 2
    if args.phase:
        return _child(args)

    t_start = time.time()
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{HERE}{os.pathsep}{env.get('PYTHONPATH', '')}"
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    report: dict = {"rehearsal": args.rehearsal, "phases": {},
                    "started_unix": round(t_start)}

    def remaining() -> float:
        return TOTAL_BUDGET_S - (time.time() - t_start)

    # the device comes first, always: nothing is timed or served off-chip
    dev = run_child("device", env, work, out.parent,
                    min(PHASE_TIMEOUT_S["device"], remaining()),
                    args.rehearsal)
    report["phases"]["device"] = dev
    device = dev.get("device") or {}
    platform = device.get("platform")
    if platform != "tpu" and not args.rehearsal:
        out.write_text(json.dumps(report, indent=1))
        print(f"chip_smoke: JAX found platform {platform!r}, not 'tpu' — "
              f"nothing was run. {dev.get('error', '')[-800:]}"
              f"{dev.get('log_tail', '')[-800:]}", file=sys.stderr)
        return 1
    print(f"device: {device}  versions: {dev.get('versions')}  cache: "
          f"{dev.get('compile_cache_dir')} "
          f"({dev.get('compile_cache_entries_at_start')} entries)", flush=True)

    for phase in phases:
        if phase == "device":
            continue
        budget = min(PHASE_TIMEOUT_S[phase], remaining())
        if budget <= 10:
            report["phases"][phase] = {
                "ok": False, "error": "no time left inside the 1200 s limit"}
            continue
        t0 = time.time()
        if phase == "serve":
            rep = phase_serve(env, work, out.parent, budget, args.rehearsal)
        else:
            rep = run_child(phase, env, work, out.parent, budget,
                            args.rehearsal)
        report["phases"][phase] = rep
        tag = ("skipped: " + rep["skipped"] if rep.get("skipped")
               else "ok" if rep.get("ok") else "FAILED")
        print(f"{phase}: {tag} in {time.time() - t0:.0f}s", flush=True)
        # (a rehearsal's times are the CPU's: never shown as a pace)
        for rows, alone in ({} if args.rehearsal else rep.get(
                "retention_decode_update_alone", {})).items():
            print(f"  retention_decode_update alone, {rows}: "
                  f"{alone['ms_a_call']:.4f} ms a call, "
                  f"{alone['gb_s']:.1f} GB/s", flush=True)
        if not rep.get("ok"):
            bad = [k for k, v in rep.get("checks", {}).items() if not v]
            print(f"  failed checks: {bad}\n  {rep.get('error', '')[-1500:]}",
                  file=sys.stderr, flush=True)

    ran = report["phases"]
    off_chip = [p for p, r in ran.items()
                if (r.get("device") or {}).get("platform") not in ("tpu", None)]
    ok = all(r.get("ok") for r in ran.values()) and (
        args.rehearsal or not off_chip)
    report["ok"] = ok
    report["wall_s"] = round(time.time() - t_start, 1)
    out.write_text(json.dumps(report, indent=1))
    shutil.rmtree(work, ignore_errors=True)
    print(f"report: {out}  wall: {report['wall_s']}s"
          + ("  (rehearsal: not a result)" if args.rehearsal else ""),
          flush=True)
    if not args.rehearsal:
        # the contract line: exactly these keys, the device as JAX reports it,
        # and nothing after it on stdout. A rehearsal never prints one
        print(result_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
