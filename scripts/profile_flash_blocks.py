"""Standalone flash-prefill kernel timing vs block geometry (DMA probe).

An earlier machine's profile: attention costs 2.7 s of the 7.0 s e2e prefill
dispatch (~39% of device time for ~18% of FLOPs), and switching the MXU
dots to bf16 moved NOTHING — so the kernel is not compute-rate-bound.
Prime suspect: K/V DMA redundancy. The grid (B, H, I, J) streams each K/V
block once per QUERY head (3x redundant under GQA 24:8) and once per
q-block (S/BQ re-streams of the prefix). If that's the bottleneck,
raising block_q (halving K/V re-streams) must cut time near-linearly
while block_k moves little (same bytes, different DMA granularity).

Times the kernel alone at the REAL e2e chunk shape (B=16, S=2048 chunk,
off=6144 — the worst chunk of the chunked prefill; C=8320, int8 cache),
28-layer-equivalent via repeated chained calls. Writes
a JSON file (--out).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/flash_block_geometry.json")
    ap.add_argument("--iters", type=int, default=28)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    enable_compilation_cache()
    B, S, H, KV, hd, C = 16, 2048, 24, 8, 128, 8320
    off = 6144
    key = jax.random.key(0)
    kq, kk, kv, ks, vs = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.bfloat16)
    cache = {
        "k": jax.random.randint(kk, (1, B, KV, C, hd), -127, 128, jnp.int8),
        "v": jax.random.randint(kv, (1, B, KV, C, hd), -127, 128, jnp.int8),
        "ks": jax.random.uniform(ks, (1, B, KV, C), jnp.float32, 0.01, 0.02),
        "vs": jax.random.uniform(vs, (1, B, KV, C), jnp.float32, 0.01, 0.02),
    }
    pad = jnp.zeros((B,), jnp.int32)

    def timed(bq: int, bk: int) -> dict:
        @jax.jit
        def run(q, cache):
            # cache enters as an ARGUMENT (a closure constant would be
            # baked into the program as 270 MB of literals). Chain iters
            # kernel calls through a data dependency so one fetch at the
            # end bounds all of them
            def body(i, acc):
                o = flash_prefill_attention(
                    acc, cache, 0, pad, H // KV,
                    q_offset=jnp.int32(off), block_q=bq, block_k=bk,
                )
                return o.astype(acc.dtype)

            out = jax.lax.fori_loop(0, args.iters, body, q)
            # reduce to a SCALAR on device: fetching the full [B,S,H,hd]
            # output (201 MB) would dominate wall otherwise
            return jnp.sum(out.astype(jnp.float32))

        try:
            t0 = time.time()
            np.asarray(run(q, cache))
            compile_s = time.time() - t0
            t1 = time.time()
            np.asarray(run(q, cache))
            wall = time.time() - t1
            row = {"block_q": bq, "block_k": bk,
                   "compile_s": round(compile_s, 1),
                   "seconds_28layer": round(wall, 3),
                   "ms_per_layer": round(1e3 * wall / args.iters, 2)}
        except Exception as e:
            row = {"block_q": bq, "block_k": bk, "status": "failed",
                   "error": str(e)[:200]}
        print(json.dumps(row), file=sys.stderr)
        return row

    rows = [
        timed(512, 512),    # production default
        timed(1024, 512),   # half the K/V re-streams
        timed(2048, 512),   # quarter the re-streams (whole chunk = 1 block)
        timed(512, 1024),   # same bytes, coarser DMA granularity
        timed(1024, 1024),
        timed(2048, 1024),
    ]
    rec = {
        "what": ("flash_prefill_attention alone at the e2e chunk shape "
                 "(B=16, S=2048@off=6144, C=8320, int8 cache, bf16 q), "
                 f"{args.iters} chained calls"),
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({"ok": True, "rows": [
        {k: r.get(k) for k in ("block_q", "block_k", "ms_per_layer", "status")}
        for r in rows
    ]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
