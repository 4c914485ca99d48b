"""The GQA prefill kernel alone on the chip, by block geometry and by how many
heads of the group a step of its head loop holds.

Times ``flash_prefill_attention`` at one dispatch's call shapes — by default
the SmallThinker cell's map dispatch: 24 rows (20 full, 4 tail rows of a
group's last chunks), 4 KV heads, a group of 7, hd 128, int8 cache, chunks of
2,048 queries at offsets 0-6,144 over C = 8,448, under no window and under
4,096 — and books each geometry's calls into the seconds the dispatch's
layers would take (``--layers-global`` / ``--layers-window`` of each kind),
beside the ns per 1,024 COMPUTED scores (``prefill_block_classes``' interior
and edge cells, whole: a wider tile computes more scores for the same
pairs). A call is timed as ``--iters`` chained calls in one program, wall
clock around a scalar fetch; the wrapper's two transposes of q and o ride
along (about 1.7 ms of a 25-50 ms call at the default shapes).

``--heads`` sets the heads a loop step by replacing
``flash_attention._heads_per_step`` for the run (0 = the rule's own choice,
G = the static unroll); ``--parent-file`` also times another copy of
``ops/flash_attention.py`` (the parent commit's) with its own rule at
``--parent-geometry``. No benchmark cell runs this script.

    chiprun -- python3 scripts/profile_flash_blocks.py \
        --geometries 512x512,512x1024,1024x1024,512x2048 --heads 1,2
    chiprun -- python3 scripts/profile_flash_blocks.py --G 4 --KV 8 \
        --pads 300x5,6292,4792,3292 --windows 0 --layers-global 36 \
        --layers-window 0 --geometries 512x1024 --heads 4,1,2
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _pads(spec: str) -> list[int]:
    """"300x20,6292,4792" -> twenty rows of pad 300, then one of each."""
    out: list[int] = []
    for part in spec.split(","):
        pad, _, n = part.partition("x")
        out += [int(pad)] * int(n or 1)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/flash_block_geometry.json")
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--G", type=int, default=7, help="query heads a KV head")
    ap.add_argument("--KV", type=int, default=4)
    ap.add_argument("--hd", type=int, default=128)
    ap.add_argument("--pads", default="300x20,6292,4792,3292,1792",
                    help="left pads of the batch's rows (pad or padxrows)")
    ap.add_argument("--S", type=int, default=2048, help="queries a chunk")
    ap.add_argument("--bucket", type=int, default=8192)
    ap.add_argument("--C", type=int, default=8448)
    ap.add_argument("--windows", default="0,4096")
    ap.add_argument("--layers-global", type=int, default=4)
    ap.add_argument("--layers-window", type=int, default=12)
    ap.add_argument("--geometries", default="512x512,512x1024,1024x1024,512x2048",
                    help="bqxbk, comma-separated")
    ap.add_argument("--heads", default="1,2",
                    help="heads a loop step, comma-separated; 0 = the rule's")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse off the chip at a tiny size")
    ap.add_argument("--parent-file", default=None)
    ap.add_argument("--parent-geometry", default="512x512")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.ops import flash_attention

    enable_compilation_cache()
    G, KV, hd, S, C = args.G, args.KV, args.hd, args.S, args.C
    pads = _pads(args.pads)
    B, H = len(pads), G * KV
    windows = [int(w) for w in args.windows.split(",")]
    layers = {w: args.layers_window if w else args.layers_global
              for w in windows}
    offsets = list(range(0, args.bucket, S))
    kq, kk, kv, ks, vs = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.bfloat16)
    cache = {
        "k": jax.random.randint(kk, (1, B, KV, C, hd), -127, 128, jnp.int8),
        "v": jax.random.randint(kv, (1, B, KV, C, hd), -127, 128, jnp.int8),
        "ks": jax.random.uniform(ks, (1, B, KV, C), jnp.float32, 0.01, 0.02),
        "vs": jax.random.uniform(vs, (1, B, KV, C), jnp.float32, 0.01, 0.02),
    }
    pad = jnp.asarray(pads, jnp.int32)

    def call_seconds(mod, bq, bk, off, win) -> float:
        @jax.jit
        def run(q, cache):
            # the cache enters as an ARGUMENT (a closure constant would be
            # baked into the program); iters calls chained through a data
            # dependency so one scalar fetch at the end bounds all of them
            def body(i, acc):
                return mod.flash_prefill_attention(
                    acc, cache, 0, pad, G, jnp.int32(win), jnp.int32(off),
                    block_q=bq, block_k=bk, interpret=args.interpret,
                ).astype(acc.dtype)

            out = jax.lax.fori_loop(0, args.iters, body, q)
            return jnp.sum(out.astype(jnp.float32))

        np.asarray(run(q, cache))                      # compile, warm
        t0 = time.perf_counter()
        np.asarray(run(q, cache))
        return (time.perf_counter() - t0) / args.iters

    def timed(mod, label, bq, bk, heads) -> dict:
        row = {"kernel": label, "block_q": bq, "block_k": bk, "heads": heads}
        rule = mod.__dict__.get("_heads_per_step")
        if heads:
            mod._heads_per_step = lambda G: heads
        jax.clear_caches()
        try:
            cells = scores = 0
            seconds = 0.0
            for win in windows:
                for off in offsets:
                    n = flash_attention.prefill_block_classes(
                        pads, S, C, off, win, G, hd, block_q=bq, block_k=bk)
                    computed = (n["interior"] + n["edge"]) * KV * layers[win]
                    t = call_seconds(mod, bq, bk, off, win)
                    row[f"ms_off{off}_win{win}"] = round(1e3 * t, 3)
                    cells += computed
                    scores += computed * G * bq * bk
                    seconds += t * layers[win]
            row.update(
                dispatch_seconds=round(seconds, 4), computed_cells=cells,
                computed_gscores=round(scores / 1e9, 1),
                us_per_cell=round(1e6 * seconds / cells, 2),
                ns_per_1024_scores=round(1e9 * seconds / (scores / 1024), 3),
            )
        except Exception as e:  # a geometry Mosaic refuses is a row too
            row.update(status="failed", error=str(e)[:300])
        finally:
            if heads:
                mod._heads_per_step = rule
        print(json.dumps(row), file=sys.stderr, flush=True)
        return row

    rows = []
    if args.parent_file:
        spec = importlib.util.spec_from_file_location(
            "_flash_attention_parent", args.parent_file)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        bq, bk = (int(x) for x in args.parent_geometry.split("x"))
        rows.append(timed(parent, "parent", bq, bk, 0))
    for geometry in args.geometries.split(","):
        bq, bk = (int(x) for x in geometry.split("x"))
        for heads in args.heads.split(","):
            rows.append(timed(flash_attention, "tree", bq, bk, int(heads)))
    rec = {
        "what": (f"flash_prefill_attention alone: B={B} KV={KV} G={G} "
                 f"hd={hd}, int8 cache, S={S} at offsets {offsets} over "
                 f"C={C}, windows {layers} (window: layers), pads "
                 f"{args.pads}; {args.iters} chained calls a timing"),
        "device": str(jax.devices()[0].device_kind),
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    keys = ("kernel", "block_q", "block_k", "heads", "dispatch_seconds",
            "ns_per_1024_scores", "us_per_cell", "status")
    print(json.dumps({"ok": True, "rows": [
        {k: r[k] for k in keys if k in r} for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
