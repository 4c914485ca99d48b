"""The GQA prefill kernel alone on the chip, by block geometry and by the
order a cell's heads are written in.

Times ``flash_prefill_attention`` at one dispatch's call shapes — by default
the SmallThinker cell's map dispatch: 24 rows (20 full, 4 tail rows of a
group's last chunks), 4 KV heads, a group of 7, hd 128, int8 cache, chunks of
2,048 queries at offsets 0-6,144 over C = 8,448, under no window and under
4,096 — and books each candidate's calls into the seconds the dispatch's
layers would take (``--layers-global`` / ``--layers-window`` of each kind),
beside the ns per 1,024 COMPUTED scores (``prefill_block_classes``' interior
and edge cells, whole: a wider tile computes more scores for the same
pairs). A call is timed as ``--iters`` chained calls in one program (offset,
window and the count of calls are traced, so a candidate compiles once),
wall clock around the result, the best of ``--repeats``; the wrapper's two
transposes of q and o ride along (about 1.7 ms of a 25-50 ms call at the
default shapes). ``--pads`` may hold several dispatches of the same batch,
``N*`` before one for how many of it a group makes: ``1*300x4,6292,4792,
3292,1792/2*300x8`` is Qwen3's group of three.

``--orders`` sets how a cell's heads are written, ``n:ahead`` each (heads a
step — the group itself for the static unroll — and how many heads a head's
score product is written ahead of its softmax), by replacing
``flash_attention._heads_per_step`` and ``_heads_ahead`` for the run (``0`` =
the rule's own choice). ``--parent-file`` also times another copy of
``ops/flash_attention.py`` (the parent commit's) with its own rule at every
geometry, and every candidate's output is compared with it bit for bit
(``equal`` in its row). ``--vmem-mib`` asks Mosaic for that much scoped VMEM
in place of ``_vmem_bytes``' count (a candidate the count does not know).
No benchmark cell runs this script.

    chiprun -- python3 scripts/profile_flash_blocks.py \
        --geometries 512x512,512x1024,1024x1024,512x2048 --orders 2:0,2:1,3:0
    chiprun -- python3 scripts/profile_flash_blocks.py --G 4 --KV 8 \
        --pads 1*300x4,6292,4792,3292,1792/2*300x8 --windows 0 \
        --layers-global 36 --layers-window 0 --geometries 512x1024,1024x1024 \
        --orders 4:0,4:1,4:3 --parent-file /path/to/parent/flash_attention.py
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


def _dispatches(spec: str) -> list[tuple[int, list[int]]]:
    """"1*300x4,6292/2*300x5" -> [(1, pads), (2, pads)]."""
    out = []
    for part in spec.split("/"):
        times, star, pads = part.rpartition("*")
        out.append((int(times) if star else 1, _pads(pads)))
    return out


def _order(spec: str) -> tuple[int, int] | None:
    if spec == "0":
        return None
    n, ahead = (int(x) for x in spec.split(":"))
    return n, ahead


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/flash_block_geometry.json")
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--G", type=int, default=7, help="query heads a KV head")
    ap.add_argument("--KV", type=int, default=4)
    ap.add_argument("--hd", type=int, default=128)
    ap.add_argument("--pads", default="300x20,6292,4792,3292,1792",
                    help="left pads of the batch's rows (pad or padxrows); "
                         "several dispatches as N*pads/N*pads")
    ap.add_argument("--S", type=int, default=2048, help="queries a chunk")
    ap.add_argument("--bucket", type=int, default=8192)
    ap.add_argument("--C", type=int, default=8448)
    ap.add_argument("--windows", default="0,4096")
    ap.add_argument("--layers-global", type=int, default=4)
    ap.add_argument("--layers-window", type=int, default=12)
    ap.add_argument("--geometries", default="512x512,512x1024,1024x1024,512x2048",
                    help="bqxbk, comma-separated")
    ap.add_argument("--orders", default="0",
                    help="n:ahead, comma-separated; 0 = the rule's")
    ap.add_argument("--vmem-mib", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse off the chip at a tiny size")
    ap.add_argument("--parent-file", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from vnsum_tpu.core.jax_cache import enable_compilation_cache
    from vnsum_tpu.ops import flash_attention

    enable_compilation_cache()
    G, KV, hd, S, C = args.G, args.KV, args.hd, args.S, args.C
    dispatches = _dispatches(args.pads)
    B, H = len(dispatches[0][1]), G * KV
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

    # this tree's kernel reads the cache as init_kv_cache lays it out
    # (64-wide heads two a lane tile); a parent file reads the same numbers
    # a head a tile
    from vnsum_tpu.models.llama import heads_to_tiles

    tile = flash_attention.heads_per_lane_tile(KV, hd)
    tiled = dict(cache, k=heads_to_tiles(cache["k"], tile),
                 v=heads_to_tiles(cache["v"], tile))

    def program(mod, bq, bk):
        @jax.jit
        def run(q, cache, pad, win, off, calls):
            # the cache enters as an ARGUMENT (a closure constant would be
            # baked into the program); the calls are chained through a data
            # dependency so the last one's result bounds all of them
            def body(i, acc):
                return mod.flash_prefill_attention(
                    acc, cache, 0, pad, G, win, off,
                    block_q=bq, block_k=bk, interpret=args.interpret,
                ).astype(acc.dtype)

            return jax.lax.fori_loop(0, calls, body, q)

        return run

    def timed(mod, label, bq, bk, order, reference) -> dict:
        """One candidate's row; ``reference`` maps (dispatch, win, off) to the
        parent's output of one call, filled by the parent's own row."""
        row = {"kernel": label, "block_q": bq, "block_k": bk,
               "order": "%d:%d" % order if order else "its own"}
        patched = {}

        def patch(name, value):
            patched[name] = mod.__dict__[name]
            setattr(mod, name, value)

        if order:
            patch("_heads_per_step", lambda G: order[0])
            patch("_heads_ahead", lambda G: order[1])
        if args.vmem_mib and label != "parent":
            patch("_vmem_bytes", lambda *a: args.vmem_mib << 20)
        jax.clear_caches()
        try:
            run = program(mod, bq, bk)
            cells = scores = 0
            seconds, equal = 0.0, True
            for d, (times, pads) in enumerate(dispatches):
                pad = jnp.asarray(pads, jnp.int32)
                dispatch_s = 0.0
                for win in windows:
                    for off in offsets:
                        n = flash_attention.prefill_block_classes(
                            pads, S, C, off, win, G, hd,
                            block_q=bq, block_k=bk)
                        computed = ((n["interior"] + n["edge"]) * KV
                                    * layers[win])
                        a = (q, cache if label == "parent" else tiled, pad,
                             jnp.int32(win), jnp.int32(off))
                        one = run(*a, jnp.int32(1)).block_until_ready()
                        if label == "parent":
                            reference[d, win, off] = one
                        elif reference:
                            equal &= bool(jnp.array_equal(
                                one, reference[d, win, off]))
                        del one
                        t = float("inf")
                        for _ in range(args.repeats):
                            t0 = time.perf_counter()
                            run(*a, jnp.int32(args.iters)).block_until_ready()
                            t = min(t, (time.perf_counter() - t0) / args.iters)
                        row[f"ms_d{d}_off{off}_win{win}"] = round(1e3 * t, 3)
                        cells += computed * times
                        scores += computed * times * G * bq * bk
                        dispatch_s += t * layers[win]
                row[f"dispatch{d}_seconds"] = round(dispatch_s, 4)
                seconds += dispatch_s * times
            row.update(
                group_seconds=round(seconds, 4), computed_cells=cells,
                computed_gscores=round(scores / 1e9, 1),
                us_per_cell=round(1e6 * seconds / cells, 3),
                ns_per_1024_scores=round(1e9 * seconds / (scores / 1024), 3),
            )
            if label != "parent" and reference:
                row["equal"] = equal
        except Exception as e:  # a geometry Mosaic refuses is a row too
            row.update(status="failed", error=str(e)[-400:])
        finally:
            for name, value in patched.items():
                setattr(mod, name, value)
        print(json.dumps(row), file=sys.stderr, flush=True)
        return row

    parent = None
    if args.parent_file:
        spec = importlib.util.spec_from_file_location(
            "_flash_attention_parent", args.parent_file)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    rows = []
    for geometry in args.geometries.split(","):
        bq, bk = (int(x) for x in geometry.split("x"))
        reference: dict = {}
        if parent:
            rows.append(timed(parent, "parent", bq, bk, None, reference))
        for order in args.orders.split(","):
            rows.append(timed(flash_attention, "tree", bq, bk, _order(order),
                              reference))
    rec = {
        "what": (f"flash_prefill_attention alone: B={B} KV={KV} G={G} "
                 f"hd={hd}, int8 cache, S={S} at offsets {offsets} over "
                 f"C={C}, windows {layers} (window: layers), dispatches "
                 f"{args.pads}; best of {args.repeats} x {args.iters} "
                 f"chained calls a timing"),
        "device": str(jax.devices()[0].device_kind),
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    keys = ("kernel", "block_q", "block_k", "order", "group_seconds",
            "ns_per_1024_scores", "us_per_cell", "equal", "status")
    print(json.dumps({"ok": True, "rows": [
        {k: r[k] for k in keys if k in r} for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
