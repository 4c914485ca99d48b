"""Time the two decode kernels alone on the chip, by block geometry.

One line of JSON a (shape, pads, geometry): the kernel's milliseconds a call,
taken from a jitted loop of ``--calls`` calls over the layers of an int8 cache
(a lone call from the host costs ~0.2 ms of its own, as much as the kernel at
the smaller shapes), the least of ``--repeats`` loops. The shapes are the
decode steps of the benchmark's eight offline and served cells at fill 8,320; ``all-live`` rows
have no pad, ``mix`` the cell's: the served mix's four rows, an offline
group's first dispatch (four tails of 20 to 75% pad).

``--other LABEL=FILE`` (repeatable) also times another tree's
``ops/decode_attention.py`` (read from FILE, its relative import pointed at
its own tree's ``flash_attention`` beside it) at its own default geometry,
under LABEL, over the same numbers one KV head a lane tile; this tree's
kernel reads them as ``init_kv_cache`` lays them out (64-wide heads two a
tile). ``cache_bytes`` is what the compiled program holds of each layout's
cache in HBM (``memory_analysis().argument_size_in_bytes``).

    chiprun -- python3 scripts/profile_decode_blocks.py --other \
        parent=.chip_checkout/parent/vnsum_tpu/ops/decode_attention.py
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vnsum_tpu.models.llama import heads_to_tiles  # noqa: E402
from vnsum_tpu.ops import decode_attention as ours  # noqa: E402
from vnsum_tpu.ops.flash_attention import heads_per_lane_tile  # noqa: E402

LAYERS = 6
C_OFFLINE, C_SERVED, FILL = 8448, 8320, 8320
TAILS = (0.2, 0.4, 0.6, 0.75)
# name, kernel, rows, KV heads, query heads a KV head, head dim, cache slots,
# window
SHAPES = [
    ("qwen3-offline", "decode", 8, 8, 4, 128, C_OFFLINE, 0),
    ("qwen3-served", "verify", 4, 8, 4, 128, C_SERVED, 0),
    ("phi4", "decode", 12, 10, 4, 128, C_OFFLINE, 0),
    ("smallthinker-global", "decode", 24, 4, 7, 128, C_OFFLINE, 0),
    ("smallthinker-window", "decode", 24, 4, 7, 128, C_OFFLINE, 4096),
    ("laguna-full", "decode", 12, 8, 6, 128, C_OFFLINE, 0),
    ("laguna-sliding", "decode", 12, 8, 9, 128, C_OFFLINE, 512),
    ("granite", "decode", 24, 8, 4, 64, C_OFFLINE, 0),
    ("nemotron", "decode", 12, 2, 16, 128, C_OFFLINE, 0),
    ("ouro", "decode", 8, 16, 1, 128, C_OFFLINE, 0),
]
SERVED_PROMPTS = (6000, 7260, 2000, 540)


def _pads(name: str, rows: int, mix: bool) -> np.ndarray:
    pads = np.zeros((rows,), np.int32)
    if mix and name == "qwen3-served":
        pads[:] = [8192 - n for n in SERVED_PROMPTS]
    elif mix:
        pads[-4:] = [int(8192 * t) for t in TAILS]
    return pads


def _load_other(label: str, path: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"{label}_flash_attention", Path(path).with_name("flash_attention.py"))
    flash = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = flash
    spec.loader.exec_module(flash)
    text = Path(path).read_text().replace(
        "from .flash_attention import", f"from {spec.name} import")
    mod = types.ModuleType(f"{label}_decode_attention")
    exec(compile(text, path, "exec"), mod.__dict__)
    return mod


def _tiled(cache: dict, tile: int) -> dict:
    """The cache as ``init_kv_cache`` lays it out: ``tile`` KV heads a lane
    tile, the scales a head."""
    return dict(cache, k=heads_to_tiles(cache["k"], tile),
                v=heads_to_tiles(cache["v"], tile))


def _cache_bytes(cache: dict) -> int:
    compiled = jax.jit(lambda c: c["k"][0, 0, 0, 0, 0]).lower(cache).compile()
    return int(compiled.memory_analysis().argument_size_in_bytes)


def _inputs(rows, KV, G, hd, C):
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (LAYERS, rows, KV, C, hd)
    cache = {
        "k": jax.random.randint(keys[0], shape, -127, 128, jnp.int8),
        "v": jax.random.randint(keys[1], shape, -127, 128, jnp.int8),
        "ks": jax.random.uniform(keys[2], shape[:4], jnp.float32, 0.01, 0.02),
        "vs": jax.random.uniform(keys[3], shape[:4], jnp.float32, 0.01, 0.02),
    }
    return jax.random.normal(keys[4], (rows, 1, KV * G, hd), jnp.bfloat16), cache


def _time(mod, kind, q, cache, G, window, pads, block_k, calls, repeats):
    rows = q.shape[0]
    kw = {} if block_k is None else {"block_k": block_k}
    win = jnp.int32(window)

    def one(q, cache, layer, pads):
        if kind == "verify":
            return mod.flash_spec_verify_attention(
                q, cache, layer, pads, jnp.full((rows,), FILL, jnp.int32), G,
                win, **kw)
        return mod.flash_decode_attention(
            q, cache, layer, pads, FILL, G, win, **kw)

    @jax.jit
    def loop(q, cache, pads):
        def body(i, acc):
            return acc + one(q, cache, i % LAYERS, pads).astype(jnp.float32)
        return jax.lax.fori_loop(0, calls, body, jnp.zeros(q.shape, jnp.float32))

    pads = jnp.asarray(pads)
    loop(q, cache, pads).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        loop(q, cache, pads).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e3


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--other", action="append", default=[],
                    metavar="LABEL=FILE",
                    help="another tree's ops/decode_attention.py")
    ap.add_argument("--blocks", default="256,512,1024,2048")
    ap.add_argument("--shapes", default="", help="comma-separated names")
    ap.add_argument("--calls", type=int, default=72)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/decode_blocks.jsonl")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("a kernel's time comes from the chip alone")
    wanted = set(filter(None, args.shapes.split(",")))
    geometries = [(label, _load_other(label, path), None) for label, path in
                  (other.split("=", 1) for other in args.other)]
    geometries += [("rule", ours, None)]
    geometries += [(f"bk{b}", ours, int(b)) for b in args.blocks.split(",") if b]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("w") as f:
        for name, kind, rows, KV, G, hd, C, window in SHAPES:
            if wanted and name not in wanted:
                continue
            q, cache = _inputs(rows, KV, G, hd, C)
            tile = heads_per_lane_tile(KV, hd)
            tiled = _tiled(cache, tile)
            for mix in (False, True):
                row = {"shape": name, "rows": rows, "kv": KV, "g": G, "hd": hd,
                       "window": window, "pads": "mix" if mix else "all-live",
                       "heads_a_tile": tile,
                       "rule_block_k": ours.decode_block_k(
                           KV // tile, hd * tile, 1, C),
                       "cache_bytes": {"a head a tile": _cache_bytes(cache),
                                       "tiled": _cache_bytes(tiled)}}
                for label, mod, bk in geometries:
                    try:
                        row[label] = round(_time(
                            mod, kind, q, tiled if mod is ours else cache, G,
                            window, _pads(name, rows, mix), bk, args.calls,
                            args.repeats), 4)
                    except Exception as e:  # a geometry Mosaic refuses
                        row[label] = f"{type(e).__name__}: {str(e)[:120]}"
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()


if __name__ == "__main__":
    main()
