"""The one-shot programs of the families a PR did NOT mean to touch, pinned:
each family's tiny form is traced as the engine builds it (`_make_fn`:
int8 weights, W8A8, chunked prefill, kernels interpreted — their bodies are
in the jaxpr) and the text of its jaxpr is hashed. A change to one family's
model or kernels leaves the other hashes as they were; a PR that moves one
on purpose recomputes it here and says so. PR 44 wrote the first five on
the tree of PR 43 and changed none. **PR 45 moved those five on purpose**:
the GQA prefill kernel (`ops/flash_attention.py`) writes a narrow group's
heads one score product ahead, its body is in every one of these families'
programs and their tiny forms all have groups of at most four heads, so
the five were recomputed; `deepseek-v2`, whose family shares no line of
that kernel, was hashed on PR 44's tree first and is what PR 45 did NOT
move.

**PR 46 added the slot programs** of the tiny llama family (`_SLOT_PINNED`),
built as `TpuSlotLoop` gets them (`_get_seg_fn`, default arguments only, so
this file reads the same on the parent's tree and on the PR's): their four
hashes were taken at PR 45's tree before PR 46 rebuilt the slot programs
from the one-shot program's parts, and PR 46 moved none of the ten.

**PR 47 moved `granite-h` on purpose and added `nemotron-h`**: the Mamba-2
mixer became `models/mamba_mixer.py`, shared by both families, and
`ops/ssd_scan.py`'s kernels take B and C with a group dim (`[B, S, 1, N]`
for Granite's one group); the other nine did not move.
`test_granites_program_calls_the_kernels_it_called` holds what had to stay:
the same kernels by name, as many calls of each.

**PR 48 moved none of the eleven and added `llama-row-pieces`**: the dense
family's chunked prefill runs a chunk a row piece at a time where a piece
holds `Family.prefill_piece_tokens` (2,048) tokens, and these tiny programs'
whole chunks hold 256, so they are the whole batch a chunk as before —
`llama`, `llama-qk-norm` and the slot programs included, which ISSUE 48
expected to move under a piece counted in rows. The new pin is the tiny
llama program with the piece set to its 128-token chunk (`_PIECE_TOKENS`):
the loop over row pieces, the fifth prefetched vector of the prefill
kernel (`cache_rows`) and the per-row cache writes are in it.

**PR 49 moved eight of the twelve on purpose**: the two GQA decode kernels
(`ops/decode_attention.py`) are one kernel body whose K/V block holds ONE
row and whose grid walks each row from its own pad to its own fill, and that
body is in the decode loop of every GQA family's one-shot program (`llama`,
`llama-qk-norm`, `smallthinker`, `laguna`, `granite-h`, `nemotron-h`,
`llama-row-pieces`) and in the slot segment (`slot_seg-4`). What it did NOT
move: `deepseek-v2`, whose decode kernel is `mla_decode_attention`, and the
three programs that decode nothing (`slot_prefill-1`, `slot_prefill-2`,
`adopt-2`).

**PR 50 moved none of the twelve and added `llama-looped`**: the dense
family's `forward` runs its stack `LlamaConfig.loop_passes` times over the
same weights (Ouro-2.6B), and at one pass it traces what it traced — the
loop over passes, the `loop_norm` and the cache-layer offset are static-gated
on the config, as the Gemma deltas are. The new pin is the tiny looped preset
(`tiny-ouro`: three passes over two layers, sandwich norms, a KV head a
query head): the scan over passes around the layer scan is in it.

**PR 51 moved none of the thirteen and added `granite-h-row-pieces`**: the
state-space hybrid names a piece of its own (`models/granite_hybrid.py`:
2,048 tokens), its `forward`, the shared mixer (`models/mamba_mixer.py`) and
`ssd_prefill_scan` take a row piece's `cache_rows`, and with none handed in
each traces what it traced — `granite-h` and `nemotron-h`, whose tiny chunks
hold fewer tokens than a piece, included. The new pin is the tiny Granite
program with the piece set to its 128-token chunk: the loop over row pieces,
the scan kernel's third prefetched vector (its body unchanged: one
`ssd_prefill_scan` body still), the per-row writes of keys, values and
convolution tail are in it.

**PR 53 moved `deepseek-v2` on purpose**: the latent prefill kernel
(`ops/mla_attention.py`) reads the stacked cache in place, so the slice of a
layer's rows is gone from the latent family's program and the kernel's
second prefetched vector holds the rows' place in the cache beside the chunk
offset (its body unchanged); the other thirteen did not move.

**PR 54 moved none of the fourteen and added `lfm2` and `lfm2-row-pieces`**:
`models/nemotron_h.py::route` became `models/experts.py::sigmoid_route` (its
epsilon under the sum static-gated: none for Nemotron),
`mamba_mixer.causal_conv` takes no bias and no activation for a seventh
family (`models/lfm2.py`) and `expert_layer` keeps a row piece's picks at
its own batch rows, and at the Mamba call sites and with no piece handed in
each traces what it traced. The new pins are the tiny LFM2 program (ten
layers as three scanned blocks: the gated short convolution, rotary
QK-normed attention, a dense and a sparse feed-forward) and the same with the
piece set to its 128-token chunk: the per-row writes of keys, values, tails
and picks are in it.

**PR 56 moved none of the sixteen and added `ling` and `ling-row-pieces`**:
the eighth family (`models/ling.py`: the two delta-rule kernels of
`ops/kda_scan.py`, the latent kernels at 4 heads with no compressed query,
the grouped expert product) whole, and with a row piece set to its 128-token
chunk: a piece's gathered latent rows and the per-row writes of latent rows,
tails and picks are in it. `deepseek-v2`, whose attention functions took a
`gate` argument this family passes, hashes as it did.

**PR 57 moved `ling` and `ling-row-pieces` on purpose**: the body of
`kda_prefill_scan` (`ops/kda_scan.py`) runs a token block in two phases —
what a chunk needs of `q, k, beta, G` alone (the triangles, the float32
inverse with its block-diagonal factors folded to a block's rows, `T [beta V
| beta K * Gamma]`) for a group of four chunks at a time into VMEM scratch,
then the four products with the state chunk by chunk — and that body is in
both Ling programs and in no other family's; the other sixteen did not move
(the file's own `__main__` printed them as they stand).

**PR 59 moved `ling` and `ling-row-pieces` on purpose**: `kda_prefill_scan`
takes the gate's projection `a` with `A_log`, `dt_bias` and the bound, `k`,
`v` and `beta` as the layer has them, and its body makes the float32
log-decay, its running sum inside each chunk (rolled float32 additions),
`beta k` and `beta v` a head's tile at a time — so the cumulative sum, the
two products with beta and the prefill's gate are gone from the program
around the kernel (`models/ling.py::_kda_mixer` makes `g` for a decode step
and for the XLA path alone) and the kernel has two more operands and one
more scratch; the other sixteen did not move (printed by the file's own
`__main__` as they stand).

**PR 60 moved none of the eighteen and added `brumby` and
`brumby-row-pieces`**: the ninth family (`models/brumby.py`: the two
power-retention kernels of `ops/power_retention.py`, no attention function
in either phase, a state and a normaliser for a cache) whole, and with a row
piece set to its 128-token chunk: the scan kernel's `rows` prefetch is in
it. The engine builds no attention function where a family has no attention
layer (`TpuBackend._attends`), which is no line of any other family's
program.

**PR 61 moved `brumby` and `brumby-row-pieces` on purpose**:
`retention_decode_update` leaves the stacked state in HBM and copies a grid
step's block itself (two blocks of scratch and two semaphore pairs, both grid
axes in sequence; `ops/power_retention.py`); the other eighteen did not move
(printed by the file's own `__main__` as they stand).

**PR 62 moved `ling` and `ling-row-pieces` on purpose**: `models/ling.py`
views the KDA group's four lane projections `[L, D, H * hd]` and `wo`
`[L, H * hd, D]` before the layers' scans (`_lane_views`; the products read
`bsd,dw->bsw` and `bsw,wd->bsd`), takes the unit norms and the head norm in
the order the `[B, S, H * hd]` arrays are tiled in where S is whole tiles of
eight tokens (`_head_tiles`, a pinned layout each way) and keeps the gate's
reshape out of its product by a barrier; the other eighteen did not move
(printed by the file's own `__main__` as they stand).

**PR 63 moved none of the twenty and added `keye` and `keye-row-pieces`**:
the tenth family (`models/keye.py`: the selection and the masked attention
of `ops/sparse_attention.py` in both phases, the grouped expert product, an
indexer-key cache and the last position's selection in the carry) whole,
and with a row piece set to its 128-token chunk: the kernels' `rows`
prefetch is in it. `models/quant.py` learned the indexer's two projections'
names, which is no line of any other family's program.

A hash says that a program moved, not what moved. `program_pins.json` beside
this file keeps, for every pinned program, one hex digit a line of the
running hash of its text: a failing pin prints the first line that differs
(`assert_pinned`). `python tests/test_one_shot_programs_pinned.py` (from the
repo's root, `PYTHONPATH=.`) traces all twenty-two, prints both tables as they
would have to read and rewrites that file — the one place that regenerates
them."""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import MODEL_REGISTRY
from vnsum_tpu.models.deepseek import tiny_deepseek

# family -> (config, config keywords, sha256 of str(jaxpr)[:16]); a config is
# a registry name, or the function itself where the family has none
_PINNED = {
    "llama": ("tiny", {}, "4aa1f80001ed0fb9"),
    "llama-qk-norm": ("tiny", {"qk_norm": True}, "2d05f67423708be5"),
    "smallthinker": ("tiny-smallthinker", {}, "a5b335b7034bb4ed"),
    "laguna": ("tiny-laguna", {}, "c8b4edca989eecec"),
    "granite-h": ("tiny-granite-h", {}, "3f9fcb64cac59b4a"),
    "nemotron-h": ("tiny-nemotron-h", {}, "c14ede11a5e68ce7"),
    "deepseek-v2": (tiny_deepseek, {}, "17a317c39a880058"),
    "llama-row-pieces": ("tiny", {}, "87c61288db10c21a"),
    "llama-looped": ("tiny-ouro", {}, "05e5ea3227bd67de"),
    "granite-h-row-pieces": ("tiny-granite-h", {}, "8ece4d118fa328f3"),
    "lfm2": ("tiny-lfm2", {}, "2ae2707f3d02f044"),
    "lfm2-row-pieces": ("tiny-lfm2", {}, "10157522ffa94d1f"),
    "ling": ("tiny-ling", {}, "fc8717d4a6edbcee"),
    "ling-row-pieces": ("tiny-ling", {}, "a7f94ff7d071bafa"),
    "brumby": ("tiny-brumby", {}, "a73eee8579c6a8ba"),
    "brumby-row-pieces": ("tiny-brumby", {}, "3e0f670ea7efbf83"),
    "keye": ("tiny-keye", {}, "b14c2362e521e912"),
    "keye-row-pieces": ("tiny-keye", {}, "a5d46e91257a11f8"),
}
# family -> the tokens a row piece of its prefill holds, where the pinned
# program is not the family's own (`Family.prefill_piece_tokens`)
_PIECE_TOKENS = {"llama-row-pieces": 128, "granite-h-row-pieces": 128,
                 "lfm2-row-pieces": 128, "ling-row-pieces": 128,
                 "brumby-row-pieces": 128, "keye-row-pieces": 128}

# the slot loop's programs of the tiny llama family, "kind-rows" -> the same
# hash: a join of 1 and of 2 rows, the segment of 4 slots, the adopt of a
# 2-row join into them
_SLOT_PINNED = {
    "slot_prefill-1": "2d5a63d1a6dc8b63",
    "slot_prefill-2": "aab563206132a4ee",
    "slot_seg-4": "1f818e78f7afc732",
    "adopt-2": "335d7da10ac8614a",
}
_SLOTS = 4

_LADDERS = Path(__file__).with_name("program_pins.json")


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _ladder(text: str) -> str:
    """One hex digit a line: of the running hash of the text up to and
    including that line, so the first digit that differs is at (or, one
    time in 16 a line, just after) the first line that does."""
    h, out = hashlib.sha256(), []
    for line in text.split("\n"):
        h.update(line.encode() + b"\n")
        out.append(h.copy().hexdigest()[0])
    return "".join(out)


def assert_pinned(name: str, text: str, want: str) -> None:
    """``text`` hashes to ``want``; where it does not, say where the two
    programs part: the first line of ``text`` whose running hash is not the
    pinned program's, with the lines before it."""
    got = _sha(text)
    if got == want:
        return
    was = json.loads(_LADDERS.read_text()).get(name, "")
    now, lines = _ladder(text), text.split("\n")
    first = next((i for i, (a, b) in enumerate(zip(was, now)) if a != b),
                 min(len(was), len(now)))
    where = "\n".join(
        f"{'>' if i == first else ' '} {i + 1}: {lines[i][:300]}"
        for i in range(max(first - 3, 0), min(first + 2, len(lines))))
    pytest.fail(
        f"{name}: the program hashes to {got}, pinned {want}; it has "
        f"{len(lines)} lines for {len(was)} and first differs at or just "
        f"before line "
        f"{first + 1}:\n{where}", pytrace=False)


def _backend(cfg, B: int, new: int) -> TpuBackend:
    return TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                      max_new_tokens=new, interpret=True, quantize=True,
                      prefill_chunk_tokens=128)


def one_shot_jaxpr(cfg, B: int = 2, S: int = 256, new: int = 8,
                   piece_tokens: int | None = None) -> str:
    """The text of the (B, S) one-shot program's jaxpr for ``cfg``, traced
    on shapes alone; ``piece_tokens`` replaces the family's own."""
    be = _backend(cfg, B, new)
    if piece_tokens:
        be.family = dataclasses.replace(
            be.family, prefill_piece_tokens=piece_tokens)
    fn = be._make_fn(B, S, new, be.gen_cfg)
    return str(jax.make_jaxpr(fn)(
        jax.eval_shape(lambda: be.params),
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.uint32)))


def slot_jaxpr(program: str, S: int = 256, new: int = 8) -> str:
    """The text of one slot-loop program's jaxpr ("kind-rows"), asked of
    the engine as ``TpuSlotLoop`` asks and traced on the shapes the loop
    calls it with (``admit`` / ``step`` of backend/inflight.py)."""
    kind, rows = program.split("-")
    B, N, C = int(rows), _SLOTS, S + new
    be = _backend(MODEL_REGISTRY["tiny"](), N, new)
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: be.params)
    seed = sds((), jnp.uint32)

    def i32(*shape):
        return sds(shape, jnp.int32)

    def done(n):
        return sds((n,), jnp.bool_)

    def cache(n):
        return jax.eval_shape(lambda: be._init_prefill_cache(n, C))

    fn = be._get_seg_fn(kind, B, S, new, be.gen_cfg)
    args = {
        # params, tokens, pad_lens, seed, uids
        "slot_prefill": lambda: (params, i32(B, S), i32(B), seed, i32(B)),
        # params, t, cur, cache, done, uids, out, pads, seed
        "slot_seg": lambda: (params, i32(B), i32(B), cache(B), done(B),
                             i32(B), i32(B, new), i32(B), seed),
        # the resident cache, cur, done, t, out, pads; the join's cache,
        # first, done0, pads; the slots it lands on
        "adopt": lambda: (cache(N), i32(N), done(N), i32(N), i32(N, new),
                          i32(N), cache(B), i32(B), done(B), i32(B), i32(B)),
    }[kind]()
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("family", list(_PINNED))
def test_the_one_shot_program_traces_to_the_pinned_jaxpr(family):
    config, kw, want = _PINNED[family]
    text = one_shot_jaxpr(MODEL_REGISTRY.get(config, config)(**kw),
                          piece_tokens=_PIECE_TOKENS.get(family))
    assert "pallas_call" in text          # the kernels' bodies are hashed too
    assert_pinned(family, text, want)


def test_granites_program_calls_the_kernels_it_called():
    """What PR 47 had to leave alone when the mixer became shared code: the
    tiny Granite program names the kernels it named on PR 46's tree, as
    often (counted in the jaxpr's text on both trees: a kernel's name
    stands at its ``pallas_call`` and at each call of its jitted wrapper),
    with one body a kernel."""
    import re
    from collections import Counter

    text = one_shot_jaxpr(MODEL_REGISTRY["tiny-granite-h"]())
    names = Counter(re.findall(r"name=(\w+)", text))
    assert {k: n for k, n in names.items() if k.split("_")[0] in (
        "ssd", "ssm", "flash", "expert", "mla")} == {
        "ssd_prefill_scan": 5, "ssm_decode_update": 3,
        "flash_prefill_attention": 3, "flash_decode_attention": 2}
    assert text.count("pallas_call[") == 4


@pytest.mark.parametrize("program", list(_SLOT_PINNED))
def test_a_slot_program_traces_to_the_pinned_jaxpr(program):
    text = slot_jaxpr(program)
    assert ("pallas_call" in text) == (not program.startswith("adopt"))
    assert_pinned(program, text, _SLOT_PINNED[program])


def regenerate() -> None:
    """Trace all twenty-two programs, print the two tables' hashes as they are now
    and rewrite the line ladders."""
    texts = [("_PINNED", family, want,
              one_shot_jaxpr(MODEL_REGISTRY.get(config, config)(**kw),
                             piece_tokens=_PIECE_TOKENS.get(family)))
             for family, (config, kw, want) in _PINNED.items()]
    texts += [("_SLOT_PINNED", program, want, slot_jaxpr(program))
              for program, want in _SLOT_PINNED.items()]
    for table, name, want, text in texts:
        print(f"{table:12} {name!r}: {_sha(text)!r}"
              f"{'' if _sha(text) == want else '   # was ' + want}")
    _LADDERS.write_text(json.dumps(
        {name: _ladder(text) for _, name, _, text in texts},
        indent=0, sort_keys=True) + "\n")


if __name__ == "__main__":
    regenerate()
