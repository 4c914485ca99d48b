"""The chunked prefill a row piece at a time (`TpuBackend._prefill_forward`
where `Family.prefill_piece_tokens` is set): a chunk's forward runs over
pieces of the batch's rows, longest pad first, and a piece whose rows hold
nothing but left pad in that chunk is not run. On the tiny llama family, on
the CPU, kernels interpreted (and the dense path): the same tokens, logits
and real cache slots as the whole batch a chunk, whatever the order of the
rows; a dead piece's slots still zero; the counter equal to the count by
hand. On the tiny Granite-4.0-H family (one period: nine Mamba layers and
an attention layer; all four kernels interpreted, and the XLA forms) the
same, and every row's recurrent state and convolution tail beside them.

The pad patterns are the benchmark's, a sixteenth the size (bucket 512 in
chunks of 128 for 8,192 in chunks of 2,048): the served mix's seven joins
(`benchmarks/traffic/serve-fanout-8k.json` in the driver's order, four a
join) and a dense offline group's first map dispatch, its four tail chunks
beside four full ones.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

import jax
import jax.numpy as jnp

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import jitted_init
from vnsum_tpu.models import granite_hybrid
from vnsum_tpu.models.llama import init_params, tiny_llama
from vnsum_tpu.models.quant import quantize_params

S, CHUNK, NEW = 512, 128, 8

_JOINS = [
    [780, 7080, 6180, 7620], [1740, 540, 7440, 2000],
    [7260, 6000, 6900, 6360], [7800, 1500, 1020, 6540],
    [300, 8000, 6720, 1260], [1500, 7800, 6900, 6360],
    [1020, 7440, 6000, 540],
]
_OFFLINE = [1900, 3400, 4900, 6500, 7800, 7800, 7800, 7800]
# name -> (prompt tokens a row at the benchmark's size; 0 = an all-pad
# filler row, tokens a piece)
_PATTERNS = {
    **{f"join{i}": (lens, CHUNK) for i, lens in enumerate(_JOINS)},
    "offline": (_OFFLINE, CHUNK),
    "offline-shuffled": ([7800, 1900, 7800, 6500, 3400, 7800, 4900, 7800],
                         CHUNK),
    "all-live": ([8192] * 4, CHUNK),
    "filler-row": ([7080, 780, 6180, 0], CHUNK),
    "two-fillers-first": ([0, 0, 2000, 7440], CHUNK),
    # two rows a piece: a piece is dead where both of its rows are
    "offline-pairs": (_OFFLINE, 2 * CHUNK),
    "shuffled-pairs": ([540, 7800, 300, 7800, 1900, 7800, 780, 7800],
                       2 * CHUNK),
    "join1-pairs": (_JOINS[1], 2 * CHUNK),
}


@pytest.fixture(scope="module")
def cfg():
    return tiny_llama(max_seq_len=S + 128)


@pytest.fixture(scope="module")
def params(cfg):
    return quantize_params(jitted_init(init_params, cfg, 0))


def _engine(cfg, params, piece_tokens, **kw):
    kw.setdefault("interpret", True)
    be = TpuBackend(
        model_config=cfg, params=params, batch_size=8, max_new_tokens=NEW,
        quantize=True, quantize_act=True, prefill_chunk_tokens=CHUNK, **kw)
    # a copy of the family with the piece the test wants: None is the whole
    # batch a chunk, the program every family had before
    be.family = dataclasses.replace(
        be.family, prefill_piece_tokens=piece_tokens)
    return be


def _prefills(made):
    """The jitted prefills of ``made`` {tokens a piece: engine} by
    (tokens a piece, batch), built once: last-position logits, the state,
    the first sampled token and which rows were done before decoding."""
    programs = {}

    def prefill(piece_tokens, B):
        key = (piece_tokens, B)
        if key not in programs:
            be = made[piece_tokens]
            use_flash, _ = be._decode_settings(S, S + NEW)
            part = be._make_prefill_part(B, S, NEW, be.gen_cfg)

            def program(params, tokens, pad_lens):
                logits, cache = be._prefill_forward(
                    params, tokens, pad_lens, B, S, S + NEW, use_flash,
                    be._layer_window_fn())
                first, _, done0 = part(params, tokens, pad_lens, 3)
                return logits[:, -1, :], cache, first, done0

            programs[key] = jax.jit(program)
        return programs[key]

    return prefill


@pytest.fixture(scope="module")
def engines(cfg, params):
    """{family-path: ({tokens a piece (None = whole batch): engine}, their
    prefills, the weights)}: the dense family with its kernels interpreted,
    the state-space hybrid with its kernels interpreted and with the XLA
    forms of attention and scan."""
    pieces = (None, CHUNK, 2 * CHUNK)
    hybrid_cfg = granite_hybrid.tiny_granite_h(n_layers=10,
                                               max_seq_len=S + 128)
    hybrid = quantize_params(
        jitted_init(granite_hybrid.init_params, hybrid_cfg, 0))
    out = {}
    for name, (c, p, kw) in {
            "llama": (cfg, params, {}),
            "granite-h": (hybrid_cfg, hybrid, {}),
            "granite-h-xla": (hybrid_cfg, hybrid, dict(flash=False))}.items():
        made = {n: _engine(c, p, n, **kw) for n in pieces}
        out[name] = (made, _prefills(made), p)
    return out


def _batch(lens, rng):
    """Left-padded tokens and pads for prompts of ``lens`` tokens at the
    benchmark's size, a sixteenth of it here."""
    lens = [L * S // 8192 for L in lens]
    tokens = np.full((len(lens), S), 256, np.int32)   # the byte pad id
    for row, L in enumerate(lens):
        tokens[row, S - L:] = rng.integers(1, 250, L)
    return tokens, np.asarray([S - L for L in lens], np.int32)


# the patterns the state-space hybrid runs: the offline group with its four
# tails (and shuffled), no pad at all, filler rows, two rows a piece
_HYBRID_PATTERNS = ("offline", "offline-shuffled", "all-live", "filler-row",
                    "two-fillers-first", "offline-pairs", "shuffled-pairs")
_KV_LEAVES = ("k", "v", "ks", "vs")


@pytest.mark.parametrize("family, name", [
    *(("llama", n) for n in _PATTERNS),
    *((f, n) for f in ("granite-h", "granite-h-xla")
      for n in _HYBRID_PATTERNS)])
def test_pieces_give_what_the_whole_batch_gives(engines, family, name):
    made, prefill, params = engines[family]
    lens, piece_tokens = _PATTERNS[name]
    tokens, pads = _batch(lens, np.random.default_rng(len(name)))
    B, R = len(lens), piece_tokens // CHUNK
    want = prefill(None, B)(params, tokens, pads)
    got = prefill(piece_tokens, B)(params, tokens, pads)
    logits, cache, first, done0 = (jax.device_get(x) for x in got)
    logits_w, cache_w, first_w, done0_w = (jax.device_get(x) for x in want)

    # (a) sampled tokens and last-position logits, real rows (a filler row
    # is done before it decodes and nobody reads its logits)
    real = pads < S
    np.testing.assert_array_equal(done0, ~real)
    np.testing.assert_array_equal(done0, done0_w)
    np.testing.assert_array_equal(first[real], first_w[real])
    np.testing.assert_allclose(logits[real], logits_w[real],
                               rtol=1e-4, atol=1e-4)
    assert not logits[~real].any()

    # (b) the cache: a row's real slots equal; the slots of a (row, chunk)
    # piece that was not run still zero
    kv = [leaf for leaf in cache if leaf in _KV_LEAVES]
    dead_by_hand = 0
    for row, pad in enumerate(pads):
        for leaf in kv:
            np.testing.assert_allclose(
                np.asarray(cache[leaf][:, row, :, pad:S], np.float32),
                np.asarray(cache_w[leaf][:, row, :, pad:S], np.float32),
                rtol=1e-4, atol=1e-4, err_msg=f"{leaf} row {row}")
    # the pieces take the rows longest pad first, R at a time
    by_pad = np.argsort(-pads, kind="stable")
    never_run = set(range(B))
    for lo in range(0, S, CHUNK):
        dead_rows = int((pads >= lo + CHUNK).sum())
        skipped = by_pad[: dead_rows // R * R]
        dead_by_hand += len(skipped)
        never_run &= set(skipped.tolist())
        for row in skipped:
            for leaf in kv:
                assert not cache[leaf][:, row, :, lo:lo + CHUNK].any(), (
                    leaf, row, lo)
                # ... which the whole batch filled with the pad token's
                assert cache_w[leaf][:, row, :, lo:lo + CHUNK].any()
    if R == 1:
        assert dead_by_hand == sum((S - L * S // 8192) // CHUNK for L in lens)

    # (b') the state that is not keys and values: every Mamba layer's
    # recurrent state and convolution tail of every row as the whole batch
    # left them; a row no piece ran (and any filler row: the pad rule)
    # still holds the zeros it came with
    for leaf in set(cache) - set(kv):
        assert leaf in ("ssm", "conv") and cache[leaf][:, real].any()
        np.testing.assert_allclose(
            np.asarray(cache[leaf], np.float32),
            np.asarray(cache_w[leaf], np.float32), rtol=1e-4, atol=1e-5,
            err_msg=leaf)
        for row in sorted(never_run | set(np.flatnonzero(~real).tolist())):
            assert not cache[leaf][:, row].any(), (leaf, row)
    assert never_run <= set(np.flatnonzero(~real).tolist())
    assert (set(cache) - set(kv) == {"ssm", "conv"}) == (family != "llama")

    # (c) the counter, by hand; the whole batch a chunk counts none dead
    for be, dead in ((made[piece_tokens], dead_by_hand), (made[None], 0)):
        was = (be.stats.prefill_row_chunks_dead,
               be.stats.prefill_row_chunks_total)
        assert be._count_row_chunks(pads, S) == (dead, B * S // CHUNK)
        assert (be.stats.prefill_row_chunks_dead,
                be.stats.prefill_row_chunks_total) == (
            was[0] + dead, was[1] + B * S // CHUNK)


def test_the_benchmarks_counts_are_the_issues():
    """35 of the served mix's 112 (row, chunk) pieces and 6 of a dense
    offline group's 96 hold no real token (host arithmetic on the traffic
    files' lengths)."""
    dead = lambda lens: sum((8192 - L) // 2048 for L in lens)  # noqa: E731
    assert sum(dead(j) for j in _JOINS) == 35
    assert 4 * 4 * len(_JOINS) == 112
    assert dead(_OFFLINE) == 6


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "dense"])
def test_generate_counts_and_says_its_dead_pieces(cfg, params, kernels):
    """Through ``generate``: the same texts as the whole batch a chunk,
    the counter from the pads the dispatch was packed with, on the
    dispatch's span and in its log line; ``prefill_blocks`` keeps its keys."""
    import logging

    kw = dict(interpret=True) if kernels else dict(flash=False)
    lens = [40, 500, 130, 260]
    prompts = ["".join(chr(97 + (i * 7 + j) % 26) for j in range(L - 1))
               for i, L in enumerate(lens)]            # + BOS: L tokens
    whole = _engine(cfg, params, None, **kw)
    pieces = _engine(cfg, params, CHUNK, **kw)
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    log = logging.getLogger("vnsum.engine")
    log.addHandler(handler)
    try:
        assert pieces.generate(prompts) == whole.generate(prompts)
    finally:
        log.removeHandler(handler)
    dead = sum((S - L) // CHUNK for L in lens)
    assert dead == 6
    assert pieces.stats.by_bucket == {(4, S): 1}
    assert (pieces.stats.prefill_row_chunks_dead,
            pieces.stats.prefill_row_chunks_total) == (dead, 16)
    assert (whole.stats.prefill_row_chunks_dead,
            whole.stats.prefill_row_chunks_total) == (0, 16)
    assert pieces.stats.prefill_blocks == whole.stats.prefill_blocks
    assert bool(pieces.stats.prefill_blocks) == kernels
    lines = [m for m in said if m.startswith("dispatch B=4")]
    assert [f", dead_row_chunks {n}/16" in m
            for m, n in zip(lines, (dead, 0))] == [True, True]


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "dense"])
def test_resume_gives_the_same_tokens_with_pieces(cfg, params, kernels):
    """The prefix cache's resume prefill (``start=K``: the forward runs
    over cache slots [K, S) of a cache seeded with pooled blocks) with
    pieces as without: a short row is dead in a chunk past K."""
    kw = dict(interpret=True) if kernels else dict(flash=False)
    header = "Ban la mot chuyen gia tom tat noi dung van ban. " * 6
    prompts = [header + f"Noi dung rieng biet so {i}: " + "cau chuyen " * n
               for i, n in enumerate((18, 2, 9))] + [header[:90]]
    outs, engines = {}, {}
    for piece_tokens in (None, CHUNK):
        be = _engine(cfg, params, piece_tokens, cache_blocks=48,
                     cache_block_tokens=64, **kw)
        be.batch_size = 4
        cold = be.generate(prompts)
        warm = be.generate(prompts)
        assert warm == cold
        assert sum(be.take_cache_report()) > 0      # the second call resumed
        outs[piece_tokens], engines[piece_tokens] = warm, be
    assert outs[CHUNK] == outs[None]
    resumed = [k for k in engines[CHUNK]._fns if k[-1]]
    assert resumed and all(k[-1] >= CHUNK for k in resumed)
    assert engines[CHUNK].stats.prefill_row_chunks_dead > 0
    assert engines[None].stats.prefill_row_chunks_dead == 0


def test_a_model_axis_mesh_runs_the_pieces(cfg, params):
    """Under a mesh whose `data` axis holds the whole batch the pieces run
    (the prefill kernel through ``sharded_flash_prefill``, ``cache_rows`` a
    replicated vector) and give the single device's texts."""
    from vnsum_tpu.parallel import make_mesh

    lens = [40, 500, 130, 260]
    prompts = ["".join(chr(97 + (i * 5 + j) % 26) for j in range(L - 1))
               for i, L in enumerate(lens)]
    mesh = make_mesh({"data": 1, "model": 2, "seq": 1}, platform="cpu")
    sharded = _engine(cfg, params, CHUNK, mesh=mesh)
    assert sharded._prefill_piece_rows(4, CHUNK) == 1
    assert sharded.generate(prompts) == _engine(
        cfg, params, None).generate(prompts)
    assert sharded.stats.prefill_row_chunks_dead == 6


def test_a_mesh_that_spreads_the_rows_keeps_the_whole_batch(cfg, params):
    """Rows spread over a mesh's `data` axis are not pieced; a family that
    names no piece, and a chunk that holds fewer tokens than a piece
    should, neither."""
    import types

    be = _engine(cfg, params, CHUNK)
    assert be._prefill_piece_rows(8, CHUNK) == 1
    assert be._prefill_piece_rows(8, CHUNK // 2) == 2
    assert be._prefill_piece_rows(1, CHUNK // 2) == 0
    be.mesh = types.SimpleNamespace(shape={"data": 2, "model": 1})
    assert be._prefill_piece_rows(8, CHUNK) == 0
    be.mesh = types.SimpleNamespace(shape={"data": 1, "model": 2})
    assert be._prefill_piece_rows(8, CHUNK) == 1
    assert _engine(cfg, params, None)._prefill_piece_rows(8, CHUNK) == 0


def test_which_families_name_a_piece():
    """The dense family and the state-space hybrid name 2,048 tokens, one
    row of the cells' prefill chunk; the four others leave None and run
    the whole batch a chunk until each has its own measurement."""
    from vnsum_tpu.models import MODEL_REGISTRY
    from vnsum_tpu.models.deepseek import tiny_deepseek
    from vnsum_tpu.models.family import family_of

    named = {name: family_of(make()).prefill_piece_tokens for name, make in (
        ("llama", MODEL_REGISTRY["tiny"]),
        ("ouro", MODEL_REGISTRY["tiny-ouro"]),
        ("smallthinker", MODEL_REGISTRY["tiny-smallthinker"]),
        ("laguna", MODEL_REGISTRY["tiny-laguna"]),
        ("granite-h", MODEL_REGISTRY["tiny-granite-h"]),
        ("nemotron-h", MODEL_REGISTRY["tiny-nemotron-h"]),
        ("deepseek-v2", tiny_deepseek))}
    assert named == {"llama": 2048, "ouro": 2048, "smallthinker": None,
                     "laguna": None, "granite-h": 2048, "nemotron-h": None,
                     "deepseek-v2": None}
