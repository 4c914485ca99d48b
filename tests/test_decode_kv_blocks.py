"""The count of the key blocks the decode kernels leave out
(`EngineStats.decode_kv_blocks_skipped` / `_total`,
`TpuBackend._count_decode_kv_blocks`): a K/V block of
`ops/decode_attention.py` holds one row, and a row is read from the block of
its first real slot, so the blocks wholly under its left pad are neither
copied nor computed. Host arithmetic on the pads, by the kernels' own block
rule: by hand at the benchmark's size — the served mix's seven joins
(`benchmarks/traffic/serve-fanout-8k.json` in the driver's order) and a dense
offline group's first map dispatch — and through `generate` and the slot loop
on the tiny llama family, on the CPU, kernels interpreted: the dispatch's INFO
line and the two spans' notes.
"""
from __future__ import annotations

import logging
import types

import numpy as np
import pytest

pytest.importorskip("jax")

from vnsum_tpu.backend.engine import EngineStats, TpuBackend
from vnsum_tpu.models.family import family_of
from vnsum_tpu.models.llama import phi4_14b, qwen3_8b, tiny_llama
from vnsum_tpu.obs.trace import BatchTrace, reset_collector, set_collector
from vnsum_tpu.ops.decode_attention import decode_block_k

_JOINS = [
    [780, 7080, 6180, 7620], [1740, 540, 7440, 2000],
    [7260, 6000, 6900, 6360], [7800, 1500, 1020, 6540],
    [300, 8000, 6720, 1260], [1500, 7800, 6900, 6360],
    [1020, 7440, 6000, 540],
]
_OFFLINE = [1900, 3400, 4900, 6500, 7800, 7800, 7800, 7800]
S = 8192


def _counter(cfg, quantize_kv=True, kernels=True):
    """The engine's counter on a bare object: a configuration, its family,
    the mesh (none) and the statistics are all it reads (no parameters, no
    program)."""
    be = types.SimpleNamespace(
        cfg=cfg, family=family_of(cfg), quantize_kv=quantize_kv, mesh=None,
        stats=EngineStats(), _decode_settings=lambda S, C: (kernels, kernels))
    be.count = types.MethodType(TpuBackend._count_decode_kv_blocks, be)
    be._model_shards = types.MethodType(TpuBackend._model_shards, be)
    return be


def _by_hand(lens, steps, bk, layers):
    """Blocks between slot 0 and the fill, and those of them before the
    block of a row's first real slot, step by step."""
    skipped = total = 0
    for t in range(steps):
        for n in lens:
            walked = (S + t) // bk + 1
            total += walked
            skipped += min((S - n) // bk, walked)
    return skipped * layers, total * layers


def test_the_served_mix_skips_two_fifths_of_its_blocks():
    """28 requests in seven joins of four, a segment of 128 steps each, 36
    layers, blocks of 512 slots in the cache of 8,320: 43% of the bucket's
    slots are pad, 40% of its 16 blocks a row wholly so, 38% of the 17 a
    step walks."""
    be = _counter(qwen3_8b())
    assert decode_block_k(8, 128, 1, S + 128) == 512
    for lens in _JOINS:
        pads = S - np.asarray(lens)
        got = be.count(pads, S + np.arange(128)[:, None], S, S + 128)
        assert got == _by_hand(lens, 128, 512, 36)
        # a step walks 17 blocks a row: 16 of the bucket and the one the
        # new tokens fill
        assert got[1] == 128 * 4 * 17 * 36
    st = be.stats
    assert st.decode_kv_blocks_total == 7 * 128 * 4 * 17 * 36
    assert st.decode_kv_blocks_skipped == 128 * 36 * sum(
        (S - n) // 512 for lens in _JOINS for n in lens)
    assert st.decode_kv_blocks_skipped / st.decode_kv_blocks_total == (
        pytest.approx(180 / (28 * 17)))   # 0.378
    pad_share = 1 - sum(map(sum, _JOINS)) / (28 * S)
    assert pad_share == pytest.approx(0.43, abs=0.005)


@pytest.mark.parametrize("cfg,rows", [(qwen3_8b(), 8), (phi4_14b(), 12)],
                         ids=["qwen3-8b", "phi4-14b"])
def test_an_offline_groups_first_dispatch_skips_its_tails_pad(cfg, rows):
    """Four tail chunks of 1,900 to 6,500 tokens beside full ones, 256
    steps: a fifth of the blocks of 8 rows, an eighth of 12; an all-live
    dispatch skips nothing."""
    lens = _OFFLINE[:4] + [7800] * (rows - 4)
    be = _counter(cfg)
    bk = decode_block_k(cfg.n_kv_heads, cfg.head_dim, 1, S + 256)
    assert bk == 512
    fills = S + np.arange(256)[:, None]
    got = be.count(S - np.asarray(lens), fills, S, S + 256)
    assert got == _by_hand(lens, 256, bk, cfg.n_layers)
    assert got[1] == 256 * rows * 17 * cfg.n_layers
    assert got[0] == 256 * cfg.n_layers * (12 + 9 + 6 + 3)
    assert got[0] / got[1] == pytest.approx(30 / (rows * 17))
    assert be.count(np.zeros(rows, np.int64), fills, S, S + 256) == (0, got[1])
    assert be.stats.decode_kv_blocks_skipped == got[0]
    assert be.stats.decode_kv_blocks_total == 2 * got[1]


def test_heads_of_64_count_their_blocks_by_the_pairs_tile():
    """Llama-3.2-1B's 8 KV heads of 64 sit two a lane tile: the kernels walk
    4 tiles of 128 in blocks of 1,024 slots (9 a row at 8,448), and every
    block counted is counted as paired; at heads of 128 none is."""
    from vnsum_tpu.models.llama import llama32_1b

    cfg = llama32_1b()
    assert (cfg.n_kv_heads, cfg.head_dim) == (8, 64)
    assert decode_block_k(4, 128, 1, S + 256) == 1024
    be = _counter(cfg)
    fills = S + np.arange(256)[:, None]
    got = be.count(S - np.asarray(_OFFLINE), fills, S, S + 256)
    assert got == _by_hand(_OFFLINE, 256, 1024, cfg.n_layers)
    assert got[1] == 256 * 8 * 9 * cfg.n_layers
    assert be.stats.decode_kv_blocks_paired == got[1]
    wide = _counter(qwen3_8b())
    wide.count(S - np.asarray(_OFFLINE), fills, S, S + 256)
    assert wide.stats.decode_kv_blocks_total > 0
    assert wide.stats.decode_kv_blocks_paired == 0


def test_rows_that_ended_stay_where_they_were_and_a_free_slot_is_all_pad():
    """A segment's fills a row: a row that ended (or a free slot, all pad)
    keeps its slot for the steps the others run."""
    be = _counter(qwen3_8b())
    t0, t1 = np.asarray([0, 100, 0, 0]), np.asarray([128, 128, 0, 40])
    pads = np.asarray([600, 7000, S, 0])
    steps = int((t1 - t0).max())
    fills = S + np.minimum(t0 + np.arange(steps)[:, None], t1)
    skipped, total = be.count(pads, fills, S, S + 128)
    assert total == 36 * 128 * 4 * 17
    # the free slot reads the one block its fill is in
    assert skipped == 36 * 128 * (1 + 13 + 16 + 0)


def test_window_layers_another_kernel_and_the_dense_path_count_nothing():
    """Only layers that attend globally are counted (a window layer starts
    at its window's floor whatever the pad), and only where the GQA decode
    kernels run."""
    from vnsum_tpu.models.smallthinker import SmallThinkerConfig

    pads, fills = np.asarray([4096, 0]), S + np.arange(4)[:, None]
    cfg = SmallThinkerConfig()
    windows = family_of(cfg).layer_windows(cfg)
    n_global = sum(1 for w in windows if not w)
    assert 0 < n_global < len(windows)
    bk = decode_block_k(cfg.n_kv_heads, cfg.head_dim, 1, S + 256)
    assert bk == 1024
    assert _counter(cfg).count(pads, fills, S, S + 256) == (
        4 * 4 * n_global, 4 * 2 * 9 * n_global)
    assert _counter(qwen3_8b(), kernels=False).count(
        pads, fills, S, S + 256) == (0, 0)
    # a bfloat16 cache holds the same bytes in half the slots
    assert _counter(qwen3_8b(), quantize_kv=False).count(
        pads, fills, S, S + 256) == (36 * 4 * 16, 36 * 4 * 2 * 33)
    from vnsum_tpu.models.deepseek import tiny_deepseek

    assert _counter(tiny_deepseek()).count(pads, fills, S, S + 256) == (0, 0)


# -- through the engine: the log line and the spans ---------------------------


@pytest.fixture
def small_blocks(monkeypatch):
    """The tiny family's keys are 32 bytes a slot of its int8 cache: blocks
    of 4 KiB are 128 slots, eight to its bucket of 1,024."""
    from vnsum_tpu.ops import decode_attention

    monkeypatch.setattr(decode_attention, "_BLOCK_KEY_BYTES", 4 * 1024)
    return 128


class Collected:
    """An obs collector installed for a block: the spans' notes by name."""

    def __enter__(self):
        self.bt = BatchTrace(batch_id=0, occupancy=1)
        self._token = set_collector(self.bt)
        return self

    def __exit__(self, *exc):
        reset_collector(self._token)

    def named(self, name):
        return [e for e in self.bt.events if e.name == name]


def _engine(**kw):
    return TpuBackend(
        model_config=tiny_llama(max_seq_len=1024 + 16), batch_size=4,
        max_new_tokens=8, segment_tokens=8, **kw)


def _prompts(lens):
    return ["".join(chr(97 + (i * 7 + j) % 26) for j in range(L - 1))
            for i, L in enumerate(lens)]            # + BOS: L tokens


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "dense"])
def test_generate_counts_and_says_the_blocks_it_skipped(kernels,
                                                        small_blocks):
    be = _engine(**(dict(interpret=True) if kernels else dict(flash=False)))
    cfg = be.cfg
    lens = [40, 1000, 300, 620]
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    log = logging.getLogger("vnsum.engine")
    log.addHandler(handler)
    try:
        with Collected() as c:
            texts = be.generate(_prompts(lens))
    finally:
        log.removeHandler(handler)
    assert be.stats.by_bucket == {(4, 1024): 1}
    bk = decode_block_k(cfg.n_kv_heads, cfg.head_dim, 1, 1024 + 8)
    assert bk == small_blocks and be.quantize_kv == kernels
    steps = max(len(be.tok.encode(t, add_bos=False)) for t in texts) + 1
    steps = min(steps, 8)
    skipped = total = 0
    if kernels:
        total = steps * 4 * (1024 // bk + 1) * cfg.n_layers
        skipped = steps * cfg.n_layers * sum((1024 - n) // bk for n in lens)
        assert skipped > 0
    assert (be.stats.decode_kv_blocks_skipped,
            be.stats.decode_kv_blocks_total) == (skipped, total)
    (line,) = [m for m in said if m.startswith("dispatch B=4")]
    assert (f", dead_row_chunks 0/4, skipped_kv_blocks {skipped}/{total}"
            in line)
    (disp,) = c.named("dispatch")
    assert disp.args["skipped_kv_blocks"] == skipped
    assert disp.args["kv_blocks"] == total


def test_a_segment_counts_its_rows_blocks_and_notes_them_on_its_span(
        small_blocks):
    be = _engine(interpret=True)
    cfg = be.cfg
    loop = be.start_slot_loop(4, max_new_tokens=8, prompt_tokens=1024)
    lens = [40, 1000, 300]
    with Collected() as c:
        admissions, rejected = loop.admit(
            [(i, p, None) for i, p in enumerate(_prompts(lens))])
        assert len(admissions) == 3 and not rejected
        res = loop.step()
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    log = logging.getLogger("vnsum.inflight")
    log.addHandler(handler)
    try:
        loop.close()
        loop.close()   # says it once
    finally:
        log.removeHandler(handler)
    bk = small_blocks
    (seg,) = c.named("decode_seg")
    # the join's fourth row is a filler, all pad like a free slot: it reads
    # the one block its fill is in
    t_end = np.asarray(loop._t_host)
    steps = int(t_end.max())
    assert 1 <= steps <= 8 and res.new_tokens == int(t_end.sum())
    assert res.steps == steps       # what the segment's pace is counted in
    pads = [1024 - n for n in lens] + [1024]
    skipped = total = 0
    for i in range(steps):
        for pad, t in zip(pads, t_end):
            walked = (1024 + min(i, t)) // bk + 1
            total += walked * cfg.n_layers
            skipped += min(pad // bk, walked) * cfg.n_layers
    assert seg.args["skipped_kv_blocks"] == skipped > 0
    assert seg.args["kv_blocks"] == total
    assert (be.stats.decode_kv_blocks_skipped,
            be.stats.decode_kv_blocks_total) == (skipped, total)
    assert said == [
        "slot loop closed after 1 segments and 3 joined rows: "
        f"skipped_kv_blocks {skipped}/{total}"]
    # the engine keeps the count by program, every loop of the shape in
    # one; a second loop's line still says what that loop added, no more
    again = be.start_slot_loop(4, max_new_tokens=8, prompt_tokens=1024)
    again.admit([(0, _prompts([1000])[0], None)])
    again.step()
    del said[:]
    log.addHandler(handler)
    try:
        again.close()
    finally:
        log.removeHandler(handler)
    ex = be.stats.executions[("segment", 4, 1024)]
    assert ex.count == 2 and ex.kv_blocks > total
    assert said == [
        "slot loop closed after 1 segments and 1 joined rows: "
        f"skipped_kv_blocks {ex.kv_blocks_skipped - skipped}"
        f"/{ex.kv_blocks - total}"]
