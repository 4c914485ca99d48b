"""Mesh-sharded serving engine byte-identity (ISSUE 11 tentpole).

Every serving path — one-shot generate, chunked prefill + radix
resume-prefill under eviction churn, spec decode, and the in-flight slot
loop with staggered joins — must produce byte-identical greedy outputs on a
multi-device mesh and on a single chip. Runs on a >=4 virtual-device CPU
mesh (conftest forces 8 for the full suite; the CI `multichip-serving` step
runs this file alone under XLA_FLAGS=--xla_force_host_platform_device_count=4,
so every mesh here uses at most 4 devices).

Tier-1 fast on purpose: tiny model, byte tokenizer, short budgets.
"""
from __future__ import annotations

import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.parallel import make_mesh

HEADER = "tieu de chung cua cac tai lieu dai: " * 6  # >128 shared byte tokens
PROMPTS = [HEADER + f"noi dung rieng {i} " * 4 for i in range(6)]
SHORT = [
    "văn bản một về kinh tế",
    "hai",
    "văn bản thứ ba dài hơn một chút",
    "bốn bốn",
]


def make_backend(mesh=None, **kw):
    kw.setdefault("model_config", tiny_llama(max_seq_len=512))
    kw.setdefault("tokenizer", "byte")
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("seed", 1)
    kw.setdefault("segment_tokens", 4)
    if not kw.get("interpret"):
        kw.setdefault("flash", False)  # off-chip: the dense path, by name
    return TpuBackend(mesh=mesh, **kw)


def tp_dp_mesh():
    return make_mesh({"data": 2, "model": 2, "seq": 1}, platform="cpu")


def dp_mesh():
    return make_mesh({"data": 4, "model": 1, "seq": 1}, platform="cpu")


@pytest.fixture(scope="module")
def reference_outputs():
    return make_backend().generate(PROMPTS)


# -- one-shot ----------------------------------------------------------------


def test_oneshot_tp_dp_matches_single_chip(reference_outputs):
    assert make_backend(mesh=tp_dp_mesh()).generate(PROMPTS) == reference_outputs


def test_oneshot_dp_only_matches_single_chip(reference_outputs):
    assert make_backend(mesh=dp_mesh()).generate(PROMPTS) == reference_outputs


# -- chunked prefill + radix resume under eviction churn ---------------------


def test_chunked_prefill_and_radix_resume_match_under_churn(reference_outputs):
    """The sharded block pool (KV heads over `model`) serves resume-prefill
    byte-identically while LRU eviction churns a deliberately tiny pool —
    and chunked prefill rides the same program. Two passes: the second must
    actually hit the cache."""
    b = make_backend(
        mesh=tp_dp_mesh(), cache_blocks=6, cache_block_tokens=64,
        prefill_chunk_tokens=128,
    )
    hints = [HEADER] * len(PROMPTS)
    assert b.generate(PROMPTS, cache_hints=hints) == reference_outputs
    assert b.generate(PROMPTS, cache_hints=hints) == reference_outputs
    assert b.stats.cache_hit_tokens > 0  # resume really fired
    st = b.prefix_cache.stats_dict()
    assert st["blocks_used"] <= 6
    # the pool shards KV heads over `model`, replicated elsewhere
    spec = b.prefix_cache.store.pool["k"].sharding.spec
    assert tuple(spec) == (None, None, "model", None, None)


def test_dp_resume_matches_single_chip_cached_run(reference_outputs):
    """Cached-resume parity on a data-only mesh (the pure-DP replica
    shape): outputs equal both the uncached single-chip reference and a
    cached single-chip run."""
    single = make_backend(cache_blocks=8, cache_block_tokens=64)
    hints = [HEADER] * len(PROMPTS)
    single.generate(PROMPTS, cache_hints=hints)
    warm_single = single.generate(PROMPTS, cache_hints=hints)
    b = make_backend(mesh=dp_mesh(), cache_blocks=8, cache_block_tokens=64)
    b.generate(PROMPTS, cache_hints=hints)
    warm_sharded = b.generate(PROMPTS, cache_hints=hints)
    assert warm_single == warm_sharded == reference_outputs
    assert b.stats.cache_hit_tokens > 0


# -- in-flight slot loop -----------------------------------------------------


def _ragged_eos_config(max_new=16):
    """Extra EOS at a mid-output token id so rows finish at different
    segments and freed slots really refill (the probe trick the in-flight
    engine tests use)."""
    probe = make_backend()
    outs = probe.generate(SHORT)
    tok = probe.tok
    ids = [tok.encode(o, add_bos=False) for o in outs if o]
    longest = max(ids, key=len)
    return GenerationConfig(
        eos_ids=(tok.eos_id, longest[len(longest) // 2]),
        max_new_tokens=max_new,
    )


@pytest.mark.parametrize("mesh_fn", [tp_dp_mesh, dp_mesh])
def test_slot_loop_staggered_joins_match_solo(mesh_fn):
    """Requests joining the sharded resident batch at different segment
    boundaries, into different slots, next to different companions, each
    match their single-chip solo run byte-for-byte."""
    gen = _ragged_eos_config()
    solo_backend = make_backend()
    solo = [solo_backend.generate([p], config=gen)[0] for p in SHORT]

    b = make_backend(mesh=mesh_fn())
    loop = b.start_slot_loop(4, config=gen)
    outs: dict[int, str] = {}
    adm, rej = loop.admit([(i, SHORT[i], None) for i in (0, 1)])
    assert rej == [] and len(adm) == 2
    pending = [i for i in range(len(SHORT)) if i not in {a.key for a in adm}]
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if pending and loop.free:
            adm, rej = loop.admit([(i, SHORT[i], None) for i in pending])
            assert rej == []
            for a in adm:
                pending.remove(a.key)
        if not pending and loop.active == 0:
            break
    assert loop.active == 0 and not pending
    assert [outs[i] for i in range(len(SHORT))] == solo
    # raggedness really happened (joins were staggered, not one batch)
    assert loop.refills == len(SHORT)


def test_slot_loop_sharded_resume_from_cache(reference_outputs):
    """Joiners resume prefill from the sharded block pool mid-flight; the
    admissions report real cached tokens and outputs match the reference."""
    b = make_backend(mesh=tp_dp_mesh(), cache_blocks=16, cache_block_tokens=64)
    loop = b.start_slot_loop(4)
    outs: dict[int, str] = {}
    adm, _ = loop.admit([(i, PROMPTS[i], HEADER) for i in (0, 1)])
    assert len(adm) == 2
    loop.step()
    adm2, _ = loop.admit([(i, PROMPTS[i], HEADER) for i in (2, 3)])
    assert len(adm2) == 2
    # the first pair seeded the pool; mid-flight joiners resume from it
    assert all(a.cached_tokens > 0 for a in adm2)
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if loop.active == 0:
            break
    assert [outs[i] for i in range(4)] == reference_outputs[:4]


def test_join_bucket_respects_data_axis():
    """With data=2, a single joiner still buckets to Bj=2 (one filler row)
    and an admit with fewer free slots than DP rows waits instead of
    building an indivisible join batch."""
    b = make_backend(mesh=tp_dp_mesh())
    loop = b.start_slot_loop(4)
    adm, rej = loop.admit([(0, SHORT[0], None)])
    assert rej == [] and len(adm) == 1    # Bj=2: joiner + filler both fit
    adm, rej = loop.admit([(1, SHORT[1], None), (2, SHORT[2], None)])
    assert len(adm) == 2                  # 3 free -> data_size*2^0 = 2 taken
    # 1 free slot < data_size=2: admission defers to the next boundary
    adm, rej = loop.admit([(3, SHORT[3], None)])
    assert adm == [] and rej == []
    outs: dict[int, str] = {}
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if loop.active == 0:
            break
    assert set(outs) == {0, 1, 2}


# -- speculative decoding ----------------------------------------------------


def test_spec_decode_dp_matches_plain_and_tp_degrades():
    """Spec decoding runs its dense verify path on a data-only mesh
    (byte-identical greedy) and degrades typed to plain decode under model
    sharding — without forcing anything else single-chip."""
    gen = GenerationConfig(spec_k=4)
    prompts = SHORT[:4]
    refs = [p + " va phat trien ben vung" for p in prompts]
    want = make_backend().generate(prompts)

    dp = make_backend(mesh=dp_mesh())
    assert dp.generate(prompts, config=gen, references=refs) == want
    assert dp.stats.spec_verify_steps > 0          # spec really ran
    assert len(dp.take_spec_report()) == len(prompts)

    tp = make_backend(mesh=tp_dp_mesh())
    assert tp.generate(prompts, config=gen, references=refs) == want
    assert tp.stats.spec_verify_steps == 0         # degraded to plain decode
