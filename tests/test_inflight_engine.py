"""In-flight slot loop over the REAL engine (CPU, tiny model).

The contract under test is the tentpole's correctness claim: a request's
greedy output is byte-identical to a solo one-shot generate() no matter when
it joined the resident batch, who it decoded next to, or which slot it
landed in — and a sampled request's stream depends only on (loop seed,
request uid, row-local step), never on join timing or companions.
"""
from __future__ import annotations

import numpy as np
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models import tiny_llama

PROMPTS = [
    "văn bản một về kinh tế",
    "hai",
    "văn bản thứ ba dài hơn một chút về xã hội",
    "bốn bốn",
    "năm năm năm",
    "sáu và bảy",
]


def make_backend(**kw):
    kw.setdefault("model_config", tiny_llama(max_seq_len=128))
    kw.setdefault("tokenizer", "byte")
    kw.setdefault("batch_size", 8)
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("seed", 1)
    kw.setdefault("segment_tokens", 4)
    if not kw.get("interpret"):
        kw.setdefault("flash", False)  # off-chip: the dense path, by name
    return TpuBackend(**kw)


def drain(loop, outs, max_segments=64):
    for _ in range(max_segments):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if loop.active == 0:
            return
    raise AssertionError("slot loop did not drain")


def ragged_eos_config(max_new=24):
    """A GenerationConfig whose extra EOS fires at scattered depths, so
    rows FINISH at different segments and freed slots actually refill
    mid-flight."""
    probe = make_backend()
    outs = probe.generate(PROMPTS)
    tok = probe.tok
    ids = [tok.encode(o, add_bos=False) for o in outs if o]
    longest = max(ids, key=len)
    return GenerationConfig(
        eos_ids=(tok.eos_id, longest[len(longest) // 2]),
        max_new_tokens=max_new,
    )


# -- greedy byte-identity ----------------------------------------------------


def test_greedy_matches_solo_with_staggered_joins_and_leaves():
    gen = ragged_eos_config()
    solo_backend = make_backend()
    solo = [solo_backend.generate([p], config=gen)[0] for p in PROMPTS]

    b = make_backend()
    loop = b.start_slot_loop(4, config=gen)
    outs: dict[int, str] = {}
    adm, rej = loop.admit([(i, PROMPTS[i], None) for i in (0, 1, 2)])
    # 3 joiners bucket to Bj=4, which fits the 4 free slots (the filler row
    # lands on the spare free slot and stays free)
    assert rej == [] and len(adm) == 3
    # rows decode; at each boundary refill whatever waits
    pending = [i for i in range(len(PROMPTS))
               if i not in {a.key for a in adm}]
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if pending and loop.free:
            adm, rej = loop.admit([(i, PROMPTS[i], None) for i in pending])
            assert rej == []
            for a in adm:
                pending.remove(a.key)
        if not pending and loop.active == 0:
            break
    assert loop.active == 0 and not pending
    assert [outs[i] for i in range(len(PROMPTS))] == solo
    # raggedness really happened: termination depths differ
    assert len({len(s) for s in solo}) > 1
    # and the loop really refilled (more admissions than one batch's worth)
    assert loop.refills == len(PROMPTS)


def test_slots_at_different_depths_decode_together():
    """A late joiner decodes next to residents that are several segments
    deep — its output must equal its solo run (per-row budgets, per-row
    masks)."""
    b = make_backend()
    solo = make_backend().generate([PROMPTS[3]])[0]
    loop = b.start_slot_loop(4)
    loop.admit([(0, PROMPTS[0], None), (1, PROMPTS[2], None)])
    loop.step()
    loop.step()  # residents now ~8 tokens deep
    adm, _ = loop.admit([(3, PROMPTS[3], None)])
    assert len(adm) == 1
    outs: dict[int, str] = {}
    drain(loop, outs)
    assert outs[3] == solo


# -- sampled-stream stability ------------------------------------------------


def test_sampled_stream_independent_of_join_timing_and_companions():
    """Same loop seed + same request uid => identical sampled stream, even
    when the request joins at a different segment, into a different slot,
    next to different companions. Streams key on (loop seed, uid, row-local
    t), so none of those may matter."""
    gen = GenerationConfig(temperature=1.0, seed=7, max_new_tokens=24)
    target = PROMPTS[2]

    # scenario A: target admitted together with a companion (uid 1, slot 1)
    a = make_backend()
    loop_a = a.start_slot_loop(4, config=gen)
    loop_a.admit([(0, PROMPTS[0], None), ("t", target, None)])
    outs_a: dict = {}
    drain(loop_a, outs_a)

    # scenario B: different companion admitted first and decoded 2 segments
    # deep; target joins mid-flight (still uid 1, different slot history)
    b = make_backend()
    loop_b = b.start_slot_loop(4, config=gen)
    loop_b.admit([(0, PROMPTS[4], None)])
    loop_b.step()
    loop_b.step()
    adm, _ = loop_b.admit([("t", target, None)])
    assert len(adm) == 1
    outs_b: dict = {}
    drain(loop_b, outs_b)

    assert outs_a["t"] == outs_b["t"]
    # the companions differed, so this was not a trivially identical run
    assert outs_a[0] != "" or outs_b[0] != ""


def test_the_key_rule_is_one_function():
    """A one-shot row and the same request joined alone into a slot loop,
    sampled at temperature 1 under the same seed and uid, draw the same
    tokens: prefill, the one-shot decode and the slot segment take their
    keys from one rule (engine._stream_keys: seed, uid, the token's index
    and nothing else)."""
    gen = GenerationConfig(temperature=1.0, seed=7, max_new_tokens=24)
    one_shot = make_backend().generate([PROMPTS[2]], config=gen)[0]
    assert one_shot   # row 0 of the first dispatch: seed fold 0, uid 0

    loop = make_backend().start_slot_loop(4, config=gen)   # seed fold 0
    loop.admit([(0, PROMPTS[2], None)])                    # uid 0
    outs: dict = {}
    drain(loop, outs)
    assert outs[0] == one_shot


# -- prefix-cache interaction ------------------------------------------------


def test_refill_resumes_from_prefix_cache_under_eviction_churn():
    """Joiners resume prefill from the radix cache while LRU eviction
    churns the (tiny) block pool — outputs stay byte-identical to a
    cache-less backend's solo runs."""
    header = "tiêu đề chung của các tài liệu dài: "
    prompts = [header + f"nội dung {i} " * 3 for i in range(6)]
    solo_backend = make_backend()
    solo = [solo_backend.generate([p])[0] for p in prompts]

    b = make_backend(cache_blocks=6, cache_block_tokens=16)
    loop = b.start_slot_loop(4)
    outs: dict[int, str] = {}
    pending = list(range(len(prompts)))
    adm, _ = loop.admit([(i, prompts[i], header) for i in pending[:2]])
    for a in adm:
        pending.remove(a.key)
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            outs[c.key] = c.text
        if pending and loop.free:
            adm, rej = loop.admit(
                [(i, prompts[i], header) for i in pending]
            )
            assert rej == []
            for a in adm:
                pending.remove(a.key)
        if not pending and loop.active == 0:
            break
    assert [outs[i] for i in range(len(prompts))] == solo
    # the pool really churned: insertions exceeded the budget
    st = b.prefix_cache.stats_dict()
    assert st["evictions"] > 0 or st["blocks_used"] <= 6


# -- preemption (serve/qos.py priority tiers) --------------------------------


def test_evict_frees_slots_and_readmit_is_byte_identical():
    """Mid-decode eviction on the REAL loop: the victim's slot frees at the
    next segment, survivors are unaffected, and re-admitting the evicted
    prompt restarts it to a byte-identical greedy output — the preemption
    round-trip losslessness claim on real engine state."""
    b = make_backend()
    solo = [make_backend().generate([p])[0] for p in PROMPTS[:2]]
    loop = b.start_slot_loop(2)
    adm, _ = loop.admit([(i, PROMPTS[i], None) for i in (0, 1)])
    assert len(adm) == 2
    loop.step()  # a couple of segments of real decode progress
    loop.step()
    victim = adm[0].key
    evs = loop.evict([victim])
    assert [e.key for e in evs] == [victim]
    assert loop.free == 1 and victim not in loop.outstanding()
    outs: dict[int, str] = {}
    drain(loop, outs)                       # survivor finishes undisturbed
    assert outs[1] == solo[1]
    adm2, _ = loop.admit([(0, PROMPTS[0], None)])  # the requeue's re-admit
    assert len(adm2) == 1
    drain(loop, outs)
    assert outs[0] == solo[0]


def test_evict_pins_prefix_blocks_until_released():
    """Eviction with the radix cache armed returns a live pin: the
    victim's cached prefix is unevictable until the scheduler-side release
    — and releasing restores the pre-eviction pin level."""
    header = "tiêu đề chung: "
    b = make_backend(cache_blocks=8, cache_block_tokens=16)
    loop = b.start_slot_loop(2)
    adm, rej = loop.admit([(0, header + "nội dung một hai", header)])
    assert len(adm) == 1 and rej == []
    loop.step()
    assert b.prefix_cache.index.pinned_blocks == 0  # admit released its pins
    evs = loop.evict([adm[0].key])
    assert evs[0].pin is not None
    assert b.prefix_cache.index.pinned_blocks > 0   # held across eviction
    cache, match = evs[0].pin
    cache.release(match)
    assert b.prefix_cache.index.pinned_blocks == 0
    loop.close()


def test_partial_outputs_are_prefixes_of_the_final_text():
    """The streaming harvest: per-segment partial detok of a resident row
    extends monotonically into exactly the harvested completion text."""
    b = make_backend()
    loop = b.start_slot_loop(2)
    adm, _ = loop.admit([(0, PROMPTS[2], None)])
    assert len(adm) == 1
    key = adm[0].key
    snapshots = []
    final = {}
    for _ in range(64):
        res = loop.step()
        for c in res.completions:
            final[c.key] = c.text
        if loop.active:
            part = loop.partial_outputs([key])
            if part:
                snapshots.append(part[id(key)])
        if not loop.active:
            break
    assert final[0] == make_backend().generate([PROMPTS[2]])[0]
    grown = [s for s in snapshots if s]
    assert grown, "no partial text surfaced during decode"
    for a, bnext in zip(grown, grown[1:]):
        assert bnext.startswith(a)
    assert final[0].startswith(grown[-1])


# -- one segment a dispatch: the stop on the device, the boundary fetch -------


def test_segment_stops_on_device_when_every_row_is_done():
    """The segment's while_loop stops the moment every row is done: with a
    segment longer than the whole budget a single resident retires in ONE
    host round trip, having run only the steps its tokens took, and the
    loop counts that one segment."""
    solo = make_backend().generate([PROMPTS[2]])[0]
    b = make_backend(segment_tokens=64)   # max_new is 24
    loop = b.start_slot_loop(2)
    adm, _ = loop.admit([(0, PROMPTS[2], None)])
    assert len(adm) == 1
    res = loop.step()
    assert loop.active == 0 and loop.segments == 1
    assert [c.text for c in res.completions] == [solo]
    assert res.new_tokens == res.completions[0].gen_tokens <= 24
    loop.close()


def test_partial_outputs_ride_the_boundary_snapshot(monkeypatch):
    """Streaming partials are served from the out buffer that rode the
    segment's one coalesced done/t/out fetch: between a step and the next
    admit ``partial_outputs`` asks the device for nothing. An adopt rewrites
    out rows, so after an admit the snapshot is gone and the cold fallback
    fetches."""
    import jax

    b = make_backend()
    loop = b.start_slot_loop(2)
    adm, _ = loop.admit([(0, PROMPTS[2], None)])
    key = adm[0].key
    loop.step()
    assert loop.active and loop._out_snap is not None
    fetches = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: fetches.append(1) or real_get(x))
    warm = loop.partial_outputs([key])[id(key)]
    assert warm and not fetches
    loop.admit([(1, PROMPTS[1], None)])
    assert loop._out_snap is None
    fetches.clear()   # the admit's own done0 fetch
    assert loop.partial_outputs([key])[id(key)] == warm and fetches == [1]
    loop.close()


# -- slot bookkeeping --------------------------------------------------------


def test_oversized_prompt_rejected_for_oneshot_fallback():
    b = make_backend()
    loop = b.start_slot_loop(2, prompt_tokens=64)
    assert loop.S == 64
    big = "x" * 200  # 200 byte tokens + bos > 64
    adm, rej = loop.admit([("big", big, None), ("ok", "nhỏ", None)])
    assert rej == ["big"]
    assert [a.key for a in adm] == ["ok"]
    outs: dict = {}
    drain(loop, outs)
    assert outs["ok"] == make_backend().generate(["nhỏ"])[0]


def test_join_bucket_never_exceeds_free_slots():
    b = make_backend()
    loop = b.start_slot_loop(4)
    loop.admit([(0, PROMPTS[0], None)])     # 1 busy, 3 free
    adm, _ = loop.admit([(i, PROMPTS[i], None) for i in (1, 2, 3)])
    # 3 joiners bucket to Bj=4 > 3 free -> clamped to a clean power of two
    assert len(adm) == 2 and loop.free == 1
    adm2, _ = loop.admit([(3, PROMPTS[3], None)])
    assert len(adm2) == 1 and loop.free == 0
    outs: dict = {}
    drain(loop, outs)
    assert set(outs) == {0, 1, 2, 3}


def test_closed_loop_refuses_work():
    b = make_backend()
    loop = b.start_slot_loop(2)
    loop.close()
    with pytest.raises(RuntimeError, match="closed"):
        loop.admit([(0, PROMPTS[0], None)])
    with pytest.raises(RuntimeError, match="closed"):
        loop.step()


def test_slot_count_must_divide_mesh_data_axis():
    """The resident batch rows shard over `data`, so a slot count the axis
    does not divide is a config error at loop construction, not an XLA
    divisibility failure mid-serve."""
    b = make_backend()

    class FakeMesh:  # engine only reads .shape before building the loop
        shape = {"data": 3}

    b.mesh = FakeMesh()
    with pytest.raises(ValueError, match="divisible by the mesh data axis"):
        b.start_slot_loop(4)


# -- a row's text ends at its cursor, not at its first pad id ---------------


@pytest.mark.parametrize(
    "row, t, text, counted",
    [
        # pad is an id the sampler can draw where the tokenizer's pad sits
        # inside the decodable range (a trained BPE's id 0): drawn FIRST it
        # used to empty the whole answer, drawn later to cut it short
        ([0, 12, 401, 454, 0, 0, 0, 0], 4, "12 401 454", 3),
        ([12, 0, 401, 454, 7, 0, 0, 0], 5, "12 401 454 7", 4),
        # a terminator still ends the text, and is not part of it
        ([12, 401, 2, 9, 0, 0, 0, 0], 4, "12 401", 4),
        ([12, 401, 99, 9, 0, 0, 0, 0], 4, "12 401", 4),
        # nothing emitted yet: nothing, whatever the buffer holds
        ([0, 0, 0, 0, 0, 0, 0, 0], 0, "", 0),
    ],
)
def test_row_text_is_cut_at_the_cursor_not_at_a_drawn_pad(row, t, text,
                                                         counted):
    from types import SimpleNamespace

    from vnsum_tpu.backend.inflight import TpuSlotLoop

    class Tok:
        pad_id, bos_id, eos_id = 0, 1, 2

        def decode(self, ids, skip_special_tokens=True):
            return " ".join(str(i) for i in ids if i > 2)

    stats = SimpleNamespace(generated_tokens=0)
    loop = SimpleNamespace(
        backend=SimpleNamespace(tok=Tok(), stats=stats),
        gen=SimpleNamespace(eos_ids=(99,)),
    )
    got = TpuSlotLoop._row_text(loop, np.asarray(row, np.int32), t)
    assert got == text
    assert stats.generated_tokens == counted
