"""Continuous scheduling (segmented decode + tail compaction).

Greedy parity: each row's token stream depends only on its own cache, so
the continuous path must produce byte-identical output to the one-shot
while_loop path, including when compaction rebatches mid-generation."""
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models import tiny_llama


def make_backend(continuous, **kw):
    if not kw.get("interpret"):
        kw.setdefault("flash", False)  # off-chip: the dense path, by name
    return TpuBackend(
        model_config=tiny_llama(max_seq_len=128),
        tokenizer="byte",
        batch_size=8,
        max_new_tokens=24,
        seed=1,
        continuous=continuous,
        **kw,
    )


PROMPTS = [
    "văn bản một về kinh tế",
    "hai",
    "văn bản thứ ba dài hơn một chút về xã hội",
    "bốn bốn",
    "năm năm năm",
    "sáu",
]


def test_continuous_matches_oneshot_greedy():
    plain = make_backend(False)
    cont = make_backend(True, segment_tokens=4, min_batch=1)
    np.testing.assert_array_equal(
        plain.generate(PROMPTS), cont.generate(PROMPTS)
    )


def test_continuous_with_ragged_eos_and_compaction():
    """Force ragged termination by declaring a COMMON token as EOS: rows
    finish at different steps, compaction must fire, and outputs still
    match the one-shot path exactly."""
    # find a token that actually appears early in greedy rollouts
    probe = make_backend(False)
    outs = probe.generate(PROMPTS)
    tok = probe.tok
    ids = [tok.encode(o, add_bos=False) for o in outs if o]
    assert ids, "probe produced no output; pick a different seed"
    # a token from the middle of the longest rollout => some rows hit it
    # early, others late or never
    longest = max(ids, key=len)
    eos_extra = longest[len(longest) // 2]
    gen = GenerationConfig(eos_ids=(tok.eos_id, eos_extra), max_new_tokens=24)

    plain = make_backend(False)
    cont = make_backend(True, segment_tokens=4, min_batch=1)
    a = plain.generate(PROMPTS, config=gen)
    b = cont.generate(PROMPTS, config=gen)
    np.testing.assert_array_equal(a, b)
    # raggedness check: termination steps must differ across rows
    lens = {len(x) for x in a}
    assert len(lens) > 1, a


def test_compaction_fires_and_is_counted():
    probe = make_backend(False)
    outs = probe.generate(PROMPTS)
    tok = probe.tok
    longest = max(
        (tok.encode(o, add_bos=False) for o in outs if o), key=len
    )
    gen = GenerationConfig(
        eos_ids=(tok.eos_id, longest[len(longest) // 2]), max_new_tokens=24
    )
    cont = make_backend(True, segment_tokens=2, min_batch=1)
    cont.generate(PROMPTS, config=gen)
    assert cont.stats.compactions >= 1


def test_continuous_single_prompt():
    cont = make_backend(True, segment_tokens=4, min_batch=1)
    plain = make_backend(False)
    np.testing.assert_array_equal(
        plain.generate(["một văn bản"]), cont.generate(["một văn bản"])
    )


def test_continuous_auto_policy_is_oneshot():
    """continuous='auto' resolves to the one-shot program: the measured A/B
    (PERF.md finding 13, artifacts/compaction_ab.json) shows the segmented
    path losing token-normalized at every tested shape. Explicit True still
    enables it."""
    auto = TpuBackend(
        model_config=tiny_llama(max_seq_len=128), batch_size=32,
        max_new_tokens=8,
        flash=False,
    )
    assert auto.continuous is False
    forced = TpuBackend(
        model_config=tiny_llama(max_seq_len=128), batch_size=4,
        max_new_tokens=8, continuous=True,
        flash=False,
    )
    assert forced.continuous is True


def test_sampled_continuous_matches_oneshot():
    """Sampled decode is compaction-safe since round 3: each row's stream is
    keyed by (seed, row uid, step) — counter-based, independent of batch
    position — so the segmented path with tail compaction must reproduce the
    one-shot sampled output bit-exactly."""
    gen = GenerationConfig(temperature=0.8, max_new_tokens=24, seed=5)
    plain = make_backend(False)
    a = plain.generate(PROMPTS, config=gen)
    cont = make_backend(True, segment_tokens=4, min_batch=1)
    b = cont.generate(PROMPTS, config=gen)
    np.testing.assert_array_equal(a, b)
    assert cont._seg_fns  # the segmented path actually ran


def test_sampled_compaction_fires_and_matches():
    """Force ragged sampled termination so compaction fires mid-stream, and
    check outputs still match the one-shot program. Sampled streams are
    counter-based — a same-seed rerun replays the probe's streams exactly —
    so declaring ids observed EARLY in most probe rows as EOS pins most
    rows' termination points near the start, guaranteeing the live set
    shrinks below the compaction threshold well before the budget."""
    gen0 = GenerationConfig(temperature=0.9, max_new_tokens=24, seed=3)
    probe = make_backend(False)
    outs = probe.generate(PROMPTS, config=gen0)
    tok = probe.tok
    ids = [tok.encode(o, add_bos=False) for o in outs if len(o) > 4]
    assert len(ids) >= 4, outs
    # position-4 byte of four rows => those rows stop by step ~5 in the
    # replay, leaving <= 2 rows live for the rest of the 24-token budget
    eos_extra = {row[4] for row in ids[:4]}
    gen = gen0.with_(eos_ids=(tok.eos_id, *sorted(eos_extra)))

    plain = make_backend(False)
    a = plain.generate(PROMPTS, config=gen)
    cont = make_backend(True, segment_tokens=2, min_batch=1)
    b = cont.generate(PROMPTS, config=gen)
    np.testing.assert_array_equal(a, b)
    assert cont.stats.compactions >= 1
    assert len({len(x) for x in a}) > 1, a
