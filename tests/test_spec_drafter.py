"""n-gram reference drafter (vnsum_tpu.spec.drafter) on Vietnamese text:
syllable-heavy inputs with diacritics, no-match rows, draft-length clamping
at the reference end, and jnp/host implementation equivalence.

Fast tier: pure array ops, no model compiles.
"""
import numpy as np
import pytest

from vnsum_tpu.spec import (
    NO_TOKEN,
    encode_references,
    history_tail,
    propose_drafts,
    propose_drafts_host,
)
from vnsum_tpu.text.tokenizer import get_tokenizer


def _pack(rows, fill=NO_TOKEN):
    R = max(len(r) for r in rows)
    out = np.full((len(rows), R), fill, dtype=np.int32)
    lens = np.zeros((len(rows),), dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
        lens[i] = len(r)
    return out, lens


def _tail(rows, n):
    out = np.full((len(rows), n), NO_TOKEN, dtype=np.int32)
    for i, r in enumerate(rows):
        take = r[-n:]
        out[i, n - len(take):] = take
    return out


def test_vietnamese_syllables_draft_the_continuation():
    """A diacritic-heavy Vietnamese sentence encodes to multi-byte UTF-8
    sequences; matching the emitted suffix must propose the exact byte
    continuation from the reference."""
    tok = get_tokenizer("byte")
    text = "Quốc hội đã thông qua nghị quyết về phát triển kinh tế xã hội."
    ids = tok.encode(text, add_bos=False)
    assert len(ids) > len(text)  # diacritics: multi-byte syllables

    ref, lens = _pack([ids])
    # emitted stream so far = the first 12 reference tokens; the 8-byte
    # suffix "c hội" occurs once, so the match is unambiguous ("hội" alone
    # also ends the sentence — a shorter tail would legitimately draft from
    # the LATER occurrence under the tie-break rule)
    tail = _tail([ids[:12]], 8)
    drafts, n = propose_drafts(ref, lens, tail, 8)
    drafts, n = np.asarray(drafts), np.asarray(n)
    assert n[0] == 8
    np.testing.assert_array_equal(drafts[0], ids[12:20])

    # the repeated-syllable case: a short tail ending at "hội" prefers the
    # sentence-final occurrence, whose continuation is the closing "."
    tail_short = _tail([ids[:12]], 4)
    drafts_s, n_s = propose_drafts(ref, lens, tail_short, 8)
    assert int(np.asarray(n_s)[0]) == 1
    assert bytes([int(np.asarray(drafts_s)[0, 0])]) == b"."


def test_no_match_and_no_reference_rows_propose_nothing():
    tok = get_tokenizer("byte")
    ids = tok.encode("văn bản nguồn về kinh tế", add_bos=False)
    ref, lens = _pack([ids, ids])
    lens[1] = 0  # row 1: no reference at all (ref tokens present but dead)
    # row 0's tail shares no byte with the reference
    tail = np.full((2, 3), NO_TOKEN, dtype=np.int32)
    tail[0] = [1, 2, 3]
    tail[1, -1] = ids[0]
    drafts, n = propose_drafts(ref, lens, tail, 4)
    assert np.asarray(n).tolist() == [0, 0]
    assert np.asarray(drafts).sum() == 0


def test_draft_length_clamps_at_reference_end():
    """A match near the end proposes only what remains; a match AT the end
    proposes nothing (no continuation exists)."""
    ref, lens = _pack([[10, 11, 12, 13, 14], [20, 21, 22]])
    tail = _tail([[12, 13], [21, 22]], 2)
    drafts, n = propose_drafts(ref, lens, tail, 4)
    drafts, n = np.asarray(drafts), np.asarray(n)
    assert n[0] == 1  # only token 14 remains after ..12,13
    assert drafts[0, 0] == 14
    assert n[1] == 0  # ..21,22 ends the reference


def test_longest_match_beats_shorter_and_later_position_breaks_ties():
    # token 5 appears twice; the 3-gram [7, 8, 5] appears once — the longer
    # match must win even though a later bare 5 exists
    ref, lens = _pack([[7, 8, 5, 30, 31, 9, 5, 40, 41]])
    tail = _tail([[7, 8, 5]], 3)
    drafts, n = propose_drafts(ref, lens, tail, 2)
    np.testing.assert_array_equal(np.asarray(drafts)[0], [30, 31])
    # a pure 1-gram tail of 5 matches both occurrences: the LATER one wins
    tail1 = _tail([[5]], 3)
    drafts1, n1 = propose_drafts(ref, lens, tail1, 2)
    np.testing.assert_array_equal(np.asarray(drafts1)[0], [40, 41])


def test_jnp_and_host_drafters_agree_on_random_cases():
    import jax

    # one program a trial's shapes, as the verify step runs it (under jit),
    # where op by op every trial compiles each of its primitives anew
    propose_jitted = jax.jit(propose_drafts, static_argnums=3)
    rng = np.random.default_rng(7)
    for trial in range(20):
        B = int(rng.integers(1, 5))
        R = int(rng.integers(4, 40))
        N = int(rng.integers(1, 5))
        k = int(rng.integers(1, 6))
        ref = rng.integers(0, 6, size=(B, R)).astype(np.int32)
        lens = rng.integers(0, R + 1, size=(B,)).astype(np.int32)
        tail = rng.integers(0, 6, size=(B, N)).astype(np.int32)
        # sprinkle NO_TOKEN padding into some tails (short histories)
        for b in range(B):
            cut = int(rng.integers(0, N))
            tail[b, :cut] = NO_TOKEN
        dj, nj = propose_jitted(ref, lens, tail, k)
        dh, nh = propose_drafts_host(ref, lens, tail, k)
        np.testing.assert_array_equal(np.asarray(nj), nh, err_msg=f"trial {trial}")
        np.testing.assert_array_equal(np.asarray(dj), dh, err_msg=f"trial {trial}")


def test_encode_references_truncates_and_handles_none():
    tok = get_tokenizer("byte")
    long = "tài liệu " * 100
    ref, lens = encode_references(tok, [long, None, "ngắn"], max_ref_tokens=64)
    assert ref.shape[1] == 64
    assert lens[0] == 64
    assert lens[1] == 0
    assert (ref[1] == NO_TOKEN).all()
    assert lens[2] == len(tok.encode("ngắn", add_bos=False))


def test_history_tail_pads_short_streams():
    out = np.array([[1, 2, 3, 0], [7, 0, 0, 0]], dtype=np.int32)
    tail = history_tail(out, np.array([3, 1]), np.array([9, 5]), 3)
    np.testing.assert_array_equal(tail, [[2, 3, 9], [NO_TOKEN, 7, 5]])


def test_drafted_tokens_never_include_reference_padding():
    """Drafts past n_draft are 0-filled, never NO_TOKEN — they must stay
    feedable to a forward pass as inert filler."""
    ref, lens = _pack([[3, 4]])
    tail = _tail([[3]], 2)
    drafts, n = propose_drafts(ref, lens, tail, 6)
    drafts = np.asarray(drafts)
    assert int(np.asarray(n)[0]) == 1
    assert (drafts[0, 1:] == 0).all()
    assert (drafts >= 0).all()
