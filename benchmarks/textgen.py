"""Seeded traffic: Vietnamese-like text, and fixed multisets in seeded order.

The language (its syllables and how often each occurs) is fixed; a seed
chooses the text. A traffic file fixes the multiset of sizes; a seed only
permutes it, so every seed gives the same work in another order.
"""
from __future__ import annotations

import itertools
import random
import unicodedata

_ONSETS = ("", "b", "c", "ch", "d", "đ", "g", "gi", "h", "k", "kh", "l", "m",
           "n", "ng", "nh", "ph", "qu", "r", "s", "t", "th", "tr", "v", "x")
_RHYMES = ("a", "an", "ang", "anh", "ao", "ai", "am", "at", "ay", "e", "en",
           "eo", "em", "ê", "ên", "ênh", "êu", "i", "in", "inh", "iên", "iêu",
           "o", "on", "ong", "oi", "ô", "ôn", "ông", "ôi", "ơ", "ơn", "ơi",
           "u", "un", "ung", "ui", "ua", "uôn", "uông", "ư", "ưng", "ưa",
           "ươn", "ương", "ươi", "uy", "uyên", "oa", "oan")
_TONES = ("", "̀", "́", "̃", "̉", "̣")
_VOWELS = set("aeiouyêôơưăâ")
LANGUAGE_SEED = 20240924   # the language is the same for every run
N_WORDS = 6000


def _syllable(onset: str, rhyme: str, tone: str) -> str:
    # the tone mark sits on the rhyme's last vowel that is not a glide end
    idx = [i for i, ch in enumerate(rhyme) if ch in _VOWELS]
    at = idx[-2] if len(idx) > 1 and rhyme[-1] in _VOWELS else idx[-1]
    marked = rhyme[:at + 1] + tone + rhyme[at + 1:]
    return unicodedata.normalize("NFC", onset + marked)


def language() -> tuple[list[str], list[float]]:
    """The word list and its cumulative Zipf weights."""
    rng = random.Random(LANGUAGE_SEED)
    every = [_syllable(o, r, t) for o, r, t in
             itertools.product(_ONSETS, _RHYMES, _TONES)]
    words = rng.sample(every, N_WORDS)
    weights = list(itertools.accumulate(1.0 / (i + 3) for i in range(N_WORDS)))
    return words, weights


class TextGen:
    """Paragraphs of seeded text; ``words`` counts whitespace tokens."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.words, self.cum = language()

    def sentence(self) -> str:
        n = self.rng.randint(8, 24)
        ws = self.rng.choices(self.words, cum_weights=self.cum, k=n)
        if n > 14:
            ws[n // 2] += ","
        ws[0] = ws[0].capitalize()
        return " ".join(ws) + "."

    def paragraph(self) -> str:
        return " ".join(self.sentence()
                        for _ in range(self.rng.randint(3, 7)))

    def paragraphs(self, words: int) -> list[str]:
        """Paragraphs until at least ``words`` whitespace tokens; every
        eighth is preceded by a section header."""
        out, have = [], 0
        while have < words:
            if len(out) % 9 == 0:
                out.append("Phần " + " ".join(self.rng.choices(
                    self.words, cum_weights=self.cum, k=4)))
            p = self.paragraph()
            out.append(p)
            have += p.count(" ") + 1
        return out

    def text_of_bytes(self, n_bytes: int) -> str:
        """Text cut to ``n_bytes`` of UTF-8 (a byte tokenizer's tokens)."""
        raw = "\n\n".join(self.paragraphs(n_bytes // 4 + 40)).encode()
        return raw[:n_bytes].decode("utf-8", "ignore")


    def text_of_tokens(self, target: int, count, tokens_per_word: float) -> str:
        """Paragraphs of at most ``target`` tokens (each paragraph break
        counted as one), by ``count(list of texts) -> token counts``."""
        ps = self.paragraphs(int(target / tokens_per_word * 1.08) + 200)
        return cut_to_tokens(ps, [c + 1 for c in count(ps)], target)


def cut_to_tokens(paragraphs: list[str], counts: list[int],
                  target: int) -> str:
    """The leading paragraphs whose token counts sum to ``target`` at most
    (one paragraph at least), joined as a document."""
    total, keep = 0, 0
    for c in counts:
        if keep and total + c > target:
            break
        total += c
        keep += 1
    return "\n\n".join(paragraphs[:keep])


def permuted(items: list, seed: int, cycle: int = 0) -> list:
    """The same multiset for every seed, in the seed's order."""
    out = list(items)
    random.Random(f"{seed}/{cycle}").shuffle(out)
    return out


def permuted_blocks(blocks: list[list], seed: int, cycle: int = 0) -> list:
    """Blocks in the seed's order, each block's items in the seed's order,
    flattened: every run of ``len(block)`` items keeps the mix's proportions,
    so a window that ends anywhere has seen nearly the same work."""
    out: list = []
    for i, block in enumerate(permuted(blocks, seed, cycle)):
        out += permuted(block, seed, cycle * 1000 + i + 1)
    return out


def fold_seed(seed: int) -> int:
    """Any whole number, folded into what an int32 program argument and
    ``jax.random.key`` take (the driver's seeds pass 2**31)."""
    return (int(seed) ^ (int(seed) >> 31)) & 0x7FFFFFFF
