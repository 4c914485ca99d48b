"""Map-reduce strategy.

Semantics follow runners/run_summarization_ollama_mapreduce.py:75-201: split →
map each chunk → collapse groups while the whitespace-token total exceeds
token_max → one final reduce. The LangGraph Send fan-out (serial in practice,
:51-52) becomes true batching: the map step for a *batch of documents* is one
backend.generate call, and each collapse round batches every group of every
document still collapsing.
"""
from __future__ import annotations

from typing import Callable

from ..backend.base import Backend
from ..text.splitter import RecursiveTokenSplitter
from ..text.tokenizer import whitespace_token_count
from .base import (
    StrategyResult,
    _BatchCounter,
    register_strategy,
    split_by_token_budget,
    strategy_span,
)
from .prompts import MAPREDUCE_MAP, MAPREDUCE_REDUCE, template_header


@register_strategy
class MapReduceStrategy:
    name = "mapreduce"

    def __init__(
        self,
        backend: Backend,
        splitter: RecursiveTokenSplitter,
        token_max: int = 10000,
        max_new_tokens: int | None = None,
        max_collapse_rounds: int = 10,
        count: Callable[[str], int] = whitespace_token_count,
        map_prompt: str = MAPREDUCE_MAP,
        reduce_prompt: str = MAPREDUCE_REDUCE,
    ) -> None:
        self.backend = backend
        self.splitter = splitter
        self.token_max = token_max
        self.max_new_tokens = max_new_tokens
        # collapse backstop, like the reference's recursion_limit=10 (:196)
        self.max_collapse_rounds = max_collapse_rounds
        self.count = count
        self.map_prompt = map_prompt
        self.reduce_prompt = reduce_prompt

    @classmethod
    def from_config(cls, backend: Backend, config, **kw):
        splitter = RecursiveTokenSplitter(
            config.chunk_size, config.chunk_overlap,
            length_function=backend.count_tokens,
            # duck-typed backends without the batch method keep working via
            # the splitter's scalar fallback
            length_batch_function=getattr(
                backend, "count_tokens_batch", None
            ),
        )
        return cls(
            backend, splitter, token_max=config.token_max,
            max_new_tokens=config.max_new_tokens, **kw,
        )

    def _reduce_one(self, texts: list[str]) -> str:
        return self.reduce_prompt.format(docs="\n\n".join(texts))

    def summarize_batch(
        self, docs: list[str], *, backend: Backend | None = None
    ) -> list[StrategyResult]:
        be = backend or self.backend
        if callable(getattr(be, "submit_round", None)) and callable(
            getattr(be, "harvest", None)
        ):
            # serving path: the backend exposes the non-blocking half of
            # generate, so the map->reduce barrier dissolves into an
            # ordered completion stream
            return self._summarize_batch_streaming(docs, be)
        gen = _BatchCounter(be, self.max_new_tokens)

        with strategy_span(self, "split", docs=len(docs)):
            chunks_per_doc = [self.splitter.split_text(d) or [d] for d in docs]
        results = [
            StrategyResult(summary="", num_chunks=len(c)) for c in chunks_per_doc
        ]

        # map: every chunk of every document in one batch. The chunk text
        # rides along as the speculation reference — a map summary is
        # largely extractive, exactly the overlap the reference drafter
        # (vnsum_tpu.spec) turns into accepted tokens — and the shared
        # template header is the cache_hint: every map prompt of every
        # document starts with it, so one prefilled header (vnsum_tpu.cache)
        # serves the whole fan-out
        with strategy_span(self, "map_prompts", docs=len(docs)):
            map_hint = template_header(self.map_prompt)
            flat = [
                (di, self.map_prompt.format(content=c), c)
                for di, chunks in enumerate(chunks_per_doc)
                for c in chunks
            ]
        outs = gen(
            [p for _, p, _ in flat],
            owners=[di for di, _, _ in flat],
            references=[c for _, _, c in flat],
            cache_hints=[map_hint] * len(flat),
        )
        summaries: list[list[str]] = [[] for _ in docs]
        for (di, _, _), out in zip(flat, outs):
            summaries[di].append(out)

        # collapse + final rounds, MERGED: a document whose summaries already
        # fit token_max submits its final reduce IN THE SAME BATCH as the
        # other documents' collapse groups (both use the same reduce
        # template), so late rounds ride full dispatches instead of a
        # trailing half-empty final round (VERDICT r4 weak #3 tail packing).
        # Prompt contents are identical to the sequential formulation — a
        # doc's final runs over exactly the summaries it would have ended
        # with — and outputs are batch-invariant in the engine, so this is
        # a pure scheduling change.
        final_texts: dict[int, str] = {}
        for round_no in range(self.max_collapse_rounds + 1):
            # the budget split and the formatting between two rounds of
            # generation: one span a round, whatever the documents
            with strategy_span(self, "reduce_prompts", round=round_no):
                over = [
                    di
                    for di, s in enumerate(summaries)
                    if di not in final_texts
                    and sum(self.count(x) for x in s) > self.token_max
                ]
                ready = [
                    di for di in range(len(docs))
                    if di not in final_texts and di not in over
                ]
                if round_no == self.max_collapse_rounds and over:
                    # collapse budget exhausted (ref recursion_limit=10, :196):
                    # force the final over whatever remains, as the sequential
                    # formulation did
                    ready += over
                    over = []
                batch: list[tuple[str, int, int]] = []
                prompts: list[str] = []
                refs: list[str] = []
                for di in ready:
                    batch.append(("final", di, 0))
                    prompts.append(self._reduce_one(summaries[di]))
                    # reduce output re-emits spans of the summaries it merges
                    refs.append("\n\n".join(summaries[di]))
                grouped: dict[int, list[list[str]]] = {}
                for di in over:
                    groups = split_by_token_budget(
                        summaries[di], self.token_max, self.count)
                    grouped[di] = groups
                    for gi, g in enumerate(groups):
                        batch.append(("collapse", di, gi))
                        prompts.append(self._reduce_one(g))
                        refs.append("\n\n".join(g))
            if not prompts:
                break
            outs = gen(
                prompts, owners=[di for _, di, _ in batch], references=refs,
                cache_hints=[template_header(self.reduce_prompt)] * len(prompts),
            )
            for di in over:
                summaries[di] = [None] * len(grouped[di])  # type: ignore[list-item]
            for (kind, di, gi), out in zip(batch, outs):
                if kind == "final":
                    final_texts[di] = out
                else:
                    summaries[di][gi] = out
            for di in over:
                results[di].rounds += 1

        for di, r in enumerate(results):
            r.summary = final_texts[di]
            r.llm_calls = gen.calls_by_owner.get(di, 0)
        return results

    def _summarize_batch_streaming(
        self, docs: list[str], be: Backend
    ) -> list[StrategyResult]:
        """Streaming map->reduce over a submit_round/harvest backend (the
        serving layer's QueuedBackend): a document's collapse/final reduce
        is submitted the moment its LAST map child completes, overlapping
        other documents' still-running maps instead of waiting out a global
        barrier. Prompt contents are byte-identical to the barrier
        formulation — each doc's reduce runs over exactly the summaries it
        would have ended with — and greedy decode is prompt-deterministic,
        so this is a pure scheduling change (the bench's gang phase pins
        byte-identity against the offline path).

        Degraded results: a MAP child failing typed POISON is dropped from
        its document's reduce (harvest marks the gang partial, so the
        parent aggregate folds to ``partial``); a REDUCE failure still
        fails the whole call — there is no summary to degrade to."""
        from concurrent.futures import FIRST_COMPLETED, wait

        with strategy_span(self, "split", docs=len(docs)):
            chunks_per_doc = [self.splitter.split_text(d) or [d] for d in docs]
        results = [
            StrategyResult(summary="", num_chunks=len(c)) for c in chunks_per_doc
        ]
        calls = [0] * len(docs)
        pending: dict = {}  # future -> ("map"|"collapse"|"final", di, idx)
        map_hint = template_header(self.map_prompt)
        reduce_hint = template_header(self.reduce_prompt)

        def submit(entries, phase, hint):
            futs = be.submit_round(
                [p for _, p, _ in entries],
                phase=phase,
                max_new_tokens=self.max_new_tokens,
                references=[r for _, _, r in entries],
                cache_hints=[hint] * len(entries),
            )
            for (tag, _, _), fut in zip(entries, futs):
                pending[fut] = tag
                calls[tag[1]] += 1

        # map: still ONE fan-out round across all docs (one gang-record
        # flush; affinity co-schedules the siblings) — only the JOIN is
        # per-document now
        summaries: list[list[str | None]] = [
            [None] * len(c) for c in chunks_per_doc
        ]
        maps_left = [len(c) for c in chunks_per_doc]
        parts_left = [0] * len(docs)
        rounds_done = [0] * len(docs)
        final_texts: dict[int, str] = {}
        with strategy_span(self, "map_prompts", docs=len(docs)):
            map_entries = [
                (("map", di, ci), self.map_prompt.format(content=c), c)
                for di, chunks in enumerate(chunks_per_doc)
                for ci, c in enumerate(chunks)
            ]
        submit(map_entries, "map", map_hint)

        def advance(di: int) -> None:
            # this doc's maps (or its current collapse round) all landed:
            # submit the next reduce stage immediately (one span a document
            # stage: the budget split and the formatting, not the submit)
            with strategy_span(self, "reduce_prompts", doc=di):
                texts = [s for s in summaries[di] if s is not None]
                final = (
                    sum(self.count(x) for x in texts) <= self.token_max
                    or rounds_done[di] >= self.max_collapse_rounds
                )
                if final:
                    entries = [(("final", di, 0), self._reduce_one(texts),
                                "\n\n".join(texts))]
                else:
                    groups = split_by_token_budget(
                        texts, self.token_max, self.count)
                    entries = [
                        (("collapse", di, gi), self._reduce_one(g),
                         "\n\n".join(g))
                        for gi, g in enumerate(groups)
                    ]
            if not final:
                summaries[di] = [None] * len(groups)
                parts_left[di] = len(groups)
            submit(entries, "reduce", reduce_hint)

        while pending:
            done, _ = wait(list(pending), return_when=FIRST_COMPLETED)
            for fut in done:
                kind, di, idx = pending.pop(fut)
                out = be.harvest(fut, tolerate_poison=(kind == "map"))
                if kind == "map":
                    maps_left[di] -= 1
                    if out is None:
                        results[di].meta["dropped_chunks"] = (
                            results[di].meta.get("dropped_chunks", 0) + 1
                        )
                    else:
                        summaries[di][idx] = out
                    if maps_left[di] == 0:
                        advance(di)
                elif kind == "collapse":
                    summaries[di][idx] = out
                    parts_left[di] -= 1
                    if parts_left[di] == 0:
                        rounds_done[di] += 1
                        results[di].rounds += 1
                        advance(di)
                else:
                    final_texts[di] = out

        for di, r in enumerate(results):
            r.summary = final_texts[di]
            r.llm_calls = calls[di]
        return results

    def summarize(self, doc: str, *, backend: Backend | None = None) -> StrategyResult:
        return self.summarize_batch([doc], backend=backend)[0]
