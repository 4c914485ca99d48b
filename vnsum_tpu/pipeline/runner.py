"""Batch evaluation pipeline.

Mirrors the reference PipelineRunner's flow (run_full_evaluation_pipeline.py:
120-947): preflight → document analysis → per-model summarization with
resume-by-file → per-model evaluation → report → structured results JSON —
with the reference's process boundaries removed: evaluation runs in-process
(no subprocess + stdout scraping, :649-784), and summarization submits
document batches to the strategy layer so all per-round LLM calls share
device batches.
"""
from __future__ import annotations

import contextlib
import os
import time
import traceback
from pathlib import Path

from ..backend.base import Backend, get_backend
from ..core.config import PipelineConfig
from ..core.faults import call_with_retries, is_retryable
from ..core.logging import get_logger, setup_run_logging
from ..core.profiling import Tracer, device_profile
from ..core.results import DocumentRecord, ModelRunRecord, PipelineResults
from ..data import DocumentDataset, analyze_documents
from ..eval import SemanticEvaluator
from ..strategies import get_strategy
from ..text import DocumentTree, clean_thinking_tokens

logger = get_logger("vnsum.pipeline")


def model_name_safe(model: str) -> str:
    """'llama3.2:3b' -> 'llama3_2_3b' (ref :170, :326)."""
    return model.replace(":", "_").replace(".", "_")


class PipelineRunner:
    def __init__(
        self,
        config: PipelineConfig,
        backend_factory=None,
        embedding_model=None,
        llm_judge=None,
    ) -> None:
        self.config = config
        self.backend_factory = backend_factory or self._default_backend_factory
        self.embedding_model = embedding_model
        # a prebuilt eval.LLMJudge (tests / artifact scripts inject tiny
        # local judges); None = resolve from EvalConfig in _build_llm_judge
        self.llm_judge = llm_judge
        self.results = PipelineResults(config=config.to_dict())
        self.tracer = Tracer()
        self._engine_record: dict | None = None
        self.log_path = setup_run_logging(config.logs_dir)
        logger.info("pipeline configured: approach=%s backend=%s models=%s",
                    config.approach, config.backend, config.models)
        # startup self-check, like the reference's cleaner sanity log (:193-197)
        if clean_thinking_tokens("<think>x</think>ok") != "ok":
            raise RuntimeError("thinking-token cleaner self-check failed")

    # -- backend -----------------------------------------------------------

    def _default_backend_factory(self, model: str, **engine_kw) -> Backend:
        """Build the configured backend for ``model``. ``engine_kw`` reaches
        the tpu engines' constructors only — off-chip callers use it to name
        the path they want (``flash=False``, ``interpret=True``,
        ``decode_kernel=False``), which the engines otherwise refuse."""
        cfg = self.config
        if cfg.backend == "ollama":
            return get_backend(
                "ollama", model=model, url=cfg.ollama_url,
                max_new_tokens=cfg.max_new_tokens,
            )
        if cfg.backend == "fake":
            return get_backend("fake")
        if cfg.backend == "hf":
            return get_backend(
                "hf", model_name_or_path=model,
                max_context=cfg.max_context,
                max_new_tokens=cfg.max_new_tokens,
            )
        if cfg.backend == "tpu":
            mesh = None
            if cfg.mesh_shape:
                from ..parallel import make_mesh

                mesh = make_mesh(dict(cfg.mesh_shape))
            model_cfg, params, tokenizer = self._resolve_model(model)
            if cfg.long_context:
                from ..backend.long_context import LongContextBackend

                return LongContextBackend(
                    model_config=model_cfg,
                    mesh=mesh,
                    tokenizer=tokenizer,
                    params=params,
                    batch_size=cfg.batch_size,
                    max_new_tokens=cfg.max_new_tokens,
                    # the truncated strategy cuts the DOCUMENT to
                    # max_context − max_new and then wraps it in a prompt
                    # template; give the backend headroom for that template
                    # so it never chops the closing instruction off a
                    # cap-length prompt
                    max_total_tokens=(
                        cfg.max_context + 1024
                        if cfg.approach == "truncated"
                        else None
                    ),
                    quantize=cfg.quantize,
                    # cfg.quantize alone promises weight-only (exact)
                    # quantization; the lossy int8 prefill cache needs its
                    # own explicit opt-in (--quantize-kv-long)
                    quantize_kv=cfg.long_context_quantize_kv,
                    **engine_kw,
                )
            return get_backend(
                "tpu",
                model_config=model_cfg,
                params=params,
                tokenizer=tokenizer,
                mesh=mesh,
                batch_size=cfg.batch_size,
                max_new_tokens=cfg.max_new_tokens,
                quantize=cfg.quantize,
                quantize_act=cfg.quantize_act,
                **engine_kw,
            )
        raise ValueError(f"unknown backend {cfg.backend!r}")

    def _resolve_model(self, model: str):
        """(model_config, params, tokenizer) for the tpu backends — ONE copy
        of the checkpoint-load / tokenizer-rewrite / registry-lookup rules.

        With weights_dir set, safetensors convert + the checkpoint's own
        tokenizer (quality-parity chain; reference loads HF checkpoints at
        runners/run_summarization.py:54-62); otherwise a registry config
        with random init (benchmarks, tests)."""
        cfg = self.config
        if cfg.weights_dir:
            import jax.numpy as jnp

            from ..models.convert import load_hf_checkpoint

            model_cfg, params = load_hf_checkpoint(
                cfg.weights_dir, dtype=getattr(jnp, cfg.dtype)
            )
            tokenizer = (
                cfg.tokenizer
                if cfg.tokenizer.startswith("hf:")
                else f"hf:{cfg.weights_dir}"
            )
            return model_cfg, params, tokenizer
        from ..models import MODEL_REGISTRY

        if model not in MODEL_REGISTRY:
            raise ValueError(
                f"unknown model {model!r} for tpu backend; "
                f"have {sorted(MODEL_REGISTRY)}"
            )
        return MODEL_REGISTRY[model](), None, cfg.tokenizer

    def preflight(self, backend: Backend) -> None:
        """Backend health check before any work (ref :199-233 checked the
        Ollama server + model availability)."""
        # .label carries wrapper decorations ("ollama+retry", "fake+faults")
        # that .name deliberately drops so the dispatch below still works
        logger.info("backend: %s", getattr(backend, "label", backend.name))
        if backend.name == "ollama":
            models = backend.health_check()
            logger.info("ollama reachable; models: %s", models)
        elif backend.name == "tpu":
            # the engines refuse a platform other than tpu at construction
            # unless the caller named an off-chip path; say which it is
            import jax

            d = jax.devices()
            logger.info(
                "jax devices: %d x %s (%s)",
                len(d), d[0].device_kind, d[0].platform,
            )

    # -- phases ------------------------------------------------------------

    def analyze(self) -> dict:
        cfg = self.config
        ds = DocumentDataset(cfg.docs_dir, cfg.summary_dir)
        stats = analyze_documents(
            ds, lambda t: len(t.split()), chunk_size=cfg.chunk_size,
            max_samples=cfg.max_samples,
        )
        d = stats.to_dict()
        d["per_document"] = d["per_document"][:1000]
        self.results.document_stats = d
        logger.info(
            "analyzed %d docs: %d tokens total, ~%.0f/doc",
            stats.total_documents, stats.total_tokens, stats.avg_tokens_per_doc,
        )
        return d

    def _output_dir(self, model: str) -> Path:
        # ref naming: <generated_summaries_dir>_<approach>_<model_safe> (:408)
        return Path(
            f"{self.config.generated_summaries_dir}_"
            f"{self.config.approach}_{model_name_safe(model)}"
        )

    def run_summarization_for_model(self, model: str) -> ModelRunRecord:
        cfg = self.config
        record = ModelRunRecord(model=model, approach=cfg.approach)
        t_start = time.time()

        # host spans (core.profiling): each names the idle gap of the device
        # it covers in a profiler trace — `pipeline/setup`, `pipeline/read`,
        # `pipeline/write` here, `strategy/*` in the strategy, under the
        # parents `pipeline/batch` and `pipeline/summarize`
        with self.tracer.span("setup"):
            backend = self.backend_factory(model)
            self.preflight(backend)
            strategy_kw = {}
            if cfg.approach == "truncated" and getattr(backend, "tok", None) is not None:
                # the truncated cut must count tokens with the backend's OWN
                # tokenizer — weights_dir/long-context runs rewrite it to the
                # checkpoint's HF tokenizer, and a byte-token cut there would
                # over-truncate ~4x
                strategy_kw["tokenizer"] = backend.tok
            strategy = get_strategy(
                cfg.approach, backend, cfg, tracer=self.tracer, **strategy_kw)

            ds = DocumentDataset(cfg.docs_dir, cfg.summary_dir)
            out_dir = self._output_dir(model)
            out_dir.mkdir(parents=True, exist_ok=True)

            tree = None
            if cfg.approach == "mapreduce_hierarchical":
                tree_path = Path(cfg.tree_json_path)
                if tree_path.is_file():
                    tree = DocumentTree.load(tree_path)
                else:
                    logger.warning(
                        "tree JSON %s missing; hierarchical will wrap plain text",
                        tree_path,
                    )

            names = ds.filenames(cfg.max_samples)
            pending: list[str] = []
            for name in names:
                gen_path = out_dir / name
                if gen_path.is_file():  # resume-by-file (ref :422-431)
                    logger.info("  %s: already exists, skipping", name)
                    continue
                if self.config.summary_dir and not ds.has_reference(name):
                    logger.warning("  %s: no reference summary, skipping", name)
                    continue
                pending.append(name)

        logger.info(
            "model %s: %d docs pending (%d total)", model, len(pending), len(names)
        )

        # submit documents in batches; each batch's map/collapse rounds share
        # device batches inside the strategy. Groups default to 4x the engine
        # batch so collapse/reduce rounds still fill whole dispatches
        group_size = cfg.doc_group_size or 4 * max(cfg.batch_size, 1)
        for start in range(0, len(pending), group_size):
            group = pending[start : start + group_size]
            batch_t0 = time.time()
            # profiler windows must stay short: capture the first batch only.
            # cms are built inside run_batch so a retry gets fresh instances
            # (a generator-backed cm cannot be re-entered)
            make_profile_cm = (
                device_profile if start == 0 else contextlib.nullcontext
            )

            def run_batch():
                with self.tracer.span("batch"), make_profile_cm():
                    if cfg.approach == "mapreduce_hierarchical" and tree is not None:
                        roots, docs_fallback = [], []
                        for name in group:
                            node = tree.get(name)
                            if node is None:
                                docs_fallback.append(name)
                            roots.append((name, node))
                        results = []
                        tree_items = [(n, r) for n, r in roots if r is not None]
                        if tree_items:
                            tree_results = strategy.summarize_tree_batch(
                                [r for _, r in tree_items]
                            )
                            results.extend(
                                zip([n for n, _ in tree_items], tree_results)
                            )
                        if docs_fallback:
                            with self.tracer.span("read", docs=len(docs_fallback)):
                                texts = [ds.read_doc(n) for n in docs_fallback]
                            results.extend(
                                zip(docs_fallback, strategy.summarize_batch(texts))
                            )
                        return results
                    with self.tracer.span("read", docs=len(group)):
                        texts = [ds.read_doc(n) for n in group]
                    return list(zip(group, strategy.summarize_batch(texts)))

            try:
                results = call_with_retries(
                    run_batch,
                    max_retries=cfg.max_batch_retries,
                    backoff=cfg.retry_backoff,
                    # deterministic host-side bugs fail fast; re-running a
                    # multi-minute device batch can't fix a TypeError
                    should_retry=is_retryable,
                    what=f"batch of {len(group)} docs",
                )
            except Exception as e:
                logger.error("batch failed (%s): %s", group, e)
                logger.debug("%s", traceback.format_exc())
                for name in group:
                    record.failed += 1
                    record.total_documents += 1
                    record.processing_details.append(
                        DocumentRecord(
                            name, 0, time.time() - batch_t0, 0,
                            status="failed", error=str(e),
                        )
                    )
                continue

            batch_time = time.time() - batch_t0
            # wall time is amortized (record.time_basis); chunk/call counts
            # are true per-document values from the strategy
            per_doc_time = batch_time / max(len(results), 1)
            with self.tracer.span("write", docs=len(results)):
                for name, res in results:
                    summary = clean_thinking_tokens(res.summary)  # ref :560-561
                    (out_dir / name).write_text(summary, encoding="utf-8")
                    record.total_documents += 1
                    record.successful += 1
                    record.total_chunks += res.num_chunks
                    record.processing_details.append(
                        DocumentRecord(
                            name, res.num_chunks, per_doc_time, len(summary),
                            llm_calls=res.llm_calls,
                        )
                    )
            logger.info(
                "  batch of %d docs in %.1fs (%.1fs/doc)",
                len(results), batch_time, per_doc_time,
            )

        record.total_time = time.time() - t_start
        self.results.add_summarization(record)
        # the engine's own account of the run (host spans, executions, the
        # held ones, the work counters), for ``tracing["engine"]``: a
        # backend without one leaves the key out; of several models the last
        self._engine_record = getattr(backend, "engine_record", lambda: None)()
        return record

    def run_evaluation_for_model(self, model: str) -> dict:
        cfg = self.config
        embedder = self.embedding_model
        if embedder is None:
            from ..eval import EmbeddingModel

            with self.tracer.span("embedder_init"):
                if cfg.evaluation.embedding_dir:
                    embedder = EmbeddingModel.from_hf(
                        cfg.evaluation.embedding_dir,
                        batch_size=cfg.evaluation.bert_batch_size,
                    )
                else:
                    embedder = EmbeddingModel(
                        batch_size=cfg.evaluation.bert_batch_size
                    )
            self.embedding_model = embedder  # reuse across the model sweep
        judge = None
        if cfg.evaluation.include_llm_eval:
            judge = self._build_llm_judge()
        evaluator = SemanticEvaluator(
            embedding_model=embedder,
            include_llm_eval=judge is not None,
            llm_judge=judge,
            tracer=self.tracer,
        )
        out_path = (
            Path(cfg.results_dir) / f"{model_name_safe(model)}_results.json"
        )
        results = evaluator.evaluate_folders(
            self._output_dir(model),
            cfg.summary_dir,
            max_samples=cfg.evaluation.max_samples or cfg.max_samples,
            output=out_path,
        )
        self.results.add_evaluation(model, results["summary_statistics"])
        return results

    def _build_llm_judge(self):
        """G-Eval judge resolution: an injected judge wins, then a local
        Backend-protocol judge (EvalConfig.judge_backend — the offline path),
        then an OpenRouter-compatible endpoint when an API key is present
        (ref use_openrouter path); otherwise skipped with a warning — never
        a hard failure."""
        import os

        from ..eval import LLMJudge

        cfg = self.config.evaluation
        if self.llm_judge is not None:
            return self.llm_judge
        if cfg.judge_backend:
            return LLMJudge(backend=self._judge_backend(cfg.judge_backend))
        api_key = os.environ.get("OPENROUTER_API_KEY") or os.environ.get(
            "OPENAI_API_KEY"
        )
        if not api_key:
            logger.warning(
                "include_llm_eval=True but no OPENROUTER_API_KEY/OPENAI_API_KEY "
                "set; skipping G-Eval"
            )
            return None
        base = (
            "https://openrouter.ai/api/v1"
            if cfg.use_openrouter
            else "https://api.openai.com/v1"
        )
        return LLMJudge(api_base=base, api_key=api_key, model=cfg.llm_model)

    def _judge_backend(self, spec: str) -> Backend:
        """Resolve EvalConfig.judge_backend into a judge Backend. A bare
        string can't carry model kwargs, so each form is explicit:
        "fake" (CI), "ollama:<model>" (local server), "tpu:<registry-name>"
        (on-device judge — RANDOM weights unless the registry model maps to
        a loaded checkpoint elsewhere, so plumbing/containment runs only)."""
        name, _, arg = spec.partition(":")
        if name == "fake":
            return get_backend("fake")
        if name == "ollama":
            if not arg:
                raise ValueError(
                    "judge_backend='ollama:<model>' needs the model tag"
                )
            return get_backend(
                "ollama", model=arg, url=self.config.ollama_url
            )
        if name == "tpu":
            from ..models import MODEL_REGISTRY

            if arg not in MODEL_REGISTRY:
                raise ValueError(
                    "judge_backend='tpu:<model>' needs a registry model "
                    f"name (have {sorted(MODEL_REGISTRY)}); a bare 'tpu' "
                    "would silently judge with an unspecified model"
                )
            logger.warning(
                "tpu judge %r runs RANDOM-INIT weights on this host — "
                "scores will mostly fail to parse; use an HTTP judge or "
                "inject PipelineRunner(llm_judge=...) for real judging",
                arg,
            )
            return get_backend(
                "tpu", model_config=MODEL_REGISTRY[arg](), max_new_tokens=64
            )
        raise ValueError(f"unknown judge_backend spec {spec!r}")

    # -- orchestration -----------------------------------------------------

    def run(self) -> PipelineResults:
        with self.tracer.span("analyze"):
            self.analyze()
        for model in self.config.models:
            try:
                with self.tracer.span("summarize"):
                    self.run_summarization_for_model(model)
            except Exception as e:
                logger.error("model %s summarization failed: %s", model, e)
                logger.debug("%s", traceback.format_exc())
                rec = ModelRunRecord(
                    model=model, approach=self.config.approach,
                    status="failed", error=str(e),
                )
                self.results.add_summarization(rec)
                continue
            try:
                with self.tracer.span("evaluate"):
                    self.run_evaluation_for_model(model)
            except Exception as e:
                logger.error("model %s evaluation failed: %s", model, e)
                self.results.add_evaluation(model, {"status": "failed", "error": str(e)})
        self.results.tracing = self.tracer.to_dict()
        if self._engine_record is not None:
            self.results.tracing["engine"] = self._engine_record
        path = self.results.save(self.config.results_dir)
        logger.info("results saved to %s", path)
        # when device profiling is armed (VNSUM_PROFILE_DIR), drop the host
        # span timeline as Chrome trace JSON into the same directory so the
        # pipeline's wall-clock phases open in Perfetto next to the XLA
        # device trace — the offline twin of serving's /debug/trace
        profile_dir = os.environ.get("VNSUM_PROFILE_DIR")
        if profile_dir:
            from ..obs.export import save_timestamped_trace

            tp = save_timestamped_trace(
                self.tracer.chrome_trace("pipeline"), profile_dir, "pipeline"
            )
            logger.info("host span timeline saved to %s", tp)
        self.report()
        return self.results

    def report(self) -> str:
        """Human-readable summary (ref generate_summary_report :841-925,
        minus its '{:.4f}'.format('N/A') crash path)."""
        lines = ["", "=" * 60, "PIPELINE SUMMARY", "=" * 60]
        lines.append(f"approach: {self.config.approach}")
        for model, rec in self.results.summarization.items():
            lines.append(f"\nmodel {model}:")
            lines.append(
                f"  docs: {rec.get('successful', 0)} ok / {rec.get('failed', 0)} failed, "
                f"chunks: {rec.get('total_chunks', 0)}, "
                f"time: {rec.get('total_time', 0.0):.1f}s "
                f"({rec.get('chunks_per_second', 0.0):.2f} chunks/s)"
            )
            ev = self.results.evaluation.get(model)
            if ev and "rouge_scores" in ev:

                def fmt(v):
                    return f"{v:.4f}" if isinstance(v, (int, float)) else str(v)

                rs = ev["rouge_scores"]
                bs = ev.get("bert_scores", {})
                ss = ev.get("semantic_similarity", {})
                lines.append(
                    f"  rouge1/2/L: {fmt(rs.get('rouge1_f1', 'N/A'))} / "
                    f"{fmt(rs.get('rouge2_f1', 'N/A'))} / {fmt(rs.get('rougeL_f1', 'N/A'))}"
                )
                lines.append(
                    f"  bert F1: {fmt(bs.get('bert_f1', 'N/A'))}  "
                    f"semsim: {fmt(ss.get('mean', 'N/A'))}"
                )
        text = "\n".join(lines)
        logger.info("%s", text)
        return text
