"""Real-weight chain, hermetic on CPU: HF checkpoint dir (real transformers
save_pretrained + trained BPE tokenizer) -> models.convert -> TpuBackend(HF
tokenizer) -> mapreduce -> ROUGE (quality-gate machinery, reference
evaluation_results/first_dataset/mapreduce/llama3_2_3b_results.json)."""
import pytest

from vnsum_tpu.core.config import PipelineConfig
from vnsum_tpu.data.synthesize import synthesize_corpus
from vnsum_tpu.pipeline.runner import PipelineRunner

transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def corpus_and_ckpt(tmp_path_factory):
    from vnsum_tpu.models.fixtures import make_tiny_hf_checkpoint

    root = tmp_path_factory.mktemp("parity")
    synthesize_corpus(
        root / "corpus", n_docs=3, tokens_per_doc=300, summary_tokens=40,
        seed=5,
    )
    docs = [
        p.read_text(encoding="utf-8")
        for p in sorted((root / "corpus/doc").glob("*.txt"))
    ]
    make_tiny_hf_checkpoint(
        root / "ckpt", docs, vocab_size=512, dim=64, n_layers=2,
        train_steps=0,
    )
    return root


def _config(root, **kw):
    base = dict(
        approach="mapreduce",
        models=["tiny-parity"],
        backend="tpu",
        weights_dir=str(root / "ckpt"),
        docs_dir=str(root / "corpus/doc"),
        summary_dir=str(root / "corpus/summary"),
        generated_summaries_dir=str(root / "gen"),
        results_dir=str(root / "results"),
        logs_dir=str(root / "logs"),
        chunk_size=120,
        chunk_overlap=12,
        token_max=100,
        max_new_tokens=12,
        batch_size=4,
    )
    base.update(kw)
    return PipelineConfig(**base)


def _runner(cfg) -> PipelineRunner:
    """The default tpu factory, off-chip: the dense path asked for by name
    (the engine refuses a platform other than tpu otherwise)."""
    runner = PipelineRunner(cfg)
    runner.backend_factory = lambda model: runner._default_backend_factory(
        model, flash=False
    )
    return runner


def test_weights_dir_end_to_end_with_rouge(corpus_and_ckpt):
    root = corpus_and_ckpt
    results = _runner(_config(root)).run()

    rec = results.summarization["tiny-parity"]
    assert rec["successful"] == 3 and rec["failed"] == 0
    ev = results.evaluation["tiny-parity"]
    assert 0.0 <= ev["rouge_scores"]["rougeL_f1"] <= 1.0
    assert "bert_scores" in ev and "semantic_similarity" in ev

    # generated files exist and decode through the checkpoint's tokenizer
    gen = root / "gen_mapreduce_tiny-parity"
    files = sorted(gen.glob("*.txt"))
    assert len(files) == 3
    for f in files:
        f.read_text(encoding="utf-8")  # valid utf-8


def test_weights_dir_tokenizer_comes_from_checkpoint(corpus_and_ckpt):
    root = corpus_and_ckpt
    runner = _runner(_config(root))
    backend = runner.backend_factory("tiny-parity")
    # trained BPE vocab, not the byte fallback
    assert backend.tok.vocab_size <= 512
    ids = backend.tok.encode("tình hình kinh tế Việt Nam")
    assert ids and backend.tok.decode(ids).strip().startswith("tình hình")
    # model config came from the checkpoint's config.json
    assert backend.cfg.dim == 64
    assert backend.cfg.vocab_size >= backend.tok.vocab_size


def test_weights_dir_resume_skips_existing(corpus_and_ckpt):
    root = corpus_and_ckpt
    # hermetic: pre-write all 3 outputs into a fresh dir; the run must skip
    # every doc (resume-by-file, ref run_full_evaluation_pipeline.py:422-431)
    cfg = _config(root, generated_summaries_dir=str(root / "gen_resume"))
    runner = _runner(cfg)
    out_dir = runner._output_dir("tiny-parity")
    out_dir.mkdir(parents=True, exist_ok=True)
    for name in ("doc_000.txt", "doc_001.txt", "doc_002.txt"):
        (out_dir / name).write_text("đã có", encoding="utf-8")
    rec = runner.run_summarization_for_model("tiny-parity")
    assert rec.total_documents == 0
    # pre-existing outputs untouched
    assert (out_dir / "doc_000.txt").read_text(encoding="utf-8") == "đã có"
