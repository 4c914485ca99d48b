import json
from pathlib import Path

import pytest

from vnsum_tpu.core import PipelineConfig
from vnsum_tpu.eval import EmbeddingModel
from vnsum_tpu.models.encoder import tiny_encoder
from vnsum_tpu.pipeline.cli import build_parser, config_from_args
from vnsum_tpu.pipeline.runner import PipelineRunner, model_name_safe


@pytest.fixture()
def workspace(tmp_path):
    docs = tmp_path / "doc"
    refs = tmp_path / "summary"
    docs.mkdir()
    refs.mkdir()
    for i in range(3):
        (docs / f"d{i}.txt").write_text(
            "\n\n".join(f"đoạn {i}-{p} " + "nội dung " * 20 for p in range(6)),
            encoding="utf-8",
        )
        (refs / f"d{i}.txt").write_text(f"tóm tắt tham chiếu {i}", encoding="utf-8")
    return tmp_path


def make_config(ws, **kw):
    base = dict(
        approach="mapreduce",
        models=["fake-model"],
        backend="fake",
        docs_dir=str(ws / "doc"),
        summary_dir=str(ws / "summary"),
        generated_summaries_dir=str(ws / "generated_summaries"),
        results_dir=str(ws / "evaluation_results"),
        logs_dir=str(ws / "logs"),
        chunk_size=50,
        chunk_overlap=5,
        token_max=60,
        batch_size=4,
    )
    base.update(kw)
    return PipelineConfig(**base)


def small_embedder():
    return EmbeddingModel(config=tiny_encoder(), max_len=64, batch_size=4)


def test_full_pipeline_fake_backend(workspace):
    cfg = make_config(workspace)
    runner = PipelineRunner(cfg, embedding_model=small_embedder())
    results = runner.run()

    out_dir = Path(f"{cfg.generated_summaries_dir}_mapreduce_fake-model")
    assert sorted(p.name for p in out_dir.glob("*.txt")) == [
        "d0.txt", "d1.txt", "d2.txt",
    ]
    rec = results.summarization["fake-model"]
    assert rec["successful"] == 3 and rec["failed"] == 0
    assert rec["total_chunks"] > 3
    ev = results.evaluation["fake-model"]
    assert "rouge_scores" in ev
    # persisted artifacts
    saved = list(Path(cfg.results_dir).glob("pipeline_results_*.json"))
    assert len(saved) == 1
    per_model = Path(cfg.results_dir) / "fake-model_results.json"
    assert per_model.exists()
    data = json.loads(per_model.read_text())
    assert len(data["detailed_results"]) == 3
    # report must not crash and must include metrics
    assert "rouge1/2/L" in runner.report()


def test_resume_skips_existing(workspace):
    cfg = make_config(workspace)
    out_dir = Path(f"{cfg.generated_summaries_dir}_mapreduce_fake-model")
    out_dir.mkdir(parents=True)
    (out_dir / "d0.txt").write_text("đã có sẵn", encoding="utf-8")

    runner = PipelineRunner(cfg, embedding_model=small_embedder())
    rec = runner.run_summarization_for_model("fake-model")
    assert rec.total_documents == 2  # d0 skipped
    assert (out_dir / "d0.txt").read_text(encoding="utf-8") == "đã có sẵn"


def test_docs_without_reference_are_skipped(workspace):
    (workspace / "doc" / "orphan.txt").write_text("no ref", encoding="utf-8")
    cfg = make_config(workspace)
    runner = PipelineRunner(cfg, embedding_model=small_embedder())
    rec = runner.run_summarization_for_model("fake-model")
    assert rec.total_documents == 3


def test_failed_model_is_contained(workspace):
    cfg = make_config(workspace, models=["boom", "fake-model"])

    calls = {"n": 0}

    def factory(model):
        from vnsum_tpu.backend import FakeBackend

        if model == "boom":
            raise RuntimeError("backend construction exploded")
        return FakeBackend(summary_words=10)

    runner = PipelineRunner(cfg, backend_factory=factory, embedding_model=small_embedder())
    results = runner.run()
    assert results.summarization["boom"]["status"] == "failed"
    assert results.summarization["fake-model"]["successful"] == 3


def test_max_samples(workspace):
    cfg = make_config(workspace, max_samples=1)
    runner = PipelineRunner(cfg, embedding_model=small_embedder())
    rec = runner.run_summarization_for_model("fake-model")
    assert rec.total_documents == 1


def test_hierarchical_with_tree_json(workspace):
    tree = {
        "d0.txt": {
            "type": "Document",
            "text": "Tài liệu 0",
            "children": [
                {
                    "type": "Header",
                    "text": "Chương",
                    "children": [{"type": "Paragraph", "text": "nội dung " * 30}],
                }
            ],
        }
    }
    tree_path = workspace / "tree.json"
    tree_path.write_text(json.dumps(tree, ensure_ascii=False), encoding="utf-8")
    cfg = make_config(
        workspace, approach="mapreduce_hierarchical", tree_json_path=str(tree_path)
    )
    runner = PipelineRunner(cfg, embedding_model=small_embedder())
    rec = runner.run_summarization_for_model("fake-model")
    # d0 via tree, d1/d2 via plain-text fallback
    assert rec.successful == 3


def test_all_approaches_run(workspace):
    for approach in (
        "mapreduce", "mapreduce_critique", "iterative", "truncated",
        "mapreduce_hierarchical",
    ):
        cfg = make_config(workspace, approach=approach)
        runner = PipelineRunner(cfg, embedding_model=small_embedder())
        rec = runner.run_summarization_for_model("fake-model")
        assert rec.successful == 3, approach


def test_model_name_safe():
    assert model_name_safe("llama3.2:3b") == "llama3_2_3b"


def test_cli_config():
    args = build_parser().parse_args(
        [
            "--approach", "mapreduce_critique", "--backend", "fake",
            "--models", "m1", "m2", "--mesh", "data=2,model=4",
            "--max-samples", "5",
        ]
    )
    cfg = config_from_args(args)
    assert cfg.approach == "mapreduce_critique"
    assert cfg.max_new_tokens == 2048  # critique override
    assert cfg.mesh_shape == {"data": 2, "model": 4}
    assert cfg.models == ["m1", "m2"]
    assert cfg.max_samples == 5


def test_utils_tools(tmp_path):
    from vnsum_tpu.utils.calculate_tokens import process_folder
    from vnsum_tpu.utils.clean_summaries import clean_summaries

    d = tmp_path / "sums"
    d.mkdir()
    (d / "a.txt").write_text("<think>bí mật</think>tóm tắt", encoding="utf-8")
    (d / "b.txt").write_text("sạch sẵn", encoding="utf-8")

    stats = process_folder(d)
    assert stats["summary"]["total_files"] == 2
    assert stats["files"]["b.txt"]["words"] == 2

    out = clean_summaries(d, preview=True)
    assert out["changed"] == ["a.txt"]
    assert "<think>" in (d / "a.txt").read_text(encoding="utf-8")  # preview untouched

    clean_summaries(d)
    assert (d / "a.txt").read_text(encoding="utf-8") == "tóm tắt"


def test_cli_long_context_and_quantize_flags():
    args = build_parser().parse_args([
        "--approach", "truncated", "--backend", "tpu",
        "--long-context", "--quantize",
        "--mesh", "data=2,seq=4",
        "--max-context", "65536",
    ])
    cfg = config_from_args(args)
    assert cfg.long_context and cfg.quantize
    assert cfg.max_context == 65536
    assert cfg.mesh_shape == {"data": 2, "seq": 4}


def _cli_argv(ws):
    return [
        "--backend", "fake", "--models", "fake-model",
        "--docs-dir", str(ws / "doc"), "--summary-dir", str(ws / "summary"),
        "--generated-summaries-dir", str(ws / "generated_summaries"),
        "--results-dir", str(ws / "evaluation_results"),
        "--chunk-size", "50", "--token-max", "60", "--batch-size", "4",
    ]


def test_cli_exit_code_zero_on_a_clean_run(workspace, monkeypatch):
    from vnsum_tpu.pipeline import cli

    monkeypatch.chdir(workspace)  # logs/ lands under the tmp dir
    assert cli.main(_cli_argv(workspace)) == 0


def test_cli_exit_code_nonzero_when_every_document_fails(
    workspace, monkeypatch, capsys
):
    """The runner's per-batch catch keeps partial progress; the exit code
    must still say that documents failed (it used to return 0 whatever
    happened — a run whose every dispatch died exited clean)."""
    from vnsum_tpu.pipeline import cli
    from vnsum_tpu.testing.faults import FaultPlan, FaultSpec, injected

    monkeypatch.chdir(workspace)
    plan = FaultPlan([FaultSpec("fake.dispatch", kind="fatal", every_n=1)])
    with injected(plan):
        rc = cli.main(_cli_argv(workspace))
    assert rc == 1
    assert plan.fired
    assert "fake-model: 3 document(s) failed" in capsys.readouterr().err


def test_cli_exit_code_nonzero_when_the_model_fails(
    workspace, monkeypatch, capsys
):
    from vnsum_tpu.pipeline import cli, runner

    monkeypatch.chdir(workspace)

    def broken(self, model, **kw):
        raise RuntimeError("no such backend today")

    monkeypatch.setattr(
        runner.PipelineRunner, "_default_backend_factory", broken
    )
    assert cli.main(_cli_argv(workspace)) == 1
    assert "summarization failed: no such backend today" in (
        capsys.readouterr().err
    )
