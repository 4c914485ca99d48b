"""Tests for the tracing/profiling subsystem (SURVEY.md §5: the reference has
only LangSmith @traceable + ad-hoc wall-clock fields; we provide aggregated
spans + gated jax.profiler traces)."""
import threading

from vnsum_tpu.core.profiling import Tracer, device_profile


def test_span_aggregates():
    t = Tracer()
    for _ in range(3):
        with t.span("work"):
            pass
    stats = t.stats()
    assert stats["work"]["count"] == 3
    assert stats["work"]["total_s"] >= 0.0
    assert stats["work"]["min_s"] <= stats["work"]["max_s"]


def test_span_nesting_builds_hierarchical_names():
    t = Tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    with t.span("inner"):
        pass
    stats = t.stats()
    assert set(stats) == {"outer", "outer/inner", "inner"}


def test_span_exception_still_recorded():
    t = Tracer()
    try:
        with t.span("boom"):
            raise ValueError
    except ValueError:
        pass
    assert t.stats()["boom"]["count"] == 1
    # stack unwound correctly: next span is top-level
    with t.span("after"):
        pass
    assert "boom/after" not in t.stats()


def test_tracer_thread_safety():
    t = Tracer()

    def worker():
        for _ in range(50):
            with t.span("shared"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.stats()["shared"]["count"] == 200


def test_record_external_duration():
    t = Tracer()
    t.record("device_step", 0.5)
    t.record("device_step", 1.5)
    s = t.stats()["device_step"]
    assert s["count"] == 2 and s["total_s"] == 2.0 and s["max_s"] == 1.5


def test_reset():
    t = Tracer()
    with t.span("x"):
        pass
    t.reset()
    assert t.stats() == {}


def test_device_profile_noop_without_dir(monkeypatch):
    monkeypatch.delenv("VNSUM_PROFILE_DIR", raising=False)
    with device_profile():  # must not require jax import side effects
        pass


def test_device_profile_writes_trace(tmp_path):
    with device_profile(str(tmp_path)):
        import jax.numpy as jnp

        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    # jax.profiler.trace writes plugins/profile/<ts>/ under the log dir
    assert any(tmp_path.rglob("*.xplane.pb"))


def test_pipeline_records_tracing(tmp_path):
    from vnsum_tpu.core.config import PipelineConfig
    from vnsum_tpu.eval import EmbeddingModel
    from vnsum_tpu.models.encoder import tiny_encoder
    from vnsum_tpu.pipeline.runner import PipelineRunner

    docs = tmp_path / "doc"
    refs = tmp_path / "summary"
    docs.mkdir()
    refs.mkdir()
    for i in range(2):
        (docs / f"d{i}.txt").write_text("một hai ba bốn năm " * 50)
        (refs / f"d{i}.txt").write_text("tóm tắt " * 5)
    cfg = PipelineConfig(
        approach="truncated",
        models=["fake"],
        backend="fake",
        docs_dir=str(docs),
        summary_dir=str(refs),
        generated_summaries_dir=str(tmp_path / "gen"),
        results_dir=str(tmp_path / "results"),
        logs_dir=str(tmp_path / "logs"),
    )
    runner = PipelineRunner(
        cfg,
        embedding_model=EmbeddingModel(config=tiny_encoder(), max_len=64, batch_size=4),
    )
    results = runner.run()
    spans = results.tracing["spans"]
    assert "analyze" in spans
    assert "summarize" in spans
    assert "summarize/batch" in spans
    assert "evaluate" in spans
    d = results.to_dict()
    assert d["results"]["tracing"]["spans"]["summarize"]["count"] == 1
