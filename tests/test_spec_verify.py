"""Speculative verify-step correctness (vnsum_tpu.spec + engine spec path):
greedy spec decode must emit EXACTLY the plain decode token stream — on the
dense path, on the (interpret-mode) Pallas verify kernel path, with custom
stop tokens, and with acceptance actually firing (oracle reference).

Deliberately in the FAST tier (ROADMAP tier-1): the module compiles a
handful of tiny-model programs, each a few seconds on CPU, and shares one
engine fixture across tests.
"""
import numpy as np
import pytest

from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models import tiny_llama

PROMPTS = [
    "văn bản một về kinh tế",
    "hai " * 5,
    "một tài liệu dài hơn hẳn về pháp luật",
]
REFS = [
    "văn bản một về kinh tế xã hội và phát triển bền vững",
    None,  # no reference: the row must degrade to plain one-token steps
    "một tài liệu dài hơn hẳn về pháp luật và đời sống",
]


@pytest.fixture(scope="module")
def engine():
    from vnsum_tpu.backend.engine import TpuBackend

    return TpuBackend(
        model_config=tiny_llama(max_seq_len=256),
        batch_size=4,
        max_new_tokens=12,
        seed=0,
        flash=False,
    )


def test_greedy_spec_matches_plain_decode(engine):
    plain = engine.generate(PROMPTS)
    spec = engine.generate(
        PROMPTS, config=GenerationConfig(spec_k=4), references=REFS
    )
    assert spec == plain
    report = engine.take_spec_report()
    assert len(report) == len(PROMPTS)
    assert report[1].draft_tokens == 0  # no reference, nothing proposed
    assert all(r.verify_steps > 0 for r in report)
    # second read is empty — the report is consumed
    assert engine.take_spec_report() == []


def test_spec_k_zero_keeps_the_plain_path(engine):
    """spec_k=0 (the default) must not even enter the spec scheduler:
    outputs byte-identical, no report, no spec counters."""
    before = engine.stats.spec_verify_steps
    plain = engine.generate(PROMPTS)
    with_refs = engine.generate(PROMPTS, references=REFS)  # spec_k defaults 0
    assert with_refs == plain
    assert engine.take_spec_report() == []
    assert engine.stats.spec_verify_steps == before


def test_greedy_spec_matches_plain_on_flash_kernel_path():
    """The multi-position Pallas verify kernel (interpret mode on CPU) must
    preserve the greedy stream too — this is the production TPU path."""
    from vnsum_tpu.backend.engine import TpuBackend

    kw = dict(
        model_config=tiny_llama(max_seq_len=256), batch_size=4,
        max_new_tokens=10, seed=0, flash=True, interpret=True,
    )
    be = TpuBackend(**kw)
    plain = be.generate(PROMPTS)
    spec = be.generate(
        PROMPTS, config=GenerationConfig(spec_k=3), references=REFS
    )
    assert spec == plain


def test_oracle_reference_is_accepted(engine):
    """Feed the row's own greedy continuation back as the reference: the
    drafter proposes exactly what the model will emit, so acceptance must
    fire and the output must STILL be byte-identical. This pins the whole
    accept path (multi-token emission, per-row fills, rollback bookkeeping)
    with a deterministic >1-token-per-step workload."""
    prompt = "một đoạn văn nguồn"
    plain = engine.generate([prompt])[0]
    if len(engine.tok.encode(plain, add_bos=False)) < 4:
        pytest.skip("greedy output too short to exercise acceptance")
    spec = engine.generate(
        [prompt], config=GenerationConfig(spec_k=4), references=[plain]
    )
    assert spec[0] == plain
    (rec,) = engine.take_spec_report()
    assert rec.accepted_tokens > 0
    # acceptance strictly compresses steps: fewer verify forwards than
    # emitted tokens
    emitted = len(engine.tok.encode(plain, add_bos=False))
    assert rec.verify_steps < emitted + 1


def test_custom_eos_stops_and_strips_under_spec(engine):
    """A custom stop token must terminate a speculative row mid-stream and
    be stripped from the text, exactly like plain decode (the terminator
    may arrive inside an ACCEPTED draft run, not only as the step token)."""
    prompt = "một đoạn văn"
    full = engine.generate([prompt])[0]
    ids = engine.tok.encode(full, add_bos=False)
    if len(ids) < 3:
        pytest.skip("rollout too short for a mid-stream stop")
    stop = ids[2]
    gen = GenerationConfig(temperature=0.0, eos_ids=(stop,), spec_k=4)
    # oracle reference makes the drafter propose the stop token inside a
    # draft run, exercising the emission cut
    out = engine.generate([prompt], config=gen, references=[full])[0]
    expect = engine.tok.decode(ids[: ids.index(stop)]).strip()
    assert out == expect


def test_spec_batch_invariance(engine):
    """A row's spec output must not depend on its batch neighbors (mirrors
    the plain engine's padding-invariance contract)."""
    gen = GenerationConfig(spec_k=4)
    alone = engine.generate([PROMPTS[0]], config=gen, references=[REFS[0]])[0]
    together = engine.generate(PROMPTS, config=gen, references=REFS)[0]
    assert alone == together


def test_sampled_spec_terminates_and_reports(engine):
    """Temperature sampling through the rejection-acceptance path: outputs
    are not required to match plain decode bit-for-bit (different
    randomness consumption), but decoding must terminate, respect the
    budget, and report coherent counters."""
    gen = GenerationConfig(spec_k=4, temperature=1.0, seed=11)
    outs = engine.generate(PROMPTS, config=gen, references=REFS)
    assert len(outs) == len(PROMPTS)
    report = engine.take_spec_report()
    for r in report:
        assert 0 <= r.accepted_tokens <= r.draft_tokens
        assert r.verify_steps <= 12  # every step retires >= 1 token


def test_mismatched_references_rejected(engine):
    with pytest.raises(ValueError, match="references must align"):
        engine.generate(
            PROMPTS, config=GenerationConfig(spec_k=2), references=["x"]
        )


def test_fake_backend_spec_contract():
    """FakeBackend mirrors the engine's spec surface so serve/strategy tests
    run without a model: references recorded, synthetic per-prompt records
    at the configured acceptance, report cleared on read."""
    from vnsum_tpu.backend.fake import FakeBackend

    fb = FakeBackend(spec_k=4, spec_acceptance=0.5)
    outs = fb.generate(
        ["Tóm tắt:\n<content>\nmột hai ba\n</content>", "b"],
        references=["một hai ba", None],
    )
    assert len(outs) == 2
    assert fb.references_seen == ["một hai ba", None]
    rep = fb.take_spec_report()
    assert len(rep) == 2
    assert rep[0].draft_tokens > 0
    assert rep[0].accepted_tokens == rep[0].draft_tokens // 2
    assert rep[1].draft_tokens == 0  # no reference
    assert fb.take_spec_report() == []
    # spec off -> empty report, references still accepted silently
    fb2 = FakeBackend()
    fb2.generate(["a"], references=["r"])
    assert fb2.take_spec_report() == []


def test_strategies_thread_chunk_references_to_backend():
    """The mapreduce map round must hand each chunk to the backend as that
    prompt's reference — the seam speculation rides end to end."""
    from vnsum_tpu.backend.fake import FakeBackend
    from vnsum_tpu.strategies.mapreduce import MapReduceStrategy
    from vnsum_tpu.text.splitter import RecursiveTokenSplitter
    from vnsum_tpu.text.tokenizer import whitespace_token_count

    fb = FakeBackend(spec_k=2)
    splitter = RecursiveTokenSplitter(
        40, 5, length_function=whitespace_token_count
    )
    st = MapReduceStrategy(fb, splitter, token_max=60)
    doc = " ".join(f"từ{i}" for i in range(120))
    res = st.summarize(doc)
    assert res.summary
    assert len(fb.references_seen) == len(fb.calls)
    # every map-round reference is a chunk of the document
    n_chunks = res.num_chunks
    for ref in fb.references_seen[:n_chunks]:
        assert ref and ref in doc


def test_serve_scheduler_attributes_spec_metrics():
    """References ride ServeRequests through the micro-batching scheduler;
    per-request records carry drafting stats and /metrics exports the
    counters (the ISSUE's acceptance-rate observability contract)."""
    from vnsum_tpu.backend.fake import FakeBackend
    from vnsum_tpu.serve.scheduler import MicroBatchScheduler

    fb = FakeBackend(spec_k=4, spec_acceptance=0.25)
    sched = MicroBatchScheduler(fb, max_batch=4, max_wait_s=0.005)
    try:
        comps = sched.generate_sync(
            ["Tóm tắt:\n<content>\nmột hai ba bốn\n</content>"] * 2,
            references=["một hai ba bốn", None],
        )
        recs = [c.record for c in comps]
        assert recs[0].draft_tokens > 0
        assert recs[0].accepted_tokens == recs[0].draft_tokens // 4
        assert recs[1].draft_tokens == 0
        snap = sched.metrics.snapshot()
        assert snap.draft_tokens == recs[0].draft_tokens
        assert snap.accepted_tokens == recs[0].accepted_tokens
        prom = sched.metrics.render_prometheus()
        assert f"vnsum_serve_spec_draft_tokens_total {snap.draft_tokens}" in prom
        assert (
            f"vnsum_serve_spec_accepted_tokens_total {snap.accepted_tokens}"
            in prom
        )
        assert "vnsum_serve_spec_acceptance_rate 0.25" in prom
    finally:
        sched.close()


def test_w8a8_prefill_does_not_quantize_the_verify_forward():
    """Code-review regression: the spec verify forward is multi-token but
    decode-phase — it must NOT trip the w8a8_prefill S>1 gate, or greedy
    spec outputs diverge from plain decode under quantize_act."""
    from vnsum_tpu.backend.engine import TpuBackend

    kw = dict(
        model_config=tiny_llama(max_seq_len=256), batch_size=4,
        max_new_tokens=10, seed=0, quantize=True, quantize_act=True,
        flash=False,
    )
    be = TpuBackend(**kw)
    plain = be.generate(PROMPTS)
    spec = be.generate(
        PROMPTS, config=GenerationConfig(spec_k=4), references=REFS
    )
    assert spec == plain


def test_server_default_spec_k_survives_other_knobs():
    """Code-review regression: a request customizing only sampling knobs
    must not silently wipe the server's --spec-k default (the fresh config
    REPLACES the backend default wholesale)."""
    from vnsum_tpu.serve.server import _gen_config_from

    cfg = _gen_config_from({"temperature": 0.7}, default_spec_k=8)
    assert cfg.spec_k == 8 and cfg.temperature == 0.7
    # explicit opt-out wins over the default
    assert _gen_config_from({"spec_k": 0}, default_spec_k=8).spec_k == 0
    # no knobs at all -> None -> the backend's own default config applies
    assert _gen_config_from({}, default_spec_k=8) is None


def test_all_refless_group_takes_the_plain_path():
    """Code-review regression: when a spec call's length-sorted grouping
    puts all the reference-less rows in one group, that group must not pay
    the (k+1)-wide verify forward — it routes to plain decode; its report
    rows come back zeroed and aligned, while the referenced group still
    speculates. An all-empty references list never enters spec at all."""
    from vnsum_tpu.backend.engine import TpuBackend

    be = TpuBackend(
        model_config=tiny_llama(max_seq_len=256), batch_size=2,
        max_new_tokens=8, seed=0,
        flash=False,
    )
    # two short refless prompts group together; two long ones carry refs
    prompts = ["a", "b", "một tài liệu dài " * 4, "văn bản nguồn khá dài " * 4]
    refs = [None, None, prompts[2], prompts[3]]
    gen = GenerationConfig(spec_k=4)

    plain = be.generate(prompts)
    spec = be.generate(prompts, config=gen, references=refs)
    assert spec == plain
    report = be.take_spec_report()
    assert len(report) == 4
    assert all(r.verify_steps == 0 for r in report[:2])   # plain-path group
    assert all(r.verify_steps > 0 for r in report[2:])    # spec group

    # an entirely refless call is spec-off: empty report, no counters moved
    before = be.stats.spec_verify_steps
    out = be.generate(prompts[:2], config=gen, references=[None, ""])
    assert out == plain[:2]
    assert be.take_spec_report() == []
    assert be.stats.spec_verify_steps == before
