"""Long-context backend: ring prefill + seq-sharded decode (VERDICT r1 #9).

Parity anchor: the long path on an 8-device CPU mesh must reproduce the plain
one-chip engine's greedy outputs given the SAME weights — including prompts
that exceed the one-chip max_seq_len ceiling (which the dense oracle only
handles because CPU hosts have no HBM limit)."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.backend.long_context import LongContextBackend, long_prefill
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.models.llama import init_params
from vnsum_tpu.parallel.mesh import make_mesh

PROMPTS = [
    "Tóm tắt văn bản sau: nền kinh tế tăng trưởng ổn định trong quý một. "
    * 2,
    "hai",
    "Một tài liệu dài hơn hẳn nói về chính sách giáo dục và y tế cơ sở "
    "tại các địa phương miền núi phía bắc. " * 3,
]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"data": 2, "seq": 4}, platform="cpu")


@pytest.fixture(scope="module")
def setup(mesh):
    # ONE set of weights; the dense oracle gets a big single-chip context
    # (fine on CPU) while the long backend shards the same lengths over seq
    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(3), cfg)
    dense = TpuBackend(
        model_config=cfg, params=params, batch_size=4, max_new_tokens=16,
        flash=False,
    )
    long = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, max_new_tokens=16,
        max_total_tokens=2048,
        decode_kernel=False,
    )
    return dense, long


def test_prefill_logits_match_dense(mesh):
    from vnsum_tpu.models.llama import (
        forward,
        init_kv_cache,
        prefill_attention_mask,
        prefill_positions,
    )
    import jax.numpy as jnp

    cfg = tiny_llama(max_seq_len=1024)
    params = init_params(jax.random.key(0), cfg)
    B, S = 2, 512  # divisible by seq axis (4)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(B, S)).astype(np.int32)
    pad = np.array([0, 100], dtype=np.int32)
    tokens[1, :100] = 258  # left padding

    logits_long, cache = long_prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray(pad), mesh
    )

    dense_cache = init_kv_cache(cfg, B, S)
    mask = prefill_attention_mask(jnp.asarray(pad), S, S)
    logits_dense, _ = forward(
        params, cfg, jnp.asarray(tokens), prefill_positions(jnp.asarray(pad), S),
        dense_cache, 0, mask, last_only=True,
    )
    np.testing.assert_allclose(
        np.asarray(logits_long), np.asarray(logits_dense)[:, -1], atol=2e-4
    )
    # engine-native stacked layout [L, B, KV, S, hd] (what the Pallas decode
    # kernel consumes shard-locally)
    assert cache["k"].shape == (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.head_dim)


def test_greedy_parity_with_dense_engine(setup):
    dense, long = setup
    expect = dense.generate(PROMPTS)
    got = long.generate(PROMPTS)
    assert got == expect


def test_exceeds_single_chip_ceiling(mesh):
    """A prompt longer than the one-chip max_seq_len runs UN-truncated on the
    seq-sharded path and matches a big-context dense oracle."""
    small_cfg = tiny_llama(max_seq_len=128)   # one-chip ceiling: 128
    big_cfg = tiny_llama(max_seq_len=2048)    # same arch, same weights
    params = init_params(jax.random.key(7), small_cfg)

    long_doc = (
        "Chính phủ ban hành nghị định mới về phát triển hạ tầng giao thông "
        "và chuyển đổi số tại đồng bằng sông Cửu Long. " * 6
    )  # ~700 bytes >> 128

    long = LongContextBackend(
        model_config=small_cfg, mesh=mesh, params=params, max_new_tokens=12,
        max_total_tokens=2048,
        decode_kernel=False,
    )
    oracle = TpuBackend(
        model_config=big_cfg, params=params, batch_size=2, max_new_tokens=12,
        flash=False,
    )
    got = long.generate([long_doc])
    expect = oracle.generate([long_doc])
    assert got == expect
    # and the one-chip engine really would have truncated this prompt
    assert len(long_doc.encode()) > small_cfg.max_seq_len


def test_truncated_strategy_untruncated_via_long_backend(mesh):
    """The reference's truncated strategy (16k cut) becomes a full-document
    one-shot summarizer when handed the long backend."""
    from vnsum_tpu.strategies.truncated import TruncatedStrategy

    cfg = tiny_llama(max_seq_len=128)
    params = init_params(jax.random.key(1), cfg)
    long = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, max_new_tokens=8,
        max_total_tokens=4096,
        decode_kernel=False,
    )
    st = TruncatedStrategy(long, max_context=4096, max_new_tokens=8)
    doc = "Báo cáo kinh tế xã hội sáu tháng đầu năm cho thấy nhiều tín hiệu tích cực. " * 10
    res = st.summarize(doc)
    assert isinstance(res.summary, str)
    assert res.num_chunks == 1


def test_batch_grouping_and_config_max_new(mesh):
    """Prompts group into batch_size rows with per-group buckets (one giant
    longest-prompt batch would OOM at real scale), and config.max_new_tokens
    is honored like TpuBackend."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(2), cfg)
    be = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, batch_size=2,
        max_new_tokens=16, max_total_tokens=2048,
        decode_kernel=False,
    )
    prompts = ["a " * n for n in (4, 300, 8, 280, 2)]
    outs = be.generate(prompts)
    assert len(outs) == 5
    # short prompts bucket separately from long ones: at least two S buckets
    assert len({k[1] for k in be._fns}) >= 2
    # per-prompt order preserved
    singles = [be.generate([p])[0] for p in prompts]
    assert outs == singles

    short = be.generate(
        ["một văn bản"], config=GenerationConfig(max_new_tokens=4)
    )[0]
    longer = be.generate(
        ["một văn bản"], config=GenerationConfig(max_new_tokens=16)
    )[0]
    assert len(short.encode()) <= len(longer.encode())


def test_long_backend_sampled_seed_replay(mesh):
    from vnsum_tpu.core.config import GenerationConfig

    cfg = tiny_llama(max_seq_len=512)
    params = init_params(jax.random.key(5), cfg)

    def fresh():
        return LongContextBackend(
            model_config=cfg, mesh=mesh, params=params, batch_size=2,
            max_new_tokens=8, max_total_tokens=512,
            decode_kernel=False,
        )

    gen = GenerationConfig(temperature=1.0, seed=4, max_new_tokens=8)
    a = fresh()
    a1 = a.generate(["văn bản"], config=gen)
    a2 = a.generate(["văn bản"], config=gen)
    assert a1 != a2  # fresh randomness per dispatch
    b = fresh()
    assert b.generate(["văn bản"], config=gen) == a1  # same-seed replay
    assert b.generate(["văn bản"], config=gen) == a2


def test_pipeline_long_context_truncated_untruncated(tmp_path):
    """--long-context end to end: the pipeline's truncated approach runs
    full documents PAST the one-chip ceiling through the seq-sharded
    backend (models registry 'tiny' has max_seq_len=256)."""
    from vnsum_tpu.core.config import PipelineConfig
    from vnsum_tpu.data.synthesize import synthesize_corpus
    from vnsum_tpu.pipeline.runner import PipelineRunner

    synthesize_corpus(
        tmp_path / "c", n_docs=2, tokens_per_doc=150, summary_tokens=30,
        seed=4,
    )  # ~150 words ≈ 900+ bytes per doc >> 256
    cfg = PipelineConfig(
        approach="truncated",
        models=["tiny"],
        backend="tpu",
        long_context=True,
        mesh_shape={"data": 2, "seq": 4},
        max_context=2048,
        max_new_tokens=8,
        batch_size=2,
        docs_dir=str(tmp_path / "c/doc"),
        summary_dir=str(tmp_path / "c/summary"),
        generated_summaries_dir=str(tmp_path / "gen"),
        results_dir=str(tmp_path / "results"),
        logs_dir=str(tmp_path / "logs"),
    )
    runner = PipelineRunner(cfg)
    # off-chip: the dense decode partial, by name (the engine refuses a
    # platform other than tpu otherwise)
    runner.backend_factory = lambda model: runner._default_backend_factory(
        model, decode_kernel=False
    )
    results = runner.run()
    rec = results.summarization["tiny"]
    assert rec["successful"] == 2 and rec["failed"] == 0
    # docs really exceeded the one-chip limit
    for p in (tmp_path / "c/doc").glob("*.txt"):
        assert len(p.read_text(encoding="utf-8").encode()) > 256


def test_long_context_config_validation():
    import pytest as _pytest

    from vnsum_tpu.core.config import PipelineConfig

    with _pytest.raises(ValueError, match="seq axis"):
        PipelineConfig(long_context=True, mesh_shape={"data": 2})
    with _pytest.raises(ValueError, match="backend='tpu'"):
        PipelineConfig(long_context=True, backend="fake",
                       mesh_shape={"seq": 4})


def test_long_context_int8_weights_and_cache(mesh):
    """int8 weights + int8 prefill cache run end to end, and the quantized
    sharded-cache decode attention stays numerically close to the fp path
    (per-vector int8 is ~1/127 relative error)."""
    import jax.numpy as jnp

    from vnsum_tpu.backend.long_context import (
        make_long_decode_attention,
        long_prefill,
        quantize_prefill_cache,
    )
    from vnsum_tpu.models.llama import init_kv_cache

    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(9), cfg)

    # numerical check: same prefill cache, fp vs int8, one decode-attention
    B, S = 2, 512
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, size=(B, S)).astype(np.int32)
    pad = jnp.asarray(np.array([0, 50], dtype=np.int32))
    _, cache = long_prefill(params, cfg, jnp.asarray(tokens), pad, mesh)

    q = jnp.asarray(
        rng.standard_normal((B, 1, cfg.n_heads, cfg.head_dim)), jnp.float32
    )
    decode_cache = init_kv_cache(cfg, B, 8)
    t = jnp.int32(0)
    attn_fp = make_long_decode_attention(mesh, cache, pad, cfg.q_per_kv)
    attn_q8 = make_long_decode_attention(
        mesh, quantize_prefill_cache(cache), pad, cfg.q_per_kv
    )
    out_fp = np.asarray(attn_fp(q, decode_cache, jnp.int32(0), t))
    out_q8 = np.asarray(attn_q8(q, decode_cache, jnp.int32(0), t))
    np.testing.assert_allclose(out_fp, out_q8, atol=0.05, rtol=0.05)

    # and the full int8 program runs end to end
    q8 = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, batch_size=2,
        max_new_tokens=12, max_total_tokens=2048,
        quantize=True, quantize_kv=True,
        decode_kernel=False,
    )
    doc = "Hội nghị thường niên về chuyển đổi năng lượng tái tạo. " * 9
    outs = q8.generate([doc])
    assert len(outs) == 1 and isinstance(outs[0], str)


def test_decode_kernel_path_greedy_parity(mesh):
    """VERDICT r3 #5: the kernelized shard-local decode (stacked-cache
    Pallas kernel per shard + LSE merge) must reproduce the dense engine's
    greedy outputs exactly — fp and int8 cache variants both run."""
    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(3), cfg)
    dense = TpuBackend(
        model_config=cfg, params=params, batch_size=4, max_new_tokens=16,
        flash=False,
    )
    kernel_long = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, max_new_tokens=16,
        max_total_tokens=2048, decode_kernel=True, interpret=True,
    )
    assert kernel_long.generate(PROMPTS) == dense.generate(PROMPTS)


def test_decode_kernel_partial_matches_dense_partial(mesh):
    """Same frozen prefill cache, kernel vs einsum shard-local partials —
    the merged attention outputs must agree to fp tolerance (fp cache) and
    int8 tolerance (quantized cache)."""
    import jax.numpy as jnp

    from vnsum_tpu.backend.long_context import (
        long_prefill,
        make_long_decode_attention,
        quantize_prefill_cache,
    )
    from vnsum_tpu.models.llama import init_kv_cache

    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(21), cfg)
    B, S = 2, 512
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 256, size=(B, S)).astype(np.int32)
    pad = jnp.asarray(np.array([0, 70], dtype=np.int32))
    _, cache = long_prefill(params, cfg, jnp.asarray(tokens), pad, mesh)

    q = jnp.asarray(
        rng.standard_normal((B, 1, cfg.n_heads, cfg.head_dim)), jnp.float32
    )
    decode_cache = init_kv_cache(cfg, B, 8)
    t = jnp.int32(0)
    for prep, tol in ((lambda c: c, 2e-5), (quantize_prefill_cache, 2e-5)):
        pc = prep(cache)
        dense_attn = make_long_decode_attention(
            mesh, pc, pad, cfg.q_per_kv, decode_kernel=False
        )
        kernel_attn = make_long_decode_attention(
            mesh, pc, pad, cfg.q_per_kv, decode_kernel=True, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(dense_attn(q, decode_cache, jnp.int32(1), t)),
            np.asarray(kernel_attn(q, decode_cache, jnp.int32(1), t)),
            rtol=tol, atol=tol,
        )


def test_long_backend_rejects_budget_exceeding_context(mesh):
    cfg = tiny_llama(max_seq_len=512)
    params = init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="max_new_tokens"):
        LongContextBackend(
            model_config=cfg, mesh=mesh, params=params,
            max_new_tokens=512, max_total_tokens=512,
            decode_kernel=False,
        )
    be = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params,
        max_new_tokens=8, max_total_tokens=512,
        decode_kernel=False,
    )
    with pytest.raises(ValueError, match="max_new_tokens"):
        be.generate(["x"], max_new_tokens=600)


def test_greedy_parity_with_model_axis_active():
    """TP x SP composition: heads sharded over `model` AND sequence over
    `seq` must still match the dense single-device engine bit-for-bit."""
    mesh = make_mesh({"data": 1, "model": 2, "seq": 4}, platform="cpu")
    cfg = tiny_llama(max_seq_len=2048)
    params = init_params(jax.random.key(13), cfg)
    dense = TpuBackend(
        model_config=cfg, params=params, batch_size=2, max_new_tokens=12,
        flash=False,
    )
    long = LongContextBackend(
        model_config=cfg, mesh=mesh, params=params, batch_size=2,
        max_new_tokens=12, max_total_tokens=2048,
        decode_kernel=False,
    )
    assert long.generate(PROMPTS) == dense.generate(PROMPTS)
