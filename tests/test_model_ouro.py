"""A dense stack LOOPED over its weights (``LlamaConfig.loop_passes``:
Ouro-2.6B) on the CPU at a tiny size: three passes over two layers, sandwich
norms, a KV head a query head, the final norm after every pass and keys and
values of their own for every (pass, layer), against the plain reference
``benchmarks/reference_ouro.py`` — through ``forward``, through the engine's
chunked prefill and decode over the ``T * L``-layer cache, and through
every other entry of the dense family."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ouro as ref
from family_harness import (
    reference as jitted,
    reference_of,
    rel as _distance,
    through_the_engine,
)
from vnsum_tpu.backend.engine import TpuBackend, trim_to_eos
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models import MODEL_REGISTRY, llama, quant
from vnsum_tpu.models.family import family_of

L, T = 2, 3
S, NEW, CHUNK = 256, 4, 128    # a bucket of two prefill chunks
SIZES = {"total_ut_steps": T, "early_exit_threshold": 1,
         "rms_norm_eps": 1e-6, "rope_theta": 10_000.0}
NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_ffw_norm")


def _varied(params: dict) -> dict:
    """Weights a fault cannot hide behind: every norm's weight drawn (a
    fresh tree's are all ones), the two output norms small and the
    embedding large, so that the stream keeps what the tokens were and a
    greedy continuation is no one token repeated."""
    key = jax.random.key(7)

    def drawn(name, i, scale=1.0):
        w = params["layers"][name] if name in NORMS else params[name]
        return scale * (1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), w.shape)).astype(w.dtype)

    layers = dict(params["layers"])
    for i, name in enumerate(NORMS):
        layers[name] = drawn(name, i, 0.05 if name.startswith("post") else 1.0)
    return dict(params, layers=layers, embed=params["embed"] * 20,
                final_norm=drawn("final_norm", 9))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_ouro(max_seq_len=512)
    assert (cfg.n_layers, cfg.loop_passes) == (L, T)
    return cfg, _varied(llama.init_params(jax.random.key(1), cfg))


def _ids(n: int, seed: int = 0) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.key(seed + n), (n,), 3, 250))


# -- the config ----------------------------------------------------------------


def test_published_config():
    cfg = llama.ouro_2p6b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate, cfg.vocab_size, cfg.loop_passes) == (
        2048, 48, 16, 16, 128, 5632, 49_152, 4)
    assert cfg.q_per_kv == 1 and cfg.sandwich_norms and not cfg.qk_norm
    assert not cfg.tie_embeddings and not cfg.norm_plus_one
    assert cfg.rope_theta == 1e6 and not cfg.use_llama3_rope_scaling
    assert cfg.norm_eps == 1e-6 and cfg.act == "silu"
    assert llama.cache_layers(cfg) == 192
    assert MODEL_REGISTRY["ouro-2.6b"](n_layers=12) == llama.ouro_2p6b(
        n_layers=12)
    assert MODEL_REGISTRY["tiny-ouro"]() == llama.tiny_ouro()
    # the parameter count of the published model, a byte a parameter
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert 48 * layer + 2 * 49_152 * 2048 == 2_667_577_344


def test_a_threshold_under_one_is_refused_by_what_it_would_take():
    with pytest.raises(NotImplementedError, match="adaptive exit"):
        llama.ouro_2p6b(early_exit_threshold=0.9)
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        ref.forward({}, jnp.zeros((4,), jnp.int32),
                    {**SIZES, "early_exit_threshold": 0.5})


def test_one_function_counts_the_caches_layers(tiny):
    """The cache, the seam, the pool and the per-layer tables take their
    layer count from ``cache_layers``: weights' layers x passes."""
    cfg, params = tiny
    fam = family_of(cfg)
    assert fam is llama.FAMILY
    assert llama.cache_layers(cfg) == fam.attention_layers(cfg) == T * L
    assert params["layers"]["wq"].shape[0] == L
    for quantized in (False, True):
        cache = llama.init_kv_cache(cfg, 2, 16, quantized=quantized)
        assert {a.shape[0] for a in cache.values()} == {T * L}
    windowed = llama.tiny_ouro(sliding_window=8,
                               layer_is_global=(True, False))
    assert fam.layer_windows(windowed) == (0, 8) * T
    assert fam.layer_windows(cfg) is None
    be = TpuBackend(model_config=cfg, params=params, tokenizer="byte",
                    batch_size=2, max_new_tokens=NEW, flash=False,
                    cache_blocks=4, cache_block_tokens=64)
    assert be.prefix_cache.store.pool["k"].shape[1] == T * L
    # a plain stack's are its layers, as they were
    plain = llama.tiny_llama()
    assert llama.cache_layers(plain) == fam.attention_layers(plain) == 2


def test_the_tree_holds_the_exit_gate_and_quantization_leaves_it(tiny):
    cfg, params = tiny
    assert params["exit_gate"]["w"].shape == (cfg.dim,)
    assert params["exit_gate"]["w"].dtype == params["exit_gate"]["b"].dtype \
        == jnp.float32
    assert "exit_gate" not in llama.init_params(
        jax.random.key(0), llama.tiny_llama())
    q = quant.quantize_params(params)
    drawn = quant.init_params_quantized(jax.random.key(3), cfg)
    for tree in (q, drawn):
        assert jax.tree.structure(tree) == jax.tree.structure(q)
        assert tree["exit_gate"]["w"].dtype == jnp.float32
        for name in NORMS:       # the four norms stay as they are
            assert not isinstance(tree["layers"][name], dict)
        assert tree["layers"]["wq"]["q"].dtype == jnp.int8
    assert q["exit_gate"] is params["exit_gate"]
    back = quant.dequantize_params(q)
    assert jax.tree.structure(back) == jax.tree.structure(params)


# -- forward against the reference ---------------------------------------------


def test_forward_agrees_with_the_reference(tiny):
    """The whole sequence at once, with a cache and without one
    (``forward_train``), and every (pass, layer)'s keys in the cache."""
    cfg, params = tiny
    n = 40
    toks = jnp.asarray(_ids(n))[None]
    want = jitted(ref, SIZES)(params, toks[0])
    cache = llama.init_kv_cache(cfg, 1, n)
    mask = llama.prefill_attention_mask(jnp.zeros((1,), jnp.int32), n, n)
    with jax.default_matmul_precision("highest"):
        got, cache = llama.forward(
            params, cfg, toks, jnp.arange(n)[None], cache, 0, mask)
        free = llama.forward_train(params, cfg, toks, remat=False)
    assert _distance(got[0], want["logits"]) < 1e-5
    assert _distance(free[0], want["logits"]) < 1e-5
    for layer in range(T * L):
        assert _distance(cache["k"][layer, 0].transpose(1, 0, 2),
                         want["k"][layer]) < 1e-5
        assert _distance(cache["v"][layer, 0].transpose(1, 0, 2),
                         want["v"][layer]) < 1e-5
    # the passes are not copies of each other: a cache indexed by the
    # layer alone would hold the last pass's
    assert _distance(want["k"][0], want["k"][(T - 1) * L]) > 0.1


def _logits_and_keys(cfg, params, n: int, **kw):
    """A prompt of ``n`` tokens through the engine's chunked prefill (two
    chunks of 128 in the 256 bucket, left pad 256 - n) and ``NEW`` forced
    decode steps: (logits [NEW + 1, V], the cache's dequantized keys
    {cache layer: [n + NEW, KV, hd]}), beside the reference's. The pad is an
    argument of the program: one engine and one compile a path, whatever
    ``n`` (``fresh=True``: an engine of the test's own)."""
    ids = _ids(n + NEW)
    if kw.setdefault("interpret", False):
        kw.setdefault("quantize_kv", False)   # "auto" follows the kernels
    else:
        kw.setdefault("flash", False)         # the dense path, by name
        kw.setdefault("quantize_kv", "auto")
    with jax.default_matmul_precision("highest"):
        be, got, state = through_the_engine(
            cfg, params, ids.tolist(), n, S, batch_size=2,
            max_new_tokens=NEW, prefill_chunk_tokens=CHUNK, **kw)
    cache = state["cache"]
    assert cache["k"].shape[0] == T * L

    def keys(layer):
        rows = cache["k"][layer, 0, :, S - n:S + NEW].astype(np.float64)
        if "ks" in cache:
            rows = rows * cache["ks"][layer, 0, :, S - n:S + NEW][..., None]
        return rows.swapaxes(0, 1)

    return be, ids, got, keys


# cache layers: the first layer's first pass, its second, its last, and
# the last layer's last
_SEEN = (0, L, (T - 1) * L, T * L - 1)


@pytest.mark.parametrize("n", [S, S - 1, S - (CHUNK - 1), S - (CHUNK + 28)],
                         ids=["pad0", "pad1", "pad-chunk-less-one",
                              "pad-over-a-chunk"])
@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_chunked_prefill_and_decode_agree_with_the_reference(tiny, n, path):
    """MORE THAN ONE prefill chunk a row, then decode steps, through the
    ``T * L``-layer cache: logits a position and the keys of four cache
    layers position by position. A cache indexed by the layer alone is
    right inside one chunk and wrong from the second. ``kernels`` runs
    both GQA kernels interpreted at one query head a KV head."""
    cfg, params = tiny
    be, ids, got, keys = _logits_and_keys(
        cfg, params, n, **({"interpret": True} if path == "kernels" else {}))
    want = reference_of(ref, SIZES, params, ids, last=NEW + 1, keep=_SEEN)
    for row in range(NEW + 1):
        assert _distance(got[row], want["logits"][row]) < 1e-5
    for layer in _SEEN:
        mine, theirs = keys(layer), np.asarray(want["k"][layer])
        worst = max(_distance(mine[i], theirs[i]) for i in range(n + NEW))
        assert worst < 1e-5, (layer, worst)
    (paths,) = be.stats.attention_paths.values()
    assert set(paths.values()) == {"kernel" if path == "kernels" else "dense"}


def test_the_int8_cache_is_within_its_own_tolerance(tiny):
    cfg, params = tiny
    n = S - 28
    _, ids, got, keys = _logits_and_keys(
        cfg, params, n, interpret=True, quantize_kv=True)
    want = reference_of(ref, SIZES, params, ids, last=NEW + 1, keep=_SEEN)
    errors = [_distance(got[r], want["logits"][r]) for r in range(NEW + 1)]
    assert 1e-5 < max(errors) < 0.05
    for layer in _SEEN:   # a value in 127 steps of its row's largest
        assert 1e-4 < _distance(keys(layer), want["k"][layer]) < 0.03


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_fault_is_another_model(tiny, fault):
    """Each named departure moves the logits or the cached keys a hundred
    times further than the program is from the reference. A rotary re-based
    a pass moves no logit (a common shift of a pass's positions cancels in
    every q . k): only the LATER passes' cached keys show it."""
    cfg, params = tiny
    n = S - 28
    _, ids, got, keys = _logits_and_keys(cfg, params, n)
    wrong = jitted(ref, SIZES, last=NEW + 1, keep=(0, (T - 1) * L),
                   faults=(fault,))(params, jnp.asarray(ids))
    logits = max(_distance(got[r], wrong["logits"][r]) for r in range(NEW + 1))
    first = _distance(keys(0), wrong["k"][0])
    last = (_distance(keys((T - 1) * L), wrong["k"][(T - 1) * L])
            if (T - 1) * L in wrong["k"] else np.inf)
    if fault == "rotary_rebased":
        assert logits < 1e-5 and first < 1e-5 and last > 0.1
    else:
        assert logits > 1e-3, (fault, logits, first, last)


def test_decode_steps_that_read_one_passes_keys_are_another_model(
        tiny, monkeypatch):
    """The shortcut the paper discusses and this repo does NOT run: decode
    steps that read the LAST pass's keys and values for every pass (a
    quarter of the cache). The prefill is the model's; every decode row
    leaves the reference."""
    cfg, params = tiny
    attend = llama._cache_attention

    def last_pass_for_all(q, cache, layer_idx, *a, **kw):
        if q.shape[1] == 1:   # a decode step
            layer_idx = (T - 1) * L + layer_idx % L
        return attend(q, cache, layer_idx, *a, **kw)

    monkeypatch.setattr(llama, "_cache_attention", last_pass_for_all)
    n = S - 28
    _, ids, got, _ = _logits_and_keys(cfg, params, n, fresh=True)
    want = reference_of(ref, SIZES, params, ids, last=NEW + 1, keep=())
    errors = [_distance(got[r], want["logits"][r]) for r in range(NEW + 1)]
    assert errors[0] < 1e-5
    assert min(errors[1:]) > 1e-3


def test_one_pass_traces_what_a_plain_stack_traced(tiny):
    """``loop_passes`` 1 adds nothing to the program: no scan over passes,
    no ``loop_norm`` (tests/test_one_shot_programs_pinned.py holds the dense
    programs to their hashes); a looped stack is ONE more loop whatever the
    number of passes, not a copy of the stack a pass."""
    def text(cfg):
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.key(0), cfg))
        toks = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1, 8))
        mask = jax.ShapeDtypeStruct((1, 8, 8), jnp.bool_)
        return str(jax.make_jaxpr(
            lambda p, t, c, m: llama.forward(p, cfg, t, t, c, 0, m))(
                params, toks, cache, mask))

    plain = text(llama.tiny_llama(sandwich_norms=True))
    assert plain.count("scan[") == 1
    for passes in (2, 3, 4):
        looped = text(llama.tiny_llama(sandwich_norms=True,
                                       loop_passes=passes))
        assert looped.count("scan[") == 2
        assert abs(len(looped.split("\n")) - len(plain.split("\n"))) < 40


# -- the counters ----------------------------------------------------------------


def test_counters_run_over_every_pass_and_layer(tiny):
    """The prefill's cells by class and the decode kernel's key blocks over
    all ``T * L`` cache layers, and the scores the prefill kernel computed
    against those its attention needs (``Family.prefill_counts``)."""
    from vnsum_tpu.ops.flash_attention import prefill_block_classes

    cfg, params = tiny
    be = TpuBackend(model_config=cfg, params=params, tokenizer="byte",
                    batch_size=2, max_new_tokens=NEW, interpret=True,
                    prefill_chunk_tokens=CHUNK)
    packed = []
    pack = be._pack_group
    be._pack_group = lambda *a: packed.append(pack(*a)) or packed[-1]
    be.generate(["một hai ba bốn " * 12, "năm sáu " * 9], max_new_tokens=NEW)
    (_, pads, B, bucket), = packed
    got = dict(be.stats.prefill_blocks)
    C = bucket + NEW
    want = {}
    for lo in range(0, bucket, CHUNK):
        for name, k in prefill_block_classes(
                pads, CHUNK, C, lo, 0, 1, cfg.head_dim).items():
            want[name] = want.get(name, 0) + k * T * L
    assert {k: got[k] for k in want} == want
    heads = cfg.n_heads * T * L
    real = [bucket - int(p) for p in pads]
    assert got["scores_needed"] == heads * sum(k * (k + 1) // 2 for k in real)
    assert got["scores_computed"] >= got["scores_needed"]
    assert got["scores_computed"] % heads == 0
    # a plain stack counts none of the two
    assert llama.FAMILY.prefill_counts(
        llama.tiny_llama(), pads, [(0, bucket)], C) == {}
    # the decode kernel walks T * L layers a step
    assert be.stats.decode_kv_blocks_total % (T * L) == 0
    assert be.stats.decode_kv_blocks_total > 0


# -- the dense family's other entries ----------------------------------------------

HEADER = "tieu de chung cua cac tai lieu dai: " * 4
PROMPTS = [HEADER + tail for tail in
           ("noi dung rieng mot", "hai ba bon nam", "va mot cau khac han")]
N_OUT = 8


def _backend(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("flash", False)
    return TpuBackend(model_config=cfg, params=params, tokenizer="byte",
                      batch_size=4, max_new_tokens=N_OUT, seed=1,
                      segment_tokens=4, **kw)


@pytest.fixture(scope="module")
def greedy(tiny):
    """The reference's own greedy continuation of each prompt — one full
    forward a token over the sequence so far, the engine's restriction on
    what may be sampled (bytes and EOS) — as text; and the same from the
    reference run ONE pass, which has to differ for the comparison to tell
    a silent single pass."""
    cfg, params = tiny
    tok = _backend(tiny).tok
    # one length for every forward, the longest sequence's: the reference is
    # causal, so tokens after a position change nothing at it
    room = max(len(tok.encode(p, add_bos=True)) for p in PROMPTS) + N_OUT

    def continuation(prompt, faults=()):
        ids, out = tok.encode(prompt, add_bos=True), []
        forward = jitted(ref, SIZES, faults=faults)
        for _ in range(N_OUT):
            seq = ids + out
            padded = jnp.asarray(seq + [tok.pad_id] * (room - len(seq)))
            row = np.array(forward(params, padded)["logits"][len(seq) - 1])
            row[[i for i in range(len(row))
                 if i >= 256 and i != tok.eos_id]] = -np.inf
            out.append(int(row.argmax()))
            if out[-1] == tok.eos_id:
                break
        return tok.decode(trim_to_eos(out, tok.eos_id, tok.pad_id, ())).strip()

    want = [continuation(p) for p in PROMPTS]
    assert len(set(want)) == len(want) and all(len(w) >= 2 for w in want)
    assert [continuation(p, ("one_pass",)) for p in PROMPTS] != want
    return want


def _one_shot(tiny):
    return _backend(tiny, prefill_chunk_tokens=CHUNK).generate(PROMPTS)


def _slot_loop(tiny):
    """Two joins (prefill + adopt), the second at a segment boundary while
    the first rows decode, then segments until every row is done."""
    loop = _backend(tiny).start_slot_loop(4)
    outs = {}
    admitted, rejected = loop.admit([(0, PROMPTS[0], None),
                                     (1, PROMPTS[1], None)])
    assert len(admitted) == 2 and not rejected
    for c in loop.step().completions:
        outs[c.key] = c.text
    admitted, rejected = loop.admit([(2, PROMPTS[2], None)])
    assert len(admitted) == 1 and not rejected
    for _ in range(16):
        for c in loop.step().completions:
            outs[c.key] = c.text
        if not loop.active:
            break
    return [outs[i] for i in range(3)]


def _prefix_cache(tiny):
    """Insert on a cold call, resume from the pool of ``T * L`` layers a
    block on the second; both are the model's."""
    be = _backend(tiny, cache_blocks=32, cache_block_tokens=64)
    cold = be.generate(PROMPTS)
    hits = be.stats.cache_hit_tokens
    warm = be.generate(PROMPTS)
    assert be.stats.cache_hit_tokens > hits
    assert warm == cold
    return warm


def _spec_verify(tiny, greedy):
    """Drafts from a reference text that starts as the answer does, so that
    verify steps accept tokens: per-row fills over every pass's cache."""
    be = _backend(tiny)
    out = be.generate(PROMPTS, config=GenerationConfig(spec_k=3),
                      references=[w[:5] + " khac" for w in greedy])
    assert sum(r.accepted_tokens for r in be.take_spec_report()) > 0
    return out


def _mesh(tiny):
    """Data x model: the cache's heads over ``model``, rows over ``data``."""
    from vnsum_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    mesh = make_mesh({"data": 2, "model": 2, "seq": 1}, platform="cpu")
    be = _backend(tiny, mesh=mesh, prefill_chunk_tokens=CHUNK)
    return be.generate(PROMPTS + PROMPTS[:1])[:3]


@pytest.mark.parametrize("entry", ["one-shot", "slot loop", "prefix cache",
                                   "speculative decoding", "mesh"])
def test_an_entry_runs_every_pass_or_refuses_by_name(tiny, greedy, entry):
    """Every entry of the dense family that takes a looped configuration
    gives the reference's greedy continuation — which the reference run one
    pass does not give —; none runs a single pass silently."""
    got = {"one-shot": _one_shot, "slot loop": _slot_loop,
           "prefix cache": _prefix_cache, "mesh": _mesh,
           "speculative decoding": lambda t: _spec_verify(t, greedy)}[entry](
               tiny)
    assert got == greedy


def test_the_trainer_runs_every_pass(tiny):
    """``forward_train`` loops as ``forward`` does (the cache-free block
    knows no pass; the loop is around it), sharded or not, and a training
    step moves the one set of weights every pass reads."""
    from vnsum_tpu.parallel import make_mesh
    from vnsum_tpu.train.trainer import TrainConfig, Trainer

    cfg, params = tiny
    toks = jnp.asarray(_ids(32)).reshape(2, 16)
    with jax.default_matmul_precision("highest"):
        got = llama.forward_train(params, cfg, toks, remat=True)
    for row in range(2):
        assert _distance(got[row], jitted(ref, SIZES)(
            params, toks[row])["logits"]) < 1e-5
    n = min(len(jax.devices()), 4)
    mesh = make_mesh({"data": n, "model": 1, "seq": 1}, platform="cpu")
    trainer = Trainer(cfg, mesh, TrainConfig(learning_rate=5e-3, remat=False))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (4, 1)) + 7
    losses = [trainer.step(tokens) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_the_long_context_ring_refuses_a_looped_stack_by_name(tiny):
    from vnsum_tpu.backend.long_context import LongContextBackend

    cfg, params = tiny
    with pytest.raises(NotImplementedError) as e:
        LongContextBackend(model_config=cfg, params=params, interpret=True)
    said = str(e.value)
    assert "long-context backend" in said and "looped 3 times" in said
    assert "ONE pass" in said
    # the family still runs it for a plain stack, and refuses nothing else
    assert llama.FAMILY.missing == {}
    assert llama.FAMILY.config_missing(llama.tiny_llama()) == {}
    assert set(llama.FAMILY.config_missing(cfg)) == {"long-context backend"}
    llama.FAMILY.refuse("long-context backend", llama.tiny_llama())
    llama.FAMILY.refuse("long-context backend")      # the family, no config
