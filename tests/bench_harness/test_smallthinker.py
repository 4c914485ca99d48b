"""CPU tests of the benchmark's own parts for the SmallThinker family: the
plain reference against the program, the run-time parity check and the
faults it has to catch, the rooflines against hand-worked numbers, the
readers, the driver's counters, and the configuration file's arithmetic.

Nothing here touches the TPU library at import.
"""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cells, engine_setup  # noqa: E402
from benchmarks import engine_setup_smallthinker as family_setup  # noqa: E402
from benchmarks import roofline_smallthinker as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIG = cells.load_config(BENCH, "smallthinker-21b-l16-int8")
CELL = "smallthinker-21b-l16-int8.offline-mapreduce-8k-moe"
# PowerInfer/SmallThinker-21BA3B-Instruct config.json, as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936, "model_name": "smallthinker_21b_instruct",
}


def _tiny(**kw):
    from vnsum_tpu.models.smallthinker import tiny_smallthinker

    return tiny_smallthinker(**kw)


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(int8):
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_smallthinker as reference
    from vnsum_tpu.models import smallthinker as st
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny()
    params = st.init_params(jax.random.key(5), cfg)
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(6), (60,), 0, cfg.vocab_size)
    want = reference.logits(params, toks, family_setup.sizes_from(cfg))
    got = st.forward_dense(params, cfg, toks[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_prefill_and_decode_agree_with_the_reference(kv):
    """The engine's chunked prefill (two chunks, kernels interpreted, the
    per-layer window) and then decode steps through the cache, against the
    reference's ONE forward, with a prompt longer than the window: float
    weights, so what is left is the cache's own rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_smallthinker as reference
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import smallthinker as st

    cfg = _tiny(max_seq_len=400)
    params = st.init_params(jax.random.key(7), cfg)
    be = TpuBackend(model_config=cfg, tokenizer="byte", params=params,
                    batch_size=1, max_new_tokens=8, interpret=True,
                    quantize_kv=(kv == "int8"), prefill_chunk_tokens=128)
    ids = np.asarray(jax.random.randint(
        jax.random.key(8), (155,), 0, cfg.vocab_size)).tolist()
    assert 150 > 4 * cfg.sliding_window     # far past the window
    got = be.prefill_then_decode_logits(ids[:150], ids[150:], bucket=256)
    want = np.asarray(reference.logits(
        params, jnp.asarray(ids), family_setup.sizes_from(cfg), last=6))
    assert got.shape == want.shape == (6, cfg.vocab_size)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < (1e-5 if kv == "bf16" else 0.02), err
    assert be.stats.attention_paths["logits[B=1,S=256]"] == {
        "prefill": "kernel", "decode": "kernel"}


# -- the run-time parity check -------------------------------------------------


def _int4_kv(x):
    """``models.llama._quantize_kv`` with 4 bits a value: the nearest
    precision below the configured int8 cache."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), -1, keepdims=True), 1e-8) / 7.0
    return (jnp.clip(jnp.round(x32 / scale), -7, 7).astype(jnp.int8),
            scale[..., 0])


def _rehearsal_parity(monkeypatch, faults=(), quantize_kv=None, seed=11):
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import llama

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, seed)
    if quantize_kv is not None:
        monkeypatch.setattr(llama, "_quantize_kv", quantize_kv)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=params, **engine_setup.backend_kwargs(config, rehearsal=True))
    return family_setup.parity_with_reference(
        backend, config, seed, rehearsal=True, faults=faults)


@pytest.mark.parametrize("fault", [
    None, "no_window", "rope_everywhere", "router_after_norm",
    "router_after_attention", "softmax_over_all", "silu"])
def test_parity_check_passes_the_program_and_catches_each_fault(
        fault, monkeypatch):
    """``parity_with_reference`` on a tiny engine with interpreted kernels
    and a prompt past the window: it passes the program as it is (prefill
    and decode steps, every row within the one tolerance, the leading
    layer's cache rows within theirs), and fails when the two stop being
    the same mathematics — the window ignored on window layers, rotary
    applied on the global layers, the router fed the normed input or the
    post-attention stream, softmax over all experts instead of the picked
    ones, SwiGLU for ReGLU."""
    got = _rehearsal_parity(monkeypatch, (fault,) if fault else ())
    assert got["ok"] is (fault is None), got
    assert got["kernel"] is True and got["prompt_tokens"] == 150
    assert got["prompt_tokens"] > got["window"] == 96
    assert got["decode_steps"] == 4 and len(got["errors"]) == 5
    assert got["error"] == max(got["errors"])
    assert len(got["took"]) == 5 and max(got["took"]) <= 8   # 8 layers
    assert got["slots_held"] == got["slots_routed"] == (150 + 4) * 2 * 8
    if fault is None:
        assert got["took"] == [8] * 5
        assert got["error"] < 0.7 * got["tolerance"]
        assert got["kv_error"] < 0.7 * got["kv_tolerance"]
    elif fault == "router_after_norm":
        # at this tiny size a norm only rescales a token's router logits:
        # the picks stay and the two weights move a little
        assert got["error"] > 1.15 * got["tolerance"]
    else:
        assert got["error"] > 1.3 * got["tolerance"]
    # none of these touches the leading layer's keys and values (it reads
    # the embedding alone) but rotary on the global layers, of which it is
    # one
    assert (got["kv_error"] <= got["kv_tolerance"]) is (
        fault != "rope_everywhere")


def test_a_cache_of_four_bits_fails_the_check_of_the_caches_rows(monkeypatch):
    """A cache that rounds keys and values to 4 bits is the nearest
    precision below the configured int8. Every row of logits still passes
    (W8A8 has rounded every row already); the cache's own rows do not: the
    leading layer's read many times their clean distance from the
    reference's keys and values, over ``kv_tolerance``."""
    clean = _rehearsal_parity(monkeypatch)
    got = _rehearsal_parity(monkeypatch, quantize_kv=_int4_kv)
    assert clean["ok"] is True and got["ok"] is False
    assert clean["kv_error"] < 0.7 * clean["kv_tolerance"]
    assert got["kv_error"] > 1.3 * got["kv_tolerance"]


def test_a_tie_is_broken_the_programs_way_and_nothing_else_is():
    """``ties_broken_their_way`` on a hand-worked router row: 6 experts,
    2 picks. Logits (2.0, 1.0, 0.97, 0.5, 0.2, -1.0): the reference picks
    experts 0 and 1."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_smallthinker as reference

    row = jnp.asarray([[2.0, 1.0, 0.97, 0.5, 0.2, -1.0]])
    ids, weights = reference.route(row, 2)
    assert sorted(np.asarray(ids[0])) == [0, 1]
    e = np.exp([2.0, 1.0])
    np.testing.assert_allclose(np.asarray(weights[0]), e / e.sum(), rtol=1e-6)

    def rightful(picks, band):
        return bool(reference.ties_broken_their_way(
            row, jnp.asarray([picks]), band)[0])

    assert rightful([0, 1], 0.0) and rightful([1, 0], 0.0)   # its own picks
    # expert 2 scores within 0.03 of expert 1: a tie inside a band of 0.05
    assert rightful([0, 2], 0.05) and not rightful([0, 2], 0.01)
    # never rightful at such a band: the best expert skipped, a far one
    # taken, the same expert twice
    assert not rightful([1, 2], 0.05)
    assert not rightful([0, 3], 0.05)
    assert not rightful([0, 0], 0.05)
    # the weights of picks taken their way are the softmax over THOSE logits
    among = jnp.asarray([[True, False, True, False, False, False]])
    ids, weights = reference.route(row, 2, among=among)
    assert sorted(np.asarray(ids[0])) == [0, 2]
    e = np.exp([2.0, 0.97])
    np.testing.assert_allclose(np.asarray(weights[0]), e / e.sum(), rtol=1e-6)


def test_one_broken_row_fails_the_check(monkeypatch):
    """Every row is held, not a quantile of them."""
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend

    real = TpuBackend.prefill_then_decode_logits

    def one_row_wrong(self, *a, **kw):
        logits, state = real(self, *a, **kw)
        logits = np.array(logits)
        logits[3] = logits[3][::-1]
        return logits, state

    monkeypatch.setattr(TpuBackend, "prefill_then_decode_logits",
                        one_row_wrong)
    got = _rehearsal_parity(monkeypatch)
    assert sorted(got["errors"])[-2] <= got["tolerance"]
    assert got["error"] > got["tolerance"] and got["ok"] is False


def test_a_prompt_inside_the_window_is_refused(monkeypatch):
    """A parity prompt that never leaves the window would pass a program
    that ignores it."""
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = 90
    monkeypatch.setattr(sys.modules[__name__], "CONFIG", config)
    with pytest.raises(ValueError, match="never leaves the window"):
        _rehearsal_parity(monkeypatch)


def test_reference_refuses_an_unknown_fault():
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_smallthinker as reference
    from vnsum_tpu.models import smallthinker as st

    cfg = _tiny(n_layers=4)
    params = st.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, jnp.arange(8), family_setup.sizes_from(cfg),
                         faults=("no_such_fault",))


# -- the configuration file -----------------------------------------------------


def test_model_config_builds_the_files_cut_of_the_published_model():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_routed_experts, cfg.n_held,
            cfg.expert_offset) == (16, 64, 64, 0)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate, cfg.num_experts_per_tok, cfg.vocab_size,
            cfg.sliding_window) == (2560, 28, 4, 128, 768, 6, 151936, 4096)
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 4
    assert cfg.max_seq_len == 8448 and cfg.act == "relu"
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG, False)


def test_config_file_keeps_every_published_width_and_states_its_cut():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 52}
    assert c["num_hidden_layers"] == 16 and 16 % 4 == 0   # whole periods
    for key, value in PUBLISHED.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert entry["source"] == c["source"] and "SmallThinker-21BA3B" in c["source"]
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "setup_module"):
        assert c[key], key
    assert "router_input" in c["assumed"] and "secondary_experts" in c["assumed"]
    assert "three times" in c["deployment"]
    assert c["engine"]["batch"] in (24, 12, 8)
    assert c["engine"]["prefill_chunk_tokens"] in (2048, 1024)
    parity = c["reference"]["parity"]
    assert parity["prompt_tokens"] >= 4096 + 512 and parity["bucket"] == 8192
    assert parity["decode_steps"] == 8


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models.quant import init_params_quantized
    from vnsum_tpu.models.smallthinker import init_cache

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b, layers = CONFIG["bytes"], tree["layers"]
    experts = sum(size(layers[n]) for n in ("we_gate", "we_up", "we_down"))
    assert b["experts_a_layer"] == experts // 16 == 64 * b["one_expert"]
    assert b["attention_a_layer"] == sum(
        size(layers[n]) for n in ("wq", "wk", "wv", "wo")) // 16
    assert b["router_a_layer"] == size(layers["router"]) // 16
    assert b["layer"] == size(layers) // 16
    assert b["layers_16"] == size(layers)
    assert b["embedding_and_head"] == size(tree["embed"]) + size(tree["lm_head"])
    assert b["weights"] == size(tree)
    # the issue's parameter arithmetic: 398.6 M a layer, int8
    s = family_setup.sizes_of(CONFIG, False)
    assert roof.attention_params(s) == 20_971_520
    assert roof.expert_params(s) == 5_898_240 and roof.router_params(s) == 163_840
    assert roof.attention_params(s) + roof.router_params(s) \
        + 64 * roof.expert_params(s) == 398_622_720
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 8448, quantized=True))
    kv = sum(size(cache[n]) for n in ("k", "v", "ks", "vs"))
    assert kv == b["kv_cache_a_row"] == 16 * 4 * 8448 * (2 * 128 + 8)


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
EXPERTS = {"slots_routed": 1000, "slots_held": 1000, "decode_touched": 20480,
           "decode_layer_steps": 4096}        # 5 experts a step and layer


def test_causal_pairs_and_decode_context_by_hand():
    assert roof.causal_pairs(4) == 10 and roof.causal_pairs(4, 8) == 10
    # window 2 over 4 tokens: 1 + 2 + 2 + 2
    assert roof.causal_pairs(4, 2) == 7
    assert roof.causal_pairs(8000, 4096) == 4096 * 4097 // 2 + 3904 * 4096
    tiny = {"num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
            "sliding_window_size": 5}
    # a row of 3 tokens, 4 steps: a global layer reads 4 + 5 + 6 + 7 slots,
    # a window layer min(., 5): 4 + 5 + 5 + 5
    assert roof.decode_context(tiny, [3], 4) == 22 + 3 * 19
    # a row already past the window reads the window every step
    assert roof.decode_context(tiny, [9], 2) == (10 + 11) + 3 * 10
    assert roof.window_layers(SIZES) == 12


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [8000, 5000], 256
    k = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    pairs = lambda n: (4 * n * (n + 1) // 2  # noqa: E731
                       + 12 * (4096 * 4097 // 2 + (n - 4096) * 4096))
    ops = 4 * 28 * 128 * (pairs(8000) + pairs(5000))
    assert roof.prefill_attention_ops(SIZES, lens) == ops
    assert k["flash_prefill_attention"] == {
        "seconds": pytest.approx(ops / 197e12), "bound": "compute"}
    # decode: both rows are past the window from the first step
    ctx = sum(4 * (steps * (n + 1) + steps * (steps - 1) // 2)
              + 12 * steps * 4096 for n in lens)
    assert roof.decode_context(SIZES, lens, steps) == ctx
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert dec["bytes"] == 4 * (2 * 128 + 8) * ctx
    assert dec["ops"] == 4 * 28 * 128 * ctx
    assert k["flash_decode_attention"] == {
        "seconds": pytest.approx(dec["bytes"] / 819e9), "bound": "memory"}
    # experts: six picks a token in prefill; decode reads 5 experts a layer
    # and step, each 5.9 MB
    ex = roof.expert_matmul(SIZES, EXPERTS, 13000, 2, steps, 1)
    assert ex["prefill_ops"] == 2 * 5_898_240 * 6 * 16 * 13000
    assert ex["decode_ops"] == 2 * 5_898_240 * 6 * 16 * 2 * steps
    assert ex["decode_bytes"] == pytest.approx(5_898_240 * 5 * 16 * steps)
    assert k["expert_grouped_matmul"]["seconds"] == pytest.approx(
        ex["prefill_ops"] / 393e12 + ex["decode_bytes"] / 819e9)
    assert k["expert_grouped_matmul"]["bound"] == "compute, then memory"
    # a bf16 cache reads no scales
    assert roof.decode_attention(SIZES, lens, steps, 2)["bytes"] == \
        4 * 2 * 128 * 2 * ctx
    # no counter, no decode bytes
    assert roof.touched(SIZES, {"decode_layer_steps": 0}, steps) == 0.0


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [8000, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    token = 16 * (20_971_520 + 163_840 + 6 * 5_898_240)
    head = 2560 * 151_936
    assert d["prefill_matmul_ops"] == pytest.approx(
        2 * token * 13000 + 2 * head * 2)
    k = d["kernels"]
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12
        + k["flash_prefill_attention"]["seconds"])
    fixed = 16 * (20_971_520 + 163_840) + head
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert d["decode_bytes"] == pytest.approx(
        fixed * steps + 5_898_240 * 5 * 16 * steps + dec["bytes"])
    assert d["decode_s"] == pytest.approx(d["decode_bytes"] / 819e9)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # half the picks held here: half the expert operations of a token
    half = dict(EXPERTS, slots_held=500)
    assert roof.layer_params_a_token(SIZES, roof.held_share(half)) == \
        20_971_520 + 163_840 + 3 * 5_898_240


# -- the readers --------------------------------------------------------------------


def _raw():
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": {**EXPERTS, "decode_reads_possible": 4096 * 64,
                               "tokens": [[25] * 63 + [50]] * 16}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["flash_prefill_attention", 2.5],
                                 ["expert_grouped_matmul", 0.5],
                                 ["fusion.7", 0.3]]},
        "traced": {"dispatches": [
            {"prompt_lens": [8000, 5000], "steps": 256, "experts": EXPERTS},
            {"prompt_lens": [2000], "steps": 256, "experts": EXPERTS}]},
    }


def _read(name, raw):
    spec = cells.load_layer_metric(name)
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_readers_on_a_known_record():
    raw = _raw()
    least = roof.kernel_least_seconds(
        SIZES, PRECISION, PEAKS, EXPERTS, [8000, 5000], 256)
    # one whole execution in the stretch: the first dispatch alone counts
    assert _read("swa_prefill_attention_roofline", raw) == pytest.approx(
        100 * least["flash_prefill_attention"]["seconds"] / 2.5)
    assert _read("moe_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 0.5)
    # the reducer kept no row for the decode kernel: both are left out
    assert _read("swa_decode_attention_roofline", raw) is None
    assert _read("swa_attention_busy_share", raw) is None
    raw["trace"]["device_ops"].append(["flash_decode_attention", 1.5])
    assert _read("swa_attention_busy_share", raw) == pytest.approx(40.0)
    assert _read("swa_decode_attention_roofline", raw) == pytest.approx(
        100 * least["flash_decode_attention"]["seconds"] / 1.5)
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(5.0)
    # a loop's self seconds are events the profiler lost inside it: counted
    # against the kernel, so that a lossy trace reads low and never high
    lossy = copy.deepcopy(raw)
    lossy["trace"]["device_ops"][-1][1] = 1.0
    lossy["trace"]["device_ops"].append(["while", 0.5])
    assert _read("swa_decode_attention_roofline", lossy) == pytest.approx(
        100 * least["flash_decode_attention"]["seconds"] / 1.5)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [8000, 5000], 256)
    assert _read("generate_roofline_share_moe_swa", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    # 5 of 64 experts a step and layer
    assert _read("expert_distinct_per_step", raw) == pytest.approx(
        100 * 5 / 64)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(
        50 / ((63 * 25 + 50) / 64))
    # two whole executions: both dispatches count
    raw["trace"]["module_calls"]["jit_generate"] = 2
    both = whole["total_s"] + roof.dispatch(
        SIZES, PRECISION, PEAKS, EXPERTS, [2000], 256)["total_s"]
    assert _read("generate_roofline_share_moe_swa", raw) == pytest.approx(
        100 * both / 9.0)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family, counter
    or driver: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    # a traced dispatch without counters (a family that counts nothing)
    raw = _raw()
    raw["traced"]["dispatches"][0]["experts"] = None
    raw["counts"]["experts"] = None
    for name in ("swa_prefill_attention_roofline",
                 "generate_roofline_share_moe_swa", "expert_distinct_per_step"):
        assert _read(name, raw) is None, name
    zero = _raw()
    zero["counts"]["experts"].update(decode_touched=0, decode_reads_possible=0)
    assert _read("expert_distinct_per_step", zero) is None


def test_the_cell_lists_its_own_metrics_and_five_it_shares():
    mine = [m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)]
    own = ["generate_roofline_share_moe_swa", "swa_prefill_attention_roofline",
           "swa_decode_attention_roofline", "moe_expert_matmul_roofline",
           "swa_attention_busy_share", "expert_distinct_per_step"]
    assert set(mine) == set(own) | {
        "host_share.offline", "generate_device_s_per_dispatch",
        "device_idle.offline", "expert_ffn_busy_share",
        "expert_load_max_over_mean"}
    # the driver takes new entries only at the end of the list
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == own
    for m in BENCH["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "docs_per_min"
        assert m["layer"] == "model and kernels"
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    assert cells.validate(BENCH, ROOT) == []
    cell = cells.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    traffic = cells.load_traffic("offline-mapreduce-8k-moe")
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach", "rehearsal"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_family"
    assert CONFIG["setup_module"] == "engine_setup_smallthinker"


# -- the driver's counters -------------------------------------------------------------


def test_expert_counts_are_the_windows_own_and_shared_by_dispatch():
    from types import SimpleNamespace

    driver = cells.load_module("drivers", "offline_pipeline_family")
    st = SimpleNamespace(expert_slots_routed=10, expert_slots_held=10,
                         expert_decode_touched=7, expert_decode_layer_steps=2,
                         expert_tokens=[[1, 3], [0, 0]])
    before = driver.snapshot(st)
    st.expert_slots_routed = st.expert_slots_held = 40
    st.expert_decode_touched, st.expert_decode_layer_steps = 19, 10
    st.expert_tokens = [[2, 8], [5, 2]]
    got = driver.expert_counts(st, before)
    assert got == {"slots_routed": 30, "slots_held": 30, "decode_touched": 12,
                   "decode_layer_steps": 8, "tokens": [[1, 5], [5, 2]],
                   "decode_reads_possible": 16}
    assert driver.share(got, 2) == {
        "slots_routed": 15, "slots_held": 15, "decode_touched": 6,
        "decode_layer_steps": 4, "tokens": [[0.5, 2.5], [2.5, 1.0]],
        "decode_reads_possible": 8}
    # an engine that counts no experts (a dense family, the parent commit)
    dense = SimpleNamespace(expert_slots_routed=0, expert_slots_held=0,
                            expert_tokens=[])
    none = driver.expert_counts(dense, driver.snapshot(dense))
    assert none["slots_routed"] == 0 and none["tokens"] == []
    assert none["decode_touched"] == 0 and none["decode_reads_possible"] == 0


def test_the_driver_finds_a_setup_module_by_the_files_name():
    """The next family brings a set-up module, not a driver: DeepSeek-V2's
    has the four functions this driver calls."""
    import importlib

    for name in ("engine_setup_smallthinker", "engine_setup_deepseek_v2"):
        mod = importlib.import_module(f"benchmarks.{name}")
        for fn in ("model_config", "start_weights", "sizes_of",
                   "parity_with_reference"):
            assert callable(getattr(mod, fn)), (name, fn)
