"""CPU tests of the benchmark's own parts for the DeepSeek-V2 family: the
plain reference against the program, the share test, the run-time parity
check and the faults it has to catch, the kernel rooflines against
hand-worked numbers, the readers, and the configuration file's arithmetic.

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
from benchmarks import engine_setup_deepseek_v2 as family_setup  # noqa: E402
from benchmarks import roofline_deepseek_v2 as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIG = cells.load_config(BENCH, "deepseek-v2-ep4-int8")
CELL = "deepseek-v2-ep4-int8.offline-mapreduce-8k-ep"


def _tiny(**kw):
    from vnsum_tpu.models.deepseek import tiny_deepseek

    return tiny_deepseek(**kw)


_sizes = family_setup.sizes_from


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("share", [(0, 0), (4, 8)])
@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(share, int8):
    """Float32 on both sides, so only the order of summation differs: 1e-5
    of logits of order 0.5. Whole, and as a share of the experts."""
    import jax
    import jax.numpy as jnp

    from vnsum_tpu.models.deepseek import forward_dense, init_params
    from vnsum_tpu.models.quant import quantize_params

    from benchmarks import reference_deepseek_v2 as reference

    offset, held = share
    cfg = _tiny(expert_offset=offset, experts_held=held)
    params = init_params(jax.random.key(0), cfg)
    if int8:
        params = jax.jit(quantize_params)(params)
    tokens = jax.random.randint(jax.random.key(1), (1, 24), 0, cfg.vocab_size)
    want = forward_dense(params, cfg, tokens)[0]
    got = reference.logits(params, tokens[0], _sizes(cfg),
                           expert_offset=offset)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5


@pytest.mark.parametrize("int8", [False, True])
def test_engine_prefill_and_absorbed_decode_agree_with_the_reference(int8):
    """Chunked prefill through the prefill kernel, then teacher-forced
    steps through the latent cache and the absorbed kernel (interpreted),
    against the reference's one full forward: logits compared. Float32
    weights leave the order of summation (1e-4); int8 weights with W8A8 add
    the rounding of the activations (a few per cent of the logits' size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models.deepseek import init_params

    from benchmarks import reference_deepseek_v2 as reference

    cfg = _tiny(expert_offset=4, experts_held=8)
    params = init_params(jax.random.key(2), cfg)
    be = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=2,
                    max_new_tokens=8, params=params, interpret=True,
                    prefill_chunk_tokens=128, quantize=int8,
                    quantize_act=int8)
    ids = list(np.asarray(jax.random.randint(
        jax.random.key(3), (156,), 0, cfg.vocab_size)))
    n = 150
    got, state = be.prefill_then_decode_logits(ids[:n], ids[n:], bucket=256,
                                               return_state=True)
    ref = reference.forward(
        be.params, jnp.asarray(ids), _sizes(cfg), expert_offset=4, last=7)
    # the routers' picks of each scored position: [rows, layers, B, k]
    assert state["rows"].shape == (7, 2, 1, 3)
    assert state["rows"].min() >= 0 and state["rows"].max() < 16
    state = state["cache"]
    want = np.asarray(ref["logits"])
    assert got.shape == want.shape == (7, cfg.vocab_size)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < (0.04 if int8 else 1e-4), err
    # what the latent cache holds of the 156 tokens (the prompt's rows end
    # at slot 256, the forced tokens' follow) is the reference's own
    # (c_kv, k_rope), layer by layer
    rows = state["latent"][:, 0, 256 - n:256 + 6]
    assert rows.shape == ref["latent"].shape == (3, 156, cfg.latent_width)
    off = np.linalg.norm(rows - ref["latent"], axis=(1, 2)) \
        / np.linalg.norm(ref["latent"], axis=(1, 2))
    assert off.max() < (0.1 if int8 else 1e-5), off


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that four chips of an expert-parallel group give
    (offsets 0, 4, 8, 12 of 16 experts; 0, 40, 80, 120 of 160 in the
    deployment), plus the shared experts counted once, are the uncut
    reference's expert layer — and the program's shares add up the same."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.models import deepseek as ds

    from benchmarks import reference_deepseek_v2 as reference

    whole = _tiny()
    params = ds.init_params(jax.random.key(5), whole)
    w = jax.tree.map(lambda a: a[1], params["layers"])       # one layer
    h = jax.random.normal(jax.random.key(6), (40, whole.dim))
    sizes = _sizes(whole)
    uncut = reference.expert_layer_routed(h, w, sizes, 0)
    assert float(jnp.abs(uncut).max()) > 0.01
    parts, program_parts = [], []
    for offset in (0, 4, 8, 12):
        share = dict(w, **{n: w[n][offset:offset + 4] for n in ds._EXPERTS})
        parts.append(reference.expert_layer_routed(h, share, sizes, offset))
        cfg = _tiny(expert_offset=offset, experts_held=4)
        scores = jax.nn.softmax(h @ w["router"], -1)
        ids, weights = ds.route(scores, cfg)
        local = ids - offset
        local = jnp.where((local >= 0) & (local < 4), local, -1)
        stacked = {n: share[n][None] for n in ds._EXPERTS}
        program_parts.append(ds.grouped_experts(
            h, local, weights, stacked, 0, cfg, interpret=True))
    np.testing.assert_allclose(sum(parts), uncut, atol=1e-5)
    np.testing.assert_allclose(sum(program_parts), uncut, atol=1e-4)
    # no share is the whole, and the shares differ
    assert float(jnp.abs(parts[0] - uncut).max()) > 0.01


# -- the run-time parity check -------------------------------------------------

FAULTS = {
    None: lambda sizes: None,
    "no_shared_experts": lambda s: s.update(n_shared_experts=0),
    "no_group_limit": lambda s: s.update(topk_group=s["n_group"]),
    "routed_scaling_factor_1": lambda s: s.update(routed_scaling_factor=1.0),
    "yarn_m_squared_left_out": lambda s: s.update(rope_scaling={
        **s["rope_scaling"], "mscale": 0.0, "mscale_all_dim": 0.0}),
}


def _rehearsal_parity(monkeypatch, fault=None, latent_rows=None):
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import deepseek as ds

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, 11)
    if latent_rows is not None:
        monkeypatch.setattr(ds, "_latent_rows", latent_rows)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=params, **engine_setup.backend_kwargs(config, rehearsal=True))
    sizes = dict(family_setup.REHEARSAL_SIZES)
    FAULTS[fault](sizes)
    monkeypatch.setattr(family_setup, "REHEARSAL_SIZES", sizes)
    return family_setup.parity_with_reference(backend, config, 11,
                                              rehearsal=True)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_parity_check_passes_the_program_and_catches_each_fault(
        fault, monkeypatch):
    """``parity_with_reference`` on a tiny engine with interpreted kernels:
    it passes the program as it is (prefill and decode steps, every row
    within the one tolerance, the leading layer's cache rows within
    theirs), and fails when the two stop being the same mathematics — a
    reference without the shared experts, without the group limit, with the
    routed weights unscaled, or with YaRN's m^2 left out of the softmax
    scale."""
    got = _rehearsal_parity(monkeypatch, fault)
    assert got["ok"] is (fault is None), got
    assert got["kernel"] is True and got["prompt_tokens"] == 150
    assert got["decode_steps"] == 4 and len(got["errors"]) == 5
    assert got["error"] == max(got["errors"])
    assert len(got["latent_errors"]) == 3
    assert got["latent_error"] == got["latent_errors"][0]
    assert len(got["took"]) == 5 and max(got["took"]) <= 2   # 2 expert layers
    if fault is not None:
        assert got["error"] > 1.5 * got["tolerance"]
        # none of these touches the leading layer's keys but YaRN's m^2,
        # which scales the softmax and not the rows the cache keeps
        assert got["latent_error"] <= got["latent_tolerance"]


def test_a_tie_is_broken_the_programs_way_and_nothing_else_is():
    """``ties_broken_their_way`` on a hand-worked router row: 8 experts in
    4 groups of 2, the best 2 groups kept, 2 picks. Scores: group 0 = (.30,
    .05), group 1 = (.20, .195), group 2 = (.19, .01), group 3 = (.04,
    .02). The reference picks experts 0 and 2 (groups 0 and 1 kept)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_deepseek_v2 as reference

    sizes = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 2}
    row = jnp.asarray([[.30, .05, .20, .195, .19, .01, .04, .02]])
    ids, _ = reference.route(row, 4, 2, 2)
    assert sorted(np.asarray(ids[0])) == [0, 2]

    def rightful(picks, band):
        return bool(reference.ties_broken_their_way(
            row, jnp.asarray([picks]), sizes, band)[0])

    assert rightful([0, 2], 0.0) and rightful([2, 0], 0.0)   # its own picks
    # expert 3 scores within 2.6% of expert 2: a tie inside a 5% band
    assert rightful([0, 3], 0.05) and not rightful([0, 3], 0.01)
    # group 2's best (.19) is within 5.2% of group 1's (.20): inside a 6%
    # band groups 0 and 2 are rightful too, and expert 4 the pick there
    assert rightful([0, 4], 0.06) and not rightful([0, 4], 0.04)
    # never rightful, whatever near-ties there are: a pick from a third
    # group, a better expert skipped, the same expert twice, a far group
    assert not rightful([2, 4], 0.06)        # the best expert of all skipped
    assert not rightful([0, 1], 0.06)        # .05 is no tie with .20
    assert not rightful([0, 0], 0.06)
    assert not rightful([0, 6], 0.06)
    assert not rightful([3, 4], 0.06)        # groups 1 and 2 without group 0


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


def test_an_int8_latent_cache_fails_the_check_of_the_caches_rows(monkeypatch):
    """A latent cache that rounds its rows to int8 (per-row scale) is the
    nearest precision below the configured bf16. The logits hardly show it
    (W8A8 has already rounded every row by more); the cache's own rows do:
    the leading layer's read twice their clean distance from the
    reference's ``(c_kv, k_rope)``, over ``latent_tolerance``."""
    import jax.numpy as jnp

    def rounded(c_kv, k_rope, dtype):
        row = jnp.concatenate([c_kv, k_rope], axis=-1)
        scale = jnp.max(jnp.abs(row), -1, keepdims=True) / 127.0
        return (jnp.round(row / scale) * scale).astype(dtype)

    clean = _rehearsal_parity(monkeypatch)
    got = _rehearsal_parity(monkeypatch, latent_rows=rounded)
    assert clean["ok"] is True and got["ok"] is False
    # most rows of logits still pass (at this tiny size a flipped pick
    # among 16 experts can throw one)
    assert sorted(got["errors"])[-2] <= got["tolerance"]
    assert clean["latent_error"] < 0.7 * clean["latent_tolerance"]
    assert got["latent_error"] > 1.3 * got["latent_tolerance"]


def test_model_config_builds_the_files_share_of_the_published_model():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_routed_experts, cfg.n_held,
            cfg.expert_offset) == (8, 160, 40, 0)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.kv_lora_rank,
            cfg.q_lora_rank, cfg.moe_intermediate, cfg.intermediate) == (
        5120, 128, 192, 512, 1536, 1536, 12288)
    assert (cfg.rope_factor, cfg.rope_original_max_len) == (40, 4096)
    assert cfg.max_seq_len == 8448
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is False


# -- the configuration file -----------------------------------------------------


def test_config_file_keeps_every_published_width_and_states_its_cut():
    from vnsum_tpu.models import MODEL_REGISTRY

    published = MODEL_REGISTRY["deepseek-v2"]()
    c = CONFIG
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert c["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160}
    assert c["num_hidden_layers"] >= 1 + 4 and c["n_routed_experts"] == 40
    for key, field in family_setup.HF_TO_FIELD.items():
        if key != "num_hidden_layers":
            assert c[key] == getattr(published, field), key
    for key, field in family_setup.ROPE_TO_FIELD.items():
        assert c["rope_scaling"][key] == getattr(published, field), key
    assert c["vocab_size"] == 102_400 and "head_dim" in c["assumed"]
    assert c["expert_parallel"] == {
        "chips_sharing_a_layer": 4, "expert_offset": 0, "experts_held": 40,
        "replicated": ["attention", "shared experts", "router", "embedding",
                       "lm_head"]}
    assert c["engine"]["kv"] == "bf16" and c["engine"]["batch"] == 24


def test_config_files_byte_arithmetic_is_the_models():
    s = family_setup.sizes_of(CONFIG, rehearsal=False)
    b = CONFIG["bytes"]
    d = s["hidden_size"]
    assert b["attention_a_layer"] == roof.attention_params(s) == 149_225_472
    assert b["one_expert"] == roof.expert_params(s) == 23_592_960
    assert b["shared_experts_a_layer"] == 2 * roof.expert_params(s)
    assert b["router_a_layer"] == d * 160
    assert b["experts_held_a_layer"] == 40 * roof.expert_params(s)
    expert_layer = (b["attention_a_layer"] + b["shared_experts_a_layer"]
                    + b["router_a_layer"] + b["experts_held_a_layer"])
    assert b["expert_layer"] == expert_layer
    assert b["dense_layer"] == b["attention_a_layer"] + 3 * d * 12288
    assert b["embedding_and_head"] == 2 * 102_400 * d
    assert b["weights_8_layers"] == (
        b["dense_layer"] + 7 * expert_layer + b["embedding_and_head"])


# -- rooflines against hand-worked numbers -------------------------------------

PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 2, "prefill_matmul": "int8"}


def test_kernel_rooflines_against_hand_worked_numbers():
    """One dispatch of 2 rows of 8,000 and 4,000 real tokens, 256 steps,
    8 layers (7 of experts), a quarter of the picks held, the load even
    over the 40 held experts."""
    s = family_setup.sizes_of(CONFIG, rehearsal=False)
    lens, steps = [8000, 4000], 256
    experts = {"slots_routed": 4000, "slots_held": 1000,
               "tokens": [[25] * 40] * 7}
    # prefill attention: 128 heads x (192 + 128) x n^2, 8 layers
    ops = 128 * 320 * (8000 ** 2 + 4000 ** 2) * 8
    assert roof.prefill_attention_ops(s, lens) == ops == 26_214_400_000_000
    # absorbed decode: context tokens over the steps = 12000 * 256 + 2 * 32640
    ctx = 12000 * 256 + 2 * (256 * 255 // 2)
    assert ctx == 3_137_280
    dec = roof.decode_attention(s, lens, steps, 2)
    assert dec["ops"] == 2 * 128 * (2 * 512 + 64) * ctx * 8
    assert dec["bytes"] == 576 * 2 * ctx * 8
    assert dec["ops"] / dec["bytes"] == pytest.approx(241.8, abs=0.1)
    # experts: 12000 tokens x 6 picks x 1/4 x 7 layers held in prefill
    ex = roof.expert_matmul(s, experts, 12000, 2, steps, 1)
    assert ex["prefill_ops"] == 2 * 23_592_960 * (12000 * 6 * 0.25 * 7)
    # a step's 3 held slots touch 3 - 3/40 + 1/1600 experts a layer (even)
    touched = 40 * (1 - (1 - 1 / 40) ** 3)
    assert roof.expected_touched([25] * 40, 3.0) == pytest.approx(touched)
    assert touched == pytest.approx(2.9256, abs=1e-4)
    assert ex["decode_bytes"] == pytest.approx(
        23_592_960 * touched * 7 * steps)
    k = roof.kernel_least_seconds(s, PRECISION, PEAKS, experts, lens, steps)
    assert k["mla_prefill_attention"]["seconds"] == pytest.approx(
        ops / 197e12)
    assert k["mla_prefill_attention"]["seconds"] == pytest.approx(0.13307,
                                                                   abs=1e-5)
    assert k["mla_decode_attention"] == {
        "seconds": pytest.approx(dec["ops"] / 197e12), "bound": "compute"}
    assert k["expert_grouped_matmul"]["seconds"] == pytest.approx(
        ex["prefill_ops"] / 393e12 + ex["decode_bytes"] / 819e9)
    assert k["expert_grouped_matmul"]["bound"] == "compute, then memory"


def test_dispatch_roofline_counts_the_experts_a_token_hits_here():
    s = family_setup.sizes_of(CONFIG, rehearsal=False)
    experts = {"slots_routed": 4000, "slots_held": 1000,
               "tokens": [[25] * 40] * 7}
    per = roof.layer_params_a_token(s, 0.25)
    assert per["dense"] == 149_225_472 + 3 * 5120 * 12288
    # attention + shared + router + 6 picks x 1/4 x one expert
    assert per["expert"] == pytest.approx(
        149_225_472 + 47_185_920 + 819_200 + 1.5 * 23_592_960)
    d = roof.dispatch(s, PRECISION, PEAKS, experts, [8000, 4000], 256)
    token_params = per["dense"] + 7 * per["expert"]
    assert d["prefill_matmul_ops"] == pytest.approx(
        2 * token_params * 12000 + 2 * 5120 * 102_400 * 2)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    assert d["prefill_s"] > d["kernels"]["mla_prefill_attention"]["seconds"]
    # no counters yet: the held experts count for nothing, nothing divides by 0
    none = {"slots_routed": 0, "slots_held": 0, "tokens": [[0] * 40] * 7}
    assert roof.expert_matmul(s, none, 12000, 2, 256, 1) == {
        "prefill_ops": 0.0, "decode_ops": 0.0, "decode_bytes": 0.0}


# -- readers and the driver's record -------------------------------------------


def _raw():
    s = family_setup.sizes_of(CONFIG, rehearsal=False)
    experts = {"slots_routed": 4000, "slots_held": 1000,
               "tokens": [[25] * 39 + [50]] * 7}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": s, "precision": PRECISION,
        "counts": {"experts": experts},
        "traced": {"dispatches": [
            {"prompt_lens": [8000, 4000], "steps": 256},
            {"prompt_lens": [2000], "steps": 256}]},
        "trace": {"busy_s": 10.0, "window_s": 11.0,
                  "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1.0},
                  "device_ops": [["mla_prefill_attention", 0.4],
                                 ["expert_grouped_matmul", 0.5],
                                 ["fusion.1 bf16[2,3]", 3.0]]},
    }


def _read(metric: str, raw: dict):
    spec = cells.load_layer_metric(metric)
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_readers_on_a_known_record():
    raw = _raw()
    s, experts = raw["sizes"], raw["counts"]["experts"]
    least = roof.kernel_least_seconds(
        s, PRECISION, PEAKS, experts, [8000, 4000], 256)
    # one whole execution in the stretch: the first dispatch alone counts
    assert _read("mla_prefill_attention_roofline", raw) == pytest.approx(
        100 * least["mla_prefill_attention"]["seconds"] / 0.4)
    assert _read("expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 0.5)
    # the reducer kept no row for the decode kernel: the metric is left out
    assert _read("mla_decode_attention_roofline", raw) is None
    assert _read("mla_attention_busy_share", raw) is None
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(5.0)
    assert _read("expert_held_share", raw) == pytest.approx(25.0)
    # the whole dispatch: one whole execution, 9 s of jit_generate
    whole = roof.dispatch(s, PRECISION, PEAKS, experts, [8000, 4000], 256)
    assert _read("generate_roofline_share_ep", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(
        350 / (7 * (39 * 25 + 50) / 40))


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such counter or
    kernel: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    zero = _raw()
    zero["counts"]["experts"] = {"slots_routed": 0, "slots_held": 0,
                                 "tokens": [[0] * 40] * 7}
    assert _read("expert_held_share", zero) is None
    assert _read("expert_load_max_over_mean", zero) is None


def test_the_cell_lists_its_own_metrics_and_four_of_the_offline_cells():
    mine = {m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    assert mine == {
        "host_share.offline", "generate_device_s_per_dispatch",
        "device_idle.offline", "mla_prefill_attention_roofline",
        "mla_decode_attention_roofline", "expert_matmul_roofline",
        "mla_attention_busy_share", "expert_ffn_busy_share",
        "expert_load_max_over_mean", "expert_held_share",
        "generate_roofline_share_ep"}
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    traffic = cells.load_traffic("offline-mapreduce-8k-ep")
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_ep"


def test_expert_counts_are_the_windows_own():
    from types import SimpleNamespace

    driver = cells.load_module("drivers", "offline_pipeline_ep")
    st = SimpleNamespace(expert_slots_routed=10, expert_slots_held=4,
                         expert_tokens=[[1, 3], [0, 0]])
    before = driver.snapshot(st)
    st.expert_slots_routed, st.expert_slots_held = 40, 13
    st.expert_tokens = [[2, 8], [5, 2]]
    assert driver.expert_counts(st, before) == {
        "slots_routed": 30, "slots_held": 9, "tokens": [[1, 5], [5, 2]]}
    fresh = SimpleNamespace(expert_slots_routed=0, expert_slots_held=0,
                            expert_tokens=[])
    before = driver.snapshot(fresh)
    fresh.expert_tokens, fresh.expert_slots_routed = [[1, 2]], 6
    fresh.expert_slots_held = 3
    assert driver.expert_counts(fresh, before)["tokens"] == [[1, 2]]
