"""Set-up of the LFM2-MoE family for a driver's chip-holding child: the
model from a configuration file, its weights, and the parity check against
``benchmarks/reference_lfm2.py``.

The same part ``engine_setup_nemotron_h.py`` plays for its family; a driver
finds this module by the ``setup_module`` its configuration file names
(``drivers/offline_pipeline_family.py``). Everything that is not the model
(the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> Lfm2Config field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "intermediate",
    "moe_intermediate_size": "moe_intermediate",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "num_dense_layers": "num_dense_layers",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "use_expert_bias": "use_expert_bias",
    "conv_L_cache": "conv_L_cache", "conv_bias": "conv_bias",
    "norm_eps": "norm_eps", "rope_theta": "rope_theta",
}
# sizes no published key states (keys of the file under the harness's names,
# each with its basis under ``assumed``) -> field
ASSUMED_TO_FIELD = {"head_dim": "head_dim",
                    "tie_word_embeddings": "tie_embeddings"}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): two
# dense layers and two periods A c c c after them, 8 experts top-2, 4 / 2
# heads of 16
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 10,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_dense_layers": 2,
    "routed_scaling_factor": 1, "norm_topk_prob": True,
    "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "rope_theta": 10000, "head_dim": 16,
    "tie_word_embeddings": True,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 2,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, with the sizes it
    assumes and the layer types of the file's depth."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    if config["model_type"] != "lfm2_moe":
        raise ValueError(f"model_type {config['model_type']!r}: this family "
                         "builds 'lfm2_moe'")
    if config["rms_norm_eps"] != config["norm_eps"]:
        raise ValueError("the norms' epsilon is stated two ways that "
                         "disagree")
    sizes = {k: config[k] for k in (*HF_TO_FIELD, *ASSUMED_TO_FIELD)}
    sizes["layer_types"] = list(
        config["layer_types"][:config["num_hidden_layers"]])
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model an ``Lfm2Config`` describes (with
    ``expert_offset`` where it holds a part of the experts)."""
    sizes = {k: getattr(cfg, field)
             for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}
    sizes["layer_types"] = list(cfg.layer_types)
    sizes["expert_offset"] = cfg.expert_offset
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states (the
    family refuses a mechanism it does not build: ``Lfm2Config``)."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k]
          for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}
    kw["layer_types"] = tuple(sizes["layer_types"])
    engine = config["rehearsal"] if rehearsal else config["engine"]
    kw["max_seq_len"] = engine["max_seq_len"]
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](**kw)


def start_weights(config: dict, cfg, seed: int):
    """Dispatch the one jitted program that makes the weights on the device
    from the seed, in the type they are served in; returns at once."""
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.lfm2 import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt behind a left pad through the
    engine's own chunked prefill (four chunks in the 8192 bucket, so every
    convolution layer's tail is handed across the chunk boundaries; W8A8;
    the GQA flash kernel at 4 query heads a KV head of 64; the grouped
    three-matrix expert product on int8 rows) and then ``decode_steps``
    teacher-forced decode steps through the tails, the int8 cache and the
    experts (the decode kernel, the expert product at 4 slots) —
    ``TpuBackend.prefill_then_decode_logits`` — against the reference's one
    full forward over prompt + forced tokens in float32 on the same
    weights.

    Four comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``, and the LAST row — the
    last forced token's — within ``decode_tolerance``. Routing is a top-k,
    which is not continuous: the engine hands out what its routers picked
    for each scored position (``Family.row_record``) and the reference
    takes those picks where, and only where, they are the top-k of ITS OWN
    ranking (score + bias) moved by less than ``tie_band``
    (``reference.ties_broken_their_way``); ``took`` counts the layers of
    each row where it did. **The picks:** on the FIRST sparse layer, which
    reads three layers' output alone, every scored row's picks have to be
    the reference's own or a rightful top-k of its ranking within
    ``tie_band`` (``first_layer_picks_ok``): a router that ranks by another
    rule (no bias, a softmax) moves more picks than rounding does. **The
    tail:** the FIRST convolution layer's tail — the gated product
    ``b * x`` of the last two positions, in the tail's own type — after
    the prompt and after each forced token within ``state_tolerance``: a
    tail that holds something else (``x``, in_proj's output) or is kept a
    precision below reads far past it. The LAST convolution layer's tail
    is reported (``last_tail_error``) and bounds nothing.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_lfm2 as reference
    from benchmarks import textgen
    from benchmarks.engine_setup_nemotron_h import _distance

    spec = {**config["reference"]["parity"],
            **(config["rehearsal"].get("parity", {}) if rehearsal else {})}
    n, seq, steps = spec["prompt_tokens"], spec["bucket"], spec["decode_steps"]
    text = textgen.TextGen(seed + 5).text_of_bytes((n + steps) * 12)
    ids = np.asarray(backend.tok.encode(text)[:n + steps], np.int32)
    if len(ids) != n + steps or n >= seq:
        raise ValueError(
            f"parity prompt: {len(ids)} tokens for {n} behind a pad in {seq}")
    sizes = sizes_of(config, rehearsal)   # the file's, not the engine's

    @jax.jit
    def plain(params, tokens, picks):
        out = reference.forward(
            params, tokens, sizes, last=steps + 1, theirs=picks,
            tie_band=spec["tie_band"], faults=tuple(faults))
        return {"logits": out["logits"], "took": out["took"],
                "ids": out["ids"][:, -(steps + 1):],
                "rows": out["tail_rows"]}

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    # the routers' picks, [rows, layers, 1, k] -> [layers, rows, k]
    picks = jnp.asarray(state["rows"]["picks"][:, :, 0].swapaxes(0, 1))
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids), picks))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))
    # the first sparse layer: a row whose picks are not a rightful top-k
    # within the band keeps the reference's own picks, which then differ
    took = want["took"].astype(bool)
    same = np.sort(np.asarray(picks[0]), -1) == np.sort(
        want["ids"][0].astype(np.int64), -1)
    picks_ok = bool((took[0] | same.all(-1)).all())
    # [rows, first | last, 1, K - 1, D] against [first | last, rows, K - 1, D]
    mine = np.asarray(state["rows"]["tail"].astype(np.float32),
                      np.float64)[:, :, 0].swapaxes(0, 1)
    first = [_distance(mine[0, r], want["rows"][0, r])
             for r in range(steps + 1)]
    last = [_distance(mine[1, r], want["rows"][1, r])
            for r in range(steps + 1)]
    cache = state["cache"]
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"], "tie_band": spec["tie_band"],
            "last_row_error": float(errors[-1]),
            "decode_tolerance": spec["decode_tolerance"],
            "took": took.sum(0).astype(int).tolist(),
            "first_layer_picks_ok": picks_ok,
            "first_layer_rows_differing": int((~same.all(-1)).sum()),
            "state_error": max(first), "state_errors": first,
            "state_tolerance": spec["state_tolerance"],
            "last_tail_error": max(last),
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and errors[-1] <= spec["decode_tolerance"]
                       and picks_ok
                       and max(first) <= spec["state_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "faults": list(faults),
            "state_dtype": str(cache["conv"].dtype),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "slots_routed": int(cache["slots_routed"]),
            "slots_held": int(cache["slots_held"]),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
