"""Set-up of the Nemotron-H family for a driver's chip-holding child: the
model from a configuration file, its weights, and the parity check against
``benchmarks/reference_nemotron_h.py``.

The same part ``engine_setup_granite_h.py`` plays for its family; a driver
finds this module by the ``setup_module`` its configuration file names
(``drivers/offline_pipeline_family.py``). Everything that is not the model
(the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> NemotronHConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate",
    "mamba_num_heads": "mamba_n_heads", "mamba_head_dim": "mamba_d_head",
    "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups",
    "conv_kernel": "mamba_d_conv",
    "moe_intermediate_size": "moe_intermediate",
    "moe_shared_expert_intermediate_size": "shared_intermediate",
    "n_routed_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
# published keys that say which mechanisms the model has; this family builds
# exactly these and refuses a file that states another
MECHANISMS = {
    "attention_bias": False, "use_conv_bias": True, "mamba_proj_bias": False,
    "mlp_bias": False, "use_bias": False, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "n_shared_experts": 1,
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): all
# three kinds in an order with no period, 2 groups of B and C, an expert
# width that is not whole lanes, 4 query heads a KV head, 16 experts top-4
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 9,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 24,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "hybrid_override_pattern": "MEM*EMEME",
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, the pattern cut to the
    file's depth."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    for key, built in MECHANISMS.items():
        if config[key] != built:
            raise ValueError(
                f"{key} = {config[key]!r}: this family builds {built!r}")
    if config["layer_norm_epsilon"] != config["norm_eps"]:
        raise ValueError("the norms' epsilon is stated two ways that "
                         "disagree")
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["chunk_size"] = config["chunk_size"]
    sizes["hybrid_override_pattern"] = config["hybrid_override_pattern"][
        :config["num_hidden_layers"]]
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``NemotronHConfig`` describes (with
    ``expert_offset`` where it holds a part of the experts)."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["chunk_size"] = cfg.mamba_chunk_size
    sizes["hybrid_override_pattern"] = cfg.layer_pattern
    sizes["expert_offset"] = cfg.expert_offset
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states. The
    scan's chunk is the engine's (``engine.scan_chunk``: how the program
    computes the recurrence), the published ``chunk_size`` without one."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    kw["layer_pattern"] = sizes["hybrid_override_pattern"]
    engine = config["rehearsal"] if rehearsal else config["engine"]
    kw["mamba_chunk_size"] = engine.get("scan_chunk", sizes["chunk_size"])
    kw["max_seq_len"] = engine["max_seq_len"]
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](**kw)


def start_weights(config: dict, cfg, seed: int):
    """Dispatch the one jitted program that makes the weights on the device
    from the seed, in the type they are served in; returns at once."""
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.nemotron_h import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def _distance(mine, theirs) -> float:
    import numpy as np

    mine = np.asarray(mine, np.float64)
    theirs = np.asarray(theirs, np.float64)
    return float(np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt behind a left pad through the
    engine's own chunked prefill (four chunks in the 8192 bucket, so the
    recurrent state is handed from chunk to chunk; the scan kernel at eight
    groups, the GQA flash kernel at 16 query heads a KV head, the grouped
    single-product expert form on int8 rows, W8A8) and then ``decode_steps``
    teacher-forced decode steps through the state, the int8 cache and the
    experts (the state-update kernel, the decode kernel) —
    ``TpuBackend.prefill_then_decode_logits`` — against the reference's one
    full forward over prompt + forced tokens in float32 on the same
    weights, its recurrence token by token.

    Five comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``, and the LAST row — the
    last forced token's, by which the W8A8 prefill's own rounding has
    decayed out of the state and the cache (rows read 0.07-0.17, then
    0.02) — within ``decode_tolerance``, which sees what the first rows'
    spread hides (a residual scaled, the bias in the weight). Routing is a
    top-k, which
    is not continuous: the engine hands out what its routers picked for
    each scored position (``Family.row_record``) and the reference takes
    those picks where, and only where, they are the top-k of ITS OWN
    ranking (score + bias) moved by less than ``tie_band``
    (``reference.ties_broken_their_way``); ``took`` counts the layers of
    each row where it did. **The picks:** on the FIRST sparse layer, which
    reads one Mamba layer's output alone, every scored row's picks have to
    be the reference's own or a rightful top-k of its ranking within
    ``tie_band`` (``first_layer_picks_ok``): a router that ranks by another
    rule (no bias, a softmax) moves more picks than rounding does.
    **The state** and **the state's steps:** the FIRST Mamba layer's
    recurrent state after the prompt and after each forced token within
    ``state_tolerance``, and what the decode steps added to it over the
    slow quarter of the layer's heads within ``state_step_tolerance`` —
    ``engine_setup_granite_h.parity_with_reference`` says why the second is
    the limit a state kept in bfloat16 does not meet. The LAST Mamba
    layer's state is reported (``last_state_error``) and bounds nothing.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_nemotron_h as reference
    from benchmarks import textgen

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
                "rows": reference.state_as_the_program_lays_it(
                    out["ssm_rows"])}

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
    # [rows, first | last, 1, N, HP] against [first | last, rows, N, HP]
    mine = np.asarray(state["rows"]["ssm"], np.float64)[:, :, 0].swapaxes(0, 1)
    first = [_distance(mine[0, r], want["rows"][0, r])
             for r in range(steps + 1)]
    last = [_distance(mine[1, r], want["rows"][1, r])
            for r in range(steps + 1)]
    # the slow quarter of the first layer's heads, as lanes of [N, H * P]
    first_layer = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                               {k: backend.params["mamba"][k]
                                for k in ("A_log", "dt_bias")})
    rate = np.exp(first_layer["A_log"]) * np.logaddexp(
        0.0, first_layer["dt_bias"])
    heads = len(rate)
    slow = np.zeros(heads, bool)
    slow[np.argsort(rate)[:max(heads // 4, 1)]] = True
    lanes = np.repeat(slow, mine.shape[-1] // heads)
    step = _distance((mine[0, -1] - mine[0, 0])[:, lanes],
                     (want["rows"][0, -1] - want["rows"][0, 0])[:, lanes])
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
            "state_step_error": step, "slow_heads": int(slow.sum()),
            "state_step_tolerance": spec["state_step_tolerance"],
            "last_state_error": max(last),
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and errors[-1] <= spec["decode_tolerance"]
                       and picks_ok
                       and max(first) <= spec["state_tolerance"]
                       and step <= spec["state_step_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "faults": list(faults),
            "state_dtype": str(cache["ssm"].dtype),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "slots_routed": int(cache["slots_routed"]),
            "slots_held": int(cache["slots_held"]),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
