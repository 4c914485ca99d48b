"""Set-up of the Granite-4.0-H family for a driver's chip-holding child: the
model from a configuration file, its weights, and the parity check against
``benchmarks/reference_granite_h.py``.

The same part ``engine_setup_smallthinker.py`` plays for its family; a
driver finds this module by the ``setup_module`` its configuration file
names (``drivers/offline_pipeline_family.py``). Everything that is not the
model (the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> GraniteHybridConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
    "mamba_d_state": "mamba_d_state", "mamba_n_groups": "mamba_n_groups",
    "mamba_d_conv": "mamba_d_conv", "mamba_chunk_size": "mamba_chunk_size",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "attention_multiplier": "attention_multiplier",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
# published keys that say which mechanisms the model has; this family builds
# exactly these and refuses a file that states another
MECHANISMS = {
    "position_embedding_type": "nope", "num_local_experts": 0,
    "attention_bias": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm",
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): two
# periods of [5 Mamba, attention, 4 Mamba], 8 Mamba heads of 16, a state of
# 16, scan chunks of 8
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 20,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "attention_multiplier": 0.015625, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "tie_word_embeddings": True,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    for key, built in MECHANISMS.items():
        if config[key] != built:
            raise ValueError(
                f"{key} = {config[key]!r}: this family builds {built!r}")
    if (config["mamba_expand"] * config["hidden_size"]
            != config["mamba_n_heads"] * config["mamba_d_head"]
            or config["shared_intermediate_size"]
            != config["intermediate_size"]):
        raise ValueError("the mixer's inner width or the feed-forward's "
                         "width is stated two ways that disagree")
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["layer_types"] = list(
        config["layer_types"][:config["num_hidden_layers"]])
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``GraniteHybridConfig`` describes."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["layer_types"] = list(cfg.layer_types)
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    kw["layer_types"] = tuple(sizes["layer_types"])
    kw["max_seq_len"] = (config["rehearsal"]["max_seq_len"] if rehearsal
                         else config["engine"]["max_seq_len"])
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](**kw)


def start_weights(config: dict, cfg, seed: int):
    """Dispatch the one jitted program that makes the weights on the device
    from the seed, in the type they are served in; returns at once."""
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.granite_hybrid import init_params
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
    recurrent state is handed from chunk to chunk; the scan kernel, the GQA
    flash kernel at 64-wide heads, W8A8) and then ``decode_steps``
    teacher-forced decode steps through the state and the int8 cache (the
    state-update kernel, the decode kernel) —
    ``TpuBackend.prefill_then_decode_logits`` — against the reference's one
    full forward over prompt + forced tokens in float32 on the same
    weights, its recurrence token by token.

    Three comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``. **The state:** the FIRST
    Mamba layer's recurrent state after the prompt and after each forced
    token (``Family.row_record`` hands it out position by position) against
    the reference's, each as one distance over the reference's length;
    every one within ``state_tolerance``. That layer reads the embedding
    alone, so its state carries the rounding of one product, the
    convolution and the scan's own arithmetic and nothing from the layers
    before. **The state's steps:** what the decode steps added to that
    state — the state after the last forced token less the state after the
    prompt — against the reference's same difference, over the SLOW quarter
    of the layer's heads (those with the smallest ``exp(A_log) *
    softplus(dt_bias)``, which keep hundreds of tokens), within
    ``state_step_tolerance``. A slow head's state is many times what a
    step adds to it, and the prefill's rounding is in both states alike and
    cancels in the difference; what is left is the decode steps' own
    arithmetic, which a state kept a precision below the configured one
    (bfloat16, rounded at every step: 2^-9 of a state twenty times the
    difference) does not meet while the two other limits hardly show it.
    The LAST Mamba layer's state is reported beside them
    (``last_state_error``) and bounds nothing: it carries every layer
    before it.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_granite_h as reference
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
    def plain(params, tokens):
        out = reference.forward(params, tokens, sizes, last=steps + 1,
                                faults=tuple(faults))
        return {"logits": out["logits"],
                "rows": reference.state_as_the_program_lays_it(
                    out["ssm_rows"])}

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids)))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))
    # [rows, first | last, 1, N, HP] against [first | last, rows, N, HP]
    mine = np.asarray(state["rows"], np.float64)[:, :, 0].swapaxes(0, 1)
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
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"],
            "state_error": max(first), "state_errors": first,
            "state_tolerance": spec["state_tolerance"],
            "state_step_error": step, "slow_heads": int(slow.sum()),
            "state_step_tolerance": spec["state_step_tolerance"],
            "last_state_error": max(last),
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and max(first) <= spec["state_tolerance"]
                       and step <= spec["state_step_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "faults": list(faults),
            "state_dtype": str(state["cache"]["ssm"].dtype),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
