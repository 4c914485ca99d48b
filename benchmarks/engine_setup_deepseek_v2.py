"""Set-up of the DeepSeek-V2 family for a driver's chip-holding child: the
model from a configuration file, its weights, and the parity check against
``benchmarks/reference_deepseek_v2.py``.

``engine_setup.py`` builds the dense families: it passes ten dense keys to
the registry and its parity is bound to ``reference.py``'s grouped-query
signature. This module is the same for the family with latent attention and
sparse experts; everything that is not the model (the device, compile
counting, the profiler, ``backend_kwargs``, ``train_bpe``) stays there.
"""
from __future__ import annotations

# published config.json key -> DeepseekV2Config field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "moe_intermediate_size": "moe_intermediate",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok", "n_group": "n_group",
    "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense_replace",
}
ROPE_TO_FIELD = {
    "factor": "rope_factor",
    "original_max_position_embeddings": "rope_original_max_len",
    "beta_fast": "rope_beta_fast", "beta_slow": "rope_beta_slow",
    "mscale": "rope_mscale", "mscale_all_dim": "rope_mscale_all_dim",
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): every
# mechanism of the family, 16 routed experts of which this share holds 8
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 24,
    "intermediate_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 16.0, "first_k_dense_replace": 1,
    "rope_scaling": {"type": "yarn", "factor": 4.0, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64},
    "experts_total": 16, "experts_held": 8, "expert_offset": 0,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, with the deployment's
    share of the experts (``experts_total`` routed, ``experts_held`` of them
    here from ``expert_offset``)."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["rope_scaling"] = dict(config["rope_scaling"])
    sizes["experts_total"] = config["published"]["n_routed_experts"]
    sizes["experts_held"] = config["n_routed_experts"]
    sizes["expert_offset"] = config["expert_parallel"]["expert_offset"]
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``DeepseekV2Config`` describes."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["rope_scaling"] = {k: getattr(cfg, field)
                             for k, field in ROPE_TO_FIELD.items()}
    sizes.update(experts_total=cfg.n_routed_experts,
                 experts_held=cfg.n_held, expert_offset=cfg.expert_offset)
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    kw.update({field: sizes["rope_scaling"][k]
               for k, field in ROPE_TO_FIELD.items()})
    kw.update(n_routed_experts=sizes["experts_total"],
              experts_held=sizes["experts_held"],
              expert_offset=sizes["expert_offset"])
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
    from vnsum_tpu.models.deepseek import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def parity_with_reference(backend, config: dict, seed: int,
                          rehearsal: bool) -> dict:
    """Outside the window: one prompt through the engine's own chunked
    prefill (the family's prefill kernel, W8A8, left padding) and then
    ``decode_steps`` teacher-forced decode steps through the latent cache
    (the absorbed kernel) — ``TpuBackend.prefill_then_decode_logits`` —
    against the reference's one full forward over prompt + forced tokens
    in float32 on the same weights.

    Two comparisons, a limit each, both from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``. Routing is a top-k, which
    is not continuous: where two experts (or two groups) score within the
    rounding of W8A8 of each other the program and the reference pick
    differently, both rightly, and the row's logits differ by several per
    cent. So the engine hands out what its routers picked for each scored
    position and the reference takes those picks where, and only where,
    they are a rightful routing of ITS OWN scores moved by less than
    ``tie_band`` (``reference.ties_broken_their_way``); ``took`` counts the
    layers of each row where it did. A program that routes wrongly outside
    the band is held to the reference's picks. **The cache's rows:** what
    the program's latent cache holds of the prompt and of the forced
    tokens against the reference's own ``(c_kv, k_rope)`` of the same
    positions, layer by layer, as one distance over the reference's
    length. The leading layer reads the embedding alone, so its rows carry
    the rounding of one product and of the cache's own type and nothing
    from the layers before: within ``latent_tolerance``, which a cache kept
    in a lower precision than the configuration states does not meet."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_deepseek_v2 as reference
    from benchmarks import textgen

    spec = {**config["reference"]["parity"],
            **(config["rehearsal"].get("parity", {}) if rehearsal else {})}
    n, seq, steps = spec["prompt_tokens"], spec["bucket"], spec["decode_steps"]
    text = textgen.TextGen(seed + 5).text_of_bytes((n + steps) * 12)
    ids = np.asarray(backend.tok.encode(text)[:n + steps], np.int32)
    if len(ids) != n + steps or n > seq:
        raise ValueError(f"parity prompt: {len(ids)} tokens for {n} in {seq}")
    sizes = sizes_of(config, rehearsal)   # the file's, not the engine's

    @jax.jit
    def plain(params, tokens, picks):
        return reference.forward(
            params, tokens, sizes, expert_offset=sizes["expert_offset"],
            last=steps + 1, theirs=picks, tie_band=spec["tie_band"])

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    # the routers' picks, [rows, expert layers, 1, k] -> [layers, rows, k]
    picks = jnp.asarray(state["rows"][:, :, 0].swapaxes(0, 1))
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids), picks))
    # both score the same positions: the token after the prompt, then the
    # token after each forced one
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))
    # the prompt's rows end at slot ``seq``, the forced tokens' follow
    cache = state["cache"]
    rows = np.asarray(cache["latent"][:, 0, seq - n:seq + steps], np.float64)
    latent_errors = (np.linalg.norm(rows - want["latent"], axis=(1, 2))
                     / np.linalg.norm(want["latent"], axis=(1, 2)))
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"], "tie_band": spec["tie_band"],
            "took": want["took"].sum(0).astype(int).tolist(),
            "latent_error": float(latent_errors[0]),
            "latent_errors": latent_errors.tolist(),
            "latent_tolerance": spec["latent_tolerance"],
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and latent_errors[0] <= spec["latent_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "held_share": int(cache["slots_held"])
            / max(int(cache["slots_routed"]), 1),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
