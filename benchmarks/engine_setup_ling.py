"""Set-up of the Ling-3.0-flash family for a driver's chip-holding child:
the model from a configuration file, its weights, and the parity check
against ``benchmarks/reference_ling.py``.

The same part ``engine_setup_lfm2.py`` plays for its family; a driver finds
this module by the ``setup_module`` its configuration file names
(``drivers/offline_pipeline_family.py``). Everything that is not the model
(the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> LingConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "intermediate",
    "moe_intermediate_size": "moe_intermediate",
    "moe_shared_expert_intermediate_size": "shared_intermediate",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_group": "n_group", "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense_replace",
    "layer_group_size": "layer_group_size",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "short_conv_kernel_size": "short_conv_kernel_size",
    "kda_lower_bound": "kda_lower_bound",
}
# sizes no published key states (keys of the file under the harness's names,
# each with its basis under ``assumed``) -> field
ASSUMED_TO_FIELD = {"tie_word_embeddings": "tie_embeddings",
                    "kda_chunk_size": "kda_chunk_size"}
# published keys that say which mechanisms the model has; this family builds
# exactly these and refuses a file that states another
MECHANISMS = {
    "q_lora_rank": None, "score_function": "sigmoid",
    "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "num_kv_heads_for_linear_attn": 0, "use_qk_norm": True,
    "use_mla_nope": False, "use_nGPT": False, "scale_router_input": False,
    "value_norm": False, "up_proj_norm": False, "partial_rotary_factor": 0.5,
    "rotary_dim": 64,
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): two
# periods K K M of which the first layer is dense, 16 experts top-3 in 4
# groups of which 2 are kept and HALF held, 4 heads of 16, a scan chunk of
# two sub-blocks
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 128,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
    "layer_group_size": 3, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "tie_word_embeddings": False, "kda_chunk_size": 32,
    "experts_total": 16, "experts_held": 8, "expert_offset": 0,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, with the sizes it
    assumes, and the expert share: ``experts_total`` (what the router keeps:
    the published count), ``experts_held`` (the file's reduced
    ``num_experts``) and ``expert_offset``."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    for key, built in MECHANISMS.items():
        if config[key] != built:
            raise ValueError(
                f"{key} = {config[key]!r}: this family builds {built!r}")
    depth = config["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config[key][:depth]):
            raise ValueError(
                f"{key} clamps a layer among the first {depth}: the clamp "
                "is not built")
    sizes = {k: config[k] for k in (*HF_TO_FIELD, *ASSUMED_TO_FIELD)}
    share = config["expert_parallel"]
    if share["experts_held"] != config["num_experts"]:
        raise ValueError("num_experts is the experts this chip holds")
    sizes.update(experts_total=config["published"]["num_experts"],
                 experts_held=share["experts_held"],
                 expert_offset=share["expert_offset"])
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``LingConfig`` describes."""
    sizes = {k: getattr(cfg, field)
             for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}
    sizes.update(experts_total=cfg.n_routed_experts,
                 experts_held=cfg.n_held, expert_offset=cfg.expert_offset)
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states (the
    family refuses a mechanism it does not build: ``sizes_of``)."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k]
          for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}
    kw.update(n_routed_experts=sizes["experts_total"],
              experts_held=sizes["experts_held"],
              expert_offset=sizes["expert_offset"])
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
    from vnsum_tpu.models.ling import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def grid_distance(rows) -> float:
    """How far latent rows [n, w] sit from an int8 grid a row: each row over
    its largest magnitude / 127, the mean distance to the nearest whole
    number. A row kept in bfloat16 reads ~0.2 (its own 8-bit steps fall
    between the grid's), one rounded to int8 where it was written ~0.03 (the
    bfloat16 rounding of the grid's own points)."""
    import numpy as np

    rows = np.asarray(rows, np.float64)
    r = rows / (np.abs(rows).max(-1, keepdims=True) / 127.0 + 1e-30)
    return float(np.abs(r - np.round(r)).mean())


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt behind a left pad through the
    engine's own chunked prefill (four chunks in the 8192 bucket, so every
    KDA layer's matrix state and tail and the latent rows cross the chunk
    boundaries; W8A8; ``kda_prefill_scan``; ``mla_prefill_attention`` at 32
    heads; the grouped expert product on int8 rows) and then
    ``decode_steps`` teacher-forced decode steps through state, tails,
    latent cache and experts (``kda_decode_update``, the absorbed
    ``mla_decode_attention``) — ``TpuBackend.prefill_then_decode_logits`` —
    against the reference's one full forward over prompt + forced tokens in
    float32 on the same weights, its delta rule token by token.

    Seven comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``, and the LAST row within
    ``decode_tolerance``. Routing is a top-k, which is not continuous: the
    engine hands out what its routers picked for each scored position
    (``Family.row_record``) and the reference takes those picks where, and
    only where, they are a rightful group-limited top-k of ITS OWN ranking
    within ``tie_band`` (``reference.ties_broken_their_way``); ``took``
    counts the layers of each row where it did. **The picks:** on the FIRST
    sparse layer every scored row's picks have to be the reference's own or
    rightful within the band. **The state** and **the state's steps:** the
    FIRST KDA layer's matrix state after the prompt and after each forced
    token within ``state_tolerance``, and what the decode steps added to it
    over the slow quarter of the layer's (head, key channel) pairs — the
    smallest ``exp(A_log) * dt_bias``, whose state is large beside a step's
    change — within ``state_step_tolerance``: the limit a state kept in
    bfloat16 (rounded after every chunk and step) does not meet. **The
    latent:** the LEADING MLA layer's latent rows of every token against
    the reference's within ``latent_tolerance``, and their distance from an
    int8 grid (``grid_distance``) at least ``latent_grid_floor``: a latent
    rounded to int8 where it is written sits ON the grid, whatever the
    layers before it added to the rows. The LAST KDA layer's state and the
    last MLA layer's rows are reported and bound nothing.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_ling as reference
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
                "rows": out["state_rows"], "latent": out["latent"]}

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    # the routers' picks, [rows, layers, 1, k] -> [layers, rows, k]
    picks = jnp.asarray(state["rows"]["picks"][:, :, 0].swapaxes(0, 1))
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids), picks))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))
    took = want["took"].astype(bool)
    same = np.sort(np.asarray(picks[0]), -1) == np.sort(
        want["ids"][0].astype(np.int64), -1)
    picks_ok = bool((took[0] | same.all(-1)).all())
    # [rows, first | last, 1, H, dv, dk] against [first | last, rows, ...]
    mine = np.asarray(state["rows"]["state"].astype(np.float32),
                      np.float64)[:, :, 0].swapaxes(0, 1)
    first = [_distance(mine[0, r], want["rows"][0, r])
             for r in range(steps + 1)]
    last = [_distance(mine[1, r], want["rows"][1, r])
            for r in range(steps + 1)]
    # the slow quarter of the first layer's (head, key channel) pairs
    kda = backend.params["kda"]
    rate = (np.exp(np.asarray(kda["A_log"][0], np.float64))[:, None]
            * np.asarray(kda["dt_bias"][0], np.float64))         # [H, dk]
    slow = rate <= np.quantile(rate, 0.25)
    lanes = np.broadcast_to(slow[:, None, :], mine.shape[2:])
    step = _distance((mine[0, -1] - mine[0, 0])[lanes],
                     (want["rows"][0, -1] - want["rows"][0, 0])[lanes])
    cache = state["cache"]
    rows = np.asarray(cache["latent"][:, 0, seq - n:seq + steps].astype(
        np.float32), np.float64)
    latent_errors = (np.linalg.norm(rows - want["latent"], axis=(1, 2))
                     / np.linalg.norm(want["latent"], axis=(1, 2)))
    grid = grid_distance(rows[0])
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
            "state_step_error": step, "slow_channels": int(slow.sum()),
            "state_step_tolerance": spec["state_step_tolerance"],
            "last_state_error": max(last),
            "latent_error": float(latent_errors[0]),
            "latent_errors": latent_errors.tolist(),
            "latent_tolerance": spec["latent_tolerance"],
            "latent_grid_distance": grid,
            "latent_grid_floor": spec["latent_grid_floor"],
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and errors[-1] <= spec["decode_tolerance"]
                       and picks_ok
                       and max(first) <= spec["state_tolerance"]
                       and step <= spec["state_step_tolerance"]
                       and latent_errors[0] <= spec["latent_tolerance"]
                       and grid >= spec["latent_grid_floor"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "faults": list(faults),
            "state_dtype": str(cache["kda"].dtype),
            "latent_dtype": str(cache["latent"].dtype),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "slots_routed": int(cache["slots_routed"]),
            "slots_held": int(cache["slots_held"]),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
