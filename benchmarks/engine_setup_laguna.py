"""Set-up of the Laguna family for a driver's chip-holding child: the model
from a configuration file, its weights, and the parity check against
``benchmarks/reference_laguna.py``.

The same part ``engine_setup_smallthinker.py`` plays for its family; a
driver finds this module by the ``setup_module`` its configuration file
names (``drivers/offline_pipeline_family.py``). Everything that is not the
model (the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> LagunaConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate",
    "moe_intermediate_size": "moe_intermediate",
    "shared_expert_intermediate_size": "shared_intermediate",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "sliding_window": "sliding_window", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# published rope_parameters.<kind> key -> LagunaConfig field
ROPE_TO_FIELD = {
    "full_attention": {
        "rope_theta": "rope_theta", "factor": "rope_factor",
        "original_max_position_embeddings": "rope_original_max_len",
        "beta_fast": "rope_beta_fast", "beta_slow": "rope_beta_slow",
        "attention_factor": "rope_attention_factor",
        "partial_rotary_factor": "partial_rotary_factor"},
    "sliding_attention": {"rope_theta": "rope_local_theta"},
}
# per layer; a file states them for the published depth and a cut model
# takes the leading entries
PER_LAYER = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
KINDS = ("full_attention", "sliding_attention")
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): the
# dense layer and two periods of [sliding, sliding, sliding, full], 3 and 2
# query heads a KV head, 16 experts top-4, a window shorter than the prompts
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 9,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
    "sliding_window": 48, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 2
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6, 4],
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, the per-layer lists cut
    to the file's depth."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["rope_parameters"] = config["rope_parameters"]
    for k in PER_LAYER:
        sizes[k] = list(config[k][:config["num_hidden_layers"]])
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``LagunaConfig`` describes."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["rope_parameters"] = {
        "full_attention": {"rope_type": "yarn", **{
            k: getattr(cfg, f)
            for k, f in ROPE_TO_FIELD["full_attention"].items()}},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": cfg.rope_local_theta,
                              "partial_rotary_factor": 1}}
    sizes["layer_types"] = [KINDS[s] for s in cfg.sliding_layout]
    sizes["mlp_layer_types"] = (["dense"] * cfg.n_dense_layers
                                + ["sparse"] * cfg.n_sparse_layers)
    sizes["num_attention_heads_per_layer"] = list(cfg.heads_per_layer)
    return sizes


def config_kwargs(sizes: dict) -> dict:
    """``LagunaConfig`` keywords from the published keys. What the family
    stacks by layer kind has to be one number a kind in the lists."""
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    for kind, fields in ROPE_TO_FIELD.items():
        kw.update({f: sizes["rope_parameters"][kind][k]
                   for k, f in fields.items()})
    kinds = sizes["layer_types"]
    kw["sliding_layout"] = tuple(KINDS.index(k) for k in kinds)
    heads = {k: {h for h, kind in zip(
        sizes["num_attention_heads_per_layer"], kinds) if kind == k}
        for k in KINDS}
    if heads["full_attention"] != {sizes["num_attention_heads"]} \
            or len(heads["sliding_attention"]) > 1:
        raise ValueError(f"query heads are not one number a layer kind: {heads}")
    kw["n_heads_sliding"] = next(iter(heads["sliding_attention"]),
                                 sizes["num_attention_heads"])
    dense = [t == "dense" for t in sizes["mlp_layer_types"]]
    kw["n_dense_layers"] = sum(dense)
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers do not lead: "
                         f"{sizes['mlp_layer_types']}")
    return kw


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    kw = config_kwargs(sizes_of(config, rehearsal))
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
    from vnsum_tpu.models.quant import init_params_quantized
    from vnsum_tpu.models.laguna import init_params

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt LONGER THAN THE WINDOW through the
    engine's own chunked prefill (the GQA flash kernel at each layer kind's
    own query heads with the per-layer window, W8A8, left padding, the
    grouped expert product on int8 rows) and
    then ``decode_steps`` teacher-forced decode steps through the int8 cache
    (the decode kernel) — ``TpuBackend.prefill_then_decode_logits`` —
    against the reference's one full forward over prompt + forced tokens in
    float32 on the same weights.

    Two comparisons, a limit each, both from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``. Routing is a top-k, which
    is not continuous: where two experts score within W8A8's rounding of
    each other the program and the reference pick differently, both
    rightly. So the engine hands out what its routers picked for each
    scored position and the reference takes those picks where, and only
    where, they are the top-k of ITS OWN logits moved by less than
    ``tie_band`` (``reference.ties_broken_their_way``); ``took`` counts the
    layers of each row where it did. **The cache's rows:** the keys and
    values the leading layer's cache holds of the prompt and of the forced
    tokens (int8 with a scale a token and KV head) against the reference's,
    as one distance over the reference's length. The leading layer reads
    the embedding alone, so its rows carry the rounding of one product and
    of the cache's own type and nothing from the layers before: within
    ``kv_tolerance``, which a cache kept a precision below the configured
    one (4 bits a value) does not meet, while its logits hardly show it.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_laguna as reference
    from benchmarks import textgen

    spec = {**config["reference"]["parity"],
            **(config["rehearsal"].get("parity", {}) if rehearsal else {})}
    n, seq, steps = spec["prompt_tokens"], spec["bucket"], spec["decode_steps"]
    text = textgen.TextGen(seed + 5).text_of_bytes((n + steps) * 12)
    ids = np.asarray(backend.tok.encode(text)[:n + steps], np.int32)
    if len(ids) != n + steps or n > seq:
        raise ValueError(f"parity prompt: {len(ids)} tokens for {n} in {seq}")
    sizes = sizes_of(config, rehearsal)   # the file's, not the engine's
    if n <= sizes["sliding_window"]:
        raise ValueError(
            f"a parity prompt of {n} tokens never leaves the window of "
            f"{sizes['sliding_window']}")

    @jax.jit
    def plain(params, tokens, picks):
        return reference.forward(
            params, tokens, sizes, last=steps + 1, theirs=picks,
            tie_band=spec["tie_band"], faults=tuple(faults))

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    # the routers' picks, [rows, layers, 1, k] -> [layers, rows, k]
    picks = jnp.asarray(state["rows"][:, :, 0].swapaxes(0, 1))
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids), picks))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))

    # the leading layer's keys and values of the prompt (its rows end at
    # slot ``seq``) and of the forced tokens (each written by its own step)
    cache = state["cache"]

    def held(name, scale):
        rows = np.asarray(cache[name][0, 0, :, seq - n:seq + steps], np.float64)
        if scale in cache:
            rows = rows * np.asarray(
                cache[scale][0, 0, :, seq - n:seq + steps], np.float64)[..., None]
        return rows.swapaxes(0, 1)               # [slots, KV, hd]

    mine = np.concatenate([held("k", "ks"), held("v", "vs")], -1)
    theirs = np.concatenate([want["k"][0], want["v"][0]], -1)
    kv = float(np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"], "tie_band": spec["tie_band"],
            "took": want["took"].sum(0).astype(int).tolist(),
            "kv_error": kv, "kv_tolerance": spec["kv_tolerance"],
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and kv <= spec["kv_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "window": sizes["sliding_window"], "faults": list(faults),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "slots_routed": int(cache["slots_routed"]),
            "slots_held": int(cache["slots_held"]),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
