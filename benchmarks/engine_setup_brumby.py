"""Set-up of the Brumby family for a driver's chip-holding child: the model
from a configuration file, its weights, and the parity check against
``benchmarks/reference_brumby.py``.

The same part ``engine_setup_granite_h.py`` plays for its family; a driver
finds this module by the ``setup_module`` its configuration file names
(``drivers/offline_pipeline_family.py``). Everything that is not the model
(the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> BrumbyConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}
# sizes no published key states (keys of the file under the harness's names,
# each with its basis under ``assumed``) -> field
ASSUMED_TO_FIELD = {"retention_degree": "retention_degree",
                    "retention_eps": "retention_eps",
                    "retention_chunk_size": "retention_chunk_size"}
# published keys that say which mechanisms the model has; this family builds
# exactly these and refuses a file that states another
MECHANISMS = {
    "model_type": "brumby", "attention_bias": False, "hidden_act": "silu",
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): three
# layers, 4 query heads on 2 KV heads of 16 (9 tiles of phi), retention
# chunks of 8
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "retention_degree": 2,
    "retention_eps": 1e-6, "retention_chunk_size": 8,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, with the sizes it
    assumes."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    for key, built in MECHANISMS.items():
        if config[key] != built:
            raise ValueError(
                f"{key} = {config[key]!r}: this family builds {built!r}")
    return {k: config[k] for k in (*HF_TO_FIELD, *ASSUMED_TO_FIELD)}


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``BrumbyConfig`` describes."""
    return {k: getattr(cfg, field)
            for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}


def model_config(config: dict, rehearsal: bool):
    """The registry family's config at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k]
          for k, field in {**HF_TO_FIELD, **ASSUMED_TO_FIELD}.items()}
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
    from vnsum_tpu.models.brumby import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def as_the_reference_lays_it(state, normaliser):
    """The program's state [..., T, dv, d] (tile r, value channel, lane i:
    the sum of ``k_i k_{i-r} v``) and unpacked normaliser [..., d, d] as the
    reference's ``state_sums`` lays them: [..., n, dv] and [..., n] over the
    ``n = d (d + 1) / 2`` pairs ``i <= j`` in row-major order, off-diagonal
    pairs times sqrt 2. Pair ``(i, j)`` sits in tile ``j - i`` at lane ``j``
    where that is at most ``d / 2``, else in tile ``d - (j - i)`` at lane
    ``i``."""
    import numpy as np

    d = state.shape[-1]
    i, j = np.triu_indices(d)
    near = (j - i) <= d // 2
    tile = np.where(near, j - i, d - (j - i))
    lane = np.where(near, j, i)
    weight = np.where(i == j, 1.0, np.sqrt(2.0))
    S = np.moveaxis(np.asarray(state)[..., tile, :, lane], 0, -2)
    return (S * weight[:, None], np.asarray(normaliser)[..., i, j] * weight)


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt behind a left pad through the
    engine's own chunked prefill (four chunks in the 8192 bucket, the left
    pad inside the first, so state and normaliser of every layer cross
    every chunk boundary; ``retention_prefill_scan``, W8A8) and then
    ``decode_steps`` teacher-forced decode steps through the state
    (``retention_decode_update``) —
    ``TpuBackend.prefill_then_decode_logits`` — against the reference's one
    full forward over prompt + forced tokens in float32 on the same
    weights, in the ATTENTION form, and its state from the definition (a sum
    over tokens).

    Four comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``. **The state** and **the
    normaliser:** the FIRST layer's, after the prompt and after each forced
    token (``Family.row_record`` hands them out position by position; laid
    as the reference lays them), each as one distance over the reference's
    length, every one within ``state_tolerance`` and
    ``normaliser_tolerance``. That layer reads the embedding alone, so its
    state carries the rounding of one product, QK-norm, rotary and the
    scan's own arithmetic and nothing from the layers before. **The state's
    steps:** what the decode steps added to that state — the state after
    the last forced token less the state after the prompt — against the
    reference's same difference, over the SLOW quarter of the layer's KV
    heads (the largest ``b_g``: they keep thousands of tokens), within
    ``state_step_tolerance``. A slow head's state is many times what a step
    adds to it, and the prefill's rounding is in both states alike and
    cancels in the difference; what is left is the decode steps' own
    arithmetic, which a state kept a precision below the configured one
    (bfloat16, rounded at every chunk and step) does not meet. The LAST
    layer's state is reported beside them (``last_state_error``) and bounds
    nothing: it carries every layer before it.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_brumby as reference
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
    def plain(params, tokens):
        return reference.forward(params, tokens, sizes, last=steps + 1,
                                 faults=tuple(faults))

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    want = jax.tree.map(np.asarray, plain(backend.params, jnp.asarray(ids)))
    got = np.asarray(got, np.float64)
    theirs = np.asarray(want["logits"], np.float64)
    errors = (np.linalg.norm(got - theirs, axis=-1)
              / np.linalg.norm(theirs, axis=-1))
    # [rows, first | last, 1, KV, ...] -> the reference's [rows, KV, n, dv]
    rows = state["rows"]
    first, first_z = as_the_reference_lays_it(
        np.asarray(rows["state"][:, 0, 0], np.float32),
        np.asarray(rows["normaliser"][:, 0, 0], np.float32))
    last, _ = as_the_reference_lays_it(
        np.asarray(rows["state"][:, 1, 0], np.float32),
        np.asarray(rows["normaliser"][:, 1, 0], np.float32))
    S, z = want["state_rows"], want["normaliser_rows"]
    state_errors = [_distance(first[r], S[0, r]) for r in range(steps + 1)]
    z_errors = [_distance(first_z[r], z[0, r]) for r in range(steps + 1)]
    last_errors = [_distance(last[r], S[1, r]) for r in range(steps + 1)]
    # the slow quarter of the first layer's KV heads: the largest b_g
    b_g = np.asarray(backend.params["layers"]["b_gate_ret"][0], np.float64)
    slow = np.argsort(-b_g)[:max(len(b_g) // 4, 1)]
    step = _distance((first[-1] - first[0])[slow],
                     (S[0, -1] - S[0, 0])[slow])
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"],
            "state_error": max(state_errors), "state_errors": state_errors,
            "state_tolerance": spec["state_tolerance"],
            "normaliser_error": max(z_errors),
            "normaliser_tolerance": spec["normaliser_tolerance"],
            "state_step_error": step, "slow_heads": sorted(slow.tolist()),
            "state_step_tolerance": spec["state_step_tolerance"],
            "last_state_error": max(last_errors),
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and max(state_errors) <= spec["state_tolerance"]
                       and max(z_errors) <= spec["normaliser_tolerance"]
                       and step <= spec["state_step_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "faults": list(faults),
            "state_dtype": str(state["cache"]["ret"].dtype),
            "cache_leaves": sorted(state["cache"]),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == theirs.argmax(-1)).all()),
            "reference_rms": float(np.sqrt(np.mean(theirs ** 2)))}
