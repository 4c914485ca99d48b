"""Set-up of a dense stack LOOPED over its weights (Ouro) for a driver's
chip-holding child: the model from a configuration file, its weights, and
the parity check against ``benchmarks/reference_ouro.py``.

The same part ``engine_setup_smallthinker.py`` plays for its family; a
driver finds this module by the ``setup_module`` its configuration file
names (``drivers/offline_pipeline_family.py``). The program is the dense
family's (``models/llama.py`` at ``loop_passes`` > 1), so what this module
adds to ``engine_setup.py``'s dense set-up is the loop's two keys, the
mechanisms the file has to state, and a parity check that looks at the
cache of the first AND the last pass. Everything that is not the model (the
device, compile counting, the profiler, ``backend_kwargs``, ``train_bpe``)
stays in ``engine_setup.py``.
"""
from __future__ import annotations

from benchmarks import engine_setup

# published config.json key -> LlamaConfig field: the dense set-up's, and the
# loop's
HF_TO_FIELD = {**engine_setup.HF_TO_FIELD, "total_ut_steps": "loop_passes"}
# published keys that say which mechanisms the model has; the program builds
# exactly these and a file that states another is refused
MECHANISMS = {
    "model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
    "use_sliding_window": False, "sliding_window": None,
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): three
# passes over two layers, a KV head a query head
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "total_ut_steps": 3,
    "early_exit_threshold": 1,
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them, ``early_exit_threshold``
    beside them (no field of the program's config: the registry's preset
    refuses a value under 1)."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    for key, built in MECHANISMS.items():
        if config[key] != built:
            raise ValueError(
                f"{key} = {config[key]!r}: the looped dense stack builds "
                f"{built!r}")
    kinds = set(config["layer_types"][:config["num_hidden_layers"]])
    if kinds != {"full_attention"}:
        raise ValueError(f"layer_types {sorted(kinds)}: every layer of this "
                         "stack attends over the whole cache")
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["early_exit_threshold"] = config["early_exit_threshold"]
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a looped ``LlamaConfig`` describes."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["early_exit_threshold"] = 1   # the only one the program runs
    return sizes


def model_config(config: dict, rehearsal: bool):
    """The registry preset's config at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    kw["max_seq_len"] = (config["rehearsal"]["max_seq_len"] if rehearsal
                         else config["engine"]["max_seq_len"])
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](
        early_exit_threshold=sizes["early_exit_threshold"], **kw)


# the one jitted program that makes the weights on the device from the seed:
# the dense family's own (its init draws the exit gate where the stack loops)
start_weights = engine_setup.start_weights


def _distance(mine, theirs) -> float:
    import numpy as np

    mine = np.asarray(mine, np.float64)
    theirs = np.asarray(theirs, np.float64)
    return float(np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt behind a left pad through the
    engine's own chunked prefill (four chunks in the 8192 bucket, each a
    row piece behind the pad; the GQA flash kernel at one query head a KV
    head, W8A8, every pass writing its own cache layers) and then
    ``decode_steps`` teacher-forced decode steps through the int8 cache of
    ``T * L`` layers (the decode kernel) —
    ``TpuBackend.prefill_then_decode_logits`` — against the reference's one
    full forward over prompt + forced tokens in float32 on the same
    weights.

    Three comparisons, a limit each, all from the file. **Logits:** the
    error of a row is the distance between the two rows of logits over the
    reference row's length, for the prefill's last position and for each
    decode step; every row within ``tolerance``. **The first pass's
    cache:** the dequantized keys and values that cache layer 0 — pass 0 of
    the first layer — holds of the prompt and of the forced tokens against
    the reference's pass 0 of that layer, as one distance; within
    ``kv_tolerance``. That layer reads the embedding alone: one W8A8
    product's rounding and the cache's own. **The last pass's cache:** the
    same for cache layer ``(T - 1) * L``, the first layer's LAST pass,
    against the reference's last pass of that layer; within
    ``kv_last_pass_tolerance``. Its input is the stream after T - 1 whole
    passes and their norms, so it carries their rounding — and it is where
    a pass that wrote or read another pass's layer, or a rotary that hangs
    on the pass, shows when the logits alone might not. The rows the DECODE
    steps wrote are reported apart (``kv_decode_errors``) and held to the
    same two limits.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_ouro as reference
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
    cfg = backend.cfg
    first, last = 0, (sizes["total_ut_steps"] - 1) * cfg.n_layers
    if backend.family.attention_layers(cfg) != (
            sizes["total_ut_steps"] * sizes["num_hidden_layers"]):
        raise ValueError("the engine's cache is not a layer a (pass, layer)")

    @jax.jit
    def plain(params, tokens):
        return reference.forward(params, tokens, sizes, last=steps + 1,
                                 keep=(first, last), faults=tuple(faults))

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    want = jax.tree.map(lambda a: np.asarray(a, np.float64),
                        plain(backend.params, jnp.asarray(ids)))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))
    cache = state["cache"]

    def held(layer):
        """[slots, KV, 2 hd]: the layer's keys | values of the prompt (its
        rows end at slot ``seq``) and of the forced tokens."""
        def rows(name, scale):
            r = np.asarray(cache[name][layer, 0, :, seq - n:seq + steps],
                           np.float64)
            if scale in cache:
                r = r * np.asarray(
                    cache[scale][layer, 0, :, seq - n:seq + steps],
                    np.float64)[..., None]
            return r.swapaxes(0, 1)
        return np.concatenate([rows("k", "ks"), rows("v", "vs")], -1)

    def theirs(layer):
        if layer not in want["k"]:      # a fault that runs fewer passes
            return None
        return np.concatenate([want["k"][layer], want["v"][layer]], -1)

    kv, kv_decode = {}, {}
    for name, layer in (("first", first), ("last", last)):
        mine, ref_rows = held(layer), theirs(layer)
        kv[name] = (_distance(mine, ref_rows)
                    if ref_rows is not None else float("inf"))
        kv_decode[name] = (_distance(mine[n:], ref_rows[n:])
                           if ref_rows is not None else float("inf"))
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"],
            "kv_error": kv["first"], "kv_tolerance": spec["kv_tolerance"],
            "kv_last_pass_error": kv["last"],
            "kv_last_pass_tolerance": spec["kv_last_pass_tolerance"],
            "kv_decode_errors": [kv_decode["first"], kv_decode["last"]],
            "cache_layers_seen": [first, last],
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and max(kv["first"], kv_decode["first"])
                       <= spec["kv_tolerance"]
                       and max(kv["last"], kv_decode["last"])
                       <= spec["kv_last_pass_tolerance"]),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "pad": seq - n, "passes": sizes["total_ut_steps"],
            "faults": list(faults),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
