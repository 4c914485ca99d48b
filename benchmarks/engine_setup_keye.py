"""Set-up of the Keye family for a driver's chip-holding child: the model
from a configuration file, its weights, and the parity check against
``benchmarks/reference_keye.py``.

The same part ``engine_setup_laguna.py`` plays for its family; a driver
finds this module by the ``setup_module`` its configuration file names
(``drivers/offline_pipeline_family.py``). Everything that is not the model
(the device, compile counting, the profiler, ``backend_kwargs``,
``train_bpe``) stays in ``engine_setup.py``.
"""
from __future__ import annotations

# published config.json key -> KeyeConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate",
    "moe_intermediate_size": "moe_intermediate",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# published sa_config key -> KeyeConfig field
SA_TO_FIELD = {"indexer_num_heads": "index_n_heads",
               "indexer_head_dim": "index_head_dim", "topk": "index_topk"}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels): three
# layers, 2 query heads a KV head, 8 experts top-2, 4 indexer heads of 8 and
# a top-k of 48 — shorter than the prompts, so that selection drops keys
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2,
    "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8,
                  "indexer_num_kv_heads": 1, "topk": 48,
                  "q_chunk_size": 512, "kv_chunk_size": 512},
}


def sizes_of(config: dict, rehearsal: bool) -> dict:
    """The published keys as the file states them."""
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    sizes = {k: config[k] for k in HF_TO_FIELD}
    sizes["rope_scaling"] = config["rope_scaling"]
    sizes["sa_config"] = config["sa_config"]
    return sizes


def sizes_from(cfg) -> dict:
    """The same keys read back from a program config: what the reference
    needs to compute the model a ``KeyeConfig`` describes."""
    sizes = {k: getattr(cfg, field) for k, field in HF_TO_FIELD.items()}
    sizes["rope_scaling"] = {"mrope_section": list(cfg.mrope_section)}
    sizes["sa_config"] = {
        **{k: getattr(cfg, field) for k, field in SA_TO_FIELD.items()},
        "indexer_num_kv_heads": 1}
    return sizes


def config_kwargs(sizes: dict) -> dict:
    """``KeyeConfig`` keywords from the published keys."""
    kw = {field: sizes[k] for k, field in HF_TO_FIELD.items()}
    kw["mrope_section"] = tuple(sizes["rope_scaling"]["mrope_section"])
    kw.update({field: sizes["sa_config"][k] for k, field in SA_TO_FIELD.items()})
    if sizes["sa_config"].get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer is built for ONE key head a token")
    return kw


def model_config(config: dict, rehearsal: bool, **more):
    """The registry family's config at the sizes the file states; ``more``
    are further fields (a faulted reading's ``index_sum_dtype``)."""
    from vnsum_tpu.models import MODEL_REGISTRY

    kw = config_kwargs(sizes_of(config, rehearsal))
    kw["max_seq_len"] = (config["rehearsal"]["max_seq_len"] if rehearsal
                         else config["engine"]["max_seq_len"])
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](**{**kw, **more})


def start_weights(config: dict, cfg, seed: int):
    """Dispatch the one jitted program that makes the weights on the device
    from the seed, in the type they are served in; returns at once."""
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.keye import init_params
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def selection_agreement(mine, scores, own, want_scores, band: float) -> dict:
    """One scored position's sets side by side: ``mine`` / ``own`` [T] bool
    (the program's and the reference's), ``scores`` / ``want_scores`` [T]
    (each side's index scores of the visible slots). -> how many slots each
    keeps, how many they do not share, the largest distance of an unshared
    slot's REFERENCE score from the reference's cut over the cut's scale
    (the standard deviation of the visible scores), how many unshared slots
    lie further than ``band`` from it, and the two score rows' distance
    over the reference row's length."""
    import numpy as np

    seen = np.isfinite(want_scores)
    cut = want_scores[own].min() if own.any() else 0.0
    spread = float(want_scores[seen].std()) or 1.0
    unshared = mine != own
    far = np.abs(want_scores[unshared] - cut) / spread
    both = seen & np.isfinite(scores)
    return {"kept": int(mine.sum()), "reference_kept": int(own.sum()),
            "unshared": int(unshared.sum()),
            "unshared_from_cut": float(far.max()) if far.size else 0.0,
            "outside_band": int((far > band).sum()),
            "same_slots_seen": bool((seen == np.isfinite(scores)).all()),
            "score_error": float(
                np.linalg.norm(scores[both] - want_scores[both])
                / max(np.linalg.norm(want_scores[both]), 1e-30))}


def parity_with_reference(backend, config: dict, seed: int, rehearsal: bool,
                          faults=()) -> dict:
    """Outside the window: one prompt several times ``topk`` long through
    the engine's own chunked prefill (left pad inside the first chunks; the
    selection and masked-attention kernels chunk by chunk, so later chunks
    select among keys of earlier ones; W8A8; the grouped expert product)
    and then ``decode_steps`` teacher-forced decode steps through the int8
    KV cache and the indexer-key cache — ``TpuBackend.
    prefill_then_decode_logits`` — against the reference's one full forward
    over prompt + forced tokens in float32 on the same weights.

    Four comparisons, a limit each, all from the file. **Logits:** a row's
    error is the distance between the two rows over the reference row's
    length, for the prefill's last position and each decode step; every row
    within ``tolerance``. A top-k is not continuous, twice over here. The
    engine hands out, for each scored position, every layer's selection
    (``sel``) and its routers' picks. The reference takes the program's
    choice of a slot where, and only where, the slot's score — in the
    REFERENCE's index scores — lies within ``select_band`` x the standard
    deviation of the row's visible scores of the reference's cut: a
    near-tie broken the program's way is the program's, a slot far from
    the cut stays the reference's own whatever the program did with it
    (``reference.their_slots_near_the_cut``; ONE band for every layer;
    ``sel_took`` counts the layers a row whose whole set lay inside it) —
    so a layer whose indexer picks other keys than the reference's moves
    the logits, in any layer. It takes the picks where they are a top-k of
    its own router logits each moved by less than ``tie_band``
    (``ties_broken_their_way``; ``took``). **The selection:** the FIRST
    layer reads the embedding alone (one W8A8 product, the bfloat16 index
    products, the float32 sums), so it is held to the reference directly:
    its index scores against the reference's as one distance a position,
    within ``score_tolerance``, and every unshared slot within
    ``select_band`` of the cut. The later layers' inputs carry every
    rounding before them and, where a router's near-tie fell the other way
    upstream, another expert's output: a few of their slots lie far from
    the reference's cut on sound runs (``selection_deep``: each layer's
    most unshared slots, their largest distance from the cut and how many
    lie outside the band, recorded every run), and no more than
    ``deep_outside_band`` of a layer's slots may. **The kernel's own sums:** the engine also hands out each scored
    position's indexer queries and head weights; with the cached indexer
    keys the check recomputes every layer's scores of that position in
    float64 FROM THE PROGRAM'S OWN OPERANDS, so that what is left is the
    selection kernel's arithmetic alone, in all twelve layers: within
    ``sum_tolerance`` (float32 sums of bfloat16 products read ~1e-7; sums
    kept in bfloat16 do not meet it — and nothing else sees them: against
    the reference's scores W8A8's rounding of the indexer's inputs is the
    larger term, PERF.md section 7 (cu)), and the program's set must be
    EXACTLY the top-k of its own recorded scores, ties to the lower slot
    (``selection_exact``), every layer and position. That side is fed what
    the program recorded, so the recorded operands are themselves held to
    the reference: the first layer's indexer queries within
    ``q_tolerance``, its head weights within ``w_tolerance``, its keys
    within ``ki_tolerance`` below. **The caches' rows:** the first layer's
    keys and values (int8, a scale a token and KV head) within
    ``kv_tolerance`` — which a 4-bit cache does not meet — and its indexer
    keys within ``ki_tolerance``.

    ``faults`` are passed to the reference (``reference.FAULTS``): the
    tests and the chip's faulted readings use them; a run passes none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_keye as reference
    from benchmarks import textgen

    spec = {**config["reference"]["parity"],
            **(config["rehearsal"].get("parity", {}) if rehearsal else {})}
    n, seq, steps = spec["prompt_tokens"], spec["bucket"], spec["decode_steps"]
    text = textgen.TextGen(seed + 5).text_of_bytes((n + steps) * 12)
    ids = np.asarray(backend.tok.encode(text)[:n + steps], np.int32)
    if len(ids) != n + steps or n > seq:
        raise ValueError(f"parity prompt: {len(ids)} tokens for {n} in {seq}")
    sizes = sizes_of(config, rehearsal)   # the file's, not the engine's
    topk = sizes["sa_config"]["topk"]
    if n <= topk:
        raise ValueError(f"a parity prompt of {n} tokens drops no key at "
                         f"top-{topk}")

    got, state = backend.prefill_then_decode_logits(
        ids[:n].tolist(), ids[n:].tolist(), bucket=seq, return_state=True)
    rows, cache = state["rows"], state["cache"]
    T = n + steps
    # [positions, L, 1, C] over cache slots -> [L, positions, T] over the
    # sequence: the prompt ends at slot ``seq``, the forced tokens follow
    at = slice(seq - n, seq + steps)
    mine_sel = np.asarray(rows["sel"])[:, :, 0, at].swapaxes(0, 1) != 0
    mine_scores = np.asarray(rows["sel_scores"], np.float64)[
        :, :, 0, at].swapaxes(0, 1)
    picks = np.asarray(rows["picks"])[:, :, 0].swapaxes(0, 1)

    def plain(params, tokens, picks, sel, sel_band):
        return reference.forward(
            params, tokens, sizes, last=steps + 1, theirs=picks,
            tie_band=spec["tie_band"], their_sel=sel, sel_band=sel_band,
            faults=tuple(faults))

    want = jax.tree.map(
        lambda a: np.asarray(a, np.float64),
        jax.jit(plain)(backend.params, jnp.asarray(ids), jnp.asarray(picks),
                       jnp.asarray(mine_sel),
                       jnp.float32(spec["select_band"])))
    got = np.asarray(got, np.float64)
    errors = (np.linalg.norm(got - want["logits"], axis=-1)
              / np.linalg.norm(want["logits"], axis=-1))

    # every layer's sets side by side, position by position: [L][positions]
    by_layer = [[selection_agreement(
        mine_sel[l, i], mine_scores[l, i], want["own"][l, i] != 0,
        np.where(np.arange(T) <= n - 1 + i, want["scores"][l, i], -np.inf),
        spec["select_band"]) for i in range(steps + 1)]
        for l in range(mine_sel.shape[0])]
    first = by_layer[0]
    deep = {name: [max(f[name] for f in layer) for layer in by_layer[1:]]
            for name in ("unshared", "unshared_from_cut", "outside_band",
                         "score_error")}

    # the kernel's own arithmetic: every layer's scores of every scored
    # position again, in float64, from the operands the program recorded
    keys = np.asarray(cache["ki"][:, 0], np.float64)[:, :, at]      # [L,di,T]
    q_rec = np.asarray(rows["sel_q"], np.float64)[:, :, 0]       # [pos,L,Hi,di]
    w_rec = np.asarray(rows["sel_w"], np.float64)[:, :, 0]          # [pos,L,Hi]
    exact = np.einsum("plh,plht->plt", w_rec, np.maximum(
        np.einsum("plhd,ldt->plht", q_rec, keys), 0.0)).swapaxes(0, 1)
    seen = np.arange(T)[None, :] <= (n - 1 + np.arange(steps + 1))[:, None]
    sum_error = max(
        float(np.linalg.norm((mine_scores[l, i] - exact[l, i])[seen[i]])
              / max(np.linalg.norm(exact[l, i][seen[i]]), 1e-30))
        for l in range(exact.shape[0]) for i in range(steps + 1))
    own_top = np.asarray(reference.top_by_sort(
        jnp.asarray(np.where(np.isfinite(mine_scores), mine_scores, -np.inf)
                    .reshape(-1, T), jnp.float32),
        jnp.asarray(np.broadcast_to(seen, mine_sel.shape).reshape(-1, T)),
        topk)).reshape(mine_sel.shape)
    selection_exact = bool((own_top == mine_sel).all())

    def held(name, scale):
        x = np.asarray(cache[name][0, 0, :, at], np.float64)
        if scale in cache:
            x = x * np.asarray(cache[scale][0, 0, :, at], np.float64)[..., None]
        return x.swapaxes(0, 1)                  # [slots, KV, hd]

    mine = np.concatenate([held("k", "ks"), held("v", "vs")], -1)
    theirs = np.concatenate([want["k"][0], want["v"][0]], -1)
    kv = float(np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))
    ki_mine = np.asarray(cache["ki"][0, 0, :, at], np.float64).T
    ki = float(np.linalg.norm(ki_mine - want["ki"][0])
               / np.linalg.norm(want["ki"][0]))
    # the operands the program recorded against the reference's own, the
    # first layer's (their keys are ``ki`` above)
    q_error = float(np.linalg.norm(q_rec[:, 0] - want["qi"][0])
                    / np.linalg.norm(want["qi"][0]))
    w_error = float(np.linalg.norm(w_rec[:, 0] - want["wi"][0])
                    / np.linalg.norm(want["wi"][0]))
    score_error = max(f["score_error"] for f in first)
    from_cut = max(f["unshared_from_cut"] for f in first)
    paths = backend.stats.attention_paths.get(f"logits[B=1,S={seq}]", {})
    return {"error": float(errors.max()), "errors": errors.tolist(),
            "tolerance": spec["tolerance"], "tie_band": spec["tie_band"],
            "took": want["took"].sum(0).astype(int).tolist(),
            "select_band": spec["select_band"],
            "sel_took": want["sel_took"].sum(0).astype(int).tolist(),
            "selection": first, "selection_deep": deep,
            "score_error": score_error,
            "score_tolerance": spec["score_tolerance"],
            "unshared_from_cut": from_cut,
            "deep_outside_band": spec["deep_outside_band"],
            "sum_error": sum_error, "sum_tolerance": spec["sum_tolerance"],
            "q_error": q_error, "q_tolerance": spec["q_tolerance"],
            "w_error": w_error, "w_tolerance": spec["w_tolerance"],
            "selection_exact": selection_exact,
            "kv_error": kv, "kv_tolerance": spec["kv_tolerance"],
            "ki_error": ki, "ki_tolerance": spec["ki_tolerance"],
            "ok": bool(np.all(np.isfinite(errors))
                       and errors.max() <= spec["tolerance"]
                       and kv <= spec["kv_tolerance"]
                       and ki <= spec["ki_tolerance"]
                       and score_error <= spec["score_tolerance"]
                       and sum_error <= spec["sum_tolerance"]
                       and q_error <= spec["q_tolerance"]
                       and w_error <= spec["w_tolerance"]
                       and selection_exact
                       and from_cut <= spec["select_band"]
                       and max(deep["outside_band"], default=0)
                       <= spec["deep_outside_band"]
                       and all(f["same_slots_seen"]
                               and f["kept"] == f["reference_kept"]
                               for f in first)),
            "prompt_tokens": n, "bucket": seq, "decode_steps": steps,
            "topk": topk, "faults": list(faults),
            "kernel": bool(paths) and all(
                p == "kernel" for p in paths.values()),
            "same_top_token": bool(
                (got.argmax(-1) == want["logits"].argmax(-1)).all()),
            "slots_routed": int(cache["slots_routed"]),
            "slots_held": int(cache["slots_held"]),
            "reference_rms": float(np.sqrt(np.mean(want["logits"] ** 2)))}
