"""What every driver's chip-holding child needs: the device, the model and
its weights from a configuration file, compile counting, and the profiler.

Only a process that may hold the chip imports this module's JAX paths.
"""
from __future__ import annotations

import os
import threading
import time

# published config.json key -> LlamaConfig field
HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}
# tiny stand-in sizes for --rehearsal (CPU, interpret-mode kernels)
REHEARSAL_SIZES = {
    "vocab_size": 640, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
}


def require_device(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. Anything but ``chips`` TPU chips ends
    the run before anything is timed; a rehearsal names what it ran on."""
    import jax

    from benchmarks.roofline import load_peaks

    d = jax.devices()
    dev = {"platform": d[0].platform, "kind": d[0].device_kind,
           "count": len(d)}
    if rehearsal:
        return dev
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{dev['count']} x {dev['kind']} ({dev['platform']}) - nothing "
            "was run")
    load_peaks(dev["kind"])   # an unknown kind fails here, not after the run
    return dev


def memory_bytes(program_temp_bytes: int | None) -> dict:
    """The result line's memory keys. ``peak_bytes_in_use`` on the fullest
    chip counts live arrays (weights, a resident cache) and not what a
    running program holds as temporaries, where the one-shot program keeps
    its KV cache; a driver that can name its largest program adds that
    program's temporaries (``one_shot_temp_bytes``). Both parts are given
    beside the sum."""
    import jax

    live = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    return {"memory_peak_bytes": live + (program_temp_bytes or 0),
            "memory_live_peak_bytes": live,
            "memory_program_temp_bytes": program_temp_bytes}


def one_shot_temp_bytes(backend, batch: int, seq: int, max_new: int) -> int:
    """Temporary bytes of the engine's one-shot program at this shape, from
    the compiler's own memory analysis. The program is built and lowered as
    ``TpuBackend.generate`` does it, so its compilation is found in the
    cache that warm-up filled."""
    import jax
    import jax.numpy as jnp

    fn = backend._make_fn(batch, seq, max_new, backend.gen_cfg)
    compiled = fn.lower(
        backend.params, jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32), 0).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def parity_with_reference(backend, config: dict, seed: int,
                          rehearsal: bool) -> dict:
    """Outside the window: the last position's logits of one prompt through
    the engine's own prefill (its kernels, chunking, padding and precision,
    ``TpuBackend._prefill_forward`` as the choice scorer calls it) against
    ``benchmarks/reference.py`` in float32 on the same weights. The error is
    the distance between the two rows over the reference row's length; the
    configuration's file states the prompt's length and the tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference, textgen

    spec = {**config["reference"]["parity"],
            **(config["rehearsal"].get("parity", {}) if rehearsal else {})}
    n, seq = spec["prompt_tokens"], spec["bucket"]
    text = textgen.TextGen(seed + 5).text_of_bytes(n * 12)
    ids = np.asarray(backend.tok.encode(text)[:n], np.int32)
    if len(ids) != n or n > seq:
        raise ValueError(f"parity prompt: {len(ids)} tokens for {n} in {seq}")
    sizes = sizes_of(config, rehearsal)   # the file's, not the engine's
    use_flash, _ = backend._decode_settings(seq, seq)
    window = backend._layer_window_fn()

    @jax.jit
    def program(params, tokens, pad_lens):
        logits, _ = backend._prefill_forward(
            params, tokens, pad_lens, 1, seq, seq, use_flash, window)
        return logits[0, -1, :]

    @jax.jit
    def plain(params, tokens):
        return reference.logits(
            params, tokens, n_heads=sizes["num_attention_heads"],
            n_kv_heads=sizes["num_key_value_heads"],
            rope_theta=sizes["rope_theta"], eps=sizes["rms_norm_eps"],
            qk_norm=config["reference"]["qk_norm"], last=1)[0]

    padded = np.full((1, seq), backend.tok.pad_id, np.int32)
    padded[0, seq - n:] = ids            # prompts are padded on the left
    got = np.asarray(program(backend.params, jnp.asarray(padded),
                             jnp.asarray([seq - n], jnp.int32)), np.float64)
    want = np.asarray(plain(backend.params, jnp.asarray(ids)), np.float64)
    error = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    return {"error": error, "tolerance": spec["tolerance"],
            "ok": bool(np.isfinite(error) and error <= spec["tolerance"]),
            "prompt_tokens": n, "bucket": seq, "kernel": bool(use_flash),
            "same_top_token": bool(got.argmax() == want.argmax()),
            "reference_rms": float(np.sqrt(np.mean(want ** 2)))}


def watch_compiles() -> dict:
    """Counts XLA backend compilations and persistent-cache hits from JAX's
    own monitoring events; read ``["compiles"]`` before and after a window.
    A cache hit is no compilation."""
    import jax.monitoring as mon

    seen = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
            "cache_misses": 0}

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compile_s"] += seconds

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return seen


def sizes_of(config: dict, rehearsal: bool) -> dict:
    if rehearsal:
        return dict(REHEARSAL_SIZES)
    return {k: config[k] for k in HF_TO_FIELD}


def model_config(config: dict, rehearsal: bool):
    """The registry family's LlamaConfig at the sizes the file states."""
    from vnsum_tpu.models import MODEL_REGISTRY

    sizes = sizes_of(config, rehearsal)
    kw = {HF_TO_FIELD[k]: v for k, v in sizes.items()}
    kw["max_seq_len"] = (config["rehearsal"]["max_seq_len"] if rehearsal
                         else config["engine"]["max_seq_len"])
    if rehearsal:
        import jax.numpy as jnp

        kw["dtype"] = jnp.float32
    return MODEL_REGISTRY[config["registry_name"]](**kw)


def make_mesh(config: dict):
    if not config.get("mesh"):
        return None
    from vnsum_tpu.parallel.mesh import mesh_from_spec

    return mesh_from_spec(config["mesh"])


def start_weights(config: dict, cfg, seed: int):
    """Dispatch the one jitted program that makes the weights on the device
    from the seed, in the type they are served in; returns at once."""
    from vnsum_tpu.models import init_params, jitted_init
    from vnsum_tpu.models.quant import init_params_quantized

    init = (init_params_quantized if config["engine"]["weights"] == "int8"
            else init_params)
    return jitted_init(init, cfg, seed)


def backend_kwargs(config: dict, rehearsal: bool) -> dict:
    """TpuBackend keywords from the file's ``engine`` group."""
    e = config["engine"]
    kw = dict(
        quantize=e["weights"] == "int8",
        quantize_act=e["activations"] == "int8",
        quantize_kv={"int8": True, "bf16": False, "auto": "auto"}[e["kv"]],
        prefill_chunk_tokens=e["prefill_chunk_tokens"],
        mesh=make_mesh(config),
    )
    if rehearsal:
        kw.update(interpret=True, mesh=None,
                  prefill_chunk_tokens=config["rehearsal"].get(
                      "prefill_chunk_tokens", 0))
    return kw


def train_bpe(gen, traffic: dict, out_dir) -> tuple:
    """A byte-level BPE of ``traffic["bpe_vocab"]`` entries trained on
    ``traffic["bpe_train_words"]`` words of the generator's text and saved
    under ``out_dir``: (the tokenizer, its ``hf:`` spec, tokens per
    whitespace word of such text)."""
    from vnsum_tpu.models.fixtures import train_bpe_tokenizer

    pilot = gen.paragraphs(traffic["bpe_train_words"])
    hf_tok = train_bpe_tokenizer(iter(pilot), vocab_size=traffic["bpe_vocab"])
    hf_tok.save_pretrained(str(out_dir))
    sample = pilot[:60]
    tokens_per_word = (
        sum(len(x) for x in hf_tok(sample, add_special_tokens=False)["input_ids"])
        / sum(p.count(" ") + 1 for p in sample))
    return hf_tok, f"hf:{out_dir}", tokens_per_word


def precision_of(config: dict) -> dict:
    """Bytes per weight and per cached value, and the peak prefill's
    matmuls run at, for roofline.least_seconds."""
    e = config["engine"]
    kv = "int8" if e["kv"] in ("int8", "auto") else "bf16"
    width = {"int8": 1, "bf16": 2}
    both = e["weights"] == "int8" and e["activations"] == "int8"
    return {"weights": width[e["weights"]], "kv": width[kv],
            "prefill_matmul": "int8" if both else "bf16"}


class Profiler:
    """Traces one stretch on the device, from a helper thread so that the
    thread doing the work is not held up: start, mark, sleep, stop."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._started = threading.Event()
        self.error: BaseException | None = None
        self.wall_s = 0.0
        self.stop_s = 0.0

    def _run(self, seconds: float) -> None:
        import jax

        from benchmarks.trace_reduce import WINDOW_MARK

        try:
            # no Python call tracing and no HLO dump: they made stopping a
            # 30 s trace take four minutes; TraceAnnotations stay
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            time.sleep(0.2)   # the device tracer arms a little after the call
            self._started.set()
            t0 = time.time()
            with jax.profiler.TraceAnnotation(WINDOW_MARK):
                self._stop.wait(seconds)
            self.wall_s = time.time() - t0
            jax.profiler.stop_trace()
            self.stop_s = time.time() - t0 - self.wall_s
        except BaseException as e:  # reported by the caller, never lost
            self.error = e
            self._started.set()

    def start(self, seconds: float) -> None:
        os.makedirs(self.trace_dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._run, args=(seconds,), name="bench-profiler")
        self._thread.start()
        self._started.wait()   # the stretch begins with the trace running

    def stop(self) -> None:
        """End the stretch now (if it has not ended) and wait for the trace
        to be written."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        if self.error is not None:
            raise RuntimeError(f"profiler failed: {self.error!r}")

    def reduce(self) -> dict:
        from benchmarks.trace_reduce import reduce_trace

        return reduce_trace(self.trace_dir)
