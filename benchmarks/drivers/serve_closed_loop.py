"""Driver ``serve_closed_loop``: N clients over HTTP against the slot loop.

The child holds the chip and builds the server the way
``vnsum_tpu.serve.server.main`` does (the CLI has no ``--quantize``, so it
cannot be used as it is): ``get_backend("tpu", ...)`` with the
configuration's weights, a supervised ``ServeState(inflight=True, ...)``
with every other knob at the server's default, ``make_server``. The parent
stays off JAX and runs the clients: each sends its next request over
``POST /v1/generate`` with SSE streaming when its last one is done, until
the file's ``max_requests`` are sent, and sends no new one once
``--seconds`` have passed. A mix sets ``max_requests`` to what the window
holds, so that a run's work is fixed and the clock only cuts a run that
is much slower: where the cut alone ended a run, a request that fell due
near it was sent or not as the host's clock jittered, and the rate stepped
by 1%. The measured window runs to the end of the last request, so every
request sent counts and so does all the time it took (cutting at
``--seconds`` dropped the four requests in flight, 300 to 8,000 tokens
each, and the rate jumped by 5% with which side of the cut a request
fell). The
traffic file fixes the prompt lengths and their order; the seed chooses the
text (a salt per request: no shared prefixes) and the weights. Lengths are
tokens of the BPE the child trains in set-up and serves with. A reply of no
tokens (EOS sampled at the first step) is an answer, not a failure.

Traffic parameters, all required: ``slots``, ``slot_prompt_tokens``,
``max_new_tokens`` (one value: the slot loop serves one batch key),
``cache_blocks``, ``prompt_token_blocks`` and ``order_seed`` (the lengths,
and the one order they are dealt in), ``bpe_vocab``, ``bpe_train_words``,
``max_requests``, ``warmup_joins`` (the join-batch sizes the loop can
form), ``trace_after_s``, ``trace_seconds``.

The parent tells the child, one line each on its standard input:
``WINDOW_START``, ``PROFILE <seconds>``, ``WINDOW_END``, ``FINISH``.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from pathlib import Path

from benchmarks import childproc, engine_setup, stats, textgen

READY_TIMEOUT_S = 1000.0
REQUEST_TIMEOUT_S = 300.0
METRIC_PREFIX = "vnsum_serve_"


# ---------------------------------------------------------------------------
# parent: the clients (no JAX here)
# ---------------------------------------------------------------------------


def sse_request(port: int, body: dict) -> dict:
    """One streamed request, timed by the client's clock."""
    t0 = time.time()
    out = {"t0": t0, "status": -1, "ttft_s": None, "deltas": "",
           "done": None, "error": None}
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read().decode("utf-8", "replace")[:300]
            return out
        name = None
        for raw in resp:
            line = raw.decode("utf-8", "replace").rstrip("\n")
            if line.startswith("event: "):
                name = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if name == "delta":
                    if data.get("text") and out["ttft_s"] is None:
                        out["ttft_s"] = time.time() - t0
                    out["deltas"] += data.get("text", "")
                elif name == "done":
                    out["done"] = data
                    break
                elif name == "error":
                    out["error"] = json.dumps(data)[:300]
                    break
    except (OSError, http.client.HTTPException, ValueError) as e:
        out["error"] = repr(e)
    finally:
        conn.close()
        out["t1"] = time.time()
    return out


def scrape(port: int) -> dict:
    """Every ``vnsum_serve_*`` family of /metrics, labels folded."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30.0)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8", "replace")
    finally:
        conn.close()
    out: dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(METRIC_PREFIX):
            continue
        head, _, val = line.rpartition(" ")
        name = head.split("{", 1)[0][len(METRIC_PREFIX):]
        try:
            out[name] = out.get(name, 0.0) + float(val)
        except ValueError:
            pass
    return out


def burst(port: int, requests: list[dict]) -> None:
    """Send these requests at the same instant and wait for them all."""
    gate = threading.Barrier(len(requests))

    def one(req: dict) -> None:
        gate.wait()
        sse_request(port, body_of(req))

    threads = [threading.Thread(target=one, args=(r,)) for r in requests]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def body_of(req: dict) -> dict:
    return {k: v for k, v in req.items() if k != "prompt_tokens"}


def load_tokenizer(tok_dir: str):
    """The BPE the child trained: (the tokenizer, ``count(texts) -> token
    counts``, tokens per word), read with ``tokenizers`` alone (no JAX in
    the parent)."""
    from tokenizers import Tokenizer

    tok = Tokenizer.from_file(str(Path(tok_dir) / "tokenizer.json"))

    def count(texts: list[str]) -> list[int]:
        return [len(e.ids) for e in
                tok.encode_batch(texts, add_special_tokens=False)]

    sample = textgen.TextGen(0).paragraphs(2000)
    words = sum(p.count(" ") + 1 for p in sample)
    return tok, count, sum(count(sample)) / words


def make_requests(traffic: dict, seed: int, n: int, count,
                  tokens_per_word: float) -> list[dict]:
    """The first ``n`` requests: the file's blocks of prompt lengths, cycle
    after cycle, in the order its ``order_seed`` gives (the same work in
    every run); the text comes from the seed."""
    gen = textgen.TextGen(seed)
    lens: list[int] = []
    cycle = 0
    while len(lens) < n:
        lens += textgen.permuted_blocks(
            traffic["prompt_token_blocks"], traffic["order_seed"], cycle)
        cycle += 1
    head = "Tóm tắt văn bản sau bằng tiếng Việt.\n\n"
    out = []
    for i, n_tokens in enumerate(lens[:n]):
        salt = f"[{seed:x}-{i}] "
        # a few tokens of room for the salt, the head and BOS
        text = gen.text_of_tokens(n_tokens - 48, count, tokens_per_word)
        out.append({"prompt": salt + head + text,
                    "max_new_tokens": traffic["max_new_tokens"],
                    "stream": True, "request_id": f"b{seed:x}-{i}",
                    "prompt_tokens": n_tokens})
    return out


def check_reply(req: dict, rep: dict, max_new: int) -> bool:
    if rep["status"] != 200 or rep["done"] is None or rep["error"]:
        return False
    comp = rep["done"]["completions"][0]
    rec = comp["record"]
    return (rep["deltas"] == comp["text"]
            and 0 <= rec["generated_tokens"] <= max_new)


def parent(ctx: dict) -> dict:
    traffic = ctx["traffic"]
    seed = textgen.fold_seed(ctx["seed"])
    ready = Path(ctx["work_dir"]) / "ready.json"
    child = childproc.Child(ctx)
    try:
        t_wait = time.time()
        while not ready.is_file():
            if not child.alive() or time.time() - t_wait > READY_TIMEOUT_S:
                raise childproc.ChildFailed("server child did not get ready")
            time.sleep(0.2)
        said = json.loads(ready.read_text())
        port = said["port"]
        tok, *sizing = load_tokenizer(said["tokenizer_dir"])
        slots = traffic["slots"]
        # the run's work: the clients share these, in this order
        pool = make_requests(traffic, seed, traffic["max_requests"], *sizing)
        # the child has run every join size on the engine itself; this
        # burst warms the HTTP path, the scheduler and the stream code
        burst(port, make_requests(traffic, seed + 1, slots, *sizing))

        before = scrape(port)
        child.send("WINDOW_START")
        t_w0 = time.time()
        t_end = t_w0 + ctx["seconds"]
        lock = threading.Lock()
        nxt = iter(pool)
        replies: list[tuple[dict, dict]] = []

        def client() -> None:
            while time.time() < t_end:
                with lock:
                    req = next(nxt, None)
                if req is None:
                    return
                rep = sse_request(port, body_of(req))
                with lock:
                    replies.append((req, rep))

        threads = [threading.Thread(target=client, name=f"client{i}")
                   for i in range(slots)]
        for t in threads:
            t.start()
        t_profile = None
        if ctx["trace"]:
            time.sleep(min(traffic["trace_after_s"], ctx["seconds"] / 3))
            child.send(f"PROFILE {traffic['trace_seconds']}")
            t_profile = time.time()
        for t in threads:       # no new request after t_end; those in
            t.join()            # flight run out, and their time counts
        t_w1 = time.time()
        after = scrape(port)
        child.send("WINDOW_END")
        child.send("FINISH")
        raw = child.wait()
    finally:
        child.kill()

    window_s = t_w1 - t_w0
    max_new = traffic["max_new_tokens"]
    good = [(q, r) for q, r in replies if check_reply(q, r, max_new)]
    tokens = sum(r["done"]["completions"][0]["record"]["prompt_tokens"]
                 + r["done"]["completions"][0]["record"]["generated_tokens"]
                 for _, r in good)
    ttft = [r["ttft_s"] * 1e3 for _, r in good if r["ttft_s"] is not None]
    texts = [r["done"]["completions"][0] for _, r in good]
    # a reply of under eight tokens may render as nothing and is no fault
    bad_rows = sum(
        c["record"]["generated_tokens"] >= 8 and stats.degenerate(
            c["text"], tok.encode(c["text"], add_special_tokens=False).ids)
        for c in texts)
    all_ok = [check_reply(q, r, max_new) for q, r in replies]
    raw["setup_s"] = t_w0 - ctx["t_start"]
    raw["window"] = {"seconds": window_s}
    raw["values"] = {
        "serve_tokens_per_s": stats.rate(tokens, window_s) if good else None,
        "ttft_p90_ms": stats.percentile(ttft, 90) if ttft else None,
    }
    raw["attempted"] = len(replies)
    raw["failed"] = len(replies) - sum(all_ok)
    raw["checks"].update({
        "every_request_answered": bool(replies) and all(all_ok),
        "outputs_not_degenerate": stats.at_most(bad_rows, len(texts), 0),
    })
    if raw.get("trace"):
        lo, hi = t_profile, t_profile + raw["trace"]["window_s"]
        raw["traced"] = {
            "requests": sum(lo <= r["t0"] <= hi for _, r in replies),
            "segment_steps": raw["trace"]["module_calls"].get(
                "jit_segment", 0) * raw["counts"]["segment_tokens"]}
    raw["server_metrics"] = {
        k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    raw["counts"].update({
        "requests_sent": len(replies),
        "slots": slots, "degenerate_rows": bad_rows,
        "first_errors": [r["error"] or r["status"] for (_, r), ok
                         in zip(replies, all_ok) if not ok][:3]})
    print(json.dumps({k: raw[k] for k in ("setup_s", "window", "values",
                                           "checks", "counts")}),
          file=sys.stderr, flush=True)
    return raw


# ---------------------------------------------------------------------------
# child: the server (holds the chip)
# ---------------------------------------------------------------------------


def warm_slot_programs(backend, traffic: dict, seed: int) -> None:
    """Run a join of each size the loop can form (powers of two up to the
    slots), an adopt and a segment on the engine's own slot loop, so that
    which programs exist does not hang on how the warm-up requests happen
    to arrive. The server's loop finds them in the engine's cache."""
    gen = textgen.TextGen(seed + 2)
    # any prompt will do: the join programs' shape is the slot's S
    n_bytes = max(max(b) for b in traffic["prompt_token_blocks"]) - 8
    loop = backend.start_slot_loop(
        traffic["slots"], max_new_tokens=traffic["max_new_tokens"],
        prompt_tokens=traffic["slot_prompt_tokens"])
    try:
        for size in traffic["warmup_joins"]:
            keys = [("warm", size, i) for i in range(size)]
            loop.admit([(k, gen.text_of_bytes(n_bytes), None) for k in keys])
            loop.step()
            loop.evict(keys, pin=False)
    finally:
        loop.close()


def child(ctx: dict) -> dict:
    from vnsum_tpu.core.jax_cache import enable_compilation_cache

    traffic, config, rehearsal = ctx["traffic"], ctx["config"], ctx["rehearsal"]
    enable_compilation_cache()
    device = engine_setup.require_device(ctx["cell"]["chips"], rehearsal)
    compiles = engine_setup.watch_compiles()
    seed = textgen.fold_seed(ctx["seed"])
    cfg = engine_setup.model_config(config, rehearsal)
    params = engine_setup.start_weights(config, cfg, seed)

    from vnsum_tpu.backend import get_backend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.serve.server import ServeState, make_server
    from vnsum_tpu.serve.supervisor import EngineSupervisor, RetryPolicy

    slots = traffic["slots"]
    work = Path(ctx["work_dir"])
    tok_dir = str(work / "tok")
    _tok, tok_spec, _ = engine_setup.train_bpe(
        textgen.TextGen(seed + 3), traffic, tok_dir)
    backend = get_backend(
        "tpu", model_config=cfg, params=params, batch_size=slots,
        tokenizer=tok_spec,
        max_new_tokens=traffic["max_new_tokens"],
        generation=GenerationConfig(temperature=1.0, seed=seed),
        cache_blocks=traffic["cache_blocks"], cache_block_tokens=64,
        **engine_setup.backend_kwargs(config, rehearsal))
    parity = engine_setup.parity_with_reference(backend, config, seed,
                                                rehearsal)
    warm_slot_programs(backend, traffic, seed)
    state = ServeState(
        backend, supervisor=EngineSupervisor(RetryPolicy()), max_batch=slots,
        inflight=True, slots=slots,
        slot_prompt_tokens=traffic["slot_prompt_tokens"])
    server = make_server(state, "127.0.0.1", 0)   # any free port
    port = server.server_address[1]
    serving = threading.Thread(target=server.serve_forever, name="http")
    serving.start()
    (work / "ready.json.tmp").write_text(json.dumps(
        {"port": port, "tokenizer_dir": tok_dir}))
    (work / "ready.json.tmp").rename(work / "ready.json")

    profiler = engine_setup.Profiler(str(work / "trace"))
    marks: dict[str, int] = {}
    profiled = False
    for line in sys.stdin:
        word, _, arg = line.strip().partition(" ")
        if word == "WINDOW_START":
            marks["start"] = compiles["compiles"]
            print("child: window starts", flush=True)
        elif word == "WINDOW_END":
            marks["end"] = compiles["compiles"]
        elif word == "PROFILE" and not rehearsal:
            profiler.start(float(arg))
            profiled = True
        elif word == "FINISH":
            break
    profiler.stop()
    server.shutdown()
    serving.join()
    server.server_close()
    state.close(drain_timeout_s=30.0)

    paths = backend.stats.attention_paths
    in_window = marks.get("end", 0) - marks.get("start", 0)
    return {
        # every large buffer of this cell is a live array (resident
        # cache, join cache, prefix pool); no program's temporaries added
        "device": {**device, **engine_setup.memory_bytes(None)},
        "checks": {
            "platform_is_tpu": device["platform"] == "tpu",
            "attention_paths_kernel": bool(paths) and all(
                p == "kernel" for prog in paths.values()
                for p in prog.values()),
            "no_compile_in_window": "end" in marks and in_window == 0,
            "parity_with_reference": parity["ok"],
        },
        "counts": {"compiles_in_window": in_window,
                   "segment_tokens": backend.segment_tokens,
                   "parity": parity,
                   "compiles_total": compiles["compiles"],
                   "compile_cache_hits": compiles["cache_hits"]},
        "sizes": engine_setup.sizes_of(config, rehearsal),
        "precision": engine_setup.precision_of(config),
        "trace": profiler.reduce() if profiled else None,
        "traced": None,
    }
