"""TpuBackend — batched, mesh-sharded on-device generation.

This is the component the reference lacks entirely: its map fan-out executes
serially over HTTP (SURVEY.md §1 "critical architectural observation",
runners/run_summarization_ollama_mapreduce.py:51-52). Here a list of prompts
becomes length-bucketed, fixed-shape [B, S] device batches:

- left-padded prompts so prefill's last row and every decode step share one
  write index across the batch (static shapes, no ragged gather);
- one jit-compiled prefill + early-exit `while_loop` decode program per
  (B, S) bucket, cached — bucketing bounds XLA recompiles, and decode stops
  as soon as every row has emitted EOS instead of paying the full budget;
- greedy or sampled decoding with per-sequence EOS masking inside the loop;
- params and token batches carry NamedShardings over a (data, model) mesh, so
  the same program runs single-chip or TP/DP-sharded with GSPMD collectives.

Telemetry: the host loops publish phase events (tokenize, dispatch,
spec_prefill, spec_step, detokenize) through obs.trace.emit() — host
timestamps around device calls whose sync the loop already paid (done-mask /
result fetches), a no-op unless a collector is installed (the serving
scheduler's BatchTrace; see backend/base.py for the contract). These feed
the vnsum_serve_ttft_seconds anchor and the /debug/trace batch tracks.
"""
from __future__ import annotations

import contextlib
import gc
import time
from collections import Counter, deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sanitizers import hot_path_transfer_guard
from ..core.config import GenerationConfig
from ..core.logging import get_logger
from .base import (
    fold_seed,
    left_pad_batch,
    mask_unsampleable,
    resolve_max_new,
    sampling_vocab,
    terminator_ids,
    trim_to_eos,
)
from ..core.profiling import (
    SpanStats,
    execution_span,
    host_span,
    snapshot_delta,
)
from ..obs.trace import emit
from ..testing.faults import fault
from ..models.family import family_of
from ..models.llama import (
    LlamaConfig,
    decode_attention_mask,
    llama32_3b,
    prefill_attention_mask,
    prefill_positions,
    verify_attention_mask,
    verify_positions,
)
from ..models.sampling import draft_acceptance_rows, sample_logits_rows
from ..text.tokenizer import Tokenizer, get_tokenizer

logger = get_logger("vnsum.engine")

_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384)


def _bucket_len(n: int, max_len: int) -> int:
    for b in _BUCKETS:
        if n <= b and b <= max_len:
            return b
    return max_len


def _stream_keys(base, uids):
    """The sampling-stream rule, stated once: the row of request ``uid`` owns
    the stream ``fold_in(base, uid)`` (``base = key(seed)``) and its token
    ``t`` draws from ``fold_in(stream, t)``. A request's tokens therefore
    depend on (seed, uid, t) alone: never on its batch position, on when it
    joined a slot loop or on whom it joined with. Returns ``at(t) -> keys``
    for token indices ``t`` that are one scalar all rows share, one a row
    ([B]) or several a row ([B, n], the spec step's positions)."""
    streams = jax.vmap(lambda u: jax.random.fold_in(base, u))(uids)

    def at(t):
        one = jax.random.fold_in
        for _ in range(jnp.ndim(t) - 1):
            one = jax.vmap(one, in_axes=(None, 0))
        return jax.vmap(one, in_axes=(0, 0 if jnp.ndim(t) else None))(
            streams, t)

    return at


# an execution is held where it took this many times its shape's pace, and
# that many seconds more than it: the factor sits between the widest step
# two clean dispatches of one bucket took (7.46 s with tails, then 8.711 s
# all live: x1.17) and the narrowest stall on record (10.778 s on 8.711:
# x1.24); the floor keeps a millisecond program's jitter and a short
# segment's boundary fetch out (tests/test_execution_account.py has the table)
HELD_FACTOR = 1.2
HELD_FLOOR_S = 0.1
HELD_KEPT = 64


@dataclass
class ExecutionStats:
    """One (program, B, S)'s executions: how many, how long the host was
    blocked on them, the work they carried, and the pace they set."""

    count: int = 0
    # a program's first call (trace, lowering, compile or the cache's load:
    # ``_timed_first_call`` times it and lists the program as warm after
    # it): counted and timed with the rest, but it sets no pace, takes no
    # snapshot and is never held
    first_calls: int = 0
    # the host's blocked seconds an execution: for ``generate`` the two
    # sides of the device queue — ``enqueue`` is the program's call alone
    # (arguments transferred, output buffers allocated, program queued),
    # ``wait`` the result fetch (execution and the copy back) — and their
    # sum; for a slot program the one span that brackets it
    blocked: SpanStats = field(default_factory=SpanStats)
    enqueue: SpanStats | None = None
    wait: SpanStats | None = None
    # the work the executions carried, as the program counts it on the host
    rows: int = 0
    pieces_live: int = 0
    pieces_dead: int = 0
    steps: int = 0
    kv_blocks: int = 0
    kv_blocks_skipped: int = 0
    # the largest blocked seconds of an earlier warm execution, per unit of
    # the work that tells this shape's executions apart (a join's live
    # pieces, a segment's steps; 1 elsewhere), and the most of each kind of
    # work an earlier warm execution carried: one that carries more of any
    # is not judged, it sets the pace (a later chunk attends to more keys,
    # so a long row's piece costs more than a short row's); one that carries
    # under half the most units seen is judged and sets none (a segment of
    # one step is mostly its boundary fetch)
    pace_s: float = 0.0
    pace_units: int = 0
    pace_work: tuple | None = None
    # the longest warm enqueue and wait, to say which side a held one grew on
    pace_sides: tuple = (0.0, 0.0)
    held: int = 0
    held_excess_s: float = 0.0

    def to_dict(self) -> dict:
        out = {
            "count": self.count, "first_calls": self.first_calls,
            "blocked": self.blocked.to_dict(),
            "rows": self.rows, "pieces_live": self.pieces_live,
            "pieces_dead": self.pieces_dead, "steps": self.steps,
            "kv_blocks": self.kv_blocks,
            "kv_blocks_skipped": self.kv_blocks_skipped,
            "pace_s": self.pace_s, "held": self.held,
            "held_excess_s": self.held_excess_s,
        }
        if self.enqueue is not None:
            out["enqueue"] = self.enqueue.to_dict()
            out["wait"] = self.wait.to_dict()
        return out


def execution_key(key: tuple) -> str:
    """``("generate", 8, 8192)`` as a record spells it: generate[B=8,S=8192]."""
    return f"{key[0]}[B={key[1]},S={key[2]}]"


@dataclass
class EngineStats:
    """Wall-clock + token accounting for run records."""

    calls: int = 0
    prompts: int = 0
    prompt_tokens: int = 0
    generated_tokens: int = 0
    # wall time of each program's FIRST call (trace + lower + compile, or a
    # persistent-cache load) — dispatch is asynchronous, so the first call
    # returns when the executable exists, not when the device finishes
    compile_seconds: float = 0.0
    generate_seconds: float = 0.0
    batches: int = 0
    # speculative decoding (spec path): batched verify forwards run, draft
    # tokens proposed to them, and draft tokens the model kept. Mean
    # accepted-per-step = spec_accepted_tokens / spec_verify_steps; every
    # step additionally retires one model-own token.
    spec_verify_steps: int = 0
    spec_draft_tokens: int = 0
    spec_accepted_tokens: int = 0
    # prefix KV cache (vnsum_tpu.cache): prompt tokens whose prefill was
    # skipped by resuming from cached prefix blocks, vs tokens prefilled
    # from scratch — hit/(hit+miss) is the prefill-token reduction
    cache_hit_tokens: int = 0
    cache_miss_tokens: int = 0
    by_bucket: dict = field(default_factory=dict)
    # grid cells of the prefill attention kernel by class, per KV head,
    # summed over layers, chunks and one-shot dispatches
    # (ops.flash_attention.prefill_block_classes): dead_causal and dead_pad
    # cells are neither fetched nor computed, interior cells run unmasked,
    # edge cells masked — what the kernel skips, counted from the pads.
    # Beside the classes, where layers have a window:
    # ``window_scores_computed`` (the cells window layers fetched x the
    # tile's area x their query heads) and ``window_scores_needed`` (for
    # each real query row min(row + 1 - pad, window) keys x those heads).
    # Where the family runs a scan (``Family.prefill_counts``):
    # ``scan_tokens_real`` (real prompt tokens x its scan layers) and
    # ``scan_tokens_computed`` (tokens of the chunks the scan kernel did not
    # skip x those layers). Where its prefill kernel expands keys and values
    # from a latent cache (the same hook; such a family counts no GQA cell):
    # ``latent_keys_real`` (the rows' keys x layers) and
    # ``latent_keys_expanded`` (the keys of the key blocks the kernel's
    # computed tiles read, each expanded in VMEM, a head x layers: a chunked
    # prefill expands a key once for every chunk that reads it)
    prefill_blocks: dict = field(default_factory=dict)
    # (row, chunk) pieces of the one-shot dispatches' and the joins'
    # prefills — a row of the batch in one prefill chunk — and those of
    # them the program did not run because the row holds nothing but left
    # pad there (``TpuBackend._prefill_forward``; 0 for a family that
    # names no piece), counted from the pads
    prefill_row_chunks_total: int = 0
    prefill_row_chunks_dead: int = 0
    # key blocks between slot 0 and the fill that the decode steps of the
    # one-shot dispatches and the slot segments walked — a row of the batch
    # on a layer that attends globally in one step, by the GQA decode
    # kernels' own block (``ops.decode_attention.decode_block_k``) — and
    # those of them the kernels neither copied nor computed because they lie
    # wholly under the row's left pad (``TpuBackend._count_decode_kv_blocks``;
    # 0 and 0 for a family whose decode kernel is another), counted from the
    # pads
    decode_kv_blocks_total: int = 0
    decode_kv_blocks_skipped: int = 0
    # those of ``decode_kv_blocks_total`` that held two KV heads a lane tile
    # (64-wide heads paired in the cache, ``models.llama.init_kv_cache``):
    # all of them or none, by the model's shapes
    decode_kv_blocks_paired: int = 0
    # which attention each built program got, keyed "program[B=..,S=..]" →
    # {"prefill"|"decode": "kernel"|"dense"}: a dense fallback (unaligned
    # head dim, the slot/verify kernel under a mesh) is visible here and in
    # the log instead of only in the timings
    attention_paths: dict = field(default_factory=dict)
    # host work between device programs (core.profiling.host_span's sink,
    # always on): {"engine/<name>": SpanStats}. The names are a contract
    # (README, "Device time by layer"); none carries a shape
    host_spans: dict = field(default_factory=dict)
    # every device execution of the four programs the engine's two loops
    # run, by {(program, B, S): ExecutionStats} with ``program`` one of
    # ``generate``, ``slot_prefill``, ``adopt``, ``segment``
    # (``note_execution``): count, blocked seconds — for ``generate`` by
    # side of the device queue, so a call that stalls says on which side it
    # did —, the work carried and the pace
    executions: dict = field(default_factory=dict)
    # executions held past their shape's pace: how many, by how many
    # seconds in all, and the last ``HELD_KEPT`` of them whole (program,
    # shape, blocked, pace, side and what the host was doing meanwhile:
    # ``core.profiling.snapshot_delta``; PERF.md section 7 (u) reads one)
    executions_held: int = 0
    held_excess_seconds: float = 0.0
    held: deque = field(default_factory=lambda: deque(maxlen=HELD_KEPT))
    # sparse-expert families (models/experts.py), summed on the device and
    # returned with each one-shot program's output: token x pick pairs the
    # router saw, those that fell on an expert held here, and tokens per
    # expert layer and held expert ([layers][experts] once a dispatch ran);
    # where the family counts them, distinct experts with a token summed
    # over decode steps and layers, and the (step, layer) pairs counted;
    # with them the row tiles of the grouped product that held a slot in
    # those steps and the row tiles its grid took a step for (used / walked:
    # the live share of the product's grid)
    expert_slots_routed: int = 0
    expert_slots_held: int = 0
    expert_tokens: list = field(default_factory=list)
    expert_decode_touched: int = 0
    expert_decode_layer_steps: int = 0
    expert_decode_tiles_used: int = 0
    expert_decode_tiles_walked: int = 0

    def note_execution(self, program: str, B: int, S: int, span,
                       closing=None, *, first: bool = False,
                       units: int = 1, rows: int = 0,
                       pieces: tuple[int, int] = (0, 0), steps: int = 0,
                       kv_blocks: tuple[int, int] = (0, 0)) -> None:
        """Book one execution under (program, B, S). ``span`` is the
        ``execution_span`` that bracketed it — or its first part (the
        enqueue), with ``closing`` the last (the wait): their ``dur`` are
        the blocked seconds, no clock is read here. ``first`` says that
        this was the program's first call: the caller looks the program up
        in ``TpuBackend._warm`` ahead of the call (``_timed_first_call``), so
        a program the account does not cover moves nothing here. ``pieces``
        is (dead, total) and ``kv_blocks`` (skipped, total), as the counting
        methods return them;
        ``units`` is how much of the work that tells this shape's
        executions apart it carried. Where the execution took more than
        ``HELD_FACTOR`` times its pace it is held: one WARNING line, one
        entry of ``held``, one ``held`` event to the obs collector."""
        ex = self.executions.get((program, B, S))
        if ex is None:
            ex = self.executions[(program, B, S)] = ExecutionStats()
            if closing is not None:
                ex.enqueue, ex.wait = SpanStats(), SpanStats()
        blocked = span.dur + closing.dur if closing is not None else span.dur
        ex.count += 1
        ex.blocked.add(blocked)
        if closing is not None:
            ex.enqueue.add(span.dur)
            ex.wait.add(closing.dur)
        ex.rows += rows
        ex.pieces_live += pieces[1] - pieces[0]
        ex.pieces_dead += pieces[0]
        ex.steps += steps
        ex.kv_blocks += kv_blocks[1]
        ex.kv_blocks_skipped += kv_blocks[0]
        if first:
            ex.first_calls += 1
            return
        units = max(units, 1)
        work = (pieces[1] - pieces[0], steps)
        judged = ex.pace_work is not None and all(
            w <= m for w, m in zip(work, ex.pace_work))
        pace = ex.pace_s * units
        if (judged and blocked > HELD_FACTOR * pace
                and blocked - pace > HELD_FLOOR_S):
            self._note_held(ex, program, B, S, span, closing, blocked, pace)
            # a held execution moves the pace by the factor at most: one
            # stall does not hide the next, and a program that has become
            # slower for good is held a few times, not for ever
            blocked = HELD_FACTOR * pace
        elif closing is not None:
            ex.pace_sides = (max(ex.pace_sides[0], span.dur),
                             max(ex.pace_sides[1], closing.dur))
        if 2 * units >= ex.pace_units:
            ex.pace_s = max(ex.pace_s, blocked / units)
            ex.pace_units = max(ex.pace_units, units)
        ex.pace_work = tuple(map(max, work, ex.pace_work or work))

    def _note_held(self, ex: ExecutionStats, program: str, B: int, S: int,
                   span, closing, blocked: float, pace: float) -> None:
        excess = blocked - pace
        ex.held += 1
        ex.held_excess_s += excess
        self.executions_held += 1
        self.held_excess_seconds += excess
        # of generate's two sides, the one further past its longest warm
        last = closing or span
        side = last.full
        if closing is not None and (span.dur - ex.pace_sides[0]
                                    > closing.dur - ex.pace_sides[1]):
            side = span.full
        doing = (snapshot_delta(span.before, last.after)
                 if span.before is not None and last.after is not None
                 else {})
        entry = {
            "program": program, "B": B, "S": S, "t0": span.t0,
            "blocked_s": blocked, "pace_s": pace, "excess_s": excess,
            "side": side, **doing,
        }
        self.held.append(entry)
        logger.warning(
            "execution held: %s B=%d S=%d blocked %.3fs on a pace of %.3fs "
            "(excess %.3fs) side %s: %s",
            program, B, S, blocked, pace, excess, side,
            " ".join(f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                     for k, v in doing.items()))
        emit("held", span.t0, last.t0 + last.dur - span.t0,
             **{k: v for k, v in entry.items() if k != "t0"})

    @property
    def tokens_per_second(self) -> float:
        total = self.prompt_tokens + self.generated_tokens
        return total / self.generate_seconds if self.generate_seconds else 0.0


class TpuBackend:
    name = "tpu"

    def __init__(
        self,
        model_config: LlamaConfig | None = None,
        tokenizer: str | Tokenizer = "byte",
        mesh=None,
        params=None,
        batch_size: int = 8,
        max_new_tokens: int = 1024,
        generation: GenerationConfig | None = None,
        seed: int = 0,
        flash: str | bool = "auto",
        quantize: bool = False,
        quantize_act: bool = False,
        quantize_kv: str | bool = "auto",
        segment_tokens: int = 128,
        interpret: bool = False,
        prefill_chunk_tokens: int = 0,
        spec_max_ref_tokens: int = 4096,
        cache_blocks: int = 0,
        cache_block_tokens: int = 64,
    ) -> None:
        from ..core.jax_cache import enable_compilation_cache

        enable_compilation_cache()  # per-bucket programs amortize on disk
        self.cfg = model_config or llama32_3b()
        # what differs between model families — the layer stack, the state
        # a program carries, the attention over it — comes from here; the
        # program around it is this module's, for every family
        self.family = family_of(self.cfg)
        if mesh is not None:
            self.family.refuse("mesh", self.cfg)
        if cache_blocks:
            self.family.refuse("prefix cache", self.cfg)
        # whether any layer keeps keys and values: where none does the
        # program carries the family's state alone and no attention
        # function over a cache is ever built, for either phase
        self._attends = self.family.attention_layers(self.cfg) > 0
        if cache_blocks and not self._attends:
            raise ValueError(
                "a prefix cache holds keys and values by block, and no "
                "layer of this configuration keeps any")
        if quantize_act:
            # W8A8 prefill (models.llama._proj): double-rate s8xs8 MXU
            # dots on multi-token forwards. LOSSY (per-token activation
            # rounding) and meaningless without int8 weights
            if not quantize:
                raise ValueError(
                    "quantize_act (W8A8 prefill) requires quantize=True — "
                    "without int8 weights there is no s8xs8 matmul to run"
                )
            import dataclasses

            self.cfg = dataclasses.replace(self.cfg, w8a8_prefill=True)
        self.interpret = bool(interpret)
        # Pallas attention kernels: "auto" means ON. They need Mosaic, so a
        # platform other than tpu is refused outright — an engine that
        # quietly built the dense XLA path on whatever was there would be
        # timed and served as if it were the chip. Off-chip callers say
        # what they want: interpret=True emulates the kernels, flash=False
        # asks for the dense path by name. Under a mesh the kernels run
        # per-shard inside shard_map — batch and heads are data/model-local,
        # so no cross-chip softmax is needed. Sliding-window (Gemma) configs
        # run them too: the per-layer window is a runtime scalar the kernels
        # clamp their k-range with.
        self.flash = True if flash == "auto" else bool(flash)
        self.mesh = mesh
        self.platform = self._devices()[0].platform
        if self.flash and not self.interpret and self.platform != "tpu":
            raise RuntimeError(
                f"TpuBackend found platform {self.platform!r}, not 'tpu': "
                "its Pallas kernels need the chip. Pass interpret=True to "
                "emulate them, or flash=False for the dense XLA path."
            )
        # int8 KV cache halves decode-attention HBM traffic; the in-kernel
        # dequant needs the Pallas path, so "auto" follows flash AND actual
        # kernel support (a head_dim the kernels take:
        # ops/flash_attention.head_dim_supported; the dense fallback would
        # dequantize the whole cache per step)
        kernels_supported = self.family.kernels_supported(
            self.cfg, self.interpret)
        if quantize_kv == "auto":
            quantize_kv = (self.flash and kernels_supported
                           and self.family.int8_cache)
        elif quantize_kv and not self.family.int8_cache:
            raise ValueError(
                f"quantize_kv=True: the {self.family.name} family's cache "
                "has no int8 form")
        elif quantize_kv and not (self.flash and kernels_supported):
            raise ValueError(
                "quantize_kv=True needs the Pallas kernels (flash=True and "
                "a head_dim of whole lane tiles or 64); the dense fallback would "
                "dequantize the whole cache per step"
            )
        self.quantize_kv = bool(quantize_kv)
        self.tok = get_tokenizer(tokenizer) if isinstance(tokenizer, str) else tokenizer
        self.batch_size = batch_size
        self.max_new_tokens = max_new_tokens
        self.gen_cfg = generation or GenerationConfig()
        if max_new_tokens >= self.cfg.max_seq_len:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} must be < "
                f"max_seq_len={self.cfg.max_seq_len}"
            )
        # decode steps per dispatch of the in-flight slot loop's segment
        # program (backend/inflight.py)
        self.segment_tokens = max(segment_tokens, 1)
        # prefill in slices of this many tokens (0 = whole prompt): caps
        # prefill transients at CL tokens' worth so decode batches beyond
        # the whole-prompt memory ceiling fit (B=16 at S=8192 on one v5e)
        if prefill_chunk_tokens < 0 or (
            prefill_chunk_tokens and prefill_chunk_tokens % 128
        ):
            raise ValueError(
                "prefill_chunk_tokens must be a non-negative multiple of 128"
            )
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.stats = EngineStats()
        # entered around each program's first call, the one that compiles.
        # The serving scheduler installs its watchdog's compile pause here so
        # a cold program is not mistaken for a hung dispatch
        self.compile_scope = contextlib.nullcontext
        self._fns: dict[tuple[int, int, int], callable] = {}
        self._seg_fns: dict = {}
        # the programs whose first call is behind them (_timed_first_call)
        self._warm: set = set()
        self._seed = seed
        self._dispatch = 0
        # reference-guided speculative decoding (vnsum_tpu.spec): cap on
        # tokens encoded per reference (matching window, not attention — a
        # longer reference only loses tail draft coverage)
        self.spec_max_ref_tokens = int(spec_max_ref_tokens)
        self._spec_report: list = []
        self._warned_spec_fallback = False
        # radix prefix KV cache (vnsum_tpu.cache): cache_blocks > 0 retains
        # prefix KV blocks on device after prefill and later batches resume
        # prefill from the matched prefix, computing only the suffix. Under
        # a mesh the block pool shards its KV heads over `model` (mirroring
        # cache_specs) and the gather/extract programs run as sharded
        # dynamic_update_slice — the host-side radix index is unchanged.
        self.prefix_cache = None
        self._cache_report: list = []
        self._hint_ids_cache: dict[str, list[int]] = {}
        # degradation-ladder hook (serve/supervisor.py NO_CACHE_INSERT):
        # False stops pool insertion/eviction churn while matched prefixes
        # keep serving resume-prefill hits
        self.cache_inserts_enabled = True
        if cache_blocks:
            if not 1 <= cache_block_tokens <= 128:
                # the resume boundary K is 128-aligned, and the padded-gather
                # safety argument (scratch writes land inside the recomputed
                # [K, S) span) needs blocks no wider than that alignment
                raise ValueError("cache_block_tokens must be in [1, 128]")
            from ..cache import PrefixCache

            self.prefix_cache = PrefixCache(
                cache_blocks, cache_block_tokens,
                n_layers=self.family.attention_layers(self.cfg),
                n_kv_heads=self.cfg.n_kv_heads,
                head_dim=self.cfg.head_dim, dtype=self.cfg.dtype,
                quantized=self.quantize_kv, mesh=mesh,
            )
            logger.info(
                "prefix KV cache: %d blocks x %d tokens (%.1f MB HBM)",
                cache_blocks, cache_block_tokens,
                self.prefix_cache.store.hbm_bytes / 1e6,
            )

        if params is None:
            t0 = time.time()
            from ..models import jitted_init

            params = jitted_init(self.family.init_params, self.cfg, seed)
            logger.info("initialized random params in %.1fs", time.time() - t0)
        if quantize:
            from ..models.quant import is_quantized, quantize_params

            if not is_quantized(params):
                t0 = time.time()
                params = jax.jit(quantize_params)(params)
                logger.info("int8-quantized params in %.1fs", time.time() - t0)
        if mesh is not None:
            from ..parallel.sharding import shard_params

            params = shard_params(params, mesh, self.cfg.tie_embeddings)
            if batch_size % mesh.shape.get("data", 1):
                raise ValueError("batch_size must be divisible by mesh data axis")
        self.params = params
        # the family's own keywords of forward (none for the dense stacks)
        self._forward_kw = self.family.forward_kwargs(
            self.cfg, self.flash, self.interpret)

    # -- compiled program per bucket ------------------------------------

    def _timed_first_call(self, fn, label: str):
        """Wrap a freshly built program so the call that compiles it runs
        inside ``compile_scope`` and lands in ``stats.compile_seconds``;
        every later call goes straight to ``fn``. A program whose first
        call is behind it is in ``self._warm``: the execution account looks
        there ahead of a call to tell a first call from a warm one."""
        compiled = False

        def call(*args):
            nonlocal compiled
            if compiled:
                return fn(*args)
            t0 = time.time()
            with self.compile_scope():
                out = fn(*args)
            compiled = True
            self._warm.add(call)
            dt = time.time() - t0
            self.stats.compile_seconds += dt
            logger.info("first call of %s took %.1fs", label, dt)
            # Tracing and lowering a program leaves millions of long-lived
            # objects in JAX's caches. A collection of the oldest generation
            # walks them all, 0.3-0.4 s with four programs built, at some
            # point of the steady state: the stall that made one run in
            # three read 0.7% low (PERF.md section 6, PR 41). Collect what
            # is garbage now and take the rest out of the collector's sight.
            gc.collect()
            gc.freeze()
            return out

        return call

    def _note_attention(self, program: str, B: int, S: int, **paths) -> None:
        """Record which attention a program being built will run, per phase
        (``prefill=``/``decode=`` → True for the Pallas kernel)."""
        chosen = {
            phase: "kernel" if on else "dense" for phase, on in paths.items()
        }
        key = f"{program}[B={B},S={S}]"
        if self.stats.attention_paths.get(key) != chosen:
            self.stats.attention_paths[key] = chosen
            logger.info("attention path %s: %s", key, chosen)

    def _devices(self) -> list:
        """The devices this engine's programs run on: the mesh's, else
        JAX's default platform's."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return jax.devices()

    def describe(self) -> dict:
        """What this engine runs on and what it compiled — the device as JAX
        reports it, the attention path per built program, compile seconds
        and per-device memory. Served on /healthz and read by
        chip_smoke.py."""
        devices = self._devices()
        return {
            "platform": self.platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "flash": self.flash,
            "interpret": self.interpret,
            "quantize_kv": self.quantize_kv,
            "attention_paths": self.stats.attention_paths.copy(),
            "compile_seconds": round(self.stats.compile_seconds, 3),
            # one row of the state a program carries, by leaf, at
            # max_seq_len slots: what grows with the row (keys and values)
            # beside what does not (a recurrent state)
            "state_bytes_per_row": {
                name: leaf.size * leaf.dtype.itemsize
                for name, leaf in jax.eval_shape(
                    lambda: self._init_cache(
                        1, self.cfg.max_seq_len)).items()},
            "memory": [
                {k: int(v) for k, v in (d.memory_stats() or {}).items()
                 if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                for d in devices
            ],
        }

    def scope_maps(self) -> list[dict]:
        """For each generation program this engine has built, which
        ``jax.named_scope`` every instruction was written under:
        ``[{"program", "module", "compile_s", "scopes"}]`` with ``module``
        the XLA module's name as the device trace has it (several programs
        share one: every one-shot bucket is ``jit_generate``) and ``scopes``
        ``core.profiling.hlo_scope_map`` of the compiled text. The device
        trace carries no scope, so this is what lets a trace be read by
        layer (``scripts/trace_by_scope.py``).

        Each program is built and lowered again at the shapes it was built
        for and compiled with the persistent compile cache off: the cache's
        key leaves metadata out, so an executable found there may hold the
        names of an older source. That is one full compile per program and
        the cache is off for the whole process meanwhile: an operator's
        call, outside any timed window, never the engine's own. The choice
        and speculation programs are not covered. Under a mesh the
        programs that take a cache argument are lowered with it unsharded.
        """
        from jax.experimental.compilation_cache import compilation_cache

        from ..core.profiling import hlo_scope_map

        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        done = lambda n: jax.ShapeDtypeStruct((n,), jnp.bool_)  # noqa: E731

        def cache_of(B, C):
            return jax.eval_shape(lambda: self._init_cache(B, C))

        programs = []   # (label, jitted function, arguments)
        for key in self._fns:
            if not isinstance(key[0], int):
                continue   # ("choice", ...), ("spec", ...)
            B, S, max_new, gen, K = key
            programs.append((
                f"generate[B={B},S={S},new={max_new},resume={K}]",
                self._make_fn(B, S, max_new, gen, K),
                (self.params, i32(B, S), i32(B), 0)
                + ((cache_of(B, S + max_new),) if K else ())))
        for kind, B, S, max_new, gen, K in self._seg_fns:
            C = S + max_new
            label = f"{kind}[B={B},S={S},new={max_new},resume={K}]"
            prompt = (self.params, i32(B, S), i32(B), 0)
            resumed = (cache_of(B, C),) if K else ()
            # a decode batch's carry after the step counter: cur, cache,
            # done, uids, out, pads, seed
            carry = (i32(B), cache_of(B, C), done(B), i32(B),
                     i32(B, max_new), i32(B), 0)
            if kind == "prefill":
                fn = self._make_prefill_fn(B, S, max_new, gen, K)
                args = prompt + resumed
            elif kind == "slot_prefill":
                fn = self._make_slot_prefill_fn(B, S, max_new, gen, K)
                args = prompt + (i32(B),) + resumed
            elif kind == "slot_seg":
                fn = self._make_slot_segment_fn(B, S, max_new, gen)
                args = (self.params, i32(B)) + carry
            else:
                # adopt. The resident batch is not in its key: it is the B
                # of the slot segment built for the same loop
                slots = {k[1] for k in self._seg_fns
                         if k[0] == "slot_seg" and k[2:5] == (S, max_new, gen)}
                fn = self._make_adopt_fn(B)
                for n in sorted(slots):
                    programs.append((f"{label[:-1]},slots={n}]", fn, (
                        cache_of(n, C), i32(n), done(n), i32(n),
                        i32(n, max_new), i32(n),
                        cache_of(B, C), i32(B), done(B), i32(B), i32(B))))
                continue
            programs.append((label, fn, args))

        maps = []
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()   # the switch is read once a process
        try:
            for label, fn, args in programs:
                t0 = time.time()
                text = fn.lower(*args).compile().as_text()
                maps.append({
                    "program": label,
                    "module": text.split(None, 2)[1].rstrip(","),
                    "compile_s": round(time.time() - t0, 3),
                    "scopes": hlo_scope_map(text),
                })
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()
        return maps

    def _sampling_setup(self, gen: GenerationConfig):
        """(eos ids, vocab limit, restrict fn) — the ONE sampling restriction
        shared by the plain decode programs (_make_parts) and the spec verify
        step, so the two paths can never disagree on what is sampleable.
        Never sample a token the tokenizer cannot render as text — but keep
        every terminator sampleable even when it sits above the decodable
        range (ByteTokenizer's eos_id=257 >= 256 raw bytes)."""
        terminators = terminator_ids(self.tok, gen)
        # HOST arrays on purpose: the traced programs close over these, and
        # a closed-over device array is fetched back to the host at trace
        # time — an implicit device-to-host transfer the transfer sanitizer
        # refuses on a TPU (first caught there: the [258] bool mask)
        eos = np.asarray(terminators, dtype=np.int32)
        vocab_limit, allowed = sampling_vocab(
            self.tok, self.cfg.vocab_size, terminators
        )
        allowed = None if allowed is None else np.asarray(allowed)

        def restrict(row_logits):  # [..., vocab_limit]
            return mask_unsampleable(row_logits, allowed)

        return eos, vocab_limit, restrict

    def _make_prefill_part(self, B: int, S: int, max_new: int,
                           gen: GenerationConfig, resume_from: int = 0):
        """The traceable prefill every generation program starts with:
        the prompt's forward into a cache of S + max_new slots and each
        row's token 0, sampled from its own stream (``uids``: the rows'
        positions where none are given, as in a one-shot batch).

        ``resume_from=K`` (prefix KV cache, vnsum_tpu.cache) builds the
        resume-prefill variant: prefill_part takes a cache pre-seeded with
        gathered prefix blocks and runs the forward only over cache slots
        [K, S) — positions and masks are unchanged, so the math over the
        computed span is identical to full prefill's."""
        C = S + max_new
        _eos, vocab_limit, restrict = self._sampling_setup(gen)
        use_flash, _ = self._decode_settings(S, C)
        layer_window = self._layer_window_fn()

        # prefill runs whole-prompt or in prefill_chunk_tokens slices —
        # chunking caps transient activations (q/k/v, MLP intermediates)
        # at a chunk's worth (of a row piece's rows, where the family names
        # one), which is what lets B=16 decode fit at S=8192; see
        # _prefill_forward
        def prefill_part(params, tokens, pad_lens, seed, cache=None,
                         uids=None):
            with jax.named_scope("prefill"):
                logits, cache = self._prefill_forward(
                    params, tokens, pad_lens, B, S, C, use_flash,
                    layer_window, cache=cache, start=resume_from,
                )
                with jax.named_scope("sample"):
                    base = jax.random.key(seed)
                    if uids is None:
                        uids = jnp.arange(B, dtype=jnp.int32)
                    keys0 = _stream_keys(base, uids)(0)
                    first = sample_logits_rows(
                        restrict(logits[:, -1, :vocab_limit]), keys0,
                        gen.temperature, gen.top_k, gen.top_p,
                    )
                # all-pad dummy rows (batch bucketing filler) start done,
                # else their garbage decode would keep the early exit from
                # firing
                done0 = pad_lens == S
            return first, cache, done0

        return prefill_part

    def _make_parts(self, B: int, S: int, max_new: int, gen: GenerationConfig,
                    resume_from: int = 0):
        """The two traceable halves every generation program is composed of:

        prefill_part(params, tokens, pad_lens, seed[, cache[, uids]])
            -> (first_token, cache, done0)        (_make_prefill_part)
        decode_part(params, t0, cur, cache, done, uids, out, pad_lens,
                    t_end, seed)
            -> (t, cur, cache, done, out)

        Sampling is counter-based per row (``_stream_keys``): prefill
        draws each row's token 0, decode step t its token t + 1. ``uids``
        are the rows' positions in a one-shot batch and the requests' own
        numbers in a slot loop's join (_make_slot_prefill_fn).

        The one-shot program is prefill + one decode to t_end=max_new in a
        single jit; the spec path jits prefill_part alone
        (_make_prefill_fn) and decodes with its own verify step, and a slot
        loop's join jits it with the requests' uids (_make_slot_prefill_fn)."""
        cfg = self.cfg
        C = S + max_new
        eos, vocab_limit, restrict = self._sampling_setup(gen)
        pad_id = self.tok.pad_id
        use_flash, use_flash_decode = self._decode_settings(S, C)
        self._note_attention(
            "generate", B, S, prefill=use_flash, decode=use_flash_decode
        )
        mesh = self.mesh
        interpret = self.interpret
        family, forward_kw = self.family, self._forward_kw
        attends = self._attends
        layer_window = self._layer_window_fn()
        prefill_part = self._make_prefill_part(B, S, max_new, gen, resume_from)

        def decode_part(
            params, t0, cur, cache, done, uids, out, pad_lens, t_end, seed
        ):
            # decode loop with early exit: a while_loop instead of a fixed
            # lax.scan, so the program stops as soon as every row has hit
            # EOS (real summaries end far before the max_new budget)
            def emit_token(out, cur, done, t):
                emit = jnp.where(done, pad_id, cur)
                out = jax.lax.dynamic_update_slice(out, emit[:, None], (0, t))
                return out, done | jnp.isin(cur, eos)

            def cond(carry):
                t, _cur, _cache, done, _out = carry
                return (t < t_end) & ~jnp.all(done)

            def body(carry):
                t, cur, cache, done, out = carry
                with jax.named_scope("emit"):
                    out, done = emit_token(out, cur, done, t)
                pos = (S - pad_lens) + t
                mask_t = decode_attention_mask(pad_lens, S + t, C)
                stacked_fn = None
                if use_flash_decode and attends:
                    stacked_fn = family.decode_attention(
                        cfg, mesh, interpret, pad_lens, S, t, layer_window)
                logits, cache = family.forward(
                    params, cfg, cur[:, None], pos[:, None], cache, S + t,
                    mask_t, stacked_attention_fn=stacked_fn, **forward_kw,
                )
                with jax.named_scope("sample"):
                    step_keys = _stream_keys(base, uids)(t + 1)
                    nxt = sample_logits_rows(
                        restrict(logits[:, -1, :vocab_limit]), step_keys,
                        gen.temperature, gen.top_k, gen.top_p,
                    )
                return (t + 1, nxt, cache, done, out)

            # each iteration emits BEFORE sampling, so on exit (budget spent
            # or all rows done) every live slot is already written and the
            # rest remain pad from the init — identical to a full-length scan
            with jax.named_scope("decode"):
                base = jax.random.key(seed)
                return jax.lax.while_loop(
                    cond, body, (t0, cur, cache, done, out)
                )

        return prefill_part, decode_part

    def _make_fn(self, B: int, S: int, max_new: int, gen: GenerationConfig,
                 resume_from: int = 0):
        pad_id = self.tok.pad_id
        prefill_part, decode_part = self._make_parts(
            B, S, max_new, gen, resume_from
        )
        # with the prefix cache on, the one-shot program also returns its
        # final cache: decode never touches slots < S, so the prompt's
        # prefix KV survives for post-call insertion into the block pool
        return_cache = self.prefix_cache is not None
        counters = self.family.counters

        def run(params, tokens, pad_lens, seed, cache):
            first, cache, done0 = prefill_part(
                params, tokens, pad_lens, seed, cache
            )
            out0 = jnp.full((B, max_new), pad_id, dtype=jnp.int32)
            uids = jnp.arange(B, dtype=jnp.int32)
            _, _, cache, _, out = decode_part(
                params, jnp.int32(0), first, cache, done0, uids, out0,
                pad_lens, max_new, seed,
            )
            if counters is not None:
                # what the family counted on the device (expert loads)
                # leaves with the tokens: no host callback, no second fetch
                return out, counters(cache)
            return (out, cache) if return_cache else out  # out: [B, max_new]

        def generate(params, tokens, pad_lens, seed):
            return run(params, tokens, pad_lens, seed, None)

        out_sh = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            out_sh = NamedSharding(self.mesh, P("data", None))
            if return_cache:
                # the returned final cache keeps the (data, model) cache
                # layout — a bare single sharding would broadcast P(data,)
                # over every cache leaf and silently re-layout the pool copies
                from ..parallel.sharding import cache_specs

                out_sh = (
                    out_sh,
                    jax.tree.map(
                        lambda s: NamedSharding(self.mesh, s),
                        cache_specs(quantized=self.quantize_kv),
                        is_leaf=lambda x: not isinstance(x, dict),
                    ),
                )
        return self._jit_program(generate, run, resume_from,
                                 out_shardings=out_sh)

    def _jit_program(self, fresh, seeded, resume_from: int, *,
                     row_args: int = 0, out_shardings=None):
        """The one way a program over (params, tokens, pad_lens, seed and
        ``row_args`` more vectors of a number a row) is jitted. A resume
        program (``seeded``: the same arguments, then the cache the prefix
        pool seeded) consumes that cache, so its buffer is donated; under a
        mesh the cache's layout was committed by the sharded gather, so
        propagation and not in_shardings carries it through. Any other
        (``fresh``) compiles against ``_mesh_in_shardings()`` under a mesh,
        the further vectors riding the batch rows on `data`."""
        if resume_from:
            return jax.jit(seeded, donate_argnums=(4 + row_args,))
        if self.mesh is None:
            return jax.jit(fresh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        shardings = {"in_shardings": self._mesh_in_shardings()
                     + (NamedSharding(self.mesh, P("data")),) * row_args}
        if out_shardings is not None:
            shardings["out_shardings"] = out_shardings
        return jax.jit(fresh, **shardings)

    def _mesh_in_shardings(self):
        """in_shardings for (params, tokens, pad_lens, seed) — shared by the
        one-shot and split-prefill builders so the two cannot compile
        against different input layouts."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..models.quant import is_quantized
        from ..parallel.sharding import param_shardings

        ns = lambda spec: NamedSharding(self.mesh, spec)
        return (
            param_shardings(
                self.mesh, self.cfg.tie_embeddings, is_quantized(self.params),
                qk_norm=self.cfg.qk_norm,
                sandwich_norms=self.cfg.sandwich_norms,
                looped="exit_gate" in self.params,
            ),
            ns(P("data", None)),
            ns(P("data")),
            None,
        )

    def _get_fn(self, B: int, S: int, max_new: int, gen: GenerationConfig,
                resume_from: int = 0):
        # seed is a runtime argument to the compiled program, not a trace
        # constant — exclude it from the cache key so seed sweeps reuse code
        key = (B, S, max_new, gen.with_(seed=0), resume_from)
        if key not in self._fns:
            self._fns[key] = self._timed_first_call(
                self._make_fn(B, S, max_new, gen, resume_from),
                f"generate[B={B},S={S},new={max_new},resume={resume_from}]",
            )
        return self._fns[key]

    # -- shared prefill wiring -------------------------------------------

    def _layer_window_fn(self):
        """Per-layer runtime window scalar for configs with sliding-window
        layers (``Family.layer_windows``): 0 on global layers, else the
        layer's window — one compiled kernel serves both kinds.
        None-returning where no layer has a window."""
        windows = self.family.layer_windows(self.cfg)
        if windows is None:
            return lambda layer_idx: None

        def layer_window(layer_idx):
            # the table is built at trace time: a device array closed over
            # from outside would be fetched to the host while tracing
            return jnp.asarray(windows, jnp.int32)[layer_idx]

        return layer_window

    def _model_shards(self) -> int:
        """The mesh's tensor axis, which the cache's KV heads divide over."""
        return 1 if self.mesh is None else self.mesh.shape.get("model", 1)

    def _init_cache(self, B: int, C: int):
        """The family's state for ``B`` rows of ``C`` slots. Under a mesh
        (the llama family alone) the cache is told the tensor axis its heads
        divide over: heads stored two a lane tile must pair off inside a
        shard (``models.llama.init_kv_cache``)."""
        shards = ({} if self.mesh is None
                  else {"model_shards": self._model_shards()})
        return self.family.init_cache(
            self.cfg, B, C, quantized=self.quantize_kv, **shards)

    def _init_prefill_cache(self, B: int, C: int):
        """Fresh KV cache with the mesh layout pinned (batch over data,
        heads over model) instead of left to GSPMD propagation."""
        cache = self._init_cache(B, C)
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            from ..parallel.sharding import cache_specs

            cache = jax.lax.with_sharding_constraint(
                cache,
                jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s),
                    cache_specs(quantized=self.quantize_kv),
                    is_leaf=lambda x: not isinstance(x, dict),
                ),
            )
        return cache

    def _prefill_stacked(self, use_flash, pad_lens, layer_window,
                         q_offset: int = 0, **piece):
        """The family's stacked-attention fn for a prefill-style forward
        whose queries start at cache slot ``q_offset`` (0 = whole prompt;
        chunked prefill passes each chunk's start), with a row piece's
        ``cache_rows`` where the forward runs one. None when the dense
        path is in effect."""
        if not (use_flash and self._attends):
            return None
        return self.family.prefill_attention(
            self.cfg, self.mesh, self.interpret, pad_lens, layer_window,
            q_offset, **piece)

    def _prefill_forward(self, params, tokens, pad_lens, B, S, C,
                         use_flash, layer_window, cache=None, start=0):
        """Whole- or chunked-prompt prefill; returns (last-position logits,
        cache). ONE copy shared by prefill_part (_make_parts) and the choice
        scorer (_make_choice_fn), so the two paths cannot drift AND the
        chunked path's memory headroom applies to both. Called inside traced
        functions — pad_lens is a tracer; chunk boundaries are trace-static.

        ``start`` > 0 is the prefix-cache resume boundary K: ``cache``
        arrives pre-seeded with gathered prefix KV for slots < K and the
        forward runs only over [K, S) — the same shape as chunked prefill's
        later chunks (positions/masks are sliced, q_offset places the
        queries), so resume and chunked share all their machinery.

        Where the family names a piece (``Family.prefill_piece_tokens``) a
        chunk runs a ROW PIECE at a time, ``_prefill_piece_rows`` rows of
        the batch: rows are left-padded, so a row holds nothing real in
        chunk [lo, hi) when its pad reaches hi, and a piece of such rows is
        not run at all — its cache slots stay as they came (zeros, which
        every kernel masks by ``pad_lens`` as it masks a pad token's keys).
        The pieces take the rows longest pad first, whatever order the batch
        holds them in, so a chunk's dead pieces are the first of its loop
        and the loop starts after them: one traced body a chunk, no
        conditional. A piece reads and writes its rows of the batch's cache
        in place (``cache_rows``); no slice of the cache is made."""
        cfg = self.cfg
        if cache is None:
            cache = self._init_prefill_cache(B, C)
        positions = prefill_positions(pad_lens, S)
        mask = prefill_attention_mask(pad_lens, S, C)

        # chunked: transient activations scale with the CHUNK length, not
        # the full S (and with a piece's rows, not the batch's) — the
        # kernel's q_offset places chunk c's queries at cache slots
        # [lo, hi) (see prefill_part's rationale comment)
        by_pad = None
        for lo, hi in self._prefill_spans(S, start):
            R = self._prefill_piece_rows(B, hi - lo)
            if not R:
                logits, cache = self.family.forward(
                    params, cfg, tokens[:, lo:hi], positions[:, lo:hi],
                    cache, lo, mask[:, lo:hi, :],
                    last_only=(hi == S),
                    stacked_attention_fn=self._prefill_stacked(
                        use_flash, pad_lens, layer_window, q_offset=lo
                    ),
                    **self._forward_kw,
                )
                continue
            if by_pad is None:
                by_pad = jnp.argsort(-pad_lens)
                # rows no piece runs (fillers) leave zeros nobody samples
                logits = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)

            # the body is traced where it stands, no helper between: a
            # frame more on the way to a layer's equations is seconds of a
            # first call on the chip's host (PERF.md section 6, PRs 38, 48)
            def piece(carry):  # traced at once, below: this turn's lo, hi, R
                i, logits, cache = carry
                rows = jax.lax.dynamic_slice_in_dim(by_pad, i * R, R)
                pads = pad_lens[rows]
                last, cache = self.family.forward(
                    params, cfg, tokens[rows][:, lo:hi],
                    prefill_positions(pads, S)[:, lo:hi], cache, lo,
                    prefill_attention_mask(pads, S, C)[:, lo:hi, :],
                    last_only=True,
                    stacked_attention_fn=self._prefill_stacked(
                        use_flash, pads, layer_window, q_offset=lo,
                        cache_rows=rows),
                    cache_rows=rows, **self._forward_kw,
                )
                if hi == S:  # an earlier chunk's logits are never computed
                    logits = logits.at[rows].set(last)
                return i + 1, logits, cache

            # the counter's type is said, not inferred: a carry whose type
            # moves in the body is traced twice, the layers with it
            dead = jnp.sum(pad_lens >= hi, dtype=jnp.int32)
            _, logits, cache = jax.lax.while_loop(
                lambda carry: carry[0] < B // R, piece,
                (dead // R, logits, cache))
        return logits, cache

    def _prefill_piece_rows(self, B: int, n: int) -> int:
        """How many of a batch's B rows one piece of an n-token prefill
        chunk holds: the fewest that divide B and hold the family's
        ``prefill_piece_tokens``. 0 is the whole batch in one forward —
        where the family names no piece, where the batch's rows are spread
        over a mesh's `data` axis, and where the whole chunk holds fewer
        tokens than a piece should."""
        floor = self.family.prefill_piece_tokens
        if floor is None or (self.mesh is not None
                             and self.mesh.shape.get("data", 1) > 1):
            return 0
        return next((R for R in range(1, B + 1)
                     if B % R == 0 and R * n >= floor), 0)

    def _count_row_chunks(self, pad_lens, S: int,
                          start: int = 0) -> tuple[int, int]:
        """Add one prefill's (row, chunk) pieces to
        ``stats.prefill_row_chunks_total`` and those ``_prefill_forward``
        did not run to ``stats.prefill_row_chunks_dead``. Pure host
        arithmetic on the pads the prefill was packed with; returns this
        prefill's (dead, total) for its span and its log line."""
        pads = np.asarray(pad_lens, np.int64)
        B, total, dead = len(pads), 0, 0
        for lo, hi in self._prefill_spans(S, start):
            R = self._prefill_piece_rows(B, hi - lo)
            total += B
            if R:
                dead += int((pads >= hi).sum()) // R * R
        self.stats.prefill_row_chunks_total += total
        self.stats.prefill_row_chunks_dead += dead
        return dead, total

    def _count_decode_kv_blocks(self, pad_lens, fills, S: int,
                                C: int) -> tuple[int, int]:
        """Add the key blocks some decode steps walked to
        ``stats.decode_kv_blocks_total`` and those the decode kernels left
        out for holding nothing but a row's left pad to
        ``stats.decode_kv_blocks_skipped``. ``fills`` [steps, rows] — or
        [steps, 1] where the rows share it — is the cache slot of a row's
        query at each step, after a prompt bucket of ``S`` in a cache of
        ``C`` slots. Window layers are not counted: they start at the window's
        floor whatever the pad. Pure host arithmetic on the pads and the
        block the kernels choose from the cache's shape; returns this call's
        (skipped, total) for its span and its log line — 0 and 0 where the
        decode steps ran another kernel than the GQA ones, or none."""
        if not (self.family.counts_prefill_blocks
                and self._decode_settings(S, C)[1]):
            return 0, 0
        from ..ops.decode_attention import decode_block_k
        from ..ops.flash_attention import heads_per_lane_tile

        cfg = self.cfg
        windows = (self.family.layer_windows(cfg)
                   or (0,) * self.family.attention_layers(cfg))
        # the cache's own shape: KV heads two a lane tile where they pair
        tile = heads_per_lane_tile(
            cfg.n_kv_heads, cfg.head_dim, self._model_shards())
        bk = decode_block_k(
            cfg.n_kv_heads // tile, cfg.head_dim * tile,
            1 if self.quantize_kv else jnp.dtype(cfg.dtype).itemsize, C)
        # a row that spent its budget sits one past the cache's last slot
        under_pad, walked = np.broadcast_arrays(
            np.asarray(pad_lens, np.int64) // bk,
            np.minimum(np.asarray(fills, np.int64), C - 1) // bk + 1)
        layers = sum(1 for w in windows if not w)
        total = int(walked.sum()) * layers
        skipped = int(np.minimum(under_pad, walked).sum()) * layers
        self.stats.decode_kv_blocks_total += total
        self.stats.decode_kv_blocks_skipped += skipped
        if tile > 1:
            self.stats.decode_kv_blocks_paired += total
        return skipped, total

    def _prefill_spans(self, S: int, start: int = 0) -> list[tuple[int, int]]:
        """Query spans [lo, hi) one prefill forward over cache slots
        [start, S) runs: the whole of it, or prefill_chunk_tokens-long
        chunks when it is longer than one."""
        CL = self.prefill_chunk_tokens
        if not CL or S - start <= CL:
            return [(start, S)]
        return [(lo, min(S, lo + CL)) for lo in range(start, S, CL)]

    def _count_prefill_blocks(self, pad_lens, S: int, C: int,
                              start: int = 0) -> str:
        """Add one dispatch's prefill-kernel cells, by class, to
        ``stats.prefill_blocks``. Pure host arithmetic on the pads the
        dispatch was packed with; nothing when its prefill is dense. The
        cells are the whole batch's in every chunk: those of a row piece
        that was not run (``_count_row_chunks``) are among ``dead_pad``,
        though the kernel no longer steps over them. Returns what the
        dispatch's log line says of the family's own counts."""
        if not self._decode_settings(S, C)[0]:
            return ""
        cfg = self.cfg
        total = self.stats.prefill_blocks
        said = ""
        if self.family.prefill_counts is not None:
            # what the family counts from the pads itself (a scan's tokens,
            # the keys a latent kernel expands)
            for name, n in self.family.prefill_counts(
                    cfg, pad_lens, self._prefill_spans(S, start), C).items():
                total[name] = total.get(name, 0) + n
                said += f", {name} {n}"
        if not self.family.counts_prefill_blocks:
            return said
        from ..ops.flash_attention import (
            BLOCK_CLASSES,
            prefill_block_class_grid,
        )

        # the layers that attend: all of them, or a family's few
        attending = self.family.attention_layers(cfg)
        windows = self.family.layer_windows(cfg) or (0,) * attending
        groups = (self.family.layer_groups(cfg)
                  or (cfg.q_per_kv,) * attending)
        pads = np.asarray(pad_lens, np.int64)
        # {(window, query heads a KV head): layers of that kind}
        for (window, group), n_layers in sorted(
                Counter(zip(windows, groups)).items()):
            computed = 0
            for lo, hi in self._prefill_spans(S, start):
                grid, (bq, bk) = prefill_block_class_grid(
                    pad_lens, hi - lo, C, lo, window, group, cfg.head_dim)
                counts = np.bincount(grid.ravel(), minlength=len(BLOCK_CLASSES))
                for name, n in zip(BLOCK_CLASSES, counts):
                    total[name] = total.get(name, 0) + int(n) * n_layers
                # interior and edge cells are fetched and computed whole
                computed += int(counts[2] + counts[3]) * bq * bk
            if window:
                # what the kernel computed on window layers against what the
                # window needs: a real query row at slot i sees
                # min(i + 1 - pad, window) keys, every query head of it
                seen = np.clip(
                    np.arange(start, S)[None, :] + 1 - pads[:, None], 0, window)
                heads = group * cfg.n_kv_heads * n_layers
                for name, n in (("window_scores_computed", computed),
                                ("window_scores_needed", int(seen.sum()))):
                    total[name] = total.get(name, 0) + n * heads
        return said

    # -- constrained choice scoring --------------------------------------

    def _make_choice_fn(self, B: int, S: int, K: int):
        """Compiled multiple-choice scorer: one prefill, last-position
        logits gathered at K candidate token ids, per-row argmax index.

        This is the constrained-decoding primitive behind the G-Eval device
        judge (eval/geval.py LLMJudge(constrained=True)): the JSON verdict
        template is forced on the host and only the score token is chosen
        by device logits, so the judge cannot emit an unparseable verdict.
        The reference's judge loop (evaluate/evaluate_summaries_semantic.py:
        203-433) trusts a remote LLM to emit parseable JSON and contains
        per-case failures; containment still exists here, but constrained
        choice makes success the typical case instead of the lucky one."""
        C = S  # no decode budget — the cache only satisfies forward()
        use_flash, _ = self._decode_settings(S, C)
        self._note_attention("choice", B, S, prefill=use_flash)
        mesh = self.mesh
        layer_window = self._layer_window_fn()

        def choose(params, tokens, pad_lens, choice_ids):
            logits, _ = self._prefill_forward(
                params, tokens, pad_lens, B, S, C, use_flash, layer_window
            )
            row = logits[:, -1, :]                       # [B, V] float32
            picked = jnp.take(row, choice_ids, axis=-1)  # [B, K]
            # argmax over the K picked logits is the full decision — no
            # softmax needed (monotone), so none is paid
            return jnp.argmax(picked, axis=-1).astype(jnp.int32)

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
            return jax.jit(
                choose,
                in_shardings=(
                    self._mesh_in_shardings()[0],
                    ns(P("data", None)),
                    ns(P("data")),
                    None,
                ),
            )
        return jax.jit(choose)

    # hot path
    def score_choices(
        self, prompts: list[str], choices: list[str]
    ) -> list[int]:
        """For each prompt, return the index of the choice whose FIRST token
        has the highest next-token logit after prefilling the prompt.

        Prompts that exceed the context are truncated from the LEFT — the
        tail is where a forced template ends, so it must survive. Choices
        must differ in their first token id (single-token constraint; the
        G-Eval judge uses the digits "1".."5", one byte each)."""
        ids = []
        for c in choices:
            enc = self.tok.encode(c, add_bos=False)
            if not enc:
                raise ValueError(f"choice {c!r} encodes to no tokens")
            ids.append(enc[0])
        if len(set(ids)) != len(ids):
            raise ValueError("choices must differ in their first token")
        choice_dev = jnp.asarray(ids, dtype=jnp.int32)

        self.stats.calls += 1
        self.stats.prompts += len(prompts)
        max_input = self.cfg.max_seq_len
        encoded: list[list[int]] = []
        sink = self.stats.host_spans
        with host_span("engine", "tokenize", sink, prompts=len(prompts)):
            for tok_ids in self.tok.encode_batch(prompts, add_bos=True):
                if len(tok_ids) > max_input:
                    tok_ids = [tok_ids[0]] + tok_ids[-(max_input - 1):]
                encoded.append(tok_ids)
                self.stats.prompt_tokens += len(tok_ids)

        order = sorted(range(len(encoded)), key=lambda i: len(encoded[i]))
        results: list[int] = [0] * len(encoded)
        # sanitizer hook (analysis pkg): nullcontext in production; under
        # VNSUM_SANITIZERS=transfer any IMPLICIT device->host transfer in
        # this dispatch loop raises, while the lint-acknowledged explicit
        # device_get fetches pass
        with hot_path_transfer_guard():
            for start in range(0, len(order), self.batch_size):
                group = order[start : start + self.batch_size]
                # max_new=0: choice scoring has no decode budget, so the
                # whole context is prompt space; bucketing/padding rules
                # are shared with generate() via _pack_group
                tokens, pad_lens, B, S = self._pack_group(group, encoded, 0)
                key = ("choice", B, S, len(ids))
                if key not in self._fns:
                    self._fns[key] = self._timed_first_call(
                        self._make_choice_fn(B, S, len(ids)),
                        f"choice[B={B},S={S}]",
                    )
                with host_span("engine", "choice", sink, B=B, S=S):
                    idx = self._fns[key](
                        self.params, tokens, pad_lens, choice_dev
                    )
                    # lint-allow[host-sync-in-hot-path]: result fetch — the host needs the chosen indices
                    idx_h = jax.device_get(idx)
                self.stats.batches += 1
                self.stats.by_bucket[(B, S)] = (
                    self.stats.by_bucket.get((B, S), 0) + 1
                )
                for row, i in enumerate(group):
                    results[i] = int(idx_h[row])
        return results

    # -- split prefill program (spec path) ------------------------------

    def _decode_settings(self, S: int, C: int):
        use_flash = self.flash
        use_flash_decode = False
        if use_flash:
            if self.interpret:  # interpret mode has no lane-alignment limits
                return True, True
            use_flash, use_flash_decode = self.family.attention_supported(
                self.cfg, S, C)
        return use_flash, use_flash_decode

    def _make_prefill_fn(self, B: int, S: int, max_new: int, gen,
                         resume_from: int = 0):
        prefill_part, _ = self._make_parts(B, S, max_new, gen, resume_from)

        def prefill(params, tokens, pad_lens, seed):
            return prefill_part(params, tokens, pad_lens, seed)

        return self._jit_program(prefill, prefill_part, resume_from)

    # -- in-flight slot serving programs (backend/inflight.py) -----------

    def _make_slot_prefill_fn(self, B: int, S: int, max_new: int, gen,
                              resume_from: int = 0):
        """Prefill for a JOIN group of the in-flight slot loop: the one-shot
        program's prefill_part (chunked and resume prefill ride along), its
        first-token keys folding the per-REQUEST uids passed in and not the
        rows' positions in the join batch — a request's sampled stream must
        not depend on when it joined or who it joined with. All-pad filler
        rows (join-batch bucketing) start done, as a one-shot batch's do."""
        use_flash, _ = self._decode_settings(S, S + max_new)
        self._note_attention("slot_prefill", B, S, prefill=use_flash)
        prefill_part = self._make_prefill_part(B, S, max_new, gen, resume_from)

        def slot_prefill(params, tokens, pad_lens, seed, uids, cache=None):
            return prefill_part(params, tokens, pad_lens, seed, cache, uids)

        return self._jit_program(slot_prefill, slot_prefill, resume_from,
                                 row_args=1)

    def _make_slot_segment_fn(self, B: int, S: int, max_new: int, gen):
        """One in-flight decode segment: advance every live slot by up to
        ``segment_tokens`` tokens with PER-ROW step counters — the refill
        path's defining requirement is that slots at different generation
        depths decode together, so the shared scalar ``t`` of decode_part
        becomes a [B] vector and masks/positions/cache-write slots ride the
        spec-verify machinery (verify_attention_mask + vector write_index,
        num_q=1). For any single row the emitted-token math is exactly
        decode_part's, so greedy outputs match the one-shot path up to the
        last bits that another batch shape's matmul tiling moves."""
        cfg = self.cfg
        C = S + max_new
        eos, vocab_limit, restrict = self._sampling_setup(gen)
        _, use_flash_decode = self._decode_settings(S, C)
        # the per-row-fills Pallas kernel is the one genuinely single-chip
        # piece left (multi-position ragged reads, like spec verify); under
        # a mesh the dense per-row path below serves the same math
        use_kernel = use_flash_decode and self.mesh is None
        self._note_attention("slot_seg", B, S, decode=use_kernel)
        interpret = self.interpret
        family, forward_kw = self.family, self._forward_kw
        layer_window = self._layer_window_fn()
        seg = self.segment_tokens

        def segment(params, t, cur, cache, done, uids, out, pads, seed):
            def emit_row(o, c, tt, d):
                # done rows hold a frozen cursor: an unguarded write would
                # clobber the row's last real token with its stale cur
                upd = jax.lax.dynamic_update_slice(o, c[None], (tt,))
                return jnp.where(d, o, upd)

            def cond(carry):
                k, _t, _cur, _cache, done, _out = carry
                return (k < seg) & ~jnp.all(done)

            def body(carry):
                k, t, cur, cache, done, out = carry
                # emit BEFORE sampling, mirroring decode_part: on exit every
                # live token is written and the rest stay pad from the init
                with jax.named_scope("emit"):
                    out = jax.vmap(emit_row)(out, cur, t, done)
                    done = done | jnp.isin(cur, eos)
                fills = S + t                                   # [B]
                positions = verify_positions(pads, fills, 1)
                mask = verify_attention_mask(pads, fills, 1, C)
                stacked_fn = None
                if use_kernel:
                    from ..ops.decode_attention import (
                        flash_spec_verify_attention,
                    )

                    def stacked_fn(q, cache_d, layer_idx):
                        return flash_spec_verify_attention(
                            q, cache_d, layer_idx, pads, fills,
                            cfg.q_per_kv, layer_window(layer_idx),
                            interpret=interpret,
                        )

                logits, cache = family.forward(
                    params, cfg, cur[:, None], positions, cache, fills,
                    mask, stacked_attention_fn=stacked_fn, **forward_kw,
                )
                with jax.named_scope("sample"):
                    step_keys = _stream_keys(base, uids)(t + 1)
                    nxt = sample_logits_rows(
                        restrict(logits[:, -1, :vocab_limit]), step_keys,
                        gen.temperature, gen.top_k, gen.top_p,
                    )
                # done rows freeze t (their out cursor) and cur; live rows
                # advance exactly like decode_part's shared t
                t = jnp.where(done, t, t + 1)
                done = done | (t >= max_new)
                cur = jnp.where(done, cur, nxt)
                return (k + 1, t, cur, cache, done, out)

            with jax.named_scope("decode"):
                base = jax.random.key(seed)
                _, t, cur, cache, done, out = jax.lax.while_loop(
                    cond, body, (jnp.int32(0), t, cur, cache, done, out)
                )
            return t, cur, cache, done, out

        # donate the resident cache and out buffers: segments overwrite
        # them in place
        return jax.jit(segment, donate_argnums=(3, 6))

    def _make_adopt_fn(self, Bj: int):
        """Refill program: scatter a join group's freshly prefilled cache
        rows and per-row state into the resident slot batch at the target
        slot indices — one advanced-index scatter per cache leaf, the same
        per-row dynamic_update_slice-class machinery the prefix-cache store
        uses for gathers. ``slot_idx`` entries are DISTINCT free slots by
        construction (the loop caps the join bucket at the free-slot
        count), so scatter ordering never matters."""
        pad_id = self.tok.pad_id

        def adopt(cache, cur, done, t, out, pads,
                  join_cache, first, done0, join_pads, slot_idx):
            with jax.named_scope("adopt"):
                cache = {
                    k: v.at[:, slot_idx].set(join_cache[k])
                    for k, v in cache.items()
                }
                cur = cur.at[slot_idx].set(first)
                done = done.at[slot_idx].set(done0)
                t = t.at[slot_idx].set(0)
                out = out.at[slot_idx].set(pad_id)
                pads = pads.at[slot_idx].set(join_pads)
            return cache, cur, done, t, out, pads

        # donate the resident cache/out (overwritten in place); the join
        # cache is NOT donated — the scatter reads it into differently
        # shaped outputs, so donation would only trigger warnings
        return jax.jit(adopt, donate_argnums=(0, 4))

    def start_slot_loop(
        self,
        slots: int | None = None,
        *,
        max_new_tokens: int | None = None,
        config: GenerationConfig | None = None,
        prompt_tokens: int = 0,
    ):
        """Open a persistent in-flight serving loop: a fixed-shape decode
        batch of ``slots`` rows where finished rows are harvested at every
        segment boundary and freed slots are REFILLED from new prompts
        (chunked prefill + adopt-scatter into the resident cache) —
        Orca-style iteration-level scheduling. Under a mesh the resident
        batch rows shard over `data` and heads over `model` (the same
        layout every other decode program uses), so the loop runs
        TP/DP-sharded; the slot count must stay divisible by the data axis.
        ``prompt_tokens`` fixes the prompt bucket S (0 = the full context
        minus the decode budget); prompts that don't fit are rejected at
        admit for the caller to route through the one-shot path, which is
        generate()'s only program."""
        from .inflight import TpuSlotLoop

        self.family.refuse("slot loop", self.cfg)
        n_slots = slots or self.batch_size
        if self.mesh is not None:
            data_size = self.mesh.shape.get("data", 1)
            if n_slots % data_size:
                raise ValueError(
                    f"slots={n_slots} must be divisible by the mesh data "
                    f"axis ({data_size}) — resident batch rows shard over it"
                )
            if data_size > 1 and n_slots < 2 * data_size:
                # join batches need >= data_size free slots before they can
                # form; at slots == data_size that means ONLY a fully
                # drained loop can refill — legal, but it silently degrades
                # iteration-level scheduling to batch dispatch
                logger.warning(
                    "slots=%d with mesh data axis %d: refill can only fire "
                    "once >= %d slots are free, so in-flight joins will be "
                    "rare — use slots >= %d to keep refill granular",
                    n_slots, data_size, data_size, 2 * data_size,
                )
        gen = config or self.gen_cfg
        max_new = resolve_max_new(max_new_tokens, gen, self.max_new_tokens)
        if max_new >= self.cfg.max_seq_len:
            raise ValueError(
                f"max_new_tokens={max_new} must be < "
                f"max_seq_len={self.cfg.max_seq_len}"
            )
        max_input = self.cfg.max_seq_len - max_new
        S = prompt_tokens or _bucket_len(max_input, max_input)
        if S > max_input:
            raise ValueError(
                f"prompt_tokens={S} exceeds the context budget "
                f"{max_input} (max_seq_len - max_new_tokens)"
            )
        return TpuSlotLoop(
            self, n_slots, S, max_new, gen, seed=self._next_seed(gen),
        )

    def _get_seg_fn(self, kind: str, B: int, S: int, max_new: int, gen,
                    resume_from: int = 0):
        key = (kind, B, S, max_new, gen.with_(seed=0), resume_from)
        if key not in self._seg_fns:
            self.family.refuse("slot loop", self.cfg)
            if kind == "prefill":
                fn = self._make_prefill_fn(B, S, max_new, gen, resume_from)
            elif kind == "slot_prefill":
                fn = self._make_slot_prefill_fn(B, S, max_new, gen, resume_from)
            elif kind == "slot_seg":
                fn = self._make_slot_segment_fn(B, S, max_new, gen)
            elif kind == "adopt":
                fn = self._make_adopt_fn(B)
            else:
                raise ValueError(f"no program of kind {kind!r}")
            self._seg_fns[key] = self._timed_first_call(
                fn,
                f"{kind}[B={B},S={S},new={max_new},resume={resume_from}]",
            )
        return self._seg_fns[key]

    def _next_seed(self, gen: GenerationConfig) -> int:
        s = fold_seed(gen.seed, self._seed, self._dispatch)
        self._dispatch += 1
        return s

    # -- speculative decoding (reference-guided, vnsum_tpu.spec) ---------

    def _make_spec_fn(self, B: int, S: int, R: int, max_new: int, k: int,
                      gen: GenerationConfig):
        """One jitted speculative step: draft (n-gram suffix match against
        the per-row reference), verify (ONE forward over k+1 query positions
        per row against the KV cache), accept (exact argmax prefix for
        greedy, rejection-style for sampling — models.sampling), emit.

        Per-row state raggedness is the defining difference from decode_part:
        rows accept different draft counts, so fills/emitted counts are [B]
        vectors, cache writes land at per-row slots (llama._cache_write),
        and rejected tokens "roll back" by simply not advancing the row's
        fill — the stale slots sit beyond every mask and are overwritten by
        the next step's write at that row's true fill.

        Cache/out geometry: C = S + max_new + k + 1 and the out buffer is
        max_new + k + 1 wide, so a step entered at e = max_new - 1 (or a
        done row parked at e = max_new) can always write its fixed-shape
        k+1 tokens without dynamic_update_slice's start-clamp silently
        shifting the write onto valid earlier slots."""
        from ..spec import NO_TOKEN, propose_drafts

        cfg = self.cfg
        k1 = k + 1
        C = S + max_new + k1
        N = max(gen.spec_ngram, 1)
        eos, vocab_limit, restrict = self._sampling_setup(gen)
        pad_id = self.tok.pad_id
        _, use_flash_decode = self._decode_settings(S, C)
        # the multi-position Pallas kernel is single-chip; under a data-only
        # mesh the dense per-row verify path serves the same math (generate()
        # degrades to plain decode only when `model` is sharded — the ragged
        # per-row fills don't compose with head-sharded kernel dispatch yet)
        use_verify_kernel = use_flash_decode and self.mesh is None
        self._note_attention("spec", B, S, decode=use_verify_kernel)
        interpret = self.interpret
        family, forward_kw = self.family, self._forward_kw
        layer_window = self._layer_window_fn()

        def spec_step(params, cur, cache, done, e, out, pads, ref,
                      ref_lens, seed):
            base = jax.random.key(seed)
            uids = jnp.arange(B, dtype=jnp.int32)
            fills = S + e                                       # [B]

            # --- draft: last N emitted tokens (incl. cur) vs reference ---
            if N > 1:
                out_pad = jnp.concatenate(
                    [jnp.full((B, N - 1), NO_TOKEN, jnp.int32), out], axis=1
                )
                hist = jax.vmap(
                    lambda row, s: jax.lax.dynamic_slice(row, (s,), (N - 1,))
                )(out_pad, e)
                tail = jnp.concatenate([hist, cur[:, None]], axis=1)
            else:
                tail = cur[:, None]
            drafts, n_draft = propose_drafts(ref, ref_lens, tail, k)
            # done rows draft nothing; live rows never draft past the token
            # budget (acceptance may not push e beyond max_new)
            n_draft = jnp.where(done, 0, n_draft)
            n_draft = jnp.minimum(n_draft, jnp.maximum(max_new - e - 1, 0))

            # --- batched verify forward over k+1 positions per row ---
            toks = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, k1]
            positions = verify_positions(pads, fills, k1)
            mask = verify_attention_mask(pads, fills, k1, C)
            stacked_fn = None
            if use_verify_kernel:
                from ..ops.decode_attention import flash_spec_verify_attention

                def stacked_fn(q, cache_d, layer_idx):
                    return flash_spec_verify_attention(
                        q, cache_d, layer_idx, pads, fills, cfg.q_per_kv,
                        layer_window(layer_idx), interpret=interpret,
                    )

            logits, cache = family.forward(
                params, cfg, toks, positions, cache, fills, mask,
                stacked_attention_fn=stacked_fn, **forward_kw,
            )
            logits = restrict(logits[:, :, :vocab_limit])

            # --- accept + emit ---
            # position i (when reached) emits stream token e + i: key on
            # that absolute position so acceptance raggedness never replays
            # a row's randomness
            pos_ids = e[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :] + 1
            keys = _stream_keys(base, uids)(pos_ids)
            m, nxt = draft_acceptance_rows(
                logits, drafts, n_draft, keys,
                gen.temperature, gen.top_k, gen.top_p,
            )

            idx = jnp.arange(k1, dtype=jnp.int32)[None, :]
            is_term = jnp.isin(toks, eos)
            no_term_before = jnp.cumprod(
                jnp.concatenate(
                    [jnp.ones((B, 1), jnp.int32),
                     (~is_term[:, :-1]).astype(jnp.int32)],
                    axis=1,
                ),
                axis=1,
            ).astype(bool)
            # emit cur plus accepted drafts, cut just after a terminator —
            # the terminator itself is emitted (and detok-stripped) exactly
            # like the plain decode path's emit-before-done-check
            valid = (idx <= m[:, None]) & no_term_before & ~done[:, None]
            emit = jnp.where(valid, toks, pad_id)
            out = jax.vmap(
                lambda o, v, s: jax.lax.dynamic_update_slice(o, v, (s,))
            )(out, emit, e)
            n_emit = valid.sum(axis=1).astype(jnp.int32)
            e_new = e + n_emit
            done_new = done | (is_term & valid).any(axis=1) | (e_new >= max_new)
            cur_new = jnp.where(done, cur, nxt)
            accepted = jnp.maximum(n_emit - 1, 0)
            return cur_new, cache, done_new, e_new, out, n_draft, accepted

        return jax.jit(spec_step, donate_argnums=(2, 5))

    def _get_spec_fn(self, B, S, R, max_new, k, gen):
        key = ("spec", B, S, R, max_new, k, gen.with_(seed=0))
        if key not in self._fns:
            self._fns[key] = self._timed_first_call(
                self._make_spec_fn(B, S, R, max_new, k, gen),
                f"spec[B={B},S={S},R={R},k={k}]",
            )
        return self._fns[key]

    # hot path
    def _run_group_spec(
        self, group, encoded, references, max_new: int, gen, results,
        report, seed: int,
    ) -> None:
        """Generate one prompt group with reference-guided speculation:
        shared prefill, then a host loop of jitted spec steps (draft →
        batched verify → accept). Every step retires >= 1 token per live
        row, so the loop is bounded by max_new; rows whose reference never
        matches degrade to exactly one token per step."""
        from ..spec import NO_TOKEN, SpecRecord, encode_references

        k = gen.spec_k
        tokens, pads, B, S = self._pack_group(group, encoded, max_new)

        # per-row reference buffers, R bucketed to a power of two so ref
        # length variation doesn't fan out fresh XLA programs
        refs_group = [references[i] if references else None for i in group]
        ref_np, ref_lens_np = encode_references(
            self.tok, refs_group, self.spec_max_ref_tokens
        )
        R = 64
        while R < ref_np.shape[1]:
            R *= 2
        ref_full = np.full((B, R), NO_TOKEN, dtype=np.int32)
        ref_full[: len(group), : ref_np.shape[1]] = ref_np
        lens_full = np.zeros((B,), dtype=np.int32)
        lens_full[: len(group)] = ref_lens_np

        sink = self.stats.host_spans
        prefill = self._get_seg_fn("prefill", B, S, max_new + k + 1, gen)
        # the dispatch is asynchronous: this bounds submission, not device
        # time (synced=False keeps it from anchoring a TTFT)
        with host_span("engine", "spec_prefill", sink, B=B, S=S,
                       occupancy=len(group), synced=False):
            cur, cache, done = prefill(self.params, tokens, pads, seed)
        self.stats.batches += 1
        self.stats.by_bucket[(B, S)] = self.stats.by_bucket.get((B, S), 0) + 1

        fn = self._get_spec_fn(B, S, R, max_new, k, gen)
        pad_dev = jnp.asarray(pads)
        ref_dev = jnp.asarray(ref_full)
        lens_dev = jnp.asarray(lens_full)
        out = jnp.full((B, max_new + k + 1), self.tok.pad_id, dtype=jnp.int32)
        e = jnp.zeros((B,), dtype=jnp.int32)

        drafted = np.zeros((B,), dtype=np.int64)
        accepted = np.zeros((B,), dtype=np.int64)
        steps_live = np.zeros((B,), dtype=np.int64)
        # lint-allow[host-sync-in-hot-path]: prefill done mask seeds the host loop's exit condition
        prev_done = jax.device_get(done)
        while not prev_done.all():
            # one span a verify step (dispatch to its fetch), as the loop
            # has one sync a step: never one a token
            with host_span("engine", "spec_step", sink, B=B, k=k) as step:
                cur, cache, done, e, out, nd, acc = fn(
                    self.params, cur, cache, done, e, out, pad_dev,
                    ref_dev, lens_dev, seed,
                )
                steps_live += ~prev_done
                # ONE explicit fetch per verify step: draft/accept counts
                # feed the acceptance stats and done drives the loop exit —
                # this is the sync the host loop already owes
                # lint-allow[host-sync-in-hot-path]: per-step nd/acc/done fetch is the verify loop's control dependency
                nd_h, acc_h, prev_done = jax.device_get((nd, acc, done))
                # drafted vs accepted feeds the rolling acceptance gauge's
                # per-step ground truth (the collector's, when installed)
                step.note(live=int((~prev_done).sum()),
                          drafted=int(nd_h.sum()), accepted=int(acc_h.sum()))
            drafted += nd_h
            accepted += acc_h
            self.stats.spec_verify_steps += 1
        self.stats.spec_draft_tokens += int(drafted[: len(group)].sum())
        self.stats.spec_accepted_tokens += int(accepted[: len(group)].sum())

        # lint-allow[host-sync-in-hot-path]: final result fetch — detok needs the emitted tokens
        out_h = jax.device_get(out)[:, :max_new]
        for row, i in enumerate(group):
            results[i] = self._detok(out_h[row], tuple(gen.eos_ids))
            report[i] = SpecRecord(
                draft_tokens=int(drafted[row]),
                accepted_tokens=int(accepted[row]),
                verify_steps=int(steps_live[row]),
            )

    # -- prefix KV cache (vnsum_tpu.cache) -------------------------------

    def _prepare_resume(self, group, encoded, matches, pad_lens, B, S,
                        max_new: int):
        """Compute the trace-static skip boundary K for one packed group and
        gather the matched prefix blocks into a seeded cache.

        Slot arithmetic (left-padded rows; pad_r = S - len_r):

        - K = 128-aligned floor of (S - longest uncovered suffix): for every
          row, slots [pad_r, K) are covered by matched blocks, so ONE static
          boundary serves the whole batch; rows whose prompt starts at or
          after K (pad_r >= K) need no blocks at all.
        - row r gathers ceil((K - pad_r)/BLK) blocks at slots pad_r + i*BLK;
          ragged rows pad with the scratch block, whose writes land at slots
          >= K — inside the span the suffix prefill (slots [K, S)) or decode
          (slots >= S, each written before it is ever attended) overwrites —
          so padding can never corrupt a live row.

        Returns (K, seeded_cache, skipped_per_row) or None when the group
        has no usable 128-aligned coverage."""
        pc = self.prefix_cache
        BLK = pc.block_tokens
        max_suffix = max(len(encoded[i]) - matches[i].tokens for i in group)
        # the scratch-padding safety argument needs clamped writes
        # (dynamic_update_slice clamps starts to C - BLK) to still land at
        # slots >= K. S is usually a 128-multiple bucket, making C - BLK >=
        # K automatic — but the bucket FALLBACK (prompt longer than the last
        # bucket) is max_input, which need not be aligned, so cap K
        # explicitly rather than assume it.
        # K is quantized to a coarse grid (max(128, S/8) steps): each
        # distinct K compiles its own resume program per (B, S, max_new)
        # bucket, so a fine grid would let a warm server accrete up to S/128
        # executables per bucket — 8 variants bounds compile churn while
        # giving up at most one step of skip
        step = max(128, S // 8 // 128 * 128)
        K = min(S - max_suffix, S + max_new - BLK) // step * step
        if K < 128:
            return None
        ids_rows: list[list[int]] = []
        nb_max = 0
        for row, i in enumerate(group):
            pad = int(pad_lens[row])
            need = K - pad
            n = -(-need // BLK) if need > 0 else 0
            blocks = matches[i].blocks[:n]
            ids_rows.append(blocks)
            nb_max = max(nb_max, len(blocks))
        if nb_max == 0:
            return None
        with host_span("engine", "cache_gather", self.stats.host_spans,
                       B=B, K=K) as sp:
            ids = np.full((B, nb_max), pc.store.scratch_id, dtype=np.int32)
            for row, blocks in enumerate(ids_rows):
                ids[row, : len(blocks)] = blocks
            cache = self._init_prefill_cache(B, S + max_new)
            cache = pc.gather(cache, ids, pad_lens)
            skipped = [
                max(K - int(pad_lens[row]), 0) for row in range(len(group))
            ]
            sp.note(blocks=int((ids != pc.store.scratch_id).sum()),
                    hit_tokens=sum(skipped))
        return K, cache, skipped

    def _cache_insert(self, cache, group, encoded, matches, hints,
                      pad_lens) -> int:
        """Index the freshly prefilled prompts and copy their new prefix
        blocks into the pool. A cache_hint bounds the insertion to the
        hint-covered prefix (template headers, carried-forward summaries) so
        unique content tails don't churn the pool; without one the whole
        prompt (minus its last token) is insertable and LRU manages it."""
        pc = self.prefix_cache
        if not self.cache_inserts_enabled:
            # ladder rung NO_CACHE_INSERT: stop pool churn; hits still serve
            return 0
        BLK = pc.block_tokens
        with host_span("engine", "cache_insert", self.stats.host_spans) as sp:
            evict0 = pc.index.stats.evictions
            rows = []
            for row, i in enumerate(group):
                ids = encoded[i]
                target = len(ids) - 1
                hint = hints[i] if hints else None
                if hint:
                    target = min(self._hint_prefix_len(hint, ids), target)
                upto = target // BLK * BLK
                if upto > matches[i].tokens:
                    rows.append((row, int(pad_lens[row]), ids, upto))
            # every row's new blocks go to the pool in one dispatch
            new_blocks = pc.insert(cache, rows)
            sp.note(blocks=new_blocks,
                    evictions=pc.index.stats.evictions - evict0)
        return new_blocks

    def _hint_prefix_len(self, hint: str, ids: list[int]) -> int:
        """Token-aligned hint boundary: the longest common prefix of the
        hint's own encoding and the prompt's. Exact when tokenization is
        prefix-stable (tests/test_text_tokenizer.py pins the shipped
        templates); safely shorter when a merge crosses the boundary."""
        hint_ids = self._hint_ids_cache.get(hint)
        if hint_ids is None:
            if len(self._hint_ids_cache) >= 256:
                self._hint_ids_cache.clear()
            hint_ids = self.tok.encode(hint, add_bos=True)
            self._hint_ids_cache[hint] = hint_ids
        n = min(len(hint_ids), len(ids))
        k = 0
        while k < n and hint_ids[k] == ids[k]:
            k += 1
        return k

    def set_prefix_cache_inserts(self, enabled: bool) -> None:
        """Degradation-ladder hook (serve/supervisor.py): gate prefix-cache
        insertion while hits keep serving. Engine-thread-only, like every
        generate() call — the serving scheduler applies rung changes
        lazily on its own thread for exactly this reason."""
        self.cache_inserts_enabled = bool(enabled)

    def cached_prefix_tokens(self, text: str, cache_hint: str | None = None) -> int:
        """Read-only probe: how many prompt tokens the prefix cache would
        serve right now. Thread-safe (the radix probe path), used by the
        serving queue to bill only uncached tokens against the admission
        token budget. An estimate — the usable skip also depends on batch
        composition (the 128-aligned K)."""
        if self.prefix_cache is None:
            return 0
        ids = self.tok.encode(text, add_bos=True)
        # same truncation generate() applies for the default decode budget,
        # so the admission discount can never exceed what a dispatch could
        # actually reuse
        max_input = self.cfg.max_seq_len - self.max_new_tokens
        if len(ids) > max_input:
            ids = ids[:max_input]
        return self.prefix_cache.probe(ids, max_tokens=len(ids) - 1)

    def prefix_cache_stats(self) -> dict | None:
        """Pool/index counters for /metrics gauges (None = cache off)."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.stats_dict()

    def engine_counters(self) -> dict:
        """The engine's work counters and its held executions as one flat
        dict, for /metrics (the ``engine_*`` families of serve/metrics.py):
        read at scrape time like ``prefix_cache_stats()``, never mirrored."""
        st = self.stats
        return {
            "prefill_row_chunks": st.prefill_row_chunks_total,
            "prefill_row_chunks_dead": st.prefill_row_chunks_dead,
            "decode_kv_blocks": st.decode_kv_blocks_total,
            "decode_kv_blocks_skipped": st.decode_kv_blocks_skipped,
            "decode_kv_blocks_paired": st.decode_kv_blocks_paired,
            "executions_held": st.executions_held,
            "held_excess_seconds": st.held_excess_seconds,
        }

    def engine_record(self) -> dict:
        """The engine's own account of a run, for the run record
        (``PipelineRunner``: ``tracing["engine"]``): host spans, executions
        by ``program[B=..,S=..]``, the held ones whole, the work counters."""
        st = self.stats
        return {
            "host_spans": {k: v.to_dict()
                           for k, v in sorted(st.host_spans.items())},
            "executions": {execution_key(k): v.to_dict()
                           for k, v in sorted(st.executions.items())},
            "held": list(st.held),
            **self.engine_counters(),
        }

    def take_cache_report(self) -> list[int]:
        """Per-prompt prefill tokens served from the prefix cache on the
        LAST generate call (empty when the cache was off), cleared on read —
        the same attribution hook shape as take_spec_report."""
        report, self._cache_report = self._cache_report, []
        return report

    def take_spec_report(self):
        """Per-prompt SpecRecords of the LAST generate call, aligned with
        its prompt order (empty when speculation was off), cleared on read.
        The serving scheduler attributes per-request acceptance metrics
        through this hook; engine access is single-threaded by the serving
        contract (serve/scheduler.py), so read-after-generate is safe."""
        report, self._spec_report = self._spec_report, []
        return report

    # -- public API ------------------------------------------------------

    def _pack_group(self, group, encoded, max_new: int):
        """Pack one prompt group into a fixed-shape left-padded batch.

        Shared by the one-shot and spec paths and the choice scorer — their
        greedy-parity guarantee depends on identical bucketing and padding."""
        with host_span("engine", "pack", self.stats.host_spans,
                       rows=len(group)):
            max_input = self.cfg.max_seq_len - max_new
            data_size = (
                self.mesh.shape.get("data", 1) if self.mesh is not None else 1
            )
            S = _bucket_len(max(len(encoded[i]) for i in group), max_input)
            # bucket the batch dim too, so a trailing partial group doesn't
            # pay for all-pad rows up to the full batch_size
            B = data_size
            while B < len(group):
                B *= 2
            B = min(B, self.batch_size)
            tokens, pad_lens = left_pad_batch(
                [encoded[i] for i in group], B, S, self.tok.pad_id
            )
        return tokens, pad_lens, B, S

    # hot path
    def generate(
        self,
        prompts: list[str],
        *,
        max_new_tokens: int | None = None,
        config: GenerationConfig | None = None,
        references: list[str | None] | None = None,
        cache_hints: list[str | None] | None = None,
    ) -> list[str]:
        gen = config or self.gen_cfg
        max_new = resolve_max_new(max_new_tokens, gen, self.max_new_tokens)
        if max_new >= self.cfg.max_seq_len:
            raise ValueError(
                f"max_new_tokens={max_new} must be < max_seq_len={self.cfg.max_seq_len}"
            )
        if not prompts:
            return []
        if references is not None and len(references) != len(prompts):
            raise ValueError(
                f"references must align with prompts: got {len(references)} "
                f"for {len(prompts)}"
            )
        if cache_hints is not None and len(cache_hints) != len(prompts):
            raise ValueError(
                f"cache_hints must align with prompts: got {len(cache_hints)} "
                f"for {len(prompts)}"
            )
        # seeded fault injection (vnsum_tpu.testing.faults): one global
        # None-check when disarmed; sits after input validation so injected
        # faults exercise DISPATCH recovery, not the argument checks
        fault("engine.dispatch", prompts=prompts)

        # reference-guided speculative decoding: needs spec_k > 0 AND at
        # least one reference to draft from. Data-parallel meshes run the
        # dense verify path (rows are replica-local, same math); only
        # `model`-sharded meshes degrade to plain decode (same outputs in
        # greedy, just one token per step) — the multi-position verify
        # kernel is the one genuinely single-chip piece left.
        spec_on = (
            gen.spec_k > 0
            and references is not None
            and any(references)
        )
        if spec_on:
            self.family.refuse("speculative decoding", self.cfg)
        if (
            spec_on
            and self.mesh is not None
            and self.mesh.shape.get("model", 1) > 1
        ):
            if not self._warned_spec_fallback:
                self._warned_spec_fallback = True
                logger.warning(
                    "spec_k=%d requested under a model-sharded mesh; the "
                    "spec verify step is data-parallel only — falling back "
                    "to plain decode",
                    gen.spec_k,
                )
            spec_on = False
        spec_report: list = (
            [None] * len(prompts) if spec_on else []
        )

        self.stats.calls += 1
        self.stats.prompts += len(prompts)
        # cleared up front: a call that errors mid-loop must not leave a
        # previous call's per-prompt cache attribution behind for the
        # scheduler's take_cache_report to misread
        self._cache_report = []

        # host spans (core.profiling.host_span): a fixed count a call and
        # a dispatch, never one a row or a token — tests/test_host_spans.py
        # holds the numbers
        sink = self.stats.host_spans
        max_input = self.cfg.max_seq_len - max_new
        encoded: list[list[int]] = []
        with host_span("engine", "tokenize", sink, prompts=len(prompts)):
            # ONE batched call into the tokenizer (Rust side parallelizes
            # and skips per-prompt Python overhead; measured 1.4x on this
            # phase)
            for ids in self.tok.encode_batch(prompts, add_bos=True):
                if len(ids) > max_input:
                    ids = ids[:max_input]
                encoded.append(ids)
                self.stats.prompt_tokens += len(ids)

        # prefix KV cache (vnsum_tpu.cache): match every prompt against the
        # radix index (pinning the matched blocks against eviction for the
        # duration of the call) and order rows by UNCOVERED suffix length —
        # a group's usable skip K is S minus its longest suffix, so one cold
        # row mixed into a warm group would zero everyone's reuse. Spec
        # calls skip the cache: the verify path's per-row fills don't share
        # prefill's single resume boundary.
        pc = self.prefix_cache
        use_cache = pc is not None and not spec_on
        matches = None
        cache_report = [0] * len(encoded)
        if use_cache:
            with host_span("engine", "cache_lookup", sink,
                           prompts=len(encoded)) as sp:
                matches = [
                    pc.match(ids, max_tokens=len(ids) - 1) for ids in encoded
                ]
                sp.note(hit_tokens=sum(m.tokens for m in matches))
            order = sorted(
                range(len(encoded)),
                key=lambda i: (len(encoded[i]) - matches[i].tokens,
                               len(encoded[i])),
            )
        else:
            # group indices by bucketed length, then emit fixed-shape batches
            order = sorted(range(len(encoded)), key=lambda i: len(encoded[i]))
        results: list[str | None] = [None] * len(encoded)
        t0 = time.time()
        try:
            # sanitizer hook (analysis pkg): nullcontext in production;
            # under VNSUM_SANITIZERS=transfer any IMPLICIT device->host
            # transfer inside the dispatch loop raises, while the
            # lint-acknowledged explicit device_get fetches pass
            with hot_path_transfer_guard():
                for start in range(0, len(order), self.batch_size):
                    group = order[start : start + self.batch_size]
                    seed = self._next_seed(gen)
                    # per-GROUP spec routing: a coalesced batch can mix
                    # referenced and reference-less requests, and length-sorting
                    # may put all the refless ones in one group — that group
                    # would pay the (k+1)-wide verify forward to retire one
                    # token per step, so it takes the plain path instead
                    # (identical greedy output either way; its spec_report rows
                    # stay zero)
                    if spec_on and any(references[i] for i in group):
                        self._run_group_spec(
                            group, encoded, references, max_new, gen, results,
                            spec_report, seed,
                        )
                        continue
                    # one dispatch of the one-shot program, each phase under
                    # its span, all inside ``engine/dispatch``, which closes
                    # with the dispatch: a phase too short to name an idle
                    # gap of the device leaves the gap to this parent. The
                    # program is CALLED FROM THIS FRAME and no other
                    # (tests/test_host_spans.py pins it, and says why)
                    with host_span("engine", "dispatch", sink,
                                   rows=len(group)) as disp:
                        tokens, pad_lens, B, S = self._pack_group(
                            group, encoded, max_new
                        )
                        disp.note(B=B, S=S, occupancy=len(group),
                                  max_new=max_new)
                        resume = None
                        if matches is not None:
                            resume = self._prepare_resume(
                                group, encoded, matches, pad_lens, B, S,
                                max_new,
                            )
                        if resume is not None:
                            for row, i in enumerate(group):
                                cache_report[i] = resume[2][row]
                        K = resume[0] if resume else 0
                        fn = self._get_fn(B, S, max_new, gen, resume_from=K)
                        # the call alone: arguments transferred, output
                        # buffers allocated, program queued — it returns
                        # before the device ends
                        warm = fn in self._warm
                        with execution_span("engine", "enqueue", sink,
                                            closes=False, probe=warm,
                                            B=B, S=S) as enqueue:
                            if K:
                                res = fn(self.params, tokens, pad_lens, seed,
                                         resume[1])
                            else:
                                res = fn(self.params, tokens, pad_lens, seed)
                        # with the prefix cache on, the program also returns
                        # its final cache so new prefix blocks can be pooled
                        out_dev, final_cache = res if pc is not None else (res, None)
                        # the fused prefill+decode program has no observable
                        # midpoint: this fetch bounds the whole device call
                        # (execution and the copy back) — TTFT consumers
                        # treat the dispatch's end as the first-token upper
                        # bound
                        with execution_span("engine", "wait", sink,
                                            opens=False, probe=warm,
                                            B=B, S=S) as wait:
                            fault("engine.wait")
                            # lint-allow[host-sync-in-hot-path]: one-shot result fetch bounds the dispatch and feeds detok
                            out = jax.device_get(out_dev)
                        with host_span("engine", "count", sink):
                            counted = None
                            if self.family.counters is not None:
                                # a family that counts returns its counters
                                # with the tokens: one fetch brought both
                                out, counted = out
                            dead, pieces = self._count_row_chunks(
                                pad_lens, S, K)
                            # the decode loop ran a step for every token of
                            # the longest row, step t at slot S + t
                            steps = int((out != self.tok.pad_id).any(0).sum())
                            skipped, blocks = self._count_decode_kv_blocks(
                                pad_lens, S + np.arange(steps)[:, None], S,
                                S + max_new)
                            disp.note(dead_row_chunks=dead,
                                      skipped_kv_blocks=skipped,
                                      kv_blocks=blocks)
                            grid = (f", dead_row_chunks {dead}/{pieces}"
                                    f", skipped_kv_blocks {skipped}/{blocks}")
                            if counted is not None:
                                grid += self._add_expert_counts(counted)
                            grid += self._count_prefill_blocks(
                                pad_lens, S, S + max_new, K)
                        self.stats.batches += 1
                        self.stats.by_bucket[(B, S)] = (
                            self.stats.by_bucket.get((B, S), 0) + 1
                        )
                        if use_cache:
                            self._cache_insert(
                                final_cache, group, encoded, matches,
                                cache_hints, pad_lens,
                            )
                        with host_span("engine", "detokenize", sink,
                                       rows=len(group)) as detok:
                            eos = tuple(gen.eos_ids)
                            for row, i in enumerate(group):
                                results[i] = self._detok(out[row], eos)
                    self.stats.note_execution(
                        "generate", B, S, enqueue, wait, first=not warm,
                        rows=len(group), pieces=(dead, pieces), steps=steps,
                        kv_blocks=(skipped, blocks))
                    logger.info(
                        "dispatch B=%d S=%d rows=%d: enqueue %.3fs wait "
                        "%.3fs detokenize %.3fs of %.3fs%s",
                        B, S, len(group), enqueue.dur, wait.dur, detok.dur,
                        disp.dur, grid,
                    )
        finally:
            if matches is not None:
                for m in matches:
                    pc.release(m)
        self.stats.generate_seconds += time.time() - t0
        if use_cache:
            hit = sum(cache_report)
            self.stats.cache_hit_tokens += hit
            self.stats.cache_miss_tokens += (
                sum(len(e) for e in encoded) - hit
            )
        self._cache_report = cache_report if use_cache else []
        if spec_on:
            from ..spec import SpecRecord

            # rows whose group took the plain path report zeros, keeping
            # the per-prompt alignment the serving scheduler relies on
            spec_report = [r if r is not None else SpecRecord()
                           for r in spec_report]
        self._spec_report = spec_report
        return results  # type: ignore[return-value]

    def _add_expert_counts(self, counted: dict) -> str:
        """Add one dispatch's expert counters (host arrays by now) to the
        statistics. Returns what the dispatch's log line says of them: the
        live share of the grouped product's grid in its decode steps."""
        st = self.stats
        st.expert_slots_routed += int(counted["slots_routed"])
        st.expert_slots_held += int(counted["slots_held"])
        decode = {name: int(counted.get(name, 0)) for name in (
            "decode_touched", "decode_layer_steps", "decode_tiles_used",
            "decode_tiles_walked")}
        for name, n in decode.items():
            setattr(st, f"expert_{name}", getattr(st, f"expert_{name}") + n)
        tokens = np.asarray(counted["expert_tokens"], np.int64)
        if st.expert_tokens:
            tokens = tokens + np.asarray(st.expert_tokens, np.int64)
        st.expert_tokens = tokens.tolist()
        used, walked = decode["decode_tiles_used"], decode["decode_tiles_walked"]
        if not walked:
            return ""
        return (f", expert tiles used {used} of {walked} walked "
                f"({used / walked:.3f})")

    def prefill_then_decode_logits(
        self, prompt_ids, forced_ids, bucket: int | None = None,
        return_state: bool = False,
    ):
        """Logits of the engine's own two phases on one prompt, for parity
        checks: the prompt (token ids) goes through the chunked prefill as
        ``generate`` runs it (left-padded into ``bucket``, default its
        length bucket; the family's prefill kernel, W8A8 where on), then
        each of ``forced_ids`` is fed as the next token through one decode
        step over the cache (the family's decode kernel), whatever the
        model would have sampled. Returns float32 ``[1 + len(forced_ids),
        vocab]``: row 0 scores the token after the prompt, row i the token
        after ``forced_ids[i - 1]``. With ``return_state`` also, as host
        arrays, ``{"cache": ..., "rows": ...}``: the state the program ends
        with (the family's cache of ``bucket + len(forced_ids)`` slots —
        the prompt's rows end at slot ``bucket``, each forced token's row
        follows — and whatever else it carries), and the family's
        ``row_record`` of each scored position, stacked in the rows' order
        (None for a family that records nothing). One row, no sampling,
        nothing cached between calls; its program is compiled per (bucket,
        steps)."""
        n, steps = len(prompt_ids), len(forced_ids)
        S = bucket or _bucket_len(n, self.cfg.max_seq_len - max(steps, 1))
        if n > S:
            raise ValueError(f"{n} prompt tokens do not fit bucket {S}")
        C = S + max(steps, 1)
        key = ("logits", S, steps)
        if key not in self._fns:
            use_flash, use_flash_decode = self._decode_settings(S, C)
            self._note_attention("logits", 1, S, prefill=use_flash,
                                 decode=use_flash_decode)
            layer_window = self._layer_window_fn()
            family, cfg = self.family, self.cfg

            def program(params, tokens, pad_lens, forced):
                with jax.named_scope("prefill"):
                    first, cache = self._prefill_forward(
                        params, tokens, pad_lens, 1, S, C, use_flash,
                        layer_window)
                rows = [first[:, -1, :]]
                record = family.row_record
                seen = [record(cache)] if record else []
                with jax.named_scope("decode"):
                    for t in range(steps):
                        stacked_fn = None
                        if use_flash_decode and self._attends:
                            stacked_fn = family.decode_attention(
                                cfg, self.mesh, self.interpret, pad_lens,
                                S, t, layer_window)
                        logits, cache = family.forward(
                            params, cfg, forced[:, t:t + 1],
                            ((S - pad_lens) + t)[:, None], cache, S + t,
                            decode_attention_mask(pad_lens, S + t, C),
                            stacked_attention_fn=stacked_fn,
                            **self._forward_kw)
                        rows.append(logits[:, -1, :])
                        if record:
                            seen.append(record(cache))
                return (jnp.concatenate(rows, axis=0), cache,
                        jax.tree.map(lambda *a: jnp.stack(a), *seen)
                        if seen else None)

            self._fns[key] = self._timed_first_call(
                jax.jit(program), f"logits[S={S},steps={steps}]")
        tokens = np.full((1, S), self.tok.pad_id, np.int32)
        tokens[0, S - n:] = np.asarray(prompt_ids, np.int32)
        forced = np.asarray(forced_ids, np.int32).reshape(1, steps)
        logits, cache, seen = self._fns[key](
            self.params, jnp.asarray(tokens),
            jnp.asarray([S - n], jnp.int32), jnp.asarray(forced))
        if return_state:
            return np.asarray(logits), jax.tree.map(
                np.asarray, {"cache": cache, "rows": seen})
        return np.asarray(logits)

    def _detok(self, ids: np.ndarray, extra_eos: tuple[int, ...] = ()) -> str:
        self.stats.generated_tokens += int((ids != self.tok.pad_id).sum())
        out = trim_to_eos(
            ids.tolist(), self.tok.eos_id, self.tok.pad_id, extra_eos
        )
        return self.tok.decode(out).strip()

    def count_tokens(self, text: str) -> int:
        return self.tok.count(text)

    def count_tokens_batch(self, texts: list[str]) -> list[int]:
        """Batched count for the splitter's length function — one Rust-side
        call per split level instead of one per sentence piece."""
        return self.tok.count_batch(texts)
