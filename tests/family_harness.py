"""What the eight family files (``tests/test_model_<family>.py``) share: the
helpers each of them typed for itself, an engine built once a (configuration,
keyword arguments) and module, and the plain reference traced once a
sequence length. A helper module: pytest collects nothing here.

A family file keeps its own tests, names, parametrisation and checks. A new
family's file imports these and writes what is its own:

    from family_harness import engine as _engine, rel as _rel, ...

Everything memoised here lives one module: ``tests/conftest.py`` calls
``forget_engines`` at every module's end, before it sheds what the worker
compiled, so no engine outlives its file.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

_ENGINES: dict = {}      # key -> (engine, its params: the key holds their id)
_PROGRAMS: dict = {}     # key -> the reference's forward, jitted
_KEPT: dict = {}         # key -> (one sequence's reference, its params)


def forget_engines() -> None:
    """Empty the three memos (a module's end)."""
    _ENGINES.clear()
    _PROGRAMS.clear()
    _KEPT.clear()


def tokens(n=60, rows=2, seed=1):
    return jax.random.randint(jax.random.key(seed), (rows, n), 0, 384)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def sizes(setup, cfg) -> dict:
    """The published keys the reference reads, off a program config;
    ``setup`` is the family's ``benchmarks.engine_setup_<family>``."""
    return setup.sizes_from(cfg)


def engine(cfg, params, piece_tokens=None, fresh=False, **kw):
    """The family's engine at the tests' defaults: one row, 8 new tokens,
    every kernel interpreted, prefill chunks of 128 and a float cache (int8
    keys and values are a rounding of their own, beside what is compared);
    a keyword replaces a default, ``piece_tokens`` the family's row piece.

    Built ONCE a (configuration, parameters, keyword arguments) and module:
    a test that asks for what another asked for gets the same engine, its
    programs compiled. ``fresh=True`` gives a test an engine of its own — for
    one that changes its engine (``be._pack_group = ...``), patches what the
    engine traces, or asserts ``stats`` that only its own calls may have
    moved; such an assertion failing is how a shared engine shows."""
    from vnsum_tpu.backend.engine import TpuBackend

    kw = {"batch_size": 1, "max_new_tokens": 8, "interpret": True,
          "prefill_chunk_tokens": 128, "quantize_kv": False, **kw}
    try:
        key = None if fresh else (cfg, id(params), piece_tokens,
                                  frozenset(kw.items()))
        if key in _ENGINES:
            return _ENGINES[key][0]
    except TypeError:        # a keyword that does not hash: nothing to share
        key = None
    be = TpuBackend(model_config=cfg, tokenizer="byte", params=params, **kw)
    if piece_tokens is not None:
        be.family = dataclasses.replace(be.family,
                                        prefill_piece_tokens=piece_tokens)
    if key is not None:
        _ENGINES[key] = (be, params)
    return be


def through_the_engine(cfg, params, ids, n, bucket, **kw):
    """``ids[:n]`` as the prompt in ``bucket``, ``ids[n:]`` forced: the
    engine, the scored rows' logits and the state it ends with."""
    be = engine(cfg, params, **kw)
    logits, state = be.prefill_then_decode_logits(
        ids[:n], ids[n:], bucket=bucket, return_state=True)
    return be, logits, state


def picks_agree(state, want, rows: int) -> bool:
    """The routers' picks of the scored rows, every sparse layer, are the
    reference's own (float32 against float32: no tie to break)."""
    mine = np.sort(np.asarray(state["rows"]["picks"])[:, :, 0], -1)
    theirs = np.sort(np.asarray(want["ids"])[:, -rows:], -1).swapaxes(0, 1)
    return bool((mine == theirs).all())


def alone_and_in_a_batch(cfg, params):
    """Two prompts' greedy tokens, kernels interpreted: both rows of one
    batch, and each as the one row of its own."""
    from vnsum_tpu.core.config import GenerationConfig

    gen = GenerationConfig(temperature=0.0)
    prompts = ["xin chào " * 22, "một hai ba"]
    both = engine(cfg, params, batch_size=2, max_new_tokens=6,
                  generation=gen).generate(prompts, max_new_tokens=6)
    alone = [engine(cfg, params, batch_size=1, max_new_tokens=6,
                    generation=gen).generate([p], max_new_tokens=6)[0]
             for p in prompts]
    return both, alone


def reference(module, sizes: dict, **static):
    """``module.forward(params, ids, sizes, **static)`` jitted, float32
    products: one compile a sequence length, where op by op it is minutes
    of a test file. ``static`` is ``last``, ``faults``, ``keep``."""
    key = (module, json.dumps(sizes, sort_keys=True, default=repr),
           tuple(sorted(static.items())))
    if key not in _PROGRAMS:
        def forward(params, ids):
            with jax.default_matmul_precision("highest"):
                return module.forward(params, ids, sizes, **static)

        _PROGRAMS[key] = jax.jit(forward)
    return _PROGRAMS[key]


def reference_of(module, sizes: dict, params, ids, **static):
    """... of one sequence, computed once a module: several tests run the
    same prompt through the engine another way."""
    key = (module, json.dumps(sizes, sort_keys=True, default=repr),
           id(params), tuple(ids), tuple(sorted(static.items())))
    if key not in _KEPT:
        _KEPT[key] = (reference(module, sizes, **static)(
            params, jnp.asarray(ids)), params)
    return _KEPT[key][0]
