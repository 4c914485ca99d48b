"""The device half of tracing: every program carries its phase and
component scopes, the kernels and the two segment programs keep the names
the device trace and the ledger know them by, and the program hands out a
map from instruction to scope that a stale compile cache cannot spoil
(CPU, tiny model)."""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.profiling import hlo_scope_map
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.models.llama import init_kv_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "scripts"))
sys.path.insert(0, str(ROOT))

import trace_by_scope  # noqa: E402
from benchmarks import trace_reduce  # noqa: E402

B, S, NEW = 4, 64, 8
MODEL = ("qkv", "kv_write", "attn", "attn_out", "mlp", "lm_head", "embed")


def make_backend():
    return TpuBackend(
        model_config=tiny_llama(max_seq_len=128), tokenizer="byte",
        batch_size=B, max_new_tokens=NEW, seed=1, segment_tokens=4,
        flash=False)   # off-chip: the dense path, by name


def paths(scopes: dict, depth: int = 2) -> set[str]:
    return {"/".join(p.split("/")[:depth]) for p in scopes.values()}


@pytest.fixture(scope="module")
def maps():
    """The engine's five kinds of program, built (not run), and their maps
    by kind: the one-shot program, the spec path's split prefill, and the
    slot loop's three."""
    b = make_backend()
    gen = b.gen_cfg
    b._get_fn(B, S, NEW, gen)
    for kind, batch in (("slot_prefill", 2), ("slot_seg", B), ("adopt", 2),
                        ("prefill", B)):
        b._get_seg_fn(kind, batch, S, NEW, gen)
    return {m["program"].split("[")[0]: m for m in b.scope_maps()}


@pytest.mark.parametrize("program, module, phase, components", [
    ("generate", "jit_generate", "prefill", MODEL + ("sample",)),
    ("generate", "jit_generate", "decode", MODEL + ("sample", "emit")),
    ("slot_prefill", "jit_slot_prefill", "prefill", MODEL + ("sample",)),
    ("slot_seg", "jit_segment", "decode", MODEL + ("sample", "emit")),
    ("adopt", "jit_adopt", "adopt", ()),
    ("prefill", "jit_prefill", "prefill", MODEL + ("sample",)),
])
def test_program_carries_its_phase_and_component_scopes(
        maps, program, module, phase, components):
    m = maps[program]
    assert m["module"] == module
    got = paths(m["scopes"])
    assert phase in paths(m["scopes"], 1)
    assert {f"{phase}/{c}" for c in components} <= got
    # a scope names a layer, never a shape: no digit in any phase/component
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]


def test_one_segment_program_and_no_other_kind(maps):
    """The slot loop's segment is ``jit_segment`` (the benchmark's
    ``segment_ms_per_step`` reads it) and nothing else decodes in segments:
    the engine builds no program of the kind the continuous path had."""
    assert maps["slot_seg"]["module"] == "jit_segment"
    assert maps["adopt"]["program"].endswith(f"slots={B}]")
    b = make_backend()
    with pytest.raises(ValueError, match="segment"):
        b._get_seg_fn("segment", B, S, NEW, b.gen_cfg)


def _pallas_names(jaxpr) -> list[str]:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                names += _pallas_names(inner)
    return names


@pytest.mark.parametrize("kernel", [
    "flash_prefill_attention", "flash_decode_attention",
    "flash_spec_verify_attention"])
def test_kernel_name_is_a_contract(kernel):
    """The ``pallas_call`` itself carries the name the ledger's breakdown
    shows, so renaming the wrapper cannot rename a ledger row."""
    from vnsum_tpu.ops import decode_attention, flash_attention

    cfg = tiny_llama(max_seq_len=128)
    cache = init_kv_cache(cfg, 2, 64, quantized=False)
    pads = jnp.zeros((2,), jnp.int32)
    q = lambda s: jnp.zeros((2, s, cfg.n_heads, cfg.head_dim))  # noqa: E731
    call = {
        "flash_prefill_attention": lambda c: flash_attention.
        flash_prefill_attention(q(16), c, 0, pads, cfg.q_per_kv,
                                interpret=True),
        "flash_decode_attention": lambda c: decode_attention.
        flash_decode_attention(q(1), c, 0, pads, 20, cfg.q_per_kv,
                               interpret=True),
        "flash_spec_verify_attention": lambda c: decode_attention.
        flash_spec_verify_attention(q(1), c, 0, pads, pads + 20,
                                    cfg.q_per_kv, interpret=True),
    }[kernel]
    assert _pallas_names(jax.make_jaxpr(call)(cache).jaxpr) == [kernel]


@pytest.mark.parametrize("kernel", ["ssd_prefill_scan", "ssm_decode_update"])
def test_scan_kernel_name_is_a_contract(kernel):
    """The two scan kernels of ``ops/ssd_scan.py``: the benchmark's
    ``ssd_prefill_scan_roofline``, ``ssm_decode_update_roofline`` and
    ``ssm_busy_share`` read the trace by these names."""
    from vnsum_tpu.ops import ssd_scan

    H, P, N = 2, 8, 4
    x = jnp.zeros((2, 8, H, P))
    dt = jnp.ones((2, 8, H))
    A, D, bc = -jnp.ones((H,)), jnp.ones((H,)), jnp.zeros((2, 8, N))
    call = {
        "ssd_prefill_scan": lambda st: ssd_scan.ssd_prefill_scan(
            x, dt, A, bc, bc, D, st, 0, jnp.zeros((2,), jnp.int32), chunk=4,
            interpret=True),
        "ssm_decode_update": lambda st: ssd_scan.ssm_decode_update(
            x[:, 0], dt[:, 0], A, bc[:, 0], bc[:, 0], D, st, 0,
            interpret=True),
    }[kernel]
    state = jnp.zeros((1, 2, N, H * P))
    assert _pallas_names(jax.make_jaxpr(call)(state).jaxpr) == [kernel]


def test_a_recurrent_familys_program_carries_its_component_scopes():
    """``ssm_in``, ``conv``, ``ssd`` and ``ssm_out`` under both phases of
    the one-shot program of ``models/granite_hybrid.py``, beside the
    attention layers' and the feed-forward's: ``scope_maps()`` books the
    family with no edit (README, "Device time by layer")."""
    from vnsum_tpu.models.granite_hybrid import init_params, tiny_granite_h

    cfg = tiny_granite_h(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in MODEL + (
            "ssm_in", "conv", "ssd", "ssm_out", "sample")} <= got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]


def test_a_one_mixer_familys_program_carries_its_component_scopes():
    """Every scope ``models/nemotron_h.py`` adds, under both phases of its
    one-shot program: the shared mixer's four, the attention layers', the
    router, the routed experts and the shared expert — no ``mlp``: no layer
    has a dense feed-forward."""
    from vnsum_tpu.models.nemotron_h import init_params, tiny_nemotron_h

    cfg = tiny_nemotron_h(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in (
            "ssm_in", "conv", "ssd", "ssm_out", "qkv", "kv_write", "attn",
            "attn_out", "router", "experts", "shared_expert", "embed",
            "lm_head", "sample")} <= got
        assert f"{phase}/mlp" not in got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]


def test_a_short_convolution_familys_program_carries_its_component_scopes():
    """Every scope ``models/lfm2.py`` adds, under both phases of its
    one-shot program: the convolution operator's three (``shortconv_in``,
    ``shortconv``, ``shortconv_out``), the attention layers', ``mlp`` on the
    two dense layers, the router and the routed experts; and every
    instruction the map places lies under a phase and a component — no
    fusion of the layer stack falls beside the scopes."""
    from vnsum_tpu.models.lfm2 import init_params, tiny_lfm2

    cfg = tiny_lfm2(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    components = ("shortconv_in", "shortconv", "shortconv_out", "qkv",
                  "kv_write", "attn", "attn_out", "mlp", "router", "experts",
                  "embed", "lm_head", "sample")
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in components} <= got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]
    # what is under a phase is under one of the components, or is the
    # phase's own glue (masks, positions, the loop): nothing else is named
    named = {p.split("/")[1] for p in got if p.count("/") >= 1}
    assert named <= set(components) | {"emit"}, named - set(components)


def test_a_delta_rule_familys_program_carries_its_component_scopes():
    """Every scope ``models/ling.py`` adds, under both phases of its
    one-shot program: the KDA mixer's four (``kda_in``, ``kda_conv``,
    ``kda_scan``, ``kda_out``), the MLA layers' (``kv_latent``,
    ``kv_write``, ``attn_gate``, ``q_lora`` — the name
    ``models/deepseek.py`` projects its queries under, here the whole query
    projection —, ``attn``, ``attn_out``), ``mlp`` on the dense layer, the
    router, the routed experts and the shared expert; and every instruction
    the map places lies under a phase and a component."""
    from vnsum_tpu.models.ling import init_params, tiny_ling

    cfg = tiny_ling(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    components = ("kda_in", "kda_conv", "kda_scan", "kda_out", "kv_latent",
                  "kv_write", "attn_gate", "q_lora", "attn", "attn_out",
                  "mlp", "router", "experts", "shared_experts", "embed",
                  "lm_head", "sample")
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in components} <= got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]
    # ... or the phase's own glue: ``emit``, and the decode phase's scans of
    # the layer stack themselves (``add;while``: a run's loop with its
    # residual add, the compiler's name for a call it closed over)
    named = {p.split("/")[1] for p in got if p.count("/") >= 1}
    assert named <= set(components) | {"emit", "add;while"}, \
        named - set(components)


def test_a_retention_familys_program_carries_its_component_scopes():
    """Every scope ``models/brumby.py`` adds, under the phase it belongs to:
    ``ret_in`` (the four projections, QK-norm, rotary, the gate) and
    ``ret_out`` under both, ``ret_scan`` under the prefill and
    ``ret_update`` under the decode loop, beside the skeleton's names for
    the feed-forward and the head; NO attention scope in either phase; and
    every instruction the map places lies under a phase and a component."""
    from vnsum_tpu.models.brumby import init_params, tiny_brumby

    cfg = tiny_brumby(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    both = ("ret_in", "ret_out", "mlp", "embed", "lm_head", "sample")
    assert {f"prefill/{c}" for c in both + ("ret_scan",)} <= got
    assert {f"decode/{c}" for c in both + ("ret_update",)} <= got
    assert "prefill/ret_update" not in got and "decode/ret_scan" not in got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]
    named = {p.split("/")[1] for p in got if p.count("/") >= 1}
    assert named <= set(both) | {"ret_scan", "ret_update", "emit"}, named
    assert not named & {"qkv", "kv_write", "attn", "attn_out"}


def test_a_sparse_attention_familys_program_carries_its_component_scopes():
    """Every scope ``models/keye.py`` adds, under BOTH phases: ``dsa_in``
    (the indexer's three projections, its LayerNorm and rotary, the write
    of the indexer-key cache) and ``dsa_select`` (index scores and the exact
    top-k), beside the skeleton's ``qkv``, ``kv_write``, ``attn`` (here the
    attention over the selection), ``attn_out``, and the expert layer's
    ``router`` and ``experts``; and every instruction the map places lies
    under a phase and a component."""
    from vnsum_tpu.models.keye import init_params, tiny_keye

    cfg = tiny_keye(max_seq_len=128)
    b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                   max_new_tokens=NEW, seed=1, flash=False,
                   params=init_params(jax.random.key(0), cfg))
    b._get_fn(B, S, NEW, b.gen_cfg)
    (m,) = b.scope_maps()
    assert m["module"] == "jit_generate"
    got = paths(m["scopes"])
    both = ("dsa_in", "dsa_select", "attn", "experts", "router", "qkv",
            "kv_write", "attn_out", "embed", "lm_head", "sample")
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in both} <= got, phase
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]
    named = {p.split("/")[1] for p in got if p.count("/") >= 1}
    assert named <= set(both) | {"emit"}, named
    assert "mlp" not in named and "shared_experts" not in named


def test_a_looped_stacks_program_carries_the_norm_between_passes():
    """A stack looped over its weights (``LlamaConfig.loop_passes``) adds
    ONE scope to the dense family's, under both phases: ``loop_norm``, the
    final norm after every pass. The pass is in no name, and a plain stack's
    program has no such scope."""
    from vnsum_tpu.models import tiny_ouro

    def scopes(cfg):
        b = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                       max_new_tokens=NEW, seed=1, flash=False)
        b._get_fn(B, S, NEW, b.gen_cfg)
        (m,) = b.scope_maps()
        assert m["module"] == "jit_generate"
        return paths(m["scopes"])

    got = scopes(tiny_ouro(max_seq_len=128))
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in MODEL + ("loop_norm", "sample")} <= got
    assert not [p for p in got if p and any(ch.isdigit() for ch in p)]
    assert not [p for p in scopes(tiny_llama(max_seq_len=128))
                if "loop_norm" in p]


def test_a_one_mixer_familys_kernels_keep_their_contract_names():
    """The tiny Nemotron-H program with every kernel on calls the six
    kernels by the names the benchmark's metrics read, and no other."""
    import re

    from vnsum_tpu.models.nemotron_h import tiny_nemotron_h

    b = TpuBackend(model_config=tiny_nemotron_h(max_seq_len=256),
                   tokenizer="byte", batch_size=2, max_new_tokens=NEW,
                   interpret=True, quantize=True, prefill_chunk_tokens=128)
    fn = b._make_fn(2, 128, NEW, b.gen_cfg)
    text = str(jax.make_jaxpr(fn)(
        jax.eval_shape(lambda: b.params),
        jax.ShapeDtypeStruct((2, 128), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.uint32)))
    # every name a ``pallas_call`` of ops/ carries is a contract
    contracted = set()
    for src in (ROOT / "vnsum_tpu" / "ops").glob("*.py"):
        contracted |= set(re.findall(r'^\s+name="(\w+)",$', src.read_text(),
                                     re.M))
    kernels = set(re.findall(r"name=(\w+)", text)) & contracted
    assert kernels == {"ssd_prefill_scan", "ssm_decode_update",
                       "flash_prefill_attention", "flash_decode_attention",
                       "expert_grouped_matmul"}, kernels


@pytest.mark.parametrize("line, name, scope", [
    ('  %dot.5 = f32[8,64]{1,0} dot(%a, %b), metadata={op_name='
     '"jit(generate)/decode/while/body/mlp/bsd,di->bsi/dot_general" '
     'stack_frame_id=3}', "dot.5", "decode/mlp/bsd,di->bsi"),
    ('  ROOT %fusion.989 = bf16[8,1,4096]{2,1,0} fusion(%p.1), kind=kOutput, '
     'calls=%fused_computation.7, metadata={op_name="jit(generate)/prefill/'
     'while/body/closed_call/attn/jit(flash_prefill_attention)/'
     'flash_prefill_attention/pallas_call"}, backend_config={"metadata={}"}',
     "fusion.989", "prefill/attn/flash_prefill_attention"),
    ("  %copy.2 = f32[2]{0} copy(%x)", "copy.2", ""),
])
def test_hlo_scope_map_line(line, name, scope):
    text = "HloModule jit_generate, is_scheduled=true\n\nENTRY %main {\n" \
        + line + "\n}\n"
    assert hlo_scope_map(text) == {name: scope}


def test_hlo_scope_map_fusion_without_metadata_takes_its_bodys_scope():
    """A multi-output fusion's root is the compiler's tuple, so the fusion
    has no metadata of its own (the rope fusions of the chip's programs)."""
    text = """HloModule jit_generate

%fused_computation.216 (p.1: bf16[8,64]) -> (bf16[8,32], bf16[8,32]) {
  %p.1 = bf16[8,64]{1,0} parameter(0)
  %slice.347 = bf16[8,32]{1,0} slice(%p.1), slice={[0:8], [0:32]}, metadata={op_name="jit(generate)/prefill/while/body/qkv/slice"}
  %slice.346 = bf16[8,32]{1,0} slice(%p.1), slice={[0:8], [32:64]}, metadata={op_name="jit(generate)/prefill/while/body/qkv/slice"}
  %neg.1 = bf16[8,32]{1,0} negate(%slice.346), metadata={op_name="jit(generate)/prefill/neg"}
  ROOT %tuple.364 = (bf16[8,32]{1,0}, bf16[8,32]{1,0}) tuple(%slice.347, %neg.1)
}

ENTRY %main (x: bf16[8,64]) -> (bf16[8,32], bf16[8,32]) {
  %x = bf16[8,64]{1,0} parameter(0)
  ROOT %fusion.870 = (bf16[8,32]{1,0}, bf16[8,32]{1,0}) fusion(%x), kind=kLoop, calls=%fused_computation.216
}
"""
    scopes = hlo_scope_map(text)
    assert scopes["fusion.870"] == "prefill/qkv"
    assert scopes["tuple.364"] == "" and scopes["x"] == ""


def test_scope_maps_ignore_a_stale_compile_cache(tmp_path, monkeypatch):
    """The persistent cache's key leaves metadata out: an executable compiled
    from a source without scopes answers for the same program with them. The
    map is made from a compile that did not come out of that cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    # the variable set too: ``core.jax_cache`` then leaves the directory
    # alone when the backends below are built, and the checkout's own
    # cache, which holds this program WITH its scopes, answers nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    args = (jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32), 0)

    def plain_text(b):   # as any caller compiles: through the cache
        fn = b._make_fn(B, S, NEW, b.gen_cfg)
        return fn.lower(b.params, *args).compile().as_text()

    try:
        with monkeypatch.context() as m:   # the older source: no scopes
            m.setattr(jax, "named_scope",
                      lambda _name: contextlib.nullcontext())
            old = plain_text(make_backend())
        assert "prefill/mlp" not in paths(hlo_scope_map(old))
        b = make_backend()
        # the trap this test is about; if it stops holding, the bypass in
        # scope_maps can go
        assert "prefill/mlp" not in paths(hlo_scope_map(plain_text(b)))
        b._get_fn(B, S, NEW, b.gen_cfg)
        (fresh,) = b.scope_maps()
        assert {"prefill/mlp", "decode/attn"} <= paths(fresh["scopes"])
        assert fresh["compile_s"] > 0
        # and the cache is back on afterwards
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


FIXTURE = ROOT / "benchmarks" / "fixtures" / "small_trace.xplane.pb"
FIXTURE_MAPS = [
    {"program": "fixture[other bucket]", "module": "jit_fixture_step",
     "scopes": {"fusion.8": "wrong"}},
    {"program": "fixture", "module": "jit_fixture_step",
     "scopes": {"fusion.8": "decode/mlp/deeper", "copy.11": "decode",
                "while": "decode", "copy-done.1": ""}},
]


def test_trace_by_scope_adds_up_to_the_reducers_self_time():
    planes = trace_reduce.read_planes(str(FIXTURE))
    reduced = trace_reduce.reduce_planes(planes, top=1000)
    self_s = sum(s for _n, s in reduced["device_ops"])
    r = trace_by_scope.by_scope(planes, FIXTURE_MAPS, depth=2)
    scopes = dict(r["by_scope"])
    assert sum(scopes.values()) == pytest.approx(self_s)
    assert r["self_s"] == pytest.approx(self_s)
    assert r["busy_s"] == pytest.approx(reduced["busy_s"])
    assert scopes["decode/mlp"] == pytest.approx(
        dict(reduced["device_ops"])["fusion.8 bf16[1024,1024]"])
    # the map that knows most of the module's instructions was taken
    (module,) = r["modules"].values()
    assert module["program"] == "fixture"
    assert module["in_map"] == 4 and module["instructions"] > 4
    # what has no scope is reported, by module and by operation
    rest = scopes[trace_by_scope.NO_SCOPE] + scopes[trace_by_scope.NOT_IN_MAP]
    assert 0 < rest < 0.1 * self_s
    assert r["scoped_share"] == pytest.approx(1 - rest / self_s)
    (unscoped,) = r["unscoped"].values()
    assert unscoped["seconds"] == pytest.approx(rest)
    assert any(n.startswith("copy-done") for n, _s in unscoped["ops"])
    assert dict(trace_by_scope.by_scope(planes, FIXTURE_MAPS, depth=1)[
        "by_scope"])["decode"] == pytest.approx(
            scopes["decode/mlp"] + scopes["decode"])
    text = trace_by_scope.render(r)
    assert "decode/mlp" in text and "under no scope in jit_fixture_step" in text


def test_trace_by_scope_without_a_map_says_so():
    planes = trace_reduce.read_planes(str(FIXTURE))
    r = trace_by_scope.by_scope(planes, [], depth=2)
    assert [p for p, _s in r["by_scope"]] == [trace_by_scope.NO_MAP]
    assert r["scoped_share"] == pytest.approx(0.0)
