"""Hermetic exercise of bench.py's instrumented device-budget phase: the
real run_device_budget flow (two-pass pipeline, split prefill/decode timing,
FLOP + HBM-byte models) on a tiny model and corpus, CPU-only. Guards the
shape of BENCH_r{N}.json's "device_budget" record without TPU hardware."""
import pytest

import bench as bench_mod
from vnsum_tpu.data.synthesize import synthesize_corpus
from vnsum_tpu.models import tiny_llama


@pytest.mark.slow
def test_run_device_budget_tiny(tmp_path, monkeypatch):
    root = str(tmp_path)
    synthesize_corpus(
        f"{root}/corpus", n_docs=2, tokens_per_doc=300, summary_tokens=40,
        seed=3,
    )
    import vnsum_tpu.models as models

    monkeypatch.setattr(
        models, "llama32_3b", lambda **kw: tiny_llama(max_seq_len=512)
    )
    # off-chip: the dense path by name, and the v5e row of the peaks table
    # (bench.py itself refuses both a CPU engine and an unknown device kind)
    e2e_kwargs = bench_mod.e2e_engine_kwargs
    monkeypatch.setattr(
        bench_mod, "e2e_engine_kwargs",
        lambda *a: {**e2e_kwargs(*a), "flash": False},
    )
    monkeypatch.setattr(
        bench_mod, "device_peaks",
        lambda: bench_mod.DEVICE_PEAKS["TPU v5 lite"],
    )
    out = bench_mod.run_device_budget(None, root, "byte", (10,))
    assert out["docs"] == 2 and out["chunks"] >= 2
    # phase totals are rounded to 0.1 s and a tiny model's prefill can land
    # under that on a fast host; the per-dispatch records keep milliseconds
    assert sum(d["prefill_s"] for d in out["dispatches"]) > 0
    assert out["decode_s"] > 0
    assert out["dispatches"] and all(
        d["steps"] <= 128 for d in out["dispatches"]
    )
    assert 0 <= out["mfu_prefill"] < 1.0
    assert out["decode_roofline_frac"] >= 0
    # phase sum cannot exceed the measured wall clock
    assert (
        out["prefill_s"] + out["decode_s"] <= out["wall_s"] + 0.5
    )
