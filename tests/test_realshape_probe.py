"""Hermetic exercise of scripts/make_multimodel_artifact.probe_real_shape:
the (B, S) ladder must return a perf row for the first shape that runs and
record the failure trail for shapes that don't — the OOM boundary is data
(VERDICT r3 #3), so the recording logic needs CI coverage without a chip."""
import importlib.util
import pathlib

import pytest

from vnsum_tpu.models import tiny_llama

_SCRIPT = (
    pathlib.Path(__file__).resolve().parent.parent
    / "scripts" / "make_multimodel_artifact.py"
)
spec = importlib.util.spec_from_file_location("make_multimodel", _SCRIPT)
mm = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mm)


@pytest.mark.slow
def test_probe_real_shape_success_row():
    row = mm.probe_real_shape(
        "tiny", lambda **kw: tiny_llama(**kw), ladder=[(2, 256)], max_new=8,
        flash=False,
    )
    assert row["status"] == "success"
    assert row["B"] == 2 and row["S"] == 256 and row["layers"] == 2
    assert row["weight_bytes"] > 0
    # prefill_s is rounded to 2 decimals and can legitimately be 0.0 for a
    # tiny model on a fast host — assert presence, not magnitude
    assert row["decode_steps"] > 0 and row["prefill_s"] >= 0
    assert row["prefill_tokens_per_sec"] >= 0
    assert row["attempts"] == []


@pytest.mark.slow
def test_probe_real_shape_ladder_steps_down_and_records_failures():
    def factory(**kw):
        # max_seq_len = S + 2*max_new; the first ladder entry asks for a
        # sequence the config cannot hold -> constructor raises, the probe
        # must record it and step down
        cfg = tiny_llama(**kw)
        if cfg.max_seq_len > 300:
            raise RuntimeError("synthetic OOM for the big shape")
        return cfg

    row = mm.probe_real_shape(
        "tiny", factory, ladder=[(4, 1024), (2, 256)], max_new=8,
        flash=False,
    )
    assert row["status"] == "success" and row["B"] == 2
    assert len(row["attempts"]) == 1
    assert row["attempts"][0]["B"] == 4
    assert "synthetic OOM" in row["attempts"][0]["error"]


@pytest.mark.slow
def test_probe_real_shape_did_not_fit():
    def factory(**kw):
        raise RuntimeError("nothing fits")

    row = mm.probe_real_shape(
        "tiny", factory, ladder=[(2, 256), (1, 128)], max_new=8
    )
    assert row["status"] == "did_not_fit"
    assert len(row["attempts"]) == 2
