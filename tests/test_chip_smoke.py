"""chip_smoke.py off the chip: it must fail, say which platform it found, and
print no result — and nothing else may time or serve from the CPU either
(TpuBackend at defaults, a tpu worker's environment, a fleet of tpu workers
on one chip)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(cmd, **kw):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=300, **kw
    )


def test_chip_smoke_fails_on_cpu_and_names_the_platform(tmp_path):
    proc = _run([sys.executable, str(REPO / "chip_smoke.py"),
                 "--out", str(tmp_path / "report.json")])
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    # no result line: the last line of stdout is not an {"ok": ...} object
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_is_not_the_program(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    proc = _run([sys.executable, str(lone)], cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _chip_smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


def test_parent_module_imports_without_jax():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "assert 'jax' not in sys.modules, 'parent imported jax'; "
        "assert not any(m.startswith('vnsum_tpu') for m in sys.modules)"
        % str(REPO)
    )
    proc = _run([sys.executable, "-c", code])
    assert proc.returncode == 0, proc.stderr


def test_result_line_has_exactly_the_contract_keys():
    chip_smoke = _chip_smoke()
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    failed = json.loads(chip_smoke.result_line(False, {}))
    assert set(failed) == {"ok", "device"} and failed["ok"] is False
    assert set(failed["device"]) == {"platform", "kind", "count"}
    assert isinstance(failed["device"]["count"], int)


def test_pick_ragged_eos_ends_some_rows_and_not_all_before_the_budget():
    from vnsum_tpu.text.tokenizer import ByteTokenizer

    chip_smoke = _chip_smoke()
    tok = ByteTokenizer()
    # 13 "e" in four rows and none in the third, against 26 "x", 5 "a",
    # 5 "y", 1 "z": the recipe wants ~3 occurrences a row
    outs = ["xaxexexexx" * 2, "xeyeyeaeyy", "xaxxxxxxxx", "xezeyeaxxx", ""]
    (eos,) = chip_smoke._pick_ragged_eos(outs, tok, budget=20)
    assert eos == tok.encode("e")[0]
    rows = [tok.encode(o) for o in outs if o]
    ended = [eos in r[:20] for r in rows]
    assert any(ended) and not all(ended)
    assert chip_smoke._pick_ragged_eos([], tok) == (10,)


def test_e2e_engine_kwargs_are_all_engine_parameters():
    import inspect

    from vnsum_tpu.backend.engine import TpuBackend

    kwargs = _chip_smoke().e2e_engine_kwargs("byte")
    accepted = set(inspect.signature(TpuBackend.__init__).parameters)
    assert set(kwargs) <= accepted - {"self"}
    assert kwargs["tokenizer"] == "byte" and kwargs["batch_size"] == 16
    assert kwargs["model_config"].max_seq_len == 8448


def test_tpu_backend_refuses_the_cpu_unless_told_how_to_run():
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import tiny_llama

    cfg = tiny_llama()
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        TpuBackend(model_config=cfg, max_new_tokens=4)  # flash="auto"
    emulated = TpuBackend(model_config=cfg, max_new_tokens=4, interpret=True)
    assert emulated.flash and emulated.platform == "cpu"
    dense = TpuBackend(model_config=cfg, max_new_tokens=4, flash=False)
    assert not dense.flash
    dense.generate(["xin chào"])
    assert dense.stats.attention_paths == {
        "generate[B=1,S=64]": {"prefill": "dense", "decode": "dense"}
    }
    assert dense.describe()["platform"] == "cpu"


def test_worker_keeps_the_cpu_default_only_off_the_tpu_backend(monkeypatch):
    from vnsum_tpu.serve import worker

    seen = {}

    class FakePopen:
        pid = 1

        def __init__(self, argv, env, **kw):
            seen["env"] = env

    monkeypatch.setattr(worker.subprocess, "Popen", FakePopen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    worker.WorkerHandle("w", 1, journal_dir="j",
                        extra_args=["--backend", "fake"]).start()
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    worker.WorkerHandle("w", 1, journal_dir="j",
                        extra_args=["--backend", "tpu"]).start()
    assert "JAX_PLATFORMS" not in seen["env"]


def test_router_refuses_to_spawn_tpu_workers_onto_one_chip(tmp_path, capsys):
    from vnsum_tpu.serve import router

    with pytest.raises(SystemExit):
        router.main(["--spawn-workers", "2", "--backend", "tpu",
                     "--fleet-dir", str(tmp_path)])
    assert "a chip belongs to one process" in capsys.readouterr().err
