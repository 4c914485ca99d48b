"""The parent's side of the one child that holds the chip.

The parent never imports JAX. It writes the cell's files and arguments to
``spec.json``, starts ``run.py --child spec.json`` in a session of its own,
sends it one-line commands on its standard input where a driver needs
that, and reads ``raw.json`` when it has ended. The child's output goes to
the parent's standard error, so the result line stays the last of stdout.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"
CHILD_TIMEOUT_S = 1150.0   # a cold first run may take 1200 s in all


class ChildFailed(RuntimeError):
    pass


class Child:
    def __init__(self, ctx: dict) -> None:
        work = Path(ctx["work_dir"])
        self.raw_path = work / "raw.json"
        spec = work / "spec.json"
        spec.write_text(json.dumps(ctx))
        self.proc = subprocess.Popen(
            [sys.executable, str(RUN), "--child", str(spec)],
            stdin=subprocess.PIPE, stdout=sys.stderr, stderr=sys.stderr,
            text=True, start_new_session=True)

    def send(self, command: str) -> None:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait(self, timeout: float = CHILD_TIMEOUT_S) -> dict:
        """Wait for the child to end; its raw record, or ChildFailed."""
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise ChildFailed(f"child still running after {timeout:.0f} s")
        if rc != 0 or not self.raw_path.is_file():
            raise ChildFailed(f"child exited {rc}")
        return json.loads(self.raw_path.read_text())

    def kill(self) -> None:
        """End the child and everything it started, and wait for it."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        if self.proc.stdin:
            self.proc.stdin.close()


def run_to_end(ctx: dict) -> dict:
    """A child that needs no word from the parent: start it, wait, read."""
    child = Child(ctx)
    try:
        return child.wait()
    finally:
        child.kill()
