"""chip_smoke.py's control flow, rehearsed on CPU.

The script's real subject is the chip; what the CPU suite can hold it to
is everything around that: the rehearsal walks the same phases (device
report, kernels, train job and service through the orchestrator, requests
through the proxy) at `tiny`, the parent never imports JAX, and without
the rehearsal flag a machine without a chip is a failure, not a CPU run.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def test_real_and_rehearsal_sizes_differ_only_in_values():
    """The rehearsal can only rehearse the keys the chip run will read
    (a key missing from the real table cost a four-chip run in PR 21)."""
    sys.path.insert(0, str(REPO))
    import chip_smoke

    assert chip_smoke.sizes(True).keys() == chip_smoke.sizes(False).keys()
    assert chip_smoke.first_divergence("abc", "abc") is None
    assert chip_smoke.first_divergence("abc", "abd") == 2
    assert chip_smoke.first_divergence("ab", "abc") == 2


def _start(*flags):
    env = dict(os.environ)
    # One CPU device: the suite's 8 virtual devices would add the sharded
    # phases (covered by running the rehearsal by hand with 4).
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, str(REPO / "chip_smoke.py"), *flags], env=env,
        cwd=REPO, text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )


@pytest.fixture(scope="module")
def runs():
    """Both invocations at once (they share nothing but the compile
    cache): {"rehearsal" | "no_chip": (returncode, stdout, stderr)}."""
    procs = {"rehearsal": _start("--rehearsal"), "no_chip": _start()}
    out = {}
    try:
        for name, proc in procs.items():
            stdout, stderr = proc.communicate(timeout=600)
            out[name] = (proc.returncode, stdout, stderr)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    return out


def test_rehearsal_passes_and_parent_stays_off_jax(runs):
    rc, stdout, stderr = runs["rehearsal"]
    assert rc == 0, stdout[-2000:] + stderr[-3000:]
    lines = stdout.splitlines()
    assert lines[0].startswith("REHEARSAL"), lines[0]
    # Last line: exactly the contract's object, nothing beside it.
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("SUMMARY ")
    summary = json.loads(lines[-2][len("SUMMARY "):])
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"] == device
    assert summary["parent_imported_jax"] is False
    assert list(summary["phases"]) == ["kernels", "train", "serve"]
    assert summary["phases"]["serve"]["compiles_after_ready"] == 0
    assert summary["phases"]["serve"]["weights_via"] != "init"
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_without_a_chip_it_fails_with_a_message(runs):
    rc, stdout, stderr = runs["no_chip"]
    assert rc != 0
    assert "chip_smoke FAILED" in stderr
    assert "Unable to initialize backend 'tpu'" in stderr
    # No result: nothing on stdout parses as the success object.
    assert not any(l.startswith("{") for l in stdout.splitlines())
