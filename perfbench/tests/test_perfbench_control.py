"""The check that decides ``correct`` fails where it must.

Each case drives a whole benchmark run of a small cell on the CPU
(``tiny_run.py``: the real served stack over HTTP, past the harness's
look for a chip) in a process of its own, with the timed path broken
underneath where the case says, and reads the result line:

* a sound run is correct, on one device and on four with LIVE rebinds
  to TP islands;
* the fp8 control, judged in the program's place on the sound run's
  sample through the harness's own result, is not correct;
* a step that returns its KV state unchanged, a token altered where it
  is produced, and (on four devices) the TP group's all-reduce left out
  each make ``correct`` false.

(A served batch has no mean over its rows, so the training fault "half
of the batch left out" has no counterpart here.)
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TINY = Path(__file__).resolve().parent / "tiny_run.py"
_runs = {}


def run_tiny(devices: int, traffic: str, fault: str) -> dict:
    key = (devices, traffic, fault)
    if key not in _runs:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count="
                             f"{devices}")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        p = subprocess.run([sys.executable, str(TINY), "--devices",
                            str(devices), "--traffic", traffic, "--fault",
                            fault], env=env, capture_output=True,
                           text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        _runs[key] = json.loads(p.stdout.strip().splitlines()[-1])
    return _runs[key]


@pytest.mark.parametrize("devices,traffic", [(1, "chat"), (4, "burst")])
def test_a_sound_run_is_correct(devices, traffic):
    r = run_tiny(devices, traffic, "none")
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    gap = r["compared"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]
    if devices == 4:
        assert r["switches"] >= 1 and r["priority_done"] > 0


@pytest.mark.parametrize("devices,traffic", [(1, "chat"), (4, "burst")])
def test_the_fp8_control_is_not_correct(devices, traffic):
    r = run_tiny(devices, traffic, "none")["control"]
    assert r["correct"] is False
    gap = r["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("devices,traffic,fault", [
    (1, "chat", "state_unchanged"), (1, "chat", "token_altered"),
    (4, "burst", "exchange_left_out")])
def test_a_broken_timed_path_is_not_correct(devices, traffic, fault):
    r = run_tiny(devices, traffic, fault)
    assert r["correct"] is False
    gap = r["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
