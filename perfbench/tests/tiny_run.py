#!/usr/bin/env python3
"""One benchmark run of a small cell on the CPU, past the harness's
look for a chip, with the timed path optionally broken underneath.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python perfbench/tests/tiny_run.py --devices 4 --traffic burst \
        --fault exchange_left_out

Faults (planted in the program's classes for this process only):
  state_unchanged    every attention step returns its KV pool unchanged
  token_altered      every token is altered where a step produces it
  exchange_left_out  the TP group's all-reduce is dropped (needs TP)

Prints the run's result line as its last line, with the result line of
the fp8 control judged in the program's place (on the same sample)
under ``control``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.time()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "perfbench"))
sys.path.insert(0, str(ROOT / "src"))

# a small dense GQA decoder with qk-norm: the code paths of both
# configurations at a size the CPU serves in seconds
CONFIG = {"name": "tiny", "source": "test", "hidden_size": 64,
          "intermediate_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
          "vocab_size": 256, "max_position_embeddings": 4096,
          "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "qk_norm": True,
          "tie_word_embeddings": False,
          # sound runs of this size read at most 0.02 on the CPU and the
          # fp8 control 0.1 or more (tests/test_perfbench_control.py)
          "correct": {"served_logit_gap": 0.05}}
CHAT = {"prompt": {"median": 40, "sigma": 0.8, "lo": 16, "hi": 200},
        "output": {"median": 8, "sigma": 0.8, "lo": 4, "hi": 24}}
TRAFFIC = {
    "chat": {"lead_s": 1.0, "tail_s": 0.0, "drain_s": 60.0,
             "warm_s": 1.0, "warm_quiet_s": 0.5,
             "serve": {"strategy": "hard"},
             "streams": [dict(CHAT, tier="standard", arrival="poisson",
                              rate=4.0)]},
    "burst": {"lead_s": 1.0, "tail_s": 0.0, "drain_s": 60.0,
              "warm_s": 1.0, "warm_quiet_s": 0.5,
              "serve": {"strategy": "live", "live_policy": True},
              "streams": [dict(CHAT, tier="standard", arrival="poisson",
                               rate=4.0),
                          dict(CHAT, tier="priority", arrival="bursts",
                               size=6, spread_s=0.3, period_s=3.0,
                               first_s=0.5, until_s=3.0)]},
}


def plant(fault: str) -> None:
    from repro.core import engine, views
    from repro.models import cache
    if fault == "state_unchanged":
        for cls in (cache.DecodeBackend, cache.PrefillBackend):
            orig = cls.attend

            def attend(self, state, *a, _orig=orig, **kw):
                out, _new = _orig(self, state, *a, **kw)
                return out, state
            cls.attend = attend
    elif fault == "token_altered":
        orig = engine.FlyingEngine._note_tokens

        def note(self, rt, key, toks, row_reqs):
            if isinstance(toks, tuple):
                toks = tuple((t + 1) % CONFIG["vocab_size"] for t in toks)
            else:
                toks = (toks + 1) % CONFIG["vocab_size"]
            return orig(self, rt, key, toks, row_reqs)
        engine.FlyingEngine._note_tokens = note
    elif fault == "exchange_left_out":
        views.TPContext.psum = lambda self, x, n=0: x
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--traffic", default="chat")
    ap.add_argument("--fault", default="none")
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    import jax
    from harness import cell as cm
    from harness import correct
    from harness import weights as wts
    from harness.spec import Cell
    assert len(jax.devices()) == args.devices, jax.devices()
    plant(args.fault)
    c = Cell(name=f"tiny.{args.traffic}", config="tiny",
             traffic=args.traffic, chips=args.devices, why="test",
             config_data=CONFIG, traffic_data=TRAFFIC[args.traffic])
    out = cm.run(c, args.seed, args.seconds, False, "/nonexistent", T0)
    line = cm.result(c, out, False, jax.devices())
    sample = correct.pick(out["rec"].records, args.seed,
                          must=cm.must_cover(out["rec"]))
    # the KV pools are freed; the weights are still the benchmark's
    ctl = correct.compare(wts.named(out["params"]), CONFIG, sample,
                          CONFIG["vocab_size"], control=True)
    line["control"] = cm.result(c, dict(out, cmp=cm.as_control(ctl)),
                                False, jax.devices())
    line["rebinds"] = len(out["rec"].rebinds)
    line["switches"] = out["door"].sched.switches
    line["layout"] = out["door"].sched.layout.describe()
    line["priority_done"] = sum(
        r.state == "done" for r in out["rec"].in_window
        if r.plan.tier == "priority")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
