#!/usr/bin/env python3
"""The on-chip benchmark of the served HTTP path.

    python3 perfbench/run.py --workload stablelm-1.6b.chat --seed 7 \
        --seconds 51 --trace 0

Run from the root of a checkout. Builds the cell named in
``BENCHMARK.json`` (configuration and traffic found by name under
``perfbench/``), serves the cell's traffic over loopback HTTP for
``--seconds`` after a warm-up, checks what was served against the plain
reference, and prints as its last line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics from a profiler trace of
the window), ``device``, ``breakdown`` (traced runs) and ``compared``
(each number compared, with its limit). The numbers compared are also
the last lines on standard error. ``--control 1`` judges the fp8
control in the program's place; that run must read ``correct`` false.

Refuses to run (non-zero exit, no result line) without a TPU, or with
fewer chips than the cell asks for. Everything it writes stays inside
the checkout: JAX's compilation cache in ``.jax_cache/`` and traces in
``.perfbench/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = float(os.environ.get("PERFBENCH_T0", time.time()))
HERE = Path(__file__).resolve().parent


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the fp8 control in the program's place "
                    "(it must come out not correct)")
    return ap.parse_args(argv)


def _pin_hash_seed(argv, seed: int) -> None:
    """The program draws each request's prompt from ``hash(req_id)``;
    fixing Python's hash seed from ``--seed`` makes the prompts follow
    the seed. Re-executes this process (no child) when it is not set."""
    want = str(int(seed) % (1 << 32))
    if os.environ.get("PYTHONHASHSEED") != want:
        env = dict(os.environ, PYTHONHASHSEED=want,
                   PERFBENCH_T0=repr(T_START))
        os.execve(sys.executable,
                  [sys.executable, str(Path(__file__).resolve())] + argv,
                  env)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = _args(argv)
    _pin_hash_seed(argv, args.seed)
    root = Path.cwd()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(root / "src"))
    from harness import cell as cellmod
    from harness.spec import SpecError, load_benchmark, resolve_cell
    try:
        cell = resolve_cell(load_benchmark(root), args.workload)
    except (SpecError, KeyError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    cache = root / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"perfbench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    out_dir = root / ".perfbench" / cell.name
    out = cellmod.run(cell, args.seed, args.seconds, bool(args.trace),
                      str(out_dir), T_START, control=bool(args.control))
    print(json.dumps(cellmod.result(cell, out, args.trace, devs)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
