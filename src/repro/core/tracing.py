"""Host spans of the serving program, on the profiler's clock.

``span(name, **args)`` marks one stretch of host work. While spans are
off it returns one shared null context: no span object is built, no
clock is read and no argument is formatted. While they are on it is a
``jax.profiler.TraceAnnotation``, so the span lands in the profiler's
own ``.xplane.pb`` on the thread that ran it, on the same clock as the
device planes: an idle gap on the device can be put down to the host
span around it. The profiler holds the spans and writes them out when
a trace stops; nothing else keeps them.

Spans are on exactly while a profiler session records in this process:
a ``jax.profiler.start_trace``, or a capture through the profiler
server that ``--profile-port`` starts.

An argument that costs something to build (a per-row list) is passed
as a callable, called only while spans are on. The profiler splits an
annotation's arguments on ``,`` and ``#``: values never carry either
(``rows`` joins a list with ``:`` between rows and ``/`` inside one).

Spans the program emits (nesting is containment on the serve thread; a
span never crosses an ``await``):

  fs.tick        FrontDoor.tick                    tick, running, queued
  fs.policy      DynamicScheduler.step: decide + transition      layout
  fs.rebind      FlyingEngine.rebind                           frm, to
  fs.step        FlyingEngine.decode/prefill/mixed   phase, step,
                 island (start/n/merge), rids, dec (lead/ctx per decode
                 row), pre (lead/prior/chunk/final per prefill chunk)
  fs.launch      each step program call                        program
  fs.wait        a window wait (block_until_ready)
  fs.harvest     FlyingEngine._harvest                           steps
  fs.drain       FlyingEngine.drain and a rebind's drains      islands
  fs.pump        AsyncServeLoop._pump                          streams
  fs.http.write  one SSE event serialised and written
"""
from __future__ import annotations

import contextlib
from typing import Iterable

from jax.profiler import TraceAnnotation

_NULL = contextlib.nullcontext()
_CLEAN = str.maketrans({",": ";", "#": "_"})


def span(name: str, **args):
    if not TraceAnnotation.is_enabled():
        return _NULL
    return TraceAnnotation(name, **{
        k: str(v() if callable(v) else v).translate(_CLEAN)
        for k, v in args.items()})


def rows(items: Iterable[Iterable]) -> str:
    """``[(0, 17), (0, 33)]`` -> ``'0/17:0/33'``."""
    return ":".join("/".join(str(int(x)) for x in row) for row in items)
