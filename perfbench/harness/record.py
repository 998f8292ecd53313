"""What one run leaves for the metric readers.

A reader (``metrics/<name>.py``) gets a :class:`RunRecord` and returns
a number, or None where it finds nothing to read.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .peaks import Peaks
from .work import Dims


@dataclass
class TraceWindow:
    """The traced part of the window: the reduced trace and the host
    clock interval it covers."""
    trace: object                  # trace.Trace
    host_t0: float                 # perf_counter at the trace's start
    host_t1: float                 # perf_counter at its stop

    @property
    def seconds(self) -> float:
        return self.host_t1 - self.host_t0


@dataclass
class RunRecord:
    cell: str
    window_s: float
    setup_s: float
    records: List                     # loadgen.Record, every request sent
    dims: Dims
    peaks: Peaks
    chips: int
    launches: List = field(default_factory=list)   # system.Launch, window
    rebinds: List[Tuple[float, float]] = field(default_factory=list)
    sync_delta: Dict[str, int] = field(default_factory=dict)
    queue_waits: List[float] = field(default_factory=list)   # seconds
    traced: Optional[TraceWindow] = None
    breakdown: Optional[Dict] = None
    # host seconds from the window's start, once the steps launched
    # before it had run, until every step launched in it had run
    gen_s: Optional[float] = None

    @property
    def in_window(self) -> List:
        """Requests whose scheduled send falls in the window."""
        return [r for r in self.records if 0.0 <= r.plan.t < self.window_s]

    def traced_launches(self) -> List:
        """Launches issued while the trace ran."""
        if self.traced is None:
            return []
        t0, t1 = self.traced.host_t0, self.traced.host_t1
        return [l for l in self.launches if t0 <= l.t0 < t1]
