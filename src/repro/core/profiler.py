"""AVEC profiler: per-cycle GPU / communication / other breakdown.

Mirrors the paper's nvprof-based accounting (Figs. 8-9): every offloaded
execution cycle is decomposed into destination compute time ("GPU"), wire +
(de)serialization time ("Communication"), and host-side application time
("Other"); FPS is derived per the paper's Table V.

``gpu_s`` is the destination's host-timed ``execute`` (``compute_s``: the
library call's dispatch plus the wait for the device), not device time; the
device's own time is read from the profiler trace.  The totals are running
sums, so a session's profiler holds constant memory however long it runs;
``cycles`` keeps only the most recent ``KEEP_CYCLES`` records."""
from __future__ import annotations

import collections
import threading
from dataclasses import dataclass, field


@dataclass
class CycleRecord:
    gpu_s: float
    comm_s: float
    bytes_sent: int
    bytes_received: int
    fn: str = ""


#: cycle records an :class:`AvecProfiler` keeps (the totals cover all)
KEEP_CYCLES = 1024


@dataclass
class AvecProfiler:
    other_s: float = 0.0
    model_transfer_s: float = 0.0
    # running sums over every cycle recorded (state, not options)
    n_cycles: int = field(default=0, init=False)
    gpu_s: float = field(default=0.0, init=False)
    comm_s: float = field(default=0.0, init=False)
    bytes_sent: int = field(default=0, init=False)
    bytes_received: int = field(default=0, init=False)
    cycles: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=KEEP_CYCLES),
        init=False, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_cycle(self, gpu_s: float, comm_s: float, bytes_sent: int,
                     bytes_received: int, fn: str = "") -> None:
        with self._lock:
            self.cycles.append(CycleRecord(gpu_s, comm_s, bytes_sent,
                                           bytes_received, fn))
            self.n_cycles += 1
            self.gpu_s += gpu_s
            self.comm_s += comm_s
            self.bytes_sent += bytes_sent
            self.bytes_received += bytes_received

    def record_other(self, seconds: float) -> None:
        with self._lock:
            self.other_s += seconds

    def record_model_transfer(self, seconds: float) -> None:
        with self._lock:
            self.model_transfer_s += seconds

    # ------------------------------------------------------------------
    @property
    def total_s(self) -> float:
        return self.gpu_s + self.comm_s + self.other_s

    def breakdown(self) -> dict:
        """Paper Figs. 8-9 categories, absolute seconds and fractions."""
        total = max(self.total_s, 1e-12)
        return {
            "gpu_s": self.gpu_s, "communication_s": self.comm_s,
            "other_s": self.other_s,
            "gpu_frac": self.gpu_s / total,
            "communication_frac": self.comm_s / total,
            "other_frac": self.other_s / total,
            "cycles": self.n_cycles,
            "bytes_sent": self.bytes_sent,
            "bytes_received": self.bytes_received,
            "model_transfer_s": self.model_transfer_s,
        }

    def fps(self, frames: int | None = None) -> float:
        n = frames if frames is not None else self.n_cycles
        return n / max(self.total_s, 1e-12)

    def per_cycle(self) -> dict:
        n = max(self.n_cycles, 1)
        return {"gpu_s": self.gpu_s / n, "communication_s": self.comm_s / n,
                "other_s": self.other_s / n,
                "bytes_per_cycle": (self.bytes_sent + self.bytes_received) / n}
