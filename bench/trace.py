"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A trace holds one plane per device (``/device:TPU:<n>``) and one for the
host (``/host:CPU``). On a device plane the line ``XLA Ops`` has one
event per operation run, named by its HLO instruction text
(``%spmm_sorted.5 = f32[...] custom-call(...), ...``); the line ``XLA
Modules`` has one event per program run, named after the jitted
function (``jit_sample(...)``). On the host plane, the spans the
benchmark opens with ``jax.profiler.TraceAnnotation`` (``bench.*``) sit
on the Python thread's line, on the same clock as the device events.

The trace does not carry the JAX op path of an operation; the compiled
program's HLO text does (``metadata={op_name="jit(step)/...
/jit(spmm_sorted)/pallas_call"}``). :func:`op_paths` reads it, keyed by
instruction name, and a trace reduced with that map gives each op its
path, by which the metrics find kernels.

The profiler is started right before the traced window and stopped
right after it, so every op in the trace belongs to the window.
Everything here is a pure function of the trace file and that map, so
the same inputs always reduce to the same numbers.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    path: str       # the JAX op path of a device op ("" where none)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def instruction(text: str) -> str:
    """The instruction name of an HLO line or op event: ``spmm_sorted.5``."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text


def op_paths(hlo_text: str) -> Dict[str, str]:
    """{instruction name: JAX op path} of a compiled program's HLO."""
    out = {}
    for line in hlo_text.splitlines():
        path = _OP_NAME.search(line)
        if path:
            out[instruction(line)] = path.group(1)
    return out


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, sorted and disjoint."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


class Device(NamedTuple):
    name: str
    ops: List[Event]
    modules: List[Event]


class Trace:
    """The device ops, device programs and host spans of one trace."""

    def __init__(self, devices: List[Device], spans: List[Event]):
        self.devices = devices
        self.spans = spans

    @classmethod
    def from_file(cls, path: str,
                  paths: Optional[Dict[str, str]] = None) -> "Trace":
        """The trace at ``path``; ``paths`` (:func:`op_paths`) gives the
        device ops their JAX op paths."""
        from jax.profiler import ProfileData

        paths = paths or {}

        pd = ProfileData.from_file(path)
        devices, spans = [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops, modules = [], []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops += [Event(e.name, e.start_ns, e.duration_ns,
                                      paths.get(instruction(e.name), ""))
                                for e in line.events]
                    elif line.name == MODULES_LINE:
                        modules += [Event(e.name, e.start_ns, e.duration_ns,
                                          "") for e in line.events]
                if ops:
                    devices.append(Device(plane.name, ops, modules))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [Event(e.name, e.start_ns, e.duration_ns, "")
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        devices.sort(key=lambda d: d.name)
        return cls(devices, spans)

    @classmethod
    def from_dir(cls, log_dir: str,
                 paths: Optional[Dict[str, str]] = None) -> "Trace":
        """The newest trace the profiler wrote under ``log_dir``."""
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_file(max(files, key=os.path.getmtime), paths)

    # -- the window -------------------------------------------------------

    def window_ns(self) -> Optional[Tuple[float, float]]:
        """The ``bench.window`` span, widened to every device op: the
        device's clock runs up to a few milliseconds apart from the
        host's, and an op the window dispatched may show before it."""
        ops = [e for d in self.devices for e in d.ops]
        spans = [s for s in self.spans if s.name == WINDOW_SPAN]
        ends = ([(s.start_ns, s.end_ns) for s in spans]
                + ([(min(e.start_ns for e in ops),
                     max(e.end_ns for e in ops))] if ops else []))
        if not ends:
            return None
        return min(a for a, _ in ends), max(b for _, b in ends)

    @property
    def window_s(self) -> Optional[float]:
        w = self.window_ns()
        return None if w is None else (w[1] - w[0]) * 1e-9

    def _busy(self, dev: Device) -> np.ndarray:
        iv = np.array([[e.start_ns, e.end_ns] for e in dev.ops], float)
        return _merge(iv.reshape(-1, 2))

    @property
    def busy_s(self) -> Optional[float]:
        """Seconds of the window in which some op ran, mean over chips."""
        if not self.devices:
            return None
        per = [float(np.sum(b[:, 1] - b[:, 0])) if b.shape[0] else 0.0
               for b in map(self._busy, self.devices)]
        return float(np.mean(per)) * 1e-9

    # -- ops and programs by name ----------------------------------------

    def kernel_seconds(self, match: Callable[[str], bool]
                       ) -> Optional[float]:
        """Device seconds of the ops whose op path ``match`` accepts,
        mean over chips; None where no op matches."""
        if not self.devices:
            return None
        per, hits = [], 0
        for d in self.devices:
            evs = [e for e in d.ops if match(e.path)]
            hits += len(evs)
            per.append(sum(e.dur_ns for e in evs))
        return float(np.mean(per)) * 1e-9 if hits else None

    def module_seconds(self, prefix: str) -> Optional[Tuple[float, int]]:
        """(device seconds, runs) of the programs whose name starts with
        ``prefix``, mean over chips; None where none ran."""
        if not self.devices:
            return None
        secs, runs = [], 0
        for d in self.devices:
            evs = [e for e in d.modules if e.name.startswith(prefix)]
            runs = max(runs, len(evs))
            secs.append(sum(e.dur_ns for e in evs))
        return (float(np.mean(secs)) * 1e-9, runs) if runs else None

    # -- the breakdown ----------------------------------------------------

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops that took most device time in the window:
        [instruction name, with its op path where known, seconds],
        first chip."""
        if not self.devices:
            return []
        d = self.devices[0]
        tot: Dict[str, float] = {}
        for e in d.ops:
            k = instruction(e.name) + (f" {e.path}" if e.path else "")
            tot[k] = tot.get(k, 0.0) + e.dur_ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest stretches of the window with no op on the
        first chip: [innermost ``bench.*`` span open at its midpoint
        (or "host: no span"), seconds]."""
        if not self.devices:
            return []
        lo, hi = self.window_ns()
        busy = self._busy(self.devices[0])
        edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:n]
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            open_ = [sp for sp in self.spans if sp.name != WINDOW_SPAN
                     and sp.start_ns <= mid < sp.end_ns]
            name = (max(open_, key=lambda sp: sp.start_ns).name if open_
                    else "host: no span")
            out.append([name, float(e - s) * 1e-9])
        return out
