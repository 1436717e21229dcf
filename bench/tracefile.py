"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device intervals.

What a TPU trace holds (looked at by hand on a v5e, JAX 0.9):

* one plane per chip, ``/device:TPU:<n>``;
* its ``XLA Modules`` line: one event per program run, named
  ``jit_<function>(<hash>)``, e.g. ``jit_fw_scan(5505914745991308705)``;
* its ``XLA Ops`` line: one event per operation executed, every loop
  iteration included, named by the HLO instruction's text
  (``%little_step_pallas.3 = s32[1]{...} custom-call(...)``).  A ``while``
  op's event spans the ops of its body, which lie inside it;
* ``/host:CPU`` planes with one line per host thread: the benchmark's own
  ``bench.*`` annotations, JAX's dispatch spans (``PjitFunction(fw_scan)``)
  and the runtime's (``CommonPjRtLoadedExecutable::Execute``).

All planes share one clock, in nanoseconds.  An operation is named here by
its instruction name without the ``%`` and the numeric suffix
(``little_step_pallas``, ``fusion``, ``while``).

Busy time is the union of the *leaf* operations of a chip (those that hold
no other operation) inside the benchmark's ``bench.window`` annotation: time
spent between the operations of a loop body counts as idle.  Each idle gap
is named after the innermost host span that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
# names too generic to tell operations apart: the output type is added
GENERIC = ("fusion", "copy", "custom-call")


def op_name(hlo_text: str) -> str:
    """``%little_step_pallas.3 = s32[1]{0} custom-call(...)`` ->
    ``little_step_pallas``; ``%fusion.7 = f32[4000]{0:T(1024)} fusion(...)``
    -> ``fusion f32[4000]``."""
    head, _, rest = hlo_text.partition(" = ")
    name = _SUFFIX.sub("", head.lstrip("%"))
    if name in GENERIC and rest:
        kind = f" {name}("
        out = rest.split(kind, 1)[0] if kind in rest else rest.split(" ")[0]
        name = f"{name} {_LAYOUT.sub('', out)}"
    return name


@dataclasses.dataclass
class Events:
    """Named intervals, nanoseconds; ``self_ns`` leaves out nested events."""

    names: List[str]
    start: np.ndarray
    dur: np.ndarray
    self_ns: np.ndarray

    @classmethod
    def of(cls, names, start, dur) -> "Events":
        start = np.asarray(start, np.int64).reshape(-1)
        dur = np.asarray(dur, np.int64).reshape(-1)
        return cls(list(names), start, dur, _self_time(start, dur))

    def __len__(self) -> int:
        return len(self.names)

    def _take(self, keep) -> "Events":
        return Events([self.names[i] for i in keep], self.start[keep],
                      self.dur[keep], self.self_ns[keep])

    def matching(self, pattern: str) -> "Events":
        rx = re.compile(pattern)
        return self._take([i for i, n in enumerate(self.names)
                           if rx.search(n)])

    def within(self, lo: int, hi: int) -> "Events":
        return self._take(np.flatnonzero((self.start >= lo)
                                         & (self.start < hi)))

    def leaves(self) -> "Events":
        return self._take(np.flatnonzero(self.self_ns == self.dur))

    def total_s(self) -> float:
        return float(self.dur.sum()) * 1e-9

    def self_by_name_s(self) -> Dict[str, float]:
        out: Dict[str, float] = collections.defaultdict(float)
        for name, d in zip(self.names, self.self_ns.tolist()):
            out[name] += d * 1e-9
        return dict(out)


def _self_time(start: np.ndarray, dur: np.ndarray) -> np.ndarray:
    """Each event's duration less the events nested directly inside it."""
    own = dur.copy()
    order = np.lexsort((-dur, start))
    stack: List[Tuple[int, int]] = []          # (end, index)
    for i in order.tolist():
        s, e = int(start[i]), int(start[i] + dur[i])
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur[i]
        stack.append((e, i))
    return own


@dataclasses.dataclass
class Trace:
    ops: Dict[int, Events]        # chip id -> operations
    modules: Dict[int, Events]    # chip id -> program runs
    host: Events                  # every host span, all threads
    window: Tuple[int, int]       # the benchmark's window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def union(self, chip: int) -> np.ndarray:
        """Merged (start, end) intervals of ``chip``'s leaf operations,
        clipped to the window; shape (K, 2)."""
        ev = self.ops.get(chip)
        if ev is None:
            return np.zeros((0, 2), np.int64)
        ev = ev.leaves()
        lo, hi = self.window
        s = np.clip(ev.start, lo, hi)
        e = np.clip(ev.start + ev.dur, lo, hi)
        keep = e > s
        s, e = s[keep], e[keep]
        if not s.size:
            return np.zeros((0, 2), np.int64)
        order = np.argsort(s, kind="stable")
        s, e = s[order], e[order]
        reach = np.maximum.accumulate(e)
        new = np.ones(s.size, bool)
        new[1:] = s[1:] > reach[:-1]
        first = np.flatnonzero(new)
        return np.stack([s[first], np.maximum.reduceat(e, first)], axis=1)

    def busy_s(self, chip: int) -> float:
        u = self.union(chip)
        return float((u[:, 1] - u[:, 0]).sum()) * 1e-9

    def idle_gaps(self, chip: int, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest idle stretches of ``chip`` in the window,
        each named after what the host was doing in its middle."""
        u = self.union(chip)
        lo, hi = self.window
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        length = edges[:, 1] - edges[:, 0]
        longest = np.argsort(-length, kind="stable")[:top]
        return [(self.host_at(int(edges[i].mean())), float(length[i]) * 1e-9)
                for i in longest if length[i] > 0]

    def host_at(self, t: int) -> str:
        """Innermost host span covering ``t`` (the window itself aside)."""
        h = self.host
        cover = [i for i in np.flatnonzero((h.start <= t)
                                           & (h.start + h.dur >= t))
                 if h.names[i] != WINDOW]
        if not cover:
            return "host: no span"
        return h.names[min(cover, key=lambda i: h.dur[i])]


def _events(line, rename=None) -> Events:
    names, start, dur = [], [], []
    for ev in line.events:
        names.append(rename(ev.name) if rename else ev.name)
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
    return Events.of(names, start, dur)


def latest_xplane(logdir: str) -> Optional[str]:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = _events(line, op_name)
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                host.append(_events(line))
    host_ev = Events.of([n for h in host for n in h.names],
                        np.concatenate([h.start for h in host] or [[]]),
                        np.concatenate([h.dur for h in host] or [[]]))
    win = host_ev.matching(f"^{re.escape(WINDOW)}$")
    if len(win):
        window = (int(win.start.min()), int((win.start + win.dur).max()))
    else:
        every = [e for e in list(ops.values()) + [host_ev] if len(e)]
        window = (min(int(e.start.min()) for e in every),
                  max(int((e.start + e.dur).max()) for e in every))
    return Trace(ops=ops, modules=modules, host=host_ev, window=window)


# Reductions that the per-layer readers (``bench/metrics/*.py``) share; each
# takes the run a reader is given and returns None where it finds nothing.

def device_events(run, pattern: str, line: str) -> Optional[Events]:
    """The first chip's program runs (``line="modules"``) or operations
    (``"ops"``) inside the window whose name matches ``pattern``."""
    if run.trace is None:
        return None
    events = getattr(run.trace, line).get(run.chips[0])
    if events is None:
        return None
    got = events.within(*run.trace.window).matching(pattern)
    return got if len(got) else None


def device_time_per(run, pattern: str, line: str, count: int,
                    scale: float) -> Optional[float]:
    """Device seconds of the matching events, times ``scale``, over
    ``count`` (fits, steps or lane-steps of the window)."""
    got = device_events(run, pattern, line)
    if got is None or not count:
        return None
    return got.total_s() * scale / count


def idle_share_pct(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on a chip,
    averaged over the chips the cell uses."""
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not all(
            len(tr.ops.get(c, ())) for c in run.chips):
        return None
    busy = sum(tr.busy_s(c) for c in run.chips) / len(run.chips)
    return 100.0 * (1.0 - busy / tr.window_s)
