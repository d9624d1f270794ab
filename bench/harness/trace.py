"""Reduce a profiler trace to device busy time, idle gaps and op times.

A trace is read into plain tuples, ``[(plane, line, [(name, start_ns,
duration_ns), ...]), ...]``, so the reduction can be tested on a small
synthetic trace. Device planes are named ``/device:<kind>:<n>``; their
``XLA Ops`` line holds one event per operation that ran (a loop and the
ops inside it both), named by its HLO text, and their ``XLA Modules``
line one event per program. The host plane holds the ``bench_window``
annotation the harness drops when the profiler starts: it ties the
trace's clock to the host's.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import threading
import time

import numpy as np

ANCHOR = "bench_window"
TRACED_S = 5.0      # the last seconds of a window run under the profiler
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = "[tpu_custom_call]"


def options():
    """Profiler options for a traced window: no Python call tracing (it
    records every Python call of every thread and slows the host many
    times over), host annotations kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class Profiler:
    """The JAX profiler over the end of a window: started ``delay`` seconds
    from now on a timer thread, which then drops the anchor (host clock
    in ``self.anchor``); ``stop()`` after the window. Writing a trace
    takes about a minute per 2 million device ops (the greedy scan runs
    600 thousand a second), so only the last ``TRACED_S`` seconds of a
    window are traced."""

    def __init__(self, log_dir: str, delay: float):
        self.log_dir = log_dir
        self.anchor = None
        self._timer = threading.Timer(delay, self._start)
        self._timer.start()

    def _start(self):
        import jax

        jax.profiler.start_trace(self.log_dir, profiler_options=options())
        self.anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def stop(self):
        import jax

        self._timer.cancel()
        self._timer.join()
        if self.anchor is not None:
            jax.profiler.stop_trace()


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[..] fusion(..), ..`` -> ``fusion.3``, marked when
    it is a Mosaic (Pallas) kernel."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in hlo:
        name += " " + KERNEL_MARK
    return name


def load(log_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``log_dir``, as plain tuples: the
    device planes' op and module lines, and the host's anchor."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    names: dict[str, str] = {}
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                events = []
                for e in line.events:
                    n = e.name
                    if n not in names:
                        names[n] = op_name(n)
                    events.append((names[n], e.start_ns, e.duration_ns))
            elif device and line.name == MODULES_LINE:
                events = [(e.name.split("(", 1)[0], e.start_ns,
                           e.duration_ns) for e in line.events]
            elif not device:
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name == ANCHOR]
            else:
                continue
            out.append((plane.name, line.name, events))
    return out


def anchor_ns(planes) -> float | None:
    for plane, _, events in planes:
        if not plane.startswith("/device:"):
            for name, start, _ in events:
                if name == ANCHOR:
                    return start
    return None


@dataclasses.dataclass
class Ops:
    """The ops of one device: ``labels[label[i]]`` names op i
    (``<program>/<op>``); ``start``/``end`` in ns, sorted by start;
    ``own`` its time less the ops that ran inside it."""

    labels: list
    label: np.ndarray
    start: np.ndarray
    end: np.ndarray
    own: np.ndarray


def device_ops(planes) -> dict:
    """``{device plane: Ops}``."""
    modules: dict[str, list] = {}
    for plane, line, events in planes:
        if plane.startswith("/device:") and line == MODULES_LINE:
            modules.setdefault(plane, []).extend(events)
    out = {}
    for plane, line, events in planes:
        if not (plane.startswith("/device:") and line == OPS_LINE) \
                or not events:
            continue
        names, start, dur = zip(*events)
        start = np.asarray(start, dtype=np.float64)
        end = start + np.asarray(dur, dtype=np.float64)
        order = np.lexsort((-end, start))
        start, end = start[order], end[order]
        op_names, op_idx = np.unique(np.asarray(names, dtype=object)[order],
                                     return_inverse=True)
        mods = sorted(modules.get(plane, []), key=lambda m: m[1])
        m_start = np.asarray([m[1] for m in mods], dtype=np.float64)
        m_end = m_start + np.asarray([m[2] for m in mods], dtype=np.float64)
        k = np.searchsorted(m_start, start, side="right") - 1
        inside = (k >= 0) & (start < m_end[np.maximum(k, 0)])
        m_names = [m[0] for m in mods] + ["?"]
        prog = np.where(inside, k, len(mods))
        codes, label = np.unique(prog * len(op_names) + op_idx,
                                 return_inverse=True)
        labels = [f"{m_names[c // len(op_names)]}/"
                  f"{op_names[c % len(op_names)]}" for c in codes]
        # own time: each op less the ops directly inside it
        own = end - start
        stack: list[int] = []
        ends = end.tolist()
        for i, (s, d) in enumerate(zip(start.tolist(), own.tolist())):
            while stack and ends[stack[-1]] <= s:
                stack.pop()
            if stack:
                own[stack[-1]] -= d
            stack.append(i)
        out[plane] = Ops(labels, label, start, end, own)
    return out


def busy_blocks(start: np.ndarray, end: np.ndarray):
    """The union of [start, end) intervals (sorted by start) as disjoint
    (block_start, block_end) arrays."""
    if not len(start):
        return start, end
    reach = np.maximum.accumulate(end)
    new = np.concatenate([[True], start[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(start) - 1]])
    return start[first], reach[last]


@dataclasses.dataclass
class DeviceTrace:
    """One traced window, on the host clock (seconds of perf_counter)."""

    window: tuple                  # (start, end)
    ops: list                      # per device: Ops, times on the host clock
    busy_s: float                  # union of op time, mean over devices
    gaps: list                     # [(start, end)] of the busiest device

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> int:
        return len(self.ops)

    def spans_of(self, mark: str):
        """(start, end) of every op whose label holds ``mark``."""
        for ops in self.ops:
            hit = np.asarray([mark in lab for lab in ops.labels])
            pick = hit[ops.label] if len(hit) else ops.label < 0
            yield from zip(ops.start[pick].tolist(), ops.end[pick].tolist())


def reduce(planes, window, anchor_host: float) -> DeviceTrace | None:
    """Ops, busy time and idle gaps inside ``window`` (host clock), the
    trace's clock tied to it by the anchor opened at ``anchor_host``.
    None when the trace holds no device op or no anchor."""
    a = anchor_ns(planes)
    per_device = device_ops(planes)
    if a is None or not per_device:
        return None
    lo, hi = window
    kept, busy, idle = [], [], None
    for plane in sorted(per_device):
        ops = per_device[plane]
        start = anchor_host + (ops.start - a) * 1e-9
        end = anchor_host + (ops.end - a) * 1e-9
        pick = (end > lo) & (start < hi)
        ops = Ops(ops.labels, ops.label[pick], np.maximum(start[pick], lo),
                  np.minimum(end[pick], hi), ops.own[pick] * 1e-9)
        kept.append(ops)
        b0, b1 = busy_blocks(ops.start, ops.end)
        busy.append(float((b1 - b0).sum()))
        if busy[-1] >= max(busy[:-1], default=0.0):
            edges = np.concatenate([[lo], np.ravel(np.column_stack(
                [b0, b1])), [hi]])
            idle = [(s, e) for s, e in zip(edges[0::2].tolist(),
                                           edges[1::2].tolist()) if e > s]
    return DeviceTrace(window=(lo, hi), ops=kept,
                       busy_s=sum(busy) / len(busy), gaps=idle)


def top_ops(trace: DeviceTrace, n: int = 10) -> list:
    """The ``n`` op labels that took the most device time of their own
    (ops inside a loop counted, the loop less them), with seconds."""
    total: dict[str, float] = {}
    for ops in trace.ops:
        sums = np.bincount(ops.label, weights=ops.own,
                           minlength=len(ops.labels))
        for lab, sec in zip(ops.labels, sums.tolist()):
            total[lab] = total.get(lab, 0.0) + sec
    return sorted(([k, v] for k, v in total.items() if v > 0),
                  key=lambda kv: -kv[1])[:n]


def label_gaps(trace: DeviceTrace, spans, n: int = 10) -> list:
    """The ``n`` longest idle gaps, each named by the innermost span that
    was open over the gap's middle (the host's work then)."""
    out = []
    for s, e in sorted(trace.gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        covering = [sp for sp in spans if sp.t0 <= mid < sp.t1]
        name = max(covering, key=lambda sp: sp.t0).name if covering \
            else "no request in service"
        out.append([name, e - s])
    return out
