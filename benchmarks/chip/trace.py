"""Reduction of a profiler trace to device metrics.

``jax.profiler.trace`` writes an ``.xplane.pb`` file. In it each chip
is a plane named ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
event per executed HLO operation, and ``Async XLA Ops`` the operations
that run beside them (asynchronous copies and collectives); the host
is the plane ``/host:CPU``, whose Python thread carries the ``jax.profiler.TraceAnnotation`` spans
the benchmark opens (their names start with ``bench.``). Host and
device events share one time base, to within about a millisecond (a
recorded v5e trace shows a step's device ops starting 0.6 ms before
the host span that dispatched it).

* busy: the union of a chip's ``XLA Ops`` intervals inside the window.
* idle share: 1 - busy / window, averaged over the chips.
* collective time: the union of a chip's collective operations
  (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute) on either line; its exposed part is what no other
  ``XLA Ops`` operation of that chip overlaps.
* breakdown: the operations with the most device self time (a ``while``
  less the body ops nested in it; seconds summed over the chips, divided
  by the number of chips), and the longest idle
  gaps of the first chip, each named after the innermost ``bench.``
  span open on the host at the gap's midpoint.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Any

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
TOP = 10


def find(log_dir: str | Path) -> str:
    """The one ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> float:
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``: the event
    names the whole HLO instruction, whose operands may name other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list[tuple[float, float, str]]):
    """(name, time not covered by the ops nested in it): a ``while``
    event spans the ops of its body on the same line."""
    out, stack = [], []                   # stack of [end, name, self]
    for s, e, n in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, name, t = stack.pop()
            out.append((name, t))
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, n, e - s])
    out += [(name, t) for _, name, t in stack]
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def host_spans(planes) -> list[tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def reduce(path: str | Path, window_span: str = "bench.window"
           ) -> dict[str, Any] | None:
    """Device numbers of the trace at ``path``, inside the host span
    ``window_span`` (the whole trace where there is no such span).
    None where the trace holds no device operation."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    spans = host_spans(planes)
    chips = []
    for plane in sorted((p for p in planes if DEVICE.match(p.name)),
                        key=lambda p: int(p.name.rsplit(":", 1)[1])):
        lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                              op_name(ev.name)) for ev in line.events]
                 for line in plane.lines
                 if line.name in (OPS_LINE, ASYNC_LINE)}
        if lines.get(OPS_LINE):
            chips.append((lines[OPS_LINE], lines.get(ASYNC_LINE, [])))
    if not chips:
        return None
    win = [s for s in spans if s[2] == window_span]
    if win:
        lo, hi = win[0][0], win[0][1]
    else:
        lo = min(s for ops, _ in chips for s, _, _ in ops)
        hi = max(e for ops, _ in chips for _, e, _ in ops)
    window_ns = hi - lo
    busy, coll, exposed, by_name = [], [], [], {}
    for ops, async_ops in chips:
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops
                  if e > lo and s < hi]
        all_iv = merge([(s, e) for s, e, _ in inside])
        c_iv = merge([(max(s, lo), min(e, hi))
                      for s, e, n in ops + async_ops
                      if e > lo and s < hi and COLLECTIVE.search(n)])
        other = merge([(s, e) for s, e, n in inside
                       if not COLLECTIVE.search(n)])
        busy.append(covered(all_iv))
        coll.append(covered(c_iv))
        exposed.append(subtract(c_iv, other))
        for n, t in self_times(inside):
            by_name[n] = by_name.get(n, 0.0) + t
    n = len(chips)
    first = merge(_clip([(s, e) for s, e, _ in chips[0][0]], lo, hi))
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    return {
        "chips": n,
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "idle_share": 1.0 - sum(busy) / n / window_ns,
        "collective_s": sum(coll) / n * 1e-9,
        "collective_exposed_s": sum(exposed) / n * 1e-9,
        "device_ops": [[name, t / n * 1e-9] for name, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(spans, (s + e) / 2), g * 1e-9]
                      for g, s, e in gaps],
    }


def _label(spans, t: float) -> str:
    """The innermost ``bench.`` span open at ``t``."""
    open_ = [(e - s, name) for s, e, name in spans if s <= t < e]
    return min(open_)[1] if open_ else "no host span"
