#!/usr/bin/env python3
"""Device time by the program's named scopes, host time by its spans.

Scopes. The model step names its work with ``jax.named_scope``:
``embed``, ``mixer``, ``ffn`` and ``head`` (``models/transformer.py``)
and ``optimizer`` (``optim/adamw.py``). XLA keeps the name stack in
each HLO instruction's ``op_name`` metadata; backward and rematerialised
operations carry it inside path components such as
``transpose(jvp(mixer))`` or ``checkpoint/mixer``. ``scope_map`` maps
every instruction of a compiled step's HLO text (``Compiled.as_text()``,
whose instruction names are the trace's operation names) to the scope
its ``op_name`` holds as a whole path token; a fusion without metadata
takes the scope most of its fused instructions hold, and an instruction
the compiler inserted, with no metadata, the scope of the first of its
operands that has one. ``split`` sums the
device self time of a window's operations by scope.

Spans. The program opens ``papas.*`` spans at the layer boundaries of
the study path (``core/telemetry.py:span``) and keeps the last closed
ones in memory (``telemetry.recent_spans``). ``study_spans`` reads the
spans of a run's last studies; the study cell's ``study_*`` readers
read it. A program without those spans gives None.

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \\
        --seconds <s>

is a stopgap until the cells report the scope and idle metrics
themselves (``harness.traced`` and the paths then call ``scope_map``,
``split`` and ``idle_unnamed``), and goes then: it drives the paths'
window loops itself. It profiles one cell's path on the chip for ``--seconds`` and prints one
JSON line: for a train cell the device self time per step of each scope,
the largest unscoped operations, the named operations' scopes, and the
lead of device operations over the host span that dispatched them (a
synchronous loop of steps); for a study cell the program's span numbers
and the chip's idle time during which no ``papas.`` span was open.
"""
from __future__ import annotations

import collections
import re
from typing import Any

from benchmarks.chip import trace

SCOPES = ("embed", "mixer", "ffn", "head", "optimizer")
SPAN_PREFIX = "papas."
_TOKEN = re.compile(r"[^/()]+")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\(([^)]*)\)")
_NAME = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` that ``op_name`` holds as a whole
    path token, bare or inside parentheses."""
    found = [t for t in _TOKEN.findall(op_name) if t in SCOPES]
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict[str, str | None]:
    """Instruction name -> scope (None where it carries none) for every
    instruction of an HLO module's text.

    An instruction takes the scope of its own ``op_name``. Without one,
    a fusion takes the scope most of its fused instructions hold, and
    an instruction with no metadata at all (one the compiler inserted,
    such as a convert or a copy) the scope of its first operand that
    has one."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    operands: dict[str, list[str]] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        inst = _INSTRUCTION.match(line)
        if inst is None or comp is None:
            continue
        name = inst.group(1)
        m = _OP_NAME.search(line)
        own[name] = scope_of(m.group(1)) if m else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        elif m is None:
            args = _OPERANDS.search(line[inst.end():])
            operands[name] = _NAME.findall(args.group(1)) if args else []
        members[comp].append(name)
    memo: dict[str, str | None] = {}

    def resolve(name: str, depth: int = 0) -> str | None:
        if own.get(name) is not None or depth > 16:
            return own.get(name)
        if name not in memo:
            if name in calls:
                votes = collections.Counter(
                    s for s in (resolve(i, depth + 1)
                                for i in members.get(calls[name], ()))
                    if s is not None)
                memo[name] = votes.most_common(1)[0][0] if votes else None
            else:
                memo[name] = next(
                    (s for s in (resolve(i, depth + 1)
                                 for i in operands.get(name, ()))
                     if s is not None), None)
        return memo[name]

    return {name: resolve(name) for name in own}


def split(self_s: dict[str, float], scopes: dict[str, str | None]
          ) -> dict[str, Any]:
    """Seconds of device self time by scope; ``unscoped`` holds the
    operations that carry no scope and ``unknown`` those the HLO text
    does not name."""
    out = dict.fromkeys(SCOPES + ("unscoped", "unknown"), 0.0)
    for name, t in self_s.items():
        if name not in scopes:
            out["unknown"] += t
        else:
            out[scopes[name] or "unscoped"] += t
    return out


# ---------------------------------------------------------------------------
# the program's spans


def study_spans(spans: list[tuple[str, float, float]], studies: int
                ) -> dict[str, float] | None:
    """Per-study numbers of the last ``studies`` studies: every span
    from the last ``studies``-th ``papas.study.plan`` span on (one opens
    each ``ParameterStudy.run``). None where there are fewer studies."""
    plans = [s for s in spans if s[0] == "papas.study.plan"]
    if not studies or len(plans) < studies:
        return None
    lo = plans[-studies][1]
    inside = [s for s in spans if s[1] >= lo]

    def total(*names: str) -> float:
        return sum(e - s for n, s, e in inside if n in names)

    gang = trace.merge([(s, e) for n, s, e in inside
                        if n.startswith("papas.gang.")])
    engine = trace.subtract(trace.merge([(s, e) for n, s, e in inside
                                         if n.startswith("papas.study.")]),
                            gang)
    lowers = sum(1 for n, _, _ in inside if n == "papas.gang.lower")
    return {"lower_s": total("papas.gang.build", "papas.gang.lower")
            / studies,
            "load_s": total("papas.gang.compile") / studies,
            "run_s": total("papas.gang.run") / studies,
            "gang_traces": lowers / studies,
            "engine_span_s": engine / studies}


def program_study_spans(ctx: dict) -> dict[str, float] | None:
    """``study_spans`` of the program's own record over the window's
    studies (``ctx["window_studies"]``); None where the program keeps
    no such record."""
    try:
        from repro.core.telemetry import recent_spans
    except ImportError:
        return None
    return study_spans(recent_spans(), ctx.get("window_studies") or 0)


# ---------------------------------------------------------------------------
# reading a profile


def read_profile(path: str, window_span: str = "bench.window"
                 ) -> dict[str, Any]:
    """Chip 0's operations and the host's ``bench.``/``papas.`` spans of
    the profile at ``path``, clipped to the host span ``window_span``:
    ``ops`` [(start, end, name)] in ns, ``spans`` likewise, ``lo``,
    ``hi``."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    spans, ops = [], []
    for plane in planes:
        if plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events
                          if ev.name.startswith(("bench.", SPAN_PREFIX))]
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            trace.op_name(ev.name)) for ev in line.events]
    win = [s for s in spans if s[2] == window_span]
    lo, hi = (win[0][0], win[0][1]) if win else (
        min(s for s, _, _ in ops), max(e for _, e, _ in ops))
    ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
           if e > lo and s < hi]
    return {"ops": ops, "spans": spans, "lo": lo, "hi": hi}


def self_seconds(ops) -> dict[str, float]:
    """Device self time of each operation name, in seconds."""
    out: dict[str, float] = collections.defaultdict(float)
    for name, t in trace.self_times(ops):
        out[name] += t * 1e-9
    return dict(out)


def idle_unnamed(prof: dict[str, Any]) -> float:
    """Seconds of the window in which chip 0 ran nothing and no
    ``papas.`` span was open on the host."""
    lo, hi = prof["lo"], prof["hi"]
    busy = trace.merge([(s, e) for s, e, _ in prof["ops"]])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = trace.merge([(max(s, lo), min(e, hi)) for s, e, n in prof["spans"]
                    if n.startswith(SPAN_PREFIX) and e > lo and s < hi])
    return trace.subtract(idle, named) * 1e-9


def leads(prof: dict[str, Any], span_name: str, gap_ns: float = 1e6
          ) -> list[float]:
    """Milliseconds by which each ``span_name`` span's device work (a
    cluster of operations after an idle gap of ``gap_ns`` or more)
    starts before the span does."""
    starts = sorted(s for s, _, n in prof["spans"] if n == span_name)
    clusters, last = [], None
    for s, e, _ in sorted(prof["ops"]):
        if last is None or s - last >= gap_ns:
            clusters.append(s)
        last = e if last is None else max(last, e)
    if len(clusters) != len(starts):
        return []
    return [(h - d) * 1e-6 for h, d in zip(starts, clusters)]


# ---------------------------------------------------------------------------
# the chip run


def _profiled(fn):
    import glob
    import tempfile
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_scopes_") as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            out = fn()
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        return out, read_profile(path)


def train_cell(spec: dict, named: list[str]) -> dict[str, Any]:
    import time
    import jax
    from benchmarks.chip import harness
    from benchmarks.chip.paths import train
    trainer = train.Trainer(spec)
    trainer.checked_steps()
    scopes = scope_map(trainer.step_fn.as_text())
    (n, window_s, _), prof = _profiled(
        lambda: trainer.window(spec["seconds"]))
    self_s = self_seconds(prof["ops"])
    busy = sum(self_s.values())
    by = split(self_s, scopes)
    unscoped = sorted(((t, k) for k, t in self_s.items()
                       if scopes.get(k) is None), reverse=True)[:8]

    def sync_steps():
        with jax.set_mesh(trainer.mesh), harness.span("window"):
            for _ in range(6):
                time.sleep(0.005)
                jax.block_until_ready(trainer.call())
    _, sync = _profiled(sync_steps)
    lead = leads(sync, "bench.step")
    trainer.free()
    return {
        "window_steps": n, "window_s": window_s,
        "busy_ms_per_step": 1e3 * busy / n,
        "scope_ms_per_step": {k: 1e3 * v / n for k, v in by.items()},
        "train_mixer_ms": 1e3 * by["mixer"] / n,
        "train_ffn_ms": 1e3 * by["ffn"] / n,
        "train_vocab_ms": 1e3 * (by["embed"] + by["head"]) / n,
        "train_optimizer_ms": 1e3 * by["optimizer"] / n,
        "train_unscoped_share": 100.0 * (by["unscoped"] + by["unknown"])
        / busy,
        "largest_unscoped": [[k, t / n * 1e3] for t, k in unscoped],
        "top_ops": [[k, scopes.get(k, "not in the HLO text"), t / n * 1e3]
                    for k, t in sorted(self_s.items(),
                                       key=lambda kv: -kv[1])[:12]],
        "named_ops": {k: [scopes.get(k, "not in the HLO text"),
                          self_s.get(k, 0.0)] for k in named},
        "lead_ms": lead,
    }


def study_cell(spec: dict) -> dict[str, Any]:
    import tempfile
    import time
    from pathlib import Path
    from benchmarks.chip import harness
    from benchmarks.chip.paths import study
    from repro.core import telemetry
    compiles = harness.Compiles()
    with tempfile.TemporaryDirectory(prefix="bench_study_") as tmp, \
            study.Studies(spec, Path(tmp)) as studies:
        studies.run(0)

        def window():
            n = 0
            with harness.span("window"):
                t0 = time.perf_counter()
                while n == 0 or time.perf_counter() - t0 < spec["seconds"]:
                    n += 1
                    studies.run(n)
            return n
        compiles.counting = True
        n, prof = _profiled(window)
        compiles.counting = False
    compiles.close()
    return {"window_studies": n,
            **(study_spans(telemetry.recent_spans(), n) or {}),
            "study_compile_s": compiles.seconds / n,
            "idle_unnamed_s": idle_unnamed(prof) / n,
            "window_s": (prof["hi"] - prof["lo"]) * 1e-9}


def main(argv=None) -> None:
    import argparse
    import json
    import os
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[2]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--named", default="",
                    help="comma-separated operation names whose scope "
                         "to print (train cells)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    sys.path[:0] = [str(root / "src"), str(root)]
    import time
    t0 = time.perf_counter()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from benchmarks.chip import harness
    from benchmarks.chip.run import cell_entries, require_chips
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = cell_entries(bench, args.workload)["cell"]
    devices = require_chips(cell["chips"])
    spec = {"config": harness.load("configs", cell["config"]),
            "traffic": harness.load("traffic", cell["traffic"]),
            "seed": args.seed, "seconds": args.seconds, "trace": False,
            "devices": devices, "t0": t0}
    if spec["traffic"]["kind"] == "train":
        out = train_cell(spec, [x for x in args.named.split(",") if x])
    else:
        out = study_cell(spec)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": devices[0].device_kind, **out}),
          flush=True)


if __name__ == "__main__":
    main()
