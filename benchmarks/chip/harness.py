"""What every run path shares: the cell's files, host spans, the count
of compilations, the traced window and the device's memory peak."""
from __future__ import annotations

import contextlib
import json
import tempfile
from pathlib import Path
from typing import Any, Iterator

HERE = Path(__file__).resolve().parent

#: jax.monitoring duration events of one compilation: tracing to a
#: jaxpr, lowering to MLIR, and the backend compile (which includes the
#: lookup in, and load from, the persistent compilation cache)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def load(kind: str, name: str) -> dict[str, Any]:
    """``<kind>/<name>.json`` under the benchmark's directory."""
    return json.loads((HERE / kind / f"{name}.json").read_text())


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Compiles:
    """Counts compilations while ``counting`` is set: backend compiles
    (persistent-cache hits included), persistent-cache misses, and the
    seconds spent tracing, lowering and compiling."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.counting = False
        self.backend = self.misses = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if not self.counting:
            return
        if event in (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT):
            self.seconds += secs
        if event == BACKEND_EVENT:
            self.backend += 1

    def _event(self, event: str, **_: Any) -> None:
        if self.counting and event == CACHE_MISS_EVENT:
            self.misses += 1

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


@contextlib.contextmanager
def traced(on: bool, into: dict) -> Iterator[None]:
    """Profile the block when ``on``; the reduced trace lands in
    ``into["trace"]`` (None where it holds no device operation)."""
    import jax
    from . import trace as trace_mod
    if not on:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans only, no Python calls
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            yield
        into["trace"] = trace_mod.reduce(trace_mod.find(tmp))


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices`` (None where the
    backend does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
