"""The readers a run collects for the per-layer metrics: snapshots of
the program's own counters at both ends of the measured window, the
flight recorder's records inside it, memory statistics, the reduced
trace and the harness's spans. Each per-layer metric is a small
function of this bundle (``benchmark/layer_metrics/<name>.py``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional


def _lane_stats() -> Dict[str, Dict[str, Any]]:
    from predictionio_tpu.ops.serving import batcher_stats

    return {s["batcher"]: s for s in batcher_stats()}


def _query_histogram() -> Dict[str, Any]:
    """Bucket counts of ``pio_query_seconds`` for the default engine
    variant (the one the harness deploys)."""
    from predictionio_tpu.utils import metrics
    from predictionio_tpu.workflow.create_server import ServerConfig

    child = metrics.QUERY_LATENCY.child(
        variant=ServerConfig().engine_variant)
    return {"bounds": list(child.bounds), "counts": child.snapshot()[0]}


def _counters() -> Dict[str, float]:
    from predictionio_tpu.utils import metrics

    return {
        "jit_compiles": metrics.JIT_COMPILES.value(),
        "jit_compile_seconds": metrics.JIT_COMPILE_SECONDS.value(),
        "batchpredict_scored":
            metrics.BATCHPREDICT_QUERIES.value(status="scored"),
    }


def snapshot() -> Dict[str, Any]:
    return {"t": time.time(), "lanes": _lane_stats(),
            "query_hist": _query_histogram(),
            "counters": _counters(), "memory": memory_bytes()}


def flight_between(t0: float, t1: float) -> List[Dict[str, Any]]:
    from predictionio_tpu.utils import device_telemetry

    recs = device_telemetry.recorder().snapshot(limit=1 << 30)
    return [r for r in recs if t0 <= r["ts"] < t1]


def memory_bytes() -> Optional[Dict[str, int]]:
    """HBM of the fullest local device at one moment (the snapshot at
    the window's end is the one reported, while the store or the tables
    are still held and the programs loaded): ``in_use_peak`` (the allocator's ``peak_bytes_in_use``: arrays, so
    the resident tables and store plus results), ``reserved``
    (``bytes_reserved``: the pool the runtime sets aside for loaded
    programs' temporaries, which ``bytes_in_use`` does not count; on
    the v5e the two are disjoint, free memory being the limit less
    both) and ``peak``, their sum. The pool is set aside when a program
    is loaded, in set-up, and held from then on, so it was there when
    the arrays peaked: the sum is one moment's figure, not two peaks
    from different times. None where the backend reports nothing (the
    CPU)."""
    import jax

    best = None
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        m = {"in_use_peak": int(stats["peak_bytes_in_use"]),
             "reserved": int(stats.get("bytes_reserved", 0))}
        m["peak"] = m["in_use_peak"] + m["reserved"]
        if best is None or m["peak"] > best["peak"]:
            best = m
    return best


class TraceSlice:
    """A ``jax.profiler`` trace of one slice of the window, with the
    Python tracer off (it records every call of every handler thread
    and would swamp both the host and the file)."""

    def __init__(self, directory: str):
        self.directory = directory
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None
        self._timer: Optional[threading.Thread] = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.started = time.time()

    def stop(self) -> None:
        import jax

        self.stopped = time.time()
        jax.profiler.stop_trace()

    def run_at(self, start_epoch: float, seconds: float) -> None:
        """Trace [start_epoch, start_epoch + seconds) from a helper
        thread while the caller keeps driving the window."""
        def body():
            time.sleep(max(0.0, start_epoch - time.time()))
            self.start()
            time.sleep(max(0.0, start_epoch + seconds - time.time()))
            self.stop()

        self._timer = threading.Thread(target=body, name="bench-trace",
                                       daemon=True)
        self._timer.start()

    def join(self, timeout: float = 120.0) -> None:
        if self._timer is not None:
            self._timer.join(timeout)
            if self._timer.is_alive():
                raise RuntimeError("the trace slice did not finish")

    def reduce(self, gap_label: Optional[Callable[[float, float], str]]
               ) -> Optional[Dict[str, Any]]:
        from benchmark.harness import trace_reduce

        path = trace_reduce.find_xplane(self.directory)
        if path is None:
            return None
        return trace_reduce.reduce_file(path, gap_label=gap_label)
