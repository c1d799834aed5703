"""What the program's two recorders hold for a run: the span trees'
stage summaries (``tracing.trace_buffer().stage_summaries``) and the
flight records' stage stamps. The per-layer metrics that read them are
small functions of these helpers. A program without them (an older
commit run under this benchmark) gives empty lists, and the metric is
left out of the line."""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Optional


def roots(root: str, t0: float = 0.0, t1: float = float("inf")
          ) -> List[Dict[str, Any]]:
    """Stage summaries of the local roots named ``root`` that started in
    [t0, t1) (epoch seconds), oldest first."""
    from predictionio_tpu.utils import tracing

    read = getattr(tracing.trace_buffer(), "stage_summaries", None)
    return read(t0, t1, root=root) if read is not None else []


def window_roots(r, root: str) -> List[Dict[str, Any]]:
    return roots(root, r["before"]["t"], r["after"]["t"])


def self_s(summary: Dict[str, Any], *names: str) -> float:
    """Seconds of self time of the named spans in one summary."""
    return sum(summary["selfUs"].get(n, 0.0) for n in names) / 1e6


def deploy_self_s(*names: str) -> Optional[float]:
    """The named spans' self time in the run's ``pio.deploy`` root (the
    last one: a run deploys once, in set-up)."""
    deploys = roots("pio.deploy")
    return self_s(deploys[-1], *names) if deploys else None


def batched(r) -> List[Dict[str, Any]]:
    """The window's dispatches that went through a batching dispatcher
    and carry stage stamps."""
    return [x for x in r.get("flight") or []
            if x.get("gapUs") is not None]


def median_of(items, value: Callable[[Any], Optional[float]]
              ) -> Optional[float]:
    values = [v for v in map(value, items) if v is not None]
    return statistics.median(values) if values else None
