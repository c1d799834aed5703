"""The sequence cell's reading of a device trace: device SELF time of
the step program and of the encode program apart, each by the
``jax.named_scope`` an op was traced under, with the Pallas kernels
(custom calls) under a scope counted apart from the XLA ops around
them. Built on ``trace_names.read_xspace`` (scope names ride in each
HLO instruction's ``op_name``) and ``trace_reduce.self_times`` (a
``while`` does not count its body twice)."""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.harness import trace_names, trace_reduce

STEP_MODULE = "jit_seq_train_step"
ENCODE_MODULE = "jit_seq_encode"
_WRAPPERS = re.compile(r"^(?:[a-z_]+\()+")


def scope_path(op_name: str) -> str:
    """``jit(seq_train_step)/while/body/closed_call/transpose(jvp(moe/
    gmm_down))/gmm_drhs/jit(tgmm)/pallas_call`` -> ``moe/gmm_down/
    gmm_drhs``: the named scopes, outermost first. A transform wraps
    a whole path (``transpose(jvp(moe/gmm_down))``), so its opening
    stands on the first component and its closing on the last: both are
    taken off each component. Program names, inner ``jit`` calls,
    control flow and the primitive are structure."""
    parts = [p for p in op_name.rstrip(":").split("/") if p]
    out: List[str] = []
    for part in parts[1:-1]:
        if "jit(" in part:
            continue
        part = _WRAPPERS.sub("", part).rstrip(")")
        if part and part not in trace_names._STRUCTURE:
            out.append(part)
    return "/".join(out)


def by_module_and_scope(planes: Sequence[trace_names.Plane]
                        ) -> Optional[Dict[str, Any]]:
    """``{module: {"seconds", "count", "scopes": {scope: s}, "kernels":
    {scope: s}}}`` of the first device plane that has ops: an op
    belongs to the module event it started inside."""
    for plane in trace_names._device_planes(planes):
        ops = trace_names._line(plane, trace_reduce.OPS_LINE)
        mods = trace_names._line(plane, trace_reduce.MODULES_LINE)
        if ops is None or mods is None or not ops.events:
            continue
        spans: List[Tuple[float, float, str]] = sorted(
            (s, e, trace_reduce.module_name(
                plane.event_names.get(mid, ""))) for mid, s, e in mods.events)
        starts = [s for s, _, _ in spans]
        out: Dict[str, Any] = {}
        for s, e, name in spans:
            m = out.setdefault(name, {"seconds": 0.0, "count": 0,
                                      "scopes": {}, "kernels": {}})
            m["seconds"] += (e - s) / 1e9
            m["count"] += 1
        labelled = []
        for mid, s, e in ops.events:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= spans[i][1]:
                continue
            full = plane.event_names.get(mid, "")
            scope = scope_path(str(plane.event_stats.get(mid, {}).get(
                trace_names.SCOPE_STAT) or ""))
            kind = "K" if " custom-call(" in full else "X"
            labelled.append((f"{spans[i][2]}\t{scope}\t{kind}", s, e))
        lo = min(s for _, s, _ in labelled)
        hi = max(e for _, _, e in labelled)
        for label, d in trace_reduce.self_times(labelled, lo, hi).items():
            module, scope, kind = label.split("\t")
            m = out[module]
            m["scopes"][scope] = m["scopes"].get(scope, 0.0) + d / 1e9
            if kind == "K":
                m["kernels"][scope] = m["kernels"].get(scope, 0.0) + d / 1e9
        return out
    return None


def reduce_file(path: str) -> Optional[Dict[str, Any]]:
    with open(path, "rb") as f:
        return by_module_and_scope(trace_names.read_xspace(f.read()))


def under(times: Dict[str, float], *prefixes: str) -> float:
    """Seconds under the scopes that start with one of ``prefixes``
    (a backward op's scope starts with its forward scope)."""
    return sum(v for k, v in times.items() if k.startswith(prefixes))
