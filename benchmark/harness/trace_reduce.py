"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane
per chip (``/device:TPU:<n>``) whose line ``XLA Ops`` holds one event
per executed HLO operation and whose line ``XLA Modules`` holds one
event per executed program. Busy time is the union of the op intervals
(module intervals where a plane has no op line); everything else in the
traced window is idle. Event times are nanoseconds from the start of
the profile; the ``Task Environment`` plane carries the profile's start
on the epoch clock, which puts the harness's own host spans (taken with
``time.time()``) on the same axis so an idle gap can be named by what
the host was doing.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_intervals(iv: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def module_name(event_name: str) -> str:
    """``jit_prog(1234567)`` -> ``jit_prog``."""
    return _MODULE_SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO line (``%fusion.7 =
    f32[...] fusion(...)``): keep what stands before the ``=``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> Dict[str, float]:
    """Self time per op name inside [lo, hi]: an op's duration minus
    the ops nested inside it (a ``while`` holds its body's fusions on
    the same line), so that the list adds up to the busy time instead
    of counting a loop and its body twice."""
    out: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            out[stack[-1][0]] = out.get(stack[-1][0], 0.0) - (e - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, e))
    return out


def _clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if min(e, hi) > max(s, lo)]


def reduce_profile(profile, window_ns: Optional[Tuple[float, float]] = None,
                   gap_label: Optional[Callable[[float, float], str]] = None,
                   top: int = 10) -> Optional[Dict[str, Any]]:
    """Reduce a ``ProfileData``. ``window_ns`` clips to a slice of the
    profile (ns from its start). ``gap_label(start_epoch_s,
    end_epoch_s)`` names an idle gap from the harness's spans. Returns
    None when no device plane holds an event (nothing ran on a chip)."""
    start_epoch_ns = stop_epoch_ns = None
    planes = []
    for plane in profile.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_epoch_ns = stats.get("profile_start_time")
            stop_epoch_ns = stats.get("profile_stop_time")
        elif _DEVICE_PLANE.match(plane.name):
            planes.append(plane)
    per_plane = []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        ops = [(op_name(e.name), float(e.start_ns),
                float(e.start_ns + e.duration_ns))
               for e in lines[OPS_LINE].events] if OPS_LINE in lines else []
        mods = [(module_name(e.name), float(e.start_ns),
                 float(e.start_ns + e.duration_ns))
                for e in lines[MODULES_LINE].events] \
            if MODULES_LINE in lines else []
        if ops or mods:
            per_plane.append((plane.name, ops, mods))
    if not per_plane:
        return None
    if window_ns is None:
        if start_epoch_ns is not None and stop_epoch_ns is not None:
            window_ns = (0.0, float(stop_epoch_ns - start_epoch_ns))
        else:
            window_ns = (
                min(s for _, o, m in per_plane for _, s, _ in (o or m)),
                max(e for _, o, m in per_plane for _, _, e in (o or m)))
    lo, hi = window_ns
    busy = []
    op_time: Dict[str, float] = {}
    modules: Dict[str, Dict[str, float]] = {}
    gaps: List[Tuple[float, float]] = []
    for pi, (_, ops, mods) in enumerate(per_plane):
        iv = union_intervals(_clip([(s, e) for _, s, e in (ops or mods)],
                                   lo, hi))
        busy.append(sum(e - s for s, e in iv))
        for name, d in self_times(ops, lo, hi).items():
            op_time[name] = op_time.get(name, 0.0) + d
        for name, s, e in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                m = modules.setdefault(name, {"seconds": 0.0, "count": 0})
                m["seconds"] += d / 1e9
                m["count"] += 1
        if pi == 0:
            edges = [lo] + [t for s, e in iv for t in (s, e)] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n = len(per_plane)
    for m in modules.values():
        m["seconds"] /= n
        m["count"] /= n
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    named = []
    for s, e in longest:
        label = "unlabelled"
        if gap_label is not None and start_epoch_ns is not None:
            label = gap_label((start_epoch_ns + s) / 1e9,
                              (start_epoch_ns + e) / 1e9)
        named.append([label, (e - s) / 1e9])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "planes": [p for p, _, _ in per_plane],
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "modules": modules,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_sorted],
        "idle_gaps": named,
        "profile_start_epoch_s": None if start_epoch_ns is None
        else start_epoch_ns / 1e9,
    }


def reduce_file(path: str, **kw) -> Optional[Dict[str, Any]]:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), **kw)
