"""Names for what ``trace_reduce.py`` can only number: device self time
grouped by the ``jax.named_scope`` an op was traced under, and each
long idle gap of the device named by what the program's own thread was
doing in it.

Both come from what the program writes into the profiler's trace since
PR 23. Scope names ride in every HLO instruction's ``op_name``
metadata, which a TPU trace carries as the stat ``tf_op`` (the name
dates from TensorFlow) of each ``XLA Ops`` event's METADATA
(``jit(two_topk)/stage1/topk/fused_topk/pallas_call:``);
``jax.profiler.ProfileData`` shows an event's own stats only, so the
few XSpace fields needed are read from the wire format here
(:func:`read_xspace`; the xplane protobuf module is not installed).
Every span and dispatcher stage is a ``TraceAnnotation`` on its
thread's line of the ``/host:CPU`` plane, on the same clock as the
device planes.

Called by nothing in the harness yet; a ``benchmark`` PR wires
``reduce_file`` in beside ``trace_reduce.reduce_file`` (``breakdown``
gains ``device_scopes`` and named ``idle_gaps``). Until then it is run
by hand on a kept xplane:

    python -m benchmark.harness.trace_names <file.xplane.pb>
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import trace_reduce

# the stat of an ``XLA Ops`` event's metadata that holds the
# instruction's HLO ``op_name``, scopes and all, with a trailing colon
SCOPE_STAT = "tf_op"
HOST_PLANE = "/host:CPU"
NO_SCOPE = "(no scope)"

# the program's stage annotations: a dispatcher thread's eight stages,
# and the spans that tile a train() call or a deploy()
STAGE_PREFIXES = ("batch.", "dispatch.", "als.", "ladder.", "store.",
                  "deploy.")

_TRANSFORM = re.compile(r"^[a-z_]+\((.*)\)$")
# path components that are program structure, not a scope someone named
_STRUCTURE = {"while", "body", "cond", "branch", "closed_call",
              "checkpoint", "remat", "custom_jvp_call", "custom_vjp_call"}


# -- the XSpace fields this file needs, from the wire format -----------------

@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[int, float, float]]   # (metadata id, start, end) ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_names: Dict[int, str]
    event_stats: Dict[int, Dict[str, Any]]   # metadata id -> {stat: value}


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        c = b[i]
        i += 1
        n |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return n, i


def _fields(b: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: ints for varints, bytes
    for length-delimited and fixed-width fields."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield num, v


def _map_entry(b: bytes) -> Dict[int, Any]:
    return dict(_fields(b))


def read_xspace(data: bytes) -> List[Plane]:
    """XSpace.planes=1; XPlane{name=2, lines=3, event_metadata=4 (map),
    stat_metadata=5 (map)}; XLine{name=2, timestamp_ns=3, events=4};
    XEvent{metadata_id=1, offset_ps=2, duration_ps=3};
    XEventMetadata{id=1, name=2, stats=5}; XStatMetadata{id=1, name=2};
    XStat{metadata_id=1, uint64=3, int64=4, str=5, ref=7}."""
    planes = []
    for num, raw in _fields(data):
        if num != 1:
            continue
        fields = list(_fields(raw))
        stat_names: Dict[int, str] = {}
        for n, v in fields:
            if n == 5:
                meta = dict(_fields(_map_entry(v)[2]))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        plane = Plane("", [], {}, {})
        for n, v in fields:
            if n == 2:
                plane.name = v.decode()
            elif n == 4:
                meta = list(_fields(_map_entry(v)[2]))
                mid = next((x for k, x in meta if k == 1), 0)
                plane.event_names[mid] = next(
                    (x for k, x in meta if k == 2), b"").decode()
                stats = {}
                for k, x in meta:
                    if k != 5:
                        continue
                    st = dict(_fields(x))
                    value = st.get(5, st.get(3, st.get(4)))
                    if 7 in st:
                        value = stat_names.get(st[7], "")
                    if isinstance(value, bytes):
                        value = value.decode(errors="replace")
                    stats[stat_names.get(st.get(1, 0), "")] = value
                plane.event_stats[mid] = stats
            elif n == 3:
                lf = list(_fields(v))
                t0 = next((x for k, x in lf if k == 3), 0)
                events = []
                for k, x in lf:
                    if k == 4:
                        e = dict(_fields(x))
                        s = t0 + e.get(2, 0) / 1e3
                        events.append((e.get(1, 0), s,
                                       s + e.get(3, 0) / 1e3))
                plane.lines.append(Line(
                    next((x for k, x in lf if k == 2), b"").decode(),
                    events))
        planes.append(plane)
    return planes


def scope_of(op_name: str, depth: int = 3) -> str:
    """``jit(two_topk)/stage1/topk/jit(_take)/gather`` -> ``stage1/topk``:
    the named scopes of an ``op_name``, outermost first, at most
    ``depth`` of them. The head (``jit(<program>)``), the tail (the
    primitive), inner ``jit(...)`` calls and control-flow components
    (``while/body``) are structure; a transformed scope
    (``vmap(gather_q)``) reads as the scope. ``(no scope)`` where
    nothing is left."""
    parts = [p for p in op_name.split("/") if p]
    if len(parts) < 2:
        return NO_SCOPE
    scopes: List[str] = []
    for part in parts[1:-1]:
        m = _TRANSFORM.match(part)
        while m is not None and not part.startswith(("jit(", "pjit(")):
            part = m.group(1)
            m = _TRANSFORM.match(part)
        if part.startswith(("jit(", "pjit(")) or part in _STRUCTURE \
                or not part:
            continue
        scopes.append(part)
    return "/".join(scopes[:depth]) if scopes else NO_SCOPE


def _device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if trace_reduce._DEVICE_PLANE.match(p.name)]


def _line(plane: Plane, name: str) -> Optional[Line]:
    return next((ln for ln in plane.lines if ln.name == name), None)


def device_scopes(planes: Sequence[Plane], depth: int = 3
                  ) -> Dict[str, Any]:
    """Device SELF time (a ``while`` does not count its body twice) of
    every ``XLA Ops`` event, grouped by its scope. An op that carries
    no scope is grouped by its op family instead: ``(no scope) copy``
    is what the compiler inserted for a layout and no traced line
    stands behind, so it inherits none; it is reported as such, never
    folded into a neighbour. Seconds, averaged over the device planes,
    largest first; ``share`` is of all op self time."""
    total: Dict[str, float] = {}
    n = 0
    for plane in _device_planes(planes):
        line = _line(plane, trace_reduce.OPS_LINE)
        if line is None or not line.events:
            continue
        n += 1
        ops = []
        for mid, s, e in line.events:
            scope = scope_of(str(plane.event_stats.get(mid, {}).get(
                SCOPE_STAT) or ""), depth)
            if scope == NO_SCOPE:
                family = re.sub(r"[.\d]+$", "", trace_reduce.op_name(
                    plane.event_names.get(mid, "")))
                scope = f"{NO_SCOPE} {family}"
            ops.append((scope, s, e))
        lo = min(s for _, s, _ in ops)
        hi = max(e for _, _, e in ops)
        for name, d in trace_reduce.self_times(ops, lo, hi).items():
            total[name] = total.get(name, 0.0) + d
    if not n:
        return {"scopes": [], "self_s": 0.0}
    whole = sum(total.values()) / n / 1e9
    rows = sorted(((k, v / n / 1e9) for k, v in total.items()),
                  key=lambda kv: -kv[1])
    return {"self_s": whole,
            "scopes": [[k, v, (v / whole if whole else 0.0)]
                       for k, v in rows]}


def stage_events(planes: Sequence[Plane],
                 prefixes: Sequence[str] = STAGE_PREFIXES
                 ) -> List[Tuple[str, float, float]]:
    """(name, start ns, end ns) of the program's stage annotations on
    every host thread's line."""
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for mid, s, e in line.events:
                name = plane.event_names.get(mid, "")
                if name.startswith(tuple(prefixes)):
                    out.append((name, s, e))
    return out


def name_gap(stages: Sequence[Tuple[str, float, float]], lo: float,
             hi: float) -> Tuple[str, float]:
    """The stage annotation that covers most of the gap [lo, hi], and
    the share of the gap it covers. Where one stage nests inside
    another (``ladder.lower`` inside ``ladder.compile``), the inner
    one's time is taken off the outer one's, as for self time."""
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in stages
              if min(e, hi) > max(s, lo)]
    cover = trace_reduce.self_times(inside, lo, hi)
    if not cover:
        return "unannotated", 0.0
    best = max(cover.items(), key=lambda kv: kv[1])
    return best[0], best[1] / (hi - lo)


def idle_gaps(planes: Sequence[Plane], top: Optional[int] = 5
              ) -> List[List[Any]]:
    """The ``top`` longest (None: all) idle gaps of the first device
    plane between its first and last op, each [stage name, seconds,
    share of the gap that stage covers], longest first."""
    devices = _device_planes(planes)
    if not devices:
        return []
    line = _line(devices[0], trace_reduce.OPS_LINE) \
        or _line(devices[0], trace_reduce.MODULES_LINE)
    if line is None or not line.events:
        return []
    iv = trace_reduce.union_intervals([(s, e) for _, s, e in line.events])
    gaps = sorted(((a[1], b[0]) for a, b in zip(iv, iv[1:])),
                  key=lambda g: g[0] - g[1])[:top]
    stages = stage_events(planes)
    out = []
    for s, e in gaps:
        name, share = name_gap(stages, s, e)
        out.append([name, (e - s) / 1e9, share])
    return out


def idle_by_stage(gaps: Sequence[Sequence[Any]]) -> List[List[Any]]:
    """ALL idle time (``idle_gaps(planes, top=None)``) by the stage
    each gap was given to: [stage name, seconds, gaps], largest
    first."""
    total: Dict[str, List[float]] = {}
    for name, seconds, _ in gaps:
        t = total.setdefault(name, [0.0, 0])
        t[0] += seconds
        t[1] += 1
    return sorted(([k, v[0], v[1]] for k, v in total.items()),
                  key=lambda r: -r[1])


def reduce_planes(planes: Sequence[Plane], top: int = 16
                  ) -> Dict[str, Any]:
    scopes = device_scopes(planes)
    gaps = idle_gaps(planes, top=None)
    return {"scope_stat": SCOPE_STAT,
            "device_self_s": scopes["self_s"],
            "device_scopes": scopes["scopes"][:top],
            "idle_gaps": gaps[:5],
            "idle_by_stage": idle_by_stage(gaps)[:top],
            "stages_seen": sorted({n for n, _, _ in stage_events(planes)})}


def reduce_file(path: str, **kw) -> Dict[str, Any]:
    with open(path, "rb") as f:
        return reduce_planes(read_xspace(f.read()), **kw)


def main(argv=None) -> int:
    (path,) = (argv if argv is not None else sys.argv[1:])
    print(json.dumps(reduce_file(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
