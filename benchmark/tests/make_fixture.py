"""Writes ``tpu_like.xplane.pb``: a hand-made profile in the layout of
a one-chip TPU trace, so the reduction's numbers can be checked against
values worked out by hand (``test_trace_reduce.py`` states them).

The XSpace wire format is written directly (the xplane protobuf module
is not installed): XSpace.planes=1; XPlane{id=1,name=2,lines=3,
event_metadata=4(map),stat_metadata=5(map),stats=6}; XLine{id=1,name=2,
timestamp_ns=3,events=4}; XEvent{metadata_id=1,offset_ps=2,
duration_ps=3}; XEventMetadata{id=1,name=2}; XStatMetadata{id=1,
name=2}; XStat{metadata_id=1,uint64_value=3}.

Layout (ns from the profile start; the profile runs 0..10,000):
  XLA Modules: jit_prog(11) 1000-3000, jit_prog(11) 5000-6000,
               jit_step(22) 7000-9000
  XLA Ops:     fusion.1 1000-2000, custom-call.2 1500-3000 (overlaps),
               fusion.1 5000-6000, while.9 7000-8500 holding
               fusion.3 7200-8200 (named by whole HLO lines, as a
               real trace names them)
"""

import os


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _int(num: int, v: int) -> bytes:
    return _field(num, 0, _varint(v))


def _bytes(num: int, b: bytes) -> bytes:
    return _field(num, 2, _varint(len(b)) + b)


def _plane(pid, name, lines=(), event_names=(), stat_names=(), stats=()):
    out = _int(1, pid) + _bytes(2, name.encode())
    for lid, lname, events in lines:
        body = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, 0)
        for mid, start_ns, dur_ns in events:
            body += _bytes(4, _int(1, mid) + _int(2, start_ns * 1000)
                           + _int(3, dur_ns * 1000))
        out += _bytes(3, body)
    for mid, ename in event_names:
        meta = _int(1, mid) + _bytes(2, ename.encode())
        out += _bytes(4, _int(1, mid) + _bytes(2, meta))
    for sid, sname in stat_names:
        meta = _int(1, sid) + _bytes(2, sname.encode())
        out += _bytes(5, _int(1, sid) + _bytes(2, meta))
    for sid, value in stats:
        out += _bytes(6, _int(1, sid) + _int(3, value))
    return out


START_EPOCH_NS = 5_000_000


def build() -> bytes:
    device = _plane(
        1, "/device:TPU:0",
        lines=[(1, "XLA Modules", [(1, 1000, 2000), (1, 5000, 1000),
                                   (2, 7000, 2000)]),
               (2, "XLA Ops", [(3, 1000, 1000), (4, 1500, 1500),
                               (3, 5000, 1000), (6, 7000, 1500),
                               (5, 7200, 1000)])],
        event_names=[(1, "jit_prog(11)"), (2, "jit_step(22)"),
                     (3, "%fusion.1 = f32[8,128]{1,0} fusion(f32[8] %p)"),
                     (4, "%custom-call.2 = s32[8]{0} custom-call()"),
                     (5, "%fusion.3 = f32[8]{0} fusion(f32[8] %q)"),
                     (6, "%while.9 = (s32[], f32[8]) while(%tuple)")])
    host = _plane(2, "/host:CPU",
                  lines=[(1, "python", [(1, 0, 10000)])],
                  event_names=[(1, "bench:window")])
    env = _plane(3, "Task Environment",
                 stat_names=[(1, "profile_start_time"),
                             (2, "profile_stop_time")],
                 stats=[(1, START_EPOCH_NS), (2, START_EPOCH_NS + 10_000)])
    return b"".join(_bytes(1, p) for p in (device, host, env))


if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpu_like.xplane.pb")
    with open(path, "wb") as f:
        f.write(build())
    print(path, os.path.getsize(path))
