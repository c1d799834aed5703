"""Writes ``chip_names.xplane.pb``: a profile in the layout of the TPU
v5 lite traces PR 23 recorded (``rec-msd`` / ``twostage-msd`` /
``rec-ml20m``), cut down to two dispatches and a few trainer ops. The
NAMES are the recorded ones: whole HLO lines as event names, the
``tf_op`` stat of the event's METADATA carrying the ``op_name`` with
its scopes and a trailing colon, the compiler's layout copy of the seen
bitmap with no ``tf_op`` at all, host threads all called ``python3``,
the dispatcher's stage annotations. The TIMES are round numbers, so
``test_trace_names.py`` can state every expected value by hand.

Wire format as in ``make_fixture.py``, plus XEventMetadata.stats=5 and
XStat.str_value=5.

Device plane (ns):
  XLA Modules  jit_two_topk(77) 1000-9000, 15000-23000
  XLA Ops      dispatch 1: copy.14 1000-6000 (no tf_op), fused_topk.1
               6000-8500, fusion.5 8500-8900, fusion.9 8900-9000;
               dispatch 2: the same four, 14000 later;
               while.2 30000-34000 holding fusion.7 30500-33500 (both
               user_step/solve/factor), fusion.8 34000-34500
               (vmap(gather_q))
Host plane, the dispatcher thread's line:
  dispatch.wait 500-9100, dispatch.fetch 9100-9600, batch.deliver
  9600-10100, batch.window 10100-12100, batch.form 12100-12300,
  dispatch.lock 12300-12310, dispatch.enqueue 12310-14800,
  dispatch.wait 14800-23100, dispatch.fetch 23100-23500, batch.idle
  23500-29500
a handler thread's line: the request root 0-40000 and its
  device.user_topk 2000-38000 (spans, not stages)
two deploy threads' lines: ladder.compile 50000-60000 on one,
  ladder.lower 52000-58000 on the other
"""

import os

from benchmark.tests.make_fixture import _bytes, _int

TOPK = "jit(two_topk)/stage1/topk/fused_topk/pallas_call:"
OPS = [  # (metadata id, HLO line, tf_op or None)
    (3, "%copy.14 = s32[571355,1288]{1,0:T(8,128)} copy(s32[571355,1288]"
        "{0,1:T(8,128)} %sb.1)", None),
    (4, "%fused_topk.1 = (f32[128,256]{1,0:T(8,128)}, s32[128,256]{1,0:"
        "T(8,128)}) custom-call(f32[256,64]{1,0:T(8,128)} %fusion.2), "
        "custom_call_target=\"tpu_custom_call\"", TOPK),
    (5, "%fusion.5 = bf16[256,128,64]{2,1,0:T(8,128)(2,1)} fusion(bf16"
        "[41216,64]{1,0:T(8,128)(2,1)} %copy.16), kind=kCustom",
     "jit(two_topk)/rerank/jit(_take)/gather:"),
    (6, "%fusion.9 = s32[256,32]{1,0:T(8,128)} fusion(f32[256,16]{1,0:"
        "T(8,128)} %top_k.1), kind=kLoop",
     "jit(two_topk)/rerank/pack/concatenate:"),
    (7, "%while.2 = (s32[], f32[64,64,4096]{2,1,0:T(8,128)}) while(%tuple.9)"
        ", condition=%cond.2, body=%body.2",
     "jit(_als_iterations_bucketed_impl)/while/body/user_step/solve/"
     "factor/while:"),
    (8, "%fusion.7 = f32[64,8,4096]{2,1,0:T(8,128)} fusion(f32[64,64,4096]"
        "{2,1,0:T(8,128)} %get-tuple-element.4), kind=kLoop",
     "jit(_als_iterations_bucketed_impl)/while/body/user_step/solve/"
     "factor/while/body/dynamic_slice:"),
    (9, "%fusion.8 = f32[8,1,8]{2,1,0:T(8,128)} fusion(f32[24,8]{1,0:"
        "T(8,128)} %X.1), kind=kCustom",
     "jit(users_topk_xla)/vmap(gather_q)/jit(_take)/gather:"),
]
TF_OP = 1          # stat metadata id


def _line(lid, name, events):
    body = _int(1, lid) + _bytes(2, name.encode()) + _int(3, 0)
    for mid, start_ns, dur_ns in events:
        body += _bytes(4, _int(1, mid) + _int(2, start_ns * 1000)
                       + _int(3, dur_ns * 1000))
    return _bytes(3, body)


def _event_meta(mid, name, tf_op=None):
    meta = _int(1, mid) + _bytes(2, name.encode())
    if tf_op is not None:
        meta += _bytes(5, _int(1, TF_OP) + _bytes(5, tf_op.encode()))
    return _bytes(4, _int(1, mid) + _bytes(2, meta))


def _stat_meta(sid, name):
    return _bytes(5, _int(1, sid) + _bytes(
        2, _int(1, sid) + _bytes(2, name.encode())))


def _dispatch(t):
    return [(3, t, 5000), (4, t + 5000, 2500), (5, t + 7500, 400),
            (6, t + 7900, 100)]


def build() -> bytes:
    device = _int(1, 1) + _bytes(2, b"/device:TPU:0")
    device += _line(1, "XLA Modules", [(1, 1000, 8000), (1, 15000, 8000)])
    device += _line(2, "XLA Ops", _dispatch(1000) + _dispatch(15000) + [
        (7, 30000, 4000), (8, 30500, 3000), (9, 34000, 500)])
    device += _event_meta(1, "jit_two_topk(77)")
    for mid, name, tf_op in OPS:
        device += _event_meta(mid, name, tf_op)
    device += _stat_meta(TF_OP, "tf_op")

    names = ["dispatch.wait", "dispatch.fetch", "batch.deliver",
             "batch.window", "batch.form", "dispatch.lock",
             "dispatch.enqueue", "batch.idle",
             "query POST /queries.json", "device.user_topk",
             "ladder.compile", "ladder.lower"]
    ids = {n: i + 1 for i, n in enumerate(names)}
    host = _int(1, 2) + _bytes(2, b"/host:CPU")
    host += _line(1, "python3", [
        (ids["dispatch.wait"], 500, 8600),
        (ids["dispatch.fetch"], 9100, 500),
        (ids["batch.deliver"], 9600, 500),
        (ids["batch.window"], 10100, 2000),
        (ids["batch.form"], 12100, 200),
        (ids["dispatch.lock"], 12300, 10),
        (ids["dispatch.enqueue"], 12310, 2490),
        (ids["dispatch.wait"], 14800, 8300),
        (ids["dispatch.fetch"], 23100, 400),
        (ids["batch.idle"], 23500, 6000)])
    host += _line(2, "python3", [
        (ids["query POST /queries.json"], 0, 40000),
        (ids["device.user_topk"], 2000, 36000)])
    host += _line(3, "python3", [(ids["ladder.compile"], 50000, 10000)])
    host += _line(4, "python3", [(ids["ladder.lower"], 52000, 6000)])
    for n, i in ids.items():
        host += _event_meta(i, n)
    return _bytes(1, device) + _bytes(1, host)


if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chip_names.xplane.pb")
    with open(path, "wb") as f:
        f.write(build())
    print(path, os.path.getsize(path))
