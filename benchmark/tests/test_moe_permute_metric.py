"""``moe_permute_device_share`` on hand-made readers: the by-hand share
of both programs of the traced call, the step's alone where the encode
was not traced, and nothing for a cell without a sequence trace."""

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import seq_trace

NAME = "moe_permute_device_share"


def _module(seconds, count, scopes):
    return {"seconds": seconds, "count": count, "scopes": scopes,
            "kernels": {}}


def _readers(**modules):
    return {"work": {"kind": "seq_train_calls", "calls": 3,
                     "traced_calls": 1},
            "device": {"kind": "TPU v5 lite"}, "trace_scopes": modules}


STEP = {"moe/combine": 6.0, "moe/dispatch": 3.0, "moe/gmm_down": 20.0,
        "moe/gmm_down/gmm_drhs": 11.0, "moe/router": 1.0, "adam": 9.0,
        "": 10.0}                                  # 9 of 60
ENCODE = {"moe/combine": 5.0, "moe/dispatch": 3.0, "moe/gmm_gate_up": 7.0,
          "attn/flash": 3.0, "": 2.0}              # 8 of 20


def _read(r):
    return cells.load_layer_metric(NAME)(r)


def test_share_of_both_programs():
    r = _readers(**{seq_trace.STEP_MODULE: _module(60.0, 2, STEP),
                    seq_trace.ENCODE_MODULE: _module(20.0, 5, ENCODE)})
    assert _read(r) == pytest.approx(100 * (9 + 8) / (60 + 20))


def test_step_alone_where_the_encode_is_missing():
    r = _readers(**{seq_trace.STEP_MODULE: _module(60.0, 2, STEP)})
    assert _read(r) == pytest.approx(100 * 9 / 60)
    # a module that was named but never ran counts as missing
    r["trace_scopes"][seq_trace.ENCODE_MODULE] = _module(0.0, 0, {})
    assert _read(r) == pytest.approx(100 * 9 / 60)


def test_backward_scopes_count_under_their_forward_scope():
    step = dict(STEP)
    step["moe/combine/mul"] = 3.0                  # 12 of 63
    r = _readers(**{seq_trace.STEP_MODULE: _module(63.0, 1, step)})
    assert _read(r) == pytest.approx(100 * 12 / 63)


@pytest.mark.parametrize("readers", [
    # another cell's readers: a trainer without a sequence trace
    {"work": {"kind": "train_calls", "calls": 2},
     "device": {"kind": "TPU v5 lite"}, "trace": None},
    # a serving cell that has scopes of its own programs
    {"work": {"kind": "http_sessions"},
     "trace_scopes": {"jit_sess_extend": _module(1.0, 1, {"moe/combine": 1.0})}},
    # the sequence cell untraced, and traced with neither program
    _readers() | {"trace_scopes": None},
    _readers(jit_prog=_module(1.0, 1, {})),
    # a program whose ops carry no time
    _readers(**{seq_trace.STEP_MODULE: _module(1.0, 1, {})}),
])
def test_nothing_where_there_is_nothing_to_read(readers):
    assert _read(readers) is None


def test_the_cell_and_no_other_lists_it():
    bench = cells.load_benchmark()
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == [bench["per_layer"][-1]]
    assert entry[0] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "expert layer",
        "moves": "train_pairs_per_s",
        "workloads": ["seqrec-olmoe-msd.train"]}
