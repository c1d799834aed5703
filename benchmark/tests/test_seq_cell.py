"""The sequence cell's own pieces: ``shapes_seq.py`` against values
worked out by hand, the trace reader on hand-made planes, each new
per-layer metric on hand-made readers (and returning nothing for a
cell, a program or a run without what it reads), the oracle held to
the package's reference, and the configuration held to the published
``config.json``. The cell's rehearsal itself is
``test_rehearse.py``'s, which runs every workload."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import cell as cells
from benchmark.harness import seq_trace, shapes, shapes_seq, trace_names

CELL = "seqrec-olmoe-msd.train"
PEAK = shapes.peaks("TPU v5 lite")


# -- shapes ------------------------------------------------------------------

def test_moe_gmm_by_hand():
    # 10 tokens, hidden 4, width 3, 5 experts, 2 a token: 20 pairs;
    # three matmuls of 4 x 3 each: 2 * 20 * 3 * 12 = 1440 flops
    need = shapes_seq.moe_gmm(10, 4, 3, 5, 2)
    assert need["flops"] == 1440
    # weights 3 * 5 * 12 * 2 B = 360; rows 20 * 2 B * (4 + 4 * 3 + 4) = 800
    assert need["bytes"] == 360 + 800
    train = shapes_seq.moe_gmm(10, 4, 3, 5, 2, passes=3)
    assert train["flops"] == 3 * 1440 and train["bytes"] == 3 * 1160


def test_olmoe_expert_flops_per_token_are_the_issues():
    # ISSUE 25: experts 8 x 3 x 2048 x 1024 x 2 = 100.7 MFLOP a token
    need = shapes_seq.moe_gmm(1, 2048, 1024, 64, 8)
    assert need["flops"] == 8 * 3 * 2048 * 1024 * 2 == 100_663_296


def test_segment_pairs_and_attention_by_hand():
    # segments of 1, 2 and 3 tokens: 1 + 3 + 6 pairs
    assert shapes_seq.segment_pairs([1, 2, 3]) == 10
    need = shapes_seq.attention(10, 6, n_heads=2, head_dim=4)
    assert need["flops"] == 2 * 2 * 10 * 2 * 4          # qk and pv
    assert need["bytes"] == 4 * 6 * 2 * 4 * 2           # q, k, v, out
    # one 4,096-token history is the causal triangle ISSUE 25 counts:
    # 16.8 MFLOP a token over 16 heads of 128
    one = shapes_seq.attention(shapes_seq.segment_pairs([4096]), 4096,
                               16, 128)
    assert one["flops"] / 4096 == pytest.approx(16.8e6, rel=0.01)
    # seventy histories of 59 in the same row need 1/68 of that
    many = shapes_seq.attention(shapes_seq.segment_pairs([59] * 69), 4071,
                                16, 128)
    assert many["flops"] < one["flops"] / 60


BLOCK = {"hidden": 4, "n_heads": 2, "head_dim": 2, "n_layers": 1,
         "n_experts": 5, "expert_width": 3, "per_token": 2}


def test_model_flops_by_hand():
    # 10 tokens, 12 pairs, 7 targets, 3 negatives
    proj = 2 * 10 * 4 * 4 * 4            # q, k, v, o: 1280
    router = 2 * 10 * 4 * 5              # 400
    experts = 1440
    attn = 2 * 2 * 12 * 2 * 2            # 192
    fwd = proj + router + experts + attn
    assert shapes_seq.model_flops(10, 12, 7, BLOCK, 3, passes=1) == fwd
    logits = 2 * 7 * 4 * 4               # (1 + 3) logits of width 4: 224
    assert shapes_seq.model_flops(10, 12, 7, BLOCK, 3, passes=3) \
        == 3 * (fwd + logits)


# -- the trace reader ----------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(seq_train_step)/while/body/closed_call/transpose(jvp(moe/"
     "gmm_down))/gmm_drhs/jit(tgmm)/pallas_call", "moe/gmm_down/gmm_drhs"),
    ("jit(seq_train_step)/adam/mul:", "adam"),
    ("jit(seq_encode)/moe/gmm_gate_up/pallas_call", "moe/gmm_gate_up"),
    ("jit(seq_train_step)/while/body/closed_call/jvp(attn/flash)/"
     "vmap(jit(wrapped))/custom_jvp_call/pallas_call", "attn/flash"),
    ("jit(seq_train_step)/while/body/closed_call/jvp(loss)/reduce_sum",
     "loss"),
    ("jit(seq_train_step)/convert_element_type", ""),
    ("", ""),
])
def test_scope_path(op_name, want):
    assert seq_trace.scope_path(op_name) == want


def _planes():
    """A device plane: two step programs of 100 ns and one encode of
    50; in each step a gmm kernel of 30 under moe/gmm_down, an XLA op
    of 10 under moe/router, one of 20 under adam; in the encode a gmm
    kernel of 40 and a flash kernel of 5."""
    names = {1: "jit_seq_train_step(7)", 2: "jit_seq_encode(9)",
             10: "%gmm.1 = bf16[8,8] custom-call(%a)",
             11: "%fusion.2 = f32[8] fusion(%b)",
             12: "%fusion.3 = f32[8] fusion(%c)",
             13: "%splash.4 = bf16[8] custom-call(%d)"}
    scope = {10: "jit(seq_train_step)/while/body/jvp(moe/gmm_down)/"
                 "pallas_call:",
             11: "jit(seq_train_step)/while/body/jvp(moe/router)/dot:",
             12: "jit(seq_train_step)/adam/mul:",
             13: "jit(seq_encode)/attn/flash/pallas_call:"}
    stats = {k: {trace_names.SCOPE_STAT: v} for k, v in scope.items()}
    mods = trace_names.Line("XLA Modules", [(1, 0, 100), (1, 200, 300),
                                            (2, 400, 450)])
    ops = trace_names.Line("XLA Ops", [
        (10, 5, 35), (11, 40, 50), (12, 60, 80),
        (10, 205, 235), (11, 240, 250), (12, 260, 280),
        (10, 400, 440), (13, 442, 447)])
    return [trace_names.Plane("/device:TPU:0", [mods, ops], names, stats)]


def test_trace_reader_separates_programs_scopes_and_kernels():
    t = seq_trace.by_module_and_scope(_planes())
    step, enc = t["jit_seq_train_step"], t["jit_seq_encode"]
    assert step["count"] == 2 and step["seconds"] == pytest.approx(200e-9)
    assert step["scopes"] == pytest.approx(
        {"moe/gmm_down": 60e-9, "moe/router": 20e-9, "adam": 40e-9})
    assert step["kernels"] == pytest.approx({"moe/gmm_down": 60e-9})
    # the encode's gmm op carries the step's scope string in this toy;
    # it is booked to the program it ran inside
    assert enc["kernels"] == pytest.approx({"moe/gmm_down": 40e-9,
                                            "attn/flash": 5e-9})
    assert seq_trace.under(step["scopes"], "moe/") == pytest.approx(80e-9)
    assert seq_trace.by_module_and_scope([]) is None


# -- the per-layer metrics -----------------------------------------------------

def _readers():
    work = {
        "kind": "seq_train_calls", "calls": 3, "traced_calls": 1,
        "steps": 2, "step_tokens": 10, "targets_per_call": 14,
        "encode_tokens": 20, "segment_lengths": np.array([4, 6, 5, 5]),
        "rows": 2, "row_len": 10, "pad_share": 0.125, "n_negatives": 3,
        "expert_load": {"max": 12.0, "mean": 8.0}, "block": BLOCK}
    return {"work": work, "device": {"kind": "TPU v5 lite"},
            "trace_scopes": seq_trace.by_module_and_scope(_planes()),
            "before": {"t": 0.0}, "after": {"t": 1e12}}


def _read(name, r):
    return cells.load_layer_metric(name)(r)


def test_metrics_on_hand_made_readers():
    r = _readers()
    assert _read("seq_step_device_ms", r) == pytest.approx(100e-9 * 1e3)
    assert _read("moe_device_share", r) == pytest.approx(100 * 80 / 120)
    assert _read("expert_load_max_over_mean", r) == 1.5
    assert _read("pack_pad_share", r) == 12.5
    # 20 step tokens forward and backward, pairs at the layout's mean
    pairs = shapes_seq.segment_pairs([4, 6, 5, 5]) / 20 * 20
    flops = shapes_seq.model_flops(20, pairs, 14, BLOCK, 3, passes=3)
    assert _read("seq_mfu", r) == pytest.approx(
        100 * flops / (200e-9 * PEAK["bf16_flops_per_s"]))
    need = [shapes_seq.moe_gmm(20, 4, 3, 5, 2, passes=3),
            shapes_seq.moe_gmm(20, 4, 3, 5, 2, passes=1)]
    least = shapes.least_time(sum(n["flops"] for n in need),
                              sum(n["bytes"] for n in need), PEAK)["seconds"]
    assert _read("moe_gmm_roofline", r) == pytest.approx(
        100 * least / (60e-9 + 40e-9))
    att = [shapes_seq.attention(pairs, 20, 2, 2, passes=p) for p in (3, 1)]
    least = shapes.least_time(sum(n["flops"] for n in att),
                              sum(n["bytes"] for n in att), PEAK)["seconds"]
    assert _read("attn_roofline", r) == pytest.approx(100 * least / 5e-9)


NEW_METRICS = ["seq_step_device_ms", "seq_mfu", "moe_gmm_roofline",
               "attn_roofline", "moe_device_share",
               "expert_load_max_over_mean", "pack_pad_share",
               "seq_encode_users_s", "seq_outside_steps_ms"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_reads_nothing_where_there_is_nothing(name):
    """Another cell's readers, a program without the spans or the
    counters (the parent under this benchmark), an untraced run: None,
    never an exception."""
    other = {"work": {"kind": "train_calls", "calls": 2},
             "device": {"kind": "TPU v5 lite"}, "trace": None,
             "before": {"t": 0.0}, "after": {"t": 1.0}}
    assert _read(name, other) is None
    bare = _readers()
    bare["trace_scopes"] = None
    bare["work"]["expert_load"] = {"max": 0.0, "mean": 0.0}
    bare["after"] = {"t": 0.0}           # no seq.train root in [0, 0)
    if name != "pack_pad_share":         # the harness's own count
        assert _read(name, bare) is None
    empty = _readers()
    empty["trace_scopes"] = {"jit_prog": {"seconds": 1.0, "count": 1,
                                          "scopes": {}, "kernels": {}}}
    if name in NEW_METRICS[:5]:
        assert _read(name, empty) is None


def test_span_metrics_read_the_programs_stage_summaries():
    from predictionio_tpu.utils import tracing

    with tracing.trace_scope("seq.train", slow_exempt=True):
        with tracing.span("seq.steps"):
            pass
        with tracing.span("seq.encode_users"):
            pass
    r = _readers()
    assert _read("seq_encode_users_s", r) is not None
    assert _read("seq_outside_steps_ms", r) >= 0


def test_the_new_entries_list_this_cell_alone():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_pairs_per_s"
    rates = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_pairs_per_s")
    assert rates["workloads"] == ["rec-ml20m.train", CELL]
    # the preparator's seconds explain this cell's set-up too
    assert by_name["prepare_s"]["workloads"] == ["rec-ml20m.train", CELL]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"train_pairs_per_s",
                                                    "setup_s"}


# -- the oracle and the configuration ------------------------------------------

def test_oracle_is_the_packages_reference():
    here = os.path.join(cells.ROOT, "benchmark", "harness", "oracle_seq.py")
    there = os.path.join(cells.ROOT, "predictionio_tpu", "ops",
                         "seqrec_reference.py")

    def code(path, until=None):
        text = open(path).read()
        text = text[text.index("from __future__"):]
        return text if until is None else text[:text.index(until)]

    assert code(here, "\n\n# -- the comparison's limits").rstrip() \
        == code(there).rstrip()


def test_configuration_keeps_every_published_number():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if '"OLMoE-1B-7B-0125-Instruct"' in line:
                row = json.loads(line)
    published = row["config"] if row else {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    cfg = cells.load_cell(CELL).config
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "shape"]
    for key, value in published.items():
        if key == "num_hidden_layers":
            assert cfg[key] == 1 and value == 16
        else:
            assert cfg[key] == value, key
    assert cfg["shape"]["n_events"] / cfg["shape"]["n_users"] \
        == pytest.approx(33_600_000 / 571_355, rel=1e-3)
    from benchmark.models import sequentialrec

    p = sequentialrec.seqrec_params(cfg, seed=1)
    assert (p.rank, p.n_heads, p.head_dim, p.n_experts, p.expert_width,
            p.experts_per_token, p.vocab_rows, p.max_seq_len) \
        == (2048, 16, 128, 64, 1024, 8, 50304, 4096)
    assert p.batch_size * p.max_seq_len == 32768 and not p.tied


# -- the comparison that decides ``correct`` -----------------------------------

@pytest.fixture(scope="module")
def toy_step():
    """A toy block (float32 on the CPU), packed rows and parameters for
    ``seq_check.check_step``."""
    from predictionio_tpu.ops import seqrec

    params = seqrec.SeqRecParams(
        block="olmoe", rank=128, n_heads=2, head_dim=64, norm="rmsnorm",
        norm_eps=1e-5, positions="rope", tied=False, vocab_rows=64,
        n_experts=4, expert_width=128, experts_per_token=2, n_layers=1,
        max_seq_len=64, batch_size=4, micro_rows=1, n_negatives=8,
        learning_rate=1e-3, seed=3)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 50, size=n).astype(np.int32)
            for n in rng.integers(2, 40, size=24)]
    return params, seqrec.pack_sequences(seqs, 64), \
        seqrec.init_theta(50, params)


def _drop_a_microbatch(monkeypatch):
    """Three of a step's four microbatches reach the gradient; the loss
    and the count of targets are still the whole batch's."""
    import jax

    from predictionio_tpu.ops import seqrec

    real = seqrec.step_gradients

    def faulty(theta, ids, seg, pos, negs, **kw):
        loss, _, n, load, dropped = real(theta, ids, seg, pos, negs, **kw)
        _, g, n3, _, _ = real(theta, ids[:-1], seg[:-1], pos[:-1], negs,
                              **kw)
        return (loss, jax.tree_util.tree_map(lambda x: x * (n3 / n), g), n,
                load, dropped)

    monkeypatch.setattr(seqrec, "step_gradients", faulty)


def _megablox_on_the_cpu(monkeypatch):
    import functools

    from predictionio_tpu.ops import moe

    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
        moe.grouped_matmul, impl="megablox", tiling=(128, 128, 128),
        interpret=True))


def _shift_the_weight_gradient(monkeypatch):
    """``tgmm`` hands each expert its neighbour's weight gradient."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    _megablox_on_the_cpu(monkeypatch)
    real = moe._megablox.tgmm
    monkeypatch.setattr(
        moe._megablox, "tgmm",
        lambda *a, **kw: jnp.roll(real(*a, **kw), 1, axis=0))


def _forget_adams_bias_correction(monkeypatch):
    from predictionio_tpu.ops import seqrec

    monkeypatch.setattr(seqrec, "ADAM_B1", 0.0)


@pytest.mark.parametrize("plant,caught", [
    (None, []),
    (_megablox_on_the_cpu, []),
    (_drop_a_microbatch, ["step_update_rel_err"]),
    (_shift_the_weight_gradient, ["step_update_rel_err"]),
    (_forget_adams_bias_correction, ["step_update_rel_err"]),
])
def test_step_check_holds_the_timed_program_to_the_oracle(
        toy_step, monkeypatch, plant, caught):
    """The sound step program passes at the limits the chip's readings
    set; a planted fault in what the forward comparison cannot see (the
    sum over microbatches, the weight gradient out of ``tgmm``, Adam)
    fails ``correct``."""
    from benchmark.harness import seq_check
    from predictionio_tpu.ops import seqrec

    params, rows, theta = toy_step
    seqrec._train_step_jit.cache_clear()
    if plant is not None:
        plant(monkeypatch)
    try:
        readings = seq_check.check_step(params, rows, 50, theta, seed=9)
    finally:
        seqrec._train_step_jit.cache_clear()
    why = []
    seq_check.verdict(readings, why)
    assert [w.split()[0] for w in why] == caught, (why, readings)
    assert readings["step_targets"][0] == readings["step_targets"][1]
