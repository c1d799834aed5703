"""The slate cell's own pieces at toy widths on the CPU: the
configuration against the catalog row, the item map with the mask
token's row, the comparison's controls through the same ``compare()``
as a lane's audits, the counters' arithmetic, the shapes' operations
and bytes, and the per-layer readers on a run without their spans."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark.harness import cell as cells
from benchmark.harness import shapes_slate, slate_check
from benchmark.models import slaterec

CELL = "seqrec-sdar.slate-gen"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_keeps_every_published_number_but_the_depth():
    cell = cells.load_cell(CELL)
    c = cell.config
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["vocab_size"]) == (2048, 32, 4, 128, 128, 8, 768, 151936)
    for key in ("block_length", "denoising_steps", "remasking", "greedy",
                "mask_token_id", "no logit shift", "weights", "histories"):
        assert key in c["assumed"], key
    assert "8 pipeline stages" in c["deployment"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "SDAR-30B-A3B-Chat")
    assert row["source_url"] == c["source"]
    differs = [k for k, v in row["config"].items() if c.get(k, "?") != v]
    assert differs == ["num_hidden_layers"]
    mix = cell.traffic
    assert (mix["num"]["values"], mix["num"]["shares"]) \
        == ([8, 16, 32], [0.5, 0.3, 0.2])
    assert mix["user_exponent"] == 0.6 and mix["timeout_ms"] == 5000
    assert mix["events"] == {"min": 1, "max": 8}
    assert mix["knee"]["limit_ms"] == 1000
    # under the knee, at or under the issue's four fifths of it
    assert 0.5 * mix["knee"]["found_qps"] <= mix["rate_qps"] \
        <= round(0.8 * mix["knee"]["found_qps"])


def test_item_ids_skip_the_mask_tokens_row():
    config = cells.load_cell(CELL, rehearse=True).config
    models, _, hist = slaterec.build(config, seed=3)
    model = models[0]
    mask = int(config["generation"]["mask_token_id"])
    assert len(model.item_map) == int(config["vocab_size"]) == mask + 1
    assert model.item_map[f"i{mask - 1}"] == mask - 1
    assert model.item_map.decode([mask])[0] == "<mask>"
    assert all(mask not in h for h in hist.values())
    assert slaterec.skip_mask(np.asarray([0, 4, 5, 6]), 5).tolist() \
        == [0, 4, 6, 7]
    params = slaterec.seqrec_params(config, seed=3)
    assert (params.block, params.block_length, params.mask_token) \
        == ("sdar_moe", 4, mask)
    # the lengths do not move with the seed; the items do
    again = slaterec.histories(config, seed=4)
    hist.pop(slaterec.short_user(config))   # the check's own, not the law's
    assert [len(h) for h in hist.values()] \
        == [len(h) for h in again.values()]
    assert any(a.tolist() != b.tolist()
               for a, b in zip(hist.values(), again.values()))


@pytest.fixture(scope="module")
def sound():
    return slate_check.control(None, seed=5, rehearse=True)


def test_the_sound_reference_passes_its_own_comparison(sound):
    assert sound["why"] == []
    assert set(sound["worst"]) == {"logit_err", "cache_err", "state_err",
                                   "gate_err", "router_margin", "unmask_gap"}
    assert all(sound["worst"][k] < sound["limits"][k] / 10
               for k in sound["worst"])
    assert {r["kind"] for r in sound["answers"]} == {"round"}


@pytest.mark.parametrize("name", slate_check.CONTROLS)
def test_control_is_caught(name, sound):
    got = slate_check.control(name, seed=5, rehearse=True)
    assert got["why"], name
    numeric = {"low_operands": ("logit_err", "cache_err", "state_err"),
               "float8_cache": ("cache_err", "logit_err"),
               "causal_mask": ("logit_err", "state_err"),
               "no_renorm": ("gate_err",)}
    for k in numeric.get(name, ()):
        assert got["worst"][k] > 3 * got["limits"][k], (name, k)
    if name == "tail_committed":
        assert any("whole blocks" in w for w in got["why"])
    if name == "pass_skipped":
        assert any("does not start from pass" in w for w in got["why"])


@pytest.mark.parametrize("name,by", [("float8_cache", "cache_err"),
                                     ("causal_mask", None)])
def test_controls_on_a_long_history(name, by):
    """``cache_err`` reads a coarse cache the same at every history
    length; a wrong mask inside a block reads far less over a long
    history than on the short session: why the cell keeps one."""
    short = slate_check.control(name, seed=5, rehearse=True)
    long = slate_check.control(name, seed=5, rehearse=True, history=96)
    if name == "causal_mask":
        assert long["worst"]["logit_err"] < short["worst"]["logit_err"] / 2
        assert long["worst"]["state_err"] < short["worst"]["state_err"] / 2
    if by:
        assert long["worst"][by] > 3 * long["limits"][by]
        assert long["worst"][by] == pytest.approx(short["worst"][by],
                                                  rel=0.5)


def test_reference_follows_given_picks_only_when_they_are_near_ties():
    import jax.numpy as jnp

    from benchmark.harness import oracle_sdar as oracle

    rng = np.random.default_rng(0)
    T, D, E, F, k = 6, 8, 6, 4, 2
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=sh), jnp.float32)
         for sh in ((D, E), (E, D, F), (E, D, F), (E, F, D))]
    y_own, own, _, probs = oracle.experts(h, *w, k)
    own, probs = np.asarray(own), np.asarray(probs)
    order = np.argsort(-probs, axis=1)
    rows = np.arange(T)
    # under the own cut: the third most likely expert, and the least
    near = 1.0 - probs[rows, order[:, 2]] / probs[rows, order[:, 1]]
    far = 1.0 - probs[rows, order[:, -1]] / probs[rows, order[:, 1]]
    tie, off = int(np.argmin(near)), int(np.argmax(far))
    assert tie != off and near[tie] < far[off]
    given = order[:, [1, 0]].copy()     # the own picks in another order
    given[tie] = order[tie, [0, 2]]     # a near-tie flipped
    given[off] = order[off, [0, -1]]    # an expert far under the cut
    y, picks, g, _ = oracle.experts(h, *w, k, given=jnp.asarray(given),
                                    margin=float(near[tie] + far[off]) / 2)
    picks, y, y_own = np.asarray(picks), np.asarray(y), np.asarray(y_own)
    assert picks[off].tolist() == own[off].tolist()     # its own picks kept
    keep = rows != off
    assert picks[keep].tolist() == given[keep].tolist()
    assert np.allclose(np.asarray(g).sum(1), 1.0, atol=1e-6)
    # the same experts in another order are the same layer; the flipped
    # tie is another
    same = keep & (rows != tie)
    assert np.allclose(y[same | ~keep], y_own[same | ~keep], atol=1e-5)
    assert not np.allclose(y[tie], y_own[tie], atol=1e-5)


def test_the_short_check_session_is_one_more_user_outside_the_traffic():
    config = cells.load_cell(CELL, rehearse=True).config
    models, _, hist = slaterec.build(config, seed=3)
    short = slaterec.short_user(config)
    assert short == int(config["shape"]["n_users"]) == len(hist) - 1
    assert len(hist[short]) == int(config["check"]["short_session"])
    assert len(hist[short]) < min(len(h) for u, h in hist.items()
                                  if u != short)
    assert models[0].user_map[f"u{short}"] == short
    full = cells.load_cell(CELL).config
    assert int(full["check"]["short_session"]) == 23
    assert int(full["check"]["short_session"]) % 4     # it has a tail


def test_two_queries_at_one_committed_length_are_told_apart():
    """A query whose events fill no block finds the session at the
    committed length the query before it found: its audits are its
    own (not duplicates of the other's), and a round whose earlier
    rounds were not kept is held against ITS OWN earlier blocks."""
    from benchmark.drivers import http_slates
    from benchmark.harness import oracle_sdar as oracle

    config, block, theta = slate_check._drawn(5, True)
    rng = np.random.default_rng(3)
    events = slaterec.skip_mask(rng.integers(0, 200, 9), block["mask_id"])
    first = slate_check.lane_like(theta, block, events[:8], [6], oracle)
    second = slate_check.lane_like(theta, block, events, [9], oracle)
    assert [(a["len0"], a["pos0"]) for a in first] == [(8, 8), (8, 12)]
    assert [(a["len0"], a["pos0"]) for a in second] \
        == [(8, 8), (8, 12), (8, 16)]

    class Lane:
        kept = first

        def audits(self, u):
            return self.kept

    records = {0: {"audits": [], "keys": set()}}
    lane = Lane()
    http_slates.take_audits(lane, records)
    lane.kept = first[1:] + second
    http_slates.take_audits(lane, records)
    assert len(records[0]["audits"]) == 5
    # the second query's first two rounds lost: its third is still its own
    why = []
    got = slate_check.compare(
        theta, block, [{"user": 0, "events": events,
                        "audits": first + second[2:]}],
        config["check"], why, oracle, compute_dtype="float32")
    assert why == [] and len(got["answers"]) == 3


def test_rule_gap_reads_zero_on_agreement_and_one_on_another_row():
    from benchmark.harness import oracle_sdar as oracle

    block = {"remasking": "low_confidence_static", "threshold": 0.9}
    logits = np.log(np.asarray([[0.7, 0.2, 0.1], [0.4, 0.35, 0.25]]))
    barred = np.zeros(3, bool)
    masked = np.asarray([True, True])
    same = slate_check.rule_gap(logits, masked, np.asarray([True, False]),
                                np.asarray([0, 9]), barred, 1, block, oracle)
    assert same == 0.0
    other = slate_check.rule_gap(logits, masked, np.asarray([False, True]),
                                 np.asarray([9, 0]), barred, 1, block,
                                 oracle)
    assert other > 0.3


def test_shapes_count_picked_experts_and_cached_rows():
    b = slaterec.block_of(cells.load_cell(CELL).config)
    fixed = shapes_slate.weights_fixed(b)
    # 6 layers of q, k, v, o in bf16 and a float32 router, and the head
    assert fixed == pytest.approx(
        6 * (18.87e6 * 2 + 2048 * 128 * 4) + 151936 * 2048 * 2, rel=0.001)
    assert shapes_slate.expert_bytes(b) == 3 * 2048 * 768 * 2
    assert shapes_slate.cache_row_bytes(b) == 6 * 2 * 512 * 2
    w = {"passes_device": 10.0, "experts_touched": 10 * 6 * 28.0,
         "cache_rows_read": 10 * 14000.0, "unmasked": 8.0, "rounds": 2.0,
         "passes_query": 10.0}
    got = shapes_slate.pass_bytes(w, b)
    assert got == pytest.approx(fixed + 6 * 28 * 9.44e6 + 14000 * 12288,
                                rel=0.001)
    # never the 128 held: 28 picked of a layer are a fifth of its bytes
    assert got < fixed + 6 * 128 * shapes_slate.expert_bytes(b) / 4
    need = shapes_slate.cache_attention(w, b)
    assert need["bytes"] == 140000 * 12288
    assert need["flops"] == 4.0 * 140000 * 4 * 6 * 32 * 128
    assert shapes_slate.model_flops(w, b) > need["flops"]


NEW_METRICS = ("slate_pass_device_ms", "slate_mfu", "slate_hbm_roofline",
               "gqa_cache_attn_roofline", "slate_moe_device_share",
               "slate_passes_per_query", "slate_tokens_per_pass",
               "slate_rounds_shared")


def test_new_metrics_list_the_new_cell_alone_and_read_nothing_from_nothing():
    bench = cells.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by[name]["workloads"] == [CELL]
        read = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read
        # the parent commit: no such module, span or counter
        assert read({"device": {"kind": "TPU v5 lite"}, "slate": None,
                     "slate_slice": None, "slate_module": None}) is None
        assert read({"device": {"kind": "TPU v5 lite"}}) is None
    for name in ("sess_cache_fill_share", "sess_prefill_s", "served_qps",
                 "query_p50_ms"):
        m = by.get(name) or next(e for e in bench["end_to_end"]
                                 if e["name"] == name)
        assert m["workloads"][-1] == CELL
    for name in ("sess_step_device_ms", "dsa_index_roofline",
                 "mla_sparse_roofline", "sess_tokens_per_dispatch"):
        assert CELL not in by[name]["workloads"]


def test_readers_on_a_slice_of_counters_and_scopes():
    b = slaterec.block_of(cells.load_cell(CELL).config)
    w = {"passes_device": 50.0, "passes_query": 150.0, "rounds": 30.0,
         "unmasked": 110.0, "experts_touched": 50 * 6 * 60.0,
         "cache_rows_read": 50 * 40000.0, "dispatches": 10, "queries": 8,
         "carried": 22.0, "block": b}
    module = {"seconds": 0.5, "count": 10,
              "scopes": {"sdar/attn": 0.1, "sdar/moe": 0.25,
                         "sdar/commit/sdar/moe": 0.05, "sdar/head": 0.1},
              "kernels": {"sdar/attn": 0.04, "sdar/moe": 0.2}}
    r = {"device": {"kind": "TPU v5 lite"}, "slate": w, "slate_slice": w,
         "slate_module": module}

    def read(name):
        return importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(r)

    assert read("slate_pass_device_ms") == pytest.approx(10.0)
    assert read("slate_moe_device_share") == pytest.approx(60.0)
    assert read("slate_passes_per_query") == pytest.approx(150 / 8)
    assert read("slate_tokens_per_pass") == pytest.approx(110 / 120)
    assert read("slate_rounds_shared") == pytest.approx(3.0)
    for name in ("slate_hbm_roofline", "gqa_cache_attn_roofline",
                 "slate_mfu"):
        assert 0 < read(name) < 100
