"""The grouped-query / window / routed-experts family
(``families/gqa_moe_lm.py``) and its cell's files, at the traffic file's
rehearsal widths (hidden 64, 4 query / 2 key heads of 16, window 8, layers
window, window, window, full, 8 experts of 32 with 4 held and top-2,
vocabulary 256, rows of 32 tokens):

- the program's loss and gradients against the plain reference's on seeded
  weights;
- the shares add up in the reference too: 4 + 4 held experts give the uncut
  layer;
- the arithmetic of the required work against XLA's count of the plain
  client step where the plain form discards nothing, its window and
  expectation terms against brute-force counts, and against the issue's own
  numbers at the published widths;
- ``run.py --rehearse``'s control flow on the new cell: correct under the
  cell's real limits, its fp8 control not, its counters valid rows;
- the new readers on rows and traces with and without what they read.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pb import cell as C
from pb import compare
from pb.manifest import CHECKOUT, Manifest, data_kind, family, reader

WORKLOAD = "mellum2_n10_median"
CONFIG = "mellum2_12b_a2p5b_ep8"
SEED = 3_300_000_011          # over 2**31, as the driver's seeds are
FILES = Manifest(CHECKOUT).cell(WORKLOAD)
SMALL = dict(FILES["config"], **FILES["traffic"]["rehearsal"]["config"])
FAM = family("gqa_moe_lm")
FED = {"num_clients": 10, "elided_lanes": 2, "batch_size": 1,
       "local_steps": 1}


def _program_task(cfg):
    from blades_tpu.core.task import TaskSpec

    model = dict(FILES["traffic"]["rehearsal"]["overrides"]["global_model"],
                 attn_block=8)
    return TaskSpec(model=model, num_classes=cfg["vocab_size"],
                    input_shape=tuple(cfg["input_shape"])).build()


def _batch(cfg, seed=0, rows=2):
    kind = data_kind("packed_token_documents")
    data = kind.make(dict(FILES["traffic"]["data"], doc_median=10), 1, cfg,
                     seed)
    x, y = kind.batches(data, data["train"][0][0, :rows])
    assert (np.asarray(x) == 0).sum() > rows      # several documents a row
    return x, y


def test_program_matches_the_plain_reference():
    cfg = SMALL
    params = FAM.init_params(cfg, 7)
    assert sum(p.size for p in jax.tree.leaves(params)) == \
        FAM.num_params(cfg) == cfg["num_params"]
    x, y = _batch(cfg)
    task = _program_task(cfg)
    want = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, want) == \
        jax.tree.map(lambda a: a.shape, params)      # same tree, same names
    l_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: FAM.loss_fn(cfg, p, x, y)))(params)
    l_prog, g_prog = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, x, y)))(params)
    # float32 on both sides: what is left is the order of sums (the
    # program gathers the routed pairs, the reference weighs every expert)
    np.testing.assert_allclose(l_prog, l_ref, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_prog),
                            jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # the program's logits too, and a window that matters: another window
    # gives another loss
    np.testing.assert_allclose(
        jax.jit(task.sequence_planes)(params, x)[0],
        jax.jit(lambda p: FAM.forward(cfg, p, x))(params),
        rtol=1e-4, atol=1e-5)
    wide = jax.jit(lambda p: FAM.loss_fn(dict(cfg, sliding_window=32),
                                         p, x, y))(params)
    assert abs(float(wide) - float(l_ref)) > 1e-6


def test_the_references_shares_add_up_to_the_uncut_layer():
    whole = dict(SMALL, num_experts=8, first_expert=0)
    p = FAM.init_params(whole, 3)["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))

    def ident(v):
        return v

    full = FAM._experts(whole, p, x, ident)
    parts = []
    for first in (0, 4):
        share = dict(SMALL, num_experts=4, first_expert=first)
        ps = dict(p, **{k: p[k][first:first + 4] for k in
                        ("experts_gate", "experts_up", "experts_down")})
        parts.append(FAM._experts(share, ps, x, ident))
    np.testing.assert_allclose(parts[0] + parts[1], full, rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(parts[0]).max()) > 0 < float(
        jnp.abs(parts[1]).max())


def test_required_work_is_the_issues_arithmetic_at_the_published_widths():
    cfg = FILES["config"]
    assert FAM.num_params(cfg) == 340_349_184 == cfg["num_params"]
    per_token = FAM.train_flops_per_sample(cfg, FED) / 8192 / 3
    assert per_token == pytest.approx(391.5e6, rel=1e-3)
    assert 8 * FAM.train_flops_per_sample(cfg, FED) == pytest.approx(
        77.0e12, rel=1e-3)
    assert 2 * FAM.matmul_params_per_token(cfg) == pytest.approx(
        (169.9 + 1.2 + 49.5 + 56.6) * 1e6, rel=2e-3)
    assert FAM.attention_positions(cfg, "sliding_attention") / 8192 == \
        pytest.approx(960.06, rel=1e-4)
    assert FAM.attention_positions(cfg, "full_attention") / 8192 == 4096.5
    assert FAM.routed_experts_per_token(cfg) == 1.0
    flops, nbytes = FAM.WORKS["grouped_matmul"](cfg, FED)
    assert flops == 9 * 2 * (4 * 8 * 8192) * 2304 * 896
    assert FAM.COUNTED_WORKS["grouped_matmul"](cfg, FED, 4 * 8 * 8192) == \
        (flops, nbytes)
    # every key of the source's config as published but the three reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(line) for line in open(catalog)] \
        if os.path.exists(catalog) else []
    for row in rows:
        if row["name"] == "Mellum2-12B-A2.5B-Instruct":
            for key, value in row["config"].items():
                if key not in cfg["published"]:
                    assert cfg[key] == value, key
                else:
                    assert cfg["published"][key] == value, key
    for key, value in dict(
            hidden_size=2304, head_dim=128, num_attention_heads=32,
            num_key_value_heads=4, moe_intermediate_size=896,
            intermediate_size=7168, sliding_window=1024, router_outputs=64,
            num_experts_per_tok=8, rms_norm_eps=1e-6).items():
        assert cfg[key] == value, key
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"] and len(cfg["layer_types"]) == 28
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 16
    doc = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (entry,) = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == \
        set(cfg["published"])
    assert entry["file"].startswith("perfbench/")
    assert any(w["config"] == entry["name"] for w in doc["workloads"])


@pytest.mark.parametrize("key", ["num_hidden_layers", "num_experts",
                                 "vocab_size"])
def test_a_reduced_key_names_no_width(key):
    """The contract's list of widths, word for word, held against each key
    this configuration reduces (``test_pb_manifest.py``'s guard reads
    "hidden" anywhere in a key as a width, so ``num_hidden_layers`` fails
    it: ``tests/conftest.py`` marks that one case, PERF.md Open question
    16; ``num_params`` against the family's count is held above)."""
    doc = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (entry,) = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert key in entry["reduced"]
    width = (key.endswith(("_dim", "_rank", "_size"))
             and key != "vocab_size"
             or any(w in key for w in ("width", "intermediate", "latent",
                                       "state", "proj", "expan", "head"))
             or key == "num_experts_per_tok"
             or "hidden" in key and key != "num_hidden_layers")
    assert not width, key
    assert FILES["config"]["published"][key] > FILES["config"][key] > 0


def test_train_flops_against_cost_analysis():
    """XLA's count of the plain client step, at a size where the plain form
    discards nothing: every expert held and selected (the dense experts are
    the required work), a window no shorter than the row, blocks of 4 of
    32 queries (XLA counts the ``lax.map``'s body once, and the body
    multiplies a block by every key under the mask where the arithmetic
    counts the causal half), nothing recomputed."""
    cfg = dict(SMALL, router_outputs=4, num_experts=4, num_experts_per_tok=4,
               attn_block=4, remat=False, sliding_window=32,
               hidden_size=128, moe_intermediate_size=64, vocab_size=512,
               head_dim=32)
    batch = 4
    params = jax.eval_shape(lambda: FAM.init_params(cfg, 0))
    x = jax.ShapeDtypeStruct((batch, 32), jnp.int32)

    def step(p, x, y):
        loss, g = jax.value_and_grad(
            lambda p: FAM.loss_fn(cfg, p, x, y))(p)
        return loss, jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g)

    cost = jax.jit(step).lower(params, x, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = FAM.train_flops_per_sample(cfg, {}) * batch
    assert abs(float(cost["flops"]) - mine) / mine < 0.05, (
        cost["flops"], mine)


@pytest.mark.parametrize("s,window", [(32, 8), (64, 24), (16, 32)])
def test_the_windows_term_is_a_count_of_the_mask(s, window):
    cfg = dict(SMALL, input_shape=[s], sliding_window=window)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    assert FAM.attention_positions(cfg, "sliding_attention") == int(
        ((j <= i) & (i - j < window)).sum())
    assert FAM.attention_positions(cfg, "full_attention") == int(
        (j <= i).sum())
    per_position = 3 * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    assert FAM.attention_flops_per_sample(cfg) == per_position * (
        3 * FAM.attention_positions(cfg, "sliding_attention")
        + FAM.attention_positions(cfg, "full_attention"))
    assert FAM.required_attention_scores(cfg, FED) == 8 * 4 * (
        3 * FAM.attention_positions(cfg, "sliding_attention")
        + FAM.attention_positions(cfg, "full_attention"))


def test_the_expectations_term_is_a_count_of_a_drawn_routing():
    """An even router over 64 outputs, top-8, 8 held: a token selects one
    held expert in expectation; a drawn routing counts it."""
    cfg = dict(SMALL, router_outputs=64, num_experts=8,
               num_experts_per_tok=8)
    rng = np.random.default_rng(0)
    picks = np.argsort(rng.random((20000, 64)), axis=1)[:, :8]
    drawn = (picks < 8).sum() / 20000
    assert FAM.routed_experts_per_token(cfg) == 1.0
    assert drawn == pytest.approx(1.0, rel=0.02)
    # and the reference's own routing sends each pair to one expert
    p = FAM.init_params(SMALL, 5)["layer_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 64))
    w, idx = FAM.routing(SMALL, p, x)
    assert idx.shape == (1, 32, 2) and w.shape == (1, 32, 4)
    held = np.asarray(idx) < 4
    np.testing.assert_array_equal((np.asarray(w) > 0).sum(-1), held.sum(-1))
    full, _ = FAM.routing(dict(SMALL, num_experts=8), dict(p), x)
    np.testing.assert_allclose(full.sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_every_seeds_router_sends_a_token_one_pair_a_chip(seed):
    """The work a run does may not follow the seed: at the published widths
    (64 outputs, 8 experts a chip, top-8) a token's top-8 holds one expert
    of each of the 8 chips, so the share held here is sent one pair a token
    a layer whatever the seed, while the pairs spread unevenly over the
    held experts by the token."""
    cfg = json.load(open(os.path.join(
        CHECKOUT, "perfbench/configs/mellum2_12b_a2p5b_ep8.json")))
    w = FAM._router_kernel(jax.random.PRNGKey(seed),
                           (cfg["hidden_size"], cfg["router_outputs"]),
                           cfg["num_experts"])
    x = jax.random.normal(jax.random.PRNGKey(seed % 1000 + 1),
                          (1, 4096, cfg["hidden_size"]))
    _, idx = FAM.routing(cfg, {"router_kernel": w}, x)
    chips = np.sort(np.asarray(idx)[0] // cfg["num_experts"], axis=-1)
    one_each = (chips == np.arange(8)).all(-1)
    assert one_each.mean() > 0.98
    here = (np.asarray(idx) < cfg["num_experts"]).sum()
    assert here == pytest.approx(4096, rel=0.01)
    # which held expert: by the token, not evenly
    slots = np.asarray(idx)[0][np.asarray(idx)[0] < cfg["num_experts"]]
    assert len(np.unique(slots)) == cfg["num_experts"]


@pytest.fixture(scope="module")
def shared(tmp_path_factory):
    os.environ["PERFBENCH_OUT"] = str(tmp_path_factory.mktemp("pbout"))
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    yield {"reference_cache": {}}
    os.environ.pop("PERFBENCH_OUT", None)


def test_the_rehearsal_is_correct_and_reports_the_new_metrics(shared):
    code, result = C.run_cell(
        CHECKOUT, WORKLOAD, SEED, 1.0, True, time.perf_counter(),
        rehearse=True, reference_cache=shared["reference_cache"])
    assert code == 0
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["compared"]) >= set(FILES["limits"]["limits"])
    metrics = result["metrics"]
    assert metrics["train_tokens_per_s"]["value"] > 0
    assert metrics["host_dispatch_ms_per_round"]["value"] > 0
    # the two counters' ratios need no chip: the rows carry them.  At
    # these widths (tiles of 128 rows over 128 pairs) the ratio is large.
    assert metrics["expert_rows_over_routed"]["value"] >= 1.0
    assert 1.0 <= metrics["attn_scores_over_required"]["value"] < 3.0
    # what needs a device's trace is left out, not zero
    assert "grouped_matmul_roofline" not in metrics
    assert "grouped_matmul_ms_per_round" not in metrics
    assert "finish_roofline" not in metrics


def test_the_fp8_control_fails_the_new_cell(shared):
    from blades_tpu.obs.schema import validate_record

    cell = C.Cell(Manifest(CHECKOUT), WORKLOAD, SEED, rehearse=True)
    row = cell.round()
    assert row["tokens_trained"] == 4 * 2 * 32
    assert 0 < row["routed_here_share"] <= 1
    assert row["expert_tokens_max"] >= row["expert_tokens_mean"] > 0
    assert 0 <= row["zero_expert_blocks"] <= 4 * 4 * 4
    assert row["expert_rows_computed"] >= row["expert_pairs_here"] > 0
    assert row["attn_scores_computed"] > 0
    assert validate_record(dict(row, experiment="e", trial="t"))["round_ok"]
    ref = shared["reference_cache"].get((WORKLOAD, SEED, True)) \
        or cell.follow()
    control = cell.follow(quant="fp8")
    cell.free()
    ok, report = compare.decide(compare.numbers(control, ref), cell.limits)
    assert not ok, report


def test_row_counter_ratio_reader():
    read = reader("row_counter_ratio")
    m = Manifest(CHECKOUT)
    rows = [{"expert_rows_computed": 300, "expert_pairs_here": 200,
             "attn_scores_computed": 2.0 * FAM.required_attention_scores(
                 FILES["config"], FED)} for _ in range(4)]
    ctx = {"rows": rows, "traced_rounds": 2, "family": FAM,
           "config": FILES["config"], "federation": FED}
    assert read(ctx, m.metric_file("expert_rows_over_routed")) == 1.5
    assert read(ctx, m.metric_file("attn_scores_over_required")) == 2.0
    # a program that stamps no such counter, or a family without the
    # table (the parent commit's): nothing to read, no error
    bare = dict(ctx, rows=[{"train_loss": 1.0}])
    assert read(bare, m.metric_file("expert_rows_over_routed")) is None
    assert read(bare, m.metric_file("attn_scores_over_required")) is None
    other = dict(ctx, family=family("mla_moe_lm"))
    assert read(other, m.metric_file("attn_scores_over_required")) is None
    assert read(dict(ctx, rows=[]),
                m.metric_file("expert_rows_over_routed")) is None


def test_device_ops_reader_reads_the_operations_line():
    read = reader("device_ops")
    m = Manifest(CHECKOUT)
    ms = m.metric_file("grouped_matmul_ms_per_round")
    share = m.metric_file("grouped_matmul_roofline")
    pairs = 4 * 8 * 8192
    flops, _ = FAM.COUNTED_WORKS["grouped_matmul"](FILES["config"], FED,
                                                   pairs)
    floor_ns = 1e9 * flops / 197e12
    trace = {"host": [["bench/round", 0.0, 4e9], ["bench/round", 4e9, 4e9]],
             "devices": {"/device:TPU:0": {
                 "modules": [["jit__train_block", 0.0, 7e9]],
                 "ops": [["jit_gmm.1", 1e9, floor_ns],
                         ["jit_tgmm.2", 5e9, 3 * floor_ns],
                         ["fusion.7", 2e9, 1e9]]}}}
    ctx = {"trace": trace, "traced_rounds": 2, "family": FAM,
           "config": FILES["config"], "federation": FED, "notes": {},
           "rows": [{"expert_pairs_here": pairs}] * 3,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert read(ctx, ms) == pytest.approx(1e3 * 4 * floor_ns / 1e9 / 2)
    assert read(ctx, share) == pytest.approx(100 * 2 / 4)
    assert ctx["notes"]["grouped_matmul_roofline.bound_by"] == "flops"
    # no operations line, no such operation, no trace, no counter, another
    # family (the parent's program and benchmark): nothing, and no error
    no_ops = {"host": trace["host"], "devices": {"/device:TPU:0": {
        "modules": trace["devices"]["/device:TPU:0"]["modules"],
        "ops": []}}}
    for broken in (dict(ctx, trace=None), dict(ctx, trace=no_ops),
                   dict(ctx, trace={"host": trace["host"], "devices": {}})):
        assert read(broken, ms) is None and read(broken, share) is None
    assert read(dict(ctx, rows=[{"train_loss": 1.0}] * 3), share) is None
    assert read(dict(ctx, family=family("mla_moe_lm")), share) is None
    plain = dict(trace, devices={"/device:TPU:0": dict(
        trace["devices"]["/device:TPU:0"], ops=[["fusion.7", 2e9, 1e9]])})
    assert read(dict(ctx, trace=plain), ms) is None
