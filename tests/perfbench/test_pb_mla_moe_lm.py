"""The MLA + expert-share + MTP family (``families/mla_moe_lm.py``) and its
cell's files, at the traffic file's rehearsal widths (hidden 64, 4 heads,
ranks 32/16, head dims 16+8/16, 8 experts of 32 with 4 held and top-2,
vocabulary 256, rows of 32 tokens):

- the program's loss and gradients against the plain reference's on seeded
  weights, MTP on and off;
- the shares add up in the reference too: 4 + 4 held experts' routed parts
  and the shared expert, counted once, give the uncut layer;
- the arithmetic of the required work against XLA's count of the plain
  client step, and against the issue's own numbers at the published widths;
- ``run.py --rehearse``'s control flow on the new cell: correct under the
  cell's real limits, its fp8 control not, its counters valid rows;
- the new reader on rows with and without the counters.
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

WORKLOAD = "joyai_n10_median"
SEED = 2_900_000_017          # over 2**31, as the driver's seeds are
FILES = Manifest(CHECKOUT).cell(WORKLOAD)
SMALL = dict(FILES["config"], **FILES["traffic"]["rehearsal"]["config"])
FAM = family("mla_moe_lm")


def _program_task(cfg):
    from blades_tpu.core.task import TaskSpec

    model = dict(FILES["traffic"]["rehearsal"]["overrides"]["global_model"],
                 num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
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


@pytest.mark.parametrize("mtp", [0, 1])
def test_program_matches_the_plain_reference(mtp):
    cfg = dict(SMALL, num_nextn_predict_layers=mtp)
    params = FAM.init_params(cfg, 7)
    assert sum(p.size for p in jax.tree.leaves(params)) == \
        FAM.num_params(cfg)
    x, y = _batch(cfg)
    task = _program_task(cfg)
    want = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, want) == \
        jax.tree.map(lambda a: a.shape, params)      # same tree, same names
    l_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: FAM.loss_fn(cfg, p, x, y)))(params)
    l_prog, g_prog = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, x, y)))(params)
    # float32 on both sides: what is left is the order of sums (and
    # HIGHEST against the CPU's default, the same arithmetic there).
    np.testing.assert_allclose(l_prog, l_ref, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_prog),
                            jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    bias = g_ref["layer_1"]["moe"]["router_bias"]
    assert float(jnp.abs(bias).max()) == 0.0          # b gets no gradient


def test_the_references_shares_add_up_to_the_uncut_layer():
    cfg = dict(SMALL, router_outputs=8)
    whole = dict(cfg, n_routed_experts=8, first_expert=0)
    p = FAM.init_params(whole, 3)["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))

    def ident(v):
        return v

    full = FAM._experts(whole, p, x, ident)
    shared = FAM._swiglu(p["shared_0"], x, ident)
    parts = []
    for first in (0, 4):
        share = dict(cfg, n_routed_experts=4, first_expert=first)
        ps = dict(p, **{k: p[k][first:first + 4] for k in
                        ("experts_gate", "experts_up", "experts_down")})
        parts.append(FAM._experts(share, ps, x, ident) - shared)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, full,
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(parts[0]).max()) > 0 < float(
        jnp.abs(parts[1]).max())


def test_required_work_is_the_issues_arithmetic_at_the_published_widths():
    cfg = dict(FILES["config"], num_nextn_predict_layers=0)
    assert FAM.num_params(cfg) == 413_959_168
    assert FAM.num_params(dict(cfg, num_nextn_predict_layers=1)) == \
        491_697_408
    assert FILES["config"]["num_params"] == FAM.num_params(FILES["config"])
    assert FAM.matmul_params_per_token(cfg) == pytest.approx(234.55e6,
                                                             rel=1e-3)
    per_token = FAM.train_flops_per_sample(cfg, {}) / 4096
    assert per_token == pytest.approx(2.04e9, rel=5e-3)
    # `reduced` names counts only (depth, experts held, vocabulary rows,
    # MTP modules), and the file lies under `paths` and some cell runs it
    doc = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (entry,) = [c for c in doc["configs"]
                if c["name"] == "joyai_llm_flash_ep32"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    assert set(entry["reduced"]) == set(FILES["config"]["reduced_why"])
    assert entry["file"].startswith("perfbench/")
    assert any(w["config"] == entry["name"] for w in doc["workloads"])
    # every published width, unchanged
    for key, value in dict(
            hidden_size=2048, q_lora_rank=1536, kv_lora_rank=512,
            qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
            num_attention_heads=32, intermediate_size=7168,
            moe_intermediate_size=768, router_outputs=256,
            num_experts_per_tok=8, routed_scaling_factor=2.5).items():
        assert FILES["config"][key] == value, key


@pytest.mark.parametrize("key", ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "num_nextn_predict_layers"])
def test_a_reduced_key_names_no_width(key):
    """The contract's list of widths, word for word, held against each key
    this configuration reduces.  ``test_pb_manifest.py``'s guard of the same
    rule reads "hidden" anywhere in a key as a width, so the published key
    of the DEPTH, ``num_hidden_layers``, fails it (``tests/conftest.py``
    marks that one case; PERF.md Open questions asks a ``benchmark`` PR to
    narrow the rule).  What the guard checks after its width rule,
    ``num_params`` against the family's count, is held in
    ``test_required_work_is_the_issues_arithmetic_at_the_published_widths``.
    """
    doc = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    (entry,) = [c for c in doc["configs"]
                if c["name"] == "joyai_llm_flash_ep32"]
    assert key in entry["reduced"]
    width = (key.endswith(("_dim", "_rank", "_size"))
             and key != "vocab_size"
             or any(w in key for w in ("width", "intermediate", "latent",
                                       "state", "proj", "expan", "head"))
             or key == "num_experts_per_tok"
             or "hidden" in key and key != "num_hidden_layers")
    assert not width, key
    # a count, smaller than published, and the published value beside it
    assert FILES["config"]["published"][key] > FILES["config"][key] >= 0


def test_train_flops_against_cost_analysis():
    """XLA's count of the plain client step.  Where every token selects
    every expert and all are held, the dense way the reference computes the
    held experts is the required work; the reference multiplies a block
    of queries by every key under the mask where the arithmetic counts the
    causal half, and XLA counts that ``lax.map``'s body once, so the blocks
    are small here (4 of 32 positions); and nothing is recomputed
    (``remat`` off: a recomputed forward pass is no required work)."""
    cfg = dict(SMALL, router_outputs=4, n_routed_experts=4,
               num_experts_per_tok=4, attn_block=4, remat=False,
               # twice the rehearsal's widths: at hidden 64 the norms,
               # activations and softmaxes XLA adds on top are 5.6%
               hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, q_lora_rank=64, kv_lora_rank=32,
               vocab_size=512)
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
    assert set(result["compared"]) == set(FILES["limits"]["limits"])
    # What the rows alone give is there without a chip; what needs a
    # device's trace is left out, not zero.
    metrics = result["metrics"]
    assert metrics["train_tokens_per_s"]["value"] > 0
    # The round body's host spans are driven here too (a block a lane and
    # the finish), so the cell reports what reads them (a window of
    # several rounds: they are differences of consecutive rows).
    assert metrics["host_dispatch_ms_per_round"]["value"] > 0
    assert "worst_round_host_excess_ms" in metrics
    assert "finish_roofline" not in metrics
    rows = json.load(open(os.path.join(
        os.environ["PERFBENCH_OUT"], "rounds",
        f"{WORKLOAD}.seed{SEED}.trace1.0.json")))
    assert rows["window_compiles"]["backend_compiles"] == 0


def test_the_fp8_control_fails_the_new_cell(shared):
    from blades_tpu.obs.schema import validate_record

    cell = C.Cell(Manifest(CHECKOUT), WORKLOAD, SEED, rehearse=True)
    row = cell.round()
    # 4 benign lanes x 1 step x 2 rows x 32 tokens; 2 expert layers and the
    # MTP module's x 4 held
    assert row["tokens_trained"] == 4 * 2 * 32
    assert 0 < row["routed_here_share"] <= 1
    assert row["expert_tokens_max"] >= row["expert_tokens_mean"] > 0
    assert 0 <= row["zero_expert_blocks"] <= 4 * 3 * 4
    assert validate_record(dict(row, experiment="e", trial="t"))["round_ok"]
    with pytest.raises(Exception):
        validate_record(dict(row, experiment="e", trial="t",
                             zero_expert_blocks=0.5))
    ref = shared["reference_cache"].get((WORKLOAD, SEED, True)) \
        or cell.follow()
    control = cell.follow(quant="fp8")
    cell.free()
    ok, report = compare.decide(compare.numbers(control, ref), cell.limits)
    assert not ok, report


def test_row_counter_reader():
    read = reader("row_counter")
    rows = [{"tokens_trained": 100} for _ in range(4)]
    ctx = {"rows": rows, "traced_round_s": [0.5, 0.5]}
    per_s = Manifest(CHECKOUT).metric_file("train_tokens_per_s")
    assert per_s["reader"] == "row_counter"
    assert read(ctx, per_s) == 200.0
    # a program (or a task) that stamps no such counter: nothing to read
    assert read({"rows": [{"train_loss": 1.0}], "traced_round_s": [1.0]},
                per_s) is None
    assert read({"rows": [], "traced_round_s": []}, per_s) is None
    assert read({"rows": rows, "traced_round_s": []}, per_s) is None
