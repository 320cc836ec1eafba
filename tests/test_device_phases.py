"""The device-phase registry (blades_tpu/obs/device_phases.py): which
``blades/*`` scope each compiled instruction belongs to, noted where the
streamed round dispatches, at no compile of its own."""

import contextlib
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from blades_tpu.obs import device_phases as dp

# -- the map from op_name to phase -----------------------------------------


@pytest.mark.parametrize("op_name,phase", [
    ("jit(f)/jvp(blades/step)/tanh", "step"),
    ("jit(f)/transpose(jvp(blades/step))/blades/attn/vmap()/mul", "attn"),
    ("jit(f)/vmap(blades/step)/blades/attn_window/blades/attn_kernel/dot",
     "attn_kernel"),
    ("jit(f)/transpose(jvp(blades/step))/transpose(jvp(blades/combine))/add",
     "combine"),
    ("jit(f)/reduce_sum", None),
    ("w", None),
])
def test_the_innermost_scope_through_the_wrappers(op_name, phase):
    assert dp.phase_of(op_name) == phase


@jax.custom_vjp
def _doubled(x):
    return 2.0 * x


def _doubled_fwd(x):
    return _doubled(x), None


def _doubled_bwd(_, g):
    return (jnp.sin(g),)     # a backward of its own, to be found by opcode


_doubled.defvjp(_doubled_fwd, _doubled_bwd)


def _scoped(buf, w, x):
    def loss(w):
        with jax.named_scope("blades/step"):
            h = jnp.tanh(x @ w)
            with jax.named_scope("blades/attn"):
                h = jax.vmap(_doubled)(h)
            return (h ** 2).sum()

    g = jax.grad(loss)(w)
    with jax.named_scope("blades/store"):
        buf = lax.dynamic_update_slice(buf, jnp.exp(g)[None], (0, 0, 0))
    return buf, jnp.cos(g).sum()


def test_a_gradient_maps_each_instruction_to_its_innermost_phase():
    args = (jnp.zeros((4, 8, 8)), jnp.ones((8, 8)), jnp.ones((3, 8)))
    name, table = dp.phases_of_text(jax.jit(_scoped).lower(*args).compile()
                                    .as_text())
    assert name == "jit__scoped"

    def phases(primitive):
        return {p for k, p in table.items() if k.split(".")[0] == primitive}

    assert phases("tanh") == {"step"}
    assert phases("sin") == {"attn"}         # the custom_vjp's backward
    assert phases("exp") == {"store"}
    assert phases("cos") == {None}           # under no scope
    assert {"step", "attn", "store", None} == set(table.values())


# What the TPU compiler leaves without an op_name, in the form it prints: a
# Mosaic kernel's call, an outlined fusion, a layout copy of a parameter, an
# asynchronous slice and a chain of in-place updates.
_TPU_LIKE = """HloModule jit__train_block, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(256)} parameter(0)
  ROOT %mul.3 = f32[8]{0:T(256)} multiply(%param_0.1, %param_0.1), \
metadata={op_name="jit(_train_block)/jvp(blades/step)/blades/combine/mul"}
}

%async_computation.2 (param_0.2: f32[2,8]) -> f32[1,8] {
  %param_0.2 = f32[2,8]{1,0:T(2,128)} parameter(0)
  ROOT %slice.9 = f32[1,8]{1,0:T(1,128)} slice(%param_0.2), slice={[0:1], [0:8]}
}

ENTRY %main.1 (w.1: f32[2,8], x.1: f32[8]) -> (f32[8], f32[8]) {
  %w.1 = f32[2,8]{1,0:T(2,128)} parameter(0), metadata={op_name="params['w']"}
  %x.1 = f32[8]{0:T(256)} parameter(1), metadata={op_name="x"}
  %copy.4 = f32[2,8]{0,1:T(2,128)} copy(%w.1)
  %slice-start.1 = ((f32[2,8]{0,1:T(2,128)}), f32[1,8]{1,0:T(1,128)}, \
s32[]{:S(2)}) async-start(%copy.4), calls=%async_computation.2
  %slice-done.1 = f32[1,8]{1,0:T(1,128)} async-done(%slice-start.1)
  %fusion.5 = f32[8]{0:T(256)} fusion(%x.1), kind=kLoop, \
calls=%fused_computation.7
  %transpose.2 = f32[8]{0:T(256)} transpose(%slice-done.1), \
dimensions={0}, metadata={op_name="jit(_train_block)/blades/attn_kernel/t"}
  %kernel.3 = (f32[8]{0:T(256)}, f32[8]{0:T(256)}) custom-call(\
%transpose.2, %fusion.5), custom_call_target="tpu_custom_call"
  %dynamic-update-slice.1 = f32[8]{0:T(256)} dynamic-update-slice(\
%transpose.2, %fusion.5), metadata={op_name="jit(_train_block)/blades/store/u"}
  %dynamic-update-slice.2 = f32[8]{0:T(256)} dynamic-update-slice(\
%dynamic-update-slice.1, %fusion.5)
  %cosine.1 = f32[8]{0:T(256)} cosine(%x.1), metadata={op_name="jit(f)/cos"}
  ROOT %tuple.1 = (f32[8]{0:T(256)}, f32[8]{0:T(256)}) tuple(\
%dynamic-update-slice.2, %cosine.1)
}
""".replace("\\\n", "")


def test_what_the_compiler_left_unscoped_takes_its_neighbours_phase():
    name, table = dp.phases_of_text(_TPU_LIKE)
    assert name == "jit__train_block"
    assert table["fusion.5"] == "combine"          # its computation's root
    assert table["kernel.3"] == "attn_kernel"      # what it reads first
    assert table["dynamic-update-slice.2"] == "store"   # the updated buffer
    # A parameter's layout copy and the slice of it: what reads them.
    assert table["copy.4"] == table["slice-start.1"] == "attn_kernel"
    # Outside every scope by its own path, and a parameter: no phase.
    assert table["cosine.1"] is None and table["x.1"] is None


def _twin(k):
    def twin(x):
        with jax.named_scope("blades/head" if k == 2.0 else "blades/embed"):
            return x * k

    return jax.jit(twin)


def test_two_programs_of_one_name_stay_apart_and_each_is_noted_once():
    first, second = _twin(2.0), _twin(3.0)
    x = jnp.ones(4)
    n0 = dp.noted()
    for fn in (first, second, first, second):
        dp.note(fn, (x,), {})
    assert dp.noted() - n0 == 2
    dp.note(first, (jnp.ones(8),), {})          # another shape: another
    assert dp.noted() - n0 == 3                 # executable
    phases = dp.op_phases()
    twins = {k: set(v.values()) - {None} for k, v in phases.items()
             if k.startswith("jit_twin")}
    # The newest under its plain name, the older ones under
    # name(fingerprint), as the CPU's device trace names their launches.
    assert twins["jit_twin"] == {"head"} and len(twins) == 3
    assert sorted(map(sorted, twins.values())) == [["embed"], ["head"],
                                                   ["head"]]


# -- noting the streamed round of the small code model ----------------------

SMALL_LM = {
    "dataset": {"type": "packed_tokens", "seq_len": 32, "vocab_size": 256,
                "train_rows": 2, "test_rows": 1, "doc_median": 10},
    "num_clients": 6, "train_batch_size": 1, "seed": 1,
    "input_shape": [32], "num_classes": 256,
    "global_model": dict(
        type="gqa_moe_lm", vocab_size=256, hidden_size=64,
        num_hidden_layers=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        sliding_window=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, first_expert=0, experts_held=4,
        num_experts_per_tok=2, moe_intermediate_size=32, attn_block=8),
    "execution": "streamed", "client_block": 1, "update_dtype": "bfloat16",
    "num_malicious_clients": 2, "adversary_config": {"type": "ALIE"},
    "server_config": {"lr": 1.0, "aggregator": {"type": "Median"}},
    "client_config": {"lr": 0.1, "num_batch_per_round": 1},
    "evaluation_interval": 0,
}
ROUND_PROGRAMS = ("_train_block", "_finish", "_round_counters")


def _small_lm():
    from blades_tpu.algorithms import get_algorithm_class

    _, config = get_algorithm_class("FEDAVG", return_config=True)
    config.update_from_dict(json.loads(json.dumps(SMALL_LM)))
    return config.build()


def _entry(text: str) -> list:
    """The entry computation's instruction lines."""
    body = text[text.index("\nENTRY") + 1:]
    return body[:body.index("\n}")].splitlines()[1:]


def _strip(text: str) -> str:
    """The computations' instructions without their metadata (and without
    the module's table of source locations)."""
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(
        line for line in text.splitlines()
        if line.startswith(("%", "ENTRY", " ", "}"))))


def _programs_since(count: int) -> dict:
    """``{module name: kept program}`` of the last ``count`` noted."""
    return {p.parsed()[0]: p for p in list(dp._programs)[-count:]}


@pytest.fixture(scope="module")
def noted_rounds():
    """Two rounds of the small code model (6 clients, 2 elided, blocks of
    one lane), the backend's compiles counted by program; then one round of
    the same model built with every ``jax.named_scope`` a no-op."""
    compiles = []
    on = [True]

    def listen(event, duration, **kw):
        if on[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    algo = _small_lm()
    n0 = dp.noted()
    rows = [algo.train()]
    first = (dp.noted() - n0, list(compiles))
    del compiles[:]
    rows.append(algo.train())
    second = (dp.noted() - n0 - first[0], list(compiles))
    on[0] = False
    progs = _programs_since(first[0])
    need = dp.hbm_need_bytes()
    n1 = dp.noted()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        _small_lm().train()
    bare = _programs_since(dp.noted() - n1)["jit__train_block"]
    return {"first": first, "second": second, "rows": rows,
            "phases": {k: p.parsed()[1] for k, p in progs.items()},
            "need": {k: need[k] for k in progs},
            "scoped": progs["jit__train_block"].executable.get_hlo_text(),
            "bare": bare.executable.get_hlo_text()}


def test_noting_compiles_nothing_and_a_second_round_notes_nothing(
        noted_rounds):
    noted, compiled = noted_rounds["first"]
    assert noted == len(ROUND_PROGRAMS)
    # Each of the round's programs compiled once: the call found what
    # noting compiled.
    assert sorted(c for c in compiled if c[4:-1] in ROUND_PROGRAMS) == \
        sorted(f"jit({p})" for p in ROUND_PROGRAMS)
    assert noted_rounds["second"] == (0, [])
    assert all(r["train_loss"] == r["train_loss"]
               for r in noted_rounds["rows"])


def test_every_block_instruction_with_metadata_finds_a_phase(noted_rounds):
    phases = noted_rounds["phases"]
    block = phases["jit__train_block"]
    for line in _entry(noted_rounds["scoped"]):
        name = dp._INSTRUCTION.match(line).group(2)
        if 'op_name="' in line and " parameter(" not in line:
            assert block[name] is not None, line
    assert {"sample", "step", "store", "embed", "attn_window", "attn_full",
            "attn_kernel", "router", "dispatch", "experts", "combine",
            "head"} <= set(block.values())
    assert {"forge", "aggregate"} <= set(phases["jit__finish"].values())
    need = noted_rounds["need"]
    assert need["jit__train_block"] > 0 and need["jit__finish"] > 0


def test_the_scopes_are_metadata_only(noted_rounds):
    """Built with every scope a no-op, the block compiles to the same
    instructions: every scope this code opens (the parent's and the new
    ones) is metadata alone."""
    assert noted_rounds["scoped"] != noted_rounds["bare"]
    assert "blades/" not in noted_rounds["bare"]
    assert _strip(noted_rounds["scoped"]) == _strip(noted_rounds["bare"])
