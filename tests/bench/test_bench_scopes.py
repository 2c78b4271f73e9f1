"""bench/scopes.py: the program's named scopes, from the compiled HLO to the
phase of each instruction, and device milliseconds per step by phase."""
import contextlib
import gzip
import pathlib
import re
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench_cells import run, tiny  # noqa: E402

from bench import scopes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
READERS = {"step.dequantize": "dequantize", "model.forward": "forward",
           "model.recompute": "recompute", "model.backward": "backward",
           "model.vocab": "vocab", "step.update": "update", "step.unscoped": "unscoped"}
PARTITION = [m for m, k in READERS.items() if k != "vocab"]

# one computation of each kind the resolution follows: a fusion without
# metadata (root with and without), copies, an async pair, a loop branch
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/step.update/mul" stack_frame_id=3}
}

%fused_computation.2 (param_0.2: f32[4]) -> (f32[4], f32[4]) {
  %param_0.2 = f32[4]{0} parameter(0)
  %neg.2 = f32[4]{0} negate(%param_0.2), metadata={op_name="jit(train_step)/transpose(jvp(lm.head))/neg"}
  ROOT %tuple.2 = (f32[4]{0}, f32[4]{0}) tuple(%neg.2, %param_0.2)
}

%branch_identity (p.3: s8[4]) -> s8[4] {
  %p.3 = s8[4]{0} parameter(0)
  ROOT %copy.3 = s8[4]{0} copy(%p.3)
}

%branch_crs (p.4: s8[4]) -> s8[4] {
  %p.4 = s8[4]{0} parameter(0)
  ROOT %negate.4 = s8[4]{0} negate(%p.4), metadata={op_name="jit(train_step)/step.update/cond/branch_1_fun/neg"}
}

ENTRY %main.9 (state_planes: s8[8,4], x: f32[4], pred.0: pred[]) -> (f32[4], f32[4]) {
  %state_planes = s8[8,4]{1,0} parameter(0), metadata={op_name="state.sliced[\\'embed\\'].planes"}
  %x = f32[4]{0} parameter(1), metadata={op_name="batch[\\'inputs\\']"}
  %pred.0 = pred[] parameter(2)
  %copy.10 = s8[8,4]{1,0} copy(%state_planes)
  %convert.11 = f32[8,4]{1,0} convert(%copy.10), metadata={op_name="jit(train_step)/step.dequantize/convert_element_type"}
  %copy-start.12 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x)
  %copy-done.13 = f32[4]{0} copy-done(%copy-start.12)
  %dot.14 = f32[4]{0} dot(%convert.11, %copy-done.13), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp(lm.head)/dot_general"}
  %copy.15 = f32[4]{0} copy(%dot.14)
  %fusion.16 = f32[4]{0} fusion(%copy.15), kind=kLoop, calls=%fused_computation.1
  %fusion.17 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.16), kind=kLoop, calls=%fused_computation.2
  %gte.18 = f32[4]{0} get-tuple-element(%fusion.17), index=0
  %add.19 = f32[4]{0} add(%gte.18, %gte.18), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/add"}
  %mul.20 = f32[4]{0} multiply(%add.19, %add.19), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mul"}
  %mul.21 = f32[4]{0} multiply(%mul.20, %mul.20), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/mul"}
  %s8.22 = s8[4]{0} convert(%mul.21), metadata={op_name="jit(train_step)/step.update/convert_element_type"}
  %conditional.23 = s8[4]{0} conditional(%pred.0, %s8.22, %s8.22), true_computation=%branch_crs, false_computation=%branch_identity, metadata={op_name="jit(train_step)/step.update/cond"}
  %add.24 = f32[4]{0} add(%mul.21, %mul.21), metadata={op_name="jit(train_step)/add"}
  ROOT %tuple.25 = (f32[4]{0}, f32[4]{0}) tuple(%add.24, %mul.21)
}
"""


def test_instruction_ops_resolve_fusions_copies_and_branches():
    ops = scopes.instruction_ops(HLO)
    # a fusion without metadata: its body's root, else the body's first
    assert ops["fusion.16"] == "jit(train_step)/step.update/mul"
    assert ops["fusion.17"] == "jit(train_step)/transpose(jvp(lm.head))/neg"
    # a copy takes its operand's; a copy of a parameter its user's
    assert ops["copy.15"] == "jit(train_step)/jvp(lm.head)/dot_general"
    assert ops["copy.10"] == "jit(train_step)/step.dequantize/convert_element_type"
    # an async pair follows the chain to the user of the copy's end
    assert ops["copy-start.12"] == ops["copy-done.13"] == ops["dot.14"]
    # a branch's copy of its parameter: the conditional that runs it
    assert ops["copy.3"] == "jit(train_step)/step.update/cond"


def test_classify_peels_transforms():
    c = scopes.classify
    assert c("jit(train_step)/step.dequantize/convert_element_type") == ("dequantize", False)
    assert c("jit(train_step)/vmap()/step.update/vmap(jit(_threefry_fold_in))/add") == ("update", False)
    assert c("jit(train_step)/jvp(lm.head)/dot_general") == ("forward", True)
    assert c("jit(train_step)/transpose(jvp(lm.embed))/scatter-add") == ("backward", True)
    assert c("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mul") \
        == ("recompute", False)
    assert c("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mul") == ("backward", False)
    assert c("jit(train_step)/jvp()/while/body/closed_call/mul") == ("forward", False)
    assert c("jit(train_step)/add") == ("unscoped", False)
    assert c(None) == ("unscoped", False)
    # only a segment before the operation is a scope
    assert c("jit(train_step)/step.update") == ("unscoped", False)
    assert c("jit(train_step)/lm.headroom/mul") == ("unscoped", False)


def test_phase_map_of_hand_written_hlo():
    pm = scopes.phase_map(HLO)
    assert pm.scoped
    assert {n: pm.phase[n] for n in ("convert.11", "dot.14", "fusion.17", "add.19", "mul.20",
                                     "mul.21", "fusion.16", "add.24")} == {
        "convert.11": "dequantize", "dot.14": "forward", "fusion.17": "backward",
        "add.19": "recompute", "mul.20": "backward", "mul.21": "forward",
        "fusion.16": "update", "add.24": "unscoped"}
    assert {"dot.14", "copy.15", "fusion.17"} <= pm.vocab
    assert "mul.21" not in pm.vocab


def _summary(op_s):
    return tr.Summary(window_s=1.0, busy_s=1.0, op_s=op_s, op_calls=dict.fromkeys(op_s, 1), idle_gaps=[])


def test_phase_ms_per_step_and_partition():
    pm = scopes.phase_map(HLO)
    # seconds over the window, 4 steps; "elsewhere" is no instruction of
    # the step and counts as unscoped
    op_s = {"convert.11": 0.004, "dot.14": 0.008, "fusion.17": 0.012, "add.19": 0.002,
            "mul.20": 0.001, "fusion.16": 0.02, "add.24": 0.0004, "elsewhere": 0.0002}
    ms = scopes.phase_ms(_summary(op_s), pm, steps=4)
    assert ms == pytest.approx({"dequantize": 1.0, "forward": 2.0, "recompute": 0.5, "backward": 3.25,
                                "update": 5.0, "unscoped": 0.15, "vocab": 5.0})
    assert sum(ms[p] for p in scopes.PHASES) == pytest.approx(1e3 * sum(op_s.values()) / 4)


def test_readers_give_none_without_step_scopes():
    no_update = HLO.replace("step.update", "optimizer")
    assert not scopes.phase_map(no_update).scoped
    summary = _summary({"dot.14": 0.01})
    for metric in READERS:
        read = run.load_reader(metric)
        assert read(types.SimpleNamespace(summary=summary, hlo=no_update, steps=2)) is None
        assert read(types.SimpleNamespace(summary=summary, hlo=HLO, steps=2)) is not None
    assert scopes.phase_ms(summary, scopes.phase_map(HLO.replace("step.dequantize", "deq")), 2) is None


def test_scope_names_are_the_programs():
    from repro.models import lm
    from repro.train import step

    assert (step.DEQUANTIZE_SCOPE, step.UPDATE_SCOPE) == (scopes.DEQUANTIZE_SCOPE, scopes.UPDATE_SCOPE)
    assert (lm.EMBED_SCOPE, lm.HEAD_SCOPE) == scopes.VOCAB_SCOPES


# ------------------------- the tiny steps on the CPU -------------------------

_TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def strip_metadata(hlo_text: str) -> str:
    """The HLO text without instruction metadata and source-location tables."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def _compile_tiny(name):
    import jax
    import jax.numpy as jnp
    from repro.optim import panther
    from repro.optim.schedules import constant
    from repro.train import step as train_step

    cell = tiny(name)
    R = run.load_reference(cell)
    m = R.Model.from_config(cell["config"])
    cfg, opt, rules = run.program_objects(cell)

    def make_state(k):
        digital, sliced = panther.init_split(R.nest(R.gen_params(k, m)), opt)
        return train_step.TrainState(step=jnp.zeros((), jnp.int32), digital=digital, sliced=sliced,
                                     rng=jax.random.fold_in(k, 1))

    state = jax.eval_shape(make_state, R.seed_key(1))
    tokens = jax.ShapeDtypeStruct((cell["traffic"]["batch"], cell["traffic"]["seq"]), jnp.int32)
    step_fn = train_step.make_train_step(cfg, opt, constant(cell["traffic"]["lr"]), plan_rules=rules)
    return jax.jit(step_fn, donate_argnums=0).lower(state, {"inputs": tokens, "labels": tokens}) \
        .compile().as_text()


@pytest.fixture(scope="module", params=["phi4-train", "phi4-train-adc9"])
def tiny_hlo(request):
    return request.param, _compile_tiny(request.param)


def test_tiny_step_phases(tiny_hlo):
    _, hlo = tiny_hlo
    ops = scopes.instruction_ops(hlo)
    seen = set()
    for op in filter(None, ops.values()):
        seen |= scopes.segments(op)[1]
    # the four scopes, and no segment of JAX's own that could pass for one
    ours = {scopes.DEQUANTIZE_SCOPE, scopes.UPDATE_SCOPE, *scopes.VOCAB_SCOPES}
    assert {s for s in seen if s.startswith(("step.", "lm."))} == ours
    pm = scopes.phase_map(hlo)
    assert pm.scoped
    instrs, _, _ = scopes._parse(hlo)
    matmuls = [n for n, ins in instrs.items() if ins.opcode in ("dot", "convolution")]
    assert matmuls
    assert [n for n in matmuls if pm.phase[n] == "unscoped"] == []
    assert set(scopes.PHASES) - {"unscoped"} <= set(pm.phase.values())
    assert pm.vocab


def test_tiny_step_scopes_are_metadata_only(tiny_hlo, monkeypatch):
    """The step compiled with every named scope made a no-op is the same
    program, instruction for instruction, but for the metadata."""
    import jax

    name, hlo = tiny_hlo
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compile_tiny(name)
    assert scopes.UPDATE_SCOPE not in bare
    assert strip_metadata(bare) == strip_metadata(hlo)


# ------------------------ a traced window on the chip ------------------------

@pytest.fixture(scope="module")
def recorded():
    """An 1.84 s traced window of phi4-train on one TPU v5 lite (11 steps),
    with the compiled step's HLO text of the same run."""
    summary = tr.load_profile(str(DATA / "phi4-train.scoped.xplane.pb"))
    with gzip.open(DATA / "phi4-train.scoped.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    return summary, hlo


def test_recorded_window_is_attributed(recorded):
    summary, hlo = recorded
    steps = summary.op_calls["panther_opa_deposit.1"]  # once a step
    assert steps == 11
    pm = scopes.phase_map(hlo)
    assert set(summary.op_s) <= set(pm.phase)
    total = sum(summary.op_s.values())
    named = sum(s for n, s in summary.op_s.items() if pm.phase[n] != "unscoped")
    assert named >= 0.98 * total
    ctx = types.SimpleNamespace(summary=summary, hlo=hlo, steps=steps)
    ms = {m: run.load_reader(m)(ctx) for m in READERS}
    assert sum(ms[m] for m in PARTITION) == pytest.approx(1e3 * total / steps, rel=1e-9)
    # device ms per step by phase (the update, then the backward, lead)
    assert ms["step.update"] > ms["model.backward"] > ms["model.forward"] > ms["model.recompute"] \
        > ms["step.dequantize"] > ms["step.unscoped"]
    assert 0 < ms["model.vocab"] < ms["model.forward"] + ms["model.backward"]


def test_recorded_instructions_land_in_their_phases(recorded):
    _, hlo = recorded
    pm = scopes.phase_map(hlo)
    where = lambda n: (pm.phase[n], n in pm.vocab)
    # the embedding's deposit and the layers' fused OPA: the update
    assert where("panther_opa_deposit.1") == ("update", False)
    assert {where(f"panther_opa_fused.{i}") for i in range(25, 30)} == {("update", False)}
    # the tied head's logits, and its input gradient from them
    assert where("fusion.258") == ("forward", True)
    assert where("fusion.207") == ("backward", True)
    # the head's weight gradient (the tied embedding's), fused with the
    # update's quantize: its root is the backward's matmul
    assert where("multiply_reduce_fusion") == ("backward", True)
    # the embedding's planes dequantized, fused into the embedding's cast to
    # bfloat16: its root is the forward's cast
    assert where("fusion.187") == ("forward", True)
    assert {pm.phase[n] for n, ins in scopes._parse(hlo)[0].items()
            if ins.op_name and "step.dequantize" in ins.op_name} == {"dequantize"}
