"""repro.obs: the pod train step names its phases, and a compiled step's
instructions map back to them.

The reduced OPT step is compiled through ``launch/train.compile_step``
(which registers it).  Which Python function made an instruction is read
from the HLO's own stack-frame tables, independently of the scopes, and
checked against the phase ``obs.phase_of`` gives it.
"""
import contextlib
import re
from types import SimpleNamespace as NS

import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import archs
from repro.configs.base import InputShape
from repro.launch import steps as steplib
from repro.launch import train as trainlib
from repro.launch.mesh import make_host_mesh

SHAPE = InputShape("obs", 16, 4, "train")


def _compile():
    cfg = archs.reduced(archs.get("opt-1.3b"))
    pod = steplib.PodConfig(lr=1e-2, rank=4, n_clients=2,
                            param_dtype=jnp.float32)
    compiled, _, _ = trainlib.compile_step(cfg, SHAPE, make_host_mesh(1, 1),
                                           pod)
    return compiled


@pytest.fixture(scope="module")
def step():
    compiled = _compile()
    text = obs.hlo_text(compiled)
    return obs.register(compiled), text


def stripped(text: str) -> str:
    """The module's computations with every instruction's metadata
    removed (the stack-frame tables are metadata too)."""
    keep, inside = [], False
    for line in text.splitlines():
        if re.match(r"^(ENTRY\s+)?%?[\w.\-]+\s.*\{\s*$", line) \
                and not line.startswith("HloModule"):
            inside = True
        if inside:
            keep.append(re.sub(r", metadata=\{[^}]*\}", "", line))
        if line.startswith("}"):
            inside = False
    return "\n".join(keep)


def made_by(text: str) -> dict[str, str]:
    """{instruction: the innermost Python function that traced it}, from
    the module's FunctionNames / FileLocations / StackFrames tables.  Only
    instructions traced in the step itself: an inner ``jit`` is traced once
    and its cached body keeps the frames of the first caller."""
    tables: dict[str, dict[int, str]] = {}
    section = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section, tables[line] = line, {}
            continue
        m = re.match(r"^(\d+) (.*)$", line)
        if section and m:
            tables[section][int(m.group(1))] = m.group(2)
        elif line.startswith(("%", "ENTRY", "HloModule")):
            section = None
    funcs = {k: v.strip('"') for k, v in tables["FunctionNames"].items()}
    loc_func = {k: funcs[int(re.search(r"function_name_id=(\d+)", v)[1])]
                for k, v in tables["FileLocations"].items()}
    frame_func = {k: loc_func[int(re.search(r"file_location_id=(\d+)", v)[1])]
                  for k, v in tables["StackFrames"].items()}
    out = {}
    for line in text.splitlines():
        m = obs._INSTRUCTION.match(line)
        f = re.search(r"stack_frame_id=(\d+)", line)
        if m and f and line.count("jit(") == 1:
            out[m.group(1)] = frame_func[int(f.group(1))]
    return out


@pytest.mark.parametrize("function,phases", [
    # every ± forward projection: attention's, and the FFN's in "mlp"
    ("Bundle.dense", {("ge",), ("ge", "mlp")}),
    ("Bundle.dense_t", {("ge", "head")}),   # the tied logits
    ("lm_loss", {("ge", "head")}),         # f32 logits, logsumexp, gold, mean
    ("subcge_apply", {("ma",)}),           # the fold of every message
    ("scatter_A", {("ma",)}),
    ("make_subspace", {("subspace",)}),    # the step's draw of U and V
])
def test_phase_of_puts_the_work_where_it_happens(step, function, phases):
    name, text = step
    ops = [op for op, f in made_by(text).items() if f == function]
    assert ops, function
    assert {obs.phase_of(name, op) for op in ops} == phases


def test_nearly_every_instruction_gets_a_phase(step):
    name, text = step
    carry = [m.group(1) for m in map(obs._INSTRUCTION.match,
                                     text.splitlines())
             if m and 'op_name="jit(' in m.group(2)]
    phased = [op for op in carry if obs.phase_of(name, op)]
    assert len(carry) > 1000
    assert len(phased) >= 0.9 * len(carry)


def test_a_loop_body_inherits_the_loop_scope(step):
    name, text = step
    bodies = {m[1]: m[0] for m in re.findall(
        r"%([\w.\-]+) = [^\n]* while\([^\n]*body=%([\w.\-]+)", text)}
    inherited = 0
    for body, loop in bodies.items():
        block = re.search(r"\n%?" + re.escape(body) + r" .*?\{\n(.*?)\n\}",
                          text, re.S)
        for line in block.group(1).splitlines():
            m = obs._INSTRUCTION.match(line)
            if m and obs.PREFIX not in line:
                assert obs.phase_of(name, m.group(1)) \
                    == obs.phase_of(name, loop)
                inherited += bool(obs.phase_of(name, loop))
    assert inherited > 0


def test_unknown_program_or_op_has_no_phase(step):
    name, _ = step
    assert obs.phase_of("jit_no_such_program", "fusion.1") == ()
    assert obs.phase_of(name, "no_such_instruction.7") == ()


def test_scopes_change_only_metadata(step, monkeypatch):
    _, text = step
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    # the unscoped step registers into a registry of its own
    monkeypatch.setattr(obs, "_programs", {})
    monkeypatch.setattr(obs, "_phases", {})
    plain = obs.hlo_text(_compile())
    assert obs.PREFIX not in plain
    assert stripped(plain) == stripped(text)


def test_text_falls_back_to_the_runtime_modules():
    """An executable that gives no text (as one loaded from the compile
    cache may) is read through its runtime executable's modules."""
    module = NS(name="jit_f", to_string=lambda: "HloModule jit_f")
    compiled = NS(as_text=lambda: None,
                  runtime_executable=lambda: NS(hlo_modules=lambda: [module]))
    assert obs.hlo_text(compiled) == "HloModule jit_f"


def test_parse_handles_root_nesting_and_inheritance():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={(f32[4])->f32[4]}",
        "",
        "%fused (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name='
        '"jit(f)/seedflood.ge/vmap(seedflood.head)/mul"}',
        "}",
        "",
        "%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {",
        "  %t = (s32[], f32[4]{0}) parameter(0)",
        "  %copy.3 = f32[4]{0} copy(%t)",
        '  %fusion.2 = f32[4]{0} fusion(%copy.3), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(f)/seedflood.ge/while/body/seedflood.mlp/'
        'mul"}',
        "  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%t, %fusion.2)",
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  %while.7 = (s32[], f32[4]{0}) while(%x), condition=%body, '
        'body=%body, metadata={op_name="jit(f)/seedflood.ge/while"}',
        '  ROOT %copy.9 = f32[4]{0} copy(%while.7), metadata={op_name='
        '"jit(f)/seedflood.ma/copy"}',
        "}",
    ])
    ph = obs.parse(text)
    assert ph["fusion.2"] == ("ge", "mlp")
    assert ph["m"] == ("ge", "head")
    assert ph["copy.3"] == ph["tuple.1"] == ph["while.7"] == ("ge",)
    assert ph["copy.9"] == ("ma",)
    assert ph["x"] == ()
