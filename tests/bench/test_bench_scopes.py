"""Device time per layer (bench/scopes.py): the reduction on small
synthetic traces and HLO snippets, and the scope names of the compiled
train step, which must be the names the reduction looks for."""
import collections

import pytest

from bench import scopes
from bench.kinds import train
from bench.tracing import Trace

HLO = """\
HloModule jit_train_step, is_scheduled=true

%body.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.1, \
metadata={op_name="jit(train_step)/transpose(jvp(backbone))/while/body/\
checkpoint/rematted_computation/attention/dot_general" stack_frame_id=3}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %while.170 = f32[8]{0} while(%x), condition=%c.1, body=%body.1, \
metadata={op_name="jit(train_step)/transpose(jvp(backbone))/while"}
  %fusion.357 = f32[8]{0} fusion(%while.170), kind=kLoop, calls=%f.2, \
metadata={op_name="jit(train_step)/optimizer/add" stack_frame_id=9}
  %copy.3 = f32[8]{0} copy(%fusion.357)
  ROOT %all-gather.2 = f32[8]{0} all-gather(%copy.3), dimensions={0}, \
metadata={op_name="jit(train_step)/transpose(jvp(head_loss))/mul"}
}
"""


def test_op_paths_from_hlo_text():
    paths = scopes.op_paths(HLO)
    assert paths["fusion.7"].endswith("rematted_computation/attention/"
                                      "dot_general")
    assert paths["while.170"] == ("jit(train_step)/transpose(jvp(backbone))"
                                  "/while")
    assert paths["all-gather.2"].endswith("transpose(jvp(head_loss))/mul")
    assert paths["copy.3"] == ""
    assert paths["x"] == "state.params[\\'w\\']"


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/transpose(jvp(head_loss))/mul", "head_loss"),
    ("jit(f)/jvp(sampler_draw)/while/body/sort", "sampler_draw"),
    ("jit(f)/transpose(jvp(backbone))/while/body/checkpoint/"
     "rematted_computation/attention/dot_general", "attention"),
    ("jit(f)/backbone/attention/closed_call/jit(_where)/select_n",
     "attention"),
    ("jit(f)/optimizer/jit(clip)/mul", "optimizer"),
    ("jit(f)/jvp()/div", "unscoped"),
    ("", "unscoped"),
    ("jit(f)/backbones/mul", "unscoped"),
])
def test_scope_is_the_innermost_layer_after_unwrapping(path, scope):
    assert scopes.scope_of(path) == scope


def test_unwrap_strips_nested_transforms():
    assert scopes.unwrap("transpose(jvp(head_loss))") == "head_loss"
    assert scopes.unwrap("jvp()") == ""
    assert scopes.unwrap("backbone") == "backbone"


def test_self_time_under_nested_while_events():
    # a while loop whose body runs two ops, one of them a loop of its own
    ops = [(0, 100, "while.1"), (10, 40, "while.2"), (12, 20, "fusion.1"),
           (25, 30, "fusion.2"), (50, 90, "fusion.3"), (120, 130, "copy.1")]
    assert scopes.self_times(ops) == [100 - 30 - 40, 30 - 8 - 5, 8, 5, 40,
                                      10]


def test_self_times_sum_to_busy_time():
    ops = [(0, 100, "while.1"), (10, 40, "fusion.1"), (10, 40, "fusion.2"),
           (95, 110, "fusion.3"), (105, 120, "fusion.4"), (200, 210, "x")]
    assert sum(scopes.self_times(ops)) == pytest.approx(
        scopes.covered(ops))


def test_split_charges_self_time_to_scopes_and_remat():
    paths = scopes.op_paths(HLO)
    ops = [(0, 100, "while.170"), (10, 40, "fusion.7"), (50, 60, "fusion.7"),
           (100, 120, "fusion.357"), (120, 125, "copy.3"),
           (125, 130, "all-gather.2"), (130, 131, "not-in-the-text.1")]
    got = scopes.split_ns(ops, paths, 0, 1000)
    assert got["backbone"] == 60
    assert got["attention"] == 40
    assert got["remat"] == 40
    assert got["optimizer"] == 20
    assert got["head_loss"] == 5
    assert got["unscoped"] == 6      # copy.3 (no op_name) + the unknown op
    assert got["mapped"] == 130
    assert got["busy"] == 131
    layers = scopes.LAYER_SCOPES + (scopes.UNSCOPED,)
    assert sum(got[k] for k in layers) == got["busy"]


def test_split_clips_to_the_window():
    paths = scopes.op_paths(HLO)
    ops = [(0, 100, "while.170"), (10, 40, "fusion.7")]
    got = scopes.split_ns(ops, paths, 20, 60)
    assert got["attention"] == 20 and got["backbone"] == 20
    assert got["busy"] == 40


def _run(devices, hlo_text=HLO, steps=2):
    return {"kind": "train", "steps": steps, "lo": 0, "hi": 10_000_000,
            "trace": Trace(devices=devices,
                           spans=[(0, 10_000_000, "bench.window")]),
            "hlo_text": hlo_text}


def test_reading_is_the_mean_over_chips_per_step():
    run = _run({"/device:TPU:0": [(0, 4_000_000, "fusion.357")],
                "/device:TPU:1": [(0, 2_000_000, "fusion.357"),
                                  (3_000_000, 3_500_000, "fusion.7")]})
    assert scopes.device_ms(run, "optimizer") == pytest.approx(1.5)
    assert scopes.device_ms(run, "attention") == pytest.approx(0.125)
    assert scopes.device_ms(run, "remat") == pytest.approx(0.125)
    assert scopes.device_ms(run, "sampler_draw") == 0.0


@pytest.mark.parametrize("case", ["no_hlo_text", "no_op_maps",
                                  "no_layer_scope", "no_steps",
                                  "not_train"])
def test_reading_is_none_when_nothing_maps(case):
    run = _run({"/device:TPU:0": [(0, 1000, "fusion.357"),
                                  (1000, 2000, "copy.3")]})
    if case == "no_hlo_text":
        del run["hlo_text"]
    elif case == "no_op_maps":
        run["hlo_text"] = HLO.replace("fusion.357", "fusion.358").replace(
            "copy.3", "copy.4")
    elif case == "no_layer_scope":
        run["hlo_text"] = HLO.replace("/optimizer/", "/")
    elif case == "no_steps":
        run["steps"] = 0
    else:
        run["kind"] = "serve"
    for name in scopes.LAYER_SCOPES + (scopes.UNSCOPED, "remat"):
        assert scopes.device_ms(run, name) is None


@pytest.fixture(scope="module")
def step_paths(lm):
    """op_name paths of the compiled train step at the LM test size, with
    the cell's sampler (block-quadratic-shared)."""
    setup = train.Setup(train.Inputs(lm, 1), lambda msg: None)
    assert setup.inputs.arch.sampler == "block-quadratic-shared"
    return scopes.op_paths(setup.compiled.as_text())


def _layers_in(path):
    """The layer scopes along ``path``, outermost first, repeats merged."""
    seq = []
    for component in path.split("/"):
        name = scopes.unwrap(component)
        if name in scopes.LAYER_SCOPES and (not seq or seq[-1] != name):
            seq.append(name)
    return tuple(seq)


def _under_transpose(path, scope):
    """``scope`` is named in ``path`` inside a ``transpose(...)`` (the
    backward pass), on its own component or an outer one."""
    outer = []
    for component in path.split("/"):
        outer.append(component)
        if scopes.unwrap(component) == scope:
            return any(c.startswith("transpose(") for c in outer)
    return False


def test_compiled_step_names_every_layer_scope(step_paths):
    named = {s for p in step_paths.values() for s in _layers_in(p)}
    assert named == set(scopes.LAYER_SCOPES)
    for scope in ("backbone", "attention", "head_loss"):
        assert any(_under_transpose(p, scope) for p in step_paths.values()), \
            scope


def test_only_attention_nests_in_another_scope(step_paths):
    nests = collections.Counter(
        seq for seq in map(_layers_in, step_paths.values()) if len(seq) > 1)
    assert set(nests) == {("backbone", "attention")}, nests
