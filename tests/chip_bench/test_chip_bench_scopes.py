"""The program's spans and scopes, and the readers of the per-layer metrics
that attribute device time to the scopes: hand-made HLO and events, the
compiled train and decode steps on the CPU, and a trace recorded on one chip
(fixtures/fixture_scoped.*, by ``tools/record_trace_fixture.py``)."""
import gzip
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, scopes, trace
from repro.configs import registry
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import zoo
from repro.runtime import spmd, tracing
from repro.runtime.controlplane import ControlPlane
from repro.runtime.trainer import Trainer, TrainerConfig

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
NEW = ("train.vote_ms", "train.optimizer_ms", "train.head_ms", "decode.kv_cache_ms",
       "decode.attention_ms")
TRAIN_NEW = NEW[:3]


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py", name)


def _run(summary, **attributions):
    return types.SimpleNamespace(summary=summary, facts={f"scopes.{k}": a for k, a in attributions.items()})


# ---------------------------------------------------------------- hand-made

HLO = """HloModule jit_decode, is_scheduled=true

%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p = (s32[], f32[4,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = f32[4,8]{1,0} get-tuple-element(%p), index=1
  %kv = f32[4,8]{1,0} fusion(%c, %i), kind=kLoop, calls=%f1, metadata={op_name="jit(decode)/while/body/attention/kv_update/dynamic_update_slice"}
  %att = f32[4,8]{1,0} fusion(%kv), kind=kLoop, calls=%f2, metadata={op_name="jit(decode)/while/body/attention/dot_general"}
  ROOT %t = (s32[], f32[4,8]) tuple(%i, %kv)
}

ENTRY %main (a: f32[4,8], b: f32[8]) -> (f32[4,8], f32[8]) {
  %a = f32[4,8]{1,0} parameter(0), metadata={op_name="cache"}
  %b = f32[8]{0} parameter(1)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,8]) tuple(%z, %a)
  %w = (s32[], f32[4,8]) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(decode)/while"}
  %g = f32[4,8]{1,0} get-tuple-element(%w), index=1
  %copy.1 = f32[4,8]{1,0} copy(%g)
  %h = f32[8]{0} fusion(%b), kind=kLoop, calls=%f3, metadata={op_name="jit(decode)/head/dot_general"}
  %odd = f32[8]{0} fusion(%h), kind=kLoop, calls=%f4, metadata={op_name="jit(decode)/mul"}
  ROOT %out = (f32[4,8], f32[8]) tuple(%copy.1, %odd)
}
"""


def _ev(name, shape="f32[4,8]{1,0}", code="fusion"):
    return f"%{name} = {shape} {code}(f32[4,8]{{1,0}} %x), calls=%f"


def _summary(ops, runs=((1.0, 5.0), (6.0, 10.0)), module="jit_decode(7)"):
    return trace.Summary([ops], [[]], [[(module, s, e) for s, e in runs]], [], (0.0, 20.0))


def test_scope_ms_attributes_ops_and_the_copies_of_their_data():
    hlo = scopes.Hlo(HLO)
    assert hlo.module == "jit_decode"
    # the copy of the loop's result takes the scope of what wrote it
    assert scopes.has_scope(hlo.scope_of("copy.1"), "kv_update")
    ops = []
    for s in (1.0, 6.0):     # one of each op a run, and a while that only holds them
        ops += [(_ev("w", "(s32[], f32[4,8])", "while"), s, s + 3.0),
                (_ev("kv"), s, s + 0.5), (_ev("att"), s + 0.5, s + 1.5),
                (_ev("copy.1", code="copy"), s + 1.5, s + 2.5),
                (_ev("h", "f32[8]{0}"), s + 2.5, s + 2.75), (_ev("odd", "f32[8]{0}"), s + 2.75, s + 3.0)]
    ops.append((_ev("h", "f32[8]{0}"), 12.0, 13.0))        # outside the program's runs
    a = scopes.Attribution(_summary(ops), hlo)
    assert a.runs == 2 and a.matched
    assert a.ms("kv_update") == pytest.approx(1500.0)        # the update and its copy, a run
    assert a.ms("attention", minus="kv_update") == pytest.approx(1000.0)
    assert a.ms("attention") == pytest.approx(2500.0)
    # %odd's op_name names no scope; its data is the head's output
    assert a.ms("head") == pytest.approx(500.0)
    assert a.ms("head", "kv_update") == pytest.approx(2000.0)
    assert a.ms("ffn") is None                               # no op under it: nothing read
    assert a.op_ms() == pytest.approx(3000.0) and a.unclaimed_share() == 0.0
    assert a.op_ms() <= 1e3 * a.run_s / a.runs


def test_a_program_other_than_the_one_that_ran_reads_nothing():
    hlo = scopes.Hlo(HLO)
    ops = [(_ev("kv"), 1.0, 2.0), (_ev("att", "f32[4,9]{1,0}"), 2.0, 3.0)]   # another result
    a = scopes.Attribution(_summary(ops, runs=((1.0, 5.0),)), hlo)
    assert a.unmatched == 1 and not a.matched
    assert a.ms("kv_update") is None and a.unclaimed_share() is None
    other = scopes.Attribution(_summary(ops, runs=((1.0, 5.0),), module="jit_other(3)"), hlo)
    assert other.runs == 0 and other.ms("kv_update") is None


def test_unscoped_ops_stay_counted():
    text = HLO.replace(', metadata={op_name="jit(decode)/head/dot_general"}', "")
    ops = [(_ev("kv"), 1.0, 2.0), (_ev("h", "f32[8]{0}"), 2.0, 3.0), (_ev("odd", "f32[8]{0}"), 3.0, 4.0)]
    a = scopes.Attribution(_summary(ops, runs=((1.0, 5.0),)), scopes.Hlo(text))
    assert a.ms("head") is None and a.unclaimed_share() == pytest.approx(2 / 3)


# ------------------------------------------------------------- on the CPU

@pytest.fixture(scope="module")
def tiny():
    return registry.get("qwen3-1.7b", reduced=True)


def test_trainer_spans_nest_under_each_step(tiny, tmp_path):
    """Two steps of Trainer.train() under the profiler, with a control plane,
    a checkpoint each step and a straggler report each step."""
    control = ControlPlane(n_nodes=3, seed=0)
    t = Trainer(TrainerConfig(arch=tiny, steps=2, global_batch=2, seq_len=16,
                              ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1, straggler_ms=0.0),
                control=control)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        t.train()
    finally:
        jax.profiler.stop_trace()
    path = next((tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb"))
    spans = scopes.program_spans(str(path))
    steps = [sp for sp in spans if sp[0] == "repro.train.step"]
    assert [sp[3].get("step_num") for sp in steps] == [0, 1]
    for _, s, e, _ in steps:
        inside = [n[len("repro.train."):] for n, a, b, _ in spans
                  if n.startswith("repro.train.") and n != "repro.train.step" and s <= a and b <= e]
        assert inside == ["data", "place", "dispatch", "sync", "straggler", "ckpt"]
    names = {sp[0] for sp in spans}
    assert {"repro.train.init", "repro.control.commit"} <= names
    kinds = {sp[3].get("kind") for sp in spans if sp[0] == "repro.control.commit"}
    assert {"straggler", "ckpt"} <= kinds
    assert all(n[len("repro."):] in tracing.SPANS for n in names)


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("track", ["fast", "classic"])
def test_the_compiled_train_step_carries_every_scope(tiny, track):
    t = Trainer(TrainerConfig(arch=tiny, steps=1, global_batch=2, seq_len=16, track=track))
    state = jax.tree_util.tree_map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                                   jax.eval_shape(t.init_state), t.state_shardings)
    batch = t.place_batch(next(iter(SyntheticLM(t.data_cfg, shard_id=0, n_shards=1))))
    with t.mesh:
        names = _op_names(t.step_fn.lower(state, batch).compile().as_text())
    want = set(tracing.SCOPES) - {"kv_update"}
    assert want == {sc for sc in tracing.SCOPES if any(scopes.has_scope(n, sc) for n in names)}
    assert any("transpose(jvp(head))" in n for n in names)            # the head's backward


def test_the_compiled_decode_step_carries_every_model_scope(tiny):
    model = zoo.build(tiny, dtype=jnp.float32)
    prefill, decode = spmd.build_serve_fns(model, make_host_mesh(), 32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    _, cache = jax.eval_shape(prefill, params, {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)})
    text = decode.lower(params, cache, {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32)}).compile().as_text()
    names = _op_names(text)
    for sc in ("embed", "attention", "kv_update", "ffn", "head"):
        assert any(scopes.has_scope(n, sc) for n in names), sc
    assert not any(scopes.has_scope(n, "train/vote") for n in names)
    assert scopes.Hlo(text).module == "jit_decode"


def test_the_benchmark_names_the_programs_scopes():
    assert scopes.SCOPES == tracing.SCOPES
    assert scopes.SPAN_PREFIX == tracing.PREFIX


# ------------------------------------------------- traces recorded on a chip

def _fixture_attributions():
    s = trace.Summary.from_file(str(FIXTURES / "fixture_scoped.xplane.pb"), 1)
    text = gzip.open(FIXTURES / "fixture_scoped.hlo.gz", "rt").read()
    mods = {h.module: h for h in (scopes.Hlo("HloModule " + m) for m in text.split("HloModule ")[1:])}
    return s, {k: scopes.Attribution(s, mods[m]) for k, m in (("train", "jit_wrapped"), ("decode", "jit_decode"))}


def test_readers_on_a_trace_recorded_on_one_chip():
    """Two train steps and two decode steps of the REDUCED Qwen3 in bf16 on
    one TPU v5e, with the HLO of both programs (recorded and stripped by
    tools/record_trace_fixture.py)."""
    s, att = _fixture_attributions()
    run = _run(s, **att)
    for k, a in att.items():
        assert a.runs >= 1 and a.matched, k
        # attributed time lies within the program's runs
        assert 0 < a.op_ms() <= 1e3 * a.run_s / a.runs * 1.0001, k
    # at these widths the decode step's index arithmetic is a tenth of it
    assert att["train"].unclaimed_share() < 0.01 and att["decode"].unclaimed_share() < 0.2
    values = {name: _reader(name).read(run) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert sum(values[n] for n in TRAIN_NEW) <= att["train"].op_ms() * 1.0001
    assert values["decode.kv_cache_ms"] + values["decode.attention_ms"] == pytest.approx(
        att["decode"].ms("attention"))


def test_old_readers_read_as_before_and_new_ones_nothing_on_the_old_fixture():
    """The fixture of the first benchmark: a jitted matmul, no scopes."""
    s = trace.Summary.from_file(str(FIXTURES / "fixture_1chip.xplane.pb"), 1)
    _, att = _fixture_attributions()
    run = _run(s, **{k: scopes.Attribution(s, a.hlo) for k, a in att.items()})
    assert {name: _reader(name).read(run) for name in NEW} == dict.fromkeys(NEW)
    assert _reader("train.step_device_ms").read(run) == pytest.approx(0.09021739999999084)
    assert _reader("train.device_idle_pct").read(run) == pytest.approx(99.20907555288082)
    assert _reader("decode.device_idle_pct").read(run) == pytest.approx(99.20907555288082)
    assert _reader("decode.step_device_ms").read(run) is None
