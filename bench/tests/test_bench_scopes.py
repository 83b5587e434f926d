"""Device time per phase: the scope map from the compiled step's text, and
its reduction on hand-made events."""
import json
import pathlib

import pytest

from bench import manifest, run, scopes, trace
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
DEV = "/device:TPU:0"
HOST = "/host:CPU"
READERS = {"fwd_bwd_ms": "csgd_grad", "armijo_ms": "csgd_armijo",
           "ef_select_ms": "csgd_ef", "codec_ms": "csgd_codec",
           "apply_ms": "csgd_apply", "unscoped_ms": scopes.UNSCOPED}


@pytest.fixture(scope="module")
def tiny_run():
    t = json.loads((ROOT / "bench" / "traffic" /
                    "csgd-s4096-b2-1x1.json").read_text())
    t.update(seq_len=64, global_batch=4)
    return {"model": tiny.TINY, "traffic": t, "chips": 1,
            "arch": manifest.architecture("transformer_lm")}


@pytest.fixture(scope="module")
def step_text(tiny_run):
    """The tiny cell's train step, compiled on the CPU, as HLO text."""
    return scopes.step_text(tiny_run)


def names(text):
    """The instruction names of an HLO module's text."""
    return {m.group(2) for ln in text.splitlines()
            if (m := scopes._INSTR.match(ln))}


def test_step_text_is_the_harness_step(tiny_run, step_text):
    """Built again from the run's record, the step has the instructions
    of the one the harness compiles and drives."""
    from bench import harness
    tr = harness.Trainer({"name": "tiny", "model": tiny_run["model"]},
                         tiny_run["traffic"], tiny_run["arch"])
    params, state = tr.fresh_state(0)
    tr.compile(params, state, tr.put(tr.host_batch(0, 0)))

    assert names(step_text) == names(tr.step_fn.as_text())


def test_step_text_compiles_past_a_cached_step_without_names(
        tiny_run, tmp_path, monkeypatch):
    """The persistent cache's key leaves out ``op_name``: where it holds
    the same step traced without the scopes, the step is compiled afresh,
    with the scopes and the cached step's instruction names."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench import harness
    hits = []

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_listener(listen)
    cc.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            tr = harness.Trainer({"name": "tiny",
                                  "model": tiny_run["model"]},
                                 tiny_run["traffic"], tiny_run["arch"])
            params, state = tr.fresh_state(0)
            tr.compile(params, state, tr.put(tr.host_batch(0, 0)))
            cached = tr.step_fn.as_text()
            assert not scopes.of_hlo(cached)
        text = scopes.step_text(tiny_run)
    finally:
        jax.monitoring.unregister_event_listener(listen)
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
        cc.reset_cache()
    assert hits
    assert set(scopes.of_hlo(text).values()) == set(scopes.PHASES)
    assert names(text) == names(cached)


def test_of_hlo_on_the_compiled_step(step_text):
    m = scopes.of_hlo(step_text)
    assert set(m.values()) == set(scopes.PHASES)
    comps = scopes._parse(step_text)
    ops = {n: v for c in comps.values() for n, v in c["ops"].items()}
    # the Armijo search's loop, and every backward op of the program
    loops = [n for n, op in ops.items()
             if n.startswith("while") and op.phase == "csgd_armijo"]
    assert loops and all(m[n] == "csgd_armijo" for n in loops)
    backward = []
    for ln in step_text.splitlines():
        op = scopes._OP_NAME.search(ln)
        if op and op.group(1).startswith("jit(worker_fn)/") \
                and "transpose(" in op.group(1):
            backward.append(scopes._INSTR.match(ln).group(2))
    assert backward and {m[n] for n in backward} == {"csgd_grad"}


def test_phase_of_takes_the_outermost_phase():
    reused = ("jit(worker_fn)/csgd_armijo/jit(rmsnorm)/jit(worker_fn)/"
              "csgd_grad/jvp(jit(rmsnorm))/while/body/mul")
    assert scopes.phase_of(reused) == "csgd_armijo"
    assert scopes.phase_of("jit(worker_fn)/csgd_gradient/mul") is None
    assert scopes.phase_of("jit(worker_fn)/add") is None


HLO = """HloModule jit_worker_fn, entry_computation_layout={()->f32[4]}

%fused_computation.1 (param_0: bf16[4], param_1: f32[]) -> (f32[4], bf16[4]) {
  %param_0 = bf16[4]{0} parameter(0)
  %convert.1 = f32[4]{0} convert(%param_0), metadata={op_name="jit(worker_fn)/csgd_ef/convert_element_type"}
  %bitcast.2 = f32[4]{0} bitcast(%convert.1)
  %param_1 = f32[] parameter(1)
  %mul.3 = f32[4]{0} multiply(%convert.1, %convert.1), metadata={op_name="jit(worker_fn)/csgd_armijo/mul"}
  %convert.4 = bf16[4]{0} convert(%mul.3)
  ROOT %tuple.5 = (f32[4]{0}, bf16[4]{0}) tuple(%bitcast.2, %convert.4)
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %sub.6 = f32[4]{0} subtract(%param_0.1, %param_0.1), metadata={op_name="jit(worker_fn)/csgd_apply/sub"}
}

%body.20 (wide.param: (u32[], f32[4])) -> (u32[], f32[4]) {
  %wide.param = (u32[], f32[4]{0}) parameter(0)
  %get-tuple-element.21 = f32[4]{0} get-tuple-element(%wide.param), index=1
  %dynamic-update-slice.22 = f32[4]{0} dynamic-update-slice(%get-tuple-element.21, %get-tuple-element.21)
  %get-tuple-element.23 = u32[] get-tuple-element(%wide.param), index=0
  ROOT %tuple.24 = (u32[], f32[4]{0}) tuple(%get-tuple-element.23, %dynamic-update-slice.22)
}

%cond.25 (wide.param.1: (u32[], f32[4])) -> pred[] {
  %wide.param.1 = (u32[], f32[4]{0}) parameter(0)
  ROOT %compare.26 = pred[] constant(false)
}

ENTRY %main.7 (p: bf16[4]) -> f32[4] {
  %p = bf16[4]{0} parameter(0)
  %c = f32[] constant(1)
  %fusion.8 = (f32[4]{0}, bf16[4]{0}) fusion(%p, %c), kind=kLoop, calls=%fused_computation.1
  %get-tuple-element.9 = f32[4]{0} get-tuple-element(%fusion.8), index=0
  %fusion.10 = f32[4]{0} fusion(%get-tuple-element.9), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(worker_fn)/csgd_codec/add"}
  %copy.11 = f32[4]{0} copy(%fusion.10)
  %add.12 = f32[4]{0} add(%copy.11, %copy.11), metadata={op_name="jit(worker_fn)/csgd_gradx/add"}
  %copy.13 = f32[4]{0} copy(%add.12)
  %multiply.14 = f32[4]{0} multiply(%copy.13, %copy.13), metadata={op_name="jit(worker_fn)/csgd_apply/mul"}
  %zero.27 = u32[] constant(0)
  %tuple.15 = (u32[], f32[4]{0}) tuple(%zero.27, %multiply.14)
  %while.16 = (u32[], f32[4]{0}) while(%tuple.15), condition=%cond.25, body=%body.20
  %get-tuple-element.17 = f32[4]{0} get-tuple-element(%while.16), index=1
  ROOT %add.18 = f32[4]{0} add(%get-tuple-element.17, %add.12), metadata={op_name="jit(worker_fn)/csgd_ef/add"}
}
"""


def test_of_hlo_rules():
    m = scopes.of_hlo(HLO)
    # no metadata, several outputs: the first output that names a phase
    assert m["fusion.8"] == "csgd_ef"
    # the fusion's own metadata comes first
    assert m["fusion.10"] == "csgd_codec"
    # a copy XLA adds serves its reader: none where the reader names no
    # phase (a misspelled scope), else the reader's phase
    assert "copy.11" not in m and "add.12" not in m
    assert m["copy.13"] == "csgd_apply"
    # a loop XLA adds takes its reader's phase, its body ops the loop's
    assert m["while.16"] == "csgd_ef"
    assert m["dynamic-update-slice.22"] == "csgd_ef"


def ev(name, start, dur, line="XLA Ops", plane=DEV):
    return {"plane": plane, "line": line, "name": name,
            "start": float(start), "dur": float(dur)}


@pytest.fixture
def events():
    """Two steps of 100 ns; each: the Armijo loop's while holding two body
    ops, a grad fusion, a copy no phase claims.  An op of another program
    runs between the steps."""
    out = [ev("bench.step", 0, 400, line="python", plane=HOST)]
    for t0 in (0, 200):
        out += [
            ev("jit_worker_fn(7)", t0, 100, line="XLA Modules"),
            ev("fusion.1", t0, 30),                 # grad
            ev("while.2", t0 + 30, 50),             # armijo, 10 own
            ev("fusion.3", t0 + 30, 25),            # armijo body
            ev("fusion.4", t0 + 55, 15),            # armijo body
            ev("copy.5", t0 + 80, 20),              # unscoped
        ]
    out += [ev("jit_other(3)", 120, 50, line="XLA Modules"),
            ev("fusion.1", 120, 50)]
    return out


MAP = {"fusion.1": "csgd_grad", "while.2": "csgd_armijo",
       "fusion.3": "csgd_armijo", "fusion.4": "csgd_armijo"}


def test_phase_ms(events):
    ms = scopes.phase_ms(events, DEV, MAP, 2)
    assert ms == pytest.approx({
        "csgd_grad": 30e-6, "csgd_armijo": 50e-6, "csgd_ef": 0.0,
        "csgd_codec": 0.0, "csgd_apply": 0.0, "unscoped": 20e-6})
    # phases and unscoped add up to the step program's busy self time
    step = scopes.step_ops(events, DEV)
    assert len(step) == 10
    assert sum(ms.values()) * 2 * 1e6 == pytest.approx(
        sum(t for _, t in trace.self_times(step)))
    assert sum(ms.values()) * 2 * 1e6 == pytest.approx(
        trace.busy(events, DEV, 0, 400) - 50)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers(events, metric):
    read = run.load_reader(metric)
    want = scopes.phase_ms(events, DEV, MAP, 2)[READERS[metric]]
    assert read({"events": events, "scopes": MAP}) == pytest.approx(want)
    # no events, no map, or a program that names no phase
    assert read({"events": None, "scopes": MAP}) is None
    assert read({"events": [], "scopes": MAP}) is None
    assert read({"events": events, "scopes": {}}) is None
    host_only = [e for e in events if e["plane"] == HOST]
    assert read({"events": host_only, "scopes": MAP}) is None


def test_readers_build_the_map_once(events, monkeypatch):
    """Without a map in the run, the first reader builds it from the step's
    text and the others reuse it; a program that names no phase gives
    none."""
    calls = []
    monkeypatch.setattr(scopes, "step_text",
                        lambda run: calls.append(run) or HLO)
    run_ = {"events": events}
    for metric in sorted(READERS):
        want = scopes.phase_ms(events, DEV, scopes.of_hlo(HLO),
                               2)[READERS[metric]]
        assert run.load_reader(metric)(run_) == pytest.approx(want)
    assert len(calls) == 1 and run_["scopes"] == scopes.of_hlo(HLO)
    monkeypatch.setattr(scopes, "step_text", lambda run: None)
    for metric in sorted(READERS):
        assert run.load_reader(metric)({"events": events}) is None
