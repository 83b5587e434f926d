"""Device time of the train step by phase, from the program's named scopes.

The step names its phases with ``jax.named_scope`` (``csgd_grad``,
``csgd_armijo``, ``csgd_ef``, ``csgd_codec``, ``csgd_apply``).  The names
travel as ``op_name`` metadata into the optimized HLO, whose instruction
names are the op names of the device trace.  So:

* :func:`of_hlo` reads the compiled step's text into ``{instruction name:
  phase}``, the phase being the outermost phase token of the
  instruction's ``op_name``; a fusion whose own metadata is empty takes
  the phase of the value its computation returns, and an instruction XLA
  added the phase of the instruction that reads it.
* :func:`phase_ms` sums the self time of the step program's device ops
  per phase; the ops no phase claims go under ``unscoped``.
* :func:`step_text` builds the cell's step again, as the harness builds
  it, for a reader that was handed only the run's record: the compile
  reads back, from the persistent cache, the executable the run traced.

A scope name the program misspells is no phase here: its ops fall under
``unscoped``, which is what that number guards.
"""
from __future__ import annotations

import re
from typing import NamedTuple

from bench import trace

PHASES = ("csgd_grad", "csgd_armijo", "csgd_ef", "csgd_codec",
          "csgd_apply")
UNSCOPED = "unscoped"
# the step program's module in the trace's module line
PROGRAM = "jit_worker_fn"

_TOKEN = re.compile(r"\b(" + "|".join(PHASES) + r")\b")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation"
                   r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")


def phase_of(op_name: str) -> str | None:
    """The outermost phase token of an ``op_name`` path, or None.

    The program's phases do not nest, so the first token is the phase.
    A later one can name another: where a jitted function is reused from
    a second phase, its ops keep the path of the phase it was first
    traced in after the path of the call site
    (``csgd_armijo/jit(rmsnorm)/.../csgd_grad/jvp(jit(rmsnorm))/...``)."""
    found = _TOKEN.search(op_name)
    return found.group(1) if found else None


class _Op(NamedTuple):
    phase: str | None        # from the instruction's own op_name
    named: bool              # it has an op_name (XLA's own ops have none)
    calls: str | None        # the fused computation it calls
    runs: tuple[str, ...]    # loop bodies, conditions, branches it runs
    operands: list[str]


def _parse(text: str) -> dict[str, dict]:
    """``{computation: {"root": name, "ops": {name: _Op}}}`` of an HLO
    module's text."""
    comps: dict[str, dict] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMP.match(line)
            if m:
                current = comps.setdefault(m.group(1),
                                           {"root": None, "ops": {}})
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        calls = _CALLS.search(rhs)
        current["ops"][name] = _Op(
            phase_of(op.group(1)) if op else None, op is not None,
            calls.group(1) if calls else None,
            tuple(c for group in _RUNS.findall(rhs)
                  for c in _REF.findall(group) or [group]),
            _REF.findall(rhs.split("), ")[0]))
        if root:
            current["root"] = name
    return comps


def of_hlo(text: str) -> dict[str, str]:
    """``{instruction name: phase}`` for every instruction of the HLO
    module ``text`` that a phase claims:

    * by its own ``op_name``;
    * a fusion whose metadata XLA left empty: by the value its computation
      returns, followed from the root through the operands (a
      multi-output fusion's root is a tuple: its first output that names
      a phase) to the first instruction that names one;
    * an instruction XLA added, with no ``op_name`` at all (a relayout
      copy, the sort and loop a scatter expands into, a zero fill): by the
      first instruction that reads its result and resolves to a phase,
      since XLA added it for that reader; inside a loop XLA added, by the
      loop.

    An instruction whose ``op_name`` names no phase is claimed by none.
    """
    comps = _parse(text)
    where = {n: comp for comp, c in comps.items() for n in c["ops"]}
    users: dict[str, list[str]] = {}
    runner: dict[str, str] = {}           # computation -> the op running it
    for c in comps.values():
        for name, op in c["ops"].items():
            for o in op.operands:
                if o in c["ops"]:
                    users.setdefault(o, []).append(name)
            for r in op.runs:
                runner[r] = name
    returned_memo: dict[str, str | None] = {}
    memo: dict[str, str | None] = {}

    def returned(comp: str) -> str | None:
        if comp not in returned_memo:
            returned_memo[comp] = None          # a cycle reads as no phase
            c = comps.get(comp)
            returned_memo[comp] = value(c, c["root"], set()) \
                if c and c["root"] else None
        return returned_memo[comp]

    def value(c: dict, name: str, seen: set) -> str | None:
        if name in seen or name not in c["ops"]:
            return None
        seen.add(name)
        op = c["ops"][name]
        phase = op.phase
        if phase is None and op.calls is not None:
            phase = returned(op.calls)
        for o in op.operands if phase is None else ():
            phase = value(c, o, seen)
            if phase is not None:
                break
        return phase

    def claimed(name: str) -> str | None:
        if name in memo:
            return memo[name]
        memo[name] = None                       # a cycle reads as no phase
        op = comps[where[name]]["ops"][name]
        phase = op.phase
        if phase is None and op.calls is not None:
            phase = returned(op.calls)
        if phase is None and not op.named:
            for u in users.get(name, ()):
                phase = claimed(u)
                if phase is not None:
                    break
            loop = runner.get(where[name])
            if phase is None and loop is not None:
                phase = claimed(loop)
        memo[name] = phase
        return phase

    return {n: p for n in where if (p := claimed(n)) is not None}


def step_ops(events: list[dict], plane: str) -> list[dict]:
    """The plane's ops that start inside a run of the step program's
    module."""
    mods = [(e["start"], e["start"] + e["dur"])
            for e in trace.modules(events, plane)
            if e["name"].startswith(PROGRAM)]
    return [e for e in trace.ops(events, plane)
            if any(a <= e["start"] < b for a, b in mods)]


def phase_ms(events: list[dict], plane: str, scope_map: dict[str, str],
             steps: int) -> dict[str, float]:
    """Milliseconds of device self time per traced step, per phase (every
    phase of :data:`PHASES` and ``unscoped``), over the step program's
    ops on ``plane``."""
    out = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    for name, t in trace.self_times(step_ops(events, plane)):
        out[scope_map.get(name, UNSCOPED)] += t
    return {k: v / 1e6 / steps for k, v in out.items()}


def step_text(run: dict) -> str | None:
    """The optimized HLO text of the run's step, compiled again from the
    run's model, architecture module, traffic and chips; None where the
    program names no phase.

    The compile finds the run's own executable in the persistent cache.
    That cache's key leaves out ``op_name``, so an executable another
    program left there under the same key carries that program's names;
    then the step is compiled afresh under a key that holds them."""
    import jax

    from bench import harness
    from repro.launch.train_step import (build_train_step, init_opt_state,
                                         opt_state_shardings)

    tr = harness.Trainer({"model": run["model"]}, run["traffic"],
                         run["arch"], devices=jax.devices()[:run["chips"]])

    def spec(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree.map(spec, tr.abstract, tr.p_sh)
    batch = {"tokens": jax.ShapeDtypeStruct((tr.rows, tr.seq), "int32",
                                            sharding=tr.b_sh)}
    with jax.set_mesh(tr.mesh):
        state = init_opt_state(tr.abstract, tr.run, tr.workers,
                               abstract=True,
                               stacked_mask=tr.model.stacked_mask(params))
        state = jax.tree.map(spec, state, opt_state_shardings(
            state, tr.abstract, tr.mesh, tr.run))

        def lower():
            return build_train_step(tr.model, tr.run, tr.mesh)(
                params, batch).lower(params, state, batch)

        lowered = lower()
        if not _TOKEN.search(lowered.as_text(debug_info=True)):
            return None
        text = lowered.compile().as_text()
        if not _TOKEN.search(text):
            # lowered anew: a lowering keeps its first executable
            key = "jax_compilation_cache_include_metadata_in_key"
            was = getattr(jax.config, key)
            jax.config.update(key, True)
            try:
                text = lower().compile().as_text()
            finally:
                jax.config.update(key, was)
    return text


def read(run: dict, phase: str) -> float | None:
    """One phase's ``phase_ms`` for a metric reader: per traced step on
    the first device plane; None without events, without a map, or where
    the program names no phase (a program without the scopes).

    The map is ``run["scopes"]`` where the run has one; else the first
    reader builds it from :func:`step_text` and keeps it there for the
    others."""
    ev = run.get("events")
    if not ev:
        return None
    planes = trace.device_planes(ev)
    if not planes:
        return None
    steps = sum(1 for e in trace.modules(ev, planes[0])
                if e["name"].startswith(PROGRAM))
    if not steps:
        return None
    if "scopes" not in run:
        text = step_text(run)
        run["scopes"] = of_hlo(text) if text else {}
    if not run["scopes"]:
        return None
    return phase_ms(ev, planes[0], run["scopes"], steps)[phase]
