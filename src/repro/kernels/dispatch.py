"""Kernel dispatch: one place decides which implementation runs per op.

Every public op in :mod:`repro.kernels.ops` is registered here with up to
three implementations:

* ``ref``              — pure jnp oracle (always present; CPU dry-run path)
* ``pallas-interpret`` — the Pallas kernel, interpret mode (CPU containers;
                         numerically identical to the TPU lowering)
* ``pallas-tpu``       — the Pallas kernel, compiled (real TPU)

Selection order for a call: explicit ``impl=`` argument > process-wide
override (:func:`set_default` / :func:`using`) > the op's registered default
policy, resolved against the active backend:

* policy ``"pallas"``  — always take the kernel path (interpret off-TPU);
  used for the EF-compression ops — including the telemetry-fused pass-1
  ``ef_stats_telemetry`` (DESIGN.md §10) — which are the paper's hot loop
  and whose interpret-mode cost is one vectorized tile evaluation per
  grid step;
* policy ``"backend"`` — kernel on TPU, ``ref`` elsewhere; used for the
  model-side ops (attention, rmsnorm, wkv) where the jnp oracle is what the
  CPU dry-run is expected to lower, and for the wire pack/unpack codec.

A compiled TPU kernel is a Mosaic custom call, which XLA cannot
partition: it lowers only where every mesh axis is manual.  So where some
axes are still auto at the call site (the train step is manual over dp and
auto over 'model'), :func:`call` runs a ``pallas-tpu`` implementation in a
shard_map manual over *every* axis (a nested shard_map that names only the
auto axes does not count the outer manual ones) with replicated specs:
along an already-manual axis each worker keeps its own operands, along an
auto axis every shard computes the whole op.

There is no silent fallback: an op whose resolved implementation is not
registered raises.  ``with recording() as seen:`` collects, per op, every
implementation a call resolved to inside the block (at trace time) — the
record a chip run prints to show that no op on its path ran the oracle.

The bucketed transport (DESIGN.md §11) reuses the registered ``wire_pack``
/ ``wire_unpack`` and EF ops at bucket-shaped geometries — whole-pytree
field streams and concatenated block rows instead of per-leaf calls — so
one registry entry serves both the per-leaf reference schedule and the
coalesced launches; no bucket-specific kernels exist to drift.

``impl="pallas"`` resolves to the backend-appropriate kernel variant, so
callers never hard-code interpret mode.
"""
from __future__ import annotations

import contextlib
from typing import Callable

import jax
from jax.sharding import PartitionSpec as P

IMPLS = ("ref", "pallas-interpret", "pallas-tpu")

_REGISTRY: dict[str, dict[str, Callable]] = {}
_POLICY: dict[str, str] = {}
_OVERRIDE: str | None = None
_RECORDERS: list[dict[str, set[str]]] = []


def register_op(name: str, *, ref: Callable,
                pallas_interpret: Callable | None = None,
                pallas_tpu: Callable | None = None,
                default: str = "backend") -> None:
    """Register an op's implementations. ``default``: "backend" | "pallas"."""
    if default not in ("backend", "pallas"):
        raise ValueError(f"bad default policy {default!r}")
    _REGISTRY[name] = {"ref": ref,
                       "pallas-interpret": pallas_interpret,
                       "pallas-tpu": pallas_tpu}
    _POLICY[name] = default


def registered() -> dict[str, tuple[str, ...]]:
    """op -> available impl names (introspection for tests/benchmarks)."""
    return {op: tuple(k for k, v in impls.items() if v is not None)
            for op, impls in _REGISTRY.items()}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(name: str, impl: str | None = None) -> str:
    """Resolve a requested impl ("ref"|"pallas"|full name|None) for an op."""
    impl = impl or _OVERRIDE or _POLICY.get(name, "backend")
    if impl == "backend":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "pallas":
        impl = "pallas-tpu" if _on_tpu() else "pallas-interpret"
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (want one of {IMPLS})")
    return impl


def call(name: str, *args, impl: str | None = None, **kwargs):
    """Dispatch ``name`` to the resolved implementation."""
    impl = resolve(name, impl)
    fn = _REGISTRY[name][impl]
    if fn is None:
        raise NotImplementedError(f"op {name!r} has no {impl!r} "
                                  f"implementation (have "
                                  f"{registered()[name]})")
    for seen in _RECORDERS:
        seen.setdefault(name, set()).add(impl)
    if impl == "pallas-tpu":
        return _manual_over_auto_axes(fn, args, kwargs)
    return fn(*args, **kwargs)


def _manual_over_auto_axes(fn: Callable, args: tuple, kwargs: dict):
    mesh = jax.sharding.get_abstract_mesh()
    if set(mesh.axis_names) <= set(mesh.manual_axes):
        return fn(*args, **kwargs)
    # array operands enter the shard_map; static ones (ints, None) close over
    slots = [i for i, a in enumerate(args) if isinstance(a, jax.Array)]

    def body(*arrays):
        full = list(args)
        for i, a in zip(slots, arrays):
            full[i] = a
        return fn(*full, **kwargs)

    return jax.shard_map(body, in_specs=P(), out_specs=P(),
                         axis_names=set(mesh.axis_names),
                         check_vma=False)(*(args[i] for i in slots))


@contextlib.contextmanager
def recording():
    """``with recording() as seen:`` — ``seen`` maps each op called in the
    block to the set of implementations its calls resolved to."""
    seen: dict[str, set[str]] = {}
    _RECORDERS.append(seen)
    try:
        yield seen
    finally:
        _RECORDERS.remove(seen)


def set_default(impl: str | None) -> None:
    """Force every op to ``impl`` process-wide (None restores per-op policy)."""
    global _OVERRIDE
    if impl is not None and impl not in IMPLS + ("pallas",):
        raise ValueError(f"unknown impl {impl!r}")
    _OVERRIDE = impl


@contextlib.contextmanager
def using(impl: str | None):
    """Scoped :func:`set_default` — ``with dispatch.using("ref"): ...``"""
    global _OVERRIDE
    prev = _OVERRIDE
    set_default(impl)
    try:
        yield
    finally:
        _OVERRIDE = prev
