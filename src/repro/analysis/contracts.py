"""Program contracts: jaxpr/HLO-level invariants of one jit entrypoint.

A :class:`ProgramContract` is what a compiled program *promises* about
its interaction with the machine, extracted purely abstractly — the
entrypoint is traced (``jit(f).trace``) and lowered (``.lower()``), but
never executed, so contract extraction is safe on a login node with no
accelerator attached:

- **collectives** — every cross-device primitive (``psum``,
  ``all_gather``, …) with its mesh axis and count.  The per-program
  ``psum`` count on the ``cells`` axis is the number the ROADMAP's
  collective-fusion item moves.
- **callbacks** — every host callback lane.  Only the live-emitter
  targets (:data:`repro.telemetry.live.CALLBACK_WHITELIST`) may appear;
  anything else is a host round-trip hiding in a hot loop.
- **dtypes** — the set of array dtypes the program touches.  ``float64``
  / ``complex128`` on device are banned outright; entry-specific checks
  pin billing to integers.
- **donation** — ``donate_argnums`` declared at the jit site must
  survive to the lowering as ``tf.aliasing_output`` markers (and, when a
  compiled executable is available, as ``input_output_alias`` in the
  optimized HLO).  A refactor that threads a donated buffer through a
  copy silently doubles peak memory; this catches it at trace time.
- **large_consts** — arrays over a size threshold baked into the jaxpr
  as constants (weights captured by closure instead of passed as args).
- **retrace stability** — tracing the same abstract signature twice must
  produce the identical (sanitized) jaxpr; divergence means an unstable
  static argument (e.g. a mutated config object) that would recompile
  every call.

Contracts serialize to plain dicts; the committed baseline lives at
``results/analysis_contracts.json`` and :func:`diff_contracts` reports
undeclared drift against it.  ``trace_hash`` is recorded for forensics
but deliberately excluded from the diff — refactors legitimately change
the jaxpr text; the contract-level fields are what must not drift
silently.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import re
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

LARGE_CONST_BYTES = 64 * 1024

# Cross-device communication primitives worth inventorying.  pmean is
# included even though it lowers through psum: at jaxpr level it is its
# own primitive.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pgather", "reduce_scatter", "psum_scatter",
})

# Host-callback primitives.  debug_callback covers jax.debug.print.
CALLBACK_PRIMS = frozenset({"io_callback", "pure_callback", "debug_callback"})

BANNED_DTYPES = frozenset({"float64", "complex128"})

# shard_map's check_vma=True binds psum of a device-varying value as
# psum_invariant inside the body jaxpr; inventory it under the plain name
# so a collective cannot hide behind the varying-axes checking path.
_PRIM_ALIASES = {"psum_invariant": "psum"}

# Jaxpr pretty-prints embed object addresses (``<function on_window at
# 0x7f..>``); strip them so equal programs hash equal across processes.
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


@dataclasses.dataclass
class ProgramContract:
    """The extracted invariants of one jit entrypoint."""
    name: str
    collectives: dict  # {prim: {axis: count}}
    psum_cells: int    # psum count on the "cells" mesh axis
    callbacks: list    # ["io_callback:on_window", ...]
    dtypes: list       # sorted dtype names touched by the program
    donated: dict      # {"declared": [...], "aliased_outputs": int}
    large_consts: list # [{"shape": [...], "dtype": ..., "bytes": n}, ...]
    n_eqns: int        # total equations (informational)
    trace_hash: str    # sanitized jaxpr digest (informational, not diffed)
    retrace_stable: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ProgramContract":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


# ---------------------------------------------------------------------------
# jaxpr walking


def _iter_sub_jaxprs(params: Mapping[str, Any]):
    """Yield every (Closed)Jaxpr nested in an equation's params — covers
    scan/while/cond bodies, pjit, shard_map, custom_* and pallas_call."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, ClosedJaxpr):
                yield u.jaxpr, u.consts
            elif isinstance(u, Jaxpr):
                yield u, ()


def walk_jaxpr(closed: ClosedJaxpr):
    """Yield ``(eqn, depth)`` for every equation, recursing into nested
    jaxprs, plus collect (aval) constants along the way.

    Returns an iterator of eqns; constants are gathered separately by
    :func:`_collect_consts` to keep this generator simple."""
    stack = [(closed.jaxpr, 0)]
    while stack:
        jaxpr, depth = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn, depth
            for sub, _consts in _iter_sub_jaxprs(eqn.params):
                stack.append((sub, depth + 1))


def _collect_consts(closed: ClosedJaxpr):
    """Every constant array baked into the program, at any nesting depth."""
    out = list(closed.consts)
    stack = [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            for sub, consts in _iter_sub_jaxprs(eqn.params):
                out.extend(consts)
                stack.append(sub)
    return out


def _axis_of(params: Mapping[str, Any]) -> str:
    """Best-effort mesh-axis label for a collective equation."""
    ax = params.get("axes", params.get("axis_name", params.get("axis")))
    if ax is None:
        return "?"
    if isinstance(ax, (tuple, list)):
        return ",".join(str(a) for a in ax)
    return str(ax)


def _callback_target(prim: str, params: Mapping[str, Any]) -> str:
    """``"io_callback:on_window"`` — recover the Python target's name."""
    cb = params.get("callback")
    fn = getattr(cb, "callback_func", cb)
    while isinstance(fn, functools.partial):
        fn = fn.func
    # bound methods: report the underlying function name (on_window),
    # matching the whitelist regardless of which emitter instance bound it
    fn = getattr(fn, "__func__", fn)
    name = getattr(fn, "__name__", None)
    if name is None:
        name = _ADDR_RE.sub("", repr(fn))
    return f"{prim}:{name}"


def _var_dtypes(jaxpr_vars: Iterable[Any], acc: set) -> None:
    for v in jaxpr_vars:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            acc.add(str(dt))


def jaxpr_fingerprint(closed: ClosedJaxpr) -> str:
    """Digest of the jaxpr text with object addresses stripped, so two
    traces of the same program hash identically."""
    text = _ADDR_RE.sub("0xX", str(closed))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# extraction


def extract_contract(
    name: str,
    closed: ClosedJaxpr,
    *,
    declared_donate: Sequence[int] = (),
    aliased_outputs: int = 0,
    retrace_stable: bool = True,
    large_const_bytes: int = LARGE_CONST_BYTES,
) -> ProgramContract:
    """Distill a traced program into its :class:`ProgramContract`."""
    collectives: dict = {}
    callbacks: list = []
    dtypes: set = set()
    n_eqns = 0

    _var_dtypes(closed.jaxpr.invars, dtypes)
    _var_dtypes(closed.jaxpr.outvars, dtypes)
    for eqn, _depth in walk_jaxpr(closed):
        n_eqns += 1
        prim = _PRIM_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if prim in COLLECTIVE_PRIMS:
            axis = _axis_of(eqn.params)
            collectives.setdefault(prim, {})
            collectives[prim][axis] = collectives[prim].get(axis, 0) + 1
        if prim in CALLBACK_PRIMS:
            callbacks.append(_callback_target(prim, eqn.params))
        _var_dtypes(eqn.invars, dtypes)
        _var_dtypes(eqn.outvars, dtypes)

    large_consts = []
    for c in _collect_consts(closed):
        arr = np.asarray(c) if not hasattr(c, "nbytes") else c
        if getattr(arr, "nbytes", 0) > large_const_bytes:
            large_consts.append({
                "shape": [int(s) for s in arr.shape],
                "dtype": str(arr.dtype),
                "bytes": int(arr.nbytes),
            })
    large_consts.sort(key=lambda d: -d["bytes"])

    return ProgramContract(
        name=name,
        collectives=collectives,
        psum_cells=collectives.get("psum", {}).get("cells", 0),
        callbacks=sorted(callbacks),
        dtypes=sorted(dtypes),
        donated={
            "declared": sorted(int(i) for i in declared_donate),
            "aliased_outputs": int(aliased_outputs),
        },
        large_consts=large_consts,
        n_eqns=n_eqns,
        trace_hash=jaxpr_fingerprint(closed),
        retrace_stable=bool(retrace_stable),
    )


def lowered_aliased_outputs(lowered_text: str) -> int:
    """Count donation markers in StableHLO text from ``lowered.as_text()``.

    Each donated input that survives to the lowering carries a
    ``tf.aliasing_output`` attribute on the entry function's argument."""
    return lowered_text.count("tf.aliasing_output")


def compiled_input_output_aliases(compiled_text: str) -> int:
    """Count ``input_output_alias`` entries in optimized HLO from
    ``compiled.as_text()`` — post-XLA confirmation that donation held."""
    return len(re.findall(r"input_output_alias\s*=", compiled_text)) + \
        len(re.findall(r'"input_output_alias"', compiled_text))


def trace_contract(
    name: str,
    build: Callable[[], tuple],
    *,
    declared_donate: Sequence[int] = (),
    large_const_bytes: int = LARGE_CONST_BYTES,
) -> ProgramContract:
    """Trace + lower one entrypoint abstractly and extract its contract.

    ``build()`` returns ``(jitted_fn, args, kwargs)`` — a *fresh* closure
    each call.  The entry is built and traced twice so an unstable static
    argument (unhashable config, mutated profile) shows up as
    ``retrace_stable=False`` rather than as a silent recompile in
    production.  Nothing executes on device."""
    fn, args, kwargs = build()
    traced = fn.trace(*args, **kwargs)
    closed = traced.jaxpr
    h1 = jaxpr_fingerprint(closed)

    fn2, args2, kwargs2 = build()
    h2 = jaxpr_fingerprint(fn2.trace(*args2, **kwargs2).jaxpr)

    aliased = lowered_aliased_outputs(traced.lower().as_text())
    return extract_contract(
        name, closed,
        declared_donate=declared_donate,
        aliased_outputs=aliased,
        retrace_stable=h1 == h2,
        large_const_bytes=large_const_bytes,
    )


# ---------------------------------------------------------------------------
# policy checks and baseline diff


def contract_problems(
    c: ProgramContract, *, callback_whitelist: frozenset
) -> list:
    """Absolute policy violations — fail regardless of what the committed
    baseline says.  Returns human-readable messages naming the contract."""
    problems = []
    for dt in c.dtypes:
        if dt in BANNED_DTYPES:
            problems.append(
                f"[{c.name}] banned dtype {dt} on device (dtype policy: "
                f"no f64 in compiled programs)")
    for cb in c.callbacks:
        target = cb.split(":", 1)[1]
        if target not in callback_whitelist:
            problems.append(
                f"[{c.name}] non-whitelisted host callback {cb!r} "
                f"(allowed targets: {sorted(callback_whitelist)})")
    if c.donated["declared"] and c.donated["aliased_outputs"] == 0:
        problems.append(
            f"[{c.name}] donate_argnums={c.donated['declared']} declared "
            f"but no input/output aliasing survived lowering — donation "
            f"was silently dropped")
    if not c.retrace_stable:
        problems.append(
            f"[{c.name}] retrace unstable: two traces at identical "
            f"abstract shapes produced different jaxprs (unstable static "
            f"argument → recompile every call)")
    return problems


_DIFFED_FIELDS = ("collectives", "psum_cells", "callbacks", "dtypes",
                  "donated", "large_consts")


def diff_contracts(
    baseline: Mapping[str, Mapping[str, Any]],
    current: Mapping[str, ProgramContract],
) -> list:
    """Undeclared drift of current contracts vs the committed baseline.

    Diffs only contract-level fields (:data:`_DIFFED_FIELDS`) — never
    ``trace_hash`` or ``n_eqns``, which legitimately move under refactors
    that preserve the contract."""
    msgs = []
    for name in sorted(set(baseline) - set(current)):
        msgs.append(f"[{name}] contract present in baseline but no longer "
                    f"traced — removed entrypoints need --update")
    for name in sorted(set(current) - set(baseline)):
        msgs.append(f"[{name}] new entrypoint not in baseline — run "
                    f"--update to declare it")
    for name in sorted(set(current) & set(baseline)):
        cur, base = current[name].to_dict(), baseline[name]
        for field in _DIFFED_FIELDS:
            if cur[field] != base.get(field):
                msgs.append(
                    f"[{name}] {field} drifted: baseline "
                    f"{base.get(field)!r} -> current {cur[field]!r}")
    return msgs
