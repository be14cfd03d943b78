"""Jaxpr lint: trace every bucket body abstractly and check the traced
program obeys the engine's hard rules.

The bucketed executor compiles each bucket body once and reuses it for
every member via a persistent, process-global cache keyed on hand-built
signatures.  Two classes of silent failure live here: (1) the traced
program itself drifts from the engine contract — a float64 promotion
(2x memory + TPU-hostile), a host callback (breaks AOT serving), a
dynamic shape (cannot compile); (2) the cache keys collide or stop
being hashable, in which case one compiled body silently serves a
different bucket's members.  Everything is checked by TRACING ONLY
(`jax.make_jaxpr` over `ShapeDtypeStruct`s) — no device execution, no
XLA compile.

  jaxpr/float64       a 64-bit float/complex dtype appears in the trace
  jaxpr/weak-float    any float dtype in a query-engine body (the
                      engine is pure int32/bool)
  jaxpr/callback      host callback primitive in the traced body
  jaxpr/dynamic-shape non-static dimension in a traced aval
  jaxpr/trace-error   the body failed to trace at all
  jaxpr/key-unhashable a compile-cache key is not hashable
  jaxpr/key-collision  two buckets with different signatures map to the
                       same compile-cache key
"""
from __future__ import annotations

import jax
import numpy as np

from repro.analysis.findings import Finding
from repro.query.buckets import BucketedProgram, body_builder

_CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                        "outside_call", "host_callback")


def _f(rule: str, severity: str, message: str, location: str = "") -> Finding:
    return Finding("jaxpr", rule, severity, message, location)


def iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, descending into sub-jaxprs
    (scan/cond/while bodies and custom-call wrappers)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_eqns(sub)


def lint_traced(fn, arg_specs, location: str = "",
                forbid_floats: bool = True) -> list[Finding]:
    """Trace `fn` over abstract `arg_specs` and lint the jaxpr.

    `forbid_floats=True` applies the query-engine contract (int32/bool
    only); pass False for numeric kernels where f32 is expected and only
    64-bit promotion is an error.
    """
    out: list[Finding] = []
    try:
        closed = jax.make_jaxpr(fn)(*arg_specs)
    except Exception as e:
        return [_f("jaxpr/trace-error", "error",
                   f"body failed to trace: {type(e).__name__}: {e}",
                   location)]

    seen_dtypes: set[str] = set()
    for eqn in iter_eqns(closed):
        prim = eqn.primitive.name
        if any(cb in prim for cb in _CALLBACK_PRIMITIVES):
            out.append(_f(
                "jaxpr/callback", "error",
                f"host callback primitive {prim!r} in a compiled body — "
                "breaks AOT serving and device portability", location))
        for var in tuple(eqn.invars) + tuple(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is None:
                continue
            shape = getattr(aval, "shape", ())
            for dim in shape:
                if not isinstance(dim, (int, np.integer)):
                    out.append(_f(
                        "jaxpr/dynamic-shape", "error",
                        f"non-static dimension {dim!r} in {prim}",
                        location))
            dtype = getattr(aval, "dtype", None)
            if dtype is not None:
                seen_dtypes.add(np.dtype(dtype).name)

    for name in sorted(seen_dtypes):
        if name in ("float64", "complex128"):
            out.append(_f(
                "jaxpr/float64", "error",
                f"{name} appears in the traced body — 64-bit promotion "
                "(check jax_enable_x64 and literal dtypes)", location))
        elif forbid_floats and name.startswith(("float", "complex",
                                                "bfloat")):
            out.append(_f(
                "jaxpr/weak-float", "error",
                f"{name} appears in a query-engine body that must be "
                "pure int32/bool — a float literal leaked into the "
                "relational path", location))
    return out


def check_cache_keys(keyed: list[tuple[object, object, str]]
                     ) -> list[Finding]:
    """`keyed` is [(signature, cache_key, location)]: every key must be
    hashable, and distinct signatures must yield distinct keys."""
    out: list[Finding] = []
    by_key: dict = {}
    for sig, key, loc in keyed:
        try:
            hash(key)
        except TypeError as e:
            out.append(_f(
                "jaxpr/key-unhashable", "error",
                f"compile-cache key is unhashable ({e}) — every lookup "
                "would crash or, worse, fall back to identity", loc))
            continue
        prev = by_key.get(key)
        if prev is not None and prev[0] != sig:
            out.append(_f(
                "jaxpr/key-collision", "error",
                f"cache key collides with {prev[1]} despite different "
                "static signatures — one compiled body would serve both",
                loc))
        else:
            by_key[key] = (sig, loc)
    return out


def lint_program(program: BucketedProgram, n_tt: int,
                 view_caps: dict[int, int] | None = None) -> list[Finding]:
    """Lint every bucket body of a `BucketedProgram` without executing:
    trace each body over abstract operands and check the compile-cache
    keys the program would use for them."""
    out: list[Finding] = []
    eff = program.static_eff_caps(view_caps)
    keyed: list[tuple[object, object, str]] = []
    for bucket in program.buckets:
        loc = f"bucket {bucket.label}"
        specs = program.abstract_args(bucket, n_tt, eff)
        fn = body_builder(bucket, program.use_pallas, program.role)
        out.extend(lint_traced(fn, specs, location=loc))
        keyed.append(((bucket.static, bucket.cap),
                      program.cache_key(bucket, specs), loc))
    out.extend(check_cache_keys(keyed))
    return out
