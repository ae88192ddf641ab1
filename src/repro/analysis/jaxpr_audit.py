"""Jaxpr auditor: static trace contracts for the jitted hot paths.

Walks the ClosedJaxpr of a registered surface (serve decode/prefill/
slot-write, the calibration search chunk) - recursing into every sub-jaxpr
(pjit, scan, while, cond, shard_map, custom_jvp) - and extracts the facts
the trace contracts gate on, without executing anything:

* primitive histogram and equation count;
* host-callback sites (``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / infeed-outfeed) - forbidden on hot paths;
* per-site collective counts: ``kernels.shard`` wraps each shard_map local
  body in ``jax.named_scope("site:<site>")``, so every psum eqn carries its
  site in ``eqn.source_info.name_stack`` and the static count per site is
  directly comparable to the flight recorder's trace-time ``dist.psum``
  counters (both advance once per traced call site);
* dtype-promotion violations: ``convert_element_type`` of a large bf16/f16
  tensor to f32/f64.  Upcasts inside a ``site:``-tagged shard_map body are
  recorded but not counted as violations - those are the intentional
  K-partial f32 accumulators;
* live-bytes estimates (sum of input / output aval bytes) and the dtype set.

``audit_donation`` complements the jaxpr walk with the compiled view:
lower+compile the surface and read XLA's ``input_output_alias`` table
(``launch.hlo_analysis.parse_input_output_aliases``) plus any "donated
buffers were not usable" warnings, so declared ``donate_argnums`` that XLA
silently refused to alias are surfaced.
"""
from __future__ import annotations

import dataclasses
import math
import re
import warnings
from typing import Any, Callable, Iterable

import jax

__all__ = ["AuditReport", "audit_jaxpr", "audit_fn", "audit_donation",
           "PSUM_PRIMS", "COLLECTIVE_PRIMS", "CALLBACK_PRIMS"]

# psum shows up as "psum2" under shard_map's replication-checking rewrite;
# both normalize to "psum" in reports so contracts survive jax upgrades.
PSUM_PRIMS = frozenset({"psum", "psum2"})
COLLECTIVE_PRIMS = PSUM_PRIMS | {
    "pmax", "pmin", "ppermute", "pshuffle", "all_gather", "all_to_all",
    "reduce_scatter"}
CALLBACK_PRIMS = frozenset({"infeed", "outfeed"})  # plus *callback* by name

_SITE = re.compile(r"site:([\w.\-]+)")
_F16 = {"bfloat16", "float16"}
_F32UP = {"float32", "float64"}


@dataclasses.dataclass
class AuditReport:
    """Everything the static walk extracts from one surface's jaxpr."""
    surface: str
    n_eqns: int = 0
    primitives: dict = dataclasses.field(default_factory=dict)
    host_callbacks: list = dataclasses.field(default_factory=list)
    collectives: dict = dataclasses.field(default_factory=dict)
    psums_by_site: dict = dataclasses.field(default_factory=dict)
    upcasts: list = dataclasses.field(default_factory=list)
    large_f32_upcasts: int = 0
    dtypes: list = dataclasses.field(default_factory=list)
    arg_bytes: int = 0
    out_bytes: int = 0
    donation: dict | None = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _sub_jaxprs(params: dict) -> Iterable[Any]:
    """Yield every (open) sub-jaxpr referenced from an eqn's params.

    Duck-typed on purpose: ClosedJaxpr has .jaxpr/.consts, Jaxpr has
    .eqns/.invars - stable across jax versions without importing either
    class from a moving module path.
    """
    def walk(v):
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):
            yield v.jaxpr
        elif hasattr(v, "eqns") and hasattr(v, "invars"):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from walk(x)
    for v in params.values():
        yield from walk(v)


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * dtype.itemsize


def _scope(eqn) -> str:
    si = getattr(eqn, "source_info", None)
    ns = getattr(si, "name_stack", None)
    return str(ns) if ns is not None else ""


def _site_of(eqn) -> str:
    m = _SITE.findall(_scope(eqn))
    return m[-1] if m else "unlabeled"


def audit_jaxpr(jaxpr: Any, *, surface: str = "?",
                upcast_numel: int = 1 << 14) -> AuditReport:
    """Walk a Jaxpr/ClosedJaxpr (recursively) into an AuditReport.

    upcast_numel: tensors at or above this element count are "large" for
    the bf16->f32 promotion check; tiny scalars/norm factors pass.
    """
    rep = AuditReport(surface=surface)
    dtypes: set[str] = set()

    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr -> open jaxpr
        jaxpr = jaxpr.jaxpr

    for v in jaxpr.invars:
        rep.arg_bytes += _aval_bytes(v)
    for v in jaxpr.outvars:
        rep.out_bytes += _aval_bytes(v)

    def walk(j, in_shard_map: bool, depth: int) -> None:
        if depth > 128:
            return
        prev_psum = None  # (axes, site) of the eqn before, if a psum
        for eqn in j.eqns:
            name = eqn.primitive.name
            rep.n_eqns += 1
            rep.primitives[name] = rep.primitives.get(name, 0) + 1
            # jax stages a variadic psum as one eqn per operand: adjacent
            # psums over the same axes at one site are one collective
            key = ((tuple(eqn.params.get("axes", ()) or ()), _site_of(eqn))
                   if name in PSUM_PRIMS else None)
            same_psum = key is not None and key == prev_psum
            prev_psum = key

            if "callback" in name or name in CALLBACK_PRIMS:
                cb = eqn.params.get("callback", None)
                rep.host_callbacks.append({
                    "primitive": name,
                    "callback": repr(cb) if cb is not None else "",
                    "scope": _scope(eqn)})

            if name in COLLECTIVE_PRIMS and not same_psum:
                canon = "psum" if name in PSUM_PRIMS else name
                rep.collectives[canon] = rep.collectives.get(canon, 0) + 1
                if name in PSUM_PRIMS:
                    site = _site_of(eqn)
                    rep.psums_by_site[site] = \
                        rep.psums_by_site.get(site, 0) + 1

            if name == "convert_element_type":
                old = getattr(getattr(eqn.invars[0], "aval", None),
                              "dtype", None)
                new = eqn.params.get("new_dtype", None)
                aval = getattr(eqn.invars[0], "aval", None)
                numel = math.prod(getattr(aval, "shape", ()) or ())
                if (old is not None and new is not None
                        and str(old) in _F16 and str(new) in _F32UP
                        and numel >= upcast_numel):
                    site = _site_of(eqn)
                    accum = in_shard_map and site != "unlabeled"
                    rep.upcasts.append({
                        "from": str(old), "to": str(new), "numel": numel,
                        "site": site, "kpartial_accum": accum})
                    if not accum:
                        rep.large_f32_upcasts += 1

            for v in eqn.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None:
                    dtypes.add(str(dt))

            inner = in_shard_map or name == "shard_map"
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, inner, depth + 1)

    for v in jaxpr.invars:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None:
            dtypes.add(str(dt))
    walk(jaxpr, False, 0)
    rep.dtypes = sorted(dtypes)
    return rep


def audit_fn(fn: Callable, *args, surface: str = "?",
             upcast_numel: int = 1 << 14, **kwargs) -> AuditReport:
    """Trace fn(*args, **kwargs) to a jaxpr and audit it."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return audit_jaxpr(closed, surface=surface, upcast_numel=upcast_numel)


def audit_donation(fn: Callable, args: tuple,
                   donate_argnums: tuple = ()) -> dict:
    """Donation effectiveness: declared donations vs XLA's actual aliasing.

    Lowers+compiles the surface, parses ``input_output_alias`` out of the
    compiled HLO, and reports jax's "donated buffers were not usable"
    warnings - or, where jax compiles without one, the donated buffers
    missing from the alias table.  ``fn`` may already be jit-wrapped (its
    own donate_argnums win); a bare callable is wrapped here with
    ``donate_argnums``.
    """
    from repro.launch.hlo_analysis import parse_input_output_aliases
    jfn = fn if hasattr(fn, "lower") else \
        jax.jit(fn, donate_argnums=donate_argnums)
    declared = sum(len(jax.tree.leaves(args[i])) for i in donate_argnums)
    with warnings.catch_warnings(record=True) as wl:
        warnings.simplefilter("always")
        compiled = jfn.lower(*args).compile()
    aliases = parse_input_output_aliases(compiled.as_text())
    undonated = [str(w.message) for w in wl
                 if "donated" in str(w.message).lower()]
    if not undonated and len(aliases) < declared:
        # jax 0.9 compiles an unusable donation without a warning: the
        # compiled program's alias table is the evidence
        undonated.append(
            f"{declared - len(aliases)} of {declared} donated buffers are "
            "aliased to no output of the compiled program")
    return {"declared": declared, "aliased": len(aliases),
            "aliases": aliases, "undonated_warnings": undonated,
            "platform": jax.default_backend()}
