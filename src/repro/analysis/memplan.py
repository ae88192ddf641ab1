"""Static memory planner: jaxpr buffer liveness, VMEM footprints, fit tables.

Answers "does this surface / this SearchState fit that mesh" without
compiling or executing anything, from three cooperating estimates:

* :func:`plan_fn` - a topological buffer-liveness sweep over the (recursive)
  jaxpr: every equation materializes its outputs while its inputs and all
  still-referenced earlier values are live, loop carries are double-buffered
  (XLA keeps the loop state separate from the entry buffers), and an
  in-place-capable update (``dynamic_update_slice`` / ``scatter`` /
  ``select_n``) whose operand dies at that equation reuses the operand's
  buffer.  The peak of that sweep is the static ``temp_bytes``; together
  with the argument / output aval bytes and the donation credit it yields
  ``total_bytes``, the static stand-in for XLA's
  ``memory_analysis()`` total (arguments + outputs + temp - aliased).
* per-``pallas_call`` VMEM footprints read off the BlockSpecs: each block
  mapping contributes ``prod(block_shape) * itemsize`` of VMEM per grid
  step - the number that decides whether a kernel tiling fits the ~16 MB
  v5e VMEM before a single lowering runs.
* :func:`search_plan` - an ``eval_shape`` of ``core.mirror.init_search``
  (zero FLOPs, zero allocation) giving the exact SearchState byte layout
  the calibration benchmark measures live (``BENCH_calibrate.json``'s
  ``search_state_bytes``), extended into a per-mesh fit table: at which
  layer-group size does SparseLLM-style O(sqrt N) streaming of the
  Gamma/V shadows become mandatory for a given HBM budget.

Model fidelity, measured against compiled ``memory_analysis()`` on the
smoke configs (see tests/test_analysis.py):

* serving surfaces with f32 params agree within ~6% on 1 device;
* bf16 surfaces compiled on CPU diverge upward on the compiled side
  because XLA *emulates* bf16 GEMMs there - every bf16 dot operand gets an
  f32 staging copy in temp (~2x the operand bytes) that does not exist on
  TPU.  :func:`crosscheck` reports that staging estimate alongside the
  relative error so the gap is attributable instead of mysterious;
* training surfaces (the search chunk) overestimate: the walk does not
  model XLA's elementwise buffer reuse in the backward pass, so the static
  number is a safe upper bound for fit decisions.

``python -m repro.analysis memplan --arch llama3.2-1b [--compile]`` prints
the per-surface table; ``--fit`` adds the whole-zoo SearchState fit table.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable

from repro.analysis.jaxpr_audit import _sub_jaxprs

__all__ = ["MemPlan", "PallasCall", "plan_jaxpr", "plan_fn", "crosscheck",
           "search_state_bytes", "search_plan", "fit_table"]

# primitives whose first operand's buffer XLA reuses for the output when the
# operand has no later use (the planner credits that reuse at the eqn)
_INPLACE = frozenset({"dynamic_update_slice", "scatter", "select_n"})
_LOOPS = frozenset({"scan", "while"})
_F16 = ("bfloat16", "float16")


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return math.prod(shape) * dtype.itemsize
    except TypeError:  # extended dtypes without itemsize: not HBM-resident
        return 0


def _is_var(v) -> bool:
    """Trackable jaxpr variable (Literals carry .val and own no buffer)."""
    return hasattr(v, "aval") and not hasattr(v, "val")


@dataclasses.dataclass
class PallasCall:
    """VMEM footprint of one ``pallas_call`` eqn, from its BlockSpecs."""
    name: str
    grid: tuple
    vmem_bytes: int
    n_blocks: int

    def to_dict(self) -> dict:
        return {"name": self.name, "grid": list(self.grid),
                "vmem_bytes": self.vmem_bytes, "n_blocks": self.n_blocks}


@dataclasses.dataclass
class MemPlan:
    """Static memory plan of one jit surface."""
    surface: str
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0          # liveness peak of intermediate buffers
    alias_bytes: int = 0         # donation credit (declared or compiled)
    donation_declared: int = 0
    bf16_staging_bytes: int = 0  # CPU-only f32 copies of bf16 dot operands
    pallas: list = dataclasses.field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return self.arg_bytes + self.out_bytes + self.temp_bytes \
            - self.alias_bytes

    def per_device(self, n_devices: int) -> int:
        """Even-sharding estimate: the planner's per-device HBM figure.
        Replicated scalars are counted sharded too - at the table's GB
        scale the error is noise."""
        return -(-self.total_bytes // max(n_devices, 1))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["pallas"] = [p.to_dict() if isinstance(p, PallasCall) else p
                       for p in self.pallas]
        d["total_bytes"] = self.total_bytes
        return d


def _pallas_vmem(eqn) -> PallasCall | None:
    """Read a pallas_call's VMEM bytes per grid step off its BlockSpecs."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return None
    total = 0
    n = 0
    for bm in getattr(gm, "block_mappings", ()) or ():
        shape = getattr(bm, "block_shape", None)
        sd = getattr(bm, "array_aval", None)
        if shape is None or sd is None:
            continue
        numel = 1
        for dim in shape:  # Blocked(n) / Squeezed (1 row) per dim
            size = getattr(dim, "block_size", dim)
            numel *= size if isinstance(size, int) else 1
        total += numel * sd.dtype.itemsize
        n += 1
    info = getattr(eqn.params.get("jaxpr"), "debug_info", None)
    name = getattr(info, "func_src_info", None) or "pallas_call"
    return PallasCall(name.split(" ")[0], tuple(getattr(gm, "grid", ()) or ()),
                      total, n)


def _walk(jaxpr, plan: MemPlan) -> tuple[int, int, int]:
    """(arg_bytes, out_bytes, temp_peak) of one (closed or open) jaxpr;
    pallas calls found anywhere are appended to ``plan``."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    invars = list(jaxpr.invars) + list(jaxpr.constvars)
    arg_b = sum(_aval_bytes(v) for v in invars)
    out_vs = [v for v in jaxpr.outvars if _is_var(v)]
    out_b = sum(_aval_bytes(v) for v in out_vs)
    inset = set(map(id, invars))
    outset = set(map(id, out_vs))
    last_use: dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[id(v)] = i
    live: dict[int, int] = {}
    peak = 0
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name == "pallas_call":
            pc = _pallas_vmem(eqn)
            if pc is not None:
                plan.pallas.append(pc)
        inner = 0
        for sub in _sub_jaxprs(eqn.params):
            _, _, t = _walk(sub, plan)
            inner = max(inner, t)
        if name in _LOOPS:
            # the loop state buffer is temp, double-buffered vs the result
            nc = eqn.params.get("num_carry", len(eqn.outvars))
            inner += sum(_aval_bytes(v) for v in eqn.outvars[:nc])
        dies = {id(v) for v in eqn.invars
                if _is_var(v) and last_use.get(id(v)) == i}
        credit = 0
        if (name in _INPLACE and eqn.invars and _is_var(eqn.invars[0])
                and id(eqn.invars[0]) in dies and id(eqn.invars[0]) in live):
            credit = min(_aval_bytes(eqn.invars[0]),
                         sum(_aval_bytes(v) for v in eqn.outvars))
        for v in eqn.outvars:
            if id(v) not in inset and id(v) not in outset:
                live[id(v)] = _aval_bytes(v)
        peak = max(peak, sum(live.values()) - credit + inner)
        for v in eqn.invars:
            if _is_var(v) and last_use.get(id(v)) == i and id(v) in live:
                del live[id(v)]
    return arg_b, out_b, peak


def _bf16_dot_operands(jaxpr, seen: set[int]) -> int:
    """Bytes of distinct bf16/f16 buffers consumed by dot/conv eqns - the
    buffers XLA's CPU backend stages as f32 copies (2x these bytes land in
    compiled temp on CPU and nowhere else)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "conv_general_dilated",
                                  "pallas_call"):
            for v in eqn.invars:
                if not _is_var(v) or id(v) in seen:
                    continue
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt) in _F16:
                    seen.add(id(v))
                    total += _aval_bytes(v)
        for sub in _sub_jaxprs(eqn.params):
            total += _bf16_dot_operands(sub, seen)
    return total


def plan_jaxpr(jaxpr, *, surface: str = "?") -> MemPlan:
    """Liveness-walk a traced jaxpr into a MemPlan (no compilation)."""
    plan = MemPlan(surface=surface)
    plan.arg_bytes, plan.out_bytes, plan.temp_bytes = _walk(jaxpr, plan)
    plan.bf16_staging_bytes = 2 * _bf16_dot_operands(jaxpr, set())
    return plan


def plan_fn(fn: Callable, *args, surface: str = "?",
            donate_argnums: tuple = ()) -> MemPlan:
    """Trace fn(*args) and plan it; declared donations credit the plan with
    ``min(donated arg bytes, out bytes)`` - the compiled alias map refines
    this in :func:`crosscheck`."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    plan = plan_jaxpr(closed, surface=surface)
    flat = []
    for i in donate_argnums:
        flat.extend(jax.tree.leaves(args[i]))
    plan.donation_declared = len(flat)
    donated = sum(getattr(x, "nbytes", 0) or _aval_bytes(
        jax.ShapeDtypeStruct(x.shape, x.dtype)) for x in flat
        if hasattr(x, "shape"))
    plan.alias_bytes = min(donated, plan.out_bytes)
    return plan


def crosscheck(fn: Callable, *args, surface: str = "?",
               donate_argnums: tuple = ()) -> dict:
    """Static plan vs compiled ``memory_analysis()`` for one surface.

    Compiles once; the donation credit on BOTH sides comes from the
    compiled ``input_output_alias`` map (``launch.hlo_analysis``), so the
    comparison isolates the liveness model (args + out + temp), not the
    aliasing bookkeeping.  Returns the static and compiled breakdowns, the
    relative error, and the CPU bf16-staging estimate explaining the known
    divergence class on emulated-bf16 backends.
    """
    import jax
    from repro.launch.hlo_analysis import parse_input_output_aliases
    plan = plan_fn(fn, *args, surface=surface, donate_argnums=donate_argnums)
    jfn = fn if hasattr(fn, "lower") else \
        jax.jit(fn, donate_argnums=donate_argnums)
    compiled = jfn.lower(*args).compile()
    ma = compiled.memory_analysis()
    aliases = parse_input_output_aliases(compiled.as_text())
    comp = {"arg_bytes": ma.argument_size_in_bytes,
            "out_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes}
    comp["total_bytes"] = (comp["arg_bytes"] + comp["out_bytes"]
                           + comp["temp_bytes"] - comp["alias_bytes"])
    plan.alias_bytes = comp["alias_bytes"]
    static_total = plan.total_bytes
    rel = (static_total - comp["total_bytes"]) / max(comp["total_bytes"], 1)
    return {"surface": surface, "static": plan.to_dict(), "compiled": comp,
            "rel_err": rel, "n_aliases": len(aliases),
            "bf16_staging_bytes": plan.bf16_staging_bytes,
            "backend": jax.default_backend()}


# ---------------------------------------------------------------------------
# SearchState fit planning
# ---------------------------------------------------------------------------

def _state_shapes(arch: str, *, smoke: bool = True):
    """Abstract SearchState (eval_shape of init_search: zero allocation)."""
    import jax
    from repro.configs.base import get_config, get_smoke_config
    from repro.core import mirror
    from repro.models import model as M
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shapes = M.param_shapes(cfg)
    state = jax.eval_shape(
        lambda p: mirror.init_search(p, jax.random.key(17)), shapes)
    return cfg, state


def _tree_bytes(tree) -> int:
    import jax
    return sum(_aval_bytes_sd(x) for x in jax.tree.leaves(
        tree, is_leaf=lambda x: x is None) if x is not None)


def _aval_bytes_sd(x) -> int:
    if not hasattr(x, "shape"):
        return 0
    try:
        return math.prod(x.shape) * x.dtype.itemsize
    except TypeError:  # extended dtype (PRNG key): matches the live bench,
        return 0       # which also sees itemsize-less leaves as 0


def search_state_bytes(arch: str, *, smoke: bool = True) -> int:
    """Static SearchState bytes, leaf-for-leaf identical to the live figure
    ``benchmarks/bench_calibrate.py`` records as ``search_state_bytes``."""
    import jax
    _, state = _state_shapes(arch, smoke=smoke)
    total = 0
    for x in jax.tree.leaves(state, is_leaf=lambda x: x is None):
        if x is None or not hasattr(x, "shape"):
            continue
        try:
            isz = x.dtype.itemsize
        except TypeError:  # PRNG key leaf: no HBM itemsize, bench skips too
            continue
        total += math.prod(x.shape) * isz
    return total


def search_plan(arch: str, *, smoke: bool = False,
                device_counts: Iterable[int] = (1, 4, 16, 256),
                budget_gb: float = 16.0) -> dict:
    """Does config ``arch``'s SearchState fit, and if not, at what
    layer-group size does O(sqrt N) streaming become mandatory?

    The streaming model keeps the full fp32 W resident (the forward needs
    every layer) and pages the Gamma/V shadow trees in groups of ``g``
    layers: ``resident(g) = W + shadows * g / L``.  Per budget and device
    count the table reports the largest feasible ``g`` (None when even
    g=1 exceeds the budget), whether streaming is mandatory (g_max < L),
    and the sqrt(L) recommendation the roadmap item targets.
    """
    import jax
    cfg, state = _state_shapes(arch, smoke=smoke)
    w_bytes = _tree_bytes(state.W)
    shadow_bytes = _tree_bytes(state.Gamma) + _tree_bytes(state.V)
    total = search_state_bytes(arch, smoke=smoke)
    L = cfg.num_layers
    budget = budget_gb * 1e9
    rows = []
    for n in device_counts:
        per_dev_full = -(-total // n)
        w_dev = w_bytes / n
        sh_dev = shadow_bytes / n
        if w_dev + sh_dev / L > budget:
            g_max = None          # even one layer group overflows
        elif w_dev + sh_dev <= budget:
            g_max = L             # whole state fits: streaming optional
        else:
            g_max = max(1, int((budget - w_dev) * L // max(sh_dev, 1)))
        rows.append({"devices": n, "state_bytes_per_device": per_dev_full,
                     "fits": bool(per_dev_full <= budget),
                     "max_group_layers": g_max,
                     "streaming_mandatory": g_max is not None and g_max < L})
    return {"arch": arch, "smoke": smoke, "num_layers": L,
            "state_bytes": total, "w_bytes": w_bytes,
            "shadow_bytes": shadow_bytes, "budget_gb": budget_gb,
            "sqrt_group_layers": max(1, round(math.sqrt(L))),
            "per_mesh": rows}


def fit_table(archs: Iterable[str] | None = None, *, smoke: bool = False,
              device_counts: Iterable[int] = (1, 4, 16, 256),
              budget_gb: float = 16.0) -> list[dict]:
    """The whole-zoo SearchState fit table (static, zero FLOPs)."""
    from repro.configs.base import ARCH_IDS
    return [search_plan(a, smoke=smoke, device_counts=device_counts,
                        budget_gb=budget_gb)
            for a in (archs or ARCH_IDS)]


def format_fit_table(rows: list[dict]) -> str:
    """Fixed-width rendering of :func:`fit_table` for the CLI."""
    out = ["arch                    layers   state GB   " +
           "fit@1dev fit@16 fit@256   sqrtL  stream@16dev"]
    for r in rows:
        per = {x["devices"]: x for x in r["per_mesh"]}
        def flag(n):
            e = per.get(n)
            return "-" if e is None else ("yes" if e["fits"] else "NO")
        s16 = per.get(16)
        stream = "-" if s16 is None else (
            "mandatory" if s16["streaming_mandatory"] else "optional")
        out.append(f"{r['arch']:<22s} {r['num_layers']:>6d} "
                   f"{r['state_bytes'] / 1e9:>9.2f}   "
                   f"{flag(1):>8s} {flag(16):>6s} {flag(256):>7s}   "
                   f"{r['sqrt_group_layers']:>5d}  {stream}")
    return "\n".join(out)
