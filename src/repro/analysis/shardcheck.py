"""Partition-spec consistency checker: prove the sharding layout executes.

Two static passes over one arch + mesh, no compilation:

* **Leaf layout proofs** - every compressed (SparseTensor) leaf's layout is
  decided once by ``dist.sharding.sparse_component_layout``; this pass
  re-derives the physical consequences and proves them:

  - vals/idx K specs agree (all-or-nothing K sharding - a split decision
    is a layout no kernel executes);
  - a K-sharded leaf's *stored* component rows actually divide over the K
    mesh axes: vals rows (K/2 for 2:4) and idx rows (K/8 packed bytes,
    K/4 int8 groups) per shard must be whole, and every leading dim
    (layers / experts) must divide its mapped axes;
  - every silent replicated-K fallback (the mesh maps K but the leaf
    cannot shard it) becomes a structured finding instead of only a
    trace-time warning.

* **shard_map/psum axis consistency** - walks the decode jaxpr's shard_map
  eqns (the ``kernels/shard.py`` wrappers) and checks each body psum
  reduces over axes that are (a) partitioned in at least one input spec
  and (b) absent from every output spec - i.e. the K-partial accumulation
  contracts what was sharded and nothing else.

Findings are structured dicts ``{leaf|surface, kind, severity, detail}``;
``severity == "error"`` means the static layout cannot execute and fails
the check (CI gates on it), ``"warn"`` marks working-but-degraded layouts
(replicated fallbacks).  ``python -m repro.analysis shardcheck --arch X
--mesh 2x2 --devices 4`` prints the report; exit code 1 on errors only.
"""
from __future__ import annotations

from typing import Any

from repro.analysis.jaxpr_audit import PSUM_PRIMS, _sub_jaxprs

__all__ = ["check_leaves", "check_psum_axes", "check_arch", "format_report"]


def _axes_size(mesh, entry) -> int:
    if entry is None:
        return 1
    n = 1
    for a in ((entry,) if isinstance(entry, str) else tuple(entry)):
        n *= mesh.shape[a]
    return n


def _finding(kind: str, severity: str, where: str, detail: str,
             **extra) -> dict:
    return {"kind": kind, "severity": severity, "where": where,
            "detail": detail, **extra}


def check_leaves(cfg, params, rules, *, quiet: bool = True
                 ) -> tuple[dict, list[dict]]:
    """Layout proofs for every compressed leaf of one params tree.

    Returns (counts, findings).  ``params`` is a sparsified tree (smoke
    scale is fine - divisibility is decided by real config shapes, which
    the smoke configs preserve modulo scale; the zoo goldens pin the smoke
    outcome, the CLI can run full configs).
    """
    import jax
    from jax.tree_util import keystr
    from repro.dist.sharding import sparse_component_layout
    from repro.models import model as M
    from repro.sparse.formats import SparseTensor
    mesh = rules.mesh
    axes_tree = M.param_axes(cfg)
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, SparseTensor))
    flat_a = jax.tree_util.tree_leaves(
        axes_tree, is_leaf=lambda x: x is None)
    assert len(flat_a) == len(flat_p), (len(flat_a), len(flat_p))
    counts = {"sparse_leaves": 0, "k_sharded": 0, "replicated_k": 0,
              "replicated_n": 0, "unmapped_k": 0}
    findings: list[dict] = []
    for (kp, leaf), axes_str in zip(flat_p, flat_a, strict=True):
        if not isinstance(leaf, SparseTensor):
            continue
        path = keystr(kp)
        counts["sparse_leaves"] += 1
        vals_spec, idx_spec, tag = sparse_component_layout(
            axes_str, leaf, rules, path=path, quiet=quiet)
        # all-or-nothing K: both components must agree on the K entry
        if tuple(vals_spec) != tuple(idx_spec):
            findings.append(_finding(
                "k_component_mismatch", "error", path,
                f"vals spec {tuple(vals_spec)} != idx spec "
                f"{tuple(idx_spec)}: a split K decision is not executable"))
            continue
        names = (axes_str or "").split("|") if axes_str else []
        dense = list(rules.spec(names)) if names else []
        dense += [None] * (len(leaf.shape) - len(dense))
        k_entry = dense[-2] if len(dense) >= 2 else None
        d = _axes_size(mesh, k_entry)
        K = leaf.shape[-2]
        group = 8 if leaf.idx_bits == 2 else 4
        if tag is not None and tag[-2] is not None:
            counts["k_sharded"] += 1
            # prove the stored planes divide: whole vals rows / idx rows
            # (bytes for packed, groups for int8) per K shard
            for comp, rows in (("vals", leaf.vals.shape[-2]),
                               ("idx", leaf.idx.shape[-2])):
                if rows % d != 0:
                    findings.append(_finding(
                        "divisibility", "error", path,
                        f"{comp} stores {rows} rows along K but the K mesh "
                        f"axes {k_entry!r} span {d} devices "
                        f"({rows} % {d} != 0): tagged layout cannot "
                        "place whole rows per shard", component=comp,
                        rows=rows, devices=d))
            # leading dims (layers scan axis / expert banks) must divide
            spec_t = tuple(vals_spec)
            for i, e in enumerate(spec_t[:-2]):
                sz = _axes_size(mesh, e)
                if sz > 1 and leaf.vals.shape[i] % sz != 0:
                    findings.append(_finding(
                        "divisibility", "error", path,
                        f"leading dim {i} ({leaf.vals.shape[i]}) does not "
                        f"divide mesh axes {e!r} ({sz} devices)", dim=i))
        elif k_entry is not None and d > 1:
            counts["replicated_k"] += 1
            findings.append(_finding(
                "replicated_k_fallback", "warn", path,
                f"K={K} cannot shard over {k_entry!r} ({d} devices, needs "
                f"K % {group * d} == 0 for idx_bits={leaf.idx_bits}): vals "
                "AND idx replicate along K - correct but every device "
                "holds the full reduction dim",
                K=K, devices=d, needs=group * d))
        else:
            counts["unmapped_k"] += 1
        n_entry = dense[-1] if dense else None
        n_sz = _axes_size(mesh, n_entry)
        if (n_entry is not None and n_sz > 1
                and tuple(vals_spec)[-1] is None):
            counts["replicated_n"] += 1
            findings.append(_finding(
                "replicated_n_fallback", "warn", path,
                f"N={leaf.shape[-1]} does not divide mesh axes "
                f"{n_entry!r} ({n_sz} devices): output dim replicates",
                N=leaf.shape[-1], devices=n_sz))
    return counts, findings


def _axis_names(names_entry) -> set[str]:
    """Flat mesh-axis names out of one shard_map in_specs/out_specs entry
    (a PartitionSpec: None, a name or a tuple of names per dim)."""
    out: set[str] = set()
    vals = names_entry.values() if hasattr(names_entry, "values") \
        else names_entry
    for v in vals:
        if isinstance(v, str):
            out.add(v)
        elif isinstance(v, (tuple, list)):
            out.update(x for x in v if isinstance(x, str))
    return out


def _collect_psum_axes(jaxpr, acc: list) -> None:
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in PSUM_PRIMS:
            axes = eqn.params.get("axes", ()) or ()
            acc.append(tuple(a for a in axes if isinstance(a, str)))
        for sub in _sub_jaxprs(eqn.params):
            _collect_psum_axes(sub, acc)


def check_psum_axes(jaxpr, *, surface: str = "?") -> tuple[dict, list[dict]]:
    """shard_map in/out specs vs the psum axes of each body.

    Every psum axis must be partitioned in at least one input spec (or the
    'reduction' never had partial values to combine) and in no output spec
    (or the combine left the result still sharded over a reduced axis).
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    counts = {"shard_maps": 0, "psums": 0}
    findings: list[dict] = []

    def walk(j) -> None:
        if hasattr(j, "jaxpr"):
            j = j.jaxpr
        for eqn in j.eqns:
            if eqn.primitive.name == "shard_map":
                counts["shard_maps"] += 1
                in_axes: set[str] = set()
                for entry in eqn.params.get("in_specs", ()):
                    in_axes |= _axis_names(entry)
                out_axes: set[str] = set()
                for entry in eqn.params.get("out_specs", ()):
                    out_axes |= _axis_names(entry)
                psums: list[tuple] = []
                for sub in _sub_jaxprs(eqn.params):
                    _collect_psum_axes(sub, psums)
                counts["psums"] += len(psums)
                for axes in psums:
                    missing = [a for a in axes if a not in in_axes]
                    if missing:
                        findings.append(_finding(
                            "psum_axis_unpartitioned", "error", surface,
                            f"psum over {axes} but {missing} partition no "
                            "shard_map input: nothing partial to combine",
                            axes=list(axes)))
                    leaked = [a for a in axes if a in out_axes]
                    if leaked:
                        findings.append(_finding(
                            "psum_axis_in_output", "error", surface,
                            f"psum reduces {axes} yet {leaked} still "
                            "partitions an output spec: the combine "
                            "leaked a sharded reduction", axes=list(axes)))
            for sub in _sub_jaxprs(eqn.params):
                walk(sub)

    walk(jaxpr)
    return counts, findings


def check_arch(arch: str, *, mesh_shape: tuple | None = (2, 2),
               trace_decode: bool = True, sparse: bool = True) -> dict:
    """Full shardcheck report for one arch on one mesh.

    sparse=False audits the dense engine (families whose kernels cannot
    take 2:4, e.g. xlstm's K=85 ff_down): no compressed leaves to prove,
    the psum pass still runs.
    """
    import jax
    from repro.launch.mesh import make_mesh
    from repro.analysis import surfaces
    from repro.dist.axes import make_rules
    report: dict[str, Any] = {"arch": arch,
                              "mesh": list(mesh_shape) if mesh_shape
                              else None}
    if mesh_shape is None:
        report.update({"skipped": "single device: no partitioning to check",
                       "findings": [], "clean": True})
        return report
    mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    rules = make_rules(mesh)
    if sparse:
        # families whose prunable kernels cannot take 2:4 (a reduction dim
        # % 4 != 0, e.g. xlstm's ff_down K=85) have no compressed layout to
        # prove; auto-fall back to auditing the dense engine
        from jax.tree_util import keystr, tree_flatten_with_path
        from repro.configs.base import get_smoke_config
        from repro.core.prunable import prunable_map
        from repro.models import model as M
        probe_cfg = get_smoke_config(arch)
        shapes = M.param_shapes(probe_cfg)
        flat, _ = tree_flatten_with_path(shapes)
        flags = jax.tree.leaves(prunable_map(shapes))
        for (kp, leaf), prunable in zip(flat, flags, strict=True):
            if prunable and leaf.shape[-2] % 4:
                sparse = False
                report["sparse_note"] = (
                    f"2:4 infeasible ({keystr(kp)} K={leaf.shape[-2]} % 4 "
                    "!= 0): auditing the dense engine")
                break
    if sparse:
        cfg, params = surfaces._sparse_smoke(arch)
        leaf_counts, findings = check_leaves(cfg, params, rules)
        report["leaves"] = leaf_counts
    else:
        from repro.configs.base import get_smoke_config
        cfg = get_smoke_config(arch)
        findings = []
        report["leaves"] = {"sparse_leaves": 0}
    if trace_decode and not cfg.is_encoder_decoder:
        surfs = surfaces.serve_surfaces(arch, mesh_shape=mesh_shape,
                                        sparse=sparse)
        for s in surfs:
            closed = jax.make_jaxpr(s.fn)(*s.args)
            c, f = check_psum_axes(closed, surface=s.name)
            report.setdefault("surfaces", {})[s.name] = c
            findings.extend(f)
    elif trace_decode:
        report["surfaces"] = {
            "skipped": "encoder-decoder: slot engine unsupported "
                       "(zoo audits decode_step directly)"}
    report["findings"] = findings
    report["clean"] = not any(f["severity"] == "error" for f in findings)
    return report


def format_report(report: dict) -> str:
    lines = [f"shardcheck {report['arch']} mesh={report.get('mesh')}"]
    if report.get("skipped"):
        lines.append(f"  SKIP: {report['skipped']}")
        return "\n".join(lines)
    if report.get("sparse_note"):
        lines.append(f"  NOTE: {report['sparse_note']}")
    lc = report.get("leaves", {})
    lines.append("  leaves: " + " ".join(f"{k}={v}" for k, v in lc.items()))
    for name, c in (report.get("surfaces") or {}).items():
        lines.append(f"  surface {name}: {c}")
    for f in report.get("findings", []):
        lines.append(f"  [{f['severity'].upper()}] {f['kind']} "
                     f"{f['where']}: {f['detail']}")
    lines.append(f"  clean={report['clean']}")
    return "\n".join(lines)
