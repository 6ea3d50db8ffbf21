"""ContractGuard layer 2 — the jaxpr / lowering hot-loop auditor.

Layer 1 reads source; this layer reads what jax actually built. Every jit
constructed through `DevicePlacement.donate_jit` lands in the placement's
`HotLoopRegistry` as a `HotLoopEntry` that captures abstract argument
signatures (shape/dtype/sharding) at its first real call. Post-warmup —
after a live `Server` has stepped real requests through the hot loops —
`audit_placement` re-traces and re-lowers each called entry from those
signatures (never touching live donated buffers) and asserts these
contracts on the artifact:

  · **purity** — no callback / debug / infeed / outfeed primitives
    anywhere in the jaxpr (a `jax.debug.print` left in a hot loop is a
    per-step host round-trip);
  · **no f64** — no `convert_element_type` to float64/complex128 and no
    f64-valued intermediate (serving runs with x64 disabled; an f64 leak
    would double KV bandwidth the moment that flag flips);
  · **donation** — for entries built with `donate_argnums`, input→output
    buffer aliasing is actually present in the lowered module
    (`tf.aliasing_output`); a dtype/shape mismatch silently turns a
    donated in-place update into a full copy per step;
  · **out-shardings** — on a multi-device mesh, the compiled executable's
    output shardings are exactly the placement's own spec tree for that
    entry, so donated layouts are a fixed point and the arg-sharding jit
    cache never churns;
  · **quant-upcast** — when a hot loop takes int8 arena payload leaves
    (QuantPlane), no floating-point eqn output may materialize a
    full-arena-sized twin of one: dequantization is licensed only on
    GATHERED views (a handful of tabled blocks), so a float tensor with
    an int8 leaf's full [N, K, bs, h] trailing shape means the whole
    quantized arena was silently upcast to f32 in HBM — exactly the copy
    the in-tile dequant contract exists to forbid;
  · **arena-copy** — when a hot loop updates scan-stacked attention-cache
    leaves (donated `cache["period"][i]["k"]` and its planes, [n_rep,
    ...]), no `copy`, `broadcast` or `dynamic-slice` in the compiled
    program (fused or not) may output a whole stacked leaf or a whole
    one-layer slice of one, and no `dynamic-update-slice` may write one:
    the layer scan carries the caches and each layer reads and writes
    only its own rows in place (models/stack.py::stack_apply). These ops
    are the signature of a cache scanned as xs/ys, sliced out and written
    back per layer.

Entries that were registered but never called (e.g. `_extract` when no
preemption happened during warmup) are reported as skipped, not failed —
pass `require_called=True` to turn those into findings instead.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.extend.core as jex_core
import numpy as np

from repro.serving.placement import DevicePlacement, HotLoopEntry

BANNED_SUBSTR = ("callback",)
BANNED_EXACT = {"infeed", "outfeed"}
BANNED_PREFIX = ("debug",)
F64_DTYPES = (np.dtype("float64"), np.dtype("complex128"))


@dataclass
class AuditFinding:
    entry: str
    check: str  # purity | f64 | quant-upcast | donation | out-shardings
    #             | arena-copy | trace
    detail: str

    def format(self) -> str:
        return f"[{self.check}] {self.entry}: {self.detail}"


@dataclass
class AuditReport:
    audited: list = field(default_factory=list)   # entry names traced
    skipped: list = field(default_factory=list)   # registered, never called
    findings: list = field(default_factory=list)
    checks: dict = field(default_factory=dict)    # check -> times performed

    def ok(self) -> bool:
        return not self.findings

    def format(self) -> str:
        lines = [f.format() for f in self.findings]
        lines.append(
            f"jaxpr audit: {len(self.audited)} hot loop(s) audited "
            f"({', '.join(self.audited)}), {len(self.skipped)} skipped, "
            f"{len(self.findings)} finding(s); checks: "
            + ", ".join(f"{k}={v}" for k, v in sorted(self.checks.items())))
        return "\n".join(lines)

    def _count(self, check: str) -> None:
        self.checks[check] = self.checks.get(check, 0) + 1


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _subjaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """Every eqn in a (closed) jaxpr, recursing into scan/cond/pjit/...
    sub-jaxprs carried in eqn params."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(_subjaxprs(eqn.params))


def _entry_jaxpr(entry: HotLoopEntry):
    """Re-trace the raw fn from the captured abstract signature (kwargs
    remapped to a positional tail so static_argnums keep their indices)."""
    args = tuple(entry.abstract_args)
    kwargs = dict(entry.abstract_kwargs or {})
    if not kwargs:
        return jax.make_jaxpr(entry.fn,
                              static_argnums=entry.static_argnums)(*args)
    names = sorted(kwargs)
    n = len(args)

    def positional(*a):
        return entry.fn(*a[:n], **dict(zip(names, a[n:])))

    call = args + tuple(kwargs[k] for k in names)
    return jax.make_jaxpr(positional,
                          static_argnums=entry.static_argnums)(*call)


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def _check_purity(entry, jaxpr, report):
    report._count("purity")
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if (name in BANNED_EXACT or name.startswith(BANNED_PREFIX)
                or any(s in name for s in BANNED_SUBSTR)):
            report.findings.append(AuditFinding(
                entry.name, "purity",
                f"banned primitive `{name}` in the hot loop — host "
                f"round-trip per step"))


def _np_dtype(dt):
    """np.dtype or None for jax extended dtypes (prng keys etc.) and
    dtype-less avals (np.dtype(None) would default to float64!)."""
    if dt is None:
        return None
    try:
        return np.dtype(dt)
    except TypeError:
        return None


def _check_f64(entry, jaxpr, report):
    report._count("f64")
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type":
            dt = _np_dtype(eqn.params.get("new_dtype"))
            if dt is not None and dt in F64_DTYPES:
                report.findings.append(AuditFinding(
                    entry.name, "f64",
                    f"convert_element_type to {dt} in the hot loop"))
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = _np_dtype(getattr(aval, "dtype", None)) \
                if aval is not None else None
            # NB: np.dtype(...) == None is True in numpy — guard explicitly
            if dt is not None and dt in F64_DTYPES:
                report.findings.append(AuditFinding(
                    entry.name, "f64",
                    f"f64 intermediate produced by `{eqn.primitive.name}`"))


FLOAT_DTYPES = (np.dtype("float32"), np.dtype("bfloat16"),
                np.dtype("float16"))


def _check_quant_upcast(entry, jaxpr, report):
    """No silent dequantized arena copy: collect the trailing-4 shapes
    [N, K, bs, h] of every int8 input leaf with ndim >= 4 (quantized
    arena payloads — the stacked [R, N, K, bs, h] leaves share the same
    trailing signature), then flag any float eqn output carrying one.
    Gathered per-block views ([M << N, K, bs, h] with M the tabled block
    count) don't collide — M never equals the pool-wide N in a hot loop."""
    jx = jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr
    sigs = set()
    for v in jx.invars:
        aval = getattr(v, "aval", None)
        dt = _np_dtype(getattr(aval, "dtype", None)) \
            if aval is not None else None
        shp = getattr(aval, "shape", None)
        if dt == np.dtype("int8") and shp is not None and len(shp) >= 4:
            sigs.add(tuple(shp[-4:]))
    if not sigs:
        return
    report._count("quant-upcast")
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = _np_dtype(getattr(aval, "dtype", None)) \
                if aval is not None else None
            shp = getattr(aval, "shape", None)
            if (dt in FLOAT_DTYPES and shp is not None and len(shp) >= 4
                    and tuple(shp[-4:]) in sigs):
                report.findings.append(AuditFinding(
                    entry.name, "quant-upcast",
                    f"`{eqn.primitive.name}` materializes a {dt} tensor "
                    f"{tuple(shp)} with a quantized arena leaf's full "
                    f"block shape — the int8 arena was upcast to float in "
                    f"HBM instead of dequantized in-tile"))


# attention-cache leaf names: KV payloads, block summaries, scale planes
KV_LEAVES = frozenset({"k", "v", "kmin", "kmax", "kmean",
                       "kscale", "vscale", "ktok", "vtok"})
COPY_OPS = frozenset({"copy", "broadcast", "dynamic-slice",
                      "dynamic-update-slice"})
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s+\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+)\s+([\w\-]+)\(([^)]*)\)(.*)$")
_HLO_DIMS = re.compile(r"^\w+\[([\d,]*)\]")
_HLO_CALLS = re.compile(r"calls=%([\w.\-]+)")


@dataclass
class _Instr:
    dims: Optional[tuple]       # None for tuple-shaped outputs
    op: str
    operands: tuple
    root: bool
    calls: Optional[str]


def _hlo_computations(text) -> dict:
    """{computation: {instruction: _Instr}} from an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {})
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        root, name, shape, op, args, rest = m.groups()
        dm = _HLO_DIMS.match(shape)
        dims = tuple(int(d) for d in dm.group(1).split(",") if d) \
            if dm else None
        operands = tuple(a.split("%")[-1].strip() for a in args.split(",")
                         if "%" in a)
        c = _HLO_CALLS.search(rest) if op == "fusion" else None
        cur[name] = _Instr(dims, op, operands, bool(root),
                           c.group(1) if c else None)
    return comps


def _materialized(comps, comp, name, fused=False):
    """[(op, dims)] of the COPY_OPS whose result instruction `name` of
    computation `comp` holds: the op itself, or for a fusion the op at its
    root, looking through bitcasts and multi-output tuples. A
    dynamic-update-slice counts by what it writes (its update operand),
    every other op by its output."""
    instrs = comps[comp]
    ins = instrs[name]
    if ins.op == "fusion" and not fused and ins.calls in comps:
        root = next((n for n, i in comps[ins.calls].items() if i.root),
                    None)
        return _materialized(comps, ins.calls, root, True) if root else []
    if fused and ins.op in ("bitcast", "tuple"):
        return [hit for o in ins.operands if o in instrs
                for hit in _materialized(comps, comp, o, True)]
    if ins.op == "dynamic-update-slice" and len(ins.operands) > 1:
        return [(ins.op, instrs.get(ins.operands[1], ins).dims)]
    return [(ins.op, ins.dims)] if ins.op in COPY_OPS else []


def _stacked_kv_leaves(entry) -> dict:
    """{path: shape} of the scan-stacked attention-cache leaves among the
    entry's donated arguments (the caches it updates): leaves named in
    KV_LEAVES under a "period" key."""
    out = {}
    for i in entry.donate_argnums:
        flat = jax.tree_util.tree_flatten_with_path(entry.abstract_args[i])
        for path, leaf in flat[0]:
            keys = [getattr(k, "key", None) for k in path]
            shp = getattr(leaf, "shape", None)
            if "period" in keys and keys[-1] in KV_LEAVES and shp:
                out[f"arg {i}{jax.tree_util.keystr(path)}"] = tuple(shp)
    return out


def _check_arena_copy(entry, compiled, report):
    """No whole-stack or whole-layer copy of a scan-stacked cache leaf in
    the compiled program (see the module docstring). One layer [N, ...]
    also matches with its unit layer axis kept, [1, N, ...]."""
    leaves = _stacked_kv_leaves(entry)
    if not leaves:
        return
    report._count("arena-copy")
    banned = {}
    for path, shp in leaves.items():
        banned.setdefault(shp, f"all layers of {path}")
        banned.setdefault(shp[1:], f"one layer of {path}")
        banned.setdefault((1,) + shp[1:], f"one layer of {path}")
    comps = _hlo_computations(compiled().as_text())
    fused = {i.calls for instrs in comps.values() for i in instrs.values()}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name in instrs:
            for op, dims in _materialized(comps, comp, name):
                hit = banned.get(dims)
                if hit:
                    report.findings.append(AuditFinding(
                        entry.name, "arena-copy",
                        f"`{name}` materializes a {op} of {list(dims)}, "
                        f"{hit}: every call moves a whole layer or stack "
                        f"of the cache, not just the rows it touches"))


def _check_donation(entry, lowered, report):
    if not entry.donate_argnums:
        return
    report._count("donation")
    text = lowered.as_text()
    n_alias = text.count("tf.aliasing_output")
    if n_alias == 0:
        report.findings.append(AuditFinding(
            entry.name, "donation",
            f"donate_argnums={entry.donate_argnums} but the lowered "
            f"module has no input-output aliasing — donation was dropped "
            f"(shape/dtype mismatch between donated input and outputs?) "
            f"and every step pays a full copy"))


def _check_out_shardings(entry, lowered, compiled, report):
    """Compiled output shardings must equal the placement's own spec tree
    — only meaningful on a multi-device mesh (the 1-device choke point
    drops the pin by design)."""
    pl = entry.placement
    if entry.out_specs is None or pl.n_devices == 1:
        return
    report._count("out-shardings")
    compiled = compiled()
    is_shard = lambda x: isinstance(x, jax.sharding.Sharding)  # noqa: E731
    actual = jax.tree.leaves(compiled.output_shardings, is_leaf=is_shard)
    expected = jax.tree.leaves(pl.tree_shardings(entry.out_specs),
                               is_leaf=is_shard)
    out_shapes = jax.tree.leaves(jax.eval_shape(
        lambda *a, **k: entry.fn(*a, **k),
        *entry.abstract_args, **(entry.abstract_kwargs or {})))
    if not (len(actual) == len(expected) == len(out_shapes)):
        report.findings.append(AuditFinding(
            entry.name, "out-shardings",
            f"spec tree shape mismatch: {len(expected)} pinned specs vs "
            f"{len(actual)} compiled outputs"))
        return
    for i, (act, exp, shp) in enumerate(zip(actual, expected, out_shapes)):
        ndim = len(shp.shape)
        eq = act.is_equivalent_to(exp, ndim) \
            if hasattr(act, "is_equivalent_to") else act == exp
        if not eq:
            report.findings.append(AuditFinding(
                entry.name, "out-shardings",
                f"output {i}: compiled sharding {act} != pinned "
                f"{exp.spec} — donated layout is not a fixed point"))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def audit_entry(entry: HotLoopEntry, report: AuditReport) -> None:
    try:
        jaxpr = _entry_jaxpr(entry)
        lowered = entry.lower()
    except Exception as e:  # re-trace must never crash the audit silently
        report.findings.append(AuditFinding(
            entry.name, "trace", f"re-trace/lower failed: {e!r}"))
        return
    _check_purity(entry, jaxpr, report)
    _check_f64(entry, jaxpr, report)
    _check_quant_upcast(entry, jaxpr, report)
    compiled = functools.cache(lowered.compile)
    _check_donation(entry, lowered, report)
    _check_out_shardings(entry, lowered, compiled, report)
    _check_arena_copy(entry, compiled, report)
    report.audited.append(entry.name)


def audit_placement(placement: DevicePlacement, *,
                    require_called: bool = False) -> AuditReport:
    """Audit every hot loop registered on (and called through) this
    placement. Call after warmup — entries capture their abstract arg
    signature at first call."""
    report = AuditReport()
    for entry in placement.hot_loops.entries:
        if entry.abstract_args is None:
            if require_called:
                report.findings.append(AuditFinding(
                    entry.name, "trace",
                    "registered but never called during warmup"))
            else:
                report.skipped.append(entry.name)
            continue
        audit_entry(entry, report)
    return report


def audit_server(server, *, require_called: bool = False) -> AuditReport:
    return audit_placement(server.placement, require_called=require_called)
