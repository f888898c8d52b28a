"""Pass 1 — AOT program auditor: prove compile-time invariants on jitted programs.

Reference analogue: the ``PADDLE_ENFORCE_*`` macro family and the PIR
pass-and-verify pipelines (SURVEY §"IR passes / program validation") —
invariants are checked on the *program*, before anything dispatches, rather
than discovered dynamically after a bench run has already paid for them.

Given any jitted callable plus example arguments, :func:`audit_program`
traces and lowers it ahead-of-time and verifies:

  * **donation-aliasing** — every leaf of every ``donate_argnums`` argument
    is actually aliased to an output in the lowered module
    (``tf.aliasing_output``).  XLA only *warns* when it drops a donation
    (and ``serving/engine.py`` suppresses even that); here a drop becomes a
    hard finding naming the dropped leaves.
  * **host-callback census** — no ``pure_callback`` / ``io_callback`` /
    ``debug_callback`` primitives anywhere in the jaxpr (they force host
    round-trips mid-program).
  * **static shapes** — no symbolic/dynamic dimensions in any aval.
  * **dtype policy** — no float64 avals (silent f64 promotion kills TPU
    throughput; the stack runs x64-disabled on purpose).
  * **collective census** — for single-device programs, statically prove
    zero collective primitives (the jaxpr-level analogue of the
    ``dist.collective_launches == 0`` counter gate); for mesh programs,
    ``expected_collectives=`` names the allowlisted in-graph kinds and the
    auditor censuses the **compiled HLO** (where GSPMD actually inserts
    them) — allowlisted kinds tick ``analysis.collectives_in_graph``,
    anything else is a finding.
  * **HBM budget** — ``memory_analysis()`` argument + output + temp bytes
    against a declared budget.

Results feed three sinks: ``analysis.*`` counters, the flight recorder
(one ``analysis.finding`` entry per finding), and — under
``FLAGS_program_audit=enforce`` — a :class:`ProgramAuditError` raised at
the compile site, after a flight-recorder dump.

``maybe_audit`` is the cheap hook used by ``jit.CompiledTrainStep`` and the
serving engines: it no-ops when ``FLAGS_program_audit=off`` (one dict read)
and audits each distinct program name at most once per process.
"""

from __future__ import annotations

import re
import threading
import warnings
from dataclasses import dataclass, field

import numpy as np

from ..core import flags as _flags
from ..profiler import counters as _counters
from ..profiler import flight as _flight

_flags.define_flag(
    "FLAGS_program_audit", "off",
    "Program-invariant auditor mode: off | warn | enforce.  'warn' files "
    "findings into counters + the flight recorder; 'enforce' additionally "
    "raises ProgramAuditError at the compile site.")
_flags.define_flag(
    "FLAGS_audit_hbm_budget_mb", 0.0,
    "Default HBM budget (MiB) the auditor checks argument+output+temp "
    "bytes against when the call site does not pass one. 0 disables.")

# Primitives that force a host round-trip mid-program.
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call",
})

# Cross-device communication primitives (jaxpr-level collective census):
# the names jax 0.9.0's ``jax._src.lax.parallel`` binds.  ``psum`` under
# ``shard_map``'s default ``check_vma=True`` traces as ``psum_invariant``;
# ``pmean`` is psum + div and ``psum_scatter`` is ``reduce_scatter``.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_invariant", "unreduced_psum", "pmax", "pmin",
    "ppermute", "pbroadcast", "psend", "precv", "pgather",
    "all_gather", "all_gather_invariant", "all_gather_reduced",
    "all_to_all", "ragged_all_to_all", "reduce_scatter",
    "unreduced_reduce_scatter",
})

# HLO op names GSPMD may insert for sharded programs (the compiled-module
# census ``expected_collectives=`` checks against; async '-start' forms
# are folded into their base kind).
HLO_COLLECTIVE_KINDS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
})

_DONATION_WARNING_RE = re.compile(r"donated buffers were not usable",
                                  re.IGNORECASE)
# One `%argN: tensor<...> {attrs}` slot in the lowered main signature.
_MLIR_ARG_RE = re.compile(r"%arg(\d+):")


class ProgramAuditError(RuntimeError):
    """Raised under FLAGS_program_audit=enforce when a program fails audit."""

    def __init__(self, report: "AuditReport"):
        self.report = report
        lines = [f"program audit failed for {report.name!r} "
                 f"({len(report.findings)} finding(s)):"]
        lines += [f"  [{f.rule}] {f.message}" for f in report.findings]
        super().__init__("\n".join(lines))


@dataclass
class Finding:
    """One violated invariant on one program."""
    rule: str          # e.g. "donation-dropped", "host-callback"
    message: str       # human-readable, names the offending leaf/primitive
    detail: dict = field(default_factory=dict)


@dataclass
class AuditReport:
    """Everything the auditor learned about one program."""
    name: str
    findings: list = field(default_factory=list)
    # census / stats gathered even when clean:
    primitive_counts: dict = field(default_factory=dict)
    collective_counts: dict = field(default_factory=dict)
    donated_leaves: int = 0
    aliased_leaves: int = 0
    memory: dict = field(default_factory=dict)
    notes: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def add(self, rule: str, message: str, **detail):
        self.findings.append(Finding(rule, message, dict(detail)))


# ---------------------------------------------------------------------------
# jaxpr census
# ---------------------------------------------------------------------------

def _iter_subjaxprs(params):
    """Yield every jaxpr-like object reachable from an eqn's params."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            jx = getattr(item, "jaxpr", item)
            if hasattr(jx, "eqns"):
                yield jx


def _walk_jaxpr(jaxpr, prim_counts, avals):
    for var in list(jaxpr.invars) + list(jaxpr.constvars):
        av = getattr(var, "aval", None)
        if av is not None:
            avals.append(av)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        prim_counts[name] = prim_counts.get(name, 0) + 1
        for var in eqn.outvars:
            av = getattr(var, "aval", None)
            if av is not None:
                avals.append(av)
        for sub in _iter_subjaxprs(eqn.params):
            _walk_jaxpr(sub, prim_counts, avals)


def _census(closed_jaxpr):
    """(primitive->count, [avals]) over the whole (nested) jaxpr."""
    prim_counts: dict = {}
    avals: list = []
    jx = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk_jaxpr(jx, prim_counts, avals)
    return prim_counts, avals


def _is_static_dim(d) -> bool:
    return isinstance(d, (int, np.integer))


# ---------------------------------------------------------------------------
# donation-aliasing check on the lowered module
# ---------------------------------------------------------------------------

def _aliased_arg_indices(mlir_text: str):
    """Flat arg indices carrying ``tf.aliasing_output`` in @main's signature."""
    m = re.search(r"func\.func\s+(?:public\s+)?@main\(", mlir_text)
    if m is None:
        return None
    # The signature runs from '(' to the matching top-level ')'.
    start = m.end() - 1
    depth = 0
    end = start
    for i in range(start, min(len(mlir_text), start + 2_000_000)):
        c = mlir_text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    sig = mlir_text[start:end]
    slots = list(_MLIR_ARG_RE.finditer(sig))
    aliased = set()
    total = len(slots)
    for j, slot in enumerate(slots):
        seg_end = slots[j + 1].start() if j + 1 < len(slots) else len(sig)
        if "tf.aliasing_output" in sig[slot.end():seg_end]:
            aliased.add(int(slot.group(1)))
    return aliased, total


def _multi_device(args) -> bool:
    """True when any arg leaf is committed to >1 device (mesh program)."""
    import jax
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        device_set = getattr(sharding, "device_set", None)
        if device_set is not None and len(device_set) > 1:
            return True
    return False


def _leaf_paths(tree) -> list:
    try:
        from jax.tree_util import keystr, tree_flatten_with_path
        leaves, _ = tree_flatten_with_path(tree)
        return [keystr(path) for path, _leaf in leaves]
    except Exception:
        import jax
        return [f"[{i}]" for i in range(len(jax.tree_util.tree_leaves(tree)))]


# ---------------------------------------------------------------------------
# core entry point
# ---------------------------------------------------------------------------

def audit_program(name, jit_fn, *args,
                  donate_argnums=(),
                  expect_no_collectives=False,
                  expected_collectives=None,
                  hbm_budget_bytes=None,
                  compile_program=True,
                  **kwargs) -> AuditReport:
    """AOT-audit one jitted program against the invariants above.

    ``jit_fn`` must be the already-``jax.jit``-wrapped callable (so the
    audit sees exactly the donation/static-argnum config the hot path
    uses); ``args``/``kwargs`` are example inputs of the real shapes.
    ``expected_collectives`` (an iterable of HLO op names, e.g.
    ``{"all-reduce"}``) marks a mesh program whose compiled module may
    contain exactly those in-graph collective kinds — any other kind is
    a ``collective-budget`` finding.  Returns an :class:`AuditReport`;
    never raises on findings (callers — see :func:`maybe_audit` — decide
    whether to enforce).
    """
    import jax

    report = AuditReport(name=name)
    donate_argnums = tuple(donate_argnums)

    # --- trace + lower once, with donation warnings force-enabled.
    # serving/engine.py installs a module-level "ignore" filter for the
    # "donated buffers were not usable" UserWarning; simplefilter("always")
    # inside catch_warnings overrides it for the duration of the audit.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            traced = jit_fn.trace(*args, **kwargs)
            lowered = traced.lower()
        except Exception as e:  # tracing itself failed — report, don't crash
            report.add("trace-error", f"AOT trace/lower failed: {e!r}")
            _file_report(report)
            return report
    dropped_msgs = [str(w.message) for w in caught
                    if _DONATION_WARNING_RE.search(str(w.message))]

    # --- jaxpr census: host callbacks, collectives, dynamic dims, f64.
    prim_counts, avals = _census(traced.jaxpr)
    report.primitive_counts = prim_counts
    for prim in sorted(HOST_CALLBACK_PRIMITIVES & set(prim_counts)):
        report.add("host-callback",
                   f"host-callback primitive '{prim}' x{prim_counts[prim]} "
                   "in jaxpr (forces a host round-trip mid-program)",
                   primitive=prim, count=prim_counts[prim])
    report.collective_counts = {
        p: c for p, c in prim_counts.items() if p in COLLECTIVE_PRIMITIVES}
    if (expect_no_collectives and expected_collectives is None
            and report.collective_counts):
        kinds = ", ".join(f"{p} x{c}"
                          for p, c in sorted(report.collective_counts.items()))
        report.add("collective-budget",
                   f"single-device program contains collectives: {kinds}",
                   collectives=report.collective_counts)

    dyn, f64 = [], []
    for av in avals:
        shape = getattr(av, "shape", None)
        if shape is not None and not all(_is_static_dim(d) for d in shape):
            dyn.append(str(av))
        dt = getattr(av, "dtype", None)
        if dt is not None and dt == np.float64:
            f64.append(str(av))
    if dyn:
        report.add("dynamic-shape",
                   f"{len(dyn)} aval(s) with non-static dims, e.g. {dyn[0]}",
                   examples=dyn[:4])
    if f64:
        report.add("f64-promotion",
                   f"{len(f64)} float64 aval(s), e.g. {f64[0]} "
                   "(dtype policy: f32/bf16 only)",
                   examples=f64[:4])

    # --- donation aliasing on the lowered module.
    if donate_argnums:
        counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
        offsets = np.concatenate([[0], np.cumsum(counts)]).tolist()
        expected = set()
        for argnum in donate_argnums:
            if argnum < len(counts):
                expected.update(range(offsets[argnum], offsets[argnum + 1]))
        report.donated_leaves = len(expected)
        parsed = _aliased_arg_indices(lowered.as_text())
        if parsed is None:
            aliased, total = set(), None
        else:
            aliased, total = parsed
        report.aliased_leaves = len(aliased)
        if (expected and not aliased and not dropped_msgs
                and _multi_device(args)):
            # jax silently skips donation *marking* for multi-device
            # programs on platforms without donation support (the forced
            # 8-device CPU CI mesh) — nothing was dropped by the program
            # itself, so record the platform gap instead of a finding;
            # on real TPU meshes the aliasing attrs appear and the full
            # check below runs
            report.notes.append(
                "donation unverifiable: platform skipped aliasing marks "
                "for this multi-device program")
        elif total == sum(counts) and not kwargs:
            # flat index spaces line up: name the exact dropped leaves
            missing = sorted(expected - aliased)
            if missing:
                names = []
                for argnum in donate_argnums:
                    if argnum >= len(counts):
                        continue
                    paths = _leaf_paths(args[argnum])
                    base = offsets[argnum]
                    names += [f"arg{argnum}{paths[i - base]}"
                              for i in missing
                              if base <= i < offsets[argnum + 1]]
                report.add(
                    "donation-dropped",
                    f"{len(missing)}/{len(expected)} donated leaves not "
                    f"aliased to any output: {', '.join(names[:6])}"
                    + (" ..." if len(names) > 6 else ""),
                    missing_indices=missing, leaves=names,
                    xla_warnings=dropped_msgs[:4])
        elif len(aliased) < len(expected):
            # token/const args shifted the index space — fall back to counts
            report.add(
                "donation-dropped",
                f"only {len(aliased)}/{len(expected)} donated leaves aliased "
                "in the lowered module",
                xla_warnings=dropped_msgs[:4])
        elif dropped_msgs:
            report.add("donation-dropped",
                       f"XLA dropped donated buffers: {dropped_msgs[0]}",
                       xla_warnings=dropped_msgs[:4])
    elif dropped_msgs:
        report.add("donation-dropped",
                   f"XLA dropped donated buffers: {dropped_msgs[0]}",
                   xla_warnings=dropped_msgs[:4])

    # --- compiled-HLO collective census (mesh programs).  GSPMD inserts
    # the TP collectives at XLA compile time, so they never appear in the
    # jaxpr census above — scan the compiled module text instead.  Kinds
    # on the allowlist tick analysis.collectives_in_graph (the
    # in-graph-collectives-only proof check_counters asserts on); any
    # other collective kind is a finding.
    compiled = None
    if expected_collectives is not None and compile_program:
        allowed = frozenset(expected_collectives)
        try:
            compiled = lowered.compile()
            hlo = compiled.as_text()
        except Exception as e:
            report.notes.append(f"HLO collective census unavailable: {e!r}")
            hlo = ""
        census = {}
        for kind in sorted(HLO_COLLECTIVE_KINDS):
            n = len(re.findall(rf"\b{re.escape(kind)}(?:-start)?\(", hlo))
            if n:
                census[kind] = n
        report.collective_counts = dict(report.collective_counts, **census)
        good = sum(c for k, c in census.items() if k in allowed)
        if good:
            _counters.inc("analysis.collectives_in_graph", good)
        bad = {k: c for k, c in census.items() if k not in allowed}
        if bad:
            kinds = ", ".join(f"{k} x{c}" for k, c in sorted(bad.items()))
            report.add("collective-budget",
                       f"mesh program contains disallowed collective "
                       f"kinds: {kinds}", collectives=bad)

    # --- compile + memory budget.  The compile is only needed to feed
    # memory_analysis(), so skip it entirely when no budget is declared —
    # the audit stays trace+lower-only and adds no second XLA compile to
    # warmup (FLAGS_device_telemetry owns the always-on HBM capture).
    if hbm_budget_bytes is None:
        budget_mb = float(_flags.flag("FLAGS_audit_hbm_budget_mb") or 0.0)
        hbm_budget_bytes = int(budget_mb * 1024 * 1024) or None
    if compile_program and hbm_budget_bytes and not report.findings:
        try:
            compiled = compiled or lowered.compile()
            mem = compiled.memory_analysis()
            if mem is not None:
                report.memory = {
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                    "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                }
                total_bytes = sum(report.memory.values())
                if hbm_budget_bytes and total_bytes > hbm_budget_bytes:
                    report.add(
                        "hbm-budget",
                        f"arg+out+temp bytes {total_bytes} exceed declared "
                        f"budget {hbm_budget_bytes}",
                        **report.memory, budget_bytes=hbm_budget_bytes)
        except Exception:
            pass  # memory analysis is best-effort (backend-dependent)

    _file_report(report)
    return report


def _file_report(report: AuditReport):
    """Feed one report into counters + the flight recorder."""
    _counters.inc("analysis.audits")
    if report.ok:
        return
    _counters.inc("analysis.findings", len(report.findings))
    for f in report.findings:
        _counters.inc(f"analysis.findings.{f.rule}")
        _flight.record("analysis.finding", program=report.name,
                       rule=f.rule, message=f.message)


# ---------------------------------------------------------------------------
# hook used by compile sites (jit.CompiledTrainStep, serving engines)
# ---------------------------------------------------------------------------

_AUDITED_LOCK = threading.Lock()
_AUDITED: set = set()


def audit_mode() -> str:
    mode = str(_flags.flag("FLAGS_program_audit") or "off").lower()
    return mode if mode in ("off", "warn", "enforce") else "off"


def audit_enabled() -> bool:
    return audit_mode() != "off"


def reset_audited():
    """Forget which program names were already audited (test isolation)."""
    with _AUDITED_LOCK:
        _AUDITED.clear()


def maybe_audit(name, jit_fn, *args, **audit_kwargs):
    """Audit ``name`` once per process if FLAGS_program_audit != off.

    Near-zero cost when off (single flag read); when on, each distinct
    program name is audited at most once, at the compile site — i.e. at
    warmup, never inside a measured steady-state window.  Under
    ``enforce``, findings dump the flight recorder and raise
    :class:`ProgramAuditError`.
    """
    mode = audit_mode()
    if mode == "off":
        return None
    with _AUDITED_LOCK:
        if name in _AUDITED:
            return None
        _AUDITED.add(name)
    report = audit_program(name, jit_fn, *args, **audit_kwargs)
    if not report.ok and mode == "enforce":
        _flight.dump("program_audit", context={
            "program": name,
            "findings": [f"[{f.rule}] {f.message}" for f in report.findings],
        })
        raise ProgramAuditError(report)
    return report
