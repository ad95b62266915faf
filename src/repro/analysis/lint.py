"""faasmlint: repo-specific static rules for the state-fabric discipline.

An AST pass (no imports of the checked code) enforcing the lexical side of
the invariants in ``docs/invariants.md``:

``stripe-access``
    ``_Stripe`` sub-map/counter attributes (``store``/``meta``/``locks``/
    ``subs``/``vc``/``pulled``/``pushed``/``copied``/``bcast``) may only be
    touched inside a ``with <stripe>.lock:`` block, or inside a function
    annotated ``@holds_stripe`` (whose callers then carry the obligation),
    or in ``__init__`` (construction precedes sharing).  Stripe variables
    are inferred from ``self._stripe(...)`` / ``self._stripes`` data flow;
    ``self`` inside ``class _Stripe`` is a stripe.

``lock-blocking``
    No blocking or full-value call — ``Event.wait``, tier ``pull``/``push``
    fan-ins, ``broadcast`` fan-out, codec ``encode``/``decode`` and the
    quantise kernels — lexically inside a stripe-lock ``with`` block or a
    key-lock region (``lock = gt.lock(k); lock.acquire_*(); try: ...
    finally: lock.release_*()``, or the ``lock_state_global_*`` /
    ``unlock_state_global_*`` try/finally idiom).  Replica RW locks are
    deliberately out of scope: encoding under the replica lock is the
    documented push pipeline.

``wire-construct``
    ``WireFrame(...)`` is constructed only by the codec layer
    (``repro/state/wire.py``).  Everyone else encodes through
    ``wire.get_codec(...)`` so frames can't skip residual and version
    stamping.

``tier-copy``
    In the tier files (``state/kv.py``, ``state/local.py``,
    ``core/host_interface.py``), no naked ``.copy()``/``.tobytes()``/
    ``np.copy`` unless the enclosing function accounts the copy
    (``s.copied += ...`` or a ``charge_net(...)`` call) — the copy
    accounting (``bytes_copied``) is a measured experiment output and
    silent copies corrupt it.

``fault-point``
    Fault-injection sites go through the public ``repro.faults`` surface —
    ``faults.point(...)`` at the site, ``arm``/``disarm``/``armed`` around
    it.  Importing or touching the module's internals (``_PLAN``,
    ``_fire``, any underscore name) outside ``repro/faults.py`` builds an
    ad-hoc ``if FAULTS:`` branch that the disarmed one-compare fast path
    can't keep free, and that schedules can't see or count.

``metric-naming``
    Metrics are registered through a ``telemetry.metrics`` registry with
    names matching ``faasm_<subsystem>_<name>_<unit>`` (string-literal
    names on ``.counter``/``.gauge``/``.histogram`` calls are checked
    against the convention), and the data-plane modules take timestamps
    from ``repro.telemetry.clock`` — a direct ``time.perf_counter()``
    there is a second clock the spans can't be correlated with.

``bounded-queue``
    In the data-plane packages (``core/``, ``state/``) every queue is
    bounded: a raw ``queue.Queue()`` (or ``Queue()``) construction is a
    violation — an unbounded queue is an invisible buffer that converts
    overload into unbounded latency and memory instead of backpressure.
    Use ``repro.overload.bounded_queue(...)`` (the blessed factory, with
    the admission-control depth default) or ``overload.CoalescingQueue``.

``suppress-justify``
    Every ``# faasmlint: disable=<rule>`` must carry a justification
    string (and name a real rule).

Suppression: ``# faasmlint: disable=<rule>[,<rule>...] -- <why>`` as a
trailing comment silences those rules on its own line; as a standalone
comment line it silences them on the next code line.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set

__all__ = ["RULES", "Violation", "lint_file", "lint_paths", "lint_source"]

RULES: Dict[str, str] = {
    "stripe-access": ("_Stripe buffer/meta/counter access outside a "
                      "'with stripe.lock:' block (or @holds_stripe)"),
    "lock-blocking": ("blocking call (wait/pull/push/broadcast/codec "
                      "encode/decode) while a stripe or key lock is held"),
    "wire-construct": ("WireFrame constructed outside the codec layer "
                       "(repro/state/wire.py)"),
    "tier-copy": ("unaccounted .copy()/.tobytes()/np.copy on a tier "
                  "buffer outside the accounted primitives"),
    "fault-point": ("fault-injection site bypassing the public "
                    "repro.faults surface (faults.point/arm/disarm) — "
                    "internals like _PLAN are off-limits outside "
                    "repro/faults.py"),
    "metric-naming": ("metric name violating faasm_<subsystem>_<name>_"
                      "<unit>, or a direct time.perf_counter() in a "
                      "data-plane module (use repro.telemetry.clock)"),
    "bounded-queue": ("raw queue.Queue() in a data-plane package (core/, "
                      "state/) — use repro.overload.bounded_queue() or "
                      "CoalescingQueue so overload becomes backpressure, "
                      "not an unbounded buffer"),
    "suppress-justify": ("faasmlint suppression without a justification "
                         "(or naming an unknown rule)"),
}

# _Stripe attributes guarded by the stripe lock ('lock' itself is exempt:
# acquiring it is the point)
STRIPE_ATTRS = frozenset({
    "store", "meta", "locks", "subs", "vc", "pulled", "pushed", "copied",
    "bcast",
})

# call names that block or do full-value work: forbidden under stripe/key
# locks (lexically)
BLOCKING_CALLS = frozenset({
    "wait",                                # Event.wait / Condition.wait
    "pull", "pull_chunk", "pull_range", "pull_wire",
    "push", "push_dirty", "push_delta",
    "broadcast",
    "encode_delta", "decode",
    "quantize_delta", "encode_pull", "apply_pull", "dequantize",
})
# 'encode' is too common a name (str.encode); flag it only on codec-like
# receivers (source text mentions codec/frame/wire)
_CODEC_ENCODE = "encode"

TIER_COPY_CALLS = frozenset({"copy", "tobytes"})
# path suffixes the tier-copy rule applies to
TIER_COPY_FILES = ("state/kv.py", "state/local.py", "core/host_interface.py")
WIRE_HOME = "state/wire.py"          # the one module allowed to build frames
FAULTS_HOME = "repro/faults.py"      # the one module allowed its internals
# the public fault-injection surface; anything else from repro.faults is an
# internal and the fault-point rule flags its use elsewhere
FAULTS_PUBLIC = frozenset({
    "point", "arm", "disarm", "armed", "active",
    "FaultPlan", "FaultRule", "FaultInjected", "HostCrash", "FAULT_POINTS",
    "_TEL",      # telemetry hook slot: written by repro.telemetry.spans
})

# data-plane modules: every timestamp comes from repro.telemetry.clock so
# spans, Call timing and benchmark rows share one monotonic timebase
DATA_PLANE_FILES = (
    "core/runtime.py", "core/faaslet.py", "core/proto.py",
    "core/host_interface.py", "state/kv.py", "state/local.py",
    "state/wire.py", "launch/serve.py", "launch/train.py",
)
# packages where the bounded-queue rule applies: the data plane, where an
# unbounded queue defeats admission control
BOUNDED_QUEUE_DIRS = ("core/", "state/")
_RAW_QUEUE_CALLS = frozenset({"Queue", "SimpleQueue", "LifoQueue"})
CLOCK_HOME = "telemetry/clock.py"    # the one module allowed perf_counter
_RAW_CLOCK_CALLS = frozenset({"perf_counter", "perf_counter_ns"})
# mirror of repro.telemetry.metrics._NAME_RE (this linter is AST-only and
# must not import the checked code); keep the unit list in sync
_METRIC_UNITS = ("seconds", "ms", "us", "ns", "bytes", "pages", "total",
                 "count", "ratio", "rps")
_METRIC_NAME_RE = re.compile(
    r"^faasm(_[a-z0-9]+)+_(" + "|".join(_METRIC_UNITS) + r")$")
_REGISTRY_METHODS = frozenset({"counter", "gauge", "histogram"})

_DISABLE_RE = re.compile(
    r"#\s*faasmlint:\s*disable=([A-Za-z0-9_,-]+)[ \t]*(.*)")


@dataclass
class Violation:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _parse_suppressions(source: str, path: str,
                        out: List[Violation]) -> Dict[int, Set[str]]:
    """Map line number -> rules suppressed there; justification-less or
    unknown-rule suppressions become ``suppress-justify`` violations."""
    lines = source.splitlines()
    sup: Dict[int, Set[str]] = {}
    for i, line in enumerate(lines, 1):
        m = _DISABLE_RE.search(line)
        if m is None:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        just = m.group(2).strip().lstrip("-—:").strip()
        for r in sorted(rules - set(RULES)):
            out.append(Violation("suppress-justify", path, i,
                                 f"suppression names unknown rule {r!r}"))
        rules &= set(RULES)
        if not just:
            out.append(Violation(
                "suppress-justify", path, i,
                "suppression without a justification (write "
                "'# faasmlint: disable=<rule> -- <why>')"))
            continue
        target = i
        if line.strip().startswith("#"):
            # standalone comment: applies to the next code line
            j = i + 1
            while j <= len(lines) and (not lines[j - 1].strip()
                                       or lines[j - 1].strip().startswith("#")):
                j += 1
            target = j
        sup.setdefault(target, set()).update(rules)
        sup.setdefault(i, set()).update(rules)
    return sup


def _yields_stripes(node: ast.AST, stripe_vars: Set[str]) -> bool:
    """True when evaluating ``node`` can produce stripe objects: mentions
    ``._stripe(...)``, ``._stripes`` or a known stripe variable."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("_stripe", "_stripes"):
            return True
        if isinstance(n, ast.Name) and n.id in stripe_vars:
            return True
    return False


def _target_names(target: ast.AST) -> List[str]:
    out = []
    for n in ast.walk(target):
        if isinstance(n, ast.Name):
            out.append(n.id)
    return out


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_accounted(fn: ast.AST) -> bool:
    """The 'accounted copy' heuristic for tier-copy: the function body
    charges the tier copy counter or the Faaslet net budget."""
    for n in ast.walk(fn):
        if isinstance(n, ast.AugAssign) and \
                isinstance(n.target, ast.Attribute) and \
                n.target.attr == "copied":
            return True
        if isinstance(n, ast.Call) and _call_name(n.func) == "charge_net":
            return True
    return False


def _has_holds_stripe(fn) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        name = _call_name(dec) if isinstance(dec, ast.Call) else None
        if isinstance(dec, (ast.Name, ast.Attribute)):
            name = dec.attr if isinstance(dec, ast.Attribute) else dec.id
        if name == "holds_stripe":
            return True
    return False


class _FunctionLinter:
    """Lints one function body, tracking lexical lock regions."""

    def __init__(self, checker: "_FileLinter", class_name: Optional[str],
                 fn: ast.AST):
        self.checker = checker
        self.fn = fn
        self.stripe_vars: Set[str] = set()
        if class_name == "_Stripe":
            self.stripe_vars.add("self")
        self.keylock_vars: Set[str] = set()
        self.locked_stripes: List[str] = []   # stripe vars whose lock is held
        self.lock_depth = 0                   # stripe/key lock regions active
        name = getattr(fn, "name", "<lambda>")
        self.access_exempt = (name == "__init__" or _has_holds_stripe(fn))
        # @holds_stripe: the body runs under the stripe lock by contract —
        # blocking calls inside it are violations even with no lexical region
        self.contract_lock = _has_holds_stripe(fn)
        self.accounted = _is_accounted(fn)

    # -- statement walk ----------------------------------------------------

    def run(self) -> None:
        if self.contract_lock:
            self.lock_depth += 1
        self.visit_body(getattr(self.fn, "body", []))

    def visit_body(self, stmts: Sequence[ast.stmt]) -> None:
        for st in stmts:
            self.visit_stmt(st)

    def visit_stmt(self, st: ast.stmt) -> None:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs run later, outside this lexical lock region
            self.checker.lint_function(st, None)
            return
        if isinstance(st, ast.ClassDef):
            self.checker.lint_class(st)
            return
        if isinstance(st, ast.Assign):
            self.scan_expr(st.value)
            for t in st.targets:
                self.scan_expr(t)
            if _yields_stripes(st.value, self.stripe_vars):
                for t in st.targets:
                    self.stripe_vars.update(_target_names(t))
            if isinstance(st.value, ast.Call) and \
                    _call_name(st.value.func) == "lock":
                for t in st.targets:
                    self.keylock_vars.update(_target_names(t))
            return
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self.scan_expr(st.iter)
            if _yields_stripes(st.iter, self.stripe_vars):
                self.stripe_vars.update(_target_names(st.target))
            self.visit_body(st.body)
            self.visit_body(st.orelse)
            return
        if isinstance(st, (ast.With, ast.AsyncWith)):
            opened: List[str] = []
            for item in st.items:
                self.scan_expr(item.context_expr)
                ctx = item.context_expr
                if isinstance(ctx, ast.Attribute) and ctx.attr == "lock" and \
                        isinstance(ctx.value, ast.Name) and \
                        ctx.value.id in self.stripe_vars:
                    opened.append(ctx.value.id)
            self.locked_stripes.extend(opened)
            self.lock_depth += len(opened)
            self.visit_body(st.body)
            self.lock_depth -= len(opened)
            del self.locked_stripes[len(self.locked_stripes) - len(opened):]
            return
        if isinstance(st, ast.Try):
            locked = self._finally_releases_keylock(st.finalbody)
            if locked:
                self.lock_depth += 1
            self.visit_body(st.body)
            if locked:
                self.lock_depth -= 1
            for h in st.handlers:
                self.visit_body(h.body)
            self.visit_body(st.orelse)
            self.visit_body(st.finalbody)
            return
        if isinstance(st, (ast.If, ast.While)):
            self.scan_expr(st.test)
            self.visit_body(st.body)
            self.visit_body(st.orelse)
            return
        # leaf statements: scan every contained expression
        for child in ast.iter_child_nodes(st):
            if isinstance(child, ast.expr):
                self.scan_expr(child)
            elif isinstance(child, ast.stmt):
                self.visit_stmt(child)

    def _finally_releases_keylock(self, finalbody: Sequence[ast.stmt]) -> bool:
        """A try/finally whose finaliser releases a key lock marks its try
        body as a key-lock region."""
        for st in finalbody:
            for n in ast.walk(st):
                if not isinstance(n, ast.Call):
                    continue
                name = _call_name(n.func)
                if name is None:
                    continue
                if name.startswith("unlock_state_global"):
                    return True
                if name in ("release_read", "release_write", "release") and \
                        isinstance(n.func, ast.Attribute) and \
                        isinstance(n.func.value, ast.Name) and \
                        n.func.value.id in self.keylock_vars:
                    return True
        return False

    # -- expression scan ---------------------------------------------------

    def scan_expr(self, node: ast.AST) -> None:
        for n in ast.walk(node):
            if isinstance(n, ast.Attribute):
                self._check_stripe_access(n)
            elif isinstance(n, ast.Call):
                self._check_call(n)

    def _check_stripe_access(self, n: ast.Attribute) -> None:
        if n.attr not in STRIPE_ATTRS:
            return
        if not (isinstance(n.value, ast.Name)
                and n.value.id in self.stripe_vars):
            return
        if self.access_exempt or n.value.id in self.locked_stripes:
            return
        self.checker.add("stripe-access", n.lineno,
                         f"access to stripe attribute "
                         f"'{n.value.id}.{n.attr}' outside "
                         f"'with {n.value.id}.lock:'")

    @staticmethod
    def _is_codec_encode(n: ast.Call, name: Optional[str]) -> bool:
        """``encode()`` on a receiver that looks like a wire codec/frame —
        plain ``str.encode()`` must not trip the rule."""
        if name != _CODEC_ENCODE or not isinstance(n.func, ast.Attribute):
            return False
        try:
            recv = ast.unparse(n.func.value).lower()
        except Exception:                      # pragma: no cover
            return True                        # can't tell: err on reporting
        return any(hint in recv for hint in ("codec", "frame", "wire"))

    def _check_call(self, n: ast.Call) -> None:
        name = _call_name(n.func)
        if name is None:
            return
        if self.lock_depth > 0 and (name in BLOCKING_CALLS
                                    or self._is_codec_encode(n, name)):
            self.checker.add(
                "lock-blocking", n.lineno,
                f"call to {name}() inside a stripe/key lock region")
        if name == "WireFrame" and \
                not self.checker.path_str.endswith(WIRE_HOME):
            self.checker.add(
                "wire-construct", n.lineno,
                "WireFrame constructed outside repro/state/wire.py — "
                "encode through wire.get_codec(...)")
        if name in _RAW_QUEUE_CALLS and self.checker.bounded_queue_scope:
            self.checker.add(
                "bounded-queue", n.lineno,
                f"raw {name}() in a data-plane package — use "
                f"repro.overload.bounded_queue() (or CoalescingQueue) so "
                f"overload turns into backpressure, not an unbounded buffer")
        if name in _RAW_CLOCK_CALLS and self.checker.data_plane_scope:
            self.checker.add(
                "metric-naming", n.lineno,
                f"direct time.{name}() in a data-plane module — take "
                f"timestamps from repro.telemetry.clock so spans and "
                f"Call timing share one timebase")
        if name in _REGISTRY_METHODS and isinstance(n.func, ast.Attribute) \
                and n.args and isinstance(n.args[0], ast.Constant) \
                and isinstance(n.args[0].value, str):
            try:
                recv = ast.unparse(n.func.value).lower()
            except Exception:                  # pragma: no cover
                recv = "registry"              # can't tell: err on checking
            if any(h in recv for h in ("metric", "reg")) and \
                    not _METRIC_NAME_RE.match(n.args[0].value):
                self.checker.add(
                    "metric-naming", n.lineno,
                    f"metric name {n.args[0].value!r} violates "
                    f"faasm_<subsystem>_<name>_<unit> "
                    f"(unit one of {', '.join(_METRIC_UNITS)})")
        if self.checker.tier_copy_scope and not self.accounted:
            is_np_copy = (name == "copy" and isinstance(n.func, ast.Attribute)
                          and isinstance(n.func.value, ast.Name)
                          and n.func.value.id == "np")
            if name in TIER_COPY_CALLS and isinstance(n.func, ast.Attribute) \
                    or is_np_copy:
                self.checker.add(
                    "tier-copy", n.lineno,
                    f"{name}() in a tier file outside an accounted "
                    f"primitive (no '.copied +=' / charge_net in scope)")


class _FileLinter:
    def __init__(self, source: str, path: str):
        self.path_str = path.replace("\\", "/")
        self.source = source
        self.violations: List[Violation] = []
        self.suppressions = _parse_suppressions(source, path, self.violations)
        self.tier_copy_scope = any(self.path_str.endswith(p)
                                   for p in TIER_COPY_FILES)
        self.bounded_queue_scope = any(d in self.path_str
                                       for d in BOUNDED_QUEUE_DIRS)
        self.data_plane_scope = (
            any(self.path_str.endswith(p) for p in DATA_PLANE_FILES)
            and not self.path_str.endswith(CLOCK_HOME))

    def add(self, rule: str, line: int, message: str) -> None:
        if rule in self.suppressions.get(line, ()):
            return
        self.violations.append(Violation(rule, self.path_str, line, message))

    def run(self) -> List[Violation]:
        tree = ast.parse(self.source, filename=self.path_str)
        self.lint_body(tree.body, None)
        self._lint_fault_points(tree)
        self.violations.sort(key=lambda v: (v.line, v.rule))
        return self.violations

    def _lint_fault_points(self, tree: ast.AST) -> None:
        """fault-point: outside repro/faults.py, only the public surface of
        the fault layer may be named — no ``from repro.faults import _PLAN``
        and no ``faults._anything`` attribute reach-through (that's an
        ad-hoc injection branch the armed/disarmed discipline can't see)."""
        if self.path_str.endswith(FAULTS_HOME):
            return
        aliases: Set[str] = set()
        for n in ast.walk(tree):
            if isinstance(n, ast.Import):
                for a in n.names:
                    if a.name == "repro.faults" and a.asname:
                        aliases.add(a.asname)
            elif isinstance(n, ast.ImportFrom):
                mod = n.module or ""
                if mod == "repro" or mod.endswith("repro"):
                    for a in n.names:
                        if a.name == "faults":
                            aliases.add(a.asname or "faults")
                if mod == "repro.faults" or mod.endswith(".faults"):
                    for a in n.names:
                        if a.name not in FAULTS_PUBLIC:
                            self.add(
                                "fault-point", n.lineno,
                                f"import of repro.faults internal "
                                f"{a.name!r} — sites use faults.point() "
                                f"and plans use arm()/disarm()/armed()")
        if not aliases:
            return
        for n in ast.walk(tree):
            if isinstance(n, ast.Attribute) and \
                    isinstance(n.value, ast.Name) and \
                    n.value.id in aliases and \
                    n.attr not in FAULTS_PUBLIC:
                self.add(
                    "fault-point", n.lineno,
                    f"reach into fault-layer internals "
                    f"'{n.value.id}.{n.attr}' — fault sites go through "
                    f"faults.point(...); plans through arm()/disarm()")

    def lint_body(self, stmts, class_name: Optional[str]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.lint_function(st, class_name)
            elif isinstance(st, ast.ClassDef):
                self.lint_class(st)
            else:
                # module/class-level statements: frame/copy rules still apply
                fl = _FunctionLinter(self, class_name, ast.Module(body=[],
                                                                  type_ignores=[]))
                fl.visit_stmt(st)

    def lint_class(self, cls: ast.ClassDef) -> None:
        self.lint_body(cls.body, cls.name)

    def lint_function(self, fn, class_name: Optional[str]) -> None:
        _FunctionLinter(self, class_name, fn).run()


def lint_source(source: str, path: str = "<string>") -> List[Violation]:
    """Lint python source text; ``path`` scopes the path-sensitive rules."""
    return _FileLinter(source, path).run()


def lint_file(path) -> List[Violation]:
    p = Path(path)
    return lint_source(p.read_text(encoding="utf-8"), str(p))


def lint_paths(paths: Sequence) -> List[Violation]:
    """Lint files and/or directory trees (``*.py``, recursively)."""
    out: List[Violation] = []
    for path in paths:
        p = Path(path)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            out.extend(lint_file(f))
    return out
