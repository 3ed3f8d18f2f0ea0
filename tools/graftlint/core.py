"""graftlint core: findings, pragmas, baselines, module loading, runner.

The analyzer is stdlib-only (``ast`` + ``re``) on purpose: the lint gate
runs before anything heavy imports, it can never be broken by a jax
version bump, and it lints files it does not import (no side effects).

Vocabulary shared by every rule:

* A **Finding** is one violation, anchored to a repo-relative path and a
  1-based line.  Its fingerprint is content-addressed (rule + path +
  normalized source line + occurrence index), so baselines survive
  unrelated line drift.
* A **pragma** is the in-source escape hatch::

      some_call()  # graftlint: allow[HS001] reason=epoch-end fetch

  A pragma covers its own line and the line directly below it (trailing
  same-line comment, or a comment line above the flagged statement — the
  pylint ``disable-next`` convention).  ``allow[...]`` without a
  ``reason=`` is
  itself reported (GL000): an unexplained suppression is how tribal
  rules rot.
* A **baseline** is a checked-in JSON file of grandfathered fingerprints
  (the burn-down list).  Baselined findings are reported as suppressed,
  not failures; fingerprints that no longer match anything are reported
  as stale so the baseline shrinks monotonically.
"""

from __future__ import annotations

import ast
import fnmatch
import hashlib
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULE_IDS = ("HS001", "DL002", "MP003", "RNG004", "CFG005", "MET006")
PRAGMA_RULE = "GL000"  # malformed/unjustified pragma

_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*allow\[([A-Za-z0-9_,\s]+)\]\s*(?:reason=(\S.*))?"
)


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str       # root-relative posix path
    line: int       # 1-based
    message: str
    fingerprint: str = ""

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _norm_line(lines: Sequence[str], lineno: int) -> str:
    if 1 <= lineno <= len(lines):
        return lines[lineno - 1].strip()
    return ""


def fingerprint(rule: str, path: str, norm: str, occurrence: int) -> str:
    digest = hashlib.sha1(norm.encode("utf-8", "replace")).hexdigest()[:12]
    return f"{rule}:{path}:{digest}:{occurrence}"


class Module:
    """One parsed python file: AST + parent links + import table."""

    def __init__(self, path: Path, root: Path):
        self.path = path
        self.rel = path.relative_to(root).as_posix()
        self.source = path.read_text()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=str(path))
        self.parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.imports = _import_table(self.tree)
        self.pragmas = _parse_pragmas(self.lines)

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            yield cur
            cur = self.parents.get(cur)

    def enclosing_funcs(self, node: ast.AST) -> List[ast.AST]:
        """Innermost-first FunctionDef/AsyncFunctionDef ancestors."""
        return [
            a for a in self.ancestors(node)
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]



def _import_table(tree: ast.Module) -> Dict[str, str]:
    """alias -> dotted module/attr (relative imports keep their suffix)."""
    table: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                table[alias.asname or alias.name.split(".")[0]] = (
                    alias.name if alias.asname else alias.name.split(".")[0]
                )
                if alias.asname:
                    table[alias.asname] = alias.name
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for alias in node.names:
                table[alias.asname or alias.name] = (
                    f"{mod}.{alias.name}" if mod else alias.name
                )
    return table


def _parse_pragmas(lines: Sequence[str]) -> Dict[int, Tuple[Set[str], Optional[str]]]:
    """lineno -> (rules allowed on that line, reason or None)."""
    out: Dict[int, Tuple[Set[str], Optional[str]]] = {}
    for i, line in enumerate(lines, start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            reason = m.group(2).strip() if m.group(2) else None
            out[i] = (rules, reason)
    return out


def dotted(node: ast.AST, imports: Dict[str, str]) -> Optional[str]:
    """Best-effort dotted name of an expression, import aliases resolved.

    ``np.asarray`` (with ``import numpy as np``) -> ``numpy.asarray``;
    ``self._fn`` -> ``self._fn``; ``holder["fn"]`` -> ``holder["fn"]``.
    """
    if isinstance(node, ast.Name):
        return imports.get(node.id, node.id)
    if isinstance(node, ast.Attribute):
        base = dotted(node.value, imports)
        return f"{base}.{node.attr}" if base else None
    if isinstance(node, ast.Subscript):
        base = dotted(node.value, imports)
        if base is None:
            return None
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return f'{base}["{sl.value}"]'
        return f"{base}[?]"
    if isinstance(node, ast.Call):
        return None
    return None


# -- baseline -----------------------------------------------------------------


def load_baseline(path: Path) -> Dict[str, Set[str]]:
    data = json.loads(path.read_text())
    if not isinstance(data, dict) or "findings" not in data:
        raise ValueError(f"{path}: not a graftlint baseline (missing 'findings')")
    return {rule: set(fps) for rule, fps in data["findings"].items()}


def write_baseline(path: Path, findings: Sequence[Finding]) -> None:
    by_rule: Dict[str, List[str]] = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f.fingerprint)
    payload = {
        "version": 1,
        "findings": {rule: sorted(fps) for rule, fps in sorted(by_rule.items())},
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")


def apply_baseline(
    findings: Sequence[Finding], baseline: Dict[str, Set[str]]
) -> Tuple[List[Finding], List[Finding], Dict[str, Set[str]]]:
    """(new, suppressed, stale-entries-by-rule)."""
    new: List[Finding] = []
    suppressed: List[Finding] = []
    seen: Dict[str, Set[str]] = {}
    for f in findings:
        if f.fingerprint in baseline.get(f.rule, set()):
            suppressed.append(f)
            seen.setdefault(f.rule, set()).add(f.fingerprint)
        else:
            new.append(f)
    stale = {
        rule: fps - seen.get(rule, set())
        for rule, fps in baseline.items()
        if fps - seen.get(rule, set())
    }
    return new, suppressed, stale


# -- config -------------------------------------------------------------------


@dataclass
class LintConfig:
    """Repo-specific rule parameters.  Tests point these at fixture trees;
    the defaults encode THIS repo's invariants (see docs/static_analysis.md
    for the rationale behind each list)."""

    root: Path = field(default_factory=Path.cwd)

    # HS001: hot-loop modules where blocking host syncs are violations
    hs001_modules: Tuple[str, ...] = (
        "handyrl_tpu/runtime/trainer.py",
        "handyrl_tpu/runtime/learner.py",
        "handyrl_tpu/runtime/device_*.py",
        "handyrl_tpu/parallel/train_step.py",
        # the serving plane's request loop is a latency hot path: one
        # stray per-batch host sync is a p99 regression on every model
        "handyrl_tpu/serving/*.py",
        # the league plane sits inside the learner's epoch/feed loops and
        # the actors' match loop: a host sync here stalls generation
        "handyrl_tpu/league/*.py",
        # the multi-process cadence runs once per SGD step on the trainer
        # thread and the health plane's threads run beside every dispatch:
        # a stray sync here is a per-step cross-host stall
        "handyrl_tpu/parallel/distributed.py",
        "handyrl_tpu/parallel/health.py",
        # the tracer's span/record path runs INSIDE every instrumented
        # hot seam (dispatch_serialized, batch waits, cadence): a host
        # sync here would be charged to every dispatch in the repo
        "handyrl_tpu/utils/trace.py",
        # the fleet tier sits on the serving request path twice (router
        # proxy + session cache lookup/store on every stateful infer):
        # a stray host sync is a per-request latency regression
        "handyrl_tpu/fleet/*.py",
        # the low-precision fast path's dequantize runs INSIDE the jitted
        # engine apply and the ring sample/forward programs: a host sync
        # here would serialize every quantized inference and train window
        "handyrl_tpu/models/quantize.py",
        # the cross-host plane transports run on threads beside the
        # trainer's dispatch stream and inside the actor host's rollout
        # loop: every host materialization must be an annotated transport
        # boundary, not an accidental sync
        "handyrl_tpu/runtime/plane.py",
        "handyrl_tpu/runtime/actor_host.py",
        "handyrl_tpu/runtime/rollout_plane.py",
        # the flywheel's harvest capture seams run INSIDE the serving
        # request path (_do_infer / _reply) and its quality tick inside
        # the watch loop: a stray host sync is a per-request regression
        "handyrl_tpu/flywheel/*.py",
    )
    # functions (bare names) that are drain/teardown/construction paths —
    # host syncs there are the POINT, not a leak
    hs001_allow_funcs: Tuple[str, ...] = (
        "__init__", "drain", "stop", "close", "teardown",
    )
    # calls that mark a loop as a dispatching hot loop (np.asarray/float
    # are only violations when their nearest enclosing loop dispatches)
    dispatch_hints: Tuple[str, ...] = (
        "dispatch_serialized", "train_step", "train_steps",
        "ingest", "ingest_counted", "generate", "evaluate", "train",
    )

    # DL002: modules whose compiled-call dispatch sites must go through
    # parallel.mesh.dispatch_serialized with an explicit device scope
    dl002_modules: Tuple[str, ...] = (
        "handyrl_tpu/runtime/trainer.py",
        "handyrl_tpu/runtime/learner.py",
        "handyrl_tpu/runtime/device_*.py",
        "handyrl_tpu/runtime/plane.py",
        # the actor host's streaming rollout dispatches onto its local
        # mesh concurrently with param polls: same lock discipline
        "handyrl_tpu/runtime/actor_host.py",
        "handyrl_tpu/runtime/rollout_plane.py",
        "handyrl_tpu/runtime/shm_batch.py",
        "handyrl_tpu/parallel/train_step.py",
        # per-model serving engines share chips with each other (and, co-
        # located, with a training plane): every engine dispatch must hold
        # its explicit device scope
        "handyrl_tpu/serving/*.py",
        # league opponent engines co-reside with the training plane (and
        # each other) on the same chips — same invariant as serving
        "handyrl_tpu/league/*.py",
        # the cadence broadcasts are device programs sharing the learner
        # mesh with the train step: same lock discipline as every dispatch
        "handyrl_tpu/parallel/distributed.py",
        "handyrl_tpu/parallel/health.py",
        # the tracer must never dispatch device programs at all — any jit
        # call appearing here is a bug, and DL002 makes it lock-scoped
        "handyrl_tpu/utils/trace.py",
        # the session cache touches the device (re-pin on restore) next
        # to serving engines sharing the same chips: same lock discipline
        "handyrl_tpu/fleet/*.py",
        # quantized engines dispatch the SAME compiled apply the serving
        # batchers route through dispatch_serialized; direct dispatches in
        # the quantize module itself must hold the same lock discipline
        "handyrl_tpu/models/quantize.py",
        # the flywheel stages candidate engines onto the same chips the
        # router's serving engines occupy — any device dispatch it grows
        # must hold the same explicit scope
        "handyrl_tpu/flywheel/*.py",
    )
    dispatch_wrapper: str = "dispatch_serialized"

    # CFG005: config defaults <-> docs parity
    cfg005_config: str = "handyrl_tpu/config.py"
    cfg005_docs: str = "docs/parameters.md"
    # dict-valued defaults whose CHILDREN are the knobs (worker.entry_port);
    # every other dict-valued default (mesh, ...) is one knob
    cfg005_nested: Tuple[str, ...] = (
        "worker", "distributed", "eval", "serving", "league", "trace",
        "observability", "fleet", "flywheel",
        # second-level section: the autoscaler's knobs are documented
        # per-knob (fleet.autoscale.enabled, ...), not as one opaque dict
        "fleet.autoscale",
    )
    # documented spellings that are intentionally not defaults (aliases
    # normalized away before validation)
    cfg005_doc_aliases: Tuple[str, ...] = ("attn_mode",)

    # MET006: metrics key registry <-> writers <-> consumers
    met006_registry: str = "handyrl_tpu/utils/metrics.py"
    met006_writers: Tuple[str, ...] = (
        "handyrl_tpu/runtime/learner.py",
        "handyrl_tpu/runtime/rollout_plane.py",
        "handyrl_tpu/runtime/trainer.py",
        "handyrl_tpu/serving/server.py",
        "handyrl_tpu/league/learner.py",
        "handyrl_tpu/fleet/router_tier.py",
        "handyrl_tpu/fleet/sessions.py",
        # the flywheel's stats_record feeds both the serving server's
        # periodic record and the learner's per-epoch record
        "handyrl_tpu/flywheel/harvest.py",
        "handyrl_tpu/flywheel/quality.py",
        "handyrl_tpu/flywheel/ingest.py",
    )
    # module-level *_KEYS tuples that feed metrics keys, with the prefix
    # they are written under
    met006_key_tuples: Dict[str, str] = field(default_factory=lambda: {
        "PIPE_STAT_KEYS": "pipe_",
        "PIPE_EVENT_KEYS": "pipe_",
        "SENTINEL_EVENT_KEYS": "",
        "WATCHDOG_EVENT_KEYS": "",
    })
    met006_record_names: Tuple[str, ...] = ("record", "rec", "r")
    met006_stats_attrs: Tuple[str, ...] = ("self.stats",)
    met006_consumers: Tuple[str, ...] = (
        "scripts/_logparse.py",
        "scripts/stats_plot.py",
        "scripts/loss_plot.py",
        "scripts/win_rate_plot.py",
        "tools/ablate_sampling_path.py",
    )
    met006_record_sources: Tuple[str, ...] = ("read_metrics", "parse_records")


def match_any(rel: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatch(rel, pat) for pat in patterns)


def collect_py_files(root: Path, paths: Sequence[str]) -> List[Path]:
    out: List[Path] = []
    for p in paths:
        full = (root / p) if not Path(p).is_absolute() else Path(p)
        if full.is_dir():
            out.extend(sorted(full.rglob("*.py")))
        elif full.suffix == ".py":
            out.append(full)
    # dedupe, keep order
    seen: Set[Path] = set()
    uniq = []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def run_lint(
    config: LintConfig,
    paths: Sequence[str],
    rules: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Run the selected rules; returns findings with fingerprints filled,
    pragma-suppressed findings already removed, and GL000 findings for
    pragmas without a reason."""
    from . import rules_contract, rules_runtime

    enabled = set(rules or RULE_IDS)
    root = config.root
    files = collect_py_files(root, paths)
    modules: List[Module] = []
    for path in files:
        try:
            modules.append(Module(path, root))
        except (SyntaxError, UnicodeDecodeError) as exc:
            raise RuntimeError(f"graftlint: cannot parse {path}: {exc}") from exc

    raw: List[Finding] = []
    if enabled & {"HS001", "DL002", "MP003", "RNG004"}:
        raw.extend(rules_runtime.run(modules, config, enabled))
    if enabled & {"CFG005", "MET006"}:
        raw.extend(rules_contract.run(config, enabled))

    # pragma handling + GL000 for reasonless pragmas.  The pragma universe
    # is every file a rule can anchor a finding in: the scanned modules
    # PLUS the contract-rule targets (config/docs/registry/writers/
    # consumers) — pragmas are text-level, so non-scanned and non-python
    # files (docs/parameters.md) carry them the same way
    kept: List[Finding] = []
    line_cache: Dict[str, List[str]] = {m.rel: m.lines for m in modules}
    pragma_cache: Dict[str, Dict[int, Tuple[Set[str], Optional[str]]]] = {
        m.rel: m.pragmas for m in modules
    }
    contract_files = (
        (config.cfg005_config, config.cfg005_docs, config.met006_registry)
        + tuple(config.met006_writers)
        + tuple(config.met006_consumers)
    )
    for rel in contract_files:
        if rel in pragma_cache:
            continue
        try:
            lines = (root / rel).read_text().splitlines()
        except OSError:
            continue
        line_cache[rel] = lines
        pragma_cache[rel] = _parse_pragmas(lines)
    for f in raw:
        pragmas = pragma_cache.get(f.path, {})
        covered = False
        for pragma_line in (f.line, f.line - 1):
            entry = pragmas.get(pragma_line)
            if entry and f.rule in entry[0]:
                covered = True
                break
        if not covered:
            kept.append(f)
    for rel, pragmas in pragma_cache.items():
        for lineno, (rules_set, reason) in pragmas.items():
            if not reason:
                kept.append(Finding(
                    PRAGMA_RULE, rel, lineno,
                    f"pragma allow[{','.join(sorted(rules_set))}] has no "
                    "reason= — every suppression must say why",
                ))

    # fingerprints (content-addressed, occurrence-indexed)
    counts: Dict[Tuple[str, str, str], int] = {}
    final: List[Finding] = []
    for f in sorted(kept, key=lambda f: (f.path, f.line, f.rule)):
        lines = line_cache.get(f.path)
        if lines is None:
            try:
                lines = (root / f.path).read_text().splitlines()
            except OSError:
                lines = []
            line_cache[f.path] = lines
        norm = _norm_line(lines, f.line)
        key = (f.rule, f.path, norm)
        occ = counts.get(key, 0)
        counts[key] = occ + 1
        final.append(Finding(
            f.rule, f.path, f.line, f.message,
            fingerprint(f.rule, f.path, norm, occ),
        ))
    return final
