"""lgbtlint rule engine: file walker, rule registry, baseline, CLI.

Design (reference analog: the C++ tree's clang-tidy/sanitizer CI lanes,
here rebuilt as AST checks because the invariants live in Python):

  * every checked file is parsed ONCE into a :class:`Module` (source +
    ``ast`` tree + lazily-built semantic model, rules/common.py);
  * a rule is a class with a ``rule_id`` and either ``check_module``
    (per-file AST pass) or ``check_repo`` (whole-repo invariants like
    config<->doc drift);
  * findings carry ``file:line``, the rule id, a one-line message and a
    fix hint, and are gated against a reviewed suppression baseline
    (``analysis/baseline.toml``) — a finding is a hard failure unless a
    baseline entry with a written justification pins it.

The engine is stdlib-only and must stay fast (< 10 s repo-wide budget —
it runs as the first stage of scripts/run_all_tests.sh): this module
imports no jax, no file is read twice, and LGB007's doc-drift check
loads the generator in-process (importlib) instead of paying a second
interpreter+package start in a subprocess.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import subprocess
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_MARKERS = ("pytest.ini", "ROADMAP.md")

# directories under the repo root that the gate walks by default; tests/
# is deliberately excluded — test files exercise tripping patterns (rule
# fixtures, chaos writes) that are violations by design
DEFAULT_SCAN = ("lightgbm_tpu", "scripts", "bench.py", "chip_smoke.py",
                "__graft_entry__.py")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str          # "LGB001"
    file: str          # repo-relative posix path
    line: int          # 1-based; 0 = whole-file finding
    message: str
    hint: str = ""

    def key(self) -> Tuple[str, str, int]:
        return (self.rule, self.file, self.line)

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        out = f"{loc}: {self.rule}: {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


class Module:
    """One parsed source file handed to every per-file rule."""

    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel              # repo-relative posix path
        self.source = source
        self.tree = ast.parse(source, filename=rel)
        self._model = None

    @property
    def model(self):
        """Lazily-built semantic model (rules/common.py) shared by rules."""
        if self._model is None:
            from .rules.common import ModuleModel
            self._model = ModuleModel(self.tree)
        return self._model

    def finding(self, rule: str, node: ast.AST, message: str,
                hint: str = "") -> Finding:
        return Finding(rule, self.rel, getattr(node, "lineno", 0),
                       message, hint)


def find_repo_root(start: Optional[Path] = None) -> Path:
    p = (start or Path(__file__)).resolve()
    for cand in [p] + list(p.parents):
        if any((cand / m).exists() for m in REPO_MARKERS):
            return cand
    return Path.cwd()


def default_files(root: Path) -> List[Path]:
    out: List[Path] = []
    for entry in DEFAULT_SCAN:
        p = root / entry
        if p.is_file():
            out.append(p)
        elif p.is_dir():
            out.extend(sorted(p.rglob("*.py")))
    return out


def _changed_files(root: Path) -> Optional[List[str]]:
    """Repo-relative paths touched vs HEAD (staged + unstaged + untracked);
    None when git is unavailable (caller falls back to the full walk)."""
    try:
        diff = subprocess.run(
            ["git", "-C", str(root), "diff", "--name-only", "HEAD", "--"],
            capture_output=True, text=True, timeout=30)
        untracked = subprocess.run(
            ["git", "-C", str(root), "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if diff.returncode != 0:
        return None
    # splitlines, not split: paths may contain spaces (git prints one
    # path per line; quoted/escaped exotic names can't match the walked
    # posix spelling anyway, so they harmlessly never filter)
    names = diff.stdout.splitlines() + (
        untracked.stdout.splitlines() if untracked.returncode == 0 else [])
    return sorted({n for n in names if n})


def _rel_to(path: Path, root: Path) -> str:
    """Repo-relative posix path; explicit CLI paths outside the repo keep
    their absolute spelling (they can't match the baseline anyway)."""
    resolved = path.resolve()
    try:
        return resolved.relative_to(root).as_posix()
    except ValueError:
        return resolved.as_posix()


def load_modules(files: Sequence[Path], root: Path
                 ) -> Tuple[List[Module], List[Finding]]:
    """Parse every file; syntax errors become findings, not crashes."""
    mods: List[Module] = []
    errors: List[Finding] = []
    for path in files:
        rel = _rel_to(path, root)
        try:
            mods.append(Module(path, rel, path.read_text(encoding="utf-8")))
        except (OSError, UnicodeDecodeError, SyntaxError) as e:
            line = getattr(e, "lineno", 0) or 0
            errors.append(Finding("LGB000", rel, line,
                                  f"cannot parse: {e}",
                                  "fix the syntax error; the gate cannot "
                                  "analyze what it cannot parse"))
    return mods, errors


def resolve_files(root: Path, files: Optional[Sequence[Path]] = None,
                  changed_only: bool = False
                  ) -> Tuple[List[Path], Optional[List[str]]]:
    """The walk a run will actually check: explicit ``files`` or the
    default repo walk, optionally narrowed to git-changed paths."""
    walked = list(files) if files is not None else default_files(root)
    changed: Optional[List[str]] = None
    if changed_only:
        changed = _changed_files(root)
        if changed is not None:
            keep = set(changed)
            walked = [p for p in walked if _rel_to(p, root) in keep]
    return walked, changed


def run_analysis(root: Optional[Path] = None,
                 files: Optional[Sequence[Path]] = None,
                 rules: Optional[Sequence] = None,
                 changed_only: bool = False) -> List[Finding]:
    """Run ``rules`` (default: the full catalog) over ``files`` (default:
    the standard repo walk) and return sorted findings."""
    from .rules import all_rules

    root = root or find_repo_root()
    rules = list(rules) if rules is not None else all_rules()
    walked, changed = resolve_files(root, files, changed_only)
    mods, findings = load_modules(walked, root)
    for rule in rules:
        for mod in mods:
            findings.extend(rule.check_module(mod))
        findings.extend(rule.check_repo(root, mods, changed=changed))
    return sorted(findings, key=lambda f: (f.file, f.line, f.rule))


# ---------------------------------------------------------------------------
# suppression baseline (analysis/baseline.toml)
# ---------------------------------------------------------------------------
#
# Format: a sequence of [[suppress]] tables, one per pinned finding:
#
#   [[suppress]]
#   rule = "LGB005"
#   file = "lightgbm_tpu/robustness/chaos.py"
#   line = 120
#   reason = "chaos once-marker: test-only latch, partial write harmless"
#
# Matching is exact on (rule, file, line): a pinned finding that moves
# re-fails the gate, which is intended — suppressions are re-reviewed
# when the code around them changes (`--update-baseline` rewrites the
# file keeping existing reasons).  Parsed with a minimal reader because
# this interpreter has no tomllib (3.10) and no third-party toml.

BASELINE_NAME = "baseline.toml"


@dataclasses.dataclass(frozen=True)
class Suppression:
    rule: str
    file: str
    line: int
    reason: str

    def key(self) -> Tuple[str, str, int]:
        return (self.rule, self.file, self.line)


def _parse_toml_value(raw: str, where: str):
    raw = raw.strip()
    if raw.startswith('"'):
        if not raw.endswith('"') or len(raw) < 2:
            raise ValueError(f"{where}: unterminated string {raw!r}")
        return raw[1:-1].replace('\\"', '"').replace("\\\\", "\\")
    if raw in ("true", "false"):
        return raw == "true"
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{where}: unsupported TOML value {raw!r} (the "
                         "baseline reader takes strings, ints, booleans)")


def load_baseline(path: Path) -> List[Suppression]:
    if not path.exists():
        return []
    entries: List[Dict[str, object]] = []
    current: Optional[Dict[str, object]] = None
    for n, raw in enumerate(path.read_text(encoding="utf-8").splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        where = f"{path.name}:{n}"
        if line == "[[suppress]]":
            current = {}
            entries.append(current)
            continue
        if line.startswith("["):
            raise ValueError(f"{where}: only [[suppress]] tables are "
                             f"supported, got {line!r}")
        if current is None:
            raise ValueError(f"{where}: key outside a [[suppress]] table")
        key, sep, value = line.partition("=")
        if not sep:
            raise ValueError(f"{where}: expected key = value, got {line!r}")
        # strip a trailing comment (only outside the quoted value)
        value = value.strip()
        if value.startswith('"'):
            # scan to the closing quote (honoring \" escapes) so a
            # trailing `# comment` after the string parses as TOML
            # instead of poisoning the value
            i, end = 1, len(value)
            while i < end and value[i] != '"':
                i += 2 if value[i] == "\\" else 1
            if i >= end:
                raise ValueError(f"{where}: unterminated string {value!r}")
            rest = value[i + 1:].strip()
            if rest and not rest.startswith("#"):
                raise ValueError(f"{where}: trailing characters after "
                                 f"string value: {rest!r}")
            value = value[:i + 1]
        elif "#" in value:
            value = value.split("#", 1)[0].strip()
        current[key.strip()] = _parse_toml_value(value, where)
    out = []
    for i, e in enumerate(entries):
        missing = {"rule", "file", "line", "reason"} - set(e)
        if missing:
            raise ValueError(f"{path.name}: [[suppress]] entry #{i + 1} "
                             f"missing {sorted(missing)}")
        if not str(e["reason"]).strip():
            raise ValueError(f"{path.name}: [[suppress]] entry #{i + 1} "
                             "has an empty reason — every suppression "
                             "needs a one-line justification")
        out.append(Suppression(str(e["rule"]), str(e["file"]),
                               int(e["line"]), str(e["reason"])))
    return out


def render_baseline(entries: Sequence[Suppression]) -> str:
    head = ("# lgbtlint suppression baseline (docs/ANALYSIS.md).\n"
            "# Every entry pins ONE finding by (rule, file, line) and "
            "carries a reviewed\n"
            "# one-line justification. Regenerate with:\n"
            "#   python -m lightgbm_tpu.analysis --update-baseline\n")
    blocks = []
    for s in sorted(entries, key=lambda s: (s.file, s.line, s.rule)):
        reason = s.reason.replace("\\", "\\\\").replace('"', '\\"')
        blocks.append("[[suppress]]\n"
                      f'rule = "{s.rule}"\n'
                      f'file = "{s.file}"\n'
                      f"line = {s.line}\n"
                      f'reason = "{reason}"\n')
    return head + "\n" + "\n".join(blocks)


def apply_baseline(findings: Sequence[Finding],
                   baseline: Sequence[Suppression]
                   ) -> Tuple[List[Finding], List[Finding],
                              List[Suppression]]:
    """Split into (active, suppressed) findings + stale baseline entries
    that matched nothing (stale entries are reported so dead pins get
    cleaned up instead of silently masking future regressions)."""
    by_key = {s.key(): s for s in baseline}
    used = set()
    active, suppressed = [], []
    for f in findings:
        if f.key() in by_key:
            used.add(f.key())
            suppressed.append(f)
        else:
            active.append(f)
    stale = [s for s in baseline if s.key() not in used]
    return active, suppressed, stale


def default_baseline_path(root: Path) -> Path:
    return root / "lightgbm_tpu" / "analysis" / BASELINE_NAME


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    from .rules import all_rules

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="lgbtlint: repo-specific static-analysis gate "
                    "(rule catalog: docs/ANALYSIS.md)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to check (default: standard repo walk)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON array")
    ap.add_argument("--changed-only", action="store_true",
                    help="check only files changed vs git HEAD (+untracked)")
    ap.add_argument("--baseline", default=None,
                    help="suppression file (default: "
                         "lightgbm_tpu/analysis/baseline.toml)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the suppression baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline to pin all current findings "
                         "(existing reasons are kept; new entries get a "
                         "TODO reason that must be edited before the gate "
                         "accepts them)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)
    if args.update_baseline and args.no_baseline:
        # --no-baseline empties `keep`, so the rewrite would replace every
        # reviewed justification with the TODO placeholder — refuse
        ap.error("--update-baseline and --no-baseline are mutually "
                 "exclusive (the rewrite preserves existing reasons)")

    rules = all_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.rule_id}  {r.title}")
        return 0

    root = find_repo_root(Path.cwd())
    files: Optional[List[Path]] = None
    if args.paths:
        files = []
        for p in args.paths:
            pp = Path(p)
            if pp.is_dir():
                files.extend(sorted(pp.rglob("*.py")))
            else:
                files.append(pp)
    try:
        findings = run_analysis(root, files=files,
                                changed_only=args.changed_only)
    except Exception as e:  # noqa: BLE001 — the gate must report, not crash
        print(f"lgbtlint: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    bpath = Path(args.baseline) if args.baseline else \
        default_baseline_path(root)
    try:
        baseline = [] if args.no_baseline else load_baseline(bpath)
    except ValueError as e:
        print(f"lgbtlint: bad baseline: {e}", file=sys.stderr)
        return 2

    if args.update_baseline:
        keep = {s.key(): s for s in baseline}
        entries = [keep.get(f.key(),
                            Suppression(f.rule, f.file, f.line,
                                        "TODO: justify this suppression"))
                   for f in findings]
        if args.paths or args.changed_only:
            # a partial walk never re-checks the sites outside its scope:
            # keep their reviewed pins verbatim instead of wiping them
            walked, _ = resolve_files(root, files=files,
                                      changed_only=args.changed_only)
            scanned = {_rel_to(p, root) for p in walked}
            have = {e.key() for e in entries}
            entries += [s for s in baseline
                        if s.file not in scanned and s.key() not in have]
        bpath.parent.mkdir(parents=True, exist_ok=True)
        # tmp + os.replace: the gate eats its own LGB005 dogfood
        from ..robustness.checkpoint import atomic_write_text
        atomic_write_text(str(bpath), render_baseline(entries))
        print(f"lgbtlint: wrote {len(entries)} suppression(s) to {bpath}")
        todo = sum(1 for e in entries if e.reason.startswith("TODO"))
        if todo:
            print(f"lgbtlint: {todo} entr{'y' if todo == 1 else 'ies'} "
                  "need a real reason before the gate passes review")
        return 0

    active, suppressed, stale = apply_baseline(findings, baseline)
    if args.paths or args.changed_only:
        # partial walks don't visit every baselined site — a pin whose
        # file wasn't checked is not stale, only the full gate can tell
        stale = []

    # an --update-baseline stamp is a placeholder, not a review: the gate
    # refuses it until a human writes the justification
    todo = [s for s in baseline if s.reason.strip().startswith("TODO")]

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_json() for f in active],
            "suppressed": [f.to_json() for f in suppressed],
            "stale_baseline": [dataclasses.asdict(s) for s in stale],
            "todo_baseline": [dataclasses.asdict(s) for s in todo],
            "checked_rules": [r.rule_id for r in rules],
        }, indent=1, sort_keys=True))
        return 1 if active or stale or todo else 0

    for f in active:
        print(f.render())
    for s in stale:
        print(f"{s.file}:{s.line}: stale baseline entry for {s.rule} "
              f"(no matching finding) — remove it or rerun "
              f"--update-baseline")
    for s in todo:
        print(f"{s.file}:{s.line}: baseline entry for {s.rule} still has "
              "the TODO placeholder reason — write the one-line "
              "justification")
    n = len(active)
    if n or stale or todo:
        print(f"lgbtlint: {n} finding(s), {len(suppressed)} suppressed, "
              f"{len(stale)} stale, {len(todo)} unjustified baseline "
              "entries")
        return 1
    print(f"lgbtlint: clean ({len(suppressed)} suppressed, "
          f"{len(rules)} rules)")
    return 0
