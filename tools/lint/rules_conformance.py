"""Rule family 5 — choke-point conformance.

``fault-site-registry``: every literal site passed to
`resilience.faults.maybe_inject` / ``corrupt`` must
be registered in `dbcsr_tpu/resilience/sites.py` — an unregistered
site is invisible to the chaos suite and to docs/resilience.md.

``fault-site-docs`` (repo): the resilience.md site table must
byte-match regeneration from the registry; `tools/chaos_suite.py`
must derive its draw from the registry (a hand-kept literal tuple is
the drift this PR converts to a checked one); registered non-dynamic
sites must actually exist in source.

``metric-docs``: every ``dbcsr_tpu_*`` metric-name literal in the
package must appear in `docs/observability.md` — an undocumented
metric family is unmonitorable.

``event-bypass``: trace/flight emissions outside `dbcsr_tpu/obs/`
must go through `obs.events.publish(...)` (which fans out the tracer
instant and the flight event, stamps `product_id` correlation, and
lands on the bounded bus) — direct `tracer.instant` /
`flight.note_event` calls lose the bus record and the correlation id.
"""

from __future__ import annotations

import ast
import os
import re

from tools.lint import registry
from tools.lint.engine import Finding

RULE_SITE = "fault-site-registry"
RULE_SITE_DOCS = "fault-site-docs"
RULE_METRIC = "metric-docs"
RULE_BYPASS = "event-bypass"

FAULT_CALLS = {"maybe_inject", "corrupt"}
FAULTS_IMPL = ("dbcsr_tpu/resilience/faults.py",
               "dbcsr_tpu/resilience/sites.py")
METRIC_RE = re.compile(r"^dbcsr_tpu_[a-z0-9_]+$")
OBS_PREFIX = "dbcsr_tpu/obs/"
OBS_DOC = "docs/observability.md"
# doc spellings: name, optional {a,b} expansions mid-name, optional
# trailing {label,...} set
_DOC_METRIC_RE = re.compile(
    r"dbcsr_tpu_[a-z0-9_]*(?:\{[a-z0-9_,]+\}[a-z0-9_]*)*")


def _expand_doc_token(tok: str) -> list:
    """'a_{x,y}_b{lbl}' -> ['a_x_b', 'a_y_b'] — comma groups expand
    into the name, a non-comma group is a label set ending it."""
    names = [""]
    rest = tok
    while rest:
        m = re.match(r"\{([a-z0-9_,]+)\}", rest)
        if m:
            alts = m.group(1).split(",")
            tail = rest[m.end():]
            # a group with nothing after it is a label set
            # (`_total{site,kind}`), not a name expansion
            if len(alts) == 1 or not re.match(r"[a-z0-9_]", tail):
                break
            names = [n + a for n in names for a in alts]
            rest = tail
            continue
        m = re.match(r"[a-z0-9_]+", rest)
        if not m:
            break
        names = [n + m.group(0) for n in names]
        rest = rest[m.end():]
    return [n for n in names if METRIC_RE.match(n)]


def _documented_metrics(repo) -> set:
    cached = getattr(repo, "_doc_metrics", None)
    if cached is not None:
        return cached
    names: set = set()
    docs_dir = os.path.join(repo.root, "docs")
    for dirpath, _, files in os.walk(docs_dir):
        for f in files:
            if not f.endswith(".md"):
                continue
            text = open(os.path.join(dirpath, f), encoding="utf-8").read()
            for tok in _DOC_METRIC_RE.findall(text):
                names |= set(_expand_doc_token(tok))
    repo._doc_metrics = names
    return names


def _sites(repo):
    cached = getattr(repo, "_sites_registry", None)
    if cached is None:
        cached = registry.load_sites(repo.root)
        repo._sites_registry = cached
    return cached


# ------------------------------------------------------ fault sites

def _check_sites(ctx, repo):
    if not (ctx.path.startswith("dbcsr_tpu/") or ctx.path == "bench.py"):
        return []
    if ctx.path in FAULTS_IMPL:
        return []
    sites = _sites(repo)
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in FAULT_CALLS and node.args):
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            continue  # dynamic site names are covered by `dynamic` entries
        if arg.value in sites:
            continue
        f = ctx.finding(
            RULE_SITE, node,
            f"fault site `{arg.value}` is not registered: add it to "
            "dbcsr_tpu/resilience/sites.py (and rerun "
            "`python -m tools.lint --gen-docs`) so the chaos suite and "
            "docs/resilience.md can see it")
        if f is not None:
            out.append(f)
    return out


def _check_site_docs(repo):
    out = []
    # generated table block freshness
    text = repo.read(registry.RESILIENCE_DOC)
    block = registry.sites_block_of(text)
    want = registry.gen_sites_block(repo.root)
    if block != want:
        out.append(Finding(
            rule=RULE_SITE_DOCS, path=registry.RESILIENCE_DOC, line=1,
            message="fault-site table out of date (or markers missing): "
                    "run `python -m tools.lint --gen-docs`"))
    # the chaos suite must derive from the registry, not keep a literal
    chaos = repo.read("tools/chaos_suite.py")
    if chaos:
        tree = ast.parse(chaos)
        for node in tree.body:
            if not isinstance(node, ast.Assign):
                continue
            names = {t.id for t in node.targets if isinstance(t, ast.Name)}
            if names & {"SITES", "CORRUPTIBLE"} and isinstance(
                    node.value, (ast.Tuple, ast.List)):
                out.append(Finding(
                    rule=RULE_SITE_DOCS, path="tools/chaos_suite.py",
                    line=node.lineno,
                    message="hand-kept site tuple: derive from "
                            "dbcsr_tpu/resilience/sites.py "
                            "(chaos_sites / chaos_corrupt_targets)"))
    # every registered non-dynamic site must exist in source
    in_source = set()
    for ctx in repo.files:
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in FAULT_CALLS and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                in_source.add(node.args[0].value)
    for name, meta in sorted(_sites(repo).items()):
        if meta.get("dynamic") or name in in_source:
            continue
        out.append(Finding(
            rule=RULE_SITE_DOCS, path=registry.SITES_MODULE, line=1,
            symbol=name,
            message=f"registered site `{name}` has no injection call in "
                    "the scanned tree: remove it or mark it dynamic"))
    return out


# ---------------------------------------------------------- metrics

def _check_metrics(ctx, repo):
    if not ctx.path.startswith("dbcsr_tpu/"):
        return []
    documented = _documented_metrics(repo)
    out = []
    seen = set()
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and METRIC_RE.match(node.value)):
            continue
        name = node.value
        if name.endswith("_"):
            continue  # family prefix for built-up names, not a metric
        if name in seen or name in documented:
            continue
        seen.add(name)
        f = ctx.finding(
            RULE_METRIC, node,
            f"metric name `{name}` is documented nowhere under docs/: "
            f"add it to the exported-families tables of {OBS_DOC} (or "
            "the owning domain doc)")
        if f is not None:
            out.append(f)
    return out


# ----------------------------------------------------- event bypass

def _emitter_aliases(tree) -> dict:
    """alias -> 'tracer'|'flight' for obs submodule imports."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
                node.module.endswith("obs") or node.module == "obs"):
            for a in node.names:
                if a.name in ("tracer", "flight"):
                    out[a.asname or a.name] = a.name
    return out


def _check_bypass(ctx, repo):
    if not ctx.path.startswith("dbcsr_tpu/"):
        return []
    if ctx.path.startswith(OBS_PREFIX):
        return []  # the bus implementation and its siblings
    aliases = _emitter_aliases(ctx.tree)
    if not aliases:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)):
            continue
        mod = aliases.get(node.func.value.id)
        if mod is None:
            continue
        if (mod == "tracer" and node.func.attr == "instant") or (
                mod == "flight" and node.func.attr == "note_event"):
            f = ctx.finding(
                RULE_BYPASS, node,
                f"direct `{mod}.{node.func.attr}` emission bypasses the "
                "event bus: use `obs.events.publish(kind, args, "
                "flight=...)` so the record lands on the bounded bus "
                "with `product_id` correlation")
            if f is not None:
                out.append(f)
    return out


FILE_RULES = [_check_sites, _check_metrics, _check_bypass]
REPO_RULES = [_check_site_docs]
