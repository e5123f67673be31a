"""telemetry-schema: emitted events match the documented catalogue.

The journal is append-only and additive — consumers must ignore unknown
events — which is exactly why drift is silent: an emit site renamed or
grown a field keeps working, the docs and the exporter just stop telling
the truth (it already happened once; review caught it). This rule makes the
three-way contract mechanical:

1. **Events** — every journal ``emit``/``span``/``begin``/``end`` event
   name, from every emit site in the package, must appear in the
   ``docs/observability.md`` event catalogue. Sites are collected through
   the declared wrappers (``Extractor._emit``/``_span`` inject ``model``,
   ``ExtractionService._emit``, the scheduler's ``_note_queued``), which
   are *discovered*, not hardcoded: any package function that forwards one
   of its parameters as the event name into a journal call (directly or
   through another wrapper) is a wrapper, and its call sites are resolved
   with the shared literal-string flow (:mod:`tools.vftlint.dataflow`) —
   so ``_note_queued(job, "video_requeued")`` resolves and an event name
   built from runtime data is a finding (unresolvable = uncheckable).
   ``obs/journal.py`` itself is the primitive layer (its span machinery
   builds ``<name>_start``/``_end`` strings) and is skipped, except its
   ``journal_open``/``journal_close`` record literals.
2. **Exporter** — ``obs/export.py``'s pairing event names (the ``name ==
   "video_popped"``-style literals), derived slice names (``slice_event``
   literals), and ``_META_EVENTS`` must all be documented.
3. **Stats schema** — the daemon ``stats`` op's top-level keys (and the
   sub-keys of statically enumerable groups: inline dict literals and
   one-hop ``self._method()`` dict returns) must match the schema-1 table
   in ``docs/serving.md``, in *both* directions — the table is the external
   scraper's contract, so a stale documented field is as bad as an
   undocumented emitted one.

Per-event fields are checked as a subset of the catalogue row's backticked
fields (plus the wrapper's injected fields and the implicit ``span``); a
row with no backticked fields is a wildcard. When the tree has no emit
sites and no stats op, the rule is silent — fixture trees without docs are
not drift.

Suppress with ``# telemetry-schema: <reason>``.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from ..core import Finding, Rule, SourceFile, register
from ..dataflow import StringFlow, literal_strings, walk_no_defs
from ..tracing import dotted_name

_EMIT_KINDS = {"emit", "span", "begin", "end"}
_OBS_DOC = "docs/observability.md"
_SERVE_DOC = "docs/serving.md"
_JOURNAL_MOD = "video_features_tpu/obs/journal.py"
_EXPORT_MOD = "video_features_tpu/obs/export.py"
_DAEMON_MOD = "video_features_tpu/serve/daemon.py"

_BACKTICK = re.compile(r"`([^`]+)`")

# options of the one span call (``Extractor._span`` → ``utils.metrics.span``):
# which clock stage the seconds go to and the payload bytes — not journal fields
_SPAN_OPTIONS = frozenset({"stage", "nbytes"})


def _receiver_is_journal(func: ast.AST) -> bool:
    if not isinstance(func, ast.Attribute):
        return False
    recv = (dotted_name(func.value) or "").lower()
    return "journal" in recv


class _Wrapper:
    __slots__ = ("rel", "name", "event_pos", "injected", "kind", "line")

    def __init__(self, rel: str, name: str, event_pos: int,
                 injected: FrozenSet[str], kind: str, line: int):
        self.rel = rel
        self.name = name
        self.event_pos = event_pos  # positional index at CALL sites
        self.injected = injected
        self.kind = kind            # emit | span | begin | end
        self.line = line


class _Site:
    __slots__ = ("rel", "line", "events", "kind", "fields", "src")

    def __init__(self, rel: str, line: int, events: FrozenSet[str],
                 kind: str, fields: FrozenSet[str], src: SourceFile):
        self.rel = rel
        self.line = line
        self.events = events
        self.kind = kind
        self.fields = fields  # literal kwargs ∪ wrapper-injected
        self.src = src

    def event_names(self) -> Iterable[str]:
        """The journal record names this site produces."""
        for ev in sorted(self.events):
            if self.kind == "emit":
                yield ev
            elif self.kind == "begin":
                yield f"{ev}_start"
            elif self.kind == "end":
                yield f"{ev}_end"
            else:  # span: both edges
                yield f"{ev}_start"
                yield f"{ev}_end"


def _parse_catalogue(text: str) -> Dict[str, Tuple[Optional[Set[str]], int]]:
    """event name -> (documented fields | None = wildcard, doc line)."""
    out: Dict[str, Tuple[Optional[Set[str]], int]] = {}
    in_section = False
    for lineno, line in enumerate(text.splitlines(), 1):
        if line.startswith("### Event catalogue"):
            in_section = True
            continue
        if in_section and (line.startswith("## ") or line.startswith("### ")):
            break
        if not in_section or not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) < 3:
            continue
        names = _BACKTICK.findall(cells[0])
        field_tokens = _BACKTICK.findall(cells[2])
        fields = set(field_tokens) if field_tokens else None
        for name in names:
            out[name] = (fields, lineno)
    return out


def _parse_stats_table(
        text: str) -> Tuple[Dict[str, int], Dict[str, Optional[Set[str]]]]:
    """(documented top-level key -> doc line,
    top-level key -> first-level sub keys | None = not enumerable)."""
    tops: Dict[str, int] = {}
    subs: Dict[str, Optional[Set[str]]] = {}
    in_section = False
    for lineno, line in enumerate(text.splitlines(), 1):
        if "`stats` payload" in line and line.startswith("#"):
            in_section = True
            continue
        if in_section and line.startswith("## "):
            break
        if not in_section or not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if not cells:
            continue
        for token in _BACKTICK.findall(cells[0]):
            top, sep, rest = token.partition(".")
            top = top.strip()
            if not top or " " in top:
                continue
            tops.setdefault(top, lineno)
            if not sep:
                continue
            first = rest.split(".", 1)[0].strip()
            if first.startswith("{") :
                inner = rest[rest.index("{") + 1:rest.rindex("}")]
                names = {s.strip() for s in inner.split(",") if s.strip()}
                cur = subs.get(top)
                subs[top] = (cur or set()) | names
            elif first.startswith("<"):
                subs[top] = None  # keyed by runtime name: not enumerable
            elif first:
                cur = subs.get(top)
                if top not in subs or cur is not None:
                    subs[top] = (cur or set()) | {first}
    return tops, subs


@register
class TelemetrySchemaRule(Rule):
    id = "telemetry-schema"
    title = "journal events/fields and stats schema match the docs"
    roots = ("video_features_tpu",)

    def prepare(self, root: str, sources, shared) -> None:
        self._root = root
        self._sources = {rel: src for rel, src in sources.items()
                         if rel.startswith("video_features_tpu/")
                         and getattr(src, "tree", None) is not None}
        self._discover_wrappers()

    # -- wrapper discovery ---------------------------------------------------

    def _classify(self, call: ast.Call, rel: str):
        """(kind, event_pos, injected) when ``call`` emits — a direct
        journal call or a call to a discovered wrapper — else None.
        Same-file wrappers win on a name collision (``_emit`` exists on
        both Extractor and ExtractionService); across files the injected
        sets intersect — under-approximating emitted fields can only
        under-check, never false-positive."""
        func = call.func
        if (isinstance(func, ast.Attribute) and func.attr in _EMIT_KINDS
                and _receiver_is_journal(func)):
            return func.attr, 0, frozenset()
        last = None
        if isinstance(func, ast.Attribute):
            last = func.attr
        elif isinstance(func, ast.Name):
            last = func.id
        infos = self._wrappers.get(last or "")
        if not infos:
            return None
        local = [i for i in infos if i.rel == rel]
        if local:
            infos = local
        injected: Optional[FrozenSet[str]] = None
        for info in infos:
            injected = (info.injected if injected is None
                        else injected & info.injected)
        return infos[0].kind, infos[0].event_pos, injected or frozenset()

    def _discover_wrappers(self) -> None:
        self._wrappers: Dict[str, List[_Wrapper]] = {}
        seen: Set[Tuple[str, int]] = set()
        changed = True
        while changed:
            changed = False
            for rel, src in sorted(self._sources.items()):
                if rel in (_JOURNAL_MOD, _EXPORT_MOD):
                    continue
                if not self._may_emit(src):
                    continue
                for fn in ast.walk(src.tree):
                    if not isinstance(fn, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
                        continue
                    params = [a.arg for a in fn.args.args]
                    if not params:
                        continue
                    self_offset = 1 if params[0] in ("self", "cls") else 0
                    for stmt in fn.body:
                        if isinstance(stmt, (ast.FunctionDef,
                                             ast.AsyncFunctionDef,
                                             ast.ClassDef)):
                            continue
                        for call in walk_no_defs(stmt):
                            if not isinstance(call, ast.Call):
                                continue
                            info = self._classify(call, rel)
                            if info is None:
                                continue
                            kind, pos, injected = info
                            if pos >= len(call.args):
                                continue
                            arg = call.args[pos]
                            if not (isinstance(arg, ast.Name)
                                    and arg.id in params):
                                continue
                            key = (rel, fn.lineno)
                            if key in seen:
                                continue
                            seen.add(key)
                            own = frozenset(
                                kw.arg for kw in call.keywords
                                if kw.arg is not None) - _SPAN_OPTIONS
                            self._wrappers.setdefault(fn.name, []).append(
                                _Wrapper(rel, fn.name,
                                         params.index(arg.id) - self_offset,
                                         own | injected, kind, fn.lineno))
                            changed = True

    def _wrapper_params(self) -> Set[Tuple[str, int]]:
        return {(w.rel, w.line) for ws in self._wrappers.values()
                for w in ws}

    def _may_emit(self, src: SourceFile) -> bool:
        """Cheap text pre-filter: a file with no 'journal' token and no
        known wrapper name cannot contain an emit site or define a new
        wrapper (text containment over-approximates the AST calls, so the
        fixpoint and the site sweep stay exact)."""
        text = src.text
        if "journal" in text:
            return True
        return any(name in text for name in self._wrappers)

    # -- site collection -----------------------------------------------------

    def _collect_sites(self) -> Tuple[List[_Site], List[Finding]]:
        sites: List[_Site] = []
        findings: List[Finding] = []
        wrapper_defs = self._wrapper_params()
        for rel, src in sorted(self._sources.items()):
            if rel == _EXPORT_MOD:
                continue
            if rel == _JOURNAL_MOD:
                self._collect_journal_literals(rel, src, sites)
                continue
            if not self._may_emit(src):
                continue
            defs = [n for n in ast.walk(src.tree)
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
            nested = {sub for fn in defs for sub in ast.walk(fn)
                      if sub is not fn and isinstance(
                          sub, (ast.FunctionDef, ast.AsyncFunctionDef))}
            for fn in defs:
                if fn in nested:
                    continue
                self._scan_fn(rel, src, fn, wrapper_defs, sites, findings)
        return sites, findings

    def _scan_fn(self, rel: str, src: SourceFile, fn, wrapper_defs,
                 sites: List[_Site], findings: List[Finding]) -> None:
        params = {a.arg for a in fn.args.args}
        is_wrapper_def = (rel, fn.lineno) in wrapper_defs

        def on_call(call: ast.Call, env) -> None:
            info = self._classify(call, rel)
            if info is None:
                return
            kind, pos, injected = info
            if pos >= len(call.args):
                return
            arg = call.args[pos]
            events = (frozenset({arg.value})
                      if isinstance(arg, ast.Constant)
                      and isinstance(arg.value, str)
                      else literal_strings(arg, env))
            if events is None:
                if (isinstance(arg, ast.Name) and arg.id in params
                        and is_wrapper_def):
                    return  # the wrapper's own forwarding call
                if self.suppressed(src, call.lineno, findings):
                    return
                findings.append(Finding(
                    rel, call.lineno, self.id,
                    "event name is not statically resolvable — emit a "
                    "literal (or declare a forwarding wrapper) so the "
                    f"{_OBS_DOC} catalogue stays checkable"))
                return
            fields = (frozenset(kw.arg for kw in call.keywords
                                if kw.arg is not None) - _SPAN_OPTIONS
                      | injected)
            sites.append(_Site(rel, call.lineno, events, kind, fields, src))

        StringFlow(on_call).scan_block(fn.body)

    def _collect_journal_literals(self, rel: str, src: SourceFile,
                                  sites: List[_Site]) -> None:
        """journal_open/journal_close are written as raw record dicts by the
        writer thread — the one place an event is born outside emit()."""
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Dict):
                continue
            keys = {}
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    keys[k.value] = v
            ev = keys.get("event")
            if not (isinstance(ev, ast.Constant)
                    and isinstance(ev.value, str)):
                continue
            fields = frozenset(k for k in keys if k not in ("ts", "event"))
            sites.append(_Site(rel, node.lineno, frozenset({ev.value}),
                               "emit", fields, src))

    # -- checks --------------------------------------------------------------

    def finalize(self, root: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        sites, findings_ = self._collect_sites()
        findings.extend(findings_)
        self._check_catalogue(root, sites, findings)
        self._check_stats(root, findings)
        return findings

    def _read_doc(self, root: str, rel: str) -> Optional[str]:
        path = os.path.join(root, rel.replace("/", os.sep))
        try:
            with open(path, encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None

    def _check_catalogue(self, root: str, sites: List[_Site],
                         findings: List[Finding]) -> None:
        export_names = self._export_names()
        if not sites and not export_names:
            return
        text = self._read_doc(root, _OBS_DOC)
        if text is None:
            findings.append(Finding(
                _OBS_DOC, 0, self.id,
                "journal emit sites exist but the event catalogue doc is "
                "missing"))
            return
        catalogue = _parse_catalogue(text)
        for site in sites:
            for name in site.event_names():
                entry = catalogue.get(name)
                if entry is None:
                    if self.suppressed(site.src, site.line, findings):
                        continue
                    findings.append(Finding(
                        site.rel, site.line, self.id,
                        f"event '{name}' is not in the {_OBS_DOC} event "
                        "catalogue — the journal is additive; document the "
                        "row (event + fields) before emitting it"))
                    continue
                doc_fields, _ = entry
                if doc_fields is None:
                    continue
                allowed = set(doc_fields) | {"span"}
                extra = sorted(site.fields - allowed)
                if extra:
                    if self.suppressed(site.src, site.line, findings):
                        continue
                    findings.append(Finding(
                        site.rel, site.line, self.id,
                        f"event '{name}' emits undocumented field(s) "
                        f"{', '.join(extra)} — update the {_OBS_DOC} "
                        "catalogue row (fields are additive but must be "
                        "listed)"))
        for name, line in sorted(export_names.items()):
            if name not in catalogue and not self._doc_mentions(text, name):
                findings.append(Finding(
                    _EXPORT_MOD, line, self.id,
                    f"exporter references '{name}' which the {_OBS_DOC} "
                    "catalogue/doc does not mention — pairing and derived "
                    "slice names are part of the documented contract"))

    @staticmethod
    def _doc_mentions(text: str, name: str) -> bool:
        return f"`{name}`" in text

    def _export_names(self) -> Dict[str, int]:
        """Event/slice names the exporter hard-codes: pairing literals in
        comparisons against the record name, ``slice_event`` literal first
        args, and ``_META_EVENTS``."""
        src = self._sources.get(_EXPORT_MOD)
        if src is None:
            return {}
        names: Dict[str, int] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Compare):
                left = node.left
                if not (isinstance(left, ast.Name) and left.id == "name"):
                    continue
                for comp in node.comparators:
                    elts = (comp.elts if isinstance(comp, (ast.Tuple,
                                                           ast.List, ast.Set))
                            else [comp])
                    for elt in elts:
                        if (isinstance(elt, ast.Constant)
                                and isinstance(elt.value, str)):
                            names.setdefault(elt.value, elt.lineno)
            elif isinstance(node, ast.Call):
                fname = dotted_name(node.func) or ""
                if (fname.rsplit(".", 1)[-1] == "slice_event" and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    names.setdefault(node.args[0].value, node.lineno)
            elif isinstance(node, ast.Assign):
                targets = [t.id for t in node.targets
                           if isinstance(t, ast.Name)]
                if "_META_EVENTS" in targets and isinstance(
                        node.value, (ast.Set, ast.Tuple, ast.List)):
                    for elt in node.value.elts:
                        if (isinstance(elt, ast.Constant)
                                and isinstance(elt.value, str)):
                            names.setdefault(elt.value, elt.lineno)
        return names

    # -- stats schema --------------------------------------------------------

    def _check_stats(self, root: str, findings: List[Finding]) -> None:
        src = self._sources.get(_DAEMON_MOD)
        if src is None:
            return
        stats_fn = None
        for node in ast.walk(src.tree):
            if (isinstance(node, ast.FunctionDef) and node.name == "stats"):
                stats_fn = node
                break
        if stats_fn is None:
            return
        payload = None
        for node in walk_no_defs(ast.Module(body=stats_fn.body,
                                            type_ignores=[])):
            if isinstance(node, ast.Dict):
                keys = [k.value for k in node.keys
                        if isinstance(k, ast.Constant)]
                if "schema" in keys:
                    payload = node
                    break
        if payload is None:
            return
        emitted: Dict[str, ast.AST] = {}
        for k, v in zip(payload.keys, payload.values):
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                emitted[k.value] = v
        text = self._read_doc(root, _SERVE_DOC)
        if text is None:
            findings.append(Finding(
                _SERVE_DOC, 0, self.id,
                "the stats op exists but its schema doc is missing"))
            return
        doc_tops, doc_subs = _parse_stats_table(text)
        if not doc_tops:
            findings.append(Finding(
                _SERVE_DOC, 0, self.id,
                "no `stats` payload schema table found — the versioned "
                "payload needs its field-tree contract documented"))
            return
        for key, value in sorted(emitted.items()):
            if key not in doc_tops:
                if self.suppressed(src, value.lineno, findings):
                    continue
                findings.append(Finding(
                    _DAEMON_MOD, value.lineno, self.id,
                    f"stats op emits undocumented top-level field '{key}' "
                    f"— the schema-1 table in {_SERVE_DOC} is the scraper "
                    "contract"))
        for key, line in sorted(doc_tops.items()):
            if key not in emitted:
                findings.append(Finding(
                    _SERVE_DOC, line, self.id,
                    f"schema table documents '{key}' but the stats op no "
                    "longer emits it — prune or restore (a silent removal "
                    "is a schema break)"))
        for key, value in sorted(emitted.items()):
            sub_emitted = self._enumerate_subkeys(src, value)
            sub_doc = doc_subs.get(key)
            if sub_emitted is None or sub_doc is None:
                continue
            for sub in sorted(sub_emitted - sub_doc):
                if self.suppressed(src, value.lineno, findings):
                    continue
                findings.append(Finding(
                    _DAEMON_MOD, value.lineno, self.id,
                    f"stats field '{key}.{sub}' is not in the "
                    f"{_SERVE_DOC} schema table"))
            for sub in sorted(sub_doc - sub_emitted):
                findings.append(Finding(
                    _SERVE_DOC, doc_tops[key], self.id,
                    f"schema table documents '{key}.{sub}' but the stats "
                    "op does not emit it"))

    def _enumerate_subkeys(self, src: SourceFile,
                           value: ast.AST) -> Optional[Set[str]]:
        """First-level sub keys when statically enumerable: an inline dict
        literal, or a one-hop ``self._method()`` whose single return is a
        dict literal."""
        if isinstance(value, ast.Dict):
            if any(k is None or not isinstance(k, ast.Constant)
                   for k in value.keys):
                return None
            return {k.value for k in value.keys
                    if isinstance(k.value, str)}
        if (isinstance(value, ast.Call) and not value.args
                and not value.keywords
                and isinstance(value.func, ast.Attribute)):
            mname = value.func.attr
            for node in ast.walk(src.tree):
                if (isinstance(node, ast.FunctionDef)
                        and node.name == mname):
                    rets = [r for r in ast.walk(node)
                            if isinstance(r, ast.Return)
                            and r.value is not None]
                    if len(rets) == 1 and isinstance(rets[0].value,
                                                     ast.Dict):
                        return self._enumerate_subkeys(src, rets[0].value)
                    return None
        return None
