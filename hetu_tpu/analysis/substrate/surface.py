"""Surface-consistency lint: what the code *does* vs what the docs *say*
(docs/ANALYSIS.md "Tier D: substrate").

Three promise surfaces, each diffed in both directions:

- **Knobs** — every quoted ``HETU_*`` / ``DMLC_*`` environment variable the
  Python layer or the C++ substrate reads must appear in the docs
  (``knob-undocumented``, warn), and every knob the docs promise must still
  be read somewhere (``knob-dead``, note: the doc row outlived the code).
- **Gauges** — every ``hetu_*`` metric name the telemetry layer emits must
  have a row in docs/OBSERVABILITY.md (``gauge-undocumented``, warn);
  documented names nothing emits or reads are stale (``gauge-stale-doc``,
  note); names a consumer (hetutop / hetuwatch / plan watch) reads but no
  producer ever emits are broken panels (``gauge-consumer-drift``, warn).
- **Fault kinds** — the :mod:`hetu_tpu.faults` registry, the
  docs/FAULT_TOLERANCE.md catalogue, the three parsers that consume the
  registry, and the C++ chaos grammar in csrc/ps/chaos.h must all agree
  (``fault-kind-undocumented`` / ``fault-kind-unknown-doc`` /
  ``fault-parser-drift`` / ``chaos-grammar-drift``, all errors: a fault
  kind that exists in one layer only is a silent no-op in the layer that
  was supposed to exercise it).
- **Plan-delta kinds** — the ``watch.DELTA_KINDS`` registry (the bounded
  deltas hetuwatch recommends and hetupilot actuates) must be catalogued
  in docs/FAULT_TOLERANCE.md (``delta-kind-undocumented``, error) and the
  pilot must consume the registry symbol rather than a private kind list
  (``delta-parser-drift``, error) — the same discipline as fault kinds: a
  kind the recommender emits but the actuator or docs never heard of is a
  recommendation that silently goes nowhere.
- **Ledger record kinds** — every ``kind`` a JSONL producer emits (Python
  dict literals, hot-path raw-JSON fragments, C++ escaped rows,
  ``tel.record(...)`` call sites) must be registered in
  ``story.LEDGER_KINDS`` and catalogued in the docs/OBSERVABILITY.md
  ledger table, and vice versa (``ledger-kind-drift``, error both
  directions; a registered-but-never-emitted kind is a warn) — a row
  hetustory cannot classify is invisible to every timeline, audit, and
  incident report built on the unified ledger.

Pure text analysis over the working tree; ``overlay`` maps repo-relative
paths to replacement text so the seeded-defect tests and ``--check`` can
analyze counterfactual trees without touching disk.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ... import faults
from ..findings import ERROR, NOTE, WARN, Finding

PASS = "surface"

# Doc set that constitutes "the promise surface". ROADMAP/ISSUE/CHANGES are
# planning artifacts, not promises, and would drown the diff in noise.
_DOC_FILES = (
    "README.md", "docs/API.md", "docs/ANALYSIS.md", "docs/COMM_QUANT.md",
    "docs/FAULT_TOLERANCE.md", "docs/KERNELS.md", "docs/MIGRATING.md",
    "docs/OBSERVABILITY.md", "docs/PROFILING.md", "docs/ROOFLINE.md",
)

# a doc knob token ending in `_` came from a wildcard row (`HETU_X_*`):
# it documents the whole prefix family
_RE_KNOB = re.compile(r"\b((?:HETU|DMLC)_[A-Z][A-Z0-9_]*_?)")
_RE_KNOB_QUOTED = re.compile(r"\"((?:HETU|DMLC)_[A-Z][A-Z0-9_]*)\"")
# metric names at emission sites only: registry method calls, or the
# conventional one-letter local binding of registry.gauge (`g("hetu_x")`).
# An f-string placeholder marks a dynamic prefix family (hetu_hbm_{k}).
_RE_GAUGE_EMIT = re.compile(
    r"\b(?:gauge|counter|histogram|g)\(\s*f?\"(hetu_[a-z0-9_]*)(\{)?")
# consumers read names anywhere (registry-dump lookups, startswith probes)
_RE_GAUGE_ANY = re.compile(r"[\"'](hetu_[a-z0-9_]*)")
_RE_DOC_GAUGE = re.compile(r"`(hetu_[a-z0-9_]+)(\{|\*)?")
_RE_DOC_FAULT = re.compile(r"`([a-z_]+)@S")

# hetu_* strings that are not metric names (paths, module prefixes)
_GAUGE_DENY = ("hetu_tpu", "hetu_telemetry", "hetu_ckpt", "hetu_elastic",
               "hetu_job_snap")

# names the registry dump derives from a histogram (hetutop reads
# hetu_ps_pull_ms_p50 off the emitted hetu_ps_pull_ms)
_HIST_SUFFIXES = ("_p50", "_p90", "_p99", "_count", "_sum", "_mean")

# gauge consumers: files that only *read* metric names from the registry
# dump (watch.py/hetuwatch both read AND emit, so they stay producers)
_CONSUMER_FILES = ("hetu_tpu/telemetry/hetutop.py",)

# the three parsers that must consume the faults registry, and the
# symbol(s) each one has no business reimplementing (any one suffices)
_FAULT_PARSERS = (
    ("hetu_tpu/resilience.py", ("parse_step_entry", "STEP_FAULT")),
    ("hetu_tpu/chaos.py", ("CHAOS_SPEC_KEYS", "CHAOS_PROB_KEYS",
                           "chaos_catalogue")),
    ("hetu_tpu/recovery.py", ("JOB_KILL_PHASES",)),
)

_CHAOS_HDR = "hetu_tpu/csrc/ps/chaos.h"

# the PlanDelta registry (producer) and its actuating consumer. Parsed as
# TEXT, not imported: watch.py is stdlib-only but this tier must analyze
# counterfactual overlay trees, and a registry literal is a surface too.
_DELTA_REGISTRY = "hetu_tpu/telemetry/watch.py"
_DELTA_CONSUMER = "hetu_tpu/pilot.py"
_RE_DELTA_KIND = re.compile(r"^\s*\"([a-z_]+)\":\s*\{\"arg\":", re.M)

# the hetustory ledger-kind registry (story.LEDGER_KINDS) — the contract
# every JSONL producer and the docs/OBSERVABILITY.md ledger catalogue must
# agree with. The registry file (and its jax-free bin loader) is excluded
# from the emission scan: it quotes every kind as data, plus fixtures.
_LEDGER_REGISTRY = "hetu_tpu/telemetry/story.py"
_LEDGER_SCAN_EXCLUDE = (_LEDGER_REGISTRY, "bin/hetustory")
# emission sites: Python dict literals ({"kind": "step"}), the hot-path
# raw-JSON fragments ('"kind":"step"'), C++ escaped JSON (\"kind\":\"srv\"),
# and the tel.record("<kind>", ...) free-form API
_RE_KIND_EMITS = (
    re.compile(r"\"kind\"\s*:\s*\"([a-z_0-9]+)\""),
    re.compile(r"\"kind\":\"([a-z_0-9]+)\""),
    re.compile(r"\\\"kind\\\":\\\"([a-z_0-9]+)"),
    re.compile(r"\.record\(\s*\"([a-z_0-9]+)\""),
)


def _read(root: str, rel: str, overlay: Optional[Dict[str, str]]) -> str:
    if overlay and rel in overlay:
        return overlay[rel]
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        return ""
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        return f.read()


def _code_files(root: str) -> List[str]:
    """Repo-relative paths of everything that can read a knob or emit a
    gauge: the Python package, the bin/ entry points, the C++ substrate."""
    out: List[str] = []
    for base, exts in (("hetu_tpu", (".py", ".h", ".cc", ".c")),
                       ("bin", None), ("tools", (".py",))):
        top = os.path.join(root, base)
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            # the analysis tier quotes knob/gauge names as *data*; scanning
            # it would make every lint string look like a live read
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "substrate")]
            for fn in sorted(filenames):
                if exts is not None and not fn.endswith(exts):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                out.append(rel.replace(os.sep, "/"))
    # top-level entry points (chip_smoke.py, conftest.py) read knobs too
    for fn in sorted(os.listdir(root)):
        if fn.endswith(".py") and os.path.isfile(os.path.join(root, fn)):
            out.append(fn)
    return out


def _doc_text(root: str, overlay: Optional[Dict[str, str]]) -> str:
    return "\n".join(_read(root, rel, overlay) for rel in _DOC_FILES)


# --------------------------------------------------------------------------
# knobs

def _check_knobs(root: str, files: List[str], doc: str,
                 overlay: Optional[Dict[str, str]]) -> List[Finding]:
    findings: List[Finding] = []
    raw = set(_RE_KNOB.findall(doc))
    doc_prefixes = {k for k in raw if k.endswith("_")}
    doc_knobs = {k for k in raw if not k.endswith("_")}

    code_knobs: Dict[str, str] = {}     # knob -> first file that reads it
    all_code = set()
    for rel in files:
        text = _read(root, rel, overlay)
        for m in _RE_KNOB_QUOTED.finditer(text):
            code_knobs.setdefault(m.group(1), rel)
        all_code.update(k.rstrip("_") for k in _RE_KNOB.findall(text))

    for knob in sorted(set(code_knobs) - doc_knobs):
        if any(knob.startswith(p) for p in doc_prefixes):
            continue                    # covered by a wildcard doc row
        findings.append(Finding(
            lint="knob-undocumented", severity=WARN,
            message=(f"{knob} is read by {code_knobs[knob]} but appears in "
                     "no doc — an operator cannot discover it; add it to "
                     "the owning knob table"),
            op_name=knob, pass_name=PASS))

    # dead the other way: the doc promises a knob nothing reads (quoted OR
    # bare — generated names like HETU_FAULT_SPEC built from f-strings
    # still show up bare somewhere in code). A wildcard row is dead only
    # if NO code knob carries its prefix.
    for knob in sorted(doc_knobs - all_code):
        findings.append(Finding(
            lint="knob-dead", severity=NOTE,
            message=(f"{knob} is documented but no code under hetu_tpu/, "
                     "bin/ or csrc/ references it — stale doc row or a "
                     "renamed knob"),
            op_name=knob, pass_name=PASS))
    for prefix in sorted(doc_prefixes):
        if not any(k.startswith(prefix) for k in all_code):
            findings.append(Finding(
                lint="knob-dead", severity=NOTE,
                message=(f"wildcard doc row {prefix}* matches no knob any "
                         "code reads — stale family"),
                op_name=prefix + "*", pass_name=PASS))
    return findings


# --------------------------------------------------------------------------
# gauges

def _deny(name: str) -> bool:
    return any(name == d or name.startswith(d + "_") or d.startswith(name)
               for d in _GAUGE_DENY)


def _emitted_names(text: str) -> Tuple[Set[str], Set[str]]:
    """(exact names, dynamic prefixes) at gauge/counter/histogram sites."""
    names: Set[str] = set()
    prefixes: Set[str] = set()
    for m in _RE_GAUGE_EMIT.finditer(text):
        name, dynamic = m.group(1), m.group(2)
        if dynamic or name.endswith("_"):
            prefixes.add(name.rstrip("_") + "_")
        elif not _deny(name) and name != "hetu":
            names.add(name)
    return names, prefixes


def _covered(name: str, names: Set[str], prefixes: Set[str]) -> bool:
    if name in names or any(name.startswith(p) or p.startswith(name + "_")
                            for p in prefixes):
        return True
    for suf in _HIST_SUFFIXES:          # registry-derived histogram stats
        if name.endswith(suf) and name[:-len(suf)] in names:
            return True
    return False


def _check_gauges(root: str, files: List[str], overlay) -> List[Finding]:
    findings: List[Finding] = []
    doc = _read(root, "docs/OBSERVABILITY.md", overlay) + _read(
        root, "docs/FAULT_TOLERANCE.md", overlay)
    doc_names: Set[str] = set()
    doc_prefixes: Set[str] = set()
    for m in _RE_DOC_GAUGE.finditer(doc):
        name, wild = m.group(1), m.group(2)
        if _deny(name):
            continue
        if wild == "*" or name.endswith("_"):
            doc_prefixes.add(name.rstrip("_") + "_")
        else:
            doc_names.add(name)

    code_names: Dict[str, str] = {}     # emitted name -> first file
    code_prefixes: Set[str] = set()
    consumer_names: Dict[str, str] = {}
    for rel in files:
        if not rel.endswith(".py") and not rel.startswith("bin/"):
            continue                    # csrc emits no Python gauges
        text = _read(root, rel, overlay)
        if rel in _CONSUMER_FILES:
            for m in _RE_GAUGE_ANY.finditer(text):
                n = m.group(1)
                if not _deny(n) and n != "hetu":
                    consumer_names.setdefault(n.rstrip("_"), rel)
            continue
        names, prefixes = _emitted_names(text)
        for n in names:
            code_names.setdefault(n, rel)
        code_prefixes.update(prefixes)

    for name in sorted(code_names):
        if not _covered(name, doc_names, doc_prefixes):
            findings.append(Finding(
                lint="gauge-undocumented", severity=WARN,
                message=(f"metric {name} is emitted by {code_names[name]} "
                         "but has no row in docs/OBSERVABILITY.md — "
                         "dashboards cannot be built from the doc"),
                op_name=name, pass_name=PASS))

    emitted = set(code_names)
    for name in sorted(doc_names):
        if not _covered(name, emitted, code_prefixes) \
                and name not in consumer_names:
            findings.append(Finding(
                lint="gauge-stale-doc", severity=NOTE,
                message=(f"docs promise metric {name} but nothing under "
                         "hetu_tpu/ or bin/ emits or reads it — stale row "
                         "or renamed metric"),
                op_name=name, pass_name=PASS))

    for name in sorted(consumer_names):
        if _covered(name, emitted, code_prefixes):
            continue
        findings.append(Finding(
            lint="gauge-consumer-drift", severity=WARN,
            message=(f"{consumer_names[name]} reads metric {name} but no "
                     "producer emits it — the panel renders blank forever"),
            op_name=name, pass_name=PASS))
    return findings


# --------------------------------------------------------------------------
# fault kinds

def _check_faults(root: str, overlay) -> List[Finding]:
    findings: List[Finding] = []
    doc = _read(root, "docs/FAULT_TOLERANCE.md", overlay)
    doc_kinds = set(_RE_DOC_FAULT.findall(doc))

    for kind in faults.STEP_FAULT_NAMES:
        if kind not in doc_kinds:
            findings.append(Finding(
                lint="fault-kind-undocumented", severity=ERROR,
                message=(f"fault kind {kind} is in the faults registry but "
                         "the docs/FAULT_TOLERANCE.md catalogue has no "
                         f"`{kind}@S` row — undiscoverable, so untested "
                         "by operators"),
                op_name=kind, pass_name=PASS))
    for kind in sorted(doc_kinds - set(faults.STEP_FAULT_NAMES)):
        findings.append(Finding(
            lint="fault-kind-unknown-doc", severity=ERROR,
            message=(f"docs/FAULT_TOLERANCE.md catalogues fault kind "
                     f"{kind} but the faults registry does not know it — "
                     "the documented spec is rejected at parse time"),
            op_name=kind, pass_name=PASS))

    for phase in faults.JOB_KILL_PHASES:
        if phase not in doc:
            findings.append(Finding(
                lint="fault-kind-undocumented", severity=ERROR,
                message=(f"job_kill phase {phase} is in the registry but "
                         "not in the docs/FAULT_TOLERANCE.md job_kill row"),
                op_name=phase, pass_name=PASS))

    # the three parsers must consume the registry, not a private copy
    for rel, symbols in _FAULT_PARSERS:
        text = _read(root, rel, overlay)
        if text and not any(s in text for s in symbols):
            findings.append(Finding(
                lint="fault-parser-drift", severity=ERROR,
                message=(f"{rel} no longer references faults."
                         f"{'/'.join(symbols)} — a parser with a private "
                         "catalogue is exactly the three-copies drift the "
                         "registry was built to end"),
                op_name=rel, pass_name=PASS))

    # the C++ chaos grammar must accept every registry spec key
    chaos_h = _read(root, _CHAOS_HDR, overlay)
    if chaos_h:
        for key in faults.CHAOS_SPEC_KEYS:
            if f'"{key}"' not in chaos_h:
                findings.append(Finding(
                    lint="chaos-grammar-drift", severity=ERROR,
                    message=(f"chaos spec key {key!r} is in the registry "
                             f"(and the Python parser) but {_CHAOS_HDR} "
                             "never matches it — HETU_CHAOS_SPEC parses "
                             "differently per language"),
                    op_name=key, pass_name=PASS))
        for key in faults.CHAOS_SPEC_KEYS:
            if key not in doc:
                findings.append(Finding(
                    lint="fault-kind-undocumented", severity=ERROR,
                    message=(f"chaos spec key {key!r} has no row in the "
                             "docs/FAULT_TOLERANCE.md chaos table"),
                    op_name=key, pass_name=PASS))
    return findings


# --------------------------------------------------------------------------
# plan-delta kinds

def _delta_kinds(text: str) -> List[str]:
    """Registry keys from the ``DELTA_KINDS = {...}`` literal (text parse:
    overlay trees must be analyzable without importing them)."""
    m = re.search(r"^DELTA_KINDS\s*=\s*\{", text, re.M)
    if not m:
        return []
    block = text[m.end():]
    end = block.find("\n}")
    if end >= 0:
        block = block[:end]
    return _RE_DELTA_KIND.findall(block)


def _check_deltas(root: str, overlay) -> List[Finding]:
    findings: List[Finding] = []
    reg_text = _read(root, _DELTA_REGISTRY, overlay)
    if not reg_text:
        return findings
    kinds = _delta_kinds(reg_text)
    if not kinds:
        findings.append(Finding(
            lint="delta-parser-drift", severity=ERROR,
            message=(f"{_DELTA_REGISTRY} has no parseable DELTA_KINDS "
                     "registry literal — the plan-delta surface lint lost "
                     "its source of truth"),
            op_name=_DELTA_REGISTRY, pass_name=PASS))
        return findings

    doc = _read(root, "docs/FAULT_TOLERANCE.md", overlay)
    doc_kinds = set(re.findall(r"`([a-z_]+)`", doc))
    for kind in kinds:
        if kind not in doc_kinds:
            findings.append(Finding(
                lint="delta-kind-undocumented", severity=ERROR,
                message=(f"plan-delta kind {kind} is in watch.DELTA_KINDS "
                         "but the docs/FAULT_TOLERANCE.md delta catalogue "
                         f"has no `{kind}` row — an operator cannot know "
                         "what the pilot is allowed to change"),
                op_name=kind, pass_name=PASS))

    pilot = _read(root, _DELTA_CONSUMER, overlay)
    if pilot and "DELTA_KINDS" not in pilot:
        findings.append(Finding(
            lint="delta-parser-drift", severity=ERROR,
            message=(f"{_DELTA_CONSUMER} no longer references "
                     "watch.DELTA_KINDS — an actuator with a private kind "
                     "catalogue is exactly the recommender/actuator drift "
                     "the registry was built to end"),
            op_name=_DELTA_CONSUMER, pass_name=PASS))
    return findings


# --------------------------------------------------------------------------
# ledger record kinds (hetustory)

def _ledger_kinds(text: str) -> Dict[str, Set[str]]:
    """Family -> kinds from the ``LEDGER_KINDS = {...}`` literal (text
    parse, same discipline as :func:`_delta_kinds`)."""
    m = re.search(r"^LEDGER_KINDS\s*=\s*\{", text, re.M)
    if not m:
        return {}
    block = text[m.end():]
    end = block.find("\n}")
    if end >= 0:
        block = block[:end]
    out: Dict[str, Set[str]] = {}
    for fam, inner in re.findall(r"\"([a-z_]+)\":\s*\(([^)]*)\)", block,
                                 re.S):
        out[fam] = set(re.findall(r"\"([a-z_0-9]+)\"", inner))
    return out


def _doc_ledger_rows(doc: str) -> Dict[str, Set[str]]:
    """Family -> kinds from the docs/OBSERVABILITY.md ledger catalogue
    table (the section under the "Ledger catalogue" heading)."""
    m = re.search(r"^#+.*Ledger catalogue.*$", doc, re.M)
    if not m:
        return {}
    section = doc[m.end():]
    nxt = re.search(r"^#+ ", section, re.M)
    if nxt:
        section = section[:nxt.start()]
    out: Dict[str, Set[str]] = {}
    for line in section.splitlines():
        mm = re.match(r"^\|\s*`([a-z_]+)`\s*\|", line)
        if not mm:
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        # first cell = family; record kinds are the backticked lowercase
        # tokens of the THIRD cell (family | files | kinds | ...)
        kinds = set(re.findall(r"`([a-z_0-9]+)`", cells[2])) \
            if len(cells) >= 3 else set()
        kinds.discard("none")
        out[mm.group(1)] = kinds
    return out


def _check_ledgers(root: str, files: List[str], overlay) -> List[Finding]:
    findings: List[Finding] = []
    reg_text = _read(root, _LEDGER_REGISTRY, overlay)
    if not reg_text:
        return findings
    registry = _ledger_kinds(reg_text)
    if not registry:
        findings.append(Finding(
            lint="ledger-kind-drift", severity=ERROR,
            message=(f"{_LEDGER_REGISTRY} has no parseable LEDGER_KINDS "
                     "registry literal — the run-ledger surface lint lost "
                     "its source of truth"),
            op_name=_LEDGER_REGISTRY, pass_name=PASS))
        return findings
    known: Set[str] = set()
    for kinds in registry.values():
        known |= kinds

    # code -> registry: every emitted kind must be one hetustory's
    # timeline/audit can classify; a kind the registry never heard of is
    # invisible to every post-mortem built on the ledger
    emitted: Dict[str, Set[str]] = {}
    for rel in files:
        if rel in _LEDGER_SCAN_EXCLUDE:
            continue
        text = _read(root, rel, overlay)
        for rx in _RE_KIND_EMITS:
            for kind in rx.findall(text):
                emitted.setdefault(kind, set()).add(rel)
    for kind in sorted(set(emitted) - known):
        findings.append(Finding(
            lint="ledger-kind-drift", severity=ERROR,
            message=(f"record kind {kind!r} is emitted by "
                     f"{sorted(emitted[kind])[0]} but story.LEDGER_KINDS "
                     "has no entry for it — hetustory's timeline and "
                     "audit cannot classify the row"),
            op_name=kind, pass_name=PASS))
    # registry -> code: a registered kind nothing emits is a stale row
    for kind in sorted(known - set(emitted)):
        findings.append(Finding(
            lint="ledger-kind-drift", severity=WARN,
            message=(f"record kind {kind!r} is in story.LEDGER_KINDS but "
                     "no code path emits it — stale registry entry"),
            op_name=kind, pass_name=PASS))

    # registry <-> docs: the OBSERVABILITY.md ledger catalogue must list
    # every family with exactly the registry's kinds, both directions
    doc = _read(root, "docs/OBSERVABILITY.md", overlay)
    doc_rows = _doc_ledger_rows(doc)
    if not doc_rows:
        findings.append(Finding(
            lint="ledger-kind-drift", severity=ERROR,
            message=("docs/OBSERVABILITY.md has no parseable ledger "
                     "catalogue table (\"Ledger catalogue\" heading) — "
                     "the ledger contract is undocumented"),
            op_name="docs/OBSERVABILITY.md", pass_name=PASS))
        return findings
    for fam in sorted(set(registry) - set(doc_rows)):
        findings.append(Finding(
            lint="ledger-kind-drift", severity=ERROR,
            message=(f"ledger family {fam!r} is in story.LEDGER_KINDS but "
                     "the docs/OBSERVABILITY.md ledger catalogue has no "
                     f"`{fam}` row"),
            op_name=fam, pass_name=PASS))
    for fam in sorted(set(doc_rows) - set(registry)):
        findings.append(Finding(
            lint="ledger-kind-drift", severity=ERROR,
            message=(f"the docs/OBSERVABILITY.md ledger catalogue lists "
                     f"family {fam!r} that story.LEDGER_KINDS does not "
                     "register — doc row outlived the code"),
            op_name=fam, pass_name=PASS))
    for fam in sorted(set(registry) & set(doc_rows)):
        for kind in sorted(registry[fam] - doc_rows[fam]):
            findings.append(Finding(
                lint="ledger-kind-drift", severity=ERROR,
                message=(f"record kind {kind!r} of family {fam!r} is "
                         "registered but missing from its "
                         "docs/OBSERVABILITY.md catalogue row"),
                op_name=f"{fam}.{kind}", pass_name=PASS))
        for kind in sorted(doc_rows[fam] - registry[fam]):
            findings.append(Finding(
                lint="ledger-kind-drift", severity=ERROR,
                message=(f"the docs/OBSERVABILITY.md catalogue row for "
                         f"{fam!r} lists kind {kind!r} that "
                         "story.LEDGER_KINDS does not register"),
                op_name=f"{fam}.{kind}", pass_name=PASS))
    return findings


# --------------------------------------------------------------------------

def analyze_surface(root: str = ".",
                    overlay: Optional[Dict[str, str]] = None
                    ) -> List[Finding]:
    files = _code_files(root)
    doc = _doc_text(root, overlay)
    findings: List[Finding] = []
    findings += _check_knobs(root, files, doc, overlay)
    findings += _check_gauges(root, files, overlay)
    findings += _check_faults(root, overlay)
    findings += _check_deltas(root, overlay)
    findings += _check_ledgers(root, files, overlay)
    return findings
