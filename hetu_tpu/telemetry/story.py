"""``hetustory`` — the unified run ledger: one registry over every artifact
family a run writes, a causal cross-subsystem timeline, an offline invariant
audit, incident reports, and cross-run diff (docs/OBSERVABILITY.md pillar 7,
docs/FAULT_TOLERANCE.md post-mortem workflow).

After PRs 5/6/7/13/15/16/17/19 a run leaves ~10 disjoint artifact formats on
disk (metrics/scope/watch JSONL, trail client+server spans, flight rings,
``pilot.jsonl``, snapshot manifests, supervisor JSONL, ``run_summary.json``).
This module is the one place that knows all of them:

- :data:`LEDGERS` — one descriptor per family: path globs (including the
  rotated ``.1`` backup every bounded writer keeps), format (JSONL vs
  atomic-rename JSON document), torn-tail policy, and the causal keys
  ``(world_version, era/epoch, step, rank)`` its rows carry.
- :func:`read_rows` / :class:`LedgerFollower` — the shared rotation- and
  torn-tail-tolerant readers that hetutop, hetutrail, hetupilot, and heturun's
  five ad-hoc loaders are built on. A torn final line is a *classification*
  (the crash left it there on purpose), not a crash of the reader.
- :func:`load_timeline` — every source merged into one ordered "who did what
  to whom" stream, cross-process-ordered via the PR 13 trail anchors when all
  ranks share one ``boot_id`` (the same condition ``hetutrace`` uses).
- :func:`audit` — recompute, from the ledgers alone, the algebra the runtime
  asserts live (push accounting, pilot-era consistency, manifest
  completeness, flight/event agreement, era sequencing); exit 0/1.
- :func:`write_incident` — called from every resilience abort path: one
  ``incident-*.json`` collecting the ±K-step window from every registered
  source, so the post-mortem starts from a single file.
- :func:`diff_runs` — two runs aligned by step/era: the gate's
  direction-aware metric comparison plus plan and episode deltas.

Stdlib-only and jax-free at module level (the hetutop/hetutrail contract):
``bin/hetustory`` loads this file by path on a login node or in CI. This
module is a *leaf* — trail/hetutop/pilot import it, never the reverse; the
profiler (for --diff) is resolved lazily through :func:`_profiler_mod` so the
standalone load needs no package.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time
from typing import Iterable, Iterator, Optional

Row = collections.namedtuple("Row", ("path", "line", "rec"))

# ---------------------------------------------------------------------------
# shared JSONL reader: torn-tail classification + rotation
# ---------------------------------------------------------------------------


def iter_rows(path: str, errors: Optional[list] = None) -> Iterator[Row]:
    """Yield :class:`Row` per valid object line of one JSONL file.

    Malformed input is *classified* into ``errors`` (dicts with ``path``,
    ``line``, ``reason``, ``error``) instead of raised: an undecodable LAST
    line is ``torn-tail`` (the expected signature of a crashed or live
    writer — JsonlSink/TrailWriter append whole lines, so only the tail can
    tear); undecodable earlier lines are ``invalid-json``; a decodable
    non-object is ``not-object``. Callers that tolerate torn tails pass
    ``errors=None``; strict callers (hetutop --check) format every entry."""
    pending = None   # a bad line is only mid-file corruption once another
    try:             # line follows it; at EOF it is the torn tail
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if pending is not None:
                    pending["reason"] = "invalid-json"
                    if errors is not None:
                        errors.append(pending)
                    pending = None
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    pending = {"path": path, "line": i,
                               "reason": "torn-tail",
                               "error": f"invalid JSON ({e})"}
                    continue
                if not isinstance(rec, dict):
                    if errors is not None:
                        errors.append({"path": path, "line": i,
                                       "reason": "not-object",
                                       "error": "record is not an object"})
                    continue
                yield Row(path, i, rec)
    except OSError:
        return
    if pending is not None and errors is not None:
        errors.append(pending)


def read_rows(path: str, errors: Optional[list] = None) -> list:
    return list(iter_rows(path, errors))


def read_jsonl(path: str, errors: Optional[list] = None) -> list:
    """Records only (the drop-in shape trail/pilot's old readers returned)."""
    return [r.rec for r in iter_rows(path, errors)]


def format_error(err: dict) -> str:
    """One classified reader error in hetutop's historical string format."""
    return f"{err['path']}:{err['line']}: {err['error']}"


def rotated_paths(path: str) -> list:
    """Backup-first read order for one bounded JSONL file: the single ``.1``
    generation (JsonlSink/TrailWriter convention), then the live file."""
    return [p for p in (path + ".1", path) if os.path.exists(p)]


def read_rows_rotated(path: str, errors: Optional[list] = None) -> list:
    out = []
    for p in rotated_paths(path):
        out.extend(iter_rows(p, errors))
    return out


def read_jsonl_rotated(path: str, errors: Optional[list] = None) -> list:
    return [r.rec for r in read_rows_rotated(path, errors)]


class LedgerFollower:
    """Shared incremental tailer: byte offset + inode per file, rotation-
    aware. Each :meth:`poll` returns only records appended since the last
    one, so a dashboard frame or monitor tick stays O(new data).

    Closes the PR 13 gap this file exists to fix: the old per-consumer
    tailers detected rotation by inode change and restarted at offset 0,
    silently dropping every record written between their last poll and the
    rename. Here the old generation now sits at ``path + ".1"`` — when its
    inode matches the one we were reading, its tail past our stored offset
    is drained first, then the fresh file is read from 0. ``backlog=True``
    additionally replays an existing ``.1`` backup the first time a path is
    seen (consumers that want history, e.g. the hetutop dashboard warm-up).
    """

    def __init__(self, backlog: bool = False):
        self.backlog = backlog
        self._offsets: dict = {}   # path -> (byte offset, inode)

    def poll(self, path: str) -> list:
        recs: list = []
        try:
            st = os.stat(path)
        except OSError:
            return recs
        off, ino = self._offsets.get(path, (None, None))
        if off is None:
            off = 0
            if self.backlog:
                recs.extend(read_jsonl(path + ".1"))
        elif ino is not None and st.st_ino != ino:
            recs.extend(self._drain_backup(path + ".1", off, ino))
            off = 0
        elif st.st_size < off:
            off = 0   # truncated in place: restart
        if st.st_size > off:
            new, off = self._read_from(path, off)
            recs.extend(new)
        self._offsets[path] = (off, st.st_ino)
        return recs

    def _drain_backup(self, backup: str, off: int, ino: int) -> list:
        # only when the backup IS the generation we were reading (inode
        # match): after a double rotation between polls the middle
        # generation is gone — a stale offset into an unrelated file must
        # not fabricate half-records
        try:
            st = os.stat(backup)
        except OSError:
            return []
        if st.st_ino != ino or st.st_size < off:
            return []
        recs, _ = self._read_from(backup, off)
        return recs

    @staticmethod
    def _read_from(path: str, off: int):
        with open(path, "rb") as f:
            f.seek(off)
            chunk = f.read()
        last_nl = chunk.rfind(b"\n")
        if last_nl < 0:
            return [], off        # partial tail line: retry next poll
        recs = []
        for raw in chunk[:last_nl].split(b"\n"):
            raw = raw.strip()
            if not raw:
                continue
            try:
                rec = json.loads(raw)
            except json.JSONDecodeError:
                continue          # torn/garbage line: skip, stay live
            if isinstance(rec, dict):
                recs.append(rec)
        return recs, off + last_nl + 1


# ---------------------------------------------------------------------------
# the ledger registry
# ---------------------------------------------------------------------------

# Every `kind` value any writer in the tree emits, by family. This literal
# is the contract hetucheck's `ledger-kind-drift` lint parses (the
# DELTA_KINDS pattern): a kind emitted anywhere but absent here — or listed
# here but emitted nowhere — is drift. `report` covers exported report
# documents (hetuprof --roofline --json), which are CLI output, not files
# under the telemetry dir.
LEDGER_KINDS = {
    "metrics": ("step", "event", "final", "ps_server", "scope", "watch",
                "plan", "model_info", "run_info", "xla_trace", "finding",
                "compile"),
    "trail_client": ("rpc", "anchor", "dropped"),
    "trail_server": ("srv", "anchor", "dropped"),
    "trail_events": ("straggler",),
    "pilot": (),            # rows are keyed by `phase`, not `kind`
    "ps_supervisor": ("event",),
    "flight": ("provenance",),
    "job_manifest": (),     # keyed by `format` (recovery.MANIFEST_FORMAT)
    "run_summary": (),
    "report": ("roofline",),
}

# One descriptor per artifact family. `globs` are relative to the telemetry
# directory (the pilot ledger and flight rings may live one level down —
# heturun points HETU_PILOT_DIR at `<dir>/pilot`). `format` is "jsonl"
# (append-only lines; torn tail = crash signature, tolerated) or "doc" (one
# JSON document written tmp + atomic rename; a torn `.tmp` is never read).
# `keys` are the causal keys rows of this family can carry.
LEDGERS = {
    "metrics": {
        "globs": ("metrics-r*.jsonl",), "format": "jsonl", "rotates": True,
        "keys": ("step", "rank", "world_version", "era", "epoch"),
        "desc": "per-rank step/event/plan/watch/scope/ps_server stream",
    },
    "trail_client": {
        "globs": ("trail-client-r*.jsonl",), "format": "jsonl",
        "rotates": True, "keys": ("step", "rank"),
        "desc": "client RPC spans + clock anchors (hetutrail)",
    },
    "trail_server": {
        "globs": ("trail-server-s*.jsonl",), "format": "jsonl",
        "rotates": True, "keys": ("step",),
        "desc": "server request timelines + clock anchors (hetutrail)",
    },
    "trail_events": {
        "globs": ("trail-events.jsonl",), "format": "jsonl",
        "rotates": True, "keys": ("step", "rank"),
        "desc": "cross-rank straggler verdicts",
    },
    "pilot": {
        "globs": ("pilot.jsonl", "pilot/pilot.jsonl"), "format": "jsonl",
        "rotates": False, "keys": ("era", "step"),
        "desc": "actuation ledger: propose/actuate/verdict/abstain phases",
    },
    "ps_supervisor": {
        "globs": ("ps_supervisor.jsonl",), "format": "jsonl",
        "rotates": False, "keys": (),
        "desc": "server liveness lapses / respawns",
    },
    "flight": {
        "globs": ("flight-r*.json", "flight/flight-r*.json"),
        "format": "doc", "rotates": False, "keys": ("step", "rank"),
        "desc": "hetuscope flight-recorder ring, flushed on abort paths",
    },
    "job_manifest": {
        "globs": ("job_epoch_*.json", "*/job_epoch_*.json"),
        "format": "doc", "rotates": False,
        "keys": ("epoch", "step", "world_version"),
        "desc": "hetusave committed job-epoch manifests",
    },
    "run_summary": {
        "globs": ("run_summary.json",), "format": "doc", "rotates": False,
        "keys": (), "desc": "heturun end-of-run digest",
    },
}


def ledger_files(family: str, dir_path: str) -> list:
    """Existing files of one family under ``dir_path``, backups first (so a
    straight concatenation reads in write order). ``.tmp`` siblings of doc
    families are a crash's torn half-write — never matched."""
    led = LEDGERS[family]
    out: list = []
    for pat in led["globs"]:
        for p in sorted(glob.glob(os.path.join(dir_path, pat))):
            if led["rotates"] and os.path.exists(p + ".1"):
                if p + ".1" not in out:
                    out.append(p + ".1")
            if p not in out:
                out.append(p)
    return out


def load_ledgers(dir_path: str, errors: Optional[dict] = None) -> dict:
    """Every registered family under ``dir_path`` → list of :class:`Row`.
    Doc families yield one Row (line 0) per document; an unparsable doc is
    classified into ``errors`` like a torn JSONL line."""
    out: dict = {}
    for family, led in LEDGERS.items():
        errs: list = []
        rows: list = []
        for path in ledger_files(family, dir_path):
            if led["format"] == "doc":
                try:
                    with open(path) as f:
                        doc = json.load(f)
                except (OSError, json.JSONDecodeError) as e:
                    errs.append({"path": path, "line": 0,
                                 "reason": "torn-doc",
                                 "error": f"invalid JSON document ({e})"})
                    continue
                if isinstance(doc, dict):
                    rows.append(Row(path, 0, doc))
            else:
                rows.extend(iter_rows(path, errs))
        out[family] = rows
        if errors is not None:
            errors[family] = errs
    return out


def causal_key(rec: dict) -> dict:
    """The (world_version, era/epoch, step, rank) coordinates a record
    carries — absent keys are simply missing, never fabricated."""
    out = {}
    for k in ("world_version", "era", "epoch", "step", "rank"):
        v = rec.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = int(v)
    if "world_version" not in out and "pending_version" in rec:
        try:
            out["world_version"] = int(rec["pending_version"])
        except (TypeError, ValueError):
            pass
    return out


# ---------------------------------------------------------------------------
# causal timeline
# ---------------------------------------------------------------------------


def clock_sync(anchors: Iterable) -> dict:
    """Cross-process ordering from the PR 13 trail anchors. Each anchor
    pairs one rank's CLOCK_MONOTONIC with its wall clock; when every anchor
    carries the same ``boot_id`` (the hetutrace condition: one machine, one
    monotonic clock), the per-rank offset ``wall_s - mono_us/1e6`` measures
    that rank's wall-clock error against the shared clock, and subtracting
    it converts any wall timestamp into the shared monotonic domain.
    Heterogeneous or absent boot ids → ``comparable=False`` (raw wall
    order, the best available)."""
    offsets: dict = {}
    boot_ids = set()
    for a in anchors:
        if a.get("kind") != "anchor":
            continue
        try:
            rank = int(a.get("rank", -1))
            off = float(a["wall_s"]) - float(a["mono_us"]) / 1e6
        except (KeyError, TypeError, ValueError):
            continue
        offsets[rank] = off   # last anchor per rank wins (freshest clock)
        boot_ids.add(a.get("boot_id") or "")
    comparable = len(boot_ids) == 1 and "" not in boot_ids and bool(offsets)
    base = sorted(offsets.values())[len(offsets) // 2] if offsets else 0.0
    return {"comparable": comparable, "offsets": offsets, "base": base,
            "boot_ids": boot_ids}


def _one_line(src: str, rec: dict) -> str:
    """The narrative rendering of one timeline entry."""
    kind = rec.get("kind") or rec.get("phase") or ""
    if src == "metrics" and kind == "event":
        extras = {k: v for k, v in rec.items()
                  if k not in ("ts", "kind", "name", "rank", "pid",
                               "run_id", "inc")}
        return f"event {rec.get('name')} {json.dumps(extras, default=str)}"
    if src == "metrics" and kind == "step":
        return (f"step {rec.get('step')} {rec.get('step_ms')}ms "
                f"sub={rec.get('sub')}")
    if src == "pilot":
        d = rec.get("delta") or {}
        tail = f" verdict={rec['verdict']}" if "verdict" in rec else ""
        return (f"pilot {kind} era={rec.get('era')} "
                f"delta={d.get('kind')}{tail}")
    if src == "flight":
        return (f"flight flush reason={rec.get('reason')} "
                f"k={rec.get('k')} records={len(rec.get('records') or [])}")
    if src == "job_manifest":
        return (f"job epoch {rec.get('epoch')} committed at step "
                f"{rec.get('step')} (world {rec.get('world')})")
    if src == "trail_events":
        return (f"straggler rank={rec.get('rank')} "
                f"step={rec.get('step')} lag_ms={rec.get('lag_ms')}")
    if src == "ps_supervisor":
        return f"supervisor: {rec.get('message')}"
    if src == "run_summary":
        return (f"run ended rc={rec.get('exit_code')} "
                f"final_steps={rec.get('final_steps')}")
    return f"{kind or src} {json.dumps(causal_key(rec), default=str)}"


def load_timeline(dir_path: str, step_range=None) -> dict:
    """The merged causal event stream of one run directory.

    Returns ``{"entries": [...], "clock": ..., "errors": {...}}``; each
    entry is ``{"t", "ts", "src", "what", **causal_key, "rec"}`` sorted by
    the anchor-corrected timestamp (see :func:`clock_sync`), then by step
    and rank. Step records ride along only near narrative entries — or
    throughout ``step_range`` when one is given — so a 100k-step run stays
    readable."""
    errors: dict = {}
    led = load_ledgers(dir_path, errors)
    anchors = [r.rec for fam in ("trail_client", "trail_server")
               for r in led[fam] if r.rec.get("kind") == "anchor"]
    clock = clock_sync(anchors)

    entries: list = []

    def add(src: str, row: Row, ts=None) -> None:
        rec = row.rec
        if ts is None:
            ts = rec.get("ts") or rec.get("flushed_ts")
        try:
            ts = float(ts)
        except (TypeError, ValueError):
            ts = 0.0
        key = causal_key(rec)
        rank = key.get("rank")
        t = ts
        if clock["comparable"] and rank in clock["offsets"]:
            t = ts - clock["offsets"][rank] + clock["base"]
        entries.append({"t": t, "ts": ts, "src": src,
                        "what": _one_line(src, rec), **key, "rec": rec,
                        "_loc": f"{row.path}:{row.line}"})

    narrative_steps: set = set()
    step_rows: list = []
    for row in led["metrics"]:
        kind = row.rec.get("kind")
        if kind == "step":
            step_rows.append(row)
        elif kind == "event":
            add("metrics", row)
            k = causal_key(row.rec)
            if "step" in k:
                narrative_steps.add((k.get("rank"), k["step"]))
        elif kind in ("plan", "run_info", "model_info", "final"):
            add("metrics", row)
        elif kind == "watch" and row.rec.get("divergence"):
            add("metrics", row)
        elif kind == "finding":
            add("metrics", row)
    for fam in ("pilot", "trail_events", "ps_supervisor", "flight",
                "job_manifest", "run_summary"):
        for row in led[fam]:
            add(fam, row)
            k = causal_key(row.rec)
            if "step" in k:
                narrative_steps.add((k.get("rank"), k["step"]))
    lo, hi = step_range if step_range else (None, None)
    for row in step_rows:
        k = causal_key(row.rec)
        s = k.get("step")
        if s is None:
            continue
        if lo is not None and lo <= s <= hi:
            add("metrics", row)
        elif step_range is None and any(
                (k.get("rank"), s + d) in narrative_steps
                for d in (-2, -1, 0, 1, 2)):
            add("metrics", row)

    entries.sort(key=lambda e: (e["t"], e.get("step", -1),
                                e.get("rank", -1), e["_loc"]))
    return {"entries": entries, "clock": clock, "errors": errors}


def render_timeline(tl: dict, out=sys.stdout) -> None:
    clock = tl["clock"]
    mode = ("anchor-corrected (shared boot_id)" if clock["comparable"]
            else "wall-clock (no shared monotonic anchor)")
    print(f"hetustory: {len(tl['entries'])} entries, ordering: {mode}",
          file=out)
    t0 = tl["entries"][0]["t"] if tl["entries"] else 0.0
    for e in tl["entries"]:
        key = " ".join(f"{k}={e[k]}" for k in
                       ("world_version", "era", "epoch", "step", "rank")
                       if k in e)
        print(f"  +{e['t'] - t0:9.3f}s [{e['src']:>13}] {e['what']}"
              f"{('  (' + key + ')') if key else ''}", file=out)
    torn = sum(len(v) for v in tl["errors"].values())
    if torn:
        print(f"hetustory: {torn} torn/invalid line(s) classified "
              "(crash signatures, not reader failures)", file=out)


# ---------------------------------------------------------------------------
# offline invariant audit
# ---------------------------------------------------------------------------


def _row_ref(row: Row) -> dict:
    return {"path": row.path, "line": row.line, "rec": row.rec}


def _violation(invariant: str, message: str, rows: Iterable) -> dict:
    return {"invariant": invariant, "message": message,
            "rows": [_row_ref(r) for r in rows]}


def _last_per(rows: Iterable, key_fn) -> dict:
    out: dict = {}
    for r in rows:
        k = key_fn(r.rec)
        if k is not None:
            out[k] = r
    return out


def _audit_push_accounting(led: dict, violations: list, notes: list) -> None:
    """`pushes_ok == Σ(updates − restored)` — the quiesce algebra recovery
    and chaos assert live (PR 15/16), recomputed from the final metrics
    snapshots alone. Needs every rank's closing `final` row (a crashed run
    has no quiesced endpoint to compare) and the pushes_ok gauge."""
    finals = _last_per((r for r in led["metrics"]
                        if r.rec.get("kind") == "final"),
                       lambda rec: rec.get("rank"))
    servers = _last_per((r for r in led["metrics"]
                         if r.rec.get("kind") == "ps_server"),
                        lambda rec: rec.get("server"))
    if not finals or not servers:
        notes.append("push-accounting: skipped (no final/ps_server rows)")
        return
    pushes = {}
    for rank, row in finals.items():
        m = row.rec.get("metrics") or {}
        if "hetu_ps_pushes_ok_total" in m:
            pushes[rank] = (float(m["hetu_ps_pushes_ok_total"]), row)
    if not pushes:
        notes.append("push-accounting: skipped (no pushes_ok gauge — "
                     "pre-PR 20 run)")
        return
    total_pushed = sum(v for v, _ in pushes.values())
    applied = sum(float(r.rec.get("updates", 0))
                  - max(float(r.rec.get("restored_updates", 0)), 0.0)
                  for r in servers.values())
    if total_pushed != applied:
        worst = max(servers.values(), key=lambda r: r.rec.get("ts", 0))
        first_rank = next(iter(pushes.values()))[1]
        violations.append(_violation(
            "push-accounting",
            f"Σ pushes_ok across {len(pushes)} rank(s) = "
            f"{total_pushed:.0f} but Σ server (updates − restored) across "
            f"{len(servers)} server(s) = {applied:.0f}",
            [first_rank, worst]))


def _audit_pilot_eras(led: dict, violations: list, notes: list) -> None:
    """Every decided pilot era must appear on BOTH sides of the actuation
    protocol: a `verdict` row in pilot.jsonl and the matching
    `pilot_<verdict>` event on the telemetry bus (the ledger row is written
    first, so only the maximal era may lack its event — the crash window).
    `failed`/`interrupted` verdicts deliberately have no event twin."""
    ledger_verdicts = {}   # era -> (verdict, row)
    for r in led["pilot"]:
        rec = r.rec
        if rec.get("phase") == "verdict" and rec.get("era") is not None:
            ledger_verdicts[int(rec["era"])] = (rec.get("verdict"), r)
    event_verdicts = {}    # era -> (verdict, row)
    for r in led["metrics"]:
        rec = r.rec
        name = rec.get("name", "")
        if rec.get("kind") == "event" and name.startswith("pilot_") \
                and name[6:] in ("commit", "rollback", "regressed") \
                and rec.get("era") is not None:
            event_verdicts[int(rec["era"])] = (name[6:], r)
    max_era = max(ledger_verdicts) if ledger_verdicts else -1
    for era, (verdict, row) in sorted(ledger_verdicts.items()):
        if verdict in ("failed", "interrupted"):
            continue
        got = event_verdicts.get(era)
        if got is None:
            if era == max_era:
                notes.append(f"pilot-era-consistency: era {era} verdict "
                             f"'{verdict}' has no bus event (crash window "
                             "on the maximal era — tolerated)")
            else:
                violations.append(_violation(
                    "pilot-era-consistency",
                    f"pilot.jsonl era {era} decided '{verdict}' but no "
                    f"pilot_{verdict} event reached the telemetry bus",
                    [row]))
        elif got[0] != verdict:
            violations.append(_violation(
                "pilot-era-consistency",
                f"era {era}: ledger verdict '{verdict}' != bus event "
                f"'pilot_{got[0]}'", [row, got[1]]))
    for era, (verdict, row) in sorted(event_verdicts.items()):
        if era not in ledger_verdicts:
            violations.append(_violation(
                "pilot-era-consistency",
                f"pilot_{verdict} event for era {era} has no pilot.jsonl "
                "verdict row (the ledger write precedes the event — this "
                "order cannot happen on a healthy run)", [row]))


def _audit_manifests(led: dict, violations: list, notes: list) -> None:
    """Every committed job-epoch manifest must name only durable artifacts:
    the epoch directory, each server snapshot's `manifest.bin`, the
    per-server LATEST pointer flips, each worker state file — the
    stdlib-only mirror of recovery._manifest_complete (recovery.py needs
    numpy, which this login-node CLI must not)."""
    for row in led["job_manifest"]:
        m = row.rec
        if m.get("format") != 1:
            notes.append(f"epoch-manifest-complete: {row.path}: unknown "
                         f"manifest format {m.get('format')!r} (skipped)")
            continue
        jobdir = os.path.dirname(row.path)
        edir = os.path.join(jobdir, f"epoch_{m.get('epoch')}")
        missing = None
        if not os.path.isdir(edir):
            missing = f"epoch dir {edir}"
        else:
            for s in m.get("servers", []):
                snap = os.path.join(edir, str(s.get("snapshot", "")),
                                    "manifest.bin")
                ptr = os.path.join(edir, f"LATEST_s{s.get('rank')}")
                if not os.path.isfile(snap):
                    missing = f"server snapshot manifest {snap}"
                    break
                if not os.path.isfile(ptr):
                    missing = f"pointer flip {ptr}"
                    break
            else:
                for w in m.get("workers", []):
                    sf = os.path.join(edir, str(w.get("state_file", "")))
                    if not os.path.isfile(sf):
                        missing = f"worker state {sf}"
                        break
        if missing:
            violations.append(_violation(
                "epoch-manifest-complete",
                f"committed manifest for epoch {m.get('epoch')} (step "
                f"{m.get('step')}) references a missing artifact: "
                f"{missing}", [row]))


# flight-flush reason prefix -> event names that must accompany it on the
# telemetry bus (the flush and the event are written by the same abort path)
_FLIGHT_EVENTS = {
    "watchdog": ("watchdog_fire",),
    "preempted": ("preempted",),
    "anomaly": ("anomaly", "nan_provenance"),
    "resize": ("resize_drain", "resize_commit", "resize_abort",
               "resize_decommissioned"),
    "slo_breach": ("slo_breach",),
}


def _audit_flight(led: dict, violations: list, notes: list) -> None:
    """A flight-ring flush is the *effect* of an abort path whose *cause*
    is a bus event from the same rank; a doc with no cause means the event
    write was lost. Also re-checks the ring bound: a flush can never hold
    more records than its configured window `k`."""
    events_by_rank: dict = {}
    for r in led["metrics"]:
        if r.rec.get("kind") == "event":
            events_by_rank.setdefault(r.rec.get("rank"), []).append(r)
    for row in led["flight"]:
        doc = row.rec
        k = doc.get("k")
        recs = doc.get("records") or []
        if isinstance(k, int) and len(recs) > k:
            violations.append(_violation(
                "flight-event-consistency",
                f"flight doc holds {len(recs)} records but its ring bound "
                f"is k={k}", [row]))
        reason = str(doc.get("reason", "")).split(":", 1)[0]
        expected = _FLIGHT_EVENTS.get(reason)
        if expected is None:
            if reason != "crash":   # crash flush may precede a restart
                notes.append(f"flight-event-consistency: unrecognized "
                             f"flush reason {doc.get('reason')!r} "
                             f"({row.path})")
            continue
        rank = doc.get("rank")
        cands = [e for e in events_by_rank.get(rank, [])
                 if e.rec.get("name") in expected]
        if not cands:
            violations.append(_violation(
                "flight-event-consistency",
                f"flight flush reason={doc.get('reason')!r} on rank {rank} "
                f"has no {' / '.join(expected)} event on the bus",
                [row]))


def _audit_eras(led: dict, violations: list, notes: list) -> None:
    """Era sequencing, the exactly-once backbone every resize rides: per
    rank, committed world versions strictly increase (a duplicate commit
    would double-count an era partition); each commit is preceded by its
    drain; all ranks agree on the committed world's shape."""
    commits: dict = {}   # rank -> [(world_version, row)]
    drains: dict = {}    # rank -> {pending_version}
    world_shape: dict = {}   # world_version -> ((nw, ns), row)
    for r in led["metrics"]:
        rec = r.rec
        if rec.get("kind") != "event":
            continue
        name, rank = rec.get("name"), rec.get("rank")
        if name == "resize_commit" and rec.get("world_version") is not None:
            wv = int(rec["world_version"])
            commits.setdefault(rank, []).append((wv, r))
            shape = (rec.get("n_workers"), rec.get("n_servers"))
            if shape != (None, None):
                prev = world_shape.get(wv)
                if prev is not None and prev[0] != shape:
                    violations.append(_violation(
                        "era-sequencing",
                        f"ranks disagree on world {wv}'s shape: "
                        f"{prev[0]} vs {shape}", [prev[1], r]))
                else:
                    world_shape[wv] = (shape, r)
        elif name == "resize_drain":
            v = rec.get("pending_version")
            if v is not None:
                drains.setdefault(rank, set()).add(int(v))
    for rank, seq in commits.items():
        seen: dict = {}
        for wv, row in seq:     # file order == write order
            if wv in seen:
                violations.append(_violation(
                    "era-sequencing",
                    f"rank {rank} committed world {wv} twice — era "
                    "partition would be consumed twice", [seen[wv], row]))
                continue
            if seen and wv <= max(seen):
                violations.append(_violation(
                    "era-sequencing",
                    f"rank {rank} commit order regressed: world {wv} "
                    f"after {max(seen)}",
                    [seen[max(seen)], row]))
            if wv not in drains.get(rank, set()):
                violations.append(_violation(
                    "era-sequencing",
                    f"rank {rank} committed world {wv} with no preceding "
                    "resize_drain for it", [row]))
            seen[wv] = row


def audit(dir_path: str):
    """Recompute every cross-ledger invariant from the artifacts alone.
    Returns ``(violations, notes)`` — each violation names the invariant
    and carries the ledger rows (path:line + record) that contradict."""
    led = load_ledgers(dir_path)
    violations: list = []
    notes: list = []
    for check in (_audit_push_accounting, _audit_pilot_eras,
                  _audit_manifests, _audit_flight, _audit_eras):
        check(led, violations, notes)
    return violations, notes


def render_audit(dir_path: str, violations: list, notes: list,
                 out=sys.stdout) -> int:
    for v in violations:
        print(f"hetustory --audit: VIOLATION [{v['invariant']}] "
              f"{v['message']}", file=out)
        for ref in v["rows"]:
            print(f"    {ref['path']}:{ref['line']}: "
                  f"{json.dumps(ref['rec'], default=str)[:300]}", file=out)
    for n in notes:
        print(f"hetustory --audit: note: {n}", file=out)
    verdict = "FAIL" if violations else "OK"
    print(f"hetustory --audit: {verdict} — {len(violations)} violation(s), "
          f"{len(notes)} note(s) over {dir_path}", file=out)
    return 1 if violations else 0


# ---------------------------------------------------------------------------
# incident reports
# ---------------------------------------------------------------------------

INCIDENT_SCHEMA = 1
_INCIDENT_K = 8          # ± steps collected around the incident step
_INCIDENT_TAIL = 32      # rows per source when no step anchors the window


def incident_enabled() -> bool:
    """Abort-path incident capture is on unless explicitly disabled —
    writing one JSON file while the process is already dying is the cheap
    half of a post-mortem."""
    return os.environ.get("HETU_STORY_INCIDENT", "1").strip().lower() \
        not in ("0", "false", "no", "off")


def write_incident(dir_path: str, reason: str, step=None, rank=None,
                   k: Optional[int] = None, extra: Optional[dict] = None):
    """Collect the ±k-step window around (step, rank) from every registered
    ledger into one ``incident-<ms>-<reason>.json`` (tmp + atomic rename,
    the doc-family convention). Called from abort paths — never raises;
    returns the written path or None."""
    try:
        if k is None:
            try:
                k = int(os.environ.get("HETU_STORY_K", _INCIDENT_K))
            except ValueError:
                k = _INCIDENT_K
        led = load_ledgers(dir_path)
        sources: dict = {}
        for family, rows in led.items():
            picked: list = []
            if step is not None:
                for r in rows:
                    key = causal_key(r.rec)
                    s = key.get("step")
                    if s is not None and abs(s - int(step)) <= k:
                        picked.append(r)
            if not picked:     # no step coords (or step unknown): the tail
                picked = [r for r in rows
                          if r.rec.get("kind") != "step"][-_INCIDENT_TAIL:]
            if picked:
                sources[family] = [
                    {"path": r.path, "line": r.line, "rec": r.rec}
                    for r in picked[-4 * _INCIDENT_TAIL:]]
        doc = {"schema": INCIDENT_SCHEMA, "reason": str(reason),
               "ts": round(time.time(), 3), "step": step, "rank": rank,
               "k": k, "run_id": os.environ.get("HETU_RUN_ID"),
               "inc": os.environ.get("HETU_RUN_INCARNATION"),
               "counts": {f: len(v) for f, v in sources.items()},
               "sources": sources}
        if extra:
            doc["extra"] = extra
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in str(reason))[:40]
        path = os.path.join(
            dir_path, f"incident-{time.time_ns() // 10**6}-{safe}.json")
        fd, tmp = tempfile.mkstemp(dir=dir_path, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path
    except Exception:  # noqa: BLE001 — the abort must proceed regardless
        return None


def incident_files(dir_path: str) -> list:
    return sorted(glob.glob(os.path.join(dir_path, "incident-*.json")))


def render_incident(path: str, out=sys.stdout) -> int:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"hetustory --incident: cannot read {path}: {e}", file=out)
        return 1
    print(f"hetustory incident: reason={doc.get('reason')!r} "
          f"step={doc.get('step')} rank={doc.get('rank')} "
          f"±{doc.get('k')} steps  run_id={doc.get('run_id')} "
          f"inc={doc.get('inc')}", file=out)
    merged: list = []
    for family, refs in (doc.get("sources") or {}).items():
        print(f"  {family}: {len(refs)} row(s)", file=out)
        for ref in refs:
            rec = ref.get("rec", {})
            ts = rec.get("ts") or rec.get("flushed_ts") or 0
            try:
                ts = float(ts)
            except (TypeError, ValueError):
                ts = 0.0
            merged.append((ts, family, rec))
    merged.sort(key=lambda x: x[0])
    for ts, family, rec in merged[-80:]:
        print(f"    {ts:14.3f} [{family:>13}] {_one_line(family, rec)}",
              file=out)
    return 0


# ---------------------------------------------------------------------------
# cross-run diff
# ---------------------------------------------------------------------------


def _profiler_mod():
    """profiler.py (the gate's home), importable from BOTH contexts: inside
    the package, or standalone when bin/hetustory loaded this file by path
    (profiler is stdlib-only at module level — the hetutop precedent)."""
    try:
        from . import profiler
        return profiler
    except ImportError:
        import importlib.util
        mod = sys.modules.get("_hetustory_profiler")
        if mod is not None:
            return mod
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "profiler.py")
        spec = importlib.util.spec_from_file_location(
            "_hetustory_profiler", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_hetustory_profiler"] = mod
        spec.loader.exec_module(mod)
        return mod


_PLAN_FIELDS = ("mesh", "comm_mode", "comm_quant", "zero1", "remat",
                "predicted_step_ms", "n_servers", "n_workers")


def _episode_counts(led: dict) -> dict:
    """The structural story of a run: how many times each subsystem acted."""
    out = collections.Counter()
    for r in led["metrics"]:
        rec = r.rec
        kind = rec.get("kind")
        if kind == "event":
            name = rec.get("name", "")
            if name in ("resize_commit", "resize_abort", "anomaly",
                        "rollback", "restart", "preempted", "watchdog_fire",
                        "plan_divergence", "slo_breach", "emergency_save"):
                out[name] += 1
            elif name.startswith("pilot_"):
                out[name] += 1
        elif kind == "step":
            out["steps"] += 1
        elif kind == "watch" and rec.get("divergence"):
            out["watch_divergence_rows"] += 1
    out["straggler"] = sum(1 for r in led["trail_events"]
                           if r.rec.get("kind") == "straggler")
    out["flight_flushes"] = len(led["flight"])
    out["job_epochs"] = len(led["job_manifest"])
    for r in led["pilot"]:
        if r.rec.get("phase") == "verdict":
            out[f"pilot_era_{r.rec.get('verdict')}"] += 1
    return dict(out)


def _pctl(vals: list, p: float) -> float:
    s = sorted(vals)
    return s[min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))]


def _run_facts(path: str) -> dict:
    """Everything --diff compares about one run: gate cells (metric level)
    plus plan and episode structure (ledger level). ``path`` is a telemetry
    directory or any summary artifact profiler.load_summary accepts."""
    prof = _profiler_mod()
    cells, meta = prof.load_summary(path)
    facts = {"path": path, "cells": dict(cells), "meta": meta, "plan": {},
             "episodes": {}, "final_step": None}
    if os.path.isdir(path):
        led = load_ledgers(path)
        plan = None
        step_ms: list = []
        for r in led["metrics"]:
            if r.rec.get("kind") == "plan":
                plan = r.rec
            elif r.rec.get("kind") == "step":
                s = r.rec.get("step")
                if isinstance(s, int):
                    facts["final_step"] = max(facts["final_step"] or 0, s)
                try:
                    step_ms.append(float(r.rec["step_ms"]))
                except (KeyError, TypeError, ValueError):
                    pass
        if plan:
            facts["plan"] = {k: plan.get(k) for k in _PLAN_FIELDS
                             if plan.get(k) is not None}
        facts["episodes"] = _episode_counts(led)
        if step_ms:
            # a run without hetuwatch rows still gates on its raw step
            # stream (keys end in _ms -> lower-is-better per the gate's
            # direction rules); watch cells, when present, ride alongside
            facts["cells"]["story_steps"] = {
                "p50_step_ms": round(_pctl(step_ms, 50), 4),
                "p99_step_ms": round(_pctl(step_ms, 99), 4),
                "step_rows": len(step_ms)}
            facts["meta"] = {"incomplete": False, "why": None}
    return facts


def diff_runs(a: str, b: str, tolerance_pct: float = 10.0) -> dict:
    """Runs A and B aligned by step/era: the gate's direction-aware metric
    comparison (same regression/improvement semantics as
    ``hetuprof --gate``), plus what the flat numbers can't say — plan
    deltas and episode-count deltas, the *why* behind a step-time shift."""
    fa, fb = _run_facts(a), _run_facts(b)
    prof = _profiler_mod()
    gate = prof.gate(fa["cells"], fb["cells"], tolerance_pct=tolerance_pct,
                     baseline_meta=fa["meta"], current_meta=fb["meta"])
    plan_delta = {}
    for k in sorted(set(fa["plan"]) | set(fb["plan"])):
        va, vb = fa["plan"].get(k), fb["plan"].get(k)
        if va != vb:
            plan_delta[k] = [va, vb]
    episode_delta = {}
    for k in sorted(set(fa["episodes"]) | set(fb["episodes"])):
        va, vb = fa["episodes"].get(k, 0), fb["episodes"].get(k, 0)
        if va != vb:
            episode_delta[k] = [va, vb]
    return {"a": a, "b": b, "gate": {
                "status": gate.status, "verdict": gate.verdict,
                "compared": gate.compared,
                "regressions": gate.regressions,
                "improvements": gate.improvements,
                "report": gate.report()},
            "plan_delta": plan_delta, "episode_delta": episode_delta,
            "final_steps": [fa["final_step"], fb["final_step"]]}


def render_diff(d: dict, out=sys.stdout) -> int:
    print(f"hetustory --diff: A={d['a']}  B={d['b']}", file=out)
    print(d["gate"]["report"], file=out)
    if d["plan_delta"]:
        print("plan deltas (A -> B):", file=out)
        for k, (va, vb) in d["plan_delta"].items():
            print(f"  {k}: {va!r} -> {vb!r}", file=out)
    if d["episode_delta"]:
        print("episode deltas (A -> B):", file=out)
        for k, (va, vb) in d["episode_delta"].items():
            print(f"  {k}: {va} -> {vb}", file=out)
    if not d["plan_delta"] and not d["episode_delta"]:
        print("no structural deltas (same plan, same episode counts)",
              file=out)
    return 0 if d["gate"]["status"] == 0 else d["gate"]["status"]


# ---------------------------------------------------------------------------
# --check: jax-free self-test (the hetuwatch/hetupilot CI pattern)
# ---------------------------------------------------------------------------


def _fixture_run(tmp: str, rank: int = 0, step_ms: float = 10.0,
                 corrupt: bool = False) -> None:
    """One synthetic-but-schema-true run directory for the self-test."""
    mpath = os.path.join(tmp, f"metrics-r{rank}.jsonl")
    with open(mpath, "w") as f:
        def w(rec):
            f.write(json.dumps(rec, separators=(",", ":")) + "\n")
        ts = 1000.0
        w({"ts": ts, "rank": rank, "kind": "run_info",
           "device_kind": "cpu"})
        w({"ts": ts, "rank": rank, "kind": "plan", "mesh": [1, 1, 1],
           "comm_mode": "ps", "predicted_step_ms": step_ms})
        for s in range(8):
            w({"ts": ts + s, "rank": rank, "kind": "step", "sub": "train",
               "step": s, "step_ms": step_ms})
        w({"ts": ts + 3.5, "rank": rank, "kind": "event",
           "name": "resize_drain", "step": 3, "pending_version": 1})
        w({"ts": ts + 3.6, "rank": rank, "kind": "event",
           "name": "resize_commit", "step": 4, "world_version": 1,
           "n_workers": 1, "n_servers": 1})
        w({"ts": ts + 6.0, "rank": rank, "kind": "event",
           "name": "pilot_commit", "era": 0, "step": 6, "ratio": 0.9})
        w({"ts": ts + 7.9, "rank": rank, "kind": "ps_server", "server": 0,
           "updates": 80 if not corrupt else 79, "restored_updates": -1})
        w({"ts": ts + 8.0, "rank": rank, "kind": "final",
           "metrics": {"hetu_ps_pushes_ok_total": 80,
                       "step_ms_p50": step_ms}})
        f.write('{"ts": 1008.1, "kind": "step", "step": 9, "trunc')
    with open(os.path.join(tmp, "pilot.jsonl"), "w") as f:
        for rec in ({"ts": 1005.0, "era": 0, "phase": "propose",
                     "step": 5, "delta": {"kind": "comm_mode_flip"}},
                    {"ts": 1005.1, "era": 0, "phase": "actuate",
                     "step": 5, "delta": {"kind": "comm_mode_flip"}},
                    {"ts": 1006.0, "era": 0, "phase": "verdict",
                     "verdict": "commit", "step": 6,
                     "delta": {"kind": "comm_mode_flip"}}):
            f.write(json.dumps(rec, separators=(",", ":")) + "\n")
    with open(os.path.join(tmp, f"trail-client-r{rank}.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "anchor", "rank": rank,
                            "mono_us": 500_000_000,
                            "wall_s": 1000.0, "boot_id": "fixture-boot"},
                           separators=(",", ":")) + "\n")
    with open(os.path.join(tmp, f"flight-r{rank}.json"), "w") as f:
        json.dump({"schema": 1, "reason": "preempted", "rank": rank,
                   "k": 4, "flushed_ts": 1007.0, "flushes": 1,
                   "records": [{"step": 6}, {"step": 7}]}, f)
    # the preempted flush needs its bus event
    with open(mpath, "r+") as f:
        lines = f.readlines()
    lines.insert(-1, json.dumps(
        {"ts": 1007.0, "rank": rank, "kind": "event", "name": "preempted",
         "step": 7, "signum": 15}, separators=(",", ":")) + "\n")
    with open(mpath, "w") as f:
        f.writelines(lines)


def self_check(out=sys.stdout) -> int:
    """End-to-end proof on synthetic fixtures, no cluster, no jax: reader
    classification, rotation recovery, timeline, audit 0/1, incident
    round-trip, diff regression detection. CI's `bin/hetustory --check`."""
    import shutil
    failures: list = []

    def check(name, ok, detail=""):
        tag = "ok" if ok else "FAIL"
        print(f"hetustory --check: {name}: {tag}"
              f"{(' — ' + detail) if detail and not ok else ''}", file=out)
        if not ok:
            failures.append(name)

    base = tempfile.mkdtemp(prefix="hetustory-check-")
    try:
        # 1. torn-tail classification vs mid-file corruption
        p = os.path.join(base, "probe.jsonl")
        with open(p, "w") as f:
            f.write('{"kind":"step","step":1}\n')
            f.write('garbage not json\n')
            f.write('[1,2,3]\n')
            f.write('{"kind":"step","step":2}\n')
            f.write('{"kind":"step","step":3,"tor')
        errs: list = []
        recs = read_jsonl(p, errs)
        reasons = sorted(e["reason"] for e in errs)
        check("torn-tail classification",
              len(recs) == 2 and reasons ==
              ["invalid-json", "not-object", "torn-tail"],
              f"recs={len(recs)} reasons={reasons}")

        # 2. rotation-under-reader: records written between the reader's
        # poll and the rename must NOT be lost
        rp = os.path.join(base, "rot.jsonl")
        fol = LedgerFollower()
        with open(rp, "w") as f:
            f.write('{"n":1}\n')
        got = [r["n"] for r in fol.poll(rp)]
        with open(rp, "a") as f:
            f.write('{"n":2}\n{"n":3}\n')   # unseen, then rotated away
        os.replace(rp, rp + ".1")
        with open(rp, "w") as f:
            f.write('{"n":4}\n')
        got += [r["n"] for r in fol.poll(rp)]
        check("rotation-under-reader recovery", got == [1, 2, 3, 4],
              f"got={got}")

        # 3/4. clean run: timeline renders, audit passes
        clean = os.path.join(base, "clean")
        os.makedirs(clean)
        _fixture_run(clean)
        tl = load_timeline(clean)
        check("timeline merge",
              len(tl["entries"]) >= 8 and tl["clock"]["comparable"]
              and any(e["src"] == "pilot" for e in tl["entries"])
              and any(e["src"] == "flight" for e in tl["entries"]),
              f"entries={len(tl['entries'])}")
        v, _ = audit(clean)
        check("audit clean run", not v,
              v[0]["invariant"] if v else "")

        # 5. seeded single-row corruption: audit names the invariant + rows
        bad = os.path.join(base, "bad")
        os.makedirs(bad)
        _fixture_run(bad, corrupt=True)
        v, _ = audit(bad)
        check("audit seeded corruption",
              len(v) == 1 and v[0]["invariant"] == "push-accounting"
              and len(v[0]["rows"]) == 2,
              f"violations={[x['invariant'] for x in v]}")

        # 6. incident write + render round-trip
        ip = write_incident(clean, "check-probe", step=6, rank=0, k=2)
        ok = ip is not None and os.path.exists(ip)
        nsrc = 0
        if ok:
            with open(ip) as f:
                doc = json.load(f)
            nsrc = len(doc.get("sources", {}))
            ok = nsrc >= 3 and doc["reason"] == "check-probe"
        check("incident round-trip", ok, f"sources={nsrc}")
        if ok:
            import io
            render_incident(ip, out=io.StringIO())

        # 7. diff: a seeded step-time regression surfaces with plan context
        slow = os.path.join(base, "slow")
        os.makedirs(slow)
        _fixture_run(slow, step_ms=14.0)
        d = diff_runs(clean, slow, tolerance_pct=10.0)
        regressed = [r.get("metric", "") for r in d["gate"]["regressions"]]
        check("diff regression detection",
              d["gate"]["status"] == 1
              and any("step_ms" in m for m in regressed)
              and "predicted_step_ms" in d["plan_delta"],
              f"status={d['gate']['status']} regressed={regressed}")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    n = 7
    if failures:
        print(f"hetustory --check: FAIL ({len(failures)}/{n}): "
              f"{', '.join(failures)}", file=out)
        return 1
    print(f"hetustory --check: all {n} checks passed", file=out)
    return 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _parse_step_range(spec: str):
    a, _, b = spec.partition(":")
    lo = int(a) if a else 0
    hi = int(b) if b else sys.maxsize
    return (lo, hi)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="hetustory",
        description="unified run ledger: causal timeline, offline invariant "
                    "audit, incident reports, cross-run diff")
    ap.add_argument("dir", nargs="?", help="telemetry directory")
    ap.add_argument("--step", metavar="A:B",
                    help="include step records in [A, B]")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--audit", action="store_true",
                    help="offline invariant audit (exit 0 ok / 1 violated)")
    ap.add_argument("--incident", nargs="?", const="", metavar="FILE",
                    help="render an incident report (default: latest in DIR)")
    ap.add_argument("--diff", nargs=2, metavar=("RUN_A", "RUN_B"),
                    help="cross-run diff (telemetry dirs or bench summaries)")
    ap.add_argument("--tolerance", type=float, default=10.0,
                    help="gate tolerance %% for --diff (default 10)")
    ap.add_argument("--check", action="store_true",
                    help="jax-free self-test on synthetic fixtures")
    args = ap.parse_args(argv)

    if args.check:
        return self_check()
    if args.diff:
        d = diff_runs(args.diff[0], args.diff[1],
                      tolerance_pct=args.tolerance)
        if args.json:
            print(json.dumps(d, indent=2, default=str))
            return 0 if d["gate"]["status"] == 0 else d["gate"]["status"]
        return render_diff(d)
    if args.dir is None:
        ap.error("DIR is required (except with --diff/--check)")
    if args.audit:
        violations, notes = audit(args.dir)
        if args.json:
            print(json.dumps({"violations": violations, "notes": notes},
                             indent=2, default=str))
            return 1 if violations else 0
        return render_audit(args.dir, violations, notes)
    if args.incident is not None:
        path = args.incident
        if not path:
            found = incident_files(args.dir)
            if not found:
                print(f"hetustory --incident: no incident-*.json under "
                      f"{args.dir}", file=sys.stderr)
                return 1
            path = found[-1]
        if args.json:
            with open(path) as f:
                sys.stdout.write(f.read())
            return 0
        return render_incident(path)
    tl = load_timeline(args.dir,
                       _parse_step_range(args.step) if args.step else None)
    if args.json:
        slim = [{k: v for k, v in e.items() if k not in ("rec", "_loc")}
                for e in tl["entries"]]
        print(json.dumps({"entries": slim,
                          "comparable": tl["clock"]["comparable"]},
                         indent=2, default=str))
        return 0
    render_timeline(tl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
