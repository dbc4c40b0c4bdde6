"""hetulint: lint graph-builder callables from the command line / CI.

    hetulint [--json] [--suppress LINT]... [--fail-on error|warn|never]
             MODULE:CALLABLE [MODULE:CALLABLE ...]

A target is ``package.module:callable`` or ``path/to/file.py:callable``. The
callable takes no arguments and returns one of:

- an Op / list of Ops / ``{target: [ops]}`` dict (an Executor eval spec), or
- ``(graph, config_kwargs)`` where ``config_kwargs`` build an
  :class:`AnalysisConfig` (e.g. ``{"comm_mode": "PS"}``) so strategy lints
  apply without spawning any runtime.

Every op constructed by the builder is recorded, so dead subgraphs (built but
unreachable from the returned eval targets) are reported. Exit status: 0
clean, 1 findings at/above ``--fail-on`` (default ``error``), 2 usage or
builder-import failure.

``--plan`` switches to the hetuplan Tier C pass (docs/ANALYSIS.md "Tier C:
planning"): instead of linting the declared layout, choose one —

    hetulint --plan [--devices N] [--calibrate TEL_DIR] [--json] \\
             MODULE:CALLABLE ...
    hetulint --plan --check        # CI self-test of the planning contract
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys

from .analyzer import AnalysisConfig, GraphAnalyzer, record_graph
from .findings import count_by_severity, sort_findings


def load_builder(spec: str):
    """Resolve ``module.path:callable`` or ``path/to/file.py:callable``."""
    if ":" not in spec:
        raise ValueError(
            f"target {spec!r} is not of the form module:callable")
    mod_spec, _, attr = spec.rpartition(":")
    if mod_spec.endswith(".py") or os.path.sep in mod_spec:
        path = os.path.abspath(mod_spec)
        name = os.path.splitext(os.path.basename(path))[0]
        spec_obj = importlib.util.spec_from_file_location(name, path)
        if spec_obj is None:
            raise ImportError(f"cannot load {path!r}")
        module = importlib.util.module_from_spec(spec_obj)
        sys.modules.setdefault(name, module)
        spec_obj.loader.exec_module(module)
    else:
        module = importlib.import_module(mod_spec)
    fn = getattr(module, attr, None)
    if not callable(fn):
        raise AttributeError(
            f"{mod_spec!r} has no callable {attr!r}")
    return fn


def _builder_result(builder):
    """Normalize one builder call: ``graph`` or ``(graph, config_kwargs)``
    -> ``(graph, config_kwargs)``."""
    result = builder()
    if isinstance(result, tuple) and len(result) == 2 \
            and isinstance(result[1], dict):
        return result
    return result, {}


def lint_target(spec: str, suppress=(), options=None, kernels=None):
    """Build one target's graph (recording the op universe) and run Tier A.
    Returns (findings, counts). ``kernels`` overrides the builder's
    hetukern mode so CI can ask "would kernels='force' fly on this
    graph?" without editing the builder (docs/KERNELS.md)."""
    builder = load_builder(spec)
    with record_graph() as universe:
        result = builder()
    config_kwargs = {}
    graph = result
    if isinstance(result, tuple) and len(result) == 2 \
            and isinstance(result[1], dict):
        graph, config_kwargs = result
    if kernels is not None:
        config_kwargs = dict(config_kwargs, kernels=kernels)
    config = AnalysisConfig(**config_kwargs)
    analyzer = GraphAnalyzer(
        graph, config=config, universe=universe, suppress=suppress,
        options=options, insert_comm=config.comm_mode is not None)
    findings = analyzer.run()
    return findings, count_by_severity(findings)


def plan_target(spec: str, devices=None, calibrate=None, suppress=()):
    """Build one target's graph and run the hetuplan Tier C pass
    (docs/ANALYSIS.md "Tier C: planning"). The builder's declared config
    is NEVER a hint — it is only diffed against the choice for the
    ``plan-divergence`` lint. Returns (plan, findings, counts)."""
    from .findings import is_suppressed
    from .planner import plan_graph

    builder = load_builder(spec)
    graph, config_kwargs = _builder_result(builder)
    config = AnalysisConfig(**config_kwargs)
    plan = plan_graph(graph, config=config, devices=devices,
                      calibrate=calibrate)
    findings = [f for f in plan.findings(config=config)
                if not is_suppressed(f, suppress)]
    findings = sort_findings(findings)
    return plan, findings, count_by_severity(findings)


def _plan_main(args) -> int:
    """The ``hetulint --plan`` mode: plan each target, print the chosen
    layout + predicted step time + per-decision rationale findings. Exit
    status follows the lint contract (0 clean under --fail-on, 1
    findings at/above it — a ``plan-infeasible`` error fails by default,
    a ``plan-divergence`` warn only under ``--fail-on warn``), 2 usage/
    builder failure."""
    if args.check:
        from .planner import plan_self_check
        return plan_self_check()
    if not args.targets:
        print("hetulint: --plan needs MODULE:CALLABLE target(s) "
              "(or --check)", file=sys.stderr)
        return 2
    devices = args.devices if args.devices is not None else 8

    def target_ok(counts) -> bool:
        if args.fail_on == "never":
            return True
        bad = counts["error"]
        if args.fail_on == "warn":
            bad += counts["warn"]
        return bad == 0

    results = []
    load_failed = False
    for spec in args.targets:
        try:
            plan, findings, counts = plan_target(
                spec, devices=devices, calibrate=args.calibrate,
                suppress=args.suppress)
        except Exception as e:  # noqa: BLE001 — builder errors are exit 2
            print(f"hetulint: cannot plan {spec!r}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            results.append({"target": spec, "plan": None, "findings": [],
                            "counts": None, "ok": False,
                            "error": f"{type(e).__name__}: {e}"})
            load_failed = True
            continue
        results.append({"target": spec, "plan": plan.as_dict(),
                        "findings": [f.as_dict() for f in findings],
                        "counts": counts, "ok": target_ok(counts)})
        if not args.as_json:
            print(f"{spec}:")
            print(plan.summary())
            for f in findings:
                print(f"  {f}")
    ok = all(r["ok"] for r in results)
    if args.as_json:
        print(json.dumps({"results": results, "ok": ok}, indent=2))
    if load_failed:
        return 2
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="hetulint",
        description="Define-time graph validation for hetu_tpu graphs.")
    ap.add_argument("targets", nargs="*", metavar="MODULE:CALLABLE",
                    help="graph-builder callable(s) to lint")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output for CI")
    ap.add_argument("--suppress", action="append", default=[],
                    metavar="LINT", help="silence a lint id (repeatable)")
    ap.add_argument("--fail-on", choices=["error", "warn", "never"],
                    default="error",
                    help="lowest severity that fails the run (default error)")
    ap.add_argument("--kernels", choices=["off", "auto", "force"],
                    default=None,
                    help="override the hetukern dispatch mode for the "
                         "kernels_pass lints (docs/KERNELS.md)")
    ap.add_argument("--plan", action="store_true",
                    help="run the hetuplan Tier C pass: choose comm-mode/"
                         "mesh/quantization/ZeRO-1/remat from the cost "
                         "model instead of linting a declared layout")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="device budget for --plan (default 8, the test "
                         "suite's virtual-mesh size; pass 1 for single-chip)")
    ap.add_argument("--calibrate", metavar="TEL_DIR",
                    help="with --plan: telemetry dir (or hetuprof "
                         "--roofline --json file) whose measured residuals "
                         "and critical-path legs calibrate the cost model")
    ap.add_argument("--check", action="store_true",
                    help="with --plan: self-test the planning contract "
                         "over the bundled builders (CI smoke)")
    args = ap.parse_args(argv)

    if args.plan:
        return _plan_main(args)
    if not args.targets:
        ap.print_usage(sys.stderr)
        return 2

    def target_ok(counts) -> bool:
        """Does this target pass under --fail-on? Keeps the per-target
        ``ok`` field and the exit status telling the same story."""
        if args.fail_on == "never":
            return True
        bad = counts["error"]
        if args.fail_on == "warn":
            bad += counts["warn"]
        return bad == 0

    results = []
    load_failed = False
    for spec in args.targets:
        try:
            findings, counts = lint_target(spec, suppress=args.suppress,
                                           kernels=args.kernels)
        except Exception as e:  # noqa: BLE001 — builder errors are exit 2
            # report on stderr, but keep the --json stdout contract: CI
            # parsers get a well-formed report carrying the partial results
            print(f"hetulint: cannot lint {spec!r}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            results.append({"target": spec, "findings": [], "counts": None,
                            "ok": False,
                            "error": f"{type(e).__name__}: {e}"})
            load_failed = True
            continue
        results.append({"target": spec,
                        "findings": [f.as_dict() for f in findings],
                        "counts": counts,
                        "ok": target_ok(counts)})
        if not args.as_json:
            total = sum(counts.values())
            print(f"{spec} — {total} finding(s) "
                  f"({counts['error']} error, {counts['warn']} warn, "
                  f"{counts['note']} note)")
            for f in sort_findings(findings):
                print(f"  {f}")

    ok = all(r["ok"] for r in results)
    if args.as_json:
        print(json.dumps({"results": results, "ok": ok}, indent=2))
    if load_failed:
        return 2
    return 0 if ok else 1
