#!/usr/bin/env python
"""Perf-regression gate: fresh BENCH_*.json vs the committed copies.

The BENCH_*.json files at the repo root are the perf trajectory -- each PR
commits the numbers its benchmarks measured.  Until now nothing *enforced*
the trajectory; this script does: after CI re-runs a benchmark (emitting a
fresh JSON over the committed one), it diffs every row of the fresh file
against the committed copy (``git show HEAD:<file>``) and fails on a
throughput regression beyond the tolerance.

Rows are matched by their identity fields (strings, bools and ints --
T/S/policy/backend/n_devices/...), and compared on their throughput metric:
``requests_per_s`` (higher is better) when present, else the first
``*_us``/``us_per_*`` field (lower is better).  A fresh row that *grew* a
new identity field the committed copy predates (e.g. a sweep gains an
``inflight`` axis) still gates against its committed predecessor: when no
exact match exists, a base row whose identity is a strict subset of the
fresh row's -- same value on every field the committed row knows about --
is compared instead, provided the subset match is unambiguous (a single
base candidate).  Exact matches claim their baselines first, then widened
rows claim what remains first-come in emission order, so a benchmark that
fans one old row out into several new ones gates one of them and reports
the rest as added.  Rows present on only one side are
reported but never fail the gate -- a benchmark may legitimately emit
fewer rows in a reduced environment (e.g. the single-device CI job skips
the multi-device sweep) or grow new rows in the PR under test.

``BENCH_autotune_gain.json`` additionally carries an *intra-file* gate: its
tuned-plan rows (``plan`` analytic/measured) must stay at or above the
default-plan row's throughput within the tolerance -- an autotuner that
"wins" the search but loses the measurement is a cost-model bug, and the
gate catches it even when the file was not re-emitted this run (the
committed rows themselves must honor the invariant).
``BENCH_cold_start.json`` carries one too: the warm rows (warm disk cache /
``--warmup``) must remove >= 80% of the cold row's time-to-first-response,
minus tolerance slack -- a warm replica that still pays compile-scale
first-request latency is a persistent-cache regression.
``BENCH_goodput.json`` carries two ratio gates (machine-independent by
construction): past saturation (load_pct > 100) the admission="shed" rows
must hold >= 1.3x the admission="none" rows' goodput, and the WFQ fairness
row's worst-tenant goodput must hold >= 2x the FIFO row's -- both with the
tolerance as multiplicative slack.  Matched goodput rows additionally gate
on ``shed_frac``: the shed fraction may not grow more than 5 percentage
points (plus slack) over the committed row -- goodput held up by shedding
ever more traffic is a capacity regression the rps diff alone can hide.

A file whose content is byte-identical to HEAD was not re-emitted this run
and is skipped for the row-vs-HEAD diff.  The tolerance (default 25% from
the CI issue) can be loosened for noisy hosts with ``--tol 0.4`` or
``CHECK_BENCH_TOL=0.4``.

Exit codes: 0 ok / nothing comparable, 1 regression, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# (metric, higher_is_better) probed in order; first hit wins
METRIC_PREFERENCE = (
    ("requests_per_s", True),
    ("goodput_rps", True),
    ("us_per_request", False),
    ("ttfr_ms", False),
    ("mm_engine_us", False),
    ("dle_scan_us", False),
    ("us_per_call", False),
    ("regret_frac", False),
    ("measured_frac", False),
)


def row_key(row: dict):
    """Identity of a row: every non-float field, sorted for determinism."""
    return tuple(sorted(
        (k, v) for k, v in row.items()
        if isinstance(v, (str, bool)) or (isinstance(v, int)
                                          and not isinstance(v, bool))))


def row_metric(row: dict, also_in: dict = None):
    """Throughput metric of ``row``; with ``also_in``, the first metric
    both rows carry (a row may grow a preferred metric the committed copy
    predates -- comparison needs a common one)."""
    for name, higher in METRIC_PREFERENCE:
        if isinstance(row.get(name), (int, float)) and (
                also_in is None
                or isinstance(also_in.get(name), (int, float))):
            return name, float(row[name]), higher
    return None


def iter_rows(doc: dict):
    """Every (section, row) of a BENCH doc: any top-level list of dicts.

    The ``provenance`` metadata block (git SHA, emission time, jax
    version -- see ``benchmarks/common.emit_json``) is explicitly not a
    row source: it describes the run, not a measurement, and must never
    enter the regression diff."""
    for section, val in sorted(doc.items()):
        if section == "provenance":
            continue
        if isinstance(val, list) and all(isinstance(r, dict) for r in val):
            for row in val:
                yield section, row


def committed_copy(name: str) -> str | None:
    r = subprocess.run(["git", "show", f"HEAD:{name}"], cwd=REPO_ROOT,
                       capture_output=True, text=True)
    return r.stdout if r.returncode == 0 else None


def pop_subset_match(base_rows: dict, section: str, fresh_key: tuple):
    """Claim the base row whose identity the fresh row's strictly extends.

    ``base_rows`` maps (section, key) -> row.  A base row is a candidate
    when it has an identity at all and every (field, value) of it also
    appears in the fresh row's identity -- i.e. the fresh row only *added*
    identity fields (an identity-less base row would be a "subset" of
    everything, so it never matches).  Exactly one candidate is required;
    ambiguity stays unmatched (better an added row than a wrong
    comparison).  The claimed row is popped so two fresh rows can never
    gate against the same baseline.
    """
    fresh_pairs = set(fresh_key)
    candidates = [k for k in base_rows
                  if k[0] == section and k[1] and set(k[1]) < fresh_pairs]
    if len(candidates) != 1:
        return None
    return base_rows.pop(candidates[0])


def autotune_gate(name: str, doc: dict, tol: float) -> tuple[list, bool]:
    """Intra-file invariant for BENCH_autotune_gain.json: every tuned-plan
    row must hold >= the default-plan row's throughput within ``tol``
    (the autotuner must never ship a plan that loses to the hand-picked
    default it searched against)."""
    rows = [r for _, r in iter_rows(doc) if isinstance(r.get("plan"), str)]
    defaults = [r for r in rows if r["plan"] == "default"
                and isinstance(r.get("requests_per_s"), (int, float))]
    if not defaults:
        return [f"{name}: no default-plan row; autotune gate skipped"], True
    base = max(float(r["requests_per_s"]) for r in defaults)
    lines, ok = [], True
    for r in rows:
        if r["plan"] == "default" or not isinstance(
                r.get("requests_per_s"), (int, float)):
            continue
        rps = float(r["requests_per_s"])
        ratio = rps / base if base > 0 else float("inf")
        verdict = "ok"
        if rps < base * (1.0 - tol):
            verdict, ok = "BELOW-DEFAULT", False
        lines.append(f"  {verdict:<13} tuned[{r['plan']}] "
                     f"{rps:.1f} vs default {base:.1f} rps "
                     f"({ratio:.2f}x)")
    header = (f"{name}: autotune gate (tuned >= default within "
              f"{tol * 100:.0f}%)")
    return [header] + lines, ok


def cold_start_gate(name: str, doc: dict, tol: float) -> tuple[list, bool]:
    """Intra-file invariant for BENCH_cold_start.json: every warm row
    (warm_disk / warmup) must remove >= 80% of the cold row's
    time-to-first-response, with the tolerance as slack on the remaining
    fraction (tol 0.25 -> warm TTFR must stay under 45% of cold).  A warm
    replica still paying compile-scale first-request latency means the
    persistent executable cache stopped doing its one job."""
    rows = [r for _, r in iter_rows(doc)
            if isinstance(r.get("mode"), str)
            and isinstance(r.get("ttfr_ms"), (int, float))]
    cold = [float(r["ttfr_ms"]) for r in rows if r["mode"] == "cold"]
    if not cold or min(cold) <= 0:
        return [f"{name}: no cold row; cold-start gate skipped"], True
    base = min(cold)
    ceiling = base * (0.2 + tol)
    lines, ok = [], True
    for r in rows:
        if r["mode"] == "cold":
            continue
        ttfr = float(r["ttfr_ms"])
        verdict = "ok"
        if ttfr > ceiling:
            verdict, ok = "STILL-COLD", False
        lines.append(f"  {verdict:<13} warm[{r['mode']}] ttfr "
                     f"{ttfr:.1f}ms vs cold {base:.1f}ms "
                     f"(reduction {1.0 - ttfr / base:.2f})")
    header = (f"{name}: cold-start gate (warm removes >= 80% of cold "
              f"TTFR, {tol * 100:.0f}% slack)")
    return [header] + lines, ok


def goodput_gate(name: str, doc: dict, tol: float) -> tuple[list, bool]:
    """Intra-file invariants for BENCH_goodput.json, both dimensionless
    ratios so they mean the same thing on any host:

      admission   past saturation (load_pct > 100) the admission="shed"
                  row must hold >= 1.3x the admission="none" row's
                  goodput at the same load -- admission control that no
                  longer beats unbounded queueing is dead weight.
      fairness    the WFQ row's worst-tenant goodput must hold >= 2x the
                  FIFO row's -- the whole point of per-tenant weighted
                  backlogs is that the mouse survives the whale.

    The tolerance is multiplicative slack on both thresholds."""
    rows = [r for _, r in iter_rows(doc)
            if isinstance(r.get("goodput_rps"), (int, float))]
    lines, ok = [], True

    by_load = {}
    for r in rows:
        if r.get("suite") == "load" and isinstance(r.get("load_pct"), int):
            by_load.setdefault(r["load_pct"], {})[r.get("admission")] = r
    checked = 0
    for load in sorted(by_load):
        pair = by_load[load]
        if load <= 100 or "shed" not in pair or "none" not in pair:
            continue
        checked += 1
        shed = float(pair["shed"]["goodput_rps"])
        none = float(pair["none"]["goodput_rps"])
        floor = 1.3 * (1.0 - tol)
        ratio = shed / none if none > 0 else float("inf")
        verdict = "ok"
        if ratio < floor:
            verdict, ok = "NO-ADMISSION-WIN", False
        lines.append(f"  {verdict:<16} load[{load}%] shed {shed:.1f} vs "
                     f"none {none:.1f} rps ({ratio:.2f}x, floor "
                     f"{floor:.2f}x)")

    fair = {r.get("scheduler"): r for r in rows
            if r.get("suite") == "fairness"}
    if "wfq" in fair and "fifo" in fair and all(
            isinstance(fair[s].get("worst_tenant_goodput_rps"),
                       (int, float)) for s in ("wfq", "fifo")):
        checked += 1
        wfq = float(fair["wfq"]["worst_tenant_goodput_rps"])
        fifo = float(fair["fifo"]["worst_tenant_goodput_rps"])
        floor = 2.0 * (1.0 - tol)
        ratio = wfq / fifo if fifo > 0 else float("inf")
        verdict = "ok"
        if ratio < floor:
            verdict, ok = "UNFAIR", False
        lines.append(f"  {verdict:<16} fairness wfq worst-tenant "
                     f"{wfq:.1f} vs fifo {fifo:.1f} rps ({ratio:.2f}x, "
                     f"floor {floor:.2f}x)")

    if not checked:
        return [f"{name}: no gateable rows; goodput gate skipped"], True
    header = (f"{name}: goodput gate (shed >= 1.3x none past saturation; "
              f"wfq worst-tenant >= 2x fifo; {tol * 100:.0f}% slack)")
    return [header] + lines, ok


def controller_gate(name: str, doc: dict, tol: float) -> tuple[list, bool]:
    """Intra-file invariants for BENCH_controller_regret.json (the
    autonomous-controller acceptance, machine-independent: the regret
    timeline runs under a virtual clock against a pinned cost model):

      regret   every suite="regret" row must hold regret_frac <= 0.10 --
               the controller captures >= 90% of the clairvoyant
               re-tuner's advantage over the static default plan.  The
               tolerance is multiplicative slack on the ceiling.
      thrash   the same rows must show swaps <= 3: adaptation, not
               oscillation.  No slack -- swap counts are deterministic.
      prune    every suite="prune" row must hold measured_evals <=
               budget_frac * grid_size (the successive-halving bandit's
               whole point vs the exhaustive measured grid).  No slack --
               eval counts are deterministic."""
    lines, ok, checked = [], True, 0
    for _, r in iter_rows(doc):
        suite = r.get("suite")
        if suite == "regret" and isinstance(r.get("regret_frac"),
                                            (int, float)):
            checked += 1
            regret = float(r["regret_frac"])
            ceiling = 0.10 * (1.0 + tol)
            verdict = "ok"
            if regret > ceiling:
                verdict, ok = "HIGH-REGRET", False
            lines.append(f"  {verdict:<13} regret[{r.get('scenario')}] "
                         f"{regret:.4f} (ceiling {ceiling:.4f})")
            swaps = r.get("swaps")
            if isinstance(swaps, int):
                verdict = "ok"
                if swaps > 3:
                    verdict, ok = "THRASHING", False
                lines.append(f"  {verdict:<13} swaps[{r.get('scenario')}] "
                             f"{swaps} (max 3)")
        elif suite == "prune" and isinstance(r.get("measured_evals"), int):
            checked += 1
            grid = int(r.get("grid_size", 0))
            budget = float(r.get("budget_frac", 0.25))
            cap = int(budget * grid)
            verdict = "ok"
            if grid and r["measured_evals"] > cap:
                verdict, ok = "NO-PRUNING", False
            lines.append(f"  {verdict:<13} prune[{r.get('scenario')}] "
                         f"{r['measured_evals']} measured evals vs "
                         f"grid {grid} (cap {cap})")
    if not checked:
        return [f"{name}: no gateable rows; controller gate skipped"], True
    header = (f"{name}: controller gate (regret <= 0.10 with "
              f"{tol * 100:.0f}% slack; swaps <= 3; measured evals <= "
              f"budget_frac * grid)")
    return [header] + lines, ok


def compare_file(name: str, tol: float) -> tuple[list, bool]:
    """Returns (report lines, ok)."""
    fresh_path = REPO_ROOT / name
    if not fresh_path.exists():
        return [f"{name}: absent from working tree; skipped"], True
    fresh_text = fresh_path.read_text()
    extra_lines: list = []
    extra_ok = True
    if name == "BENCH_autotune_gain.json":
        # intra-file gates run on the working-tree copy whether or not it
        # was re-emitted: committed rows must honor the invariant too
        extra_lines, extra_ok = autotune_gate(name, json.loads(fresh_text),
                                              tol)
    elif name == "BENCH_cold_start.json":
        extra_lines, extra_ok = cold_start_gate(name,
                                                json.loads(fresh_text), tol)
    elif name == "BENCH_goodput.json":
        extra_lines, extra_ok = goodput_gate(name, json.loads(fresh_text),
                                             tol)
    elif name == "BENCH_controller_regret.json":
        extra_lines, extra_ok = controller_gate(name,
                                                json.loads(fresh_text), tol)
    base_text = committed_copy(name)
    if base_text is None:
        return ([f"{name}: not in HEAD (new benchmark); diff skipped"]
                + extra_lines), extra_ok
    if fresh_text == base_text:
        return ([f"{name}: identical to HEAD (not re-emitted); diff "
                 f"skipped"] + extra_lines), extra_ok
    lines, ok = compare_docs(name, json.loads(base_text),
                             json.loads(fresh_text), tol)
    return lines + extra_lines, ok and extra_ok


def compare_docs(name: str, base_doc: dict, fresh_doc: dict,
                 tol: float) -> tuple[list, bool]:
    """Diff two BENCH documents row-by-row; returns (report lines, ok)."""
    base_rows = {}
    for section, row in iter_rows(base_doc):
        base_rows[(section, row_key(row))] = row

    # two passes: every exact identity match claims its baseline first, so
    # a widened row can never steal the base row an exact fresh row needs
    fresh = [(section, row, row_key(row))
             for section, row in iter_rows(fresh_doc)]
    matches = {}
    for i, (section, row, key) in enumerate(fresh):
        base = base_rows.pop((section, key), None)
        if base is not None:
            matches[i] = (base, False)
    for i, (section, row, key) in enumerate(fresh):
        if i not in matches:
            base = pop_subset_match(base_rows, section, key)
            if base is not None:
                matches[i] = (base, True)

    lines, ok, compared = [], True, 0
    for i, (section, row, key) in enumerate(fresh):
        ident = ", ".join(f"{k}={v}" for k, v in key) or "<no id>"
        base, widened = matches.get(i, (None, False))
        if widened:
            ident += " (identity widened)"
        if base is None:
            lines.append(f"  NEW     {section}[{ident}]")
            continue
        metric = row_metric(row, also_in=base)
        if metric is None:
            lines.append(f"  NOMETRIC {section}[{ident}]")
            continue
        mname, fresh_v, higher = metric
        base_v = float(base[mname])
        if base_v <= 0:
            continue
        compared += 1
        # delta > 0 is always an improvement, < 0 a regression
        delta = ((fresh_v - base_v) / base_v if higher
                 else (base_v - fresh_v) / base_v)
        verdict = "ok"
        if delta < -tol:
            verdict = "REGRESSION"
            ok = False
        lines.append(
            f"  {verdict:<10} {section}[{ident}] {mname}: "
            f"{base_v:.1f} -> {fresh_v:.1f} ({delta * 100:+.1f}%)")
        # shed_frac band: goodput held up by shedding ever more traffic is
        # a capacity regression the rps diff alone can hide
        if isinstance(row.get("shed_frac"), (int, float)) and isinstance(
                base.get("shed_frac"), (int, float)):
            grew = float(row["shed_frac"]) - float(base["shed_frac"])
            band = 0.05 + 0.2 * tol
            if grew > band:
                ok = False
                lines.append(
                    f"  SHED-GREW  {section}[{ident}] shed_frac: "
                    f"{float(base['shed_frac']):.3f} -> "
                    f"{float(row['shed_frac']):.3f} "
                    f"(+{grew * 100:.1f}pp > {band * 100:.1f}pp band)")
    for (section, key), _ in sorted(base_rows.items()):
        ident = ", ".join(f"{k}={v}" for k, v in key) or "<no id>"
        lines.append(f"  MISSING {section}[{ident}] (not emitted this run)")
    header = (f"{name}: {compared} rows compared, tolerance "
              f"{tol * 100:.0f}%")
    return [header] + lines, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="BENCH_*.json names (default: every tracked one)")
    ap.add_argument("--tol", type=float,
                    default=float(os.environ.get("CHECK_BENCH_TOL", "0.25")),
                    help="allowed fractional throughput regression "
                         "(default 0.25, env CHECK_BENCH_TOL)")
    args = ap.parse_args(argv)
    if args.tol < 0:
        ap.error("--tol must be >= 0")

    names = args.files
    if not names:
        r = subprocess.run(["git", "ls-files", "BENCH_*.json"],
                           cwd=REPO_ROOT, capture_output=True, text=True)
        if r.returncode != 0:
            print("check_bench: git unavailable and no files given",
                  file=sys.stderr)
            return 2
        names = r.stdout.split()
    if not names:
        print("check_bench: no BENCH_*.json files to compare")
        return 0

    all_ok = True
    for name in names:
        lines, ok = compare_file(name, args.tol)
        print("\n".join(lines))
        all_ok = all_ok and ok
    print("check_bench:", "OK" if all_ok else "FAILED (throughput "
          "regression beyond tolerance; see REGRESSION rows above)")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
