"""Perf-regression gate: row identity matching (exact and widened),
regression detection, and the added/missing-row tolerance -- driven through
``compare_docs`` so no git state or benchmark re-run is needed."""
import importlib.util
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "check_bench", REPO_ROOT / "scripts" / "check_bench.py")
check_bench = importlib.util.module_from_spec(_spec)
sys.modules["check_bench"] = check_bench
_spec.loader.exec_module(check_bench)


def _doc(rows, section="rows"):
    return {section: rows}


def test_exact_identity_match_flags_regression():
    base = _doc([{"T": 16, "S": 4, "policy": "tile",
                  "requests_per_s": 100.0}])
    ok_doc = _doc([{"T": 16, "S": 4, "policy": "tile",
                    "requests_per_s": 90.0}])
    lines, ok = check_bench.compare_docs("x.json", base, ok_doc, tol=0.25)
    assert ok and any("ok" in ln for ln in lines)
    bad_doc = _doc([{"T": 16, "S": 4, "policy": "tile",
                     "requests_per_s": 50.0}])
    lines, ok = check_bench.compare_docs("x.json", base, bad_doc, tol=0.25)
    assert not ok and any("REGRESSION" in ln for ln in lines)


def test_widened_identity_still_gates_against_predecessor():
    """A sweep that grows a new identity axis (e.g. ``inflight``) keeps
    gating: the fresh row whose identity strictly extends the committed
    row's compares against it; extra fan-out rows are added, not errors."""
    base = _doc([{"T": 16, "S": 1, "policy": "tile",
                  "requests_per_s": 100.0}])
    fresh = _doc([
        {"T": 16, "S": 1, "policy": "tile", "inflight": 1,
         "requests_per_s": 40.0},                      # would-be regression
        {"T": 16, "S": 1, "policy": "tile", "inflight": 2,
         "requests_per_s": 150.0},                     # new fan-out row
    ])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert not ok
    text = "\n".join(lines)
    assert "identity widened" in text and "REGRESSION" in text
    assert any(ln.strip().startswith("NEW") and "inflight=2" in ln
               for ln in lines)
    # a healthy widened row passes
    fresh["rows"][0]["requests_per_s"] = 95.0
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok


def test_exact_match_claims_baseline_before_widened_rows():
    """A widened row must never steal the baseline an exact fresh row
    still matches -- exact matches claim first, regardless of emission
    order, so the exact row's regression stays gated."""
    base = _doc([{"T": 16, "requests_per_s": 100.0}])
    fresh = _doc([
        {"T": 16, "inflight": 2, "requests_per_s": 150.0},  # widened, first
        {"T": 16, "requests_per_s": 40.0},                  # exact, regressed
    ])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert not ok
    assert any("REGRESSION" in ln and "identity widened" not in ln
               for ln in lines)
    assert any(ln.strip().startswith("NEW") and "inflight=2" in ln
               for ln in lines)


def test_identity_less_base_row_is_never_a_subset_match():
    """A committed row with no identity fields at all (all floats) would be
    a 'subset' of everything; it must stay unmatched instead of gating an
    unrelated widened row."""
    base = _doc([{"requests_per_s": 100.0}])
    fresh = _doc([{"T": 16, "inflight": 2, "requests_per_s": 10.0}])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok
    text = "\n".join(lines)
    assert "NEW" in text and "MISSING" in text


def test_ambiguous_subset_match_stays_unmatched():
    """Two committed candidates for one widened row: refuse to guess."""
    base = _doc([
        {"T": 16, "policy": "tile", "requests_per_s": 100.0},
        {"S": 4, "policy": "tile", "requests_per_s": 100.0},
    ])
    fresh = _doc([{"T": 16, "S": 4, "policy": "tile", "inflight": 1,
                   "requests_per_s": 10.0}])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok                       # unmatched rows never fail the gate
    assert any(ln.strip().startswith("NEW") for ln in lines)
    assert sum("MISSING" in ln for ln in lines) == 2


def test_added_and_missing_rows_never_fail():
    base = _doc([{"backend": "pallas", "us_per_call": 10.0}])
    fresh = _doc([{"backend": "interpret", "us_per_call": 900.0}])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok
    text = "\n".join(lines)
    assert "NEW" in text and "MISSING" in text


def test_lower_is_better_metrics():
    base = _doc([{"name": "mm", "us_per_call": 100.0}])
    slower = _doc([{"name": "mm", "us_per_call": 200.0}])
    lines, ok = check_bench.compare_docs("x.json", base, slower, tol=0.25)
    assert not ok
    faster = _doc([{"name": "mm", "us_per_call": 50.0}])
    lines, ok = check_bench.compare_docs("x.json", base, faster, tol=0.25)
    assert ok


def test_metric_must_be_shared_by_both_sides():
    """A row that grew a preferred metric the committed copy predates is
    compared on the first metric both rows carry."""
    base = _doc([{"name": "mm", "us_per_call": 100.0}])
    fresh = _doc([{"name": "mm", "requests_per_s": 1.0,
                   "us_per_call": 90.0}])
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok and any("us_per_call" in ln for ln in lines)


# ---------------------------------------------------------------------------
# the intra-file autotune gate (BENCH_autotune_gain.json)
# ---------------------------------------------------------------------------

def _autotune_doc(default_rps, tuned):
    rows = [{"plan": "default", "T": 16, "requests_per_s": default_rps}]
    rows += [{"plan": label, "T": 16, "requests_per_s": rps}
             for label, rps in tuned]
    return _doc(rows)


def test_autotune_gate_tuned_above_default_passes():
    doc = _autotune_doc(100.0, [("analytic", 140.0), ("measured", 150.0)])
    lines, ok = check_bench.autotune_gate("a.json", doc, tol=0.25)
    assert ok and sum("ok" in ln for ln in lines) == 2


def test_autotune_gate_tuned_within_tolerance_passes():
    """Tuned may sit slightly below default (measurement noise) as long as
    it stays within the tolerance band."""
    doc = _autotune_doc(100.0, [("measured", 80.0)])
    lines, ok = check_bench.autotune_gate("a.json", doc, tol=0.25)
    assert ok


def test_autotune_gate_tuned_losing_to_default_fails():
    doc = _autotune_doc(100.0, [("analytic", 130.0), ("measured", 60.0)])
    lines, ok = check_bench.autotune_gate("a.json", doc, tol=0.25)
    assert not ok
    assert any("BELOW-DEFAULT" in ln and "measured" in ln for ln in lines)


def test_autotune_gate_without_default_row_skips():
    doc = _doc([{"plan": "measured", "T": 16, "requests_per_s": 10.0}])
    lines, ok = check_bench.autotune_gate("a.json", doc, tol=0.25)
    assert ok and any("skipped" in ln for ln in lines)


# ---------------------------------------------------------------------------
# the intra-file cold-start gate (BENCH_cold_start.json)
# ---------------------------------------------------------------------------

def _cold_start_doc(cold_ms, warm):
    rows = [{"mode": "cold", "ttfr_ms": cold_ms}]
    rows += [{"mode": mode, "ttfr_ms": ms} for mode, ms in warm]
    return _doc(rows)


def test_cold_start_gate_warm_fast_passes():
    doc = _cold_start_doc(650.0, [("warm_disk", 45.0), ("warmup", 3.0)])
    lines, ok = check_bench.cold_start_gate("c.json", doc, tol=0.25)
    assert ok and sum(ln.strip().startswith("ok") for ln in lines) == 2


def test_cold_start_gate_warm_within_slack_passes():
    """80% reduction required, tolerance as slack on the remainder: at tol
    0.25 a warm TTFR up to 45% of cold still passes."""
    doc = _cold_start_doc(100.0, [("warm_disk", 44.0)])
    lines, ok = check_bench.cold_start_gate("c.json", doc, tol=0.25)
    assert ok


def test_cold_start_gate_still_cold_warm_row_fails():
    doc = _cold_start_doc(100.0, [("warm_disk", 90.0), ("warmup", 3.0)])
    lines, ok = check_bench.cold_start_gate("c.json", doc, tol=0.25)
    assert not ok
    assert any("STILL-COLD" in ln and "warm_disk" in ln for ln in lines)


def test_cold_start_gate_without_cold_row_skips():
    doc = _doc([{"mode": "warmup", "ttfr_ms": 3.0}])
    lines, ok = check_bench.cold_start_gate("c.json", doc, tol=0.25)
    assert ok and any("skipped" in ln for ln in lines)


def test_ttfr_rows_gate_lower_is_better():
    """The cold_start rows' ttfr_ms is a first-class (lower-is-better)
    metric for the row-vs-HEAD diff too."""
    base = _doc([{"mode": "warm_disk", "ttfr_ms": 40.0}])
    fresh = _doc([{"mode": "warm_disk", "ttfr_ms": 90.0}])
    lines, ok = check_bench.compare_docs("c.json", base, fresh, tol=0.25)
    assert not ok and any("REGRESSION" in ln for ln in lines)


# ---------------------------------------------------------------------------
# the intra-file goodput gate (BENCH_goodput.json) + the shed_frac band
# ---------------------------------------------------------------------------

def _goodput_doc(loads, fairness):
    """loads: {load_pct: (shed_rps, none_rps)};
    fairness: (wfq_worst, fifo_worst) or None."""
    rows = []
    for load, (shed, none) in sorted(loads.items()):
        rows.append({"suite": "load", "admission": "shed",
                     "load_pct": load, "goodput_rps": shed})
        rows.append({"suite": "load", "admission": "none",
                     "load_pct": load, "goodput_rps": none})
    if fairness is not None:
        wfq, fifo = fairness
        rows.append({"suite": "fairness", "scheduler": "wfq",
                     "goodput_rps": 300.0,
                     "worst_tenant_goodput_rps": wfq})
        rows.append({"suite": "fairness", "scheduler": "fifo",
                     "goodput_rps": 300.0,
                     "worst_tenant_goodput_rps": fifo})
    return _doc(rows)


def test_goodput_gate_healthy_rows_pass():
    doc = _goodput_doc({60: (230.0, 235.0), 150: (360.0, 220.0),
                        250: (380.0, 120.0)}, fairness=(16.0, 4.0))
    lines, ok = check_bench.goodput_gate("g.json", doc, tol=0.25)
    assert ok
    # sub-saturation pairs are exempt: shed ~ none there by design
    assert not any("load[60%]" in ln for ln in lines)
    assert any("load[150%]" in ln for ln in lines)
    assert any("fairness" in ln for ln in lines)


def test_goodput_gate_admission_not_winning_fails():
    """Past saturation, admission must beat unbounded queueing by 1.3x
    (minus slack; 0.975x at tol 0.25) -- a shed row that *loses* to the
    none row fails."""
    doc = _goodput_doc({250: (110.0, 120.0)}, fairness=None)
    lines, ok = check_bench.goodput_gate("g.json", doc, tol=0.25)
    assert not ok
    assert any("NO-ADMISSION-WIN" in ln for ln in lines)


def test_goodput_gate_admission_within_slack_passes():
    """1.3x floor with tol 0.25 as multiplicative slack -> 0.975x floor:
    a near-tie passes, leaving headroom for noisy hosts."""
    doc = _goodput_doc({150: (118.0, 120.0)}, fairness=None)
    lines, ok = check_bench.goodput_gate("g.json", doc, tol=0.25)
    assert ok


def test_goodput_gate_unfair_wfq_fails():
    doc = _goodput_doc({}, fairness=(5.0, 4.0))
    lines, ok = check_bench.goodput_gate("g.json", doc, tol=0.25)
    assert not ok
    assert any("UNFAIR" in ln for ln in lines)


def test_goodput_gate_without_rows_skips():
    lines, ok = check_bench.goodput_gate("g.json", _doc([]), tol=0.25)
    assert ok and any("skipped" in ln for ln in lines)


def test_goodput_rows_gate_higher_is_better():
    base = _doc([{"suite": "load", "admission": "shed", "load_pct": 150,
                  "goodput_rps": 360.0}])
    fresh = _doc([{"suite": "load", "admission": "shed", "load_pct": 150,
                   "goodput_rps": 100.0}])
    lines, ok = check_bench.compare_docs("g.json", base, fresh, tol=0.25)
    assert not ok and any("REGRESSION" in ln for ln in lines)


def test_shed_frac_band_growth_beyond_5pp_fails():
    """Goodput can hold steady while the server sheds ever more traffic;
    the shed_frac band catches that even when the rps diff passes."""
    base = _doc([{"suite": "load", "admission": "shed", "load_pct": 150,
                  "goodput_rps": 360.0, "shed_frac": 0.19}])
    fresh = _doc([{"suite": "load", "admission": "shed", "load_pct": 150,
                   "goodput_rps": 360.0, "shed_frac": 0.40}])
    lines, ok = check_bench.compare_docs("g.json", base, fresh, tol=0.25)
    assert not ok
    assert any("SHED-GREW" in ln for ln in lines)


def test_shed_frac_band_small_growth_and_shrink_pass():
    base = _doc([{"suite": "load", "admission": "shed", "load_pct": 150,
                  "goodput_rps": 360.0, "shed_frac": 0.19}])
    for frac in (0.22, 0.05):       # +3pp and a shrink both pass
        fresh = _doc([{"suite": "load", "admission": "shed",
                       "load_pct": 150, "goodput_rps": 360.0,
                       "shed_frac": frac}])
        lines, ok = check_bench.compare_docs("g.json", base, fresh,
                                             tol=0.25)
        assert ok, frac


# ---------------------------------------------------------------------------
# provenance metadata (benchmarks/common.emit_json stamps it; the gate
# must ignore it)
# ---------------------------------------------------------------------------

def test_provenance_block_is_not_a_row_source():
    """The provenance block describes the run (git SHA, emission time, jax
    version), not a measurement: it must never enter the row diff, so two
    docs differing only in provenance compare clean."""
    rows = [{"T": 16, "S": 4, "policy": "tile", "requests_per_s": 100.0}]
    base = {"rows": rows,
            "provenance": {"git_sha": "aaa", "emitted_at": "2026-01-01",
                           "jax_version": "0.4", "device_count": 1}}
    fresh = {"rows": rows,
             "provenance": {"git_sha": "bbb", "emitted_at": "2026-08-07",
                            "jax_version": "0.5", "device_count": 8}}
    assert [s for s, _ in check_bench.iter_rows(base)] == ["rows"]
    lines, ok = check_bench.compare_docs("x.json", base, fresh, tol=0.25)
    assert ok
    text = "\n".join(lines)
    assert "git_sha" not in text and "REGRESSION" not in text
    # even a list-of-dicts-shaped provenance block stays out of the diff
    weird = {"rows": rows, "provenance": [{"git_sha": "ccc"}]}
    assert [s for s, _ in check_bench.iter_rows(weird)] == ["rows"]


def test_emit_json_stamps_provenance(tmp_path, monkeypatch):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_common", REPO_ROOT / "benchmarks" / "common.py")
    common = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(common)
    monkeypatch.setattr(common, "REPO_ROOT", tmp_path)
    path = common.emit_json("provtest", {"rows": [{"T": 8, "x_us": 1.0}]})
    doc = __import__("json").loads(path.read_text())
    prov = doc["provenance"]
    assert set(prov) == {"git_sha", "emitted_at", "jax_version", "backend",
                        "device_count"}
    assert prov["jax_version"] and prov["device_count"] >= 1
    assert prov["emitted_at"].startswith("20")


# ---------------------------------------------------------------------------
# the intra-file controller gate (BENCH_controller_regret.json)
# ---------------------------------------------------------------------------

def _controller_doc(regret, swaps, measured=18, grid=72, budget=0.25):
    return _doc([
        {"suite": "regret", "scenario": "regime_shift",
         "regret_frac": regret, "swaps": swaps, "requests_per_s": 100.0},
        {"suite": "prune", "scenario": "bimodal",
         "measured_evals": measured, "grid_size": grid,
         "budget_frac": budget, "measured_frac": measured / grid},
    ])


def test_controller_gate_healthy_rows_pass():
    doc = _controller_doc(regret=0.01, swaps=3)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert ok
    assert any("regret[regime_shift]" in ln for ln in lines)
    assert any("prune[bimodal]" in ln for ln in lines)


def test_controller_gate_high_regret_fails():
    """Ceiling is 0.10 with multiplicative slack: 0.125 at tol 0.25."""
    doc = _controller_doc(regret=0.12, swaps=2)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert ok                               # inside the slack band
    doc = _controller_doc(regret=0.13, swaps=2)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert not ok and any("HIGH-REGRET" in ln for ln in lines)


def test_controller_gate_thrashing_fails_without_slack():
    doc = _controller_doc(regret=0.01, swaps=4)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert not ok and any("THRASHING" in ln for ln in lines)


def test_controller_gate_unpruned_search_fails_without_slack():
    doc = _controller_doc(regret=0.01, swaps=2, measured=19)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert not ok and any("NO-PRUNING" in ln for ln in lines)
    # exactly at the budget cap passes
    doc = _controller_doc(regret=0.01, swaps=2, measured=18)
    lines, ok = check_bench.controller_gate("k.json", doc, tol=0.25)
    assert ok


def test_controller_gate_without_rows_skips():
    lines, ok = check_bench.controller_gate("k.json", _doc([]), tol=0.25)
    assert ok and any("skipped" in ln for ln in lines)


def test_regret_frac_gates_lower_is_better():
    """regret_frac is a first-class lower-is-better metric for the
    row-vs-HEAD diff: a fresh copy with triple the regret regresses even
    when it still clears the intra-file ceiling."""
    base = _doc([{"suite": "regret", "scenario": "regime_shift",
                  "regret_frac": 0.01}])
    fresh = _doc([{"suite": "regret", "scenario": "regime_shift",
                   "regret_frac": 0.03}])
    lines, ok = check_bench.compare_docs("k.json", base, fresh, tol=0.25)
    assert not ok and any("REGRESSION" in ln for ln in lines)
    better = _doc([{"suite": "regret", "scenario": "regime_shift",
                    "regret_frac": 0.005}])
    lines, ok = check_bench.compare_docs("k.json", base, better, tol=0.25)
    assert ok
