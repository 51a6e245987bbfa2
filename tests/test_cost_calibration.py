"""Calibrated cost model: CostCoeffs load/apply/digest semantics, the
calibrate fit, the cost_check CI gate, roofline-efficiency reporting,
and the report.load_records missing-dir fix."""
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro import sparse
from repro.analysis import calibrate, report, roofline
from repro.analysis.hlo_cost import sddmm_cost_dict, spmm_cost_dict
from repro.analysis.roofline import V5E, route_efficiency
from repro.core import dispatch
from repro.core.bsr import BlockSparseMatrix

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "tools"))
import cost_check  # noqa: E402


@pytest.fixture
def _restore_coeffs():
    prev = dispatch.cost_coeffs()
    try:
        yield
    finally:
        dispatch.set_cost_coeffs(prev)


def _bsr(m=256, n=256, b=16, density=0.25, seed=0):
    return BlockSparseMatrix.random(
        jax.random.PRNGKey(seed), m, n, b, density=density)


# ---------------------------------------------------------------------------
# CostCoeffs: load / apply / digest / cache-key join
# ---------------------------------------------------------------------------

def test_load_missing_file_is_identity():
    c = dispatch.load_cost_coeffs("/nonexistent/cost_coeffs.json")
    assert c.is_identity
    assert c.digest == ""
    assert c.apply("static_pallas", 1e-6) == 1e-6


def test_load_garbage_is_identity(tmp_path):
    bad = tmp_path / "cost_coeffs.json"
    bad.write_text("{not json")
    assert dispatch.load_cost_coeffs(str(bad)).is_identity
    bad.write_text('{"routes": 42}')
    assert dispatch.load_cost_coeffs(str(bad)).is_identity


def test_apply_affine_and_unknown_route_passthrough():
    c = dispatch.CostCoeffs(route_scale={"static_xla": 2.0},
                            route_fixed_us={"static_xla": 5.0},
                            digest="abc")
    assert c.apply("static_xla", 1e-6) == pytest.approx(7e-6)
    # a route the fit never saw stays on the hand-tuned model
    assert c.apply("dynamic_xla", 3e-6) == pytest.approx(3e-6)


def test_digest_deterministic_and_sensitive():
    routes = {"static_xla": {"scale": 1.1, "fixed_us": 2.0, "n_obs": 9}}
    skew = {"imb_slope": 0.4}
    d1 = dispatch.coeffs_digest(routes, skew, 1)
    assert d1 == dispatch.coeffs_digest(routes, skew, 1)
    # diagnostic fields are excluded: same coefficients, same digest
    routes2 = {"static_xla": {"scale": 1.1, "fixed_us": 2.0,
                              "n_obs": 1, "median_rel_err": 0.5}}
    assert dispatch.coeffs_digest(routes2, skew, 1) == d1
    # any coefficient value change moves it
    routes3 = {"static_xla": {"scale": 1.2, "fixed_us": 2.0}}
    assert dispatch.coeffs_digest(routes3, skew, 1) != d1
    assert dispatch.coeffs_digest(routes, {"imb_slope": 0.5}, 1) != d1
    assert dispatch.coeffs_digest(routes, skew, 2) != d1


def test_file_roundtrip_through_loader(tmp_path):
    blob = {"version": 1,
            "routes": {"static_xla": {"scale": 1.5, "fixed_us": 2.5}},
            "skew": {"imb_knee": 1.5, "imb_slope": 0.5, "cv_knee": 0.3,
                     "cv_slope": 0.2, "cap": 2.5}}
    path = tmp_path / "cost_coeffs.json"
    path.write_text(json.dumps(blob))
    c = dispatch.load_cost_coeffs(str(path))
    assert not c.is_identity
    assert c.route_scale == {"static_xla": 1.5}
    assert c.route_fixed_us == {"static_xla": 2.5}
    assert (c.skew_imb_knee, c.skew_imb_slope) == (1.5, 0.5)
    assert (c.skew_cv_knee, c.skew_cv_slope, c.skew_cap) == (0.3, 0.2, 2.5)
    assert c.digest == dispatch.coeffs_digest(
        blob["routes"], blob["skew"], 1)


def test_calibrated_estimate_applies_affine(_restore_coeffs):
    dispatch.set_cost_coeffs(dispatch.IDENTITY_COEFFS)
    raw = dispatch._estimate("static_xla", 1024, 1024, 256, 16, 0.25,
                             "float32")
    dispatch.set_cost_coeffs(dispatch.CostCoeffs(
        route_scale={"static_xla": 2.0},
        route_fixed_us={"static_xla": 10.0}, digest="t"))
    cal = dispatch._estimate("static_xla", 1024, 1024, 256, 16, 0.25,
                             "float32")
    assert cal == pytest.approx(2.0 * raw + 10e-6)


def test_cache_key_joins_nonidentity_digest(_restore_coeffs):
    ctx = dispatch.DispatchContext()
    args = ("static", 1024, 1024, 256, 16, 0.25, "float32", ctx)
    dispatch.set_cost_coeffs(dispatch.IDENTITY_COEFFS)
    key_id = dispatch._cache_key(*args)
    assert "coeffs" not in key_id
    dispatch.set_cost_coeffs(dispatch.CostCoeffs(digest="deadbeef0000"))
    key_cal = dispatch._cache_key(*args)
    assert key_cal[-2:] == ("coeffs", "deadbeef0000")
    assert key_cal[:-2] == key_id


def test_plan_fingerprint_changes_on_refit(tmp_path, _restore_coeffs):
    sparse.configure(str(tmp_path))
    bsr = _bsr()
    try:
        dispatch.set_cost_coeffs(dispatch.IDENTITY_COEFFS)
        k1 = sparse.plan(bsr, 64).key
        sparse.reset()
        dispatch.set_cost_coeffs(dispatch.CostCoeffs(digest="deadbeef0000"))
        k2 = sparse.plan(bsr, 64).key
    finally:
        sparse.reset()
        sparse.configure(None)
    assert k1 != k2          # a refit orphans persisted verdicts


def test_set_cost_coeffs_none_reloads_committed_file(_restore_coeffs):
    dispatch.set_cost_coeffs(dispatch.CostCoeffs(digest="t"))
    dispatch.set_cost_coeffs(None)
    committed = json.load(open(os.path.join(
        REPO, "benchmarks", "baselines", "cost_coeffs.json")))
    assert dispatch.cost_coeffs().digest == committed["digest"]


# ---------------------------------------------------------------------------
# calibrate: corpus extraction + fit
# ---------------------------------------------------------------------------

def test_committed_corpus_loads_and_fit_is_committed_coeffs():
    obs = calibrate.load_corpus()
    assert len(obs) >= 50
    assert {o.fig for o in obs} <= set(calibrate.EXTRACTORS)
    blob = calibrate.fit(obs)
    # the corpus is the analytic model's own output, so every fitted
    # correction snaps to identity...
    for route, c in blob["routes"].items():
        assert c["scale"] == 1.0, route
        assert c["fixed_us"] == 0.0, route
    assert blob["fit_median_rel_err"] < 0.01
    # ...and a refit of the unchanged corpus reproduces the committed
    # file exactly (idempotence: CI can re-run `calibrate --update`)
    committed = json.load(open(os.path.join(
        calibrate.BASELINE_DIR, "cost_coeffs.json")))
    assert blob["digest"] == committed["digest"]
    assert blob["routes"] == committed["routes"]
    assert blob["skew"] == committed["skew"]


def test_load_corpus_bad_glob_raises():
    with pytest.raises(FileNotFoundError, match="matched nothing"):
        calibrate.load_corpus(["/nonexistent/BENCH_*.json"])


def test_fit_recovers_synthetic_scale(_restore_coeffs):
    # measurements at 1.3x the raw model (well outside SCALE_SNAP) over
    # shapes with real spread: OLS must recover scale~1.3, intercept~0
    shapes = [(256, 64), (512, 128), (1024, 256), (2048, 256), (4096, 512)]
    obs = []
    with calibrate._identity_model():
        for m, n in shapes:
            o = calibrate.Observation(
                fig="dispatch", route="static_xla", m=m, k=m, n=n,
                b=16, density=0.25)
            obs.append(dataclasses.replace(
                o, measured_us=1.3 * calibrate._raw_us(o)))
    blob = calibrate.fit(obs)
    c = blob["routes"]["static_xla"]
    assert c["scale"] == pytest.approx(1.3, abs=0.02)
    assert c["fixed_us"] == 0.0
    assert c["median_rel_err"] < 0.01


def test_fit_empty_corpus_raises():
    with pytest.raises(ValueError, match="empty corpus"):
        calibrate.fit([])


# ---------------------------------------------------------------------------
# cost_check: the CI gate
# ---------------------------------------------------------------------------

def test_cost_check_passes_at_head():
    rep = cost_check.run_check()
    assert rep["pass"], rep
    assert rep["n_obs"] >= 50
    assert rep["median_rel_err"] <= 0.15
    assert rep["crossover_flips"] == []
    assert rep["coeffs"]["digest"] == dispatch.cost_coeffs().digest


def test_cost_check_catches_broken_calibration(_restore_coeffs):
    # 5x-ing one route must both blow the error gate and flip at least
    # one corpus race -- the two failure modes the gate exists for
    dispatch.set_cost_coeffs(dispatch.CostCoeffs(
        route_scale={r: 5.0 for r in dispatch.ROUTES},
        digest="broken000000"))
    rep = cost_check.run_check()
    assert not rep["pass"]
    assert rep["median_rel_err"] > 0.15


def test_cost_check_detects_crossover_flip(_restore_coeffs):
    # slow down only the static routes: dense wins races it lost in the
    # corpus -> flips reported even though many estimates stay exact
    dispatch.set_cost_coeffs(dispatch.CostCoeffs(
        route_scale={"static_xla": 4.0, "static_pallas": 4.0,
                     "static_balanced": 4.0}, digest="flip00000000"))
    rep = cost_check.run_check()
    assert rep["crossover_flips"], "expected at least one flipped race"
    assert not rep["pass"]
    flip = rep["crossover_flips"][0]
    assert {"fig", "point", "corpus", "model"} <= set(flip)


def test_cost_check_rc2_without_coeffs_file(tmp_path):
    import subprocess
    env = dict(os.environ,
               REPRO_COST_COEFFS=str(tmp_path / "nope.json"),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cost_check.py")],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "NO COEFFICIENTS" in r.stdout


# ---------------------------------------------------------------------------
# roofline efficiency
# ---------------------------------------------------------------------------

def test_route_efficiency_at_bound():
    cost = {"flops": V5E.peak_flops_bf16, "bytes": 0,
            "collective_bytes": 0}           # exactly 1s compute bound
    eff = route_efficiency(1.0, cost, V5E)
    assert eff["dominant"] == "compute"
    assert eff["efficiency"] == pytest.approx(1.0)
    assert eff["headroom"] == pytest.approx(1.0)
    assert not eff["flagged"]


def test_route_efficiency_flags_headroom():
    cost = {"flops": V5E.peak_flops_bf16, "bytes": 0,
            "collective_bytes": 0}
    eff = route_efficiency(10.0, cost, V5E)
    assert eff["headroom"] == pytest.approx(10.0)
    assert eff["efficiency"] == pytest.approx(0.1)
    assert eff["flagged"]
    assert not route_efficiency(10.0, cost, V5E,
                                flag_headroom=20.0)["flagged"]


def test_route_efficiency_memory_bound():
    cost = {"flops": 1.0, "bytes": V5E.hbm_bw,
            "collective_bytes": 0}           # exactly 1s memory bound
    eff = route_efficiency(2.0, cost, V5E)
    assert eff["dominant"] == "memory"
    assert eff["bound_seconds"] == pytest.approx(1.0)


def test_peaks_keyed_by_device_kind():
    assert roofline.peaks_for("tpu", "TPU v5 lite") is V5E
    assert roofline.peaks_for("cpu", "cpu") is None
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("tpu", "TPU v99")


def test_spmm_sddmm_cost_dicts():
    c = spmm_cost_dict(64, 128, 32, density=0.25, bytes_el=4)
    assert c["flops"] == 2 * 64 * 128 * 32 * 0.25
    assert c["bytes"] == (64 * 128 * 0.25 + 128 * 32 + 64 * 32) * 4
    s = sddmm_cost_dict(64, 128, 32, density=0.25, bytes_el=2)
    assert s["flops"] == 2 * 64 * 128 * 32 * 0.25
    assert s["bytes"] == (64 * 32 + 128 * 32 + 64 * 128 * 0.25) * 2
    for d in (c, s):     # analyzer-shaped: roofline_terms accepts both
        assert d["collective_bytes"] == 0 and d["warnings"] == []


def test_plan_explain_reports_roofline(tmp_path, monkeypatch):
    sparse.configure(str(tmp_path))
    try:
        p = sparse.plan(_bsr(), 64)
        # the host CPU has no published peaks: no figures, and why
        off = p.explain()["roofline"]
        monkeypatch.setattr(roofline, "device_peaks", lambda: V5E)
        roof = p.explain()["roofline"]
    finally:
        sparse.reset()
        sparse.configure(None)
    assert off["hw"] is None and off["chosen"] is None
    assert not off["routes"] and "cpu" in off["unavailable"]
    assert roof["hw"] == V5E.name
    assert roof["chosen"] is not None
    assert roof["chosen"] == roof["routes"][p.route]
    for r, e in roof["routes"].items():
        assert r not in ("static_tp", "static_tp_shardmap")
        assert e["bound_us"] > 0
        assert 0 < e["efficiency"] <= 1.0
        assert e["flagged"] == (e["headroom"] > roof["flag_headroom"])
    assert roof["kernel_work"] == sorted(
        r for r, e in roof["routes"].items() if e["flagged"])
    assert "roofline:" in sparse.format_plan(p)


def test_roofline_report_totals(tmp_path, monkeypatch):
    monkeypatch.setattr(roofline, "device_peaks", lambda: V5E)
    sparse.configure(str(tmp_path))
    try:
        sparse.plan(_bsr(), 64)
        sparse.plan(_bsr(m=512, n=512, seed=1), 128)
        rep = sparse.roofline_report()
    finally:
        sparse.reset()
        sparse.configure(None)
    assert rep["totals"]["plans"] == 2
    assert rep["totals"]["min_chosen_efficiency"] is not None
    assert 0 < rep["totals"]["min_chosen_efficiency"] <= 1.0
    assert isinstance(rep["totals"]["kernel_work_routes"], list)
    for per in rep["per_plan"].values():
        assert {"route", "chosen", "kernel_work"} <= set(per)


def test_dense_routes_priced_at_full_density(tmp_path):
    # dense_xla executes the full m*k*n product regardless of operand
    # sparsity: its bound must not borrow the sparse discount, or every
    # dense route would flag as kernel work on sparse problems
    sparse.configure(str(tmp_path))
    try:
        p = sparse.plan(_bsr(density=0.125), 64)
    finally:
        sparse.reset()
        sparse.configure(None)
    dense = p.spec.roofline_cost("dense_xla")
    sparse_c = p.spec.roofline_cost("static_xla")
    assert dense["flops"] == pytest.approx(8 * sparse_c["flops"], rel=0.01)


# ---------------------------------------------------------------------------
# report.load_records missing-dir fix
# ---------------------------------------------------------------------------

def test_load_records_missing_dir_raises(tmp_path, monkeypatch):
    missing = str(tmp_path / "dryrun")
    monkeypatch.setattr(report, "DRYRUN_DIR", missing)
    with pytest.raises(FileNotFoundError, match="dry-run records"):
        report.load_records()
    try:
        report.load_records()
    except FileNotFoundError as e:     # the path must be actionable
        assert os.path.normpath(missing) in str(e)


def test_load_records_empty_dir_returns_empty(tmp_path, monkeypatch):
    d = tmp_path / "dryrun"
    d.mkdir()
    monkeypatch.setattr(report, "DRYRUN_DIR", str(d))
    assert report.load_records() == []
