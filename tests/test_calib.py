"""Calibration subsystem (core/calib/): records, harness, fit, online.

Pins the ISSUE-10 contracts: char-DB round-trip serialization with
provenance preserved, merge keeping the stronger provenance, stub-backend
byte-determinism (two runs identical), the calibrated-beats-seed error
reduction on every SKU, the trace-doc consumption path, the online EWMA
tightening predictions through a real Cluster run, and calibration-free
cells staying byte-identical (calibrator is opt-in).
"""
import json

import pytest

from repro.core.calib import (
    CharDB,
    CharRecord,
    KernelBackend,
    OnlineCalibrator,
    StubBackend,
    calibration_report,
    fit_from_error_doc,
    fit_residuals,
    miso_probe_keys,
    refine_db,
    run_calibration,
    seed_provenance,
    step_error_doc,
    step_error_rows,
    with_profile_interpolation,
)
from repro.core.device import SKUS, get_sku
from repro.launch.simulate import synthetic_char_db


def _rec(arch="a", shape="sim", profile="1g.5gb", **kw):
    base = dict(
        arch=arch, shape=shape, profile=profile, step_s=1.0, compute_s=0.9,
        memory_s=0.3, collective_s=0.1, peak_bytes_per_device=1e9, fits=True,
    )
    base.update(kw)
    return CharRecord(**base)


# -- records: round-trip + provenance ---------------------------------------


def test_chardb_json_round_trip_preserves_everything():
    db = CharDB("a100-40gb", seed=7)
    db.add(_rec(provenance="measured", source="stub", n_samples=3))
    db.add(_rec(profile="7g.40gb", provenance="refined", source="fit"))
    again = CharDB.loads(db.dumps())
    assert again == db
    assert again.seed == 7
    assert again.records[("a", "sim", "1g.5gb")].provenance == "measured"
    assert again.records[("a", "sim", "1g.5gb")].n_samples == 3


def test_plain_db_round_trip_and_extrapolated_default():
    # a bare hand-seeded dict loads as extrapolated — the tentpole's pin
    plain = {("a", "sim", "1g.5gb"): {"fits": True, "step_s": 1.0,
                                      "compute_s": 0.9, "memory_s": 0.3,
                                      "collective_s": 0.1,
                                      "peak_bytes_per_device": 1e9}}
    db = CharDB.from_plain_db(plain, sku="a100-40gb")
    rec = db.records[("a", "sim", "1g.5gb")]
    assert rec.provenance == "extrapolated"
    out = db.to_plain_db()[("a", "sim", "1g.5gb")]
    # scheduler-facing keys survive; provenance rides along inertly
    for key in plain[("a", "sim", "1g.5gb")]:
        assert out[key] == plain[("a", "sim", "1g.5gb")][key]
    assert out["provenance"] == "extrapolated"


def test_seed_catalog_carries_per_sku_provenance():
    # satellite: h100/a30 entries are visibly extrapolated; only the
    # paper's device is measured
    for sku, expected in (("a100-40gb", "measured"),
                          ("h100-80gb", "extrapolated"),
                          ("a30-24gb", "extrapolated")):
        assert seed_provenance(sku) == expected
        db = synthetic_char_db(sku=sku)
        assert all(rec["provenance"] == expected for rec in db.values())


def test_unknown_provenance_rejected():
    with pytest.raises(ValueError):
        _rec(provenance="vibes")
    with pytest.raises(ValueError):
        CharDB.from_doc({"schema": "something/v9", "sku": "x", "records": []})


def test_merge_keeps_stronger_provenance():
    db = CharDB("a100-40gb")
    db.add(_rec(provenance="measured", step_s=1.0, n_samples=3))
    # weaker incoming record must not clobber the measurement
    changed = db.merge([_rec(provenance="refined", step_s=9.9)])
    assert changed == 0
    assert db.records[("a", "sim", "1g.5gb")].step_s == 1.0
    # stronger incoming record upgrades
    changed = db.merge([_rec(provenance="measured", step_s=2.0, n_samples=5)])
    assert changed == 1
    assert db.records[("a", "sim", "1g.5gb")].step_s == 2.0


# -- harness: stub backend + calibration loop --------------------------------


def test_stub_backend_byte_determinism():
    # two full passes, two separate backends, same seed -> identical JSON
    def one_pass():
        db = synthetic_char_db()
        backend = StubBackend(db, seed=3)
        return run_calibration(db, backend, seed=3).calibrated.dumps()

    assert one_pass() == one_pass()


def test_stub_backend_seed_changes_truth():
    db = synthetic_char_db()
    key = next(iter(sorted(db)))
    t0 = StubBackend(db, seed=0).true_step_s(key)
    t1 = StubBackend(db, seed=1).true_step_s(key)
    assert t0 != t1


@pytest.mark.parametrize("sku_name", sorted(SKUS))
def test_calibrated_beats_seed_on_every_sku(sku_name):
    # the acceptance inequality: strictly lower mean |rel err| than the
    # hand-seeded catalog against the stub's ground truth
    dev = get_sku(sku_name)
    db = synthetic_char_db(sku=dev)
    backend = StubBackend(db, sku=dev, seed=0)
    result = run_calibration(db, backend, sku=dev, seed=0)
    score = calibration_report(result, backend.true_step_s)
    assert score["calibrated_mean_abs_rel_err"] < score["seed_mean_abs_rel_err"]
    # and not marginally: the fit removes the systematic bias
    assert score["error_reduction"] > 0.5
    # measurements landed with measured provenance at the probe keys
    prov = score["provenance"]
    assert prov.get("measured", 0) == len(miso_probe_keys(db, dev))


def test_probe_plan_is_full_plus_smallest():
    dev = get_sku("a100-40gb")
    db = synthetic_char_db(sku=dev)
    keys = miso_probe_keys(db, dev)
    profiles = {k[2] for k in keys}
    assert profiles == {dev.profile_order[0], dev.full_profile}
    archs = {k[0] for k in keys}
    assert len(keys) == 2 * len(archs)


def test_refine_never_overwrites_backend_measurements():
    db = CharDB("a100-40gb")
    db.add(_rec(provenance="measured", step_s=1.0, n_samples=3))
    db.add(_rec(profile="7g.40gb", provenance="extrapolated", step_s=2.0))
    fit = fit_residuals([("a", "1g.5gb", 1.5, 1.0),
                         ("a", "7g.40gb", 3.0, 2.0)], sku="a100-40gb")
    out = refine_db(db, fit)
    assert out.records[("a", "sim", "1g.5gb")].step_s == 1.0  # untouched
    assert out.records[("a", "sim", "1g.5gb")].provenance == "measured"
    assert out.records[("a", "sim", "7g.40gb")].provenance == "refined"


# -- fit: residuals, interpolation, trace-doc consumption --------------------


def test_fit_recovers_systematic_scale():
    pairs = [("m1", "1g.5gb", 1.3, 1.0), ("m1", "7g.40gb", 1.3, 1.0),
             ("m2", "1g.5gb", 2.6, 2.0), ("m2", "7g.40gb", 2.6, 2.0)]
    fit = fit_residuals(pairs, sku="a100-40gb")
    assert fit.correction("m1", "1g.5gb") == pytest.approx(1.3)
    assert fit.correction("m2", "7g.40gb") == pytest.approx(1.3)
    assert fit.correction("unseen-arch", "unseen-prof") == 1.0


def test_profile_interpolation_fills_between_endpoints():
    fit = fit_residuals(
        [("m", "1g.5gb", 1.2, 1.0), ("m", "7g.40gb", 1.0, 1.0)],
        sku="a100-40gb",
    )
    fracs = {"1g.5gb": 1 / 8, "2g.10gb": 2 / 8, "3g.20gb": 4 / 8,
             "7g.40gb": 1.0}
    filled = with_profile_interpolation(fit, fracs)
    c1, c2, c3, c7 = (filled.correction("m", p) for p in
                      ("1g.5gb", "2g.10gb", "3g.20gb", "7g.40gb"))
    # measured endpoints reproduce exactly (the arch scale and the profile
    # residual compose back to the observed ratio), interpolated profiles
    # land strictly between and monotone along the slice fraction
    assert c1 == pytest.approx(1.2) and c7 == pytest.approx(1.0)
    assert c1 > c2 > c3 > c7


def test_step_error_doc_round_trip_feeds_fit():
    # the report's machine-readable table is exactly what the harness fits
    # from (satellite: no re-derived aggregation)
    samples = [
        {"arch": "m", "profile": "1g.5gb", "measured_s": 1.2, "predicted_s": 1.0},
        {"arch": "m", "profile": "1g.5gb", "measured_s": 1.2, "predicted_s": 1.0},
        {"arch": "m", "profile": "7g.40gb", "measured_s": 0.9, "predicted_s": 1.0},
    ]
    rows = step_error_rows(samples)
    assert [r["n"] for r in rows] == [2, 1]
    doc = json.loads(json.dumps(step_error_doc(samples, meta={"seed": 0})))
    fit = fit_from_error_doc(doc, sku="a100-40gb")
    assert fit.correction("m", "1g.5gb") == pytest.approx(1.2)
    assert fit.correction("m", "7g.40gb") == pytest.approx(0.9)
    with pytest.raises(ValueError):
        fit_from_error_doc({"schema": "nope", "rows": []}, sku="a100-40gb")


# -- online: EWMA refinement ------------------------------------------------


def test_online_calibrator_converges_and_is_deterministic():
    def run():
        c = OnlineCalibrator()
        errs = []
        base, true = 1.0, 1.4  # seed underpredicts by 40%
        for t in range(40):
            pred = c.correct(base, sku="s", arch="m", profile="p")
            errs.append(abs(pred - true) / true)
            c.observe(sku="s", arch="m", profile="p",
                      measured_s=true, predicted_s=pred, t_s=float(t))
        return errs, c.snapshot()

    errs1, snap1 = run()
    errs2, snap2 = run()
    assert errs1 == errs2 and snap1 == snap2  # pure fold, no clocks
    assert errs1[-1] < 0.01 < errs1[0]  # converged onto the true bias
    assert snap1["residuals"][0]["residual"] == pytest.approx(1.4, rel=0.01)


def test_online_calibrator_clamps_corrupt_samples():
    c = OnlineCalibrator(alpha=1.0, bound=2.0)
    c.observe(sku="s", arch="m", profile="p", measured_s=1e9, predicted_s=1.0)
    assert c.residual(sku="s", arch="m", profile="p") == 2.0
    # non-positive samples are ignored entirely
    c2 = OnlineCalibrator()
    c2.observe(sku="s", arch="m", profile="p", measured_s=0.0, predicted_s=1.0)
    assert c2.n_observed == 0


def test_cluster_observe_step_feeds_calibrator():
    # the integration hook: a Cluster run with a calibrator attached folds
    # observe_step samples in, and predict_step output moves accordingly
    from repro.core.cluster import Cluster
    from repro.core.instance import JobSpec
    from repro.core.sharing import CollocationMode
    from repro.launch.simulate import SIM_SUITE

    db = synthetic_char_db()
    calib = OnlineCalibrator()
    cl = Cluster(db, [("d0", CollocationMode.MIG)], calibrator=calib)
    spec = JobSpec("j0", "granite-3-2b", SIM_SUITE)
    cl.submit(spec, 0.0, epochs=1)
    cl.run_until(0.0)
    dev = cl.devices["d0"]
    assert dev.scheduler.calibrator is calib
    prof = dev.assignments["j0"].placement.profile
    base = dev.scheduler.predict_step(spec, prof)
    # the device consistently runs 30% slower than the char DB claims
    true_s = base * 1.3
    for i in range(30):
        cl.observe_step("j0", true_s, at_s=0.001 * (i + 1))
    assert calib.n_observed == 30
    corrected = dev.scheduler.predict_step(spec, prof)
    assert abs(corrected - true_s) / true_s < 0.02  # tightened onto truth
    assert abs(base - true_s) / true_s > 0.2


def test_cluster_without_calibrator_is_byte_identical():
    # the acceptance bar: calibration-free cells do not move at all
    from repro.launch.simulate import run_cell

    a = run_cell("train_serve_mix", "all-mig", seed=0, n_jobs=10, n_devices=2)
    b = run_cell("train_serve_mix", "all-mig", seed=0, n_jobs=10, n_devices=2)
    assert json.dumps(a, sort_keys=True, default=str) == json.dumps(
        b, sort_keys=True, default=str
    )


# -- CLI artifacts -----------------------------------------------------------


def test_calibrate_cli_writes_deterministic_artifacts(tmp_path):
    from repro.launch.calibrate import main

    out1, out2 = tmp_path / "one", tmp_path / "two"
    assert main(["--out", str(out1), "--skus", "a100-40gb,a30-24gb"]) == 0
    assert main(["--out", str(out2), "--skus", "a100-40gb,a30-24gb"]) == 0
    names = sorted(p.name for p in out1.iterdir())
    assert names == ["_summary.json", "calib_db__a100-40gb.json",
                     "calib_db__a30-24gb.json"]
    for name in names:
        assert (out1 / name).read_bytes() == (out2 / name).read_bytes()
    summary = json.loads((out1 / "_summary.json").read_text())
    for sku, s in summary["skus"].items():
        card = s["scorecard"]
        assert card["calibrated_mean_abs_rel_err"] < card["seed_mean_abs_rel_err"]
        online = s["online"]
        assert (online["last_step_mean_abs_rel_err"]
                < online["first_step_mean_abs_rel_err"])
    # the written DB is a valid versioned document that loads back
    db = CharDB.loads((out1 / "calib_db__a100-40gb.json").read_text())
    assert db.sku == "a100-40gb" and len(db) == 40


def test_kernel_backend_times_compiled_kernels_on_tpu(monkeypatch):
    """The measured path times the interpreter only off the chip: on a TPU
    backend it asks kernel_bench for the compiled Pallas kernels."""
    import jax

    assert jax.default_backend() == "cpu"
    assert KernelBackend.kernel_mode() == "interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert KernelBackend.kernel_mode() == "tpu"
