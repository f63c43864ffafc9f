"""Perf-regression sentry tests (ISSUE 12): tools/perf_report.py
wired into tier-1 like the chaos_check/fleet_report selftests, plus
unit coverage of the comparison rules (spread-aware thresholds,
cross-environment refusal, comparable=false skip)."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cli():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import perf_report
    finally:
        sys.path.pop(0)
    return perf_report


def _rec(metric, value, spread=0.02, reps=3, capture_id="envA", **kw):
    rec = {"metric": metric, "value": value, "unit": "u",
           "vs_baseline": 1.0, "reps": reps, "spread": spread,
           "capture_id": capture_id}
    rec.update(kw)
    return rec


BASE = [("BENCH_r90.json", [_rec("tok_s", 1000.0)])]


class TestCompare:
    def test_regression_caught_with_named_finding(self, cli):
        rep = cli.compare([_rec("tok_s", 800.0)], BASE)
        assert len(rep["findings"]) == 1
        f = rep["findings"][0]
        assert f["code"] == "perf-regression" and f["metric"] == "tok_s"
        assert f["baseline_capture"] == "BENCH_r90.json"
        assert "20.0%" in f["message"]

    def test_drop_inside_spread_band_passes(self, cli):
        # allowed = max(3 * 0.02, 0.05) = 6%; a 4% drop is noise
        rep = cli.compare([_rec("tok_s", 960.0)], BASE)
        assert rep["findings"] == [] and rep["compared"] == 1

    def test_noisier_side_widens_the_band(self, cli):
        noisy_base = [("b.json", [_rec("tok_s", 1000.0, spread=0.10)])]
        assert cli.compare([_rec("tok_s", 750.0)],
                           noisy_base)["findings"] == []
        assert cli.compare([_rec("tok_s", 1000.0, spread=0.10)],
                           BASE)["findings"] == []

    def test_improvement_never_fires(self, cli):
        rep = cli.compare([_rec("tok_s", 2000.0)], BASE)
        assert rep["findings"] == []

    def test_cross_env_capture_refused(self, cli):
        rep = cli.compare([_rec("tok_s", 10.0, capture_id="envB")],
                          BASE)
        assert rep["findings"] == [] and rep["compared"] == 0
        assert any("env mismatch" in r["verdict"] for r in rep["rows"])

    def test_unfingerprinted_records_refused(self, cli):
        legacy_base = [("b.json", [{"metric": "tok_s", "value": 1000.0,
                                    "reps": 3, "spread": 0.01}])]
        rep = cli.compare([_rec("tok_s", 10.0)], legacy_base)
        assert rep["findings"] == [] and rep["compared"] == 0
        assert any("no env fingerprint" in r["verdict"]
                   for r in rep["rows"])

    def test_one_shot_comparable_false_skipped(self, cli):
        base = [("b.json", [_rec("serve", 50.0, reps=1, spread=0.0,
                                 comparable=False)])]
        rep = cli.compare([_rec("serve", 1.0)], base)
        assert rep["findings"] == [] and rep["compared"] == 0

    def test_stray_cross_env_capture_cannot_shadow_baseline(self, cli):
        """A legacy/cross-env capture appended to the trajectory must
        not disable the gate: the judge walks back to the newest
        MATCHING-fingerprint baseline."""
        traj = BASE + [("BENCH_r91.json",
                        [_rec("tok_s", 1000.0, capture_id="envB")]),
                       ("BENCH_r92.json",
                        [{"metric": "tok_s", "value": 1000.0,
                          "reps": 3, "spread": 0.01}])]
        rep = cli.compare([_rec("tok_s", 700.0)], traj)
        assert len(rep["findings"]) == 1
        assert rep["findings"][0]["baseline_capture"] \
            == "BENCH_r90.json"
        # and a clean matching capture still passes
        assert cli.compare([_rec("tok_s", 990.0)],
                           traj)["findings"] == []

    def test_newest_baseline_wins(self, cli):
        traj = [("BENCH_r1.json", [_rec("tok_s", 500.0)]),
                ("BENCH_r2.json", [_rec("tok_s", 1000.0)])]
        rep = cli.compare([_rec("tok_s", 940.0)], traj)
        assert rep["findings"] == []
        assert rep["rows"][0]["baseline"] == 1000.0
        rep = cli.compare([_rec("tok_s", 700.0)], traj)
        assert rep["findings"]          # vs r2, not the older r1

    def test_bench_error_line_fails_the_gate(self, cli):
        """A crashed leg emits only <config>_bench_error — its real
        metrics vanish, and vanishing must not read as clean."""
        rep = cli.compare(
            [{"metric": "llama_bench_error", "value": 0,
              "unit": "rc=1"}], BASE)
        assert len(rep["findings"]) == 1
        assert rep["findings"][0]["code"] == "bench-error"

    def test_vanished_metric_surfaced_not_failed(self, cli):
        rep = cli.compare([_rec("other", 1.0)], BASE)
        assert rep["findings"] == []
        missing = [r for r in rep["rows"]
                   if r["verdict"].startswith("missing")]
        assert [r["metric"] for r in missing] == ["tok_s"]
        assert missing[0]["baseline"] == 1000.0
        assert "missing" in cli.render(rep)

    def test_render_names_verdicts(self, cli):
        rep = cli.compare([_rec("tok_s", 800.0)], BASE)
        out = cli.render(rep)
        assert "REGRESSION" in out and "perf-regression" in out


class TestLoading:
    def test_parse_driver_capture_and_jsonl(self, cli, tmp_path):
        drv = tmp_path / "BENCH_r1.json"
        lines = [json.dumps(_rec("a", 1.0)), "WARNING: noise",
                 json.dumps(_rec("b", 2.0))]
        drv.write_text(json.dumps(
            {"n": 1, "rc": 0, "tail": "\n".join(lines)}))
        recs = cli.parse_capture(str(drv))
        assert [r["metric"] for r in recs] == ["a", "b"]
        raw = tmp_path / "run.jsonl"
        raw.write_text("\n".join(lines))
        recs = cli.parse_capture(str(raw))
        assert [r["metric"] for r in recs] == ["a", "b"]

    def test_load_trajectory_orders_by_round(self, cli, tmp_path):
        for n, v in ((2, 20.0), (10, 100.0), (1, 10.0)):
            (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
                {"tail": json.dumps(_rec("m", v))}))
        traj = cli.load_trajectory(str(tmp_path))
        assert [name for name, _ in traj] == [
            "BENCH_r01.json", "BENCH_r02.json", "BENCH_r10.json"]

    def test_real_trajectory_parses(self, cli):
        traj = cli.load_trajectory(REPO)
        assert len(traj) >= 1       # BENCH_r05: the one on-chip record
        latest = traj[-1][1]
        assert any(r["metric"] == "llama_train_tokens_per_sec_per_chip"
                   for r in latest)


class TestCLI:
    def test_selftest(self, cli):
        assert cli.main(["--selftest"]) == 0

    def test_cli_detects_planted_regression(self, cli, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(
            {"tail": json.dumps(_rec("tok_s", 1000.0))}))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps(
            {"tail": json.dumps(_rec("tok_s", 500.0))}))
        assert cli.main(["--trajectory", str(tmp_path)]) == 1
        # and a clean follow-up passes
        (tmp_path / "BENCH_r03.json").write_text(json.dumps(
            {"tail": json.dumps(_rec("tok_s", 995.0))}))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps(
            {"tail": json.dumps(_rec("tok_s", 1000.0))}))
        assert cli.main(["--trajectory", str(tmp_path)]) == 0

    def test_cli_current_file(self, cli, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(
            {"tail": json.dumps(_rec("tok_s", 1000.0))}))
        cur = tmp_path / "run.jsonl"
        cur.write_text(json.dumps(_rec("tok_s", 100.0)))
        assert cli.main(["--trajectory", str(tmp_path),
                         "--current", str(cur)]) == 1

