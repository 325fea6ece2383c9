"""mx.ledger tests: torn-line-tolerant append/read round-trips, the
strictly-like-provenance series keying (CPU-smoke can never share a
series with TPU — the structural impossibility the ISSUE demands),
the windowed median+MAD drift detector against hand-computed windows,
verdict escalation (suspect vs confirmed vs sustained), gate exit
codes including the smoke-only warn path and the ledger_gate=warn
downgrade, tools/ledger_report.py backfill idempotence + report
rendering + tier-1 budget burn, and the ledger-off zero-hook fast
path every bench entrypoint rides."""
import importlib.util
import io
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu import config, ledger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REPORT = os.path.join(ROOT, "tools", "ledger_report.py")


@pytest.fixture(autouse=True)
def _clean():
    yield
    ledger.reset()
    config.reset()


def _load_report_mod():
    spec = importlib.util.spec_from_file_location("_ledger_report_t",
                                                  REPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(rows, platform="tpu", devices=4, smoke=False, cfg="cafef00d",
         bench="bench.py", label=None, ts=1000.0):
    prov = ledger.build_provenance(
        platform=platform, devices=devices, smoke_mode=smoke,
        rev="testrev", fingerprint=cfg, knobs={})
    return ledger.build_run_record(bench, rows, provenance=prov,
                                   ts=ts, label=label)


def _history(values, degraded=None, **prov_kw):
    """Run records for one metric series, labelled run0..runN (+ the
    optional trailing 'degraded-run')."""
    recs = [_run([{"metric": "m", "value": v}], label=f"run{i}",
                 ts=1000.0 + i, **prov_kw)
            for i, v in enumerate(values)]
    if degraded is not None:
        recs.append(_run([{"metric": "m", "value": degraded}],
                         label="degraded-run", ts=2000.0, **prov_kw))
    return recs


# ---------------------------------------------------------------------------
# append / read round-trip, torn lines
# ---------------------------------------------------------------------------

def test_append_read_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rec = _run([{"metric": "m", "value": 1.5}], label="a")
    assert ledger.append_record(path, rec) is True
    assert ledger.append_record(path, _run([{"metric": "m",
                                             "value": 2.5}])) is True
    recs = ledger.read_records(path)
    # a meta header is stamped on the fresh file, then the two runs
    assert recs[0]["kind"] == "meta" and recs[0]["schema"] == ledger.SCHEMA
    runs = [r for r in recs if r["kind"] == "run"]
    assert len(runs) == 2
    assert runs[0]["label"] == "a"
    assert runs[0]["metrics"] == {"m": 1.5}
    # read_records accepts the directory too
    assert ledger.read_records(str(tmp_path)) == recs


def test_torn_trailing_line_skipped_and_healed(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append_record(path, _run([{"metric": "m", "value": 1.0}],
                                    label="whole"))
    # a crashed writer left half a record with no trailing newline
    with open(path, "a") as f:
        f.write('{"kind": "run", "bench": "bench.py", "metr')
    recs = ledger.read_records(path)
    assert [r["kind"] for r in recs] == ["meta", "run"]  # torn line skipped
    # the next append heals onto a fresh line instead of concatenating
    ledger.append_record(path, _run([{"metric": "m", "value": 2.0}],
                                    label="after-tear"))
    runs = [r for r in ledger.read_records(path) if r["kind"] == "run"]
    assert [r["label"] for r in runs] == ["whole", "after-tear"]
    # the torn fragment stayed on its own (still-unparseable) line
    lines = open(path).read().splitlines()
    assert any(ln.endswith('"metr') for ln in lines)


def test_garbage_lines_never_fatal(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with open(path, "w") as f:
        f.write("not json at all\n[1, 2, 3]\n\n")
        f.write(json.dumps(_run([{"metric": "m", "value": 3.0}])) + "\n")
    runs = [r for r in ledger.read_records(path) if r.get("kind") == "run"]
    assert len(runs) == 1 and runs[0]["metrics"] == {"m": 3.0}
    assert ledger.read_records(str(tmp_path / "missing.jsonl")) == []


# ---------------------------------------------------------------------------
# provenance: keying, recovery, fingerprint
# ---------------------------------------------------------------------------

def test_cross_provenance_series_are_disjoint():
    """The acceptance criterion: a CPU-smoke row and a TPU row of the
    SAME metric land in different series keys — comparing them is
    structurally impossible, not merely warned about."""
    recs = (_history([100.0, 101.0], platform="tpu", devices=1,
                     smoke=False)
            + _history([10.0, 11.0], platform="cpu", devices=1,
                       smoke=True))
    s = ledger.series(recs)
    keys = {k for k, _ in s}
    assert keys == {
        "bench=bench.py|platform=tpu|devices=1|smoke=False|cfg=cafef00d",
        "bench=bench.py|platform=cpu|devices=1|smoke=True|cfg=cafef00d",
    }
    tpu_pts = s[("bench=bench.py|platform=tpu|devices=1|smoke=False"
                 "|cfg=cafef00d", "m")]
    assert [p["value"] for p in tpu_pts] == [100.0, 101.0]
    # a config-fingerprint change alone also splits the series
    recs.append(_run([{"metric": "m", "value": 99.0}], platform="tpu",
                     devices=1, smoke=False, cfg="deadbeef"))
    assert len({k for k, _ in ledger.series(recs)}) == 3


def test_provenance_of_rows_explicit_and_smoke_error():
    assert ledger.provenance_of_rows(
        [{"platform": "tpu", "devices": 8, "smoke_mode": False}]) \
        == ("tpu", 8, False)
    # pre-PR-11 CPU fallback rows only carried the error annotation
    assert ledger.provenance_of_rows(
        [{"metric": "m", "value": 1.0,
          "error": "tpu backend unavailable; CPU smoke-mode number"}]) \
        == ("cpu", None, True)
    assert ledger.provenance_of_rows([{"metric": "m"}]) \
        == (None, None, None)


def test_config_fingerprint_tracks_perf_knobs():
    fp1, knobs = ledger.config_fingerprint()
    assert fp1 is not None and knobs["kernels"] == config.get("kernels")
    config.set("zero", "off" if config.get("zero") != "off" else "on")
    fp2, _ = ledger.config_fingerprint()
    assert fp2 != fp1


def test_flatten_metrics_prefixes_and_direction():
    # single generic row: 'value' collapses onto the metric name
    assert ledger.flatten_metrics(
        [{"metric": "tps", "value": 5.0, "note": "x"}]) == {"tps": 5.0}
    # multi-row bench: every numeric ledger field gets the row prefix
    out = ledger.flatten_metrics(
        [{"metric": "kernel_a", "speedup": 2.0, "pallas_ms": 1.0},
         {"path": "on_device", "tokens_per_sec": 10.0}])
    assert out == {"kernel_a.speedup": 2.0, "kernel_a.pallas_ms": 1.0,
                   "on_device.tokens_per_sec": 10.0}
    assert ledger.higher_is_better("kernel_a.speedup")
    assert not ledger.higher_is_better("kernel_a.pallas_ms")
    assert not ledger.higher_is_better("x.step_p99_ms")
    assert ledger.higher_is_better("anything_unknown")


# ---------------------------------------------------------------------------
# drift detector — hand-computed windows
# ---------------------------------------------------------------------------

def test_detect_flat_window_hand_computed():
    """History [100,100,101,99,100]: median 100, mad 0, so the robust
    scale is the 2% rel floor = 2.0. A drop to 70 is z = 30/2 = 15,
    rel = 0.30 -> flagged; 98 is z = 1, rel = 0.02 -> clean."""
    base = [100.0, 100.0, 101.0, 99.0, 100.0]
    marks = ledger.detect(base + [70.0])
    assert marks[-1] == {"flag": True, "z": 15.0, "rel": 0.3,
                         "median": 100.0, "mad": 0.0}
    marks = ledger.detect(base + [98.0])
    assert marks[-1]["flag"] is False
    assert marks[-1]["z"] == 1.0 and marks[-1]["rel"] == 0.02
    # the first min_samples points are never judged
    assert all(m["flag"] is None for m in marks[:3])


def test_detect_noisy_window_needs_bigger_move():
    """History [100,104,96,108,92]: median 100, mad 4, scale
    1.4826*4 = 5.9304. A drop to 80 is z ~= 3.37 < 4 -> NOT flagged
    even though rel = 0.20; a drop to 60 (z ~= 6.74) is."""
    base = [100.0, 104.0, 96.0, 108.0, 92.0]
    m80 = ledger.detect(base + [80.0])[-1]
    assert m80["flag"] is False and m80["mad"] == 4.0
    assert m80["z"] == pytest.approx(20.0 / 5.9304, abs=1e-3)
    m60 = ledger.detect(base + [60.0])[-1]
    assert m60["flag"] is True and m60["rel"] == 0.4


def test_detect_lower_better_direction():
    # for a lower-better metric (latency) the BAD direction is up
    base = [10.0, 10.0, 10.2, 9.8, 10.0]
    up = ledger.detect(base + [14.0], higher_better=False)[-1]
    assert up["flag"] is True and up["rel"] == 0.4
    down = ledger.detect(base + [7.0], higher_better=False)[-1]
    assert down["flag"] is False          # got FASTER: never a drift


def test_verdict_statuses_and_first_bad():
    # too few points: min_samples prior values + the judged one
    assert ledger.verdict([{"value": v, "label": str(v), "index": i}
                           for i, v in enumerate([100, 100, 70])]
                          )["status"] == "insufficient"
    # big single drop -> confirmed, naming the bad run
    pts = [{"value": v, "label": f"run{i}", "index": i}
           for i, v in enumerate([100.0, 100.0, 101.0, 99.0, 70.0])]
    v = ledger.verdict(pts)
    assert v["status"] == "confirmed"
    assert v["first_bad"] == {"label": "run4", "index": 4, "value": 70.0}
    # small drop (rel 0.15 < 0.25), one point -> suspect only
    pts = [{"value": v, "label": f"run{i}", "index": i}
           for i, v in enumerate([100.0] * 6 + [85.0])]
    assert ledger.verdict(pts)["status"] == "suspect"
    # the SAME small drop sustained for two runs -> confirmed, and
    # first_bad names the START of the flagged streak
    pts = [{"value": v, "label": f"run{i}", "index": i}
           for i, v in enumerate([100.0] * 6 + [85.0, 85.0])]
    v = ledger.verdict(pts)
    assert v["status"] == "confirmed"
    assert v["first_bad"]["label"] == "run6"
    # an excursion that RECOVERED does not fail the latest run
    pts = [{"value": v, "label": f"run{i}", "index": i}
           for i, v in enumerate([100.0] * 5 + [70.0, 100.0])]
    assert ledger.verdict(pts)["status"] == "ok"


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def test_gate_exit_codes():
    # nothing with enough history -> rc 2
    rc, findings = ledger.gate(_history([100.0, 101.0]))
    assert rc == 2 and findings == []
    # healthy history -> rc 0
    rc, findings = ledger.gate(_history([100.0, 101.0, 99.0, 100.0,
                                         100.5]))
    assert rc == 0 and findings == []
    # confirmed regression on REAL (non-smoke) provenance -> rc 1
    rc, findings = ledger.gate(
        _history([100.0, 101.0, 99.0, 100.0], degraded=70.0))
    assert rc == 1
    assert findings[0]["severity"] == "fail"
    assert findings[0]["metric"] == "m"
    assert findings[0]["first_bad"]["label"] == "degraded-run"
    # the SAME rows under smoke provenance only warn -> rc 0
    rc, findings = ledger.gate(
        _history([100.0, 101.0, 99.0, 100.0], degraded=70.0,
                 platform="cpu", smoke=True))
    assert rc == 0
    assert findings[0]["severity"] == "warn"
    # ...and a smoke warn next to a real failure does not mask it
    rc, findings = ledger.gate(
        _history([100.0, 101.0, 99.0, 100.0], degraded=70.0)
        + _history([100.0, 101.0, 99.0, 100.0], degraded=70.0,
                   platform="cpu", smoke=True))
    assert rc == 1
    assert sorted(f["severity"] for f in findings) == ["fail", "warn"]


# ---------------------------------------------------------------------------
# enable/disable + hook fast path
# ---------------------------------------------------------------------------

def test_ledger_off_is_a_zero_hook_fast_path(monkeypatch, tmp_path):
    """With the knob unset the bench hook must reduce to one bool
    check: no record built, nothing appended, nothing written."""
    from benchmarks import _provenance
    assert not ledger.enabled()

    def boom(*a, **k):
        raise AssertionError("hook ran with the ledger off")

    monkeypatch.setattr(ledger, "build_run_record", boom)
    monkeypatch.setattr(ledger, "append_record", boom)
    assert _provenance.ledger_append(
        "bench.py", [{"metric": "m", "value": 1.0}]) is None
    assert ledger.record_run("bench.py", [{"metric": "m",
                                           "value": 1.0}]) is None
    assert ledger.record_tier1(10.0, 5, 0) is None
    assert list(tmp_path.iterdir()) == []


def test_enable_via_knob_and_record_run(tmp_path):
    config.set("ledger_dir", str(tmp_path))
    ledger.enable()
    assert ledger.enabled()
    assert ledger.ledger_path() == str(tmp_path / "ledger.jsonl")
    rec = ledger.record_run("bench.py",
                            [{"metric": "m", "value": 2.0,
                              "platform": "cpu", "devices": 1,
                              "smoke_mode": True}])
    assert rec["metrics"] == {"m": 2.0}
    assert rec["provenance"]["platform"] == "cpu"
    assert rec["provenance"]["fingerprint"]        # live config hashed
    on_disk = [r for r in ledger.read_records(str(tmp_path))
               if r.get("kind") == "run"]
    assert len(on_disk) == 1 and on_disk[0]["metrics"] == {"m": 2.0}
    ledger.disable()
    assert ledger.record_run("bench.py", [{"metric": "m",
                                           "value": 3.0}]) is None


def test_enable_without_dir_raises():
    with pytest.raises(ValueError):
        ledger.enable()


# ---------------------------------------------------------------------------
# tools/ledger_report.py — backfill, report, tier-1 budget, gate CLI
# ---------------------------------------------------------------------------

def _driver_artifacts(dirpath):
    """Five driver bench artifacts in the shape the driver wrote them: a
    crash with no row, one TPU row behind its `# backend=` marker, and
    three CPU rows annotated as smoke-mode."""
    metric = "bert_base_pretrain_tokens_per_sec_per_chip"
    cmd = "python bench.py"

    def row(value, **extra):
        return dict({"metric": metric, "value": value,
                     "unit": "tokens/s/chip", "vs_baseline": 1.0}, **extra)

    smoke = "tpu backend unavailable; CPU smoke-mode number"
    specs = [
        {"rc": 1, "tail": "Traceback (most recent call last):\n"
                          "RuntimeError: backend init failed\n"},
        {"rc": 0, "tail": "# backend=tpu devices=1 params=110.1M\n"
                          + json.dumps(row(132473.34)) + "\n",
         "parsed": row(132473.34)},
    ] + [
        {"rc": 0, "tail": "# backend=cpu devices=1 params=0.1M\n"
                          + json.dumps(row(v, error=smoke)) + "\n",
         "parsed": row(v, error=smoke)}
        for v in (15869.92, 16010.5, 15790.1)]
    paths = []
    for n, spec in enumerate(specs, start=1):
        path = os.path.join(dirpath, f"BENCH_r{n:02d}.json")
        with open(path, "w") as f:
            json.dump(dict({"n": n, "cmd": cmd}, **spec), f)
        paths.append(path)
    return paths


def test_backfill_import_idempotent_and_anchor_renders(tmp_path):
    """Driver artifacts: BENCH_r02's 132k TPU row must come back as a
    smoke=False TPU series (the anchor), the smoke runs as a separate
    series, and a re-import must be a no-op."""
    art_dir = tmp_path / "artifacts"
    art_dir.mkdir()
    artifacts = _driver_artifacts(str(art_dir))
    tmp_path = tmp_path / "ledger"
    tmp_path.mkdir()
    env = dict(os.environ, MXNET_TPU_LEDGER_GATE="")
    r = subprocess.run(
        [sys.executable, REPORT, str(tmp_path), "--import"] + artifacts,
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    assert "imported BENCH_r02.json: 1 row(s), platform=tpu" in r.stdout
    assert "5 imported, 0 skipped" in r.stdout
    again = subprocess.run(
        [sys.executable, REPORT, str(tmp_path), "--import"] + artifacts,
        capture_output=True, text=True, env=env)
    assert "0 imported, 5 skipped" in again.stdout

    recs = ledger.read_records(str(tmp_path))
    keys = {ledger.provenance_key(r) for r in recs
            if r.get("kind") == "run"}
    assert "bench=bench.py|platform=tpu|devices=1|smoke=False|cfg=None" \
        in keys
    rep = subprocess.run([sys.executable, REPORT, str(tmp_path)],
                         capture_output=True, text=True, env=env)
    assert rep.returncode == 0, rep.stderr
    assert "TPU anchors" in rep.stdout
    assert "132,473" in rep.stdout           # run 2's tokens/s/chip
    assert "[BENCH_r02.json]" in rep.stdout


def test_report_parse_pytest_log_and_budget_warning(tmp_path):
    rep = _load_report_mod()
    log = ("============ test session starts ============\n"
           "........\n"
           "============ slowest 10 durations ============\n"
           "12.31s call     tests/unittest/test_a.py::test_x\n"
           "4.50s setup    tests/unittest/test_b.py::test_y\n"
           "0.80s call     tests/unittest/test_c.py::test_z\n"
           "== 880 passed, 2 skipped, 1 failed in 801.2s ==\n")
    passed, failed, errors, skipped, slowest = rep.parse_pytest_log(log)
    assert (passed, failed, errors, skipped) == (880, 1, 0, 2)
    assert slowest[0] == ("tests/unittest/test_a.py::test_x", 12.31)

    log_path = tmp_path / "sweep.log"
    log_path.write_text(log)
    env = dict(os.environ, MXNET_TPU_LEDGER_GATE="")
    r = subprocess.run(
        [sys.executable, REPORT, str(tmp_path), "--record-tier1",
         str(log_path), "--wall", "801"],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    assert "880 passed" in r.stdout and "(92%)" in r.stdout
    out = subprocess.run([sys.executable, REPORT, str(tmp_path)],
                         capture_output=True, text=True, env=env)
    # 801/870 = 92% of the sweep timeout: the burn line must WARN
    assert "tier-1 budget burn: 801s / 870s (92%)" in out.stdout
    assert "WARNING" in out.stdout
    assert "test_a.py::test_x" in out.stdout


def test_gate_cli_seeded_regression(tmp_path):
    """The acceptance smoke, in-process: a 30%-degraded like-provenance
    run -> exit 1 naming the metric and the first bad run; the same
    rows under smoke provenance only warn; ledger_gate=warn
    downgrades the failure to exit 0."""
    path = str(tmp_path / "ledger.jsonl")
    for rec in _history([100000, 101000, 99500, 100500], degraded=70000):
        ledger.append_record(path, rec)
    for rec in _history([100000, 101000, 99500, 100500], degraded=70000,
                        platform="cpu", devices=1, smoke=True):
        ledger.append_record(path, rec)
    env = dict(os.environ)
    env.pop("MXNET_TPU_LEDGER_GATE", None)
    r = subprocess.run([sys.executable, REPORT, str(tmp_path), "--gate"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "CONFIRMED regression: m" in r.stdout
    assert "first bad run: degraded-run" in r.stdout
    assert "30% worse than the window median" in r.stdout
    assert "warn (smoke-mode provenance)" in r.stdout
    env["MXNET_TPU_LEDGER_GATE"] = "warn"
    r = subprocess.run([sys.executable, REPORT, str(tmp_path), "--gate"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0
    assert "DOWNGRADED" in r.stdout


def test_gate_cli_nothing_to_judge(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append_record(path, _run([{"metric": "m", "value": 1.0}]))
    r = subprocess.run([sys.executable, REPORT, str(tmp_path), "--gate"],
                       capture_output=True, text=True)
    assert r.returncode == 2
    assert "nothing to judge yet" in r.stdout


def test_render_report_sparklines_and_verdict():
    rep = _load_report_mod()
    out = io.StringIO()
    rep.render_report(
        _history([100.0, 101.0, 99.0, 100.0], degraded=70.0), out=out)
    text = out.getvalue()
    assert "mx.ledger report — 5 run record(s)" in text
    assert "bench=bench.py|platform=tpu|devices=4|smoke=False" in text
    assert "confirmed (first bad: degraded-run)" in text
    assert any(c in text for c in rep.SPARK)
    assert rep.sparkline([1.0, 1.0]) == rep.SPARK[3] * 2
    assert rep.sparkline([0.0, 1.0]) == rep.SPARK[0] + rep.SPARK[-1]


def test_tier1_record_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rec = ledger.build_tier1_record(
        500.0, 880, 0, skipped=3,
        slowest=[("t%d" % i, 20.0 - i) for i in range(12)], ts=1234.0)
    assert rec["metrics"] == {"wall_s": 500.0, "passed": 880,
                              "failed": 0, "errors": 0}
    assert len(rec["slowest"]) == 10          # top-10, not all 12
    ledger.append_record(path, rec)
    s = ledger.series(ledger.read_records(path))
    (key, metric) = next(k for k in s if k[1] == "wall_s")
    assert "bench=tier1" in key
    # wall_s is lower-better: a slower sweep is the regression
    assert not ledger.higher_is_better("wall_s")
