"""Ecosystem tools: dump/load, backup/restore with resume, CSV, CLI.

Mirrors the reference's BR/dumpling/lightning test surfaces (SURVEY §2.5,
br/pkg/task tests) at the scale the in-process engine serves — incl. the
checkpoint/resume discipline (a crash mid-backup resumes where it
stopped, the br/lightning checkpoint pattern)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from tidb_tpu import tools
from tidb_tpu.session import Engine


def make_engine():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE a (k BIGINT PRIMARY KEY, v VARCHAR(12), "
              "d DECIMAL(8,2), t DATE)")
    s.execute("CREATE INDEX iv ON a (v)")
    s.execute("CREATE TABLE b (x BIGINT, y DOUBLE)")
    s.execute("INSERT INTO a VALUES (1,'one',1.25,'2024-01-01'),"
              "(2,'it''s',NULL,'2024-02-02'),(3,NULL,3.75,NULL)")
    s.execute("INSERT INTO b VALUES (10, 1.5), (20, NULL)")
    s.execute("DELETE FROM b WHERE x = 20")   # tombstones excluded
    return eng, s


def contents(s):
    return {
        "a": sorted(map(str, s.query("SELECT * FROM a").rows)),
        "b": sorted(map(str, s.query("SELECT * FROM b").rows)),
    }


def test_backup_restore_roundtrip(tmp_path):
    eng, s = make_engine()
    want = contents(s)
    done = s.query(f"BACKUP TO '{tmp_path}/bk'").rows
    assert sorted(r[0] for r in done) == ["a", "b"]

    eng2 = Engine()
    s2 = eng2.new_session()
    s2.execute(f"RESTORE FROM '{tmp_path}/bk'")
    assert contents(s2) == want
    # schema incl. PK and index survived
    ddl = s2.query("SHOW CREATE TABLE a").rows[0][1]
    assert "PRIMARY KEY" in ddl and "iv" in ddl


def test_backup_resume_after_crash(tmp_path):
    from tidb_tpu.util import failpoint
    eng, s = make_engine()
    bkdir = str(tmp_path / "bk2")

    calls = {"n": 0}

    def boom(**kw):
        calls["n"] += 1
        if calls["n"] == 2:      # crash before the SECOND table
            raise RuntimeError("injected crash")

    failpoint.enable("backup-table", hook=boom)
    try:
        with pytest.raises(RuntimeError, match="injected crash"):
            tools.backup(eng, bkdir)
    finally:
        failpoint.disable("backup-table")
    # one table landed, checkpoint recorded it
    assert os.path.exists(os.path.join(bkdir, "checkpoint.json"))
    resumed = tools.backup(eng, bkdir)
    assert len(resumed) == 1          # only the remaining table
    assert not os.path.exists(os.path.join(bkdir, "checkpoint.json"))

    eng2 = Engine()
    tools.restore(eng2, bkdir)
    assert contents(eng2.new_session()) == contents(s)


def test_dump_and_load(tmp_path):
    eng, s = make_engine()
    out = str(tmp_path / "dump")
    written = tools.dump_sql(s, out)
    assert sorted(written) == ["a", "b"]
    assert os.path.exists(os.path.join(out, "a-schema.sql"))
    eng2 = Engine()
    s2 = eng2.new_session()
    tools.load_dump(s2, out)
    assert contents(s2) == contents(s)


def test_csv_roundtrip(tmp_path):
    eng, s = make_engine()
    path = str(tmp_path / "a.csv")
    n = tools.export_csv(s, "a", path)
    assert n == 3
    s.execute("CREATE TABLE a2 (k BIGINT, v VARCHAR(12), d DECIMAL(8,2), "
              "t DATE)")
    assert tools.import_csv(s, "a2", path) == 3
    assert sorted(map(str, s.query("SELECT * FROM a2").rows)) == \
        sorted(map(str, s.query("SELECT * FROM a").rows))


def test_backup_requires_superuser(tmp_path):
    eng, s = make_engine()
    s.execute("CREATE USER u1 IDENTIFIED BY 'x'")
    s2 = eng.new_session()
    s2.user = "u1"
    with pytest.raises(Exception, match="denied"):
        s2.execute(f"BACKUP TO '{tmp_path}/nope'")


def test_dump_cli_over_the_wire(tmp_path):
    from tidb_tpu.server import Server
    eng, s = make_engine()
    srv = Server(eng, port=0).start()
    try:
        out = str(tmp_path / "wire_dump")
        env = dict(os.environ)
        r = subprocess.run(
            [sys.executable, "-m", "tidb_tpu.tools", "dump",
             "--port", str(srv.port), "-o", out],
            capture_output=True, text=True, env=env, timeout=120,
            cwd="/root/repo")
        assert r.returncode == 0, r.stderr
        assert "dumped 2 table(s)" in r.stdout
        eng2 = Engine()
        s2 = eng2.new_session()
        tools.load_dump(s2, out)
        assert contents(s2) == contents(s)
    finally:
        srv.stop()


# ---- device-coverage ratchet (tools/check_coverage.py) --------------------

def _load_check_coverage():
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_coverage", os.path.join(repo, "tools", "check_coverage.py"))
    cc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cc)
    return cc


def test_check_coverage_negative_fails_on_regression(tmp_path,
                                                     monkeypatch):
    """The ratchet: a query pinned fused in COVERAGE.json that now falls
    back is a reported problem; so is a drifted or out-of-taxonomy
    fallback reason. A newly-fused query is NOT a problem."""
    import json
    cc = _load_check_coverage()
    (tmp_path / "COVERAGE.json").write_text(json.dumps({"queries": {
        "q1": {"fused": True, "fallback": None},
        "q2": {"fused": False, "fallback": "shape"},
        "q3": {"fused": False, "fallback": "shape"},
    }}))
    monkeypatch.setattr(cc, "_sweep", lambda root: {
        "q1": {"fused": False, "fallback": "device-error"},  # regressed
        "q2": {"fused": False, "fallback": "group-cap"},     # drifted
        "q3": {"fused": True, "fallback": None},             # advanced
    })
    problems = cc.run(str(tmp_path))
    assert any("q1" in p and "REGRESSED" in p for p in problems)
    assert any("q2" in p and "drifted" in p for p in problems)
    assert not any("q3" in p for p in problems)
    # and the clean case really is clean
    monkeypatch.setattr(cc, "_sweep", lambda root: {
        "q1": {"fused": True, "fallback": None},
        "q2": {"fused": False, "fallback": "shape"},
        "q3": {"fused": False, "fallback": "shape"},
    })
    assert cc.run(str(tmp_path)) == []


def test_check_coverage_missing_baseline_is_a_problem(tmp_path):
    cc = _load_check_coverage()
    problems = cc.run(str(tmp_path))
    assert problems and "COVERAGE.json" in problems[0]


def test_check_coverage_out_of_taxonomy_reason(tmp_path, monkeypatch):
    import json
    cc = _load_check_coverage()
    (tmp_path / "COVERAGE.json").write_text(json.dumps({"queries": {
        "q1": {"fused": False, "fallback": "shape"}}}))
    monkeypatch.setattr(cc, "_sweep", lambda root: {
        "q1": {"fused": False, "fallback": "weird"}})
    problems = cc.run(str(tmp_path))
    assert any("taxonomy" in p for p in problems)


def test_check_coverage_wired_into_chaos_preflight():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = open(os.path.join(repo, "tidb_tpu", "tools",
                            "chaos_sweep.py")).read()
    assert '"check_coverage"' in src, \
        "check_coverage must run as a chaos-sweep preflight"


def test_committed_coverage_baseline_shape():
    """COVERAGE.json exists, covers 22 queries, and every pinned
    fallback reason is in the fragment taxonomy."""
    import json

    from tidb_tpu.executor.eligibility import FALLBACK_REASONS
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "COVERAGE.json")) as f:
        base = json.load(f)
    assert base["total"] == len(base["queries"]) == 22
    assert base["fused"] == sum(
        1 for v in base["queries"].values() if v["fused"])
    assert base["fused"] >= 16          # the ISSUE 20 coverage floor
    for q, v in base["queries"].items():
        if not v["fused"]:
            assert v["fallback"] in FALLBACK_REASONS, (q, v)
