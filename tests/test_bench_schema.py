"""Perf-trajectory schema contract (`benchmarks/common.py`).

CI persists every benchmark's rows as ``BENCH_<suite>.json`` artifacts;
this suite pins the record shape those artifacts (and any trajectory
consumer diffing them run-over-run) rely on, and the merge semantics
that let several benchmarks of one CI job share a file.
"""
import json

import pytest

from benchmarks.common import (BENCH_SCHEMA_VERSION, bench_record,
                               parse_row, validate_record,
                               write_bench_json)

ROWS = [
    "engine_throughput/steady,12.41 req/s,0.97s for 12 reqs "
    "(max_batch=4),traces +0",
    "serving_cache/bytes,paged 34.8 KB,naive high-water 66.6 KB "
    "(1.9x, 4 waves)",
    "streaming_smoke/slo,edf hit-rate 100%,fifo hit-rate 75%",
]


class TestParseRow:
    def test_name_value_detail_split(self):
        e = parse_row("a/b,1.5 req/s,extra, commas, kept", bench="x")
        assert e == {"bench": "x", "name": "a/b", "value": "1.5 req/s",
                     "detail": "extra, commas, kept"}

    def test_detail_optional(self):
        assert parse_row("a,1")["detail"] == ""

    def test_representative_benchmark_rows(self):
        for row in ROWS:
            e = parse_row(row, bench="b")
            assert e["name"].count("/") == 1 and e["value"]

    @pytest.mark.parametrize("bad", ["", "loner", ",noname"])
    def test_malformed_rows_rejected(self, bad):
        with pytest.raises(ValueError, match="malformed"):
            parse_row(bad)


class TestRecord:
    def test_roundtrip_validates(self):
        rec = bench_record("unit", [parse_row(r, bench="b") for r in ROWS])
        validate_record(rec)
        assert rec["schema_version"] == BENCH_SCHEMA_VERSION
        assert rec["suite"] == "unit"
        assert {"python", "jax", "backend", "platform"} <= set(rec["env"])
        # survives JSON serialization (the artifact is a file)
        validate_record(json.loads(json.dumps(rec)))

    @pytest.mark.parametrize("mutate,match", [
        (lambda r: r.update(schema_version=99), "schema_version"),
        (lambda r: r.update(suite=""), "suite"),
        (lambda r: r.update(env=None), "env"),
        (lambda r: r.update(entries={"not": "a list"}), "entries"),
        (lambda r: r["entries"].append({"bench": "b"}), "field"),
        (lambda r: r["entries"].append(
            {"bench": "b", "name": "", "value": "v", "detail": ""}),
         "non-empty"),
    ])
    def test_bad_records_rejected(self, mutate, match):
        rec = bench_record("unit", [parse_row(ROWS[0], bench="b")])
        mutate(rec)
        with pytest.raises(ValueError, match=match):
            validate_record(rec)


class TestWriteMerge:
    def test_create_then_merge(self, tmp_path):
        path = str(tmp_path / "BENCH_serving.json")
        write_bench_json(path, "serving", ROWS[:1], bench="a")
        write_bench_json(path, "serving", ROWS[1:], bench="b")
        with open(path) as f:
            rec = json.load(f)
        validate_record(rec)
        assert [e["bench"] for e in rec["entries"]] == ["a", "b", "b"]
        assert rec["suite"] == "serving"

    def test_rerun_replaces_same_bench_entries(self, tmp_path):
        """Re-running a benchmark against a stale file must replace
        its old entries, not accumulate two runs' numbers."""
        path = str(tmp_path / "BENCH_serving.json")
        write_bench_json(path, "serving", ROWS[:1], bench="a")
        write_bench_json(path, "serving", ROWS[1:], bench="b")
        write_bench_json(path, "serving", [ROWS[2]], bench="a")  # re-run
        with open(path) as f:
            rec = json.load(f)
        assert [e["bench"] for e in rec["entries"]] == ["b", "b", "a"]
        assert sum(e["bench"] == "a" for e in rec["entries"]) == 1

    def test_suite_mismatch_rejected(self, tmp_path):
        path = str(tmp_path / "BENCH_unit.json")
        write_bench_json(path, "unit", ROWS[:1], bench="a")
        with pytest.raises(ValueError, match="suite mismatch"):
            write_bench_json(path, "serving", ROWS[1:], bench="b")

    def test_corrupt_existing_file_rejected(self, tmp_path):
        path = str(tmp_path / "BENCH_unit.json")
        with open(path, "w") as f:
            f.write('{"schema_version": 0, "suite": "unit"}')
        with pytest.raises(ValueError, match="schema_version"):
            write_bench_json(path, "unit", ROWS[:1], bench="a")
