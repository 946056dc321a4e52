"""The fig1_monthly configuration on the CPU at its test size: the table
lands as one file per calendar month, each window's run through the
program agrees with the plain reference, the planner reads only the
month files the window touches, and the readers of the cell's new
metrics read what a run holds, and nothing where a program has no such
spans."""
import shutil
import tempfile
import types

import numpy as np
import pytest

from bench import harness

CELL = harness.Cell("fig1-monthly")
CONFIG = dict(CELL.config, **CELL.config["test_sizes"])
MOD = CELL.config_module
SEED = 2**31 + 777

# start days: Jan 1 to Jan 31 (one file), Feb 15 to Mar 17 (two months),
# Nov 20 to Dec 20 (two), Dec 1 to Dec 31 (one, the year's edge)
WINDOWS = [(0, 1), (45, 2), (323, 2), (334, 1)]


@pytest.fixture(scope="module")
def system():
    workdir = tempfile.mkdtemp(prefix="fig1_monthly_test_")
    s = MOD.System(CONFIG, SEED, workdir)
    try:
        yield s
    finally:
        s.close()
        shutil.rmtree(workdir, ignore_errors=True)


def _reader(name):
    metric = next(m for m in CELL.per_layer if m["name"] == name)
    return CELL.reader(metric, "layers")


def test_the_table_lands_as_one_file_per_month(system):
    files = system.snapshot.files
    assert len(files) == 12
    assert sum(f.num_rows for f in files) == CONFIG["rows"]
    for month, f in enumerate(files, start=1):
        stats = f.column_stats["eventTime"]
        assert stats["min"] // 100 == stats["max"] // 100 == 202300 + month


def test_every_window_ends_in_the_year():
    last = MOD.window(334, CONFIG)
    assert (last["lo"], last["hi"]) == (20231201, 20231231)
    assert last["date_filter"] == \
        "eventTime BETWEEN 2023-12-01 AND 2023-12-31"
    assert MOD.days_in_year(CONFIG) - MOD.WINDOW_DAYS == 334


@pytest.mark.parametrize("start,files", WINDOWS)
def test_a_window_matches_the_reference_and_reads_its_months(system, start,
                                                             files):
    win = MOD.window(start, CONFIG)
    assert MOD.files_in_window(win) == files
    system.begin_window()
    out = system.run(win)
    want = MOD.reference(system.cols, win, CONFIG)
    assert sorted(want) == sorted(CONFIG["countries"])
    checks = MOD.compare(out["groups"], want)
    assert checks["group_diff"] == 0
    assert checks["max_rel_err"] <= CONFIG["limits"]["max_rel_err"]
    run = types.SimpleNamespace(program=system.observations(), trace=None)
    # the pruned set: the scan read only the months the window touches
    assert _reader("scan_files.run").read(run) == files
    plan = system.window_runs[0]["plan"]
    scanned = [t for t in plan.tasks.values() if t.kind == "scan"]
    assert sum(len(t.files) for t in scanned) == files
    # the compaction's work: the window's rows in, the euro rows out
    rows_in, rows_out = run.program["compact_work"][0]
    keep = (system.cols["eventTime"] >= win["lo"]) \
        & (system.cols["eventTime"] <= win["hi"])
    assert rows_in == int(keep.sum())
    assert rows_out == int((keep & MOD.euro_mask(system.cols,
                                                 CONFIG)).sum())
    for name in ("op_mask_s.run", "op_gather_s.run"):
        assert _reader(name).read(run) > 0


def test_the_readers_read_nothing_without_their_spans(system):
    system.begin_window()
    system.run(MOD.window(100, CONFIG))
    program = system.observations()
    # the program as it was before the filter and scan spans: no op.mask,
    # no op.gather, no files on task.scan
    for r in program["runs"]:
        for e in r["events"]:
            if e.kind == "spans":
                e.payload["spans"] = [
                    dict(s, attrs={k: v for k, v in s["attrs"].items()
                                   if k != "files"})
                    for s in e.payload["spans"]
                    if s["name"] not in ("op.mask", "op.gather")]
    run = types.SimpleNamespace(program=program, trace=None)
    for name in ("op_mask_s.run", "op_gather_s.run", "scan_files.run",
                 "compact_roofline.run"):
        assert _reader(name).read(run) is None, name


def test_the_compaction_roofline_counts_the_data_not_the_padding():
    reduced = types.SimpleNamespace(module_seconds=lambda names: (
        1e-3 if tuple(names) == ("compact",) else 0.0))
    run = types.SimpleNamespace(
        program={"compact_work": [(849_000, 510_000), (848_000, 509_000)]},
        trace=reduced, device_kind="TPU v5 lite")
    share = _reader("compact_roofline.run").read(run)
    # 4 bytes per row in and per row out, at 819 GB/s, over 1 ms
    want = 100 * 4 * (849_000 + 510_000 + 848_000 + 509_000) / 819e9 / 1e-3
    assert share == pytest.approx(want)
    assert 0 < share < 100


def test_the_buckets_cover_every_padded_size():
    from repro.kernels import ops

    assert MOD._buckets(0, 65536) == [1024 << i for i in range(7)]
    assert MOD._buckets(500_000, 520_000) == [524288]
    for n in (1, 1024, 1025, 509_000, 849_000):
        b = MOD._buckets(n, n)
        assert b == [max(ops.ROW_BLOCK, 1 << (n - 1).bit_length())]


def test_the_data_follows_the_distributions():
    cols = MOD.generate(dict(CONFIG, rows=200_000), SEED)
    et = cols["eventTime"]
    assert np.all(np.diff(et) >= 0)
    assert et.min() == 20230101 and et.max() == 20231231
    assert len(np.unique(et)) == 365
    assert set(np.unique(cols["country"])) == set(range(10))
    assert np.all(cols["usd"] > 0)
    assert np.allclose(cols["usd"], np.round(cols["usd"], 2))
    assert 95 < cols["usd"].mean() < 105       # gamma(2, 50): mean 100
    assert cols["client_id"].min() >= 0 and cols["client_id"].max() < 10_000
