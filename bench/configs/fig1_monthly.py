"""The paper's Fig. 1 pipeline as ``bp.run`` over 31-day windows of a
month-partitioned transactions table.

The user's pipeline (arXiv 2410.17465, Fig. 1) is ``fig1_paper.py``'s
``build_project``, loaded from that file: ``transactions`` scanned with the
run's date window pushed down (the planner prunes the month files the
window cannot touch by their ``eventTime`` stats), ``euro_selection`` keeps
the euro-area countries (a rowwise ``country IN (...)`` filter whose
compaction runs on the device), and ``usd_by_country`` sums USD per country
(a declared group-by whose aggregation runs on the device). Each job is one
run for one window: ``2023-01-01 + d`` through 30 days later.

The table is generated from the seed with vectorised numpy, in event-time
order, and written as one file per calendar month, as a table that lands
by monthly appends is laid out. The plain reference computes each run's
answer from the generated arrays in float64 and imports nothing of the
program.
"""
from __future__ import annotations

import hashlib
import importlib.util
import os
import time

import numpy as np

WINDOW_DAYS = 31
# the columns the plan's scan reads: euro_selection's and its filter's
SCANNED = ["id", "usd", "country", "eventTime"]


def _load_paper():
    """fig1_paper.py beside this file: the user's code, shared."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fig1_paper.py")
    spec = importlib.util.spec_from_file_location("bench_fig1_paper_code",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PAPER = _load_paper()


def year_start(config: dict) -> np.datetime64:
    return np.datetime64(f"{int(config['year'])}-01-01")


def days_in_year(config: dict) -> int:
    start = year_start(config)
    return int((np.datetime64(f"{int(config['year']) + 1}-01-01")
                - start).astype(np.int64))


def yyyymmdd(days: np.ndarray) -> np.ndarray:
    """datetime64[D] -> int64 yyyymmdd (the repo's date encoding)."""
    y = days.astype("datetime64[Y]").astype(np.int64) + 1970
    m = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    return y * 10000 + m * 100 + d


def generate(config: dict, seed: int) -> dict:
    """transactions(id, usd, country, eventTime, client_id) as arrays, in
    event-time order; country as an index into config["all_countries"]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    n = int(config["rows"])
    day = np.sort(rng.integers(0, days_in_year(config), n))
    return {
        "id": np.arange(n, dtype=np.int64),
        "usd": np.round(rng.gamma(2.0, 50.0, n), 2),
        "country": rng.integers(0, len(config["all_countries"]), n,
                                dtype=np.int8),
        "eventTime": yyyymmdd(year_start(config) + day),
        "client_id": rng.integers(0, 10_000, n).astype(np.int64),
    }


def window(d: int, config: dict) -> dict:
    """The run for start day `d`: its bounds as yyyymmdd and as the
    user's filter string, both ends inclusive."""
    first = year_start(config) + int(d)
    last = first + (WINDOW_DAYS - 1)
    return {"start_day": int(d),
            "lo": int(yyyymmdd(np.array([first]))[0]),
            "hi": int(yyyymmdd(np.array([last]))[0]),
            "date_filter": f"eventTime BETWEEN {first} AND {last}"}


def month_bounds(event_time: np.ndarray) -> list:
    """(start, stop) row ranges of each calendar month present, for arrays
    in event-time order."""
    month = event_time // 100
    cuts = np.flatnonzero(np.diff(month)) + 1
    edges = [0, *cuts.tolist(), len(month)]
    return list(zip(edges[:-1], edges[1:]))


def files_in_window(win: dict) -> int:
    """The month files a window touches: what pruning leaves to read."""
    lo, hi = win["lo"] // 100, win["hi"] // 100
    return (hi // 100 - lo // 100) * 12 + hi % 100 - lo % 100 + 1


def euro_mask(cols: dict, config: dict) -> np.ndarray:
    names = config["all_countries"]
    wanted = np.array([c in config["countries"] for c in names])
    return wanted[cols["country"]]


def compact_work(cols: dict, win: dict, euro_before: np.ndarray) -> tuple:
    """The filter compaction's work in one run, from the data and the
    window: the rows delivered to euro_selection (each read once as a
    4-byte mask element) and the rows that survive (each index written
    once as 4 bytes). Returns (rows_in, rows_out). `euro_before` is the
    count of euro rows before each row (`euro_prefix`)."""
    # rows are in event-time order: the window is one contiguous range
    a, b = np.searchsorted(cols["eventTime"], [win["lo"], win["hi"] + 1])
    return int(b - a), int(euro_before[b] - euro_before[a])


def euro_prefix(cols: dict, config: dict) -> np.ndarray:
    """Euro-area rows before each row index, and the total last."""
    out = np.zeros(len(cols["country"]) + 1, np.int64)
    np.cumsum(euro_mask(cols, config), out=out[1:])
    return out


# ---------------------------------------------------------------------------
# the plain reference (numpy, float64) and its lower-precision control
# ---------------------------------------------------------------------------


def reference(cols: dict, win: dict, config: dict,
              precision: str = "float64") -> dict:
    """usd_by_country of one window: {country: usd}. ``"bfloat16"`` is the
    control: usd rounded to bfloat16, sums accumulated in float32."""
    keep = ((cols["eventTime"] >= win["lo"]) & (cols["eventTime"] <= win["hi"])
            & euro_mask(cols, config))
    usd = cols["usd"][keep]
    if precision == "bfloat16":
        import ml_dtypes

        usd = usd.astype(ml_dtypes.bfloat16).astype(np.float32)
        acc = np.float32
    elif precision == "float64":
        acc = np.float64
    else:
        raise ValueError(precision)
    code = cols["country"][keep]
    names = config["all_countries"]
    return {names[c]: float(np.sum(usd[code == c], dtype=acc))
            for c in np.unique(code)}


compare = PAPER.compare


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def write_monthly(catalog, name: str, table, event_time: np.ndarray):
    """Write `table` (rows in event-time order) as one data file per
    calendar month, committed as one snapshot of all of them."""
    from repro.columnar.catalog import Snapshot

    files = []
    for start, stop in month_bounds(event_time):
        files += catalog.write_table(name, table.slice(start, stop - start)
                                     ).files
    snap_id = hashlib.sha256("".join(f.key for f in files).encode()
                             ).hexdigest()[:16]
    snap = Snapshot(snapshot_id=snap_id, schema=table.schema(),
                    files=tuple(files), created_at=time.time())
    catalog.commit("main", {name: snap}, f"{name}: {len(files)} months")
    return snap


def _buckets(lo: int, hi: int) -> list:
    """The padded row counts the kernel entry points use for lo to hi
    rows: powers of two of at least the kernels' row block."""
    from repro.kernels import ops

    b = ops.ROW_BLOCK
    while b < lo:
        b *= 2
    out = [b]
    while b < hi:
        b *= 2
        out.append(b)
    return out


class System:
    """transactions in an object store as 12 month files, a warm
    LocalCluster of in-process workers; one job = one ``bp.run`` of the
    Fig. 1 DAG for one 31-day window."""

    def __init__(self, config: dict, seed: int, workdir: str):
        from repro.columnar import Catalog, ObjectStore
        from repro.core import LocalCluster

        self.config = config
        self.cols = generate(config, seed)
        self.catalog = Catalog(ObjectStore(os.path.join(workdir, "s3")))
        self.snapshot = write_monthly(
            self.catalog, "transactions",
            PAPER.to_table(self.cols, config), self.cols["eventTime"])
        self.cluster = LocalCluster(self.catalog, self.catalog.store,
                                    os.path.join(workdir, "cluster"),
                                    n_workers=int(config["workers"]))
        self._warm()
        self.window_runs = None
        self._closed = False

    def _warm(self) -> None:
        """What a deployment that serves this table holds warm, loaded in
        set-up so that no window pays for it: every worker's columnar scan
        cache holds the scanned columns of every month file (paper 4.2),
        and every padded shape a run can reach is compiled: the compaction
        of any streamed chunk, the group-by of any chunk of a window's euro
        rows (the last chunk of a stream can be of any size), and the
        combine of one partial per month file a window reads."""
        from repro.core import defaults
        from repro.kernels import ops

        for profile in self.cluster.profiles():
            self.cluster.get(profile.worker_id).scan_cache.read_snapshot(
                self.snapshot, SCANNED)
        self.euro_before = euro_prefix(self.cols, self.config)
        month = max(b - a for a, b in month_bounds(self.cols["eventTime"]))
        for rows in _buckets(0, min(month, defaults.STREAM_CHUNK_ROWS)):
            ops.compact_indices(np.zeros(rows, bool))
        wins = [window(d, self.config)
                for d in range(days_in_year(self.config) - WINDOW_DAYS + 1)]
        euro = max(compact_work(self.cols, w, self.euro_before)[1] for w in wins)
        groups = len(self.config["countries"])
        for rows in _buckets(0, euro):
            ops.groupby_aggregate_rows(np.zeros(rows), np.zeros(rows, int),
                                       groups)
        # a static argument left to its default keys another program than
        # one passed as the combine passes it (compute._combine_states_jax)
        for parts in range(1, max(map(files_in_window, wins)) + 1):
            ops.combine_aggregate(np.zeros((parts, groups)), groups, "sum")

    def prepare(self, d: int, rng) -> dict:
        return window(d, self.config)

    def run(self, win: dict) -> dict:
        from repro.core.runtime import Client, execute_run

        client = Client()
        config = dict(self.config, date_filter=win["date_filter"])
        submit = time.time()
        # the call bp.run wraps, which takes the engine settings that
        # bp.run does not pass on: straggler speculation off ("reduced")
        res = execute_run(PAPER.build_project(config, config["backend"]),
                          cluster=self.cluster, client=client,
                          **self.config["engine"])
        table = res.read("usd_by_country", self.cluster)
        if self.window_runs is not None:
            self.window_runs.append({"submit": submit, "done": time.time(),
                                     "events": list(client.events),
                                     "plan": res.plan, "window": win})
        countries = table.column("country").to_numpy()
        usd = table.column("usd").to_numpy()
        return dict(win, groups={str(c): float(u)
                                 for c, u in zip(countries, usd)})

    def begin_window(self) -> None:
        self.window_runs = []

    def observations(self) -> dict:
        runs = self.window_runs or []
        hits = sum(e.kind == "cache_hit" for r in runs for e in r["events"])
        work = [compact_work(self.cols, r["window"], self.euro_before)
                for r in runs]
        return {"runs": runs, "cache_hits": hits, "compact_work": work}

    def host_activity(self, t: float, run) -> str:
        """What the host was doing at wall time t: the tasks in flight."""
        for r in self.window_runs or []:
            if r["submit"] <= t <= r["done"]:
                start, end = {}, {}
                for e in r["events"]:
                    if e.kind == "task_start":
                        start.setdefault(e.task_id, e.ts)
                    elif e.kind == "task_done":
                        end[e.task_id] = e.ts
                busy = sorted(tid for tid, s in start.items()
                              if s <= t <= end.get(tid, float("inf")))
                return "fig1 run: " + (", ".join(busy) or "no task in flight")
        return "between runs"

    def check(self, outcomes) -> tuple:
        checks = {"group_diff": 0, "max_rel_err": 0.0, "unanswered": 0}
        for o in outcomes:
            if o.output is None:
                checks["unanswered"] += 1
                continue
            c = compare(o.output["groups"],
                        reference(self.cols, o.output, self.config))
            for k, v in c.items():
                checks[k] = max(checks[k], v)
        limits = self.config["limits"]
        out = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
        return out, all(v <= limits[k] for k, v in checks.items())

    def control_output(self, outcome) -> dict:
        """The control put in the program's place: the run's answer as the
        reference computes it in bfloat16."""
        out = outcome.output
        return dict(out, groups=reference(self.cols, out, self.config,
                                          "bfloat16"))

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.cluster.close()


def plant_overpruned(monkeypatch):
    """A fault of this configuration's own: the planner's file pruning
    drops the last data file that the window matches, where it matches
    more than one, so a run loses part of its window. (A scan pruned to no
    file at all makes the run retry its scan without end: PERF.md,
    section 7.)"""
    import dataclasses

    from repro.core import physical

    plan = physical.Planner.plan

    def overpruned(self, *args, **kwargs):
        out = plan(self, *args, **kwargs)
        for tid, task in list(out.tasks.items()):
            if isinstance(task, physical.ScanTask) and len(task.files) > 1:
                out.tasks[tid] = dataclasses.replace(task,
                                                     files=task.files[:-1])
        return out
    monkeypatch.setattr(physical.Planner, "plan", overpruned)
