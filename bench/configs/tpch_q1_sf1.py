"""TPC-H Q1 over lineitem as pipeline runs on a warm cluster.

The query (TPC-H v3.0.1, clause 2.4.1)::

    select l_returnflag, l_linestatus,
           sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '[DELTA]' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

The user's pipeline: a scan of ``lineitem`` with the date predicate pushed
down, a rowwise model that prices each line, and a declared group-by
(``bp.GroupByCombine(backend="jax")``) that the planner splits into
per-shard partial aggregates and one combine.

Data follows clause 4.2.3 for the seven columns Q1 reads, generated with
vectorised numpy from the seed. The plain reference below computes Q1 from
those arrays in float64 and imports nothing of the program.
"""
from __future__ import annotations

import time

import numpy as np

KEYS = ["l_returnflag", "l_linestatus"]
AGGS = {
    "sum_qty": ("l_quantity", "sum"),
    "sum_base_price": ("l_extendedprice", "sum"),
    "sum_disc_price": ("disc_price", "sum"),
    "sum_charge": ("charge", "sum"),
    "avg_qty": ("l_quantity", "mean"),
    "avg_price": ("l_extendedprice", "mean"),
    "avg_disc": ("l_discount", "mean"),
    "count_order": ("l_quantity", "count"),
}
# clause 4.2.3's dates: orders from STARTDATE to ENDDATE - 151 days
START_DATE = np.datetime64("1992-01-01")
LAST_ORDER_DATE = np.datetime64("1998-08-02")
CURRENT_DATE = np.datetime64("1995-06-17")
Q1_BASE_DATE = np.datetime64("1998-12-01")


def yyyymmdd(days: np.ndarray) -> np.ndarray:
    """datetime64[D] -> int64 yyyymmdd (the repo's date encoding)."""
    y = days.astype("datetime64[Y]").astype(np.int64) + 1970
    m = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    return y * 10000 + m * 100 + d


def cutoff(delta_days: int) -> int:
    return int(yyyymmdd(np.array([Q1_BASE_DATE - np.timedelta64(
        int(delta_days), "D")]))[0])


def generate(config: dict, seed: int) -> dict:
    """lineitem's Q1 columns, clause 4.2.3, as numpy arrays. The two flags
    come as byte codes (one ASCII letter each)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    n = int(config["rows"])
    # lines per order: uniform 1..7; an order's lines share its date
    counts = rng.integers(1, 8, n // 4 + 8)
    while counts.sum() < n:
        counts = np.concatenate([counts, rng.integers(1, 8, n // 4 + 8)])
    span = int((LAST_ORDER_DATE - START_DATE).astype(np.int64))
    order_date = START_DATE + rng.integers(0, span + 1, len(counts))
    order_date = np.repeat(order_date, counts)[:n]
    ship = order_date + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n)
    flag = np.where(receipt <= CURRENT_DATE,
                    np.where(returned == 0, ord("R"), ord("A")),
                    ord("N")).astype(np.uint8)
    status = np.where(ship > CURRENT_DATE, ord("O"), ord("F")).astype(np.uint8)
    qty = rng.integers(1, 51, n).astype(np.float64)
    partkey = rng.integers(1, int(config["scale_factor"] * 200_000) + 1, n)
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100
    return {
        "l_quantity": qty,
        "l_extendedprice": np.round(qty * retail, 2),
        "l_discount": rng.integers(0, 11, n) / 100,
        "l_tax": rng.integers(0, 9, n) / 100,
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_shipdate": yyyymmdd(ship),
    }


def to_table(cols: dict):
    """The arrays as the repo's columnar table; the flags become utf8
    columns of one byte each, built without Python strings."""
    from repro.columnar.table import Column, ColumnTable

    n = len(cols["l_quantity"])
    offsets = np.arange(n + 1, dtype=np.int32)
    out = {}
    for name, values in cols.items():
        if name in KEYS:
            out[name] = Column("utf8", values.copy(), offsets)
        else:
            out[name] = Column("numeric", values)
    return ColumnTable(out)


def build_project(cut: int, backend: str):
    """The user's pipeline for one DELTA (cut = the shipdate bound)."""
    import repro as bp
    from repro.columnar import compute

    proj = bp.Project("tpch_q1")

    @proj.model(rowwise=True)
    def priced(data=bp.Model("lineitem",
                             columns=["l_returnflag", "l_linestatus",
                                      "l_quantity", "l_extendedprice",
                                      "l_discount", "l_tax"],
                             filter=f"l_shipdate <= {cut}")):
        price = data.column("l_extendedprice").data
        disc_price = price * (1 - data.column("l_discount").data)
        charge = disc_price * (1 + data.column("l_tax").data)
        return data.with_column("disc_price", disc_price) \
                   .with_column("charge", charge)

    @proj.model(materialize=True,
                combinable=bp.GroupByCombine(KEYS, AGGS, backend=backend))
    def q1(data=bp.Model("priced")):
        return compute.group_by(data, KEYS, AGGS, backend=backend)

    return proj


# ---------------------------------------------------------------------------
# the plain reference (numpy, float64) and its lower-precision control
# ---------------------------------------------------------------------------


def reference(cols: dict, cut: int, precision: str = "float64") -> dict:
    """Q1 from the generated arrays: {(flag, status): {agg: value}}.
    ``precision="bfloat16"`` is the control: every input and product
    rounded to bfloat16, sums accumulated in float32."""
    keep = cols["l_shipdate"] <= cut
    flag, status = cols["l_returnflag"][keep], cols["l_linestatus"][keep]
    qty, price = cols["l_quantity"][keep], cols["l_extendedprice"][keep]
    disc, tax = cols["l_discount"][keep], cols["l_tax"][keep]
    if precision == "bfloat16":
        import ml_dtypes

        bf = lambda x: np.asarray(x).astype(ml_dtypes.bfloat16) \
                                    .astype(np.float32)
        qty, price, disc, tax = bf(qty), bf(price), bf(disc), bf(tax)
        disc_price = bf(price * bf(1 - disc))
        charge = bf(disc_price * bf(1 + tax))
        acc = np.float32
    elif precision == "float64":
        disc_price = price * (1 - disc)
        charge = disc_price * (1 + tax)
        acc = np.float64
    else:
        raise ValueError(precision)
    out = {}
    for f in np.unique(flag):
        for s in np.unique(status):
            g = (flag == f) & (status == s)
            n = int(g.sum())
            if n == 0:
                continue
            sums = {k: np.sum(v[g], dtype=acc) for k, v in
                    (("qty", qty), ("price", price), ("disc", disc),
                     ("disc_price", disc_price), ("charge", charge))}
            out[(chr(f), chr(s))] = {
                "sum_qty": float(sums["qty"]),
                "sum_base_price": float(sums["price"]),
                "sum_disc_price": float(sums["disc_price"]),
                "sum_charge": float(sums["charge"]),
                "avg_qty": float(sums["qty"] / acc(n)),
                "avg_price": float(sums["price"] / acc(n)),
                "avg_disc": float(sums["disc"] / acc(n)),
                "count_order": n,
            }
    return out


def compare(got: dict, want: dict) -> dict:
    """The numbers `correct` rests on: groups present on one side only,
    the largest count difference, and the largest relative error of any
    other aggregate of any group."""
    missing = set(got) ^ set(want)
    count_diff, rel = 0, 0.0
    for key in set(got) & set(want):
        for agg, ref in want[key].items():
            val = got[key][agg]
            if agg == "count_order":
                count_diff = max(count_diff, abs(int(val) - int(ref)))
            else:
                rel = max(rel, abs(val - ref) / max(abs(ref), 1e-300))
    return {"group_diff": len(missing), "count_diff": count_diff,
            "max_rel_err": rel}


def _rows(table) -> dict:
    cols = {n: table.column(n).to_numpy() for n in table.column_names}
    out = {}
    for i in range(table.num_rows):
        out[(str(cols[KEYS[0]][i]), str(cols[KEYS[1]][i]))] = {
            agg: (int(cols[agg][i]) if agg == "count_order"
                  else float(cols[agg][i])) for agg in AGGS}
    return out


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class System:
    """lineitem in an object store, a warm LocalCluster of in-process
    workers; one job = one ``bp.run`` of Q1 for one DELTA."""

    def __init__(self, config: dict, seed: int, workdir: str):
        import os

        from repro.columnar import Catalog, ObjectStore
        from repro.core import LocalCluster

        self.config = config
        self.cols = generate(config, seed)
        self.catalog = Catalog(ObjectStore(os.path.join(workdir, "s3")))
        self.catalog.write_table("lineitem", to_table(self.cols),
                                 rows_per_file=int(config["rows_per_file"]))
        self.cluster = LocalCluster(self.catalog, self.catalog.store,
                                    os.path.join(workdir, "cluster"),
                                    n_workers=int(config["workers"]))
        self.window_runs = None
        self._closed = False

    def prepare(self, delta: int, rng) -> int:
        return delta

    def run(self, delta: int) -> dict:
        from repro.core.runtime import Client, execute_run

        client = Client()
        cut = cutoff(delta)
        submit = time.time()
        # the call bp.run wraps, which takes the engine settings that
        # bp.run does not pass on: straggler speculation off ("reduced")
        res = execute_run(build_project(cut, self.config["backend"]),
                          cluster=self.cluster, client=client,
                          shard_threshold_bytes=int(
                              self.config["shard_threshold_bytes"]),
                          **self.config["engine"])
        table = res.read("q1", self.cluster)
        if self.window_runs is not None:
            self.window_runs.append({"submit": submit, "done": time.time(),
                                     "events": list(client.events),
                                     "plan": res.plan})
        return {"delta": delta, "cut": cut, "rows": _rows(table)}

    def begin_window(self) -> None:
        self.window_runs = []

    def observations(self) -> dict:
        runs = self.window_runs or []
        hits = sum(e.kind == "cache_hit" for r in runs for e in r["events"])
        return {"runs": runs, "cache_hits": hits}

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
                busy = sorted({type(r["plan"].tasks[tid]).__name__
                               + " " + tid.split("#")[0]
                               for tid, s in start.items()
                               if s <= t <= end.get(tid, float("inf"))})
                return "q1 run: " + (", ".join(busy) or "no task in flight")
        return "between runs"

    def check(self, outcomes) -> tuple:
        checks = {"group_diff": 0, "count_diff": 0, "max_rel_err": 0.0,
                  "unanswered": 0}
        for o in outcomes:
            if o.output is None:
                checks["unanswered"] += 1
                continue
            c = compare(o.output["rows"], reference(self.cols,
                                                    o.output["cut"]))
            for k, v in c.items():
                checks[k] = max(checks[k], v)
        limits = self.config["limits"]
        out = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
        return out, all(v <= limits[k] for k, v in checks.items())

    def control_output(self, outcome) -> dict:
        """The control put in the program's place: the run's answer as the
        reference computes it in bfloat16."""
        out = outcome.output
        return dict(out, rows=reference(self.cols, out["cut"], "bfloat16"))

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.cluster.close()
