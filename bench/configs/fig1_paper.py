"""The paper's Fig. 1 pipeline served as an endpoint of ``bp.serve``.

The user's pipeline (arXiv 2410.17465, Fig. 1): ``transactions`` scanned
with a date window pushed down, ``euro_selection`` keeps the euro-area
countries (a rowwise filter whose compaction runs on the device), and
``usd_by_country`` sums USD per country (a declared group-by whose
aggregation runs on the device). Each request is a table of transactions;
its response is that table's ``usd_by_country``.

Requests follow the repo's transactions distributions (``data/synthetic``),
generated here with vectorised numpy. The plain reference computes each
response from the request's arrays in float64 and imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np


def make_request(n: int, rng: np.random.Generator, config: dict) -> dict:
    """transactions(id, usd, country, eventTime, client_id) as arrays;
    country as an index into config["all_countries"]."""
    months = rng.integers(1, 13, n)
    days = rng.integers(1, 29, n)
    return {
        "id": np.arange(n, dtype=np.int64),
        "usd": np.round(rng.gamma(2.0, 50.0, n), 2),
        "country": rng.integers(0, len(config["all_countries"]), n),
        "eventTime": (config["year"] * 10000 + months * 100
                      + days).astype(np.int64),
        "client_id": rng.integers(0, 10_000, n).astype(np.int64),
    }


def to_table(req: dict, config: dict):
    """The arrays as the repo's columnar table (utf8 country built from
    the code array, without Python strings)."""
    from repro.columnar.table import Column, ColumnTable

    names = [c.encode() for c in config["all_countries"]]
    lens = np.array([len(c) for c in names], np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    pool = np.frombuffer(b"".join(names), np.uint8)
    code = req["country"]
    n = len(code)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lens[code], out=offsets[1:])
    idx = np.repeat(starts[code] - offsets[:-1], lens[code]) \
        + np.arange(offsets[-1])
    cols = {k: Column("numeric", v) for k, v in req.items()
            if k != "country"}
    cols["country"] = Column("utf8", pool[idx], offsets)
    return ColumnTable({k: cols[k] for k in
                        ("id", "usd", "country", "eventTime", "client_id")})


def build_project(config: dict, backend: str):
    """The user's pipeline, as in the paper's Fig. 1."""
    import repro as bp
    from repro.columnar import compute

    proj = bp.Project("fig1")
    countries = "country IN (%s)" % ",".join(
        f"'{c}'" for c in config["countries"])
    aggs = {"usd": ("usd", "sum")}

    @proj.model(rowwise=True)
    @proj.python("3.11", {"pandas": "2.0"})
    def euro_selection(data=bp.Model("transactions",
                                     columns=["id", "usd", "country"],
                                     filter=config["date_filter"])):
        return compute.filter_table(data, countries, backend=backend)

    @proj.model(materialize=True,
                combinable=bp.GroupByCombine(["country"], aggs,
                                             backend=backend))
    @proj.python("3.10", {"pandas": "1.5.3"})
    def usd_by_country(data=bp.Model("euro_selection")):
        return compute.group_by(data, ["country"], aggs, backend=backend)

    return proj


# ---------------------------------------------------------------------------
# the plain reference (numpy, float64) and its lower-precision control
# ---------------------------------------------------------------------------


def reference(req: dict, config: dict, precision: str = "float64") -> dict:
    """usd_by_country of one request: {country: usd}. ``"bfloat16"`` is
    the control: usd rounded to bfloat16, sums accumulated in float32."""
    names = config["all_countries"]
    wanted = np.array([c in config["countries"] for c in names])
    keep = ((req["eventTime"] >= config["date_lo"])
            & (req["eventTime"] <= config["date_hi"])
            & wanted[req["country"]])
    usd = req["usd"][keep]
    if precision == "bfloat16":
        import ml_dtypes

        usd = usd.astype(ml_dtypes.bfloat16).astype(np.float32)
        acc = np.float32
    elif precision == "float64":
        acc = np.float64
    else:
        raise ValueError(precision)
    code = req["country"][keep]
    return {names[c]: float(np.sum(usd[code == c], dtype=acc))
            for c in np.unique(code)}


def compare(got: dict, want: dict) -> dict:
    missing = set(got) ^ set(want)
    rel = 0.0
    for key in set(got) & set(want):
        rel = max(rel, abs(got[key] - want[key]) / max(abs(want[key]), 1e-300))
    return {"group_diff": len(missing), "max_rel_err": rel}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class _Pending:
    def __init__(self, ticket):
        self.ticket = ticket

    def wait(self, timeout: float):
        table = self.ticket.result(timeout)
        done = self.ticket.submitted + self.ticket.latency_s
        countries = table.column("country").to_numpy()
        usd = table.column("usd").to_numpy()
        return done, {str(c): float(u) for c, u in zip(countries, usd)}


class System:
    """A ``bp.serve`` gateway over a warm LocalCluster; one request = one
    ``Gateway.submit`` of a transactions table."""

    def __init__(self, config: dict, seed: int, workdir: str):
        import os

        import repro as bp
        from repro.columnar import Catalog, ObjectStore

        self.config = config
        self.catalog = Catalog(ObjectStore(os.path.join(workdir, "s3")))
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        # the endpoint's base table on main, so registration can check the
        # pipeline against a real schema; requests replace it per batch
        self.catalog.write_table("transactions", to_table(
            make_request(int(config["base_rows"]), rng, config), config))
        self.gateway = bp.serve(build_project(config, config["backend"]),
                                catalog=self.catalog,
                                scratch_root=os.path.join(workdir, "gw"),
                                source_table="transactions",
                                target="usd_by_country",
                                n_workers=int(config["workers"]))
        self.base = None
        self._closed = False

    def prepare(self, rows: int, rng) -> dict:
        req = make_request(rows, rng, self.config)
        return {"arrays": req, "table": to_table(req, self.config)}

    def submit(self, item: dict) -> _Pending:
        return _Pending(self.gateway.submit(
            "default", item["table"], slo=self.config["slo"],
            tenant=self.config["tenant"]))

    def _counters(self) -> dict:
        return {k: sum(v.values()) for k, v in
                self.gateway.metrics()["counters"].items()}

    def begin_window(self) -> None:
        self.base = self._counters()

    def observations(self) -> dict:
        now = self._counters()
        delta = {k: v - (self.base or {}).get(k, 0) for k, v in now.items()}
        return {"counters": delta,
                "cache_hits": delta.get("engine_cache_hits", 0)}

    def host_activity(self, t: float, run) -> str:
        w = run.window
        offset = w.wall0 - w.t0
        inflight = sum(1 for o in run.outcomes
                       if o.sent + offset <= t
                       and (o.done is None or t <= o.done + offset))
        return f"{inflight} requests in flight"

    def _classify(self, err) -> str:
        from repro.core.errors import DeadlineExceeded
        from repro.serving import AdmissionError

        if isinstance(err, (AdmissionError, DeadlineExceeded)):
            return "refused"
        return "unanswered"

    def check(self, outcomes) -> tuple:
        checks = {"group_diff": 0, "max_rel_err": 0.0, "unanswered": 0}
        for o in outcomes:
            if o.output is None:
                if o.error is None or self._classify(o.error) != "refused":
                    checks["unanswered"] += 1
                continue
            c = compare(o.output, reference(o.item["arrays"], self.config))
            for k, v in c.items():
                checks[k] = max(checks[k], v)
        limits = self.config["limits"]
        out = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
        return out, all(v <= limits[k] for k, v in checks.items())

    def control_output(self, outcome) -> dict:
        """The control put in the program's place: the response as the
        reference computes it in bfloat16."""
        return reference(outcome.item["arrays"], self.config, "bfloat16")

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.gateway.close()
