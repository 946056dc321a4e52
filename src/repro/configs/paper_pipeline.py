"""The paper's own 'architecture': the Fig. 1 demo DAG as a config.

Not one of the 10 assigned LM architectures — this is the workload the paper
itself evaluates (transactions -> euro_selection -> usd_by_country), exposed
the same way the LM configs are so the CLI / benchmarks / tests can select it
(`examples/quickstart_project.py` is the runnable form).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

ARCH_ID = "paper-fig1-pipeline"


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    name: str = ARCH_ID
    source_table: str = "transactions"
    source_rows: int = 1_000_000
    rows_per_file: int = 100_000
    date_filter: str = "eventTime BETWEEN 2023-01-01 AND 2023-02-01"
    countries: Tuple[str, ...] = ("IT", "FR", "DE", "ES", "NL", "GB")
    pushdown_columns: Tuple[str, ...] = ("id", "usd", "country")
    envs: Tuple[Tuple[str, Tuple[Tuple[str, str], ...]], ...] = (
        ("3.11", (("pandas", "2.0"),)),
        ("3.10", (("pandas", "1.5.3"),)),
    )


def get_config() -> PipelineConfig:
    return PipelineConfig()


def smoke_config() -> PipelineConfig:
    return dataclasses.replace(get_config(), source_rows=20_000,
                               rows_per_file=5_000)


def build_project(cfg: PipelineConfig, backend: str = "numpy"):
    """Instantiate the DAG from the config (used by tests/benchmarks).

    The filter is row-local, so a sharded scan stays sharded through it,
    and the aggregation is a declared group-by, so each shard aggregates
    locally and only per-country states meet at the combine.
    ``backend="jax"`` runs the filter's compaction and both halves of the
    group-by on the Pallas kernels (`repro.kernels`)."""
    import repro as bp
    from repro.columnar import compute

    proj = bp.Project(cfg.name)
    filt = "country IN (%s)" % ",".join(f"'{c}'" for c in cfg.countries)
    aggs = {"usd": ("usd", "sum")}

    @proj.model(rowwise=True)
    @proj.python(cfg.envs[0][0], dict(cfg.envs[0][1]))
    def euro_selection(data=bp.Model(cfg.source_table,
                                     columns=list(cfg.pushdown_columns),
                                     filter=cfg.date_filter)):
        return compute.filter_table(data, filt, backend=backend)

    @proj.model(materialize=True,
                combinable=bp.GroupByCombine(["country"], aggs,
                                             backend=backend))
    @proj.python(cfg.envs[1][0], dict(cfg.envs[1][1]))
    def usd_by_country(data=bp.Model("euro_selection")):
        return compute.group_by(data, ["country"], aggs, backend=backend)

    return proj
