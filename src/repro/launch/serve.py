"""Serving drivers: model decode AND warm-cluster pipeline serving.

Model generation on this container with reduced configs:

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b --smoke \
        --steps 32 --batch 4

Pipeline serving — one warm LocalCluster, N concurrent invocations
multiplexed through the event-driven ExecutionEngine:

    PYTHONPATH=src python -m repro.launch.serve \
        --pipeline examples.quickstart_project --workdir /tmp/bp \
        --concurrency 4

The model server buckets incoming prompts to a fixed batch, replays them
into the ring-buffer KV caches, then decodes in lockstep (per-slot indices
are a continuous-batching extension; see DESIGN.md). Intermediate
request/response dataframes ride the same zero-copy transport as pipeline
tables.
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import Optional, Sequence


class PipelineServer:
    """A long-lived pipeline endpoint: one warm worker fleet, shared caches,
    N concurrent invocations in flight (paper §4.2's warm single-tenant host
    plus this PR's multi-run engine).

    Each `submit` gets an isolated Client + run id; results are isolated per
    run while scan/result caches and environments stay warm across
    invocations."""

    def __init__(self, catalog, scratch_root: str, n_workers: int = 4,
                 memory_gb: float = 4.0, validate: str = "warn"):
        from repro.core.runtime import LocalCluster

        if validate not in ("off", "warn", "strict"):
            raise ValueError(f"validate must be off/warn/strict, got "
                             f"{validate!r}")
        self.catalog = catalog
        self.validate = validate
        self.cluster = LocalCluster(catalog, catalog.store, scratch_root,
                                    n_workers=n_workers, memory_gb=memory_gb)
        self._seq = 0
        self._lock = threading.Lock()
        self._checked: set = set()   # id(project)s already analyzed

    def register(self, project, branch: str = "main") -> None:
        """Statically analyze a project once, per the server's `validate`
        mode — a broken project fails at deploy time, not on its first
        request. `submit` registers implicitly on first sight."""
        import sys

        if self.validate == "off":
            return
        with self._lock:
            if id(project) in self._checked:
                return
            self._checked.add(id(project))
        from repro.analysis import check_project

        report = check_project(project, catalog=self.catalog, branch=branch)
        if self.validate == "strict":
            report.raise_first()
        elif report.diagnostics:
            print(f"[serve] project {project.name!r}:\n{report.render()}",
                  file=sys.stderr)

    def submit(self, project, branch: str = "main",
               targets: Optional[Sequence[str]] = None,
               run_id: Optional[str] = None, verbose: bool = False):
        """Non-blocking: returns a RunHandle; concurrent submissions share
        the fleet through the cluster's engine."""
        from repro.core.runtime import Client, submit_run

        self.register(project, branch=branch)
        with self._lock:
            self._seq += 1
            run_id = run_id or f"serve-{self._seq:06d}"
        return submit_run(project, self.cluster, branch=branch,
                          targets=targets, client=Client(verbose=verbose),
                          run_id=run_id)

    def invoke(self, project, **kw):
        """Blocking invocation: submit + wait."""
        return self.submit(project, **kw).wait()

    def close(self) -> None:
        self.cluster.close()


def serve_pipeline_main(args) -> None:
    import importlib
    import os

    from repro.columnar import Catalog, ObjectStore

    mod = importlib.import_module(args.pipeline)
    project = mod.PROJECT
    store = ObjectStore(os.path.join(args.workdir, "s3"))
    catalog = Catalog(store)
    if hasattr(mod, "seed_catalog"):
        mod.seed_catalog(catalog)
    server = PipelineServer(catalog, os.path.join(args.workdir, "dp"),
                            n_workers=args.workers)
    t0 = time.time()
    try:
        handles = [server.submit(project) for _ in range(args.concurrency)]
        for h in handles:
            res = h.wait()
            print(f"run {res.run_id}: {len(res.handles)} tables in "
                  f"{res.wall_seconds:.3f}s")
        print(f"{args.concurrency} concurrent invocations in "
              f"{time.time() - t0:.3f}s on one warm cluster")
    finally:
        server.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", default="the quick brown fox")
    ap.add_argument("--pipeline", default=None,
                    help="module exposing PROJECT: serve pipelines instead "
                         "of a model")
    ap.add_argument("--workdir", default="/tmp/repro_serve")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--concurrency", type=int, default=4)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.pipeline:
        serve_pipeline_main(args)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ARCH_IDS, get_config, smoke_config
    from repro.data.tokenizer import ByteTokenizer
    from repro.models import build_model
    from repro.train import serve_step as ss

    if args.arch not in ARCH_IDS:
        raise SystemExit(f"unknown arch {args.arch!r}; one of {ARCH_IDS}")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tok = ByteTokenizer()
    cfg = dataclasses.replace(cfg, vocab_size=max(tok.vocab_size, 512))
    model = build_model(cfg)
    if cfg.family in ("whisper", "vlm"):
        raise SystemExit("serve CLI demo targets text decoders; whisper/vlm "
                         "decode is exercised in tests")
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)

    ids = tok.encode(args.prompt)
    prompt = jnp.asarray(np.tile(ids, (args.batch, 1)), jnp.int32)
    max_seq = prompt.shape[1] + args.steps + 1
    t0 = time.time()
    out = ss.generate(model, cfg, params, prompt, args.steps, max_seq)
    out = np.asarray(out)
    dt = time.time() - t0
    print(f"generated {args.batch}x{args.steps} tokens in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print("sample:", tok.decode(out[0]))


if __name__ == "__main__":
    main()
