"""Bring-up smoke on one TPU: the main path through the normal entry points,
each phase checked against a reference.

    python chip_smoke.py

Phases, all in this one process (a chip belongs to one process):

  pipeline    the paper's Fig. 1 DAG (configs/paper_pipeline.py) at paper
              scale, 10M generated rows in 10 files, through ``bp.run`` and
              then two concurrent ``bp.submit``s on a LocalCluster of 4
              in-process workers, with the filter's compaction and both
              halves of the group-by on the Pallas kernels. Reference: the
              same project with ``backend="numpy"`` on the same catalog.
  model seam  codeqwen1.5-7b at published widths, depth cut so params and
              KV cache fit one chip, bf16 random weights from a seed. A
              prefill through the flash-attention kernel against the XLA
              attention path, then greedy decode through
              ``train/serve_step.py`` checked against a teacher-forced
              prefill of the tokens it produced.

Every phase that runs a Pallas kernel shows that its compiled program holds
a ``tpu_custom_call``. Without a TPU, or when any phase fails, the script
exits non-zero and prints no result line. On success the last line of
standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import repro as bp  # noqa: E402  (needs the src/ path above)

SEED = 0
# pipeline: the paper-scale source table (benchmarks/run.py --full)
PAPER_ROWS = 10_000_000
ROWS_PER_FILE = 1_000_000
N_WORKERS = 4
# sums through the kernels are float32 ("float32 profile", bp.GroupByCombine)
FLOAT32_RTOL = 1e-4
# model seam: 8 of codeqwen1.5-7b's 32 layers
LAYERS = 8
PREFILL_TOKENS = 4096           # batch 1 x 32 heads x 4096 x head_dim 128
LOGIT_STRIDE = 16               # prefill positions compared: every 16th
DECODE_BATCH = 8
CACHE_SEQ = 2048
PROMPT_TOKENS = 128
DECODE_STEPS = 16
# bf16 keeps 8 significant bits (eps 2**-8 = 3.9e-3). The two attention
# paths round differently in each of 8 layers, and the residual stream
# carries that into the logits: allow a dozen eps of the largest logit.
BF16_RTOL = 5e-2


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); no phase was run")
    return dev


def compiled_for_chip(jitted, *args):
    """Compile `jitted` for these arguments and require a Pallas kernel in
    the compiled program; returns the compiled executable."""
    compiled = jitted.lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{jitted.__name__}: no tpu_custom_call in the "
                             "compiled program")
    return compiled


# ---------------------------------------------------------------------------
# pipeline phase
# ---------------------------------------------------------------------------


def pipeline_phase(workdir: str, rows: int, rows_per_file: int) -> None:
    import numpy as np

    from repro.columnar import Catalog, ObjectStore
    from repro.configs import paper_pipeline
    from repro.core import LocalCluster
    from repro.data.synthetic import make_transactions_table

    cfg = dataclasses.replace(paper_pipeline.get_config(), source_rows=rows,
                              rows_per_file=rows_per_file)
    t0 = time.perf_counter()
    source = make_transactions_table(cfg.source_rows, seed=SEED)
    catalog = Catalog(ObjectStore(os.path.join(workdir, "s3")))
    catalog.write_table(cfg.source_table, source,
                        rows_per_file=cfg.rows_per_file)
    log("pipeline", f"source {cfg.source_table}: {source.num_rows} rows in "
        f"{-(-rows // rows_per_file)} files, set-up "
        f"{time.perf_counter() - t0:.3f}s")

    def cluster(name):
        return LocalCluster(catalog, catalog.store,
                            os.path.join(workdir, name), n_workers=N_WORKERS)

    # reference: the same DAG on host numpy
    ref_cluster = cluster("dp-numpy")
    try:
        t0 = time.perf_counter()
        ref = bp.run(paper_pipeline.build_project(cfg, "numpy"),
                     catalog=catalog, cluster=ref_cluster)
        ref_sel = ref.read("euro_selection", ref_cluster)
        ref_agg = ref.read("usd_by_country", ref_cluster)
    finally:
        ref_cluster.close()
    log("pipeline", f"numpy reference run {time.perf_counter() - t0:.3f}s: "
        f"{ref_sel.num_rows} rows selected, {ref_agg.num_rows} groups")

    def check(label, res, cl):
        shards = sorted(t for t in res.plan.tasks
                        if t.startswith("func:euro_selection#"))
        kinds = {getattr(t, "kind", "") for t in res.plan.tasks.values()}
        if len(shards) < 2 or "combine" not in kinds:
            raise AssertionError(f"{label}: expected a sharded filter and a "
                                 f"combine, plan has {len(shards)} filter "
                                 f"shards and task kinds {sorted(kinds)}")
        sel = res.read("euro_selection", cl)
        if not sel.equals(ref_sel):
            raise AssertionError(f"{label}: filtered rows differ from the "
                                 "numpy run")
        agg = res.read("usd_by_country", cl)
        keys = list(agg.column("country").to_numpy())
        if keys != list(ref_agg.column("country").to_numpy()):
            raise AssertionError(f"{label}: groups {keys} differ from the "
                                 "numpy run")
        got = agg.column("usd").to_numpy().astype(np.float64)
        want = ref_agg.column("usd").to_numpy().astype(np.float64)
        rel = float(np.max(np.abs(got - want) / np.abs(want)))
        if not rel <= FLOAT32_RTOL:
            raise AssertionError(f"{label}: sums differ from the numpy run "
                                 f"by {rel:.3e} > {FLOAT32_RTOL}")
        log("pipeline", f"{label}: {len(shards)} filter shards + combine, "
            f"{sel.num_rows} rows identical to numpy, {len(keys)} sums "
            f"within rtol {FLOAT32_RTOL} (max rel err {rel:.3e}) -> match")

    proj = paper_pipeline.build_project(cfg, "jax")
    dev_cluster = cluster("dp-jax")
    try:
        t0 = time.perf_counter()
        res = bp.run(proj, catalog=catalog, cluster=dev_cluster)
        log("pipeline", f"bp.run backend=jax {time.perf_counter() - t0:.3f}s")
        check("bp.run", res, dev_cluster)
    finally:
        dev_cluster.close()

    # a fresh cluster: two concurrent runs, neither served from a warm cache
    conc_cluster = cluster("dp-jax-concurrent")
    try:
        t0 = time.perf_counter()
        handles = [bp.submit(proj, cluster=conc_cluster) for _ in range(2)]
        results = [h.wait() for h in handles]
        log("pipeline", f"2 concurrent bp.submit backend=jax "
            f"{time.perf_counter() - t0:.3f}s")
        for i, r in enumerate(results):
            check(f"bp.submit #{i}", r, conc_cluster)
    finally:
        conc_cluster.close()

    # On a TPU `ops` never interprets, so the runs above executed compiled
    # kernels. Show the compiled programs at the full table's size: the
    # largest shapes the host entry points hand the wrappers at this scale.
    import jax
    import jax.numpy as jnp

    from repro.columnar.expr import parse_predicate
    from repro.kernels import ops

    scanned = int(np.sum(parse_predicate(cfg.date_filter).evaluate(source)))
    n_sel, n_groups = ref_sel.num_rows, ref_agg.num_rows
    compiled_for_chip(ops.compact, jax.ShapeDtypeStruct((ops._bucket(scanned),),
                                                        jnp.bool_))
    compiled_for_chip(ops.groupby_aggregate,
                      jax.ShapeDtypeStruct((ops._bucket(n_sel),), jnp.float32),
                      jax.ShapeDtypeStruct((ops._bucket(n_sel),), jnp.int32),
                      ops._lane_pad(n_groups))
    compiled_for_chip(ops.combine_aggregate,
                      jax.ShapeDtypeStruct((N_WORKERS, n_groups), jnp.float32),
                      n_groups)
    log("pipeline", f"compact ({scanned} rows), groupby_aggregate ({n_sel} "
        f"rows, {n_groups} groups) and combine_aggregate ({N_WORKERS} x "
        f"{n_groups}) compile to tpu_custom_call")


# ---------------------------------------------------------------------------
# model-seam phase
# ---------------------------------------------------------------------------


def model_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.train import serve_step as ss

    log("model", f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
        f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.n_layers} layers, {cfg.param_count() / 1e9:.3f}B params bf16")
    xla = build_model(cfg)
    pallas = build_model(dataclasses.replace(cfg, attention_impl="pallas"))
    k_params, k_prefill, k_prompt = jax.random.split(jax.random.PRNGKey(SEED),
                                                     3)
    t0 = time.perf_counter()
    params = jax.block_until_ready(xla.init(k_params, dtype=jnp.bfloat16))
    log("model", f"random bf16 params from seed {SEED}: "
        f"{time.perf_counter() - t0:.3f}s")

    def prefill_fn(model):
        def prefill(params, tokens):
            logits, _ = model.prefill(params, {"tokens": tokens})
            return logits[:, ::LOGIT_STRIDE]
        return jax.jit(prefill)

    tokens = jax.random.randint(k_prefill, (1, PREFILL_TOKENS), 0,
                                cfg.vocab_size, jnp.int32)
    t0 = time.perf_counter()
    flash = compiled_for_chip(prefill_fn(pallas), params, tokens)
    got = jax.block_until_ready(flash(params, tokens))
    t_flash = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = jax.block_until_ready(prefill_fn(xla)(params, tokens))
    t_xla = time.perf_counter() - t0
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    rms = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    if not (np.isfinite(scale) and err <= BF16_RTOL * scale):
        raise AssertionError(f"prefill: flash vs xla max abs diff {err:.4e} "
                             f"> {BF16_RTOL} x {scale:.4e}")
    log("model", f"prefill 1x{PREFILL_TOKENS}: flash kernel compiles to "
        f"tpu_custom_call; logits vs xla path max abs diff {err:.4e} <= "
        f"{BF16_RTOL} x max |logit| {scale:.4e} (relative rms {rms:.4e}, "
        f"argmax agrees at {agree:.4f} of positions) -> match "
        f"(compile+run flash {t_flash:.3f}s, xla {t_xla:.3f}s)")
    del got, want

    prompt = jax.random.randint(k_prompt, (DECODE_BATCH, PROMPT_TOKENS), 0,
                                cfg.vocab_size, jnp.int32)
    generate = jax.jit(lambda p, x: ss.generate(xla, cfg, p, x, DECODE_STEPS,
                                                CACHE_SEQ))
    t0 = time.perf_counter()
    seq = jax.block_until_ready(generate(params, prompt))
    log("model", f"decode: batch {DECODE_BATCH}, cache {CACHE_SEQ}, prompt "
        f"{PROMPT_TOKENS}, {DECODE_STEPS} greedy steps through serve_step "
        f"(compile+run {time.perf_counter() - t0:.3f}s; no Pallas kernel on "
        "the decode path)")

    # teacher-forced reference: position p's logits predict token p + 1
    @jax.jit
    def greedy_gap(params, seq):
        logits, _ = xla.prefill(params, {"tokens": seq})
        lg = logits[:, PROMPT_TOKENS - 1:-1]                 # (B, steps, V)
        chosen = jnp.take_along_axis(lg, seq[:, PROMPT_TOKENS:, None], -1)
        best = jnp.max(lg, axis=-1)
        return best - chosen[..., 0], best, jnp.argmax(lg, -1)

    gap, best, argmax = greedy_gap(params, seq)
    gap, best = np.asarray(gap), np.asarray(best)
    same = int(np.sum(np.asarray(argmax) == np.asarray(seq[:, PROMPT_TOKENS:])))
    tol = BF16_RTOL * float(np.max(np.abs(best)))
    if not (np.all(np.isfinite(gap)) and float(np.max(gap)) <= tol):
        raise AssertionError(f"decode: a greedy token is {np.max(gap):.4e} "
                             f"below the reference's best logit (> {tol:.4e})")
    log("model", f"decode tokens vs teacher-forced prefill: {same}/{gap.size} "
        f"identical argmax, every chosen logit within {tol:.4e} of the best "
        f"(max gap {float(np.max(gap)):.4e}) -> match")


def main() -> None:
    dev = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log("device", f"platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(jax.devices())}; compile cache {enable_compile_cache()}")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        t0 = time.perf_counter()
        pipeline_phase(workdir, PAPER_ROWS, ROWS_PER_FILE)
        log("pipeline", f"phase passed in {time.perf_counter() - t0:.3f}s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    t0 = time.perf_counter()
    model_phase(dataclasses.replace(get_config("codeqwen1.5-7b"),
                                    n_layers=LAYERS))
    log("model", f"phase passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
