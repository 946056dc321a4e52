"""End-to-end trainer: bauplan data pipeline -> sharded train loop ->
fault-tolerant checkpoints.

Runs REAL training on this container for reduced configs (the full configs
are exercised by the dry-run):

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
        --steps 50 --batch 8 --seq 128

Features: bauplan-DAG data prep (tokenize/pack with caching), deterministic
seekable data stream, async checkpointing + restart (--resume), simulated
failure injection (--fail-at) to exercise restart, elastic device-count
changes between runs (checkpoints are mesh-agnostic).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.columnar import Catalog, ObjectStore
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.core.runtime import Client, LocalCluster, execute_run
from repro.data.pipeline import TokenBatchStream, build_data_project
from repro.data.synthetic import make_corpus_table
from repro.data.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import checkpoint as ckpt
from repro.train import train_step as ts
from repro.train.optimizer import OptimizerConfig


def prepare_data(workdir: str, seq_len: int, n_docs: int,
                 client: Client) -> TokenBatchStream:
    """Run the tokenize/pack DAG under the bauplan runtime."""
    store = ObjectStore(os.path.join(workdir, "s3"))
    catalog = Catalog(store)
    if "corpus" not in catalog.list_tables():
        catalog.write_table("corpus", make_corpus_table(n_docs),
                            rows_per_file=max(n_docs // 4, 1))
    tok = ByteTokenizer.train(
        [str(t) for t in
         catalog.read_table("corpus", columns=["text"],
                            local_dir=os.path.join(workdir, "scan"))
         .column("text").to_numpy()[:64]], num_merges=64)
    proj = build_data_project(tok, seq_len)
    cluster = LocalCluster(catalog, store, os.path.join(workdir, "dp"),
                           n_workers=2)
    try:
        res = execute_run(proj, catalog=catalog, cluster=cluster,
                          client=client,
                          journal_path=os.path.join(workdir, "journal.jsonl"))
        packed = res.read("packed_tokens", cluster)
    finally:
        cluster.close()
    return TokenBatchStream(packed, seq_len, batch_size=1), tok


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a crash at this step (restart demo)")
    ap.add_argument("--n-docs", type=int, default=256)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    workdir = args.workdir or tempfile.mkdtemp(prefix="repro_train_")
    os.makedirs(workdir, exist_ok=True)
    print(f"workdir: {workdir}")

    client = Client(verbose=False)
    t0 = time.time()
    stream, tok = prepare_data(workdir, args.seq, args.n_docs, client)
    stream.batch = args.batch
    print(f"data pipeline done in {time.time() - t0:.2f}s "
          f"({stream.n_rows} rows, vocab {tok.vocab_size}) "
          f"events={len(client.events)}")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, vocab_size=max(tok.vocab_size, 512))
    model = build_model(cfg)
    tcfg = ts.TrainConfig(
        optimizer=OptimizerConfig(learning_rate=args.lr, warmup_steps=10,
                                  total_steps=args.steps),
        microbatches=args.microbatches)
    step_fn = jax.jit(ts.make_train_step(model, cfg, tcfg),
                      donate_argnums=(0,))

    ckpt_dir = os.path.join(workdir, "ckpt")
    saver = ckpt.AsyncCheckpointer(ckpt_dir)
    start_step = 0
    if args.resume and ckpt.latest_step(ckpt_dir) is not None:
        payload = ckpt.restore_checkpoint(ckpt_dir)
        state = payload["state"]
        state = jax.tree.map(jnp.asarray, state)
        stream.seek({k: int(v) for k, v in payload["data_state"].items()})
        start_step = int(np.asarray(state["step"]))
        print(f"resumed from step {start_step}")
    else:
        state = ts.make_train_state(model, jax.random.PRNGKey(0),
                                    dtype=jnp.float32)

    losses = []
    for step in range(start_step, args.steps):
        batch = next(stream)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            saver.save(step + 1, {"state": state,
                                  "data_state": stream.state()})
        if args.fail_at == step:
            saver.wait()
            raise SystemExit(f"injected failure at step {step} "
                             f"(rerun with --resume)")
    saver.save(args.steps, {"state": state, "data_state": stream.state()})
    saver.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
