"""End-to-end driver: PIPER preprocessing → DLRM training (the paper's
Figure 2 system, in one program) — streamed and overlapped.

Training pulls its batches straight from the
:class:`~repro.stream.StreamingPreprocessService` through the
:class:`~repro.train.input_pipeline.TrainInputPipeline` bridge: raw
utf8 payloads are preprocessed on the fly, assembled into fixed-shape
batches, staged onto the device while the donated train step runs, and
cached content-addressed (:class:`~repro.data.chunk_cache.ChunkCache`)
so epochs ≥ 2 skip preprocessing entirely. Nothing is materialized up
front, and the hot path has no blocking host sync (the loss scalar is
read one step lagged).

At exit the driver prints the e2e stall split (input_wait vs
train_step), the service's own stall buckets, and the cache counters.

    PYTHONPATH=src python examples/train_dlrm_e2e.py [--steps 300]
        [--no-overlap] [--cache-mb 64] [--prefetch-depth 2]
        [--trace out.json]
"""

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import obs
from repro.core import pipeline as P
from repro.core import schema as schema_lib
from repro.data import chunk_cache as chunk_cache_lib
from repro.data import synth
from repro.launch import compile_cache
from repro.models import dlrm
from repro.stream import StreamingPreprocessService
from repro.train import checkpoint as ckpt_lib
from repro.train import input_pipeline as input_lib
from repro.train import optimizer as opt_lib
from repro.train import steps as steps_lib


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--rows", type=int, default=8_192)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=5_000)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_dlrm_ckpt")
    ap.add_argument(
        "--no-overlap",
        action="store_true",
        help="stage batches synchronously inside next() (the stall baseline)",
    )
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument(
        "--cache-mb",
        type=int,
        default=64,
        help="chunk-cache capacity in MiB (0 disables the cache)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="export a Perfetto trace of the run plus a metrics snapshot "
        "(OUT.metrics.json) — the PR 7 observability machinery",
    )
    args = ap.parse_args()

    if args.trace:
        obs.enable()

    # ---- preprocessing service (the paper's contribution) ------------ #
    schema = dataclasses.replace(schema_lib.CRITEO, vocab_range=args.vocab)
    scfg = synth.SynthConfig(schema=schema, rows=args.rows, seed=0)
    t0 = time.perf_counter()
    buf, table = synth.make_dataset(scfg)
    payload_rows = min(args.batch, args.rows)
    config = P.PipelineConfig(
        schema=schema,
        chunk_bytes=1 << 17,
        max_rows_per_chunk=payload_rows,
    )
    pipe = P.PiperPipeline(config)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 1 << 17))
    n_payloads = args.rows // payload_rows
    payloads = list(
        synth.request_payloads(buf, table, [payload_rows] * n_payloads)
    )
    cache = None
    if args.cache_mb > 0:
        cache = chunk_cache_lib.ChunkCache(capacity_bytes=args.cache_mb << 20)
    service = StreamingPreprocessService(
        config, state, bucket_rows=(payload_rows,), cache=cache
    ).start()
    print(
        f"PIPER loop-1 vocab over {args.rows} rows in "
        f"{time.perf_counter()-t0:.2f}s; streaming loop-2 from here on"
        f" (cache={'off' if cache is None else f'{args.cache_mb}MiB'})"
    )

    # ---- DLRM training, fed by the overlapped input bridge ----------- #
    # bottom_mlp must end at embed_dim (the dense vector joins the
    # per-table embeddings in the pairwise interaction)
    mcfg = dlrm.DLRMConfig(
        vocab_range=args.vocab,
        embed_dim=16,
        bottom_mlp=(128, 64, 16),
        top_mlp=(128, 64, 1),
    )
    params = dlrm.init(jax.random.PRNGKey(0), mcfg)
    opt_state = opt_lib.adamw_init(params)
    ocfg = opt_lib.AdamWConfig(
        schedule=opt_lib.cosine_schedule(2e-3, 20, args.steps), weight_decay=0.0
    )
    ckpt = ckpt_lib.AsyncCheckpointer(args.ckpt_dir, keep=2)
    step = jax.jit(
        steps_lib.make_tabular_train_step(dlrm.loss, ocfg), donate_argnums=(0, 1)
    )

    pipe_in = input_lib.TrainInputPipeline(
        service,
        lambda: iter(payloads),
        batch_rows=args.batch,
        n_steps=args.steps,
        overlap=not args.no_overlap,
        prefetch_depth=args.prefetch_depth,
    )

    losses: list[float] = []
    pending = None  # one-step-lagged loss sync: no blocking read on the
    # hot path — step i's scalar is resolved while step i+1 computes
    i = 0
    t0 = time.perf_counter()
    try:
        for batch in pipe_in:
            params, opt_state, metrics = step(params, opt_state, batch)
            if pending is not None:
                losses.append(float(pending["loss"]))
            pending = metrics
            i += 1
            if i % 100 == 0:
                losses.append(float(pending["loss"]))  # drain before save
                pending = None
                ckpt.save_async(i, {"params": params, "opt": opt_state})
                print(f"step {i}: loss={np.mean(losses[-50:]):.4f}")
        if pending is not None:
            losses.append(float(pending["loss"]))
        jax.block_until_ready(params)
    finally:
        service.stop()
    ckpt.wait()
    dt = time.perf_counter() - t0

    # ---- exit reports ------------------------------------------------ #
    print(
        f"trained {args.steps} steps in {dt:.1f}s "
        f"({args.steps*args.batch/dt:.0f} rows/s); "
        f"loss {np.mean(losses[:20]):.4f} → {np.mean(losses[-20:]):.4f}"
    )
    e2e = pipe_in.stall_report()
    print(
        f"e2e stall split: input_wait={e2e['fractions']['input_wait']:.1%} "
        f"train_step={e2e['fractions']['train_step']:.1%} "
        f"(attributed {e2e['attributed_s']:.2f}s of {e2e['wall_s']:.2f}s wall)"
    )
    svc_stall = service.stall_report()
    print(f"service stall buckets: {svc_stall['fractions']}")
    if cache is not None:
        st = cache.stats()
        print(
            f"chunk cache: {st['hits_total']} hits / {st['misses_total']} "
            f"misses ({st['items']} resident, {st['mem_bytes']/2**20:.1f} MiB)"
        )
    if args.trace:
        obs.tracer().export(args.trace)
        mpath = args.trace.replace(".json", "") + ".metrics.json"
        pipe_in.registry.export_jsonl(mpath)
        print(f"wrote {args.trace} + {mpath}")
    assert np.mean(losses[-20:]) < np.mean(losses[:20])
    print(f"checkpoints at {args.ckpt_dir}: steps {ckpt_lib.list_steps(args.ckpt_dir)}")


if __name__ == "__main__":
    main()
