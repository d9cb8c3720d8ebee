"""Serving launcher: batched continuous-batching demo on a smoke config.

``python -m repro.launch.serve --arch gemma-2b --requests 8``

``--piper-stream`` runs the *preprocessing* serving demo instead: the
online streaming service (``repro.stream``) over a synthetic Criteo
stream — offline loop ① freezes the vocabulary, then randomized-size
requests flow through the bucketed micro-batch scheduler and the
latency/throughput metrics are printed:

``python -m repro.launch.serve --piper-stream --rows 4096``
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models import lm as lm_lib
from repro.serve import engine as engine_lib


def run_piper_stream(args) -> None:
    """Streaming preprocessing service demo (Piper-as-a-service)."""
    from repro.core import pipeline as pipeline_lib
    from repro.data import synth
    from repro.stream import StreamingPreprocessService

    cfg = synth.SynthConfig(rows=args.rows, seed=0)
    buf, _ = synth.make_dataset(cfg)
    pc = pipeline_lib.PipelineConfig(schema=cfg.schema)
    pipe = pipeline_lib.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 1 << 14))

    rng = np.random.default_rng(0)
    buckets = (256, 1024, 4096)
    sizes, left = [], args.rows
    while left > 0:
        n = int(min(rng.integers(1, 512), left))
        sizes.append(n)
        left -= n
    svc = StreamingPreprocessService(
        pc, state, bucket_rows=buckets, queue_depth=32
    ).start()
    try:
        # warm every bucket so the printed latencies are steady-state
        svc.warmup(
            next(synth.request_payloads(buf, None, [min(c, args.rows)]))
            for c in buckets
        )
        handles = [svc.submit(p) for p in synth.request_payloads(buf, None, sizes)]
        svc.drain()
        snap = svc.metrics.snapshot()
    finally:
        svc.stop()
    print(
        f"streamed {snap['requests']} requests / {snap['rows']} rows in "
        f"{snap['wall_s']:.2f}s — {snap['rows_per_s']:.0f} rows/s, "
        f"p50={snap['p50_ms']}ms p95={snap['p95_ms']}ms p99={snap['p99_ms']}ms "
        f"({svc.compile_cache_size()} compiled shapes)"
    )


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=configs.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument(
        "--piper-stream",
        action="store_true",
        help="run the streaming preprocessing service demo instead of LM serving",
    )
    ap.add_argument("--rows", type=int, default=4096, help="--piper-stream dataset size")
    args = ap.parse_args()

    if args.piper_stream:
        run_piper_stream(args)
        return

    cfg = configs.get_smoke(args.arch)
    if cfg.family == "audio":
        raise SystemExit("use a decoder-only arch for the serve demo")
    model = lm_lib.LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = engine_lib.ServeEngine(
        model, params, batch_slots=args.slots, cache_len=args.cache_len
    )
    rng = np.random.default_rng(0)
    reqs = [
        engine_lib.Request(
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).tolist(),
            max_new_tokens=args.new_tokens,
        )
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s on CPU smoke config)")
    for i, r in enumerate(reqs[:4]):
        print(f"  req{i}: {r.generated}")


if __name__ == "__main__":
    main()
