"""Batched LM serving with continuous batching (smoke-scale).

    PYTHONPATH=src python examples/serve_lm.py --arch hymba-1.5b
"""

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models import lm as lm_lib
from repro.serve import engine as engine_lib


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b", choices=configs.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch)
    if cfg.family == "audio":
        raise SystemExit("pick a decoder-only arch")
    model = lm_lib.LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = engine_lib.ServeEngine(model, params, batch_slots=4, cache_len=48)

    rng = np.random.default_rng(1)
    reqs = [
        engine_lib.Request(
            prompt=rng.integers(0, cfg.vocab_size, 6).tolist(), max_new_tokens=12
        )
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in reqs)
    print(f"{args.arch}: {len(reqs)} requests / {toks} tokens in {dt:.2f}s")
    print("first generations:", [r.generated[:6] for r in reqs[:3]])


if __name__ == "__main__":
    main()
