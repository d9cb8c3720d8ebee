"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant trainer on any assigned architecture. On this
CPU container the default is the reduced (smoke) config — the full
configs are exercised through the dry-run; on a real TPU fleet pass
``--full --mesh-shape ...`` (same code path, real devices).

The data source is PIPER: a synthetic Criteo-format stream is
preprocessed by the two-loop engine and its vocabulary-encoded ordinals
feed the LM as token batches (DESIGN.md §4).
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.core import pipeline as pipeline_lib
from repro.data import loader, synth
from repro.launch import compile_cache
from repro.launch import specs as specs_lib
from repro.train import optimizer as opt_lib
from repro.train import trainer as trainer_lib


def preprocess_tokens(schema_rows: int, vocab_size: int, seed: int = 0):
    """PIPER two-loop preprocessing → LM token stream."""
    scfg = synth.SynthConfig(rows=schema_rows, seed=seed)
    buf, _ = synth.make_dataset(scfg)
    pipe = pipeline_lib.PiperPipeline(
        pipeline_lib.PipelineConfig(schema=scfg.schema, max_rows_per_chunk=2048)
    )
    sparse = []
    for out in pipe.run_stream(lambda: synth.chunk_stream(buf, 1 << 17)):
        v = np.asarray(out.valid)
        sparse.append(np.asarray(out.sparse)[v])
    return np.concatenate(sparse)


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=configs.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--rows", type=int, default=2048, help="synthetic dataset rows")
    args = ap.parse_args()

    cfg = configs.get(args.arch) if args.full else configs.get_smoke(args.arch)
    model = specs_lib.build_model(cfg, remat=not args.full)

    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")
    print("preprocessing synthetic Criteo stream through PIPER...")
    sparse = preprocess_tokens(args.rows, cfg.vocab_size)
    base_fn = loader.PiperTokenBatches(sparse, cfg.vocab_size, args.batch, args.seq)

    def batch_fn(step: int) -> dict:
        batch = dict(base_fn(step))
        rng = np.random.default_rng((1234, step))
        if cfg.family == "audio":
            batch["frames"] = rng.standard_normal(
                (args.batch, cfg.encoder_frames, cfg.d_model)
            ).astype(np.float32) * 0.1
        if cfg.vision_tokens:
            batch["vision"] = rng.standard_normal(
                (args.batch, cfg.vision_tokens, cfg.d_model)
            ).astype(np.float32) * 0.1
        return batch

    tcfg = trainer_lib.TrainerConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        microbatches=args.microbatches,
    )
    opt_cfg = opt_lib.AdamWConfig(
        schedule=opt_lib.cosine_schedule(args.lr, args.steps // 10 + 1, args.steps)
    )
    trainer = trainer_lib.Trainer(model, opt_cfg, tcfg, batch_fn)
    out = trainer.run(jax.random.PRNGKey(0))
    losses = out["losses"]
    print(
        f"done: step={out['final_step']} loss {losses[0]:.3f} → {losses[-1]:.3f} "
        f"({np.mean(out['step_times']):.2f}s/step, {out['stragglers']} stragglers)"
    )


if __name__ == "__main__":
    main()
