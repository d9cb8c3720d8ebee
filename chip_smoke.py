"""Smoke run of the two-loop preprocessing engine on a TPU.

Drives the main path once through the entry points a user calls, at full
Criteo width (1 label + 13 dense + 26 hex sparse columns), with data
made from a fixed seed:

  offline-5k  ``PiperPipeline``: loop ① then loop ② over >= 256 chunks of
              1 MiB UTF-8 rows (the default engine sizes: 1 MiB chunks,
              16384-row capacity), ``schema.CRITEO``;
  offline-1m  the same data through ``schema.CRITEO_1M`` (a full
              ``[26, 1_000_000]`` first-occurrence state);
  service     ``StreamingPreprocessService`` on the offline-5k loop-①
              state with its default bucket ladder: warm-up, then a few
              hundred requests of mixed sizes;
  train       DLRM train steps fed by that service through
              ``TrainInputPipeline``.

Each phase checks what comes out against a plain numpy reference that
shares no code with the engine (the generator's ground-truth table, a
vectorized first-occurrence vocabulary, and a numpy DLRM forward), plus
the row-wise ``core/baseline.py`` pipeline on a prefix. Each phase prints
one JSON line: rows, wall seconds, compile seconds apart from the rest,
and the routes its loops took. Timings are smoke timings, not a
benchmark.

``--chips 4`` runs only the data-parallel engine, ``ShardedPiperPipeline``
(per-shard loop ① plus the ``vocab.merge_tree`` merge) at the 1M point on
four chips, and compares its state bit for bit with the single-device
engine on the same data.

The script refuses to run unless JAX's first device is a TPU. Its last
line is ``{"ok": true, "device": {...}}``; any failed phase raises and
the line is not printed. The compile cache goes where
``repro.launch.compile_cache`` puts it.

    python chip_smoke.py [--chips 4] [--rows N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

CHUNK_BYTES = 1 << 20
DEFAULT_ROWS = 980_000  # >= 256 chunks of 1 MiB at ~276 B/row
MIN_CHUNKS = 256
SEED = 0
N_REQUESTS = 300
TRAIN_STEPS = 20
TRAIN_BATCH = 2048
PREFIX_ROWS = 1000
DENSE_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent lowering and compiling (persistent-cache reads
    included), and persistent-cache hits, from ``jax.monitoring``."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.cache_hits


class Phase:
    """Wall time and compile time of one phase; prints its JSON line."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name, self.fields = clock, name, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.read()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        c1, h1 = self.clock.read()
        line = {
            "phase": self.name,
            "wall_s": wall,
            "compile_s": c1 - self.c0,
            "run_s": wall - (c1 - self.c0),
            "cache_hits": h1 - self.h0,
        }
        line.update(self.fields)
        print(json.dumps(line), flush=True)
        return False


# ---------------------------------------------------------------------- #
# plain numpy references (no engine code)
# ---------------------------------------------------------------------- #
def first_occurrence_ids(sparse: np.ndarray, vocab_range: int) -> np.ndarray:
    """Per column: each row's value ``uint32 % vocab_range`` replaced by
    its ordinal in order of first appearance."""
    u = sparse.view(np.uint32).astype(np.int64) % vocab_range
    out = np.empty(u.shape, np.int32)
    for c in range(u.shape[1]):
        uniq, first, inv = np.unique(u[:, c], return_index=True, return_inverse=True)
        rank = np.empty(len(uniq), np.int64)
        rank[np.argsort(first)] = np.arange(len(uniq))
        out[:, c] = rank[inv.reshape(-1)]
    return out


def dense_reference(dense: np.ndarray) -> np.ndarray:
    return np.log1p(np.maximum(dense.astype(np.float64), 0.0))


def check_rows(what: str, got: dict, table: dict, ids: np.ndarray, lo: int) -> None:
    n = got["label"].shape[0]
    hi = lo + n
    check(np.array_equal(got["label"], table["label"][lo:hi]), f"{what}: labels")
    check(np.array_equal(got["sparse"], ids[lo:hi]), f"{what}: sparse ordinals")
    # f32 log1p on a TPU v5e against float64: up to 5.7e-5 relative over
    # the integers 0..200000 (523 ulp at x = 2), measured on the chip
    want = dense_reference(table["dense"][lo:hi])
    err = np.abs(got["dense"] - want)
    worst = np.unravel_index(np.argmax(err / (1e-6 + DENSE_RTOL * np.abs(want))), err.shape)
    check(
        np.allclose(got["dense"], want, rtol=DENSE_RTOL, atol=1e-6),
        f"{what}: dense features, worst at row {lo + worst[0]} col {worst[1]}: "
        f"{got['dense'][worst]!r} vs {want[worst]!r} (raw {table['dense'][lo:hi][worst]})",
    )


def dlrm_loss_reference(params: dict, batch: dict) -> float:
    """DLRM forward + mean binary cross-entropy in float64 numpy."""
    f64 = lambda x: np.asarray(x, np.float64)

    def mlp(x, layers):
        for i, p in enumerate(layers):
            x = x @ f64(p["w"]) + f64(p["b"])
            if i + 1 < len(layers):
                x = np.maximum(x, 0.0)
        return x

    bot = mlp(f64(batch["dense"]), params["bottom"])
    tables = f64(params["tables"])
    ids = np.asarray(batch["sparse"])
    emb = tables[np.arange(ids.shape[1])[None, :], ids]
    feats = np.concatenate([bot[:, None], emb], axis=1)
    gram = np.einsum("bfe,bge->bfg", feats, feats)
    iu = np.triu_indices(feats.shape[1], k=1)
    logits = mlp(np.concatenate([bot, gram[:, iu[0], iu[1]]], axis=1), params["top"])[:, 0]
    y = f64(batch["label"])
    return float(np.mean(np.maximum(logits, 0) - logits * y + np.log1p(np.exp(-np.abs(logits)))))


# ---------------------------------------------------------------------- #
# phases
# ---------------------------------------------------------------------- #
def make_data(rows: int):
    from repro.core import schema as schema_lib
    from repro.data import synth

    t0 = time.perf_counter()
    cfg = synth.SynthConfig(schema=schema_lib.CRITEO, rows=rows, seed=SEED)
    buf, table = synth.make_dataset(cfg)
    chunks = list(synth.chunk_stream(buf, CHUNK_BYTES))
    print(json.dumps({
        "phase": "data", "rows": rows, "bytes": int(buf.size),
        "chunks": len(chunks), "chunk_bytes": CHUNK_BYTES, "seed": SEED,
        "wall_s": time.perf_counter() - t0,
    }), flush=True)
    return buf, table, chunks


def offline(clock, name, schema, buf, table, chunks, ids):
    """Loop ① then loop ② through ``PiperPipeline``; returns the loop-①
    state (un-finalized, as the service takes it)."""
    import jax

    from repro.core import baseline
    from repro.core import pipeline as P
    from repro.core import vocab as vocab_lib

    pipe = P.PiperPipeline(P.PipelineConfig(schema=schema))
    cfg = pipe.config
    with Phase(clock, f"offline-{name}") as ph:
        with Phase(clock, f"offline-{name}/loop1") as p1:
            state = pipe.build_state_stream(chunks)
            vocabulary = vocab_lib.finalize(state)
            jax.block_until_ready(vocabulary)
            p1.fields.update(route=pipe.compiled.vocab_route, tier=pipe.compiled.vocab_tier,
                             slabs=pipe.compiled.vocab_slabs, chunks=len(chunks))
        with Phase(clock, f"offline-{name}/loop2") as p2:
            parts = {"label": [], "dense": [], "sparse": []}
            for out in pipe.transform_stream(vocabulary, chunks):
                valid = np.asarray(out.valid)
                for k in parts:
                    parts[k].append(np.asarray(getattr(out, k))[valid])
            got = {k: np.concatenate(v) for k, v in parts.items()}
            p2.fields.update(route=pipe.compiled.xform_route, tier=pipe.compiled.tier,
                             chunks=len(chunks))
        rows = got["label"].shape[0]
        check(rows == table["label"].shape[0], f"offline-{name}: {rows} rows out")
        check(int(state.rows_seen) == rows, f"offline-{name}: rows_seen")
        check(np.array_equal(np.asarray(vocabulary.sizes), ids.max(axis=0) + 1),
              f"offline-{name}: vocabulary sizes")
        check_rows(f"offline-{name}", got, table, ids, 0)
        # the row-wise CPU pipeline on a prefix: the prefix's first
        # occurrences precede every later one, so its ordinals match
        end = int(np.flatnonzero(buf == ord("\n"))[PREFIX_ROWS - 1]) + 1
        oracle = baseline.run_pipeline(buf[:end], schema)
        check(np.array_equal(oracle["sparse"], got["sparse"][:PREFIX_ROWS]),
              f"offline-{name}: baseline prefix ordinals")
        ph.fields.update(rows=rows, chunk_bytes=cfg.chunk_bytes,
                         max_rows_per_chunk=cfg.max_rows_per_chunk,
                         vocab_route=pipe.compiled.vocab_route,
                         xform_route=pipe.compiled.xform_route,
                         checked="ground truth + first-occurrence vocab, baseline prefix")
    return state


def service_phase(clock, state, buf, table, ids):
    """The online service on the offline loop-① state; returns it
    started, for the training phase."""
    from repro.core import pipeline as P
    from repro.core import schema as schema_lib
    from repro.data import synth
    from repro.stream import StreamingPreprocessService

    rows = table["label"].shape[0]
    spans = synth.row_spans(buf)
    rng = np.random.default_rng(SEED)
    top = min(8192, rows - 1)
    sizes = np.exp(rng.uniform(0.0, np.log(top), N_REQUESTS)).astype(int).clip(1, top)
    starts = rng.integers(0, rows - sizes)
    payload = lambda lo, n: buf[spans[lo, 0] : spans[lo + n - 1, 1]]

    svc = StreamingPreprocessService(P.PipelineConfig(schema=schema_lib.CRITEO), state)
    svc.start()
    try:
        buckets = [b.rows for b in svc.scheduler.buckets]
        recompiles = svc.registry.counter("stream.recompiles_total")
        with Phase(clock, "service/warmup") as pw:
            svc.warmup(payload(0, min(b, rows)) for b in buckets)
            pw.fields.update(buckets=buckets, compiled_shapes=svc.compile_cache_size())
        with Phase(clock, "service") as ph:
            handles = [svc.submit(payload(int(lo), int(n))) for lo, n in zip(starts, sizes)]
            svc.drain(timeout=600)
            for h, lo in zip(handles, starts):
                check_rows("service", h.result(), table, ids, int(lo))
            check(recompiles.value == len(buckets), "service: recompiled after warmup")
            snap = svc.metrics.snapshot()
            ph.fields.update(requests=len(handles), rows=int(sizes.sum()),
                             xform_route=svc.scheduler.compiled.xform_route,
                             p50_ms=snap["p50_ms"], p99_ms=snap["p99_ms"],
                             checked="every request against ground truth + first-occurrence vocab")
    except BaseException:
        svc.stop()
        raise
    return svc


def train_phase(clock, svc, buf, table, ids):
    import jax

    from repro.configs import piper_dlrm
    from repro.data import synth
    from repro.models import dlrm
    from repro.train import input_pipeline as input_lib
    from repro.train import optimizer as opt_lib
    from repro.train import steps as steps_lib

    mcfg = piper_dlrm.CONFIG_5K.model
    params = dlrm.init(jax.random.PRNGKey(SEED), mcfg)
    params0 = jax.tree.map(np.asarray, params)
    opt_state = opt_lib.adamw_init(params)
    ocfg = opt_lib.AdamWConfig(schedule=opt_lib.cosine_schedule(2e-3, 5, TRAIN_STEPS),
                               weight_decay=0.0)
    step = jax.jit(steps_lib.make_tabular_train_step(dlrm.loss, ocfg), donate_argnums=(0, 1))
    payloads = list(synth.request_payloads(buf, table, [TRAIN_BATCH] * TRAIN_STEPS))
    feed = input_lib.TrainInputPipeline(svc, lambda: iter(payloads),
                                        batch_rows=TRAIN_BATCH, n_steps=TRAIN_STEPS)
    with Phase(clock, "train") as ph:
        losses = []
        for i, batch in enumerate(feed):
            host = jax.tree.map(np.asarray, batch)
            check_rows(f"train batch {i}", host, table, ids, i * TRAIN_BATCH)
            if i == 0:
                first_ref = dlrm_loss_reference(params0, host)
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
        jax.block_until_ready(params)
        check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)), "train: losses finite")
        check(all(np.isfinite(x).all() for x in jax.tree.leaves(jax.tree.map(np.asarray, params))),
              "train: parameters finite")
        # f32 matmuls on the chip against float64 numpy
        check(abs(losses[0] - first_ref) <= 2e-2 * abs(first_ref),
              f"train: first loss {losses[0]} vs reference {first_ref}")
        ph.fields.update(steps=TRAIN_STEPS, batch_rows=TRAIN_BATCH,
                         rows=TRAIN_STEPS * TRAIN_BATCH, model="DLRM " + str(mcfg),
                         first_loss=losses[0], first_loss_ref=first_ref, last_loss=losses[-1],
                         checked="batches against ground truth, first loss against numpy DLRM")


def sharded_phase(clock, buf, table, chunks, n_chips):
    """Data-parallel loop ① at 1M on ``n_chips`` chips against the
    single-device engine on the same chunks."""
    import jax

    from repro.core import pipeline as P
    from repro.core import schema as schema_lib
    from repro.core import sharded_pipeline
    from repro.core import vocab as vocab_lib
    from repro.data import loader
    from repro.distributed.sharding import put_shard_feed
    from repro.launch.mesh import make_data_mesh

    cfg = P.PipelineConfig(schema=schema_lib.CRITEO_1M)
    mesh = make_data_mesh(n_chips)
    feed = loader.TabularChunkFeed(buf, CHUNK_BYTES, n_row_shards=n_chips)
    stacks, offsets = put_shard_feed(*feed.shard_stacks(), mesh)
    placed = sorted(
        (s.device.id, s.data.shape[0]) for s in stacks.addressable_shards
    )
    check(len({d for d, _ in placed}) == n_chips and all(n == 1 for _, n in placed),
          f"feed not spread one stack per chip: {placed}")
    eng = sharded_pipeline.ShardedPiperPipeline(cfg, mesh)
    with Phase(clock, f"sharded-1m/{n_chips}-chips") as ph:
        merged = eng.build_state_scan(stacks, offsets)
        jax.block_until_ready(merged)
        ph.fields.update(route=eng.compiled.vocab_route, tier=eng.compiled.vocab_tier,
                         chunks=len(chunks), steps_per_shard=feed.n_steps,
                         devices=[d for d, _ in placed])
    with Phase(clock, "single-1m/1-chip") as ps:
        pipe = P.PiperPipeline(cfg)
        single = pipe.build_state_stream(chunks)
        jax.block_until_ready(single)
        ps.fields.update(route=pipe.compiled.vocab_route)
    fp_sharded, fp_single = np.asarray(merged.first_pos), np.asarray(single.first_pos)
    identical = np.array_equal(fp_sharded, fp_single) and int(merged.rows_seen) == int(single.rows_seen)
    ids = first_occurrence_ids(table["sparse"], cfg.schema.vocab_range)
    sizes = np.asarray(vocab_lib.finalize(merged).sizes)
    print(json.dumps({
        "phase": "sharded-vs-single", "bit_identical": bool(identical),
        "rows_seen": int(merged.rows_seen), "state_shape": list(fp_sharded.shape),
        "sizes_match_reference": bool(np.array_equal(sizes, ids.max(axis=0) + 1)),
    }), flush=True)
    check(identical, "sharded state differs from the single-device state")
    check(np.array_equal(sizes, ids.max(axis=0) + 1), "sharded vocabulary sizes")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help="dataset rows (below the default only to fit a time limit)")
    args = ap.parse_args()

    import jax

    from repro.core import schema as schema_lib
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "start", "device": device, "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)
    if args.rows < DEFAULT_ROWS:
        print(json.dumps({"phase": "cut", "rows": args.rows, "default_rows": DEFAULT_ROWS}),
              flush=True)
    clock = CompileClock()
    buf, table, chunks = make_data(args.rows)
    if args.rows >= DEFAULT_ROWS:
        check(len(chunks) >= MIN_CHUNKS, f"only {len(chunks)} chunks")

    if args.chips > 1:
        sharded_phase(clock, buf, table, chunks, args.chips)
    else:
        ids5k = first_occurrence_ids(table["sparse"], schema_lib.CRITEO.vocab_range)
        ids1m = first_occurrence_ids(table["sparse"], schema_lib.CRITEO_1M.vocab_range)
        state = offline(clock, "5k", schema_lib.CRITEO, buf, table, chunks, ids5k)
        offline(clock, "1m", schema_lib.CRITEO_1M, buf, table, chunks, ids1m)
        svc = service_phase(clock, state, buf, table, ids5k)
        try:
            train_phase(clock, svc, buf, table, ids5k)
        finally:
            svc.stop()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
