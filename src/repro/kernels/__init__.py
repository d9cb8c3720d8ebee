# Pallas TPU kernels for the compute hot-spots PIPER optimizes in hardware,
# plus the model-side attention kernel. One subpackage per kernel, each with
#   kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
#   ops.py    — jit'd public wrapper (tier/strategy selection, fallbacks)
#   ref.py    — pure-jnp oracle

from __future__ import annotations

import functools


@functools.cache
def pallas_available() -> bool:
    """Whether the jax.experimental.pallas toolchain imports on this
    install. One of the gates for defaults that route through kernels
    (``PipelineConfig.use_fused_kernel=None`` → auto additionally
    requires a TPU backend, where Pallas compiles instead of
    interpreting): a jax build without Pallas falls back to the
    pure-jnp op chain instead of failing at trace time."""
    try:
        import jax.experimental.pallas  # noqa: F401
    except Exception:  # pragma: no cover — bare installs only
        return False
    return True


def on_tpu(backend: str | None = None) -> bool:
    """Whether ``backend`` (default: the process's default jax backend)
    is a TPU, where Mosaic compiles Pallas kernels."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend == "tpu"


def interpret(backend: str | None = None) -> bool:
    """The ``interpret`` flag every kernel wrapper passes to its Pallas
    call: compiled through Mosaic on a TPU, interpreted everywhere else
    (CPU CI). The one per-backend decision, so no kernel interprets on a
    TPU."""
    return not on_tpu(backend)


def resolve_fused(backend: str | None = None) -> bool:
    """The single source of truth for the fused-kernel auto knob.

    True iff the Pallas toolchain imports *and* ``backend`` compiles it
    through Mosaic — i.e. TPU. Everywhere else Pallas only interprets,
    which is slower than the XLA-fused unfused chain, so auto resolves
    off and callers opt in explicitly. Consumers:
    ``PipelineConfig.fused_enabled`` / ``fused_vocab_enabled`` and the
    plan compiler's ``fused=None`` hints.
    """
    return pallas_available() and on_tpu(backend)
