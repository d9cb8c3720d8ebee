"""DLRM — the recommender model the paper's pipeline feeds (Naumov et al.).

Consumes exactly what PIPER emits: log-transformed dense features +
vocabulary-encoded sparse ordinals. Bottom MLP embeds the dense features;
per-column embedding tables (through the kernels/embedding_bag tier
dispatch) embed the sparse ones; pairwise-dot feature interaction; top
MLP → CTR logit. This is the end-to-end example model: PIPER
preprocessing → DLRM training in one program.

Embedding tables shard over the ``model`` axis per *table* (column) — the
same columnar, state-local layout as the vocabulary stage, so the
preprocessing output feeds training without any resharding collective.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Params


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense: int = 13
    n_sparse: int = 26
    vocab_range: int = 5000
    embed_dim: int = 64
    bottom_mlp: tuple[int, ...] = (512, 256, 64)
    top_mlp: tuple[int, ...] = (512, 256, 1)

    def __post_init__(self):
        # The embedding is one [n_sparse, vocab_range, dim] stack: a
        # schema whose columns have ranges of their own has no such stack.
        if not isinstance(self.vocab_range, int):
            raise ValueError(
                "DLRM's embedding stack needs one vocab_range for every "
                f"sparse column, got per-column ranges {self.vocab_range}"
            )

    @property
    def n_pairs(self) -> int:
        f = self.n_sparse + 1  # +1 for the bottom-MLP dense vector
        return f * (f - 1) // 2


def init(key, cfg: DLRMConfig) -> Params:
    ks = jax.random.split(key, 4)
    tables = (
        jax.random.normal(ks[0], (cfg.n_sparse, cfg.vocab_range, cfg.embed_dim))
        * (cfg.embed_dim ** -0.5)
    ).astype(jnp.float32)

    def mlp_init(key, d_in, widths):
        layers = []
        for i, w in enumerate(widths):
            key, sub = jax.random.split(key)
            layers.append(common.dense_init(sub, d_in, w, bias=True))
            d_in = w
        return layers

    d_inter = cfg.n_pairs + cfg.bottom_mlp[-1]
    return {
        "tables": tables,
        "bottom": mlp_init(ks[1], cfg.n_dense, cfg.bottom_mlp),
        "top": mlp_init(ks[2], d_inter, cfg.top_mlp),
    }


def _mlp(x: jnp.ndarray, layers: list[Params]) -> jnp.ndarray:
    for i, p in enumerate(layers):
        x = common.dense(x, p)
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def forward(
    params: Params,
    dense: jnp.ndarray,    # f32 [B, n_dense] (PIPER-transformed)
    sparse: jnp.ndarray,   # int32 [B, n_sparse] (vocab ordinals)
    use_kernel: bool = False,
) -> jnp.ndarray:
    """→ CTR logits f32 [B]."""
    from repro.kernels.embedding_bag import ops as eb_ops

    bot = _mlp(dense, params["bottom"])                     # [B, E]
    emb = eb_ops.embedding_gather(params["tables"], sparse, use_kernel=use_kernel)
    feats = jnp.concatenate([bot[:, None], emb], axis=1)    # [B, F, E]
    gram = jnp.einsum("bfe,bge->bfg", feats, feats)         # [B, F, F]
    f = feats.shape[1]
    iu = jnp.triu_indices(f, k=1)
    pairs = gram[:, iu[0], iu[1]]                           # [B, F(F-1)/2]
    top_in = jnp.concatenate([bot, pairs], axis=1)
    return _mlp(top_in, params["top"])[:, 0]


def loss(params: Params, batch: dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Binary cross-entropy on the click label."""
    logits = forward(params, batch["dense"], batch["sparse"])
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
