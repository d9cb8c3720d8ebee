"""MLP layers: gated (SwiGLU/GeGLU), plain (GELU/ReLU²), and MoE.

The MoE layer is GShard/Switch-style with fixed expert capacity: top-k
routing → position-in-expert via cumulative one-hot → scatter to
[E, capacity, d] → batched expert matmuls → combine. All shapes static;
under expert-parallel sharding (experts over the ``model`` axis) XLA
lowers the dispatch/combine scatters to all-to-alls.

Shared experts (DeepSeek/Qwen-MoE style) are dense MLPs applied to every
token alongside the routed path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import common
from repro.models.common import ModelConfig, Params


# --------------------------------------------------------------------- #
# dense MLP
# --------------------------------------------------------------------- #
def init(key, cfg: ModelConfig, kind: str, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": common.dense_init(ks[0], d, ff), "down": common.dense_init(ks[1], ff, d)}
    if kind in ("swiglu", "geglu"):
        p["gate"] = common.dense_init(ks[2], d, ff)
    return p


def forward(x: jnp.ndarray, params: Params, kind: str) -> jnp.ndarray:
    up = common.dense(x, params["up"])
    if kind in ("swiglu", "geglu"):
        h = common.activation(common.dense(x, params["gate"]), kind) * up
    else:
        h = common.activation(up, kind)
    if h.ndim == 3:
        h = constrain(h, "ffn")
    return common.dense(h, params["down"])


# --------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------- #
def moe_init(key, cfg: ModelConfig, kind: str) -> Params:
    m = cfg.moe
    assert m is not None
    d, fe = cfg.d_model, m.d_expert_ff
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p: Params = {
        "router": common.dense_init(ks[0], d, m.n_experts, scale=scale),
        "w_gate": jax.random.normal(ks[1], (m.n_experts, d, fe)) * scale,
        "w_up": jax.random.normal(ks[2], (m.n_experts, d, fe)) * scale,
        "w_down": jax.random.normal(ks[3], (m.n_experts, fe, d)) * (fe ** -0.5),
    }
    if m.n_shared:
        fs = m.d_shared_ff or m.d_expert_ff
        p["shared"] = init(ks[4], cfg, kind, d_ff=fs * m.n_shared)
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = int(m.capacity_factor * tokens * m.top_k / m.n_experts)
    return max(8, ((cap + 7) // 8) * 8)  # 8-aligned, nonzero


# Expert-parallel alignment: the expert dim must divide the ``model``
# mesh axis (16-way) or GSPMD replicates the dispatch buffers (observed:
# 60-expert qwen2-moe inflating 250× in the dry-run). Weights are padded
# with zero experts AT USE — the parameter tree keeps the exact assigned
# expert count; padding experts are unreachable (router has no logit for
# them).
EXPERT_PAD_MULTIPLE = 16


def _pad_experts(w: jnp.ndarray, e_pad: int) -> jnp.ndarray:
    e = w.shape[0]
    if e == e_pad:
        return w
    return jnp.concatenate(
        [w, jnp.zeros((e_pad - e,) + w.shape[1:], w.dtype)], axis=0
    )


def moe_forward(x: jnp.ndarray, params: Params, cfg: ModelConfig, kind: str):
    """x [B,S,d] → (out [B,S,d], aux_loss scalar).

    Dispatches between two implementations:
      * **EP shard_map** (active mesh whose ``model`` axis divides E):
        tokens stay local to their data shard, experts local to their
        model shard; each model rank routes the (model-replicated) local
        tokens, runs only ITS experts, and the per-layer combine is ONE
        psum over ``model`` — the row-parallel pattern. This sidesteps
        GSPMD's handling of capacity scatter/gather, which replicated
        the E-sharded expert buffers (observed: 100× FLOPs/HBM inflation
        on the 1T-param kimi dry-run).
      * **dense jit path** (no mesh / indivisible E): plain scatter
        dispatch — used by single-device tests and smoke configs.

    Returns the load-balancing auxiliary loss (Switch §2.2) so the train
    step can add it; serve steps drop it.
    """
    from repro.distributed import sharding as shard_lib

    mesh = shard_lib.current_mesh()
    m = cfg.moe
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and m.n_experts % mesh.shape["model"] == 0
        and mesh.shape["model"] > 1
    ):
        return _moe_forward_ep(x, params, cfg, kind, mesh)
    return _moe_forward_dense(x, params, cfg, kind)


def _moe_forward_ep(x, params, cfg, kind, mesh):
    """Expert-parallel shard_map path (see moe_forward docstring)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import data_axes

    m = cfg.moe
    dp = data_axes(mesh)
    tp = mesh.shape["model"]
    e_loc = m.n_experts // tp
    fsdp = 1
    for a in dp:
        fsdp *= mesh.shape[a]
    d_sharded = x.shape[-1] % fsdp == 0  # whether FSDP split d evenly

    def inner(x_blk, router_w, wg, wu, wd):
        b, s, d = x_blk.shape
        tokens = b * s
        xt = x_blk.reshape(tokens, d)
        # FSDP all-gather of this layer's expert weights (bf16 payload)
        if d_sharded and fsdp > 1:
            router_w = jax.lax.all_gather(router_w, dp, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg.astype(x.dtype), dp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu.astype(x.dtype), dp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd.astype(x.dtype), dp, axis=2, tiled=True)
        else:
            wg, wu, wd = (w.astype(x.dtype) for w in (wg, wu, wd))

        # fp32 router — same rationale as the dense path: bf16 logits
        # make expert selection sensitive to 1-ulp input noise
        logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # sort-based position within expert (local tokens only)
        flat_e = expert_idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        start = jnp.searchsorted(sorted_e, jnp.arange(m.n_experts))
        ranks = jnp.arange(flat_e.shape[0]) - start[sorted_e]
        pos_flat = jnp.zeros_like(ranks).at[order].set(ranks)

        cap = _capacity(tokens, cfg)
        rank_id = jax.lax.axis_index("model")
        is_local = flat_e // e_loc == rank_id
        keep = (pos_flat < cap) & is_local
        e_local = jnp.where(keep, flat_e - rank_id * e_loc, e_loc)  # OOB drop

        # dispatch via K scatter passes — never materializes the
        # [T·K, d] token copy (7.5 GB/layer at 32k prefill)
        e_lp = e_local.reshape(tokens, m.top_k)
        p_lp = pos_flat.reshape(tokens, m.top_k)
        tok_range = jnp.arange(tokens, dtype=jnp.int32)
        buf = jnp.zeros((e_loc, cap, d), x.dtype)
        slot_token = jnp.zeros((e_loc, cap), jnp.int32)
        slot_gate = jnp.zeros((e_loc, cap), jnp.float32)
        for k in range(m.top_k):
            buf = buf.at[e_lp[:, k], p_lp[:, k]].set(xt, mode="drop")
            slot_token = slot_token.at[e_lp[:, k], p_lp[:, k]].set(
                tok_range, mode="drop"
            )
            slot_gate = slot_gate.at[e_lp[:, k], p_lp[:, k]].set(
                gate_vals[:, k], mode="drop"
            )

        h_g = jnp.einsum("ecd,edf->ecf", buf, wg)
        h_u = jnp.einsum("ecd,edf->ecf", buf, wu)
        hh = common.activation(h_g, kind) * h_u
        out_buf = jnp.einsum("ecf,efd->ecd", hh, wd)

        partial = jnp.zeros((tokens, d), jnp.float32).at[
            slot_token.reshape(-1)
        ].add(
            (out_buf * slot_gate[..., None].astype(out_buf.dtype)).reshape(-1, d)
        )
        out = jax.lax.psum(partial, "model").astype(x.dtype)

        density = jnp.zeros(m.n_experts, jnp.float32).at[flat_e].add(1.0) / tokens
        aux = m.n_experts * jnp.sum(density * jnp.mean(probs, axis=0)) / m.top_k
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return out.reshape(b, s, d), aux

    # layerwise specs: inside the scan body params carry no n_sb axis
    w_spec_g = P("model", dp if d_sharded else None, None)
    w_spec_d = P("model", None, dp if d_sharded else None)
    router_spec = P(dp if d_sharded else None, None)

    out, aux = shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),
            router_spec,
            w_spec_g,
            w_spec_g,
            w_spec_d,
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, params["router"]["w"], params["w_gate"], params["w_up"], params["w_down"])

    if "shared" in params:
        b, s, d = x.shape
        out = out + forward(x.reshape(b * s, d), params["shared"], kind).reshape(
            b, s, d
        )
    return out, aux


def _moe_forward_dense(x: jnp.ndarray, params: Params, cfg: ModelConfig, kind: str):
    """Dense-jit dispatch path (single-device tests, smoke configs)."""
    m = cfg.moe
    b, s, d = x.shape
    tokens = b * s
    xt = x.reshape(tokens, d)
    cap = _capacity(tokens, cfg)

    # fp32 router: bf16 logits quantize near-ties, so the top_k winner
    # would depend on 1-ulp input noise (and on how XLA fused the
    # surrounding graph — scan vs unrolled layer loops compiled the same
    # block differently and flipped experts). f32 in, f32 matmul.
    logits = common.dense(xt.astype(jnp.float32), params["router"])  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)             # [T,K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # position of each (token, k) within its expert — SORT-based rank
    # (stable sort keeps (token, k) order, so this is bit-identical to
    # the cumulative-one-hot formulation but O(T·K) instead of O(T·K·E):
    # the one-hot version materialized terabytes at 1M-token batches)
    flat_e = expert_idx.reshape(-1)                                   # [T·K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(m.n_experts))       # [E]
    ranks_sorted = jnp.arange(flat_e.shape[0]) - start[sorted_e]
    pos_flat = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)
    pos = pos_flat.reshape(tokens, m.top_k).astype(jnp.int32)         # [T,K]
    keep = pos < cap

    # scatter tokens into [E_pad, cap, d] (EP-aligned expert dim)
    e_pad = ((m.n_experts + EXPERT_PAD_MULTIPLE - 1) // EXPERT_PAD_MULTIPLE) * EXPERT_PAD_MULTIPLE
    e_idx = expert_idx.reshape(-1)
    p_idx = pos.reshape(-1)
    k_mask = keep.reshape(-1)
    src = jnp.repeat(xt[:, None], m.top_k, axis=1).reshape(-1, d)
    e_idx = jnp.where(k_mask, e_idx, e_pad)  # dropped → OOB (mode=drop)
    buf = jnp.zeros((e_pad, cap, d), x.dtype)
    buf = buf.at[e_idx, p_idx].set(src, mode="drop")
    buf = constrain(buf, "experts")  # EP: dispatch becomes an all-to-all

    # expert MLPs, batched over E_pad
    h_g = jnp.einsum(
        "ecd,edf->ecf", buf, _pad_experts(params["w_gate"], e_pad).astype(x.dtype)
    )
    h_u = jnp.einsum(
        "ecd,edf->ecf", buf, _pad_experts(params["w_up"], e_pad).astype(x.dtype)
    )
    h = common.activation(h_g, kind) * h_u
    out_buf = jnp.einsum(
        "ecf,efd->ecd", h, _pad_experts(params["w_down"], e_pad).astype(x.dtype)
    )
    out_buf = constrain(out_buf, "experts")

    # gather back + weighted combine
    gathered = out_buf[jnp.where(k_mask, expert_idx.reshape(-1), 0), p_idx]
    gathered = jnp.where(k_mask[:, None], gathered, 0)
    gathered = gathered.reshape(tokens, m.top_k, d)
    out = jnp.sum(gathered * gate_vals[..., None].astype(x.dtype), axis=1)

    # Switch load-balance aux loss: E · Σ_e f_e · P_e
    # (density via scatter-add, not a [T,E] one-hot materialization)
    density = (
        jnp.zeros(m.n_experts, jnp.float32).at[flat_e].add(1.0) / tokens
    )
    router_prob = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(density * router_prob) / m.top_k

    if "shared" in params:
        out = out + forward(xt, params["shared"], kind)
    return out.reshape(b, s, d), aux
