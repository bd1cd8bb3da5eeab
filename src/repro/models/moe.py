"""Mixture-of-Experts with equal-capacity token-dropping dispatch.

TPU adaptation note (DESIGN.md §3): CUDA MoE implementations use ragged
grouped GEMMs (megablocks). Ragged matmuls do not map onto the MXU; the
TPU-native formulation is an equal-capacity batched einsum: tokens are
scattered into a dense (experts, capacity, d_model) buffer, all experts run
as one batched matmul, and results are gathered back. Tokens beyond an
expert's capacity are dropped (standard Switch/MaxText "dropping" strategy);
the capacity factor bounds the dropped fraction.

Expert weights are laid out (E, D, F) so the expert axis shards over the
"model" mesh axis (expert parallelism) while activations stay data-sharded;
GSPMD inserts the dispatch all-to-alls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import MoEConfig
from repro.models.layers import dense_init, init_mlp, apply_mlp


def init_moe(key, d_model: int, moe: MoEConfig, dtype) -> dict:
    ks = jax.random.split(key, 5)
    e, f = moe.num_experts, moe.expert_d_ff
    params = {
        "router": dense_init(ks[0], (d_model, e), jnp.float32),
        "experts": {
            "w_gate": dense_init(ks[1], (e, d_model, f), dtype),
            "w_up": dense_init(ks[2], (e, d_model, f), dtype),
            "w_down": dense_init(ks[3], (e, f, d_model), dtype),
        },
    }
    if moe.num_shared_experts:
        params["shared"] = init_mlp(
            ks[4], d_model, moe.shared_d_ff, "swiglu", dtype)
    return params


def _top_k(probs: jnp.ndarray, k: int):
    """top-k with renormalized weights. probs: (T, E) → (T, k) ids/weights."""
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return ids, weights


def _wsc(x, spec):
    if spec is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# shard_map dispatch (the production path on a mesh)
# ---------------------------------------------------------------------------
#
# GSPMD replicates the dispatch scatter's operands ("involuntary full
# rematerialization"): for deepseek-v2 train_4k the (G·E·C, D) buffer is
# 80 GiB/device replicated. The fix is to take the dispatch out of GSPMD's
# hands: shard_map splits tokens over the data axes, every device scatters
# its own tokens into a LOCAL (E, C_loc, D) buffer, and expert parallelism
# becomes one explicit all_to_all pair over the "model" axis (exactly the
# DeepSpeed/MaxText EP schedule, expressed in jax.lax collectives).

def _local_dispatch(xf, router, k, e, cf, compute_dtype):
    """Route + scatter local tokens. xf: (T, D) → (buf (E,C,D), meta)."""
    import math
    t, d = xf.shape
    logits = (xf @ router.astype(compute_dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    ids, weights = _top_k(probs, k)  # (T, k)
    flat_ids = ids.reshape(t * k)
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    first = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    pos_sorted = jnp.arange(t * k, dtype=jnp.int32) - first
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    capacity = int(min(t, max(1, math.ceil(t * k / e * cf))))
    keep = pos < capacity
    pos = jnp.minimum(pos, capacity - 1)
    slot = flat_ids * capacity + pos
    x_rep = jnp.repeat(xf, k, axis=0)
    upd = jnp.where(keep[:, None], x_rep, 0).astype(compute_dtype)
    buf = jnp.zeros((e * capacity, d), compute_dtype).at[slot].add(
        upd, mode="drop").reshape(e, capacity, d)
    meta = (slot, keep, weights, probs, ids)
    return buf, capacity, meta


def _local_combine(out_buf, meta, t, k, d):
    slot, keep, weights, _probs, _ids = meta
    e, c, _ = out_buf.shape
    y_rep = out_buf.reshape(e * c, d)[slot]
    y_rep = jnp.where(keep[:, None], y_rep, 0)
    y_rep = y_rep * weights.reshape(t * k)[:, None].astype(y_rep.dtype)
    return y_rep.reshape(t, k, d).sum(axis=1)


def apply_moe_shard_map(params, x, moe: MoEConfig, mesh_info,
                        capacity_factor: float | None = None):
    """Explicit-collective MoE. x: (B, S, D) → (y, aux_loss)."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    k = moe.num_experts_per_tok
    e = moe.num_experts
    cf = (capacity_factor if capacity_factor is not None
          else moe.capacity_factor)
    mi = mesh_info
    tp = mi.tp_size
    ep = e % tp == 0
    dp = mi.dp_spec
    dp_total = 1
    for a in mi.dp_axes:
        dp_total *= mi.mesh.shape[a]
    # Shard tokens as finely as possible: batch over dp AND (when the
    # sequence divides) seq over the model axis — otherwise every
    # model-axis peer dispatches identical tokens and the all_to_all
    # just duplicates work 16× (observed: 9.4 GiB work buffers).
    b_ax = dp if b % dp_total == 0 and b >= dp_total else None
    s_ax = mi.tp_axis if s % tp == 0 and s >= tp else None
    x_spec = P(b_ax, s_ax, None)
    w_spec = (P("model", None, None) if ep
              else P(None, None, "model"))
    wd_spec = (P("model", None, None) if ep
               else P(None, "model", None))
    compute_dtype = x.dtype

    def local_fn(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        t = bl * sl
        xf = xl.reshape(t, d)
        buf, cap, meta = _local_dispatch(xf, router, k, e, cf, compute_dtype)
        if ep:
            e_loc = e // tp
            b4 = buf.reshape(tp, e_loc, cap, d)
            recv = jax.lax.all_to_all(b4, mi.tp_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv: (tp, e_loc, cap, d) — dim0 = source peer
            work = recv.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, d)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", work, wg))
            h = h * jnp.einsum("ecd,edf->ecf", work, wu)
            out = jnp.einsum("ecf,efd->ecd", h, wd)  # (e_loc, tp*cap, d)
            back = out.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
            out_buf = jax.lax.all_to_all(back, mi.tp_axis, split_axis=0,
                                         concat_axis=0, tiled=False)
            out_buf = out_buf.reshape(e, cap, d)
        else:
            # tensor parallel inside experts: F sharded, psum the output
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
            h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
            out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
            out_buf = jax.lax.psum(out_buf, mi.tp_axis)
        y = _local_combine(out_buf, meta, t, k, d)
        # load-balance aux (local → mean over data shards)
        _slot, _keep, _w, probs, ids = meta
        counts = jnp.zeros((e,), jnp.float32).at[ids[:, 0]].add(1.0)
        frac_tokens = counts / t
        frac_probs = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac_tokens * frac_probs) * moe.router_aux_loss_coef
        mean_axes = tuple(a for a, used in
                          ((mi.dp_axes, b_ax is not None),
                           ((mi.tp_axis,), s_ax is not None)) if used
                          for a in a)
        if mean_axes:
            aux = jax.lax.pmean(aux, mean_axes)
        return y.reshape(bl, sl, d), aux

    fn = jax.shard_map(
        local_fn, mesh=mi.mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    w = params["experts"]
    y, aux = fn(x, params["router"], w["w_gate"], w["w_up"], w["w_down"])
    if "shared" in params:
        y = y + apply_mlp(params["shared"], x, "swiglu")
    return y, aux


def apply_moe(params: dict, x: jnp.ndarray, moe: MoEConfig,
              capacity_factor: float | None = None,
              groups: int | None = None,
              buf_spec: tuple | None = None,
              hidden_spec: tuple | None = None):
    """x: (B, S, D) → (y, aux_loss).

    On a registered mesh (sharding/runtime.py) this routes to the
    shard_map + explicit-all_to_all path; otherwise the pure-GSPMD grouped
    dispatch below (single-device tests, and the recorded §Perf baseline).
    """
    from repro.sharding.runtime import get_mesh_info
    mi = get_mesh_info()
    if mi is not None:
        return apply_moe_shard_map(params, x, moe, mi,
                                   capacity_factor=capacity_factor)
    return _apply_moe_gspmd(params, x, moe, capacity_factor, groups,
                            buf_spec, hidden_spec)


def _apply_moe_gspmd(params: dict, x: jnp.ndarray, moe: MoEConfig,
                     capacity_factor: float | None = None,
                     groups: int | None = None,
                     buf_spec: tuple | None = None,
                     hidden_spec: tuple | None = None):
    """GSPMD grouped-dispatch path (see apply_moe).

    Grouped dispatch: tokens are split into ``groups`` independent dispatch
    groups (default: one per sequence; 1 for decode). Each group routes
    top-k, computes every token's position within its expert via a
    cumulative one-hot count, scatters into a (G, E, C, D) buffer, and the
    experts run as one batched einsum. The group dim G shards over the
    data axes and C is per-group — this is what keeps the dispatch buffer
    O(tokens/device) instead of O(global tokens) per device (the naive
    ungrouped buffer was 40 GiB/device for mixtral train_4k; see
    EXPERIMENTS.md §Perf).

    ``capacity_factor`` overrides the config value at call time; pass
    ``num_experts / num_experts_per_tok`` for guaranteed-dropless dispatch
    (capacity = T_group) — the serving engine does this for decode steps,
    where dropping a token corrupts its output.
    """
    import math

    b, s, d = x.shape
    k = moe.num_experts_per_tok
    e = moe.num_experts
    g = groups if groups is not None else (b if s > 1 else 1)
    tg = (b * s) // g  # tokens per dispatch group
    assert b * s == g * tg, (b, s, g)
    tok_spec = (buf_spec[0], None, None) if buf_spec else None
    xg = _wsc(x.reshape(g, tg, d), tok_spec)

    # Router matmul in compute dtype (cotangent stays bf16 — an f32 router
    # matmul promotes the *entire* token-stream cotangent chain to f32 via
    # cotangent accumulation, doubling activation-grad memory); softmax and
    # everything after in f32.
    router_logits = (xg @ params["router"].astype(x.dtype)).astype(
        jnp.float32)  # (G, TG, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    ids, weights = _top_k(probs, k)  # (G, TG, k)

    # ---- load-balancing auxiliary loss (Switch-style, global) ----
    counts = jnp.zeros((e,), jnp.float32).at[ids[..., 0].reshape(-1)].add(1.0)
    frac_tokens = counts / (g * tg)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux_loss = e * jnp.sum(frac_tokens * frac_probs) * moe.router_aux_loss_coef

    # ---- position of each (token, slot) within its expert, per group ----
    # Sort-based ranking: O(T log T) and no (T, E) one-hot — the cumsum
    # formulation materialized a (G, TG·k, E) tensor, 4 TB for deepseek-v2
    # train_4k (§Perf iteration).
    flat_ids = ids.reshape(g, tg * k)
    order = jnp.argsort(flat_ids, axis=1)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=1)
    first = jax.vmap(
        lambda row: jnp.searchsorted(row, row, side="left"))(sorted_ids)
    pos_sorted = jnp.arange(tg * k, dtype=jnp.int32)[None] - first
    inv = jnp.argsort(order, axis=1)
    pos = jnp.take_along_axis(pos_sorted, inv, axis=1)  # (G, TG*k)

    cf = capacity_factor if capacity_factor is not None else moe.capacity_factor
    capacity = int(min(tg, max(1, math.ceil(tg * k / e * cf))))
    keep = pos < capacity
    pos = jnp.minimum(pos, capacity - 1)

    # ---- scatter tokens into the (G·E·C, D) buffer ----
    # Single-index-dim scatter/gather along dim 0: the canonical form the
    # SPMD partitioner can keep sharded (multi-dim-index scatter made GSPMD
    # replicate the operands — 120 GiB/device for deepseek-v2; §Perf).
    compute_dtype = x.dtype
    x_rep = _wsc(jnp.repeat(xg, k, axis=1), tok_spec)  # (G, TG*k, D)
    upd = jnp.where(keep[..., None], x_rep, 0).astype(compute_dtype)
    g_idx = jnp.broadcast_to(jnp.arange(g)[:, None], flat_ids.shape)
    slot = (g_idx * e + flat_ids) * capacity + pos  # (G, TG*k) flat index
    buf_flat = jnp.zeros((g * e * capacity, d), compute_dtype)
    buf_flat = buf_flat.at[slot.reshape(-1)].add(
        upd.reshape(-1, d), mode="drop")
    buf = _wsc(buf_flat.reshape(g, e, capacity, d), buf_spec)

    # ---- batched expert FFN (swiglu) ----
    w = params["experts"]
    hg = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, w["w_gate"]))
    hg = hg * jnp.einsum("gecd,edf->gecf", buf, w["w_up"])
    hg = _wsc(hg, hidden_spec)
    out_buf = _wsc(jnp.einsum("gecf,efd->gecd", hg, w["w_down"]),
                   buf_spec)  # (G, E, C, D)

    # ---- gather back and combine ----
    y_rep = out_buf.reshape(g * e * capacity, d)[slot.reshape(-1)]
    y_rep = _wsc(y_rep.reshape(g, tg * k, d), tok_spec)
    y_rep = jnp.where(keep[..., None], y_rep, 0)
    y_rep = y_rep * weights.reshape(g, tg * k)[..., None].astype(y_rep.dtype)
    y = _wsc(y_rep.reshape(g, tg, k, d).sum(axis=2), tok_spec)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], xg, "swiglu")

    return y.reshape(b, s, d), aux_loss
