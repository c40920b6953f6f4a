"""Feed-forward layers: gated dense MLP and GShard-style capacity MoE.

MoE (qwen3-moe / qwen2-moe): top-k routing with per-sequence groups and a
fixed expert capacity C = ceil(k*S/E * capacity_factor).  Dispatch is
sort-free scatter/gather (no (T,E,C) one-hot tensor is ever materialized):

  router -> top-k expert ids -> position-in-expert via masked cumulative
  count -> scatter tokens into the (B, E, C, D) buffer -> batched expert
  einsum (E-sharded => all-to-all at the scatter, expert parallelism) ->
  gather back, gate-weighted combine.

Shared experts (qwen2-moe) run as a dense gated MLP on every token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import activation, dense_init, logical_constraint


# ---------------------------------------------------------------------------
# dense gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f)),
        "w_up": dense_init(ks[1], (d, f)),
        "w_down": dense_init(ks[2], (f, d)) / (2.0 * cfg.num_layers) ** 0.5,
    }


def mlp(x, p, cfg):
    dtype = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dtype))
    h = logical_constraint(h, "batch", "seq", "mlp")
    u = logical_constraint(u, "batch", "seq", "mlp")
    h = activation(h, cfg.act) * u
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dtype))
    return logical_constraint(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# mixture of experts
# ---------------------------------------------------------------------------

def init_moe(key, cfg) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e)),
        "w_gate": dense_init(ks[1], (e, d, f)),
        "w_up": dense_init(ks[2], (e, d, f)),
        "w_down": dense_init(ks[3], (e, f, d)) / (2.0 * cfg.num_layers) ** 0.5,
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_mlp(
            ks[4], cfg, d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.num_shared_experts)
    return p


def _shardmap_combine(y_pad, slot, gates, b, s, k, d, e, c, dtype):
    """Expert->token combine with an explicit shard_map.

    GSPMD resolves the cross-sharding gather (expert-sharded y_pad ->
    token-space output) as a masked f32 (B, A, D) all-reduce *before* the
    k-sum (measured: 3×8.6 GB/layer/device on qwen3-moe).  Doing the masked
    local gather + gate-weight + k-sum inside shard_map and psum-ing the
    (B, S, D) partial moves 8×k fewer bytes."""
    from jax.sharding import PartitionSpec as P
    from .common import current_mesh

    mesh = current_mesh()
    n_model = mesh.shape["model"] if (mesh and "model" in mesh.axis_names) \
        else 1
    c1 = c + 1
    if mesh is None or n_model == 1 or e % n_model or (b % _batch_size(mesh)):
        y_assign = jnp.take_along_axis(y_pad, slot[:, :, None], axis=1)
        return (y_assign * gates[..., None]).reshape(b, s, k, d).sum(axis=2)

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) != 1 else batch_axes[0]

    def local(y_pad_l, slot_l, gates_l):
        shard = jax.lax.axis_index("model")
        base = shard * (e // n_model) * c1
        sl = slot_l - base
        valid = (sl >= 0) & (sl < y_pad_l.shape[1])
        sl = jnp.clip(sl, 0, y_pad_l.shape[1] - 1)
        ya = jnp.take_along_axis(y_pad_l, sl[:, :, None], axis=1)
        ya = ya * valid[:, :, None].astype(ya.dtype) * gates_l[..., None]
        y_tok = ya.reshape(ya.shape[0], s, k, d).sum(axis=2)
        return jax.lax.psum(y_tok, "model")

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(bspec, None), P(bspec, None)),
        out_specs=P(bspec, None, None), check_vma=False)
    return fn(y_pad, slot, gates)


def _batch_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _capacity(cfg, seq: int) -> int:
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    c = int(k * seq / e * cfg.capacity_factor)
    return max(-(-c // 4) * 4, 4)                 # round up to a lane multiple


def moe(x, p, cfg):
    """x (B, S, D) -> (B, S, D), plus the router aux loss.

    Groups = sequences (GShard): capacity is per sequence, dispatch tensors
    are (B, E, C, D) sharded batch->data, expert->model.
    """
    dtype = x.dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    c = _capacity(cfg, s)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)        # (B, S, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)            # renormalize top-k

    # --- position-in-expert via stable sort (no (A,E) one-hot materialized)
    flat_e = expert_ids.reshape(b, s * k)                  # (B, A)
    a = s * k
    flat_e = logical_constraint(flat_e, "batch", None)
    order = jnp.argsort(flat_e, axis=1)                    # stable
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    idx = jnp.broadcast_to(jnp.arange(a)[None, :], (b, a))
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    pos_sorted = idx - run_start                           # rank within expert
    inv = jnp.argsort(order, axis=1)
    pos = jnp.take_along_axis(pos_sorted, inv, axis=1)     # (B, A)
    in_cap = pos < c

    # --- dispatch: scatter token *indices*, then one batched gather --------
    # (token-index plumbing is (B, A) int32 — bytes are negligible; the
    # big (·, D) tensors below are sharded batch×expert / batch×seq so the
    # cross-device exchange is the canonical EP all-to-all volume)
    tok = jnp.broadcast_to((jnp.arange(a) // k)[None, :], (b, a))
    bidx = jnp.arange(b)[:, None]
    pos_c = jnp.where(in_cap, pos, c)                      # OOB -> dropped
    slot = flat_e * (c + 1) + pos_c                        # (B, A) flat slots
    buf_idx = jnp.full((b, e * (c + 1)), s, jnp.int32)     # sentinel = pad row
    buf_idx = buf_idx.at[bidx, slot].set(tok)
    buf_idx = logical_constraint(
        buf_idx.reshape(b, e, c + 1)[:, :, :c], "batch", "expert", None)
    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), dtype)], axis=1)
    buf = jnp.take_along_axis(
        x_pad[:, :, None, :].transpose(0, 2, 1, 3),        # (B, 1, S+1, D)
        buf_idx.reshape(b, 1, e * c, 1), axis=2
    ).reshape(b, e, c, d)
    buf = logical_constraint(buf, "batch", "expert", None, None)

    # --- expert computation (E batched einsum; E sharded => EP) ------------
    h = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dtype))
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dtype))
    h = logical_constraint(h, "batch", "expert", None, "expert_mlp")
    h = activation(h, cfg.act) * u
    y = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(dtype))
    y = logical_constraint(y, "batch", "expert", None, None)

    # --- combine: batched gather back, gate-weight, sum over k -------------
    # y_assign is sharded (batch, moe_tokens->model, -): each device pulls
    # only its A/|model| slice from the expert shards (all-to-all volume
    # ~ tokens*k*D/devices, not a replicated (B,A,D) monster).
    y_pad = jnp.concatenate([y, jnp.zeros((b, e, 1, d), dtype)],
                            axis=2).reshape(b, e * (c + 1), d)
    gates = gate_vals.reshape(b, s * k).astype(dtype) * in_cap.astype(dtype)
    if cfg.moe_shardmap_combine:
        y_tok = _shardmap_combine(y_pad, slot, gates, b, s, k, d, e, c, dtype)
        out = y_tok
        if "shared" in p:
            out = out + mlp(x, p["shared"], cfg)
        out = logical_constraint(out, "batch", "seq", "embed")
        me = probs.mean(axis=(0, 1))
        ce = jnp.zeros((e,), jnp.float32).at[flat_e.reshape(-1)].add(
            1.0 / (b * s * k))
        aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
        return out, aux
    slot_g = logical_constraint(slot, "batch", "moe_tokens")
    y_assign = jnp.take_along_axis(y_pad, slot_g[:, :, None], axis=1)
    y_assign = logical_constraint(y_assign, "batch", "moe_tokens", None)
    if cfg.moe_seq_combine:
        # gate-weight and k-sum while still seq-sharded over 'model' (the
        # reshape keeps whole tokens per shard because k | A/shards), so the
        # final all-gather moves (B,S,D) bf16, not (B,S,k,D):
        y_bsk = (y_assign * gates[..., None]).reshape(b, s, k, d)
        y_bsk = logical_constraint(y_bsk, "batch", "moe_tokens", None, None)
        y_tok = y_bsk.sum(axis=2)
        y_tok = logical_constraint(y_tok, "batch", "moe_tokens", None)
    else:
        y_tok = (y_assign * gates[..., None]).reshape(b, s, k, d).sum(axis=2)
    y_tok = logical_constraint(y_tok, "batch", "seq", None)

    out = y_tok
    if "shared" in p:
        out = out + mlp(x, p["shared"], cfg)
    out = logical_constraint(out, "batch", "seq", "embed")

    # --- router aux load-balancing loss (Switch-style) ---------------------
    me = probs.mean(axis=(0, 1))                                   # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[flat_e.reshape(-1)].add(
        1.0 / (b * a))                                             # (E,)
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
    return out, aux
