"""Dense MLP and Mixture-of-Experts layers.

Two MoE dispatch implementations:
  * "dense"  — one-hot combine einsum over ALL experts. Simple, differentiable,
               pure-pjit friendly. FLOPs cost = E/k x the active compute; used
               for smoke tests / small expert counts and as a fallback.
  * "sorted" — capacity-based sort+gather dispatch (GShard/MaxText style) that
               only computes routed tokens (x capacity factor). Tokens are
               sorted by expert id, gathered into an (E, C, D) buffer via an
               offset table, batched-matmul'd, and scatter-added back. This is
               the production path; it runs inside the global pjit with local
               token views (sort is per data shard by construction as the
               token axis is data-sharded and the op chain is elementwise in
               the shard dimension; see launch/shardings.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ACTS, dense_init


# ------------------------------------------------------------------ dense MLP
def init_mlp(key, cfg, d_model=None, d_ff=None):
    D = d_model or cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act == "silu":  # SwiGLU
        return {"w_gate": dense_init(ks[0], D, F), "w_up": dense_init(ks[1], D, F),
                "w_down": dense_init(ks[2], F, D)}
    return {"w_up": dense_init(ks[0], D, F), "b_up": jnp.zeros((F,), jnp.float32),
            "w_down": dense_init(ks[1], F, D), "b_down": jnp.zeros((D,), jnp.float32)}


def mlp(x, p, cfg):
    dt = x.dtype
    act = ACTS[cfg.mlp_act]
    if cfg.mlp_act == "silu":
        h = act(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
        return h @ p["w_down"].astype(dt)
    h = act(x @ p["w_up"].astype(dt) + p["b_up"].astype(dt))
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)


# ------------------------------------------------------------------------ MoE
def init_moe(key, cfg):
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    std = D ** -0.5
    return {
        "router": dense_init(ks[0], D, E),
        "w_gate": (std * jax.random.truncated_normal(ks[1], -3, 3, (E, D, F))).astype(jnp.float32),
        "w_up":   (std * jax.random.truncated_normal(ks[2], -3, 3, (E, D, F))).astype(jnp.float32),
        "w_down": (F ** -0.5 * jax.random.truncated_normal(ks[3], -3, 3, (E, F, D))).astype(jnp.float32),
    }


def router_topk(x, w_router, cfg):
    """Returns (weights (T,k), indices (T,k), aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.router_norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balancing auxiliary loss.
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return weights, idx, aux


def moe_dense(x, p, cfg):
    """One-hot combine over all experts.  x: (T, D)."""
    T, D = x.shape
    dt = x.dtype
    weights, idx, aux = router_topk(x, p["router"], cfg)
    E = cfg.num_experts
    # combine (T, E): sum of gate weights routed to each expert
    comb = jax.nn.one_hot(idx, E, dtype=jnp.float32) * weights[..., None]  # (T,k,E)
    comb = comb.sum(axis=1)                                  # (T, E)
    act = ACTS[cfg.mlp_act]
    h = jnp.einsum("td,edf->etf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->etf", x, p["w_up"].astype(dt))
    y = jnp.einsum("etf,efd->etd", act(h) * u, p["w_down"].astype(dt))
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), comb)
    return out.astype(dt), aux


def moe_sorted(x, p, cfg):
    """Capacity-based sort+gather dispatch.  x: (T, D) local tokens.

    Both dispatch and combine are pure gathers (the combine inverts the
    sort permutation instead of scatter-adding): GSPMD partitions batched
    gathers along the vmapped row dim, whereas a batched scatter forces an
    all-gather of every row's (E, C, D) buffer."""
    T, D = x.shape
    dt = x.dtype
    k = cfg.num_experts_per_tok
    E = cfg.num_experts
    C = max(int(T * k / E * cfg.moe_capacity_factor), 1)

    weights, idx, aux = router_topk(x, p["router"], cfg)      # (T,k)
    flat_e = idx.reshape(-1)                                  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)                  # sorted slot -> flat slot
    tok_of_slot = order // k                                  # token id per sorted slot
    gate_of_slot = weights.reshape(-1)[order]                 # (T*k,)
    # count via comparison matrix, NOT jnp.bincount: bincount lowers to a
    # scatter-add, and batched scatters make GSPMD replicate the whole
    # vmapped dispatch (all rows' (E,C,D) buffers on every device).
    sizes = (flat_e[None, :] == jnp.arange(E)[:, None]).sum(axis=1)   # (E,)
    offsets = jnp.cumsum(sizes) - sizes                       # exclusive cumsum
    gidx = offsets[:, None] + jnp.arange(C)[None, :]          # (E, C) slots per expert
    valid = (jnp.arange(C)[None, :] < sizes[:, None])         # (E, C)
    gidx = jnp.where(valid, gidx, 0)
    tok = jnp.where(valid, tok_of_slot[gidx], 0)              # (E, C) token ids
    gates = jnp.where(valid, gate_of_slot[gidx], 0.0)         # (E, C)

    xg = x[tok] * valid[..., None].astype(dt)                 # (E, C, D)
    act = ACTS[cfg.mlp_act]
    h = act(jnp.einsum("ecd,edf->ecf", xg, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", xg, p["w_up"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))  # (E, C, D)
    y = (y.astype(jnp.float32) * gates[..., None]).astype(dt)

    # scatter-free combine: flat slot j sits at sorted position inv[j],
    # which is position inv[j] - offsets[e_j] within expert e_j's buffer.
    inv = jnp.argsort(order)                                  # flat -> sorted pos
    c_of_flat = inv - offsets[flat_e]                         # (T*k,)
    ok = c_of_flat < C
    vals = y[flat_e, jnp.where(ok, c_of_flat, 0)]             # (T*k, D)
    vals = vals * ok[:, None].astype(dt)
    out = vals.reshape(T, k, D).sum(axis=1)
    return out.astype(dt), aux


def _pin_rows(x3):
    """Anchor the dispatch-batch dim onto the dp axes before the vmapped
    sort/gather chain: in python-unrolled graphs (scan_layers=False)
    GSPMD otherwise replicates some layers' (rows, E, C, D) buffers."""
    from repro.launch.mesh import get_abstract_mesh
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x3
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in mesh.axis_names if a != "model") or None
    if dp is None:
        return x3
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    if dpn <= 1 or x3.shape[0] % dpn:
        return x3
    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x3, P(dp, U, U))


def moe(x3, p, cfg):
    """x3: (B, S, D) -> (B, S, D), aux loss.

    Sorted dispatch runs PER BATCH ROW via vmap: the argsort/bincount chain
    then lives entirely inside each data shard (the batch dim is
    dp-sharded), so GSPMD never all-gathers the global token axis — the
    unbatched formulation forced a full-token gather per MoE layer
    (observed: 300+ s of collective time per prefill step at 32k).
    Capacity is enforced per row (C = S*k/E * factor), a slightly stronger
    balance condition than the global bound.

    The sorted path also needs S >> E for the capacity bound to be
    statistically safe; for tiny token counts (decode steps, smoke tests)
    it would drop tokens, so fall back to the exact dense combine there."""
    B, S, D = x3.shape
    if cfg.moe_impl == "sorted" and S >= 4 * cfg.num_experts:
        # bound the (E, C, D) buffers: split long sequences into dispatch
        # chunks via a NESTED vmap (batch, then seq-chunks). The seq split
        # must not be folded into the batch dim — reshaping through the
        # dp-sharded batch axis breaks GSPMD propagation and every row's
        # dispatch buffer gets replicated (observed 80 GiB/device at 32k).
        C0 = cfg.moe_dispatch_chunk
        x3 = _pin_rows(x3)
        row_fn = lambda xr: moe_sorted(xr, p, cfg)
        if S > C0 and S % C0 == 0:
            # scan (not vmap) over the seq chunks: one chunk's (E, C, D)
            # dispatch buffers live at a time — 8x less prefill memory at
            # 32k; chunks would serialize through the MXU anyway.
            # (unrolled when cfg.scan_layers=False so XLA's cost analysis
            # counts every chunk — see models.model._scan)
            chunks = jnp.moveaxis(x3.reshape(B, S // C0, C0, D), 1, 0)

            if cfg.scan_layers:
                def step(_, xc):
                    return None, jax.vmap(row_fn)(xc)

                _, (out, aux) = jax.lax.scan(step, None, chunks)
            else:
                outs = [jax.vmap(row_fn)(chunks[i])
                        for i in range(chunks.shape[0])]
                out = jnp.stack([o for o, _ in outs])
                aux = jnp.stack([a for _, a in outs])
            out = jnp.moveaxis(out, 0, 1).reshape(B, S, D)
            return out, jnp.mean(aux)
        out, aux = jax.vmap(row_fn)(x3)
        return out, jnp.mean(aux)
    x = x3.reshape(B * S, D)
    out, aux = moe_dense(x, p, cfg)
    return out.reshape(B, S, D), aux
