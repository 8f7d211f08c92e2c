"""Mixture-of-Experts with sort-based capacity dispatch + expert parallelism.

Scalable design (no GShard dense-dispatch einsum, which is O(T·E·Cap·d) and
collapses at 160 experts): tokens are routed with an argsort over expert ids,
scattered into a static (E, capacity, d) buffer (overflow tokens drop — the
standard capacity-factor contract), processed with one batched per-expert
GEMM (exactly the active FLOPs), and gathered back with their top-k gate
weights.  The expert buffer is sharded over the ``ep`` (model) mesh axis, so
the scatter/gather pair is where XLA materializes the MoE all-to-alls.

Includes the standard load-balance auxiliary loss and deepseek-style shared
experts (always-on dense FFN).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import modules as nn
from repro.sharding.rules import maybe_constrain

__all__ = ["moe_init", "moe_forward", "ffn_init", "ffn_forward", "moe_capacity"]


def ffn_init(key, d: int, f: int, dtype):
    ks = nn.split_key_tree(key, ["w_gate", "w_up", "w_down"])
    return {
        "w_gate": nn.dense_init(ks["w_gate"], d, f, dtype),
        "w_up": nn.dense_init(ks["w_up"], d, f, dtype),
        "w_down": nn.dense_init(ks["w_down"], f, d, dtype, scale=f**-0.5),
    }


def ffn_forward(p, x):
    g = nn.dense(p["w_gate"], x)
    u = nn.dense(p["w_up"], x)
    h = nn.swiglu(g, u)
    h = maybe_constrain(h, ("batch", None, "tp"))
    return nn.dense(p["w_down"], h)


def moe_init(key, cfg, dtype):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = nn.split_key_tree(key, ["router", "w_gate", "w_up", "w_down", "shared"])
    p = {
        "router": {"gate_w": nn.dense_init(ks["router"], d, E, dtype, scale=d**-0.5)},
        "experts": {
            "w_gate": _expert_init(ks["w_gate"], E, d, f, dtype),
            "w_up": _expert_init(ks["w_up"], E, d, f, dtype),
            "w_down": _expert_init(ks["w_down"], E, f, d, dtype),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(ks["shared"], d, cfg.n_shared_experts * f, dtype)
    return p


def _expert_init(key, E, d_in, d_out, dtype):
    return (
        jax.random.normal(key, (E, d_in, d_out), dtype=jnp.float32) * d_in**-0.5
    ).astype(dtype)


def moe_capacity(tokens: int, cfg) -> int:
    cap = int(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(((cap + 127) // 128) * 128, 128)  # lane-align


def _route(xf, gate_w, cfg):
    """fp32 routing: probs, normalized top-k gates, aux load-balance loss."""
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.matmul(
        xf, gate_w.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, gate_ids = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_ids, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = E * jnp.sum(me * ce)
    return gate_ids, gate_vals, aux


def _dispatch_compute_combine(xf, ids, gates, experts, C, E, dtype):
    """Capacity dispatch -> batched expert GEMMs -> weighted combine.

    Memory discipline: no (T*K, d) tensor is ever built.  Routing metadata
    stays 1-D int/float (cheap); activations exist only at capacity size:
    a slot->token index map gathers straight into the (E*C, d) buffer, and
    the combine scatter-adds (E*C, d) expert outputs back into (T, d).
    Works on LOCAL (per-shard) experts: ids must already be local ([0, E))
    with out-of-shard tokens set to E (the drop sentinel)."""
    T, d = xf.shape
    K = ids.shape[-1]
    ids_flat = ids.reshape(-1)  # (T*K,)
    order = jnp.argsort(ids_flat)
    sorted_ids = ids_flat[order]
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(E), side="left")
    pos_sorted = jnp.arange(T * K) - seg_start[jnp.minimum(sorted_ids, E - 1)]
    pos_flat = jnp.zeros((T * K,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))

    keep = (pos_flat < C) & (ids_flat < E)
    slot = jnp.where(keep, ids_flat * C + pos_flat, E * C)  # E*C == drop
    tok_idx = (jnp.arange(T * K) // K).astype(jnp.int32)

    # slot -> (token, gate, occupied); all 1-D, scatter mode="drop"
    slot_tok = jnp.zeros((E * C,), jnp.int32).at[slot].set(tok_idx, mode="drop")
    slot_gate = (
        jnp.zeros((E * C,), jnp.float32)
        .at[slot]
        .set(gates.reshape(-1).astype(jnp.float32), mode="drop")
    )
    occupied = jnp.zeros((E * C,), jnp.float32).at[slot].set(
        keep.astype(jnp.float32), mode="drop"
    )

    buf = (xf[slot_tok].astype(jnp.float32) * occupied[:, None]).astype(dtype)
    buf = buf.reshape(E, C, d)

    def emm(t, w):  # (E,C,a) @ (E,a,b)
        if isinstance(w, dict):  # RSI-compressed expert kernels: the stacked
            # (E, ...) factors route through the dispatcher, which can launch
            # ONE batched fused kernel over the expert axis instead of E
            # two-GEMM round-trips.
            from repro.core.lowrank import apply_linear

            return apply_linear(w, t)
        return jnp.einsum("eca,eab->ecb", t, w, preferred_element_type=jnp.float32).astype(
            dtype
        )

    h = nn.swiglu(emm(buf, experts["w_gate"]), emm(buf, experts["w_up"]))
    y = emm(h, experts["w_down"]).reshape(E * C, d)

    weighted = y.astype(jnp.float32) * (slot_gate * occupied)[:, None]  # (E*C, d)
    out = jnp.zeros((T, d), jnp.float32).at[slot_tok].add(weighted, mode="drop")
    return out  # fp32 (T, d)


def _moe_local(p, x, cfg):
    """Single-device / no-mesh path (tests, small runs)."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    ids, gates, aux = _route(xf, p["router"]["gate_w"], cfg)
    C = moe_capacity(T, cfg)
    out = _dispatch_compute_combine(
        xf, ids, gates, p["experts"], C, cfg.n_experts, x.dtype
    ).astype(x.dtype)
    return out.reshape(B, S, d), aux


def _moe_expert_parallel(p, x, cfg, rules):
    """Expert-parallel MoE via shard_map (the production path).

    Layout: tokens sharded over the batch axes and REPLICATED over "model";
    experts sharded over "model" (E/m per shard).  Each (data, model) device
    selects the subset of ITS tokens routed to ITS expert shard, dispatches
    locally (no all-to-all!), runs the expert GEMMs, and scatters results
    back to token positions; a single psum over "model" sums each token's
    top-k expert outputs.  Communication per layer = one fp32 (T_local, d)
    all-reduce — the same volume as a standard TP activation reduce, and
    independent of E.  Routing is computed redundantly per model shard
    (d x E GEMM — negligible) to avoid broadcasting gate decisions.
    """
    mesh = rules.mesh
    m_size = mesh.shape["model"]
    E_loc = cfg.n_experts // m_size
    B, S, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    T_loc = (B // dp if B % dp == 0 else B) * S
    # local per-expert capacity: tokens of ONE data shard to ONE expert;
    # higher slack than the global rule because local loads vary more.
    C = int(T_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor * 1.6)
    C = max(((C + 127) // 128) * 128, 128)

    from jax.sharding import PartitionSpec as P

    x_spec = P(batch_axes if B % dp == 0 else None, None, None)
    e_spec = jax.tree_util.tree_map(lambda _: P("model"), p["experts"])

    def block(gate_w, experts_loc, x_blk):
        Bl, Sl, _ = x_blk.shape
        xf = x_blk.reshape(Bl * Sl, d)
        ids, gates, aux = _route(xf, gate_w, cfg)
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        j = jax.lax.axis_index("model")
        lo = j * E_loc
        local = jnp.where(
            (ids >= lo) & (ids < lo + E_loc), ids - lo, E_loc
        )  # E_loc == drop sentinel
        out = _dispatch_compute_combine(
            xf, local, gates, experts_loc, C, E_loc, x_blk.dtype
        )
        out = jax.lax.psum(out, "model")
        return out.astype(x_blk.dtype).reshape(Bl, Sl, d), aux

    out, aux = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(), e_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"]["gate_w"], p["experts"], x)
    return out, aux


def moe_forward(p, x, cfg):
    """x: (B, S, d).  Returns (out, aux_loss)."""
    from repro.sharding.rules import active_rules

    rules = active_rules()
    if (
        rules is not None
        and "model" in rules.mesh.shape
        and cfg.n_experts % rules.mesh.shape["model"] == 0
    ):
        out, aux = _moe_expert_parallel(p, x, cfg, rules)
    else:
        out, aux = _moe_local(p, x, cfg)

    if "shared" in p:
        out = out + ffn_forward(p["shared"], x)
    return out, aux
