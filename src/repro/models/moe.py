"""Mixture-of-experts layer: capacity-based top-k routing with
scatter/gather dispatch.

The classic GShard formulation materializes a (tokens, experts, capacity)
one-hot dispatch tensor; at 1M tokens x 32 experts x 300k capacity that is
~1e13 elements — the dry-run flagged exactly this (granite train_4k at 135x
HBM).  Since the dispatch tensor is a permutation in disguise, we instead
scatter-add tokens into the (experts, capacity, d) buffer and gather them
back: O(T·k·d) data movement, buffer sharded over the model axis (expert
parallelism), positions from a per-round cumsum over the one-hot (O(T·E)).
Under GSPMD the scatter/gather between token-sharded and expert-sharded
layouts lowers to the expected all-to-all exchange.

Top-k routing runs k rounds of top-1 dispatch against a shared capacity
budget; capacity-overflow tokens are dropped (standard GShard semantics),
counted in the aux loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.sharding_ctx import constrain

__all__ = ["init_moe_params", "moe_layer"]


def init_moe_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    assert cfg.moe is not None
    d, e, f = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_ff_expert
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) * d**-0.5,
        "w_in": jax.random.normal(k2, (e, d, f), dtype) * d**-0.5,
        "w_gate": jax.random.normal(k3, (e, d, f), dtype) * d**-0.5,
        "w_out": jax.random.normal(k4, (e, f, d), dtype) * f**-0.5,
    }
    if cfg.moe.dense_d_ff:
        from repro.models.layers import init_mlp_params

        p["dense"] = init_mlp_params(cfg, key, dtype, d_ff=cfg.moe.dense_d_ff)
    return p


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    moe = cfg.moe
    # k dispatch slots per token spread over E experts.
    cap = int(moe.capacity_factor * n_tokens * moe.top_k / moe.n_experts) + 1
    # Round to a lane-friendly size; tiny smoke configs keep at least 4.
    return max(4, -(-cap // 4) * 4)


def moe_layer(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (out, aux_loss).  aux is the standard load-balancing
    loss (mean over experts of fraction_dispatched * mean_gate * E)."""
    moe = cfg.moe
    e = moe.n_experts
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    cap = _capacity(cfg, t)
    remaining = probs
    expert_fill = jnp.zeros((e,), jnp.int32)
    frac_dispatched = jnp.zeros((e,), jnp.float32)
    buf = constrain(jnp.zeros((e, cap, d), xt.dtype), ("experts", None, None))
    routes = []  # per round: (dest_e (T,), dest_c (T,), gate (T,) masked)

    for _ in range(moe.top_k):
        gate = jnp.max(remaining, axis=-1)                      # (T,)
        expert = jnp.argmax(remaining, axis=-1)                 # (T,)
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)   # (T, E)
        # Prefix sum over the (possibly GSPMD-sharded) token axis: the
        # log-depth scan partitions correctly on the installed jax.
        csum = jax.lax.associative_scan(jnp.add, onehot, axis=0)
        pos = (csum - 1.0) + expert_fill[None, :].astype(jnp.float32)
        pos_tok = jnp.sum(pos * onehot, axis=-1)                # (T,)
        keep = pos_tok < cap
        # Capacity-dropped slots scatter zeros into expert 0 (harmless) and
        # their gates are zeroed, so no dump row is needed and the buffer
        # keeps its clean (E, C, d) expert sharding.
        dest_e = jnp.where(keep, expert, 0).astype(jnp.int32)
        dest_c = jnp.clip(pos_tok, 0, cap - 1).astype(jnp.int32)
        src = jnp.where(keep[:, None], xt, jnp.zeros_like(xt))
        buf = buf.at[dest_e, dest_c].add(src)                   # O(T d) scatter
        routes.append((dest_e, dest_c, jnp.where(keep, gate, 0.0)))
        expert_fill = expert_fill + jnp.sum(
            onehot * keep[:, None].astype(jnp.float32), axis=0
        ).astype(jnp.int32)
        frac_dispatched = frac_dispatched + jnp.mean(onehot, axis=0)
        remaining = remaining * (1.0 - onehot)

    expert_in = constrain(buf, ("experts", None, None))         # (E, C, d)
    hidden = jnp.einsum("ecd,edf->ecf", expert_in, p["w_in"])
    gated = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"])) * hidden
    expert_out = jnp.einsum("ecf,efd->ecd", gated, p["w_out"])  # (E, C, d)

    combined = jnp.zeros_like(xt, dtype=jnp.float32)
    for dest_e, dest_c, gate in routes:
        combined = combined + expert_out[dest_e, dest_c].astype(jnp.float32) * gate[:, None]

    aux = jnp.sum(frac_dispatched / moe.top_k * jnp.mean(probs, axis=0)) * e
    out = combined.astype(x.dtype).reshape(b, s, d)
    if "dense" in p:
        from repro.models.layers import mlp

        out = out + mlp(cfg, p["dense"], x)
    return constrain(out, ("batch", "seq", "embed")), aux


def _moe_local(cfg: ModelConfig, p: dict, xt: jax.Array, n_local_experts: int, axis: str):
    """Per-device body of the manual expert-parallel layer (inside
    shard_map over ('pod','data','model')).

    Tokens are local to this data shard (replicated over 'model'); this
    device hosts ``n_local_experts`` consecutive experts.  Routing runs
    against the full router (replicated, tiny); only tokens whose expert
    lives here are scattered into the local buffer; the combined output is
    psum'd over the model axis — wire cost O(T_local * d) instead of the
    O(E*C*d) buffer all-reduce GSPMD chooses for the scatter formulation
    (EXPERIMENTS.md §Perf B4)."""
    moe = cfg.moe
    e = moe.n_experts
    t, d = xt.shape
    shard = jax.lax.axis_index(axis)
    first = shard * n_local_experts

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    # capacity against *local* tokens (each data shard routes independently)
    cap = max(4, int(moe.capacity_factor * t * moe.top_k / e) + 4)

    remaining = probs
    expert_fill = jnp.zeros((e,), jnp.int32)
    frac_dispatched = jnp.zeros((e,), jnp.float32)
    buf = jnp.zeros((n_local_experts, cap, d), xt.dtype)
    routes = []
    for _ in range(moe.top_k):
        gate = jnp.max(remaining, axis=-1)
        expert = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
        # Token axis is device-local inside shard_map; same scan as the
        # GSPMD path above.
        csum = jax.lax.associative_scan(jnp.add, onehot, axis=0)
        pos_tok = jnp.sum((csum - 1.0 + expert_fill[None].astype(jnp.float32)) * onehot, -1)
        local = (expert >= first) & (expert < first + n_local_experts)
        keep = (pos_tok < cap) & local
        dest_e = jnp.where(keep, expert - first, 0).astype(jnp.int32)
        dest_c = jnp.clip(pos_tok, 0, cap - 1).astype(jnp.int32)
        buf = buf.at[dest_e, dest_c].add(jnp.where(keep[:, None], xt, jnp.zeros_like(xt)))
        routes.append((dest_e, dest_c, jnp.where(keep, gate, 0.0)))
        expert_fill = expert_fill + jnp.sum(
            onehot * (pos_tok < cap)[:, None].astype(jnp.float32), axis=0
        ).astype(jnp.int32)
        frac_dispatched = frac_dispatched + jnp.mean(onehot, axis=0)
        remaining = remaining * (1.0 - onehot)

    w_in, w_gate, w_out = p["w_in"], p["w_gate"], p["w_out"]  # local (E_loc, ...)
    hidden = jnp.einsum("ecd,edf->ecf", buf, w_in)
    gated = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * hidden
    expert_out = jnp.einsum("ecf,efd->ecd", gated, w_out)

    combined = jnp.zeros_like(xt, dtype=jnp.float32)
    for dest_e, dest_c, gate in routes:
        combined = combined + expert_out[dest_e, dest_c].astype(jnp.float32) * gate[:, None]
    # Each token's experts live on exactly the shards that contributed;
    # summing over the model axis assembles the full top-k mixture.
    combined = jax.lax.psum(combined, axis)
    aux = jnp.sum(frac_dispatched / moe.top_k * jnp.mean(probs, axis=0)) * e
    return combined.astype(xt.dtype), aux


def moe_layer_manual(cfg: ModelConfig, p: dict, x: jax.Array, mesh) -> tuple[jax.Array, jax.Array]:
    """Manual expert-parallel MoE via shard_map (moe_impl='manual')."""
    from jax.sharding import PartitionSpec as P

    from repro.runtime.sharding import batch_axes

    moe = cfg.moe
    dp = batch_axes(mesh)
    tp = mesh.shape["model"]
    if moe.n_experts % tp:
        # cannot split experts evenly: fall back to the GSPMD path
        return moe_layer(cfg, p, x)
    n_local = moe.n_experts // tp
    b, s, d = x.shape

    def local_fn(p_local, x_local):
        bl, sl, _ = x_local.shape
        out, aux = _moe_local(cfg, p_local, x_local.reshape(bl * sl, d), n_local, "model")
        aux = jax.lax.pmean(aux, dp)  # replicate the load-balance stat
        return out.reshape(bl, sl, d), aux

    p_specs = {
        "router": P(),
        "w_in": P("model", None, None),
        "w_gate": P("model", None, None),
        "w_out": P("model", None, None),
    }
    if "dense" in p:
        p_specs["dense"] = jax.tree.map(lambda _: P(), p["dense"])
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(p_specs, P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(p, x)
    if "dense" in p:
        from repro.models.layers import mlp

        out = out + mlp(cfg, p["dense"], x)
    return constrain(out, ("batch", "seq", "embed")), aux
