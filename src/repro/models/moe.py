"""Mixture-of-Experts FFN: top-k router + capacity-based scatter dispatch.

Dispatch strategy (baseline): tokens are scattered into per-expert buffers
of capacity ``C = tokens*k/E * capacity_factor`` (GShard/Switch-style,
"dropping" implementation — the standard MaxText formulation).  Expert and
buffer dims carry logical sharding annotations so GSPMD lowers the dispatch
to all-to-all on the expert axis under expert parallelism; the roofline
§Perf iterations on the MoE archs start from this baseline.

FLOPs scale with *active* experts (k/E of dense-all-experts), which is what
the MODEL_FLOPS/HLO_FLOPs roofline ratio checks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.logical import current_rules, shard
from .layers import dense_init, mlp_init, mlp_apply

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, dtype):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dtype),
        "w_up": dense_init(ks[2], (e, d, f), dtype),
        "w_down": dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * cfg.n_shared_experts, "swiglu", dtype)
    return p


def _route(p, cfg: ModelConfig, xf):
    """Shared router math. xf: (T, D) -> (gate (T,k), idx (T,k), aux)."""
    t = xf.shape[0]
    e, k = cfg.n_experts, cfg.experts_per_token
    router_logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    return probs, gate, idx


def _positions(idx, e: int):
    """Slot positions via cumsum over the flat (T*k,) assignment order."""
    t, k = idx.shape
    assign = jax.nn.one_hot(idx, e, dtype=jnp.float32)
    flat = assign.reshape(t * k, e)
    pos = ((jnp.cumsum(flat, axis=0) - flat) * flat).sum(-1).astype(jnp.int32)
    return assign, pos


def _expert_mlp(cfg: ModelConfig, p, buf):
    """Per-expert GLU MLP on a dispatch buffer (E_loc, C, D)."""
    h_up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(g) * h_up
    else:
        h = jax.nn.gelu(h_up, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _moe_shard_map(p, cfg: ModelConfig, x, mesh, rules):
    """Partition-local EP dispatch (§Perf): local scatter -> all_to_all(E)
    -> local expert GEMMs -> psum(model) -> all_to_all back -> local
    combine.  No data-dependent global scatter ever crosses the mesh, so
    the only collectives are the canonical MoE all-to-alls + one psum.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    data_ax = rules["expert"]                 # expert exchange axis
    model_ax = rules["model"]
    bx = rules["batch"]
    batch_axes = bx if isinstance(bx, tuple) else ((bx,) if bx else ())
    n_tok_shards = 1
    for a in batch_axes:
        n_tok_shards *= mesh.shape[a]
    t = b * s
    t_loc = t // n_tok_shards
    cap_loc = max(int(t_loc * k / e * cfg.capacity_factor), 1)
    n_data = mesh.shape[data_ax]

    def local_fn(xl, router, wg, wu, wd, shared):
        # xl: (t_loc, d); wg/wu: (1, d, f_loc); wd: (1, f_loc, d)
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        probs, gate, idx = _route(pl, cfg, xl)
        assign, pos = _positions(idx, e)
        f_e = jax.lax.psum(assign.sum(axis=(0, 1)), batch_axes) / (t * k)
        p_e = jax.lax.psum(probs.sum(axis=0), batch_axes) / t
        aux = e * jnp.sum(f_e * p_e)

        eid = idx.reshape(t_loc * k)
        keep = pos < cap_loc
        slot = jnp.minimum(pos, cap_loc - 1)
        xk = jnp.repeat(xl[:, None, :], k, axis=1).reshape(t_loc * k, d)
        contrib = jnp.where(keep[:, None], xk, 0).astype(x.dtype)
        buf = jnp.zeros((e, cap_loc, d), x.dtype).at[eid, slot].add(contrib)

        # exchange: every shard sends expert j's slice to shard j
        buf = jax.lax.all_to_all(buf, data_ax, split_axis=0, concat_axis=1,
                                 tiled=True)          # (e_loc, C, d)
        y = _expert_mlp(cfg, pl, buf)                 # partial over f_loc
        y = jax.lax.psum(y.astype(xl.dtype), model_ax)  # bf16 on the wire
        y = jax.lax.all_to_all(y, data_ax, split_axis=1, concat_axis=0,
                               tiled=True)            # (e, cap_loc, d)

        w = (gate.reshape(t_loc * k) * keep).astype(x.dtype)
        out = (y[eid, slot] * w[:, None]).reshape(t_loc, k, d).sum(axis=1)
        if shared is not None:
            sh_up = xl @ shared["w_up"]
            sh_g = jax.nn.silu(xl @ shared["w_gate"])
            out = out + jax.lax.psum((sh_g * sh_up) @ shared["w_down"],
                                     model_ax)
        return out, aux

    tok_spec = P(bx) if batch_axes else P()
    shared_specs = ({"w_gate": P(None, model_ax), "w_up": P(None, model_ax),
                     "w_down": P(model_ax, None)}
                    if cfg.n_shared_experts else None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bx, None), P(None, None),
                  P(data_ax, None, model_ax), P(data_ax, None, model_ax),
                  P(data_ax, model_ax, None), shared_specs),
        out_specs=(P(bx, None), P()),
        check_vma=False)
    y, aux = fn(x.reshape(t, d), p["router"], p["w_gate"], p["w_up"],
                p["w_down"], p.get("shared") if cfg.n_shared_experts else None)
    return y.reshape(b, s, d), aux


def _moe_sharding_ok(cfg: ModelConfig, x, mesh, rules) -> bool:
    """shard_map path needs even divisibility everywhere."""
    if rules is None or mesh is None:
        return False
    data_ax, model_ax, bx = rules.get("expert"), rules.get("model"), rules.get("batch")
    if rules.get("moe") != "shard_map" or not data_ax or not model_ax:
        return False
    batch_axes = bx if isinstance(bx, tuple) else ((bx,) if bx else ())
    n_tok = 1
    for a in batch_axes:
        n_tok *= mesh.shape[a]
    t = x.shape[0] * x.shape[1]
    # partition-local capacity must stay statistically safe: with too few
    # tokens per shard (decode), local top-k skew would drop tokens, so
    # fall back to the global-dispatch path there.
    enough = t // max(n_tok, 1) * cfg.experts_per_token >= 4 * cfg.n_experts
    return (n_tok > 0 and t % n_tok == 0 and enough
            and cfg.n_experts % mesh.shape[data_ax] == 0
            and cfg.d_ff % mesh.shape[model_ax] == 0)


def moe_apply(p, cfg: ModelConfig, x):
    """x: (B, S, D) -> (y, aux_loss).  Top-k routing, renormalized weights."""
    rules, mesh = current_rules()
    if _moe_sharding_ok(cfg, x, mesh, rules):
        return _moe_shard_map(p, cfg, x, mesh, rules)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)

    router_logits = xf.astype(jnp.float32) @ p["router"]           # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                            # (T, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)      # renorm

    # Load-balancing aux loss (Switch §2.2): E * sum_e f_e * P_e.
    assign = jax.nn.one_hot(idx, e, dtype=jnp.float32)             # (T, k, E)
    f_e = assign.sum(axis=(0, 1)) / (t * k)
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)

    # --- capacity-based scatter dispatch ---------------------------------
    cap = max(int(t * k / e * cfg.capacity_factor), 1)
    flat_assign = assign.reshape(t * k, e)
    pos = ((jnp.cumsum(flat_assign, axis=0) - flat_assign) * flat_assign).sum(-1)
    pos = pos.astype(jnp.int32)                                    # (T*k,)
    eid = idx.reshape(t * k)
    keep = (pos < cap)
    slot = jnp.minimum(pos, cap - 1)

    xk = jnp.repeat(xf[:, None, :], k, axis=1).reshape(t * k, d)
    contrib = jnp.where(keep[:, None], xk, 0).astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[eid, slot].add(contrib)
    # Dispatch-buffer layout is a perf lever (EXPERIMENTS.md §Perf):
    #   baseline  expert->data, expert_capacity->None : buffer sharded on E
    #     — the token->buffer scatter crosses the data axis and GSPMD
    #     lowers it to full-buffer all-reduces;
    #   optimized expert->None, expert_capacity->data : buffer sharded on C
    #     — the scatter is local and the expert einsum reshard lowers to
    #     all-to-all (canonical MoE EP dispatch).
    buf = shard(buf, ("expert", "expert_capacity", None))

    # --- expert computation (per-expert GLU MLP) -------------------------
    h_up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(g) * h_up
    else:
        h = jax.nn.gelu(h_up, approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_buf = shard(out_buf, ("expert", "expert_capacity", None))

    # --- combine ----------------------------------------------------------
    gathered = out_buf[eid, slot]                                  # (T*k, D)
    w = (gate.reshape(t * k) * keep).astype(x.dtype)
    y = (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], xf, "swiglu")
    return y.reshape(b, s, d), aux
