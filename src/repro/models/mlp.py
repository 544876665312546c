"""Feed-forward variants (SwiGLU / squared-ReLU / GeLU) and MoE.

The MoE layer implements capacity-based token dispatch: tokens pick top-k
experts; positions within each expert's buffer come from a one-hot cumsum;
overflow beyond ``capacity = ceil(T*k/E * cf)`` is dropped (standard
Switch/GShard semantics).  Expert compute is a single batched einsum over
the (E, C, D) buffer so the expert axis shards cleanly over the mesh
"model" axis (expert parallelism) — the dispatch scatter/gather become
all-to-alls under pjit.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.pspec import constrain
from repro.models.layers import dense_init


# --------------------------------------------------------------------------- #
# Dense FFN
# --------------------------------------------------------------------------- #
def init_ffn(rng, cfg: ModelConfig, d_ff: int, dtype) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(rng, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": dense_init(ks[0], d, d_ff, dtype),
            "w_up": dense_init(ks[1], d, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d, dtype),
        }
    return {
        "w_up": dense_init(ks[0], d, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d, dtype),
    }


def ffn(p: Dict, cfg: ModelConfig, x: jax.Array, constrained: bool = True) -> jax.Array:
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        if cfg.mlp_type == "squared_relu":  # nemotron-4
            r = jax.nn.relu(u.astype(jnp.float32))
            h = (r * r).astype(x.dtype)
        else:  # gelu
            h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    if constrained:  # skipped inside shard_map (axes are manual there)
        h = constrain(h, *([None] * (h.ndim - 1)), "ff")
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# --------------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------------- #
def init_moe(rng, cfg: ModelConfig, dtype) -> Dict:
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(rng, 6)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.truncated_normal(ks[1], -2, 2, (e, d, ff)) / math.sqrt(d)).astype(dtype),
        "w_up": (jax.random.truncated_normal(ks[2], -2, 2, (e, d, ff)) / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.truncated_normal(ks[3], -2, 2, (e, ff, d)) / math.sqrt(ff)).astype(dtype),
    }
    if cfg.num_shared_experts:
        sh_ff = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = init_ffn(ks[4], cfg, sh_ff, dtype)
    return p


def moe_ffn_sharded(p: Dict, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert-parallel MoE (EXPERIMENTS.md §Perf H1, iteration 3).

    pjit's SPMD partitioner lowers the dispatch scatter by replicating the
    full (T*k, D) token tensor over the model axis (observed: 6.4 GB f32
    all-gathers per layer).  Here the parallelism is explicit instead:

      * tokens stay data-sharded and (within a data shard) replicated over
        the model axis — so dispatch (router, top-k, prefix-sum, scatter)
        is 100% local;
      * each model shard slices ITS experts' buffer rows, all-gathers the
        fsdp-sharded expert weights (standard ZeRO-3), runs the expert
        einsum, and combines gated outputs for its experts only;
      * one bf16 psum over the model axis sums the partial combines —
        (T_local, D) bytes instead of gathering (T*k, D) in f32.

    Semantics match :func:`moe_ffn` up to capacity granularity (capacity is
    enforced per data shard here; tests pin exact equality on a 1-device
    mesh).
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.pspec import current_rules

    rules = current_rules()
    mesh = rules.mesh
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else rules.dp_axes[0]
    m_size = mesh.shape["model"]
    e = cfg.num_experts
    if e % m_size != 0:
        return moe_ffn(p, cfg, x)  # cannot slice experts evenly
    e_loc = e // m_size

    def local(x_loc, router, wg, wu, wd, shared):
        b_loc, s, d = x_loc.shape
        tl = b_loc * s
        k = cfg.num_experts_per_token
        xt = x_loc.reshape(tl, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        token_frac = (
            jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0)
            / (tl * k)
        )
        aux_local = cfg.router_aux_coef * e * jnp.sum(token_frac * probs.mean(0))
        aux = jax.lax.pmean(aux_local, rules.dp_axes if isinstance(dp, tuple) else dp)

        capg = capacity_of(cfg, tl)
        flat_e = expert_idx.reshape(-1)  # (Tl*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1
        )[:, 0]
        keep = pos < capg

        # keep only choices routed to THIS model shard's experts
        m_idx = jax.lax.axis_index("model")
        mine = (flat_e >= m_idx * e_loc) & (flat_e < (m_idx + 1) * e_loc) & keep
        local_e = jnp.where(mine, flat_e - m_idx * e_loc, 0)
        safe_pos = jnp.where(mine, pos, capg - 1)
        src = jnp.repeat(xt, k, axis=0)
        buf = jnp.zeros((e_loc, capg, d), x_loc.dtype)
        buf = buf.at[local_e, safe_pos].add(
            jnp.where(mine[:, None], src, 0), mode="drop"
        )

        # ZeRO-3: gather the fsdp-sharded expert weights for this layer
        def gather_fsdp(w):
            for ax in (rules.dp_axes if isinstance(dp, tuple) else (dp,)):
                w = jax.lax.all_gather(w, ax, axis=1, tiled=True)
            return w

        wg_f, wu_f, wd_f = gather_fsdp(wg), gather_fsdp(wu), wd
        for ax in (rules.dp_axes if isinstance(dp, tuple) else (dp,)):
            wd_f = jax.lax.all_gather(wd_f, ax, axis=2, tiled=True)

        if cfg.mlp_type == "swiglu":
            g = jnp.einsum("ecd,edf->ecf", buf, wg_f)
            u = jnp.einsum("ecd,edf->ecf", buf, wu_f)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        else:
            u = jnp.einsum("ecd,edf->ecf", buf, wu_f)
            h = jax.nn.gelu(u.astype(jnp.float32)).astype(x_loc.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd_f)

        gathered = out_buf[local_e, safe_pos]
        gathered = jnp.where(mine[:, None], gathered, 0)
        partial = (
            (gathered * gate_vals.reshape(-1)[:, None].astype(x_loc.dtype))
            .reshape(tl, k, d)
            .sum(axis=1)
        )
        out = jax.lax.psum(partial, "model")  # combine across expert shards
        if cfg.num_shared_experts:
            out = out + ffn(shared, cfg, xt, constrained=False)
        return out.reshape(b_loc, s, d), aux

    b, s, d = x.shape
    # match the actual (expert->model, fsdp->data) weight shardings
    in_specs = (
        P(dp, None, None),
        P(None, None),
        P("model", dp, None),
        P("model", dp, None),
        P("model", None, dp),
        P(),
    )
    shared = p.get("shared", {"w_up": jnp.zeros((0,)), "w_down": jnp.zeros((0,))})
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    return fn(
        x,
        p["router"],
        p["w_gate"],
        p["w_up"],
        p["w_down"],
        shared,
    )


def capacity_of(cfg: ModelConfig, num_tokens: int) -> int:
    cap = int(
        math.ceil(num_tokens * cfg.num_experts_per_token / cfg.num_experts * cfg.capacity_factor)
    )
    return max(8, (cap + 7) // 8 * 8)


def moe_ffn(p: Dict, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss)."""
    import os

    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_token
    xt = x.reshape(t, d)

    if os.environ.get("REPRO_ABLATE_MOE") == "1":
        # profiling bisection knob: router only, zero expert compute
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
        return jnp.zeros_like(x), 1e-9 * logits.sum()

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch): E * sum_e f_e * P_e
    token_frac = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    prob_frac = probs.mean(axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(token_frac * prob_frac)

    # ---- group-local dispatch (EXPERIMENTS.md §Perf H1) ----------------- #
    # Tokens are split into G groups aligned with the data shards; each
    # group computes buffer positions with a LOCAL prefix sum and scatters
    # into its own slice of the (E, G, C_g, D) buffers, so dispatch needs
    # no cross-device position exchange and the expert routing lowers to an
    # all-to-all.  G is installed by the launcher (REPRO_MOE_GROUPS = dp
    # size when the token count divides it; 1 on single-device runs).
    groups = int(os.environ.get("REPRO_MOE_GROUPS", "1"))
    if t % groups != 0:
        groups = 1
    tg = t // groups
    capg = capacity_of(cfg, tg)

    flat_e = expert_idx.reshape(groups, tg * k)  # group-major token order
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (G, Tg*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1  # group-LOCAL prefix sum
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    keep = pos < capg
    safe_pos = jnp.where(keep, pos, capg - 1)

    # scatter tokens into (E, G*Cg, D) buffers at group-local slots
    src = jnp.repeat(xt.reshape(groups, tg, d), k, axis=1)  # (G, Tg*k, D)
    gates_flat = gate_vals.reshape(-1)
    gidx = jnp.arange(groups, dtype=jnp.int32)[:, None]
    slot = gidx * capg + safe_pos  # (G, Tg*k)
    buf = jnp.zeros((e, groups * capg, d), x.dtype)
    buf = buf.at[flat_e.reshape(-1), slot.reshape(-1)].add(
        jnp.where(keep.reshape(-1)[:, None], src.reshape(-1, d), 0),
        mode="drop",
    )
    buf = buf.reshape(e, groups, capg, d)
    buf = constrain(buf, "expert", "batch", None, "embed")

    # expert computation (batched over E; shards over the model axis)
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("egcd,edf->egcf", buf, p["w_gate"])
        u = jnp.einsum("egcd,edf->egcf", buf, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = jnp.einsum("egcd,edf->egcf", buf, p["w_up"])
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    out_buf = jnp.einsum("egcf,efd->egcd", h, p["w_down"])
    out_buf = constrain(out_buf, "expert", "batch", None, "embed")

    # gather back with gates
    out_flat = out_buf.reshape(e, groups * capg, d)
    gathered = out_flat[flat_e.reshape(-1), slot.reshape(-1)]  # (T*k, D)
    gathered = jnp.where(keep.reshape(-1)[:, None], gathered, 0)
    out = (
        (gathered.astype(jnp.float32) * gates_flat[:, None])
        .reshape(t, k, d)
        .sum(axis=1)
        .astype(x.dtype)
    )

    if cfg.num_shared_experts:
        out = out + ffn(p["shared"], cfg, xt)
    return out.reshape(b, s, d), aux
