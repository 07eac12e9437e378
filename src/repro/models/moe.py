"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Dispatch avoids the GShard (tokens, E, capacity) dense one-hot (which is
~10^10 elements for kimi-k2's E=384): instead tokens are ARGSORTED by
assigned expert and ranked within expert via searchsorted - O(NK log NK)
with no (N, E) intermediates - then scattered into an (E*C, d) buffer.

Sharding: the dispatch buffer is constrained to be expert-sharded over the
TP/EP axis ("model"); expert weights are E-sharded over "model" and
d-sharded over "data" (ZeRO-3 all-gather at use).  GSPMD converts the
token->buffer scatter into cross-shard communication; replacing that with an
explicit shard_map all_to_all is a recorded perf-iteration (EXPERIMENTS.md
SSPerf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig
from repro.sharding.api import batch_axes, constrain
from .layers import dense_init


def _f0(x):
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


# Both routing maps are INJECTIVE on their kept entries, so their vjp
# transposes are gathers too.  Without these custom VJPs, autodiff emits
# scatter-adds whose GSPMD lowering all-reduces full (G, E*C, d) cotangents
# over the EP axis - 9.4 TiB/device/step on kimi-k2 train_4k
# (EXPERIMENTS.md SSPerf, iteration A1).


@jax.custom_vjp
def _dispatch_gather(tokens, src, buf_valid, dest):
    """tokens (G, Ng, d) -> buf (G, E*C, d) via slot->token gather."""
    buf = jnp.take_along_axis(tokens, src[..., None], axis=1)
    return buf * buf_valid[..., None].astype(tokens.dtype)


def _dispatch_fwd(tokens, src, buf_valid, dest):
    return _dispatch_gather(tokens, src, buf_valid, dest), (
        src, buf_valid, dest, tokens.shape)


def _dispatch_bwd(res, d_buf):
    src, buf_valid, dest, tok_shape = res
    G, Ng, d = tok_shape
    EC = d_buf.shape[1]
    K = dest.shape[1] // Ng
    # token t's cotangent = sum over its kept assignments' buffer slots
    safe = jnp.clip(dest, 0, EC - 1)
    picked = jnp.take_along_axis(d_buf, safe[..., None], axis=1)
    picked = picked * (dest < EC)[..., None].astype(d_buf.dtype)
    d_tokens = jnp.sum(picked.reshape(G, Ng, K, d), axis=2)
    return d_tokens, _f0(src), _f0(buf_valid), _f0(dest)


_dispatch_gather.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_gather(out_buf, dest, order, inv_order, s_safe, buf_valid):
    """out_buf (G, E*C, d) -> per-assignment slots (G, Ng*K, d)."""
    EC = out_buf.shape[1]
    safe = jnp.clip(dest, 0, EC - 1)
    slot_sorted = jnp.take_along_axis(out_buf, safe[..., None], axis=1)
    slot_sorted = slot_sorted * (dest < EC)[..., None].astype(out_buf.dtype)
    return jnp.take_along_axis(slot_sorted, inv_order[..., None], axis=1)


def _combine_fwd(out_buf, dest, order, inv_order, s_safe, buf_valid):
    return (_combine_gather(out_buf, dest, order, inv_order, s_safe, buf_valid),
            (dest, order, inv_order, s_safe, buf_valid))


def _combine_bwd(res, d_slot):
    dest, order, inv_order, s_safe, buf_valid = res
    d_sorted = jnp.take_along_axis(d_slot, order[..., None], axis=1)
    d_out_buf = jnp.take_along_axis(d_sorted, s_safe[..., None], axis=1)
    d_out_buf = d_out_buf * buf_valid[..., None].astype(d_slot.dtype)
    return (d_out_buf, _f0(dest), _f0(order), _f0(inv_order), _f0(s_safe),
            _f0(buf_valid))


_combine_gather.defvjp(_combine_fwd, _combine_bwd)


def init_moe_layer(cfg: LMConfig, key):
    dt = jnp.dtype(cfg.dtype)
    d, L, m = cfg.d_model, cfg.n_layers, cfg.moe
    ks = jax.random.split(key, 7)

    def stack(f, k):
        return jax.vmap(f)(jax.random.split(k, L))

    params = {
        "router": stack(lambda k: dense_init(k, d, m.n_experts, jnp.float32), ks[0]),
        "e_gate": stack(lambda k: dense_init(k, m.n_experts * d, m.d_ff_expert, dt)
                        .reshape(m.n_experts, d, m.d_ff_expert), ks[1]),
        "e_up": stack(lambda k: dense_init(k, m.n_experts * d, m.d_ff_expert, dt)
                      .reshape(m.n_experts, d, m.d_ff_expert), ks[2]),
        "e_down": stack(lambda k: dense_init(k, m.n_experts * m.d_ff_expert, d, dt)
                        .reshape(m.n_experts, m.d_ff_expert, d), ks[3]),
    }
    if m.n_shared:
        ff_sh = m.d_ff_expert * m.n_shared
        params.update(
            {
                "sh_gate": stack(lambda k: dense_init(k, d, ff_sh, dt), ks[4]),
                "sh_up": stack(lambda k: dense_init(k, d, ff_sh, dt), ks[5]),
                "sh_down": stack(lambda k: dense_init(k, ff_sh, d, dt), ks[6]),
            }
        )
    return params


def moe_layer_specs(cfg: LMConfig, fsdp_axis: str = "data", tp_axis: str = "model"):
    m = cfg.moe
    specs = {
        "router": P(None, None, None),
        # E over TP/EP axis; d over FSDP axis (all-gathered at use)
        "e_gate": P(None, tp_axis, fsdp_axis, None),
        "e_up": P(None, tp_axis, fsdp_axis, None),
        "e_down": P(None, tp_axis, None, fsdp_axis),
    }
    if m.n_shared:
        specs.update(
            {
                "sh_gate": P(None, fsdp_axis, tp_axis),
                "sh_up": P(None, fsdp_axis, tp_axis),
                "sh_down": P(None, tp_axis, fsdp_axis),
            }
        )
    return specs


def _capacity(n_tokens: int, cfg: LMConfig) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def _group_count(batch: int) -> int:
    """Dispatch groups = number of DP shards (GShard 'groups'), so each
    group's capacity slice is LOCAL to its data shard (zero-copy dispatch:
    activations are already replicated over the EP axis by TP)."""
    from repro.sharding.api import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return 1
    g = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            g *= mesh.shape[a]
    while batch % g:  # smoke meshes may not divide tiny batches
        g //= 2
    return max(g, 1)


def _routing_plan(idx, E: int, C: int):
    """Batched (over groups) sort-based routing plan, GATHER-only.

    idx: (G, Ng, K) expert assignments.  Returns int32 index arrays:
      src      (G, E*C)  source slot in the (Ng*K) flat assignment order
                         for each buffer slot (clipped; see buf_valid)
      buf_valid(G, E*C)  buffer slot actually filled
      dest     (G, Ng*K) buffer slot for each sorted assignment (or E*C)
      order    (G, Ng*K) argsort of assignments, inv_order its inverse
    TPU note: everything is argsort/searchsorted/take_along_axis - no
    scatter anywhere (scatters defeat GSPMD batch-sharding and lower badly
    on TPU; the previous scatter-based dispatch replicated (G, NgK, d)
    tensors per device - EXPERIMENTS.md SSPerf).
    """
    G, Ng, K = idx.shape
    NK = Ng * K
    flat_e = idx.reshape(G, NK)
    order = jnp.argsort(flat_e, axis=1)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    # start offset of each expert's run inside the sorted assignments
    start_e = jax.vmap(lambda se: jnp.searchsorted(se, jnp.arange(E), side="left"))(
        sorted_e
    )  # (G, E)
    rank = jnp.arange(NK)[None, :] - jnp.take_along_axis(start_e, sorted_e, axis=1)
    keep = rank < C
    dest = jnp.where(keep, sorted_e * C + rank, E * C)

    # buffer-slot -> sorted-slot source index
    s = start_e[:, :, None] + jnp.arange(C)[None, None, :]  # (G, E, C)
    s = s.reshape(G, E * C)
    s_safe = jnp.clip(s, 0, NK - 1)
    buf_valid = (s < NK) & (
        jnp.take_along_axis(sorted_e, s_safe, axis=1)
        == (jnp.arange(E * C)[None, :] // C)
    )
    src_sorted = jnp.take_along_axis(order, s_safe, axis=1)  # flat slot ids
    src = src_sorted // K  # token ids (G, E*C)
    inv_order = jnp.argsort(order, axis=1)
    return {"src": src, "buf_valid": buf_valid, "dest": dest, "order": order,
            "inv_order": inv_order, "s_safe": s_safe}


def moe_ffn(h, lp, cfg: LMConfig):
    """h: (B, T, d) -> (B, T, d), aux load-balance loss (scalar f32).

    On-mesh: explicit expert-parallel shard_map (``_moe_ffn_ep``) - LOCAL
    dispatch (activations are already replicated over the EP axis by TP, so
    each expert shard gathers its own slots with zero communication),
    local expert matmuls, and ONE (N_loc, d) partial-combine psum over the
    EP axis.  This replaced a GSPMD-auto path whose gather/scatter
    lowering all-reduced full (G, Ng*K, d) buffers four times per layer -
    the kimi-k2 train_4k dominant term (EXPERIMENTS.md SSPerf A3).

    Off-mesh (smoke tests / references): the batched gather-only path
    below - same math, G = 1 group.
    """
    from repro.sharding.api import current_mesh

    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and cfg.moe.n_experts % mesh.shape["model"] == 0:
        return _moe_ffn_ep(h, lp, cfg, mesh)
    return _moe_ffn_gather(h, lp, cfg)


def _moe_ffn_ep(h, lp, cfg: LMConfig, mesh):
    """Expert-parallel MoE under shard_map (see moe_ffn docstring)."""

    m = cfg.moe
    B, T, d = h.shape
    E, K = m.n_experts, m.top_k
    tp = mesh.shape["model"]
    E_loc = E // tp
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None
    dp_size = 1
    for a in (dp or ()):
        dp_size *= mesh.shape[a]
    if not dp or B % dp_size != 0:
        dp, dp_size = None, 1  # tiny/indivisible batch: replicate over DP
    B_loc = B // dp_size
    N_loc = B_loc * T
    C = _capacity(N_loc, cfg)
    # FSDP weight-gather axes = ALL data-parallel axes (matches param specs)
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None

    def local(x, router, e_gate, e_up, e_down, *shared):
        # x: (B_loc, T, d) - replicated over the EP ("model") axis by TP
        tokens = x.reshape(N_loc, d)
        logits = tokens.astype(jnp.float32) @ router  # (N_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        assign = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        aux = E * jnp.sum((assign / (N_loc * K)) * jnp.mean(probs, axis=0))

        plan = _routing_plan(idx[None], E, C)  # G=1 leading axis
        shard = jax.lax.axis_index("model")
        lo = shard * (E_loc * C)

        # ---- LOCAL dispatch: slice this shard's expert slots ----
        src = jax.lax.dynamic_slice_in_dim(plan["src"][0], lo, E_loc * C)
        valid = jax.lax.dynamic_slice_in_dim(plan["buf_valid"][0], lo, E_loc * C)
        buf = tokens[src] * valid[:, None].astype(x.dtype)  # (E_loc*C, d)
        buf = buf.reshape(E_loc, C, d)

        # ---- ZeRO-3 weight gather over the FSDP axis + local matmuls ----
        if fsdp:
            e_gate = jax.lax.all_gather(e_gate, fsdp, axis=1, tiled=True)
            e_up = jax.lax.all_gather(e_up, fsdp, axis=1, tiled=True)
            e_down = jax.lax.all_gather(e_down, fsdp, axis=2, tiled=True)
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e_gate)) * jnp.einsum(
            "ecd,edf->ecf", buf, e_up)
        out_buf = jnp.einsum("ecf,efd->ecd", act, e_down).reshape(E_loc * C, d)

        # ---- partial combine: only assignments routed to LOCAL experts ----
        dest = plan["dest"][0]  # (N_loc*K,) global buffer slots (or E*C)
        rel = dest - lo
        mine = (rel >= 0) & (rel < E_loc * C)
        picked = out_buf[jnp.clip(rel, 0, E_loc * C - 1)]
        picked = picked * mine[:, None].astype(x.dtype)
        slot = picked[plan["inv_order"][0]]  # unsort to (N_loc*K, d)
        partial = jnp.sum(
            slot.reshape(N_loc, K, d) * gate_vals[..., None].astype(x.dtype),
            axis=1)

        # ---- shared experts: ff sharded over EP axis -> partial too ----
        if shared:
            sh_gate, sh_up, sh_down = shared
            if fsdp:
                sh_gate = jax.lax.all_gather(sh_gate, fsdp, axis=0, tiled=True)
                sh_up = jax.lax.all_gather(sh_up, fsdp, axis=0, tiled=True)
                sh_down = jax.lax.all_gather(sh_down, fsdp, axis=1, tiled=True)
            partial = partial + (
                jax.nn.silu(tokens @ sh_gate) * (tokens @ sh_up)) @ sh_down

        out = jax.lax.psum(partial, "model")  # ONE (N_loc, d) combine
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out.reshape(B_loc, T, d), aux

    in_specs = [
        P(dp, None, None),  # x
        P(None, None),  # router (replicated)
        P("model", fsdp, None),  # e_gate (E, d, ff)
        P("model", fsdp, None),  # e_up
        P("model", None, fsdp),  # e_down (E, ff, d)
    ]
    args = [h, lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"]]
    if m.n_shared:
        in_specs += [P(fsdp, "model"), P(fsdp, "model"), P("model", fsdp)]
        args += [lp["sh_gate"], lp["sh_up"], lp["sh_down"]]

    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(*args)
    return out, aux.astype(jnp.float32)


def _moe_ffn_gather(h, lp, cfg: LMConfig):
    m = cfg.moe
    B, T, d = h.shape
    N = B * T
    E, K = m.n_experts, m.top_k
    G = _group_count(B)
    Ng = N // G
    C = _capacity(Ng, cfg)
    tokens = h.reshape(G, Ng, d)

    # ---- routing (f32 for stable softmax) ----
    logits = tokens.astype(jnp.float32) @ lp["router"]  # (G, Ng, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)  # (G, Ng, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux loss (Switch-style load balance over assignments) ----
    assign_frac = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (N * K)
    prob_frac = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(assign_frac * prob_frac)

    # ---- grouped sort-based routing plan (gather-only fwd AND bwd) ----
    plan = _routing_plan(idx, E, C)
    bt = batch_axes() or None

    # dispatch: one batched gather tokens -> (G, E, C, d)
    buf = _dispatch_gather(tokens, plan["src"], plan["buf_valid"], plan["dest"])
    buf = constrain(buf.reshape(G, E, C, d), P(bt, "model", None, None))

    # ---- expert computation (batched per expert, per group) ----
    act = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, lp["e_gate"])) * jnp.einsum(
        "gecd,edf->gecf", buf, lp["e_up"]
    )
    out_buf = jnp.einsum("gecf,efd->gecd", act, lp["e_down"])
    out_buf = constrain(out_buf, P(bt, "model", None, None))
    out_buf = out_buf.reshape(G, E * C, d)

    # ---- combine: batched gathers back to (Ng, K) slots ----
    slot = _combine_gather(out_buf, plan["dest"], plan["order"],
                           plan["inv_order"], plan["s_safe"], plan["buf_valid"])
    slot = constrain(slot, P(bt, None, None))  # (G, Ng*K, d)
    out = jnp.sum(
        slot.reshape(G, Ng, K, d) * gate_vals[..., None].astype(h.dtype), axis=2
    )

    # ---- shared experts (dense) ----
    if m.n_shared:
        sh = jax.nn.silu(tokens @ lp["sh_gate"]) * (tokens @ lp["sh_up"])
        out = out + sh @ lp["sh_down"]

    out = constrain(out.reshape(B, T, d), P(bt, None, None))
    return out, aux.astype(jnp.float32)
