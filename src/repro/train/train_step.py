"""Train-step factories: loss functions + grad + optimizer, per family.

``make_train_step`` is what the launcher jits with in/out shardings; it
supports gradient accumulation (microbatch scan) and returns scalar metrics
only (loss, grad-norm, lr-free step counter lives in opt state).

LM loss: cross-entropy against vocab-sharded logits - the logsumexp
reduction over the sharded vocab axis becomes one all-reduce under GSPMD
(DESIGN.md SS5); computed in f32.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .optimizer import Optimizer, clip_by_global_norm


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def sharded_xent(hidden, head, labels, mesh, *, tp_axis: str = "model",
                 t_chunk: int = 512):
    """Cross-entropy with the LM head fused inside an explicit shard_map.

    Memory is DETERMINISTIC: per-device logits exist only as
    (B_local, t_chunk, V_local) f32 chunks (lax.map + checkpoint recompute
    in the backward), and the V-reductions are explicit pmax/psum over the
    TP axis.  This replaces a GSPMD-auto xent whose head-gradient strategy
    all-gathered (B, T, V) logits - a 427 GiB/device temp on the dry-run
    (EXPERIMENTS.md SSPerf, hypothesis P1).
    """
    from jax.sharding import PartitionSpec as P

    B, T, d = hidden.shape
    V = head.shape[1]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None
    tp_size = mesh.shape[tp_axis]
    V_local = V // tp_size
    tc = min(t_chunk, T)
    n_chunks = max(T // tc, 1)

    def local(x, head_l, labels_l):
        v_lo = jax.lax.axis_index(tp_axis) * V_local

        def chunk_nll(args):
            xc, lc = args  # (Bl, tc, d), (Bl, tc)
            logits = (xc @ head_l).astype(jnp.float32)  # (Bl, tc, V_local)
            # pmax has no AD rule; all_gather + max is equivalent and tiny
            m_parts = jax.lax.all_gather(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), tp_axis)
            m = jnp.max(m_parts, axis=0)
            se = jax.lax.psum(
                jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), tp_axis)
            lse = jnp.log(se) + m
            lrel = lc - v_lo
            pick = jnp.where(
                jnp.arange(V_local, dtype=jnp.int32)[None, None, :]
                == lrel[..., None], logits, 0.0)
            ll = jax.lax.psum(jnp.sum(pick, axis=-1), tp_axis)
            return jnp.sum(lse - ll)

        Bl = x.shape[0]
        xs = x.reshape(Bl, n_chunks, tc, d).transpose(1, 0, 2, 3)
        ls = labels_l.reshape(Bl, n_chunks, tc).transpose(1, 0, 2)
        per_chunk = jax.lax.map(jax.checkpoint(chunk_nll), (xs, ls))
        total = jnp.sum(per_chunk)
        if dp:
            total = jax.lax.psum(total, dp)
        return total

    total = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, tp_axis), P(dp, None)),
        out_specs=P(),
        check_vma=False,
    )(hidden, head, labels)
    return total / (B * T)


def lm_loss(params, batch, cfg, aux_weight: float = 0.01, **fwd_kw):
    """Next-token cross-entropy (+ MoE aux). batch: tokens/labels (B, T).

    On-mesh, the loss runs through ``sharded_xent`` (explicit shard_map);
    off-mesh (smoke tests) it uses the plain jnp path - same math.
    """
    from repro.models.transformer import forward, forward_hidden, lm_head
    from repro.sharding.api import current_mesh

    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        hidden, aux = forward_hidden(params, batch["tokens"], cfg, **fwd_kw)
        nll = sharded_xent(hidden, lm_head(params, cfg), batch["labels"], mesh)
    else:
        logits, aux = forward(params, batch["tokens"], cfg, **fwd_kw)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
        nll = jnp.mean(lse - ll)
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}


def gnn_loss(params, batch, cfg, **kw):
    from repro.models.gnn import loss_fn

    loss = loss_fn(params, batch, cfg, mask=batch.get("mask"), **kw)
    return loss, {"nll": loss}


def recsys_loss(params, batch, cfg, **kw):
    from repro.models.recsys import bce_loss, inbatch_softmax_loss

    if cfg.interaction == "dot":
        loss = inbatch_softmax_loss(params, batch, cfg)
    else:
        loss = bce_loss(params, batch, cfg)
    return loss, {"nll": loss}


# ---------------------------------------------------------------------------
# step factory
# ---------------------------------------------------------------------------


def make_train_step(loss_fn: Callable, optimizer: Optimizer, *,
                    grad_clip: float = 1.0, accum_steps: int = 1,
                    accum_dtype=jnp.float32):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    With ``accum_steps > 1`` the batch's leading axis is split into
    microbatches and gradients are averaged with a lax.scan (constant
    memory in the number of microbatches).  ``accum_dtype=bfloat16`` halves
    the per-microbatch gradient-sync wire bytes AND the accumulator memory
    for very large models (kimi-k2; EXPERIMENTS.md SSPerf A2) at a ~2-3 bit
    grad-precision cost (mitigated by loss pre-division by accum_steps).
    """

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, aux), grads = grad_fn(params, batch)
            return loss, aux, grads

        def micro(carry, mb):
            (loss, aux), grads = grad_fn(params, mb)
            acc_loss, acc_grads = carry
            return (acc_loss + loss / accum_steps,
                    jax.tree.map(
                        lambda a, g: a + (g / accum_steps).astype(accum_dtype),
                        acc_grads, grads)), aux

        split = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
            batch,
        )
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
        (loss, grads), auxes = jax.lax.scan(micro, (0.0, zeros), split)
        aux = jax.tree.map(lambda a: a[-1], auxes)
        return loss, aux, grads

    def step(params, opt_state, batch):
        loss, aux, grads = compute_grads(params, batch)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm, **aux}
        return params, opt_state, metrics

    return step
