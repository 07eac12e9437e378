"""Decoder-only transformer LM (dense + MoE) with scan-over-layers.

Parameters are stored STACKED over layers (leading L axis) and the forward
pass is a single `lax.scan` - one layer's HLO regardless of depth, which
keeps 60-layer dry-run compiles tractable and gives GSPMD a uniform
per-layer collective schedule.

Sharding (DESIGN.md SS5, "2D FSDP + TP"):
  weights  (L, D_in, D_out) -> P(None, "data", "model")
      in-dim sharded over the FSDP axis (all-gathered per scan step =
      ZeRO-3), out-dim over the TP axis.
  embeddings (V, D)         -> P("model", None)  (vocab-sharded logits/xent)
  activations (B, T, D)     -> P(("pod","data"), None, None)

Layer heterogeneity (gemma3's 5:1 local:global pattern) stays inside the
uniform scan: each layer carries a scalar `is_local` flag; both the sliding
-window and the full mask predicates are evaluated blockwise, and the flag
selects per tile - no per-layer HLO specialisation needed.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig
from .layers import (
    apply_rope,
    blockwise_attention,
    decode_attention_local,
    dense_init,
    lse_combine,
    rms_norm,
    swiglu,
)
from .moe import init_moe_layer, moe_layer_specs, moe_ffn


def _dt(cfg: LMConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: LMConfig, key) -> Dict[str, Any]:
    dt = _dt(cfg)
    keys = jax.random.split(key, 12)
    d, L = cfg.d_model, cfg.n_layers
    hq = cfg.n_heads_padded * cfg.d_head  # TP-divisibility padding (SSPerf B2)
    hkv = cfg.n_kv_heads * cfg.d_head

    def stack(f, k):
        return jax.vmap(lambda kk: f(kk))(jax.random.split(k, L))

    layer = {
        "ln_attn": jnp.ones((L, d), dt),
        "ln_mlp": jnp.ones((L, d), dt),
        "wq": stack(lambda k: dense_init(k, d, hq, dt), keys[0]),
        "wk": stack(lambda k: dense_init(k, d, hkv, dt), keys[1]),
        "wv": stack(lambda k: dense_init(k, d, hkv, dt), keys[2]),
        "wo": stack(lambda k: dense_init(k, hq, d, dt), keys[3]),
    }
    if cfg.is_moe:
        layer.update(init_moe_layer(cfg, keys[4]))
    else:
        layer.update(
            {
                "w_gate": stack(lambda k: dense_init(k, d, cfg.d_ff, dt), keys[5]),
                "w_up": stack(lambda k: dense_init(k, d, cfg.d_ff, dt), keys[6]),
                "w_down": stack(lambda k: dense_init(k, cfg.d_ff, d, dt), keys[7]),
            }
        )
    params = {
        "embed": dense_init(keys[8], cfg.vocab_size, d, dt, scale=1.0),
        "ln_f": jnp.ones((d,), dt),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[9], d, cfg.vocab_size, dt)
    return params


def param_specs(cfg: LMConfig, fsdp_axis: str = "data", tp_axis: str = "model"):
    """PartitionSpec pytree matching init_params (DESIGN.md SS5).

    ``fsdp_axis=None`` gives TP-only sharding (serving mode: no per-layer
    weight all-gathers; only models whose bf16 params fit HBM x tp_size).
    """
    w2 = P(None, fsdp_axis, tp_axis)  # (L, d_in, d_out)
    layer = {
        "ln_attn": P(None, None),
        "ln_mlp": P(None, None),
        "wq": w2,
        "wk": w2,
        "wv": w2,
        "wo": P(None, tp_axis, fsdp_axis),  # out-proj: reduce over tp dim
    }
    if cfg.is_moe:
        layer.update(moe_layer_specs(cfg, fsdp_axis, tp_axis))
    else:
        layer.update({"w_gate": w2, "w_up": w2, "w_down": P(None, tp_axis, fsdp_axis)})
    specs = {
        "embed": P(tp_axis, fsdp_axis),  # vocab-sharded
        "ln_f": P(None),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(fsdp_axis, tp_axis)
    return specs


def _wo_masked(lp, cfg: LMConfig):
    """o-proj with hard-zeroed rows for padded heads: the padded model is
    EXACTLY the unpadded one (padded heads attend but contribute nothing) -
    only clean 16-way head sharding is gained (SSPerf B2)."""
    if cfg.n_heads_padded == cfg.n_heads:
        return lp["wo"]
    mask = (jnp.arange(cfg.n_heads_padded) < cfg.n_heads)
    mask = jnp.repeat(mask, cfg.d_head).astype(lp["wo"].dtype)
    return lp["wo"] * mask[:, None]


def layer_locality(cfg: LMConfig) -> jnp.ndarray:
    """(L,) bool: True = sliding-window (local) layer (gemma3 5:1 pattern)."""
    n_local, n_global = cfg.local_global
    period = max(n_local + n_global, 1)
    idx = jnp.arange(cfg.n_layers)
    return (idx % period) < n_local


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _attention_block(x, lp, cfg: LMConfig, positions, is_local, *, block_q, block_kv):
    B, T, d = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(B, T, cfg.n_heads_padded, cfg.d_head)
    k = (h @ lp["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    v = (h @ lp["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # both local & global predicates ride the same blockwise kernel; the
    # per-layer scalar picks the window (0 = unlimited)
    window = jnp.where(is_local, cfg.sliding_window, 0)
    out = blockwise_attention(
        q, k, v, causal=True, window=window, block_q=block_q, block_kv=block_kv
    )
    return x + out.reshape(B, T, -1) @ _wo_masked(lp, cfg)


def _ffn_block(x, lp, cfg: LMConfig):
    h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
    if cfg.is_moe:
        out, aux = moe_ffn(h, lp, cfg)
    else:
        out, aux = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), 0.0
    return x + out, aux


def forward_hidden(params, tokens, cfg: LMConfig, *, block_q: int = 512,
                   block_kv: int = 512):
    """tokens (B, T) -> final-norm hidden states (B, T, d), MoE aux sum."""
    B, T = tokens.shape
    x = params["embed"][tokens].astype(_dt(cfg))
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    locality = layer_locality(cfg)

    def layer_fn(x, inputs):
        lp, is_local = inputs
        x = _attention_block(x, lp, cfg, positions, is_local,
                             block_q=block_q, block_kv=block_kv)
        x, aux = _ffn_block(x, lp, cfg)
        return x, aux

    if cfg.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, auxes = jax.lax.scan(layer_fn, x, (params["layers"], locality))
    return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.sum(auxes)


def lm_head(params, cfg: LMConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, tokens, cfg: LMConfig, *, block_q: int = 512, block_kv: int = 512):
    """tokens (B, T) -> logits (B, T, V). Scan over stacked layers."""
    x, aux = forward_hidden(params, tokens, cfg, block_q=block_q, block_kv=block_kv)
    return x @ lm_head(params, cfg), aux


def prefill(params, tokens, cfg: LMConfig, *, max_len: int | None = None,
            block_q: int = 512, block_kv: int = 512):
    """Prefill: forward over the prompt, materialising the KV cache.

    Returns (last-position logits (B, V), cache).  The cache seq dim is
    padded to ``max_len`` (decode continues into the padding).
    """
    B, T = tokens.shape
    max_len = max_len or T
    x = params["embed"][tokens].astype(_dt(cfg))
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    locality = layer_locality(cfg)

    def layer_fn(x, inputs):
        lp, is_local = inputs
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, T, cfg.n_heads_padded, cfg.d_head)
        k = (h @ lp["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
        v = (h @ lp["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        window = jnp.where(is_local, cfg.sliding_window, 0)
        out = blockwise_attention(q, k, v, causal=True, window=window,
                                  block_q=block_q, block_kv=block_kv)
        x = x + out.reshape(B, T, -1) @ _wo_masked(lp, cfg)
        x, _ = _ffn_block(x, lp, cfg)
        return x, (k, v)

    if cfg.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, (ks, vs) = jax.lax.scan(layer_fn, x, (params["layers"], locality))
    pad = max_len - T
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "length": jnp.full((B,), T, jnp.int32),
    }
    x = rms_norm(x[:, -1], params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, cache


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None):
    dt = dtype or _dt(cfg)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "length": jnp.zeros((batch,), jnp.int32)}


def kv_cache_specs(seq_axes=("model",), batch_axes=("data",)):
    """KV cache sharded along SEQUENCE over ``seq_axes`` (flash-decoding
    combine restores exactness) and along batch over the DP axes.  batch=1
    cells pass batch_axes=() and widen seq_axes to ("data", "model")."""
    ba = tuple(batch_axes) or None
    kv = P(None, ba, tuple(seq_axes), None, None)
    return {"k": kv, "v": kv, "length": P(ba)}


def decode_step(params, cache, tokens, cfg: LMConfig, *, mesh=None,
                seq_axes=("model",), dp=None):
    """One decode step: tokens (B,) -> logits (B, V), updated cache.

    When ``mesh`` is given, attention runs sequence-parallel over
    ``seq_axes`` via shard_map with an exact LSE combine (DESIGN.md SS5);
    otherwise it runs locally (single host testing).  ``dp`` = axes sharding
    the batch dim (None => derive from mesh; pass () for batch=1 cells like
    long_500k, whose KV cache is instead sharded over ("data", "model")).
    """
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(_dt(cfg))[:, None, :]  # (B, 1, d)
    positions = cache["length"][:, None]  # (B, 1)
    locality = layer_locality(cfg)

    def layer_fn(x, inputs):
        lp, is_local, k_cache, v_cache = inputs
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, 1, cfg.n_heads_padded, cfg.d_head)
        k_new = (h @ lp["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.d_head)
        v_new = (h @ lp["wv"]).reshape(B, 1, cfg.n_kv_heads, cfg.d_head)
        q = apply_rope(q, positions, cfg.rope_theta)[:, 0]  # (B, Hq, dh)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)

        window = jnp.where(is_local, cfg.sliding_window, 0)
        if mesh is not None:
            # the KV append happens INSIDE the shard_map, local to each seq
            # shard - a global scatter at a traced index would make GSPMD
            # all-gather the whole cache (EXPERIMENTS.md SSPerf)
            out, kc, vc = _sp_decode_attention(
                q, k_cache, v_cache, cache["length"], k_new, v_new, window,
                mesh, seq_axes, dp)
        else:
            kc, vc = _append_kv(k_cache, v_cache, k_new, v_new, cache["length"])
            o, m, l = decode_attention_local(q, kc, vc, cache["length"] + 1,
                                             window=window)
            out = lse_combine([(o, m, l)])
        out = out.astype(x.dtype).reshape(B, 1, -1)
        x = x + out @ _wo_masked(lp, cfg)
        x, _ = _ffn_block(x, lp, cfg)
        return x, (kc, vc)

    x, (k_upd, v_upd) = jax.lax.scan(
        layer_fn, x, (params["layers"], locality, cache["k"], cache["v"])
    )
    cache = {"k": k_upd, "v": v_upd, "length": cache["length"] + 1}
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head)[:, 0], cache


def _append_kv(k_cache, v_cache, k_new, v_new, length):
    """Place the new token's kv at ``length`` (per batch row)."""
    B = k_new.shape[0]
    b_idx = jnp.arange(B)
    kc = k_cache.at[b_idx, length].set(k_new[:, 0])
    vc = v_cache.at[b_idx, length].set(v_new[:, 0])
    return kc, vc


def _sp_decode_attention(q, k_cache, v_cache, length, k_new, v_new, window,
                         mesh, seq_axes=("model",), dp=None):
    """Sequence-parallel flash-decoding over ``seq_axes`` with exact LSE
    combine (psum of shifted numerator/denominator).  Sliding windows mask
    by ABSOLUTE position (each shard knows its seq offset), so local layers
    stay exact across shards.  ``seq_axes`` may span multiple mesh axes
    (long_500k shards 512k positions over data x model); ``dp`` axes shard
    the batch dim (empty tuple for batch=1 cells)."""

    seq_axes = tuple(seq_axes)
    if dp is None:
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                   and a not in seq_axes)
    dp = tuple(dp) or None
    n_seq_shards = 1
    for a in seq_axes:
        n_seq_shards *= mesh.shape[a]
    S = k_cache.shape[1]
    S_local = S // n_seq_shards

    def local(q, kc, vc, length, k_new, v_new, window):
        shard = jnp.int32(0)
        for a in seq_axes:  # row-major linearization matching PartitionSpec
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        offset = shard * S_local
        # new token lands in the shard containing position ``length``
        in_shard = (length >= offset) & (length < offset + S_local)
        pos = jnp.clip(length - offset, 0, S_local - 1)
        b_idx = jnp.arange(q.shape[0])
        k_upd = jnp.where(in_shard[:, None, None], k_new[:, 0], kc[b_idx, pos])
        kc = kc.at[b_idx, pos].set(k_upd)
        v_upd = jnp.where(in_shard[:, None, None], v_new[:, 0], vc[b_idx, pos])
        vc = vc.at[b_idx, pos].set(v_upd)
        o, m, l = decode_attention_local(
            q, kc, vc, length + 1, window=window, pos_offset=offset
        )
        m_g = jax.lax.pmax(m, seq_axes)
        num = jax.lax.psum(o * jnp.exp(m - m_g)[..., None], seq_axes)
        den = jax.lax.psum(l * jnp.exp(m - m_g), seq_axes)
        return num / jnp.maximum(den[..., None], 1e-30), kc, vc

    spec_kv = P(dp, seq_axes, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None, None), spec_kv, spec_kv, P(dp),
                  P(dp, None, None, None), P(dp, None, None, None), P()),
        out_specs=(P(dp, None, None), spec_kv, spec_kv),
        check_vma=False,
    )(q, k_cache, v_cache, length, k_new, v_new, window)
