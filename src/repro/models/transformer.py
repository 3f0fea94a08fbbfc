"""Model assembly: init, forward (train/prefill), decode_step (serving) for
every assigned architecture family.

Parameter layout: nested dicts; repeated layers are STACKED along a leading
axis and executed with ``lax.scan`` (MaxText-style), which keeps HLO size and
compile time independent of depth — essential for the 88-layer dry-runs.
Attention projections are kept 3-D (d, heads, head_dim) so head dimensions
shard naturally over the model axis.

Families:
  dense   — pre-norm GQA + SwiGLU (llama/qwen/granite/tinyllama, internvl LM)
  moe     — GQA or MLA attention + routed experts (qwen3-moe, deepseek-v2)
  ssm     — Mamba-2 stack (mamba2-1.3b)
  hybrid  — Mamba-2 + shared attention block every k layers (zamba2)
  encdec  — whisper: bidirectional encoder + causal decoder w/ cross-attn
  vlm     — dense LM whose first ``vision_patches`` positions take patch
            embeddings from the (stubbed) vision frontend
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .attention import (
    decode_attention,
    flash_attention,
    mla_decode_attention,
    mla_expand,
)
from .config import ModelConfig
from .layers import KeyGen, apply_rope, dense_init, embed_init, rms_norm, sinusoidal_positions, swiglu
from .moe import moe_ffn
from .ssm import mamba2_decode, mamba2_forward


# =============================== init =========================================
def _init_attn(kg, cfg: ModelConfig, dt):
    d, Hq, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.hdim
    p = {
        "wq": dense_init(kg(), (d, Hq, Dh), dt),
        "wk": dense_init(kg(), (d, Hkv, Dh), dt),
        "wv": dense_init(kg(), (d, Hkv, Dh), dt),
        "wo": dense_init(kg(), (Hq, Dh, d), dt, scale=1.0 / np.sqrt(Hq * Dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq, Dh), dt)
        p["bk"] = jnp.zeros((Hkv, Dh), dt)
        p["bv"] = jnp.zeros((Hkv, Dh), dt)
    return p


def _init_mla(kg, cfg: ModelConfig, dt):
    d, H = cfg.d_model, cfg.num_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "w_q": dense_init(kg(), (d, H, dn + dr), dt),
        "w_dkv": dense_init(kg(), (d, r + dr), dt),
        "w_uk": dense_init(kg(), (r, H, dn), dt),
        "w_uv": dense_init(kg(), (r, H, dv), dt),
        "wo": dense_init(kg(), (H, dv, d), dt, scale=1.0 / np.sqrt(H * dv)),
    }


def _init_mlp(kg, cfg: ModelConfig, dt, ff=None):
    d = cfg.d_model
    ff = ff or cfg.d_ff
    return {
        "w_gate": dense_init(kg(), (d, ff), dt),
        "w_up": dense_init(kg(), (d, ff), dt),
        "w_down": dense_init(kg(), (ff, d), dt, scale=1.0 / np.sqrt(ff)),
    }


def _init_moe(kg, cfg: ModelConfig, dt):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    p = {
        "router": dense_init(kg(), (d, E), jnp.float32),
        "experts": {
            "w_gate": dense_init(kg(), (E, d, f), dt),
            "w_up": dense_init(kg(), (E, d, f), dt),
            "w_down": dense_init(kg(), (E, f, d), dt, scale=1.0 / np.sqrt(f)),
        },
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(kg(), (d, fs), dt),
            "w_up": dense_init(kg(), (d, fs), dt),
            "w_down": dense_init(kg(), (fs, d), dt, scale=1.0 / np.sqrt(fs)),
        }
    return p


def _init_mamba(kg, cfg: ModelConfig, dt):
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * N
    return {
        "in_proj": dense_init(kg(), (d, 2 * di + 2 * N + H), dt),
        "conv_w": dense_init(kg(), (cfg.ssm_conv, conv_ch), dt, scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((di,), dt),
        "out_proj": dense_init(kg(), (di, d), dt),
    }


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    kg = KeyGen(key)
    dt = cfg.jdtype
    d = cfg.d_model
    params: Dict[str, Any] = {
        "embed": embed_init(kg(), (cfg.vocab_size, d), dt),
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kg(), (d, cfg.vocab_size), dt)

    def dense_block():
        return {
            "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
            "attn": _init_attn(kg, cfg, dt), "mlp": _init_mlp(kg, cfg, dt),
        }

    if cfg.family in ("dense", "vlm"):
        params["blocks"] = _stack([dense_block() for _ in range(cfg.num_layers)])
    elif cfg.family == "moe":
        # Uniform stacked blocks so a single lax.scan covers mixed layers:
        # every layer carries MoE params; when first_dense_layers > 0 every
        # layer also carries a dense MLP and `is_dense` selects per layer
        # (the dense dup costs one small MLP per MoE layer — dwarfed by the
        # expert stack — and keeps the scan pytree uniform).
        nd = cfg.first_dense_layers
        blocks = []
        for li in range(cfg.num_layers):
            b = {
                "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
                "attn": _init_mla(kg, cfg, dt) if cfg.mla else _init_attn(kg, cfg, dt),
                "moe": _init_moe(kg, cfg, dt),
            }
            if nd:
                b["mlp"] = _init_mlp(kg, cfg, dt, ff=cfg.dense_d_ff or cfg.d_ff)
            blocks.append(b)
        params["blocks"] = _stack(blocks)
    elif cfg.family == "ssm":
        params["blocks"] = _stack([
            {"ln": jnp.ones((d,), dt), "mamba": _init_mamba(kg, cfg, dt)}
            for _ in range(cfg.num_layers)
        ])
    elif cfg.family == "hybrid":
        params["blocks"] = _stack([
            {"ln": jnp.ones((d,), dt), "mamba": _init_mamba(kg, cfg, dt)}
            for _ in range(cfg.num_layers)
        ])
        params["shared_block"] = dense_block()
    elif cfg.family == "encdec":
        params["enc_blocks"] = _stack([dense_block() for _ in range(cfg.enc_layers)])
        dec = []
        for _ in range(cfg.num_layers):
            b = dense_block()
            b["ln_x"] = jnp.ones((d,), dt)
            b["xattn"] = _init_attn(kg, cfg, dt)
            dec.append(b)
        params["blocks"] = _stack(dec)
        params["enc_norm"] = jnp.ones((d,), dt)
    else:
        raise ValueError(cfg.family)
    return params


# =============================== forward ======================================
def _attn_sublayer(blk, h, cfg: ModelConfig, *, causal: bool, pos_offset: int = 0,
                   use_rope: bool = True, kv_override=None, mesh=None):
    """Standard GQA attention over a full sequence.

    When the head count does not divide the model axis (qwen2.5's 40 heads
    on a 16-way axis), the partitioner would REPLICATE the whole attention
    computation over `model` (16x redundant flops + a full-size score
    buffer).  Fallback: sequence-parallel attention — shard q's sequence dim
    over `model` (KV replicated there), compute 1/16 of the rows per shard,
    then return to the batch-sharded layout for the residual add."""
    B, S, d = h.shape
    a = blk["attn"]
    x = rms_norm(h, blk["ln1"], cfg.rms_eps)
    seq_par = (mesh is not None and "model" in mesh.axis_names
               and cfg.num_heads % mesh.shape["model"] != 0
               and S % mesh.shape["model"] == 0)
    if seq_par:
        bs = _bspec(mesh, B)
        x = _constrain(x, mesh, P(bs, "model", None))
    q = jnp.einsum("bsd,dhk->bshk", x, a["wq"])
    kv_src = kv_override if kv_override is not None else x
    k = jnp.einsum("bsd,dhk->bshk", kv_src, a["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, a["wv"])
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if use_rope:
        qpos = pos_offset + jnp.arange(S)
        kpos = jnp.arange(k.shape[1])
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, kpos, cfg.rope_theta)
    if seq_par:
        bs = _bspec(mesh, B)
        q = _constrain(q, mesh, P(bs, "model", None, None))
        k = _constrain(k, mesh, P(bs, None, None, None))  # replicated on model
        v = _constrain(v, mesh, P(bs, None, None, None))
    o = flash_attention(q, k, v, causal=causal, q_offset=pos_offset)
    out = jnp.einsum("bshk,hkd->bsd", o, a["wo"])
    if seq_par:
        out = _constrain(out, mesh, P(_bspec(mesh, B), None, None))
    return h + out


def _mla_sublayer(blk, h, cfg: ModelConfig):
    B, S, d = h.shape
    a = blk["attn"]
    x = rms_norm(h, blk["ln1"], cfg.rms_eps)
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, a["w_q"])          # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ckv_kr = x @ a["w_dkv"]                                # (B,S,r+dr)
    c_kv, k_rope = ckv_kr[..., :cfg.kv_lora_rank], ckv_kr[..., cfg.kv_lora_rank:]
    pos = jnp.arange(S)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta)  # (B,S,1,dr)
    k_nope, v = mla_expand(a, c_kv, cfg)                  # (B,S,H,dn),(B,S,H,dv)
    H = cfg.num_heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = flash_attention(q_full, k, v, causal=True, scale=(dn + dr) ** -0.5)
    return h + jnp.einsum("bshk,hkd->bsd", o, a["wo"]), (c_kv, k_rope[:, :, 0, :])


def _mlp_sublayer(blk, h, cfg: ModelConfig, key="mlp", ln="ln2"):
    x = rms_norm(h, blk[ln], cfg.rms_eps)
    m = blk[key]
    return h + swiglu(x, m["w_gate"], m["w_up"], m["w_down"])


def _moe_sublayer(blk, h, cfg: ModelConfig, mesh):
    x = rms_norm(h, blk["ln2"], cfg.rms_eps)
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        batch_axes = tuple(a for a in mesh.axis_names if a != "model")
        fn = functools.partial(
            moe_ffn, cfg=cfg, axis="model", axis_size=mesh.shape["model"])
        param_specs = {
            "router": P(None, None),
            "experts": {
                "w_gate": P("model", None, None),
                "w_up": P("model", None, None),
                "w_down": P("model", None, None),
            },
        }
        if cfg.num_shared_experts:
            param_specs["shared"] = {
                "w_gate": P(None, None), "w_up": P(None, None),
                "w_down": P(None, None),
            }
        out = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(param_specs, P(batch_axes, None, None)),
            out_specs=P(batch_axes, None, None),
            check_vma=False,
        )(blk["moe"], x)
    else:
        out = moe_ffn(blk["moe"], x, cfg)
    return h + out


def _shared_attn_block(shared, h, cfg: ModelConfig):
    h = _attn_sublayer(shared, h, cfg, causal=True)
    h = _mlp_sublayer(shared, h, cfg)
    return h


def _maybe_ckpt(fn, remat: bool):
    return jax.checkpoint(fn) if remat else fn


def _bspec(mesh, batch: int):
    """Batch-axis names if they divide the batch, else None."""
    if mesh is None:
        return None
    ba = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if not ba:
        return None
    import numpy as _np
    nb = int(_np.prod([mesh.shape[a] for a in ba]))
    return ba if batch % nb == 0 else None


def _constrain(x, mesh, spec: P):
    """Activation sharding constraint — without these the partitioner is free
    to replicate the batch dim whenever an FSDP-sharded weight contraction
    competes for the data axis (it does, and it costs ~5x memory)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def forward(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,                  # (B, S) int32 (decoder tokens)
    *,
    patches: Optional[jax.Array] = None,      # vlm: (B, n_patch, d)
    enc_inputs: Optional[jax.Array] = None,   # encdec: (B, S_enc, d) frame embeds
    mesh=None,
    remat: bool = False,
) -> jax.Array:
    """Full-sequence forward; returns logits (B, S, vocab)."""
    dt = cfg.jdtype
    bs = _bspec(mesh, tokens.shape[0])
    act_spec = P(bs, None, None)
    h = params["embed"][tokens]
    h = _constrain(h, mesh, act_spec)
    if cfg.family == "vlm" and patches is not None:
        npatch = patches.shape[1]
        h = jnp.concatenate([patches.astype(h.dtype), h[:, npatch:]], axis=1)
    if cfg.encdec:
        h = h + sinusoidal_positions(h.shape[1], cfg.d_model).astype(h.dtype)
    _c = lambda x: _constrain(x, mesh, act_spec)

    if cfg.family in ("dense", "vlm"):
        def body(carry, blk):
            x = _attn_sublayer(blk, carry, cfg, causal=True, mesh=mesh)
            x = _mlp_sublayer(blk, x, cfg)
            return _c(x), None
        h, _ = lax.scan(_maybe_ckpt(body, remat), h, params["blocks"])

    elif cfg.family == "moe":
        has_dense = bool(cfg.first_dense_layers)

        def moe_body(carry, xs):
            blk, is_dense = xs
            if cfg.mla:
                x, _ = _mla_sublayer(blk, carry, cfg)
            else:
                x = _attn_sublayer(blk, carry, cfg, causal=True, mesh=mesh)
            if has_dense:
                x = lax.cond(
                    is_dense > 0,
                    lambda hh: _mlp_sublayer(blk, hh, cfg),
                    lambda hh: _moe_sublayer(blk, hh, cfg, mesh),
                    x,
                )
            else:
                x = _moe_sublayer(blk, x, cfg, mesh)
            return _c(x), None
        is_dense = (jnp.arange(cfg.num_layers) < cfg.first_dense_layers).astype(jnp.int32)
        h, _ = lax.scan(_maybe_ckpt(moe_body, remat), h,
                        (params["blocks"], is_dense))

    elif cfg.family == "ssm":
        def body(carry, blk):
            x = rms_norm(carry, blk["ln"], cfg.rms_eps)
            y, _ = mamba2_forward(blk["mamba"], x, cfg)
            return _c(carry + y), None
        h, _ = lax.scan(_maybe_ckpt(body, remat), h, params["blocks"])

    elif cfg.family == "hybrid":
        shared = params["shared_block"]
        every = cfg.shared_attn_every

        def body(carry, xs):
            idx, blk = xs
            h_in = carry
            x = rms_norm(h_in, blk["ln"], cfg.rms_eps)
            y, _ = mamba2_forward(blk["mamba"], x, cfg)
            h_out = h_in + y
            h_out = lax.cond(
                (idx % every) == (every - 1),
                lambda hh: _shared_attn_block(shared, hh, cfg),
                lambda hh: hh,
                h_out,
            )
            return _c(h_out), None
        idxs = jnp.arange(cfg.num_layers)
        h, _ = lax.scan(_maybe_ckpt(body, remat), h, (idxs, params["blocks"]))

    elif cfg.family == "encdec":
        enc = enc_inputs.astype(dt)
        enc = enc + sinusoidal_positions(enc.shape[1], cfg.d_model).astype(dt)

        def enc_body(carry, blk):
            x = _attn_sublayer(blk, carry, cfg, causal=False, use_rope=False,
                               mesh=mesh)
            x = _mlp_sublayer(blk, x, cfg)
            return _c(x), None
        enc, _ = lax.scan(_maybe_ckpt(enc_body, remat), enc, params["enc_blocks"])
        enc = rms_norm(enc, params["enc_norm"], cfg.rms_eps)

        def dec_body(carry, blk):
            x = _attn_sublayer(blk, carry, cfg, causal=True, use_rope=False,
                               mesh=mesh)
            # cross-attention (queries from x, kv from encoder output)
            a = blk["xattn"]
            xx = rms_norm(x, blk["ln_x"], cfg.rms_eps)
            q = jnp.einsum("bsd,dhk->bshk", xx, a["wq"])
            k = jnp.einsum("bsd,dhk->bshk", enc, a["wk"])
            v = jnp.einsum("bsd,dhk->bshk", enc, a["wv"])
            o = flash_attention(q, k, v, causal=False)
            x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
            x = _mlp_sublayer(blk, x, cfg)
            return _c(x), None
        h, _ = lax.scan(_maybe_ckpt(dec_body, remat), h, params["blocks"])
    else:
        raise ValueError(cfg.family)

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    vshard = ("model" if mesh is not None and "model" in mesh.axis_names
              and cfg.vocab_size % mesh.shape["model"] == 0 else None)
    return _constrain(logits, mesh, P(bs, None, vshard))


def loss_fn(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    labels: jax.Array,
    *,
    patches=None,
    enc_inputs=None,
    mesh=None,
    remat: bool = True,
) -> jax.Array:
    logits = forward(params, cfg, tokens, patches=patches, enc_inputs=enc_inputs,
                     mesh=mesh, remat=remat)
    logits = logits.astype(jnp.float32)
    # Partitioner-friendly NLL: the vocab dim is sharded over `model`, and a
    # take_along_axis gather there would all-gather the full (B,S,V) logits.
    # logsumexp + masked-sum both reduce over the sharded dim (lowered to
    # per-shard partials + psum), so nothing is ever gathered.
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    nll = lse - picked
    zloss = 1e-4 * jnp.square(lse)  # PaLM-style stabiliser
    return jnp.mean(nll + zloss)


# =============================== decode =======================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0, dtype=None) -> Dict:
    """Allocate the serving cache for one model."""
    dt = dtype or cfg.jdtype
    L, Hkv, Dh = cfg.num_layers, cfg.kv_heads, cfg.hdim
    cache: Dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "vlm"):
        cache["k"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
        cache["v"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
    elif cfg.family == "moe":
        nm = cfg.num_layers - cfg.first_dense_layers
        if cfg.mla:
            cache["ckv"] = jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dt)
            cache["kr"] = jnp.zeros((L, batch, max_len, cfg.qk_rope_dim), dt)
        else:
            cache["k"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
            cache["v"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
    elif cfg.family == "ssm":
        cache["ssm"] = jnp.zeros(
            (L, batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32)
        cache["conv"] = jnp.zeros(
            (L, batch, cfg.ssm_conv - 1, cfg.d_inner + 2 * cfg.ssm_state), dt)
    elif cfg.family == "hybrid":
        cache["ssm"] = jnp.zeros(
            (L, batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32)
        cache["conv"] = jnp.zeros(
            (L, batch, cfg.ssm_conv - 1, cfg.d_inner + 2 * cfg.ssm_state), dt)
        sites = cfg.num_layers // cfg.shared_attn_every
        cache["sk"] = jnp.zeros((sites, batch, max_len, Hkv, Dh), dt)
        cache["sv"] = jnp.zeros((sites, batch, max_len, Hkv, Dh), dt)
    elif cfg.family == "encdec":
        cache["k"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
        cache["v"] = jnp.zeros((L, batch, max_len, Hkv, Dh), dt)
        cache["enc_k"] = jnp.zeros((L, batch, enc_len, Hkv, Dh), dt)
        cache["enc_v"] = jnp.zeros((L, batch, enc_len, Hkv, Dh), dt)
    return cache


def _decode_attn(blk, h, cfg, k_cache, v_cache, cur_len, use_rope=True):
    """One-token attention; returns (h', new_k_cache, new_v_cache)."""
    B = h.shape[0]
    a = blk["attn"]
    x = rms_norm(h, blk["ln1"], cfg.rms_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, a["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, a["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, a["wv"])
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if use_rope:
        posv = jnp.full((1,), 1, jnp.int32) * cur_len
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    k_cache = lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                       (0, cur_len, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                       (0, cur_len, 0, 0))
    o = decode_attention(q, k_cache, v_cache, cur_len + 1)
    return h + jnp.einsum("bshk,hkd->bsd", o, a["wo"]), k_cache, v_cache


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    cache: Dict,
    tokens: jax.Array,        # (B,) int32 — the new token per sequence
    *,
    mesh=None,
) -> Tuple[jax.Array, Dict]:
    """One serving step: consume one token, return logits and updated cache."""
    B = tokens.shape[0]
    cur = cache["len"]
    h = params["embed"][tokens][:, None, :]           # (B,1,d)
    if cfg.encdec:
        # positions are handled by sinusoidal add at embed time in forward;
        # decode uses the position slice at cur.
        pe = sinusoidal_positions(cache["k"].shape[2], cfg.d_model)
        h = h + lax.dynamic_slice(pe, (cur, 0), (1, cfg.d_model))[None].astype(h.dtype)

    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm"):
        def body(carry, xs):
            hh = carry
            blk, kc, vc = xs
            hh, kc, vc = _decode_attn(blk, hh, cfg, kc, vc, cur)
            hh = _mlp_sublayer(blk, hh, cfg)
            return hh, (kc, vc)
        h, (ks, vs) = lax.scan(body, h, (params["blocks"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = ks, vs

    elif cfg.family == "moe":
        has_dense = bool(cfg.first_dense_layers)
        dense_mask = (jnp.arange(cfg.num_layers) < cfg.first_dense_layers).astype(jnp.int32)

        def ffn_select(blk, is_dense, hh):
            if has_dense:
                return lax.cond(
                    is_dense > 0,
                    lambda x_: _mlp_sublayer(blk, x_, cfg),
                    lambda x_: _moe_sublayer(blk, x_, cfg, mesh),
                    hh,
                )
            return _moe_sublayer(blk, hh, cfg, mesh)

        if cfg.mla:
            def body(carry, xs):
                hh = carry
                blk, is_dense, ckv_c, kr_c = xs
                a = blk["attn"]
                x = rms_norm(hh, blk["ln1"], cfg.rms_eps)
                dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
                q = jnp.einsum("bsd,dhk->bshk", x, a["w_q"])
                q_nope, q_rope = q[..., :dn], q[..., dn:]
                ckv_kr = x @ a["w_dkv"]
                c_kv = ckv_kr[..., :cfg.kv_lora_rank]
                k_r = ckv_kr[..., cfg.kv_lora_rank:]
                posv = jnp.full((1,), 1, jnp.int32) * cur
                q_rope = apply_rope(q_rope, posv, cfg.rope_theta)
                k_r = apply_rope(k_r[:, :, None, :], posv, cfg.rope_theta)[:, :, 0, :]
                ckv_c = lax.dynamic_update_slice(
                    ckv_c, c_kv.astype(ckv_c.dtype), (0, cur, 0))
                kr_c = lax.dynamic_update_slice(
                    kr_c, k_r.astype(kr_c.dtype), (0, cur, 0))
                ctx = mla_decode_attention(a, q_nope, q_rope, ckv_c, kr_c, cur + 1, cfg)
                hh = hh + jnp.einsum("bshk,hkd->bsd", ctx, a["wo"])
                hh = ffn_select(blk, is_dense, hh)
                return hh, (ckv_c, kr_c)
            h, (cs, ks) = lax.scan(
                body, h,
                (params["blocks"], dense_mask, cache["ckv"], cache["kr"]))
            new_cache["ckv"], new_cache["kr"] = cs, ks
        else:
            def body(carry, xs):
                hh = carry
                blk, is_dense, kc, vc = xs
                hh, kc, vc = _decode_attn(blk, hh, cfg, kc, vc, cur)
                hh = ffn_select(blk, is_dense, hh)
                return hh, (kc, vc)
            h, (ks, vs) = lax.scan(
                body, h,
                (params["blocks"], dense_mask, cache["k"], cache["v"]))
            new_cache["k"], new_cache["v"] = ks, vs

    elif cfg.family == "ssm":
        def body(carry, xs):
            hh = carry
            blk, ss, cs = xs
            x = rms_norm(hh, blk["ln"], cfg.rms_eps)
            y, ss, cs = mamba2_decode(blk["mamba"], x[:, 0, :], cfg, ss, cs)
            return hh + y[:, None, :], (ss, cs)
        h, (ss, cs) = lax.scan(body, h, (params["blocks"], cache["ssm"], cache["conv"]))
        new_cache["ssm"], new_cache["conv"] = ss, cs

    elif cfg.family == "hybrid":
        shared = params["shared_block"]
        every = cfg.shared_attn_every

        def body(carry, xs):
            hh, sk, sv = carry
            idx, blk, ss, cs = xs
            x = rms_norm(hh, blk["ln"], cfg.rms_eps)
            y, ss, cs = mamba2_decode(blk["mamba"], x[:, 0, :], cfg, ss, cs)
            hh = hh + y[:, None, :]

            def apply_shared(args):
                hh_, sk_, sv_ = args
                site = idx // every
                kc = sk_[site]
                vc = sv_[site]
                hh_, kc, vc = _decode_attn(shared, hh_, cfg, kc, vc, cur)
                hh_ = _mlp_sublayer(shared, hh_, cfg)
                sk_ = lax.dynamic_update_index_in_dim(sk_, kc, site, 0)
                sv_ = lax.dynamic_update_index_in_dim(sv_, vc, site, 0)
                return hh_, sk_, sv_

            hh, sk, sv = lax.cond(
                (idx % every) == (every - 1), apply_shared, lambda a: a, (hh, sk, sv))
            return (hh, sk, sv), (ss, cs)

        idxs = jnp.arange(cfg.num_layers)
        (h, sk, sv), (ss, cs) = lax.scan(
            body, (h, cache["sk"], cache["sv"]),
            (idxs, params["blocks"], cache["ssm"], cache["conv"]))
        new_cache.update(ssm=ss, conv=cs, sk=sk, sv=sv)

    elif cfg.family == "encdec":
        def body(carry, xs):
            hh = carry
            blk, kc, vc, ekc, evc = xs
            hh, kc, vc = _decode_attn(blk, hh, cfg, kc, vc, cur, use_rope=False)
            a = blk["xattn"]
            xx = rms_norm(hh, blk["ln_x"], cfg.rms_eps)
            q = jnp.einsum("bsd,dhk->bshk", xx, a["wq"])
            enc_len = ekc.shape[1]
            o = decode_attention(q, ekc, evc, jnp.asarray(enc_len, jnp.int32))
            hh = hh + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
            hh = _mlp_sublayer(blk, hh, cfg)
            return hh, (kc, vc)
        h, (ks, vs) = lax.scan(
            body, h,
            (params["blocks"], cache["k"], cache["v"], cache["enc_k"], cache["enc_v"]))
        new_cache["k"], new_cache["v"] = ks, vs
    else:
        raise ValueError(cfg.family)

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)[:, 0, :]
    new_cache["len"] = cur + 1
    return logits, new_cache
