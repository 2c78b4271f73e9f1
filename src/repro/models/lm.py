"""TransformerLM orchestrator: pattern-driven block groups under lax.scan.

A config's ``pattern`` is an ordered tuple of ``(block_name, count)`` groups.
Blocks within a group share one ``lax.scan`` over stacked params (MaxText
style — keeps HLO size and compile time independent of depth). Heterogeneous
stacks (gemma2 local/global alternation, zamba2 mamba+shared-attention units)
are expressed as composite block types so the scan body stays uniform.

All block ``apply`` fns return ``(h, aux)`` (aux = MoE load-balance loss
contribution); ``prefill``/``decode`` thread a cache pytree instead.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import attention as att
from . import mamba2 as m2
from . import xlstm as xl
from .common import LMConfig, dense_init, embed_init, rms_norm, rms_norm_init, softcap
from .common import is_paged_cache as common_is_paged
from .common import paged_gather as common_paged_gather
from .common import xbar_linear as common_xbar_linear
from .mlp import mlp_apply, mlp_init, moe_apply, moe_init


class BlockDef(NamedTuple):
    init: Callable
    apply: Callable  # (cfg, params, h, ctx) -> (h, aux)
    prefill: Callable  # (cfg, params, h, ctx) -> (h, cache)
    decode: Callable  # (cfg, params, h, cache, ctx) -> (h, cache)
    cache_spec: Callable  # (cfg, B, S, dtype) -> pytree of ShapeDtypeStruct
    # optional chunked-prefill continuation: (cfg, params, h, cache, ctx) ->
    # (h, cache), processing ctx["positions"] absolute positions against a
    # dense cache holding positions < ctx["start"]. None = block only
    # supports single-shot prefill (the serving engine falls back).
    cont: Callable | None = None


def _no_aux(f):
    def g(cfg, p, h, ctx):
        return f(cfg, p, h, ctx), jnp.zeros((), jnp.float32)

    return g


# ---------------------------- simple attn blocks ----------------------------


def _mk_attn_block(window_from_cfg: bool):
    def init(cfg, key):
        return att.block_init(cfg, key)

    def apply(cfg, p, h, ctx):
        w = cfg.window if window_from_cfg else None
        return att.block_apply(cfg, p, h, ctx["positions"], w)

    def prefill(cfg, p, h, ctx):
        w = cfg.window if window_from_cfg else None
        return att.block_prefill(cfg, p, h, ctx["positions"], w)

    def decode(cfg, p, h, cache, ctx):
        w = cfg.window if window_from_cfg else None
        return att.block_decode(cfg, p, h, cache, ctx["pos"], w)

    def cache_spec(cfg, b, s, dt):
        # a windowed layer only ever needs `window` KV slots
        s_eff = min(s, cfg.window) if (window_from_cfg and cfg.window) else s
        return att.attn_cache_spec(cfg, b, s_eff, dt)

    def cont(cfg, p, h, cache, ctx):
        w = cfg.window if window_from_cfg else None
        return att.block_cont(cfg, p, h, cache, ctx["positions"], ctx["start"], w)

    return BlockDef(init, _no_aux(apply), prefill, decode, cache_spec, cont)


_DENSE = _mk_attn_block(False)
_LOCAL = _mk_attn_block(True)


def _local_decode_pos(cfg, pos):
    """Ring-buffer position for a windowed cache."""
    return pos % cfg.window if cfg.window else pos


# local decode with bounded cache: override decode to write modulo window
def _local_decode(cfg, p, h, cache, ctx):
    pos = ctx["pos"]
    vec = jnp.ndim(pos) == 1
    paged = common_is_paged(cache)
    table = cache.get("table") if paged else None
    # emulate sliding window on a ring buffer: positions are stored modulo W
    if paged:
        W = table.shape[1] * cache["k"]["q"].shape[1]
    else:
        W = cache["k"]["q"].shape[1]
    write = pos % W
    x = rms_norm(p["attn"]["ln"], h, cfg.norm_eps)
    q, k_new, v_new = att._qkv(cfg, p["attn"], x, pos[..., None] if pos.ndim else pos.reshape(1))
    cdtype = cache["k"]["q"].dtype
    wpos = write if (table is None or vec) else jnp.full((h.shape[0],), write, jnp.int32)
    k = att._entry_write(cache["k"], att._cache_store(k_new, cdtype), wpos, table)
    v = att._entry_write(cache["v"], att._cache_store(v_new, cdtype), wpos, table)
    if paged:
        kd = jax.tree.map(lambda c: common_paged_gather(c, table), k)
        vd = jax.tree.map(lambda c: common_paged_gather(c, table), v)
        new_cache = {"table": table, "k": k, "v": v}
    else:
        kd, vd = k, v
        new_cache = {"k": k, "v": v}
    # ring slots with index > pos are empty early on
    slot = jnp.arange(W)
    if vec:
        posb = pos[:, None]
        age = posb - ((posb - slot[None, :]) % W)
        ok = (age >= 0) & (age > posb - cfg.window)
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None, None, None, :]
    else:
        age = pos - ((pos - slot) % W)  # absolute position stored in each slot
        ok = (age >= 0) & (age > pos - cfg.window)  # window mask, not ring size
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[None, :]
    o = att._sdpa(cfg, q, att._cache_load(kd, q.dtype), att._cache_load(vd, q.dtype), mask)
    o = common_xbar_linear(o.reshape(*o.shape[:2], -1), p["attn"]["wo"], h.dtype)
    if cfg.post_norm:
        o = rms_norm(p["attn"]["post_ln"], o, cfg.norm_eps)
    h = h + o
    return mlp_apply(cfg, p["mlp"], h), new_cache


_LOCAL = _LOCAL._replace(decode=_local_decode)


# ------------------------------ gemma2 pair ---------------------------------


def _pair_init(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"local": att.block_init(cfg, k1), "global": att.block_init(cfg, k2)}


def _pair_apply(cfg, p, h, ctx):
    h = att.block_apply(cfg, p["local"], h, ctx["positions"], cfg.window)
    return att.block_apply(cfg, p["global"], h, ctx["positions"], None)


def _pair_prefill(cfg, p, h, ctx):
    h, c1 = att.block_prefill(cfg, p["local"], h, ctx["positions"], cfg.window)
    h, c2 = att.block_prefill(cfg, p["global"], h, ctx["positions"], None)
    return h, {"local": c1, "global": c2}


def _pair_decode(cfg, p, h, cache, ctx):
    h, c1 = _local_decode(cfg, p["local"], h, cache["local"], ctx)
    h, c2 = att.block_decode(cfg, p["global"], h, cache["global"], ctx["pos"], None)
    return h, {"local": c1, "global": c2}


def _pair_cache_spec(cfg, b, s, dt):
    return {
        "local": att.attn_cache_spec(cfg, b, min(s, cfg.window or s), dt),
        "global": att.attn_cache_spec(cfg, b, s, dt),
    }


def _pair_cont(cfg, p, h, cache, ctx):
    h, c1 = att.block_cont(cfg, p["local"], h, cache["local"], ctx["positions"], ctx["start"], cfg.window)
    h, c2 = att.block_cont(cfg, p["global"], h, cache["global"], ctx["positions"], ctx["start"], None)
    return h, {"local": c1, "global": c2}


_GEMMA2_PAIR = BlockDef(
    _pair_init, _no_aux(_pair_apply), _pair_prefill, _pair_decode, _pair_cache_spec, _pair_cont
)


# ------------------------------ MoE blocks ----------------------------------


def _moe_init(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"attn": att.attn_init(cfg, k1), "moe": moe_init(cfg, k2)}


def _moe_apply(cfg, p, h, ctx):
    h = att.attn_apply(cfg, p["attn"], h, ctx["positions"])
    # single router read per step: the aux loss shares moe_apply's logits
    # (an operand-mapped router weight must not be read twice)
    return moe_apply(cfg, p["moe"], h, with_aux=True)


def _moe_prefill(cfg, p, h, ctx):
    h, cache = att.attn_apply(cfg, p["attn"], h, ctx["positions"], with_cache=True)
    return moe_apply(cfg, p["moe"], h), cache


def _moe_decode(cfg, p, h, cache, ctx):
    h, cache = att.attn_decode(cfg, p["attn"], h, cache, ctx["pos"])
    return moe_apply(cfg, p["moe"], h), cache


def _moe_cont(cfg, p, h, cache, ctx):
    h, cache = att.attn_cont(cfg, p["attn"], h, cache, ctx["positions"], ctx["start"])
    return moe_apply(cfg, p["moe"], h), cache


_MOE = BlockDef(_moe_init, _moe_apply, _moe_prefill, _moe_decode, att.attn_cache_spec, _moe_cont)


# ------------------------------ MLA blocks ----------------------------------


def _mla_dense_init(cfg, key):
    k1, k2 = jax.random.split(key)
    d_ff = cfg.dense_ff_prefix or cfg.d_ff
    return {"attn": att.mla_init(cfg, k1), "mlp": mlp_init(cfg, k2, d_ff)}


def _mla_dense_apply(cfg, p, h, ctx):
    h = att.mla_apply(cfg, p["attn"], h, ctx["positions"])
    return mlp_apply(cfg, p["mlp"], h)


def _mla_dense_prefill(cfg, p, h, ctx):
    h, cache = att.mla_apply(cfg, p["attn"], h, ctx["positions"], with_cache=True)
    return mlp_apply(cfg, p["mlp"], h), cache


def _mla_dense_decode(cfg, p, h, cache, ctx):
    h, cache = att.mla_decode(cfg, p["attn"], h, cache, ctx["pos"])
    return mlp_apply(cfg, p["mlp"], h), cache


def _mla_dense_cont(cfg, p, h, cache, ctx):
    h, cache = att.mla_cont(cfg, p["attn"], h, cache, ctx["positions"], ctx["start"])
    return mlp_apply(cfg, p["mlp"], h), cache


_MLA_DENSE = BlockDef(
    _mla_dense_init, _no_aux(_mla_dense_apply), _mla_dense_prefill, _mla_dense_decode,
    att.mla_cache_spec, _mla_dense_cont,
)


def _mla_moe_init(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"attn": att.mla_init(cfg, k1), "moe": moe_init(cfg, k2)}


def _mla_moe_apply(cfg, p, h, ctx):
    h = att.mla_apply(cfg, p["attn"], h, ctx["positions"])
    # single router read per step (see _moe_apply)
    return moe_apply(cfg, p["moe"], h, with_aux=True)


def _mla_moe_prefill(cfg, p, h, ctx):
    h, cache = att.mla_apply(cfg, p["attn"], h, ctx["positions"], with_cache=True)
    return moe_apply(cfg, p["moe"], h), cache


def _mla_moe_decode(cfg, p, h, cache, ctx):
    h, cache = att.mla_decode(cfg, p["attn"], h, cache, ctx["pos"])
    return moe_apply(cfg, p["moe"], h), cache


def _mla_moe_cont(cfg, p, h, cache, ctx):
    h, cache = att.mla_cont(cfg, p["attn"], h, cache, ctx["positions"], ctx["start"])
    return moe_apply(cfg, p["moe"], h), cache


_MLA_MOE = BlockDef(
    _mla_moe_init, _mla_moe_apply, _mla_moe_prefill, _mla_moe_decode, att.mla_cache_spec, _mla_moe_cont
)


# ------------------------------ SSM blocks ----------------------------------


def _mamba_prefill(cfg, p, h, ctx):
    return m2.mamba2_apply(cfg, p, h, with_state=True)


_MAMBA2 = BlockDef(
    m2.mamba2_init,
    _no_aux(lambda cfg, p, h, ctx: m2.mamba2_apply(cfg, p, h)),
    _mamba_prefill,
    lambda cfg, p, h, cache, ctx: m2.mamba2_decode(cfg, p, h, cache, ctx["pos"]),
    m2.mamba2_cache_spec,
    lambda cfg, p, h, cache, ctx: m2.mamba2_apply(cfg, p, h, with_state=True, state=cache),
)

_MLSTM = BlockDef(
    xl.mlstm_init,
    _no_aux(lambda cfg, p, h, ctx: xl.mlstm_apply(cfg, p, h)),
    lambda cfg, p, h, ctx: xl.mlstm_apply(cfg, p, h, with_state=True),
    lambda cfg, p, h, cache, ctx: xl.mlstm_decode(cfg, p, h, cache, ctx["pos"]),
    xl.mlstm_cache_spec,
)

_SLSTM = BlockDef(
    xl.slstm_init,
    _no_aux(lambda cfg, p, h, ctx: xl.slstm_apply(cfg, p, h)),
    lambda cfg, p, h, ctx: xl.slstm_apply(cfg, p, h, with_state=True),
    lambda cfg, p, h, cache, ctx: xl.slstm_decode(cfg, p, h, cache, ctx["pos"]),
    xl.slstm_cache_spec,
)


# ------------------------------ zamba2 unit ---------------------------------
# N mamba2 blocks followed by one invocation of the *shared* attention block
# (params live at top level, passed via ctx) over concat(h, x0).


def _zamba_unit_init(cfg, key):
    n = cfg.zamba.share_every
    ks = jax.random.split(key, n)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[m2.mamba2_init(cfg, k) for k in ks])
    return {"mamba": stacked}


def zamba_shared_init(cfg: LMConfig, key) -> dict:
    """The shared transformer block: attention + MLP over concat(h, x0)."""
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    return {
        "ln": rms_norm_init(2 * d),
        "wq": dense_init(ks[0], 2 * d, H * hd),
        "wk": dense_init(ks[1], 2 * d, cfg.n_kv_heads * hd),
        "wv": dense_init(ks[2], 2 * d, cfg.n_kv_heads * hd),
        "wo": dense_init(ks[3], H * hd, d),
        "mlp_ln": rms_norm_init(2 * d),
        "mlp_up": dense_init(ks[4], 2 * d, cfg.d_ff),
        "mlp_down": dense_init(ks[5], cfg.d_ff, d),
    }


def _zamba_shared_apply(cfg, sp, h, x0, positions, cache=None, pos=None):
    B = h.shape[0]
    H, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cat = jnp.concatenate([h, x0], axis=-1)
    x = rms_norm(sp["ln"], cat, cfg.norm_eps)
    S = x.shape[1]
    q = (x @ sp["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (x @ sp["wk"].astype(x.dtype)).reshape(B, S, kv, hd)
    v = (x @ sp["wv"].astype(x.dtype)).reshape(B, S, kv, hd)
    from .common import apply_rope

    if cache is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        mask = att.causal_mask(S, S, None)
        new_cache = {"k": {"q": k}, "v": {"q": v}}
    else:
        rpos = pos[..., None] if pos.ndim else pos.reshape(1)
        q = apply_rope(q, rpos, cfg.rope_theta)
        k = apply_rope(k, rpos, cfg.rope_theta)
        cdtype = cache["k"]["q"].dtype
        table = cache.get("table") if common_is_paged(cache) else None
        wpos = pos if (table is None or pos.ndim) else jnp.full((B,), pos, jnp.int32)
        kc = att._entry_write(cache["k"], att._cache_store(k, cdtype), wpos, table)
        vc = att._entry_write(cache["v"], att._cache_store(v, cdtype), wpos, table)
        if table is not None:
            kd = jax.tree.map(lambda c: common_paged_gather(c, table), kc)
            vd = jax.tree.map(lambda c: common_paged_gather(c, table), vc)
            S_c = table.shape[1] * kc["q"].shape[1]
            new_cache = {"table": table, "k": kc, "v": vc}
        else:
            kd, vd = kc, vc
            S_c = kc["q"].shape[1]
            new_cache = {"k": kc, "v": vc}
        mask = att.decode_posmask(pos, S_c)
        if jnp.ndim(pos):
            mask = mask[:, None, None, None, :]
        k, v = att._cache_load(kd, q.dtype), att._cache_load(vd, q.dtype)
    o = att._sdpa(cfg, q, k, v, mask)
    h = h + o.reshape(B, -1, H * hd) @ sp["wo"].astype(h.dtype)
    xm = rms_norm(sp["mlp_ln"], jnp.concatenate([h, x0], axis=-1), cfg.norm_eps)
    h = h + jax.nn.gelu(xm @ sp["mlp_up"].astype(h.dtype)) @ sp["mlp_down"].astype(h.dtype)
    return h, new_cache


def _zamba_unit_apply(cfg, p, h, ctx):
    def body(carry, mp):
        out = m2.mamba2_apply(cfg, mp, carry)
        return out, None

    h, _ = jax.lax.scan(body, h, p["mamba"])
    h, _ = _zamba_shared_apply(cfg, ctx["shared"], h, ctx["x0"], ctx["positions"])
    return h


def _zamba_unit_prefill(cfg, p, h, ctx):
    def body(carry, mp):
        out, st = m2.mamba2_apply(cfg, mp, carry, with_state=True)
        return out, st

    h, mstates = jax.lax.scan(body, h, p["mamba"])
    h, scache = _zamba_shared_apply(cfg, ctx["shared"], h, ctx["x0"], ctx["positions"])
    return h, {"mamba": mstates, "shared": scache}


def _zamba_unit_decode(cfg, p, h, cache, ctx):
    def body(carry, xs):
        mp, mc = xs
        out, st = m2.mamba2_decode(cfg, mp, carry, mc, ctx["pos"])
        return out, st

    h, mstates = jax.lax.scan(body, h, (p["mamba"], cache["mamba"]))
    h, scache = _zamba_shared_apply(
        cfg, ctx["shared"], h, ctx["x0"], None, cache=cache["shared"], pos=ctx["pos"]
    )
    return h, {"mamba": mstates, "shared": scache}


def _zamba_unit_cache_spec(cfg, b, s, dt):
    n = cfg.zamba.share_every
    mspec = m2.mamba2_cache_spec(cfg, b, s, dt)
    stacked = jax.tree.map(lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), mspec)
    shared = att.attn_cache_spec(cfg, b, s, dt)
    return {"mamba": stacked, "shared": shared}


_ZAMBA_UNIT = BlockDef(
    _zamba_unit_init, _no_aux(_zamba_unit_apply), _zamba_unit_prefill, _zamba_unit_decode, _zamba_unit_cache_spec
)


BLOCKS: dict[str, BlockDef] = {
    "dense": _DENSE,
    "local": _LOCAL,
    "gemma2_pair": _GEMMA2_PAIR,
    "moe": _MOE,
    "mla_dense": _MLA_DENSE,
    "mla_moe": _MLA_MOE,
    "mamba2": _MAMBA2,
    "mlstm": _MLSTM,
    "slstm": _SLSTM,
    "zamba_unit": _ZAMBA_UNIT,
}


# =============================== model API ==================================


def init_params(cfg: LMConfig, key) -> dict:
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    params: dict[str, Any] = {"final_ln": rms_norm_init(cfg.d_model)}
    if cfg.input_mode == "tokens":
        params["embed"] = embed_init(keys[0], cfg.vocab, cfg.d_model)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab)
    else:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab)
    groups = []
    for gi, (name, count) in enumerate(cfg.pattern):
        block = BLOCKS[name]
        gkeys = jax.random.split(keys[2 + gi], count)
        if count == 1:
            groups.append(block.init(cfg, gkeys[0]))
        else:
            groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *[block.init(cfg, k) for k in gkeys]))
    params["groups"] = groups
    if cfg.zamba is not None:
        params["shared"] = zamba_shared_init(cfg, keys[-1])
    return params


# Named scopes of the vocabulary-wide work: their names reach every HLO
# instruction's metadata (op_name), through autodiff and remat, so a device
# trace can attribute time to them.
EMBED_SCOPE = "lm.embed"
HEAD_SCOPE = "lm.head"


def _embed_in(cfg: LMConfig, params, tokens_or_embeds):
    with jax.named_scope(EMBED_SCOPE):
        if cfg.input_mode == "tokens":
            h = params["embed"].astype(cfg.dtype)[tokens_or_embeds]
        else:
            h = tokens_or_embeds.astype(cfg.dtype)
        if cfg.embed_scale:
            h = h * jnp.asarray(jnp.sqrt(float(cfg.d_model)), cfg.dtype)
        return h


def _head_out(cfg: LMConfig, params, h):
    with jax.named_scope(HEAD_SCOPE):
        h = rms_norm(params["final_ln"], h, cfg.norm_eps)
        if cfg.tie_embeddings and cfg.input_mode == "tokens":
            logits = h @ params["embed"].astype(h.dtype).T
        else:
            logits = h @ params["lm_head"].astype(h.dtype)
        return softcap(logits, cfg.softcap_final)


def forward(cfg: LMConfig, params, inputs, remat: bool = True, shard_fn=None):
    """Training forward. Returns (logits [B,S,V], aux_loss scalar)."""
    h, aux_total = hidden(cfg, params, inputs, remat=remat, shard_fn=shard_fn)
    return _head_out(cfg, params, h), aux_total


def _remat_wrap(body, remat):
    """remat: False | True ('full', save nothing) | 'dots' (save matmul
    outputs — trades activation memory for eliminating the backward's
    forward-matmul recompute; the §Perf compute-term lever)."""
    if not remat:
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return jax.checkpoint(body, prevent_cse=False)


def hidden(cfg: LMConfig, params, inputs, remat=True, shard_fn=None, wshard=None):
    """Backbone forward without the LM head. Returns (h [B,S,d], aux).

    ``wshard``: optional list (one entry per pattern group) of functions
    constraining a *single layer's* param slice to its storage sharding —
    applied inside the scan body so FSDP all-gather/reduce-scatter stay
    per-layer and the backward dW accumulator keeps the ZeRO layout."""
    h = _embed_in(cfg, params, inputs)
    S = h.shape[1]
    ctx = {"positions": jnp.arange(S), "x0": h, "shared": params.get("shared")}
    aux_total = jnp.zeros((), jnp.float32)
    shard_fn = shard_fn or (lambda x: x)
    for gi, ((name, count), gparams) in enumerate(zip(cfg.pattern, params["groups"])):
        block = BLOCKS[name]
        wsc = wshard[gi] if wshard is not None else (lambda p: p)

        def body(carry, p_i, _block=block, _wsc=wsc):
            hh, aux = carry
            hh = shard_fn(hh)
            hh, a = _block.apply(cfg, _wsc(p_i), hh, ctx)
            return (hh, aux + a), None

        body = _remat_wrap(body, remat)
        if count == 1:
            (h, aux_total), _ = body((h, aux_total), gparams)
        else:
            (h, aux_total), _ = jax.lax.scan(lambda c, p: body(c, p), (h, aux_total), gparams)
    return shard_fn(h), aux_total


def _nll_of_chunk(cfg: LMConfig, params, h_c, labels_c):
    """Fused head matmul + stable CE for one token chunk (f32 math bounded
    to the chunk — the full [B,S,V] f32 logits never exist)."""
    logits = _head_out(cfg, params, h_c)
    with jax.named_scope(HEAD_SCOPE):
        logits = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        shifted = logits - m
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        onehot = jax.nn.one_hot(labels_c, cfg.vocab, dtype=jnp.bfloat16)
        ll = jnp.einsum("bsv,bsv->bs", shifted.astype(jnp.bfloat16), onehot, preferred_element_type=jnp.float32)
        return lse - ll


LOSS_CHUNK = 1024


def loss_fn(cfg: LMConfig, params, batch, remat: bool = True, shard_fn=None, aux_weight: float = 0.01, wshard=None):
    """Next-token cross entropy (+ MoE aux). batch: {inputs, labels, mask?}.

    The head+softmax is evaluated in token chunks under jax.checkpoint so
    peak memory is O(B * chunk * V/tp) instead of O(B * S * V/tp) — the
    256k-vocab cells do not fit otherwise."""
    h, aux = hidden(cfg, params, batch["inputs"], remat=remat, shard_fn=shard_fn, wshard=wshard)
    labels = batch["labels"]
    B, S, _ = h.shape
    C = min(LOSS_CHUNK, S)
    if S % C == 0 and S > C:
        nq = S // C
        hc = h.reshape(B, nq, C, -1).swapaxes(0, 1)
        lc = labels.reshape(B, nq, C).swapaxes(0, 1)

        def body(acc, xs):
            h_c, l_c = xs
            return acc + _nll_of_chunk(cfg, params, h_c, l_c).sum(), None

        body = jax.checkpoint(body, prevent_cse=False)
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
        nll_sum = total
        denom = jnp.asarray(B * S, jnp.float32)
        mask = batch.get("mask")
        if mask is not None:  # masked variant falls back to unchunked
            nll = _nll_of_chunk(cfg, params, h, labels) * mask
            nll_sum, denom = nll.sum(), jnp.maximum(mask.sum(), 1.0)
    else:
        nll = _nll_of_chunk(cfg, params, h, labels)
        mask = batch.get("mask")
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(mask.sum(), 1.0)
        else:
            denom = jnp.asarray(nll.size, jnp.float32)
        nll_sum = nll.sum()
    return nll_sum / denom + aux_weight * aux


def cache_specs(cfg: LMConfig, batch: int, max_seq: int, dtype=None, layout: str = "stacked"):
    """Cache ShapeDtypeStructs. ``layout='stacked'``: [count, ...] arrays
    (prefill's scan output). ``layout='list'``: one entry per layer — the
    decode layout, where every leaf is its own donatable buffer."""
    dtype = dtype or cfg.dtype
    specs = []
    for name, count in cfg.pattern:
        spec = BLOCKS[name].cache_spec(cfg, batch, max_seq, dtype)
        if count > 1:
            if layout == "stacked":
                spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct((count,) + x.shape, x.dtype), spec)
            else:
                spec = [jax.tree.map(lambda x: x, spec) for _ in range(count)]
        specs.append(spec)
    return specs


def unstack_caches(cfg: LMConfig, caches):
    """Convert prefill's stacked group caches to the decode list layout."""
    out = []
    for (name, count), cache in zip(cfg.pattern, caches):
        if count == 1:
            out.append(cache)
        else:
            out.append([jax.tree.map(lambda x: x[i], cache) for i in range(count)])
    return out


def init_cache(cfg: LMConfig, batch: int, max_seq: int, dtype=None):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_specs(cfg, batch, max_seq, dtype))


def prefill(cfg: LMConfig, params, inputs, shard_fn=None, cshard=None, caches=None, start=0):
    """Full-sequence prefill. Returns (last-position logits, caches).

    ``cshard``: optional list (per pattern group) of constraint fns applied
    to each layer's cache *inside* the scan body — without this the scan's
    stacked-ys KV buffer materializes under-sharded (multi-TB at 32k).

    Chunked-prefill continuation: pass ``caches`` (the stacked-layout tree
    from a previous call, or zeros allocated at the full prompt length) and
    ``start`` (absolute position of ``inputs[:, 0]``) and each block's
    ``cont`` processes the chunk against the existing cache — the serving
    engine uses this to interleave long-prompt prefill with decode rounds.
    Requires every block in the pattern to define ``cont`` (see
    :func:`supports_chunked_prefill`)."""
    h = _embed_in(cfg, params, inputs)
    S = h.shape[1]
    ctx = {"positions": jnp.arange(S) + start, "x0": h, "shared": params.get("shared"), "start": start}
    shard_fn = shard_fn or (lambda x: x)
    if caches is None:
        out_caches = []
        for gi, ((name, count), gparams) in enumerate(zip(cfg.pattern, params["groups"])):
            block = BLOCKS[name]
            csc = cshard[gi] if cshard is not None else (lambda c: c)
            if count == 1:
                h, cache = block.prefill(cfg, gparams, shard_fn(h), ctx)
                cache = csc(cache)
            else:

                def body(carry, p_i, _block=block, _csc=csc):
                    hh, cache_i = _block.prefill(cfg, p_i, shard_fn(carry), ctx)
                    return hh, _csc(cache_i)

                h, cache = jax.lax.scan(body, h, gparams)
            out_caches.append(cache)
    else:
        out_caches = []
        for gi, ((name, count), gparams) in enumerate(zip(cfg.pattern, params["groups"])):
            block = BLOCKS[name]
            if block.cont is None:
                raise NotImplementedError(
                    f"block {name!r} does not support chunked prefill (no cont)"
                )
            if count == 1:
                h, cache = block.cont(cfg, gparams, shard_fn(h), caches[gi], ctx)
            else:

                def cbody(carry, xs, _block=block):
                    p_i, c_i = xs
                    hh, c_new = _block.cont(cfg, p_i, shard_fn(carry), c_i, ctx)
                    return hh, c_new

                h, cache = jax.lax.scan(cbody, h, (gparams, caches[gi]))
            out_caches.append(cache)
    # head on the LAST position only — the full [B,S,V] logits of a 32k
    # prefill are tens of GiB (and useless: decode continues from position S)
    return _head_out(cfg, params, h[:, -1:])[:, 0], out_caches


def supports_chunked_prefill(cfg: LMConfig) -> bool:
    """True when every block in ``cfg.pattern`` defines a prefill
    continuation (``BlockDef.cont``) — the serving engine falls back to
    single-shot prefill otherwise (zamba units and xLSTM blocks currently)."""
    return all(BLOCKS[name].cont is not None for name, _ in cfg.pattern)


def decode_step(cfg: LMConfig, params, token_or_embed, caches, pos, shard_fn=None):
    """One decode step. token [B] ids (or [B,1,d] embeds); pos: scalar int32.
    Returns (logits [B,V], new caches).

    Layer groups are *unrolled* (not scanned): lax.scan cannot donate its
    cache xs into its ys, which double-buffers the multi-GiB KV state. The
    unrolled ``cache.at[i].set(...)`` writes alias in place under donation —
    one resident cache buffer, the serving memory contract."""
    if cfg.input_mode == "tokens":
        inp = token_or_embed[:, None]
    else:
        inp = token_or_embed
    h = _embed_in(cfg, params, inp)
    ctx = {"pos": pos, "x0": h, "shared": params.get("shared")}
    shard_fn = shard_fn or (lambda x: x)
    new_caches = []
    for (name, count), gparams, cache in zip(cfg.pattern, params["groups"], caches):
        block = BLOCKS[name]
        if count == 1:
            h, c = block.decode(cfg, gparams, shard_fn(h), cache, ctx)
        else:
            c = []
            for i in range(count):
                p_i = jax.tree.map(lambda x: x[i], gparams)
                h, c_new = block.decode(cfg, p_i, shard_fn(h), cache[i], ctx)
                c.append(c_new)
        new_caches.append(c)
    return _head_out(cfg, params, h)[:, -1], new_caches
