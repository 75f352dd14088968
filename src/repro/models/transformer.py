"""Model assembly: block-pattern stacks scanned over repeats.

A model is ``embed -> scan_{repeat}(pattern blocks) -> final_norm -> head``.
Patterns mix "attn" / "moe" / "mamba" blocks (DESIGN.md §4); whisper adds an
encoder stack + cross-attention; qwen2-vl consumes a stub vision prefix with
M-RoPE positions.

Three entry points per model: ``loss_fn`` (train), ``prefill`` and
``decode_step`` (serve). All are pure functions of (params, batch).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba2, moe as moe_lib
from repro.sharding.rules import constraint


# ---------------------------------------------------------------- positions

def sinusoidal_pos(positions, d):
    """positions: (B, S) -> (B, S, d) float32 sinusoids."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def mrope_positions(cfg: ModelConfig, batch: int, seq: int):
    """(B, S, 3) t/h/w ids: a vision grid prefix then sequential text."""
    vp = cfg.vision_prefix
    grid_w = max(int(math.sqrt(max(vp, 1))), 1)
    i = jnp.arange(vp)
    vis = jnp.stack([jnp.zeros_like(i), i // grid_w, i % grid_w], axis=-1)
    start = (vp + grid_w - 1) // grid_w if vp else 0
    t = jnp.arange(seq - vp) + start
    txt = jnp.stack([t, t, t], axis=-1)
    pos = jnp.concatenate([vis, txt], axis=0).astype(jnp.int32)
    return jnp.broadcast_to(pos[None], (batch, seq, 3))


def text_positions(batch: int, seq: int, offset: int = 0):
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None] + offset,
                            (batch, seq))


# ------------------------------------------------------------------- blocks

def _block_init(key, kind: str, cfg: ModelConfig, *, cross: bool):
    ks = jax.random.split(key, 6)
    p, lg = {}, {}
    p["ln1"], lg["ln1"] = layers.norm_init(cfg.d_model, cfg.norm,
                                           jnp.dtype(cfg.param_dtype))
    if kind == "mamba":
        p["mamba"], lg["mamba"] = mamba2.mamba_init(ks[0], cfg)
        return p, lg
    p["attn"], lg["attn"] = attention.attn_init(ks[0], cfg)
    if cross:
        p["ln_cross"], lg["ln_cross"] = layers.norm_init(
            cfg.d_model, cfg.norm, jnp.dtype(cfg.param_dtype))
        p["cross"], lg["cross"] = attention.cross_attn_init(ks[1], cfg)
    p["ln2"], lg["ln2"] = layers.norm_init(cfg.d_model, cfg.norm,
                                           jnp.dtype(cfg.param_dtype))
    if kind == "moe":
        p["moe"], lg["moe"] = moe_lib.moe_init(
            ks[2], cfg, experts_padded=cfg.moe.experts_padded(_model_axis()))
    else:
        p["mlp"], lg["mlp"] = layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff,
                                              cfg.mlp_act,
                                              jnp.dtype(cfg.param_dtype))
    return p, lg


def _model_axis() -> int:
    from repro.sharding.rules import get_abstract_mesh_or_none
    m = get_abstract_mesh_or_none()
    return m.shape.get("model", 1) if m is not None else 1


def _block_apply(p, kind: str, cfg: ModelConfig, x, positions, *, mode: str,
                 cache=None, window=None, enc_kv=None, causal=True):
    """Returns (x, new_cache, aux)."""
    aux = {}
    h = layers.norm_apply(p["ln1"], x, cfg.norm, impl=cfg.norm_impl)
    if kind == "mamba":
        if mode == "decode":
            y, new_cache = mamba2.mamba_decode(p["mamba"], cfg, h, cache)
        else:
            y, new_cache = mamba2.mamba_train(p["mamba"], cfg, h)
        return x + y, new_cache, aux
    if mode == "decode":
        y, new_cache = attention.attn_decode(p["attn"], cfg, h, cache,
                                             window=window,
                                             positions=positions)
    else:
        if causal:
            y, kv = attention.attn_train(p["attn"], cfg, h, positions,
                                         window=window)
        else:  # encoder: bidirectional
            q_pos = positions if positions.ndim == 2 else positions[..., 0]
            qkv = attention._project(p["attn"], cfg, h, positions)
            out = attention.flash_attention(
                qkv[0], qkv[1], qkv[2], q_pos, q_pos, causal=False,
                window=None)
            b, s = out.shape[:2]
            y = out.reshape(b, s, -1) @ p["attn"]["wo"].astype(h.dtype)
            kv = None
        new_cache = kv
    x = x + y
    if enc_kv is not None:
        hc = layers.norm_apply(p["ln_cross"], x, cfg.norm, impl=cfg.norm_impl)
        x = x + attention.cross_attn_apply(p["cross"], cfg, hc, enc_kv)
    h2 = layers.norm_apply(p["ln2"], x, cfg.norm, impl=cfg.norm_impl)
    if kind == "moe":
        y2, moe_aux = moe_lib.moe_apply(p["moe"], cfg, h2)
        aux.update(moe_aux)
    else:
        y2 = layers.mlp_apply(p["mlp"], h2, cfg.mlp_act)
    return x + y2, new_cache, aux


# -------------------------------------------------------------------- init

def init_params(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    """Returns (params, logical) pytrees. Stacked block params have a leading
    repeat ('layers') dim."""
    rep = cfg.resolved_repeat()
    pat = cfg.block_pattern
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    vpad = layers.pad_vocab(cfg.vocab_size)

    params: Dict[str, Any] = {}
    logical: Dict[str, Any] = {}
    params["embed"], logical["embed"] = layers.embed_init(keys[0], vpad,
                                                          cfg.d_model, dtype)

    def stack_init(key, kind, cross=False):
        ks = jax.random.split(key, rep)
        per = [_block_init(k, kind, cfg, cross=cross) for k in ks]
        p = jax.tree.map(lambda *xs: jnp.stack(xs), *[pp for pp, _ in per])
        lg = jax.tree.map(lambda ax: ("layers",) + tuple(ax), per[0][1],
                          is_leaf=lambda t: isinstance(t, tuple))
        return p, lg

    blocks, blocks_lg = [], []
    bkeys = jax.random.split(keys[1], len(pat))
    for i, kind in enumerate(pat):
        p, lg = stack_init(bkeys[i], kind, cross=cfg.is_encoder_decoder
                           and kind != "mamba")
        blocks.append(p)
        blocks_lg.append(lg)
    params["blocks"] = tuple(blocks)
    logical["blocks"] = tuple(blocks_lg)

    params["final_norm"], logical["final_norm"] = layers.norm_init(
        cfg.d_model, cfg.norm, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": layers._normal(keys[2], (cfg.d_model, vpad),
                                1 / math.sqrt(cfg.d_model), dtype)}
        logical["lm_head"] = {"w": ("fsdp", "tensor")}

    if cfg.is_encoder_decoder:
        erep = cfg.n_encoder_layers
        ekeys = jax.random.split(keys[3], erep)
        per = [_block_init(k, "attn", cfg, cross=False) for k in ekeys]
        params["enc_blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *[pp for pp, _ in per])
        logical["enc_blocks"] = jax.tree.map(
            lambda ax: ("layers",) + tuple(ax), per[0][1],
            is_leaf=lambda t: isinstance(t, tuple))
        params["enc_final_norm"], logical["enc_final_norm"] = \
            layers.norm_init(cfg.d_model, cfg.norm, dtype)
    return params, logical


def init_shapes(cfg: ModelConfig):
    """Shape-only init (no allocation) for the dry-run."""
    return jax.eval_shape(lambda k: init_params(k, cfg)[0],
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def logical_axes(cfg: ModelConfig):
    """Logical tree without materialising params (the logical tree is pure
    python, captured as a side-effect of an abstract trace)."""
    box = {}

    def f(k):
        p, lg = init_params(k, cfg)
        box["lg"] = lg
        return p

    jax.eval_shape(f, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return box["lg"]


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ----------------------------------------------------------------- encoder

def encode(params, cfg: ModelConfig, audio_embeds):
    """Whisper encoder over stub frame embeddings (B, Senc, D)."""
    b, s, _ = audio_embeds.shape
    pos = text_positions(b, s)
    x = audio_embeds + sinusoidal_pos(pos, cfg.d_model).astype(
        audio_embeds.dtype)

    def body(x, blk):
        x, _, _ = _block_apply(blk, "attn", cfg, x, pos, mode="train",
                               causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return layers.norm_apply(params["enc_final_norm"], x, cfg.norm, impl=cfg.norm_impl)


# ------------------------------------------------------------------ embed+

def _embed_inputs(params, cfg: ModelConfig, tokens, extra_embeds):
    """tokens: (B, S_text); extra_embeds: vision/audio prefix or None.
    Returns (x, positions)."""
    x = layers.embed_apply(params["embed"], tokens)
    b = tokens.shape[0]
    if cfg.family == "vlm" and extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        pos = mrope_positions(cfg, b, x.shape[1])
    else:
        pos = text_positions(b, x.shape[1])
        if cfg.rope_theta <= 0:   # whisper: sinusoidal absolute
            x = x + sinusoidal_pos(pos, cfg.d_model).astype(x.dtype)
    x = constraint(x, "batch", None, None)
    return x, pos


# ------------------------------------------------------------------- train

def forward_train(params, cfg: ModelConfig, batch, *, remat: bool = True,
                  window=None):
    """batch: {tokens, labels, [vision_embeds|audio_embeds]}.
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["audio_embeds"])
    x, pos = _embed_inputs(params, cfg, tokens,
                           batch.get("vision_embeds"))
    pat = cfg.block_pattern

    def body(x, blk):
        # carry saved by remat: shard d_model over `model` to keep the
        # per-layer checkpoint small (all-gathered on first use inside).
        # The barrier stops XLA hoisting a whole-stack f32 convert of the
        # saved carries out of the backward loop (a 2x memory pessimisation
        # observed on the CPU backend).
        x = jax.lax.optimization_barrier(x)
        x = constraint(x, "batch", None, "tensor")
        aux_sum = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pat):
            enc_kv = None
            if cfg.is_encoder_decoder and kind != "mamba":
                enc_kv = attention.encode_cross_kv(blk[i]["cross"], cfg,
                                                   enc_out)
            x, _, aux = _block_apply(blk[i], kind, cfg, x, pos, mode="train",
                                     window=window, enc_kv=enc_kv)
            if "load_balance_loss" in aux:
                aux_sum = aux_sum + aux["load_balance_loss"]
        return x, aux_sum

    if remat:
        body = jax.checkpoint(body)
    x, aux = jax.lax.scan(body, x, params["blocks"])
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, impl=cfg.norm_impl)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    labels = batch["labels"]
    if cfg.family == "vlm":  # no loss on the vision prefix
        prefix = x.shape[1] - labels.shape[1]
        x = x[:, prefix:]
    vpad = layers.pad_vocab(cfg.vocab_size)
    if x.shape[1] * vpad > 2 ** 26:  # large S*V: stream the loss
        loss = layers.chunked_cross_entropy(x, head, labels, cfg.vocab_size,
                                            tied=cfg.tie_embeddings)
    else:
        logits = layers.logits_apply(head, x, tied=cfg.tie_embeddings)
        loss = layers.cross_entropy(logits, labels, cfg.vocab_size)
    metrics = {"loss": loss, "aux_loss": jnp.mean(aux)}
    total = loss + 0.01 * jnp.mean(aux)
    return total, metrics


# ----------------------------------------------------------------- serving

def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                      window, dtype, enc_out=None, blk=None):
    if kind == "mamba":
        return mamba2.make_mamba_cache(cfg, batch, dtype)
    return attention.make_decode_cache(cfg, batch, cache_len, window=window,
                                       dtype=dtype)


def prefill(params, cfg: ModelConfig, batch, *, window=None,
            extra_slots: int = 0):
    """Full forward over the prompt; returns (last_logits, caches, enc_out).
    ``extra_slots`` reserves KV-cache room for subsequent decode steps."""
    tokens = batch["tokens"]
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["audio_embeds"])
    x, pos = _embed_inputs(params, cfg, tokens, batch.get("vision_embeds"))
    pat = cfg.block_pattern

    def body(x, blk):
        caches = []
        for i, kind in enumerate(pat):
            enc_kv = None
            if cfg.is_encoder_decoder and kind != "mamba":
                enc_kv = attention.encode_cross_kv(blk[i]["cross"], cfg,
                                                   enc_out)
            x, c, _ = _block_apply(blk[i], kind, cfg, x, pos, mode="prefill",
                                   window=window, enc_kv=enc_kv)
            if kind != "mamba":
                k, v = c["k"], c["v"]
                if extra_slots:
                    padw = [(0, 0), (0, extra_slots), (0, 0), (0, 0)]
                    k, v = jnp.pad(k, padw), jnp.pad(v, padw)
                c = {"k": k, "v": v,
                     "idx": jnp.array(x.shape[1], jnp.int32),
                     "slot_pos": jnp.arange(k.shape[1], dtype=jnp.int32)}
            caches.append(c)
        return x, tuple(caches)

    x, caches = jax.lax.scan(body, x, params["blocks"])
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, impl=cfg.norm_impl)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.logits_apply(head, x[:, -1:], tied=cfg.tie_embeddings)
    return logits, caches, enc_out


def make_caches(cfg: ModelConfig, batch: int, cache_len: int, *, window=None,
                dtype=jnp.bfloat16):
    """Empty stacked caches for `serve_step` input specs: pytree matching the
    scan layout (leading repeat dim per pattern element)."""
    rep = cfg.resolved_repeat()
    caches = []
    for kind in cfg.block_pattern:
        one = _init_block_cache(cfg, kind, batch, cache_len, window, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (rep,) + x.shape), one))
    return tuple(caches)


def decode_step(params, cfg: ModelConfig, token, caches, *, window=None,
                enc_out=None):
    """token: (B, 1) -> (logits (B,1,V), new caches). The per-layer caches
    are scan xs/ys so the stacked layout is preserved."""
    x = layers.embed_apply(params["embed"], token)
    b = token.shape[0]
    if cfg.rope_theta <= 0 and "idx" in caches[0]:
        idx = caches[0]["idx"][0]
        pos = jnp.broadcast_to(jnp.reshape(idx, (1, 1)), (b, 1))
        x = x + sinusoidal_pos(pos, cfg.d_model).astype(x.dtype)
    x = constraint(x, "batch", None, None)
    pat = cfg.block_pattern

    # M-RoPE decode positions: a text token at absolute index i sits at
    # rotary position start + (i - vision_prefix) on all three streams
    dec_pos = None
    if cfg.mrope and cfg.vision_prefix:
        idx0 = None
        for c0 in caches:
            if isinstance(c0, dict) and "idx" in c0:
                idx0 = c0["idx"][0]
                break
        if idx0 is not None:
            grid_w = max(int(math.sqrt(max(cfg.vision_prefix, 1))), 1)
            start = (cfg.vision_prefix + grid_w - 1) // grid_w
            p1 = (idx0 - cfg.vision_prefix + start).astype(jnp.int32)
            dec_pos = jnp.broadcast_to(p1.reshape(1, 1, 1), (b, 1, 3))

    def body(x, xs):
        blk, caches_l = xs
        new = []
        for i, kind in enumerate(pat):
            enc_kv = None
            if cfg.is_encoder_decoder and kind != "mamba" and enc_out is not None:
                enc_kv = attention.encode_cross_kv(blk[i]["cross"], cfg,
                                                   enc_out)
            x, c, _ = _block_apply(blk[i], kind, cfg, x, dec_pos,
                                   mode="decode", cache=caches_l[i],
                                   window=window, enc_kv=enc_kv)
            new.append(c)
        return x, tuple(new)

    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, impl=cfg.norm_impl)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.logits_apply(head, x, tied=cfg.tie_embeddings)
    return logits, new_caches
