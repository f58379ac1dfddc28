"""Config-driven model assembly.

A model is a list of *block groups*; each group is a repeating unit of layer
kinds scanned ``count`` times (``jax.lax.scan`` over stacked params) so that
64-layer 32B configs lower to compact HLO.

  dense:   [Group(("attn",), L)]
  moe:     [Group(("attn",), first_dense, moe=False), Group(("attn",), rest, moe=True)]
  hybrid:  [Group((rec,rec,attn), L//3), Group((rec,rec), 1)]   # RecurrentGemma
  ssm:     [Group(("ssd",), L)]
  enc-dec: encoder groups (non-causal) + decoder groups (cross=True)

Three entry points:
  train_forward(params, cfg, batch)                    -> logits (B,S,V)
  prefill(params, cfg, inputs, caches)                 -> (last_logits, caches)
  decode_step(params, cfg, tokens, positions, caches)  -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, RECURRENT, SSD, ModelConfig
from repro.models import dist
from repro.models import layers as L

Params = Dict[str, Any]


def _constrain(x: jax.Array) -> jax.Array:
    """Anchor the residual stream to the launch layer's activation spec."""
    dctx = dist.ctx()
    if dctx.act_spec is None or dctx.mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dctx.mesh, dctx.act_spec))


@dataclasses.dataclass(frozen=True)
class Group:
    kinds: Tuple[str, ...]
    count: int
    moe: bool = False
    cross: bool = False     # decoder layers of an enc-dec model
    causal: bool = True


def block_groups(cfg: ModelConfig) -> List[Group]:
    if cfg.family == "ssm":
        return [Group((SSD,), cfg.num_layers)]
    if cfg.recurrent is not None:
        pat = cfg.recurrent.block_pattern
        full, rem = divmod(cfg.num_layers, len(pat))
        gs = [Group(pat, full)]
        if rem:
            gs.append(Group(pat[:rem], 1))
        return gs
    if cfg.is_moe and cfg.moe.first_dense_layers:
        fd = cfg.moe.first_dense_layers
        return [Group((ATTN,), fd, moe=False),
                Group((ATTN,), cfg.num_layers - fd, moe=True)]
    cross = cfg.is_enc_dec
    return [Group((ATTN,), cfg.num_layers, moe=cfg.is_moe, cross=cross)]


def encoder_groups(cfg: ModelConfig) -> List[Group]:
    return [Group((ATTN,), cfg.encoder_layers, causal=False)]


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #
def _init_layer(rng, cfg: ModelConfig, kind: str, moe: bool, cross: bool) -> Params:
    d = cfg.d_model
    ks = jax.random.split(rng, 6)
    if kind == SSD:
        return {"norm": jnp.ones((d,), cfg.pdtype),
                "ssd": L.init_ssd(ks[0], cfg)}
    if kind == RECURRENT:
        return {"norm1": jnp.ones((d,), cfg.pdtype),
                "rglru": L.init_rglru(ks[0], cfg),
                "norm2": jnp.ones((d,), cfg.pdtype),
                "mlp": L.init_mlp(ks[1], cfg)}
    p = {"norm1": jnp.ones((d,), cfg.pdtype),
         "norm2": jnp.ones((d,), cfg.pdtype)}
    if cfg.attention_kind == "mla":
        p["attn"] = L.init_mla(ks[0], cfg)
    else:
        p["attn"] = L.init_attention(ks[0], cfg)
    p["mlp"] = L.init_moe(ks[1], cfg) if moe else L.init_mlp(ks[1], cfg)
    if cross:
        p["norm_x"] = jnp.ones((d,), cfg.pdtype)
        p["cross"] = L.init_cross_attention(ks[2], cfg)
    return p


def _init_group(rng, cfg: ModelConfig, g: Group) -> Tuple[Params, ...]:
    """Returns tuple (per position in kinds) of stacked (count, ...) params."""
    out = []
    for i, kind in enumerate(g.kinds):
        keys = jax.random.split(jax.random.fold_in(rng, i), g.count)
        out.append(jax.vmap(
            lambda k: _init_layer(k, cfg, kind, g.moe, g.cross))(keys))
    return tuple(out)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random parameters from ``rng``. Built as one jitted program, so each
    leaf is produced directly in ``param_dtype`` (run eagerly, the largest
    stacked leaf would first be drawn whole in float32, then cast)."""
    return _init_params(rng, cfg)


@partial(jax.jit, static_argnums=1)
def _init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(rng, 8)
    d, v = cfg.d_model, cfg.vocab_size
    p: Params = {
        "embed": (jax.random.normal(ks[0], (v, d)) * 0.02).astype(cfg.pdtype),
        "final_norm": jnp.ones((d,), cfg.pdtype),
        "groups": tuple(_init_group(jax.random.fold_in(ks[1], i), cfg, g)
                        for i, g in enumerate(block_groups(cfg))),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[2], (d, v)) / math.sqrt(d)).astype(cfg.pdtype)
    if cfg.is_enc_dec:
        p["enc_groups"] = tuple(
            _init_group(jax.random.fold_in(ks[3], i), cfg, g)
            for i, g in enumerate(encoder_groups(cfg)))
        p["enc_norm"] = jnp.ones((d,), cfg.pdtype)
    return p


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# --------------------------------------------------------------------------- #
# Caches
# --------------------------------------------------------------------------- #
def layer_cache_init(cfg: ModelConfig, kind: str, cross: bool, batch: int,
                     capacity: int, dtype, mem_len: int = 0,
                     full_capacity: bool = False):
    if kind == SSD:
        return L.ssm_state_init(batch, cfg, dtype)
    if kind == RECURRENT:
        return L.rglru_state_init(batch, cfg, dtype)
    cap = capacity
    if (cfg.attention_kind == "sliding" and cfg.sliding_window
            and not full_capacity):
        # ring buffer sized to the window. Chunked prefill must opt OUT
        # (full_capacity): writing chunk c would evict positions still
        # inside the window of chunk c's own queries; window masking is
        # applied by attention instead, so slot == position.
        cap = min(cap, cfg.sliding_window)
    if cfg.attention_kind == "mla":
        c = L.mla_cache_init(batch, cap, cfg, dtype)
    else:
        c = L.kv_cache_init(batch, cap, cfg.num_kv_heads, cfg.hd, dtype)
    if cross:
        return {"self": c,
                "cross_k": jnp.zeros((batch, mem_len, cfg.num_kv_heads, cfg.hd), dtype),
                "cross_v": jnp.zeros((batch, mem_len, cfg.num_kv_heads, cfg.hd), dtype),
                "mem_len": jnp.zeros((batch,), jnp.int32)}
    return c


def init_caches(cfg: ModelConfig, batch: int, capacity: int,
                dtype=None, mem_len: int = 0, full_capacity: bool = False):
    """Nested cache pytree matching ``params['groups']`` structure, with every
    leaf stacked (count, ...) per group position."""
    dtype = dtype or cfg.cdtype
    out = []
    for g in block_groups(cfg):
        per_pos = []
        for kind in g.kinds:
            one = layer_cache_init(cfg, kind, g.cross, batch, capacity,
                                   dtype, mem_len, full_capacity)
            per_pos.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (g.count,) + x.shape), one))
        out.append(tuple(per_pos))
    return tuple(out)


def abstract_caches(cfg: ModelConfig, batch: int, capacity: int,
                    dtype=None, mem_len: int = 0):
    return jax.eval_shape(
        lambda: init_caches(cfg, batch, capacity, dtype, mem_len))


# --------------------------------------------------------------------------- #
# Layer application
# --------------------------------------------------------------------------- #
def _window(cfg: ModelConfig) -> int:
    return cfg.sliding_window if cfg.attention_kind == "sliding" else 0


def _mlp_apply(p: Params, cfg: ModelConfig, moe: bool, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        return L.moe_mlp(p, cfg, x) if moe else L.swiglu_mlp(p, x)


def _apply_layer_full(p, cfg: ModelConfig, g: Group, kind: str, x,
                      positions, lengths, cache, memory=None, mem_lengths=None):
    """Full-sequence pass (train/prefill). Returns (x, new_cache).

    ``cache`` may be None (train mode) — then no cache is built.
    """
    build = cache is not None
    if kind == SSD:
        h, st = L.ssd_block(p["ssd"], cfg, L.rms_norm(p["norm"], x, cfg.norm_eps),
                            cache if build else L.ssm_state_init(x.shape[0], cfg, x.dtype))
        return x + h, (st if build else None)
    if kind == RECURRENT:
        h, st = L.rglru_block(p["rglru"], cfg,
                              L.rms_norm(p["norm1"], x, cfg.norm_eps),
                              cache if build else L.rglru_state_init(x.shape[0], cfg, x.dtype))
        x = x + h
        x = x + L.swiglu_mlp(p["mlp"], L.rms_norm(p["norm2"], x, cfg.norm_eps))
        return x, (st if build else None)
    # attention layer
    win = _window(cfg) if kind == ATTN and g.causal else 0
    h = L.rms_norm(p["norm1"], x, cfg.norm_eps)
    self_cache = cache["self"] if (build and g.cross) else cache
    new_cache: Any = None
    with jax.named_scope("attention"):
        if cfg.attention_kind == "mla":
            out, (ckv, kpe) = L.mla_block(p["attn"], cfg, h, positions,
                                          lengths)
            if build:
                new_cache = L.mla_cache_from_prefill(self_cache, ckv, kpe,
                                                     positions)
        else:
            out, (k, v) = L.attention_block(p["attn"], cfg, h, positions,
                                            causal=g.causal, lengths=lengths,
                                            window=win)
            if build:
                new_cache = L.kv_cache_from_prefill(self_cache, k, v,
                                                    positions)
    x = x + out
    if g.cross:
        hx = L.rms_norm(p["norm_x"], x, cfg.norm_eps)
        mk, mv = L.cross_attention_kv(p["cross"], cfg, memory)
        x = x + L.cross_attention(p["cross"], cfg, hx, (mk, mv), mem_lengths)
        if build:
            new_cache = {"self": new_cache, "cross_k": mk, "cross_v": mv,
                         "mem_len": mem_lengths if mem_lengths is not None
                         else jnp.full((x.shape[0],), mk.shape[1], jnp.int32)}
    h2 = L.rms_norm(p["norm2"], x, cfg.norm_eps)
    x = x + _mlp_apply(p["mlp"], cfg, g.moe, h2)
    return x, new_cache


def _apply_layer_decode(p, cfg: ModelConfig, g: Group, kind: str, x,
                        positions, cache):
    if kind == SSD:
        # ssd_decode is the single-token recurrence; multi-token chunks
        # (chunked prefill) go through the chunk-scan with the incoming
        # state as scan carry. Static shape branch — resolved at trace.
        ssd = L.ssd_block if x.shape[1] > 1 else L.ssd_decode
        h, st = ssd(p["ssd"], cfg,
                    L.rms_norm(p["norm"], x, cfg.norm_eps), cache)
        return x + h, st
    if kind == RECURRENT:
        h, st = L.rglru_decode(p["rglru"], cfg,
                               L.rms_norm(p["norm1"], x, cfg.norm_eps), cache)
        x = x + h
        x = x + L.swiglu_mlp(p["mlp"], L.rms_norm(p["norm2"], x, cfg.norm_eps))
        return x, st
    win = _window(cfg)
    h = L.rms_norm(p["norm1"], x, cfg.norm_eps)
    self_cache = cache["self"] if g.cross else cache
    with jax.named_scope("attention"):
        if cfg.attention_kind == "mla":
            out, new_self = L.mla_decode(p["attn"], cfg, h, positions,
                                         self_cache)
        else:
            out, new_self = L.attention_decode(p["attn"], cfg, h, positions,
                                               self_cache, window=win)
    x = x + out
    new_cache: Any = new_self
    if g.cross:
        hx = L.rms_norm(p["norm_x"], x, cfg.norm_eps)
        x = x + L.cross_attention(p["cross"], cfg, hx,
                                  (cache["cross_k"], cache["cross_v"]),
                                  cache["mem_len"])
        new_cache = {"self": new_self, "cross_k": cache["cross_k"],
                     "cross_v": cache["cross_v"], "mem_len": cache["mem_len"]}
    h2 = L.rms_norm(p["norm2"], x, cfg.norm_eps)
    x = x + _mlp_apply(p["mlp"], cfg, g.moe, h2)
    return x, new_cache


# --------------------------------------------------------------------------- #
# Group scan
# --------------------------------------------------------------------------- #
def _layer_of(tree, li):
    """Layer ``li`` of a group's stacked caches."""
    return jax.tree.map(
        lambda buf: jax.lax.dynamic_index_in_dim(buf, li, 0, keepdims=False),
        tree)


def _with_layer(tree, new, li):
    """A group's stacked caches with layer ``li`` replaced by ``new``."""
    return jax.tree.map(
        lambda buf, n: jax.lax.dynamic_update_index_in_dim(
            buf, n.astype(buf.dtype), li, 0), tree, new)


def _scan_group(gp, cfg: ModelConfig, g: Group, x, apply_pos, caches_g,
                remat: bool, stacked: bool = False):
    """Scan a group over its ``count`` repetitions.

    gp: tuple(len(kinds)) of stacked params; caches_g same structure or None.
    apply_pos(p_i, kind_i, x, cache_i) -> (x, new_cache_i), or with
    ``stacked`` apply_pos(p_i, kind_i, x, stacked_cache_i, layer) ->
    (x, new_stacked_cache_i): it reads and writes its own layer, so a
    kernel can address a layer's pool inside the stack.

    Caches ride in the scan CARRY (sliced / written back per layer with
    dynamic-(index|update)-slice) rather than as scan xs/ys: the carry is
    aliased in place by XLA buffer assignment, so a donated multi-GB KV
    cache is updated without a second stacked copy.
    """
    unroll = True if dist.ctx().unroll else 1

    if caches_g is None:
        def body(carry, ps):
            xx = _constrain(carry)
            for i, kind in enumerate(g.kinds):
                xx, _ = apply_pos(ps[i], kind, xx, None)
            return xx, None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, gp, unroll=unroll)
        return x, None

    def body(carry, ps):
        xx, caches, li = carry
        xx = _constrain(xx)
        new_caches = []
        for i, kind in enumerate(g.kinds):
            if stacked:
                xx, nc = apply_pos(ps[i], kind, xx, caches[i], li)
            else:
                xx, nc = apply_pos(ps[i], kind, xx, _layer_of(caches[i], li))
                nc = _with_layer(caches[i], nc, li)
            new_caches.append(nc)
        return (xx, tuple(new_caches), li + 1), None

    if remat:
        body = jax.checkpoint(body)
    (x, new_caches, _), _ = jax.lax.scan(
        body, (x, caches_g, jnp.zeros((), jnp.int32)), gp, unroll=unroll)
    return x, new_caches


def _run_groups(params, cfg: ModelConfig, groups: List[Group], gparams, x,
                mode: str, positions, lengths, caches, memory=None,
                mem_lengths=None, remat: bool = False):
    new_caches = []
    for gi, g in enumerate(groups):
        cg = None if caches is None else caches[gi]
        if mode == "decode":
            def apply_pos(p_i, kind, xx, c_i, _g=g):
                return _apply_layer_decode(p_i, cfg, _g, kind, xx, positions, c_i)
        else:
            def apply_pos(p_i, kind, xx, c_i, _g=g):
                return _apply_layer_full(p_i, cfg, _g, kind, xx, positions,
                                         lengths, c_i, memory, mem_lengths)
        x, nc = _scan_group(gparams[gi], cfg, g, x, apply_pos, cg, remat)
        new_caches.append(nc)
    return x, tuple(new_caches)


# --------------------------------------------------------------------------- #
# Embedding / head
# --------------------------------------------------------------------------- #
def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    e = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    return e * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype) \
        if cfg.tie_embeddings else e


def lm_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        head = params.get("lm_head", None)
        if head is None:
            head = params["embed"].T
        return jnp.einsum("...d,dv->...v", x, head.astype(x.dtype))


def _merge_frontend(params, cfg: ModelConfig, inputs: Dict[str, jax.Array]):
    """Returns (x (B,S,d), positions (B,S), lengths or None)."""
    tokens = inputs["tokens"]
    b = tokens.shape[0]
    emb = embed_tokens(params, cfg, tokens)
    lengths = inputs.get("lengths")
    if cfg.frontend.kind == "vision" and "patches" in inputs:
        patches = inputs["patches"].astype(cfg.cdtype)
        emb = jnp.concatenate([patches, emb], axis=1)
        if lengths is not None:
            lengths = lengths + patches.shape[1]
    s = emb.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if lengths is not None:
        positions = jnp.where(positions < lengths[:, None], positions, -1)
    return emb, positions, lengths


def encode(params, cfg: ModelConfig, frames: jax.Array,
           frame_lengths: Optional[jax.Array] = None) -> jax.Array:
    """Encoder forward (audio frontend STUB: frames are embeddings)."""
    x = frames.astype(cfg.cdtype)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = _run_groups(params, cfg, encoder_groups(cfg), params["enc_groups"],
                       x, "full", positions, frame_lengths, None)
    return L.rms_norm(params["enc_norm"], x, cfg.norm_eps)


# --------------------------------------------------------------------------- #
# Entry points
# --------------------------------------------------------------------------- #
def train_forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  remat: bool = True) -> jax.Array:
    """Teacher-forced logits (B,S,V)."""
    memory = mem_lengths = None
    if cfg.is_enc_dec:
        memory = encode(params, cfg, batch["frames"], batch.get("frame_lengths"))
        mem_lengths = batch.get("frame_lengths")
    x, positions, lengths = _merge_frontend(params, cfg, batch)
    x, _ = _run_groups(params, cfg, block_groups(cfg), params["groups"], x,
                       "full", positions, lengths, None, memory, mem_lengths,
                       remat=remat)
    return lm_logits(params, cfg, x)


def prefill(params, cfg: ModelConfig, inputs: Dict[str, jax.Array], caches,
            remat: bool = False):
    """Build caches from a prompt. Returns (last_token_logits (B,V), caches).

    inputs: tokens (B,S), optional lengths (B,), frames (enc-dec),
    patches (vlm).
    """
    memory = mem_lengths = None
    if cfg.is_enc_dec:
        memory = encode(params, cfg, inputs["frames"], inputs.get("frame_lengths"))
        mem_lengths = inputs.get("frame_lengths")
    x, positions, lengths = _merge_frontend(params, cfg, inputs)
    x, caches = _run_groups(params, cfg, block_groups(cfg), params["groups"],
                            x, "full", positions, lengths, caches, memory,
                            mem_lengths, remat=remat)
    x = lm_logits(params, cfg, x)                        # (B,S,V)
    if lengths is None:
        last = x[:, -1]
    else:
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32),
                                   axis=1)[:, 0]
        if cfg.frontend.kind == "vision" and "patches" in inputs:
            pass  # lengths already include patches via _merge_frontend
    return last, caches


def decode_step(params, cfg: ModelConfig, tokens: jax.Array,
                positions: jax.Array, caches):
    """One decode step. tokens: (B,T) new token ids; positions: (B,T) absolute
    (text-space positions are offset by num_patches for VLM prompts upstream).
    Returns (logits (B,T,V), caches)."""
    return decode_step_embeds(params, cfg, embed_tokens(params, cfg, tokens),
                              positions, caches)


def decode_step_embeds(params, cfg: ModelConfig, embeds: jax.Array,
                       positions: jax.Array, caches):
    """Decode path over precomputed embeddings (B,T,d) — the chunked-prefill
    route for multimodal prompts, where patch embeddings and token
    embeddings interleave in one merged sequence."""
    x = embeds.astype(cfg.cdtype)
    x, caches = _run_groups(params, cfg, block_groups(cfg), params["groups"],
                            x, "decode", positions, None, caches)
    return lm_logits(params, cfg, x), caches


def encoder_cross_kv(params, cfg: ModelConfig, memory: jax.Array):
    """Per-decoder-layer cross-attention K/V from encoder ``memory``
    (B,S_mem,d) — the non-resumable preamble of a chunked enc-dec prefill.
    Returns {(gi, pi): (mk, mv)} with mk/mv stacked (count, B, S_mem, KV, hd)
    to match the cache leaf layout (tuple keys stay static under jit)."""
    out = {}
    for gi, g in enumerate(block_groups(cfg)):
        if not g.cross:
            continue
        for pi, _kind in enumerate(g.kinds):
            cp = params["groups"][gi][pi]["cross"]
            mk, mv = jax.vmap(
                lambda c: L.cross_attention_kv(c, cfg, memory))(cp)
            out[(gi, pi)] = (mk, mv)
    return out


# --------------------------------------------------------------------------- #
# Paged decode (serving path). Pool pytree mirrors ``params['groups']``:
# attn positions hold {"k_pool","v_pool"} (or {"ckv_pool","kpe_pool"} for MLA,
# plus cross_* for enc-dec); ssm/rglru positions hold their dense states.
# block_table/seq_lens/write_* are shared across layers.
# --------------------------------------------------------------------------- #
def init_paged_caches(cfg: ModelConfig, specs: Dict[str, Any],
                      num_blocks: int, batch: int = 0, mem_len: int = 0):
    """specs: {"kv": KVPageSpec} or {"ckv": ..., "kpe": ...} for MLA."""
    from repro.serving import paged_cache as PC
    out = []
    for g in block_groups(cfg):
        per_pos = []
        for kind in g.kinds:
            if kind == SSD:
                one: Any = L.ssm_state_init(batch, cfg, cfg.cdtype)
            elif kind == RECURRENT:
                one = L.rglru_state_init(batch, cfg, cfg.cdtype)
            elif cfg.attention_kind == "mla":
                one = {"ckv_pool": PC.init_pool(specs["ckv"], num_blocks),
                       "kpe_pool": PC.init_pool(specs["kpe"], num_blocks)}
            else:
                one = {"k_pool": PC.init_pool(specs["kv"], num_blocks),
                       "v_pool": PC.init_pool(specs["kv"], num_blocks)}
            if g.cross and kind == ATTN:
                one.update({
                    "cross_k": jnp.zeros((batch, mem_len, cfg.num_kv_heads,
                                          cfg.hd), cfg.cdtype),
                    "cross_v": jnp.zeros((batch, mem_len, cfg.num_kv_heads,
                                          cfg.hd), cfg.cdtype),
                    "mem_len": jnp.zeros((batch,), jnp.int32)})
            per_pos.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (g.count,) + x.shape), one))
        out.append(tuple(per_pos))
    return tuple(out)


def _apply_layer_decode_paged(p, cfg: ModelConfig, g: Group, kind: str, x,
                              positions, cache, li, block_table, seq_lens,
                              write_blocks, write_slots, specs):
    """One layer of the paged decode step; ``cache`` is the group's
    stacked caches of this position, read and written at layer ``li``."""
    if kind == SSD:
        h, st = L.ssd_decode(p["ssd"], cfg,
                             L.rms_norm(p["norm"], x, cfg.norm_eps),
                             _layer_of(cache, li))
        return x + h, _with_layer(cache, st, li)
    if kind == RECURRENT:
        h, st = L.rglru_decode(p["rglru"], cfg,
                               L.rms_norm(p["norm1"], x, cfg.norm_eps),
                               _layer_of(cache, li))
        x = x + h
        x = x + L.swiglu_mlp(p["mlp"], L.rms_norm(p["norm2"], x, cfg.norm_eps))
        return x, _with_layer(cache, st, li)
    win = _window(cfg)
    h = L.rms_norm(p["norm1"], x, cfg.norm_eps)
    if cfg.attention_kind == "mla":
        pools = {k: cache[k] for k in ("ckv_pool", "kpe_pool")}
        with jax.named_scope("attention"):
            out, new_pools = L.mla_decode_paged(
                p["attn"], cfg, h, positions, _layer_of(pools, li),
                block_table, seq_lens, write_blocks, write_slots,
                specs["ckv"], specs["kpe"])
        new_pools = _with_layer(pools, new_pools, li)
    else:
        # the stacked pools go to the attention whole: the kernel reads
        # layer li in HBM, with no per-layer copy of a pool
        with jax.named_scope("attention"):
            out, new_pools = L.attention_decode_paged(
                p["attn"], cfg, h, positions, cache, li, block_table,
                seq_lens, write_blocks, write_slots, specs["kv"], window=win)
    x = x + out
    new_cache = dict(new_pools)
    if g.cross:
        hx = L.rms_norm(p["norm_x"], x, cfg.norm_eps)
        x = x + L.cross_attention(p["cross"], cfg, hx,
                                  (_layer_of(cache["cross_k"], li),
                                   _layer_of(cache["cross_v"], li)),
                                  _layer_of(cache["mem_len"], li))
        new_cache.update({k: cache[k] for k in ("cross_k", "cross_v",
                                                "mem_len")})
    h2 = L.rms_norm(p["norm2"], x, cfg.norm_eps)
    x = x + _mlp_apply(p["mlp"], cfg, g.moe, h2)
    return x, new_cache


def decode_step_paged(params, cfg: ModelConfig, tokens: jax.Array,
                      seq_lens: jax.Array, block_table: jax.Array,
                      write_blocks: jax.Array, write_slots: jax.Array,
                      caches, specs: Dict[str, Any]):
    """One continuous-batching decode step against paged pools.

    tokens: (B,1); seq_lens: (B,) lengths BEFORE this step (== rope position);
    block_table: (B, max_blocks); write_blocks/slots: (B,) current page slot.
    Returns (logits (B,1,V), caches)."""
    positions = seq_lens[:, None].astype(jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    groups = block_groups(cfg)
    new_caches = []
    for gi, g in enumerate(groups):
        def apply_pos(p_i, kind, xx, c_i, li, _g=g):
            return _apply_layer_decode_paged(
                p_i, cfg, _g, kind, xx, positions, c_i, li, block_table,
                seq_lens, write_blocks, write_slots, specs)
        x, nc = _scan_group(params["groups"][gi], cfg, g, x, apply_pos,
                            caches[gi], remat=False, stacked=True)
        new_caches.append(nc)
    return lm_logits(params, cfg, x), tuple(new_caches)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = True) -> jax.Array:
    """Mean next-token cross-entropy over positions with label >= 0."""
    logits = train_forward(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    if cfg.frontend.kind == "vision" and "patches" in batch:
        np_ = batch["patches"].shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], np_), -1, labels.dtype), labels], 1)
    mask = labels >= 0
    lab = jnp.where(mask, labels, 0)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, lab[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
