"""Dense decoder with grouped-query attention and per-head q/k RMSNorm
(Qwen3): pre-norm residual blocks, half-split RoPE, SwiGLU MLP, an
embedding and an output head of its own, no biases, no embedding
scaling."""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from bench.common.weights import Spec

HI = jax.lax.Precision.HIGHEST
Q = Callable[[jax.Array], jax.Array]


def dims(m: Dict[str, Any]):
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return d, h, m["num_key_value_heads"], m.get("head_dim") or d // h


def spec(m: Dict[str, Any]) -> Spec:
    d, h, kv, hd = dims(m)
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    dt = m["torch_dtype"]
    out_std = 1.0 / math.sqrt(2 * n)
    layer = {
        "norm1": ((d,), dt, ("norm", 0.1)),
        "norm2": ((d,), dt, ("norm", 0.1)),
        "attn/wq": ((d, h, hd), dt, ("normal", 1 / math.sqrt(d))),
        "attn/wk": ((d, kv, hd), dt, ("normal", 1 / math.sqrt(d))),
        "attn/wv": ((d, kv, hd), dt, ("normal", 1 / math.sqrt(d))),
        "attn/wo": ((h, hd, d), dt, ("normal",
                                     out_std / math.sqrt(h * hd))),
        "attn/q_norm": ((hd,), dt, ("norm", 0.1)),
        "attn/k_norm": ((hd,), dt, ("norm", 0.1)),
        "mlp/w_gate": ((d, f), dt, ("normal", 1 / math.sqrt(d))),
        "mlp/w_up": ((d, f), dt, ("normal", 1 / math.sqrt(d))),
        "mlp/w_down": ((f, d), dt, ("normal", out_std / math.sqrt(f))),
    }
    if m["tie_word_embeddings"]:
        raise ValueError("tied embeddings are not supported: run untied")
    glob = {"embed": ((v, d), dt, ("normal", 0.02)),
            "final_norm": ((d,), dt, ("norm", 0.1)),
            "lm_head": ((d, v), dt, ("normal", 1 / math.sqrt(d)))}
    return Spec.make(glob, {"attn": layer}, [("attn", n)])


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w


def rope(x, pos, theta):
    """Half-split rotary embedding. x (S, heads, dim), pos (S,)."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v, scale, block=512):
    """q (S,H,dk), k (S,H,dk), v (S,H,dv): softmax attention over each
    query's own prefix, in query blocks so that scores stay small."""
    s = q.shape[0]
    nb = -(-s // block)
    pad = nb * block - s
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, block, *q.shape[1:])
    kpos = jnp.arange(s)

    def one(args):
        qb, b0 = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = b0 + jnp.arange(block)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (qp, jnp.arange(nb) * block))
    return out.reshape(nb * block, *out.shape[2:])[:s]


def swiglu(x, wg, wu, wd, q: Q):
    x = q(x)
    g = jnp.dot(x, q(wg), precision=HI)
    u = jnp.dot(x, q(wu), precision=HI)
    return jnp.dot(q(jax.nn.silu(g) * u), q(wd), precision=HI)


def embed(m, g, tokens):
    return g["embed"][tokens]


def layer(m, kind, w, x, pos, q: Q):
    d, h, kv, hd = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = q(rms(x, w["norm1"], eps))
    qh = jnp.einsum("sd,dhk->shk", a, q(w["attn/wq"]), precision=HI)
    kh = jnp.einsum("sd,dhk->shk", a, q(w["attn/wk"]), precision=HI)
    vh = jnp.einsum("sd,dhk->shk", a, q(w["attn/wv"]), precision=HI)
    qh = rope(rms(qh, w["attn/q_norm"], eps), pos, theta)
    kh = rope(rms(kh, w["attn/k_norm"], eps), pos, theta)
    rep = h // kv
    kh, vh = jnp.repeat(kh, rep, axis=1), jnp.repeat(vh, rep, axis=1)
    o = causal_attention(qh, kh, vh, 1.0 / math.sqrt(hd))
    x = x + jnp.einsum("shk,hkd->sd", q(o), q(w["attn/wo"]), precision=HI)
    b = rms(x, w["norm2"], eps)
    return x + swiglu(b, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"], q)


def head(m, g, x, q: Q):
    x = rms(x, g["final_norm"], m["rms_norm_eps"])
    return jnp.dot(q(x), q(g["lm_head"]), precision=HI)
