"""The comparison that decides ``correct``.

The sample is drawn from the seed before the window opens, from the
requests' planned sizes, and always holds the longest request; while the
window runs the harness keeps the logits the D engine samples each of
their decode tokens from. Once the window has closed and the program's
state is freed, the sampled requests that finished run through the plain
float32 reference (``references/<name>.py``) over their prompt and their
served tokens, layer by layer. Three numbers are compared, each the
widest over the sample:

  logit_gap            at each served position, the gap by which the
                       served token's reference logit lies below the
                       reference's best logit there
  first_logit_rel_l2   relative L2 error of the logits the P engine served
                       a first token from (``Request.first_logits``): the
                       chunked prefill
  decode_logit_rel_l2  relative L2 error of the logits the D engine served
                       each later token from, per position: the wire, the
                       re-page and the paged decode step

The control puts the reference, computed in float8 (e4m3, scaled per row
of every matrix-product operand), in the program's place: its gap at a
position is that of the token float8 ranks first, and its logit errors
those of its own logits at the same positions.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

F8_MAX = 448.0


def sample(requests: Sequence[Any], seed: int, tokens: int,
           most: int) -> List[Any]:
    """The longest request by planned size, then a seeded draw, until
    ``tokens`` planned new tokens or ``most`` requests."""
    if not requests:
        return []
    longest = max(requests, key=lambda r: (r.prompt_len + r.max_new_tokens,
                                           r.req_id))
    rest = [r for r in requests if r is not longest]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    out, n = [longest], longest.max_new_tokens
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += rest[i].max_new_tokens
    return out


def finished(samples: Sequence[Any], decode_logits: Dict[str, List]
             ) -> List[Any]:
    """The sampled requests that served every planned token, each with the
    D engine's logits for every token after the first."""
    return [r for r in samples
            if len(r.output_tokens) == r.max_new_tokens
            and len(decode_logits.get(r.req_id, ())) == r.max_new_tokens - 1]


def fp8(x):
    """Round to float8 e4m3 with one scale per row of the last axis."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _identity(x):
    return x


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def rows_rel_l2(got, want) -> float:
    """Widest relative L2 error over the rows of (positions, vocab)."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1)
    ref = jnp.maximum(jnp.linalg.norm(want, axis=-1), 1e-30)
    return float(jnp.max(err / ref))


@lru_cache(maxsize=None)
def _programs(ref, conf_json: str):
    """The reference's jitted layer and head for one configuration, built
    once a process, so that later comparisons reuse their programs."""
    import jax
    import jax.numpy as jnp
    conf = json.loads(conf_json)

    @partial(jax.jit, static_argnums=(0, 1))
    def layer(kind, quant, w, x):
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        return ref.layer(conf, kind, w, x, pos, fp8 if quant else _identity)

    @partial(jax.jit, static_argnums=0)
    def head(quant, g, x):
        return ref.head(conf, g, x, fp8 if quant else _identity)
    return layer, head


def reference_gaps(ref, conf: Dict[str, Any], spec, seed: int,
                   seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                   pad_to: int, control: bool = False, first_logits=None,
                   decode_logits=None) -> Dict[str, Any]:
    """``seqs``: (prompt, served tokens) pairs, each padded at the end to
    ``pad_to`` tokens (the mix's longest sequence: causal attention leaves
    the real positions as they are, and one shape compiles, whatever the
    sample); ``first_logits``: the
    program's logits at each prompt's last position; ``decode_logits``:
    per sequence, the D engine's logits for each served token after the
    first. Returns per sequence the served tokens' gaps and the logits'
    relative L2 errors and, with ``control``, the float8 control's."""
    import jax
    import jax.numpy as jnp

    from bench.common import weights as W

    kinds = spec.layer_kinds()
    toks, rows = [], []
    for prompt, served in seqs:
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks.append(np.pad(full, (0, pad_to - len(full))))
        rows.append(np.arange(len(prompt) - 1, len(full)))

    layer, head = _programs(ref, json.dumps(conf, sort_keys=True))
    g = W.global_weights(spec, seed)
    xs = [ref.embed(conf, g, jnp.asarray(t)) for t in toks]
    streams = {False: xs, True: list(xs) if control else None}
    for li, kind in enumerate(kinds):
        w = W.layer_weights(spec, seed, li)
        for quant, st in streams.items():
            if st is not None:
                streams[quant] = [layer(kind, quant, w, x) for x in st]
        del w
    out = []
    for i, (prompt, served) in enumerate(seqs):
        r = jnp.asarray(rows[i])
        ref_logits = head(False, g, streams[False][i][r])
        best = jnp.max(ref_logits, axis=-1)
        got = jnp.take_along_axis(
            ref_logits, jnp.asarray(served, jnp.int32)[:, None], 1)[:, 0]
        item = {"gaps": np.asarray(best - got, np.float64),
                "ref_argmax_equal": int(np.sum(np.asarray(
                    jnp.argmax(ref_logits, -1)) == np.asarray(served)))}
        ref_first = np.asarray(ref_logits[0])
        if first_logits is not None:
            item["first_rel_l2"] = rel_l2(first_logits[i], ref_first)
        if decode_logits is not None and len(served) > 1:
            item["decode_rel_l2"] = rows_rel_l2(
                np.stack(decode_logits[i]), ref_logits[1:])
        if control:
            c = head(True, g, streams[True][i][r])
            top = jnp.argmax(c, axis=-1)
            cg = best - jnp.take_along_axis(ref_logits, top[:, None], 1)[:, 0]
            item["control_gaps"] = np.asarray(cg, np.float64)
            item["control_first_rel_l2"] = rel_l2(np.asarray(c[0]),
                                                  ref_first)
            if len(served) > 1:
                item["control_decode_rel_l2"] = rows_rel_l2(
                    c[1:], ref_logits[1:])
        out.append(item)

    def worst(key):
        vals = [o[key] for o in out if key in o]
        return float(max(np.max(v) for v in vals)) if vals else None
    return {"per_seq": out, "gap": worst("gaps"),
            "control_gap": worst("control_gaps"),
            "first_rel_l2": worst("first_rel_l2"),
            "control_first_rel_l2": worst("control_first_rel_l2"),
            "decode_rel_l2": worst("decode_rel_l2"),
            "control_decode_rel_l2": worst("control_decode_rel_l2"),
            "tokens": int(sum(len(o["gaps"]) for o in out))}
