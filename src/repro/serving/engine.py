"""Model-instance engine: prefill, continuous-batching paged decode.

One Engine == one "model instance" in the paper's sense (a P instance, a D
instance, or an integrated instance). Vendor-specific VRAM management is the
engine's ``KVPageSpec`` (block size / layout / dtype); compute dtype and the
logical TP degree used for KV sharding complete the vendor profile.

The engine runs on whatever single device JAX gives its process: the TPU
chip on an accelerator host, the CPU backend in tests.
"""
from __future__ import annotations

import dataclasses
import enum
import logging
import time
import warnings
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PrefillCapabilities
from repro.kernels import ops
from repro.models import model as M
from repro.serving.paged_cache import BlockAllocator, KVPageSpec
from repro.serving.prefix_cache import HostPrefixStore, PrefixStore, hashing
from repro.serving.request import Request, State
from repro.serving.tracing import span

log = logging.getLogger(__name__)


class PrefillMode(enum.Enum):
    """Explicit prefill compute mode (replaces the old chunk_tokens
    None/0/negative sentinel tri-state).

      INCREMENTAL  chunk-at-a-time compute; requires positive chunk_tokens
      MONOLITHIC   whole-prompt compute in one pass (the wire may still
                   stream in chunk_tokens slices)
      AUTO         incremental when the family supports it and
                   chunk_tokens subdivides the prompt, else monolithic
    """
    INCREMENTAL = "incremental"
    MONOLITHIC = "monolithic"
    AUTO = "auto"


class PrefillModeError(ValueError):
    """A requested prefill mode is unsupported for this engine/request —
    typed so callers can distinguish a capability mismatch from generic
    argument errors."""


# families already warned about silent prefix-replay degradation (log
# once per family, count every occurrence in EngineStats)
_RESUME_WARNED: set = set()


def page_specs_for(cfg: ModelConfig, block_size: int, layout: str,
                   dtype: str) -> Dict[str, KVPageSpec]:
    if cfg.attention_kind == "mla":
        m = cfg.mla
        return {
            "ckv": KVPageSpec(block_size, layout, dtype, 1, m.kv_lora_rank),
            "kpe": KVPageSpec(block_size, layout, dtype, 1, m.qk_rope_head_dim),
        }
    return {"kv": KVPageSpec(block_size, layout, dtype,
                             max(cfg.num_kv_heads, 1), cfg.hd)}


@dataclasses.dataclass(frozen=True)
class VendorProfile:
    """The 'vendor' of an instance — everything the heterogeneous compat
    module must align across instances."""
    name: str
    block_size: int = 16
    layout: str = "nbhd"
    kv_dtype: str = "float32"
    tp: int = 1                 # logical TP degree of stored KV shards
    hardware: str = "tpu-v5e"   # planner HardwareSpec key


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    prefill_chunks: int = 0         # compute chunks (1 per monolithic prefill)
    decode_steps: int = 0
    decode_tokens: int = 0
    decode_kv_pages: int = 0        # KV pages below the active slots'
    #                                 lengths, summed over decode steps
    prefill_seconds: float = 0.0
    failures_injected: int = 0
    prefix_cached_tokens: int = 0   # prompt tokens replayed from the P-side
    #                                 host prefix store instead of recomputed
    # measured decode-stall: prefill compute seconds spent on an integrated
    # (role="both") engine while decode-ready sequences sat waiting — the
    # interference disaggregation removes (~0 on pure P or pure D roles)
    contention_stall_seconds: float = 0.0
    # requests that wanted prefix-cache replay / mid-stream resume but the
    # family cannot support it — previously a silent full recompute
    resume_unsupported: int = 0
    # prompt tokens whose compute was skipped via a mid-stream snapshot
    # resume after a failure (state-carrying families)
    resumed_tokens: int = 0

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def _chronological(arr: np.ndarray, pos: np.ndarray) -> Tuple[np.ndarray, int]:
    """Ring-buffer shard (count, cap, ...) + pos (count, cap) →
    chronological (count, cap, ...) and the absolute start position."""
    order = np.argsort(pos[0])                    # same order across layers
    return arr[:, order], int(pos[0][order[0]])


def kv_entries_with_start(package_kv: List[Tuple]) -> List[Tuple]:
    """Normalize a prefill package's KV entries to chronological order with
    an absolute ``start`` position — the canonical pre-wire form that both
    the monolithic encoder and the chunk splitter consume.

    Returns [(kind, gi, pi, entry)] where entry holds contiguous arrays of
    shape (count, S', ...) covering absolute positions [start, start+S')."""
    out = []
    for kind, gi, pi, entry in package_kv:
        if kind == "mla":
            out.append((kind, gi, pi, {"ckv": np.asarray(entry["ckv"]),
                                       "kpe": np.asarray(entry["kpe"]),
                                       "start": 0}))
            continue
        k, v = np.asarray(entry["k"]), np.asarray(entry["v"])
        start = 0
        if "pos" in entry and k.shape[1] < np.max(entry["pos"]) + 1:
            pos = np.asarray(entry["pos"])
            k, start = _chronological(k, pos)
            v, _ = _chronological(v, pos)
        out.append((kind, gi, pi, {"k": k, "v": v, "start": start}))
    return out


def slice_kv_entries(entries: List[Tuple], w0: int, w1: int) -> List[Tuple]:
    """Restrict normalized entries to the absolute token window [w0, w1)."""
    out = []
    for kind, gi, pi, ent in entries:
        start = ent["start"]
        arrs = {n: a for n, a in ent.items() if n != "start"}
        length = next(iter(arrs.values())).shape[1]
        lo = max(w0, start)
        hi = min(w1, start + length)
        if hi <= lo:
            continue
        sl = {n: a[:, lo - start:hi - start] for n, a in arrs.items()}
        sl["start"] = lo
        out.append((kind, gi, pi, sl))
    return out


class PrefillStream:
    """Resumable chunked prefill on one P engine (paper §III-B overlap).

    ``next_chunk()`` yields KV chunk packages ``{"kv": entries, "start",
    "length"}`` until exhausted (then returns ``None``). Two compute modes
    (:class:`PrefillMode`):

      * *incremental* — every family runs the prompt through the decode
        path over a dense full-capacity cache, one chunk of tokens per
        call, so each chunk's KV can hit the wire while the next chunk
        computes (Mooncake-style streaming). Sliding-window families chunk
        with window-aware masking and ship only positions above the window
        floor; recurrent/SSM layers carry their state across chunks (and
        can snapshot/resume mid-stream); enc-dec and vision families run a
        non-resumable encoder/embedding preamble, then chunk the sequence.
      * *monolithic*  — whole-prompt compute in one pass on the first
        call; the wire still streams in ``chunk_tokens`` slices.

    ``first_token`` / ``tail_package()`` (states, cross-attention memory)
    become available once the final chunk has been produced."""

    def __init__(self, engine: "Engine", req: Request,
                 chunk_tokens: Optional[int] = None,
                 chunked_compute: Optional[bool] = None,
                 mode: Optional[PrefillMode] = None,
                 resume: Optional[Dict[str, Any]] = None):
        self.engine = engine
        self.req = req
        self.caps: PrefillCapabilities = engine.prefill_capabilities()
        patches = req.patches.shape[0] if req.patches is not None else 0
        self.seq_len = req.prompt_len + patches
        if chunk_tokens is not None and chunk_tokens <= 0:
            warnings.warn(
                "chunk_tokens <= 0 as a monolithic sentinel is deprecated; "
                "pass mode=PrefillMode.MONOLITHIC", DeprecationWarning,
                stacklevel=3)
            chunk_tokens = None               # deprecated shim
        self.chunk_tokens = chunk_tokens
        if mode is None:
            # deprecated bool kwarg shim: True/False force the mode, None
            # keeps the automatic choice
            if chunked_compute is None:
                mode = PrefillMode.AUTO
            else:
                mode = PrefillMode.INCREMENTAL if chunked_compute \
                    else PrefillMode.MONOLITHIC
        if not isinstance(mode, PrefillMode):
            raise PrefillModeError(f"unknown prefill mode {mode!r}")
        self.mode = mode
        if mode is PrefillMode.INCREMENTAL:
            if not self.caps.incremental:
                raise PrefillModeError(
                    f"{engine.cfg.name}: incremental chunked prefill is not "
                    f"supported for family {self.caps.family!r}")
            if chunk_tokens is None:
                raise PrefillModeError(
                    f"{engine.cfg.name}: PrefillMode.INCREMENTAL requires "
                    "positive chunk_tokens")
            self.chunked_compute = True
        elif mode is PrefillMode.MONOLITHIC:
            self.chunked_compute = False
        else:
            self.chunked_compute = (self.caps.incremental
                                    and chunk_tokens is not None
                                    and chunk_tokens < self.seq_len)
        self.first_token: Optional[int] = None
        self.chunks_emitted = 0
        self._next_start = 0
        self._wire_sent = 0                           # wire progress (abs pos)
        self._tail: Optional[Dict[str, Any]] = None
        self._entries: Optional[List[Tuple]] = None   # monolithic mode
        self._caches = None                           # incremental mode
        self._emb = None                              # vision: merged embeds
        # mid-stream snapshot resume (state-carrying families): skip the
        # already-computed prefix, re-ship the wire from the window floor
        self._resume: Optional[Dict[str, Any]] = None
        if resume is not None:
            if not self.caps.resumable:
                engine._note_resume_unsupported()
                raise PrefillModeError(
                    f"{engine.cfg.name}: mid-stream resume is not supported "
                    f"for family {self.caps.family!r}")
            if not self.chunked_compute:
                raise PrefillModeError(
                    "resume requires incremental chunked compute")
            if int(resume.get("seq_len", -1)) != self.seq_len:
                raise PrefillModeError(
                    "resume snapshot does not match this request")
            self._resume = resume
        # P-side shared-prefix reuse: replay cached chunks instead of
        # recomputing them, and seed the dense cache so compute resumes
        # at the divergence point. Only safe when every cached row stays
        # attendable (caps.prefix_cache); the final token is always
        # computed (first_token).
        self.prefix_tokens = 0
        self._p_store = None
        self._cached_entries: Optional[List[Tuple]] = None
        self._collect: Optional[List[Tuple]] = None
        store = getattr(engine, "host_prefix_store", None)
        if store is not None and self._resume is None:
            if self.chunked_compute and self.caps.prefix_cache:
                self._p_store = store
                self._collect = []
                hit, entries = store.match(req.prompt, self.seq_len - 1)
                if hit > 0:
                    self.prefix_tokens = hit
                    self._cached_entries = entries
            else:
                # a prefix store exists but this stream cannot replay from
                # it — previously a silent full recompute
                engine._note_resume_unsupported()

    @property
    def done(self) -> bool:
        return self._next_start >= self.seq_len and self.chunks_emitted > 0

    def tail_package(self) -> Dict[str, Any]:
        assert self.done, "tail_package before stream exhausted"
        return self._tail if self._tail is not None \
            else {"states": [], "cross": []}

    def next_chunk(self) -> Optional[Dict[str, Any]]:
        if self.done:
            return None
        if self._next_start < self.prefix_tokens:
            chunk = self._next_cached()
        elif self.chunked_compute:
            chunk = self._next_incremental()
        else:
            chunk = self._next_monolithic()
        self.chunks_emitted += 1
        if self._collect is not None:
            self._collect.extend(chunk["kv"])
            if self._next_start >= self.seq_len:
                self._p_store.insert_prompt(self.req.prompt, self._collect,
                                            self.seq_len)
        return chunk

    # -- replay from the host prefix store ------------------------------- #
    def _next_cached(self) -> Dict[str, Any]:
        eng = self.engine
        if eng.failed:
            raise RuntimeError(f"instance {eng.name} is down")
        c0 = self._next_start
        c1 = min(c0 + (self.chunk_tokens or self.prefix_tokens),
                 self.prefix_tokens)
        self._next_start = c1
        self._wire_sent = c1
        eng.stats.prefix_cached_tokens += c1 - c0
        return {"kv": slice_kv_entries(self._cached_entries, c0, c1),
                "start": c0, "length": c1 - c0, "compute_seconds": 0.0}

    # -- monolithic compute, chunked wire ------------------------------- #
    def _next_monolithic(self) -> Dict[str, Any]:
        compute_s = 0.0
        if self._entries is None:
            t0 = time.perf_counter()
            package = self.engine.prefill(self.req)
            compute_s = time.perf_counter() - t0
            self.first_token = package["first_token"]
            self._tail = {"states": package["states"],
                          "cross": package["cross"]}
            self._entries = kv_entries_with_start(package["kv"])
            if self._entries:
                # ring-buffer (sliding) entries only cover the last window
                # of the prompt — don't ship empty chunks for the evicted
                # prefix, start streaming at the first position on the wire
                self._next_start = min(
                    min(e[3]["start"] for e in self._entries), self.seq_len)
        w0 = self._next_start
        if not self._entries or self.chunk_tokens is None:
            w1 = self.seq_len        # states-only: nothing to chunk
        else:
            w1 = min(w0 + self.chunk_tokens, self.seq_len)
        self._next_start = w1
        return {"kv": slice_kv_entries(self._entries, w0, w1),
                "start": w0, "length": w1 - w0,
                "compute_seconds": compute_s}

    # -- incremental compute (all families) ------------------------------ #
    @property
    def _wire_floor(self) -> int:
        """First absolute position the D side can still attend. Sliding-
        window KV below ``seq_len - window`` is dead weight — never ship."""
        if self.caps.window:
            return max(0, self.seq_len - self.caps.window)
        return 0

    def _next_incremental(self) -> Dict[str, Any]:
        """Compute exactly ONE chunk per call (one unit of per-tick P
        work). When the chunk produced nothing for the wire — pure-SSM
        layers, or sliding-window positions below the wire floor — the
        returned package is a zero-``length`` *progress marker* that
        drivers account but never send."""
        eng, req = self.engine, self.req
        if eng.failed:
            raise RuntimeError(f"instance {eng.name} is down")
        t0 = time.perf_counter()
        if self._caches is None:
            self._setup_incremental()
        c0 = self._next_start
        c1 = min(c0 + self.chunk_tokens, self.seq_len)
        with span("pd.prefill.chunk", req=req.req_id, tokens=c1 - c0):
            logits = self._compute_chunk(c0, c1)
        self._next_start = c1
        eng.stats.prefill_tokens += c1 - c0
        eng.stats.prefill_chunks += 1
        if c1 == self.seq_len:
            with span("pd.prefill.first_token", req=req.req_id):
                self.first_token = eng._sample_first(
                    np.asarray(logits[:, -1]), req)
            self._tail = self._extract_tail()
        dt = time.perf_counter() - t0
        eng._note_prefill_compute(dt)
        if not self.caps.kv_on_wire:
            # pure-SSM: no attention KV ever lands on the wire — the one
            # final package declares full coverage, states ride the tail
            if c1 < self.seq_len:
                return {"kv": [], "start": c0, "length": 0,
                        "compute_seconds": dt}
            return {"kv": [], "start": 0, "length": self.seq_len,
                    "compute_seconds": dt}
        w0 = max(self._wire_sent, self._wire_floor)
        if c1 <= w0:
            return {"kv": [], "start": c0, "length": 0,
                    "compute_seconds": dt}
        with span("pd.handoff.extract", req=req.req_id):
            entries = self._extract_entries(w0, c1)
        self._wire_sent = c1
        return {"kv": entries, "start": w0, "length": c1 - w0,
                "compute_seconds": dt}

    def _setup_incremental(self) -> None:
        eng, cfg, req = self.engine, self.engine.cfg, self.req
        # capacity rounded to a chunk multiple: prompts within the same
        # chunk bucket share one compiled cache shape (_chunk_fn traces
        # per (cache capacity, chunk length)); entries past seq_len stay
        # pos=-1 and are masked. full_capacity keeps sliding-window layers
        # dense (slot == position) — the window is enforced by attention
        # masking, never by ring eviction mid-prompt.
        cap = -(-self.seq_len // self.chunk_tokens) * self.chunk_tokens
        mem = eng.mem_len if cfg.is_enc_dec else 0
        self._caches = M.init_caches(cfg, 1, cap, cfg.cdtype, mem_len=mem,
                                     full_capacity=True)
        if req.frames is not None:
            # non-resumable encoder preamble: run the encoder on P once,
            # seed every decoder layer's cross-attention K/V
            memory = eng._encode_fn(eng.params, jnp.asarray(req.frames)[None])
            self._seed_cross(memory)
        if req.patches is not None:
            # vision prefix: merge patch + token embeddings once; chunks
            # slice the merged sequence (absolute positions span both)
            self._emb = eng._embed_fn(
                eng.params, jnp.asarray(req.patches)[None],
                jnp.asarray(req.prompt, jnp.int32)[None])
        if self.prefix_tokens:
            self._caches = self._preload_caches(self._caches)
        if self._resume is not None:
            self._apply_resume(self._resume)

    def _seed_cross(self, memory: jax.Array) -> None:
        eng = self.engine
        cross = eng._cross_kv_fn(eng.params, memory)
        mem = memory.shape[1]
        caches = [list(g) for g in self._caches]
        for (gi, pi), (mk, mv) in cross.items():
            c = dict(caches[gi][pi])
            c["cross_k"] = c["cross_k"].at[:, :, :mem].set(
                mk.astype(c["cross_k"].dtype))
            c["cross_v"] = c["cross_v"].at[:, :, :mem].set(
                mv.astype(c["cross_v"].dtype))
            c["mem_len"] = jnp.full_like(c["mem_len"], mem)
            caches[gi][pi] = c
        self._caches = tuple(tuple(g) for g in caches)

    def _compute_chunk(self, c0: int, c1: int) -> jax.Array:
        eng = self.engine
        positions = jnp.arange(c0, c1, dtype=jnp.int32)[None]
        if self._emb is not None:
            logits, self._caches = eng._chunk_embeds_fn(
                eng.params, self._emb[:, c0:c1], positions, self._caches)
        else:
            tokens = jnp.asarray(self.req.prompt[c0:c1], jnp.int32)[None]
            logits, self._caches = eng._chunk_fn(eng.params, tokens,
                                                 positions, self._caches)
        return logits

    def _extract_entries(self, w0: int, w1: int) -> List[Tuple]:
        """Wire entries for absolute positions [w0, w1) — slot == position
        because incremental caches are full-capacity."""
        entries = []
        for gi, g in enumerate(M.block_groups(self.engine.cfg)):
            for pi, kind in enumerate(g.kinds):
                if kind in ("ssd", "rglru"):
                    continue
                c = self._caches[gi][pi]
                self_c = c["self"] if isinstance(c, dict) else c
                if self.caps.latent_kv:
                    entries.append(("mla", gi, pi, {
                        "ckv": np.asarray(self_c.ckv[:, 0, w0:w1]),
                        "kpe": np.asarray(self_c.kpe[:, 0, w0:w1]),
                        "start": w0}))
                else:
                    entries.append(("kv", gi, pi, {
                        "k": np.asarray(self_c.k[:, 0, w0:w1]),
                        "v": np.asarray(self_c.v[:, 0, w0:w1]),
                        "start": w0}))
        return entries

    def _extract_tail(self) -> Dict[str, Any]:
        """States / cross-KV that ride with finalize (same shape as the
        monolithic ``_package_handoff`` tail)."""
        states, cross = [], []
        for gi, g in enumerate(M.block_groups(self.engine.cfg)):
            for pi, kind in enumerate(g.kinds):
                c = self._caches[gi][pi]
                if kind in ("ssd", "rglru"):
                    states.append(("state", gi, pi,
                                   jax.tree.map(lambda x: x[:, 0], c)))
                elif isinstance(c, dict):                  # enc-dec cross
                    cross.append((gi, pi, {
                        "cross_k": c["cross_k"][:, 0],
                        "cross_v": c["cross_v"][:, 0],
                        "mem_len": c["mem_len"][:, 0]}))
        return {"states": states, "cross": cross}

    # -- mid-stream snapshot resume (state-carrying families) ------------ #
    def snapshot(self) -> Optional[Dict[str, Any]]:
        """Portable mid-stream progress: recurrent/SSM layer states plus
        the KV rows still inside the sliding window. Replaying it on a
        fresh stream (same request, same params) skips recomputing the
        first ``next_start`` prompt tokens after a failure."""
        if not (self.caps.resumable and self.chunked_compute):
            return None
        if self._caches is None or self._next_start <= 0 or self.done:
            return None
        ns = self._next_start
        lo = max(0, ns - self.caps.window) if self.caps.window else ns
        states, kv = [], []
        for gi, g in enumerate(M.block_groups(self.engine.cfg)):
            for pi, kind in enumerate(g.kinds):
                c = self._caches[gi][pi]
                if kind in ("ssd", "rglru"):
                    states.append((gi, pi, jax.tree.map(np.asarray, c)))
                else:
                    self_c = c["self"] if isinstance(c, dict) else c
                    kv.append((gi, pi, {
                        "k": np.asarray(self_c.k[:, :, lo:ns]),
                        "v": np.asarray(self_c.v[:, :, lo:ns])}))
        return {"seq_len": self.seq_len, "next_start": ns,
                "row_start": lo, "states": states, "kv": kv}

    def _apply_resume(self, snap: Dict[str, Any]) -> None:
        ns = int(snap["next_start"])
        s0 = int(snap["row_start"])
        caches = [list(g) for g in self._caches]
        for gi, pi, st in snap["states"]:
            old = caches[gi][pi]
            caches[gi][pi] = jax.tree.map(
                lambda o, n: jnp.asarray(n, o.dtype), old, st)
        for gi, pi, ent in snap["kv"]:
            c = caches[gi][pi]
            self_c = c["self"] if isinstance(c, dict) else c
            pos = jnp.broadcast_to(
                jnp.arange(s0, ns, dtype=self_c.pos.dtype),
                self_c.pos[:, :, s0:ns].shape)
            new_self = dataclasses.replace(
                self_c,
                k=self_c.k.at[:, :, s0:ns].set(
                    jnp.asarray(ent["k"], self_c.k.dtype)),
                v=self_c.v.at[:, :, s0:ns].set(
                    jnp.asarray(ent["v"], self_c.v.dtype)),
                pos=self_c.pos.at[:, :, s0:ns].set(pos))
            caches[gi][pi] = ({**c, "self": new_self}
                              if isinstance(c, dict) else new_self)
        self._caches = tuple(tuple(g) for g in caches)
        self._next_start = ns
        self.engine.stats.resumed_tokens += ns

    def _preload_caches(self, caches):
        """Seed the dense chunked-prefill cache with the replayed prefix
        KV so computed chunks resume at ``prefix_tokens`` with the exact
        bits a cold run would have produced. ``pos`` rows must carry the
        real absolute positions — attention masks on them."""
        caches = [list(g) for g in caches]
        for kind, gi, pi, ent in self._cached_entries:
            c = caches[gi][pi]
            s0 = int(ent["start"])
            if kind == "mla":
                n = int(np.asarray(ent["ckv"]).shape[1])
                c = dataclasses.replace(
                    c,
                    ckv=c.ckv.at[:, 0, s0:s0 + n].set(
                        jnp.asarray(ent["ckv"]).astype(c.ckv.dtype)),
                    kpe=c.kpe.at[:, 0, s0:s0 + n].set(
                        jnp.asarray(ent["kpe"]).astype(c.kpe.dtype)),
                    pos=c.pos.at[:, 0, s0:s0 + n].set(
                        jnp.arange(s0, s0 + n, dtype=c.pos.dtype)))
            else:
                n = int(np.asarray(ent["k"]).shape[1])
                c = dataclasses.replace(
                    c,
                    k=c.k.at[:, 0, s0:s0 + n].set(
                        jnp.asarray(ent["k"]).astype(c.k.dtype)),
                    v=c.v.at[:, 0, s0:s0 + n].set(
                        jnp.asarray(ent["v"]).astype(c.v.dtype)),
                    pos=c.pos.at[:, 0, s0:s0 + n].set(
                        jnp.arange(s0, s0 + n, dtype=c.pos.dtype)))
            caches[gi][pi] = c
        return tuple(tuple(g) for g in caches)


class Engine:
    """One model instance with paged KV and slot-based continuous batching."""

    def __init__(self, name: str, cfg: ModelConfig, params,
                 vendor: VendorProfile, *, num_blocks: int = 256,
                 max_batch: int = 8, max_seq_len: int = 512,
                 mem_len: int = 0, role: str = "both",
                 prefix_cache: bool = False):
        self.name = name
        self.cfg = cfg
        self.params = params
        self.vendor = vendor
        self.role = role
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.mem_len = mem_len or (cfg.max_source_len if cfg.is_enc_dec else 0)
        self.specs = page_specs_for(cfg, vendor.block_size, vendor.layout,
                                    vendor.kv_dtype)
        self.block_size = vendor.block_size
        # the decode attention this engine's pool takes (span attribute)
        self.decode_attention = (ops.paged_decode_path(self.specs["kv"])
                                 if "kv" in self.specs else "ref")
        self.max_blocks_per_seq = -(-max_seq_len // vendor.block_size)
        self.allocator = BlockAllocator(num_blocks)
        self.allocator.allocate("__scratch__", 1)   # trash page for idle slots
        self._scratch_block = self.allocator.blocks_of("__scratch__")[0]
        # a prefill-only instance never decodes: it holds no paged pool
        # (its KV leaves through the wire, the D side owns the pages)
        self.caches = None if role == "prefill" else M.init_paged_caches(
            cfg, self.specs, num_blocks, batch=max_batch, mem_len=self.mem_len)
        # slot bookkeeping (host side)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        # a slot is reserved when slot_req is set; ready once its KV has
        # fully landed (streamed chunks materialized + first token known)
        self.slot_ready: List[bool] = [False] * max_batch
        self.block_tables = np.full((max_batch, self.max_blocks_per_seq),
                                    self._scratch_block, np.int32)
        self.seq_lens = np.zeros((max_batch,), np.int32)
        self.last_token = np.zeros((max_batch,), np.int32)
        self.stats = EngineStats()
        self.failed = False
        # shared-prefix KV cache (opt-in): the decode role indexes pool
        # pages by hash chain; the prefill role keeps host-side wire
        # entries to replay instead of recomputing
        self.prefix_cache_enabled = bool(prefix_cache)
        self.prefix_store: Optional[PrefixStore] = None
        self.host_prefix_store: Optional[HostPrefixStore] = None
        if prefix_cache and role in ("decode", "both"):
            self.prefix_store = PrefixStore(self.allocator, self.block_size)
        if prefix_cache and role in ("prefill", "both"):
            self.host_prefix_store = HostPrefixStore(self.block_size)
        # per-slot prefix tokens already resident at reservation time —
        # the handoff skips exactly this many tokens on the wire
        self.slot_prefix_tokens: List[int] = [0] * max_batch
        self._rng = np.random.default_rng(abs(hash(name)) % (2 ** 31))
        self._build_jits()

    # ------------------------------------------------------------------ #
    def _build_jits(self) -> None:
        cfg = self.cfg

        @partial(jax.jit, static_argnames=("prompt_len",))
        def _prefill(params, inputs, prompt_len):
            caches = M.init_caches(cfg, inputs["tokens"].shape[0], prompt_len,
                                   cfg.cdtype, mem_len=self.mem_len)
            return M.prefill(params, cfg, inputs, caches)

        # the pools are donated: each step updates them in place instead of
        # writing a second copy of the whole pool
        @partial(jax.jit, donate_argnames=("caches",))
        def _decode(params, tokens, seq_lens, block_table, write_blocks,
                    write_slots, caches):
            return M.decode_step_paged(params, cfg, tokens, seq_lens,
                                       block_table, write_blocks, write_slots,
                                       caches, self.specs)

        def _place(caches, updates, slot):
            """Write per-sequence rows (states / cross kv) into batch axis 1."""
            def upd(c, u):
                return c.at[:, slot].set(u.astype(c.dtype))
            return jax.tree.map(upd, caches, updates)

        @jax.jit
        def _prefill_chunk(params, tokens, positions, caches):
            """One chunk of incremental prefill: the decode path over a
            dense prompt-capacity cache (retraced per distinct chunk len)."""
            return M.decode_step(params, cfg, tokens, positions, caches)

        @jax.jit
        def _prefill_chunk_embeds(params, embeds, positions, caches):
            """Chunked prefill over precomputed embeddings (vision prefix)."""
            return M.decode_step_embeds(params, cfg, embeds, positions, caches)

        @jax.jit
        def _encode(params, frames):
            """Encoder preamble of a chunked enc-dec prefill."""
            return M.encode(params, cfg, frames)

        @jax.jit
        def _cross_kv(params, memory):
            return M.encoder_cross_kv(params, cfg, memory)

        @jax.jit
        def _merged_embeds(params, patches, tokens):
            emb = M.embed_tokens(params, cfg, tokens)
            return jnp.concatenate([patches.astype(cfg.cdtype), emb], axis=1)

        self._prefill_fn = _prefill
        self._decode_fn = _decode
        self._chunk_fn = _prefill_chunk
        self._chunk_embeds_fn = _prefill_chunk_embeds
        self._encode_fn = _encode
        self._cross_kv_fn = _cross_kv
        self._embed_fn = _merged_embeds
        self._place_fn = jax.jit(_place, donate_argnums=(0,))

    @property
    def supports_chunked_prefill(self) -> bool:
        """Incremental chunk compute is a model-structure property — see
        ModelConfig.prefill_capabilities."""
        return self.prefill_capabilities().incremental

    def prefill_capabilities(self) -> PrefillCapabilities:
        """What this instance's family supports on the prefill path — a
        frozen descriptor consumed (not introspected) by the scheduler,
        router and planner, mirroring the connector ``capabilities()``
        convention."""
        return self.cfg.prefill_capabilities()

    def prefill_stream(self, req: Request,
                       chunk_tokens: Optional[int] = None,
                       chunked_compute: Optional[bool] = None,
                       mode: Optional[PrefillMode] = None,
                       resume: Optional[Dict[str, Any]] = None
                       ) -> PrefillStream:
        """Start a resumable (chunked) prefill for ``req``."""
        return PrefillStream(self, req, chunk_tokens, chunked_compute,
                             mode=mode, resume=resume)

    # ------------------------------------------------------------------ #
    # Prefill (P role)
    # ------------------------------------------------------------------ #
    def prefill(self, req: Request) -> Dict[str, Any]:
        """Run prefill for one request; returns the handoff package:
        {"first_token", "kv": per-group list, "states", "cross", "logits"}.

        The KV part stays in *this* engine's canonical per-layer form — the
        transfer module converts it to the wire and the D instance's format.
        """
        if self.failed:
            raise RuntimeError(f"instance {self.name} is down")
        t0 = time.perf_counter()
        cfg = self.cfg
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        inputs: Dict[str, Any] = {"tokens": tokens}
        if req.frames is not None:
            inputs["frames"] = jnp.asarray(req.frames)[None]
        if req.patches is not None:
            inputs["patches"] = jnp.asarray(req.patches)[None]
        plen = req.prompt_len + (req.patches.shape[0] if req.patches is not None else 0)
        last_logits, caches = self._prefill_fn(self.params, inputs, plen)
        first_token = self._sample_first(np.asarray(last_logits), req)
        package = self._package_handoff(caches, plen)
        package["first_token"] = first_token
        package["seq_len"] = plen
        self.stats.prefill_tokens += plen
        self.stats.prefill_chunks += 1
        self._note_prefill_compute(time.perf_counter() - t0)
        return package

    def _note_prefill_compute(self, dt: float) -> None:
        """Account prefill compute time. On an integrated (role="both")
        instance, compute spent while decode-ready sequences sat waiting
        is measured decode-stall — the interference disaggregation
        removes (~0 on pure P or pure D roles)."""
        self.stats.prefill_seconds += dt
        if self.role == "both" and any(
                r is not None and self.slot_ready[i]
                for i, r in enumerate(self.slot_req)):
            self.stats.contention_stall_seconds += dt

    def _note_resume_unsupported(self) -> None:
        """A request wanted prefix-cache replay or mid-stream resume but
        this family cannot support it — count every occurrence, log once
        per (family, attention_kind)."""
        self.stats.resume_unsupported += 1
        key = (self.cfg.family, self.cfg.attention_kind)
        if key not in _RESUME_WARNED:
            _RESUME_WARNED.add(key)
            log.warning(
                "family %s (attention=%s): prefix-cache replay / mid-stream "
                "resume unsupported — falling back to full recompute", *key)

    def _package_handoff(self, caches, seq_len: int) -> Dict[str, Any]:
        """Extract per-layer canonical KV (+ states / cross) for transfer."""
        cfg = self.cfg
        groups = M.block_groups(cfg)
        kv, states, cross = [], [], []
        for gi, g in enumerate(groups):
            for pi, kind in enumerate(g.kinds):
                c = caches[gi][pi]
                if kind == "ssd" or kind == "rglru":
                    states.append(("state", gi, pi,
                                   jax.tree.map(lambda x: x[:, 0], c)))
                    continue
                self_c = c["self"] if isinstance(c, dict) else c
                if cfg.attention_kind == "mla":
                    kv.append(("mla", gi, pi, {
                        "ckv": self_c.ckv[:, 0, :seq_len],       # (count,S,lora)
                        "kpe": self_c.kpe[:, 0, :seq_len]}))
                else:
                    cap = self_c.k.shape[2]
                    s = min(seq_len, cap)
                    kv.append(("kv", gi, pi, {
                        # (count, S', kv, hd) — last `cap` tokens for SWA
                        "k": self_c.k[:, 0, :s] if cap >= seq_len else self_c.k[:, 0],
                        "v": self_c.v[:, 0, :s] if cap >= seq_len else self_c.v[:, 0],
                        "pos": self_c.pos[:, 0]}))
                if isinstance(c, dict):                          # enc-dec cross
                    cross.append((gi, pi, {
                        "cross_k": c["cross_k"][:, 0],
                        "cross_v": c["cross_v"][:, 0],
                        "mem_len": c["mem_len"][:, 0]}))
        return {"kv": kv, "states": states, "cross": cross}

    # ------------------------------------------------------------------ #
    # Decode (D role)
    # ------------------------------------------------------------------ #
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def load(self) -> float:
        """Outstanding work (for the global scheduler's load-aware routing)."""
        active = sum(1 for r in self.slot_req if r is not None)
        return active / self.max_batch

    def can_admit(self, seq_len: int, new_tokens: int) -> bool:
        need = -(-(seq_len + new_tokens) // self.block_size)
        free = self.allocator.free_blocks
        if self.prefix_store is not None:
            # zero-ref cached blocks are reclaimable on demand
            free += self.prefix_store.evictable_blocks()
        return (not self.failed and len(self.free_slots()) > 0
                and free >= need
                and seq_len + new_tokens <= self.max_seq_len)

    def _prefix_eligible(self, req: Request) -> bool:
        """Prefix reuse needs every cached row to stay attendable across
        the whole decode (caps.prefix_cache) and a pure-token prompt —
        mirrors PrefillStream's gate."""
        return (self.prefill_capabilities().prefix_cache
                and req.patches is None and req.frames is None)

    def reserve_sequence(self, req: Request, seq_len: int, *,
                         use_prefix_cache: bool = False
                         ) -> Tuple[int, np.ndarray]:
        """Claim a decode slot + paged blocks for an in-flight handoff.

        The slot is occupied (counts toward load, not free) but NOT decoded
        until ``activate_sequence`` — streamed KV chunks land in between.

        With ``use_prefix_cache`` (and a store), the block table's head
        borrows the store's pages for the longest cached prefix — pinned,
        read-shared — plus an optional copy-on-write divergence block;
        ``slot_prefix_tokens[slot]`` records how many leading tokens need
        no wire transfer. All writes (RMW re-page and decode appends) land
        at positions ≥ that count, i.e. strictly in private blocks."""
        if self.failed:
            raise RuntimeError(f"instance {self.name} is down")
        slot = self.free_slots()[0]
        nblocks = -(-(seq_len + req.max_new_tokens) // self.block_size)
        nblocks = min(nblocks, self.max_blocks_per_seq)
        store = self.prefix_store
        prefix_tokens = 0
        if (use_prefix_cache and store is not None
                and self._prefix_eligible(req)):
            # reuse limit seq_len-1: P always computes ≥ 1 trailing token
            # (it must sample first_token from real logits)
            match = store.match(req.prompt, min(seq_len, req.prompt_len) - 1)
            match = match.truncated(max(nblocks - 1, 0), self.block_size)
            store.acquire(match, req.req_id)
            shared = list(match.block_ids)
            need = nblocks - len(shared)
            short = need - self.allocator.free_blocks
            if short > 0:
                store.evict(short)
            try:
                private = self.allocator.allocate(req.req_id, need)
            except MemoryError:
                store.release_seq(req.req_id)
                raise
            if match.cow_src is not None and need > 0:
                # mid-block divergence: private copy of the source page,
                # valid up to match.tokens — later rows are overwritten
                # by the stream's RMW re-page
                self._copy_block(match.cow_src, private[0])
            prefix_tokens = match.tokens
            block_ids = shared + private
        else:
            if (use_prefix_cache and store is not None
                    and not self._prefix_eligible(req)):
                # the router asked for prefix reuse but this family's rows
                # can't be replayed — previously a silent full recompute
                self._note_resume_unsupported()
            short = nblocks - self.allocator.free_blocks
            if store is not None and short > 0:
                store.evict(short)
            block_ids = self.allocator.allocate(req.req_id, nblocks)
        self.block_tables[slot, :] = self._scratch_block
        self.block_tables[slot, :nblocks] = block_ids
        self.seq_lens[slot] = 0
        self.slot_req[slot] = req
        self.slot_ready[slot] = False
        self.slot_prefix_tokens[slot] = prefix_tokens
        return slot, np.asarray(block_ids, np.int32)

    def _copy_block(self, src: int, dst: int) -> None:
        """Copy one physical page across every paged pool (COW)."""
        caches = [list(g) for g in self.caches]
        for gi, g in enumerate(caches):
            for pi, c in enumerate(g):
                if not isinstance(c, dict):
                    continue
                new = dict(c)
                changed = False
                for name, arr in c.items():
                    if name.endswith("_pool"):
                        # pools stack layers on axis 0: (count, blocks, ...)
                        new[name] = arr.at[:, dst].set(arr[:, src])
                        changed = True
                if changed:
                    g[pi] = new
        self.caches = tuple(tuple(g) for g in caches)

    def activate_sequence(self, slot: int, first_token: int,
                          seq_len: int) -> None:
        """All KV landed — the slot joins continuous batching next step.

        With a prefix store, the sequence's full prompt blocks are adopted
        into it here (ownership transfer, still pinned for this sequence):
        every block is fully written by now, and decode appends only at
        positions ≥ seq_len, which live past the last full prompt block."""
        self.seq_lens[slot] = seq_len
        self.last_token[slot] = first_token
        self.slot_ready[slot] = True
        req = self.slot_req[slot]
        if (self.prefix_store is not None and req is not None
                and self._prefix_eligible(req)):
            self._adopt_prompt_blocks(req, min(seq_len, req.prompt_len), slot)

    def _adopt_prompt_blocks(self, req: Request, prompt_len: int,
                             slot: int) -> None:
        store = self.prefix_store
        prompt = np.asarray(req.prompt)
        bs = self.block_size
        parent = hashing.ROOT
        for b in range(min(prompt_len, len(prompt)) // bs):
            blk = prompt[b * bs:(b + 1) * bs]
            digest = hashing.block_hash(parent, blk)
            # blocks borrowed from the store at reservation re-hash to a
            # cached digest → insert() is a refresh no-op; only this
            # sequence's own (private) blocks transfer ownership
            store.insert(req.req_id, digest, parent, blk,
                         int(self.block_tables[slot, b]))
            parent = digest

    def abort_reservation(self, slot: int) -> None:
        """Handoff failed mid-stream: free the slot and its blocks."""
        if self.failed:
            # node is down: recover() rebuilds the allocator and pools, but
            # the slot must drop its request NOW so the failure sweep does
            # not requeue it a second time (two parallel lives)
            self.slot_req[slot] = None
            self.slot_ready[slot] = False
            self.slot_prefix_tokens[slot] = 0
            return
        self.release(slot)

    def add_sequence(self, req: Request, package: Dict[str, Any],
                     materialize_fn) -> int:
        """Admit a fully-transferred request into a decode slot.

        ``materialize_fn(engine, slot, block_ids, package)`` is provided by
        the disagg orchestrator (it owns the compat conversion)."""
        if self.failed:
            raise RuntimeError(f"instance {self.name} is down")
        seq_len = package["seq_len"]
        slot, block_ids = self.reserve_sequence(req, seq_len)
        materialize_fn(self, slot, block_ids, package)
        self.activate_sequence(slot, package["first_token"], seq_len)
        return slot

    def release(self, slot: int) -> None:
        req = self.slot_req[slot]
        if req is not None:
            if self.prefix_store is not None:
                # unpin borrowed/adopted prefix blocks (they stay cached
                # at zero refs until LRU eviction), then free whatever
                # this sequence still owns privately
                self.prefix_store.release_seq(req.req_id)
            self.allocator.free(req.req_id)
        self.slot_req[slot] = None
        self.slot_ready[slot] = False
        self.seq_lens[slot] = 0
        self.slot_prefix_tokens[slot] = 0
        self.block_tables[slot, :] = self._scratch_block

    def decode_step(self) -> List[Tuple[int, Request, int]]:
        """One continuous-batching step. Returns [(slot, request, token)]."""
        if self.failed:
            raise RuntimeError(f"instance {self.name} is down")
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and self.slot_ready[i]]
        if not active:
            return []
        kv_pages = int((-(-(self.seq_lens[active] + 1)
                          // self.block_size)).sum())
        with span("pd.decode.step", batch=len(active),
                  attn=self.decode_attention, kv_pages=kv_pages):
            with span("pd.decode.prepare"):
                write_slots = self.seq_lens % self.block_size
                write_block_idx = self.seq_lens // self.block_size
                write_blocks = self.block_tables[
                    np.arange(self.max_batch),
                    np.minimum(write_block_idx, self.max_blocks_per_seq - 1)]
                idle = np.asarray([r is None or not self.slot_ready[i]
                                   for i, r in enumerate(self.slot_req)])
                write_blocks = np.where(idle, self._scratch_block,
                                        write_blocks)
                args = (jnp.asarray(self.last_token[:, None]),
                        jnp.asarray(self.seq_lens),
                        jnp.asarray(self.block_tables),
                        jnp.asarray(write_blocks.astype(np.int32)),
                        jnp.asarray(write_slots.astype(np.int32)))
            with span("pd.decode.launch"):
                logits, self.caches = self._decode_fn(self.params, *args,
                                                      self.caches)
            with span("pd.decode.fetch"):
                logits = np.asarray(logits[:, 0])
            out = []
            with span("pd.decode.sample"):
                for slot in active:
                    req = self.slot_req[slot]
                    tok = self._sample(logits[slot:slot + 1], req)[0]
                    self.seq_lens[slot] += 1
                    self.last_token[slot] = tok
                    out.append((slot, req, int(tok)))
        self.stats.decode_steps += 1
        self.stats.decode_tokens += len(active)
        self.stats.decode_kv_pages += kv_pages
        return out

    # ------------------------------------------------------------------ #
    def _sample_first(self, logits: np.ndarray, req: Request) -> int:
        """Sample the first token from the last prompt position's logits
        (1, V), keeping them on the request as served."""
        req.first_logits = np.asarray(logits[0], np.float32)
        return int(self._sample(logits, req)[0])

    def _sample(self, logits: np.ndarray, req: Request) -> np.ndarray:
        if req.temperature <= 0.0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        z = logits.astype(np.float64) / req.temperature
        z -= z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=-1, keepdims=True)
        return np.asarray([self._rng.choice(p.shape[-1], p=p[i])
                           for i in range(p.shape[0])], np.int32)

    # -- fault injection ------------------------------------------------ #
    def fail(self) -> None:
        self.failed = True
        self.stats.failures_injected += 1

    def recover(self) -> None:
        """Restart: all volatile KV state is lost (as on a real node)."""
        self.failed = False
        for slot in range(self.max_batch):
            self.release(slot)
        self.allocator = BlockAllocator(self.allocator.num_blocks)
        self.allocator.allocate("__scratch__", 1)
        self._scratch_block = self.allocator.blocks_of("__scratch__")[0]
        if self.prefix_store is not None:
            # the pages the store indexed died with the pool
            self.prefix_store = PrefixStore(self.allocator, self.block_size)
        self.slot_prefix_tokens = [0] * self.max_batch
