"""Paged prefix-shared KV cache for the continuous-batching engine.

The engine's monolithic slot cache `(L, n_slots, Hkv, token_budget, hd)`
stays the *decode* surface (one persistent jitted step, zero recompiles
after warmup — the PR-3 contract). What changes is where a prompt's
prefix K/V comes from: this module adds a device-resident **page pool**
`(L, n_pages, Hkv, page_size, hd)` plus a host-side **ref-counted radix
index** over page-aligned token blocks, so requests sharing a prompt
prefix (system prompts, few-shot headers) stop re-prefilling it:

- **Chain hashes.** A prompt is split into `page_size`-token blocks;
  block i's identity is `blake2b(hash[i-1] || tokens[i])` — a chain, so
  equal hashes imply equal *full* prefixes, never just equal blocks.
  The same function runs in the engine (index keys), the router
  (prefix-affinity), and the bench (traffic synthesis) — one definition,
  `chain_hashes`, deterministic across processes (never Python `hash`,
  which is salted per process).
- **Admission-time gather (copy-on-write).** Matching index pages are
  gathered on-device into the request's slot rows `[0, start)` in ONE
  fixed-shape jitted op (the page-id table is padded to
  `token_budget // page_size` entries with the reserved scratch page 0,
  so there is exactly one compile, ever); the admission then prefills
  only the unmatched suffix. All decode writes land in the slot — the
  pooled pages are immutable once sealed, which is what makes the
  sharing copy-on-write at the divergence token.
- **Sealing.** After admission the slot holds the full prompt K/V;
  complete blocks not yet in the index are copied out into freshly
  allocated pages (one padded fixed-shape scatter) and registered, so
  the NEXT request sharing the prefix hits.
- **Ref-counted LRU eviction.** A node is pinned while an admission is
  using it and held by its children; under pressure `allocate()` evicts
  the least-recently-used unpinned *leaf* (interior nodes are protected
  transitively). Hit/miss/evict counters feed `/v1/metrics` and the
  router's `/v1/load` probe.

The module also owns the **migration wire format** for prefill/decode
disaggregation: a prefill-role replica extracts a slot's computed K/V
rows `[0, pos)` plus sampler state, `pack_migration` frames it (JSON
header line + raw leaf bytes), and the decode-role replica installs it
into a free slot via one fixed-shape `install_rows` — the K/V bytes
transplant exactly, so greedy decode across a migrate is bit-identical
to decoding locally.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tony_tpu.models.llama import (
    LlamaConfig, Params, embed_lookup, qkv_proj, rope_tables,
)
from tony_tpu.models.quant import (
    dequantize_layer, dequantize_rows, maybe_dequantize, quantize_rows,
)
from tony_tpu.ops.attention import NEG_INF
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.ops.rope import apply_rope

# page 0 is the reserved scratch page: padded gather/scatter entries
# point at it so every page-table op runs at ONE fixed shape (garbage
# written to / read from it is always masked or overwritten)
SCRATCH_PAGE = 0

# bound on the prefix-hash set a replica advertises on /v1/load (the
# router's affinity source): most-recently-used first, so the hottest
# prefixes are always visible even on a large index
ADVERTISE_CAP = 256


def chain_hashes(tokens: Sequence[int], page_size: int) -> list[str]:
    """Cumulative block hashes of the COMPLETE page-aligned blocks of
    `tokens`: out[i] identifies tokens[0 : (i+1)*page_size]. Equal
    hashes ⇒ equal full prefixes (chained, not per-block)."""
    if page_size <= 0:
        return []
    out: list[str] = []
    prev = b""
    for i in range(len(tokens) // page_size):
        block = np.asarray(tokens[i * page_size:(i + 1) * page_size],
                           np.int32).tobytes()
        prev = hashlib.blake2b(prev + block, digest_size=12).hexdigest() \
            .encode("ascii")
        out.append(prev.decode("ascii"))
    return out


# ---------------------------------------------------------------------------
# fixed-shape page-table ops (module level: one compile cache each)
# ---------------------------------------------------------------------------

@partial(jax.jit, donate_argnames=("cache",))
def gather_pages(cache, pool, page_ids: jax.Array, slot: jax.Array):
    """Copy `page_ids` (padded to blocks-per-slot with SCRATCH_PAGE)
    from the pool into the slot's cache rows [0, n*page_size). ONE
    compile: the page table is data, never a shape. Padded entries
    write scratch-page garbage into rows the suffix prefill (or the
    decode mask) immediately covers."""
    out = {}
    for name, arr in cache.items():
        pages = jnp.take(pool[name], page_ids, axis=1)  # (L,n,Hkv,P,d)
        l, n, h, p, d = pages.shape
        row = pages.transpose(0, 2, 1, 3, 4).reshape(l, h, n * p, d)
        out[name] = lax.dynamic_update_slice(
            arr, row[:, None].astype(arr.dtype), (0, slot, 0, 0, 0))
    return out


@partial(jax.jit, donate_argnames=("pool",))
def seal_pages(pool, cache, page_ids: jax.Array, slot: jax.Array):
    """Copy the slot's cache rows out into pool pages: block i of the
    slot lands in page page_ids[i]. Padded (and already-indexed) blocks
    carry SCRATCH_PAGE and scribble the scratch page. One compile."""
    out = {}
    n = page_ids.shape[0]
    for name, buf in pool.items():
        l, _, h, p, d = buf.shape
        row = lax.dynamic_slice(cache[name], (0, slot, 0, 0, 0),
                                (l, 1, h, n * p, d))
        pages = row[:, 0].reshape(l, h, n, p, d).transpose(0, 2, 1, 3, 4)
        out[name] = buf.at[:, page_ids].set(pages.astype(buf.dtype))
    return out


@partial(jax.jit, donate_argnames=("cache",))
def install_rows(cache, rows, slot: jax.Array):
    """Install one full-budget slot row tree (L, 1, Hkv, S, d) — a
    migrated-in request's K/V, zero-padded past its pos — into `slot`.
    Fixed shapes: one compile, same dynamic_update_slice discipline as
    admission."""
    return {name: lax.dynamic_update_slice(
        arr, rows[name].astype(arr.dtype), (0, slot, 0, 0, 0))
        for name, arr in cache.items()}


# ---------------------------------------------------------------------------
# suffix prefill
# ---------------------------------------------------------------------------

def prefill_suffix(params: Params, config: LlamaConfig, cache,
                   suffix: jax.Array, start: jax.Array, slot: jax.Array,
                   quant_cache: bool):
    """Prefill ONLY the unmatched suffix of a prompt into `slot`.

    suffix: (W,) int32 — prompt tokens [start, start+W); the slot's
    cache rows [0, start) already hold the gathered prefix K/V. Writes
    the suffix K/V into rows [start, start+W) and returns (last-position
    logits (1, V), cache). `start` and `slot` are traced scalars — one
    compile per distinct SUFFIX length, the paged analogue of the
    per-prompt-length admission compile.

    Attention is the masked-einsum form (suffix query i sees cache
    positions j <= start + i), sharing decode_step's GQA grouped-einsum
    discipline; RoPE uses the gather-form positions, which read the
    identical table rows as the offline flash prefill."""
    from tony_tpu.models.generate import _mlp

    w = suffix.shape[0]
    cache_len = cache["k"].shape[3]
    cos, sin = rope_tables(config, cache_len)
    positions = start + jnp.arange(w, dtype=jnp.int32)          # (W,)
    x = embed_lookup(params["embed"], suffix[None, :], config)  # (1,W,D)

    def body(x, layer_and_cache):
        if quant_cache:
            layer, kc, vc, ksc, vsc = layer_and_cache
        else:
            layer, kc, vc = layer_and_cache
            ksc = vsc = None
        layer = dequantize_layer(layer)
        h = rms_norm(x, layer["attn_norm"], config.norm_eps)
        # (1,H,W,hd)/(1,Hkv,W,hd); heads split on the result, as in
        # window_logits and generate.prefill
        q, k, v = qkv_proj(h, layer, config, split_on_result=True)
        q = apply_rope(q, cos, sin, positions=positions)
        k = apply_rope(k, cos, sin, positions=positions)
        row_k = lax.dynamic_index_in_dim(kc, slot, axis=0, keepdims=True)
        row_v = lax.dynamic_index_in_dim(vc, slot, axis=0, keepdims=True)
        if quant_cache:
            row_ks = lax.dynamic_index_in_dim(ksc, slot, axis=0,
                                              keepdims=True)
            row_vs = lax.dynamic_index_in_dim(vsc, slot, axis=0,
                                              keepdims=True)
            qk, k_s = quantize_rows(k)
            qv, v_s = quantize_rows(v)
            row_k = lax.dynamic_update_slice(row_k, qk, (0, 0, start, 0))
            row_v = lax.dynamic_update_slice(row_v, qv, (0, 0, start, 0))
            row_ks = lax.dynamic_update_slice(row_ks, k_s,
                                              (0, 0, start, 0))
            row_vs = lax.dynamic_update_slice(row_vs, v_s,
                                              (0, 0, start, 0))
            k_eff = dequantize_rows(row_k, row_ks)
            v_eff = dequantize_rows(row_v, row_vs)
        else:
            row_k = lax.dynamic_update_slice(
                row_k, k.astype(row_k.dtype), (0, 0, start, 0))
            row_v = lax.dynamic_update_slice(
                row_v, v.astype(row_v.dtype), (0, 0, start, 0))
            k_eff, v_eff = row_k, row_v
        b, nh, _, hd = q.shape
        nkv = k_eff.shape[1]
        rep = nh // nkv
        qg = q.reshape(b, nkv, rep, w, hd).astype(jnp.float32) \
            * hd ** -0.5
        scores = jnp.einsum("bgrwd,bgsd->bgrws", qg,
                            k_eff.astype(jnp.float32))  # (1,G,rep,W,S)
        iota_w = lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        iota_s = lax.broadcasted_iota(jnp.int32, scores.shape, 4)
        scores = jnp.where(iota_s <= start + iota_w, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrws,bgsd->bgrwd", probs,
                         v_eff.astype(jnp.float32))
        attn = out.reshape(b, nh, w, hd).astype(q.dtype)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, w, -1)
        x = x + jnp.einsum("bsh,hd->bsd", attn, layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
        x = x + _mlp(h, layer, config)
        kc = lax.dynamic_update_slice_in_dim(kc, row_k, slot, axis=0)
        vc = lax.dynamic_update_slice_in_dim(vc, row_v, slot, axis=0)
        if quant_cache:
            ksc = lax.dynamic_update_slice_in_dim(ksc, row_ks, slot,
                                                  axis=0)
            vsc = lax.dynamic_update_slice_in_dim(vsc, row_vs, slot,
                                                  axis=0)
            return x, (kc, vc, ksc, vsc)
        return x, (kc, vc)

    if quant_cache:
        xs = (params["layers"], cache["k"], cache["v"],
              cache["k_scale"], cache["v_scale"])
        x, (ks, vs, kscs, vscs) = lax.scan(body, x, xs)
        new_cache = {"k": ks, "v": vs, "k_scale": kscs, "v_scale": vscs}
    else:
        x, (ks, vs) = lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]))
        new_cache = {"k": ks, "v": vs}
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1],
                        maybe_dequantize(params["output"]),
                        preferred_element_type=jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# host-side radix index + page allocator
# ---------------------------------------------------------------------------

@dataclass
class _PageNode:
    digest: str
    parent: str          # parent block's digest ("" at depth 1)
    page_id: int
    depth: int           # 1-based block count this node's chain covers
    children: int = 0    # ref count: live child nodes
    pins: int = 0        # ref count: admissions mid-flight using it
    seq: int = 0         # LRU clock (monotonic use counter)


class KVPagePool:
    """Device page pool + host radix index. Single-writer: only the
    engine's stepper thread mutates the index (admission/seal/evict);
    probe-path readers see atomic snapshots (`advertised`, int
    counters) — the engine's lock-free `/v1/load` contract holds."""

    def __init__(self, config: LlamaConfig, token_budget: int,
                 page_size: int = 16, n_pages: int = 0,
                 n_slots: int = 4, quant_cache: bool = False):
        if page_size <= 0:
            raise ValueError("kv page_size must be positive")
        self.page_size = min(page_size, token_budget)
        self.blocks_per_slot = max(1, token_budget // self.page_size)
        if n_pages <= 0:
            # default: every slot can seal a full prefix, + scratch
            n_pages = 1 + n_slots * self.blocks_per_slot
        self.n_pages = max(2, n_pages)          # >= scratch + 1 usable
        self.quant_cache = quant_cache
        c = config
        shape = (c.n_layers, self.n_pages, c.n_kv_heads, self.page_size,
                 c.head_dim)
        if quant_cache:
            scale = shape[:-1] + (1,)
            self.pool = {"k": jnp.zeros(shape, jnp.int8),
                         "v": jnp.zeros(shape, jnp.int8),
                         "k_scale": jnp.zeros(scale, jnp.float32),
                         "v_scale": jnp.zeros(scale, jnp.float32)}
        else:
            self.pool = {"k": jnp.zeros(shape, c.dtype),
                         "v": jnp.zeros(shape, c.dtype)}
        self._nodes: dict[str, _PageNode] = {}
        self._free: list[int] = list(range(1, self.n_pages))
        self._clock = 0
        # lock-free probe surface: atomically-swapped tuple + plain ints
        self.advertised: tuple[str, ...] = ()
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.evicted_pages = 0
        self.sealed_pages = 0
        self.req_hits = 0
        self.req_misses = 0

    # -- index ----------------------------------------------------------
    @property
    def pages_total(self) -> int:
        return self.n_pages - 1                 # scratch excluded

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        return len(self._nodes)

    def evictable_pages(self) -> int:
        return sum(1 for n in self._nodes.values()
                   if n.children == 0 and n.pins == 0)

    def headroom_pages(self) -> int:
        """Free + evictable — the router's load-score input: a pool
        whose every page is pinned/interior has NO headroom even though
        pages_used < pages_total never shows it."""
        return self.pages_free + self.evictable_pages()

    def match(self, hashes: list[str]) -> tuple[list[int], int]:
        """Longest indexed prefix of `hashes`: (page ids, depth). The
        deepest matched node is PINNED (caller must unpin after the
        admission's gather+seal) — its ancestors are protected by child
        refs, so one pin guards the whole chain."""
        ids: list[int] = []
        deepest: Optional[_PageNode] = None
        for digest in hashes:
            node = self._nodes.get(digest)
            if node is None:
                break
            ids.append(node.page_id)
            deepest = node
        self._clock += 1
        if deepest is not None:
            deepest.pins += 1
            for digest in hashes[:len(ids)]:
                self._nodes[digest].seq = self._clock
        return ids, len(ids)

    def pin(self, digest: str) -> None:
        """Protect one node from eviction (an admission mid-gather, or a
        just-registered block whose page bytes are not sealed yet)."""
        node = self._nodes.get(digest)
        if node is not None:
            node.pins += 1

    def unpin(self, digest: str) -> None:
        node = self._nodes.get(digest)
        if node is not None and node.pins > 0:
            node.pins -= 1

    def allocate(self) -> Optional[int]:
        """One free page id, evicting the LRU unpinned leaf when the
        free list is empty. None when every page is pinned or interior
        (the caller skips sealing — reuse degrades, correctness never)."""
        if self._free:
            return self._free.pop()
        victim: Optional[_PageNode] = None
        for node in self._nodes.values():
            if node.children or node.pins:
                continue
            if victim is None or node.seq < victim.seq:
                victim = node
        if victim is None:
            return None
        self._evict(victim)
        return self._free.pop() if self._free else None

    def _evict(self, node: _PageNode) -> None:
        del self._nodes[node.digest]
        parent = self._nodes.get(node.parent)
        if parent is not None and parent.children > 0:
            parent.children -= 1
        self._free.append(node.page_id)
        self.evicted_pages += 1
        self._refresh_advertised()

    def register(self, parent: str, digest: str, page_id: int,
                 depth: int) -> None:
        """Insert one sealed block under `parent` (its chain
        predecessor; "" at depth 1)."""
        if digest in self._nodes:               # lost a race with a twin
            self._free.append(page_id)          # admission — keep theirs
            return
        self._clock += 1
        self._nodes[digest] = _PageNode(digest, parent, page_id, depth,
                                        seq=self._clock)
        p = self._nodes.get(parent)
        if p is not None:
            p.children += 1
        self.sealed_pages += 1
        self._refresh_advertised()

    def _refresh_advertised(self) -> None:
        nodes = sorted(self._nodes.values(), key=lambda n: -n.seq)
        self.advertised = tuple(n.digest for n in nodes[:ADVERTISE_CAP])

    def check_invariants(self) -> None:
        """Test hook: page ids partition into {scratch} ∪ free ∪ indexed,
        and every parent's child refcount equals its live children."""
        indexed = [n.page_id for n in self._nodes.values()]
        all_ids = sorted([SCRATCH_PAGE] + list(self._free) + indexed)
        assert all_ids == list(range(self.n_pages)), all_ids
        kids: dict[str, int] = {}
        for n in self._nodes.values():
            if n.parent:
                kids[n.parent] = kids.get(n.parent, 0) + 1
        for n in self._nodes.values():
            assert n.children == kids.get(n.digest, 0), n
        for parent in kids:
            assert parent in self._nodes, f"dangling parent {parent}"

    # -- probe surface --------------------------------------------------
    def hit_rate_pct(self) -> float:
        total = self.hit_tokens + self.miss_tokens
        return 100.0 * self.hit_tokens / total if total else 0.0

    def load_fields(self) -> dict:
        """Fields merged into the engine's lock-free /v1/load snapshot
        (plain ints / an atomically-swapped tuple — no locking)."""
        return {
            "kv_page_size": self.page_size,
            "kv_pages_total": self.pages_total,
            "kv_pages_free": self.pages_free,
            "kv_pages_headroom": self.headroom_pages(),
            "kv_hit_rate_pct": round(self.hit_rate_pct(), 2),
            "prefix_hashes": list(self.advertised),
        }

    def stats_fields(self) -> dict:
        """Gauges for the engine snapshot → /v1/metrics → Prometheus
        (tony_serving_kv_{hit,miss,evict}_total families)."""
        used = self.pages_used
        return {
            "kv_hit_total": self.hit_tokens,
            "kv_miss_total": self.miss_tokens,
            "kv_evict_total": self.evicted_pages,
            "kv_sealed_total": self.sealed_pages,
            "kv_req_hit_total": self.req_hits,
            "kv_req_miss_total": self.req_misses,
            "kv_pages_total": self.pages_total,
            "kv_pages_free": self.pages_free,
            "kv_page_size": self.page_size,
            "kv_occupancy_pct": (100.0 * used / self.pages_total
                                 if self.pages_total else 0.0),
            "kv_hit_rate_pct": round(self.hit_rate_pct(), 2),
        }


# ---------------------------------------------------------------------------
# migration wire format (prefill → decode handoff)
# ---------------------------------------------------------------------------

def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def pack_migration(meta: dict, leaves: dict[str, np.ndarray]) -> bytes:
    """Frame one migrated request: JSON header line (sampler state +
    leaf manifest) followed by the raw leaf bytes, concatenated in
    manifest order. The K/V bytes travel VERBATIM — the greedy
    bit-identity across a migrate rests on exactly that."""
    header = dict(meta)
    header["leaves"] = [
        {"name": k, "shape": list(v.shape), "dtype": str(v.dtype),
         "nbytes": int(v.nbytes)} for k, v in leaves.items()]
    blob = b"".join(np.ascontiguousarray(v).tobytes()
                    for v in leaves.values())
    return json.dumps(header).encode("utf-8") + b"\n" + blob


def unpack_migration(body: bytes) -> tuple[dict, dict[str, np.ndarray]]:
    head, sep, blob = body.partition(b"\n")
    if not sep:
        raise ValueError("migration payload missing header line")
    try:
        header = json.loads(head.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError):
        raise ValueError("migration header is not valid JSON") from None
    manifest = header.pop("leaves", None)
    if not isinstance(manifest, list):
        raise ValueError("migration header missing leaf manifest")
    leaves: dict[str, np.ndarray] = {}
    off = 0
    for spec in manifest:
        n = int(spec["nbytes"])
        if off + n > len(blob):
            raise ValueError("migration payload truncated")
        arr = np.frombuffer(blob[off:off + n],
                            dtype=_np_dtype(str(spec["dtype"])))
        leaves[str(spec["name"])] = arr.reshape(
            [int(s) for s in spec["shape"]])
        off += n
    return header, leaves
