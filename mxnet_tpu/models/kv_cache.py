"""KV caches for autoregressive decode — a first-class primitive.

Reference parity: NONE, by design. SURVEY.md §3.5 documents the
reference's decode wart: GluonNLP models thread per-layer (k, v) NDArrays
and `nd.concat(prev_k, new_k, dim=time)` every step — reallocating the
whole cache and forcing CachedOp shape re-inference per length. The brief
calls the static-shape replacement out as the one primitive the rebuild
must provide. Two variants, both functional pytrees (carried through
`lax.while_loop` decode bodies, updated in place by XLA via buffer
donation):

  * KVCache — contiguous per-layer (B, H, T_max, D) buffers written with
    `lax.dynamic_update_slice`. The fast path for fixed-batch decode.
  * PagedKVCache — a static PAGE POOL (L, num_pages, page_size, H*D)
    plus a per-sequence page table (B, pages_per_seq). Attention gathers
    pages through the table, so sequences own arbitrary page sets —
    the serving-style layout (cf. ragged paged attention, PAPERS.md)
    with O(1) append and no per-length recompilation. A token's heads
    are PACKED heads-major into one row (column h*D + d): that is the
    operand the span kernel reads, so the pool is written and read in
    place. A pool ending in (H, D) would not be: the TPU's tiled layout
    pads (20, 64) to (24, 128), so handing the kernel H*D columns is a
    real copy there, of every layer on every dispatch.

    RAGGED mode (the continuous-batching serving path, serving/engine.py):
    `length` may be a (B,) int32 vector — each slot has its own live
    length. Ragged caches take decode writes through `write_decode`
    (each slot's rows at its own offset, NO dense gather: a page at a
    time through the Mosaic call kv_page_write where the pool and the
    `attn_impl` knob admit it, a row scatter elsewhere) and
    attention reads the pools directly via the ragged paged-attention
    kernel (ops/pallas_attention.ragged_span_attention), so per-token
    HBM traffic scales with live length instead of max_length. The
    static `attn_impl` knob ('auto'|'pallas'|'pallas_interpret'|'xla')
    rides in the pytree aux so it is part of the jit signature.

Both share the same API so models are cache-agnostic:
    write(layer, k_new, v_new)  -> (k_all, v_all, new_cache)
    write_prompt(layer, k, v)   -> (k_all, v_all, new_cache)  # prefill
    advance(n)                  -> new_cache  # once per model forward
    key_mask(extra)             -> (T_view,) bool validity over k_all
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..ops.kernel_paths import note_path

__all__ = ["KVCache", "PagedKVCache", "gather_kv_pages",
           "scatter_kv_pages"]


def gather_kv_pages(k_pages, v_pages, idx, k_scale=None, v_scale=None):
    """Gather whole pages (rows of the page axis) out of paged pools —
    the KV-spill tier's device→host read (serving/host_tier.py).

    ``idx`` is a FIXED-width (P,) int32 vector so the jitted gather is
    one program regardless of how many pages spill this step: the host
    pads short batches with page 0 and slices the valid prefix off the
    ``jax.device_get`` result. Returns (k, v, ks, vs) with k/v of shape
    (L, P, page_size, H*D) — pages leave the device packed as the pool
    stores them, so no program relayouts one — and ks/vs (L, P, H) f32
    (None on float pools). Under tp=N sharded pools the take propagates
    the pools' sharding of the packed axis (whole-head column blocks)
    into the slices; ``device_get`` then assembles the global array —
    no reshard, no explicit sharding annotations (same contract as the
    engine's _copy_page_fn)."""
    k = jnp.take(k_pages, idx, axis=1)
    v = jnp.take(v_pages, idx, axis=1)
    ks = None if k_scale is None else jnp.take(k_scale, idx, axis=1)
    vs = None if v_scale is None else jnp.take(v_scale, idx, axis=1)
    return k, v, ks, vs


def scatter_kv_pages(k_pages, v_pages, idx, k_val, v_val,
                     k_scale=None, v_scale=None,
                     ks_val=None, vs_val=None):
    """Scatter whole pages back into paged pools — the spill tier's
    host→device page-in write (the inverse of gather_kv_pages).

    ``idx`` is the same fixed-width (P,) vector, padded with
    ``num_pages`` (out of range) so pad rows DROP instead of landing in
    page 0. Payloads are (L, P, page_size, H*D), the pool's own packed
    rows, and are written verbatim — int8 codes and their
    f32 scale leaves land exactly as gathered, which is what makes a
    page-in bit-identical to the never-evicted run. Returns the
    updated (k_pages, v_pages, k_scale, v_scale); the engine jits this
    with the pool arguments donated so the write is in-place."""
    k_pages = k_pages.at[:, idx].set(k_val.astype(k_pages.dtype),
                                     mode="drop")
    v_pages = v_pages.at[:, idx].set(v_val.astype(v_pages.dtype),
                                     mode="drop")
    if k_scale is not None and ks_val is not None:
        k_scale = k_scale.at[:, idx].set(ks_val, mode="drop")
        v_scale = v_scale.at[:, idx].set(vs_val, mode="drop")
    return k_pages, v_pages, k_scale, v_scale


@jax.tree_util.register_pytree_node_class
class KVCache:
    """Contiguous static cache: k/v of shape (L, B, H, T_max, D)."""

    def __init__(self, k, v, length):
        self.k = k
        self.v = v
        self.length = length  # scalar int32: tokens written so far

    @classmethod
    def create(cls, num_layers, batch, num_heads, max_length, head_dim,
               dtype=jnp.float32):
        shape = (num_layers, batch, num_heads, max_length, head_dim)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))

    @property
    def max_length(self):
        return self.k.shape[3]

    ragged = False  # contiguous caches are always lockstep

    def write(self, layer, k_new, v_new):
        """Write one step: k_new/v_new (B, H, t, D) at offset `length`.
        Returns the FULL (B, H, T_max, D) views + the updated cache."""
        start = (0, 0, self.length, 0)
        k_l = lax.dynamic_update_slice(self.k[layer],
                                       k_new.astype(self.k.dtype), start)
        v_l = lax.dynamic_update_slice(self.v[layer],
                                       v_new.astype(self.v.dtype), start)
        new = KVCache(self.k.at[layer].set(k_l), self.v.at[layer].set(v_l),
                      self.length)
        return k_l, v_l, new

    # prefill is the same dynamic-slice write (t = prompt length)
    write_prompt = write

    def advance(self, n):
        return KVCache(self.k, self.v, self.length + n)

    def key_mask(self, extra=0):
        """(T_max,) bool: True for written positions (+ `extra` being
        written this step)."""
        return jnp.arange(self.max_length) < (self.length + extra)

    def tree_flatten(self):
        return (self.k, self.v, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Page-pool cache: k/v pools (L, num_pages, page_size, H*D) indexed
    through a per-sequence page_table (B, pages_per_seq). `length` is a
    scalar (all sequences in lockstep — generate()'s fixed-batch decode)
    or a (B,) vector (ragged serving decode, one live length per slot).
    The last axis packs a token's heads (column h*D + d): the ONE layout,
    because it is what ragged_span_attention's page blocks read. Writes
    place rows of that width (whole pages rewritten in place by
    kv_page_write, or a row scatter) and the kernels pick their layer
    and page in their BlockSpecs, so no program slices, reshapes or
    copies a pool.
    The head count comes back from the (B, H, t, D) operand of each
    write wherever a method needs (H, D) again.

    ONE pool (`create(row_width=...)`, `v_pages` None): a layer whose
    token keeps a single row with no head axis (latent attention: the
    value is a column slice of the row the scores read). `write_decode`
    takes the rows as (B, 1, t, row_width) and no values, the page write
    and the scatter carry one pool, and the lockstep views (`write`,
    `write_prompt`), int8 pages and the head split of tp serving do not
    apply: there is no head to scale or split by.

    QUANTIZED page mode (``kv_dtype="int8"``): pools are stored int8
    with per-page-per-head f32 scale leaves ``k_scale``/``v_scale`` of
    shape (L, num_pages, H) riding in the pytree. Writes quantize
    in-program with a MONOTONE scale: position i's scale is the running
    max of absmax/127 over every position ever written to its page up
    through i (gathered old page scale ⊔ within-write same-page running
    max), so already-written int8 codes are never re-rounded and the
    codes are a pure function of the token stream — independent of how
    prefill was chunked. Reads dequantize with the CURRENT page scale
    (earlier positions come back slightly inflated when the scale grew
    after they were written; the tolerance oracle bounds this). Dequant
    happens where the page bytes are touched — fused into the ragged
    Pallas kernel's page DMA (ops/pallas_attention) or on the gathered
    view for the lockstep path — so HBM traffic stays int8.

    TENSOR-PARALLEL serving (ServingEngine(tp=N)): every method here is
    already head-count-agnostic, so inside the engine's shard_map the
    SAME code runs on per-shard pool slices — k/v pools sharded on the
    packed axis (axis 3, in whole-head blocks of D columns) and int8
    scale leaves on their head axis (axis 2), while
    page_table / length / spans / page_lock stay replicated so every
    shard computes identical page geometry. Nothing in this file
    branches on the shard; the split is purely the caller's sharding of
    the pool leaves."""

    def __init__(self, k_pages, v_pages, page_table, length,
                 page_lock=None, spans=None, k_scale=None, v_scale=None,
                 attn_impl="auto", recurrent=None):
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.page_table = page_table
        self.length = length
        # optional (num_pages,) bool: True = page is SHARED/cached
        # (refcount > 1 or owned by the prefix cache) — write_decode
        # must never land in it (the CoW invariant; the host performs
        # the actual copy-on-write split, this mask is the in-program
        # guarantee that a stray write drops instead of corrupting)
        self.page_lock = page_lock
        # optional (B,) int32: live query tokens per slot for the
        # CURRENT dispatch (decode=1, verify=S, prefill chunk=C,
        # idle=0). Rows past a slot's span neither write KV
        # (write_decode drops them) nor attend (the span attention
        # kernel masks them to exact zeros) — the unified fixed-shape
        # serving dispatch rides on this
        self.spans = spans
        # optional (L, num_pages, H) f32: per-page-per-head dequant
        # scales for int8 pools — None on float caches
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.attn_impl = attn_impl
        # optional pytree of per-slot state that does not grow with the
        # sequence (a state-space layer's convolution tail and SSM
        # state), each leaf a whole pool (L, B, ...) like the pages. The
        # cache only carries it through a forward: the model reads and
        # replaces its leaves (`with_recurrent`), the serving engine
        # donates them with the pages
        self.recurrent = recurrent

    @classmethod
    def create(cls, num_layers, batch, num_heads, max_length, head_dim,
               dtype=jnp.float32, page_size=64, num_pages=None,
               page_table=None, lengths=None, attn_impl="auto",
               kv_dtype=None, num_kv_heads=None, recurrent=None,
               row_width=None):
        """`num_heads` query heads over `num_kv_heads` KV heads (None:
        as many): the pools hold the KV heads only. With `row_width`,
        ONE pool of rows that wide and no V pool (no head axis:
        `num_heads` and `head_dim` are not read)."""
        num_heads = int(num_kv_heads or num_heads)
        if row_width is not None and kv_dtype is not None:
            raise MXNetError("kv_dtype needs a head axis to scale by: a "
                             "pool of whole rows has none")
        if max_length % page_size:
            raise MXNetError(
                f"max_length {max_length} not a multiple of page_size "
                f"{page_size}")
        per_seq = max_length // page_size
        if num_pages is None:
            num_pages = batch * per_seq
        if page_table is None:
            # default allocation: sequence b owns pages [b*P, (b+1)*P) —
            # any permutation works (attention always goes through the
            # table; tests permute it to prove real paging)
            page_table = jnp.arange(batch * per_seq, dtype=jnp.int32
                                    ).reshape(batch, per_seq)
            if num_pages < batch * per_seq:
                raise MXNetError(
                    f"{num_pages} pages < {batch}x{per_seq} required")
        else:
            # a table referencing pages outside the pool would silently
            # gather garbage (jnp.take clips) — fail loudly instead
            import numpy as np
            tbl = np.asarray(page_table)
            if tbl.size and (tbl.min() < 0 or tbl.max() >= num_pages):
                raise MXNetError(
                    f"page_table references pages outside the pool: "
                    f"entries span [{int(tbl.min())}, {int(tbl.max())}] "
                    f"but only pages [0, {num_pages}) exist")
        if kv_dtype is not None and jnp.dtype(kv_dtype) != jnp.int8:
            raise MXNetError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        store = jnp.int8 if kv_dtype is not None else dtype
        shape = (num_layers, num_pages, page_size,
                 int(row_width or num_heads * head_dim))
        length = jnp.zeros((), jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        scales = (None, None)
        if kv_dtype is not None:
            sshape = (num_layers, num_pages, num_heads)
            scales = (jnp.zeros(sshape, jnp.float32),
                      jnp.zeros(sshape, jnp.float32))
        return cls(jnp.zeros(shape, store),
                   None if row_width is not None else jnp.zeros(shape, store),
                   jnp.asarray(page_table, jnp.int32), length,
                   k_scale=scales[0], v_scale=scales[1],
                   attn_impl=attn_impl, recurrent=recurrent)

    @property
    def quantized(self):
        return self.k_scale is not None

    @property
    def ragged(self):
        return getattr(self.length, "ndim", 0) == 1

    @property
    def page_size(self):
        return self.k_pages.shape[2]

    @property
    def max_length(self):
        return self.page_table.shape[1] * self.page_size

    def _gather(self, pages, layer, heads, scale=None):
        # (num_pages, page_size, H*D)[table (B, P)] → (B, T, H, D) → BHTD
        B, P = self.page_table.shape
        g = jnp.take(pages[layer], self.page_table, axis=0)
        g = g.reshape(B, P, self.page_size, heads, -1)
        if scale is not None:
            # dequant the gathered view: one f32 scale per (page, head)
            gs = jnp.take(scale[layer], self.page_table, axis=0)
            g = g.astype(jnp.float32) * gs[:, :, None, :, None]
        return g.reshape(B, self.max_length, heads, -1).transpose(0, 2, 1, 3)

    def _quant_encode(self, x_t, pages, page_idx, scale, layer):
        """Quantize an append chunk against the monotone page scales.

        x_t (B, t, H, D) float activations; pages (B, t) physical page
        per position (num_pages = dropped row); page_idx (B, t) logical
        page per position; scale the (L, N, H) leaf. Position i's scale
        is max(old page scale, running same-page absmax/127 through i) —
        the running max (not the chunk max) makes the emitted int8 codes
        of GIVEN values independent of how the stream was cut into
        chunks. (The values themselves are not: a mid-chunk row's
        attention reads page scales that already reflect the whole
        chunk, so deep-layer activations depend on chunk boundaries —
        the serving engine replays a request's recorded write schedule
        on restart/migration for exactly that reason.) Returns
        (q int8 (B,t,H*D) packed as the pool stores it, scale_used f32
        (B,t,H))."""
        N = self.k_pages.shape[1]
        xf = x_t.astype(jnp.float32)
        live = pages < N                               # (B, t)
        a = jnp.max(jnp.abs(xf), axis=-1)              # (B, t, H)
        # dead rows carry garbage activations — they must not raise the
        # scale of live rows sharing their page
        a = jnp.where(live[..., None], a, 0.0)
        t = x_t.shape[1]
        i = jnp.arange(t)
        same = (page_idx[:, :, None] == page_idx[:, None, :]) \
            & (i[None, :, None] >= i[None, None, :])   # (B, i, j): j<=i
        run = jnp.max(jnp.where(same[..., None], a[:, None, :, :], 0.0),
                      axis=2)                          # (B, t, H)
        s_old = jnp.take(scale[layer], jnp.minimum(pages, N - 1), axis=0)
        s_old = jnp.where(live[..., None], s_old, 0.0)
        s = jnp.maximum(s_old, run * (1.0 / 127.0))
        q = jnp.where(s[..., None] > 0, xf / s[..., None], 0.0)
        q = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
        return q.reshape(q.shape[0], t, -1), s

    def write(self, layer, k_new, v_new):
        """Decode write: k_new/v_new (B, H, 1, D) appended at `length`.
        Returns full gathered (B, H, T_max, D) views + updated cache.
        Quantized caches route through the write_decode scatter (which
        owns the scale bookkeeping) and return DEQUANTIZED f32 views."""
        B, H = k_new.shape[:2]
        if self.v_pages is None:
            raise MXNetError("a one-pool cache has no gathered (H, D) "
                             "view: its rows are read by "
                             "latent_span_attention")
        if self.quantized:
            new = self.write_decode(layer, k_new, v_new)
            return (new._gather(new.k_pages, layer, H, new.k_scale),
                    new._gather(new.v_pages, layer, H, new.v_scale), new)
        page_idx = self.length // self.page_size
        slot = self.length % self.page_size
        pages = self.page_table[:, page_idx]          # (B,) physical page
        # pool slot layout is (page, slot, H*D) → one (B, H*D) row each
        k_t = k_new[:, :, 0, :].reshape(B, -1)
        v_t = v_new[:, :, 0, :].reshape(B, -1)
        kp = self.k_pages.at[layer, pages, slot].set(
            k_t.astype(self.k_pages.dtype))
        vp = self.v_pages.at[layer, pages, slot].set(
            v_t.astype(self.v_pages.dtype))
        new = PagedKVCache(kp, vp, self.page_table, self.length,
                           page_lock=self.page_lock, spans=self.spans,
                           attn_impl=self.attn_impl,
                            recurrent=self.recurrent)
        return new._gather(kp, layer, H), new._gather(vp, layer, H), new

    def _page_write_impl(self, t):
        """How write_decode places `t` new rows a slot: 'pallas' or
        'pallas_interpret', whole pages at a time through
        ops/pallas_attention.kv_page_write, where the cache is ragged,
        its pool a float pool, `attn_impl` resolves to the Mosaic path as
        the span kernel's does ('auto' on a TPU) and the shapes keep
        Mosaic's rules at full width (a packed row of whole 128-lane
        tiles, a page of whole sublane tiles, at most a page of rows);
        'xla', the row scatter, for every other call. Decided from what
        the call shows alone."""
        impl = self.attn_impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        pool = self.k_pages
        S, HD = pool.shape[2:]
        if impl not in ("pallas", "pallas_interpret") or not self.ragged \
                or self.quantized or t > S or HD % 128 \
                or pool.dtype not in (jnp.float32, jnp.bfloat16) \
                or S % (32 // pool.dtype.itemsize):
            return "xla"
        return impl

    def write_decode(self, layer, k_new, v_new):
        """Ragged decode write: each slot appends its token(s) at its OWN
        length. k_new/v_new (B, H, t, D) — t = 1 for plain decode, t > 1
        for a speculative-verification dispatch (slot b's token j lands
        at position length[b] + j). Returns just the updated cache — no
        gathered views (the ragged attention kernel reads the pools
        directly; materializing the dense view is exactly the HBM cost
        this path removes). Two forms leave the same bytes
        (_page_write_impl says which a call takes): kv_page_write reads
        the at most two pages a slot's rows fall in, replaces the live
        rows and writes the pages back, one Mosaic call for K and V;
        the row scatter places every row on its own. Positions past
        capacity are DROPPED (the page write skips the page, the
        scatter goes out of bounds under mode='drop') instead of
        clobbering a live page; so is any write aimed at a page the
        page_lock mask marks as shared — the copy-on-write invariant:
        a page with refcount > 1 (or owned by the prefix cache) is
        read-only, and the host must CoW-split it before a slot may
        write there.
        Rejected speculative drafts rely on the same discipline: their
        KV stays behind `length`, invisible to attention, and the next
        accepted write overwrites it in place.
        A one-pool cache takes its rows as k_new (B, 1, t, row_width) and
        v_new None."""
        B, _, t, _ = k_new.shape
        one = self.v_pages is None
        if one != (v_new is None):
            raise MXNetError("a one-pool cache is written rows and no "
                             "values; a K/V cache both")
        S = self.page_size
        P = self.page_table.shape[1]
        impl = self._page_write_impl(t)
        note_path("kv_page_write", "xla" if impl == "xla" else "pallas")
        if impl != "xla":
            # the kernels' module loads Pallas: imported where a program
            # that runs one is traced, not with the package
            from ..ops.pallas_attention import kv_page_write
            return self._with_pages(*kv_page_write(
                self.k_pages, self.v_pages,
                k_new.transpose(0, 2, 1, 3).reshape(B, t, -1),
                None if one
                else v_new.transpose(0, 2, 1, 3).reshape(B, t, -1),
                layer, self.page_table, self.length, self.spans,
                self.page_lock, interpret=impl == "pallas_interpret"))
        length = self.length if self.ragged \
            else jnp.broadcast_to(self.length, (B,))
        pos = length[:, None] + jnp.arange(t)         # (B, t)
        page_idx = pos // S
        slot = pos % S
        safe = jnp.take_along_axis(self.page_table,
                                   jnp.minimum(page_idx, P - 1), axis=1)
        num_pages = self.k_pages.shape[1]
        # positions past capacity get an out-of-range pool page → drop
        pages = jnp.where(page_idx < P, safe, num_pages)
        if self.spans is not None:
            # unified fixed-shape dispatch: slot b only has spans[b] live
            # query rows this step (decode=1, verify=S, chunk=C, idle=0);
            # dead rows carry garbage activations and must not land
            live = jnp.arange(t)[None, :] < self.spans[:, None]
            pages = jnp.where(live, pages, num_pages)
        if self.page_lock is not None:
            locked = jnp.take(self.page_lock,
                              jnp.minimum(pages, num_pages - 1)) \
                & (pages < num_pages)
            pages = jnp.where(locked, num_pages, pages)
        k_t = k_new.transpose(0, 2, 1, 3)             # (B, t, H, D)
        if one:
            return self._with_pages(self.k_pages.at[layer, pages, slot].set(
                k_t.reshape(B, t, -1).astype(self.k_pages.dtype),
                mode="drop"), None)
        v_t = v_new.transpose(0, 2, 1, 3)
        if self.quantized:
            qk, sk = self._quant_encode(k_t, pages, page_idx,
                                        self.k_scale, layer)
            qv, sv = self._quant_encode(v_t, pages, page_idx,
                                        self.v_scale, layer)
            kp = self.k_pages.at[layer, pages, slot].set(qk, mode="drop")
            vp = self.v_pages.at[layer, pages, slot].set(qv, mode="drop")
            # scatter-max keeps the monotone invariant under duplicate
            # page indices; dropped rows never touch the scale either
            ks = self.k_scale.at[layer, pages].max(sk, mode="drop")
            vs = self.v_scale.at[layer, pages].max(sv, mode="drop")
            return self._with_pages(kp, vp, ks, vs)
        # one row of H*D columns per token, heads-major: the pool's own
        # minor axis, so the scatter lands in place
        kp = self.k_pages.at[layer, pages, slot].set(
            k_t.reshape(B, t, -1).astype(self.k_pages.dtype), mode="drop")
        vp = self.v_pages.at[layer, pages, slot].set(
            v_t.reshape(B, t, -1).astype(self.v_pages.dtype), mode="drop")
        return self._with_pages(kp, vp)

    def _with_pages(self, k_pages, v_pages, k_scale=None, v_scale=None):
        """This cache with its pools (and an int8 pool's scale leaves)
        replaced."""
        return PagedKVCache(k_pages, v_pages, self.page_table, self.length,
                            page_lock=self.page_lock, spans=self.spans,
                            k_scale=k_scale, v_scale=v_scale,
                            attn_impl=self.attn_impl,
                            recurrent=self.recurrent)

    def write_prompt(self, layer, k, v):
        """Prefill write of a whole (B, H, T, D) chunk starting at
        position `length`. Folded onto the write_decode positional
        scatter (token j of slot b lands at length + j through the page
        table), so any offset works — page-aligned starts (the classic
        whole-prompt prefill at length==0, or a suffix landing right
        after prefix-cache pages) and mid-page chunk cursors alike.
        Lockstep (scalar-length) caches only; ragged slots prefill
        through the unified chunked dispatch (serving.ServingEngine),
        which IS write_decode. Returns gathered (B, H, T_max, D) views
        + the updated cache, like write()."""
        if self.ragged:
            raise MXNetError("write_prompt needs a lockstep cache "
                             "(scalar length); ragged slots prefill "
                             "individually (serving.ServingEngine)")
        new = self.write_decode(layer, k, v)
        H = k.shape[1]
        return (new._gather(new.k_pages, layer, H, new.k_scale),
                new._gather(new.v_pages, layer, H, new.v_scale), new)

    def advance(self, n):
        return PagedKVCache(self.k_pages, self.v_pages, self.page_table,
                            self.length + n, page_lock=self.page_lock,
                            spans=self.spans, k_scale=self.k_scale,
                            v_scale=self.v_scale, attn_impl=self.attn_impl,
                            recurrent=self.recurrent)

    def key_mask(self, extra=0):
        """Validity over key positions: (T_max,) in lockstep mode,
        (B, T_max) in ragged mode."""
        pos = jnp.arange(self.max_length)
        if self.ragged:
            return pos[None, :] < (self.length + extra)[:, None]
        return pos < (self.length + extra)

    def with_recurrent(self, recurrent):
        """This cache with its recurrent-state pytree replaced."""
        new = self.advance(0)
        new.recurrent = recurrent
        return new

    def tree_flatten(self):
        return (self.k_pages, self.v_pages, self.page_table,
                self.length, self.page_lock, self.spans,
                self.k_scale, self.v_scale, self.recurrent), self.attn_impl

    @classmethod
    def tree_unflatten(cls, aux, children):
        *rest, recurrent = children
        return cls(*rest, attn_impl=aux, recurrent=recurrent)
