"""GPT-2 model family with static-cache autoregressive decode.

Reference parity: GluonNLP's GPT-2 (gluon-nlp model zoo, text-generation
scripts; target workload "GPT-2 774M" in BASELINE.json). SURVEY.md §3.5
documents the reference's decode loop: hybridized step with per-layer
(k, v) state lists re-`nd.concat`-ed every token — reallocation plus
per-length shape re-inference. Here decode runs against the static
KVCache/PagedKVCache primitive (models/kv_cache.py) inside ONE compiled
`lax.while_loop` program (ops/control_flow.py), so the whole generation
is a single XLA computation with no host round-trips and no
recompilation per length.

Attr names (query/key/value/proj, fc1/fc2, *_embed) line up with
parallel.megatron_dense_rules so tp/fsdp sharding attaches unchanged.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock, _trace_channel
from ..gluon.nn import Dense, Dropout, Embedding, LayerNorm
from ..ndarray.ndarray import NDArray
from ..ops import nn as _opnn
from .kv_cache import KVCache, PagedKVCache

__all__ = ["GPT2Config", "GPT2Model", "GPT2ForCausalLM", "gpt2_small_config",
           "gpt2_medium_config", "gpt2_774m_config", "gpt2_xl_config",
           "set_adapter_ctx", "set_tp_ctx"]

# -- serving LoRA adapter context -------------------------------------------
# The serving engine sets this (to TRACED slab arrays) around
# model.forward while tracing its compiled programs, so the batched
# forward gathers each row's low-rank delta without the model's public
# signature growing adapter arguments. (A, B, scale, slots): the
# AdapterPool slab — A (4, L, S, U, R), B (4, L, S, R, U), scale (S,)
# — plus the per-batch-row slab slot ids (Bsz,) int32. Slot 0 is the
# null adapter (zeros, scale 0), so rows without an adapter add an
# exact zero. None everywhere outside those traces.
_adapter_ctx = None


def set_adapter_ctx(ctx):
    """Install the serving adapter context; returns the previous value
    so callers can restore it in a finally block."""
    global _adapter_ctx
    prev = _adapter_ctx
    _adapter_ctx = ctx
    return prev


# -- serving tensor-parallel context ----------------------------------------
# The serving engine sets this while tracing its unified dispatch inside
# a shard_map over the mesh's "tp" axis: (axis_name, size). Under it the
# forward is the megatron head-wise split — qkv/fc1 run on head-sliced
# weights unchanged (column parallel), `_split` reshapes to the
# per-shard head count, and proj/fc2 become row-parallel: a no-bias
# partial matmul + ONE lax.psum + the (replicated) bias added once.
# None everywhere outside those traces, where every code path below is
# byte-identical to the unsharded program.
_tp_ctx = None


def set_tp_ctx(ctx):
    """Install the serving tensor-parallel context ((axis_name, size)
    or None); returns the previous value so callers can restore it in a
    finally block."""
    global _tp_ctx
    prev = _tp_ctx
    _tp_ctx = ctx
    return prev


class GPT2Config:
    def __init__(self, vocab_size=50257, units=768, num_layers=12,
                 num_heads=12, max_length=1024, dropout=0.1,
                 attention_dropout=0.1, layer_norm_eps=1e-5,
                 activation="gelu_tanh", attention_impl="auto",
                 dtype="float32"):
        self.vocab_size = vocab_size
        self.units = units
        self.hidden_size = 4 * units
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_length = max_length
        self.dropout = dropout
        self.attention_dropout = attention_dropout
        self.layer_norm_eps = layer_norm_eps
        self.activation = activation
        self.attention_impl = attention_impl
        self.dtype = dtype

    def num_params(self):
        c = self
        embed = (c.vocab_size + c.max_length) * c.units
        per_layer = (4 * (c.units * c.units + c.units)
                     + 2 * c.units * c.hidden_size
                     + c.hidden_size + c.units
                     + 4 * c.units)
        return embed + c.num_layers * per_layer + 2 * c.units  # final LN


def gpt2_small_config(**kw):           # 124M
    return GPT2Config(**kw)


def gpt2_medium_config(**kw):          # 355M
    kw.setdefault("units", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    return GPT2Config(**kw)


def gpt2_774m_config(**kw):            # the BASELINE.json target workload
    kw.setdefault("units", 1280)
    kw.setdefault("num_layers", 36)
    kw.setdefault("num_heads", 20)
    return GPT2Config(**kw)


def gpt2_xl_config(**kw):              # 1.5B
    kw.setdefault("units", 1600)
    kw.setdefault("num_layers", 48)
    kw.setdefault("num_heads", 25)
    return GPT2Config(**kw)


class GPT2Attention(HybridBlock):
    """Causal self-attention with optional static-cache decode."""

    def __init__(self, units, num_heads, dropout=0.0,
                 attention_impl="auto", **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} % heads {num_heads} != 0")
        self._units, self._num_heads = units, num_heads
        self._dropout = dropout
        self._impl = attention_impl
        self.query = Dense(units, flatten=False, in_units=units)
        self.key = Dense(units, flatten=False, in_units=units)
        self.value = Dense(units, flatten=False, in_units=units)
        self.proj = Dense(units, flatten=False, in_units=units)

    def _split(self, x, bthd=False):
        b, t, _ = x.shape
        h, d = self._num_heads, self._units // self._num_heads
        if _tp_ctx is not None:
            h //= _tp_ctx[1]     # per-shard head slice inside shard_map
        x = x.reshape((b, t, h, d))
        return x if bthd else x.transpose((0, 2, 1, 3))

    def _lora(self, y, pidx, layer_idx, x):
        """y + this batch's low-rank delta for projection `pidx`
        (0..3 = query/key/value/proj, the slab's leading axis):
        ``x @ A_s @ B_s * alpha/r`` with each row s gathering its own
        slab slot. No-op (returns y untouched — the compiled program
        is byte-identical to the adapter-free one) outside a serving
        adapter context."""
        if _adapter_ctx is None or layer_idx is None:
            return y
        d = self._lora_delta(pidx, layer_idx, x)
        yd = y._data if isinstance(y, NDArray) else y
        return NDArray(yd + d)

    def _lora_delta(self, pidx, layer_idx, x):
        """The low-rank delta itself. Under a serving tp context the
        slabs enter head-sliced on their U axis (A on in-features for
        pidx 3, B on out-features for 0..2), so the rank reduction is a
        per-shard partial summed with ONE psum; for the row-parallel
        proj (pidx 3) the local out-slice is scattered to its head
        offset so the CALLER's psum assembles the full-width delta —
        no collective beyond the one the matmul already pays."""
        ctx = _adapter_ctx
        # 4-tuple = float slab; 6-tuple = int8 slab with per-(proj,
        # layer, slot) dequant scales appended (serving.AdapterPool
        # quantized mode) — dequant on the gathered slot slices, so HBM
        # traffic for the slab stays one byte per element
        A, B, scale, slots = ctx[:4]
        xd = x._data if isinstance(x, NDArray) else x
        ag = jnp.take(A[pidx, layer_idx], slots, axis=0)   # (Bsz, U, R)
        bg = jnp.take(B[pidx, layer_idx], slots, axis=0)   # (Bsz, R, U)
        s = jnp.take(scale, slots, axis=0)                 # (Bsz,)
        if len(ctx) == 6:
            asc, bsc = ctx[4], ctx[5]
            sa = jnp.take(asc[pidx, layer_idx], slots, axis=0)  # (Bsz,)
            sb = jnp.take(bsc[pidx, layer_idx], slots, axis=0)
            ag = ag.astype(jnp.float32) * sa[:, None, None]
            bg = bg.astype(jnp.float32) * sb[:, None, None]
        tp = _tp_ctx
        if tp is None:
            d = jnp.einsum("btu,bur->btr", xd.astype(ag.dtype), ag)
            d = jnp.einsum("btr,bru->btu", d, bg)
            return (d.astype(jnp.float32)
                    * s[:, None, None]).astype(xd.dtype)
        axis, size = tp
        u_loc = ag.shape[1]
        i = jax.lax.axis_index(axis)
        if pidx == 3:
            xs = xd          # proj input is already the local head slice
        else:
            # qkv deltas contract the REPLICATED residual against the
            # local U-rows of A: slice x to match
            xs = jax.lax.dynamic_slice_in_dim(xd, i * u_loc, u_loc, 2)
        r = jax.lax.psum(
            jnp.einsum("btu,bur->btr", xs.astype(ag.dtype), ag), axis)
        d = jnp.einsum("btr,bru->btu", r, bg)
        d = (d.astype(jnp.float32) * s[:, None, None]).astype(xd.dtype)
        if pidx == 3:
            full = jnp.zeros(d.shape[:2] + (u_loc * size,), d.dtype)
            d = jax.lax.dynamic_update_slice_in_dim(full, d, i * u_loc, 2)
        return d

    def _proj_out(self, out, layer_idx):
        """proj(out) + LoRA delta. Under a serving tp context `out` is
        the local head slice and proj is row-parallel: a no-bias partial
        matmul plus the scattered LoRA partial, ONE psum assembling
        both, the (replicated) bias added once after."""
        tp = _tp_ctx
        if tp is None:
            return self._lora(self.proj(out), 3, layer_idx, out)
        part = _opnn.FullyConnected(out, self.proj.weight.data(), None,
                                    no_bias=True, flatten=False)
        part = part._data if isinstance(part, NDArray) else part
        if _adapter_ctx is not None and layer_idx is not None:
            part = part + self._lora_delta(3, layer_idx, out)
        full = jax.lax.psum(part, tp[0])
        if self.proj.bias is not None:
            full = full + self.proj.bias.data()._data
        return NDArray(full)

    def forward(self, x, cache=None, layer_idx=None):
        if cache is None:
            # training path: head split stays in BTHD — the attention op
            # consumes it natively (packed Pallas kernel), so no
            # (B,T,H,D)->(B,H,T,D) relayout copies hit HBM
            q = self._split(self._lora(self.query(x), 0, layer_idx, x),
                            bthd=True)
            k = self._split(self._lora(self.key(x), 1, layer_idx, x),
                            bthd=True)
            v = self._split(self._lora(self.value(x), 2, layer_idx, x),
                            bthd=True)
            out = _opnn.dot_product_attention(
                q, k, v, causal=True, dropout_p=self._dropout,
                impl=self._impl, layout="BTHD")
            b, t, h, d = out.shape
            out = out.reshape((b, t, h * d))
            return self._proj_out(out, layer_idx), cache
        # static-cache path (inference): write this chunk at position
        # cache.length, attend over the full buffer under a validity ×
        # causal mask. The chunk is either the whole prompt (prefill)
        # or one token (decode). Cache blocks are laid out BHTD.
        q = self._split(self._lora(self.query(x), 0, layer_idx, x))
        k = self._split(self._lora(self.key(x), 1, layer_idx, x))
        v = self._split(self._lora(self.value(x), 2, layer_idx, x))
        t = q.shape[2]
        if getattr(cache, "ragged", False):
            # ragged serving path: each slot appends at its OWN length
            # and attends only its live pages through the span kernel —
            # no dense (B, T_max) gather at all. t == 1 is plain decode;
            # t > 1 is a multi-query dispatch (speculative verify, or
            # the unified chunked-prefill serving step) where query
            # position j attends < length + j + 1 through the kernel's
            # per-position causal offsets. When the cache carries
            # per-slot `spans` (the unified fixed-shape dispatch), rows
            # past a slot's span neither attend nor write — the kernel
            # emits exact zeros for them.
            from ..ops.pallas_attention import ragged_span_attention
            cache = cache.write_decode(layer_idx, k._data, v._data)
            impl = cache.attn_impl
            interp = impl == "pallas_interpret"
            impl = "pallas" if interp else impl
            quant = getattr(cache, "quantized", False)
            # int8 pages keep q in its own compute dtype (casting q to
            # the pool dtype would destroy it) and thread the per-(page,
            # head) scales into the fused dequant
            qd = q._data.transpose(0, 2, 1, 3)
            if not quant:
                qd = qd.astype(cache.k_pages.dtype)
            # the whole pools go in and the kernel's BlockSpec picks
            # the layer: slicing one out here would copy it
            out = ragged_span_attention(
                qd, cache.k_pages, cache.v_pages,
                cache.page_table, cache.length + 1,
                q_counts=getattr(cache, "spans", None),
                impl=impl, interpret=interp,
                k_scale=cache.k_scale, v_scale=cache.v_scale,
                layer=layer_idx)
            b, tq, h, d = out.shape
            out = out.astype(q._data.dtype).reshape(b, tq, h * d)
            out = NDArray(out)
            return self._proj_out(out, layer_idx), cache
        if t > 1:
            k_all, v_all, cache = cache.write_prompt(
                layer_idx, k._data, v._data)
        else:
            k_all, v_all, cache = cache.write(
                layer_idx, k._data, v._data)
        valid = cache.key_mask(extra=t)           # (T_max,)
        q_pos = cache.length + jnp.arange(t)      # global positions
        k_pos = jnp.arange(k_all.shape[2])
        causal = k_pos[None, :] <= q_pos[:, None]  # (t, T_max)
        mask = (valid[None, :] & causal)[None, None]  # (1,1,t,T_max)
        out = _opnn.dot_product_attention(
            q, NDArray(k_all.astype(q._data.dtype)),
            NDArray(v_all.astype(q._data.dtype)), NDArray(mask),
            impl="xla" if self._impl == "ring" else self._impl)
        b, h, t, d = out.shape
        out = out.transpose((0, 2, 1, 3)).reshape((b, t, h * d))
        return self._proj_out(out, layer_idx), cache


class GPT2Block(HybridBlock):
    """Pre-LN transformer block (GPT-2 style)."""

    def __init__(self, cfg: GPT2Config, **kwargs):
        super().__init__(**kwargs)
        c = cfg
        self.ln1 = LayerNorm(epsilon=c.layer_norm_eps, in_channels=c.units)
        self.attn = GPT2Attention(c.units, c.num_heads,
                                  dropout=c.attention_dropout,
                                  attention_impl=c.attention_impl)
        self.ln2 = LayerNorm(epsilon=c.layer_norm_eps, in_channels=c.units)
        self.fc1 = Dense(c.hidden_size, flatten=False, in_units=c.units)
        self.fc2 = Dense(c.units, flatten=False, in_units=c.hidden_size)
        self._activation = c.activation
        self.dropout = Dropout(c.dropout) if c.dropout else None

    def _fc2_out(self, h):
        """fc2(h). Under a serving tp context fc1 was column-parallel
        (h is the local hidden slice), so fc2 is row-parallel: no-bias
        partial matmul, ONE psum, the replicated bias added once."""
        tp = _tp_ctx
        if tp is None:
            return self.fc2(h)
        part = _opnn.FullyConnected(h, self.fc2.weight.data(), None,
                                    no_bias=True, flatten=False)
        part = part._data if isinstance(part, NDArray) else part
        full = jax.lax.psum(part, tp[0])
        if self.fc2.bias is not None:
            full = full + self.fc2.bias.data()._data
        return NDArray(full)

    def forward(self, x, cache=None, layer_idx=None):
        h, cache = self.attn(self.ln1(x), cache, layer_idx)
        if self.dropout is not None:
            h = self.dropout(h)
        x = x + h
        h = _opnn.Activation(self.fc1(self.ln2(x)),
                             act_type=self._activation)
        h = self._fc2_out(h)
        if self.dropout is not None:
            h = self.dropout(h)
        return x + h, cache


class GPT2Model(HybridBlock):
    """Embeddings + pre-LN blocks + final LN."""

    def __init__(self, config: GPT2Config, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.word_embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        self.position_embed = Embedding(c.max_length, c.units, dtype=c.dtype)
        self.embed_dropout = Dropout(c.dropout) if c.dropout else None
        for i in range(c.num_layers):
            self.register_child(GPT2Block(c), name=f"layer{i}")
        self.ln_f = LayerNorm(epsilon=c.layer_norm_eps, in_channels=c.units)

    def blocks(self):
        return [child for name, child in self._children.items()
                if name.startswith("layer")]

    def forward(self, inputs, cache=None):
        b, t = inputs.shape
        start = cache.length if cache is not None else 0
        if cache is not None and cache.ragged:
            # per-slot positions: slot b's token sits at its own length
            positions = NDArray(start[:, None]
                                + jnp.arange(t, dtype=jnp.int32))
        else:
            positions = NDArray(start + jnp.arange(t, dtype=jnp.int32))
        x = self.word_embed(inputs) + self.position_embed(positions)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        for i, block in enumerate(self.blocks()):
            x, cache = block(x, cache, i)
        x = self.ln_f(x)
        if cache is not None:
            cache = cache.advance(t)
        return x, cache


class GPT2ForCausalLM(HybridBlock):
    """GPT-2 with the weight-tied LM head + static-cache generate().
    `forward` is `head(hidden(ids, cache))`: serving.ServingEngine calls
    the two apart, so only the rows it samples pass the head."""

    def __init__(self, config: GPT2Config, **kwargs):
        super().__init__(**kwargs)
        self.config = config
        self.backbone = GPT2Model(config)

    def hidden(self, inputs, cache=None):
        """Everything up to and including the final LayerNorm:
        (B, T) ids -> ((B, T, C) hidden states, advanced cache)."""
        return self.backbone(inputs, cache)

    def head(self, h):
        """(..., C) final hidden states -> (..., V) logits, row by row."""
        w = self.backbone.word_embed.weight.data()   # (V, C) tied
        return _opnn.FullyConnected(h, w, None, no_bias=True,
                                    flatten=False)

    def forward(self, inputs, cache=None):
        h, cache = self.hidden(inputs, cache)
        logits = self.head(h)
        if cache is None:
            return logits
        return logits, cache

    def state_spec(self):
        """What a serving slot holds for this model: KV pages of
        `num_kv_heads` x `head_dim` a layer and no `recurrent` state
        (models/falcon_h1.py declares some)."""
        c = self.config
        return {"num_layers": c.num_layers, "num_kv_heads": c.num_heads,
                "head_dim": c.units // c.num_heads, "recurrent": {}}

    # -- decode -----------------------------------------------------------
    def make_cache(self, batch, max_length, paged=False, page_size=64,
                   dtype=None, page_table=None, lengths=None,
                   attn_impl="auto", kv_dtype=None):
        c = self.config
        cls = PagedKVCache if paged else KVCache
        if kv_dtype is not None and not paged:
            raise MXNetError("kv_dtype needs a paged cache")
        kw = dict(page_size=page_size, page_table=page_table,
                  lengths=lengths, attn_impl=attn_impl,
                  kv_dtype=kv_dtype) if paged else {}
        return cls.create(c.num_layers, batch, c.num_heads, max_length,
                          c.units // c.num_heads,
                          dtype=dtype or jnp.dtype(c.dtype), **kw)

    def generate(self, input_ids, max_new_tokens, do_sample=False,
                 temperature=1.0, top_k=None, top_p=None,
                 eos_token_id=None, seed=0, paged=False, page_size=64,
                 mesh=None):
        """Autoregressive generation: prefill + ONE compiled while_loop
        decode over the static cache (greedy, or top-k/temperature
        sampling). Returns (B, max_new_tokens) int32 NDArray; positions
        after an eos_token_id hit are padded with eos.

        This is the SURVEY §3.5 fix: the reference re-concats KV state and
        re-infers shapes per token; here token t+1 costs exactly one
        cached-program execution.

        mesh: pass a device mesh EXPLICITLY for sharded decode —
        parameters enter with their `param.sharding` specs
        (apply_sharding_rules / megatron_dense_rules for tensor
        parallelism) and XLA partitions the whole decode program, cache
        included, inserting the tp collectives; prompt/outputs stay
        replicated. An ambient mesh_scope is deliberately NOT picked up
        (an eval-sample generate inside a training mesh scope should not
        silently compile a partitioned replica-everything program)."""
        from ..ops.control_flow import while_loop
        from ..parallel.mesh import PartitionSpec, mesh_scope, \
            named_sharding

        if top_p is not None and top_p >= 1.0:
            top_p = None  # the full distribution — a true no-op (f32
            # cumsum rounding above 1.0 would otherwise cut tail tokens)
        ids = input_ids._data if isinstance(input_ids, NDArray) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        B, T0 = ids.shape
        total = T0 + max_new_tokens
        c = self.config
        if total > c.max_length:
            raise MXNetError(
                f"prompt {T0} + {max_new_tokens} new > max_length "
                f"{c.max_length}")
        if paged:
            total = ((total + page_size - 1) // page_size) * page_size
        params = list(self.collect_params().values())
        param_datas = tuple(p.data()._data for p in params)
        eos = -1 if eos_token_id is None else int(eos_token_id)

        def _select(logits, key, step):
            logits = logits.astype(jnp.float32)
            if not do_sample:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if temperature != 1.0:
                logits = logits / temperature
            if top_k is not None or top_p is not None:
                # ONE descending sort serves both filters (per decode
                # step in the compiled loop — don't sort twice)
                sort_idx = jnp.argsort(-logits, axis=-1)
                sorted_logits = jnp.take_along_axis(logits, sort_idx,
                                                    axis=-1)
                cut_sorted = jnp.zeros(logits.shape, bool)
                if top_k is not None:
                    cut_sorted |= jnp.arange(
                        logits.shape[-1])[None, :] >= top_k
                if top_p is not None:
                    # nucleus: cut token i only if the mass STRICTLY
                    # before it already exceeds top_p — the top-1 token
                    # always survives (even top_p=0)
                    probs = jax.nn.softmax(sorted_logits, axis=-1)
                    cum = jnp.cumsum(probs, axis=-1)
                    cut_sorted |= (cum - probs) > top_p
                cut = jnp.zeros_like(cut_sorted).at[
                    jnp.arange(logits.shape[0])[:, None], sort_idx].set(
                    cut_sorted)
                logits = jnp.where(cut, -jnp.inf, logits)
            k = jax.random.fold_in(key, step)
            return jax.random.categorical(k, logits, axis=-1).astype(
                jnp.int32)

        def run(param_arrays, prompt, key):
            saved = [p._data for p in params]
            _trace_channel.push_frame()
            try:
                for p, d in zip(params, param_arrays):
                    arr = NDArray(d)
                    arr._grad_req = "null"
                    p._data = arr
                cache = self.make_cache(B, total, paged=paged,
                                        page_size=page_size)
                logits, cache = self.forward(NDArray(prompt), cache)
                next_tok = _select(logits._data[:, -1, :], key, 0)
                raw = lambda x: x._data if isinstance(x, NDArray) else x  # noqa: E731

                def cond_fn(i, tok, cache, out, done):
                    i, done = raw(i), raw(done)
                    return (i < max_new_tokens) & ~done.all()

                def body_fn(i, tok, cache, out, done):
                    i, tok, out, done = map(raw, (i, tok, out, done))
                    # the eos token itself is emitted; rows already done
                    # keep padding with eos
                    out = out.at[:, i].set(jnp.where(done, eos, tok))
                    logits, cache2 = self.forward(
                        NDArray(tok[:, None]), cache)
                    nxt = _select(logits._data[:, -1, :], key, i + 1)
                    done = done | (tok == eos)
                    return (), (i + 1, nxt, cache2, out, done)

                # body writes slot i each iteration (0..max_new-1); on an
                # all-eos early exit the untouched tail keeps the eos fill
                out0 = jnp.full((B, max_new_tokens),
                                eos if eos_token_id is not None else 0,
                                jnp.int32)
                done0 = jnp.zeros((B,), bool)
                _, final = while_loop(
                    cond_fn, body_fn,
                    [jnp.zeros((), jnp.int32), next_tok, cache, out0,
                     done0],
                    max_iterations=max_new_tokens)
                return raw(final[3])
            finally:
                _trace_channel.pop_frame()
                for p, d in zip(params, saved):
                    p._data = d

        import os as _os
        key = jax.random.PRNGKey(seed)
        # bounded: (B, T0, sampling-config, mesh) churn across serving-
        # style callers must not grow the cache without limit
        jitted = self.__dict__.get("_generate_cache")
        if jitted is None:
            from ..gluon.block import LRUTraceCache
            jitted = LRUTraceCache(
                int(_os.environ.get("MXNET_TPU_GENERATE_CACHE_SIZE", 16)))
            self.__dict__["_generate_cache"] = jitted
        # Mesh and PartitionSpec hash by value, so equal meshes share the
        # compiled program, and changing sharding rules between calls
        # compiles a fresh one instead of reusing stale in_shardings
        shard_sig = tuple(p.sharding for p in params) \
            if mesh is not None else None
        sig = (B, T0, max_new_tokens, do_sample, temperature, top_k,
               top_p, eos_token_id, paged, page_size, mesh, shard_sig)
        fn = jitted.get(sig)
        if fn is None:
            if mesh is not None:
                with mesh_scope(mesh):
                    repl = named_sharding(PartitionSpec())
                    pshard = tuple(
                        named_sharding(p.sharding
                                       if p.sharding is not None
                                       else PartitionSpec())
                        for p in params)
                    fn = jax.jit(run,
                                 in_shardings=(pshard, repl, repl))
            else:
                fn = jax.jit(run)
            jitted[sig] = fn
        if mesh is not None:
            with mesh_scope(mesh):
                out = fn(param_datas, ids, key)
        else:
            out = fn(param_datas, ids, key)
        return NDArray(out)


def gpt2_pp_functions(model, n_stages):
    """Split a GPT2ForCausalLM into the (embed_fn, stage_fn,
    head_loss_fn) functional triple `parallel.PPTrainStep` consumes,
    plus its parameter pytrees: returns (embed_fn, stage_fn,
    head_loss_fn, embed_params, stacked_body_params, head_params, tied).

    Stage s owns num_layers/n_stages consecutive GPT2Blocks; the token+
    position embedding runs on stage 0 and the final-LN + weight-tied LM
    head + causal cross-entropy on the last stage (tied=("wte", "wte")
    tells PPTrainStep to sum the two wte gradients and mirror the master
    copy). Dropout must be 0 (the pipeline recomputes stages for the
    1F1B backward; a stochastic forward would not reproduce).

    Parity note: the reference has no pipeline parallelism at all —
    SURVEY.md §2.4 'Model parallelism (manual, group2ctx)'; this is the
    brief's first-class TPU replacement (SURVEY §7.2 M8).
    """
    from .. import autograd as _ag
    from ..parallel import stack_stage_params

    c = model.config
    if c.dropout or c.attention_dropout:
        raise MXNetError("gpt2_pp_functions: build the model with "
                         "dropout=0 (pipeline recompute must be "
                         "deterministic)")
    backbone = model.backbone
    blocks = backbone.blocks()
    L = len(blocks)
    if L % n_stages:
        raise MXNetError(f"{L} layers not divisible by {n_stages} stages")
    k = L // n_stages

    def block_params(b):
        return {name: p.data()._data
                for name, p in b.collect_params().items()}

    stage_trees = [[block_params(b) for b in blocks[s * k:(s + 1) * k]]
                   for s in range(n_stages)]
    stacked = stack_stage_params(stage_trees)
    template = blocks[:k]

    def apply_block(b, params, h):
        ps = b.collect_params()
        saved = [(p, p._data) for p in ps.values()]
        try:
            for name, p in ps.items():
                arr = NDArray(params[name])
                arr._grad_req = "null"
                p._data = arr
            with _ag._Scope(False, False):
                out, _ = b.forward(NDArray(h), None, None)
            return out._data
        finally:
            for p, d in saved:
                p._data = d

    def stage_fn(stage_params, h):
        for i in range(k):
            h = apply_block(template[i], stage_params[i], h)
        return h

    wte = backbone.word_embed.weight.data()._data
    embed_params = {"wte": wte,
                    "wpe": backbone.position_embed.weight.data()._data}
    head_params = {"g": backbone.ln_f.gamma.data()._data,
                   "b": backbone.ln_f.beta.data()._data,
                   "wte": wte}
    eps = c.layer_norm_eps

    def embed_fn(ep, ids):
        t = ids.shape[1]
        return ep["wte"][ids] + ep["wpe"][:t][None]

    def head_loss_fn(hp, h, labels):
        x32 = h.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = x32.var(-1, keepdims=True)
        xn = (x32 - mean) * jax.lax.rsqrt(var + eps)
        xn = xn * hp["g"].astype(jnp.float32) + hp["b"].astype(jnp.float32)
        logits = xn @ hp["wte"].astype(jnp.float32).T
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, labels[..., None].astype(jnp.int32),
                                   -1)
        return nll.mean().astype(jnp.float32)

    return (embed_fn, stage_fn, head_loss_fn, embed_params, stacked,
            head_params, [("wte", "wte")])
