"""Continuous-batching serving engine.

Execution model (docs/SERVING.md):

  * B fixed decode SLOTS share one PagedKVCache page pool. Each slot has
    its own live length; the decode forward runs all B slots through the
    ragged paged-attention kernel, so per-token HBM traffic is the sum
    of LIVE lengths, not B × max_length.
  * PAGE OWNERSHIP is explicit: a host-side ref-counted allocator
    (serving/page_pool.py) hands each admitted request its pages, and a
    radix-tree prefix cache (serving/prefix_cache.py) lets requests
    SHARE the pages of a common prompt prefix — admission does a
    longest-prefix match, maps the cached pages into the slot's table
    by page-table surgery, and prefills only the uncached suffix.
    Shared pages are read-only through the page table (the decode
    kernel is unchanged); the in-program page_lock mask plus a host
    copy-on-write split for fully-cached prompts guarantee no write
    ever lands in a shared page.
  * EVERY dispatch is ONE fixed-shape unified program of width W =
    max(chunk_tokens, spec_tokens, 2): each slot consumes q_counts[b]
    of its W query positions — a PREFILL CHUNK (C tokens of the prompt
    streamed through the span kernel's per-slot query counts), a
    DECODE step (1), a SPECULATIVE VERIFY (1 + drafts), or idle (0).
    Admission never runs a forward: it maps pages, parks the prompt as
    a host-side chunk queue, and the regular dispatch loop feeds
    chunk_tokens of it per tick next to everyone else's decode — so a
    4k-token prompt never monopolizes a dispatch, and prompt length is
    DATA, not a program shape axis (zero prefill retraces, ever).
  * The final chunk of a prompt samples the request's first token in
    the same dispatch; prefill_chunk_budget caps the prompt tokens fed
    per dispatch across all slots (round-robin), bounding every other
    slot's inter-token latency to one dispatch period.
  * SPECULATIVE mode (speculative=True) rides the same program: a
    host-side prompt-lookup drafter (serving/speculative.py) proposes
    up to spec_tokens-1 candidates from each request's own history,
    the span kernel verifies all of them under per-position causal
    offsets, and only the accepted count advances the slot's length —
    greedy output bit-identical to spec-off, sampled output
    distribution-preserving. A degraded engine keeps dispatching the
    same program with zero drafts (bit-identical to plain decode).
  * Per-slot scalar state (lengths, budgets, sampling knobs, tables,
    page_lock) is DEVICE-RESIDENT between dispatches; admission/finish/
    cancel upload one slot's delta in one jitted scatter (_sync_slot),
    so a decode dispatch pays zero host->device state uploads.
  * Between dispatches the host frees finished slots (releasing page
    leases back to the pool/prefix cache) and admits queued requests
    (FIFO) — continuous batching: nobody waits for the slowest
    sequence in a fixed batch.

Everything per-request (sampling knobs, seeds, eos, budgets, chunk
cursors) is a per-slot ARRAY in the compiled program, so admission
never recompiles: the engine owns at most two programs (greedy-only
and mixed-sampling flavors of the one unified dispatch) for its whole
lifetime — there is no prefill program family and no bucket axis.

ROBUSTNESS (docs/SERVING.md "Robustness"): step() is supervised — a
dispatch exception no longer wedges the engine. The supervisor catches
it, audits the page pool, rolls the implicated slots back (leases
released, state parked), re-queues innocents with backoff, and
quarantines a request whose dispatches fail `max_retries` times
(terminal reason="error"). Requests carry deadlines (expired queued
work is shed before admission; running work past deadline is cancelled
at the next dispatch boundary) and priority classes; an attached
SheddingPolicy (serving/policy.py) sheds or down-prioritizes work
before it queues and latches graceful degradation under sustained
overload. A re-queued, partially-decoded request restarts by
prefilling prompt+emitted and resuming its RNG counter at the next
token index — per-request streams are keyed (seed, token_index), so
restarted outputs are bit-identical to an uninterrupted run.
"""
from __future__ import annotations

import copy
import heapq
import inspect
import itertools
import time
import weakref
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..analysis import loop_only, supervised, thread_safe
from ..telemetry import cost as _cost
from ..telemetry import ledger as _ledger
from ..base import MXNetError
from ..gluon.block import _trace_channel
from ..ops.kernel_paths import PATHS as _KERNEL_PATHS, \
    TILES as _KERNEL_TILES
from ..models.kv_cache import (RING_LEAVES, PagedKVCache, gather_kv_pages,
                               scatter_kv_pages)
from ..ndarray.ndarray import NDArray
from ..telemetry import server as _tserver
from ..telemetry import gc_totals, span
from ..models.gpt2 import set_adapter_ctx as _set_adapter_ctx
from ..models.gpt2 import set_tp_ctx as _set_tp_ctx
from ..parallel.mesh import (AXIS_TP, PartitionSpec, named_sharding,
                             serving_tp_mesh, shard_map_compat)
from ..parallel.rules import serving_tp_rules
from .adapters import AdapterPoolExhausted
from .host_tier import HostPagePool
from .page_pool import PagePool, PagePoolExhausted
from .prefix_cache import PrefixCache
from .sampling import sample_tokens, slot_keys
from .weight_quant import (build_weight_plan, deregister_w8_weight,
                           register_w8_weight)
from .scheduler import (QueueFullError, Request, ShedError,
                        SlotScheduler, TenantQuotaError, _seq_counter)
from .speculative import PromptLookupProposer, verify_tokens

__all__ = ["ServingEngine"]

_engine_ids = itertools.count()

# Engine metrics live as per-engine labeled children (engine=<ordinal>)
# of process-global instruments: `ServingEngine.stats` reads this
# engine's children, the registry/prometheus view aggregates across
# engines. docs/OBSERVABILITY.md catalogs each one.
_E = ("engine",)
# how often a dispatch folds the model's device counters into the host's
# totals when nobody reads stats (ServingEngine._fold_model_counts)
_FOLD_EVERY = 4096
# serving_tick_seconds: 1 ms to 16.4 s, four buckets an octave
_TICK_BUCKETS = telemetry.exponential_buckets(1e-3, 2 ** 0.25, 57)
# how many of its slowest ticks an engine keeps whole
SLOWEST_TICKS_KEPT = 8


def _engine_metrics(eid):
    c, g, h = telemetry.counter, telemetry.gauge, telemetry.histogram
    m = {
        "prefills": c("serving_prefill_total",
                      "prompts fully prefilled — final chunk landed and "
                      "the first token sampled (one per admission)", _E),
        "prefill_tokens": c(
            "serving_prefill_tokens_total",
            "prompt tokens actually computed by prefill chunks (the "
            "uncached suffix only when the prefix cache hits)", _E),
        "prefill_chunks": c(
            "serving_prefill_chunks_total",
            "prompt chunks fed through the unified dispatch (a prompt "
            "of T uncached tokens streams in ceil(T / chunk_tokens) "
            "chunks, budget permitting)", _E),
        "prefill_pending": g(
            "serving_prefill_pending_tokens",
            "chunk-queue depth: admitted prompt tokens not yet fed to "
            "a dispatch, summed over slots", _E),
        "decode_dispatches": c("serving_decode_dispatch_total",
                               "unified dispatches run (one fixed-shape "
                               "program per tick)", _E),
        "decode_steps": c("serving_decode_steps_total",
                          "decode steps run (== dispatches: one "
                          "forward per tick)", _E),
        "tokens_emitted": c("serving_tokens_emitted_total",
                            "tokens sampled and handed to requests", _E),
        "requests_finished": c("serving_requests_finished_total",
                               "requests completed (eos or budget)", _E),
        "requests_rejected": c(
            "serving_requests_rejected_total",
            "submissions refused (queue full / prompt too long)", _E),
        "requests_cancelled": c(
            "serving_requests_cancelled_total",
            "requests aborted via cancel() (queued or running)", _E),
        "prefix_hits": c(
            "serving_prefix_cache_hits_total",
            "admissions whose prompt matched >= 1 cached page", _E),
        "prefix_misses": c(
            "serving_prefix_cache_misses_total",
            "admissions with no cached prefix", _E),
        "prefix_tokens_saved": c(
            "serving_prefix_tokens_saved_total",
            "prompt tokens skipped at prefill (attached from cache)", _E),
        "prefix_evicted_pages": c(
            "serving_prefix_cache_evicted_pages_total",
            "cached pages reclaimed by the LRU-by-leaf policy", _E),
        "spec_draft_tokens": c(
            "serving_spec_draft_tokens_total",
            "draft tokens proposed by the prompt-lookup drafter", _E),
        "spec_accepted_tokens": c(
            "serving_spec_accepted_tokens_total",
            "draft tokens accepted by verification and emitted", _E),
        "spec_rollbacks": c(
            "serving_spec_rollbacks_total",
            "draft tokens rejected by verification (their KV stays "
            "invisible and is overwritten in place)", _E),
        "model_flops": c(
            "serving_model_flops_total",
            "registered cost_analysis FLOPs of every dispatched "
            "prefill/decode/verify program (goodput numerator)", _E),
        "wasted_flops": c(
            "serving_wasted_flops_total",
            "FLOPs spent on drafted-but-rejected speculative "
            "positions (program FLOPs x rejected share)", _E),
        "flops_per_token": g(
            "serving_flops_per_token",
            "model FLOPs per emitted token (goodput: "
            "model_flops_total / tokens_emitted_total)", _E),
        "admission_capacity": g(
            "serving_admission_capacity",
            "estimated max concurrent requests at the current page "
            "budget: active slots + (free + idle cached pages) / "
            "pages per slot", _E),
        "queue_depth": g("serving_queue_depth",
                         "requests waiting for a slot", _E),
        "slot_occupancy": g("serving_slot_occupancy",
                            "slots decoding right now", _E),
        "num_slots": g("serving_slots", "configured decode slots", _E),
        "prefix_cache_pages": g(
            "serving_prefix_cache_pages",
            "KV pages held by the prefix-cache radix tree", _E),
        "prefix_pages_shared": g(
            "serving_prefix_pages_shared",
            "pool pages currently mapped by more than one lease", _E),
        "pool_free_pages": g("serving_page_pool_free",
                             "unallocated pages in the KV page pool", _E),
        "admission_wait": h("serving_admission_wait_seconds",
                            "submit -> slot admission wait", _E),
        "ttft": h("serving_ttft_seconds",
                  "submit -> first token (queue wait + prefill)", _E),
        "token_latency": h(
            "serving_token_latency_seconds",
            "per-token decode latency at dispatch resolution "
            "(dispatch wall / tokens the slot emitted, weighted)", _E),
        "drain_seconds": h("serving_drain_seconds",
                           "serve(): last submit -> queue+slots empty", _E),
        "tick_seconds": h(
            "serving_tick_seconds",
            "wall of one scheduling tick (the serving.step span), in "
            "buckets a factor 2**0.25 apart from 1 ms to 16 s; the exact "
            "maximum is kept beside them", _E, _TICK_BUCKETS),
        "tick_cpu_seconds": c(
            "serving_tick_cpu_seconds_total",
            "CPU time of the serving thread (time.thread_time) between "
            "the two ends of the serving.step spans: the part of "
            "serving_tick_seconds it spent computing; the rest of it the "
            "thread was blocked (on the device, a copy, an upload, a "
            "lock) or descheduled", _E),
        "tick_gc_seconds": c(
            "serving_tick_gc_seconds_total",
            "seconds the garbage collector ran inside scheduling ticks "
            "(a part of their wall, whatever phase it interrupted)", _E),
        "dispatch_errors": c(
            "serving_dispatch_errors_total",
            "dispatch faults the engine supervisor caught (batch rolled "
            "back, engine kept serving)", _E),
        "dispatch_retries": c(
            "serving_dispatch_retries_total",
            "requests re-queued with backoff after a caught dispatch "
            "fault or transient allocation failure", _E),
        "requests_failed": c(
            "serving_requests_failed_total",
            "requests quarantined after max_retries failed dispatches "
            "(terminal reason=\"error\")", _E),
        "overload_level": g(
            "serving_overload_level",
            "shedding-policy assessment: 0 ok, 1 elevated, "
            "2 overloaded", _E),
        "degraded": g(
            "serving_degraded",
            "1 while the engine is gracefully degraded (speculation "
            "suspended, /healthz flagged)", _E),
        "retry_after": g(
            "serving_retry_after_seconds",
            "drain-rate estimate of when a rejected submission could "
            "succeed (attached to shed / queue-full rejections)", _E),
        "adapter_page_ins": c(
            "serving_adapter_page_ins_total",
            "LoRA adapters paged into the device slab (slab-slot scatter "
            "on an acquire miss)", _E),
        "adapter_evictions": c(
            "serving_adapter_evictions_total",
            "resident LoRA adapters LRU-evicted to make room for a "
            "page-in (plus explicit evict() calls)", _E),
        "adapter_resident": g(
            "serving_adapter_resident",
            "LoRA adapters currently resident in the device slab", _E),
        "adapter_pinned": g(
            "serving_adapter_pinned",
            "slab slots pinned by active requests (unevictable)", _E),
        "adapter_slab_bytes": g(
            "serving_adapter_slab_bytes",
            "device bytes held by the LoRA adapter slab (A + B + "
            "scale)", _E),
        "kv_quant_enabled": g(
            "serving_kv_quant_enabled",
            "1 when the KV page pools store int8 codes with per-page "
            "dequant scales (kv_dtype=\"int8\"), else 0", _E),
        "kv_page_bytes": g(
            "serving_kv_page_bytes",
            "HBM bytes one KV page really costs: k+v slabs across all "
            "layers plus the per-page dequant scales when quantized", _E),
        "kv_bytes_per_token": g(
            "serving_kv_bytes_per_token",
            "KV-cache HBM bytes per token position "
            "(kv_page_bytes / page_size) — the capacity headline int8 "
            "pages shrink ~4x", _E),
        "tp_shards": g(
            "serving_tp_shards",
            "tensor-parallel shards the unified dispatch runs across "
            "(head-wise shard_map over the tp mesh axis; 1 = "
            "unsharded)", _E),
        "weight_quant_enabled": g(
            "serving_weight_quant_enabled",
            "1 when the engine serves the megatron col/row dense "
            "weights as int8 codes with fused per-out-tile dequant "
            "(weight_dtype=\"int8\"), else 0", _E),
        "kv_spill_pages": c(
            "serving_kv_spill_pages_total",
            "KV pages whose payload moved device -> host RAM "
            "(prefix-cache eviction spills plus whole-request "
            "preemption swaps)", _E),
        "kv_spill_bytes": c(
            "serving_kv_spill_bytes_total",
            "bytes admitted to the host spill tier", _E),
        "kv_pagein_pages": c(
            "serving_kv_pagein_pages_total",
            "KV pages restored host -> device (radix hits on spilled "
            "nodes plus preemption resumes)", _E),
        "kv_pagein_bytes": c(
            "serving_kv_pagein_bytes_total",
            "bytes read back from the host tier by page-ins", _E),
        "kv_host_evictions": c(
            "serving_kv_host_evictions_total",
            "spilled payloads LRU-dropped by the host tier to admit "
            "newer spills (that state re-prefills if hit again)", _E),
        "preempts": c(
            "serving_preempt_total",
            "running requests preempted by the shedding policy to "
            "free a slot for more-urgent queued work", _E),
        "preempt_resumed": c(
            "serving_preempt_resumed_total",
            "preempted requests spliced straight back into decode "
            "from their swapped KV (no re-prefill)", _E),
        "preempt_restarted": c(
            "serving_preempt_restarted_total",
            "preempted requests that fell back to the replay/restart "
            "path (swap payload or prefix nodes gone) — output still "
            "bit-identical, compute is not saved", _E),
        "recurrent_state_bytes": g(
            "serving_recurrent_state_bytes",
            "device bytes of the slots' recurrent state (fixed-size "
            "per-slot leaves the model declares beside its KV pages; 0 "
            "for a model with pages only)", _E),
        "kv_pool_bytes": g(
            "serving_kv_pool_bytes",
            "device bytes of the page pools as allocated: K and V, or the "
            "one pool of a model whose page row has no head axis "
            "(state_spec()['row_width']); int8 scale leaves not counted",
            _E),
        "kv_layers": g(
            "serving_kv_layers",
            "cache layers that hold KV pages (state_spec()['kv_layers']; "
            "every layer for a model that says nothing, more than the "
            "model has layers where every pass of a looped stack keeps "
            "its own)", _E),
        "recurrent_layers": g(
            "serving_recurrent_layers",
            "layers that hold the slots' recurrent leaves "
            "(state_spec()['recurrent_layers']; 0 for a model with pages "
            "only)", _E),
        "loop_steps": g(
            "serving_loop_steps",
            "passes a dispatch makes over the model's stack "
            "(state_spec()['loop_steps']; 1 for a model that runs each "
            "layer once): serving_kv_layers counts every pass's cache "
            "layers", _E),
        "kv_window_layers": g(
            "serving_kv_window_layers",
            "layers whose attention sees a window of keys and keeps them "
            "in a per-slot ring of pages among the recurrent leaves "
            "(state_spec()['window_layers']; 0 for a model without)", _E),
        "kv_ring_pages": g(
            "serving_kv_ring_pages",
            "pages a window layer's ring holds a slot "
            "(state_spec()['ring_pages']: the window and one dispatch's "
            "rows; 0 for a model without window layers)", _E),
        "expert_weight_bytes": g(
            "serving_expert_weight_bytes",
            "device bytes of the routed experts this engine's model holds "
            "(state_spec()['expert_weight_bytes']; 0 for a model without "
            "experts)", _E),
        "state_resets": c(
            "serving_state_resets_total",
            "slots that began from zero recurrent state: admissions and "
            "re-prefills with no context, which the program zeroes from "
            "the slot's length (no host-side clearing)", _E),
        "head_rows": c(
            "serving_head_rows_total",
            "rows put through the LM head: slots x 1 a dispatch, slots "
            "x spec_tokens with speculation, of the slots x W rows the "
            "body computes", _E),
        "kv_spill_seconds": h(
            "serving_kv_spill_seconds",
            "wall time of one spill batch (device page gather + host "
            "copy)", _E),
        "kv_pagein_seconds": h(
            "serving_kv_pagein_seconds",
            "wall time of one page-in batch (host read + device page "
            "scatter)", _E),
        "kv_host_pages": g(
            "serving_kv_host_pages",
            "payload entries resident in the host spill tier", _E),
        "kv_host_bytes": g(
            "serving_kv_host_bytes",
            "host-RAM bytes the spill tier currently holds", _E),
        "prefix_resident_pages": g(
            "serving_prefix_resident_pages",
            "radix-tree nodes whose KV page is device-resident "
            "(published even with the spill tier off, so tier "
            "occupancy is always observable)", _E),
        "prefix_spilled_pages": g(
            "serving_prefix_spilled_pages",
            "radix-tree nodes whose KV payload lives in the host "
            "tier", _E),
    }
    _shed_family()                  # registered per-process; children
    _tick_phase_family()
    _tenant_families()
    _ttft_family()
    _ttft_phase_family()
    _weight_bytes_family()
    return {k: inst.labels(eid) for k, inst in m.items()}


def _ttft_family():
    """TTFT split by power-of-two prompt-length bucket AND the KV tier
    the admission landed on: the chunked-prefill TTFT model
    (docs/SERVING.md) predicts TTFT grows with ceil(prompt /
    chunk_tokens) dispatch periods, and the tier label is how
    p99-under-tiered-load is attributed — a host page-in admission
    (`spilled`) pays transfer latency a `resident` radix hit never
    sees."""
    return telemetry.histogram(
        "serving_ttft_by_prompt_seconds",
        "submit -> first token, split by power-of-two prompt-length "
        "bucket (label prompt_bucket=le<N>) and KV tier "
        "(kv_tier=resident|spilled|cold)",
        ("engine", "prompt_bucket", "kv_tier"))


def _ttft_phase_family():
    """The TTFT phase budget, aggregated: every first token observes
    one sample per recorded phase (queue_wait, prefix_match,
    host_pagein, prefill_chunks, first_decode — telemetry.PHASES),
    labeled with the admission's KV tier, so p99 TTFT decomposes into
    WHERE the time went without reading per-request timelines."""
    return telemetry.histogram(
        "serving_ttft_phase_seconds",
        "per-request TTFT phase durations (label phase=one of "
        "telemetry.PHASES, kv_tier=resident|spilled|cold)",
        ("engine", "phase", "kv_tier"))


def _weight_bytes_family():
    """Served weight bytes split by storage dtype (ISSUE 19): with
    weight_dtype="int8" the `int8` child is the code slabs and the
    `float32` child is everything still full-width (embeddings, the
    tied LM head, norms, biases, the dequant scales); w8-off puts the
    whole slab under `float32`. The dtype split IS the capacity
    headline: int8 slabs are about a quarter of the float32 ones."""
    return telemetry.gauge(
        "serving_weight_bytes",
        "device bytes of the served weight operands, by storage dtype "
        "(int8 code slabs vs float32 params + dequant scales)",
        ("engine", "dtype"))


# The spans of one scheduling tick, by phase (the span's name without
# `serving.`), each nested where ServingEngine._tick_span opens it:
# step > admit > sync_slot; step > assemble; step > dispatch >
# dispatch.{launch,wait,fetch}; step > fanout > finish > sync_slot.
TICK_PHASES = ("step", "admit", "sync_slot", "assemble", "dispatch",
               "dispatch.launch", "dispatch.wait", "dispatch.fetch",
               "fanout", "finish")


def _tick_phase_family():
    """Host seconds of the scheduling tick by phase: each tick span's
    SELF time (its duration minus its child spans'), so the phases
    partition the ticks' wall and sum to the total of `serving.step`."""
    return telemetry.counter(
        "serving_tick_phase_seconds_total",
        "self time of the serving.<phase> spans of step(): host seconds "
        "of the scheduling tick by phase, summing to the wall of "
        "serving.step", ("engine", "phase"))


def _kernel_path_family():
    """Which implementation each kernel call of a unified program took
    when it was traced: 'pallas' (the Mosaic kernel) or 'xla' (the dense
    or einsum form). On a TPU anything under 'xla' is a kernel that fell
    off its path."""
    return telemetry.counter(
        "serving_kernel_path_total",
        "kernel calls of this engine's unified programs by the "
        "implementation they were traced with", ("engine", "kernel",
                                                 "path"))


def _kernel_tile_family():
    """The block a Mosaic kernel call of a unified program was BUILT with,
    where the kernel chooses one from the call's shapes (the span kernel:
    pages and keys a grid step, rows a head): `tile` is the sizes as
    `name=value` pairs."""
    return telemetry.counter(
        "serving_kernel_tile_total",
        "Mosaic kernel calls of this engine's unified programs by the "
        "block sizes they were built with", ("engine", "kernel", "tile"))


class _TickSpan(span):
    """A telemetry span of one engine's scheduling tick. When it closes
    the engine books its self time under the span's phase
    (`ServingEngine._book_tick_span`): the counters are the spans' sums,
    not a second clock. The span of the whole tick, `serving.step`, also
    reads the thread's CPU clock and the garbage collector's totals at
    its two ends and carries the differences, `cpu_s` and `gc_s`, on its
    event. Only that span: where system calls are served in user space
    (gVisor) a reading of `time.thread_time()` costs 5 us in a tight
    loop and several times that between other work, and the value moves
    in steps of 10 ms, so a phase's CPU time is not worth its price; the
    tick's is, summed over a window."""

    __slots__ = ("cpu_s", "gc_delta", "_eng", "_phase", "_c0", "_gc0")

    def __enter__(self):
        super().__enter__()
        if self._phase == "step":
            self._eng._tick_acc.clear()     # a new tick's record begins
            self._gc0 = gc_totals()
            # the CPU clock inside the wall clock, so cpu_s <= dur
            self._c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        if self._phase == "step":
            self.cpu_s = time.thread_time() - self._c0
            # (seconds, collections of generation 0, 1, 2) in this tick
            self.gc_delta = tuple(b - a for a, b
                                  in zip(self._gc0, gc_totals()))
            self.attrs["cpu_s"] = self.cpu_s
            self.attrs["gc_s"] = self.gc_delta[0]
        super().__exit__(exc_type, exc_val, exc_tb)
        self._eng._book_tick_span(self)
        return False


def _shed_family():
    """The one three-label family: shed traffic split by reason AND the
    shed request's priority class (aggregate reads stay cheap; the
    split is what capacity debugging needs)."""
    return telemetry.counter(
        "serving_shed_total",
        "requests shed by the robustness layer, by reason (queue_full, "
        "overload, deadline, deadline_queued, deadline_running) and "
        "priority class", ("engine", "reason", "priority"))


def _tenant_families():
    """Per-tenant families (labeled {engine, tenant}); children are
    created lazily as tenants appear in traffic, so an engine without
    tenant_quotas pays nothing."""
    return {
        "admitted": telemetry.counter(
            "serving_tenant_admitted_total",
            "requests admitted to a decode slot, split by tenant",
            ("engine", "tenant")),
        "shed": telemetry.counter(
            "serving_tenant_shed_total",
            "requests shed or rejected, split by tenant and reason "
            "(tenant_quota adds the per-tenant queue bound to the "
            "engine-wide taxonomy)", ("engine", "tenant", "reason")),
        "active": telemetry.gauge(
            "serving_tenant_active_slots",
            "decode slots currently held by each tenant",
            ("engine", "tenant")),
        "queued": telemetry.gauge(
            "serving_tenant_queued",
            "queued (admitted-but-waiting) requests per tenant",
            ("engine", "tenant")),
    }


class ServingEngine:
    """Continuous-batching generation over a model with the GPT-2 cache
    contract (hidden(ids, cache) -> (h, cache) and head(h) -> logits,
    whose composition is the model's forward; make_cache()). The
    dispatch calls them apart: it picks the rows it samples from the
    final hidden states and only those pass the head.

    num_slots: concurrent decode sequences (the compiled batch).
    max_length: per-slot KV capacity (prompt + generated), rounded down
        to a whole number of pages; defaults to the model's max_length.
    page_size: KV page granularity. chunk_tokens: prompt tokens one
    slot feeds per dispatch while prefilling (default page_size) — the
    dispatch width is W = max(chunk_tokens, spec_tokens, 2), fixed for
    the engine's lifetime. prefill_chunk_budget: prompt tokens per
    dispatch across ALL slots (default chunk_tokens), round-robined so
    concurrent long prompts share the prefill lane fairly while decode
    rows ride every dispatch untouched. attn_impl: 'auto' (ragged Pallas
    kernel on TPU, dense XLA elsewhere), 'pallas', 'pallas_interpret'
    (the kernel in interpret mode — CPU tests), or 'xla'. max_queue
    bounds the admission queue (None = unbounded); a full queue rejects
    submit() with QueueFullError and counts
    serving_requests_rejected_total.

    prefix_cache=True turns on radix-tree prompt reuse: admission
    longest-prefix-matches each prompt against previously served ones
    and attaches the shared KV pages instead of recomputing them.
    prefix_cache_pages sizes BOTH the extra physical pages added to the
    pool for retained prefixes and the tree's eviction budget (default:
    one full slot-set, num_slots * pages_per_slot). Sampled output is
    bit-identical with the cache on or off.

    speculative=True turns on prompt-lookup speculative decoding
    (serving/speculative.py, docs/SERVING.md): each dispatch feeds up
    to spec_tokens positions per decoding slot — the current token
    plus up to spec_tokens-1 n-gram drafts from the request's own
    history — and the same unified forward verifies all of them.
    Greedy output is bit-identical to speculative=False; sampled output
    is distribution-preserving and reproducible across schedules.

    Every engine reports into mx.telemetry as per-engine labeled
    children (docs/OBSERVABILITY.md): TTFT, admission wait, per-token
    decode latency, queue depth, slot occupancy, dispatch counts/wall
    times, prefix-cache hits/misses/tokens-saved/evictions. `stats` is
    a dict view of this engine's children; `reset_stats()` zeroes them.
    """

    def __init__(self, model, num_slots, max_length=None, page_size=64,
                 attn_impl="auto", chunk_tokens=None,
                 prefill_chunk_budget=None, dtype=None, max_queue=None,
                 prefix_cache=False, prefix_cache_pages=None,
                 speculative=False, spec_tokens=4, num_priorities=3,
                 policy=None, max_retries=3, retry_backoff_s=0.02,
                 clock=None, adapter_pool=None, tenant_quotas=None,
                 kv_dtype=None, hbm_budget_bytes=None, host_kv_bytes=None,
                 tp=1, tp_devices=None, weight_dtype=None,
                 hbm_budget_includes_weights=False):
        self.model = model
        cfg = model.config
        self.num_slots = int(num_slots)
        max_length = int(max_length or cfg.max_length)
        max_length -= max_length % page_size
        if max_length < page_size:
            raise MXNetError(f"max_length {max_length} < one page "
                             f"({page_size})")
        if max_length > cfg.max_length:
            raise MXNetError(f"max_length {max_length} exceeds the "
                             f"model's position range {cfg.max_length}")
        self.max_length = max_length
        self.page_size = int(page_size)
        self.attn_impl = attn_impl
        # tensor-parallel serving (docs/SERVING.md "Tensor-parallel
        # serving"): tp > 1 runs the ONE unified program shard_map'ed
        # over a {tp: N} mesh — qkv/fc1 column-parallel, proj/fc2
        # row-parallel, KV pages split on the HEAD axis, one psum per
        # projection reassembling full activations so the in-program
        # sampler sees full logits on every shard. Shard count is a
        # construction-time MODE, never a program shape axis: tp=1
        # builds the exact pre-tp program, and a tp=N engine still owns
        # at most two compiled programs for its lifetime.
        self._tp = int(tp or 1)
        if self._tp < 1:
            raise MXNetError(f"tp must be >= 1, got {tp}")
        for part in ("hidden", "head"):
            if not callable(getattr(model, part, None)):
                raise MXNetError(
                    f"{type(model).__name__} has no `{part}`: "
                    "ServingEngine calls model.hidden(ids, cache) -> "
                    "(h, cache) and model.head(h) -> logits apart "
                    "(models/gpt2.py, models/falcon_h1.py)")
        # what a slot holds is the model's to declare (state_spec()):
        # KV pages of so many KV heads, and `recurrent` leaves of fixed
        # size a slot (a state-space layer's convolution tail and SSM
        # state). No model is known here by name.
        slot_state = model.state_spec() if hasattr(model, "state_spec") else {
            "num_layers": cfg.num_layers, "num_kv_heads": cfg.num_heads,
            "head_dim": cfg.units // cfg.num_heads, "recurrent": {}}
        # state by layer kind: `kv_layers` of the layers hold pages and
        # `recurrent_layers` hold the recurrent leaves; a model whose
        # layers all hold both says neither. The model maps its layer
        # index to its page layer and its state layer.
        self._kv_layers = int(slot_state.get("kv_layers",
                                             slot_state["num_layers"]))
        self._rec_layers = int(slot_state.get(
            "recurrent_layers", slot_state["num_layers"])) \
            if slot_state["recurrent"] else 0
        self._expert_weight_bytes = int(
            slot_state.get("expert_weight_bytes", 0))
        # a model whose token keeps ONE row with no head axis (latent
        # attention) says `row_width`: one page pool that wide, no V pool
        row_width = slot_state.get("row_width")
        self._one_pool = row_width is not None
        # the features a model's state may refuse, and whether each is on
        asked = {"tp": self._tp > 1, "speculative": speculative,
                 "prefix_cache": prefix_cache,
                 "host_kv_bytes": host_kv_bytes is not None,
                 "kv_dtype": kv_dtype is not None,
                 "weight_dtype": weight_dtype is not None,
                 "adapter_pool": adapter_pool is not None}
        # what the model itself says its state or its blocks do not carry
        # (state_spec()["refuses"]: feature -> why), before what the kinds
        # of state below refuse on their own
        for feature, why in (slot_state.get("refuses") or {}).items():
            if asked[feature]:
                raise MXNetError(f"{feature} is not supported for "
                                 f"{type(model).__name__}: {why}")
        if self._one_pool:
            # what splits a page by head, scales it by head or ships it as
            # a K and a V page has nothing to hold on to
            # (docs/SERVING.md "A pool with no head axis")
            for feature in ("tp", "kv_dtype", "host_kv_bytes"):
                if asked[feature]:
                    raise MXNetError(
                        f"{feature} is not supported for a model whose "
                        f"page row has no head axis (row_width {row_width})"
                        ": the tp split and the int8 scale leaves are per "
                        "head, and the spill tier ships a K and a V page")
        if slot_state["recurrent"]:
            # recurrent state is not paged and cannot be shared, copied
            # page by page, split over heads or rebuilt from pages: what
            # leases, copies or ships pages knows nothing of it yet
            # (docs/SERVING.md "Recurrent state")
            for feature in ("prefix_cache", "speculative", "host_kv_bytes",
                            "tp", "kv_dtype", "weight_dtype",
                            "adapter_pool"):
                if asked[feature]:
                    raise MXNetError(
                        f"{feature} is not supported for a model that "
                        "declares recurrent state "
                        f"({sorted(slot_state['recurrent'])}): a slot's state "
                        "beside its KV pages cannot be shared by prefix, "
                        "verified and rolled back, spilled, sharded, "
                        "quantized or adapted yet")
        # a model that runs its stack several times a dispatch says how
        # often (`loop_steps`): its `kv_layers` then count every pass's
        self._loop_steps = int(slot_state.get("loop_steps", 1))
        if self._tp > 1:
            if cfg.num_heads % self._tp:
                raise MXNetError(
                    f"tp={self._tp} must divide num_heads "
                    f"({cfg.num_heads}) — the KV pool and the qkv/proj "
                    "weights shard head-wise")
            if cfg.hidden_size % self._tp:
                raise MXNetError(
                    f"tp={self._tp} must divide the FFN hidden size "
                    f"({cfg.hidden_size}) — fc1/fc2 shard on it")
        self._mesh = serving_tp_mesh(self._tp, devices=tp_devices)
        self.chunk_tokens = int(chunk_tokens or page_size)
        if self.chunk_tokens < 1:
            raise MXNetError("chunk_tokens must be >= 1")
        self.prefill_chunk_budget = int(
            prefill_chunk_budget or self.chunk_tokens)
        if self.prefill_chunk_budget < 1:
            raise MXNetError("prefill_chunk_budget must be >= 1")
        self.speculative = bool(speculative)
        self.spec_tokens = int(spec_tokens)
        if self.speculative:
            if self.spec_tokens < 2:
                raise MXNetError("spec_tokens must be >= 2 (the current "
                                 "token + at least one draft)")
            self._proposer = PromptLookupProposer(self.spec_tokens - 1)
            # per-slot token history (prompt + emitted) the prompt-lookup
            # drafter matches against — the request's OWN history only,
            # so drafting is schedule-independent
            self._hist = [None] * int(num_slots)
        # ONE dispatch width forever: wide enough for a prefill chunk,
        # a speculative verify window, or a decode step (>= 2 keeps
        # every dispatch on the span kernel's multi-query path)
        self._width = max(self.chunk_tokens,
                          self.spec_tokens if self.speculative else 0, 2)
        self.scheduler = SlotScheduler(num_slots, max_queue=max_queue,
                                       num_priorities=num_priorities,
                                       tenant_quotas=tenant_quotas)
        # robustness layer (docs/SERVING.md "Robustness"): supervisor
        # retry budget + backoff, optional shedding policy, and an
        # injectable clock so deadline/backoff behavior is testable
        # without wall-time races (the default IS perf_counter)
        self.policy = policy
        self.max_retries = int(max_retries)
        if self.max_retries < 1:
            raise MXNetError("max_retries must be >= 1")
        self.retry_backoff_s = float(retry_backoff_s)
        self._clock = clock if clock is not None else time.perf_counter
        self._degraded = False
        self._draining = False
        self._finish_times = deque(maxlen=64)   # drain-rate window
        # extra lease rows audit_pages() should account for (the
        # fault-injection harness registers pages it holds here)
        self.audit_extra_leases = []

        self._params = list(model.collect_params().values())
        if self._mesh is not None:
            # per-param layout from the serving tp rules (embeddings +
            # LM head replicated, qkv/fc1 column-, proj/fc2 row-
            # parallel; unmatched leaves replicated). Weights are
            # placed onto the mesh ONCE and cached by array identity
            # (_placed) — a dispatch never re-shards them.
            rules = serving_tp_rules(AXIS_TP)
            self._param_specs = tuple(
                rules.spec_for(name) or PartitionSpec()
                for name in model.collect_params().keys())
            self._placed = {}
        else:
            self._param_specs = None
            self._placed = None
        self._slab_cache = None
        # w8 weight serving (docs/SERVING.md "Weight quantization"): the
        # megatron col/row dense weights are quantized ONCE here to int8
        # codes with per-out-tile f32 scales (per shard for the column
        # split, shard-invariant for the row split — see
        # serving/weight_quant.py). The code arrays ride the SAME
        # dispatch operand positions and PartitionSpecs the fp32 weights
        # did, the scales travel as extra operands, and the dequant is
        # fused into FullyConnected as an output epilogue. Weight
        # identity stays runtime data: w8 on/off never adds a program
        # shape axis, and w8-off builds the exact pre-w8 program.
        if weight_dtype is not None:
            try:
                w8_ok = jnp.dtype(weight_dtype) == jnp.int8
            except TypeError:
                w8_ok = False
            if not w8_ok:
                raise MXNetError(f"weight_dtype {weight_dtype!r} "
                                 "unsupported (int8 or None)")
        self._w8 = weight_dtype is not None
        self.weight_dtype = "int8" if self._w8 \
            else str(jnp.dtype(dtype or jnp.dtype(cfg.dtype)))
        self._w8_plan = ()
        self._w8_codes = {}
        self._w8_scale_ops = ()
        if self._w8:
            plan = build_weight_plan(model.collect_params().items(),
                                     tp=self._tp, tp_axis=AXIS_TP,
                                     max_shards=cfg.num_heads)
            if not plan:
                raise MXNetError(
                    "weight_dtype='int8' found no megatron col/row "
                    "dense weights to quantize on this model")
            self._w8_plan = tuple(plan)
            self._w8_codes = {q.index: q.codes for q in plan}
            if self._mesh is not None:
                self._w8_scale_ops = tuple(
                    jax.device_put(
                        q.scale,
                        named_sharding(q.scale_spec, mesh=self._mesh))
                    for q in plan)
            else:
                self._w8_scale_ops = tuple(q.scale for q in plan)
        # byte-denominated weight accounting (feeds the
        # serving_weight_bytes{dtype} gauges, /statusz, the HBM ledger
        # and — when hbm_budget_includes_weights — the page budget):
        # int8 = code slabs, float32 = everything else incl. the dequant
        # scales; per-chip divides sharded arrays by tp.
        wb_int8 = wb_fp = wb_chip = 0
        w8_by_idx = {q.index: q for q in self._w8_plan}
        for i, p in enumerate(self._params):
            d = p.data()._data
            spec = self._param_specs[i] if self._param_specs else None
            div = self._tp if (spec is not None
                               and any(a is not None for a in spec)) \
                else 1
            q = w8_by_idx.get(i)
            if q is not None:
                cb = int(q.codes.size)          # 1 B/element
                sb = int(q.scale.size) * 4
                s_div = self._tp if any(a is not None
                                        for a in q.scale_spec) else 1
                wb_int8 += cb
                wb_fp += sb
                wb_chip += cb // div + sb // s_div
            else:
                nb = int(d.size) * jnp.dtype(d.dtype).itemsize
                wb_fp += nb
                wb_chip += nb // div
        self._weight_bytes = {"int8": int(wb_int8),
                              "float32": int(wb_fp)}
        self._weight_bytes_per_chip = int(wb_chip)
        B = self.num_slots
        P = self._pages_per_slot = max_length // page_size
        # pool sizing: every slot can always claim a full P exclusive
        # pages (worst case, zero sharing) + `extra` pages so the prefix
        # cache can retain prefixes across request lifetimes
        extra = 0
        if prefix_cache:
            extra = B * P if prefix_cache_pages is None \
                else int(prefix_cache_pages)
            if extra < 0:
                raise MXNetError("prefix_cache_pages must be >= 0")
        total_pages = B * P + extra
        dt = dtype or jnp.dtype(cfg.dtype)
        # quantized page mode (docs/SERVING.md "Quantized KV pages"):
        # int8 codes + per-(layer, page, head) f32 dequant scales kept
        # as separate pool leaves. page_bytes is the HONEST per-page
        # HBM cost (k+v slabs across all layers, plus scales) — the
        # byte-denominated budget below trades the ~4x smaller pages
        # for MORE pages, i.e. real admitted capacity.
        if kv_dtype is not None:
            try:
                ok = jnp.dtype(kv_dtype) == jnp.int8
            except TypeError:
                ok = False
            if not ok:
                raise MXNetError(f"kv_dtype {kv_dtype!r} unsupported "
                                 "(int8 or None)")
        self._quant = kv_dtype is not None
        self.kv_dtype = "int8" if self._quant else str(jnp.dtype(dt))
        store = jnp.dtype(jnp.int8) if self._quant else jnp.dtype(dt)
        # the pools hold the KV heads: fewer than the query heads
        # under grouped-query attention
        L = self._kv_layers
        if self._one_pool:
            H, width, pools = 0, int(row_width), 1
        else:
            H = slot_state["num_kv_heads"]
            width, pools = H * slot_state["head_dim"], 2
        page_bytes = pools * L * page_size * width * store.itemsize
        if self._quant:
            page_bytes += 2 * L * H * 4    # f32 scales ride each page
        self._hbm_budget = None if hbm_budget_bytes is None \
            else int(hbm_budget_bytes)
        self._hbm_includes_weights = bool(hbm_budget_includes_weights)
        # recurrent state: one whole leaf (state layers, slots, ...) per
        # declared name, resident like the pages and donated with them
        rec_shapes = {
            name: ((self._rec_layers, B) + tuple(shape), jnp.dtype(rdt))
            for name, (shape, rdt) in slot_state["recurrent"].items()}
        self._rec_bytes = sum(int(np.prod(shape)) * rdt.itemsize
                              for shape, rdt in rec_shapes.values())
        # window layers keep their keys in rings of pages among the
        # recurrent leaves (models/kv_cache.py RING_LEAVES), counted in
        # _rec_bytes as well: `window_layers` of them, `ring_pages` a slot
        self._window_layers = int(slot_state.get("window_layers", 0))
        self._ring_pages = int(slot_state.get("ring_pages", 0))
        self._ring_bytes = sum(
            int(np.prod(shape)) * rdt.itemsize
            for name, (shape, rdt) in rec_shapes.items()
            if name in RING_LEAVES)
        if self._hbm_budget is not None:
            # under tp each CHIP holds 1/tp of every page (the head
            # axis shards), so the budget — the quantity that actually
            # OOMs — is per chip and buys tp x the pages. The slots'
            # recurrent state comes out of it first.
            page_budget = self._hbm_budget - self._rec_bytes
            if self._hbm_includes_weights:
                # the served weight slab comes out of the same per-chip
                # HBM the pages do: charging it here is what turns the
                # w8 ~4x weight shrink into ADMITTED pages where fp32
                # weights are the binding constraint
                page_budget -= self._weight_bytes_per_chip
                if page_budget <= 0:
                    raise MXNetError(
                        f"hbm_budget_bytes {self._hbm_budget} is below "
                        f"the {self._weight_bytes_per_chip} B/chip the "
                        f"{self.weight_dtype} weights alone need")
            chip_page = page_bytes // self._tp
            afford = page_budget // chip_page
            if afford < P:
                raise MXNetError(
                    f"hbm_budget_bytes {self._hbm_budget} affords "
                    f"{afford} pages at {chip_page} B/page/chip — below "
                    f"the {P} pages one full-length slot needs")
            total_pages = min(total_pages, afford)
        # heads packed into the last axis (column h*Dh + d): the layout
        # the span kernel reads, so every dispatch works on the donated
        # pools in place (models/kv_cache.py)
        pool_shape = (L, total_pages, page_size, width)
        self._kp = jnp.zeros(pool_shape, store)
        self._vp = None if self._one_pool else jnp.zeros(pool_shape, store)
        self._kv_pool_bytes = pools * int(np.prod(pool_shape)) \
            * store.itemsize
        if self._quant:
            self._ks = jnp.zeros((L, total_pages, H), jnp.float32)
            self._vs = jnp.zeros((L, total_pages, H), jnp.float32)
        else:
            self._ks = self._vs = None
        self._rec = {name: jnp.zeros(shape, rdt)
                     for name, (shape, rdt) in rec_shapes.items()}
        # the model's own cumulative counters (state_spec()["counters"]:
        # whole leaves, no slot's): they ride the donated state beside
        # the recurrent leaves, the program adds to them, and the host
        # folds them into `_model_totals` when stats are read, never a
        # tick (`_fold_model_counts`)
        self._model_counts = {
            name: jnp.zeros(tuple(shape), jnp.dtype(cdt))
            for name, (shape, cdt) in
            (slot_state.get("counters") or {}).items()}
        self._model_totals = {
            name: np.zeros(a.shape, np.float64 if jnp.issubdtype(
                a.dtype, jnp.floating) else np.int64)
            for name, a in self._model_counts.items()}
        if self._mesh is not None:
            # the pools LIVE sharded (global shape above, the packed
            # axis split over the mesh in whole-head blocks): every
            # eager page op — scrub, CoW copy, scale zeroing — follows
            # the input layout, and the unified dispatch's donation
            # keeps the shards in place
            kv_sh = named_sharding(self._kv_pspec(), mesh=self._mesh)
            self._kp = jax.device_put(self._kp, kv_sh)
            self._vp = jax.device_put(self._vp, kv_sh)
            if self._quant:
                sc_sh = named_sharding(self._scale_pspec(),
                                       mesh=self._mesh)
                self._ks = jax.device_put(self._ks, sc_sh)
                self._vs = jax.device_put(self._vs, sc_sh)
        self.page_pool = PagePool(total_pages, page_bytes=page_bytes)
        self.prefix_cache = PrefixCache(self.page_pool, page_size,
                                        budget_pages=extra) \
            if prefix_cache else None
        # host-RAM KV spill tier (docs/SERVING.md "Tiered KV cache"):
        # an evicted prefix page spills its payload (codes AND the int8
        # scale leaves) to host RAM instead of vanishing, a radix hit
        # on a spilled node pages it back in, and preemption swaps
        # whole requests out through the same tier. All tier traffic
        # runs OUTSIDE the traced dispatch — two tiny fixed-shape
        # jitted page programs plus explicit transfers — so the
        # unified program and steady_state_compiles never see it.
        self._host_kv_bytes = None if host_kv_bytes is None \
            else int(host_kv_bytes)
        self.host_pool = None
        if self._host_kv_bytes is not None:
            if self.prefix_cache is None:
                raise MXNetError("host_kv_bytes needs prefix_cache=True "
                                 "— the spill tier is keyed by radix "
                                 "nodes")
            self.host_pool = HostPagePool(self._host_kv_bytes,
                                          evict_cb=self._host_evict)
            self.prefix_cache.evict_hook = self._spill_node
            self.prefix_cache.pagein_hook = self._pagein_nodes
        # tier transfer programs: ONE fixed index width (P = pages
        # per slot) however many pages move. Gather pads its index
        # with page 0 and the host slices the valid prefix after
        # device_get; scatter pads with an out-of-range id that
        # mode="drop" ignores. Gather must NOT donate (the pools
        # live on); scatter donates them like every dispatch.
        # under tp>1 the scatter pins out_shardings to the pools'
        # own shardings: the donated outputs must come back in
        # EXACTLY the layout the dispatch expects (XLA would
        # otherwise return a spec-normalized NamedSharding that
        # misses the dispatch cache key). tp=1 must NOT pin — the
        # pool chain is uncommitted end to end, and committing it
        # here would mint a second pjit entry in every downstream
        # page program. Built whether or not a host tier is on: the
        # same movers carry the cross-process prefill->decode handoff
        # (export_handoff / _adopt_payload, serving/fleet) — jit is
        # lazy, so an engine that never moves a page never traces them.
        pin = self._tp > 1
        if self._quant:
            def _tier_gather_q(kp, vp, ks, vs, idx):
                return gather_kv_pages(kp, vp, idx, ks, vs)

            def _tier_scatter_q(kp, vp, ks, vs, idx, kv, vv,
                                ksv, vsv):
                return scatter_kv_pages(kp, vp, idx, kv, vv,
                                        ks, vs, ksv, vsv)

            self._tier_gather_fn = jax.jit(_tier_gather_q)
            self._tier_scatter_fn = jax.jit(
                _tier_scatter_q, donate_argnums=(0, 1, 2, 3),
                out_shardings=(
                    (self._kp.sharding, self._vp.sharding,
                     self._ks.sharding, self._vs.sharding)
                    if pin else None))
        else:
            def _tier_gather_f(kp, vp, idx):
                return gather_kv_pages(kp, vp, idx)[:2]

            def _tier_scatter_f(kp, vp, idx, kv, vv):
                return scatter_kv_pages(kp, vp, idx, kv, vv)[:2]

            self._tier_gather_fn = jax.jit(_tier_gather_f)
            self._tier_scatter_fn = jax.jit(
                _tier_scatter_f, donate_argnums=(0, 1),
                out_shardings=(
                    (self._kp.sharding, self._vp.sharding)
                    if pin else None))
        # per-slot page tables are HOST state now (page-table surgery at
        # admission); uploaded with each dispatch
        self._table_host = np.zeros((B, P), np.int32)
        self._mapped = np.zeros(B, bool)   # slot holds page leases
        # per-slot host state (tiny; uploaded per dispatch, fetched back
        # with the decoded tokens — one round trip per K tokens).
        # Unmapped slots park at length == max_length: their in-program
        # decode writes fall off the page table and DROP, so a freed
        # slot can never scribble on pages that were recycled to a new
        # owner or retained by the prefix cache.
        self._lengths = np.full(B, self.max_length, np.int32)
        self._cur_tok = np.zeros(B, np.int32)
        self._done = np.ones(B, bool)          # free slots are inactive
        self._remaining = np.zeros(B, np.int32)
        self._counters = np.zeros(B, np.int32)
        self._seeds = np.zeros(B, np.int32)
        self._temp = np.ones(B, np.float32)
        self._top_k = np.zeros(B, np.int32)
        self._top_p = np.ones(B, np.float32)
        self._do_sample = np.zeros(B, bool)
        self._eos = np.full(B, -1, np.int32)
        # multi-tenant LoRA (serving/adapters.py, docs/SERVING.md
        # "Multi-tenant LoRA serving"): the pool's slab is device-
        # resident; each slot carries its adapter's SLAB SLOT index as
        # one more per-slot scalar (0 = null adapter = exact zeros), so
        # adapter identity is runtime data — never a program shape axis
        self.adapter_pool = adapter_pool
        self._aslot = np.zeros(B, np.int32)
        self._adapter_of = [None] * B   # slot -> pinned adapter_id

        # per-slot chunk queues: the not-yet-fed tail of each admitted
        # prompt (np.int32; None = slot has no prefill work). The
        # dispatch loop drains them chunk_tokens at a time under the
        # prefill_chunk_budget, starting at a rotating slot cursor.
        self._pending = [None] * B
        self._base = np.zeros(B, np.int32)   # resume offset per slot
        # quantized restart replay: when a slot re-prefills a request
        # that already emitted tokens, this holds the exact chunk sizes
        # to feed (deque; None = feed on the natural chunk_tokens
        # grid). See _admit — per-page dequant scales make deep-layer
        # KV codes chunk-boundary-dependent, so only replaying the
        # recorded write schedule keeps the continuation bit-identical.
        self._replay = [None] * B
        self._chunk_rr = 0
        # the unified program comes in two flavors selected PER
        # DISPATCH: the general mixed-sampling one and a greedy-only
        # one that skips the filtered-distribution sort and the RNG
        # draws entirely (greedy batches dominate production serving;
        # greedy rows are bit-identical through either program). These
        # two keys are the engine's ENTIRE program registry.
        self._programs = {}

        if self._quant:
            def _copy_page(kp, vp, ks, vs, src, dst):
                # CoW split: the dequant scales are part of a page's
                # identity — they travel with the slab on every clone
                return (kp.at[:, dst].set(kp[:, src]),
                        vp.at[:, dst].set(vp[:, src]),
                        ks.at[:, dst].set(ks[:, src]),
                        vs.at[:, dst].set(vs[:, src]))

            self._copy_page_fn = jax.jit(_copy_page,
                                         donate_argnums=(0, 1, 2, 3))

            def _zero_scales(ks, vs, idx):
                # fresh pages must start from scale 0 or the monotone
                # max-update would inherit a recycled page's old scale;
                # idx is FIXED-length (padded with an out-of-range id
                # that mode="drop" ignores) so admissions never mint
                # new program shapes in steady state
                z = jnp.zeros((), jnp.float32)
                return (ks.at[:, idx].set(z, mode="drop"),
                        vs.at[:, idx].set(z, mode="drop"))

            self._zero_scales_fn = jax.jit(_zero_scales,
                                           donate_argnums=(0, 1))
        else:
            def _copy_page(kp, vp, src, dst):
                # CoW split: clone one physical page's (L, S, H*D) slab
                # (vp None for a one-pool model: an empty pytree)
                return jax.tree_util.tree_map(
                    lambda pool: pool.at[:, dst].set(pool[:, src]),
                    (kp, vp))

            self._copy_page_fn = jax.jit(_copy_page,
                                         donate_argnums=(0, 1))
        # the per-slot scalar state is DEVICE-RESIDENT between decode
        # dispatches: the decode program reads these arrays directly and
        # returns the updated ones, and the host uploads deltas only on
        # admission/finish/cancel (_sync_slot) — not ~12 small
        # jnp.asarray transfers on every dispatch
        self._upload_fn = self._build_slot_upload()
        scalars = [self._lengths, self._cur_tok, self._done,
                   self._remaining, self._counters, self._seeds,
                   self._temp, self._top_k, self._top_p,
                   self._do_sample, self._eos]
        if self.adapter_pool is not None:
            scalars.append(self._aslot)
        self._dstate = tuple(self._rep(jnp.asarray(a))
                             for a in scalars + [self._table_host])
        self._d_lock = self._rep(jnp.asarray(self._page_lock_host()))
        self._eid = str(next(_engine_ids))
        self._metrics = _engine_metrics(self._eid)
        self._metrics["num_slots"].set(self.num_slots)
        self._wbytes_fam = _weight_bytes_family()
        self._set_static_gauges()
        self._tick = 0             # step() calls so far (span attr)
        fam = _tick_phase_family()
        self._tick_children = {ph: fam.labels(self._eid, ph)
                               for ph in TICK_PHASES}
        # the tick that is running: phase -> [self s, spans closed];
        # cleared when serving.step opens
        self._tick_acc = {}
        # the slowest ticks so far, a heap of (wall, tick, record) with
        # the fastest of them on top
        self._slowest = []
        telemetry.install_gc_hook()
        self._path_children = {}   # (kernel, path) -> labeled child
        self._tile_children = {}   # (kernel, tile) -> labeled child
        # (PATHS, TILES) when a program was last built
        self._paths_before = None
        self._shed = _shed_family()
        self._shed_children = {}   # (reason, priority) -> labeled child
        self._shed_counts = {}     # same keys, host-side for stats
        self._ttft_fam = _ttft_family()
        self._ttft_children = {}   # (prompt bucket, tier) -> child
        self._phase_fam = _ttft_phase_family()
        self._phase_children = {}  # (phase, tier) -> labeled child
        # TTFT phase-budget bookkeeping (docs/OBSERVABILITY.md "Phase
        # taxonomy"): per-admission host page-in accumulator (the
        # prefix-cache pagein hook fires inside _map_slot_pages, so
        # per-request attribution needs this bracket), the KV tier the
        # admission landed on, and the prefill chunks fed per slot
        self._pagein_acc = 0.0
        self._kv_tier = ["cold"] * B
        self._chunks_fed = np.zeros(B, np.int32)
        self._tenant_fams = _tenant_families()
        self._tenant_children = {}   # (family, tenant[, reason]) -> child
        self._tenant_shed_counts = {}  # (tenant, reason) -> n
        self._tenants_seen = set()
        self._adapter_page_ins_seen = 0
        self._adapter_evictions_seen = 0
        self._hook_kw_cache = None
        # a collected engine must not leave /healthz stuck degraded
        weakref.finalize(self, _tserver.clear_degraded,
                         f"engine{self._eid}")
        self._evictions_seen = 0
        self._host_evictions_seen = 0
        self._set_pool_gauges()
        # live introspection: /statusz shows this engine's config +
        # occupancy, the flight-recorder watchdog probes its progress
        # (both hold weak refs — a collected engine just drops out),
        # and every request records a lifecycle timeline into
        # telemetry.request_log. dispatch_hook is a test/extension
        # seam called at the top of every step().
        self.dispatch_hook = None
        # device-cost accounting (telemetry.cost, docs/OBSERVABILITY.md
        # "Device-cost accounting"): every program this engine builds is
        # wrapped in a CostedFunction keyed engine<eid>/<program>, so
        # compiles are attributed and MFU/roofline gauges go live.
        # mark_warm() flips the steady flag: any compile after that is a
        # retrace storm the flight recorder latches a dump for.
        self._steady = False
        telemetry.register_status_provider(
            f"engine/{self._eid}", self._statusz)
        telemetry.flight.watch(f"engine{self._eid}", self._flight_probe)
        # /readyz: readiness (warmed AND not degraded AND not draining)
        # is per-component state, distinct from /healthz liveness — an
        # intentionally-draining replica is healthy but not ready
        _tserver.register_ready_probe(f"engine{self._eid}",
                                      self._ready_probe)
        weakref.finalize(self, _tserver.unregister_ready_probe,
                         f"engine{self._eid}")
        # HBM ledger: weights + KV page slab + device-resident slot
        # state, with the prefix-cache-held page subset as an
        # informational detail (it lives inside kv_pages)
        _ledger.register(f"engine/{self._eid}", self._hbm_ledger)

    # -- telemetry ---------------------------------------------------------
    @property
    def stats(self):
        """This engine's counters/gauges as a plain dict (a live read of
        the telemetry children — the PR-1 bare-dict keys kept intact)."""
        m = self._metrics
        self._fold_model_counts()
        return {
            "prefills": int(m["prefills"].value),
            "prefill_tokens": int(m["prefill_tokens"].value),
            "prefill_chunks": int(m["prefill_chunks"].value),
            "prefill_pending": int(m["prefill_pending"].value),
            "decode_dispatches": int(m["decode_dispatches"].value),
            "decode_steps": int(m["decode_steps"].value),
            "tokens_emitted": int(m["tokens_emitted"].value),
            "requests_finished": int(m["requests_finished"].value),
            "requests_rejected": int(m["requests_rejected"].value),
            "requests_cancelled": int(m["requests_cancelled"].value),
            "prefix_hits": int(m["prefix_hits"].value),
            "prefix_misses": int(m["prefix_misses"].value),
            "prefix_tokens_saved": int(m["prefix_tokens_saved"].value),
            "prefix_evicted_pages": int(m["prefix_evicted_pages"].value),
            "spec_draft_tokens": int(m["spec_draft_tokens"].value),
            "spec_accepted_tokens": int(m["spec_accepted_tokens"].value),
            "spec_rollbacks": int(m["spec_rollbacks"].value),
            "model_flops": int(m["model_flops"].value),
            "wasted_flops": int(m["wasted_flops"].value),
            "admission_capacity": int(m["admission_capacity"].value),
            "prefix_cache_pages": int(m["prefix_cache_pages"].value),
            "prefix_pages_shared": int(m["prefix_pages_shared"].value),
            "pool_free_pages": int(m["pool_free_pages"].value),
            "queue_depth": int(m["queue_depth"].value),
            "slot_occupancy": int(m["slot_occupancy"].value),
            "dispatch_errors": int(m["dispatch_errors"].value),
            "dispatch_retries": int(m["dispatch_retries"].value),
            "requests_failed": int(m["requests_failed"].value),
            "overload_level": int(m["overload_level"].value),
            "degraded": int(m["degraded"].value),
            "draining": self._draining,
            "shed": sum(self._shed_counts.values()),
            "adapter_page_ins": int(m["adapter_page_ins"].value),
            "adapter_evictions": int(m["adapter_evictions"].value),
            "adapter_resident": int(m["adapter_resident"].value),
            "adapter_pinned": int(m["adapter_pinned"].value),
            "kv_quant_enabled": int(m["kv_quant_enabled"].value),
            "kv_page_bytes": int(m["kv_page_bytes"].value),
            "kv_bytes_per_token": float(
                m["kv_bytes_per_token"].value),
            "tp_shards": int(m["tp_shards"].value),
            "weight_quant_enabled": int(
                m["weight_quant_enabled"].value),
            "weight_bytes_int8": self._weight_bytes["int8"],
            "weight_bytes_float32": self._weight_bytes["float32"],
            "weight_bytes_total": (self._weight_bytes["int8"]
                                   + self._weight_bytes["float32"]),
            "weight_bytes_per_chip": self._weight_bytes_per_chip,
            "kv_spill_pages": int(m["kv_spill_pages"].value),
            "kv_spill_bytes": int(m["kv_spill_bytes"].value),
            "kv_pagein_pages": int(m["kv_pagein_pages"].value),
            "kv_pagein_bytes": int(m["kv_pagein_bytes"].value),
            "kv_host_evictions": int(m["kv_host_evictions"].value),
            "kv_host_pages": int(m["kv_host_pages"].value),
            "kv_host_bytes": int(m["kv_host_bytes"].value),
            "prefix_resident_pages": int(
                m["prefix_resident_pages"].value),
            "prefix_spilled_pages": int(
                m["prefix_spilled_pages"].value),
            "preempts": int(m["preempts"].value),
            "preempt_resumed": int(m["preempt_resumed"].value),
            "preempt_restarted": int(m["preempt_restarted"].value),
            "tick_phase_seconds": {ph: c.value for ph, c
                                   in self._tick_children.items()},
            "tick_seconds": self._tick_seconds(),
            "tick_cpu_seconds": m["tick_cpu_seconds"].value,
            "tick_gc_seconds": m["tick_gc_seconds"].value,
            "slowest_ticks": [copy.deepcopy(rec) for _, _, rec
                              in sorted(self._slowest, reverse=True)],
            "recurrent_state_bytes": self._rec_bytes,
            "kv_pool_bytes": self._kv_pool_bytes,
            "kv_layers": self._kv_layers,
            "recurrent_layers": self._rec_layers,
            "loop_steps": self._loop_steps,
            "kv_window_layers": self._window_layers,
            "kv_ring_pages": self._ring_pages,
            "kv_ring_bytes": self._ring_bytes,
            "expert_weight_bytes": self._expert_weight_bytes,
            "model_counters": {name: total.tolist() for name, total
                               in self._model_totals.items()},
            "state_resets": int(m["state_resets"].value),
            "head_rows": int(m["head_rows"].value),
            "kernel_paths": {f"{kernel}/{path}": int(c.value)
                             for (kernel, path), c
                             in self._path_children.items()},
            "kernel_tiles": {f"{kernel}/{tile}": int(c.value)
                             for (kernel, tile), c
                             in self._tile_children.items()},
        }

    def tenant_stats(self):
        """Per-tenant occupancy + lifetime accounting: the scheduler's
        queued/active/admitted/quota view plus this engine's shed
        taxonomy split by tenant. Keys are stringified tenant ids."""
        out = self.scheduler.tenants_snapshot()
        for (tenant, reason), n in sorted(self._tenant_shed_counts.items()):
            row = out.setdefault(str(tenant), {})
            row.setdefault("shed", {})[reason] = n
        return out

    def _set_static_gauges(self):
        """Configuration gauges — set at construction and re-applied
        after reset_stats (they describe the engine, not traffic)."""
        pb = self.page_pool.page_bytes
        self._metrics["kv_quant_enabled"].set(int(self._quant))
        self._metrics["kv_page_bytes"].set(pb)
        self._metrics["kv_bytes_per_token"].set(pb / self.page_size)
        self._metrics["tp_shards"].set(self._tp)
        self._metrics["weight_quant_enabled"].set(int(self._w8))
        self._metrics["recurrent_state_bytes"].set(self._rec_bytes)
        self._metrics["kv_pool_bytes"].set(self._kv_pool_bytes)
        self._metrics["kv_layers"].set(self._kv_layers)
        self._metrics["recurrent_layers"].set(self._rec_layers)
        self._metrics["loop_steps"].set(self._loop_steps)
        self._metrics["kv_window_layers"].set(self._window_layers)
        self._metrics["kv_ring_pages"].set(self._ring_pages)
        self._metrics["expert_weight_bytes"].set(self._expert_weight_bytes)
        for wd, nb in self._weight_bytes.items():
            self._wbytes_fam.labels(self._eid, wd).set(nb)

    def reset_stats(self):
        """Zero this engine's telemetry children (other engines and the
        rest of the registry are untouched)."""
        for inst in self._metrics.values():
            inst.reset()
        for child in self._shed_children.values():
            child.reset()
        for child in self._tick_children.values():
            child.reset()
        self._slowest = []
        self._set_static_gauges()
        self._fold_model_counts()
        for total in self._model_totals.values():
            total[...] = 0
        self._shed_counts = {}
        for child in self._tenant_children.values():
            child.reset()
        self._tenant_shed_counts = {}
        for child in self._ttft_children.values():
            child.reset()
        self._adapter_page_ins_seen = 0
        self._adapter_evictions_seen = 0
        self._metrics["num_slots"].set(self.num_slots)
        self._set_pool_gauges()

    def _tick_span(self, phase, **attrs):
        """The span `serving.<phase>` of one scheduling tick; on exit
        its self time lands in serving_tick_phase_seconds_total
        (`stats["tick_phase_seconds"][phase]`) and in the record of the
        tick that is running."""
        sp = _TickSpan("serving." + phase, engine=self._eid, **attrs)
        sp._eng, sp._phase = self, phase
        return sp

    def _book_tick_span(self, sp):
        phase = sp._phase
        self._tick_children[phase].inc(sp.self_s)
        acc = self._tick_acc.get(phase)
        if acc is None:
            self._tick_acc[phase] = [sp.self_s, 1]
        else:
            acc[0] += sp.self_s
            acc[1] += 1
        if phase == "step":
            self._close_tick(sp)

    def _close_tick(self, sp):
        """`serving.step` closed: the tick's wall into its histogram,
        its CPU and the collector's seconds into their counters, and the
        tick's record among the slowest kept if it is one of them. A
        record is made only when it is kept."""
        wall = sp.dur
        m = self._metrics
        m["tick_seconds"].observe(wall)
        m["tick_cpu_seconds"].inc(sp.cpu_s)
        m["tick_gc_seconds"].inc(sp.gc_delta[0])
        kept = self._slowest
        full = len(kept) >= SLOWEST_TICKS_KEPT
        if full and wall <= kept[0][0]:
            return
        acc = self._tick_acc
        none = (0.0, 0)
        rec = {"tick": sp.attrs["tick"], "wall_s": wall, "cpu_s": sp.cpu_s,
               "gc_s": sp.gc_delta[0],
               "gc_collections": list(sp.gc_delta[1:]),
               "phases": {ph: acc.get(ph, none)[0] for ph in TICK_PHASES},
               "spans": {ph: acc.get(ph, none)[1] for ph in TICK_PHASES},
               "queued": sp.attrs["queued"], "active": sp.attrs["active"]}
        (heapq.heapreplace if full else heapq.heappush)(
            kept, (wall, rec["tick"], rec))

    def _tick_seconds(self):
        """`stats["tick_seconds"]`: the ticks' wall since the last
        reset. Zeros while there is none, so the dict is always JSON."""
        h = self._metrics["tick_seconds"]
        n = h.count
        if not n:
            return {"count": 0, "sum": 0.0, "max": 0.0, "p50": 0.0,
                    "p99": 0.0}
        return {"count": n, "sum": h.sum, "max": h.max,
                "p50": h.percentile(50), "p99": h.percentile(99)}

    def _shed_inc(self, reason, priority, tenant=None):
        key = (reason, int(priority))
        child = self._shed_children.get(key)
        if child is None:
            child = self._shed.labels(self._eid, reason, str(priority))
            self._shed_children[key] = child
        child.inc()
        self._shed_counts[key] = self._shed_counts.get(key, 0) + 1
        if tenant is not None:
            self._tenant_child("shed", tenant, reason).inc()
            tk = (tenant, reason)
            self._tenant_shed_counts[tk] = \
                self._tenant_shed_counts.get(tk, 0) + 1

    def _tenant_child(self, family, tenant, reason=None):
        key = (family, tenant) if reason is None \
            else (family, tenant, reason)
        child = self._tenant_children.get(key)
        if child is None:
            fam = self._tenant_fams[family]
            child = fam.labels(self._eid, str(tenant)) if reason is None \
                else fam.labels(self._eid, str(tenant), reason)
            self._tenant_children[key] = child
        self._tenants_seen.add(tenant)
        return child

    def _observe_ttft(self, prompt_len, dt, kv_tier="cold"):
        """The labeled TTFT-vs-prompt-length child (power-of-two
        buckets x KV tier; children created lazily as combinations
        appear in traffic)."""
        b = 1
        while b < prompt_len:
            b <<= 1
        key = (f"le{b}", kv_tier)
        child = self._ttft_children.get(key)
        if child is None:
            child = self._ttft_fam.labels(self._eid, key[0], kv_tier)
            self._ttft_children[key] = child
        child.observe(dt)

    def _phase(self, req, name, dur, **attrs):
        """Record one TTFT phase span: trace event + per-request
        accumulation (`req.phases` — it rides the Request through
        export/adopt, which is what keeps a migrated request's phase
        budget continuous). Disabled with the request log for honest
        A/B overhead runs."""
        if not telemetry.request_log.enabled:
            return
        dur = max(float(dur), 0.0)
        ph = getattr(req, "phases", None)
        if not isinstance(ph, dict):
            ph = req.phases = {}
        ph[name] = ph.get(name, 0.0) + dur
        telemetry.request_log.phase(req.id, self._eid, name, dur,
                                    **attrs)

    def _observe_phase_budget(self, req, kv_tier):
        """Publish the request's accumulated phase budget into the
        phase histogram at first token (one sample per phase)."""
        ph = getattr(req, "phases", None)
        if not isinstance(ph, dict):
            return
        for name, dur in ph.items():
            key = (name, kv_tier)
            child = self._phase_children.get(key)
            if child is None:
                child = self._phase_fam.labels(self._eid, name, kv_tier)
                self._phase_children[key] = child
            child.observe(dur)

    def _set_load_gauges(self):
        self._metrics["queue_depth"].set(self.scheduler.num_queued)
        self._metrics["slot_occupancy"].set(self.scheduler.num_active)
        self._metrics["admission_capacity"].set(
            self.admission_capacity_estimate())
        self._set_tenant_gauges()

    def _set_tenant_gauges(self):
        # one pass over the scheduler's queues/actives; zero the gauges
        # of tenants seen earlier but absent now so they don't stick
        sched = self.scheduler
        if not sched.tenant_quotas and not self._tenants_seen:
            return
        queued, active = {}, {}
        for q in sched._queues:
            for req in q:
                if req.tenant is not None:
                    queued[req.tenant] = queued.get(req.tenant, 0) + 1
        for req in sched._active.values():
            if req.tenant is not None:
                active[req.tenant] = active.get(req.tenant, 0) + 1
        for t in (set(queued) | set(active) | set(sched.tenant_quotas)
                  | self._tenants_seen):
            if t is None:
                continue
            self._tenant_child("queued", t).set(queued.get(t, 0))
            self._tenant_child("active", t).set(active.get(t, 0))

    def admission_capacity_estimate(self):
        """Max concurrent requests the current page budget supports:
        the slots already decoding plus how many more worst-case
        (full-length, zero-sharing) requests the pool could map —
        idle prefix-cache pages count as reclaimable. Derived from the
        same accounting the HBM ledger reports, published as
        serving_admission_capacity (never above num_slots)."""
        free = self.page_pool.num_free
        if self.prefix_cache is not None:
            idle = int((self.prefix_cache.member_mask()
                        & (self.page_pool.refcounts() == 0)).sum())
            free += idle
        return min(self.scheduler.num_active + free // self._pages_per_slot,
                   self.num_slots)

    def _set_pool_gauges(self):
        m = self._metrics
        m["pool_free_pages"].set(self.page_pool.num_free)
        m["prefix_pages_shared"].set(
            int(self.page_pool.shared_mask().sum()))
        pc = self.prefix_cache
        if pc is not None:
            m["prefix_cache_pages"].set(pc.num_pages)
            m["prefix_resident_pages"].set(pc.num_resident)
            m["prefix_spilled_pages"].set(pc.num_spilled)
            delta = pc.evicted_pages - self._evictions_seen
            if delta:
                m["prefix_evicted_pages"].inc(delta)
                self._evictions_seen = pc.evicted_pages
        hp = self.host_pool
        if hp is not None:
            m["kv_host_pages"].set(hp.num_entries)
            m["kv_host_bytes"].set(hp.bytes_used)
            delta = hp.evictions - self._host_evictions_seen
            if delta:
                m["kv_host_evictions"].inc(delta)
                self._host_evictions_seen = hp.evictions
        pool = self.adapter_pool
        if pool is not None:
            m["adapter_resident"].set(pool.num_resident)
            m["adapter_pinned"].set(pool.num_pinned)
            m["adapter_slab_bytes"].set(pool.slab_bytes())
            delta = pool.page_ins - self._adapter_page_ins_seen
            if delta:
                m["adapter_page_ins"].inc(delta)
                self._adapter_page_ins_seen = pool.page_ins
            delta = pool.evictions - self._adapter_evictions_seen
            if delta:
                m["adapter_evictions"].inc(delta)
                self._adapter_evictions_seen = pool.evictions

    def _statusz(self):
        """The /statusz + flight-recorder view of this engine: static
        config, the scheduler's slot/queue snapshot, and the headline
        rates derived from this engine's counters."""
        s = self.stats
        lookups = s["prefix_hits"] + s["prefix_misses"]
        drafted = s["spec_draft_tokens"]
        return {
            "config": {
                "num_slots": self.num_slots,
                "max_length": self.max_length,
                "page_size": self.page_size,
                "chunk_tokens": self.chunk_tokens,
                "prefill_chunk_budget": self.prefill_chunk_budget,
                "dispatch_width": self._width,
                "attn_impl": self.attn_impl,
                "prefix_cache": self.prefix_cache is not None,
                "speculative": self.speculative,
                "spec_tokens": self.spec_tokens
                if self.speculative else None,
                "max_queue": self.scheduler.max_queue,
                "num_priorities": self.scheduler.num_priorities,
                "max_retries": self.max_retries,
                "retry_backoff_s": self.retry_backoff_s,
                "total_pages": self.page_pool.num_pages,
                "kv_dtype": self.kv_dtype,
                "kv_page_bytes": self.page_pool.page_bytes,
                "weight_dtype": self.weight_dtype,
                "weight_bytes": dict(self._weight_bytes),
                "weight_bytes_per_chip": self._weight_bytes_per_chip,
                "quantized_weights": len(self._w8_plan),
                "hbm_budget_bytes": self._hbm_budget,
                "hbm_budget_includes_weights":
                    self._hbm_includes_weights,
                "host_kv_bytes": self._host_kv_bytes,
                "steady_state": self._steady,
                "adapter_pool": self.adapter_pool is not None,
                "adapter_slots": self.adapter_pool.slots
                if self.adapter_pool is not None else None,
                "adapter_max_rank": self.adapter_pool.max_rank
                if self.adapter_pool is not None else None,
                "tp_shards": self._tp,
            },
            "sharding": None if self._mesh is None else {
                "tp_shards": self._tp,
                "mesh_devices": [str(d)
                                 for d in self._mesh.devices.flat],
                "heads_per_shard":
                    self.model.config.num_heads // self._tp,
                "kv_page_bytes_per_chip":
                    self.page_pool.page_bytes // self._tp,
                "replicated": ["embeddings", "lm_head", "layernorm",
                               "page_table", "page_lock",
                               "slot_scalars", "logits"],
            },
            "admission_capacity": self.admission_capacity_estimate(),
            "kv_tier": None if self.host_pool is None else {
                "host_budget_bytes": self.host_pool.budget_bytes,
                "host_bytes_used": self.host_pool.bytes_used,
                "host_entries": self.host_pool.num_entries,
                "host_evictions": self.host_pool.evictions,
                "resident_pages": self.prefix_cache.num_resident,
                "spilled_pages": self.prefix_cache.num_spilled,
                "spilled_total": self.prefix_cache.spilled_pages,
                "paged_in_total": self.prefix_cache.paged_in_pages,
            },
            "robustness": {
                "degraded": self._degraded,
                "draining": self._draining,
                "warmed": self._steady,
                "overload_level": int(s["overload_level"]),
                "policy": None if self.policy is None
                else self.policy.snapshot(),
                "shed": {f"{r}/p{p}": n
                         for (r, p), n in sorted(self._shed_counts.items())},
                "quarantined": int(s["requests_failed"]),
                "dispatch_errors": int(s["dispatch_errors"]),
                "retry_after_s": self.estimated_queue_wait(),
            },
            "scheduler": self.scheduler.snapshot(),
            "tenants": self.tenant_stats(),
            "adapters": self.adapter_pool.snapshot()
            if self.adapter_pool is not None else None,
            "prefix_hit_rate": s["prefix_hits"] / lookups
            if lookups else None,
            "spec_acceptance": s["spec_accepted_tokens"] / drafted
            if drafted else None,
            "stats": s,
        }

    def _flight_probe(self):
        """Watchdog probe (telemetry.flight): progress is the count of
        host-visible scheduling events; busy while work is pending. A
        busy engine whose progress freezes is a stalled dispatch loop."""
        m = self._metrics
        progress = int(m["prefills"].value
                       + m["decode_dispatches"].value
                       + m["requests_finished"].value
                       + m["requests_cancelled"].value
                       + m["requests_failed"].value
                       + m["dispatch_retries"].value
                       + sum(self._shed_counts.values()))
        return progress, self.scheduler.has_work

    # -- device-cost accounting --------------------------------------------
    def mark_warm(self):
        """Declare warmup over: every program this engine should ever
        need is compiled. Any compile after this point is steady-state
        shape churn — the compile still succeeds, but the event is
        flagged and an armed flight recorder latches a
        `retrace_storm:<program>` dump naming the offending key."""
        self._steady = True

    def _steady_probe(self):
        return self._steady

    def _program(self, name):
        """Program-signature key for telemetry.cost: engine-scoped so
        two engines with different model configs never share (and so
        poison) one cost record."""
        return f"engine{self._eid}/{name}"

    def _wrap_program(self, fn, name, cost_scale=1.0):
        # shards: under SPMD, cost_analysis() reports PER-PARTITION
        # figures — the cost layer re-multiplies registration to
        # whole-model and divides the per-chip MFU/bandwidth gauges
        return _cost.CostedFunction(fn, self._program(name),
                                    steady_fn=self._steady_probe,
                                    cost_scale=cost_scale,
                                    shards=self._tp)

    def _account_flops(self, program, wall, wasted_fraction=0.0):
        """Per-dispatch device-cost bookkeeping: attribute the wall to
        the program (live MFU/bandwidth gauges) and advance this
        engine's goodput counters from the program's registered FLOPs."""
        rec = _cost.note_dispatch(program, wall)
        if rec is None or not rec.flops:
            return
        m = self._metrics
        m["model_flops"].inc(rec.flops)
        if wasted_fraction > 0.0:
            m["wasted_flops"].inc(rec.flops * wasted_fraction)
        tokens = m["tokens_emitted"].value
        if tokens:
            m["flops_per_token"].set(m["model_flops"].value / tokens)

    def _hbm_ledger(self):
        """telemetry.ledger provider: where this engine's HBM goes.
        Weights are shared arrays (the ledger dedupes them across
        engines); the prefix-cache figure is a Detail — those pages
        live inside the kv_pages slab already counted above."""
        kv = [self._kp] if self._one_pool else [self._kp, self._vp]
        if self._quant:
            kv += [self._ks, self._vs]   # dequant scales live with KV
        # w8: the slab the engine SERVES is int8 codes + dequant scales
        # for the quantized weights (plus the still-fp32 leftovers); the
        # model's original fp32 arrays for those weights are a Detail —
        # retained by the owning net, not part of the serving deployment
        if self._w8:
            weights = [self._w8_codes[i] if i in self._w8_codes
                       else p.data()
                       for i, p in enumerate(self._params)]
            weights += list(self._w8_scale_ops)
        else:
            weights = [p.data() for p in self._params]
        out = {
            "weights": weights,
            "kv_pages": kv,
            "slot_state": list(self._dstate) + [self._d_lock],
        }
        if self._rec:
            out["recurrent_state"] = list(self._rec.values())
        if self._w8:
            shadow = sum(
                int(p.data()._data.size
                    * jnp.dtype(p.data()._data.dtype).itemsize)
                for i, p in enumerate(self._params)
                if i in self._w8_codes)
            out["weights_fp32_shadow"] = _ledger.Detail(shadow)
        pool = self.adapter_pool
        if pool is not None:
            slab = [pool.A, pool.B, pool.scale]
            if pool.quantized:
                slab += [pool.a_scale, pool.b_scale]
            out["adapter_slab"] = slab
        # gluon-initialized params usually carry gradient buffers even
        # when only serving — account them so /memz reconciles
        grads = [g for g in (getattr(p._data, "_grad", None)
                             for p in self._params if p._data is not None)
                 if g is not None]
        if grads:
            out["weight_grads"] = grads
        pc = self.prefix_cache
        if pc is not None:
            out["prefix_cache_pages"] = _ledger.Detail(
                pc.num_pages * self.page_pool.page_bytes)
        if self.host_pool is not None:
            # host-tier bytes are NOT HBM: a Detail row so /memz shows
            # the spill tier next to the device figures it relieves,
            # without polluting the accounted device total
            out["host_kv"] = _ledger.Detail(self.host_pool.bytes_used)
        return out

    # -- admission control -------------------------------------------------
    def _drain_rate(self):
        """Recent finishes per second (None until two finishes land in
        the window) — the denominator of every retry-after estimate."""
        ft = self._finish_times
        if len(ft) < 2:
            return None
        dt = ft[-1] - ft[0]
        if dt <= 0:
            return None
        return (len(ft) - 1) / dt

    def estimated_queue_wait(self):
        """Seconds until the current backlog would drain at the recent
        finish rate — the retry-after estimate rejections carry and the
        deadline-feasibility signal the shedding policy uses. None when
        the engine has no recent drain history."""
        rate = self._drain_rate()
        if rate is None:
            return None
        return self.scheduler.num_queued / rate

    def estimated_drain_wait(self):
        """Seconds until EVERYTHING in flight (queued + active) would
        complete at the recent finish rate — the retry-after estimate a
        draining replica attaches to its rejections (retrying sooner
        than the drain completes cannot succeed)."""
        rate = self._drain_rate()
        if rate is None:
            return None
        return (self.scheduler.num_queued
                + self.scheduler.num_active) / rate

    def _reject(self, request, reason, cause=None):
        """Common rejection tail: count, record the terminal timeline
        with structured context, and raise (the scheduler's
        QueueFullError enriched in place, or a fresh ShedError)."""
        depth = self.scheduler.num_queued
        active = self.scheduler.num_active
        wait = self.estimated_drain_wait() if self._draining \
            else self.estimated_queue_wait()
        if wait is not None:
            self._metrics["retry_after"].set(wait)
        request.status = "shed"
        self._metrics["requests_rejected"].inc()
        self._shed_inc(reason, request.priority, request.tenant)
        telemetry.request_log.terminal(
            request.id, self._eid, "rejected", reason=reason,
            priority=request.priority, prompt_len=request.prompt_len,
            queue_depth=depth, active_slots=active,
            retry_after_s=None if wait is None else round(wait, 4))
        suffix = (f" [queue_depth={depth}, active_slots={active}"
                  + (f", retry_after~{wait:.3f}s" if wait is not None
                     else "") + "]")
        if cause is not None:
            telemetry.flight.note_queue_full(f"engine{self._eid}")
            cause.queue_depth = depth
            cause.active_slots = active
            cause.retry_after_s = wait
            cause.args = (str(cause.args[0]) + suffix,)
            raise cause
        telemetry.flight.note_shed(f"engine{self._eid}")
        raise ShedError(
            f"request {request.id} shed ({reason})" + suffix,
            reason=reason, queue_depth=depth, active_slots=active,
            retry_after_s=wait, priority=request.priority)

    # -- drain / readiness (serving/router.py consumes these) --------------
    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        """True once a drain() completed: admission closed AND no
        queued or running work remains (slots and pages all released —
        audit_pages() is clean here by construction)."""
        return self._draining and not self.scheduler.has_work

    @property
    def warmed(self):
        """True after mark_warm(): every program is compiled."""
        return self._steady

    def is_ready(self):
        """Readiness for new traffic: warmed AND not degraded AND not
        draining — the /readyz conjunction. Liveness is separate: a
        not-ready engine still serves its in-flight work."""
        return self._steady and not self._degraded \
            and not self._draining

    def _ready_probe(self):
        return {"warmed": self._steady, "degraded": self._degraded,
                "draining": self._draining}

    @thread_safe
    def drain(self):
        """Begin a rolling-restart drain: new submit() rejects with
        ShedError(reason="draining", retry_after_s=<drain estimate>),
        while queued and running requests keep being served by step()
        until the engine is empty (`drained` flips True, page audit
        clean). Rejoin the fleet with undrain(); readiness also needs
        mark_warm() (a restarted replica recompiles). Idempotent."""
        if self._draining:
            return
        self._draining = True
        telemetry.flight.record("draining", engine=self._eid)

    @thread_safe
    def undrain(self):
        """Reopen admission after a drain (no-op when not draining)."""
        if not self._draining:
            return
        self._draining = False
        telemetry.flight.record("undrained", engine=self._eid)

    # -- public API --------------------------------------------------------
    @loop_only
    def submit(self, request):
        """Queue a Request (validated against this engine's capacity).
        Rejections — over-long prompt, full admission queue, policy
        shed — count into serving_requests_rejected_total (sheds also
        into serving_shed_total{reason,priority}) AND record a terminal
        `rejected` timeline with queue depth / active slots / a
        retry-after estimate, so /requests shows rejected traffic too,
        then raise."""
        if request.prompt_len > self.max_length:
            self._metrics["requests_rejected"].inc()
            telemetry.request_log.terminal(
                request.id, self._eid, "rejected",
                reason="prompt_too_long",
                prompt_len=request.prompt_len)
            raise MXNetError(
                f"prompt of {request.prompt_len} tokens exceeds slot "
                f"capacity {self.max_length}")
        if request.adapter_id not in (None, 0):
            pool = self.adapter_pool
            if pool is None or not pool.has(request.adapter_id):
                self._metrics["requests_rejected"].inc()
                telemetry.request_log.terminal(
                    request.id, self._eid, "rejected",
                    reason="unknown_adapter",
                    adapter_id=str(request.adapter_id))
                raise MXNetError(
                    f"adapter {request.adapter_id!r} is not registered "
                    + ("(engine has no adapter pool)" if pool is None
                       else "with this engine's adapter pool"))
        if self._draining:
            self._reject(request, "draining")
        now = self._clock()
        request.t_submit = now
        request.t_deadline = None if request.deadline_ms is None \
            else now + request.deadline_ms / 1e3
        request.output_tokens = []
        request.token_times = []
        request.dispatch_failures = 0
        request.t_not_before = 0.0
        if self.policy is not None:
            action, reason = self.policy.on_submit(self, request, now)
            if action == "shed":
                self._reject(request, reason)
        try:
            out = self.scheduler.submit(request)
        except QueueFullError as e:
            self._reject(request,
                         "tenant_quota" if isinstance(e, TenantQuotaError)
                         else "queue_full", cause=e)
        request.status = "queued"
        request.phases = {}
        request.t_enqueue = now
        t = getattr(request, "trace", None) or {}
        tr = telemetry.request_log.begin(
            request.id, self._eid, trace_id=t.get("trace_id"),
            prompt_len=request.prompt_len,
            max_new_tokens=request.max_new_tokens,
            priority=request.priority,
            deadline_ms=request.deadline_ms,
            parent_span=t.get("parent_span"))
        if tr is not None and not t:
            # no upstream trace context (direct engine submit): the
            # trace id minted here still rides the Request so a later
            # migration/hedge correlates to ONE trace
            request.trace = {"trace_id": tr.trace_id}
        self._metrics["queue_depth"].set(self.scheduler.num_queued)
        return out

    @loop_only
    def cancel(self, request_id):
        """Abort a request by id, queued OR running. A queued request is
        simply dequeued; a running one releases its slot and its page
        leases immediately (tokens already emitted stay on the Request).

        Idempotent: returns the cancelled Request on success, or False
        when the id is unknown to the scheduler — never submitted, or
        already terminal (finished/cancelled/shed/failed). The
        late-cancel leg of the disconnect vs natural-finish race is
        therefore a no-op that records no second terminal timeline
        event. Call from the serving thread — cancellation mutates slot
        state between dispatches."""
        req = self.scheduler.cancel_queued(request_id)
        if req is None:
            slot = self.scheduler.slot_of(request_id)
            if slot is None:
                return False
            req = self._release_slot(slot)
        self._drop_swap(req)
        req.t_finish = self._clock()
        req.status = "cancelled"
        self._metrics["requests_cancelled"].inc()
        telemetry.request_log.end(
            request_id, self._eid, "cancelled",
            tokens=len(req.output_tokens))
        self._stream_close(req)
        self._set_load_gauges()
        self._set_pool_gauges()
        return req

    # -- migration seams (serving/router.py failover + drain) --------------
    @loop_only
    def adopt(self, request, migrated_from=None):
        """Queue a request EXPORTED from another replica, preserving
        its emitted tokens: admission re-prefills prompt+emitted and
        resumes the RNG counter at the next token index (the same
        restart continuation a rolled-back request uses), so a migrated
        output is bit-identical to an unfaulted run on the original
        replica. Unlike submit(), class queue bounds do not apply —
        the fleet already accepted this request — and t_submit /
        t_deadline carry over (router and replicas share one clock
        domain). Raises while draining; rejects oversized sequences."""
        if self._draining:
            self._reject(request, "draining")
        total = request.prompt_len + len(request.output_tokens)
        if total > self.max_length:
            self._metrics["requests_rejected"].inc()
            raise MXNetError(
                f"sequence of {total} tokens (prompt + emitted) exceeds "
                f"slot capacity {self.max_length}")
        now = self._clock()
        if request.t_submit is None:
            request.t_submit = now
        request.priority = min(max(int(request.priority), 0),
                               self.scheduler.num_priorities - 1)
        if request._seq is None:
            request._seq = next(_seq_counter)
        request.dispatch_failures = 0
        request.t_not_before = 0.0
        self.scheduler.requeue(request)
        request.status = "queued"
        request.t_enqueue = now
        if not isinstance(getattr(request, "phases", None), dict):
            request.phases = {}
        # stitch: export_requests packed the origin timeline's trace id
        # and start onto the Request — the continuation opens with the
        # SAME trace id, the ORIGINAL t_begin, and the phase budget
        # accumulated so far, so the migrated request reads as one
        # trace, not two orphans
        t = getattr(request, "trace", None) or {}
        telemetry.request_log.begin(
            request.id, self._eid, trace_id=t.get("trace_id"),
            t_begin=t.get("t_begin"), phases=request.phases,
            prompt_len=request.prompt_len,
            max_new_tokens=request.max_new_tokens,
            priority=request.priority,
            deadline_ms=request.deadline_ms,
            migrated_from=migrated_from,
            resumed_tokens=len(request.output_tokens))
        self._metrics["queue_depth"].set(self.scheduler.num_queued)
        return request

    def _refuse_recurrent(self, feature):
        if self._rec:
            raise MXNetError(
                f"{feature} is not supported for a model that declares "
                f"recurrent state ({sorted(self._rec)}): a request's "
                "state beside its KV pages is not exported")
        if self._one_pool:
            raise MXNetError(
                f"{feature} is not supported for a model whose page row "
                "has no head axis: a payload ships a K and a V page")

    @loop_only
    def export_requests(self):
        """Remove and return EVERY queued and in-flight request
        (original submit order), releasing slots and page leases. The
        emitted tokens stay on each Request, so a survivor replica can
        adopt() them and continue bit-identically. Device syncs are
        best-effort — the caller may be abandoning a wedged replica,
        whose device state no longer matters; host-side lease
        accounting is always rolled back."""
        self._refuse_recurrent("export_requests")
        out = list(self.scheduler.queued_requests())
        for q in self.scheduler._queues:
            q.clear()
        for slot in list(self.scheduler.active_slots):
            req = self.scheduler.request_at(slot)
            try:
                self._release_slot(slot)
            except Exception:       # noqa: BLE001 — wedged replica
                try:
                    self.scheduler.release(slot)
                except Exception:   # noqa: BLE001
                    pass
                self._free_slot_pages(slot)
                try:
                    self._release_adapter(slot)
                except Exception:   # noqa: BLE001
                    pass
            out.append(req)
        out.sort(key=lambda r: r._seq if r._seq is not None else -1)
        for req in out:
            # a swap payload cannot travel to another replica — drop
            # it; the adopter restarts via the replay path instead
            self._drop_swap(req)
            req.status = "exported"
            # pack the stitch context BEFORE ending the timeline: the
            # adopting replica re-opens the trace with the same id and
            # original start (adopt() passes these back to begin())
            tr = telemetry.request_log.live_trace(req.id, self._eid)
            if tr is not None:
                t = dict(getattr(req, "trace", None) or {})
                t.setdefault("trace_id", tr.trace_id)
                t["t_begin"] = tr.t_begin
                req.trace = t
            telemetry.request_log.end(
                req.id, self._eid, "migrated",
                tokens=len(req.output_tokens))
        self._set_load_gauges()
        self._set_pool_gauges()
        return out

    @loop_only
    def export_handoff(self, request_id):
        """Export ONE decoding request WITH its device KV — the
        prefill->decode handoff seam (serving/fleet, docs/SERVING.md
        "Disaggregated prefill/decode"). The slot's used pages (codes
        AND the int8 scale leaves, via the tier gather) and the decode
        cursor scalars land in `req.kv_payload`; the slot and every
        lease release; the timeline ends "migrated" with the stitch
        context packed like export_requests. An engine that adopts the
        payload (`_adopt_payload`) scatters the pages back verbatim and
        continues decoding bit-identically with no re-prefill.

        Returns None when the request is not actively decoding here:
        already terminal, never admitted, or still mid-prefill (its
        un-fed chunk queue is host state the payload format does not
        carry — the caller retries after the final chunk lands)."""
        self._refuse_recurrent("export_handoff")
        slot = None
        for s in self.scheduler.active_slots:
            if self.scheduler.request_at(s).id == request_id:
                slot = s
                break
        if slot is None:
            return None
        req = self.scheduler.request_at(slot)
        if self._pending[slot] is not None or not req.output_tokens:
            return None         # mid-prefill: nothing decodable yet
        length = int(self._lengths[slot])
        n_used = min(self._pages_per_slot,
                     -(-length // self.page_size))
        row = [int(p) for p in self._table_host[slot][:n_used]]
        req.kv_payload = {
            "length": length,
            "cur_tok": int(self._cur_tok[slot]),
            "remaining": int(self._remaining[slot]),
            "counters": int(self._counters[slot]),
            "pages": self._tier_gather(row),
            # wall-clock stamp (telemetry's re-anchored perf_counter):
            # the ONLY clock an adopting PROCESS shares with us — the
            # adopter's "handoff" phase measures from here
            "t_export": telemetry.request_trace.now(),
        }
        self._drop_swap(req)
        self._release_slot(slot)
        req.status = "exported"
        tr = telemetry.request_log.live_trace(req.id, self._eid)
        if tr is not None:
            t = dict(getattr(req, "trace", None) or {})
            t.setdefault("trace_id", tr.trace_id)
            t["t_begin"] = tr.t_begin
            req.trace = t
        telemetry.request_log.end(
            req.id, self._eid, "migrated", reason="handoff",
            tokens=len(req.output_tokens))
        self._set_load_gauges()
        self._set_pool_gauges()
        return req

    @property
    def has_work(self):
        return self.scheduler.has_work

    @loop_only
    def step(self):
        """One SUPERVISED scheduling round: shed queued work past its
        deadline, cancel running work past its deadline, admit free
        slots (queue their prompt chunks), run ONE unified dispatch
        (prefill chunks + decode + verify in the same fixed-shape
        program), free finished slots.

        Runtime dispatch faults do NOT propagate. The supervisor
        catches them, runs the page-pool invariant audit, latches a
        flight-recorder dump, rolls the implicated slots back (leases
        released, device state parked), re-queues the requests with
        backoff — and quarantines a request whose dispatches failed
        `max_retries` times (terminal reason="error"). Rolled-back
        requests restart by re-prefilling prompt+emitted with their RNG
        counter resumed, so recovered outputs are bit-identical to an
        uninterrupted run. The one exception that DOES propagate is
        telemetry.cost.ProgramCompileError: a program that does not
        compile fails identically on every retry, for every request, so
        it is the operator's error to see, not a request's to absorb.

        Returns every request that reached a TERMINAL state this round:
        finished, deadline-shed/-cancelled, or quarantined."""
        self._tick += 1
        with self._tick_span("step", tick=self._tick,
                             queued=self.scheduler.num_queued,
                             active=self.scheduler.num_active):
            now = self._clock()
            self._fire_hook("step")
            finished = []
            for req in self.scheduler.pop_expired(now):
                finished.append(self._shed_expired(req))
            for slot in list(self.scheduler.active_slots):
                req = self.scheduler.request_at(slot)
                if req.t_deadline is not None and now >= req.t_deadline:
                    finished.append(self._deadline_cancel(slot))
            for slot, req in self.scheduler.admit(now):
                try:
                    with self._tick_span("admit", slot=slot,
                                         request=req.id,
                                         prompt_len=req.prompt_len):
                        fin = self._admit(slot, req)
                except Exception as e:          # noqa: BLE001 — supervisor
                    q = self._on_admit_fault(slot, req, e)
                    if q is not None:
                        finished.append(q)
                    continue
                if fin is not None:
                    finished.append(fin)
            if self.policy is not None:
                # Assess AFTER admission: the overload level must reflect the
                # backlog this tick's dispatch actually leaves queued, not the
                # pre-admission spike that free slots are about to absorb.
                self.policy.on_step(self, now)
                if self.host_pool is not None \
                        and hasattr(self.policy, "preempt_victim"):
                    # whole-request swap: with every slot busy and strictly
                    # more-urgent work queued, swap the least-urgent running
                    # request out through the host tier — its slot admits
                    # the urgent request next tick, and it resumes
                    # bit-identically later (page-in or replay)
                    victim = self.policy.preempt_victim(self)
                    if victim is not None:
                        self._preempt_slot(victim)
            self._set_load_gauges()
            if self.scheduler.num_active:
                try:
                    finished.extend(self._dispatch())
                except _cost.ProgramCompileError:
                    raise
                except Exception as e:          # noqa: BLE001 — supervisor
                    finished.extend(self._on_decode_fault(e))
                self._set_load_gauges()
            return finished

    @loop_only
    def serve(self, requests=()):
        """Submit `requests`, run until the queue and all slots drain,
        and return every TERMINAL request (submission order) —
        finished requests plus any shed, deadline-cancelled, or
        quarantined along the way (check `.status`). Rejected
        submissions raise out of submit() and are not returned. Drain
        wall time (last submit -> empty) lands in
        serving_drain_seconds."""
        done = []
        for r in requests:
            try:
                self.submit(r)
            except (QueueFullError, ShedError):
                done.append(r)      # terminal: status == "shed"
        t_drain0 = self._clock()
        with span("serving.drain", engine=self._eid):
            while self.has_work:
                done.extend(self.step())
        self._metrics["drain_seconds"].observe(
            self._clock() - t_drain0)
        done.sort(key=lambda r: r.t_submit)
        return done

    def generate(self, prompts, max_new_tokens, **request_kw):
        """Convenience: serve a list of prompts with shared settings and
        return their generated token lists in order."""
        reqs = [Request(p, max_new_tokens, **request_kw) for p in prompts]
        by_id = {r.id: r for r in reqs}
        self.serve(reqs)
        return [by_id[r.id].output_tokens for r in reqs]

    # -- dispatch hook ------------------------------------------------------
    def _hook_takes_phase(self, hook):
        """Legacy dispatch hooks take (engine) and fire once per step;
        phase-aware hooks accept phase=/requests= keywords (or **kw)
        and fire at every prefill/decode boundary too — the seam the
        fault-injection harness (serving/faults.py) installs into.
        Detected once per hook identity from its signature."""
        cached = self._hook_kw_cache
        if cached is not None and cached[0] is hook:
            return cached[1]
        try:
            params = inspect.signature(hook).parameters
            takes = any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                or name in ("phase", "requests")
                for name, p in params.items())
        except (TypeError, ValueError):
            takes = False
        self._hook_kw_cache = (hook, takes)
        return takes

    def _fire_hook(self, phase, requests=()):
        hook = self.dispatch_hook
        if hook is None:
            return
        if self._hook_takes_phase(hook):
            hook(self, phase=phase, requests=tuple(requests))
        elif phase == "step":
            hook(self)

    # -- graceful degradation ----------------------------------------------
    def _set_degraded(self, on, reason="overload"):
        """Latch / clear graceful degradation. While degraded the
        engine suspends speculative decoding (wasted verify FLOPs are
        pure loss when demand exceeds capacity — the plain decode
        program serves until recovery), serving_degraded flips, and
        /healthz reports the engine degraded."""
        on = bool(on)
        if on == self._degraded:
            return
        self._degraded = on
        self._metrics["degraded"].set(int(on))
        name = f"engine{self._eid}"
        if on:
            _tserver.set_degraded(name, reason)
            telemetry.flight.record("degraded", engine=self._eid,
                                    reason=reason)
        else:
            _tserver.clear_degraded(name)
            telemetry.flight.record("recovered", engine=self._eid)

    # -- deadline enforcement ----------------------------------------------
    def _shed_expired(self, req):
        """A queued request whose deadline passed before admission:
        terminal `rejected(deadline)` — no tokens were produced, no
        slot or page was ever touched."""
        self._drop_swap(req)
        req.status = "shed"
        req.t_finish = self._clock()
        self._shed_inc("deadline_queued", req.priority, req.tenant)
        telemetry.request_log.end(
            req.id, self._eid, "rejected", reason="deadline",
            queued=True, tokens=0)
        self._stream_close(req)
        return req

    def _deadline_cancel(self, slot):
        """A running request past its deadline, cancelled at the
        dispatch boundary: slot and page leases released; the tokens
        already emitted stay on the Request; terminal
        `finished(deadline)`."""
        req = self._release_slot(slot)
        req.status = "deadline"
        self._shed_inc("deadline_running", req.priority, req.tenant)
        telemetry.request_log.end(
            req.id, self._eid, "finished", reason="deadline",
            tokens=len(req.output_tokens))
        self._stream_close(req)
        self._set_pool_gauges()
        return req

    # -- fault supervision --------------------------------------------------
    @thread_safe
    def audit_pages(self, raise_on_error=False):
        """Page-pool invariant audit with this engine's full lease map:
        every mapped slot's table row, any extra lease rows registered
        in `audit_extra_leases` (the fault-injection harness registers
        pages it holds), and the prefix cache's member pages. With the
        host tier on, the CROSS-TIER check rides along: the tier's
        node keys must match the tree's spilled keypaths exactly, its
        swap keys must belong to queued preempted requests, and the
        host pool's own byte accounting must balance — no page may
        leak across tiers in either direction. Returns the violation
        list ([] = clean)."""
        leases = [self._table_host[s] for s in range(self.num_slots)
                  if self._mapped[s]]
        leases.extend(self.audit_extra_leases)
        members = ()
        if self.prefix_cache is not None:
            members = np.nonzero(self.prefix_cache.member_mask())[0]
        scales = None
        if self._quant:
            # per-page scale summary for the pool's lease-consistency
            # check: the max magnitude over layers/heads — NaN/inf
            # propagates and gets flagged as corrupt quant state
            scales = np.maximum(
                np.abs(np.asarray(self._ks)).max(axis=(0, 2)),
                np.abs(np.asarray(self._vs)).max(axis=(0, 2)))
        host_keys = spilled_keys = None
        extra = []
        if self.host_pool is not None:
            spilled_keys = set(self.prefix_cache.spilled_keypaths())
            # swap payloads are legitimate host entries only while a
            # queued preempted request references them (the stale
            # inverse — a swap record whose payload the host LRU
            # dropped — is fine: resume detects it and restarts)
            swaps = {("req", r.id)
                     for r in self.scheduler.queued_requests()
                     if getattr(r, "swap", None) is not None
                     and r.swap.get("key") is not None}
            host_keys = set()
            for key in self.host_pool.keys():
                kind = key[0] if isinstance(key, tuple) and key else None
                if kind == "node":
                    host_keys.add(key[1])
                elif kind == "req":
                    if key not in swaps:
                        extra.append(
                            f"host tier holds swap payload {key!r} "
                            "with no queued preempted request "
                            "(leaked)")
                else:
                    extra.append(
                        f"host tier holds unknown key {key!r}")
            extra.extend(self.host_pool.audit())
        out = self.page_pool.audit(leases=leases, members=members,
                                   scales=scales, host_keys=host_keys,
                                   spilled_keys=spilled_keys)
        out.extend(extra)
        if out and raise_on_error:
            raise MXNetError("page pool audit failed: "
                             + "; ".join(out))
        return out

    @thread_safe
    def audit_adapters(self, raise_on_error=False):
        """Adapter-pool invariant audit with this engine's slot
        assignments: every active slot's pinned adapter must be
        resident with a pin count that matches the assignment count
        exactly (a leaked pin would wedge the slab). Returns the
        violation list ([] = clean; also [] without a pool)."""
        if self.adapter_pool is None:
            return []
        assignments = [aid for aid in self._adapter_of if aid is not None]
        return self.adapter_pool.audit(assignments=assignments,
                                       raise_on_error=raise_on_error)

    def _audit_and_latch(self, phase, exc):
        """Post-fault integrity check: run the page-pool AND
        adapter-pool audits while the implicated slots still hold their
        leases/pins (so the maps are complete) and latch a
        flight-recorder dump naming the fault. Returns the violation
        list (normally empty — the fault was caught BEFORE any
        accounting was rolled back)."""
        violations = self.audit_pages() + self.audit_adapters()
        detail = f"{phase}: {type(exc).__name__}: {exc}"
        if violations:
            detail += " | audit: " + "; ".join(violations)
        telemetry.flight.record("dispatch_error", engine=self._eid,
                                phase=phase, error=str(exc)[:200],
                                audit_violations=len(violations))
        telemetry.flight.trigger(
            f"dispatch_error:engine{self._eid}", detail)
        return violations

    def _quarantine(self, req, error):
        """Terminal failure: this request's dispatches failed
        `max_retries` times — it is poison as far as the engine can
        tell. Terminal `failed(error)`; the engine keeps serving
        everyone else."""
        self._drop_swap(req)
        req.status = "failed"
        req.t_finish = self._clock()
        self._metrics["requests_failed"].inc()
        telemetry.request_log.end(
            req.id, self._eid, "failed", reason="error",
            failures=req.dispatch_failures, error=str(error)[:200],
            tokens=len(req.output_tokens))
        telemetry.flight.record("quarantined", engine=self._eid,
                                request=req.id,
                                failures=req.dispatch_failures)
        self._stream_close(req)
        return req

    def _requeue(self, req, now, blamed, error=""):
        """Roll one request back to the queue after a caught fault.
        A `blamed` request carries the failure: exponential backoff,
        probation (the scheduler re-tries it alone), quarantine at
        max_retries. Innocents re-queue with one flat backoff tick and
        no blame — their emitted tokens ride along and the restart
        continuation keeps their output bit-identical. Returns the
        quarantined Request when the retry budget is spent, else
        None."""
        if blamed:
            req.dispatch_failures += 1
            if req.dispatch_failures >= self.max_retries:
                return self._quarantine(req, error)
            backoff = self.retry_backoff_s * (
                2 ** (req.dispatch_failures - 1))
        else:
            backoff = self.retry_backoff_s
        req.t_not_before = now + backoff
        req.t_enqueue = now     # queue_wait re-counts from HERE, not
        self._metrics["dispatch_retries"].inc()   # from t_submit
        self.scheduler.requeue(req)
        req.status = "queued"
        telemetry.request_log.event(
            req.id, self._eid, "requeued", blamed=blamed,
            failures=req.dispatch_failures, backoff_s=round(backoff, 4))
        return None

    def _on_admit_fault(self, slot, req, exc):
        """Supervise one failed admission: roll the slot fully back
        (scheduler, page leases, parked device state) and re-queue the
        request. Pool exhaustion is BACKPRESSURE — pages will drain, so
        nobody is blamed and no dump is latched; anything else counts
        against the request's retry budget. Returns the quarantined
        Request, or None."""
        now = self._clock()
        self._metrics["dispatch_errors"].inc()
        backpressure = isinstance(exc, (PagePoolExhausted,
                                        AdapterPoolExhausted))
        self.scheduler.release(slot)
        self._free_slot_pages(slot)
        self._release_adapter(slot)
        self._pending[slot] = None
        self._replay[slot] = None
        self._done[slot] = True
        self._remaining[slot] = 0
        self._lengths[slot] = self.max_length
        self._sync_slot(slot)
        if not backpressure:
            self._audit_and_latch("prefill", exc)
        self._set_pool_gauges()
        return self._requeue(req, now, blamed=not backpressure,
                             error=str(exc))

    def _on_decode_fault(self, exc):
        """Supervise a failed decode dispatch: audit while the batch's
        leases are still mapped, then roll every active slot back.
        Blame assignment: when the batch held probationers (requests
        with prior failures) only THEY are blamed — the scheduler
        admits at most one probationer at a time, so repeat faults
        converge on the poison request; a first fault (no history
        anywhere) blames the whole batch, and a later clean dispatch
        resets the innocents' counters. Returns the requests
        quarantined by this fault."""
        now = self._clock()
        self._metrics["dispatch_errors"].inc()
        self._audit_and_latch("decode", exc)
        active = [(slot, self.scheduler.request_at(slot))
                  for slot in self.scheduler.active_slots]
        probationers = {id(r) for _, r in active
                        if r.dispatch_failures > 0}
        blame_all = not probationers
        quarantined = []
        # reversed + appendleft in requeue() restores admission order
        for slot, req in reversed(active):
            self._release_slot(slot)
            q = self._requeue(
                req, now,
                blamed=blame_all or id(req) in probationers,
                error=str(exc))
            if q is not None:
                quarantined.append(q)
        self._set_pool_gauges()
        return quarantined

    def _scrub_slot_pages(self, slot):
        """Zero the KV of the slot's EXCLUSIVE, non-tree pages (the
        only pages a poisoned write can live in) before their leases
        are released — a recycled page must not carry NaN residue into
        the next owner's attention window, whatever the kernel's
        masking does with out-of-range positions."""
        if not self._mapped[slot]:
            return
        ref = self.page_pool.refcounts()
        member = self.prefix_cache.member_mask() \
            if self.prefix_cache is not None else None
        pages = [int(p) for p in self._table_host[slot]
                 if ref[int(p)] == 1
                 and (member is None or not member[int(p)])]
        if not pages:
            return
        idx = jnp.asarray(pages, jnp.int32)
        zero = jnp.zeros((), self._kp.dtype)
        self._kp = self._kp.at[:, idx].set(zero)
        if not self._one_pool:
            self._vp = self._vp.at[:, idx].set(zero)
        if self._quant:
            # a poisoned slot may have bumped these pages' scales with
            # NaN/inf absmaxes — scrub them with the codes
            zs = jnp.zeros((), jnp.float32)
            self._ks = self._ks.at[:, idx].set(zs)
            self._vs = self._vs.at[:, idx].set(zs)

    def _on_bad_slots(self, bad, exc_msg):
        """Slots whose dispatch produced non-finite final hidden
        states at a live position or non-finite logits in a sampled row
        (the in-program finite guard; the logits of rows nobody samples
        are not computed): this dispatch's tokens for them are
        already discarded by the caller; scrub their exclusive pages,
        roll them back blamed, and latch a dump. Co-batched finite
        slots keep their tokens — their state never mixed with the
        poison. Returns the requests quarantined."""
        now = self._clock()
        self._metrics["dispatch_errors"].inc()
        self._audit_and_latch("decode_nonfinite",
                              MXNetError(exc_msg))
        quarantined = []
        for slot in reversed(bad):
            req = self.scheduler.request_at(slot)
            telemetry.request_log.event(
                req.id, self._eid, "decode_discarded", slot=slot,
                reason="nonfinite_logits")
            self._scrub_slot_pages(slot)
            self._release_slot(slot)
            q = self._requeue(req, now, blamed=True, error=exc_msg)
            if q is not None:
                quarantined.append(q)
        self._set_pool_gauges()
        return quarantined

    # -- device-resident slot state ----------------------------------------
    def _build_slot_upload(self):
        """One jitted scatter that refreshes EVERY device-resident
        per-slot array for one slot in a single dispatch."""
        def upload(state, slot, vals, row):
            *scalars, table = state
            out = tuple(a.at[slot].set(v) for a, v in zip(scalars, vals))
            return out + (table.at[slot].set(row),)
        return jax.jit(upload, donate_argnums=(0,))

    def _sync_slot(self, slot):
        """Upload one slot's host-side scalar state (plus its page-table
        row and the pool's page_lock mask, which change in the same
        events) to the device-resident copies. Called on admission,
        finish and cancel — never per decode dispatch."""
        with self._tick_span("sync_slot", slot=slot):
            vals = (self._lengths[slot], self._cur_tok[slot],
                    self._done[slot], self._remaining[slot],
                    self._counters[slot], self._seeds[slot],
                    self._temp[slot], self._top_k[slot], self._top_p[slot],
                    self._do_sample[slot], self._eos[slot])
            if self.adapter_pool is not None:
                vals = vals + (self._aslot[slot],)
            self._dstate = self._upload_fn(self._dstate, np.int32(slot),
                                           vals, self._table_host[slot])
            self._d_lock = self._rep(jnp.asarray(self._page_lock_host()))

    def _adapter_args(self, aslot):
        """The extra dispatch operands when the adapter pool is on: the
        slab-slot index array plus the slab itself (read-only — never
        donated, so page-ins and dispatches interleave freely). () when
        the pool is off, keeping the dispatch signature — and the trace
        — byte-identical to a pre-adapter engine."""
        pool = self.adapter_pool
        if pool is None:
            return ()
        if isinstance(aslot, tuple):    # the _dstate tail
            aslot = aslot[0]
        args = (aslot, pool.A, pool.B, pool.scale)
        if pool.quantized:
            args = args + (pool.a_scale, pool.b_scale)
        if self._mesh is not None:
            args = (aslot,) + self._placed_slab(args[1:])
        return args

    # -- pages -------------------------------------------------------------
    def _page_lock_host(self):
        """(total_pages,) bool for the decode program: True = this page
        must not be written (shared, cached, or free). Decode writes are
        only legal in pages the writing slot holds EXCLUSIVELY."""
        lock = self.page_pool.refcounts() != 1
        if self.prefix_cache is not None:
            lock |= self.prefix_cache.member_mask()
        return lock

    def _map_slot_pages(self, slot, tokens, match=True):
        """Page-table surgery for an admission (`tokens` = the ids the
        slot must hold: the prompt, plus already-emitted tokens when a
        rolled-back request restarts): longest-prefix match, CoW split
        when the whole sequence is cached, exclusive allocation for the
        rest. Returns the prefix offset (tokens NOT recomputed; prefill
        starts there). On an allocation failure every lease taken by
        the match is released before the exception propagates — a
        faulted admission must not leak refcounts. match=False skips
        the prefix lookup (quantized restarts must recompute every
        position to replay the recorded write schedule)."""
        S, P = self.page_size, self._pages_per_slot
        Tp = int(tokens.size)
        pc = self.prefix_cache
        matched = pc.match(tokens) if (pc is not None and match) else []
        leased = list(matched)         # every lease match() took
        cow_src = None
        if matched and len(matched) * S >= Tp:
            # Fully cached sequence (page-aligned): the last token must
            # still run through the model for its logits, and that
            # rewrites the KV at position Tp-1 — INSIDE the last cached
            # page. Copy-on-write: re-home that page to an exclusive
            # copy; the other matched pages stay shared.
            cow_src = matched.pop()
        n_shared = len(matched)
        need = P - n_shared
        try:
            if pc is not None and self.page_pool.num_free < need:
                pc.reclaim(need)       # LRU-evict idle cached prefixes
            fresh = self.page_pool.alloc(need)
        except Exception:
            if pc is not None and leased:
                pc.release(leased)
            raise
        if self._quant and fresh:
            # reset recycled pages' dequant scales BEFORE any CoW copy
            # lands (the copy then stamps the source page's scale over
            # the zero). Fixed-length padded index: one compile, ever.
            idx = np.full(P, self.page_pool.num_pages, np.int32)
            idx[:len(fresh)] = fresh
            self._ks, self._vs = self._zero_scales_fn(
                self._ks, self._vs, jnp.asarray(idx))
        if cow_src is not None:
            dst = fresh[0]             # lands at row index n_shared
            src = jnp.asarray(cow_src, jnp.int32)
            dsti = jnp.asarray(dst, jnp.int32)
            if self._quant:
                self._kp, self._vp, self._ks, self._vs = \
                    self._copy_page_fn(self._kp, self._vp, self._ks,
                                       self._vs, src, dsti)
            else:
                self._kp, self._vp = self._copy_page_fn(
                    self._kp, self._vp, src, dsti)
            pc.release([cow_src])      # drop our lease on the source
            offset = Tp - 1
        else:
            offset = n_shared * S
        self._table_host[slot] = np.asarray(matched + fresh, np.int32)
        self._mapped[slot] = True
        return offset

    def _free_slot_pages(self, slot):
        if not self._mapped[slot]:
            return
        row = [int(p) for p in self._table_host[slot]]
        if self.prefix_cache is not None:
            self.prefix_cache.release(row)
        else:
            self.page_pool.free(self.page_pool.decref(row))
        self._mapped[slot] = False

    # -- host KV tier (docs/SERVING.md "Tiered KV cache") ------------------
    def _tier_gather(self, pages):
        """Device -> host payload read: the fixed-width jitted page
        gather (index padded with page 0, one compiled program however
        many pages move) plus one device_get. Returns one payload dict
        per page — int8 codes AND the per-page scale leaves travel
        together, so a later page-in restores the page verbatim and
        every future read of it is bit-identical."""
        P = self._pages_per_slot
        out = []
        for i in range(0, len(pages), P):
            blk = [int(p) for p in pages[i:i + P]]
            idx = np.zeros(P, np.int32)
            idx[:len(blk)] = blk
            if self._quant:
                k, v, ks, vs = self._tier_gather_fn(
                    self._kp, self._vp, self._ks, self._vs,
                    jnp.asarray(idx))
                k, v, ks, vs = jax.device_get((k, v, ks, vs))
            else:
                k, v = self._tier_gather_fn(self._kp, self._vp,
                                            jnp.asarray(idx))
                k, v = jax.device_get((k, v))
                ks = vs = None
            for j in range(len(blk)):
                # copy out of the gathered block: a view would pin the
                # whole (L, P, ...) buffer in host RAM per page
                pl = {"k": np.ascontiguousarray(k[:, j]),
                      "v": np.ascontiguousarray(v[:, j])}
                if ks is not None:
                    pl["ks"] = np.ascontiguousarray(ks[:, j])
                    pl["vs"] = np.ascontiguousarray(vs[:, j])
                out.append(pl)
        return out

    def _tier_scatter(self, items):
        """Host -> device page-in write for `items` = [(page_id,
        payload)]: assemble the fixed-width value block, upload it, and
        run the donated jitted scatter (pad rows target an out-of-range
        page id and drop). Scale leaves are written with the codes, so
        a paged-in int8 page needs no re-quantization — and no
        _zero_scales pass — to read back exactly."""
        P = self._pages_per_slot
        L = self._kp.shape[0]
        for i in range(0, len(items), P):
            blk = items[i:i + P]
            idx = np.full(P, self.page_pool.num_pages, np.int32)
            # P pages as the pools hold them: (L, P, S, H*D), (L, P, H)
            kval = np.zeros((L, P) + self._kp.shape[2:], self._kp.dtype)
            vval = np.zeros_like(kval)
            ksv = vsv = None
            if self._quant:
                ksv = np.zeros((L, P) + self._ks.shape[2:], np.float32)
                vsv = np.zeros_like(ksv)
            for j, (page, pl) in enumerate(blk):
                idx[j] = int(page)
                kval[:, j] = pl["k"]
                vval[:, j] = pl["v"]
                if self._quant:
                    ksv[:, j] = pl["ks"]
                    vsv[:, j] = pl["vs"]
            if self._quant:
                (self._kp, self._vp, self._ks,
                 self._vs) = self._tier_scatter_fn(
                    self._kp, self._vp, self._ks, self._vs,
                    jnp.asarray(idx),
                    self._rep(jnp.asarray(kval)),
                    self._rep(jnp.asarray(vval)),
                    self._rep(jnp.asarray(ksv)),
                    self._rep(jnp.asarray(vsv)))
            else:
                self._kp, self._vp = self._tier_scatter_fn(
                    self._kp, self._vp, jnp.asarray(idx),
                    self._rep(jnp.asarray(kval)),
                    self._rep(jnp.asarray(vval)))

    def _spill_node(self, keypath, page):
        """PrefixCache evict_hook: offer one evicted node's payload to
        the host tier (gather runs BEFORE the cache frees the device
        page). False — payload not taken, host budget unmeetable —
        makes the cache fall back to plain discard."""
        t0 = self._clock()
        key = ("node", keypath)
        payload = self._tier_gather([int(page)])[0]
        if not self.host_pool.put(key, payload):
            return False
        m = self._metrics
        m["kv_spill_pages"].inc()
        m["kv_spill_bytes"].inc(self.host_pool.entry_bytes(key))
        m["kv_spill_seconds"].observe(self._clock() - t0)
        return True

    def _pagein_nodes(self, items):
        """PrefixCache pagein_hook: restore `items` = [(keypath,
        fresh_page)] from the host tier in one batched scatter. Each
        payload is checked out (pinned) for the duration and released
        with drop=True only once the scatter landed — on any failure
        the entries survive for the next attempt."""
        t0 = self._clock()
        taken, ok, nbytes = [], False, 0
        try:
            payloads = []
            for kp, _ in items:
                key = ("node", kp)
                payloads.append(self.host_pool.checkout(key))
                taken.append(key)
                nbytes += self.host_pool.entry_bytes(key)
            self._tier_scatter(
                [(pg, pl) for (_, pg), pl in zip(items, payloads)])
            ok = True
        finally:
            for key in taken:
                self.host_pool.release(key, drop=ok)
        dt = self._clock() - t0
        m = self._metrics
        m["kv_pagein_pages"].inc(len(items))
        m["kv_pagein_bytes"].inc(nbytes)
        m["kv_pagein_seconds"].observe(dt)
        # per-request attribution: _admit zeroes this bracket before
        # the page map, so whatever the match paged in lands in the
        # admitting request's host_pagein phase
        self._pagein_acc += dt

    def _host_evict(self, key):
        """HostPagePool evict_cb: the tier wants to LRU-drop `key` to
        admit a newer spill. Node payloads go through the prefix
        cache's drop_spilled (vetoed while the node still anchors a
        spilled subtree); swap payloads are always droppable — the
        preempted request's resume detects the loss and falls back to
        the replay/restart path, which is bit-identical anyway."""
        kind, val = key
        if kind == "node":
            return self.prefix_cache.drop_spilled(val)
        return True

    def _drop_swap(self, req):
        """Discard a preempted request's swap record and host payload
        (the request went terminal, migrated, or its record went
        stale). If it ever runs again it restarts via the replay
        path. No-op for requests that were never preempted."""
        swap = getattr(req, "swap", None)
        if swap is None:
            return
        req.swap = None
        key = swap.get("key")
        if key is not None and self.host_pool is not None \
                and key in self.host_pool:
            self.host_pool.discard(key)

    def _preempt_slot(self, slot):
        """Whole-request swap under overload: gather the victim's
        EXCLUSIVE pages (the shared prefix stays in the radix tree) to
        one host-tier payload, release the slot and every page lease,
        and requeue the request unblamed at the front of its class
        with a swap record naming its prefix nodes and slot scalars.
        If the host tier cannot take the payload the request still
        yields its slot, but will restart via the replay path instead
        of resuming. Either way the continuation is bit-identical —
        swapping just skips the re-prefill compute."""
        req = self.scheduler.request_at(slot)
        S, P = self.page_size, self._pages_per_slot
        pc = self.prefix_cache
        length = int(self._lengths[slot])
        n_used = min(P, -(-length // S))
        row = [int(p) for p in self._table_host[slot][:n_used]]
        member = pc.member_mask()
        n_shared = 0
        for p in row:
            if not member[p]:
                break
            n_shared += 1
        excl = row[n_shared:]
        m = self._metrics
        m["preempts"].inc()
        key = ("req", req.id) if excl else None
        swapped = True
        if excl:
            t0 = self._clock()
            pls = self._tier_gather(excl)
            payload = {name: np.stack([pl[name] for pl in pls])
                       for name in pls[0]}
            swapped = self.host_pool.put(key, payload)
            if swapped:
                m["kv_spill_pages"].inc(len(excl))
                m["kv_spill_bytes"].inc(
                    self.host_pool.entry_bytes(key))
                m["kv_spill_seconds"].observe(self._clock() - t0)
        nodes = [pc._by_page.get(p) for p in row[:n_shared]]
        if swapped and all(n is not None for n in nodes):
            req.swap = {
                "key": key,
                "nodes": nodes,
                "n_excl": len(excl),
                "length": length,
                "cur_tok": int(self._cur_tok[slot]),
                "remaining": int(self._remaining[slot]),
                "counters": int(self._counters[slot]),
            }
        else:
            if swapped and key is not None:
                self.host_pool.discard(key)
            m["preempt_restarted"].inc()
        self._release_slot(slot)
        req.t_enqueue = self._clock()
        self.scheduler.requeue(req)
        req.status = "queued"
        telemetry.request_log.event(
            req.id, self._eid, "preempted", slot=slot,
            swapped=req.swap is not None,
            tokens=len(req.output_tokens))
        self._set_pool_gauges()

    def _try_resume(self, slot, req):
        """Splice a swapped request straight back into decode: re-lease
        its shared prefix nodes (paging spilled ones back in), restore
        its exclusive pages from the swap payload into fresh device
        pages, and rebuild the slot scalars from the swap record — no
        prefill, no replay. Returns False when the record went stale
        (payload LRU-dropped, a prefix node discarded); the caller
        falls back to the plain restart. PagePoolExhausted mid-resume
        rolls every lease taken here back and propagates — the
        supervisor requeues unblamed with the swap kept."""
        swap = req.swap
        pc = self.prefix_cache
        key = swap["key"]
        nodes = swap["nodes"]
        if (key is not None and key not in self.host_pool) \
                or any(n.dead for n in nodes):
            return False
        P = self._pages_per_slot
        n_shared = len(nodes)
        n_excl = int(swap["n_excl"])
        t0 = self._clock()
        m = self._metrics
        taken, ok, payload, nbytes = [], False, None, 0
        try:
            if key is not None:
                # pin the payload FIRST: the reclaim below may spill
                # into the host tier and LRU-pressure it out otherwise
                payload = self.host_pool.checkout(key)
                nbytes = self.host_pool.entry_bytes(key)
            resident = [n for n in nodes if not n.spilled]
            spilled = [n for n in nodes if n.spilled]
            self.page_pool.adopt([n.page for n in resident])
            taken.extend(n.page for n in resident)
            if spilled:
                pin = pc._pagein(
                    [(pc._keypath(n), n) for n in spilled],
                    next(pc._clock))
                taken.extend(pin)
                if len(pin) < len(spilled):
                    raise PagePoolExhausted(
                        f"page-in of {len(spilled)} spilled prefix "
                        f"pages restored {len(pin)} — resume of "
                        f"request {req.id} waits for pages to drain")
            need = P - n_shared
            if self.page_pool.num_free < need:
                pc.reclaim(need)
            fresh = self.page_pool.alloc(need)
            taken.extend(fresh)
            if self._quant and fresh:
                # recycled pages beyond the payload rows still need
                # zeroed scales before decode's monotone max-update
                idx = np.full(P, self.page_pool.num_pages, np.int32)
                idx[:len(fresh)] = fresh
                self._ks, self._vs = self._zero_scales_fn(
                    self._ks, self._vs, jnp.asarray(idx))
            if n_excl:
                items = []
                for j in range(n_excl):
                    pl = {"k": payload["k"][j], "v": payload["v"][j]}
                    if self._quant:
                        pl["ks"] = payload["ks"][j]
                        pl["vs"] = payload["vs"][j]
                    items.append((fresh[j], pl))
                self._tier_scatter(items)
            ok = True
        except BaseException:
            if taken:
                pc.release(taken)
            raise
        finally:
            if payload is not None:
                self.host_pool.release(key, drop=ok)
        if n_excl:
            m["kv_pagein_pages"].inc(n_excl)
            m["kv_pagein_bytes"].inc(nbytes)
            m["kv_pagein_seconds"].observe(self._clock() - t0)
        self._table_host[slot] = np.asarray(
            [n.page for n in nodes] + fresh, np.int32)
        self._mapped[slot] = True
        self._pending[slot] = None
        self._replay[slot] = None
        self._base[slot] = len(req.output_tokens)
        self._lengths[slot] = swap["length"]
        self._cur_tok[slot] = swap["cur_tok"]
        self._remaining[slot] = swap["remaining"]
        self._counters[slot] = swap["counters"]
        self._seeds[slot] = req.seed
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        self._do_sample[slot] = req.do_sample
        self._eos[slot] = -1 if req.eos_token_id is None \
            else req.eos_token_id
        self._done[slot] = False
        if self.speculative:
            self._hist[slot] = [int(t) for t in req.prompt] \
                + [int(t) for t in req.output_tokens]
        req.swap = None
        req.status = "running"
        self._sync_slot(slot)
        m["preempt_resumed"].inc()
        telemetry.request_log.event(
            req.id, self._eid, "resumed_swap", slot=slot,
            tokens=len(req.output_tokens))
        self._set_pool_gauges()
        return True

    def _adopt_payload(self, slot, req):
        """Splice a handed-off request straight into decode from its
        shipped KV payload (export_handoff on the exporting engine,
        possibly in another PROCESS): a full row of fresh exclusive
        pages, one batched scatter of the shipped page slabs — int8
        codes and their scale leaves land verbatim, so no
        re-quantization and no replay — and the decode cursor restored
        from the payload scalars. The continuation is bit-identical to
        the exporter having kept decoding. Returns False when the
        payload cannot land here (geometry/dtype mismatch, page-pool
        pressure): the caller falls back to the replay restart, which
        reaches the same tokens by recomputing."""
        if self._rec or self._one_pool:
            # a payload carries a K and a V page, not recurrent state and
            # not a pool of whole rows
            return False
        kvp = req.kv_payload
        pages = kvp.get("pages") or []
        length = int(kvp.get("length", -1))
        P, S = self._pages_per_slot, self.page_size
        if (not pages or length < 1 or length > self.max_length
                or len(pages) != min(P, -(-length // S))):
            return False
        L, _, S_, HD = self._kp.shape
        k0 = np.asarray(pages[0].get("k"))
        if k0.shape != (L, S_, HD) \
                or k0.dtype != np.dtype(self._kp.dtype) \
                or self._quant != ("ks" in pages[0]):
            return False
        pc = self.prefix_cache
        try:
            try:
                if pc is not None and self.page_pool.num_free < P:
                    pc.reclaim(P)
                fresh = self.page_pool.alloc(P)
            except Exception:   # noqa: BLE001 — pool pressure: replay
                return False
            if self._quant:
                # recycled pages must start from scale 0 before the
                # shipped scales stamp over the payload rows (the tail
                # rows stay zeroed for decode's monotone max-update)
                idx = np.full(P, self.page_pool.num_pages, np.int32)
                idx[:len(fresh)] = fresh
                self._ks, self._vs = self._zero_scales_fn(
                    self._ks, self._vs, jnp.asarray(idx))
            self._tier_scatter(list(zip(fresh[:len(pages)], pages)))
        except Exception:
            # the slot table does not reference `fresh` yet, so the
            # lease goes straight back to the pool
            self.page_pool.free(fresh)
            raise
        self._table_host[slot] = np.asarray(fresh, np.int32)
        self._mapped[slot] = True
        self._pending[slot] = None
        self._replay[slot] = None
        self._base[slot] = len(req.output_tokens)
        self._lengths[slot] = length
        self._cur_tok[slot] = int(kvp["cur_tok"])
        self._remaining[slot] = int(kvp["remaining"])
        self._counters[slot] = int(kvp["counters"])
        self._seeds[slot] = req.seed
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        self._do_sample[slot] = req.do_sample
        self._eos[slot] = -1 if req.eos_token_id is None \
            else req.eos_token_id
        self._done[slot] = False
        self._kv_tier[slot] = "cold"
        if self.speculative:
            self._hist[slot] = [int(t) for t in req.prompt] \
                + [int(t) for t in req.output_tokens]
        req.kv_payload = None
        req.status = "running"
        self._sync_slot(slot)
        # the handoff TTFT phase: export stamp -> payload scattered,
        # on the shared wall clock. The exporter already closed the
        # five in-process phases at the first token; this engine owns
        # only the hop, and publishes it into the phase histogram
        # directly (the first-token budget publication ran over there).
        t_exp = kvp.get("t_export")
        if t_exp is not None and telemetry.request_log.enabled:
            dur = max(0.0, telemetry.request_trace.now() - float(t_exp))
            self._phase(req, "handoff", dur)
            key = ("handoff", "cold")
            child = self._phase_children.get(key)
            if child is None:
                child = self._phase_fam.labels(self._eid, *key)
                self._phase_children[key] = child
            child.observe(dur)
        telemetry.request_log.event(
            req.id, self._eid, "adopted_payload", slot=slot,
            pages=len(pages), tokens=len(req.output_tokens))
        self._set_pool_gauges()
        return True

    # -- admission ---------------------------------------------------------
    @supervised("adapter/page leases taken here are rolled back by "
                "_on_admit_fault (slot state parked, leases released, "
                "pool audited) when any later admission step raises")
    def _admit(self, slot, req):
        """Map pages and park the prompt as this slot's chunk queue —
        NO forward runs here. The unified dispatch streams the queue
        chunk_tokens at a time next to everyone else's decode work and
        samples the first token when the final chunk lands.

        Restart continuation: a request rolled back after a caught
        fault already emitted `base` tokens — re-feed the prompt PLUS
        those tokens and resume the RNG stream at token index `base`,
        making the recovered output bit-identical to an uninterrupted
        run (streams are keyed (seed, token_index))."""
        base = len(req.output_tokens)
        tokens = req.prompt if not base else np.concatenate(
            [req.prompt, np.asarray(req.output_tokens, np.int32)])
        Tp = int(tokens.size)
        telemetry.request_log.event(req.id, self._eid, "admitted",
                                    slot=slot)
        if base:
            telemetry.request_log.event(
                req.id, self._eid, "resumed", tokens=base)
        self._fire_hook("prefill", (req,))
        if self.adapter_pool is not None:
            # pin BEFORE the page map: either acquire can raise
            # (AdapterPoolExhausted is backpressure, like
            # PagePoolExhausted) and _on_admit_fault rolls back
            # whatever was taken
            aslot = self.adapter_pool.acquire(req.adapter_id)
            self._adapter_of[slot] = req.adapter_id \
                if req.adapter_id not in (None, 0) else None
            self._aslot[slot] = aslot
        if req.kv_payload is not None:
            # cross-process handoff (serving/fleet): scatter the
            # shipped KV pages and splice straight into decode — no
            # re-prefill. A payload that cannot land here falls
            # through to the replay restart below, which reaches the
            # same tokens by recomputing (`kv_history` rode the wire).
            if self._adopt_payload(slot, req):
                return None
            req.kv_payload = None
            telemetry.request_log.event(req.id, self._eid,
                                        "handoff_fallback")
        if req.swap is not None:
            # preempted request: splice straight back into decode from
            # its swapped KV — no prefill. A stale swap (payload
            # LRU-dropped from the host tier, prefix nodes discarded)
            # falls through to the plain restart below, which replays
            # to the same output; PagePoolExhausted mid-resume
            # propagates as backpressure with the swap kept for a
            # later retry.
            if self._try_resume(slot, req):
                return None
            self._drop_swap(req)
            self._metrics["preempt_restarted"].inc()
            telemetry.request_log.event(req.id, self._eid,
                                        "swap_stale")
        # a prefix-cache hit seeds the chunk cursor past the shared
        # pages: length starts at the cached offset and the queue holds
        # only the uncached tail (>= 1 token — a fully cached prompt is
        # re-homed by the CoW split to recompute its last position)
        t_map0 = self._clock()
        self._pagein_acc = 0.0
        offset = self._map_slot_pages(slot, tokens,
                                      match=not (self._quant and base))
        t_map1 = self._clock()
        pagein_s = self._pagein_acc
        req.status = "prefilling"
        if req.tenant is not None:
            self._tenant_child("admitted", req.tenant).inc()
        m = self._metrics
        pc = self.prefix_cache
        if pc is not None:
            telemetry.request_log.event(
                req.id, self._eid, "prefix_match", cached_tokens=offset)
            if offset:
                m["prefix_hits"].inc()
                m["prefix_tokens_saved"].inc(offset)
            else:
                m["prefix_misses"].inc()
        # KV tier of THIS admission: a page-in during the match means
        # the prefix came back from the host tier; a hit without one
        # was device-resident; no cached prefix is a cold start
        self._kv_tier[slot] = "spilled" if pagein_s > 0.0 \
            else ("resident" if offset else "cold")
        self._chunks_fed[slot] = 0
        if not base:
            # latency SLO metrics describe the FIRST admission only —
            # a restart's wait is retry bookkeeping, not user TTFT
            m["admission_wait"].observe(self._clock() - req.t_submit)
            # TTFT phase budget: queue_wait ends where the page map
            # begins; the map splits into prefix_match (radix walk +
            # CoW/alloc) and host_pagein (tier transfers the match
            # triggered). The remaining TTFT share lands at the first
            # token (_dispatch): prefill_chunks up to the final
            # chunk's dispatch, first_decode for that dispatch itself.
            t_enq = getattr(req, "t_enqueue", None)
            self._phase(req, "queue_wait",
                        t_map0 - (t_enq if t_enq is not None
                                  else req.t_submit))
            self._phase(req, "prefix_match",
                        (t_map1 - t_map0) - pagein_s,
                        cached_tokens=int(offset))
            if pagein_s > 0.0:
                self._phase(req, "host_pagein", pagein_s)
            req.t_mark = t_map1
        # budget: every decode step writes one KV; the last sampled
        # token is never written, so a sequence of Tp supports up to
        # max_length - Tp + 1 further generated tokens; `base` already
        # spent that much of max_new_tokens. The dispatch decrements
        # remaining when the first token is emitted.
        cap = min(req.max_new_tokens - base, self.max_length - Tp + 1)
        self._pending[slot] = np.asarray(tokens[offset:], np.int32)
        if self._quant:
            if base:
                # Replay the recorded write schedule: prefix tokens the
                # first admission attached (best-effort re-chunked on
                # the natural grid — those positions were never computed
                # here), then the recorded prefill chunks, then every
                # emitted token as its own 1-token chunk, exactly how
                # decode wrote it. The trim below keeps the plan honest
                # if a replica with a different chunk_tokens adopted us.
                plan, head = [], int(req.kv_attach)
                while head > 0:
                    plan.append(min(head, self.chunk_tokens))
                    head -= plan[-1]
                plan += [int(c) for c in req.kv_history]
                tot, trimmed = 0, []
                for c in plan:
                    c = min(c, Tp - tot)
                    if c <= 0:
                        break
                    trimmed.append(c)
                    tot += c
                trimmed += [1] * (Tp - tot)
                req.kv_attach = 0
                req.kv_history = list(trimmed)
                self._replay[slot] = deque(trimmed)
            else:
                # fresh admission: nothing emitted yet, so the schedule
                # is free — reset the recording (a pre-first-token
                # rollback may have recorded chunks it then discarded)
                req.kv_history = []
                req.kv_attach = int(offset)
                self._replay[slot] = None
        if self._rec and not offset:
            # no context: the program reads zeros for this slot's state
            m["state_resets"].inc()
        self._base[slot] = base
        self._lengths[slot] = offset
        self._cur_tok[slot] = 0
        self._remaining[slot] = cap
        self._counters[slot] = base
        self._seeds[slot] = req.seed
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        self._do_sample[slot] = req.do_sample
        self._eos[slot] = -1 if req.eos_token_id is None \
            else req.eos_token_id
        self._done[slot] = False
        if self.speculative:
            self._hist[slot] = None     # drafting starts after prefill
        self._sync_slot(slot)
        m["prefill_pending"].set(self._pending_tokens())
        # the page map above leased pages whatever the engine's options
        m["pool_free_pages"].set(self.page_pool.num_free)
        if pc is not None or self.adapter_pool is not None:
            self._set_pool_gauges()
        return None

    def _pending_tokens(self):
        return sum(int(p.size) for p in self._pending if p is not None)

    # -- tensor parallelism ------------------------------------------------
    def _kv_pspec(self):
        """KV pool layout under tp: (L, pages, page, H*Dh) with the
        packed axis split over the mesh in whole-head blocks of Dh
        columns (H % tp == 0). Page structure is replicated, so
        the page table, the lock mask, and every host-side lease
        decision are shard-count-independent — prefix sharing, CoW and
        migration never see the mesh."""
        return PartitionSpec(None, None, None, AXIS_TP)

    def _scale_pspec(self):
        # int8 dequant scales are per-(layer, page, head): they shard
        # head-wise alongside the codes they decode
        return PartitionSpec(None, None, AXIS_TP)

    def _rep(self, arr):
        """Replicate a freshly-built array onto the tp mesh (identity
        at tp=1). Every dispatch operand must keep a STABLE layout
        across calls — an operand flipping between single-device and
        mesh-replicated would be a new jit cache entry, i.e. a
        steady-state recompile."""
        if self._mesh is None:
            return arr
        return jax.device_put(
            arr, named_sharding(PartitionSpec(), mesh=self._mesh))

    def _placed_params(self):
        """The dispatch's weight operands, placed onto the tp mesh ONCE
        per array (cached by identity, the source pinned so ids can't
        be recycled): qkv/fc1 column-sharded, proj/fc2 row-sharded,
        embeddings and norms replicated. set_data swaps the underlying
        array and therefore re-places. With weight_dtype="int8" the
        quantized positions carry the int8 CODE arrays instead of the
        fp32 weights — same positions, same specs, stable identities
        (quantized once at construction), so the jit cache and the
        placement cache behave exactly as in the fp path."""
        if self._w8:
            datas = tuple(
                self._w8_codes[i] if i in self._w8_codes
                else p.data()._data
                for i, p in enumerate(self._params))
        else:
            datas = tuple(p.data()._data for p in self._params)
        if self._mesh is None:
            return datas
        placed = []
        for d, spec in zip(datas, self._param_specs):
            hit = self._placed.get(id(d))
            if hit is None:
                hit = (d, jax.device_put(
                    d, named_sharding(spec, mesh=self._mesh)))
                self._placed[id(d)] = hit
            placed.append(hit[1])
        return tuple(placed)

    def _placed_slab(self, arrs):
        """Mesh placement for the adapter slab leaves (A sharded on its
        input/U axis, B on its output axis — the SAME head-aligned
        split as the base weights, so the per-shard LoRA delta lands in
        the projection's psum; scales replicated). Cached by identity
        and replaced wholesale when a page-in swaps the slab."""
        key = tuple(map(id, arrs))
        cache = self._slab_cache
        if cache is not None and cache[0] == key:
            return cache[2]
        specs = [PartitionSpec(None, None, None, AXIS_TP, None),
                 PartitionSpec(None, None, None, None, AXIS_TP)]
        specs += [PartitionSpec()] * (len(arrs) - 2)
        placed = tuple(
            jax.device_put(a, named_sharding(s, mesh=self._mesh))
            for a, s in zip(arrs, specs))
        self._slab_cache = (key, arrs, placed)
        return placed

    # -- unified dispatch --------------------------------------------------
    def _device_state(self):
        """Everything a dispatch updates in place, as the ONE pytree the
        unified program takes and donates: the page pools, the int8 scale
        pools where pages are quantized, the recurrent state where the
        model declares some."""
        state = {"k": self._kp}
        if not self._one_pool:
            state["v"] = self._vp
        if self._quant:
            state.update(ks=self._ks, vs=self._vs)
        if self._rec or self._model_counts:
            state["rec"] = {**self._rec, **self._model_counts}
        return state

    def _take_device_state(self, state):
        self._kp, self._vp = state["k"], state.get("v")
        if self._quant:
            self._ks, self._vs = state["ks"], state["vs"]
        rec = state.get("rec")
        if rec is not None:
            self._rec = {k: rec[k] for k in self._rec}
            self._model_counts = {k: rec[k] for k in self._model_counts}

    def _fold_model_counts(self):
        """The model's device counters added to the host's totals and
        zeroed: when stats are read, and every `_FOLD_EVERY` dispatches so
        that an int32 counter that grows by less than 2**31 / _FOLD_EVERY
        a dispatch never wraps."""
        for name, dev in self._model_counts.items():
            self._model_totals[name] += np.asarray(dev)
            self._model_counts[name] = jnp.zeros_like(dev)

    def _count_kernel_paths(self, before):
        """serving_kernel_path_total and serving_kernel_tile_total: which
        implementation each kernel call of a program took, and which
        block it was built with, when the program was traced (its first
        call), as ops/kernel_paths PATHS and TILES counted them since
        `before`, the copies of both taken when the program was built."""
        for registry, seen, children, family in (
                (_KERNEL_PATHS, before[0], self._path_children,
                 _kernel_path_family),
                (_KERNEL_TILES, before[1], self._tile_children,
                 _kernel_tile_family)):
            for key, n in registry.items():
                n -= seen.get(key, 0)
                if n:
                    child = children.get(key)
                    if child is None:
                        child = children[key] = family().labels(
                            self._eid, *key)
                    child.inc(n)

    def _unified_fn(self):
        """The unified program for this dispatch: greedy-only (no
        sort/RNG in-program) when no active slot samples, the general
        mixed-sampling flavor otherwise. Both are cached forever — two
        compiles per engine lifetime, never per admission, never per
        prompt length."""
        greedy_only = not bool(
            self._do_sample[self.scheduler.active_slots].any())
        fn = self._programs.get(greedy_only)
        if fn is None:
            variant = "greedy" if greedy_only else "sampled"
            name = (f"unified/W{self._width}/S{self.spec_tokens}"
                    f"/{variant}" if self.speculative
                    else f"unified/W{self._width}/{variant}")
            if self._tp > 1:
                name += f"/tp{self._tp}"
            if self._w8:
                name += "/w8"
            fn = self._wrap_program(self._build_unified(greedy_only),
                                    name)
            self._programs[greedy_only] = fn
            self._paths_before = (dict(_KERNEL_PATHS),
                                  dict(_KERNEL_TILES))
        return fn

    def _build_unified(self, greedy_only=False):
        """ONE fixed-shape program for every kind of work a slot can
        carry in a dispatch (ISSUE 11 / ROADMAP §2): per-slot q_counts
        route each of the B rows down the span kernel as a prefill
        chunk (chunk_len), a decode step (1), a speculative verify
        (1 + drafts), or idle (0). Dead query rows write no KV and emit
        exact zeros, so activity is runtime DATA — the program's shape
        never changes after its first compile."""
        model, params = self.model, self._params
        W, impl = self._width, self.attn_impl
        spec = self.speculative
        S = self.spec_tokens
        quant = self._quant
        recurrent = bool(self._rec or self._model_counts)
        tp = self._tp
        # w8: positions whose param_arrays entry is an int8 code array;
        # the per-out-tile dequant scales arrive as the operands right
        # after the KV scale pools and are bound to the traced code
        # arrays by identity (ops.nn registry) for the duration of the
        # trace — the same trace-time ctx discipline as the adapter/tp
        # contexts above, because apply_op strips NDArray attributes
        # before FullyConnected runs
        w8_idx = tuple(q.index for q in self._w8_plan)

        def unified(param_arrays, state, table, lock, lengths, cur_tok,
                    done, remaining, counters, seeds, temp, top_k,
                    top_p, do_sample, eos, toks_in, chunk_len, is_final,
                    decode_mask, *rest):
            if spec:
                drafts, n_draft, *rest = rest
            wscales = ()
            if w8_idx:
                wscales = tuple(rest[:len(w8_idx)])
                rest = rest[len(w8_idx):]
            adapter = tuple(rest)
            saved = [p._data for p in params]
            _trace_channel.push_frame()
            prev_ctx = None
            if adapter:
                aslot, a_A, a_B, a_scale, *a_qs = adapter
                prev_ctx = _set_adapter_ctx(
                    (a_A, a_B, a_scale, aslot) + tuple(a_qs))
            # tp > 1: this body traces INSIDE the shard_map, so the
            # model sees per-shard weight slices; the tp context makes
            # the attention head split and the proj/fc2 psum explicit
            prev_tp = _set_tp_ctx((AXIS_TP, tp)) if tp > 1 else None
            try:
                for p, d in zip(params, param_arrays):
                    arr = NDArray(d)
                    arr._grad_req = "null"
                    p._data = arr
                for i, s in zip(w8_idx, wscales):
                    register_w8_weight(param_arrays[i], s)
                active = decode_mask & (~done) & (remaining > 0)
                prefilling = chunk_len > 0
                finishing = prefilling & is_final
                if spec:
                    nd = jnp.where(active, n_draft, 0)
                    qn = jnp.where(prefilling, chunk_len,
                                   jnp.where(active, 1 + nd, 0))
                else:
                    qn = jnp.where(prefilling, chunk_len,
                                   jnp.where(active, 1, 0))
                cache = PagedKVCache(
                    state["k"], state.get("v"), table, lengths,
                    page_lock=lock, spans=qn, k_scale=state.get("ks"),
                    v_scale=state.get("vs"), attn_impl=impl,
                    recurrent=state.get("rec"))
                h, cache = model.hidden(NDArray(toks_in), cache)
                h = h._data
                # the rows somebody reads, picked BEFORE the head, R a
                # slot: a decoding slot's positions 0..R-1 (the one
                # decode row; with speculation the S verify rows), a
                # prefilling slot's last live position in every place
                # (a slot is one or the other in a dispatch) — the
                # distribution of the token after the full prompt.
                # The head never sees the other W - R rows of a slot.
                rows = jnp.where(prefilling[:, None],
                                 (chunk_len - 1)[:, None],
                                 jnp.arange(S if spec else 1)[None, :])
                lg = model.head(NDArray(jnp.take_along_axis(
                    h, rows[:, :, None], axis=1)))._data
                # in-program finite guard: a slot whose state went
                # non-finite (corrupted KV, numeric blowup) is flagged;
                # the host discards its tokens from this dispatch and
                # re-prefills the request. It reads the final hidden
                # states at LIVE positions (a non-finite value in any
                # K/V a live row read, or in a row that writes K/V, is
                # in that row's residual stream) and the logits of the
                # live picked rows. Not seen: finite hidden states whose
                # logits overflow in a row nobody samples.
                live = jnp.arange(W)[None, :] < qn[:, None]
                ok = (jnp.isfinite(jnp.where(live[:, :, None], h, 0.0))
                      .all(axis=(1, 2))
                      & jnp.isfinite(jnp.where(
                          (rows < qn[:, None])[:, :, None], lg, 0.0))
                      .all(axis=(1, 2))) | ~(active | prefilling)
                sel = lg[:, 0]
                if greedy_only:
                    nxt = jnp.argmax(sel, axis=-1).astype(jnp.int32)
                else:
                    keys = slot_keys(seeds, counters)
                    nxt = sample_tokens(sel, keys, do_sample, temp,
                                        top_k, top_p)
                if spec:
                    emitted, n_acc = verify_tokens(
                        lg, drafts, nd, seeds, counters,
                        do_sample, temp, top_k, top_p,
                        greedy_only=greedy_only)
                    vpos = jnp.arange(S)[None, :]
                    # emit the accepted drafts + one verifier token,
                    # capped by the remaining budget, truncated at the
                    # first eos; only the emitted count advances
                    # `lengths` — rejected drafts' KV stays behind the
                    # length (invisible) and is overwritten in place
                    n_em = jnp.minimum(n_acc + 1, remaining)
                    hit = ((emitted == eos[:, None])
                           & (eos >= 0)[:, None]
                           & (vpos < n_em[:, None]))
                    any_hit = hit.any(axis=1)
                    n_em = jnp.where(
                        any_hit,
                        jnp.minimum(n_em, jnp.argmax(hit, 1) + 1),
                        n_em)
                    n_em = jnp.where(active, n_em, 0)
                    # a finishing prefill emits exactly its first token
                    n_em = jnp.where(finishing, 1, n_em)
                    toks = jnp.where(vpos < n_em[:, None], emitted, -1)
                    toks = jnp.where(
                        finishing[:, None],
                        jnp.where(vpos == 0, nxt[:, None], -1), toks)
                    last = jnp.take_along_axis(
                        emitted, jnp.maximum(n_em - 1, 0)[:, None],
                        axis=1)[:, 0]
                    last = jnp.where(finishing, nxt, last)
                    stop = jnp.where(finishing,
                                     (nxt == eos) & (eos >= 0), any_hit)
                    n_acc_em = jnp.minimum(n_acc, n_em)
                else:
                    n_em = jnp.where(active | finishing, 1, 0)
                    toks = jnp.where((active | finishing)[:, None],
                                     nxt[:, None], -1)
                    last = nxt
                    stop = (nxt == eos) & (eos >= 0)
                    n_acc_em = jnp.zeros_like(n_em)
                emit = active | finishing
                # a prefill chunk advances by the tokens it FED (the
                # first sampled token is never written — the next
                # decode writes it); a verify row by the tokens emitted
                adv = jnp.where(prefilling, chunk_len,
                                jnp.where(active, n_em, 0))
                new_len = lengths + adv
                new_rem = remaining - jnp.where(emit, n_em, 0)
                new_done = done | (emit & (stop | (new_rem <= 0)))
                new_cur = jnp.where(emit, last, cur_tok)
                new_cnt = counters + jnp.where(emit, n_em, 0)
            finally:
                for i in w8_idx:
                    deregister_w8_weight(param_arrays[i])
                if adapter:
                    _set_adapter_ctx(prev_ctx)
                if tp > 1:
                    _set_tp_ctx(prev_tp)
                _trace_channel.pop_frame()
                for p, d in zip(params, saved):
                    p._data = d
            new_state = {"k": cache.k_pages}
            if cache.v_pages is not None:
                new_state["v"] = cache.v_pages
            if quant:
                new_state.update(ks=cache.k_scale, vs=cache.v_scale)
            if recurrent:
                new_state["rec"] = cache.recurrent
            return (new_state, new_len, new_cur, new_done, new_rem,
                    new_cnt, ok, toks, n_em, n_acc_em)

        # everything a dispatch updates in place is ONE pytree, donated
        # whole: the page pools, the int8 scale pools, the recurrent
        # state (_device_state)
        donate = (1,)
        if tp == 1:
            return jax.jit(unified, donate_argnums=donate)
        # tp > 1: the SAME body runs shard_map'ed over the {tp: N}
        # mesh. KV pools and int8 scales enter/leave split on the head
        # axis; weights enter per the serving tp rules; everything the
        # host schedules with (tables, locks, slot scalars, token
        # grids, drafts) is replicated, and every scalar OUTPUT is
        # replicated too — each shard computes the identical
        # post-psum sampler, so the result is well-defined without a
        # replication check (check_rep off: psum breaks jax's
        # conservative replication inference).
        kv, rep = self._kv_pspec(), PartitionSpec()
        state_spec = {"k": kv, "v": kv}
        if quant:
            state_spec.update(ks=self._scale_pspec(),
                              vs=self._scale_pspec())
        # positions 2..18: table, lock, the 11 slot scalars, toks_in,
        # chunk_len, is_final, decode_mask — all replicated
        in_specs = [tuple(self._param_specs), state_spec] + [rep] * 17
        if spec:
            in_specs += [rep, rep]            # drafts, n_draft
        # w8 dequant scales: column-parallel scales shard with the out
        # dim they describe, row-parallel scales are replicated (see
        # serving/weight_quant.py for why row scales are shard-
        # invariant); read-only, so never donated
        in_specs += [q.scale_spec for q in self._w8_plan]
        if self.adapter_pool is not None:
            in_specs += [rep,                  # aslot
                         PartitionSpec(None, None, None, AXIS_TP,
                                       None),  # A (input/U axis)
                         PartitionSpec(None, None, None, None,
                                       AXIS_TP),  # B (output axis)
                         rep]                  # scale
            if self.adapter_pool.quantized:
                in_specs += [rep, rep]         # a_scale, b_scale
        out_specs = [state_spec] + [rep] * 9
        fn = shard_map_compat(unified, mesh=self._mesh,
                              in_specs=tuple(in_specs),
                              out_specs=tuple(out_specs),
                              check_rep=False)
        return jax.jit(fn, donate_argnums=donate)

    def _dispatch(self):
        """ONE unified dispatch: assemble the per-slot work rows
        (prefill chunk / decode / verify / idle) on the host, run the
        fixed-shape program, then fan the results back out — emitted
        tokens, chunk-cursor advances, first tokens of prompts whose
        final chunk landed, and finish/rollback bookkeeping."""
        with self._tick_span("assemble"):
            spec = self.speculative
            spec_on = spec and not self._degraded
            B, W = self.num_slots, self._width
            S = self.spec_tokens if spec else 1
            toks_in = np.zeros((B, W), np.int32)
            chunk_len = np.zeros(B, np.int32)
            is_final = np.zeros(B, bool)
            decode_mask = np.zeros(B, bool)
            drafts = np.zeros((B, S - 1), np.int32) if spec else None
            n_draft = np.zeros(B, np.int32)
            budget = self.prefill_chunk_budget
            active_slots = list(self.scheduler.active_slots)
            self._fire_hook("decode", [self.scheduler.request_at(s)
                                       for s in active_slots])
            # prefill-budget fairness: visit slots round-robin from a
            # rotating cursor, so concurrent long prompts take turns when
            # the budget can't cover everyone each dispatch
            for slot in sorted(active_slots,
                               key=lambda s: (s - self._chunk_rr) % B):
                pend = self._pending[slot]
                if pend is not None and pend.size:
                    rq = self._replay[slot]
                    if rq:
                        # quantized restart: feed the recorded chunk size
                        # exactly — splitting it would re-quantize deep
                        # layers under different scale views and break
                        # continuation bit-identity. A chunk the current
                        # dispatch budget can't cover waits for a fresh
                        # budget; only one that can NEVER fit is split.
                        want = min(int(rq[0]), self.chunk_tokens)
                        if want > budget and want <= self.prefill_chunk_budget:
                            continue
                        n = min(want, budget)
                        if n <= 0:
                            continue
                        if n >= int(rq[0]):
                            rq.popleft()
                        else:
                            rq[0] = int(rq[0]) - n
                    else:
                        n = min(int(pend.size), self.chunk_tokens, budget)
                        if n <= 0:
                            continue    # budget spent: the chunk waits
                        if self._quant:
                            self.scheduler.request_at(slot) \
                                .kv_history.append(n)
                    budget -= n
                    toks_in[slot, :n] = pend[:n]
                    chunk_len[slot] = n
                    is_final[slot] = n == pend.size
                elif not self._done[slot] and self._remaining[slot] > 0:
                    decode_mask[slot] = True
                    toks_in[slot, 0] = self._cur_tok[slot]
                    if spec_on and self._hist[slot] is not None:
                        d = self._proposer.propose(self._hist[slot])
                        n_draft[slot] = d.size
                        drafts[slot, :d.size] = d
                        toks_in[slot, 1:1 + d.size] = d
            self._chunk_rr = (self._chunk_rr + 1) % B
            fn = self._unified_fn()
            param_datas = self._placed_params()
            st = self._dstate
            (lengths, cur_tok, done, remaining, counters, seeds, temp,
             top_k, top_p, do_sample, eos) = st[:11]
            tail, table = st[11:-1], st[-1]   # (aslot,) with the pool on
            extra = (jnp.asarray(drafts), jnp.asarray(n_draft)) \
                if spec else ()
            if self._w8:
                extra = extra + self._w8_scale_ops
        t0 = self._clock()
        with self._tick_span("dispatch", active=len(active_slots),
                             prefill_tokens=int(chunk_len.sum()),
                             drafted=int(n_draft.sum())):
            with self._tick_span("dispatch.launch"):
                out = fn(
                    param_datas, self._device_state(), table, self._d_lock,
                    lengths, cur_tok, done, remaining, counters, seeds,
                    temp, top_k, top_p, do_sample, eos,
                    jnp.asarray(toks_in), jnp.asarray(chunk_len),
                    jnp.asarray(is_final), jnp.asarray(decode_mask),
                    *extra, *self._adapter_args(tail))
                if self._paths_before is not None:
                    # the program was built for this dispatch and this
                    # call traced it
                    self._count_kernel_paths(self._paths_before)
                    self._paths_before = None
            (state, lengths, cur_tok, done, remaining, counters, okc,
             toks, n_em, n_acc) = out
            self._take_device_state(state)
            self._dstate = (lengths, cur_tok, done, remaining, counters,
                            seeds, temp, top_k, top_p, do_sample,
                            eos) + tail + (table,)
            # the wait for the device, apart from the copies: block on
            # the small outputs (the pools stay on device, donated
            # through), then fetch them — nine device-to-host copies,
            # one after another
            with self._tick_span("dispatch.wait"):
                jax.block_until_ready((lengths, cur_tok, done, remaining,
                                       counters, okc, toks, n_em, n_acc))
            with self._tick_span("dispatch.fetch"):
                (self._lengths, self._cur_tok, self._done,
                 self._remaining, self._counters) = (
                    np.array(lengths), np.array(cur_tok), np.array(done),
                    np.array(remaining), np.array(counters))
                toks, n_em, n_acc, ok = (
                    np.asarray(toks), np.asarray(n_em),
                    np.asarray(n_acc), np.asarray(okc))
        now = self._clock()
        dt = now - t0
        with self._tick_span("fanout"):
            m = self._metrics
            m["decode_dispatches"].inc()
            if self._model_counts and \
                    not int(m["decode_dispatches"].value) % _FOLD_EVERY:
                self._fold_model_counts()
            m["decode_steps"].inc()
            m["head_rows"].inc(B * S)
            n_chunks = int((chunk_len > 0).sum())
            if n_chunks:
                m["prefill_chunks"].inc(n_chunks)
                m["prefill_tokens"].inc(int(chunk_len.sum()))
            rl = telemetry.request_log
            finished = []
            bad = []
            overflowed = []
            n_emitted = 0
            accepted = 0
            for slot in active_slots:
                req = self.scheduler.request_at(slot)
                if not ok[slot]:
                    # non-finite logits: every token this dispatch produced
                    # for the slot is garbage — discard it all, roll the
                    # request back (handled below, after accounting)
                    bad.append(slot)
                    continue
                cl = int(chunk_len[slot])
                if cl:
                    self._pending[slot] = self._pending[slot][cl:]
                    self._chunks_fed[slot] += 1
                    if rl.enabled:
                        rl.event(req.id, self._eid, "prefill_chunk",
                                 dur=dt, tokens=cl,
                                 final=bool(is_final[slot]))
                    if not is_final[slot]:
                        req.dispatch_failures = 0
                        req.t_not_before = 0.0
                        continue
                    # final chunk: the request's first token landed in the
                    # same dispatch — the slot decodes from the next tick
                    self._pending[slot] = None
                    self._replay[slot] = None
                    first = int(toks[slot, 0])
                    req.output_tokens.append(first)
                    req.token_times.append(now)
                    streamed = self._stream_emit(req, [first])
                    req.dispatch_failures = 0
                    req.t_not_before = 0.0
                    req.status = "running"
                    rl.event(req.id, self._eid, "prefill", dur=dt,
                             first_token=first)
                    m["prefills"].inc()
                    n_emitted += 1
                    if not self._base[slot]:
                        req.t_admit = now
                        ttft = now - req.t_submit
                        tier = self._kv_tier[slot]
                        m["ttft"].observe(ttft)
                        self._observe_ttft(req.prompt_len, ttft, tier)
                        # close the TTFT phase budget: everything between
                        # the admit mark and this dispatch's start is
                        # prefill_chunks (earlier chunk dispatches + the
                        # waits between them); the dispatch that sampled
                        # the first token is first_decode. With the marks
                        # on ONE clock the five phases sum to TTFT exactly
                        # (minus re-queue gaps on restart/migration paths).
                        t_mark = getattr(req, "t_mark", None)
                        if rl.enabled and t_mark is not None:
                            self._phase(req, "prefill_chunks", t0 - t_mark,
                                        chunks=int(self._chunks_fed[slot]))
                            self._phase(req, "first_decode", dt)
                            rl.event(req.id, self._eid, "first_token",
                                     ttft=ttft, kv_tier=tier)
                        self._observe_phase_budget(req, tier)
                        telemetry.slo.observe_ttft(
                            ttft, priority=req.priority, tenant=req.tenant)
                    pc = self.prefix_cache
                    if pc is not None:
                        # adopt the PROMPT's full pages into the radix
                        # tree: the next request sharing this prefix
                        # attaches instead of recomputing. Membership
                        # changes the page_lock mask — refresh the device
                        # copy before the next dispatch.
                        n_full = req.prompt_len // self.page_size
                        if n_full:
                            pc.insert(
                                req.prompt,
                                [int(p)
                                 for p in self._table_host[slot][:n_full]])
                            self._d_lock = self._rep(jnp.asarray(
                                self._page_lock_host()))
                        self._set_pool_gauges()
                    if spec:
                        self._hist[slot] = [int(t) for t in req.prompt] \
                            + [int(t) for t in req.output_tokens]
                    if not streamed:
                        overflowed.append(slot)
                    elif self._done[slot] or self._remaining[slot] <= 0:
                        finished.append(self._finish(slot))
                    continue
                if not decode_mask[slot]:
                    continue            # chunk queued but out of budget
                n = int(n_em[slot])
                emitted = [int(t) for t in toks[slot, :n]]
                req.output_tokens.extend(emitted)
                req.token_times.extend([now] * n)
                streamed = self._stream_emit(req, emitted) if n else True
                # a clean dispatch clears the request's failure history —
                # probation is for consecutive faults, not per-lifetime
                req.dispatch_failures = 0
                req.t_not_before = 0.0
                if spec and self._hist[slot] is not None:
                    self._hist[slot].extend(emitted)
                if rl.enabled:
                    if spec:
                        rl.event(req.id, self._eid, "verify", dur=dt,
                                 drafted=int(n_draft[slot]),
                                 accepted=int(n_acc[slot]), tokens=n)
                    else:
                        rl.event(req.id, self._eid, "decode", dur=dt,
                                 tokens=n)
                n_emitted += n
                accepted += int(n_acc[slot])
                # dispatch resolution: a slot that got n of this dispatch's
                # tokens saw dt/n per token — the ACTUAL emitted count
                if n:
                    m["token_latency"].observe(dt / n, n)
                if not streamed:
                    overflowed.append(slot)
                elif self._done[slot] or self._remaining[slot] <= 0:
                    finished.append(self._finish(slot))
            for slot in overflowed:
                finished.append(self._overflow_cancel(slot))
            m["tokens_emitted"].inc(n_emitted)
            m["prefill_pending"].set(self._pending_tokens())
            if spec:
                drafted = int(n_draft.sum())
                m["spec_draft_tokens"].inc(drafted)
                m["spec_accepted_tokens"].inc(accepted)
                m["spec_rollbacks"].inc(drafted - accepted)
                # goodput: the unified program computes B x W query
                # positions a dispatch; the drafted-but-rejected share is
                # speculation waste (idle padding is a separate,
                # structural cost the MFU gauges already show)
                waste = (drafted - accepted) / (B * W)
            else:
                waste = 0.0
            self._account_flops(fn.program, dt, wasted_fraction=waste)
            if bad:
                finished.extend(self._on_bad_slots(
                    bad, "non-finite logits in unified dispatch"))
            return finished

    # -- per-request token streaming (serving/frontend.py subscribes) ------
    def _stream_emit(self, req, tokens):
        """Feed freshly emitted tokens to the request's subscriber
        stream, if any (duck-typed: anything with emit(list) -> bool).
        Returns False when the stream's bounded buffer could not absorb
        them — the slow-client overflow signal. A raising subscriber is
        treated the same way; it must never take the engine down."""
        st = req.stream
        if st is None:
            return True
        try:
            return bool(st.emit(tokens))
        except Exception:           # noqa: BLE001 — subscriber fault
            return False

    def _stream_close(self, req):
        """Close the request's subscriber stream (if any) with its
        terminal status, waking any reader blocked on it. Best-effort
        and exception-proof for the same reason as _stream_emit."""
        st = req.stream
        if st is None:
            return
        try:
            st.close(req.status)
        except Exception:           # noqa: BLE001 — subscriber fault
            pass

    def _overflow_cancel(self, slot):
        """Slow-client policy: the request's subscriber stream could
        not absorb this dispatch's tokens (bounded buffer full).
        Rather than queue tokens unboundedly on the host, cancel the
        request — slot, page, and adapter leases released, terminal
        `cancelled(stream_overflow)`. The stream closes with its
        overflow flag set, so the front-end sends the client a
        structured overflow error event instead of silently dropping
        tokens."""
        req = self._release_slot(slot)
        req.status = "cancelled"
        self._metrics["requests_cancelled"].inc()
        telemetry.request_log.end(
            req.id, self._eid, "cancelled", reason="stream_overflow",
            tokens=len(req.output_tokens))
        telemetry.flight.record("stream_overflow", engine=self._eid,
                                request=req.id)
        self._stream_close(req)
        self._set_pool_gauges()
        return req

    def _release_slot(self, slot):
        """Free a slot mid-flight or at completion: scheduler slot back
        to the pool, page leases released, in-program writes parked OOB
        (length = max_length) so the recycled pages can't be touched."""
        req = self.scheduler.release(slot)
        req.t_finish = self._clock()
        self._pending[slot] = None
        self._replay[slot] = None
        self._done[slot] = True
        self._remaining[slot] = 0
        self._lengths[slot] = self.max_length
        self._free_slot_pages(slot)
        self._release_adapter(slot)
        if self.speculative:
            self._hist[slot] = None
        self._sync_slot(slot)
        return req

    def _release_adapter(self, slot):
        """Drop the slot's adapter pin (no-op without a pool or for the
        null adapter) and park the slot on slab slot 0 so the next
        _sync_slot uploads a null-adapter row."""
        if self.adapter_pool is None:
            return
        aid = self._adapter_of[slot]
        if aid is not None:
            self.adapter_pool.release(aid)
            self._adapter_of[slot] = None
        self._aslot[slot] = 0

    def _finish(self, slot):
        with self._tick_span(
                "finish", slot=slot,
                request=self.scheduler.request_at(slot).id):
            # read the stop cause BEFORE release zeroes the slot state:
            # budget exhaustion leaves remaining <= 0, eos leaves budget
            reason = "budget" if self._remaining[slot] <= 0 else "eos"
            req = self._release_slot(slot)
            req.status = "finished"
            self._finish_times.append(self._clock())   # drain-rate window
            self._metrics["requests_finished"].inc()
            if req.t_admit is not None and req.t_finish > req.t_admit \
                    and len(req.output_tokens) > 1:
                # per-request decode goodput (tokens/s from first token to
                # finish) — the goodput_min SLO's observation stream
                telemetry.slo.observe_goodput(
                    (len(req.output_tokens) - 1)
                    / (req.t_finish - req.t_admit),
                    priority=req.priority, tenant=req.tenant)
            telemetry.request_log.end(
                req.id, self._eid, "finished", reason=reason,
                tokens=len(req.output_tokens))
            self._stream_close(req)
            self._set_pool_gauges()
            return req
