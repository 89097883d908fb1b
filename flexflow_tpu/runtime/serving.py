"""Continuous-batching serving runtime: slot decode over a paged KV cache.

The reference's only inference story is the training graph run forward-only
(CompMode::COMP_MODE_INFERENCE); runtime/generation.py added the modern
one-program KV-cache decode, but as a FIXED batch: finished rows burn full
decode steps emitting pads, a new request cannot start until the whole
batch retires, and every (prompt shape, max_new_tokens) pair compiles its
own program. This module is the serving-side performance subsystem on top
of it:

  * ONE jitted slot-decode step of fixed shape ``(serve_slots, 1)`` runs
    for the life of the engine — the compiled program never changes shape,
    the HOST scheduler moves work in and out of slots (the partition-
    don't-pad philosophy applied to serving: keep XLA static, move the
    raggedness to the host).
  * The KV cache is a POOL of ``(kv_pages, kv_page_size, KVH, Dh)`` blocks
    with a per-slot page table (ops/attention.py paged_decode_forward):
    long and short requests share HBM instead of every slot preallocating
    ``max_seq_len``. Pages are allocated at admission and freed at
    retirement; page 0 is a scratch page inactive slots harmlessly write.
  * Admission prefills the prompt into the slot's pages through the
    EXISTING prefill path (Generator._prefill, chunked via chunk_forward
    when ``prefill_chunk`` is set) on a contiguous per-request cache, then
    scatters that k/v into the pool — prefill numerics are therefore
    identical to batch generate's, and greedy continuous batching is
    token-identical to per-request Generator.generate
    (tests/test_serving.py).
  * Prompt lengths are rounded up to SHAPE BUCKETS (powers of two by
    default, ``decode_buckets`` to pin explicit boundaries) so warm
    prefill programs are reused across mixed lengths; ``recompile_count``
    exposes every program build, and after bucket warmup it stays flat.
  * Every compiled program returns a per-slot finiteness flag computed
    in-graph; a request whose logits go non-finite (e.g. FF_FAULT
    ``nan_loss@serve:<n>`` poisons the n-th admitted request) is retired
    as ``failed`` without stalling the other slots — serving inherits the
    fault-injection story of runtime/faultinject.py.
  * ``drain()``/``health()``: graceful shutdown for deploys and elastic
    topology changes (docs/resilience.md) — stop admitting, finish the
    in-flight slots, final stats snapshot; queued-but-unadmitted requests
    stay queued for re-submission to the replacement engine.
  * FLEET-READY: one engine lock serializes every queue/slot/counter
    mutation so a router (runtime/router.py ServingRouter) can drive
    each replica from its own thread while other threads submit and
    probe; ``submit(..., deadline=)`` retires requests that expire while
    queued as ``"timeout"`` without ever prefilling; ``load()`` is the
    lock-free dispatch signal.
  * ONE OWNER OF THE PAGES (runtime/kv_pool.py KVPagePool): the free
    list, the pool's device arrays, the page movers and the RADIX
    PREFIX CACHE — a trie over page-aligned prompt chunks with a
    refcount per page and an optional pinned-host second tier. The
    engine only asks: admission RESERVES the longest cached prefix plus
    fresh pages for the rest and prefills ONLY the tail (copy-on-write:
    a shared page is never written in place; the tail, including the
    recompute of the matched prefix's partial last page, and every
    decode append land in the request's own pages), a finished prefill
    PUBLISHES its full pages, retirement RELEASES (refcount-0 pages
    stay cached until LRU eviction under pool pressure). Identical
    prompts across millions of requests then share prefill compute AND
    the HBM pages it produced (ROADMAP item 1).
  * SPECULATIVE DECODING (``draft_model`` + ``speculate_k``): a small
    draft model proposes K greedy tokens per slot from its own paged
    pool (same page ids — the prefix cache shares draft pages too), and
    ONE fixed-shape verify program scores all K+1 positions against the
    target in a single dispatch
    (MultiHeadAttention.paged_verify_forward). Greedy slots accept the
    longest prefix of proposals matching the target's argmax (the
    stream is token-identical to non-speculative greedy decode);
    SAMPLED slots run the REJECTION-SAMPLED accept rule (ISSUE 14):
    accept proposal i w.p. min(1, p_i(d_i)/q_i(d_i)), re-draw the
    first rejection in-graph from the residual norm(max(p - q, 0)) —
    distribution-identical to the non-speculative sampler by
    construction. The accept rate rides ``stats()``.

  * PER-REQUEST SAMPLING (ISSUE 14): temperature / top-p / top-k /
    seed are SLOT-RESIDENT STATE inside the one fixed-shape program
    (per-slot scalar arrays, like ``write_pos``) — mixed sampling
    configs never recompile, and greedy is the bitwise temperature-0
    degenerate case. Sample streams are counter-based
    (ops/sampling.py): a pure function of (seed, stream, token index),
    reproducible across slot reassignment and failover resubmission.

  * PAGED LoRA ADAPTER POOL (ISSUE 14): per-request adapters served
    from a fixed-geometry device pool mirroring the KV pool's design —
    host allocator/LRU with refcounts (runtime/lora.py), ONE
    fixed-shape fault-in writer, per-slot adapter pages gathered into
    batched segmented LoRA matmuls inside the slot program
    (ops/lora.py; page 0 = the zero null adapter). The radix trie and
    router affinity are namespaced per adapter (KV depends on the
    adapter), and telemetry gains per-adapter labeled series. N
    tenants share a replica with zero recompiles.

  * QUANTIZED SERVING TIER (``FFConfig.kv_cache_dtype`` /
    ``serve_weight_dtype``, ISSUE 11): the paged pool stores int8/fp8
    payload with per-(page, kv-head) f32 scales alongside, so each page
    holds 2-4x more tokens per HBM byte — prefix-cache capacity and
    slots-per-chip multiply at fixed pool bytes while the allocator,
    COW rule, radix trie, router affinity and speculation (all
    page-granular) are untouched. Dequantization happens in VMEM:
    inside the Pallas paged-attention kernel against scalar-prefetched
    scales, or fused into the einsum gather (the parity oracle) — wide
    KV never materializes in HBM. Serving weights quantize ONCE at
    engine init (per-output-channel scales) and dequantize fused into
    each consuming matmul. Quantization is lossy: greedy streams carry
    a documented per-dtype divergence budget vs the full-width path
    (docs/serving.md "Quantized tier"); pallas-vs-einsum token identity
    and pool bitwise equality still hold exactly.

  * DISAGGREGATION PRIMITIVES over the pool's page slabs:
    ``prefill_into_cache()`` runs a prompt's prefill through the normal
    bucket programs and publishes its full pages at refcount 0,
    ``export_prefix_slab()`` serializes them (+ draft-pool KV +
    quantized scales) to host bytes, and a decode replica's
    ``import_prefix_slab()`` scatters them in through ONE fixed-shape
    page-writer program and republishes the trie path — the subsequent
    submit admits as a prefix hit, so the prefill/decode role split
    (runtime/router.py) moves pages, never tokens. ``warmup(prompts)``
    drives every reachable (bucket, matched_pages) prefill variant plus
    the page writer.

Per-slot cache layout (identical to the ragged rule of
MultiHeadAttention.decode_forward, with a per-slot prompt pad width):
logical positions ``[0, row_len)`` hold the true prompt, ``[row_len,
prompt_pad)`` hold masked bucket-pad garbage, decode tokens append from
``prompt_pad``; RoPE positions stay LOGICAL (``row_len + emitted``).
"""

from __future__ import annotations

import collections
import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu.logger import fflogger
from flexflow_tpu.ops import sampling as sampling_ops
from flexflow_tpu.runtime import (faultinject, flightrec, locks, profiler,
                                  telemetry)
from flexflow_tpu.runtime.generation import Generator
from flexflow_tpu.runtime.kv_pool import (KVPagePool, Lease, op_keeps,
                                          shared_page_groups)
from flexflow_tpu.runtime.lora import LoraAdapterPool

# process-wide engine ids: the default telemetry `replica` label when no
# router assigns a fleet identity (set_telemetry_identity)
_ENGINE_IDS = iter(range(1 << 30))

# the weight version every engine serves until a rolling deploy swaps it
# (runtime/deploy.py). The default version salts NOTHING — version_ns
# returns the bare adapter namespace, so pre-deploy behavior (cache keys,
# affinity hashes, slab namespaces) is bit-identical to builds without
# versioning.
DEFAULT_WEIGHT_VERSION = "v0"


def version_ns(version, adapter=None):
    """The prefix-cache namespace for (weight version, LoRA adapter) —
    the ISSUE-14 ``("ns", adapter)`` salt extended to versions (ISSUE
    17): KV depends on the weights that produced it, so cached prefixes
    must never cross weight versions during an A/B roll. Kept in one place
    (beside its users) so the engine, router affinity, and
    slab import/export derive the SAME key and cannot drift. The default
    version maps to the bare adapter (None for no adapter): zero change
    to any pre-deploy trie or affinity key."""
    if version in (None, "", DEFAULT_WEIGHT_VERSION):
        return adapter
    return (version, adapter)


@dataclass
class Request:
    """One serving request and its full lifecycle record."""

    rid: int
    prompt: np.ndarray              # (S,) int32, true (unpadded) prompt
    max_new_tokens: int
    state: str = "queued"       # queued | running | done | failed | timeout
    # per-request sampling config (ISSUE 14): slot-resident scalars in
    # the ONE fixed-shape program — temperature 0 is the greedy
    # degenerate case (bitwise the pre-sampling argmax). ``seed`` keys
    # the request's counter-based sample streams (ops/sampling.py): the
    # stream is a pure function of (seed, stream, token index), so it
    # reproduces across slot reassignment and failover resubmission.
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0
    # multi-tenant LoRA (ISSUE 14): the registered adapter this request
    # decodes under (None = base model / null adapter page 0), and the
    # adapter-pool page pinned for it while the slot is live
    adapter: Optional[str] = None
    adapter_page: int = 0
    # absolute time.perf_counter() deadline (None = none): a request that
    # expires while QUEUED retires as "timeout" without ever prefilling
    # (no pages, no dispatch); an already-admitted request is never
    # cancelled mid-batch — cancellation would disturb the fixed-shape
    # slot program — its late completion is the caller's to discard
    deadline: Optional[float] = None
    tokens: List[int] = field(default_factory=list)  # emitted tokens
    slot: int = -1
    bucket: int = 0
    # what the request holds of the page pool (runtime/kv_pool.py): the
    # shared prefix + published pages by reference, the rest outright;
    # ``lease.pages`` is its full logical page table
    lease: Optional[Lease] = None
    prefix_tokens: int = 0          # prefill positions served from cache
    t_submit: float = 0.0
    t_admit: float = 0.0            # left the queue for a slot (perf_counter)
    ttft: float = 0.0               # submit -> first emitted token (s)
    t_done: float = 0.0
    error: str = ""
    # telemetry (runtime/telemetry.py): the trace id this request's
    # spans carry — a router-assigned fleet id survives resubmission and
    # the prefill->decode handoff; engine-local requests get their own.
    # t_last_tok clocks the inter-token-latency histogram; decode_span
    # is the open cross-thread span handle closed at retirement.
    trace_id: str = ""
    t_last_tok: float = 0.0
    decode_span: int = 0

    @property
    def output(self) -> np.ndarray:
        """prompt + emitted tokens, the shape generate() would return
        for this request alone (minus trailing pads it never emitted)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# a tick of ``ServingEngine.step()`` longer than this explains itself in one
# warning built from the ring's events of that tick
HELD_TICK_S = 1.0

_KEY_FIELDS = {"prefill": "b", "prefill_hit": "bm", "draft_prefill": "b",
               "draft_prefill_hit": "bm", "prefill_ichunk": "bs",
               "prefill_ifinal": "b", "decode": ("k", "shared"),
               "draft_propose": "k", "verify": "k", "spec_uniforms": "k"}


def program_name(key) -> str:
    """The short name of an engine program's key, as the registry
    (runtime/profiler.py), the ``program`` count of the dispatch spans and
    the benchmark's scope tables know it: ``("decode", 8)`` ->
    ``decode_k8`` (``("decode", 8, 8)``, the program of an engine with
    prefix-hit slots, whose groups have at most 8 members ->
    ``decode_k8_shared8``), ``("prefill", 2048, 16, 0)`` -> ``prefill_b2048``,
    ``("prefill_hit", 128, 255)`` -> ``prefill_hit_b128_m255``. Fields a
    key's bucket already fixes (its page count, the engine's chunk) are
    left out."""
    fields = _KEY_FIELDS.get(key[0], "")
    return "_".join([key[0]] + [f"{f}{v}" for f, v in zip(fields, key[1:])])


class ServingEngine:
    """Continuous-batching engine over a compiled FFModel decoder LM.

    Build once (after model.compile()); ``submit()`` requests and drive
    ``step()`` yourself, or hand ``run()`` a list of prompts. Construction
    knobs default to the model's FFConfig (serve_slots, kv_page_size,
    kv_pages, decode_buckets)."""

    def __init__(self, model, serve_slots: Optional[int] = None,
                 kv_page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 decode_buckets: Optional[List[int]] = None,
                 max_seq_len: int = 1024,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 pad_id: int = 0, prefill_chunk: int = 0,
                 decode_chunk: int = 8,
                 quantize: Optional[str] = None, seed: int = 0,
                 prefix_cache: Optional[bool] = None,
                 host_kv_pages: Optional[int] = None,
                 draft_model=None, speculate_k: Optional[int] = None,
                 paged_attention_impl: Optional[str] = None,
                 kv_cache_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 adapter_pool_pages: Optional[int] = None,
                 lora_rank: Optional[int] = None,
                 lora_targets: Optional[List[str]] = None,
                 prefill_interleave_chunks: Optional[int] = None,
                 state_snapshots: Optional[int] = None,
                 prefill_chunk_loop: bool = False):
        cfg = model.config
        # sanitize mode is read at LOCK CREATION time: adopt
        # FFConfig.sanitize before this engine (or its pools)
        # creates a single lock (runtime/locks.py)
        locks.configure(cfg)
        self.model = model
        # ---- per-request sampling defaults (ISSUE 14) ----
        # requests carry their own temperature/top_p/top_k/seed as
        # slot-resident state inside the one fixed-shape program
        # (ops/sampling.py); the engine-level values are only the
        # submit() defaults. temperature 0 = greedy argmax, bitwise the
        # pre-sampling path.
        t0 = (temperature if temperature is not None
              else getattr(cfg, "serve_temperature", 0.0))
        p0 = (top_p if top_p is not None
              else getattr(cfg, "serve_top_p", 1.0))
        k0 = (top_k if top_k is not None
              else getattr(cfg, "serve_top_k", 0))
        self.default_temperature, self.default_top_p, self.default_top_k \
            = sampling_ops.validate_sampling(t0, p0, k0, "ServingEngine")
        # request-seed base: a submit() without an explicit seed gets a
        # deterministic per-rid seed derived from the engine seed. Fleet
        # routers pass explicit seeds (stable across failover
        # resubmission — engine rids differ between replicas).
        self._seed_base = (int(seed) * 1000003) & 0x7FFFFFFF
        self.slots = int(serve_slots or getattr(cfg, "serve_slots", 4))
        # decode steps per device dispatch (an in-graph lax.scan): host
        # round-trips amortize over the chunk — the per-token dispatch of
        # chunk=1 dominates small-model decode. Retirement granularity
        # coarsens to the chunk; tokens a slot computes past its own
        # eos/length are truncated by the host, so outputs are identical
        # at any chunk (tests/test_serving.py). Waste is bounded by
        # chunk-1 steps per retirement, idle-slot time by chunk-1 per
        # admission — keep it well under typical max_new_tokens.
        self.decode_chunk = max(1, int(decode_chunk))
        self.page_size = int(kv_page_size
                             or getattr(cfg, "kv_page_size", 128))
        buckets = (decode_buckets
                   if decode_buckets is not None
                   else getattr(cfg, "decode_buckets", None))
        self.buckets = sorted(int(b) for b in buckets) if buckets else None
        self.max_seq_len = int(max_seq_len)
        self.prefill_chunk = int(prefill_chunk)
        # chunk-interleaved admission (ISSUE 18): > 0 makes each cold
        # prompt's prefill chunks schedulable quanta — step() runs at
        # most this many chunks per tick between decode dispatches, so
        # a maximal prompt admits without stalling live decode streams.
        # Needs prefill_chunk > 0 (the chunk IS the quantum).
        self.prefill_interleave_chunks = int(
            prefill_interleave_chunks
            if prefill_interleave_chunks is not None
            else getattr(cfg, "prefill_interleave_chunks", 0))
        if self.prefill_interleave_chunks < 0:
            raise ValueError(
                f"prefill_interleave_chunks="
                f"{self.prefill_interleave_chunks}: must be >= 0")
        # a cold prefill's chunks as ONE loop whose body compiles once
        # (runtime/generation.py `_prefill_loop`), not a body a chunk
        self.prefill_chunk_loop = bool(prefill_chunk_loop)
        if self.prefill_chunk_loop and (
                self.prefill_chunk <= 0 or self.prefill_interleave_chunks):
            raise ValueError(
                "prefill_chunk_loop loops over chunks of prefill_chunk "
                "rows inside one program: it needs prefill_chunk > 0 and "
                "prefill_interleave_chunks == 0")
        if self.prefill_interleave_chunks and self.prefill_chunk <= 0:
            raise ValueError(
                "prefill_interleave_chunks > 0 needs prefill_chunk > 0: "
                "the chunk is the interleave quantum")
        if self.slots < 1 or self.page_size < 1 or self.max_seq_len < 2:
            raise ValueError(
                f"serve_slots={self.slots}, kv_page_size={self.page_size},"
                f" max_seq_len={self.max_seq_len}: all must be positive "
                f"(max_seq_len >= 2)")
        self.pages_per_slot = math.ceil(self.max_seq_len / self.page_size)
        # prefix-cache membership decides the derived pool size below, so
        # resolve it before the derive (the trie itself is built later)
        enable_prefix = (prefix_cache if prefix_cache is not None
                         else getattr(cfg, "serve_prefix_cache", True))
        # kv_pages = 0 derive: scratch page + one slot's worth of pages
        # per slot + prefix-cache slack. The slack matters: with exactly
        # slots*pages_per_slot pages, a full house leaves ZERO free pages
        # for refcount-0 cached prefixes, so every retirement's pages are
        # immediately reclaimed by the next admission and the radix cache
        # silently goes cold (ISSUE 18; found as PR 11's derive bug).
        # Half the slot pages — at least one slot's worth — keeps a warm
        # working set of shared prefixes alive at full occupancy. Page
        # ids are allocated pool-size-independently (pop from the low
        # end), so growing the pool never changes which pages a request
        # gets — streams are bitwise unaffected.
        slot_pages = self.slots * self.pages_per_slot
        cache_slack = (max(self.pages_per_slot, slot_pages // 2)
                       if enable_prefix else 0)
        want_pages = 1 + slot_pages + cache_slack  # +1: scratch
        explicit_pages = int(kv_pages or getattr(cfg, "kv_pages", 0) or 0)
        self.num_pages = explicit_pages or want_pages
        if not explicit_pages:
            fflogger.info(
                "serving: derived kv_pages=%d (scratch 1 + slots %d x "
                "pages_per_slot %d = %d + prefix-cache slack %d)",
                self.num_pages, self.slots, self.pages_per_slot,
                slot_pages, cache_slack)
        if self.num_pages < 1 + self.pages_per_slot:
            raise ValueError(
                f"kv_pages={self.num_pages} cannot hold even one "
                f"max_seq_len={self.max_seq_len} request "
                f"(needs {1 + self.pages_per_slot} incl. scratch page 0)")

        # ---- quantized serving tier (ISSUE 11) ----
        # weights: FFConfig.serve_weight_dtype (or the per-engine
        # weight_dtype override) promotes the weight-only quantized
        # decode path into a first-class serving mode — per-output-
        # channel scales, quantized ONCE below so the fixed-shape
        # programs trace against a stable quantized tree and never
        # retrace. The legacy `quantize` arg keeps working; mixing the
        # two with different values is a config error, not a silent pick.
        wd = (weight_dtype if weight_dtype is not None
              else getattr(cfg, "serve_weight_dtype", "native"))
        if wd not in ("native", "int8", "fp8"):
            raise ValueError(
                f"weight_dtype={wd!r}: must be 'native', 'int8' or 'fp8'")
        if wd != "native":
            if quantize not in (None, wd):
                raise ValueError(
                    f"weight_dtype={wd!r} conflicts with quantize="
                    f"{quantize!r}: pass one or the other")
            quantize = wd
        self.weight_dtype = quantize or "native"
        # KV pool storage: FFConfig.kv_cache_dtype (or the per-engine
        # override). int8/fp8 pools carry per-(page, kv-head) scales and
        # dequantize in VMEM (inside the Pallas kernel / fused into the
        # einsum gather); every page then holds 2-4x more tokens per HBM
        # byte, multiplying prefix-cache capacity and slots-per-chip —
        # the allocator, COW rule, radix trie, router affinity and
        # speculation are page-granular and unchanged.
        from flexflow_tpu.ops.attention import kv_storage_dtype

        kv_raw = (kv_cache_dtype if kv_cache_dtype is not None
                  else getattr(cfg, "kv_cache_dtype", "native"))
        kv_storage_dtype(kv_raw)  # validate early (incl. the fp8 gate)
        self._kv_dtype_arg = (None if kv_raw in (None, "", "native")
                              else kv_raw)

        # Generator supplies graph validation, the graph walk and prefill
        # — serving adds scheduling, the paged pool and the PER-SLOT
        # sampler (ops/sampling.py) around them, so the Generator's own
        # engine-wide sampler is never used by serving programs
        self.gen = Generator(model, temperature=0.0, top_k=0,
                             eos_id=eos_id, pad_id=pad_id, quantize=quantize)
        self.eos_id = eos_id
        self.pad_id = pad_id
        refusal = self.prefill_chunk_loop and self.gen.chunk_loop_refusal()
        if refusal:
            raise ValueError(f"prefill_chunk_loop: {refusal}")
        cdtype = self.gen._compute_dtype()
        if self._kv_dtype_arg is None:
            self.kv_cache_dtype = jnp.dtype(cdtype).name
        elif kv_raw == "bf16":
            self.kv_cache_dtype = "bfloat16"
        else:
            self.kv_cache_dtype = kv_raw
        if self.gen.quantize:
            # quantize once at engine init: the cached quantized tree is
            # what every program traces against — admission/decode never
            # pays the quantization pass, and the params cache cannot
            # invalidate mid-stream
            self.gen._quantized_params()
        # host_kv_pages > 0 gives the radix prefix cache a pinned
        # host-memory second tier (runtime/kv_pool.py): the shared-prefix
        # corpus is then host-RAM-sized
        hp = int(host_kv_pages if host_kv_pages is not None
                 else getattr(cfg, "host_kv_pages", 0))
        if hp < 0:
            raise ValueError(f"host_kv_pages={hp}: must be >= 0")
        if hp and not enable_prefix:
            raise ValueError(
                "host_kv_pages > 0 needs the radix prefix cache: the "
                "host tier lives UNDER the trie (prefix_cache=False "
                "engines have nothing to demote)")
        self.host_kv_pages = hp

        # speculative decoding: a draft model proposes K greedy tokens
        # per slot from its own paged pool; one fixed-shape verify
        # program scores all K+1 positions in a single dispatch
        self.speculate_k = int(speculate_k if speculate_k is not None
                               else getattr(cfg, "serve_speculate_k", 0))
        self.draft_model = (draft_model if draft_model is not None
                            else getattr(cfg, "draft_model", None))
        if self.speculate_k < 0:
            raise ValueError(
                f"speculate_k={self.speculate_k}: must be >= 0")
        # a graph whose cached ops are ALL recurrent states (every mixer a
        # state: models/brumby.py) holds no per-token row: the page pool is
        # then a free list with no array behind it (a page is only the unit
        # in which the trie keys a prefix and a lease counts a context), and
        # what fills the chip is the slots' states and their snapshots
        # snapshots of the recurrent state on the trie's nodes (runtime/
        # kv_pool.py): how many the pool holds, for a model with state
        # ops under a prefix cache; nothing is allocated for any other
        self.state_snapshots = 0
        if self.gen.state_ops:
            # a recurrent state is one array a slot, overwritten every
            # step: a prefix hit resumes from a SNAPSHOT of it that a
            # trie node carries, which the host tier does not move, and
            # one verify pass cannot score several positions of it
            named = self.gen.state_ops[0].name
            if enable_prefix and hp:
                raise ValueError(
                    f"{named} keeps a recurrent state: the host tier "
                    "moves pages of per-token rows only, a demoted "
                    "prefix would leave its snapshot behind; "
                    "host_kv_pages must be 0")
            if self.speculate_k > 0:
                raise ValueError(
                    f"{named} keeps a recurrent state: speculative "
                    "verification scores K+1 positions in one pass, which "
                    "a state advanced in place cannot undo; speculate_k "
                    "must be 0")
        windowed = sorted({w for w in map(op_keeps, self.gen.attn_ops)
                           if w is not None})
        if windowed:
            # a window layer keeps a ring of pages a slot (runtime/
            # kv_pool.py WindowPageGroup). At a page-aligned match point
            # its whole state is the pages of the window before it, so it
            # joins the snapshot protocol of the recurrent state: a trie
            # node that ends a published prefix carries those pages, a hit
            # seats a COPY of them in the slot's ring. The host tier does
            # not move a snapshot, a rejected draft position cannot be
            # taken back out of a ring, and an interleaved chunk program
            # does not seat one
            named = next(op.name for op in self.gen.attn_ops
                         if op_keeps(op) is not None)
            for bad, what in (
                    (enable_prefix and hp, "host_kv_pages must be 0 (the "
                     "host tier moves pages of the global table only, a "
                     "demoted prefix would leave its window's snapshot "
                     "behind)"),
                    (self.speculate_k > 0, "speculate_k must be 0 (a "
                     "ring of pages cannot take back rejected draft "
                     "positions)"),
                    (self.prefill_interleave_chunks > 0,
                     "prefill_interleave_chunks must be 0 (the chunk "
                     "programs do not seat a window layer's ring)")):
                if bad:
                    raise ValueError(
                        f"{named} keeps a window of {windowed[0]} "
                        f"positions: {what}")
        if enable_prefix and (self.gen.state_ops or windowed):
            self.state_snapshots = int(
                self.slots if state_snapshots is None else state_snapshots)
            if self.state_snapshots < 1:
                raise ValueError(
                    f"state_snapshots={state_snapshots}: a prefix cache "
                    f"over {named}'s recurrent state or window needs at "
                    "least one snapshot (or prefix_cache=False)")
        self.draft_gen = None
        if self.speculate_k > 0:
            if self.draft_model is None:
                raise ValueError(
                    "speculate_k > 0 needs a draft model (FFConfig."
                    "draft_model or the draft_model constructor arg): "
                    "speculative decoding verifies a DRAFT's proposals")
            tgt_v = int(model._final_tensor.dims[-1])
            dft_v = int(self.draft_model._final_tensor.dims[-1])
            if tgt_v != dft_v:
                raise ValueError(
                    f"draft/target vocab mismatch: draft emits {dft_v} "
                    f"logits, target {tgt_v} — the accept rule compares "
                    f"token ids, so the vocabularies must be identical")
            self.draft_gen = Generator(
                self.draft_model, temperature=0.0, top_k=0, eos_id=eos_id,
                pad_id=pad_id, quantize=quantize)
            if self.draft_gen.quantize:
                self.draft_gen._quantized_params()  # once, at init

        # the page pool: free list, device arrays (target and draft),
        # trie and page movers; its one compiled program, the page
        # writer, goes through this engine's program table
        self.kv = KVPagePool(
            self.gen, self.draft_gen, self.num_pages, self.page_size,
            self.pages_per_slot, self._kv_dtype_arg, enable_prefix, hp,
            lambda build, *args: self._compiled_call(
                ("page_import",), build, *args), slots=self.slots,
            snapshots=self.state_snapshots)
        # the trie, for the router and stats() to READ: pages enter and
        # leave it only through self.kv
        self.prefix_cache = self.kv.prefix_cache

        # pool-capacity observability (the router's placement signals),
        # computed once — the pool's geometry is fixed
        # for the engine's life. The bf16 reference prices the SAME
        # geometry at 2 bytes/element, so kv_capacity_vs_bf16 is exactly
        # the capacity multiplier a quantized pool buys at equal HBM.
        self._pool_bytes = sum(
            int(a.nbytes) for op in self.gen.attn_ops
            for a in jax.tree_util.tree_leaves(self.kv.pool[op.name]))
        # the recurrent ops' state: fixed bytes a slot, no per-token part
        self._state_pool_bytes = sum(
            int(a.nbytes) for op in self.gen.state_ops
            for a in jax.tree_util.tree_leaves(self.kv.pool[op.name]))
        self._state_bytes_per_slot = self._state_pool_bytes // self.slots
        self._snapshot_pool_bytes = sum(
            int(a.nbytes) for a in jax.tree_util.tree_leaves(
                self.kv.snapshots or {}))
        self._snapshot_bytes = (self._snapshot_pool_bytes
                                // (self.state_snapshots + 1))
        # bytes a token of context takes: in the table of the ops that
        # keep everything (a window group's arrays are a fixed size a slot)
        self._window_pool_bytes = sum(
            int(a.nbytes) for op in self.gen.attn_ops
            if op_keeps(op) is not None
            for a in jax.tree_util.tree_leaves(self.kv.pool[op.name]))
        self._kv_bytes_per_token = (
            (self._pool_bytes - self._window_pool_bytes)
            / (self.num_pages * self.page_size))
        self._bf16_bytes_per_token = sum(
            op.cache_bytes_per_token() for op in self.gen.attn_ops
            if op_keeps(op) is None)
        # what a bf16 pool of the same geometry would hold against this
        # one (1 where no op keeps a row: nothing to compare)
        self._kv_capacity_vs_bf16 = (
            self._bf16_bytes_per_token / self._kv_bytes_per_token
            if self._kv_bytes_per_token else 1.0)

        # the paged pool's decode attention and its prefill/append page
        # write, one choice for both, resolved ONCE here ("auto" -> the
        # backend's: the kernels on a TPU, the einsum page-gather and the
        # whole-slab scatter elsewhere) so every program this engine
        # builds, and stats(), agree on it. The einsum paths stay the
        # parity oracle: greedy streams are token-identical and prefill
        # writes bitwise identical either way (tests/test_pallas_paged.py)
        from flexflow_tpu.ops.attention import resolve_paged_attention_impl

        self.paged_attention_impl = resolve_paged_attention_impl(
            paged_attention_impl)
        # pages a turn of the paged kernel takes on the ops that keep
        # everything (a static of their pools' shapes and the table's width:
        # ops/pallas_kernels.py `paged_turn_pages`); the gather has no turns
        self._paged_turn_pages = 1
        if self.paged_attention_impl == "pallas":
            self._paged_turn_pages = max(
                (op.paged_turn_pages(self.kv.pool[op.name],
                                     self.pages_per_slot)
                 for op in self.gen.attn_ops if op_keeps(op) is None),
                default=1)
        # the shared-page form of the paged kernel (a page several live
        # slots hold, streamed once for all of them): the most members one
        # group may have, by the ops that can take it (the kernel, a pool at
        # full width, no window's ring), or None. Whether a dispatch TAKES
        # it is read off the traffic: from the first request admitted on a
        # prefix hit on (`_shared_seen`), every decode dispatch is handed
        # the groups its live slots' page tables show (`_shared_plan`, kept
        # from one seating or retirement to the next) and runs the program
        # that reads them; an engine that never sees a hit runs the
        # per-slot program it always ran
        self._shared_cap = None
        if self.paged_attention_impl == "pallas":
            caps = [op.shared_members_cap() for op in self.gen.attn_ops
                    if op_keeps(op) is None
                    and hasattr(op, "shared_members_cap")
                    and "k_scale" not in self.kv.pool[op.name]]
            cap = min(*caps, self.slots) if caps else 0
            if cap > 1:
                self._shared_cap = cap
        self._shared_seen = False
        self._shared_plan = None
        self._shared_groups = 0
        self._shared_pages_saved = 0
        fflogger.info(
            "serving: paged decode attention and prefill write impl=%s "
            "kv_cache_dtype=%s "
            "weight_dtype=%s (%.1f KV bytes/token, %.2fx bf16 capacity)",
            self.paged_attention_impl, self.kv_cache_dtype,
            self.weight_dtype, self._kv_bytes_per_token,
            self._kv_capacity_vs_bf16)

        # ---- paged LoRA adapter pool (ISSUE 14) ----
        # fixed-geometry adapter pages mirroring the KV pool's design: a
        # host allocator/LRU (runtime/lora.py) decides residency, ONE
        # fixed-shape writer program faults adapters in, and the slot
        # program gathers each slot's adapter page (page 0 = null
        # adapter) into batched segmented LoRA matmuls — N tenants, one
        # replica, zero recompiles.
        app = int(adapter_pool_pages if adapter_pool_pages is not None
                  else getattr(cfg, "serve_adapter_pool_pages", 0))
        if app < 0:
            raise ValueError(
                f"adapter_pool_pages={app}: must be >= 0 (0 = no "
                f"adapter pool)")
        self.adapter_pool_pages = app
        self.lora = None
        self.lora_pool = None
        self.lora_rank = int(lora_rank if lora_rank is not None
                             else getattr(cfg, "serve_lora_rank", 8))
        if app > 0:
            from flexflow_tpu.ffconst import OperatorType
            from flexflow_tpu.ops import lora as lora_ops

            targets = [op for op in model.ops
                       if op.op_type == OperatorType.OP_LINEAR]
            if lora_targets is not None:
                want = set(lora_targets)
                unknown = want - {op.name for op in targets}
                if unknown:
                    raise ValueError(
                        f"lora_targets {sorted(unknown)} are not Linear "
                        f"ops of this graph (Linear ops: "
                        f"{sorted(op.name for op in targets)})")
                targets = [op for op in targets if op.name in want]
            if not targets:
                raise ValueError(
                    "adapter_pool_pages > 0 but the graph has no "
                    "LoRA-targetable Linear ops")
            self._lora_ops = lora_ops
            self._lora_targets = targets
            self.lora = LoraAdapterPool(app, self.lora_rank, targets)
            repl = NamedSharding(model.mesh, PartitionSpec())
            self.lora_pool = jax.tree.map(
                lambda a: jax.device_put(a, repl),
                lora_ops.init_lora_pool(targets, app, self.lora_rank))
            self._zero_payload = lora_ops.zero_payload(targets,
                                                       self.lora_rank)

        # per-slot scheduler state (host side, shipped to device each step)
        n = self.slots
        self.page_tables = np.zeros((n, self.pages_per_slot), np.int32)
        self.row_len = np.zeros((n,), np.int32)
        self.prompt_pad = np.zeros((n,), np.int32)
        self.emitted = np.zeros((n,), np.int32)
        self.last_tok = np.zeros((n,), np.int32)
        self.active = np.zeros((n,), bool)
        self.poison = np.zeros((n,), np.float32)
        self.slot_req: List[Optional[Request]] = [None] * n
        # slot-resident sampling state (ISSUE 14): just more per-slot
        # scalars, like write_pos — idle slots sit at the greedy
        # defaults and their draws are discarded with the scratch writes
        self.temps = np.zeros((n,), np.float32)
        self.top_ps = np.ones((n,), np.float32)
        self.top_ks = np.zeros((n,), np.int32)
        self.seeds = np.zeros((n,), np.int32)
        # per-slot adapter-pool page (0 = null adapter)
        self.lora_pages = np.zeros((n,), np.int32)
        self._vocab = int(model._final_tensor.dims[-1])

        self._queue: List[Request] = []
        self._draining = False
        # mid-prefill slots (ISSUE 18): slot -> partial-prefill state
        # (request, chunked caches so far, next chunk start, padded
        # tokens). The slot is HELD (slot_req set) but inactive, so
        # decode dispatches clamp its writes to scratch page 0; the
        # state survives the scheduler loop until _finish_prefill flips
        # the slot active. _prefill_rr round-robins chunk budget across
        # mid-prefill slots so two long prompts make equal progress.
        self._partial: Dict[int, dict] = {}
        self._prefill_rr = 0
        # rolling-deploy identity (ISSUE 17): the weight version this
        # engine serves (salts cache namespaces + affinity keys via
        # version_ns) and where it stands in a roll —
        # "serving" | "draining" | "swapping" | "canary". Both ride
        # stats()/health()/telemetry.
        self.weight_version = DEFAULT_WEIGHT_VERSION
        self.deploy_state = "serving"
        self._weight_swaps = 0
        self._programs: Dict = {}
        # key -> profiler.Program: the registry holds these weakly, so a
        # program is listed for as long as this engine lives
        self._registered: Dict = {}
        self._graph_ops = profiler.graph_op_phases(self.model)
        if self.draft_model is not None:
            self._graph_ops.update(
                profiler.graph_op_phases(self.draft_model))
        # ffsan retrace sentinel: warmup() closes the program set;
        # armed + sanitize on, _compiled_call reports any further
        # jit cache miss with the argument signature that diverged
        self._retrace = locks.RetraceSentinel()
        self._key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        # ONE engine lock around every queue/slot/counter mutation so a
        # router can drive this replica from its own thread while other
        # threads submit(), probe health() or snapshot stats(). Reentrant:
        # step() holds it across the whole tick (including the device
        # dispatch) and calls locked helpers underneath — cross-thread
        # callers simply serialize behind the tick.
        self._lock = locks.make_rlock("engine")
        self.recompile_count = 0
        self.decode_steps = 0
        self._occupancy_sum = 0
        # aggregate counters instead of retaining every Request: a
        # long-lived engine must not grow memory with total traffic.
        # Retired Request objects are dropped (callers keep their own
        # handles — submit()/run() return them); TTFT percentiles come
        # from a bounded window of recent completions
        self._submitted = 0
        self._completed = 0
        self._failed = 0
        self._timeouts = 0      # expired while queued, never dispatched
        self._tokens_emitted = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_dispatches = 0
        # disaggregated-fleet counters (ISSUE 12): prefill-only
        # admissions run for the role split, page slabs exported to /
        # imported from peer replicas, and the pages those imports wrote
        self._prefill_only = 0
        self._slab_exports = 0
        self._slab_imports = 0
        self._import_pages = 0
        # long-context counters (ISSUE 18): prefill chunks run
        # interleaved with decode ticks, ticks where a mid-prefill slot
        # still had chunks left when the per-tick budget ran out, and
        # partial-prefix slab imports (start_page > 0 merges from
        # sequence-parallel prefill shards)
        self._prefill_chunks_interleaved = 0
        self._prefill_preempted_ticks = 0
        self._partial_slab_imports = 0
        # decode-attention observability (ISSUE 7 satellite): pool pages
        # the attention body READS per dispatch (sum over active slots
        # of the final-step frontier's page count — what the pallas
        # kernel streams / the einsum path gathers), plus a snapshot
        # baseline for the kernel-tune table counters. The counters are
        # PROCESS-GLOBAL (lookups fire inside kernel traces, which have
        # no engine identity), so stats() reports the process's
        # consultations since THIS engine was constructed — exact when
        # the engine is the only tracer (the usual serving process),
        # approximate when training or a second engine traces alongside
        self._pages_touched = 0
        self._last_pages_touched = 0
        self._kv_read_bytes = 0
        self._kv_streamed_bytes = 0
        self._kv_attended_bytes = 0
        # pages held, summed over decode steps, by kind of table (a model
        # with window layers: `_reach_windows`)
        self._page_steps = {"global": 0, "window": 0}
        self._tick_seq = 0
        self._ttfts = collections.deque(maxlen=4096)
        # per-adapter ledgers (ISSUE 14 telemetry satellite): requests,
        # spec proposals/accepts — keyed by adapter label ("none" for
        # base-model traffic); bounded by the registry, not by traffic
        self._adapter_requests: Dict[str, int] = {}
        self._adapter_spec: Dict[str, List[int]] = {}
        self._sampled_requests = 0
        self._sampled_slot_steps = 0
        self._sampler_gated_steps = 0
        # dropless MoE routing of the DECODE dispatches, summed over
        # steps and MoE layers (counted inside the program, live rows
        # only): assignments, experts with at least one row
        self._moe_assignments = 0
        self._moe_experts_hit = 0
        # of a model with zero-computation experts (ops/moe.py
        # `zero_experts`), counted on the device beside them: the live
        # rows' picks of an identity column and of a real expert (held
        # here or elsewhere); the real picks that landed on a held expert
        # are the assignments
        self._moe_counts_picks = any(
            op.zero_experts for op in self.gen.dropless_moe_ops)
        self._moe_zero_picks = 0
        self._moe_real_picks = 0
        # {program key: 'streamed' | 'grouped' of each dropless MoE call of
        # the program, in trace order}: a static fact of the program
        # (ops/moe.py `dropless_lowering`), collected by a list of the
        # program's own that its builder hands to the walk; and the decode
        # / prefill dispatches of programs whose calls all streamed
        self._moe_took: Dict = {}
        self._moe_streamed_dispatches = 0
        # the rows the grouped expert products of the run-to-completion
        # prefills were given (ops/moe.py `expert_rows`: a held share's
        # passes x its rows a pass, counted inside the program; the N*k of
        # a layer that holds every expert, a static fact its program's own
        # list {program key: [rows of each such call]} collects while it is
        # traced), and those prefills' assignments: rows / assignments is
        # what the products are handed for each row that needs them
        self._moe_static_rows: Dict = {}
        self._moe_expert_rows = 0
        self._moe_prefill_assignments = 0
        # what the attention ops count of their own decode dispatches
        # (`decode_span_counts`: a selecting attention's index bytes, kept
        # and seen tokens; nothing for plain attention, whose engines then
        # skip the count), and the prompt tokens admissions found cached /
        # were asked to prefill
        none_live = np.zeros((0, 1), np.int64)
        self._counting_attn_ops = [
            op for op in self.gen.attn_ops
            if op.decode_span_counts(none_live, self.page_size)]
        self._attn_counts: Dict[str, int] = {}
        for op in self._counting_attn_ops:
            self._attn_counts.update(
                op.decode_span_counts(none_live, self.page_size))
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0

        # ---- unified telemetry plane (ISSUE 13) ----
        # the engine's latency histograms (TTFT / inter-token / queue
        # wait) are observed at the event sites below; everything
        # stats() already counts is exported by the scrape-time
        # collector (_tm_collect), so the ad-hoc dict and the registry
        # can never disagree — the dict IS the collector's source.
        # FFConfig.telemetry="off" skips every emit at one predicate.
        self._tm_on = getattr(cfg, "telemetry", "on") != "off"
        self._tm_labels = {"replica": f"engine{next(_ENGINE_IDS)}",
                           "role": "solo"}
        self._retrace.owner = self._tm_labels["replica"]
        self._tm_ch: Dict = {}
        if self.lora is not None:
            # compile + run the one fixed-shape adapter writer NOW
            # (writing the null page's zeros is a no-op): every later
            # fault-in of a real adapter reuses this program, so tenant
            # churn never compiles — and recompile-flatness tests see
            # the build at construction, outside any warm window
            self._write_adapter_page(0, self._zero_payload, 0.0)
        # flight recorder + SLO plane adopt the config's knobs
        # UNCONDITIONALLY: configure() is how telemetry="off" reaches
        # the recorder's own gate — skipping it when off would leave an
        # env-configured FF_FLIGHT_DIR recorder live under an "off"
        # config
        flightrec.configure(cfg)
        if self._tm_on:
            if getattr(cfg, "metrics_port", 0):
                telemetry.start_http_server(cfg.metrics_port)
            self._tm_bind_children()
            telemetry.registry().add_collector(self._tm_collect)
            # ISSUE 15: register this engine as a post-mortem bundle
            # source (stats/health snapshot), an HBM-ledger source (KV
            # pool incl. host tier, adapter pool, quantized serving
            # weights), an SLO ratio source (prefix-hit / spec-accept
            # window floors) and a lock-free health probe for /healthz
            # — all weakly referenced, same off predicate
            flightrec.recorder().attach_source(self._flightrec_source)
            flightrec.hbm_ledger().add_source(self._hbm_source)
            flightrec.slo_monitor().add_source(self._slo_source)
            flightrec.register_health_source(self._health_probe)

    # ---- telemetry ----------------------------------------------------------

    def set_telemetry_identity(self, replica, role: str):
        """Fleet identity for this engine's metric labels and trace
        track (the router stamps replica index + role at construction;
        standalone engines keep their process-unique engine id). The
        scrape topology is one fleet per process — a second router's
        replica 0 shares the first's labeled series
        (docs/observability.md)."""
        self._tm_labels = {"replica": str(replica), "role": str(role)}
        if self._tm_on:
            self._tm_bind_children()

    def _tm_bind_children(self):
        """Resolve the hot-path histogram children ONCE per identity:
        per-token emits then cost a single predicate + one lock-cheap
        observe — no registry/family lookup, no label-tuple build."""
        reg = telemetry.registry()
        lab = (self._tm_labels["replica"], self._tm_labels["role"])
        self._tm_ch = {
            "ttft": reg.histogram(
                "ff_serving_ttft_seconds",
                "engine submit -> first token",
                labels=("replica", "role")).labels(*lab),
            "itl": reg.histogram(
                "ff_serving_intertoken_seconds",
                "gap between consecutive emitted tokens",
                labels=("replica", "role")).labels(*lab),
            "queue": reg.histogram(
                "ff_serving_queue_wait_seconds",
                "engine queue wait: submit -> admission",
                labels=("replica", "role")).labels(*lab),
        }
        # per-adapter families (ISSUE 14): children resolved lazily per
        # adapter label and cached (bounded by the adapter registry)
        self._tm_fam_req = reg.counter(
            "ff_serving_requests_total",
            "requests submitted, labeled by LoRA adapter "
            "('none' = base model)",
            labels=("replica", "role", "adapter"))
        self._tm_fam_attft = reg.histogram(
            "ff_serving_adapter_ttft_seconds",
            "engine submit -> first token, labeled by LoRA adapter",
            labels=("replica", "role", "adapter"))
        self._tm_adapter_ch = {}

    def _tm_adapter(self, adapter: Optional[str]):
        key = adapter or "none"
        ch = self._tm_adapter_ch.get(key)
        if ch is None:
            lab = (self._tm_labels["replica"], self._tm_labels["role"],
                   key)
            ch = self._tm_adapter_ch[key] = (
                self._tm_fam_req.labels(*lab),
                self._tm_fam_attft.labels(*lab))
        return ch

    @property
    def _tm_track(self) -> str:
        return f"replica{self._tm_labels['replica']}"

    def _tm_collect(self, reg):
        """Scrape-time collector: publish every numeric stats() key as
        a ``ff_serving_<key>`` gauge labeled (replica, role), plus one
        info series carrying the engine's dtype/impl identity. stats()
        serializes behind a running tick — scrapes are rare and the
        snapshot is exact."""
        st = self.stats()
        lab = (self._tm_labels["replica"], self._tm_labels["role"])
        for k, v in st.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            reg.gauge(f"ff_serving_{k}",
                      f"ServingEngine stats()['{k}']",
                      labels=("replica", "role")).labels(*lab).set(v)
        reg.gauge("ff_serving_engine_info",
                  "engine identity (value is always 1)",
                  labels=("replica", "role", "kv_cache_dtype",
                          "weight_dtype", "impl")).labels(
            *lab, st["kv_cache_dtype"], st["weight_dtype"],
            st["paged_attention_impl"]).set(1)
        # rolling-deploy identity (ISSUE 17): the string-valued version
        # and deploy state ride a labeled info gauge (value always 1) —
        # the numeric loop above only exports numbers
        reg.gauge("ff_replica_weight_version",
                  "weight version + deploy state per replica "
                  "(value is always 1)",
                  labels=("replica", "role", "version", "state")).labels(
            *lab, st["weight_version"], st["deploy_state"]).set(1)
        # per-adapter speculation accept rate (ISSUE 14): one labeled
        # series per adapter that has seen speculative traffic
        if self._adapter_spec:
            fam = reg.gauge(
                "ff_serving_spec_accept_rate_by_adapter",
                "speculative accept rate, labeled by LoRA adapter",
                labels=("replica", "role", "adapter"))
            with self._lock:
                rows = {k: (v[0], v[1])
                        for k, v in self._adapter_spec.items()}
            for name, (prop, acc) in rows.items():
                fam.labels(*lab, name).set(
                    round(acc / max(1, prop), 4))

    # ---- flight recorder / SLO / HBM sources (ISSUE 15) ---------------------

    def _flightrec_source(self):
        """Post-mortem bundle payload: the full stats/health snapshot.
        Takes the engine lock — the recorder collects sources with a
        per-source timeout, so a wedged replica yields an error row in
        its own incident's bundle instead of hanging the write."""
        return (f"engine-{self._tm_labels['replica']}",
                {"stats": self.stats(), "health": self.health()})

    def _slo_source(self):
        """Lock-free counter reads for the ratio-floor SLOs (windowed
        prefix hit rate / speculative accept rate). Plain int attribute
        reads racing the tick by design — a monitoring window tolerates
        one tick of skew; a monitor stalled behind the tick does not."""
        pc = self.prefix_cache
        return (self._tm_labels["replica"], {
            "prefix_hits": pc.hits if pc else 0,
            "prefix_lookups": pc.lookups if pc else 0,
            "spec_accepted": self._spec_accepted,
            "spec_proposed": self._spec_proposed})

    def _hbm_source(self):
        """HBM ledger row: what this engine holds in device (and pinned
        host) memory, per subsystem — the per-pool resolution the
        memory-objective search consumes. Geometry is fixed for the
        engine's life, so these are cheap nbytes sums."""
        def _nbytes(tree):
            return sum(int(a.nbytes)
                       for a in jax.tree_util.tree_leaves(tree))

        subs = {"kv_pool": self._pool_bytes}
        pc = self.prefix_cache
        if pc is not None and pc.host_pages:
            page_bytes = self._pool_bytes / max(1, self.num_pages)
            subs["kv_host_tier"] = int(pc.host_used * page_bytes)
        if self.kv.draft_pool is not None:
            subs["kv_draft_pool"] = _nbytes(self.kv.draft_pool)
        if self.lora_pool is not None:
            subs["adapter_pool"] = _nbytes(self.lora_pool)
        if self.gen.quantize:
            # a quantized serving copy is a SEPARATE device allocation
            # (native-weight serving reads the model params, which the
            # model's own ledger row counts — never double-book)
            subs["serve_weights"] = _nbytes(self.gen._quantized_params())
        dg = self.draft_gen
        if dg is not None and dg.quantize:
            subs["draft_weights"] = _nbytes(dg._quantized_params())
        return (f"engine-{self._tm_labels['replica']}", subs)

    def _health_probe(self):
        """Lock-free /healthz row: never compiles, never blocks behind
        a mid-tick replica (the load() discipline)."""
        return {"kind": "engine",
                "replica": self._tm_labels["replica"],
                "role": self._tm_labels["role"],
                "status": "draining" if self._draining else "up",
                "weight_version": self.weight_version,
                "deploy_state": self.deploy_state,
                **self.load()}

    # ---- request lifecycle --------------------------------------------------

    def _bucket(self, prompt_len: int) -> int:
        if self.buckets:
            for b in self.buckets:
                if b >= prompt_len:
                    return b
            raise ValueError(
                f"prompt length {prompt_len} exceeds the largest decode "
                f"bucket {self.buckets[-1]}")
        return _pow2_bucket(prompt_len)

    def submit(self, prompt, max_new_tokens: int,
               deadline: Optional[float] = None,
               trace_id: Optional[str] = None,
               temperature: Optional[float] = None,
               top_p: Optional[float] = None,
               top_k: Optional[int] = None,
               seed: Optional[int] = None,
               adapter: Optional[str] = None) -> Request:
        """Queue one request. ``deadline`` is an absolute
        ``time.perf_counter()`` instant: a request still queued past it
        retires as ``"timeout"`` without ever prefilling (an admitted
        request is never cancelled — see Request.deadline).
        ``trace_id`` threads an existing fleet trace through this
        engine's spans (the router passes its request id, so a
        resubmitted or handed-off request keeps ONE span tree); None
        mints an engine-local id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens}: must be >= 1")
        bucket = self._bucket(prompt.size)
        if bucket + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"bucketed prompt ({bucket}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len {self.max_seq_len}")
        t, p, k = sampling_ops.validate_sampling(
            temperature if temperature is not None
            else self.default_temperature,
            top_p if top_p is not None else self.default_top_p,
            top_k if top_k is not None else self.default_top_k,
            "submit")
        if adapter is not None:
            if self.lora is None:
                raise ValueError(
                    f"adapter={adapter!r}: this engine has no adapter "
                    f"pool (build with adapter_pool_pages > 0 / "
                    f"--serve-adapter-pool-pages)")
            if adapter not in self.lora.registry:
                raise ValueError(
                    f"adapter {adapter!r} is not registered (known: "
                    f"{sorted(self.lora.registry)}) — register_adapter"
                    f" first")
        with self._lock:
            if self._draining:
                # the serving-side preemption notice: a draining engine is
                # on its way down (elastic restart / deploy) — callers
                # must route new traffic elsewhere, not queue behind a
                # shutdown
                raise RuntimeError(
                    "ServingEngine is draining: new requests are not "
                    "admitted (health()['status'] exposes this to the "
                    "router)")
            req = Request(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=int(max_new_tokens), bucket=bucket,
                          deadline=deadline, t_submit=time.perf_counter(),
                          temperature=t, top_p=p, top_k=k,
                          seed=(int(seed) if seed is not None
                                else (self._seed_base + self._next_rid)
                                & 0x7FFFFFFF),
                          adapter=adapter)
            req.trace_id = trace_id or (
                f"{self._tm_labels['replica']}-r{req.rid}")
            self._next_rid += 1
            self._submitted += 1
            if t > 0.0:
                self._sampled_requests += 1
            akey = adapter or "none"
            self._adapter_requests[akey] = \
                self._adapter_requests.get(akey, 0) + 1
            if self._tm_on:
                self._tm_adapter(adapter)[0].inc()
            self._queue.append(req)
        return req

    def pending(self) -> bool:
        with self._lock:
            return bool(self._queue) or bool(self.active.any()) \
                or bool(self._partial)

    def _retire(self, slot: int, state: str, error: str = ""):
        req = self.slot_req[slot]
        req.state = state
        req.error = error
        req.t_done = time.perf_counter()
        if state == "done":
            self._completed += 1
        elif state == "timeout":
            # a mid-prefill slot whose deadline expired before its last
            # chunk ran (ISSUE 18) — never decoded, same bucket as
            # queue-expiry
            self._timeouts += 1
        else:
            self._failed += 1
        # drop any partial-prefill state (mid-prefill abort: the chunked
        # caches are device arrays — releasing the reference frees them)
        self._partial.pop(slot, None)
        if req.ttft:
            self._ttfts.append(req.ttft)
        # close the cross-thread decode span (0-handle = telemetry off)
        telemetry.tracer().end(req.decode_span, state=state,
                               tokens=len(req.tokens),
                               **({"error": error} if error else {}))
        req.decode_span = 0
        # COW teardown: shared and published pages are decref'd (they
        # stay cached), only the private ones return to the free list
        self.kv.release(req.lease)
        # unpin the adapter page (it stays RESIDENT, warm for the
        # tenant's next request, until adapter-pool pressure evicts it)
        if req.adapter is not None and self.lora is not None:
            self.lora.release(req.adapter)
        req.slot = -1
        self.slot_req[slot] = None
        self.active[slot] = False
        self.poison[slot] = 0.0
        self.page_tables[slot, :] = 0   # scratch page: dead writes land there
        self._shared_plan = None
        self.kv.release_windows(slot)
        self.row_len[slot] = 0
        self.prompt_pad[slot] = 0
        self.emitted[slot] = 0
        # idle-slot sampling state back to the greedy defaults
        self.temps[slot] = 0.0
        self.top_ps[slot] = 1.0
        self.top_ks[slot] = 0
        self.seeds[slot] = 0
        self.lora_pages[slot] = 0

    def _seed_slot(self, slot: int, req: Request, poison):
        """_retire's inverse: the slot-resident state the fixed-shape
        programs read every dispatch (page table, lengths, sampling and
        adapter state). Until it is set a decode dispatch sees the slot
        as idle."""
        self.temps[slot] = req.temperature
        self.top_ps[slot] = req.top_p
        self.top_ks[slot] = req.top_k
        self.seeds[slot] = req.seed
        self.lora_pages[slot] = req.adapter_page
        self.poison[slot] = poison
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[:len(req.lease.pages)] = req.lease.pages
        self.page_tables[slot] = table
        self._shared_plan = None
        if req.lease.matched:
            self._shared_seen = True
        self.kv.seat_windows(slot, req.prompt.size)
        self.row_len[slot] = req.prompt.size
        self.prompt_pad[slot] = req.bucket
        self.emitted[slot] = 0

    def _record_token(self, slot: int, tok: int, ok: bool):
        """Append a sampled token to the slot's request and retire on
        non-finite logits, eos, or length — shared by prefill/decode."""
        req = self.slot_req[slot]
        if not ok:
            self._retire(slot, "failed", "non-finite logits")
            return
        req.tokens.append(int(tok))
        self._tokens_emitted += 1
        now = time.perf_counter()
        if not req.ttft:
            req.ttft = now - req.t_submit
            if self._tm_on:
                self._tm_ch["ttft"].observe(req.ttft)
                self._tm_adapter(req.adapter)[1].observe(req.ttft)
        elif self._tm_on:
            # host-observed inter-token latency: tokens inside one
            # decode_chunk dispatch arrive together, so sub-chunk gaps
            # read ~0 and the chunk boundary carries the dispatch time —
            # the histogram measures what a streaming caller would see
            self._tm_ch["itl"].observe(now - req.t_last_tok)
        req.t_last_tok = now
        self.emitted[slot] += 1
        self.last_tok[slot] = tok
        if (self.eos_id is not None and tok == self.eos_id) \
                or len(req.tokens) >= req.max_new_tokens:
            self._retire(slot, "done")

    def _span(self, name: str, **counts):
        """One phase of the tick as a live span on this engine's track:
        a ring event and, under a running profiler trace, ``ff.<name>``
        in its host plane (telemetry.Tracer.span). One per phase per
        tick, never one per token or slot; a span whose name ends in
        ``_fetch`` is the host BLOCKED on the device and holds nothing
        else."""
        if not self._tm_on:
            return telemetry.NULL_SPAN
        return telemetry.tracer().span(name, track=self._tm_track, **counts)

    # ---- compiled programs --------------------------------------------------

    def _compiled_call(self, key, build, *args):
        """Program-cache lookup; a miss builds + runs the program under a
        ``compile`` span and bumps recompile_count, logging whether jax's
        persistent compilation cache (placed by
        _env.resolve_compilation_cache or JAX_COMPILATION_CACHE_DIR)
        absorbed the compile: jax's own cache events, booked to the span
        (runtime/telemetry.py). Every shape-affecting datum is part of
        `key`, so this counter is exactly the number of XLA compiles the
        engine caused."""
        fn = self._programs.get(key)
        if profiler.tracing() and key in self._registered:
            # a traced slice's tables are read after the window
            profiler.note_traced(self._registered[key])
        if fn is not None:
            # armed sentinel: bracket the dispatch with the jitted
            # callable's trace-cache size — growth means a WARM
            # program silently retraced (the PR-3/7/10/11 bug class)
            return self._retrace.call(key, fn, args)
        self._retrace.note_miss(key, args)
        fn = self._programs[key] = build()
        self.recompile_count += 1
        # abstract arguments only, taken before the call donates them:
        # nothing is lowered until profiler.program_scopes() asks
        self._registered[key] = profiler.register_program(
            program_name(key), fn, args, self._graph_ops)
        t0 = time.perf_counter()
        with self._span("compile", key=str(key),
                        program=program_name(key)) as span:
            out = fn(*args)
            with self._span("compile_fetch"):
                jax.block_until_ready(out)
            # what jax said of its persistent cache while this span was
            # the innermost open (telemetry.JAX_COUNTS; nothing is booked
            # under telemetry="off")
            counts = getattr(span, "args", {})
            asked = counts.get("cache_requests", 0)
            missed = asked - counts.get("cache_hits", 0)
            cache = "unobserved" if not asked else "miss" if missed \
                else "hit"
            span.annotate(cache=cache)
        fflogger.info(
            "serving: compiled %r in %.2fs — persistent cache %s", key,
            time.perf_counter() - t0,
            f"MISS ({missed} of {asked} programs compiled)" if missed
            else cache.upper())
        return out

    @staticmethod
    def _seed_prefix_caches(gen, bucket: int, p0: int, pool, prefix_pages):
        """Gather ``p0`` positions of cached prefix KV READ-ONLY into
        the front of a fresh contiguous per-request cache for every
        attention op — the shared half of every hit prefill. Quantized
        pools dequantize in the gather (op.gather_paged_kv), so the
        borrower attends exactly the lossy values the donor's decode
        sees. Target and draft builders use this one helper so the two
        pools (which share page ids) can never drift apart."""
        cdtype = gen._compute_dtype()
        caches = {}
        for op in gen.attn_ops:
            if op_keeps(op) is not None:
                continue    # a window layer resumes from its snapshot
            with jax.named_scope(op.name), jax.named_scope("gather"):
                c = op.init_cache(1, bucket, cdtype)
                g = op.gather_paged_kv(pool[op.name], prefix_pages)
                caches[op.name] = {
                    name: c[name].at[:, :p0].set(
                        g[name].astype(c[name].dtype))
                    for name in c}
        return caches

    def _scatter_tail(self, gen, pool, caches, pages, p0: int = 0,
                      slot=None, length=None, rings=None):
        """COW scatter: write the contiguous cache's positions past
        ``p0`` into ``pages`` — the request's own fresh pages, never the
        shared ones. ``p0=0`` is the cold (whole-bucket) case. Routed
        through the engine's resolved prefill impl: 'einsum' is the
        big-scatter oracle, 'pallas' the page-at-a-time VMEM kernel
        (ISSUE 18); both are bitwise-identical so the choice is purely
        a perf knob — resolution happens at TRACE time inside the
        prefill builders, warm programs pay nothing."""
        out = {}
        for op in gen.attn_ops:
            # the op's own scope (runtime/profiler.py scope_table): the
            # write is its attention's work, as in the walk
            with jax.named_scope(op.name):
                keep = op_keeps(op)
                if keep is not None:
                    # a window layer: the pages that hold the prompt's
                    # last window, into the slot's ring; the rest of its
                    # rows travelled in the program and end with it
                    out[op.name] = op.scatter_window_tail(
                        pool[op.name], caches[op.name], length, rings[keep],
                        impl=self.paged_attention_impl)
                    continue
                out[op.name] = op.scatter_cache_tail(
                    pool[op.name], caches[op.name], p0, pages,
                    impl=self.paged_attention_impl)
        for op in gen.state_ops:
            # the prefilled state takes the request's slot in the pool
            with jax.named_scope(op.name), jax.named_scope("seat"):
                state, at = caches[op.name], slot[0]
                if self.kv.snapshots:
                    # a publisher (`prefill_into_cache`) holds no slot and
                    # says -1: row 0 keeps what it holds
                    at = jnp.maximum(slot[0], 0)
                    state = {k: jnp.where(
                        slot[0] >= 0, state[k],
                        pool[op.name][k][at][None].astype(state[k].dtype))
                        for k in pool[op.name]}
                out[op.name] = op.seat_state(pool[op.name], state, at)
        return out

    def _take_snapshot(self, gen, snaps, caches, snap, length):
        """Write the state every recurrent op ends a prefill with into row
        ``snap`` of the snapshot arrays (row 0, the scratch row, where the
        prefill publishes nothing), and of every window layer the pages of
        the window that ends at the prompt's ``length`` (a snapshot is taken
        where that is a page boundary: the window before a match point)."""
        out = {}
        for op in gen.state_ops:
            with jax.named_scope(op.name), jax.named_scope("seat"):
                out[op.name] = op.seat_state(snaps[op.name],
                                             caches[op.name], snap)
        for op in gen.attn_ops:
            if op_keeps(op) is None:
                continue
            with jax.named_scope(op.name):
                out[op.name] = op.take_window_snapshot(
                    snaps[op.name], caches[op.name], length, snap,
                    impl=self.paged_attention_impl)
        return out

    def _seed_window_caches(self, gen, bucket, p0, snaps, snap):
        """The contiguous caches of a hit prefill's WINDOW layers: zeros
        but for the pages of the window before the match point ``p0``,
        read from row ``snap`` of the snapshot arrays, READ-ONLY (the tail
        is written behind them, and the seat copies them into the slot's
        ring: copy-on-write)."""
        cdtype = gen._compute_dtype()
        caches = {}
        for op in gen.attn_ops:
            if op_keeps(op) is None:
                continue
            with jax.named_scope(op.name), jax.named_scope("gather"):
                caches[op.name] = op.seed_window_cache(
                    op.init_cache(1, bucket, cdtype), snaps[op.name], snap,
                    p0)
        return caches

    @staticmethod
    def _seed_state_caches(gen, snaps, snap, dtype):
        """The state caches of a hit prefill: every recurrent op's state
        read from row ``snap`` of the snapshot arrays, READ-ONLY (the
        copy-on-write rule: the tail advances a copy)."""
        caches = {}
        for op in gen.state_ops:
            with jax.named_scope(op.name), jax.named_scope("seat"):
                st = op.init_state(1, dtype)
                caches[op.name] = {
                    **st, **{k: snaps[op.name][k][snap][None].astype(
                        st[k].dtype) for k in snaps[op.name]}}
        return caches

    @staticmethod
    @jax.named_scope("sampler")
    def _first_token(logits, poison, temps, top_ps, top_ks, seeds):
        """(tok, ok) of a prefill program's last position: the request's
        first emitted token is TARGET-stream draw 0. The poison and the
        finite check belong to the sampler's scope."""
        logits = logits[:, -1] + poison                # (1, V)
        ok = jnp.isfinite(logits).all(axis=-1)
        return sampling_ops.sample_tokens(
            logits, temps, top_ps, top_ks, seeds, jnp.zeros_like(seeds)), ok

    @staticmethod
    def _routing_sum(routing, expert_rows=(), static_rows=None):
        """The extra trailing output of a serve program whose model has
        dropless MoE ops: their int32 (2,) routing counts [assignments,
        experts hit] (and [identity picks, real picks] of a model with
        zero-computation experts) summed over the ops one walk ran.
        Nothing for any other model, whose programs are unchanged.
        A prefill also collects `expert_rows`: what is counted on the
        device (a held share's passes) rides as a third entry, what is
        static goes to `static_rows`, the program's own list, so the
        program of a model that holds every expert is the one it was."""
        if not routing:
            return ()
        # an op with zero-computation experts counts two picks more
        width = max(r.shape[0] for r in routing)
        counts = sum(r if r.shape[0] == width
                     else jnp.pad(r, (0, width - r.shape[0]))
                     for r in routing)
        static = [r for r in expert_rows if isinstance(r, int)]
        if static_rows is not None:
            static_rows[:] = static     # the same again if traced again
        if len(static) < len(expert_rows):
            counted = sum(r for r in expert_rows if not isinstance(r, int))
            counts = jnp.concatenate(
                [counts, jnp.reshape(counted, (1,)).astype(counts.dtype)])
        return (counts,)

    def _moe_took_list(self, key):
        """The list in which program `key` collects the lowering of each
        of its dropless MoE calls as it is traced (its builder hands it to
        the walks); None for a model without such an op."""
        if not self.gen.dropless_moe_ops:
            return None
        took = self._moe_took[key] = []
        return took

    def _moe_rows_list(self, key):
        """The list in which prefill program `key` collects the static
        `expert_rows` of its MoE calls as it is traced; None for a model
        without such an op."""
        if not self.gen.dropless_moe_ops:
            return None
        rows = self._moe_static_rows[key] = []
        return rows

    def _note_moe_lowering(self, key, span):
        """A dispatch of an expert model's program `key` says on its span
        whether the program's MoE calls all took the expert-stream kernel
        (`moe_streamed` 1 or 0) and counts it; nothing for a program that
        holds no dropless MoE op. Called after the dispatch: a program's
        list fills as its first call traces it."""
        took = self._moe_took.get(key)
        if took:
            streamed = int(all(t == "streamed" for t in took))
            self._moe_streamed_dispatches += streamed
            span.annotate(moe_streamed=streamed)

    def _build_prefill(self, bucket: int, n_pages: int, took=None,
                       static_rows=None):
        gen = self.gen
        cdtype = gen._compute_dtype()
        has_lora = self.lora_pool is not None

        def prefill(params, state, tokens, length, pool, pages, poison,
                    temps, top_ps, top_ks, seeds, lora_pool, lora_pages,
                    *seat):
            # `seat` (`_seat_args`): for a model with recurrent-state ops
            # the pool row its prefilled state is seated in, then for one
            # with window layers the slot's ring tables; under a prefix
            # cache the state ops' snapshot arrays and the row to write
            # come last
            slot = seat[:1] if gen.state_ops else ()
            snaps, snap = seat[-2:] if self.kv.snapshots else (None, None)
            rings = seat[len(slot)] if self.kv.window_groups else None
            caches = gen.init_caches(1, bucket, cdtype)
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            routing = [] if gen.dropless_moe_ops else None
            rows = []
            logits, caches = gen._prefill(params, state, tokens, caches,
                                          length, self.prefill_chunk,
                                          lora=lora, routing=routing,
                                          lowerings=took, expert_rows=rows,
                                          loop=self.prefill_chunk_loop)
            tok, ok = self._first_token(logits, poison, temps, top_ps,
                                        top_ks, seeds)
            taken = (() if snaps is None else
                     (self._take_snapshot(gen, snaps, caches, snap, length),))
            return (tok, ok, self._scatter_tail(gen, pool, caches, pages,
                                                slot=slot, length=length,
                                                rings=rings),
                    *self._routing_sum(routing, rows, static_rows), *taken)

        # the snapshot arrays come last but one: donated where present
        return jax.jit(prefill, donate_argnums=(
            (4, 13 + len(self._seat_args(0))) if self.kv.snapshots
            else (4,)))

    def _build_prefill_hit(self, bucket: int, full: int, took=None,
                           static_rows=None):
        """Prefix-hit prefill: ``full`` cached pages are gathered
        READ-ONLY into the front of a contiguous per-request cache, the
        tail slab [full*ps, bucket) runs as one chunk_forward pass (each
        tail position attends the gathered prefix + the tail's own causal
        window — bitwise the whole-prompt einsum, runtime/generation.py),
        a gather-last query scores the prompt's true last position, and
        ONLY the tail k/v scatters out — into the request's fresh pages,
        never the shared ones (the copy-on-write rule; the matched
        prefix's partial last page is re-materialized here too)."""
        gen = self.gen
        p0 = full * self.page_size
        has_lora = self.lora_pool is not None

        def prefill(params, state, tokens_tail, tok_last, length, pool,
                    prefix_pages, tail_pages, poison,
                    temps, top_ps, top_ks, seeds, lora_pool, lora_pages,
                    *resume):
            # `resume`, for an engine that holds snapshots (a model with
            # recurrent-state ops or window layers): what `_seat_args`
            # gives (the pool row the request is seated in, the slot's ring
            # tables), then the snapshot arrays, the row the tail resumes
            # FROM and the row it writes (0 = scratch)
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            caches = self._seed_prefix_caches(gen, bucket, p0, pool,
                                              prefix_pages)
            slot = rings = None
            if resume:
                *seat, snaps, snap_from, snap = resume
                slot = seat[0] if gen.state_ops else None
                rings = seat[-1] if self.kv.window_groups else None
                caches.update(self._seed_state_caches(
                    gen, snaps, snap_from, gen._compute_dtype()))
                caches.update(self._seed_window_caches(
                    gen, bucket, p0, snaps, snap_from))
            routing = [] if gen.dropless_moe_ops else None
            rows = []
            # row_lengths on the tail walk: its attention does not read
            # it, a dropless MoE masks the padding rows by it
            _, caches = gen._walk(params, state, tokens_tail, caches,
                                  None, chunk_start=p0, skip_tail=True,
                                  lora=lora, row_lengths=length,
                                  routing=routing, lowerings=took,
                                  expert_rows=rows)
            logits, caches = gen._walk(params, state, tok_last, caches,
                                       None, last_only=True,
                                       row_lengths=length,
                                       gather_last=True, lora=lora,
                                       routing=routing, lowerings=took,
                                       expert_rows=rows)
            tok, ok = self._first_token(logits, poison, temps, top_ps,
                                        top_ks, seeds)
            taken = (() if not resume else
                     (self._take_snapshot(gen, snaps, caches, snap, length),))
            return (tok, ok, self._scatter_tail(
                        gen, pool, caches, tail_pages, p0,
                        slot=None if slot is None else (slot,),
                        length=length, rings=rings),
                    *self._routing_sum(routing, rows, static_rows), *taken)

        return jax.jit(prefill, donate_argnums=(
            (5, 15 + len(self._seat_args(0))) if self.kv.snapshots
            else (5,)))

    def _build_draft_prefill(self, bucket: int, n_pages: int):
        """Cold draft prefill: fill the draft pool's pages for the whole
        bucket. Cache-only (skip_tail) — the draft's first proposal is
        sampled by the draft-decode scan, so its prefill logits are
        never needed."""
        gen = self.draft_gen
        cdtype = gen._compute_dtype()

        def prefill(params, state, tokens, pool, pages):
            caches = gen.init_caches(1, bucket, cdtype)
            _, caches = gen._walk(params, state, tokens, caches, None,
                                  skip_tail=True)
            return self._scatter_tail(gen, pool, caches, pages)

        return jax.jit(prefill, donate_argnums=(3,))

    def _build_draft_prefill_hit(self, bucket: int, full: int):
        """Prefix-hit draft prefill: same gather + tail-chunk + COW
        scatter as the target's hit program (the shared helpers), minus
        the logits tail."""
        gen = self.draft_gen
        p0 = full * self.page_size

        def prefill(params, state, tokens_tail, pool, prefix_pages,
                    tail_pages):
            caches = self._seed_prefix_caches(gen, bucket, p0, pool,
                                              prefix_pages)
            _, caches = gen._walk(params, state, tokens_tail, caches,
                                  None, chunk_start=p0, skip_tail=True)
            return self._scatter_tail(gen, pool, caches, tail_pages, p0)

        return jax.jit(prefill, donate_argnums=(3,))

    # ---- chunk-interleaved admission programs (ISSUE 18) ------------------

    def _build_prefill_ichunk(self, bucket: int, st: int):
        """ONE schedulable prefill chunk of a cold bucket-shaped prompt:
        positions [st, st+prefill_chunk) write their k/v into the
        contiguous per-request cache, cache-only (skip_tail) — exactly
        iteration ``st`` of Generator._prefill's ragged chunked loop, so
        the chunk sequence is bitwise the run-to-completion prefill. The
        FULL padded (1, bucket) prompt is the input and the chunk slice
        is static, so every chunk of a bucket shares one argument
        signature; st=0 creates the caches, later chunks take + donate
        them (the cursor state the scheduler carries between ticks).
        These programs take no prompt length, so a dropless MoE sees no
        live-row mask here: a chunk's padding rows route to experts
        (outputs unchanged, rows are independent) and the routing is not
        counted; the draft prefill programs likewise."""
        gen = self.gen
        cdtype = gen._compute_dtype()
        has_lora = self.lora_pool is not None
        chunk = self.prefill_chunk

        # `length`: one more argument of a model with recurrent-state ops,
        # whose state the prompt's padding rows must not reach
        if st == 0:
            def prefill_chunk0(params, state, tokens, lora_pool,
                               lora_pages, *length):
                caches = gen.init_caches(1, bucket, cdtype)
                lora = ({"pool": lora_pool, "pages": lora_pages}
                        if has_lora else None)
                _, caches = gen._walk(
                    params, state, tokens[:, :chunk], caches, None,
                    chunk_start=0, skip_tail=True, lora=lora,
                    row_lengths=length[0] if length else None)
                return caches

            return jax.jit(prefill_chunk0)

        def prefill_chunk(params, state, tokens, caches, lora_pool,
                          lora_pages, *length):
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            _, caches = gen._walk(
                params, state, tokens[:, st:st + chunk], caches, None,
                chunk_start=st, skip_tail=True, lora=lora,
                row_lengths=length[0] if length else None)
            return caches

        return jax.jit(prefill_chunk, donate_argnums=(3,))

    def _build_prefill_ifinal(self, bucket: int, n_pages: int):
        """The last quantum of an interleaved prefill: the ragged
        gather-last pass over the filled chunk caches (the prompt's true
        last position scores the first emitted token), then the COW
        scatter of the whole bucket's k/v into the request's pages —
        Generator._prefill's final _walk plus _build_prefill's sampling
        tail, so (tok, ok, pool) match run-to-completion admission
        bitwise."""
        gen = self.gen
        has_lora = self.lora_pool is not None

        def prefill_final(params, state, tokens, length, caches, pool,
                          pages, poison, temps, top_ps, top_ks, seeds,
                          lora_pool, lora_pages, *slot):
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            tok_last = jnp.take_along_axis(
                tokens, (length - 1)[:, None], axis=1)       # (1, 1)
            logits, caches = gen._walk(params, state, tok_last, caches,
                                       None, last_only=True,
                                       row_lengths=length,
                                       gather_last=True, lora=lora)
            tok, ok = self._first_token(logits, poison, temps, top_ps,
                                        top_ks, seeds)
            return tok, ok, self._scatter_tail(gen, pool, caches, pages,
                                               slot=slot)

        # donate the pool only: the chunk caches feed the scatter but
        # back no output (tok/ok are tiny, pool aliases the pool input),
        # so donating them just trips jax's unusable-donation warning
        return jax.jit(prefill_final, donate_argnums=(5,))

    def _build_verify(self, k: int):
        """Speculative verify: ONE dispatch scores all K+1 candidate
        positions per slot — the slab [last_tok, d_1..d_K] flows through
        the target graph with paged_verify_forward writing each
        position's k/v at its own (host-clamped) slot and attending at
        its own frontier. Returns the target's greedy argmax at every
        position, the per-slot WARPED sampling distribution at every
        position (the rejection-sampling ``p`` — one-hot at argmax for
        greedy slots), and per-position finiteness. Acceptance stays
        host-side: greedy slots compare proposals to argmax, sampled
        slots run the accept/resample rule (_spec_step)."""
        gen = self.gen
        has_lora = self.lora_pool is not None

        def decode_verify(params, state, pool, page_table, slab,
                          write_pos, rope_pos0, row_len, prompt_pad, poison,
                          temps, top_ps, top_ks, lora_pool, lora_pages):
            paged = {"page_table": page_table, "write_pos": write_pos,
                     "rope_pos": rope_pos0, "row_len": row_len,
                     "prompt_pad": prompt_pad,
                     "impl": self.paged_attention_impl}
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            logits, pool = gen._walk(params, state, slab, pool, None,
                                     paged=paged, lora=lora)
            with jax.named_scope("sampler"):
                logits = logits.astype(jnp.float32) \
                    + poison[:, None, None]            # (B, K+1, V)
                ok = jnp.isfinite(logits).all(axis=-1)     # (B, K+1)
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                b, s, v = logits.shape
                probs = sampling_ops.sampling_probs(
                    logits.reshape(b * s, v),
                    jnp.repeat(temps, s), jnp.repeat(top_ps, s),
                    jnp.repeat(top_ks, s)).reshape(b, s, v)
            return toks, probs, ok, pool

        return jax.jit(decode_verify, donate_argnums=(2,))

    def _build_decode(self, n_steps: int, took=None, shared: bool = False):
        gen = self.gen
        has_lora = self.lora_pool is not None

        def decode(params, state, pool, page_table, last_tok, write_pos0,
                   rope_pos0, row_len, prompt_pad, budget, poison,
                   temps, top_ps, top_ks, seeds, ctr0,
                   lora_pool, lora_pages, *more):
            """`n_steps` slot-decode steps as ONE in-graph scan. `more`:
            of the program of an engine with prefix-hit slots (`shared`),
            the two arrays of the groups of slots whose rows begin with the
            same pages (`_shared_pages_plan`), fixed like the table over
            the dispatch's steps; then, of a model with window layers, its
            groups' ring tables as they will stand at the dispatch's last
            step. Past a
            slot's own budget (prompt_pad + its max_new_tokens) the write
            position and RoPE clamp to the final allocated slot — those
            steps only produce tokens the host truncates, and the
            repeated overwrite stays inside the slot's own pages. Step i
            samples TARGET-stream draw ctr0 + i per slot (counter-based:
            no engine key state) and applies each slot's own
            temperature/top-p/top-k — temperature-0 slots take argmax,
            bitwise the greedy program this replaced."""
            rope_cap = budget - prompt_pad + row_len - 1
            lora = ({"pool": lora_pool, "pages": lora_pages}
                    if has_lora else None)
            groups, rings = (more[:2], more[2:]) if shared else ((), more)

            def body(carry, i):
                pool, tok = carry
                paged = {
                    "page_table": page_table,
                    "write_pos": jnp.minimum(write_pos0 + i, budget - 1),
                    "rope_pos": jnp.minimum(rope_pos0 + i, rope_cap),
                    "row_len": row_len, "prompt_pad": prompt_pad,
                    "impl": self.paged_attention_impl}
                if groups:
                    paged["shared"] = groups
                if rings:
                    paged["window_tables"] = rings[0]
                routing = [] if gen.dropless_moe_ops else None
                logits, pool = gen._walk(params, state, tok[:, None],
                                         pool, None, paged=paged,
                                         lora=lora, routing=routing,
                                         lowerings=took)
                with jax.named_scope("sampler"):
                    logits = logits[:, 0] + poison[:, None]  # (B_slots, V)
                    ok = jnp.isfinite(logits).all(axis=-1)
                    nxt = sampling_ops.sample_tokens(
                        logits, temps, top_ps, top_ks, seeds, ctr0 + i)
                return (pool, nxt), (nxt, ok, *self._routing_sum(routing))

            (pool, _), (toks, oks, *routed) = jax.lax.scan(
                body, (pool, last_tok),
                jnp.arange(n_steps, dtype=jnp.int32))
            # (n_steps, B_slots) tokens; an expert model adds its (2,)
            # routing counts summed over the dispatch's steps and layers
            return toks, oks, pool, *(r.sum(axis=0) for r in routed)

        return jax.jit(decode, donate_argnums=(2,))

    def _build_draft_propose(self, n_steps: int):
        """Speculative draft proposals: the draft's own K-step paged
        decode scan, sampling each proposal from the DRAFT stream under
        the REQUEST's sampling config (greedy slots propose argmax —
        the pre-sampling draft decode bitwise), and returning the
        draft's per-step sampling distribution ``q`` — the denominator
        of the host accept rule and the subtrahend of the residual
        resample."""
        gen = self.draft_gen

        def decode_propose(params, state, pool, page_table, last_tok,
                           write_pos0, rope_pos0, row_len, prompt_pad,
                           budget, temps, top_ps, top_ks, seeds, ctr0):
            rope_cap = budget - prompt_pad + row_len - 1

            def body(carry, i):
                pool, tok = carry
                paged = {
                    "page_table": page_table,
                    "write_pos": jnp.minimum(write_pos0 + i, budget - 1),
                    "rope_pos": jnp.minimum(rope_pos0 + i, rope_cap),
                    "row_len": row_len, "prompt_pad": prompt_pad,
                    "impl": self.paged_attention_impl}
                logits, pool = gen._walk(params, state, tok[:, None],
                                         pool, None, paged=paged)
                with jax.named_scope("sampler"):
                    logits = logits[:, 0].astype(jnp.float32)  # (B, V)
                    nxt = sampling_ops.sample_tokens(
                        logits, temps, top_ps, top_ks, seeds, ctr0 + i,
                        tag=sampling_ops.TAG_DRAFT)
                    probs = sampling_ops.sampling_probs(
                        logits, temps, top_ps, top_ks)
                return (pool, nxt), (nxt, probs)

            (pool, _), (toks, probs) = jax.lax.scan(
                body, (pool, last_tok),
                jnp.arange(n_steps, dtype=jnp.int32))
            return toks, probs, pool        # (k, B), (k, B, V)

        return jax.jit(decode_propose, donate_argnums=(2,))

    def _split_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # ---- per-request sampling / adapter plumbing (ISSUE 14) ---------------

    def _sampling_args_1(self, req: Request):
        """(1,)-shaped sampling-state arrays for the prefill programs."""
        return (np.asarray([req.temperature], np.float32),
                np.asarray([req.top_p], np.float32),
                np.asarray([req.top_k], np.int32),
                np.asarray([req.seed], np.int32))

    def _lora_args_1(self, adapter_page: int):
        """(lora_pool, (1,) page) prefill args; (None, None) — empty
        pytrees to jit — when the engine has no adapter pool."""
        if self.lora_pool is None:
            return (None, None)
        return (self.lora_pool, np.asarray([adapter_page], np.int32))

    def _lora_args_slots(self):
        if self.lora_pool is None:
            return (None, None)
        return (self.lora_pool, self.lora_pages)

    def register_adapter(self, name: str, weights: Dict,
                         alpha: Optional[float] = None) -> None:
        """Register a LoRA adapter for multi-tenant serving: host-RAM
        weights ({Linear op name -> {"a": (in, rank), "b": (rank,
        out)}}, ops omitted get a zero delta; scale = alpha / rank,
        alpha defaults to rank). Registration is host-only — the
        adapter faults into a device pool page on its first
        ``submit(adapter=name)`` and stays resident (LRU at refcount 0)
        until pool pressure evicts it. Re-registering REPLACES the
        adapter (rejected while live slots are pinned to it): the old
        device copy is dropped and the adapter's prefix-cache namespace
        is flushed — KV computed under the old weights must never serve
        a hit for the new ones."""
        if self.lora is None:
            raise RuntimeError(
                "this engine has no adapter pool: build with "
                "adapter_pool_pages > 0 (--serve-adapter-pool-pages)")
        with self._lock:
            replacing = name in self.lora.registry
            self.lora.register(name, weights, alpha)
            if replacing:
                self.kv.flush_namespace(name)

    def _write_adapter_page(self, page: int, payload: Dict, scale: float):
        """Fault an adapter into pool ``page`` through the ONE
        fixed-shape writer program (``page`` is traced data, so tenant
        churn never compiles; the null-page write at construction
        compiles it once)."""
        buf = {}
        for op in self._lora_targets:
            sub = payload.get(op.name)
            if sub is None:
                sub = self._zero_payload[op.name]
            buf[op.name] = {"a": np.asarray(sub["a"], np.float32),
                            "b": np.asarray(sub["b"], np.float32)}
        lora_ops = self._lora_ops

        def build():
            def adapter_page_write(pool, page, payload, scale):
                return lora_ops.write_adapter_page(pool, page, payload,
                                                   scale)

            return jax.jit(adapter_page_write, donate_argnums=(0,))

        self.lora_pool = self._compiled_call(
            ("adapter_write",), build, self.lora_pool,
            np.int32(page), buf, np.float32(scale))

    # ---- the scheduler loop -------------------------------------------------

    def _expire_queued(self):
        """Retire queued requests whose deadline has passed as "timeout"
        — they never prefill, hold no pages and cost no dispatch (the
        per-request-deadline half of the fleet-router contract: expiring
        work is dropped at the cheapest possible point)."""
        now = time.perf_counter()
        kept: List[Request] = []
        for req in self._queue:
            if req.deadline is not None and now >= req.deadline:
                req.state = "timeout"
                req.error = "deadline expired while queued"
                req.t_done = now
                self._timeouts += 1
                if self._tm_on:
                    telemetry.tracer().instant(
                        "timeout", trace_id=req.trace_id,
                        track=self._tm_track, where="engine_queue")
            else:
                kept.append(req)
        self._queue = kept

    def _cache_ns(self, adapter):
        """The trie namespace this engine files prefixes under: the
        adapter salt (ISSUE 14) plus this engine's weight-version salt
        (ISSUE 17, rolling deploy) — ``version_ns`` keeps the default
        version bit-identical to the bare adapter key."""
        return version_ns(self.weight_version, adapter)

    def _refuse_state_pages(self, what: str, with_snapshot: bool = False):
        """Page slabs carry per-token rows only: for a model whose ops keep
        a recurrent state they would move a prefix without the snapshot
        that goes with it (`with_snapshot`: `prefill_into_cache` publishes
        pages AND snapshot through the normal programs, and is refused for
        a window layer's ring alone)."""
        if self.gen.state_ops and not with_snapshot:
            raise NotImplementedError(
                f"{what}: {self.gen.state_ops[0].name} keeps a recurrent "
                "state, which no page slab carries (export, import and "
                "evacuation move pages of per-token rows only)")
        if self.kv.window_groups and not with_snapshot:
            raise NotImplementedError(
                f"{what}: a window layer's rows live in a ring of pages a "
                "slot, which no page slab carries (export, import and "
                "evacuation move the global table's pages only)")

    def _state_slot_args(self, slot):
        """The trailing `slot` argument of the prefill programs of a model
        with recurrent-state ops; nothing for any other model."""
        if not self.gen.state_ops:
            return ()
        return (np.int32(slot),)

    def _seat_args(self, slot):
        """What a cold prefill program takes to seat a request beyond the
        global table's pages: the state's pool row, then the window
        groups' ring tables of the slot; nothing for most models."""
        if not self.kv.window_groups:
            return self._state_slot_args(slot)
        rings = self.kv.window_tables(max(slot, 0))
        if slot < 0:
            # a publisher (`prefill_into_cache`) holds no slot: its window
            # layers' tail lands in the scratch page
            rings = {w: np.zeros_like(t) for w, t in rings.items()}
        return (*self._state_slot_args(slot), rings)

    def _snapshot_args(self, lease, hit: bool):
        """What a prefill program of an engine that holds snapshots takes
        last: the arrays (donated), for a hit the row it resumes from,
        and the row it writes; nothing for any other engine."""
        if not self.kv.snapshots:
            return ()
        return (self.kv.snapshots,
                *((np.int32(lease.snap_from),) if hit else ()),
                np.int32(lease.snap))

    def slot_state(self, slot: int) -> dict:
        """Host copies of one slot's recurrent state, {op name: the op's
        state arrays for that slot, as its equations write them
        (`logical_state`: the pool's own layout is the op's business)};
        empty for a model without such ops. For checks that hold what
        prefill seated and decode advanced to a reference: the state covers
        the prompt and every emitted token but the last (which no step has
        read yet)."""
        with self._lock:
            return {op.name: op.logical_state(jax.device_get(
                jax.tree_util.tree_map(lambda a: a[slot],
                                       self.kv.pool[op.name])))
                for op in self.gen.state_ops}

    def _run_prefill(self, prompt, bucket: int, lease, sampling,
                     adapter_page: int, poison, span=telemetry.NULL_SPAN,
                     slot: int = 0):
        """Dispatch one run-to-completion prefill of ``prompt`` into
        ``lease``'s pages, target then draft; returns the device values
        ``(tok, ok, routed)``, the program's static ``expert_rows``, and
        notes the target program's MoE lowering on ``span``. A prefix hit
        gathers the matched pages
        read-only and prefills only the tail slab [full*ps, bucket) into
        FRESH pages — the matched prefix's partial last page (tokens
        past full*ps) is re-materialized into the lease's own first tail
        page, never written in the donor's (the COW rule). One program
        per (bucket, full): bounded like the buckets themselves, flat
        after warmup. The ONE frame admission adds between ``step()``
        and a prefill program's first call (see _admit)."""
        kv = self.kv
        full = len(lease.matched)
        n_prefill = math.ceil(bucket / self.page_size)
        prefix_pages = np.asarray(lease.pages[:full], np.int32)
        tail_pages = np.asarray(lease.pages[full:n_prefill], np.int32)
        length = np.asarray([prompt.size], np.int32)
        p0 = full * self.page_size
        padded = np.full((1, bucket - p0), self.pad_id, np.int32)
        padded[0, :prompt.size - p0] = prompt[p0:]
        if full:
            key = ("prefill_hit", bucket, full)
            tok, ok, kv.pool, *routed = self._compiled_call(
                key, lambda: self._build_prefill_hit(
                    bucket, full, self._moe_took_list(key),
                    self._moe_rows_list(key)),
                self.gen._params(), self.model.bn_state, padded,
                np.asarray([[prompt[-1]]], np.int32), length, kv.pool,
                prefix_pages, tail_pages, poison, *sampling,
                *self._lora_args_1(adapter_page),
                *(self._seat_args(slot) if kv.snapshots else ()),
                *self._snapshot_args(lease, True))
        else:
            if self.prefill_chunk_loop and self.buckets:
                # the chunk loop runs only the chunks that hold live rows,
                # so ONE program, the largest pinned bucket's, serves every
                # bucket: the rows behind the prompt's own bucket are never
                # walked and the pages behind its own are the scratch page
                bucket = self.buckets[-1]
                n_prefill = math.ceil(bucket / self.page_size)
                padded = np.concatenate([padded, np.full(
                    (1, bucket - padded.shape[1]), self.pad_id, np.int32)],
                    axis=1)
                tail_pages = np.concatenate([tail_pages, np.zeros(
                    n_prefill - tail_pages.size, np.int32)])
            key = ("prefill", bucket, n_prefill, self.prefill_chunk)
            tok, ok, kv.pool, *routed = self._compiled_call(
                key, lambda: self._build_prefill(
                    bucket, n_prefill, self._moe_took_list(key),
                    self._moe_rows_list(key)),
                self.gen._params(), self.model.bn_state, padded, length,
                kv.pool, tail_pages, poison, *sampling,
                *self._lora_args_1(adapter_page),
                *self._seat_args(slot), *self._snapshot_args(lease, False))
        if kv.snapshots:
            # the arrays come back last, behind the routing counts
            *routed, kv.snapshots = routed
            span.annotate(snapshot=int(bool(full)),
                          snapshot_bytes=self._snapshot_bytes
                          * (bool(full) + bool(lease.snap)))
        span.annotate(program=program_name(key))
        self._note_moe_lowering(key, span)
        if self.draft_gen is not None:
            # the draft model's prefix KV rides the same page ids, so its
            # prefill mirrors the target's hit/cold split exactly
            if full:
                kv.draft_pool = self._compiled_call(
                    ("draft_prefill_hit", bucket, full),
                    lambda: self._build_draft_prefill_hit(bucket, full),
                    self.draft_gen._params(), self.draft_model.bn_state,
                    padded, kv.draft_pool, prefix_pages, tail_pages)
            else:
                kv.draft_pool = self._compiled_call(
                    ("draft_prefill", bucket, n_prefill),
                    lambda: self._build_draft_prefill(bucket, n_prefill),
                    self.draft_gen._params(), self.draft_model.bn_state,
                    padded, kv.draft_pool, tail_pages)
        return tok, ok, routed, sum(self._moe_static_rows.get(key, ()))

    def _scan_rows(self, bucket: int) -> Dict:
        """`scan_rows` of a prefill span: the rows the recurrent ops' chunked
        scans walk (the bucket, padding included, times those ops); nothing
        for a model without them."""
        if not self.gen.state_ops:
            return {}
        return {"scan_rows": bucket * len(self.gen.state_ops)}

    def _admit(self) -> int:
        """Move queued requests into free slots: look up the longest
        cached prompt prefix, allocate fresh pages for everything past it
        (copy-on-write — shared pages are never written), prefill the
        tail (bucket-shaped program) and seed the slot; one ``prefill``
        span per admitted request, from the bucket program's dispatch to
        its first token. Returns the number admitted. The span stays a
        ``with`` block here and the dispatch (_run_prefill, shared with
        prefill_into_cache) is the only call in between: every Python
        frame between ``step()`` and a program's first call makes
        tracing and lowering that program slower (0.2-0.4 s a frame for
        a 24-layer prefill on the chip's host: PERF.md section 6,
        PR 23)."""
        self._expire_queued()
        admitted = 0
        while self._queue:
            try:
                # a mid-prefill slot is inactive but HELD (slot_req set)
                slot = next(i for i in range(self.slots)
                            if not self.active[i]
                            and self.slot_req[i] is None)
            except StopIteration:
                return admitted
            req = self._queue[0]
            total = req.bucket + req.max_new_tokens
            n_total = math.ceil(total / self.page_size)
            # the cached prefix is capped so at least the prompt's LAST
            # token is always prefilled (its logits seed the first
            # emitted token), and namespaced per (weight version,
            # adapter): KV depends on both. None = pool pressure: wait
            # for a retirement, QUEUED with nothing held. Head-of-line
            # blocking is deliberate — FIFO admission keeps TTFT
            # fairness; submit() already guarantees the request fits an
            # EMPTY pool (the trie is fully evictable once its users
            # retire), so progress is always possible.
            ns = self._cache_ns(req.adapter)
            lease = self.kv.reserve(
                req.prompt, ns, (req.prompt.size - 1) // self.page_size,
                n_total, hold=True)
            if lease is None:
                return admitted
            adapter_page = 0
            if req.adapter is not None:
                # pin the tenant's adapter page; a miss FAULTS it in
                # through the one fixed-shape writer (compiled at
                # construction). A pool full of pinned pages leaves the
                # request queued — the KV-pool-pressure rule: progress
                # resumes when a retirement releases a page.
                got = self.lora.checkout(req.adapter)
                if got is None:
                    return admitted
                adapter_page, ent = got
                if ent is not None:
                    self._write_adapter_page(adapter_page,
                                             ent["payload"],
                                             ent["scale"])
            self._queue.pop(0)
            admitted += 1
            # the engine queue wait ends here (admission starts): a
            # wait, not host work, so its span is retrospective and
            # ring-only. The prefill span opens at the dispatch below,
            # tagged cold vs hit (a handoff-import shows as a preceding
            # handoff_import span on the same trace id)
            req.t_admit = t_adm = time.perf_counter()
            tm = self._tm_on and telemetry.enabled()
            if tm:
                wait = t_adm - req.t_submit
                self._tm_ch["queue"].observe(wait)
                telemetry.tracer().complete(
                    "queue_wait", req.t_submit, wait,
                    trace_id=req.trace_id, track=self._tm_track)
            # fault injection: FF_FAULT=slow(<ms>)@serve:<n> stalls the
            # n-th admission host-side — the deterministic slow-replica
            # drill (a deadline set tighter than <ms> expires while this
            # request is in flight; the router must NOT resubmit it)
            if faultinject.active_plan().fire("slow", "serve"):
                # ffsan: allow(lock-across-blocking) — stalling
                # this replica's tick IS the slow() drill's point
                time.sleep((faultinject.active_plan().last_value or 0)
                           / 1000.0)
            # FF_FAULT=slow(<ms>)@canary:<n> — the deterministic canary
            # SLO-breach drill (ISSUE 17): stall admissions ONLY while
            # this engine is the deploy canary, inflating its TTFT past
            # the slo_ttft_p99_s bound so the RollingDeployer's soak
            # judges a breach and rolls back. Non-canary replicas never
            # consume from the plan (fire() checks deploy_state first).
            if (self.deploy_state == "canary"
                    and faultinject.active_plan().fire("slow", "canary")):
                # ffsan: allow(lock-across-blocking) — the stall is
                # the injected breach itself
                time.sleep((faultinject.active_plan().last_value or 0)
                           / 1000.0)
            self.kv.commit(lease)
            full = len(lease.matched)
            req.lease = lease
            req.prefix_tokens = full * self.page_size
            req.slot = slot
            req.state = "running"
            req.adapter_page = adapter_page
            self.slot_req[slot] = req
            n_prefill = math.ceil(req.bucket / self.page_size)
            # fault injection: FF_FAULT=nan_loss@serve:<n> poisons the
            # n-th ADMITTED request in-graph (NaN added to its logits), so
            # the detect-and-retire path runs end to end, not a host
            # stub. Consumed HERE — in admission order — so the drill's
            # index is independent of how the prefill is scheduled; an
            # interleaved admission carries the poison in its partial
            # state until the final chunk's program applies it.
            poison = (np.float32(np.nan)
                      if faultinject.active_plan().fire("nan_loss",
                                                        "serve")
                      else np.float32(0.0))
            if (self.prefill_interleave_chunks > 0 and full == 0
                    and req.bucket > self.prefill_chunk):
                # chunk-interleaved admission (ISSUE 18): don't run the
                # prefill here — park the slot mid-prefill and let
                # _prefill_tick spend the per-tick chunk budget on it
                # between decode dispatches. The slot's decode-state
                # arrays stay ZEROED (decode writes clamp to scratch
                # page 0, budget stays 1 — indistinguishable from an
                # idle slot to the fixed-shape programs) until
                # _finish_prefill seeds and activates it. Prefix HITS
                # keep the run-to-completion path: the hit already
                # removed the long prefill this knob exists to split.
                padded = np.full((1, req.bucket), self.pad_id, np.int32)
                padded[0, :req.prompt.size] = req.prompt
                self._partial[slot] = {
                    "req": req, "caches": None, "next": 0,
                    "padded": padded, "n_prefill": n_prefill,
                    "t_adm": t_adm, "tm": tm, "poison": poison,
                    "adapter_page": adapter_page}
                continue
            with self._span("prefill", trace_id=req.trace_id,
                            kind="hit" if full else "cold",
                            bucket=req.bucket,
                            prompt_tokens=int(req.prompt.size),
                            matched_pages=full,
                            tail_tokens=int(req.prompt.size)
                            - req.prefix_tokens,
                            **self._scan_rows(req.bucket)) as psp:
                self._prefix_hit_tokens += req.prefix_tokens
                self._prefix_prompt_tokens += int(req.prompt.size)
                self._seed_slot(slot, req, poison)
                tok, ok, routed, rows = self._run_prefill(
                    req.prompt, req.bucket, lease,
                    self._sampling_args_1(req), adapter_page, poison, psp,
                    slot)
                with self._span("prefill_fetch"):
                    # ONE device_get: the copies back start together
                    ok, tok, *routed = jax.device_get((ok, tok, *routed))
                    ok_host, tok_host = bool(ok[0]), int(tok[0])
                psp.annotate(ok=ok_host)
                if routed:
                    # a held share's passes are counted on the device
                    assigned, hit, *counted = (int(v) for v in routed[0])
                    if self._moe_counts_picks:
                        # a prefill's picks are not summed: the counters
                        # are the decode dispatches'
                        counted = counted[2:]
                    rows += sum(counted)
                    psp.annotate(assignments=assigned, experts_hit=hit,
                                 expert_rows=rows)
                    if rows:
                        self._moe_expert_rows += rows
                        self._moe_prefill_assignments += assigned
                if self._tm_on:
                    req.decode_span = telemetry.tracer().begin(
                        "decode", trace_id=req.trace_id,
                        track=self._tm_track)
                self.kv.publish(lease, req.prompt, ns, ok_host)
                self.active[slot] = True
                self._record_token(slot, tok_host, ok_host)
        return admitted

    # ---- chunk-interleaved prefill scheduling (ISSUE 18) ------------------

    def _prefill_tick(self):
        """Spend up to ``prefill_interleave_chunks`` prefill chunks this
        tick, round-robined across mid-prefill slots so concurrent long
        prompts make equal progress; a slot whose last chunk lands is
        finished (sampled + activated) inline, mid-tick. Deadlines are
        swept FIRST so an expired mid-prefill request costs no further
        dispatches — it retires as "timeout" and frees its pages without
        ever decoding."""
        if not self._partial:
            return
        with self._span("prefill_tick") as sp:
            now = time.perf_counter()
            for slot in sorted(self._partial):
                req = self._partial[slot]["req"]
                if req.deadline is not None and now >= req.deadline:
                    self._abort_partial(slot, "timeout",
                                        "deadline expired mid-prefill")
            budget = self.prefill_interleave_chunks
            while budget > 0 and self._partial:
                slots = sorted(self._partial)
                slot = slots[self._prefill_rr % len(slots)]
                self._prefill_rr += 1
                self._run_prefill_chunk(slot)
                budget -= 1
            sp.annotate(chunks=self.prefill_interleave_chunks - budget)
        if self._partial:
            # chunks remained when the tick's budget ran out — the
            # decode streams get the device back; this counter is the
            # proof the knob actually preempted a long prefill
            self._prefill_preempted_ticks += 1

    def _run_prefill_chunk(self, slot: int):
        """One prefill quantum: run the slot's next chunk program,
        advancing the slot-resident cache cursor."""
        ps = self._partial[slot]
        req = ps["req"]
        st = ps["next"]
        with self._span("prefill_chunk", slot=slot, bucket=req.bucket,
                        program=program_name(
                            ("prefill_ichunk", req.bucket, st))):
            length = ((np.asarray([req.prompt.size], np.int32),)
                      if self.gen.state_ops else ())
            if st == 0:
                ps["caches"] = self._compiled_call(
                    ("prefill_ichunk", req.bucket, 0),
                    lambda: self._build_prefill_ichunk(req.bucket, 0),
                    self.gen._params(), self.model.bn_state, ps["padded"],
                    *self._lora_args_1(ps["adapter_page"]), *length)
            else:
                ps["caches"] = self._compiled_call(
                    ("prefill_ichunk", req.bucket, st),
                    lambda: self._build_prefill_ichunk(req.bucket, st),
                    self.gen._params(), self.model.bn_state, ps["padded"],
                    ps["caches"], *self._lora_args_1(ps["adapter_page"]),
                    *length)
            ps["next"] = st + self.prefill_chunk
            self._prefill_chunks_interleaved += 1
            if ps["next"] >= req.bucket:
                self._finish_prefill(slot)

    def _finish_prefill(self, slot: int):
        """The last interleaved quantum: run the gather-last + COW
        scatter program, seed the slot's decode-state arrays and
        activate it — from here on the request is indistinguishable
        from a run-to-completion admission (same pages, same sampled
        first token, same published prefix)."""
        ps = self._partial.pop(slot)
        req = ps["req"]
        n_prefill = ps["n_prefill"]
        pages = np.asarray(req.lease.pages[:n_prefill], np.int32)
        tok, ok, self.kv.pool = self._compiled_call(
            ("prefill_ifinal", req.bucket, n_prefill),
            lambda: self._build_prefill_ifinal(req.bucket, n_prefill),
            self.gen._params(), self.model.bn_state, ps["padded"],
            np.asarray([req.prompt.size], np.int32), ps["caches"],
            self.kv.pool, pages, ps["poison"], *self._sampling_args_1(req),
            *self._lora_args_1(ps["adapter_page"]),
            *self._state_slot_args(slot))
        if self.draft_gen is not None:
            # the draft pool rides the same page ids; its cold prefill
            # program (shared with run-to-completion admission) fills
            # them in one pass — the TARGET's prefill is the
            # head-of-line blocker this path splits, not the draft's
            self.kv.draft_pool = self._compiled_call(
                ("draft_prefill", req.bucket, n_prefill),
                lambda: self._build_draft_prefill(req.bucket, n_prefill),
                self.draft_gen._params(), self.draft_model.bn_state,
                ps["padded"], self.kv.draft_pool, pages)
        with self._span("prefill_fetch"):
            ok, tok = jax.device_get((ok, tok))
            ok_host, tok_host = bool(ok[0]), int(tok[0])
        # decode-state arrays applied only NOW: until this instant every
        # decode dispatch saw this slot as idle
        self._seed_slot(slot, req, ps["poison"])
        if ps["tm"]:
            telemetry.tracer().complete(
                "prefill", ps["t_adm"],
                time.perf_counter() - ps["t_adm"],
                trace_id=req.trace_id, track=self._tm_track,
                kind="interleaved", bucket=req.bucket,
                matched_pages=0, ok=ok_host)
            req.decode_span = telemetry.tracer().begin(
                "decode", trace_id=req.trace_id, track=self._tm_track)
        self.kv.publish(req.lease, req.prompt,
                        self._cache_ns(req.adapter), ok_host)
        self.active[slot] = True
        self._record_token(slot, tok_host, ok_host)

    def _abort_partial(self, slot: int, state: str, error: str):
        """Retire a mid-prefill slot (deadline/poison/fault paths): the
        chunked caches are dropped, pages freed, and the request retires
        without ever decoding. _retire clears the partial state."""
        ps = self._partial[slot]
        if ps["tm"]:
            telemetry.tracer().complete(
                "prefill", ps["t_adm"],
                time.perf_counter() - ps["t_adm"],
                trace_id=ps["req"].trace_id, track=self._tm_track,
                kind="interleaved", aborted=state)
        self._retire(slot, state, error)

    # ---- disaggregated fleet: prefill-only + page-slab handoff -----------

    def _sampling_args_greedy(self):
        """Dummy (1,) greedy sampling args for prefill-only admissions
        (the sampled token is discarded — no slot is seeded)."""
        return (np.zeros((1,), np.float32), np.ones((1,), np.float32),
                np.zeros((1,), np.int32), np.zeros((1,), np.int32))

    def prefill_into_cache(self, prompt,
                           adapter: Optional[str] = None) -> Optional[int]:
        """Prefill-only admission — the prefill half of the
        disaggregated fleet (runtime/router.py): run the prompt's (cold
        or prefix-hit) prefill through the NORMAL bucket-shaped programs
        — same compile keys, so a warmed engine compiles nothing — and
        publish its full pages into the radix trie at refcount 0. No
        slot is held and no token emitted; the pages are then
        ``export_prefix_slab()``'s payload for the handoff to a decode
        replica, or simply a warm local cache (the reference-seeding
        primitive the identity tests use). Returns the number of full
        pages now cached for this prompt, or None when pool pressure or
        a non-finite prefill prevented publishing — the caller falls
        back to the cold path."""
        self._refuse_state_pages("prefill_into_cache", with_snapshot=True)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if self.prefix_cache is None:
            raise RuntimeError(
                "prefill_into_cache needs the radix prefix cache "
                "(prefix_cache=False engines cannot publish pages)")
        bucket = self._bucket(prompt.size)
        if bucket > self.max_seq_len:
            raise ValueError(
                f"bucketed prompt ({bucket}) exceeds max_seq_len "
                f"{self.max_seq_len}")
        with self._span("prefill_into_cache", prompts=1,
                        prompt_tokens=int(prompt.size), tokens=0):
            with self._lock:
                apage = 0
                if adapter is not None:
                    if self.lora is None or adapter not in self.lora.registry:
                        raise ValueError(
                            f"adapter {adapter!r} is not registered on this "
                            f"engine")
                    got = self.lora.checkout(adapter)
                    if got is None:
                        return None     # adapter-pool pressure: fall back
                    apage, ent = got
                    if ent is not None:
                        self._write_adapter_page(apage, ent["payload"],
                                                 ent["scale"])
                try:
                    # the checkout pins the adapter only for the duration of
                    # the prefill (no slot holds it afterwards)
                    ns = self._cache_ns(adapter)
                    last = prompt.size // self.page_size  # publishable pages
                    lease = self.kv.reserve(
                        prompt, ns, (prompt.size - 1) // self.page_size,
                        math.ceil(bucket / self.page_size), hold=False)
                    if lease is None:
                        return None
                    if not lease.need:
                        return last             # already fully published
                    self.kv.commit(lease)
                    # no slot is held: -1 seats the state nowhere
                    _, ok, *_ = self._run_prefill(
                        prompt, bucket, lease, self._sampling_args_greedy(),
                        apage, np.float32(0.0), slot=-1)
                    ok = bool(np.asarray(ok)[0])
                    self.kv.publish(lease, prompt, ns, ok)
                    if not ok:
                        return None
                    self._prefill_only += 1
                    return last
                finally:
                    if adapter is not None:
                        self.lora.release(adapter)

    def export_prefix_slab(self, prompt,
                           adapter: Optional[str] = None,
                           start_page: int = 0) -> Optional[Dict]:
        """Serialize the prompt's cached full-page prefix as a
        host-memory page slab — the bytes a prefill->decode handoff
        moves: per page, every attention op's pool storage (target and
        draft pools) plus quantized scales, verbatim. Host-tier pages
        export straight from their pinned host payload (no promotion);
        HBM pages D2H on the spot. None when the prefix is not fully
        cached — the caller falls back cold.

        ``start_page`` > 0 exports a PARTIAL-PREFIX slab (ISSUE 18,
        sequence-parallel prefill): only pages [start_page, last) ride
        the payload — the shard this replica computed — while
        ``tokens`` still names the whole prefix, so the importer can
        verify the pages extend an already-merged path. The whole
        prefix must still be cached HERE (shards import their
        predecessors' slabs before prefilling), so the exported pages'
        KV attends the true full prefix."""
        return self.export_prefix_path(prompt, self._cache_ns(adapter),
                                       start_page)

    def export_prefix_path(self, prompt, ns, start_page: int = 0) \
            -> Optional[Dict]:
        """export_prefix_slab against an EXPLICIT salted namespace — the
        evacuation path (ISSUE 20) re-exports each
        cached_prefix_manifest() entry under the (version, adapter) salt
        read back off the trie itself, so a retiring replica's
        A/B-versioned and per-adapter prefixes land on survivors under
        the exact key they were cached under. None when the path's pages
        were evicted since the manifest walk — the entry simply drops
        out of the evacuation."""
        self._refuse_state_pages("export_prefix_slab")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            slab = self.kv.export_slab(prompt, ns, start_page)
            if slab is not None:
                self._slab_exports += 1
            return slab

    def import_prefix_slab(self, slab) -> int:
        """Decode-side handoff ingestion: scatter a peer replica's page
        slab into this engine's pools (ONE fixed-shape writer program —
        no per-page compiles) and publish the chunks into the radix trie
        at refcount 0, so the subsequent ``submit()`` of the same prompt
        admits as a prefix HIT. Chunks already cached are skipped;
        returns the number of pages written. Partial imports are safe
        (the trie path stays a valid prefix).

        Partial-prefix slabs (``start_page`` > 0, ISSUE 18) compose
        MID-prefix: the slab's pages extend an already-imported path —
        sequence-parallel prefill merges its shards by importing them
        in order. A slab whose predecessors have not merged yet is
        refused (return 0, no pages written): publishing pages past a
        gap would cache a prefix whose middle was never written."""
        self._refuse_state_pages("import_prefix_slab")
        with self._lock:
            imported = self.kv.import_slab(slab)
            if imported:
                self._slab_imports += 1
                self._import_pages += imported
                if int(slab.get("start_page", 0)) > 0:
                    self._partial_slab_imports += 1
            return imported

    def cached_prefix_manifest(self) -> List[Tuple[np.ndarray, object]]:
        """Evacuation manifest (ISSUE 20): ``(tokens, ns)`` per cached
        root-to-leaf prefix path on this engine, hottest first, each
        under its original salted namespace. A preempted or retiring
        replica walks this in heat order, re-exporting each entry with
        export_prefix_path() — checking its evacuation deadline BETWEEN
        slabs — so the hottest state lands on survivors first."""
        with self._lock:
            if self.prefix_cache is None:
                return []
            return [(t, ns) for t, ns, _ in self.prefix_cache
                    .cached_paths()]

    def warm_page_import(self, prompt) -> bool:
        """Compile and run the shared page-import writer once (H2D tier
        promotion and fleet-handoff ingestion both ride it): publish the
        prompt's prefix, export it, forget it, re-import it — the trie
        ends bit-identical to where it started, with the writer program
        warm. Router/engine ``warmup()`` call this so the first real
        promotion or handoff never compiles mid-traffic."""
        with self._lock, self._retrace.suspended():
            if self.prefix_cache is None:
                return False
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.size < self.page_size:
                return False
            if self.prefill_into_cache(prompt) is None:
                return False
            slab = self.export_prefix_slab(prompt)
            if slab is None:
                return False
            self.kv.forget(prompt)
            return self.import_prefix_slab(slab) > 0

    def warmup(self, prompts, max_new_tokens: int = 4) -> Dict:
        """Warm EVERY program this prompt set can reach — a gotcha
        relearned in PRs 7, 8 and 10, promoted to an API: a
        prompt REPEATED after its first run reaches (bucket,
        matched_pages) hit-prefill variants the first pass never
        compiled, so any timed window that repeats prompts (best-of-N
        rounds!) compiles mid-measurement unless every variant was
        driven. Pass 1 runs every prompt (cold prefill per bucket, the
        partial-prefix hits submission order reaches, decode/verify
        programs); pass 2 repeats them against the now-published trie
        (the SATURATED matches that repeat traffic reaches). With a host
        tier the shared page-import writer is warmed too. Returns
        {"programs": compiles this warmup caused, "requests", and the
        warmed program "variants"}."""
        plist = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        self._retrace.armed = False   # re-warming reopens the set
        before = self.recompile_count
        req0 = self._submitted
        self.run(list(plist), max_new_tokens=max_new_tokens)
        if self.speculate_k > 0 and self.draft_gen is not None:
            # force-build the sampled-speculation helpers (accept
            # uniforms + residual resample): they only dispatch when a
            # sampled slot is live, so a greedy-only warmup would leave
            # them cold and the first sampled tenant mid-traffic would
            # compile. Both are pure functions — running them mutates no
            # engine state.
            k = self.speculate_k
            self._compiled_call(
                ("spec_uniforms", k),
                lambda: jax.jit(
                    lambda s, c: sampling_ops.accept_uniforms(s, c, k)),
                self.seeds, self.emitted.astype(np.int32))
            self._compiled_call(
                ("spec_resample",),
                lambda: jax.jit(sampling_ops.residual_sample),
                np.full((self.slots, self._vocab), 1.0 / self._vocab,
                        np.float32),
                np.zeros((self.slots, self._vocab), np.float32),
                self.seeds, self.emitted.astype(np.int32))
        if self.prefix_cache is not None:
            self.run(list(plist), max_new_tokens=max_new_tokens)
            if self.host_kv_pages:
                cand = max((p for p in plist
                            if p.size >= self.page_size),
                           key=lambda p: p.size, default=None)
                if cand is None or not self.warm_page_import(cand):
                    fflogger.warning(
                        "serving: warmup could not warm the page-import"
                        " writer (no full-page prompt, pool pressure, "
                        "or nothing to re-import) — the first real "
                        "promotion/handoff will compile it")
        if self._tm_on:
            # restart the SLO window clock past the warmup: a
            # compile-inflated warmup TTFT must never be judged as a
            # breach (the benchmark's window starts after its warm-up;
            # the same rule for the health plane)
            flightrec.slo_monitor().rebaseline()
        self._retrace.arm()
        return {"programs": self.recompile_count - before,
                "requests": self._submitted - req0,
                "variants": sorted(self._programs.keys(), key=repr)}

    def drain_tier_events(self) -> List:
        """Pop the trie's depth-1 tier transitions — the router's
        tier-aware affinity feed (key = the prompt's first full-page
        chunk, exactly the affinity hash)."""
        if self.prefix_cache is None:
            return []
        with self._lock:
            return self.prefix_cache.drain_tier_events()

    def _slot_decode_state(self):
        """(write_pos, rope_pos, budget) for one decode/speculate
        dispatch. Inactive slots: state arrays are zeroed, so write_pos
        = -1 would index page -1 — clamp to 0 (the write lands in
        scratch page 0) and give them budget 1, clamping every later
        step there too. Budget is the last legal write position + 1
        (bucket + the request's own max_new_tokens)."""
        write_pos = np.maximum(self.prompt_pad + self.emitted - 1,
                               0).astype(np.int32)
        rope_pos = np.maximum(self.row_len + self.emitted - 1,
                              0).astype(np.int32)
        budget = np.ones((self.slots,), np.int32)
        for slot in range(self.slots):
            req = self.slot_req[slot]
            # mid-prefill slots (slot_req set, inactive) keep budget 1:
            # their state arrays are still zeroed, so decode writes
            # clamp to scratch page 0 exactly like an idle slot's
            if req is not None and self.active[slot]:
                budget[slot] = req.bucket + req.max_new_tokens
        return write_pos, rope_pos, budget

    def _note_pages_touched(self, frontier, budget, held_again: int = 0,
                            saved: int = 0):
        """Record the pool pages this dispatch's attention READS: per
        active slot, pages up to its final-step write frontier (what the
        pallas kernel streams through VMEM — the einsum path gathers the
        whole table width regardless, which is exactly the delta the
        kernel exists to remove). ``frontier`` is (slots, steps): the
        write position of each attention pass the dispatch makes.
        Returns three byte counts of ONE layer over those passes (pages x
        page_size x kv_bytes_per_token), and the pages of the second: what the active slots ATTEND, a
        page once for every slot that holds it (``kv_attended_bytes``);
        what must be READ for that, each distinct page once a pass
        (``kv_read_bytes``: ``held_again`` pages of the slots' prompts are
        held by an earlier slot too, `_shared_pages_plan`); and what the
        kernel's turns FETCH (``kv_streamed_bytes``): a slot's whole blocks
        of `paged_turn_pages` pages and, one a turn, the pages past its
        last whole block, so no page past the last live one, less the
        ``saved`` pages a pass that its group's stream fetched for a slot's
        group and not for the slot. With no page held twice the three are
        one number (the index kernel's stream rounds a slot up to whole
        blocks and reads more than it needs: ``index_streamed_bytes``; a
        tail that fetched whole blocks would round `pages` up to
        `paged_turn_pages` here)."""
        fr = np.minimum(frontier, (budget - 1)[:, None])
        pages = (fr // self.page_size + 1)[self.active]  # (active, steps)
        touched = int(pages[:, -1].sum())
        self._last_pages_touched = touched
        self._pages_touched += touched
        page_bytes = self.page_size * self._kv_bytes_per_token
        attended, steps = int(pages.sum()), frontier.shape[1]
        kv_attended = int(attended * page_bytes)
        kv_read = int((attended - steps * held_again) * page_bytes)
        kv_streamed = int((attended - steps * saved) * page_bytes)
        self._kv_attended_bytes += kv_attended
        self._kv_read_bytes += kv_read
        self._kv_streamed_bytes += kv_streamed
        return (kv_attended, kv_read, kv_streamed,
                attended - steps * held_again)

    def _shared_pages_plan(self):
        """What the live slots' page tables say of pages held by more than
        one of them, for the next decode dispatch: (the shared-page form's
        two arrays or None, its groups, the pages a step they save, the
        pages held again). A slot may share the whole pages of its prompt:
        it attends every token of them and writes none (decode writes at
        `prompt_pad` and after). The GROUPS are the kernel's
        (`kv_pool.shared_page_groups`; none on an engine whose ops cannot
        take the form); `held again` is the traffic's: the columns of such
        pages over all live slots less the distinct pool pages in them, which
        is what ONE read of every distinct key leaves out of the per-slot
        sums. Kept until a slot is seated or retired: a dispatch's tables
        are fixed, and so is this. An engine that has admitted no request
        on a prefix hit has no such page and is not asked."""
        if not self._shared_seen:
            return None, 0, 0, 0
        if self._shared_plan is None:
            shareable = np.where(self.active,
                                 self.row_len // self.page_size, 0)
            held = self.page_tables[
                np.arange(self.pages_per_slot)[None] < shareable[:, None]]
            groups = shared_page_groups(self.page_tables, shareable,
                                        self._shared_cap or 0)
            arrays = None
            if self._shared_cap:
                from flexflow_tpu.ops.pallas_kernels import \
                    pack_shared_groups

                arrays = pack_shared_groups(groups, self.slots,
                                            self._shared_cap)
            self._shared_plan = (
                arrays, len(groups),
                sum((len(m) - 1) * pages for m, pages in groups),
                int(held.size - np.unique(held).size))
        return self._shared_plan

    def _reach_windows(self, rope_pos, budget, k: int,
                       held_again: int = 0) -> Dict:
        """Before a decode dispatch of `k` steps over window layers: every
        live slot's rings are made to hold the page of its last step's
        position (the program is handed the tables as they will stand
        then: WindowPageGroup), and the dispatch's span gets the context
        tokens ONE layer of each kind reads over its steps, summed over
        the live slots: all of a sequence on a global layer (less the
        `held_again` pages a step that an earlier slot holds too: each
        distinct key once; the per-slot sum is `..._attended_global`), the
        window at most on a window layer, whose ring is a slot's own."""
        cap = budget - self.prompt_pad + self.row_len - 1
        # (slots, k): the sequence position each step's token takes
        pos = np.minimum(rope_pos[:, None] + np.arange(k), cap[:, None])
        for slot in np.flatnonzero(self.active):
            self.kv.reach_windows(int(slot), int(pos[slot, -1]))
        self._page_steps["global"] += k * (self.num_pages - 1
                                           - self.kv.free_pages)
        self._page_steps["window"] += k * sum(
            g.held_pages for g in self.kv.window_groups.values())
        seen = pos[self.active] + 1
        counts = {"context_tokens_global": int(seen.sum()) - k * held_again
                  * self.page_size,
                  "context_tokens_attended_global": int(seen.sum())}
        # one number for the window layers: the engine's cells have one
        # window size (each further size adds its own tokens here)
        counts["context_tokens_window"] = int(sum(
            np.minimum(seen, w).sum() for w in self.kv.window_groups))
        return counts

    def _decode_step(self, sampled: int):
        k = self.decode_chunk
        with self._span("decode_prepare"):
            write_pos, rope_pos, budget = self._slot_decode_state()
            live = int(self.active.sum())
            # what the paged kernel attends at the chunk's first step,
            # and what its k steps stream: the engine alone knows both
            # at dispatch (the bytes roofline of the kernel reads them)
            attended = int((np.minimum(write_pos, budget - 1)
                            + 1)[self.active].sum())
            # pages more than one live slot holds: the groups the kernel
            # streams once, and the counts with each distinct key once
            arrays, groups, saved, held_again = self._shared_pages_plan()
            self._shared_groups += groups
            self._shared_pages_saved += k * saved
            (kv_attended, kv_read, kv_streamed,
             distinct) = self._note_pages_touched(
                write_pos[:, None] + np.arange(k), budget, held_again, saved)
            attn = collections.Counter()
            if self._counting_attn_ops:
                # per live row and step, the tokens its attention may see
                # (`context` above is the first step's column, summed)
                seen = (np.minimum(write_pos[:, None] + np.arange(k),
                                   (budget - 1)[:, None]) + 1)[self.active]
                for op in self._counting_attn_ops:
                    attn.update(op.decode_span_counts(seen, self.page_size))
                for name, v in attn.items():
                    self._attn_counts[name] += v
            # per-slot draw counters: the next token's index is exactly
            # the count already emitted — slot- and replica-invariant,
            # so a failover replay reproduces the stream
            args = (self.gen._params(), self.model.bn_state, self.kv.pool,
                    self.page_tables, self.last_tok, write_pos, rope_pos,
                    self.row_len, self.prompt_pad, budget, self.poison,
                    self.temps, self.top_ps, self.top_ks, self.seeds,
                    self.emitted.copy(), *self._lora_args_slots())
            if arrays is not None:
                args += arrays
            if self.kv.window_groups:
                attn.update(self._reach_windows(rope_pos, budget, k,
                                                held_again))
                args += (self.kv.window_tables(),)
            if self.gen.state_ops:
                # each step reads and writes every live slot's state once
                attn["state_bytes"] = (2 * k * live
                                       * self._state_bytes_per_slot)
        # an engine that has seen a prefix hit runs the program that reads
        # the groups, whether or not two slots share a page right now
        key = ("decode", k) if arrays is None \
            else ("decode", k, self._shared_cap)
        with self._span("decode_dispatch", k=k, slots=live, sampled=sampled,
                        context_tokens=attended
                        - held_again * self.page_size,
                        context_tokens_attended=attended,
                        kv_read_bytes=kv_read, kv_streamed_bytes=kv_streamed,
                        kv_attended_bytes=kv_attended,
                        shared_groups=groups, shared_pages_saved=k * saved,
                        # the DISTINCT live pages of its steps: what a
                        # stream that fetched a shared page once would read
                        live_pages_distinct=distinct,
                        live_row_tokens=int(
                            (rope_pos + 1)[self.active].sum()),
                        paged_turn_pages=self._paged_turn_pages,
                        program=program_name(key), **attn) as sp:
            toks, oks, self.kv.pool, *routed = self._compiled_call(
                key, lambda: self._build_decode(k, self._moe_took_list(key),
                                                shared=arrays is not None),
                *args)
            self._note_moe_lowering(key, sp)
        with self._span("token_fetch"):
            # ONE device_get: the copies back start together, so neither
            # `oks` nor the routing counts wait a round trip of their own
            toks, oks, *routed = jax.device_get((toks, oks, *routed))
        self.decode_steps += k
        with self._span("record_tokens") as sp:
            if routed:
                # counted on the device, over live rows only
                assigned, hit, *picks = (int(v) for v in routed[0])
                self._moe_assignments += assigned
                self._moe_experts_hit += hit
                sp.annotate(assignments=assigned, experts_hit=hit)
                if picks:
                    self._moe_zero_picks += picks[0]
                    self._moe_real_picks += picks[1]
                    sp.annotate(zero_picks=picks[0], real_picks=picks[1],
                                held_picks=assigned)
            kept0 = self._tokens_emitted
            for slot in range(self.slots):
                for t in range(k):
                    if not self.active[slot]:
                        break  # retired mid-chunk: the rest is truncated
                    # occupancy counts USEFUL slot-steps only — a slot
                    # that retires mid-chunk stops counting, so the
                    # metric is not inflated by the truncated
                    # past-retirement steps
                    self._occupancy_sum += 1
                    self._record_token(slot, int(toks[t, slot]),
                                       bool(oks[t, slot]))
            sp.annotate(tokens=self._tokens_emitted - kept0,
                        retired=live - int(self.active.sum()))

    def _spec_step(self):
        """One speculative iteration: the draft proposes K tokens per
        slot from its OWN sampling distribution ``q`` (greedy slots:
        argmax — the pre-sampling path bitwise), the target scores all
        K+1 candidate positions in ONE verify dispatch (argmax + the
        warped sampling distribution ``p``), and the host applies the
        accept rule per slot:

          * greedy (temperature 0): emit the longest proposal prefix
            matching the target's argmax, plus the target's own next
            token — every emitted token is the TARGET's argmax, so the
            stream is token-identical to non-speculative greedy decode
            at any K (unchanged from PR 6);
          * sampled: REJECTION-SAMPLED — proposal i is accepted with
            probability min(1, p_i(d_i) / q_i(d_i)) against an
            ACCEPT-stream uniform; the first rejection re-draws from
            the residual distribution norm(max(p - q, 0)) in-graph
            (ops/sampling.py residual_sample), and a fully-accepted
            window draws its bonus token from ``p_K`` (q = 0 residual).
            Emitted tokens are then EXACTLY distributed as the
            non-speculative sampler's (the classic rejection-sampling
            identity) — property-tested in tests/test_sampled_spec.py.

        All draws are counter-based on the request's seed (draw index =
        the emitted token's position), so the whole trajectory replays
        bit-for-bit after failover resubmission. k/v written for
        rejected positions sit past the slot's new write frontier and
        are overwritten by the next dispatch before anything can attend
        them — the resampled token's k/v is written by the NEXT
        iteration's slab position 0, exactly like the greedy path's
        mismatch token."""
        k = self.speculate_k
        with self._span("decode_prepare"):
            write_pos, rope_pos, budget = self._slot_decode_state()
        ctr0 = self.emitted.copy().astype(np.int32)
        # greedy-only iterations never read the p/q probability tensors
        # — skip their device-to-host transfers (B*(K+1)*V floats per
        # dispatch at real vocab sizes) and the uniforms/resample
        # dispatches; the device arrays themselves are cheap (softmax
        # over logits the walk already materialized)
        sampled_live = bool(self.active.any()) and bool(
            np.any(self.temps[self.active] > 0.0))
        # verify-slab frontier (the draft's decode mirrors the same pages);
        # the slab reads every slot's pages for the slot
        self._note_pages_touched((write_pos + k)[:, None], budget,
                                 self._shared_pages_plan()[3])
        d_toks, d_probs, self.kv.draft_pool = self._compiled_call(
            ("draft_propose", k),
            lambda: self._build_draft_propose(k),
            self.draft_gen._params(), self.draft_model.bn_state,
            self.kv.draft_pool, self.page_tables, self.last_tok, write_pos,
            rope_pos, self.row_len, self.prompt_pad, budget,
            self.temps, self.top_ps, self.top_ks, self.seeds, ctr0)
        with self._span("draft_fetch"):
            d_toks = np.asarray(d_toks)                # (k, B_slots)
            if sampled_live:
                d_probs = np.asarray(d_probs)          # (k, B_slots, V)
        slab = np.concatenate(
            [self.last_tok[:, None].astype(np.int32), d_toks.T], axis=1)
        # per-position write slots, clamped to each request's own budget
        # (positions an emitted token can attend never reach the clamp —
        # emission stops at max_new first, so clamp-duplicated writes are
        # only ever visible to host-truncated tokens)
        pos = np.minimum(
            write_pos[:, None] + np.arange(k + 1, dtype=np.int32)[None, :],
            (budget - 1)[:, None]).astype(np.int32)
        t_toks, t_probs, t_oks, self.kv.pool = self._compiled_call(
            ("verify", k), lambda: self._build_verify(k),
            self.gen._params(), self.model.bn_state, self.kv.pool,
            self.page_tables, slab, pos, rope_pos, self.row_len,
            self.prompt_pad, self.poison,
            self.temps, self.top_ps, self.top_ks,
            *self._lora_args_slots())
        with self._span("verify_fetch"):
            t_toks = np.asarray(t_toks)                # (B_slots, k+1)
            if sampled_live:
                t_probs = np.asarray(t_probs)          # (B, k+1, V)
            t_oks = np.asarray(t_oks)
        self.decode_steps += k + 1
        self._spec_dispatches += 1
        # sampled slots need the accept uniforms; greedy-only iterations
        # skip the dispatch (warmup() force-builds the programs so a
        # first sampled request mid-traffic compiles nothing)
        u = None
        if sampled_live:
            u = self._compiled_call(
                ("spec_uniforms", k),
                lambda: jax.jit(
                    lambda s, c: sampling_ops.accept_uniforms(s, c, k)),
                self.seeds, ctr0)
            with self._span("uniforms_fetch"):
                u = np.asarray(u)                      # (B_slots, k)
        # ---- the HOST-side accept rule --------------------------------
        accepts = np.zeros((self.slots,), np.int32)
        p_rows = np.zeros((self.slots, self._vocab), np.float32)
        q_rows = np.zeros((self.slots, self._vocab), np.float32)
        for slot in range(self.slots):
            if not self.active[slot]:
                continue
            accepted = 0
            if self.temps[slot] <= 0.0:
                while accepted < k \
                        and d_toks[accepted, slot] == t_toks[slot,
                                                             accepted]:
                    accepted += 1
            else:
                while accepted < k:
                    d = int(d_toks[accepted, slot])
                    pd = float(t_probs[slot, accepted, d])
                    qd = float(d_probs[accepted, slot, d])
                    # accept w.p. min(1, p/q): u*q < p, STRICT — u is
                    # uniform over [0, 1), so strictness leaves the
                    # accept probability unchanged for p > 0 but
                    # guarantees a proposal OUTSIDE the target's
                    # top-k/top-p keep-set (p == 0 exactly) is always
                    # rejected, even on a u == 0.0 draw (q > 0 always —
                    # the draft just sampled d from q)
                    if u[slot, accepted] * qd < pd:
                        accepted += 1
                    else:
                        break
                p_rows[slot] = t_probs[slot, accepted]
                if accepted < k:   # bonus draw after a clean window
                    #                keeps q = 0 (residual == p)
                    q_rows[slot] = d_probs[accepted, slot]
            accepts[slot] = accepted
        res = None
        if sampled_live:
            # the in-graph residual re-draw (one fixed-shape dispatch
            # covers every sampled slot's rejection OR bonus draw; the
            # draw index is the emitted token's position)
            res = self._compiled_call(
                ("spec_resample",),
                lambda: jax.jit(sampling_ops.residual_sample),
                p_rows, q_rows, self.seeds,
                (ctr0 + accepts).astype(np.int32))
            with self._span("resample_fetch"):
                res = np.asarray(res)
        # ---- emit -----------------------------------------------------
        for slot in range(self.slots):
            if not self.active[slot]:
                continue
            req = self.slot_req[slot]
            arow = self._adapter_spec.setdefault(
                (req.adapter or "none") if req else "none", [0, 0])
            accepted = int(accepts[slot])
            self._spec_proposed += k
            self._spec_accepted += accepted
            arow[0] += k
            arow[1] += accepted
            sampled = self.temps[slot] > 0.0
            for m in range(accepted + 1):
                if not self.active[slot]:
                    break  # retired mid-window: the rest is truncated
                self._occupancy_sum += 1
                if sampled:
                    tok = (int(d_toks[m, slot]) if m < accepted
                           else int(res[slot]))
                else:
                    tok = int(t_toks[slot, m])
                self._record_token(slot, tok, bool(t_oks[slot, m]))

    def _decode_tick(self):
        # one engine-track span per decode dispatch: the fleet timeline
        # shows each replica's chunk cadence without per-token events
        toks0 = self._tokens_emitted
        steps0 = self.decode_steps
        # live slots whose sampler's warp is USED this dispatch; with
        # none, the program's gate (ops/sampling.py) skips the warp and
        # the draw for every step of it (a free slot's temperature is 0)
        sampled = int(np.count_nonzero(self.temps[self.active] > 0.0))
        with self._span("decode_chunk",
                        slots=int(self.active.sum())) as sp:
            if self.speculate_k > 0 and self.draft_gen is not None:
                self._spec_step()
            else:
                self._decode_step(sampled)
            sp.annotate(tokens=self._tokens_emitted - toks0)
        self._sampled_slot_steps += sampled * (self.decode_steps - steps0)
        if sampled == 0:
            self._sampler_gated_steps += self.decode_steps - steps0

    def step(self) -> bool:
        """One scheduler tick: admit what fits (unless draining), then one
        slot-decode step if any slot is live. Returns whether
        PROGRESSABLE work remains — on a draining engine only live slots
        count (the frozen queue can never be admitted here), so a
        while-step loop always terminates. Holds the engine lock for the
        whole tick: concurrent submit()/stats() callers serialize behind
        it (thread-per-replica routers drive step from one thread, so
        the tick itself never contends)."""
        try:
            with self._lock:
                self._tick_seq += 1
                t_tick, compiles = telemetry.now_us(), self.recompile_count
                with self._span("engine_step", tick=self._tick_seq,
                                queued=len(self._queue),
                                active=int(self.active.sum())):
                    if self._queue and not self._draining:
                        with self._span("admit") as sp:
                            # requeued: still waiting for a slot, pages
                            # or an adapter page
                            sp.annotate(admitted=self._admit(),
                                        requeued=len(self._queue))
                    # mid-prefill slots spend their per-tick chunk
                    # budget between admit and the decode dispatch —
                    # draining included (an admitted request is never
                    # cancelled, so a drain must finish its prefill to
                    # retire it)
                    self._prefill_tick()
                    if self.active.any():
                        self._decode_tick()
                    if self._draining:
                        out = bool(self.active.any()) \
                            or bool(self._partial)
                    else:
                        out = self.pending()
                if telemetry.now_us() - t_tick > HELD_TICK_S * 1e6 \
                        and self.recompile_count == compiles:
                    # (a tick that compiled says so in its compile line)
                    self._explain_held_tick(t_tick)
        except Exception as e:  # noqa: BLE001 — an uncaught engine
            #   exception is a flight-recorder trigger (the lock is
            #   released by the time we get here; trip() only schedules,
            #   so the bundle's stats source cannot deadlock)
            if self._tm_on:
                flightrec.trip(
                    "engine_exception", exc=e,
                    replica=self._tm_labels["replica"],
                    error=f"{type(e).__name__}: {e}")
            raise
        if self._tm_on:
            # serving-side SLO tick: one predicate + one time compare
            # until a full window has elapsed
            with self._span("slo_tick"):
                flightrec.slo_monitor().maybe_evaluate()
        return out

    def _explain_held_tick(self, t_tick: float):
        """ONE warning for a tick that took over HELD_TICK_S: every span
        the ring holds of it (this engine's track, since ``t_tick`` on the
        ring's clock) with its duration and counts, ``program`` among
        them, so the run in which a tick was held says whether the host
        or the chip held it (a ``*_fetch`` span is the host waiting for
        the chip, any other is host work)."""
        spans = [e for e in telemetry.tracer().events()
                 if e["ph"] == "X" and e["ts"] >= t_tick
                 and e["pid"] == self._tm_track]
        fflogger.warning(
            "serving: tick %d took %.3f s (over %.1f s): %s",
            self._tick_seq, (telemetry.now_us() - t_tick) / 1e6,
            HELD_TICK_S, "; ".join(
                f"{e['name']} {e['dur'] / 1e6:.3f} s {e.get('args') or ''}"
                for e in spans) or "the ring holds no span of it "
            "(telemetry off)")

    def run(self, prompts=None, max_new_tokens: int = 32,
            **submit_kw) -> List[Request]:
        """Submit `prompts` (list of 1-D int32 arrays) and drive the
        scheduler until the engine is idle; returns THIS call's requests
        in submission order (with prompts=None: whatever was pending at
        entry). Extra kwargs (temperature/top_p/top_k/seed/adapter)
        forward to submit(). The engine holds no reference to retired
        requests. The whole call is one ``run`` span (how a caller warms
        an engine: the ``compile`` spans of the programs it reaches nest
        in it)."""
        with self._span("run") as span:
            if prompts is not None:
                batch = [self.submit(p, max_new_tokens, **submit_kw)
                         for p in prompts]
            else:
                batch = [r for r in self.slot_req if r is not None] \
                    + list(self._queue)
            while self.step():
                pass
            span.annotate(prompts=len(batch),
                          prompt_tokens=sum(int(r.prompt.size)
                                            for r in batch),
                          tokens=sum(len(r.tokens) for r in batch))
        return batch

    # ---- graceful shutdown --------------------------------------------------

    def drain(self) -> Dict:
        """Graceful shutdown (the serving half of elastic recovery: a
        preemption notice or planned restart must not drop tokens already
        being decoded): stop admitting new requests, run the decode loop
        until every in-flight slot retires on eos/length/failure, and
        return a final stats snapshot. Requests still QUEUED (never
        admitted) stay queued untouched — the caller re-submits them to
        the replacement engine; their count rides the snapshot. Idempotent
        — a second drain() finds no live slots and returns the snapshot
        again."""
        with self._lock:
            self._draining = True
        while True:
            # lock per tick, not across the drain: submit() callers get a
            # prompt RuntimeError instead of blocking on the whole drain
            with self._lock:
                if not self.active.any() and not self._partial:
                    break
                # a mid-prefill slot is in-flight work too: finish its
                # chunks (deadline sweep included) so it can decode and
                # retire — drain never strands a half-prefilled request
                self._prefill_tick()
                if self.active.any():
                    self._decode_tick()
        if self.prefix_cache is not None:
            # quiesce the ordered tier publisher: a drained engine owes
            # no in-flight D2H migrations (and the leak check below must
            # see final tier state)
            self.prefix_cache.wait_migrations()
        with self._lock:
            snap = self.stats()
            snap["drained"] = True
            snap["queued"] = len(self._queue)
        fflogger.info(
            "serving: drained — %d completed, %d failed, %d still queued "
            "(re-submit to the replacement engine), occupancy %.2f, "
            "%d recompiles", snap["completed"], snap["failed"],
            snap["queued"], snap["occupancy"], snap["recompiles"])
        return snap

    def reclaim_queued(self) -> List["Request"]:
        """Pull every queued-never-admitted request OUT of this engine
        and return it — the missing half of the drain() contract
        (ISSUE 20 bugfix): drain() deliberately parks queued requests
        for the caller to re-submit, but the fleet's scale-in path never
        collected them, stranding work on a retiring engine. A retiring
        or preempted replica's owner calls this (before or after the
        drain — the queue gate is the engine lock either way) and
        requeues the returned requests on survivors. The requests are
        untouched: never admitted, no slots, no pages, no counters to
        unwind."""
        with self._lock:
            out = list(self._queue)
            del self._queue[:]
            return out

    def reopen(self):
        """Readmit after a drain() (ISSUE 17 satellite: drain used to be
        terminal). The drained engine's slots are all free and its
        counters/pages consistent — reopening is just lifting the
        admission gate; queued requests (if any survived the drain
        untouched) admit on the next tick, and ``submit()`` works again.
        Idempotent; a no-op on an engine that was never drained."""
        with self._lock:
            self._draining = False
            if self.deploy_state == "draining":
                self.deploy_state = "serving"
        fflogger.info("serving: reopened — admitting again (version %s)",
                      self.weight_version)

    def swap_weights(self, params, version: str) -> Dict:
        """Hot-swap this engine's serving weights in place (ISSUE 17):
        install ``params`` (a device tree matching ``model.params`` in
        structure/shape/dtype — same geometry, so every warm fixed-shape
        program stays valid and nothing retraces) as the generator's
        per-engine override, re-quantize ONCE if this is a quantized
        tier, and flush the prefix cache (a drained engine holds every
        cached page at refcount 0, so the flush is total; stale-KV
        safety does not depend on it — the version salt already
        partitions the trie). ``params=None`` reverts to the shared
        ``model.params`` (rollback to the construction-time weights).

        The engine must be DRAINED: swapping under live slots would
        hand in-flight decodes a mid-stream weight change.

        FF_FAULT=swap_fail@deploy:<n> dies AFTER the install — the torn
        mid-swap drill; the deployer catches it, restores the prior
        version and rolls the whole deploy back."""
        with self._lock:
            if self.active.any():
                raise RuntimeError(
                    "swap_weights: engine has live slots — drain() first "
                    "(a mid-stream weight change corrupts in-flight "
                    "decodes)")
            prev = (self.gen._params_override, self.weight_version)
            self.deploy_state = "swapping"
            try:
                self.gen.set_params(params)
                if self.gen.quantize:
                    # re-quantize once, now, under the swap — admission
                    # and decode never pay the quantization pass
                    self.gen._quantized_params()
                faultinject.maybe_fail("swap_fail", "deploy")
            except BaseException:
                # restore the prior weights before re-raising: a failed
                # swap must leave the engine serving what it served
                self.gen.set_params(prev[0])
                if self.gen.quantize:
                    self.gen._quantized_params()
                self.deploy_state = "serving"
                raise
            self.weight_version = str(version)
            self._weight_swaps += 1
            flushed = self.flush_prefix_cache()
            self.deploy_state = "serving"
        fflogger.info(
            "serving: weight swap -> %s (%d cached pages flushed, "
            "swap #%d)", self.weight_version, flushed, self._weight_swaps)
        return {"version": self.weight_version, "flushed_pages": flushed,
                "swaps": self._weight_swaps}

    def health(self) -> Dict:
        """Cheap liveness/readiness probe for a router: admission status
        plus the load counters a balancer steers by, sliced from the one
        ``stats()`` snapshot so the two probes share every formula and
        key name. Never compiles or touches the device. Serializes
        behind a running tick — for a contention-free mid-tick load
        estimate use ``load()``."""
        with self._lock:
            active = int(self.active.sum())
            if self._draining:
                # the frozen queue does not hold "draining": those
                # requests can never be admitted here (they belong to the
                # replacement engine), so the drain is over when the live
                # slots are
                status = "draining" if active else "drained"
            else:
                status = "busy" if (active or self._queue) else "idle"
            snap = self.stats()
            return {
                "status": status,
                "admitting": not self._draining,
                "active_slots": active,
                "queued": len(self._queue),
                "weight_version": self.weight_version,
                "deploy_state": self.deploy_state,
                **{k: snap[k] for k in ("serve_slots", "free_pages",
                                        "completed", "failed", "timeouts",
                                        "occupancy", "recompiles",
                                        "pages_in_use", "kv_pages_shared",
                                        "prefix_hit_rate",
                                        "spec_accept_rate",
                                        "kv_cache_dtype", "weight_dtype",
                                        "kv_bytes_per_token",
                                        "tokens_per_pool_gb")},
            }

    def load(self) -> Dict:
        """Lock-free load snapshot for a router's dispatch loop: active
        slots + queue depth, read WITHOUT the engine lock so a dispatcher
        never blocks behind a replica mid-tick. The reads race the owning
        thread by design — a balancer steering on slightly stale load is
        correct; a balancer stalled behind every decode dispatch is not."""
        return {"active_slots": int(self.active.sum()),
                "queued": len(self._queue)}

    # ---- metrics ------------------------------------------------------------

    def flush_prefix_cache(self) -> int:
        """Evict EVERY refcount-0 cached page back to the free list;
        returns the number reclaimed. For weight hot-swap (cached KV is
        stale under new weights) and for page-leak accounting: after
        drain() + flush, free_pages must equal kv_pages - 1. Pages still
        mounted by live requests survive (and stay cached)."""
        if self.prefix_cache is None:
            return 0
        with self._lock:
            return self.kv.flush()

    def _window_stats(self) -> Dict:
        """What `stats()` says of a model with window layers: the pages
        the live requests hold in the global table and in the window
        groups' rings (page ids: each backs every op of its group), now
        and summed over the decode steps so far, the rings' bound, and how
        many times a ring's page went on to the next page_size positions
        instead of a new page being taken."""
        groups = self.kv.window_groups
        if not groups:
            return {}
        return {
            "kv_pages_held_global": self.num_pages - 1 - self.kv.free_pages,
            "kv_pages_held_window": sum(g.held_pages
                                        for g in groups.values()),
            "kv_window_pages_recycled": sum(g.recycled
                                            for g in groups.values()),
            # the two gauges above summed over every decode step so far: a
            # window's average holding is a delta of these over its steps
            "kv_page_steps_global": self._page_steps["global"],
            "kv_page_steps_window": self._page_steps["window"],
            "kv_window_ring_pages": max(g.ring for g in groups.values()),
            "kv_window_pool_bytes": self._window_pool_bytes,
        }

    def stats(self) -> Dict:
        with self._lock:
            return self._stats_locked()

    def _stats_locked(self) -> Dict:
        pc = self.prefix_cache
        ttfts = sorted(self._ttfts)  # bounded window of completions

        def pct(p):
            if not ttfts:
                return 0.0
            return ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))]

        return {
            "requests": self._submitted,
            "completed": self._completed,
            "failed": self._failed,
            "timeouts": self._timeouts,
            # rolling-deploy identity (ISSUE 17): the weight version this
            # engine serves, where it stands in a roll, and how many
            # in-place swaps it has taken (keys pinned)
            "weight_version": self.weight_version,
            "deploy_state": self.deploy_state,
            "weight_swaps": self._weight_swaps,
            "tokens_generated": self._tokens_emitted,
            "decode_steps": self.decode_steps,
            "recompiles": self.recompile_count,
            # post-warmup jit cache misses the ffsan sentinel saw
            # (0 unless FF_SANITIZE is on and a warm program
            # retraced — tests/test_router.py asserts this stays 0)
            "sanitizer_retraces": self._retrace.hits,
            # mean fraction of computed positions doing USEFUL work per
            # decode step (mid-chunk retirements stop counting) — the
            # engine's steady-state utilization headline. Under
            # speculation the denominator counts all K+1 verify
            # positions, so occupancy folds the accept rate in
            # ((1 + aK)/(K+1) on a saturated engine): it measures wasted
            # COMPUTE, not idle slots — a router balancing on busyness
            # should use active_slots/queued (health()) and read
            # spec_accept_rate separately. occupied_slot_steps is the
            # raw numerator so callers can compute occupancy over a
            # WINDOW from two stats() snapshots
            "occupancy": (self._occupancy_sum
                          / max(1, self.decode_steps) / self.slots),
            "occupied_slot_steps": self._occupancy_sum,
            # slot-steps DISPATCHED with temperature > 0 (live sampled
            # slots x the dispatch's steps, counted at dispatch: a slot
            # that retires mid-chunk still counts its whole chunk)
            "sampled_slot_steps": self._sampled_slot_steps,
            # decode steps of dispatches in which no live slot samples:
            # over decode_steps, the share of steps whose sampler took
            # the gate's greedy branch
            "sampler_gated_steps": self._sampler_gated_steps,
            # decode dispatches of a model with dropless MoE ops (0
            # otherwise): experts_hit / (experts x MoE layers x
            # decode_steps) is the share of the expert weights a step
            # streams
            "prefix_hit_tokens": self._prefix_hit_tokens,
            "prefix_prompt_tokens": self._prefix_prompt_tokens,
            **self._attn_counts,
            "moe_assignments": self._moe_assignments,
            "moe_experts_hit": self._moe_experts_hit,
            # a model with zero-computation experts: its decode dispatches'
            # picks of an identity column, of a real expert, and the real
            # picks that landed on an expert held here
            **({"moe_zero_picks": self._moe_zero_picks,
                "moe_real_picks": self._moe_real_picks,
                "moe_held_picks": self._moe_assignments}
               if self._moe_counts_picks else {}),
            # decode and run-to-completion prefill dispatches whose
            # program's MoE calls all took the expert-stream kernel
            "moe_streamed_dispatches": self._moe_streamed_dispatches,
            # over the run-to-completion prefills whose experts took the
            # grouped lowering: the rows its products were given and the
            # assignments that needed them (a held share: about its slack,
            # 2, where every pass is the first; a layer that holds every
            # expert: 1 but for bucket padding)
            "moe_expert_rows": self._moe_expert_rows,
            "moe_prefill_assignments": self._moe_prefill_assignments,
            "ttft_p50_ms": round(pct(0.50) * 1e3, 3),
            "ttft_p99_ms": round(pct(0.99) * 1e3, 3),
            "free_pages": self.kv.free_pages,
            "kv_pages": self.num_pages,
            "kv_page_size": self.page_size,
            "serve_slots": self.slots,
            # quantized-tier observability (ISSUE 11): what the pool and
            # weights are stored as, what a token of KV costs in HBM
            # (scales included), how many tokens a GB of pool holds, and
            # the capacity multiplier vs a bf16 pool of the same
            # geometry — effective page capacity = kv_page_size x that
            # multiplier in bf16-equivalent tokens per page's bytes.
            # These are the router's placement signals: a quantized
            # replica advertises more tokens per byte, not more bytes.
            "kv_cache_dtype": self.kv_cache_dtype,
            "weight_dtype": self.weight_dtype,
            "kv_pool_bytes": self._pool_bytes,
            # the recurrent-state ops' pool beside the pages (0 for a
            # model without them): its bytes, the fixed bytes a slot holds
            # whatever its context, and the slots whose state is live
            "state_pool_bytes": self._state_pool_bytes,
            "state_bytes_per_slot": self._state_bytes_per_slot,
            "state_slots_live": (int((self.row_len > 0).sum())
                                 if self.gen.state_ops else 0),
            # the snapshots of that state on the trie's nodes (all 0 for
            # an engine without state ops or without a prefix cache):
            # rows held now, the arrays' bytes (scratch row included),
            # admissions that resumed from one, snapshots published, and
            # snapshots that left with their node (eviction, flush, forget)
            "state_snapshots_held": (pc.snapshots_held if pc else 0),
            "state_snapshot_pool_bytes": self._snapshot_pool_bytes,
            "state_snapshot_hits": (pc.snapshot_hits if pc else 0),
            "state_snapshots_taken": (pc.snapshots_taken if pc else 0),
            "state_snapshots_evicted": (pc.snapshots_evicted if pc else 0),
            "kv_bytes_per_token": round(self._kv_bytes_per_token, 3),
            # (0 tokens a GB where a token takes no bytes: a graph of
            # recurrent states alone, whose context is free)
            "tokens_per_pool_gb": (
                int((1 << 30) / self._kv_bytes_per_token)
                if self._kv_bytes_per_token else 0),
            "kv_capacity_vs_bf16": round(self._kv_capacity_vs_bf16, 3),
            "kv_effective_page_capacity": round(
                self.page_size * self._kv_capacity_vs_bf16, 1),
            # KV-pool observability (ROADMAP item 1: the router balances
            # on these): in-use counts every non-free page (live-private
            # + cached), cached the pages the radix trie holds (warm,
            # reclaimable at refcount 0), shared those mounted by >1
            # live request right now
            "pages_in_use": self.num_pages - 1 - self.kv.free_pages,
            **self._window_stats(),
            "kv_pages_cached": pc.pages if pc else 0,
            "kv_pages_shared": pc.shared_pages() if pc else 0,
            # tiered-cache observability (ISSUE 12): pages by tier (hbm
            # = trie-cached pool pages, host = pinned host copies incl.
            # publishes still in flight), the migration counters the
            # router steers by, and the handoff ledger (prefill-
            # only admissions run for the role split, slabs moved)
            "host_kv_pages": pc.host_pages if pc else 0,
            "kv_pages_hbm": pc.pages if pc else 0,
            "kv_pages_host": pc.host_used if pc else 0,
            "tier_demotions": pc.demotions if pc else 0,
            "tier_promotions": pc.promotions if pc else 0,
            "tier_demote_failures": pc.demote_failures if pc else 0,
            "tier_promote_failures": pc.promote_failures if pc else 0,
            "tier_host_evictions": pc.host_evictions if pc else 0,
            "tier_pending_migrations": (pc.pending_migrations()
                                        if pc else 0),
            "prefill_only_requests": self._prefill_only,
            "prefix_slab_exports": self._slab_exports,
            "prefix_slab_imports": self._slab_imports,
            "prefix_pages_imported": self._import_pages,
            # long-context serving (ISSUE 18): interleaved-admission
            # progress (chunks run between decode ticks, ticks where a
            # long prefill was preempted by the budget, slots currently
            # mid-prefill) and partial-prefix merges (start_page > 0
            # slab imports from sequence-parallel prefill shards)
            "prefill_interleave_chunks": self.prefill_interleave_chunks,
            "prefill_chunks_interleaved":
                self._prefill_chunks_interleaved,
            "prefill_preempted_ticks": self._prefill_preempted_ticks,
            "prefill_partial_slots": len(self._partial),
            "partial_slab_imports": self._partial_slab_imports,
            "prefix_cache": pc is not None,
            "prefix_lookups": pc.lookups if pc else 0,
            "prefix_hits": pc.hits if pc else 0,
            "prefix_hit_rate": (round(pc.hits / max(1, pc.lookups), 4)
                                if pc else 0.0),
            "prefill_tokens_saved": pc.tokens_saved if pc else 0,
            "prefix_evictions": pc.evictions if pc else 0,
            # live references into the trie: must be 0 after drain() —
            # nonzero at idle means a refcount leak
            "prefix_refs_live": pc.live_refs() if pc else 0,
            "speculate_k": self.speculate_k,
            "spec_proposed": self._spec_proposed,
            "spec_accepted": self._spec_accepted,
            "spec_accept_rate": round(
                self._spec_accepted / max(1, self._spec_proposed), 4),
            # per-request sampling + multi-tenant adapter pool
            # (ISSUE 14): requests that sampled (temperature > 0), the
            # engine-level submit() defaults, and the adapter pool's
            # occupancy/fault/eviction ledger (zeros without a pool —
            # the keys are pinned either way). spec_accept_by_adapter
            # mirrors the labeled telemetry series for host callers.
            "sampled_requests": self._sampled_requests,
            "serve_temperature": self.default_temperature,
            "serve_top_p": self.default_top_p,
            "serve_top_k": self.default_top_k,
            "lora_rank": self.lora_rank,
            **(self.lora.stats() if self.lora is not None else {
                "adapter_pool_pages": 0, "adapters_registered": 0,
                "adapters_resident": 0, "adapter_pages_in_use": 0,
                "adapter_pool_occupancy": 0.0, "adapter_lookups": 0,
                "adapter_hits": 0, "adapter_faults": 0,
                "adapter_evictions": 0, "adapter_refs_live": 0}),
            "spec_accept_by_adapter": {
                name: round(v[1] / max(1, v[0]), 4)
                for name, v in self._adapter_spec.items()},
            "requests_by_adapter": dict(self._adapter_requests),
            # decode-attention hot-path observability (ISSUE 7): which
            # impl this engine's programs trace (one choice, under the
            # two names its readers ask for: the decode attention's and
            # the prefill page write's) and how many pool pages the last
            # dispatch's attention read (vs the table-width gather the
            # einsum path always re-materializes)
            "paged_attention_impl": self.paged_attention_impl,
            "paged_prefill_impl": self.paged_attention_impl,
            "pages_touched": self._pages_touched,
            "kv_read_bytes": self._kv_read_bytes,
            # what the kernel's turns fetched for them, and the pages a
            # turn takes (`_note_pages_touched`)
            "kv_streamed_bytes": self._kv_streamed_bytes,
            "paged_turn_pages": self._paged_turn_pages,
            "last_pages_touched": self._last_pages_touched,
            # pages more than one live slot holds (`_shared_pages_plan`):
            # `kv_read_bytes` counts such a page once a pass,
            # `kv_attended_bytes` once a slot; the groups the decode
            # dispatches streamed once, and the page fetches that saved
            "kv_attended_bytes": self._kv_attended_bytes,
            "shared_groups": self._shared_groups,
            "shared_pages_saved": self._shared_pages_saved,
            "shared_members_cap": self._shared_cap or 0,
        }
