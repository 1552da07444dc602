"""LLMEngine — continuous-batching serving over the ragged Pallas kernel.

Turns the repo's existing pieces (models/generation.py forward math,
kernels/paged_attention.py ragged kernel, the refcounted PagedKVPool, the
chunked-prefill Scheduler) into a request-lifecycle engine:

    engine = LLMEngine(model, max_len=256, page_size=16)
    rid = engine.add_request([1, 2, 3], max_new_tokens=8)
    while engine.has_unfinished():
        for out in engine.step():       # incremental token streaming
            ...
    tokens = engine.outputs()[rid].token_ids

Compilation contract (the TPU-shaped core of the design): EVERY engine
step — any mix of decode rows and prefill chunks, any batch composition,
any lengths — is one launch of ONE jitted ragged step whose input shapes
never change: ``step_token_budget`` packed query tokens over
``max_num_seqs`` row slots and ``max_pages_per_seq``-wide block tables.
XLA compiles exactly one step executable for the lifetime of the process
(gated by tests/test_serving_compile_gate.py) — down from the previous
``len(batch_buckets) * len(pages_buckets) + #prefill_buckets`` zoo.
Everything request-specific — block tables, (q_start, q_len, kv_len)
row metadata, sampling temperature — is data, not shape.

Prefix caching: after a prompt is fully committed, the engine registers
its page-aligned token-prefix chains in a hash map; a later request whose
prompt starts with a registered chain is admitted by FORKING the donor's
pages (``PagedKVPool.fork`` — refcount + 1, zero prefill compute, zero
page storage for the shared region). An identical prompt shares even the
partially-filled tail page; the first divergent append then triggers one
copy-on-write page duplication. int8 pools share full pages only: an
append can requantize a page in place (running-amax scale growth), which
must never perturb another reader's view.

Sampling: per-request knobs (temperature/top_k/top_p) travel as per-row
data through the one step, and every random draw comes from a
per-request ``fold_in(seed, generation position, tag)`` stream — a
request's sampled tokens are bit-identical across batch compositions,
chunking, preemption-recompute, and per-token vs burst execution.

Speculative decoding: ``LLMEngine(draft_model=..., spec_tokens=k)``
adds an int4 draft (serving/spec_decode.py) whose k proposals per
decode row are verified in ONE launch of the same ragged executable
(rows become q_len=k+1 prefill-shaped chunks); accepted tokens commit
normally, rejected tails roll the KV length back without freeing pages.

Greedy outputs are token-identical to sequential ``Generator.generate``:
the ragged step computes each token's K/V and logits independently of how
the work was chunked, so chunk boundaries, preemption-with-requeue
(recompute mode) and prefix forks all reproduce the same continuation —
with or without a draft model (rejection sampling degenerates to
argmax-equality on greedy rows).
"""
from __future__ import annotations

import itertools
import os
import time
import zlib
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from ..models.generation import (LayerKind, _logits, _rms_norm, _rope,
                                 _wmat, extract_params, layer_kinds,
                                 request_keys, sample_rows)
from ..kernels.paged_attention import (kv_append, ragged_kv_tokens_read,
                                       ragged_paged_attention)
from ..profiler import phases, spans
from .kv_cache import NULL_PAGE, PagedKVPool, PoolExhausted
from .metrics import ServingMetrics
from .scheduler import Scheduler, SchedulerConfig, Sequence, SequenceStatus
from .spec_decode import (FINAL_TAG, StepOperands, _ragged_fp_layer,
                          _ragged_packing, speculative_sample)


class PrefixStoreMismatch(ValueError):
    """A persisted prefix store cannot feed the live pool: the stored
    geometry/dtype disagrees with the engine's. This is an OPERATOR
    error (pointing a differently-configured engine at an old store),
    not corruption — so unlike a corrupt store (which cold-starts with
    a counter), it raises, carrying BOTH configs so the drift is
    diagnosable from the exception alone."""

    def __init__(self, live_config, stored_config):
        self.live_config = dict(live_config)
        self.stored_config = dict(stored_config)
        drift = {k for k in set(live_config) | set(stored_config)
                 if live_config.get(k) != stored_config.get(k)}
        super().__init__(
            f"prefix store does not match the live KV pool "
            f"(drifted: {sorted(drift)}): live={self.live_config} "
            f"stored={self.stored_config}")


class RequestRejected(ValueError):
    """Structured admission rejection: the request could never be served
    (prompt + max_new_tokens exceeds max_len or the pool's page limit).
    The engine records a finalized ``RequestOutput`` (status "aborted",
    ``finish_reason`` describing why) under ``request_id`` before
    raising, so the serving loop keeps running and polling clients see a
    terminal state instead of the whole engine dying mid-``step()``."""

    def __init__(self, request_id, reason, *, needed_pages=None,
                 limit=None, message=None):
        super().__init__(message or reason)
        self.request_id = request_id
        self.reason = reason
        self.needed_pages = needed_pages
        self.limit = limit


@dataclass
class Request:
    """What a client submits."""
    prompt_token_ids: list
    max_new_tokens: int = 16
    temperature: float = 0.0
    #: per-request sampling knobs: top-k (0/None = off), top-p nucleus
    #: (None/1.0 = off), and the request's own PRNG seed — a fixed
    #: (seed, prompt) reproduces the same sampled tokens bit for bit
    #: regardless of batch composition (None derives a stable seed from
    #: the request_id)
    top_k: int | None = None
    top_p: float | None = None
    seed: int | None = None
    eos_token_id: int | None = None
    #: relative SLO in seconds: if the request is still *waiting* this long
    #: after submission, the scheduler sheds it instead of serving it late
    deadline_s: float | None = None
    #: relative e2e SLO in seconds: if the request is still UNFINISHED
    #: this long after submission — running rows included — it is
    #: aborted at the next step boundary (reason "deadline_exceeded")
    #: instead of decoding tokens nobody will read
    abort_after_s: float | None = None
    request_id: str | None = None
    #: multi-tenant serving (paddle_tpu.tenancy): the submitting tenant
    #: (None = untenanted) and the LoRA adapter the request wears —
    #: None resolves to the tenant's default adapter (or the base
    #: model), 0 is explicitly the base model
    tenant_id: str | None = None
    adapter_id: object = None


@dataclass
class RequestOutput:
    """Live view of one request; ``token_ids`` grows as tokens stream."""
    request_id: str
    prompt_token_ids: list
    token_ids: list = field(default_factory=list)
    status: str = "waiting"
    finish_reason: str | None = None
    num_preemptions: int = 0
    tenant_id: str | None = None

    @property
    def finished(self) -> bool:
        return self.status in ("finished", "shed", "cancelled", "aborted")


def _quantized_append(Pp, Ps, tok, page_ids, off, page_size, live):
    """Append one token per row into an int8 page with per-(head, page)
    scales. The page's scale is the running amax/127 of everything in it:
    when the new token raises it, the page's existing values are
    requantized in place (dequant -> round at the new scale), so earlier
    tokens stay within one rounding step of their fp values.

    Pp: [Hkv, num_pages, ps, d] int8; Ps: [Hkv, num_pages] f32;
    tok: [Hkv, B, d] fp; page_ids/off/live: [B]. Dead rows (live=False)
    target the null page with an unchanged scale and write nothing.
    Returns (Pp, Ps).
    """
    old_s = Ps[:, page_ids]                              # [Hkv, B]
    amax = jnp.max(jnp.abs(tok), axis=-1)                # [Hkv, B]
    new_s = jnp.where(live[None, :],
                      jnp.maximum(old_s, jnp.maximum(amax, 1e-8) / 127.0),
                      old_s)
    ratio = jnp.where(new_s > 0, old_s / new_s, 0.0)
    page_q = jnp.clip(jnp.round(
        Pp[:, page_ids].astype(jnp.float32) * ratio[:, :, None, None]),
        -127, 127)                                       # [Hkv, B, ps, d]
    tok_q = jnp.clip(jnp.round(tok / jnp.maximum(new_s[:, :, None], 1e-30)),
                     -127, 127)
    sel = (jnp.arange(page_size)[None, None, :, None]
           == off[None, :, None, None]) & live[None, :, None, None]
    page_new = jnp.where(sel, tok_q[:, :, None, :], page_q) \
        .astype(jnp.int8)
    return Pp.at[:, page_ids].set(page_new), \
        Ps.at[:, page_ids].set(new_s)


def _segmented_quant_append(Pp, Ps, chunk, tbls, q_starts, q_lens, kv_lens,
                            page_size, max_pages, chunk_cap):
    """Segmented int8 chunk append: ONE running-amax requant per touched
    (head, page) instead of the old per-token chunk walk (chunk_cap
    sequential rounds of dequant->round, PR 6's named follow-up).

    Per touched page the final scale is ``max(old_scale, amax(new
    tokens in the page) / 127)`` — exactly what the sequential walk
    converges to — the page's existing content is requantized ONCE at
    that scale, and every new token is quantized directly at it (the
    walk round-tripped early tokens through each intermediate scale;
    quantizing at the final scale skips that double rounding, so values
    land within one rounding step of the walk and a single-token append
    is :func:`_quantized_append`'s math exactly). The loop runs over
    touched page SLOTS (``chunk_cap // page_size + 1`` worst case,
    traced-bounded to the live maximum — ONE iteration for
    decode-heavy launches) not chunk positions.

    Pp: [Hkv, num_pages, ps, d] int8; Ps: [Hkv, num_pages] f32;
    chunk: [Hkv, T, d] fp new tokens packed row-wise (the ragged step's
    query packing); tbls/q_starts/q_lens/kv_lens as in the ragged step.
    Rows own disjoint write pages (CoW guarantees it), dead rows target
    the null page and write nothing. Returns (Pp, Ps).
    """
    ps = page_size
    rows = jnp.arange(tbls.shape[0])
    start = jnp.maximum(kv_lens - q_lens, 0)               # [R]
    first_page = start // ps
    last_page = jnp.where(q_lens > 0, jnp.maximum(kv_lens - 1, 0) // ps,
                          first_page - 1)
    max_touched = -(-chunk_cap // ps) + 1
    bound = jnp.clip(jnp.max(last_page - first_page + 1), 0, max_touched)

    def body(j, carry):
        Pp, Ps = carry
        pidx = first_page + j                              # [R]
        pg_lo = pidx * ps
        w_lo = jnp.maximum(start, pg_lo)                   # write range
        w_hi = jnp.minimum(kv_lens, pg_lo + ps)            # ∩ this page
        live = (w_lo < w_hi) & (q_lens > 0)
        page = jnp.where(live,
                         tbls[rows, jnp.clip(pidx, 0, max_pages - 1)],
                         NULL_PAGE)
        slot_pos = pg_lo[:, None] + jnp.arange(ps)[None, :]   # [R, ps]
        tok_idx = jnp.clip(q_starts[:, None] + slot_pos - start[:, None],
                           0, chunk.shape[1] - 1)
        sel = (slot_pos >= w_lo[:, None]) & (slot_pos < w_hi[:, None]) \
            & live[:, None]                                # [R, ps]
        new = chunk[:, tok_idx]                            # [Hkv, R, ps, d]
        amax = jnp.max(jnp.where(sel[None, :, :, None], jnp.abs(new), 0.0),
                       axis=(2, 3))                        # [Hkv, R]
        old_s = Ps[:, page]
        new_s = jnp.where(live[None, :],
                          jnp.maximum(old_s,
                                      jnp.maximum(amax, 1e-8) / 127.0),
                          old_s)
        ratio = jnp.where(new_s > 0, old_s / new_s, 0.0)
        page_q = jnp.clip(jnp.round(
            Pp[:, page].astype(jnp.float32) * ratio[:, :, None, None]),
            -127, 127)
        tok_q = jnp.clip(jnp.round(
            new / jnp.maximum(new_s[:, :, None, None], 1e-30)), -127, 127)
        page_new = jnp.where(sel[None, :, :, None], tok_q, page_q) \
            .astype(jnp.int8)
        return (Pp.at[:, page].set(page_new), Ps.at[:, page].set(new_s))

    return jax.lax.fori_loop(0, bound, body, (Pp, Ps))


def _sampler_counts(temps, top_ks, top_ps):
    """What a launch's sampling epilogue must do, as counts on
    ``serve.step`` from its per-row knobs (pad rows are greedy and
    unmasked): it runs the sampler where a row samples, the top-k /
    nucleus sorts where a row asks for the mask
    (``generation.if_any_samples``, ``_masked_logits``)."""
    return {"sampled_rows": int((temps > 0).sum()),
            "masked_rows": int(((top_ks > 0) | (top_ps < 1.0)).sum())}


class LLMEngine:
    """Continuous-batching serving engine over a paged KV pool."""

    def __init__(self, model, *, max_len=256, page_size=16, num_pages=None,
                 max_num_seqs=None, chunk_size=None, q_block=8,
                 step_token_budget=None, batch_buckets=None,
                 pages_buckets=None, prefill_buckets=None,
                 max_prefills_per_step=4, prefix_caching=True,
                 prefix_cache_size=4096, pinned_prefix_pages=0,
                 high_watermark=0.90, low_watermark=0.50, seed=0,
                 stream_cb=None, now_fn=time.monotonic, interpret=None,
                 quantized_mode=None, kv_cache_dtype=None,
                 burst_tokens=None, draft_model=None, spec_tokens=None,
                 draft_quantized_mode="weight_only_int4",
                 draft_num_pages=None, mesh=None, tracer=None,
                 flight_recorder=None, flight_capacity=256,
                 engine_id=None, gauge_stale_after_s=None,
                 prefix_store=None, prefix_store_autosave=None,
                 host_kv_pages=0, kv_prefetch=True, kv_prefetch_depth=4,
                 kv_spill_seed=0, fleet_prefix_cache=None,
                 tenants=None, adapter_slots=0, adapter_rank=8,
                 adapter_store=None, adapter_store_autosave=None,
                 megakernel_scope=None, prefill_megakernel=None):
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size}")
        if burst_tokens is None:
            from ..core.flags import GLOBAL_FLAGS
            burst_tokens = int(GLOBAL_FLAGS.get("decode_burst_tokens"))
        if burst_tokens < 1:
            raise ValueError(f"burst_tokens must be >= 1, got "
                             f"{burst_tokens}")
        # speculative decoding: active iff a draft model is given; the
        # draft length comes from spec_tokens / FLAGS_spec_decode_tokens
        # (a draft model with neither set gets a default of 4)
        if spec_tokens is None:
            from ..core.flags import GLOBAL_FLAGS
            spec_tokens = int(GLOBAL_FLAGS.get("spec_decode_tokens"))
            if draft_model is not None and spec_tokens < 1:
                spec_tokens = 4
        if spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {spec_tokens}")
        if draft_model is None:
            spec_tokens = 0
        if spec_tokens > 0 and burst_tokens > 1:
            raise ValueError(
                "speculative decoding and the on-device burst loop are "
                "mutually exclusive decode accelerations — set "
                "burst_tokens=1 (the default) when passing draft_model")
        # whole-model decode megakernel scope (ROADMAP item 4 / MPK):
        # 'layer' keeps today's unrolled per-layer launches; 'model'
        # moves the layer loop inside the traced program as a lax.scan
        # over stacked [L, ...] weights + KV pools — one launch per
        # token (and per burst). Token output is bitwise identical
        # between scopes; jit/hlo_forensics.launch_stats holds the
        # collapse (engine.launch_stats()).
        from ..models.generation import (resolve_megakernel_scope,
                                         resolve_prefill_megakernel)
        self.megakernel_scope = resolve_megakernel_scope(megakernel_scope)
        # ragged prefill launch shape (ROADMAP item 4's prefill-side
        # remainder): 'unfused' keeps the per-projection layer bodies;
        # 'fused' routes the whole ragged chain through
        # kernels/prefill_megakernel.fused_prefill_layer — fused
        # concat-dot projections over a step-hoisted rope/slot/block-row
        # prologue. Token output is bitwise identical between modes
        # (tests/test_prefill_megakernel.py).
        self.prefill_megakernel = resolve_prefill_megakernel(
            prefill_megakernel)
        # multi-tenant LoRA (paddle_tpu.tenancy): an adapter store with
        # no explicit slot count still needs a registry to reload into
        if adapter_store is not None and not adapter_slots:
            adapter_slots = 4
        if adapter_slots and burst_tokens > 1:
            raise ValueError(
                "batched LoRA adapters run inside the ragged step; the "
                "on-device burst loop (decode megakernel) has no adapter "
                "path — set burst_tokens=1 (the default) when passing "
                "adapter_slots/adapter_store")
        self.spec_tokens = spec_tokens
        #: runtime eligibility gate for speculative rounds — the
        #: degradation ladder's first rung flips it off under pressure
        #: (and back on when pressure clears). It never changes operand
        #: shapes: the one compiled executable keeps its K = spec_tokens
        #: layout, disabled rounds simply stop planning spec rows.
        self.spec_enabled = True
        #: on-device generation burst length: when > 1 and every running
        #: row is a caught-up decode row, the engine dispatches ONE
        #: jitted lax.while_loop of up to this many sample->append->gate
        #: iterations instead of one ragged step per token; the
        #: scheduler re-syncs (admission / preemption / CoW / prefix
        #: registration) at burst boundaries. 1 = the per-token path,
        #: bit-identical to the pre-burst engine.
        self.burst_tokens = burst_tokens
        self.cfg = cfg = model.config
        # what each layer is (models/generation.py LayerKind): static
        # data of the config. A model whose layers are not all Llama
        # layers (q/k head norm, window layers, routed experts) runs
        # through the one ragged step; the modes that have not been
        # carried over to such layers are refused here, by name
        self._kinds = layer_kinds(cfg)
        self._plain_layers = all(k == LayerKind() for k in self._kinds)
        windows = sorted({k.window for k in self._kinds if k.window})
        #: latent layers (a token caches one compressed row every head
        #: reads): the pool is then a latent one, all layers of the kind
        self._latent = any(k.latent for k in self._kinds)
        if not self._plain_layers:
            refused = {
                "quantized_mode": quantized_mode is not None,
                "kv_cache_dtype int8": kv_cache_dtype in (
                    "int8", jnp.int8, jnp.dtype(jnp.int8)),
                "prefill_megakernel='fused'":
                    self.prefill_megakernel == "fused",
                "megakernel_scope='model'": self.megakernel_scope == "model",
                "burst_tokens > 1": burst_tokens > 1,
                "draft_model": draft_model is not None,
                "adapter_slots / adapter_store": bool(adapter_slots),
                "mesh": mesh is not None,
                "host_kv_pages": bool(host_kv_pages),
                "prefix_caching=True (with window layers: a fork would "
                "need window pages that were released)":
                    bool(windows) and (bool(prefix_caching)
                                       or prefix_store is not None
                                       or bool(pinned_prefix_pages)
                                       or fleet_prefix_cache is not None),
                "layers with different windows": len(windows) > 1,
                "latent layers beside layers that cache keys and values":
                    self._latent and not all(k.latent for k in self._kinds),
                "prefix_store / fleet_prefix_cache / pinned_prefix_pages "
                "(with latent layers: they move pages as (K, V) blocks)":
                    self._latent and (prefix_store is not None
                                      or fleet_prefix_cache is not None
                                      or bool(pinned_prefix_pages)),
            }
            if any(refused.values()):
                raise ValueError(
                    f"LLMEngine: {type(cfg).__name__}'s layers are not all "
                    f"Llama layers, and these have not been carried over "
                    f"to them: " + "; ".join(k for k, v in refused.items()
                                             if v))
        self.params = extract_params(model)
        # low-bit serving weights: the jitted ragged step traces over a
        # quantized pytree; projections run the fused dequant-matmul
        self.quantized_mode = quantized_mode
        if quantized_mode is not None:
            from ..quantization.low_bit import quantize_params
            self.params = quantize_params(self.params, quantized_mode)
        # tensor-parallel serving (distributed/gspmd.py): every
        # projection splits over the mesh's model axis (column/row
        # parallel; embed/lm_head on the vocab axis) and the paged KV
        # pool shards its kv-head axis the same way — the ONE jitted
        # ragged step picks the placements up by sharding inference, so
        # the trace-count==1 compile gate is untouched. Accepts a jax
        # Mesh with a 'model' axis, a ProcessMesh, or an int tp degree.
        self.mesh = None
        if mesh is not None:
            from ..distributed import gspmd as _gspmd
            import jax as _jax
            if isinstance(mesh, int):
                mesh = _gspmd.build_mesh(
                    _gspmd.ShardingConfig(data=1, model=mesh),
                    devices=_jax.devices()[:mesh])
            elif hasattr(mesh, "jax_mesh"):       # ProcessMesh
                mesh = mesh.jax_mesh
            if _gspmd.MODEL_AXIS not in mesh.shape:
                raise ValueError(
                    f"LLMEngine(mesh=...) needs a '{_gspmd.MODEL_AXIS}' "
                    f"mesh axis, got axes {tuple(mesh.shape)}")
            tp = mesh.shape[_gspmd.MODEL_AXIS]
            if cfg.num_key_value_heads % tp:
                raise ValueError(
                    f"LLMEngine(mesh=...): {cfg.num_key_value_heads} kv "
                    f"heads do not divide over the {tp}-way model axis "
                    f"(the KV pool shards per kv head)")
            self.mesh = mesh
            self.params = _gspmd.shard_serving_params(self.params, mesh)
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages_per_seq = max_len // page_size
        # legacy bucket knobs: max(batch_buckets) still sets the row-slot
        # count; pages_buckets/prefill_buckets are obsolete (the ragged
        # step has ONE shape) and accepted only for call-site compat
        del prefill_buckets
        if max_num_seqs is None:
            max_num_seqs = max(batch_buckets) if batch_buckets else 8
        if chunk_size is None:
            chunk_size = min(64, max_len)
        chunk_size = min(chunk_size, max_len)
        self.chunk_size = chunk_size
        if self.spec_tokens > 0:
            # a speculative round packs spec_tokens+1 query tokens into
            # EVERY row slot; the fixed-shape budget must hold that for
            # a full house of rows (spec_len never shrinks under
            # pressure — that would change which stream positions get
            # drafted and break per-request bit-reproducibility)
            need = max_num_seqs * (-(-(self.spec_tokens + 1) // q_block)
                                   * q_block)
            if step_token_budget is None:
                default = max_num_seqs * q_block + \
                    -(-chunk_size // q_block) * q_block
                step_token_budget = max(default, need)
            elif step_token_budget < need:
                raise ValueError(
                    f"step_token_budget {step_token_budget} cannot hold "
                    f"a speculative round: max_num_seqs {max_num_seqs} x "
                    f"(spec_tokens {self.spec_tokens} + 1) needs {need} "
                    f"packed query tokens")
        if num_pages is None:
            # default: every row slot can hold a max_len sequence, so
            # preemption never fires unless the operator shrinks the pool
            num_pages = max_num_seqs * self.max_pages_per_seq + 1
        if kv_cache_dtype in ("int8", jnp.int8, jnp.dtype(jnp.int8)):
            dtype = jnp.int8          # int8 pool: ~2x sequences per byte
        elif kv_cache_dtype is not None:
            dtype = jnp.dtype(kv_cache_dtype)
        else:
            dtype = self.params["embed"].dtype
        # two-tier KV (serving/kv_tier.py, ROADMAP 5a): host_kv_pages >
        # 0 backs the HBM pool with a host-RAM spill arena — preemption
        # victims PARK (exact-byte spill/restore) instead of
        # recomputing, live context is bounded by hbm + host pages, and
        # a background staging thread prefetches parked sequences back
        # ahead of re-admission. kv_prefetch=False is the injected
        # regression hook: every restore then stages synchronously and
        # counts as a kv_prefetch_stall.
        self._kv_prefetch_depth = max(int(kv_prefetch_depth), 1)
        if host_kv_pages and int(host_kv_pages) > 0:
            from .kv_tier import TieredKVPool
            self.pool = TieredKVPool(
                cfg.num_hidden_layers, cfg.num_key_value_heads,
                cfg.head_dim, num_pages=num_pages, page_size=page_size,
                host_pages=int(host_kv_pages), dtype=dtype,
                high_watermark=high_watermark,
                low_watermark=low_watermark,
                pinned_page_budget=pinned_prefix_pages, mesh=self.mesh,
                prefetch=bool(kv_prefetch),
                prefetch_depth=self._kv_prefetch_depth,
                spill_seed=kv_spill_seed)
        elif self._latent:
            # one compressed row a token a layer, one array a layer
            self.pool = PagedKVPool(
                cfg.num_hidden_layers, 1, cfg.latent_row,
                num_pages=num_pages, page_size=page_size, dtype=dtype,
                high_watermark=high_watermark, low_watermark=low_watermark,
                latent_row=cfg.latent_row)
        else:
            # window layers keep their pages in a second group of the
            # pool (kv_cache.py), sized so that every row slot can hold
            # its bound: that group never preempts
            group = {}
            if windows:
                group = dict(
                    window_layers=[i for i, k in enumerate(self._kinds)
                                   if k.window],
                    window=windows[0],
                    window_pages=1 + max_num_seqs
                    * PagedKVPool.window_pages_per_row(
                        windows[0], chunk_size, page_size))
            self.pool = PagedKVPool(
                cfg.num_hidden_layers, cfg.num_key_value_heads,
                cfg.head_dim, num_pages=num_pages, page_size=page_size,
                dtype=dtype, high_watermark=high_watermark,
                low_watermark=low_watermark,
                pinned_page_budget=pinned_prefix_pages, mesh=self.mesh,
                **group)
        self._tiered = hasattr(self.pool, "arena")
        # gauge_stale_after_s: snapshot-side staleness horizon — gauges
        # last set longer ago than this read as null (listed under
        # "stale_gauges") instead of as current values; the telemetry
        # scraper applies its own horizon independently
        self.metrics = ServingMetrics(now_fn=now_fn,
                                      stale_after_s=gauge_stale_after_s)
        # observability (serving/tracing.py): the per-request span
        # tracer is OPT-IN (None = zero per-request bookkeeping); the
        # flight recorder is ALWAYS ON — a bounded ring of step/fleet
        # events whose last-N context auto-dumps on InvariantViolation,
        # nonfinite-logits aborts, and (cluster) replica crashes. Both
        # are host-side appends stamped on now_fn: they add zero jitted
        # dispatches and zero device syncs (tests/test_tracing.py gates
        # the trace-count and dispatch ratios with tracing enabled).
        from .tracing import FlightRecorder
        self.tracer = tracer
        self.flight = flight_recorder if flight_recorder is not None \
            else FlightRecorder(flight_capacity)
        #: replica id under a ClusterEngine (fleet flight entries carry
        #: it); None for a standalone engine
        self.engine_id = engine_id
        #: always-on spans (profiler/spans.py): the tag by which
        #: ``metrics_snapshot()["spans"]`` tells a replica's step and
        #: request spans from its neighbours', and each request's open
        #: life span (``serve.queue`` or ``serve.prefill``)
        self._span_tags = {} if engine_id is None else {"engine": engine_id}
        self._life = {}
        # a failing pool audit raises InvariantViolation WITH the
        # flight recorder's last-N context attached (kv_cache.py reads
        # these back-references at raise time; the counter keeps
        # metrics.flight_dumps honest for audit-triggered dumps too)
        self.pool.flight_recorder = self.flight
        self.pool.flight_dump_counter = self.metrics.flight_dumps
        self.scheduler = Scheduler(
            self.pool,
            SchedulerConfig(max_num_seqs=max_num_seqs,
                            chunk_size=chunk_size, q_block=q_block,
                            step_token_budget=step_token_budget,
                            max_prefills_per_step=max_prefills_per_step,
                            now_fn=now_fn),
            self.max_pages_per_seq, metrics=self.metrics)
        self.max_num_seqs = self.scheduler.config.max_num_seqs
        self.q_block = self.scheduler.config.q_block
        self.step_token_budget = self.scheduler.config.step_token_budget
        # remember whether the caller PINNED the execution mode: the
        # megakernel honors an explicit knob but otherwise stays
        # env-driven (jnp fallback off-TPU, int8_matmul's discipline)
        self._interpret_explicit = interpret is not None
        if interpret is None:
            from ..kernels import _on_tpu
            interpret = not _on_tpu()
        self._interpret = interpret
        self._now = now_fn
        self._stream_cb = stream_cb
        #: every sampling draw is a per-request stream folded off this
        #: one base key (models/generation.request_keys) — the engine
        #: never consumes shared key state, so batch composition cannot
        #: perturb any request's draws
        self._base_key = jax.random.key(seed)
        self._draft = None
        if self.spec_tokens > 0:
            from .spec_decode import DraftWorker
            if draft_num_pages is None:
                # the draft holds every running row's FULL context with
                # no prefix sharing and no preemption of its own — size
                # it for the no-sharing worst case, independent of how
                # starved the operator made the target pool (draft pages
                # are small-model bytes; explicit draft_num_pages
                # overrides)
                draft_num_pages = \
                    self.max_num_seqs * self.max_pages_per_seq + 1
            self._draft = DraftWorker(
                draft_model, target_cfg=cfg, page_size=page_size,
                max_num_seqs=self.max_num_seqs,
                max_pages_per_seq=self.max_pages_per_seq,
                num_pages=draft_num_pages,
                step_token_budget=self.step_token_budget,
                q_block=self.q_block, chunk_size=self.chunk_size,
                seed=seed, quantized_mode=draft_quantized_mode,
                interpret=interpret if self._interpret_explicit else None)
        self._ids = itertools.count()
        self._seqs: dict[str, Sequence] = {}
        self._outputs: dict[str, RequestOutput] = {}
        self.prefix_caching = prefix_caching
        self.prefix_cache_size = prefix_cache_size
        #: token-chain -> (donor seq_id, chain length); valid while the
        #: donor still owns the chain's pages (it leaves the map's truth
        #: when the donor is freed — the probe re-validates on every hit)
        self._prefix_cache: dict[tuple, tuple[str, int]] = {}
        #: page-aligned token-prefix -> (pinned chain id, length): the
        #: pinned-LRU fallback when no LIVE donor matches — a chain the
        #: pool still pins can be re-forked long after its last sequence
        #: sharer left (repeated cold prompts skip the re-prefill). LRU
        #: capped alongside _prefix_cache; entries whose chain the pool
        #: evicted fail ``is_pinned`` and are pruned on probe.
        self._pinned_index: dict[tuple, tuple[tuple, int]] = {}
        # persistent cross-restart prefix store (io/persist.py): pinned
        # prefix chains — pages, int8 scales, and the token-chain index
        # — survive process death. Construction WARM-RELOADS whatever
        # the store holds (corrupt/missing degrades to a cold start with
        # a restore_fallbacks count + flight event, never an exception;
        # a geometry/dtype drift raises PrefixStoreMismatch); afterwards
        # every pin-set change re-persists the chains (autosave), so a
        # crashed replica's successor re-forks fleet-wide shared system
        # prompts instead of paying the re-prefill TTFT cliff.
        self.prefix_store = None
        self._prefix_autosave = False
        self._prefix_store_sig = frozenset()
        if prefix_store is not None:
            if isinstance(prefix_store, (str, os.PathLike)):
                from ..io.persist import ArtifactStore
                prefix_store = ArtifactStore(
                    prefix_store, flight_recorder=self.flight,
                    now_fn=self._now)
            self.prefix_store = prefix_store
            self._prefix_autosave = True if prefix_store_autosave is None \
                else bool(prefix_store_autosave)
            self._restore_prefix_store()
        #: fleet-wide prefix cache (serving/fabric.py FleetPrefixCache,
        #: cluster-scope, shared by every replica): chains this engine
        #: pins publish into it, and the admission probe falls back to
        #: it when no local donor or pinned chain matches — a prompt
        #: prefilled once anywhere in the fleet is never re-prefilled
        #: here, even if the publishing replica has since crashed.
        self.fleet_prefix = fleet_prefix_cache
        # multi-tenant LoRA serving (paddle_tpu.tenancy): a
        # fixed-capacity adapter slab whose slot ids travel the ragged
        # step as per-token DATA (slot 0 = zeros = the base model), and
        # an optional per-tenant economy — weighted-fair admission,
        # refilling token quotas, cost ledgers. Both are strictly
        # additive: without them the step's operand list gains NOTHING
        # (None legs are empty pytrees) and admission stays bare FIFO.
        self.adapters = None
        if adapter_slots:
            from ..tenancy.adapters import AdapterRegistry
            self.adapters = AdapterRegistry(
                cfg, n_slots=int(adapter_slots), rank=int(adapter_rank))
        self.tenant_policy = None
        if tenants is not None:
            from ..tenancy.policy import TenantPolicy
            if isinstance(tenants, TenantPolicy):
                self.tenant_policy = tenants
            else:
                self.tenant_policy = TenantPolicy(tenants,
                                                  now_fn=self._now)
            self.scheduler.policy = self.tenant_policy
        #: wall/virtual time of the last per-step cost accrual (KV
        #: byte-seconds, adapter-slot-seconds); None until the first step
        self._last_cost_t = None
        # persistent adapter store (io/persist.py): published adapters
        # survive process death — construction warm-reloads the newest
        # verified version (corruption degrades to a cold start inside
        # ArtifactStore; geometry drift raises AdapterStoreMismatch),
        # and every add/evict re-persists when autosave is on.
        self.adapter_store = None
        self._adapter_autosave = False
        if adapter_store is not None:
            if isinstance(adapter_store, (str, os.PathLike)):
                from ..io.persist import ArtifactStore
                adapter_store = ArtifactStore(
                    adapter_store, flight_recorder=self.flight,
                    now_fn=self._now)
            self.adapter_store = adapter_store
            self._adapter_autosave = True if adapter_store_autosave \
                is None else bool(adapter_store_autosave)
            restored = self.adapters.restore(self.adapter_store)
            if restored:
                self.metrics.adapter_restores.inc(restored)
                self.record_fleet_event("adapter_restore",
                                        adapters=restored)
        # the params the TWO step executables trace over: model scope
        # stacks the per-layer dicts into one [L, ...] LayerStack tree
        # ONCE here (fp arrays and int8 QuantizedWeight leaves alike);
        # self.params stays per-layer for everything host-side
        # (prefix/persist export, megakernel_mode probing)
        from ..kernels.decode_megakernel import stack_layer_params
        if self.megakernel_scope == "model":
            self._step_params = dict(
                self.params,
                layers=stack_layer_params(self.params["layers"]))
        else:
            self._step_params = self.params
        # fused ragged prefill (FLAGS_prefill_megakernel): the RAGGED
        # step traces over concat-fused projection weights (qkv, gate|up
        # — column-exact for fp and int8 alike) while the burst step
        # keeps the per-projection tree it scans today. int4/mixed
        # layouts have no fused geometry: fall back to the unfused
        # bodies and report it honestly (prefill_megakernel_mode).
        self._fused_layers = None
        if self.prefill_megakernel == "fused":
            from ..kernels.prefill_megakernel import fuse_layer_weights
            fused = [fuse_layer_weights(l) for l in self.params["layers"]]
            if any(f is None for f in fused):
                self.prefill_megakernel = "unfused"
            else:
                self._fused_layers = fused
        if self._fused_layers is not None:
            layers = self._fused_layers
            if self.megakernel_scope == "model":
                layers = stack_layer_params(layers)
            self._ragged_params = dict(self.params, layers=layers)
        else:
            self._ragged_params = self._step_params
        self._step_launched = False
        self._burst_launched = False
        self._build_step()

    # ------------------------------------------------------------------
    # the ONE jitted step (fixed shapes: any traffic mix, one executable)
    # ------------------------------------------------------------------
    def _build_step(self):
        cfg = self.cfg
        ps = self.page_size
        qb = self.q_block
        T = self.step_token_budget
        R = self.max_num_seqs
        PPS = self.max_pages_per_seq
        # a speculative row appends spec_tokens+1 tokens in one round:
        # the segmented int8 append's touched-page bound must cover it
        chunk_cap = max(self.chunk_size, self.spec_tokens + 1)
        K = self.spec_tokens
        interpret = self._interpret
        # the megakernel's mode: an explicit LLMEngine(interpret=...)
        # pins it (both launch paths then obey one knob); None stays
        # env-driven — Pallas on TPU, jnp fallback off it, interpreter
        # under PADDLE_TPU_FORCE_PALLAS (int8_matmul's discipline)
        mk_interpret = interpret if self._interpret_explicit else None
        quant_pool = self.pool.quantized
        # a latent model's sizes are its own (spec_decode._latent_attention)
        H, Hkv, d = (None,) * 3 if self._latent else (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
        scope = self.megakernel_scope
        num_layers = cfg.num_hidden_layers
        prefill_fused = self.prefill_megakernel == "fused"
        kinds = self._kinds
        operands = self._operands = StepOperands(
            T, R, PPS, K, window=bool(self.pool.window_layers),
            adapters=self.adapters is not None)

        def ragged_step(params, kv, kv_scales, ctl, draft_tokens,
                        draft_probs, base_key, adapters):
            # ctl: the launch's ONE int32 control buffer
            # (spec_decode.StepOperands), unpacked here by static slices:
            # tokens/positions [T] packed row-wise (pad rows: q_len=0,
            # q_start=T); tbls [R, PPS]; kv_lens = committed + q_len per
            # row (the attention length AFTER this step's appends);
            # sample_idx [R, K+1] flat indices of each row's verify
            # positions (ordinary rows: K+1 copies of the last live
            # token). Sampling is fully in-graph: per-row knobs
            # (temps/top_ks/top_ps, the floats by their own bits),
            # per-request PRNG streams (seeds/sample_pos off base_key),
            # and — on speculative rounds — the rejection sampler over
            # the draft's candidates (spec_lens/draft_tokens/draft_probs;
            # all-zero on ordinary rounds, where the sampler degenerates
            # to one direct draw from the last position's distribution).
            # adapters (paddle_tpu.tenancy): the LoRA slab pytree (None,
            # an empty pytree, without a registry); with one the buffer
            # carries slot_ids, the per-token slot ids: which adapter a
            # token wears is a gather — data, never shape.
            # tbls_w: the window page group's tables, in the buffer for
            # a model with window layers.
            o = operands.unpack(ctl)
            tokens, positions, tbls = o["tokens"], o["positions"], o["tbls"]
            q_starts, q_lens, kv_lens = (o["q_starts"], o["q_lens"],
                                         o["kv_lens"])
            sample_idx, spec_lens = o["sample_idx"], o["spec_lens"]
            temps, top_ks, top_ps = o["temps"], o["top_ks"], o["top_ps"]
            seeds, sample_pos = o["seeds"], o["sample_pos"]
            adapter_slots, tbls_w = o.get("slot_ids"), o.get("tbls_w")
            tok_row = live = pre = None
            moe_stats = []
            if prefill_fused:
                # the layer-invariant ragged prologue, hoisted: rope
                # phase tables, the page-slot scatter map, the packed
                # row/liveness masks and the attention block-row map
                # are computed ONCE per step and shared by every fused
                # layer body (value-identical to the per-layer
                # derivations — bitwise-neutral for the tokens)
                from ..kernels.prefill_megakernel import ragged_prologue
                pre = ragged_prologue(
                    positions, tbls, q_starts, q_lens,
                    theta=cfg.rope_theta, head_dim=d, page_size=ps,
                    max_pages=PPS, q_block=qb)
            else:
                tok_row, live = _ragged_packing(q_starts, q_lens, T)

            def lo(ad, p):
                if ad is None:
                    return None
                A, B = ad[p]
                return (A, B, adapter_slots)

            def fp_layer(lyr, ad, h, Kp, Vp, kind=LayerKind()):
                if prefill_fused:
                    from ..kernels.prefill_megakernel import \
                        fused_prefill_layer
                    h, Kp, Vp, _, _ = fused_prefill_layer(
                        lyr, h, Kp, Vp, tbls, pre, q_starts, q_lens,
                        kv_lens, eps=cfg.rms_norm_eps, num_heads=H,
                        q_block=qb, interpret=mk_interpret,
                        attn_interpret=interpret, adapters=ad,
                        slots=adapter_slots, scope=scope,
                        num_layers=num_layers)
                    return h, Kp, Vp
                # the shared fp layer body (spec_decode), which the
                # draft worker also runs — draft/target numerics come
                # from ONE definition
                return _ragged_fp_layer(
                    lyr, h, Kp, Vp, positions,
                    tbls_w if kind.window else tbls, tok_row, live,
                    q_starts, q_lens, kv_lens, cfg, ps, PPS, qb,
                    interpret, adapters=ad, slots=adapter_slots,
                    kind=kind, moe_stats=moe_stats)

            def int8_layer(lyr, ad, h, Kp, Ks, Vp, Vs):
                if prefill_fused:
                    from ..kernels.prefill_megakernel import \
                        fused_prefill_layer

                    def qafn(Kp, Ks, Vp, Vs, kt, vt):
                        return _append_quant(Kp, Ks, Vp, Vs, kt, vt,
                                             tbls, q_starts, q_lens,
                                             kv_lens)
                    h2, Kp, Vp, Ks, Vs = fused_prefill_layer(
                        lyr, h, Kp, Vp, tbls, pre, q_starts, q_lens,
                        kv_lens, eps=cfg.rms_norm_eps, num_heads=H,
                        q_block=qb, interpret=mk_interpret,
                        attn_interpret=interpret, k_scales=Ks,
                        v_scales=Vs, quant_append_fn=qafn, adapters=ad,
                        slots=adapter_slots, scope=scope,
                        num_layers=num_layers)
                    return h2, Kp, Ks, Vp, Vs
                with phases.phase("norm"):
                    x = _rms_norm(h, lyr["ln1"], cfg.rms_norm_eps)
                with phases.phase("attn.qkv"):
                    q = _wmat(x, lyr["q"], lora=lo(ad, "q")) \
                        .reshape(1, T, H, d)
                    k = _wmat(x, lyr["k"], lora=lo(ad, "k")) \
                        .reshape(1, T, Hkv, d)
                    v = _wmat(x, lyr["v"], lora=lo(ad, "v")) \
                        .reshape(1, T, Hkv, d)
                    q = _rope(q, positions[None], cfg.rope_theta, d)
                    k = _rope(k, positions[None], cfg.rope_theta, d)
                with phases.phase("attn.core"):
                    kt = jnp.transpose(k[0], (1, 0, 2))     # [Hkv, T, d]
                    vt = jnp.transpose(v[0], (1, 0, 2))
                    Kp, Ks, Vp, Vs = _append_quant(
                        Kp, Ks, Vp, Vs, kt, vt, tbls, q_starts, q_lens,
                        kv_lens)
                    o = ragged_paged_attention(
                        q[0], Kp, Vp, tbls, q_starts, q_lens, kv_lens,
                        q_block=qb, interpret=interpret,
                        k_scales=Ks, v_scales=Vs)
                with phases.phase("attn.out"):
                    h = h + _wmat(o.reshape(1, T, H * d), lyr["o"],
                                  lora=lo(ad, "o"))
                with phases.phase("norm"):
                    x = _rms_norm(h, lyr["ln2"], cfg.rms_norm_eps)
                with phases.phase("mlp"):
                    h = h + _wmat(
                        jax.nn.silu(_wmat(x, lyr["gate"],
                                          lora=lo(ad, "gate")))
                        * _wmat(x, lyr["up"], lora=lo(ad, "up")),
                        lyr["down"], lora=lo(ad, "down"))
                return h, Kp, Ks, Vp, Vs

            with phases.phase("embed"):
                h = params["embed"][tokens][None]           # [1, T, hid]
            if scope == "model":
                # scan-over-layers: pools (and the LoRA slab views)
                # stack inside the jit, the SAME layer bodies as the
                # unrolled path run as the scan body — ONE layer-body
                # site in the lowered program, so the prologue/epilogue
                # chains (rms_norm->qkv->rope, o-proj->residual->mlp)
                # appear once instead of L times in the compiled HLO
                Kst = jnp.stack([K for K, _ in kv])
                Vst = jnp.stack([V for _, V in kv])
                ad_st = None
                if adapters is not None:
                    ad_st = jax.tree.map(lambda *xs: jnp.stack(xs),
                                         *adapters)
                if not quant_pool:
                    def layer_body(hc, xs):
                        lyr, ad, Kp, Vp = xs
                        hc, Kp, Vp = fp_layer(lyr, ad, hc, Kp, Vp)
                        return hc, (Kp, Vp)
                    h, (Kn, Vn) = jax.lax.scan(
                        layer_body, h, (params["layers"], ad_st, Kst,
                                        Vst))
                    new_kv = [(Kn[li], Vn[li])
                              for li in range(num_layers)]
                    new_scales = []
                else:
                    Kss = jnp.stack([a for a, _ in kv_scales])
                    Vss = jnp.stack([b for _, b in kv_scales])

                    def layer_body(hc, xs):
                        lyr, ad, Kp, Vp, Ks, Vs = xs
                        hc, Kp, Ks, Vp, Vs = int8_layer(lyr, ad, hc, Kp,
                                                        Ks, Vp, Vs)
                        return hc, (Kp, Vp, Ks, Vs)
                    h, (Kn, Vn, Ksn, Vsn) = jax.lax.scan(
                        layer_body, h, (params["layers"], ad_st, Kst,
                                        Vst, Kss, Vss))
                    new_kv = [(Kn[li], Vn[li])
                              for li in range(num_layers)]
                    new_scales = [(Ksn[li], Vsn[li])
                                  for li in range(num_layers)]
            else:
                new_kv, new_scales = [], []
                for li, (lyr, pages) in enumerate(
                        zip(params["layers"], kv)):
                    if kinds[li].latent:
                        # a latent layer's pages are one array
                        h, pages, _ = fp_layer(lyr, None, h, pages, None,
                                               kinds[li])
                        new_kv.append(pages)
                        continue
                    Kp, Vp = pages
                    ad = adapters[li] if adapters is not None else None
                    if not quant_pool:
                        h, Kp, Vp = fp_layer(lyr, ad, h, Kp, Vp, kinds[li])
                        new_kv.append((Kp, Vp))
                        continue
                    Ks, Vs = kv_scales[li]
                    h, Kp, Ks, Vp, Vs = int8_layer(lyr, ad, h, Kp, Ks,
                                                   Vp, Vs)
                    new_scales.append((Ks, Vs))
                    new_kv.append((Kp, Vp))
            with phases.phase("head"):
                h = _rms_norm(h, params["norm"], cfg.rms_norm_eps)
                verify = h[0, sample_idx.reshape(-1)]   # [R*(K+1), hid]
                logits = _logits(params, verify, cfg) \
                    .reshape(R, K + 1, -1)              # [R, K+1, V]
            # non-finite guard: one in-graph isfinite all-reduce per
            # ragged row over its verify logits — a NaN/Inf surfaces at
            # commit time as a per-row flag the host turns into a
            # structured abort, instead of argmax/categorical silently
            # sampling token 0 from garbage. Pad rows (q_len == 0)
            # always read finite: their logits are null-page noise.
            with phases.phase("guard"):
                finite = jnp.all(jnp.isfinite(logits.reshape(R, -1)),
                                 axis=-1) | (q_lens <= 0)
            out, n_out = speculative_sample(
                logits, draft_tokens, draft_probs, spec_lens, temps,
                top_ks, top_ps, base_key, seeds, sample_pos)
            if moe_stats:
                # the routed layers' counts ride home behind n_out, in
                # the transfer the host makes anyway: pairs computed
                # here and held experts touched, summed over layers, and
                # the most tokens any one expert got
                with phases.phase("moe.combine"):
                    st = jnp.stack(moe_stats)
                    n_out = jnp.concatenate([
                        n_out,
                        jnp.stack([st[:, 0].sum(), st[:, 1].sum(),
                                   st[:, 2].max()]).astype(n_out.dtype)])
            # the step's small results go home as one array too
            return (operands.pack_results(out, n_out, finite), new_kv,
                    new_scales if quant_pool else None)

        def _append_quant(Kp, Ks, Vp, Vs, kt, vt, tbls, q_starts, q_lens,
                          kv_lens):
            # segmented int8 append: one running-amax requant per
            # touched (head, page) — a chunk costs pages-touched
            # iterations, not chunk-length iterations
            Kp, Ks = _segmented_quant_append(
                Kp, Ks, kt, tbls, q_starts, q_lens, kv_lens, ps, PPS,
                chunk_cap)
            Vp, Vs = _segmented_quant_append(
                Vp, Vs, vt, tbls, q_starts, q_lens, kv_lens, ps, PPS,
                chunk_cap)
            return Kp, Ks, Vp, Vs

        def burst_step(params, kv, kv_scales, tokens, kv_lens, tbls,
                       live0, caps, temps, top_ks, top_ps, seeds, gpos0,
                       eos_ids, n_steps, base_key):
            # the on-device token loop (decode megakernel mode): up to
            # burst_tokens sample -> KV append -> EOS/length gate
            # iterations inside ONE executable. Every row is a
            # caught-up decode row; block tables, the int8 running-amax
            # scales, and the per-row live mask all ride the loop
            # carry. n_steps (traced) bounds the trip count so every
            # burst size reuses the same compilation; eos_ids < 0 means
            # "no eos" for that row. Sampling draws come from the same
            # per-request (seed, generation position) streams as the
            # per-token path — a request's sampled tokens are identical
            # whether it was served per-token or in bursts.
            from ..kernels.decode_megakernel import (fused_decode_layer,
                                                     fused_decode_model)
            R = self.max_num_seqs
            B = self.burst_tokens
            rows = jnp.arange(R)
            out0 = jnp.zeros((R, B), jnp.int32)
            gen0 = jnp.zeros((R,), jnp.int32)
            if not quant_pool:
                kv_scales = ()
            if scope == "model":
                # stack the pools ONCE per burst (outside the token
                # loop); the while_loop then carries the stacked [L,
                # ...] layout and the scanned body indexes it in place —
                # the stack/unstack round-trip amortizes over the whole
                # burst instead of repeating per token
                kv = (jnp.stack([K for K, _ in kv]),
                      jnp.stack([V for _, V in kv]))
                if quant_pool:
                    kv_scales = (jnp.stack([a for a, _ in kv_scales]),
                                 jnp.stack([b for _, b in kv_scales]))

            def cond(c):
                i, live = c[0], c[5]
                return (i < n_steps) & jnp.any(live)

            def body(c):
                i, tokens, kv, kv_scales, kv_lens, live, gen, out, ok = c
                h = params["embed"][tokens]                  # [R, hid]
                pos = kv_lens                                # append slot
                page_idx = jnp.clip(pos // ps, 0, PPS - 1)
                # rows live at iteration start append this iteration's
                # token; rows that die below stop appending next round
                live_in = live
                page = jnp.where(live, tbls[rows, page_idx], NULL_PAGE)
                off = pos % ps
                att_len = pos + 1       # attention covers the new token
                if scope == "model":
                    # ONE launch for the whole model: the fused layer
                    # body scans over the stacked weights/pools; the
                    # pool writes stay caller-owned closures so they
                    # replay the layer-scope appends bit for bit
                    if quant_pool:
                        def quant_append_fn(Kp, Ks, Vp, Vs, kc, vc):
                            Kp, Ks = _quantized_append(
                                Kp, Ks, jnp.transpose(kc, (1, 0, 2)),
                                page, off, ps, live)
                            Vp, Vs = _quantized_append(
                                Vp, Vs, jnp.transpose(vc, (1, 0, 2)),
                                page, off, ps, live)
                            return Kp, Ks, Vp, Vs
                        h, Kn, Vn, Ksn, Vsn = fused_decode_model(
                            params["layers"], h, kv[0], kv[1], tbls,
                            att_len, eps=cfg.rms_norm_eps,
                            theta=cfg.rope_theta, num_heads=H,
                            self_kv=False, interpret=mk_interpret,
                            k_scales=kv_scales[0],
                            v_scales=kv_scales[1],
                            quant_append_fn=quant_append_fn)
                        new_kv = (Kn, Vn)
                        new_scales = (Ksn, Vsn)
                    else:
                        def append_fn(Kp, Vp, kc, vc):
                            slot = page * ps + off
                            kt = jnp.transpose(kc, (1, 0, 2))
                            vt = jnp.transpose(vc, (1, 0, 2))
                            return (kv_append(Kp, slot, kt,
                                              interpret=interpret),
                                    kv_append(Vp, slot, vt,
                                              interpret=interpret))
                        h, Kn, Vn, _, _ = fused_decode_model(
                            params["layers"], h, kv[0], kv[1], tbls,
                            att_len, eps=cfg.rms_norm_eps,
                            theta=cfg.rope_theta, num_heads=H,
                            self_kv=True, interpret=mk_interpret,
                            append_fn=append_fn)
                        new_kv = (Kn, Vn)
                        new_scales = None
                    hn = _rms_norm(h[None], params["norm"],
                                   cfg.rms_norm_eps)[0]
                    logits = _logits(params, hn, cfg)        # [R, V]
                    ok = ok & (jnp.all(jnp.isfinite(logits), axis=-1)
                               | ~live_in)
                    keys = request_keys(base_key, seeds, gpos0 + gen,
                                        FINAL_TAG)
                    nxt = sample_rows(logits, keys, temps, top_ks,
                                      top_ps)
                    out = out.at[:, i].set(jnp.where(live, nxt, 0))
                    gen = gen + live.astype(jnp.int32)
                    hit_eos = live & (eos_ids >= 0) & (nxt == eos_ids)
                    live = live & ~hit_eos & (gen < caps)
                    kv_lens = kv_lens + live_in.astype(jnp.int32)
                    tokens = jnp.where(live_in, nxt, tokens)
                    return (i + 1, tokens, new_kv,
                            new_scales if quant_pool else kv_scales,
                            kv_lens, live, gen, out, ok)
                new_kv, new_scales = [], []
                for li, (lyr, (Kp, Vp)) in enumerate(
                        zip(params["layers"], kv)):
                    if quant_pool:
                        # append-first: the running-amax requant must be
                        # visible to the attention gather, so k/v are
                        # projected here, quantize-appended, and the
                        # megakernel attends over all att_len positions
                        x = _rms_norm(h[None], lyr["ln1"],
                                      cfg.rms_norm_eps)[0]
                        kc = _rope(_wmat(x, lyr["k"])
                                   .reshape(R, Hkv, d)[None],
                                   pos[None], cfg.rope_theta, d)[0]
                        vc = _wmat(x, lyr["v"]).reshape(R, Hkv, d)
                        Ks, Vs = kv_scales[li]
                        Kp, Ks = _quantized_append(
                            Kp, Ks, jnp.transpose(kc, (1, 0, 2)), page,
                            off, ps, live)
                        Vp, Vs = _quantized_append(
                            Vp, Vs, jnp.transpose(vc, (1, 0, 2)), page,
                            off, ps, live)
                        new_scales.append((Ks, Vs))
                        h, _, _ = fused_decode_layer(
                            lyr, h, Kp, Vp, tbls, att_len,
                            eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
                            num_heads=H, self_kv=False,
                            interpret=mk_interpret, k_scales=Ks,
                            v_scales=Vs)
                    else:
                        # the megakernel computes this token's k/v
                        # in-kernel (self-attention term in-register)
                        # and returns them for the page append —
                        # lossless for fp pools
                        h, kc, vc = fused_decode_layer(
                            lyr, h, Kp, Vp, tbls, att_len,
                            eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
                            num_heads=H, self_kv=True,
                            interpret=mk_interpret)
                        slot = page * ps + off
                        kt = jnp.transpose(kc, (1, 0, 2))    # [Hkv, R, d]
                        vt = jnp.transpose(vc, (1, 0, 2))
                        Kp = kv_append(Kp, slot, kt, interpret=interpret)
                        Vp = kv_append(Vp, slot, vt, interpret=interpret)
                    new_kv.append((Kp, Vp))
                hn = _rms_norm(h[None], params["norm"],
                               cfg.rms_norm_eps)[0]
                logits = _logits(params, hn, cfg)            # [R, V]
                # the per-row isfinite guard, burst edition: a row whose
                # logits go non-finite at ANY loop iteration is flagged;
                # the host aborts it at the burst boundary rather than
                # committing tokens sampled from garbage
                ok = ok & (jnp.all(jnp.isfinite(logits), axis=-1)
                           | ~live_in)
                keys = request_keys(base_key, seeds, gpos0 + gen,
                                    FINAL_TAG)
                nxt = sample_rows(logits, keys, temps, top_ks, top_ps)
                out = out.at[:, i].set(jnp.where(live, nxt, 0))
                gen = gen + live.astype(jnp.int32)
                hit_eos = live & (eos_ids >= 0) & (nxt == eos_ids)
                live = live & ~hit_eos & (gen < caps)
                kv_lens = kv_lens + live_in.astype(jnp.int32)
                tokens = jnp.where(live_in, nxt, tokens)
                return (i + 1, tokens, new_kv,
                        tuple(new_scales) if quant_pool else kv_scales,
                        kv_lens, live, gen, out, ok)

            init = (jnp.asarray(0, jnp.int32), tokens, kv,
                    tuple(kv_scales), kv_lens, live0, gen0, out0,
                    jnp.ones((R,), bool))
            c = jax.lax.while_loop(cond, body, init)
            if scope == "model":
                # unstack the carried [L, ...] pools back into the
                # pool's per-layer list layout (host code indexes it)
                Kn, Vn = c[2]
                new_kv = [(Kn[li], Vn[li]) for li in range(num_layers)]
                if quant_pool:
                    Ksn, Vsn = c[3]
                    new_scales = [(Ksn[li], Vsn[li])
                                  for li in range(num_layers)]
                else:
                    new_scales = None
                return (c[7], c[6], c[8], new_kv, new_scales)
            return (c[7], c[6], c[8], c[2],
                    list(c[3]) if quant_pool else None)

        # donate the pool buffers (args 1-2: pages + scales) so the step
        # updates in place on TPU; CPU/PJRT-cpu ignores donation with a
        # warning, so skip there
        from ..kernels import _on_tpu
        donate = (1, 2) if _on_tpu() else ()
        self._ragged_jit = jax.jit(ragged_step, donate_argnums=donate)
        self._burst_jit = jax.jit(burst_step, donate_argnums=donate)
        # ordinary rounds of a spec-enabled engine still feed the fixed
        # (R, K[, V]) draft operands — build the all-zero versions ONCE
        # instead of allocating + shipping R*K*V float zeros per step
        self._zero_draft = (
            jnp.zeros((self.max_num_seqs, K), jnp.int32),
            jnp.zeros((self.max_num_seqs, K, cfg.vocab_size),
                      jnp.float32))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def add_request(self, prompt_token_ids, *, max_new_tokens=16,
                    temperature=0.0, top_k=None, top_p=None, seed=None,
                    eos_token_id=None, deadline_s=None, abort_after_s=None,
                    request_id=None, tenant_id=None, adapter_id=None):
        """Queue a request; returns its id. Accepts a Request too.

        ``top_k``/``top_p``/``seed`` are per-request sampling state: the
        knobs travel as per-row DATA through the one jitted step, and
        every random draw the request consumes is a pure function of
        ``(seed, generation position)`` — so a fixed (seed, prompt)
        reproduces the same sampled tokens bit for bit regardless of
        what it is co-scheduled with. ``seed=None`` derives a stable
        seed from the request_id.

        An unserviceable request (prompt + max_new_tokens over max_len or
        over the pool's page limit) raises :class:`RequestRejected` AFTER
        recording a finalized aborted output under its id — the serving
        loop and every other in-flight request keep running.
        """
        if isinstance(prompt_token_ids, Request):
            r = prompt_token_ids
            return self.add_request(
                r.prompt_token_ids, max_new_tokens=r.max_new_tokens,
                temperature=r.temperature, top_k=r.top_k, top_p=r.top_p,
                seed=r.seed, eos_token_id=r.eos_token_id,
                deadline_s=r.deadline_s, abort_after_s=r.abort_after_s,
                request_id=r.request_id, tenant_id=r.tenant_id,
                adapter_id=r.adapter_id)
        prompt = [int(t) for t in np.asarray(prompt_token_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if top_k is not None and int(top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        rid = request_id or f"req-{next(self._ids)}"
        if rid in self._seqs or rid in self._outputs:
            raise KeyError(f"duplicate request_id {rid!r}")
        total = len(prompt) + max_new_tokens
        needed = self.pool.pages_for(total)
        limit = min(self.pool.capacity, self.max_pages_per_seq)
        if total > self.max_len or needed > limit:
            self._outputs[rid] = RequestOutput(
                rid, prompt, status="aborted",
                finish_reason="rejected_oversize")
            self.metrics.rejected_requests.inc()
            raise RequestRejected(
                rid, "rejected_oversize", needed_pages=needed, limit=limit,
                message=(
                    f"request {rid}: prompt {len(prompt)} + "
                    f"max_new_tokens {max_new_tokens} needs {needed} pages "
                    f"(limit {limit}) / {total} tokens (max_len "
                    f"{self.max_len}) — rejected at admission"))
        # adapter resolution (paddle_tpu.tenancy): an explicit
        # adapter_id wins; None falls back to the tenant's declared
        # default (or the base model). A request naming an adapter the
        # registry does not hold is REJECTED with a structured output
        # — serving it the base model silently would be a correctness
        # bug, not a degradation.
        if adapter_id is None:
            adapter_id = self.tenant_policy.adapter_for(tenant_id) \
                if self.tenant_policy is not None else 0
        adapter_slot = 0
        if adapter_id not in (0, None):
            from ..tenancy.adapters import UnknownAdapter
            try:
                if self.adapters is None:
                    raise UnknownAdapter(adapter_id)
                adapter_slot = self.adapters.acquire(adapter_id)
            except UnknownAdapter:
                self._outputs[rid] = RequestOutput(
                    rid, prompt, status="aborted",
                    finish_reason="rejected_unknown_adapter")
                self.metrics.rejected_requests.inc()
                raise RequestRejected(
                    rid, "rejected_unknown_adapter",
                    message=(
                        f"request {rid}: adapter {adapter_id!r} is not "
                        f"in the registry "
                        f"({self.adapters.adapter_ids() if self.adapters is not None else 'no registry'}) "
                        f"— publish it (engine.add_adapter / "
                        f"AdapterTuner.publish) before submitting"))
        else:
            adapter_id = 0
        now = self._now()
        seq = Sequence(
            seq_id=rid, prompt_ids=prompt, max_new_tokens=max_new_tokens,
            arrival=now,
            deadline=None if deadline_s is None else now + deadline_s,
            abort_deadline=None if abort_after_s is None
            else now + abort_after_s,
            temperature=temperature,
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p),
            # seeds ride an int32 operand array: mask wide seeds into
            # range instead of blowing up the serving loop at launch
            seed=((int(seed) & 0x7FFFFFFF) if seed is not None
                  else zlib.crc32(str(rid).encode("utf-8")) & 0x7FFFFFFF),
            eos_token_id=eos_token_id, tenant_id=tenant_id,
            adapter_id=adapter_id, adapter_slot=adapter_slot)
        self.scheduler.add(seq)
        self._seqs[rid] = seq
        self._outputs[rid] = RequestOutput(rid, prompt)
        self.metrics.requests_added.inc()
        self._life_span(seq, "serve.queue")
        self._trace(rid, "enqueue", t=now, prompt_len=len(prompt),
                    max_new_tokens=int(max_new_tokens))
        return rid

    def cancel(self, request_id) -> bool:
        """Gracefully cancel: frees pages if running, keeps the tokens
        streamed so far in the output. Returns False if already done."""
        seq = self.scheduler.remove(request_id)
        if seq is None:
            return False
        self._finalize(seq, "cancelled")
        self.metrics.cancelled_requests.inc()
        return True

    def withdraw(self, request_id) -> bool:
        """Remove a WAITING request entirely — the cluster router's
        drain path (serving/cluster.py): the request is requeued onto a
        surviving replica, so THIS engine must forget it without
        recording a terminal output (unlike :meth:`cancel`). Returns
        False for unknown, running, or already-resolved requests —
        running rows stay to finish their drain."""
        seq = self._seqs.get(request_id)
        if seq is None or seq.status is not SequenceStatus.WAITING:
            return False
        if not any(s is seq for s in self.scheduler.waiting):
            return False
        if seq.seq_id in self.pool:
            # a PARKED sequence (two-tier pools) owns pages and streamed
            # tokens: requeueing it elsewhere would silently drop both —
            # it stays to finish its drain, exactly like a running row
            return False
        self.scheduler.waiting = type(self.scheduler.waiting)(
            s for s in self.scheduler.waiting if s is not seq)
        if self._draft is not None:
            self._draft.drop(request_id)
        if self.adapters is not None and seq.adapter_id not in (0, None):
            self.adapters.release(seq.adapter_id)
        self._life_span(seq, None)
        del self._seqs[request_id]
        del self._outputs[request_id]
        return True

    # ------------------------------------------------------------------
    # disaggregated serving: KV handoff (serving/fabric.py KVFabric)
    # ------------------------------------------------------------------
    def _llama_layers_only(self, what):
        if not self._plain_layers:
            raise ValueError(
                f"LLMEngine.{what}: {type(self.cfg).__name__}'s layers are "
                f"not all Llama layers, and the handoff's wire format has "
                f"not been carried over to them")

    def extract_request(self, request_id) -> dict:
        """Pull a caught-up RUNNING request out of this engine for a
        prefill->decode handoff: its committed KV pages leave as the
        host-side layers wire format, its row slot and pages free
        IMMEDIATELY (the prefill-pool win — the slot takes the next
        prompt while the request decodes elsewhere), and the returned
        payload is everything :meth:`inject_request` needs to resume it
        bit-identically on another replica. Only a caught-up row
        (``uncached_len == 1`` with at least the first token sampled)
        extracts — mid-prefill rows keep chunking here."""
        self._llama_layers_only("extract_request")
        seq = self._seqs.get(request_id)
        if seq is None:
            raise KeyError(f"unknown request {request_id!r}")
        if seq.status is not SequenceStatus.RUNNING \
                or seq.uncached_len != 1 or not seq.tokens:
            raise ValueError(
                f"request {request_id!r} is not a caught-up decode row "
                f"(status={seq.status.value}, uncached={seq.uncached_len}, "
                f"tokens={len(seq.tokens)}) — not extractable")
        num_tokens, layers = self.pool.export_pages(request_id,
                                                    seq.cached_len)
        self.scheduler.running.remove(seq)
        self.pool.free(request_id)
        if self._draft is not None:
            self._draft.drop(request_id)
        if self.adapters is not None and seq.adapter_id not in (0, None):
            self.adapters.release(seq.adapter_id)
        self._life_span(seq, None)
        del self._seqs[request_id]
        del self._outputs[request_id]
        self.flight.record("handoff_out", self._now(), request=request_id,
                           pages=self.pool.pages_for(num_tokens))
        return {"request_id": request_id,
                "prompt_ids": list(seq.prompt_ids),
                "tokens": list(seq.tokens),
                "max_new_tokens": seq.max_new_tokens,
                "arrival": seq.arrival,
                "deadline": seq.deadline,
                "abort_deadline": seq.abort_deadline,
                "temperature": seq.temperature,
                "top_k": seq.top_k, "top_p": seq.top_p,
                "seed": seq.seed, "eos_token_id": seq.eos_token_id,
                "num_preemptions": seq.num_preemptions,
                "first_token_at": seq.first_token_at,
                "tenant_id": seq.tenant_id,
                "adapter_id": seq.adapter_id,
                "cached_len": seq.cached_len,
                "num_tokens": num_tokens, "layers": layers}

    def inject_request(self, payload: dict) -> str:
        """Land an extracted request on THIS engine. The transferred
        pages adopt into the pool (two-tier pools stage them in the
        host arena as a PARKED sequence, so re-admission rides the
        cursor-ahead prefetch path; single-tier pools land them in HBM
        directly) and the sequence enqueues as a caught-up decode row —
        its next sampled token is a pure function of (seed, position),
        so the handoff is invisible in the token stream. Counted on
        ``kv_pages_transferred``."""
        self._llama_layers_only("inject_request")
        rid = payload["request_id"]
        if rid in self._seqs or rid in self._outputs:
            raise KeyError(f"duplicate request_id {rid!r}")
        cached_len = int(payload["cached_len"])
        if int(payload["num_tokens"]) != cached_len:
            raise ValueError(
                f"request {rid!r}: payload carries "
                f"{payload['num_tokens']} tokens of KV but cached_len is "
                f"{cached_len}")
        adapter_id = payload.get("adapter_id") or 0
        adapter_slot = 0
        if adapter_id not in (0, None):
            from ..tenancy.adapters import UnknownAdapter
            if self.adapters is None:
                raise UnknownAdapter(adapter_id)
            adapter_slot = self.adapters.acquire(adapter_id)
        self.pool.adopt_sequence(rid, cached_len, payload["layers"])
        seq = Sequence(
            seq_id=rid, prompt_ids=list(payload["prompt_ids"]),
            max_new_tokens=payload["max_new_tokens"],
            arrival=payload["arrival"], deadline=payload["deadline"],
            abort_deadline=payload["abort_deadline"],
            temperature=payload["temperature"],
            top_k=payload["top_k"], top_p=payload["top_p"],
            seed=payload["seed"], eos_token_id=payload["eos_token_id"],
            num_preemptions=payload["num_preemptions"],
            tenant_id=payload.get("tenant_id"),
            adapter_id=adapter_id, adapter_slot=adapter_slot)
        try:
            self.scheduler.add(seq)
        except ValueError:
            self.pool.free(rid)
            if self.adapters is not None and adapter_id not in (0, None):
                self.adapters.release(adapter_id)
            raise
        # carried progress: add() enqueues a WAITING row; these fields
        # make it a caught-up decode row the parked-admission path
        # restores instead of re-prefilling
        seq.tokens = list(payload["tokens"])
        seq.cached_len = cached_len
        seq.first_token_at = payload["first_token_at"]
        self._life_span(seq, "serve.queue")
        self._seqs[rid] = seq
        self._outputs[rid] = RequestOutput(
            rid, list(seq.prompt_ids), token_ids=list(seq.tokens),
            status=seq.status.value, num_preemptions=seq.num_preemptions)
        n_pages = self.pool.pages_for(cached_len)
        self.metrics.kv_pages_transferred.inc(n_pages)
        self.flight.record("handoff_in", self._now(), request=rid,
                           pages=n_pages)
        return rid

    def has_unfinished(self) -> bool:
        return self.scheduler.has_unfinished()

    def outputs(self) -> dict:
        return dict(self._outputs)

    def release(self, request_id) -> "RequestOutput":
        """Drop a RESOLVED request's retained state (the client has
        consumed its output). A long-running server must call this (or
        use stream_cb and release on the finished event) — the engine
        retains finished outputs until released so polling clients can
        always fetch them."""
        out = self._outputs.get(request_id)
        if out is None:
            raise KeyError(f"unknown request {request_id!r}")
        if not out.finished:
            raise ValueError(
                f"request {request_id!r} is still {out.status}; "
                f"cancel() it before release()")
        del self._outputs[request_id]
        self._seqs.pop(request_id, None)
        return out

    # ------------------------------------------------------------------
    # observability (serving/tracing.py)
    # ------------------------------------------------------------------
    def _trace(self, rid, kind, t=None, **detail):
        """Record one request span when a tracer is attached — a plain
        host-side append stamped on now_fn; no-op (one attribute read)
        without a tracer."""
        if self.tracer is not None:
            self.tracer.span(rid, kind, self._now() if t is None else t,
                             **detail)

    def _life_span(self, seq, name):
        """Move the request to the next span of its life: end the one it
        has open (``serve.queue`` or ``serve.prefill``) and begin
        ``name`` at the same instant (None: nothing follows). Log only,
        these cross steps (profiler/spans.py)."""
        now_ns = time.perf_counter_ns()
        cur = self._life.pop(seq.seq_id, None)
        if cur is not None:
            cur.end(now_ns)
        if name is not None:
            self._life[seq.seq_id] = spans.begin(
                name, now_ns, request=seq.seq_id, **self._span_tags)

    def record_fleet_event(self, kind, **detail):
        """Engine-scope event onto the flight recorder (always) and the
        tracer's event stream (when attached) — degradation rung moves,
        fault effects, anything not owned by one request."""
        now = self._now()
        if self.engine_id is not None:
            detail.setdefault("engine", self.engine_id)
        self.flight.record(kind, now, **detail)
        if self.tracer is not None:
            self.tracer.event(kind, now, **detail)

    def flight_dump(self, reason, **detail) -> dict:
        """Snapshot the flight recorder's last-N events as a structured
        post-mortem (counted on ``metrics.flight_dumps``)."""
        if self.engine_id is not None:
            detail.setdefault("engine", self.engine_id)
        self.metrics.flight_dumps.inc()
        return self.flight.dump(reason, t=self._now(), **detail)

    def _zero_step_args(self):
        """The ragged step's arguments as ``_launch`` dispatches them, the
        control buffer all pad rows (the AOT lowering surface — never
        dispatched)."""
        return (self._ragged_params, self.pool.kv, self.pool.kv_scales,
                jnp.asarray(self._operands.host()[0]), *self._zero_draft,
                self._base_key,
                self.adapters.slab if self.adapters is not None else None)

    def _zero_burst_args(self):
        """Zero-filled burst-step operands at the exact launch shapes."""
        R, PPS = self.max_num_seqs, self.max_pages_per_seq
        z = jnp.zeros
        return (self._step_params, self.pool.kv, self.pool.kv_scales,
                z((R,), jnp.int32), z((R,), jnp.int32),
                jnp.full((R, PPS), NULL_PAGE, jnp.int32),
                z((R,), bool), z((R,), jnp.int32), z((R,), jnp.float32),
                z((R,), jnp.int32), jnp.ones((R,), jnp.float32),
                z((R,), jnp.int32), z((R,), jnp.int32),
                jnp.full((R,), -1, jnp.int32),
                jnp.asarray(0, jnp.int32), self._base_key)

    def ragged_step_hlo(self):
        """Compiled HLO text of the ONE ragged-step executable, lowered
        AOT over zero-filled operands at the exact launch shapes
        (jit/hlo_forensics.py parses it; tests/test_hlo_forensics.py and
        tests/test_prefill_megakernel.py read it). Out-of-band by construction:
        the jit dispatch cache and the trace-count==1 gate are
        untouched."""
        return self._ragged_jit.lower(
            *self._zero_step_args()).compile().as_text()

    def ragged_step_lowering(self):
        """UNOPTIMIZED StableHLO of the ragged step — the launch-
        accounting surface (jit/hlo_forensics.launch_stats): a scanned
        layer loop appears as ONE body inside ``stablehlo.while``; the
        unrolled loop appears L times. Pre-optimization by design, so
        the count is the program's structure, not an XLA fusion
        decision."""
        return self._ragged_jit.lower(*self._zero_step_args()).as_text()

    def burst_step_lowering(self):
        """UNOPTIMIZED StableHLO of the burst executable (the on-device
        token loop), for the same launch accounting."""
        return self._burst_jit.lower(*self._zero_burst_args()).as_text()

    def launch_stats(self, burst=False, kinds=None):
        """jit/hlo_forensics.launch_stats over the step executable's
        unoptimized lowering, with this engine's marker constants
        supplied: the fp/int8 ragged layer bodies and the fp burst body
        carry 2 rms_norm (rsqrt) markers each, the int8 burst body
        carries 3 (the pre-append prologue norm), and the final norm is
        the single non-layer marker. ``burst=True`` accounts the burst
        executable, whose one invocation covers up to ``burst_tokens``
        tokens per row.

        ``kinds`` (a ``{name: markers_per_body}`` dict) routes to
        ``mixed_launch_stats`` instead: the ragged step is a MIXED
        invocation (prefill-chunk rows and decode rows share its one
        fixed shape), and the per-kind decomposition attributes the
        body sites — or refuses with ValueError when the marker algebra
        cannot, rather than fabricate a launch count. This engine's
        unified ragged body is one kind (``{"ragged": 2}``); separate
        prefill/decode bodies come from callers gluing programs."""
        from ..jit.hlo_forensics import launch_stats, mixed_launch_stats
        if kinds is not None:
            return mixed_launch_stats(
                self.burst_step_lowering() if burst
                else self.ragged_step_lowering(),
                num_layers=self.cfg.num_hidden_layers, kinds=kinds,
                tokens_per_invocation=self.burst_tokens if burst else 1)
        if burst:
            return launch_stats(
                self.burst_step_lowering(),
                num_layers=self.cfg.num_hidden_layers,
                markers_per_body=3 if self.pool.quantized else 2,
                tokens_per_invocation=self.burst_tokens)
        return launch_stats(
            self.ragged_step_lowering(),
            num_layers=self.cfg.num_hidden_layers,
            markers_per_body=2, tokens_per_invocation=1)

    def metrics_snapshot(self) -> dict:
        if self.adapters is not None:
            # registry counters fold in as deltas so repeated snapshots
            # never double-count a hot-add or eviction
            m = self.metrics
            m.adapter_hot_adds.inc(
                self.adapters.hot_adds - m.adapter_hot_adds.value)
            m.adapter_evictions.inc(
                self.adapters.evictions - m.adapter_evictions.value)
            m.adapter_evict_refusals.inc(
                self.adapters.evict_refusals
                - m.adapter_evict_refusals.value)
            m.adapter_slots_used.set(self.adapters.slots_used)
        snap = self.metrics.snapshot()
        # the step's phases and the requests' waits over the newest
        # records of the always-on span log (profiler/spans.py): a few
        # hundred steps, 2 ms at most of a snapshot once the log is
        # full; replicas of one process share the log, each reads its
        # own by its tag
        snap["spans"] = spans.summary(prefix="serve.", last=2048,
                                      **self._span_tags)
        snap["decode_cache_size"] = self.decode_cache_size()
        snap["burst_tokens"] = self.burst_tokens
        # tensor-parallel forensics: 1 = single-device engine
        snap["model_parallel_degree"] = self.pool.model_parallel_degree
        # two-tier KV forensics (kv_tier.py): per-tier page/byte budgets
        # — None for single-tier engines, so pre-tiering consumers see
        # explicit absence, never a fabricated zero-sized host tier
        snap["kv_hbm_pages"] = self.pool.capacity
        snap["kv_hbm_bytes"] = self.pool.pool_bytes
        if self._tiered:
            snap["kv_host_pages"] = self.pool.arena.capacity
            snap["kv_host_bytes"] = self.pool.host_bytes
            snap["kv_host_chain_promotions"] = \
                self.pool.host_chain_promotions
        else:
            snap["kv_host_pages"] = None
            snap["kv_host_bytes"] = None
            snap["kv_host_chain_promotions"] = None
        from ..kernels.decode_megakernel import megakernel_mode
        snap["megakernel_mode"] = megakernel_mode(
            self.params["layers"][0],
            interpret=self._interpret if self._interpret_explicit
            else None) if self.burst_tokens > 1 else None
        snap["megakernel_scope"] = self.megakernel_scope
        # fused ragged prefill forensics: the resolved flag plus the
        # honest kernel-tier report (Pallas / interpret / jnp fallback)
        # — "unfused" engines report mode None, never a fabricated tier
        snap["prefill_megakernel"] = self.prefill_megakernel
        if self._fused_layers is not None:
            from ..kernels.prefill_megakernel import \
                prefill_megakernel_mode
            snap["prefill_megakernel_mode"] = prefill_megakernel_mode(
                self._fused_layers[0],
                interpret=self._interpret if self._interpret_explicit
                else None)
        else:
            snap["prefill_megakernel_mode"] = None
        tok = snap["tokens_generated"]
        snap["host_dispatches_per_token"] = \
            snap["host_dispatches"] / tok if tok else None
        # speculative-decoding forensics: target launches per committed
        # token is the headline win (< 1.0 iff speculation pays), draft
        # trace count mirrors the engine's one-executable discipline
        snap["spec_tokens"] = self.spec_tokens
        snap["target_steps_per_token"] = \
            snap["decode_steps"] / tok if tok else None
        snap["draft_launches"] = \
            self._draft.launches if self._draft is not None else None
        snap["draft_decode_compiles"] = \
            self._draft.decode_cache_size() if self._draft is not None \
            else None
        # the k-step proposal loop is ONE scan executable (and one
        # launch per spec round) — the ROADMAP item 4 leftover's gate
        snap["draft_propose_compiles"] = \
            self._draft.propose_cache_size() if self._draft is not None \
            else None
        # multi-tenancy forensics: slab capacity + per-tenant ledgers —
        # explicit None for single-tenant engines, never fabricated zeros
        snap["adapter_slots"] = \
            self.adapters.n_slots if self.adapters is not None else None
        snap["tenants"] = \
            self.tenant_policy.snapshot() \
            if self.tenant_policy is not None else None
        return snap

    def decode_cache_size(self):
        """Actual XLA compile count of the ragged step — the compile gate
        asserts this stays 1 under ANY traffic mix (falls back to the
        launch-signature count when the jit cache is not introspectable).
        """
        try:
            return int(self._ragged_jit._cache_size())
        except Exception:
            return 1 if self._step_launched else 0

    def step(self):
        """One scheduler round: shed -> admit (prefix-fork) -> one
        device launch covering every running row. When every row is a
        caught-up decode row and ``burst_tokens > 1``, the launch is an
        on-device generation BURST (up to burst_tokens tokens per row,
        one host dispatch); otherwise it is one ragged step (decode
        steps and prefill chunks interleaved). Returns the
        RequestOutputs touched this step (admitted, token streamed,
        finished, shed, or preempted).

        The step is the span ``serve.step``, cut into the phases
        ``serve.plan`` / ``.assemble`` / ``.dispatch`` / ``.wait`` /
        ``.commit`` (``.draft`` on a speculative round); what it carried
        is counted on it where the work happens (profiler/spans.py,
        docs/OBSERVABILITY.md)."""
        with spans.span("serve.step", **self._span_tags) as sp:
            return self._step(sp)

    def _step(self, sp):
        touched = {}
        sp.phase("serve.plan")
        if self._tiered:
            # advance the pool's virtual round clock FIRST: a restore
            # this step claims at clock c, so a prefetch issued at the
            # END of the previous step (clock c-1) classifies as a hit
            # — the deterministic hit-vs-stall rule (kv_tier.py)
            self.pool.tick()
        for seq in self.scheduler.shed_expired():
            self._finalize(seq, "shed")
            touched[seq.seq_id] = self._outputs[seq.seq_id]
        # mid-flight SLO abort: running/waiting rows whose absolute e2e
        # deadline passed finalize HERE, at the step boundary — pages
        # freed through the normal finish path (CoW refcounts and
        # pinned chains intact), no more tokens decoded for them
        for seq in self.scheduler.abort_expired():
            self.metrics.deadline_aborts.inc()
            self._finalize(seq, "shed", reason="deadline_exceeded")
            touched[seq.seq_id] = self._outputs[seq.seq_id]
        if self.tenant_policy is not None:
            # quota shed: still-WAITING rows of tenants whose refilling
            # token bucket is exhausted beyond the grace window leave
            # with a structured reason instead of starving the queue
            for seq in self.scheduler.shed_quota():
                self.metrics.quota_shed_requests.inc()
                self.tenant_policy.count_shed(seq.tenant_id)
                self._finalize(seq, "shed",
                               reason=seq.shed_reason or "quota_exceeded")
                touched[seq.seq_id] = self._outputs[seq.seq_id]
        hook = self._prefix_probe if self.prefix_caching else None
        for seq in self.scheduler.admit(prefix_hook=hook):
            touched[seq.seq_id] = self._sync_output(seq)
            # a row preempted after its first token re-prefills unseen
            self._life_span(
                seq, "serve.prefill" if seq.first_token_at is None else None)
            if self.tracer is not None:
                now = self._now()
                extra = {} if seq.tenant_id is None \
                    else {"tenant": seq.tenant_id}
                self._trace(
                    seq.seq_id, "admission", t=now,
                    prefix_shared=seq.cached_len,
                    queue_s=now - (seq.enqueued_at
                                   if seq.enqueued_at is not None
                                   else seq.arrival), **extra)
        plan = None
        bplan = None
        splan = None
        preempted = []
        if self._draft is not None and self.spec_enabled:
            # speculative round: eligible only when every running row is
            # a caught-up decode row (prompt chunks go through the
            # ordinary ragged path; the draft catches up lazily).
            # spec_enabled is the degradation ladder's runtime kill
            # switch: it gates ELIGIBILITY only — operand shapes (and
            # the one compiled executable) never change with it.
            splan = self.scheduler.prepare_spec(self.spec_tokens)
            preempted += self.scheduler.last_preempted
        if splan is None and self.burst_tokens > 1:
            bplan = self.scheduler.prepare_burst(self.burst_tokens)
            preempted += self.scheduler.last_preempted
        if splan is None and bplan is None:
            plan = self.scheduler.prepare_step()
            preempted += self.scheduler.last_preempted
        for t in preempted:
            if self._draft is not None:
                self._draft.drop(t.seq_id)  # recompute re-syncs from 0
            self._sync_output(t)           # surface fresh preemptions once
            touched[t.seq_id] = self._outputs[t.seq_id]
            self._trace(t.seq_id, "preempt",
                        num_preemptions=t.num_preemptions)
            self.flight.record("preempt", self._now(), request=t.seq_id)
            self._life_span(t, "serve.queue")
        if splan is not None:
            if splan.cow_copies:
                self.metrics.cow_copies.inc(splan.cow_copies)
            if self._launch_spec(splan, touched, sp):
                self.metrics.decode_steps.inc()
                self.metrics.ragged_pad_fraction.set(splan.pad_fraction)
            else:
                # the DRAFT pool could not serve the round (operator
                # under-sized draft_num_pages): speculation is demoted
                # to an ordinary decode round — target pressure
                # preempts, draft pressure must never kill the loop
                splan = None
                sp.phase("serve.plan")
                plan = self.scheduler.prepare_step()
                for t in self.scheduler.last_preempted:
                    self._draft.drop(t.seq_id)
                    self._sync_output(t)
                    touched[t.seq_id] = self._outputs[t.seq_id]
                    self._life_span(t, "serve.queue")
        if splan is None and bplan is not None:
            if bplan.cow_copies:
                self.metrics.cow_copies.inc(bplan.cow_copies)
            self._launch_burst(bplan, touched, sp)
            self.metrics.decode_steps.inc()
            # pad fraction is a ragged-packing concept; zero it so the
            # gauge never freezes on a stale prefill step's value while
            # bursts serve the traffic
            self.metrics.ragged_pad_fraction.set(0.0)
        elif plan is not None:
            if plan.cow_copies:
                self.metrics.cow_copies.inc(plan.cow_copies)
            sampled, _, finite = self._launch(plan, sp)
            sp.phase("serve.commit")
            step_prefill_rows = 0
            for i, (seq, q_start, q_len) in enumerate(plan.rows):
                if not finite[i]:
                    # NaN/Inf logits: the row's state (this step's KV
                    # appends included) is poison — abort the request
                    # with a structured error BEFORE any commit or
                    # prefix registration could propagate it
                    self._abort_nonfinite(seq)
                    touched[seq.seq_id] = self._outputs[seq.seq_id]
                    continue
                before = seq.cached_len
                seq.cached_len += q_len
                # a prefill-chunk row is one that committed prompt tokens
                # (incl. a 1-token final chunk) or any multi-token
                # recompute chunk; pure decode rows start caught-up past
                # the prompt
                if q_len > 1 or before < len(seq.prompt_ids):
                    self.metrics.prefill_chunks.inc()
                    step_prefill_rows += 1
                    life = self._life.get(seq.seq_id)
                    if life is not None:
                        life.set(chunks=life.attrs.get("chunks", 0) + 1)
                if self.prefix_caching and \
                        before < len(seq.prompt_ids) <= seq.cached_len:
                    self._register_prefix(seq)
                caught_up = seq.cached_len == seq.total_len
                if caught_up:
                    # the row is caught up: its sampled token is the next
                    # generated token. Mid-prompt chunks discard theirs.
                    self._commit_token(seq, int(sampled[i, 0]))
                if self.tracer is not None:
                    if q_len > 1 or before < len(seq.prompt_ids):
                        self._trace(seq.seq_id, "prefill_chunk",
                                    q_len=int(q_len),
                                    cached=int(seq.cached_len),
                                    new_tokens=1 if caught_up else 0,
                                    fused=self.prefill_megakernel
                                    == "fused")
                    else:
                        # a 1-token recompute row inside the generated
                        # region commits nothing until it catches up
                        self._trace(seq.seq_id, "decode",
                                    new_tokens=1 if caught_up else 0)
                touched[seq.seq_id] = self._outputs[seq.seq_id]
            self.metrics.decode_steps.inc()
            if step_prefill_rows:
                # the ragged step is ONE executable: a step serving any
                # number of prefill-chunk rows is ONE prefill launch
                self.metrics.prefill_launches.inc()
            self.metrics.ragged_pad_fraction.set(plan.pad_fraction)
        if self._tiered or self.tenant_policy is not None:
            sp.phase("serve.commit")
        if self._tiered:
            # cursor-ahead prefetch: issue background staging for the
            # parked sequences the NEXT admission round will restore —
            # the staging thread gets a full step of compute to overlap
            for sid in self.scheduler.prefetch_candidates(
                    self._kv_prefetch_depth):
                self.pool.prefetch(sid)
            # tier events (stalls, host-chain promotions) surface on
            # the flight recorder (+ tracer span for request-owned
            # stalls) in the deterministic order the pool recorded them
            for kind, detail in self.pool.drain_events():
                self.flight.record(kind, self._now(), **detail)
                rid = detail.get("request")
                if rid is not None:
                    self._trace(rid, kind,
                                **{k: v for k, v in detail.items()
                                   if k != "request"})
        if self.tenant_policy is not None:
            # cost attribution on the engine's own clock: KV byte-seconds
            # for resident pages and adapter-slot residency seconds accrue
            # against the owning tenant's ledger every step
            now = self._now()
            dt = (now - self._last_cost_t) \
                if self._last_cost_t is not None else 0.0
            self._last_cost_t = now
            if dt > 0:
                bpt = self.pool.kv_bytes_per_token
                for seq in self.scheduler.running:
                    self.tenant_policy.charge_kv(
                        seq.tenant_id, seq.cached_len * bpt * dt)
                    if seq.adapter_slot:
                        self.tenant_policy.charge_slot(seq.tenant_id, dt)
        # what is left of the step is serve.step's own time
        sp.phase(None)
        self.metrics.record_step(self.scheduler, self.pool)
        # one O(1) flight-recorder entry per step: the bounded last-N
        # context a post-mortem dump replays (ints only — cheap and
        # deterministic)
        f = {"running": len(self.scheduler.running),
             "waiting": len(self.scheduler.waiting),
             "used_pages": self.pool.used_pages,
             "tokens": self.metrics.tokens_generated.value}
        if self.engine_id is not None:
            f["engine"] = self.engine_id
        self.flight.record("step", self._now(), **f)
        sp.set(used_pages=f["used_pages"], num_pages=self.pool.capacity,
               max_num_seqs=self.max_num_seqs)
        if self.pool.window_layers:
            sp.set(window_pages_used=self.pool.window_pages_used,
                   window_pages=self.pool.window_capacity)
        return list(touched.values())

    def run(self, max_steps=None):
        """Drive step() until every request resolves; returns outputs."""
        steps = 0
        while self.has_unfinished():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps")
        return self.outputs()

    # ------------------------------------------------------------------
    # prefix cache
    # ------------------------------------------------------------------
    def _register_prefix(self, seq: Sequence):
        """Index the sequence's prompt as a fork donor: one entry per
        page-aligned prefix plus the full prompt (the identical-prompt
        fast path, which shares even the partial tail page). Newest
        registration wins, so a chain stays alive as long as ANY sharer
        of its pages is — entries whose donor left the pool fail the
        probe's liveness re-validation and are simply re-prefilled. The
        map is LRU-bounded (``prefix_cache_size``): re-registration
        refreshes recency, the oldest entries fall off — a long-running
        server's cache footprint is capped, not proportional to every
        prompt ever served."""
        P = seq.prompt_ids
        ps = self.page_size
        for j in list(range(ps, len(P) + 1, ps)) + [len(P)]:
            key = tuple(P[:j])
            self._prefix_cache.pop(key, None)      # refresh LRU position
            self._prefix_cache[key] = (seq.seq_id, j)
        while len(self._prefix_cache) > self.prefix_cache_size:
            self._prefix_cache.pop(next(iter(self._prefix_cache)))
        # pinned-LRU registration: the FULL pages of the prompt prefix
        # get an rc floor in the pool, so the chain survives its last
        # sequence sharer (up to the pinned-page budget) and repeated
        # cold prompts re-fork instead of re-prefilling. Only full pages
        # pin: partial tail pages are append targets (and, int8, requant
        # targets) — they must die with their writers.
        full = (len(P) // ps) * ps
        if full >= ps and self.pool.pinned_page_budget > 0:
            chain = tuple(P[:full])
            if self.pool.pin(chain, seq.seq_id, full):
                for j in list(range(ps, full + 1, ps)):
                    key = tuple(P[:j])
                    self._pinned_index.pop(key, None)
                    self._pinned_index[key] = (chain, j)
                while len(self._pinned_index) > self.prefix_cache_size:
                    self._pinned_index.pop(next(iter(self._pinned_index)))
                if self.fleet_prefix is not None \
                        and not self.fleet_prefix.contains(chain):
                    # fleet publication: one device->host export per NEW
                    # chain (content-addressed — a chain already in the
                    # fleet index costs one dict probe). Any replica in
                    # either pool can now fault these pages in.
                    self.fleet_prefix.publish(
                        chain, full, self.pool.export_chain(chain),
                        self.pool.config(), page_size=ps)
            if self._prefix_autosave:
                # write-ahead warm-start discipline: the pin set changed
                # (or an eviction shifted it) — persist the chains NOW,
                # because a crash never schedules a save first.
                # save_prefix_store no-ops when membership is unchanged.
                self.save_prefix_store()

    # ---- persistent prefix store (io/persist.py) ----
    PREFIX_STORE_TAG = "prefix_store"

    def export_prefix_store(self):
        """Serialize the pool's pinned chains + the engine's token-chain
        index as an (arrays, meta) pair for
        :meth:`~paddle_tpu.io.persist.ArtifactStore.save`. Chain ids at
        the engine level ARE the token tuples, so the index restores
        content-addressed — no donor liveness to re-validate."""
        chains = self.pool.export_pinned()
        arrays = {}
        meta_chains = []
        for ci, ch in enumerate(chains):
            for li, ent in enumerate(ch["layers"]):
                for part, arr in ent.items():
                    arrays[f"c{ci}/L{li}/{part}"] = arr
            meta_chains.append({"tokens": [int(t) for t in ch["chain_id"]],
                                "num_tokens": int(ch["num_tokens"])})
        meta = {"format": 1, "config": self.pool.config(),
                "chains": meta_chains}
        return arrays, meta

    def save_prefix_store(self) -> bool:
        """Persist the current pinned-chain set (atomic, versioned,
        checksummed). No-op without a store or without pins changed
        since the last save. Counted on ``prefix_store_saves``.

        Cost: one device->host copy + npz write of EVERY pinned chain —
        O(pinned bytes), bounded by ``pinned_prefix_pages`` (pin churn
        amortizes through the membership-signature dedup). Deployments
        with large pin budgets under heavy churn should construct with
        ``prefix_store_autosave=False`` and call this explicitly at
        drain/idle boundaries instead."""
        if self.prefix_store is None:
            return False
        sig = frozenset(self.pool._pins) \
            | frozenset(getattr(self.pool, "_host_chains", ()))
        if sig == self._prefix_store_sig:
            return False
        arrays, meta = self.export_prefix_store()
        self.prefix_store.save(self.PREFIX_STORE_TAG, arrays, meta)
        self._prefix_store_sig = sig
        self.metrics.prefix_store_saves.inc()
        return True

    # ------------------------------------------------------------------
    # adapter registry (tenancy/adapters.py)
    # ------------------------------------------------------------------
    def add_adapter(self, adapter_id, arrays) -> int:
        """Hot-publish a LoRA adapter into the serving slab — an in-place
        ``.at[slot].set`` on the stacked factors, so slab SHAPES never
        change and the ragged executable never retraces. Returns the
        slot. Re-publishing an id updates it in place (new requests see
        the new factors; in-flight rows keep decoding on the slab they
        were launched with)."""
        if self.adapters is None:
            raise ValueError(
                "engine was built without adapter_slots; construct with "
                "adapter_slots=N to serve LoRA adapters")
        slot = self.adapters.add(adapter_id, arrays)
        self.flight.record("adapter_add", self._now(),
                           adapter=str(adapter_id), slot=slot)
        if self._adapter_autosave:
            self.save_adapters()
        return slot

    def evict_adapter(self, adapter_id):
        """Drop an adapter from the slab (slot zeroes back to the base
        identity). Refuses with :class:`~paddle_tpu.tenancy.adapters.
        AdapterInUse` while any in-flight request references it."""
        if self.adapters is None:
            raise ValueError("engine has no adapter registry")
        self.adapters.evict(adapter_id)
        self.flight.record("adapter_evict", self._now(),
                           adapter=str(adapter_id))
        if self._adapter_autosave:
            self.save_adapters()

    def save_adapters(self) -> bool:
        """Persist the adapter slab (atomic, versioned, checksummed via
        io/persist.py). No-op without a store or without publishes since
        the last save. Counted on ``adapter_store_saves``."""
        if self.adapter_store is None or self.adapters is None \
                or not self.adapters.dirty:
            return False
        if self.adapters.save(self.adapter_store) is None:
            return False
        self.metrics.adapter_store_saves.inc()
        return True

    def _restore_prefix_store(self):
        """Warm-reload pinned chains at construction. Failure ladder:
        geometry/dtype drift raises :class:`PrefixStoreMismatch`
        (operator error); everything else — no store yet, every version
        corrupt, a chain that no longer fits the budget — degrades to a
        cold start with the ``restore_fallbacks`` counter and a flight-
        recorder event. Silent wrong KV bytes are impossible: data
        arrives checksum-verified or not at all."""
        store = self.prefix_store
        tag = self.PREFIX_STORE_TAG
        res = store.load(tag)
        if res is None:
            if store.versions(tag):
                # versions exist but none verified: a real loss, not a
                # first boot — count it and leave a post-mortem trail
                self.metrics.restore_fallbacks.inc()
                self.record_fleet_event(
                    "prefix_restore_fallback", reason="all_corrupt",
                    versions=len(store.versions(tag)))
            return
        if res.fallbacks:
            # a newer version was torn/corrupt and an older one served:
            # the warm start still happens, but the loss is visible
            self.metrics.restore_fallbacks.inc(res.fallbacks)
            self.record_fleet_event(
                "prefix_restore_fallback", reason="stale_version",
                served_version=res.version, skipped=res.fallbacks)
        live = self.pool.config()
        stored = dict(res.meta.get("config", {}))
        if stored != live:
            raise PrefixStoreMismatch(live, stored)
        restored = 0
        for ci, ch in enumerate(res.meta.get("chains", [])):
            tokens = tuple(int(t) for t in ch["tokens"])
            n = int(ch["num_tokens"])
            layers = []
            try:
                for li in range(self.pool.num_layers):
                    ent = {"K": res.arrays[f"c{ci}/L{li}/K"],
                           "V": res.arrays[f"c{ci}/L{li}/V"]}
                    if self.pool.quantized:
                        ent["Ks"] = res.arrays[f"c{ci}/L{li}/Ks"]
                        ent["Vs"] = res.arrays[f"c{ci}/L{li}/Vs"]
                    layers.append(ent)
            except KeyError:
                # manifest verified, so a missing leaf means the chain
                # was saved under a different pool mode (fp chains into
                # an int8 pool slips past the config gate only when the
                # configs were hand-edited) — skip it, count it
                self.metrics.restore_fallbacks.inc()
                continue
            try:
                ok = self.pool.restore_pinned_chain(tokens, n, layers)
            except ValueError as e:
                raise PrefixStoreMismatch(
                    live, dict(stored, chain_error=str(e)))
            if not ok:
                continue                 # over budget: cache, not demand
            ps = self.page_size
            for j in range(ps, n + 1, ps):
                key = tokens[:j]
                self._pinned_index.pop(key, None)
                self._pinned_index[key] = (tokens, j)
            restored += 1
        while len(self._pinned_index) > self.prefix_cache_size:
            self._pinned_index.pop(next(iter(self._pinned_index)))
        if restored:
            self.metrics.prefix_chains_restored.inc(restored)
            self.record_fleet_event("prefix_restore", chains=restored,
                                    version=res.version)
        # membership signature spans BOTH tiers: a host-tier chain
        # promoting to HBM (or being added/evicted) must re-arm the
        # autosave dedup like any pin-set change
        self._prefix_store_sig = frozenset(self.pool._pins) \
            | frozenset(getattr(self.pool, "_host_chains", ()))

    def _prefix_probe(self, seq: Sequence) -> int:
        """Admission hook: longest registered chain matching the prompt
        -> fork the donor's pages. Returns the shared (committed) token
        count, 0 on miss. The last prompt token is never shared — its
        logits must be computed to sample the first generated token — so
        an identical prompt re-runs exactly one token, whose append
        copy-on-writes the shared tail page."""
        P = seq.prompt_ids
        ps = self.page_size
        cands = sorted({len(P)} | set(range(ps, len(P) + 1, ps)),
                       reverse=True)
        for j in cands:
            ent = self._prefix_cache.get(tuple(P[:j]))
            if ent is None:
                continue
            donor, length = ent
            if donor == seq.seq_id or donor not in self.pool:
                continue
            if self._tiered and not self.pool.fully_resident(donor):
                # a parked donor's prefix may be spilled: forking would
                # map host sentinels into the child — skip (the pinned
                # index below may still serve the chain)
                continue
            if self.pool.seq_len(donor) < length:
                continue
            # a request_id can be reused after release(): the entry's
            # donor id may now name a DIFFERENT prompt's pages, so the
            # chain must be re-validated against the donor's actual
            # prompt tokens, not just its liveness
            donor_seq = self._seqs.get(donor)
            if donor_seq is None or \
                    donor_seq.prompt_ids[:j] != P[:j]:
                continue
            shared = min(j, len(P) - 1)
            if self.pool.quantized:
                # int8 pages requantize in place on append; only FULL
                # (append-free) pages are safe to share without a copy
                shared = (shared // ps) * ps
            if shared < 1:
                continue
            self.pool.fork(seq.seq_id, donor, num_tokens=shared)
            self.metrics.prefix_cache_hits.inc()
            return shared
        # no LIVE donor: fall back to the pinned-LRU chains — a prefix
        # whose last sharer already left the pool can still be forked
        # as long as its pin survived (budget LRU / pressure eviction)
        for j in cands:
            ent = self._pinned_index.get(tuple(P[:j]))
            if ent is None:
                continue
            chain, length = ent
            if not self.pool.is_pinned(chain):
                self._pinned_index.pop(tuple(P[:j]), None)   # evicted
                continue
            # pinned chains are full pages, registered under their exact
            # token tuple — content revalidation is the key itself. The
            # last prompt token is never shared (its logits seed the
            # first generated token); int8 full-page-only is automatic.
            shared = min(j, len(P) - 1)
            if self.pool.quantized:
                shared = (shared // ps) * ps
            if shared < 1:
                continue
            try:
                self.pool.fork_pinned(seq.seq_id, chain, shared)
            except PoolExhausted:
                # a HOST-tier chain (two-tier warm restart) could not
                # promote into HBM right now — treat as a miss rather
                # than killing admission; it stays restorable later
                continue
            self.metrics.prefix_cache_hits.inc()
            self.metrics.pinned_prefix_hits.inc()
            return shared
        # no local donor and no local pin: the FLEET prefix cache — a
        # chain some other replica published lands here through the
        # same two-tier restore + fork machinery the warm-restart store
        # uses. Store-backed bytes are checksum-verified; a geometry
        # mismatch is a counted miss, never a wrong-shape fork.
        if self.fleet_prefix is not None and self.pool.pinned_page_budget:
            for j in cands:
                if j % ps:
                    continue           # fleet chains are full pages only
                hit = self.fleet_prefix.lookup(tuple(P[:j]),
                                               self.pool.config())
                if hit is None:
                    continue
                chain, length, layers = hit
                if not self.pool.is_pinned(chain):
                    if not self.pool.restore_pinned_chain(
                            chain, length, layers):
                        continue       # over pin budget: cache, not demand
                shared = min(j, len(P) - 1)
                if self.pool.quantized:
                    shared = (shared // ps) * ps
                if shared < 1:
                    continue
                try:
                    self.pool.fork_pinned(seq.seq_id, chain, shared)
                except PoolExhausted:
                    continue
                for k in range(ps, length + 1, ps):
                    key = chain[:k]
                    self._pinned_index.pop(key, None)
                    self._pinned_index[key] = (chain, k)
                while len(self._pinned_index) > self.prefix_cache_size:
                    self._pinned_index.pop(next(iter(self._pinned_index)))
                self.metrics.prefix_cache_hits.inc()
                self.metrics.fleet_prefix_hits.inc()
                return shared
        self.metrics.prefix_cache_misses.inc()
        return 0

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _launch(self, plan, sp, draft_tokens=None, draft_probs=None):
        """Assemble the fixed-shape operands for the plan and run the one
        ragged-step executable: one put, one call, one read-back
        (``metrics.host_transfers`` counts the two transfers; a
        speculative round's candidates are one more). Returns ``(out
        [R, K+1], n_out [R], finite [R])`` — ordinary rounds commit
        ``out[i, 0]`` (n_out is 1), speculative rounds commit
        ``out[i, :n_out[i]]``; a row with ``finite[i] == False``
        produced NaN/Inf logits and must be aborted instead of committed
        (the in-graph isfinite guard).

        ``sp`` is the step's span: the launch is its phases
        ``serve.assemble`` (the rows written into views of the one
        control buffer, ``spec_decode.StepOperands``),
        ``serve.dispatch`` (the put and the call, until it returns) and
        ``serve.wait`` (the host blocked on the one array of results),
        and what the step carries is counted on it from the operands
        built here."""
        PPS, K = self.max_pages_per_seq, self.spec_tokens
        sp.phase("serve.assemble")
        m = self.metrics
        m.host_dispatches.inc()
        first = not self._step_launched
        if first:
            self._step_launched = True
            m.decode_compiles.inc()
        # the rows go straight into views of the one control buffer
        buf, o = self._operands.host()
        tokens, positions, tbls = o["tokens"], o["positions"], o["tbls"]
        q_starts, q_lens, kv_lens = o["q_starts"], o["q_lens"], o["kv_lens"]
        sample_idx, spec_lens = o["sample_idx"], o["spec_lens"]
        temps, top_ks, top_ps = o["temps"], o["top_ks"], o["top_ps"]
        seeds, sample_pos = o["seeds"], o["sample_pos"]
        tbls_w, slot_ids = o.get("tbls_w"), o.get("slot_ids")
        specs = plan.spec_lens
        prefill_tokens = 0
        for i, (seq, q_start, q_len) in enumerate(plan.rows):
            ids = seq.all_ids
            lo = seq.cached_len
            spec = specs[i] if specs is not None else 0
            if spec > 0:
                # verification chunk: the row's one uncached token plus
                # its draft candidates (not part of all_ids yet)
                row_toks = [ids[lo]] + [int(t) for t in
                                        draft_tokens[i, :spec]]
            else:
                row_toks = ids[lo:lo + q_len]
                if q_len > 1 or lo < len(seq.prompt_ids):
                    # a prefill-chunk row, as step() counts them
                    prefill_tokens += q_len
            tokens[q_start:q_start + q_len] = row_toks
            positions[q_start:q_start + q_len] = np.arange(lo, lo + q_len)
            tbls[i] = self.pool.padded_block_table(seq.seq_id, PPS)
            if tbls_w is not None:
                tbls_w[i] = self.pool.padded_window_table(seq.seq_id, PPS)
            q_starts[i] = q_start
            q_lens[i] = q_len
            kv_lens[i] = lo + q_len
            last = q_start + q_len - 1
            sample_idx[i] = np.clip(last - spec + np.arange(K + 1),
                                    0, last)
            temps[i] = seq.temperature
            top_ks[i] = seq.top_k or 0
            top_ps[i] = 1.0 if seq.top_p is None else seq.top_p
            seeds[i] = seq.seed
            sample_pos[i] = len(seq.tokens)
            spec_lens[i] = spec
            if slot_ids is not None and seq.adapter_slot:
                slot_ids[q_start:q_start + q_len] = seq.adapter_slot
        live_kv = int(kv_lens.sum())
        sp.set(rows=len(plan.rows), prefill_tokens=prefill_tokens,
               decode_tokens=int(q_lens.sum()) - prefill_tokens,
               # what attention must read: every row's context
               live_kv_tokens=live_kv,
               **_sampler_counts(temps, top_ks, top_ps))
        if self._latent:
            # what the latent kernel's FLOPs stand on: over the layers,
            # the rows and each row's query tokens, the keys each sees
            # (the token at position p sees p + 1); and the bytes the
            # pool really holds for the rows aboard: its pages in use
            # now, this step's appends claimed, over every layer, the
            # rows' lane padding included
            ql, kl = q_lens.astype(np.int64), kv_lens.astype(np.int64)
            sp.set(attn_qk_pairs=len(self._kinds) * int(
                (ql * (kl - ql) + ql * (ql + 1) // 2).sum()),
                latent_bytes_held=self.pool.used_pages
                * self.pool.page_bytes)
        else:
            # by layer kind: the layers that see every key, and those
            # that see a window (all of one width, LLMEngine.__init__)
            window = self.pool.window
            n_win = len(self.pool.window_layers)
            n_full = len(self._kinds) - n_win

            def walked(w):
                return ragged_kv_tokens_read(
                    q_lens, kv_lens, q_block=self.q_block,
                    page_size=self.page_size, pages_per_seq=PPS, window=w)
            # a child span of its own, so the device's idle time under
            # these always-on counts has a name in a trace's breakdown
            with spans.span("serve.assemble.counts"):
                sp.set(
                    # the rows' contexts summed over the layers, a window
                    # layer's rows counted up to window + chunk
                    attn_kv_tokens_live=n_full * live_kv + (
                        n_win and n_win * int(
                            np.minimum(kv_lens, window + q_lens).sum())),
                    # what the ragged kernel's walk covers, a kv head, a
                    # layer (the mean over layers where they differ)
                    attn_kv_tokens_read=(
                        (n_full and n_full * walked(None))
                        + (n_win and n_win * walked(window)))
                    // len(self._kinds))
        sp.phase("serve.dispatch")
        if draft_tokens is None:
            # ordinary round: the prebuilt zero operands on the device
            draft_tokens, draft_probs = self._zero_draft
        else:
            # a speculative round's candidates are a put of their own
            # (draft_probs [R, K, V] is the draft's device array), made
            # here so that the call sees a device array on every round
            draft_tokens = jax.device_put(draft_tokens)
            m.host_transfers.inc()
        # the host buffer goes to the call as it is: the executable's own
        # argument handling makes the one put, 0.3 ms a step sooner than
        # jax.device_put + the call (PERF.md section 6, PR 32)
        args = (self._ragged_params, self.pool.kv, self.pool.kv_scales, buf,
                draft_tokens, draft_probs, self._base_key,
                self.adapters.slab if self.adapters is not None else None)
        specs = phases.launch_specs(args) if first else None
        back, new_kv, new_scales = self._ragged_jit(*args)
        m.host_transfers.inc()
        if first:
            self._register_step(specs)
        self.pool.kv = new_kv
        if new_scales is not None:
            self.pool.kv_scales = new_scales
        sp.phase("serve.wait")
        out, n_out, finite = self._operands.read_results(np.asarray(back))
        m.host_transfers.inc()
        R = len(finite)
        if len(n_out) > R:
            # routed layers' counts, behind the rows' (ragged_step)
            pairs, touched, most = (int(x) for x in n_out[R:])
            sp.set(moe_pairs_held=pairs, moe_experts_touched=touched,
                   moe_max_expert_tokens=most)
            n_out = n_out[:R]
        return out, n_out, finite

    def _register_step(self, specs):
        """Hand the step executable's compiled handle to
        ``profiler/phases.py`` (device time by phase), once, after the
        first launch: lowering over the launch's own shapes hits JAX's
        in-memory caches, so this traces and compiles nothing and leaves
        ``decode_cache_size()`` where it was. The registry keeps the
        handle, which holds no weights, past this engine's life."""
        name = "serve.step" if self.engine_id is None \
            else f"serve.step:{self.engine_id}"
        with spans.span("serve.register"):      # what it costs, once
            phases.register(name, self._ragged_jit.lower(*specs).compile())

    def _launch_spec(self, plan, touched, sp):
        """One speculative round: draft sync + k proposal steps, then
        ONE target launch verifying every row's k+1 positions through
        the ordinary ragged executable. Accepted tokens commit through
        the normal path (streaming, eos/length finalization); the
        rejected tail rolls the pool's committed length back WITHOUT
        freeing pages (the slots are garbage the next append
        overwrites), and the draft pool rolls back the same way."""
        K = self.spec_tokens
        R = self.max_num_seqs
        seqs = [seq for seq, _, _ in plan.rows]
        spec_lens = plan.spec_lens
        sp.phase("serve.draft")
        try:
            self._draft.sync(seqs)
            d_toks, d_probs = self._draft.propose(seqs, spec_lens, K)
        except PoolExhausted:
            # the draft pool cannot hold this round: forget every draft
            # allocation (they re-sync from scratch when pressure
            # clears), roll the target pool's speculative page claims
            # back to the committed lengths (pages stay owned), and
            # tell step() to run an ordinary round instead
            for s in seqs:
                self._draft.drop(s.seq_id)
            for seq, _, _ in plan.rows:
                self.pool.rollback(seq.seq_id, seq.cached_len)
            self.metrics.spec_draft_fallbacks.inc()
            return False
        # d_toks are host-side (the verifier packs them into its query
        # buffer); d_probs is already the [R, K, V] DEVICE operand
        draft_tokens = np.zeros((R, K), np.int32)
        draft_tokens[:len(seqs)] = d_toks
        out, n_out, finite = self._launch(plan, sp, draft_tokens, d_probs)
        sp.phase("serve.commit")
        drafted = accepted = rollbacks = 0
        for i, (seq, _q_start, _q_len) in enumerate(plan.rows):
            if not finite[i]:
                self._abort_nonfinite(seq)
                touched[seq.seq_id] = self._outputs[seq.seq_id]
                continue
            spec = spec_lens[i]
            cached_old = seq.cached_len
            n = int(n_out[i])            # 1..spec+1 tokens to commit
            drafted += spec
            accepted += n - 1
            if n - 1 < spec:
                rollbacks += 1
            committed = 0
            for j in range(n):
                committed += 1
                self._commit_token(seq, int(out[i, j]))
                if seq.status is not SequenceStatus.RUNNING:
                    break                # eos/length finalized mid-chain
            if seq.status is SequenceStatus.RUNNING:
                seq.cached_len = cached_old + committed
                self.pool.rollback(seq.seq_id, seq.cached_len)
                self._draft.commit(seq.seq_id, cached_old,
                                   committed - 1, spec)
            self._trace(seq.seq_id, "spec_round", drafted=int(spec),
                        accepted=int(n - 1), new_tokens=int(committed),
                        rollback=bool(n - 1 < spec))
            touched[seq.seq_id] = self._outputs[seq.seq_id]
        m = self.metrics
        m.spec_rounds.inc()
        if drafted:
            m.spec_drafted_tokens.inc(drafted)
        if accepted:
            m.spec_accepted_tokens.inc(accepted)
        if rollbacks:
            m.spec_rollbacks.inc(rollbacks)
        if m.spec_drafted_tokens.value:
            m.spec_accept_rate.set(m.spec_accepted_tokens.value
                                   / m.spec_drafted_tokens.value)
        return True

    def _launch_burst(self, bplan, touched, sp):
        """Assemble the fixed-shape burst operands and run the
        on-device token loop: ONE host dispatch for up to
        ``burst_tokens`` tokens per row. The host then replays the
        returned token buffer through the normal commit path (stream
        callbacks, EOS/length finalization, prefix registration) and
        re-syncs the pool's committed lengths."""
        R, PPS = self.max_num_seqs, self.max_pages_per_seq
        sp.phase("serve.assemble")
        tokens = np.zeros((R,), np.int32)
        kv_lens = np.zeros((R,), np.int32)
        tbls = np.full((R, PPS), NULL_PAGE, np.int32)
        live = np.zeros((R,), bool)
        caps = np.zeros((R,), np.int32)
        temps = np.zeros((R,), np.float32)
        top_ks = np.zeros((R,), np.int32)
        top_ps = np.ones((R,), np.float32)
        seeds = np.zeros((R,), np.int32)
        gpos = np.zeros((R,), np.int32)
        eos_ids = np.full((R,), -1, np.int32)
        for i, (seq, cap) in enumerate(bplan.rows):
            tokens[i] = seq.all_ids[-1]
            kv_lens[i] = seq.cached_len
            tbls[i] = self.pool.padded_block_table(seq.seq_id, PPS)
            live[i] = True
            caps[i] = cap
            temps[i] = seq.temperature
            top_ks[i] = seq.top_k or 0
            top_ps[i] = 1.0 if seq.top_p is None else seq.top_p
            seeds[i] = seq.seed
            gpos[i] = len(seq.tokens)
            if seq.eos_token_id is not None:
                eos_ids[i] = seq.eos_token_id
        self.metrics.host_dispatches.inc()
        self.metrics.burst_launches.inc()
        if not self._burst_launched:
            # the burst loop is a second step executable: its compile
            # rides the same forensics counter as the ragged step's
            self._burst_launched = True
            self.metrics.decode_compiles.inc()
        sp.phase("serve.dispatch")
        out, gen, ok, new_kv, new_scales = self._burst_jit(
            self._step_params, self.pool.kv, self.pool.kv_scales,
            jnp.asarray(tokens), jnp.asarray(kv_lens), jnp.asarray(tbls),
            jnp.asarray(live), jnp.asarray(caps), jnp.asarray(temps),
            jnp.asarray(top_ks), jnp.asarray(top_ps), jnp.asarray(seeds),
            jnp.asarray(gpos), jnp.asarray(eos_ids),
            jnp.asarray(bplan.burst_len, jnp.int32), self._base_key)
        self.pool.kv = new_kv
        if new_scales is not None:
            self.pool.kv_scales = new_scales
        sp.phase("serve.wait")
        out = np.asarray(out)
        gen = np.asarray(gen)
        ok = np.asarray(ok)
        sp.phase("serve.commit")
        # a burst reads each row's context again for every token of it:
        # the context is of its first iteration, the tokens of all
        sp.set(rows=len(bplan.rows), prefill_tokens=0,
               decode_tokens=int(gen.sum()),
               live_kv_tokens=int(kv_lens.sum()),
               **_sampler_counts(temps, top_ks, top_ps),
               # as the ragged walk would cover these rows, so that the
               # two counts stay a pair on every step
               attn_kv_tokens_read=ragged_kv_tokens_read(
                   live, kv_lens, q_block=1, page_size=self.page_size,
                   pages_per_seq=PPS))
        for i, (seq, cap) in enumerate(bplan.rows):
            if not ok[i]:
                # the row went non-finite at some loop iteration: every
                # token of this burst is suspect — commit none, roll the
                # pool's committed length back to the pre-burst state,
                # and abort with the structured error
                self.pool.set_seq_len(seq.seq_id, seq.cached_len)
                self._abort_nonfinite(seq)
                touched[seq.seq_id] = self._outputs[seq.seq_id]
                continue
            g = int(gen[i])
            seq.cached_len += g
            # prepare_burst committed cached + cap up front; shrink the
            # pool's committed length back to what the burst actually
            # appended (a row that finished mid-burst appended fewer)
            self.pool.set_seq_len(seq.seq_id, seq.cached_len)
            for j in range(g):
                self._commit_token(seq, int(out[i, j]))
            self._trace(seq.seq_id, "burst", new_tokens=g,
                        burst_cap=int(cap))
            touched[seq.seq_id] = self._outputs[seq.seq_id]

    def _commit_token(self, seq: Sequence, tok: int):
        seq.tokens.append(int(tok))
        first = seq.first_token_at is None
        if first:
            # TTFT numerator. Burst mode commits a whole burst at one
            # host boundary, so a burst's tokens share this timestamp —
            # latency quantizes to burst length by design
            seq.first_token_at = self._now()
            self._life_span(seq, None)
        self.metrics.tokens_generated.inc()
        if self.tenant_policy is not None:
            self.tenant_policy.charge_tokens(seq.tenant_id, 1)
            if first:
                self.tenant_policy.record_ttft(
                    seq.tenant_id, seq.first_token_at - seq.arrival)
        out = self._sync_output(seq)
        if seq.eos_token_id is not None and tok == seq.eos_token_id:
            self._finalize(seq, "finished", reason="eos")
        elif len(seq.tokens) >= seq.max_new_tokens:
            self._finalize(seq, "finished", reason="length")
        elif self._stream_cb is not None:
            self._stream_cb(seq.seq_id, int(tok), False)
        return out

    def _abort_nonfinite(self, seq: Sequence):
        """Structured abort for a row the in-graph isfinite guard
        flagged: the request finalizes with ``finish_reason
        "nonfinite_logits"`` (status aborted), its pages are freed, and
        the ``nonfinite_rows`` counter records the event — the engine
        keeps serving every other row instead of streaming garbage.
        The flight recorder auto-dumps its last-N context (the steps
        LEADING INTO the numeric blow-up are the post-mortem)."""
        self.metrics.nonfinite_rows.inc()
        self.flight.record("nonfinite", self._now(), request=seq.seq_id)
        self.flight_dump("nonfinite_logits", request=seq.seq_id)
        self._finalize(seq, "aborted", reason="nonfinite_logits")

    def _finalize(self, seq: Sequence, status: str, reason=None):
        if self._draft is not None:
            self._draft.drop(seq.seq_id)
        if self.adapters is not None and seq.adapter_id not in (0, None):
            self.adapters.release(seq.adapter_id)
            seq.adapter_id = 0        # idempotent across double-finalize
        self.scheduler.finish(seq, {
            "finished": SequenceStatus.FINISHED,
            "shed": SequenceStatus.SHED,
            "cancelled": SequenceStatus.CANCELLED,
            "aborted": SequenceStatus.ABORTED,
        }[status])
        out = self._sync_output(seq)
        out.finish_reason = reason or status
        if seq.seq_id in self._life:
            # left before its first token: shed, cancelled, aborted
            self._life_span(seq, None)
        if self.tracer is not None:
            # terminal span: kind encodes the lifecycle exit so the
            # breakdown/post-mortem can branch without string-matching
            # reasons (deadline_abort/nonfinite_abort/shed/finish)
            if reason == "deadline_exceeded":
                kind = "deadline_abort"
            elif reason == "nonfinite_logits":
                kind = "nonfinite_abort"
            elif status == "shed":
                kind = "shed"
            else:
                kind = "finish"
            # tenant attribution rides the span ONLY when set — classic
            # (no-tenant) traces stay byte-identical per seed
            extra = {} if seq.tenant_id is None \
                else {"tenant": seq.tenant_id}
            self._trace(seq.seq_id, kind, status=status,
                        reason=out.finish_reason,
                        tokens=len(seq.tokens), **extra)
        if status in ("shed", "aborted"):
            extra = {} if seq.tenant_id is None \
                else {"tenant": seq.tenant_id}
            self.flight.record(status, self._now(), request=seq.seq_id,
                               reason=out.finish_reason, **extra)
        if status == "finished":
            self.metrics.finished_requests.inc()
            self.metrics.record_request_end(
                arrival=seq.arrival, first_token_at=seq.first_token_at,
                finished_at=self._now(), n_tokens=len(seq.tokens))
            if self.tenant_policy is not None:
                self.tenant_policy.count_finished(seq.tenant_id)
        if self._stream_cb is not None:
            last = seq.tokens[-1] if seq.tokens else None
            self._stream_cb(seq.seq_id, last, True)
        return out

    def _sync_output(self, seq: Sequence) -> RequestOutput:
        out = self._outputs[seq.seq_id]
        out.token_ids = list(seq.tokens)
        out.status = seq.status.value
        out.num_preemptions = seq.num_preemptions
        out.tenant_id = seq.tenant_id
        return out


__all__ = ["LLMEngine", "Request", "RequestOutput", "RequestRejected"]
