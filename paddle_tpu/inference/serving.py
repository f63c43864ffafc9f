"""Continuous batching with CHUNKED PREFILL over a PAGED KV cache —
the serving scheduler (round-5 verdict item 8; round-6 perf rework:
admission no longer stops the world; round-12 perf rework: the KV
cache is a shared page pool with prefix sharing and optional int8).

Reference: `python/paddle/incubate/nn/functional/
block_multihead_attention.py` — the reference's paged-KV block tables
exist to admit/evict sequences mid-flight.  The r6 design kept a FIXED
batch of `max_batch_size` slots, each a dense per-slot KV ring buffer
sized for the worst case — HBM (the binding resource in decode) went
to padding and to duplicated system prompts.  The r12 design keeps the
r6 scan untouched in shape but rebuilds its KV storage around pages
(the PagedAttention/vLLM design point, adapted to a statically-shaped
XLA program):

  * ONE device page pool `[num_pages, layers, kv_heads, page_size,
    head_dim]` per K and V (models.llama.init_paged_cache) backs every
    slot, addressed through a per-slot page table `[B, pages_per_slot]`
    carried through the scan; page 0 is a reserved null page.  What a
    token's row IS the model says (`kv_row_spec`): a latent-attention
    model keeps one pool of `[num_pages, layers, page_size, width]`
    rows, and page copy, prefix sharing and hand-off move its pages by
    the same programs (they index pages, whatever a page holds);
  * attention gathers by page table INSIDE the kernel
    (ops.paged_attention: a Pallas kernel on TPU that walks each
    slot's live pages, a `take`-gather jnp twin elsewhere —
    bit-identical to the dense path off-TPU); writes touch only the
    page window overlapping the step's rows (ops.paged_kv_update),
    gathered from and scattered into the WHOLE pool by (page, layer):
    the carried pools then keep the default dimension order, the only
    one the kernel takes — a write that slices the layer out first
    costs a pool-sized layout copy in front of every kernel call;
  * PREFIX SHARING (inference/paged_kv.py): a host-side token-exact
    trie over page-sized prompt chunks maps admissions onto already-
    resident pages with refcounts — matched tokens SKIP their prefill
    chunks entirely (pos starts at the shared depth), and a mid-page
    divergence copies the matched page once (copy-on-write) before
    private prefill continues from the divergence row;
  * int8 KV (`FLAGS_kv_cache_dtype=int8` or kv_dtype="int8"): the pool
    stores 1 byte/element with per-page per-head scales, dequant fused
    into the paged-attention kernel — roughly double the resident
    batch/context in the same KV HBM;
  * a pool smaller than total demand EVICTS cached prefix pages
    LRU-first and, beyond that, defers admissions until live requests
    finish — every request still completes (eviction-under-pressure
    contract).

The r6 serving contracts are preserved and regression-pinned WITH the
paged path: one `[B, C]` step body serves both phases, exactly TWO
compiled programs per batcher shape (prompt length never reaches a
shape), and every carry buffer — the page pool, the page tables, the
token/pos/mode state, the prompt buffer — is donated into the jitted
scan.  `kv_layout="dense"` keeps the r6 per-slot ring buffers (the
parity baseline the paged tests compare against).

Compiled programs are cached ON THE MODEL (inference.generation's
compile-cache idiom, keys fingerprinted with the KV-layout flags), so
successive batchers over one model reuse them.  `stats()` reports slot
occupancy, the prefill-vs-decode token split, per-chunk wall times and
the KV-pool counters (pages used/free, prefix-hit tokens, evictions,
pool bytes) that feed `serve.kv` telemetry and the serve bench.

The r9 training plane got fault tolerance (atomic checkpoints, fault
injection, SIGTERM drain); this file carries the SERVE-plane half of
that contract (ISSUE 9) — all host-plane control flow, so the compiled
step programs and their cache keys stay byte-identical with the
robustness flags off (bench-asserted):

  * SLO classes: every request is `interactive` / `batch` /
    `best_effort` with an optional arrival DEADLINE.  Admission is a
    priority queue — classes in priority order, strict FIFO by arrival
    within a class, and a class head deferred by KV-pool pressure
    blocks its own and lower classes (no head-of-line bypass, so a
    stream of short prompts can never starve a deferred long one);
  * load shedding: a bounded queue (`FLAGS_serve_queue_depth`) sheds
    the lowest-SLO newest-arrival QUEUED request on overflow
    (best_effort first), and a request still queued past its deadline
    is shed as a deadline miss — an in-flight decode is NEVER shed;
  * fault injection (`distributed/fault.py` points `serve.admit`,
    `serve.kv_alloc`, `serve.chunk`, `serve.decode`) + recovery: a
    faulted admission retries FIFO-in-place (bounded by
    `FLAGS_serve_retry_budget`), a faulted chunk fires BEFORE the
    donated carries are touched and simply retries, and a poisoned
    SLOT is evicted — pages released, request requeued at its arrival
    position for a from-scratch re-decode (greedy decode is
    deterministic, so the re-decode is bit-exact vs a fault-free run;
    `tools/chaos_check.py --serve` pins this) — while the rest of the
    batch keeps decoding;
  * a serve watchdog riding `distributed/watchdog.py`: every chunk
    dispatch runs under `watched("serve.chunk")`
    (FLAGS_stop_check_timeout), and a chunk that aged past the
    deadline while in flight is counted/published as hung;
  * SIGTERM drain mirroring the r9 training contract: once
    `guard.drain_requested()` is set, admissions stop (queued requests
    shed with reason "drain"), in-flight decodes finish within
    PADDLE_DRAIN_GRACE, and on grace expiry partial results are
    flushed — the caller exits ELASTIC_EXIT_CODE
    (`chaos_check --serve --selftest` runs the e2e).

Greedy decoding (temperature 0) — the deterministic serving mode whose
per-sequence outputs are testable against isolated `generate()` runs.

A scan step does NOT always yield one token a slot.  A model that says it
generates by diffusion over blocks of L tokens (`model.block_diffusion()`,
SDAR) is served by the same two programs with a BLOCK as a slot's decode
state: a step is a denoise or a commit pass of the slot's block and yields
0 or L tokens (`_step_fn`, "the block schedule"); speculative decoding is
the other such path (`_spec_step_fn`).  Hence two counts: `decode_tokens`
are tokens the decode side COMMITTED, `decode_lanes` valid lanes it
processed.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.flags import get_flag
from ..framework.tensor import Tensor

__all__ = ["ContinuousBatcher", "Request", "SLO_CLASSES",
           "pack_handoff", "unpack_handoff"]


def pack_handoff(meta, data) -> bytes:
    """Serialize one hand-off (meta + gathered KV pages) for the KV
    launch plane: multi-process fleets move prefill->decode hand-offs
    as a single value under ``<job>/serve/handoff/<gid>`` (host plane
    over the r14 KV plane); in-process fleets skip this entirely and
    pass the device arrays straight into import_handoff."""
    import io
    import json
    m = dict(meta)
    m["prompt"] = [int(t) for t in np.asarray(meta["prompt"]).tolist()]
    arrays = {k: np.asarray(v) for k, v in data.items()}
    # npz has no bfloat16: ship raw bytes (uint16 view) and record the
    # real dtype in the header for the view-cast on unpack
    m["_dtypes"] = {k: str(a.dtype) for k, a in arrays.items()}
    header = json.dumps(m).encode("utf-8")
    buf = io.BytesIO()
    np.savez(buf, **{k: a.view(np.dtype(f"uint{8 * a.dtype.itemsize}"))
                     if a.dtype.kind not in "iufb" else a
                     for k, a in arrays.items()})
    return len(header).to_bytes(8, "big") + header + buf.getvalue()


def unpack_handoff(blob: bytes):
    """Inverse of pack_handoff: (meta, data) with device arrays, ready
    for import_handoff().  Byte-identical round trip (pinned by
    tests/test_serve_disagg.py)."""
    import io
    import json
    n = int.from_bytes(blob[:8], "big")
    meta = json.loads(blob[8:8 + n].decode("utf-8"))
    meta["prompt"] = np.asarray(meta["prompt"], np.int32)
    dtypes = meta.pop("_dtypes", {})
    npz = np.load(io.BytesIO(blob[8 + n:]))
    data = {}
    for k in npz.files:
        a = npz[k]
        want = dtypes.get(k)
        if want and str(a.dtype) != want:
            a = a.view(np.dtype(want))
        data[k] = jnp.asarray(a)
    return meta, data

# admission priority order, highest first; shedding walks it in reverse
SLO_CLASSES = ("interactive", "batch", "best_effort")

# what stats() and each chunk's serve.dispatch span count beside
# kv_pages_live / kv_pages_walked for a model with KINDS of attention
# layer: the pages the attention walks, summed over the kind's layers,
# the slots and the scan steps, and the pages that hold the rows a window
# layer's VALID lanes may attend (occupied slots only)
KIND_PAGE_COUNTS = ("kv_pages_walked_full", "kv_pages_walked_window",
                    "kv_pages_window_needed")

# what the block schedule of a model that generates by diffusion counts a
# step, on the device, summed over a chunk's scan steps and read with its
# tokens (beside the model's own counts, stats()).  Each with its use:
#   diffusion_denoise_passes          slot-passes that sampled; with the
#       blocks committed (a commit pass each) the passes the device ran
#   diffusion_blocks_committed, diffusion_committed_block_passes
#       blocks committed and the passes THOSE blocks had had, their denoise
#       passes and the commit (a window's slot-passes over its blocks would
#       count the blocks its edges cut): passes a block, the schedule's cost
#   diffusion_tokens_unmasked, diffusion_unmasked_by_threshold
#       lanes fixed, and those of them the confidence threshold took: the
#       share of the work the dynamic threshold saves (0 on random weights)
#   decode_lanes   valid lanes the decode side processed (the operation
#       counts' unit; `decode_tokens` is tokens COMMITTED)
DIFFUSION_COUNTERS = ("diffusion_denoise_passes",
                      "diffusion_blocks_committed",
                      "diffusion_committed_block_passes",
                      "diffusion_tokens_unmasked",
                      "diffusion_unmasked_by_threshold", "decode_lanes")


@functools.partial(jax.jit, donate_argnums=0)
def _slot_writes(state, masks, values):
    """{field: [B, ...]} with `values` where `masks` [B] says so."""
    return {f: jnp.where(masks[f].reshape((-1,) + (1,) * (old.ndim - 1)),
                         values[f], old) for f, old in state.items()}


def _step_quotas(block_length: int, denoising_steps: int) -> List[int]:
    """Lanes each denoising step of a block must fix at least: L // S,
    the first L mod S steps one more (SDAR's get_num_transfer_tokens)."""
    L, S = block_length, denoising_steps
    return [L // S + (s < L % S) for s in range(S)]


def _unmask_choice(conf, masked, quota, threshold):
    """The lanes a denoise pass fixes, [B, L] bool, and whether the
    threshold chose them, [B]: every masked lane whose confidence is above
    `threshold` if they number at least the pass's `quota` [B], else the
    quota's most confident masked lanes (ties: the lower lane)."""
    conf = jnp.where(masked, conf, -jnp.inf)
    high = conf > threshold
    rank = jnp.argsort(jnp.argsort(-conf, axis=-1, stable=True),
                       axis=-1, stable=True)
    by_threshold = jnp.sum(high, axis=-1) >= quota
    return jnp.where(by_threshold[:, None], high,
                     masked & (rank < quota[:, None])), by_threshold


# what one step() spends its time in, each a `serve.<phase>` span.
# `deliver` lies inside `harvest` as a span; as a duration `harvest` is
# what is left without it, so the six add up to at most the step
PHASES = ("evict", "admit", "dispatch", "device_wait", "harvest",
          "deliver")


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray              # [L] int32
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    # parallel to `tokens`, for a model that generates by diffusion over
    # blocks (empty otherwise): the denoising pass of its block at which
    # each token was fixed — tokens are delivered in position order but
    # fixed in confidence order, and what a token was conditioned on is
    # the lanes fixed before it
    token_passes: List[int] = field(default_factory=list)
    finished: bool = False
    # -- SLO / robustness state (ISSUE 9) --
    slo: str = "batch"
    deadline: Optional[float] = None   # absolute monotonic seconds
    arrival: int = 0                   # global arrival sequence number
    shed: bool = False
    shed_reason: Optional[str] = None
    requeues: int = 0                  # faulted-slot re-admissions
    admit_faults: int = 0              # injected admission-fault retries
    partial: bool = False              # drain-flushed mid-generation
    # -- per-request latency spans (ISSUE 10): monotonic stamps at the
    # queue -> admit -> first-token -> finish boundaries; TTFT/e2e are
    # measured from SUBMIT (a requeue resets admit/first, so the spans
    # describe the decode that actually served the user)
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # the chunk (the batcher's running number, the `chunk` id of its
    # `serve.step` span) in which each of those happened, -1 until it
    # has: a request's queue / prefill / decode intervals name the
    # chunk spans that caused them
    admit_chunk: int = -1
    first_token_chunk: int = -1
    done_chunk: int = -1
    # -- streaming (ISSUE 11 satellite): per-request token callback,
    # fired as chunks complete with each NEW burst of output-surviving
    # tokens (speculation delivers a whole accepted run in one burst);
    # `delivered` is the count already handed out — it survives a
    # faulted-slot requeue, so the bit-exact re-decode never re-sends
    # the prefix the caller already has
    on_token: Optional[object] = None
    # authoritative copy of every token actually handed to on_token —
    # a shed after repeated faults restores it as the partial output,
    # so the final result can never disown a streamed token even when
    # intermediate requeues discarded (and re-decoded) `tokens`
    delivered_tokens: List[int] = field(default_factory=list)

    @property
    def delivered(self) -> int:
        """Tokens already streamed — DERIVED from the authoritative
        delivered_tokens copy, so no second counter can drift out of
        sync with what the consumer actually holds."""
        return len(self.delivered_tokens)

    def output(self) -> np.ndarray:
        return np.asarray(self.tokens[: self.max_new_tokens], np.int32)

    def output_passes(self) -> np.ndarray:
        """token_passes of output()'s tokens."""
        return np.asarray(self.token_passes[: self.max_new_tokens],
                          np.int32)


class ContinuousBatcher:
    """One model, `max_batch_size` sequence slots, insert/evict at
    chunk boundaries, chunked prefill through the decode program, KV
    in a shared page pool.  How a slot decodes is the MODEL's to say:
    one token a step (autoregressive), or a block of L tokens in up to
    denoising_steps + 1 passes (`model.block_diffusion()`; paged layout,
    unified role, no speculation, prefill_chunk and page_size multiples
    of L — anything else is refused here, not served wrong).

    chunk: decode steps (for a block-diffusion model: passes) per host
    round trip (a per-token host loop would pay a dispatch and a
    blocking transfer per token).
    prefill_chunk: prompt tokens a slot being admitted consumes per
    step of the admission-mode scan (the decode-shaped chunk width).
    admit_steps: scan length of the admission-mode program (defaults
    to chunk//4 — admission rounds are short; decode rounds are long).
    kv_layout: "paged" (default when the model has a paged decode
    path) or "dense" (the r6 per-slot ring buffers).
    page_size/num_pages/kv_dtype: paged-pool geometry and precision;
    None reads FLAGS_kv_page_size / FLAGS_kv_pool_pages /
    FLAGS_kv_cache_dtype (num_pages 0 = dense-equivalent capacity).
    prefix_sharing: admissions whose prompt prefix matches resident
    pages map them instead of re-prefilling (paged only).  None =
    True, except under speculative decoding where it defaults False
    (skipped prefill chunks starve the draft cache and collapse the
    accept rate; explicit True keeps both and warns).
    """

    def __init__(self, model, max_batch_size: int = 4,
                 max_len: int = 256, chunk: int = 16,
                 prefill_chunk: int = 32,
                 admit_steps: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 kv_layout: Optional[str] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_sharing: Optional[bool] = None,
                 weight_only_dtype: Optional[str] = None,
                 spec_tokens: Optional[int] = None,
                 draft_model=None,
                 draft_layers: Optional[int] = None,
                 role: str = "unified"):
        if not hasattr(model, "forward_cached"):
            raise TypeError("ContinuousBatcher needs a decode-capable "
                            "model (forward_cached/init_cache)")
        # -- weight-only quantization (ISSUE 11): pack the model's
        # decode weights in place BEFORE the state_dict walk below, so
        # the packed params + scales ride the compiled scan.  None
        # reads FLAGS_weight_only_dtype; "none" leaves the model (and
        # therefore every compiled program) untouched.
        wo = weight_only_dtype if weight_only_dtype is not None \
            else get_flag("weight_only_dtype", "none")
        if str(wo) not in ("none", "", "None"):
            from ..quantization.weight_only import quantize_model
            quantize_model(model, wo)
        if kv_layout is None:
            kv_layout = "paged" if hasattr(model, "forward_cached_paged") \
                else "dense"
        if kv_layout not in ("paged", "dense"):
            raise ValueError(f"kv_layout {kv_layout!r}: paged|dense")
        if kv_layout == "paged" and not hasattr(model,
                                               "forward_cached_paged"):
            raise TypeError("kv_layout='paged' needs a paged-decode "
                            "model (forward_cached_paged/"
                            "init_paged_cache)")
        self.model = model
        self.B = int(max_batch_size)
        self.max_len = int(max_len)
        self.chunk = int(chunk)
        self.prefill_chunk = max(1, min(int(prefill_chunk),
                                        self.max_len))
        # how the model generates: None = one token a step; else the
        # block schedule's four numbers (models.llama.LlamaConfig)
        self._diffusion = getattr(model, "block_diffusion",
                                  lambda: None)()
        self.block_len = self._diffusion["block_length"] \
            if self._diffusion else 1
        self.admit_steps = max(1, int(admit_steps)
                               if admit_steps is not None
                               else self.chunk // 4)
        self.eos = eos_token_id
        self.kv_layout = kv_layout
        # -- speculative decoding (ISSUE 11): K>0 swaps the pure-decode
        # program for a draft/verify body — draft K tokens with the
        # (small) draft model, verify them in ONE target pass of width
        # K+1 through the same chunked scan, accept the longest
        # matching prefix plus the target's bonus token.  Greedy output
        # is bit-exact vs non-speculative decode (the verify lanes ARE
        # the non-speculative logits), and with K=0 nothing below
        # exists — carries, programs and keys stay byte-identical.
        k = spec_tokens if spec_tokens is not None \
            else get_flag("serve_spec_tokens", 0)
        self.spec_k = max(0, int(k or 0))
        self._spec_w = self.spec_k + 1          # verify width
        if self._diffusion:
            self._refuse_for_diffusion(kv_layout, role)
        # KINDS of attention layer (sliding-window beside full): the
        # model's kv_row_spec names them, and its paged cache is a pool a
        # kind — a ring of O(window) rows a slot in the window layers
        self._kinds = None
        if hasattr(model, "kv_row_spec"):
            self._kinds = model.kv_row_spec(kv_dtype).get("kinds")
        if self._kinds:
            self._refuse_for_kinds(kv_layout, role, prefix_sharing)
            prefix_sharing = False
        # lanes a slot feeds the decode program a step: the token, the
        # verify window, or the block
        self._decode_width = max(self._spec_w, self.block_len)
        self._draft = None
        self._draft_names: List[str] = []
        self._draft_key = ()
        if self.spec_k:
            if draft_model is None:
                n = draft_layers if draft_layers is not None \
                    else get_flag("serve_draft_layers", 0)
                n = int(n or 0)
                if n <= 0:
                    raise ValueError(
                        "speculative decoding needs a draft: pass "
                        "draft_model= or draft_layers= (or set "
                        "FLAGS_serve_draft_layers) for early-exit "
                        "self-drafting")
                if not hasattr(model, "early_exit_draft"):
                    raise TypeError(
                        f"{type(model).__name__} has no "
                        "early_exit_draft(); pass an explicit "
                        "draft_model instead")
                draft_model = model.early_exit_draft(n)
                self._draft_key = ("selfdraft", n)
            else:
                if not hasattr(draft_model, "forward_cached"):
                    raise TypeError("draft_model needs a cached decode "
                                    "path (forward_cached/init_cache)")
                # the compiled program closes over the draft OBJECT
                # (its params are swapped in per call), so the program
                # key carries the draft's identity — two batchers with
                # different drafts can never share a program
                # (satellite 2: draft identity in the program keys)
                self._draft_key = ("draft", id(draft_model))
                # self-speculation (draft IS the target) needs no
                # second parameter list: the target's _swapped_state
                # already covers every weight the draft reads —
                # shipping state_dict twice per chunk would double the
                # parameter traffic for nothing
                if draft_model is not model \
                        and hasattr(draft_model, "state_dict"):
                    self._draft_names = list(
                        draft_model.state_dict().keys())
            self._draft = draft_model
        # speculation accounting (host plane)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        self._spec_emit_window: deque = deque(maxlen=4096)
        # one FIFO per SLO class (admission walks SLO_CLASSES in
        # priority order; within a class strictly by arrival).  The
        # lock makes queue STRUCTURE atomic against a submit() racing
        # the run()/step() thread (and the router's balance reads):
        # without it stats()["queued"] / the per-class snapshot could
        # see a torn count mid-append (ISSUE 15 satellite).  Reentrant
        # because a shed inside submit() fires the user's on_token
        # callback, which may itself submit()
        self._qlock = threading.RLock()
        self._queues: Dict[str, deque] = {c: deque()
                                          for c in SLO_CLASSES}
        self._slots: List[Optional[Request]] = [None] * self.B
        self._finished: Dict[int, Request] = {}
        self._next_id = 0
        # -- disaggregated serving (ISSUE 20): a prefill-role batcher
        # runs ONLY chunked-prefill (admit) programs; a slot that
        # finishes its prompt is FROZEN (done=True device-side, pages
        # pinned) until export_handoff() ships its KV pages +
        # page-table row to a decode-role batcher, which admits it at
        # pos >= prompt_len via import_handoff() — no prefill is ever
        # recomputed.  "unified" is the classic symmetric replica and
        # the default: with no prefill/decode batchers in the fleet,
        # every code path below is dormant and the serve-step programs
        # are byte-identical (tests/test_program_contracts.py:
        # test_disagg_flags_leave_the_serve_programs_identical).
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role {role!r}: unified|prefill|decode")
        if role != "unified" and kv_layout != "paged":
            raise TypeError("disaggregated roles need kv_layout="
                            "'paged' (the hand-off ships pages)")
        self.role = role
        self._handoff_ready: Dict[int, int] = {}   # rid -> slot index
        self._no_freeze: set = set()    # unfrozen rids: decode HERE
        self._handoffs_out = 0
        self._handoffs_in = 0
        self._handoff_bytes = 0
        self._arrival_seq = 0
        self._now = time.monotonic     # patchable time source (tests)
        self._has_deadlines = False    # sweep is skipped until a
        #                                deadline ever enters the queue
        self._draining = False
        self._drain_deadline = None
        # serve-robustness accounting (the chaos no-leak contract:
        # submitted == completed + shed once queue and slots drain)
        self._submitted = 0
        self._admissions = 0           # admission EVENTS (requeues
        #                                re-admit, so >= completed)
        self._completed = 0
        self._shed_count = 0
        self._shed_by_class = {c: 0 for c in SLO_CLASSES}
        # sliding-window shed signal (ISSUE 19 satellite): one 0/1
        # sample per TERMINAL request (shed=1, delivered=0) in a
        # bounded window — the rate the router/autoscaler policy reads
        # is CURRENT pressure, not lifetime history (an old shed burst
        # ages out as later terminals push it off the window).  Same
        # bounded-window discipline as the latency deques below
        self._terminal_window: deque = deque(maxlen=256)
        self._deadline_misses = 0
        self._requeue_count = 0
        self._chunk_retries = 0
        self._consecutive_chunk_faults = 0
        self._hung_chunks = 0
        self._cb_errors = 0
        from ..distributed.watchdog import watched
        self._watch = watched("serve.chunk")

        sd = model.state_dict()
        self._names = list(sd.keys())
        # the logical KV depth is C-1 rows DEEPER than max_len: a
        # [B, C] step's pad lanes write up to C-1 rows past a slot's
        # valid depth — without the margin a near-capacity write would
        # land on valid rows.  Under speculation the widest writer is
        # the verify pass, and a done slot's frozen pos can sit up to
        # spec_w-1 rows past the clamp with another spec_w junk rows
        # written beyond it — hence the 2*K+2 floor.
        self._eff_chunk = max(self.prefill_chunk,
                              2 * self.spec_k + 2) if self.spec_k \
            else self.prefill_chunk
        self._cache_len = self.max_len + self._eff_chunk - 1
        if kv_layout == "paged":
            from .paged_kv import PageAllocator
            (self.page_size, self.pages_per_slot,
             self.num_pages) = self._paged_geometry(
                self.B, self.max_len, self._eff_chunk, page_size,
                num_pages)
            # prefix sharing defaults OFF under speculation: a shared
            # prefix SKIPS its prefill chunks, so the draft's dense
            # cache never sees those rows — greedy output stays
            # bit-exact (acceptance is exact-match against the target)
            # but the accept rate silently collapses on every prefix
            # hit, making speculation a net slowdown exactly when
            # sharing works.  An explicit True keeps both and warns.
            if prefix_sharing is None:
                self.prefix_sharing = not self.spec_k
            else:
                self.prefix_sharing = bool(prefix_sharing)
                if self.prefix_sharing and self.spec_k:
                    import warnings
                    warnings.warn(
                        "prefix_sharing=True with speculative decoding:"
                        " shared-prefix admissions skip the prefill"
                        " chunks that would fill the DRAFT cache, so"
                        " accept_rate degrades on every prefix hit"
                        " (output stays bit-exact). Prefer one or the"
                        " other per workload.", stacklevel=2)
            # rows a slot can write past prompt+new before the host
            # evicts it: up to max(chunk, admit_steps)-1 junk decode
            # steps inside the finishing chunk (each advancing up to
            # spec_w rows under speculation), plus C-1 junk lanes
            self._overshoot = max(self.chunk * self._decode_width,
                                  self.admit_steps * self.block_len) \
                + self._eff_chunk
            self._alloc = PageAllocator(self.num_pages, self.page_size)
            self._plans: List[Optional[object]] = [None] * self.B
            # a window layer's ring: the pages its window and one step's
            # lanes can straddle, a slot's own for its whole life —
            # nothing to allocate or free (0: no such layers)
            self.ring_pages = self._ring_pages(
                self._kinds, self._eff_chunk, self.page_size)
            more = {"window_pages": self.B * self.ring_pages} \
                if self._kinds else {}
            self._cache = model.init_paged_cache(self.num_pages,
                                                 self.page_size,
                                                 kv_dtype, **more)
            spec = model.kv_row_spec(kv_dtype)
            self._kv_dtype = str(np.dtype(spec["dtype"]))
            self._pages_walked = spec["pages_walked"]
            if self._kinds:
                self._kv_pool_bytes = self._kind_pool_bytes(
                    spec, self.num_pages, self.B * self.ring_pages,
                    self.page_size)
            if self._diffusion and spec["scales"]:
                raise ValueError(
                    "int8 KV under the block schedule is not supported: "
                    "every pass of a block rewrites its rows, and a page "
                    "would be requantised denoising_steps + 1 times a "
                    "block")
            if self.page_size % self.block_len:
                raise ValueError(
                    f"page_size {self.page_size} is not a multiple of the "
                    f"model's block length {self.block_len}: a shared "
                    "prefix page must end where a block ends")
            self._page_table = jnp.zeros((self.B, self.pages_per_slot),
                                         jnp.int32)
        else:
            self.prefix_sharing = False
            self._cache = model.init_cache(self.B, self._cache_len)
        # the draft's KV cache is DENSE per-slot ring buffers even over
        # a paged target pool: the draft is small (that is the point),
        # its rows are never shared, and a second page plane would buy
        # nothing — it rides the scan carry and is donated like every
        # other buffer
        self._dcache = self._draft.init_cache(self.B, self._cache_len) \
            if self.spec_k else None
        self._pos = jnp.zeros((self.B,), jnp.int32)
        # a slot's decode state: the token it feeds next, or (a model
        # that generates by diffusion) its block — L ids, L masked
        # flags, the pass at which each lane was fixed, and the passes
        # the block has had (0: nothing yet, _step_fn seeds it)
        self._tok = jnp.zeros((self.B,), jnp.int32)
        if self._diffusion:
            BL = (self.B, self.block_len)
            self._tok = {"ids": jnp.zeros(BL, jnp.int32),
                         "masked": jnp.zeros(BL, bool),
                         "fixed_at": jnp.zeros(BL, jnp.int32),
                         "step": jnp.zeros((self.B,), jnp.int32)}
        # the host's view of the blocks, for _kv_page_counts' replay
        self._block_step_host = np.zeros((self.B,), np.int64)
        self._block_masked_host = np.zeros((self.B,), np.int64)
        self._mode = jnp.zeros((self.B,), bool)  # True = prefilling
        self._plen = jnp.zeros((self.B,), jnp.int32)
        self._prompts = jnp.zeros((self.B, self.max_len), jnp.int32)
        self._done = jnp.ones((self.B,), bool)   # free slots are "done"
        # slot -> {field: value}: what eviction and admission write of
        # the slots' state, staged on the host until the next chunk
        self._staged: Dict[int, dict] = {}
        self._mode_host = np.zeros((self.B,), bool)
        self._done_host = np.ones((self.B,), bool)
        self._pos_host = np.zeros((self.B,), np.int64)
        # stats() accumulators — running aggregates plus a BOUNDED
        # window of recent chunk times (a long-lived server would
        # otherwise grow per-chunk lists forever); p50 is over the
        # window, max/counts/occupancy over the whole lifetime
        self._chunk_times: deque = deque(maxlen=1024)
        # beside it, each chunk's step() split by phase (ms, in PHASES'
        # order), taken whether or not anything listens: whether a slow
        # stretch was the host's or the device's
        self._phase_times: deque = deque(maxlen=1024)
        self._phase_ms = dict.fromkeys(PHASES, 0.0)
        self._chunk_no = 0              # the chunk step() is working on
        # paged attention's page walk, summed over every chunk's scan
        # steps (_kv_page_counts): what occupied slots hold / what the
        # kernel walks
        self._kv_pages_live = 0
        self._kv_pages_walked = 0
        # and, for a model with kinds of layer, the walk by kind (summed
        # over the kind's layers too) beside the pages a window layer's
        # valid lanes need: KIND_PAGE_COUNTS
        self._kv_pages_by_kind = dict.fromkeys(KIND_PAGE_COUNTS, 0) \
            if self._kinds else {}
        # what the MODEL counts a step on the device (a dropless expert
        # layer's routing, models.llama.step_counter_names): summed in
        # the scan, read with the chunk's tokens, never under
        # speculation (its decode program is a different scan)
        names = getattr(model, "step_counter_names", tuple)()
        self._model_counter_names = () if self.spec_k \
            or kv_layout != "paged" else tuple(names)
        # and what the block schedule counts beside them
        self._counter_names = self._model_counter_names \
            + (DIFFUSION_COUNTERS if self._diffusion else ())
        self._model_counts = dict.fromkeys(self._counter_names, 0)
        self._chunk_event = None        # serve.chunk's fields
        # per-request latency windows (bounded, same discipline as the
        # chunk times) + per-SLO-class deadline attainment — host
        # aggregates that always accumulate so stats() answers sink-less
        self._lat: Dict[str, deque] = {
            k: deque(maxlen=1024)
            for k in ("queue_ms", "ttft_ms", "tpot_ms", "e2e_ms")}
        self._slo_lat = {c: {"completed": 0, "with_deadline": 0,
                             "deadline_met": 0} for c in SLO_CLASSES}
        self._chunk_count = 0
        self._chunk_kind_counts = {"admit": 0, "decode": 0}
        self._chunk_time_max = 0.0
        self._occupancy_total = 0
        self._prefill_tok_total = 0
        self._decode_tok_total = 0
        self._lanes = self._lanes_valid = 0
        self._programs_used: set = set()
        self._first_use = False
        # HBM memory ledger (ISSUE 10): register both step programs as
        # lazy providers — lower_step is the side-effect-free probe, so
        # nothing compiles until telemetry.memory_report() asks; the
        # weakref keeps the ledger from pinning a dead batcher (and
        # its KV pool) alive
        import weakref
        from ..telemetry import memledger as _ml
        _ref = weakref.ref(self)
        _meta = {"kv_layout": self.kv_layout, "slots": self.B,
                 "max_len": self.max_len}

        def _provider(mixed):
            def provider():
                bat = _ref()
                if bat is None:
                    raise RuntimeError("batcher was garbage-collected")
                return bat.lower_step(mixed=mixed).compile()
            return provider
        _ml.register("serve_step.decode", _provider(False), meta=_meta)
        _ml.register("serve_step.admit", _provider(True), meta=_meta)
        # build-level static sentinel (analysis.passes): structural
        # passes over the serve build path.  The full catalog (donation
        # aliasing over the paged carries — costs a lower per program)
        # runs via .preflight() / tools/static_check.py.
        from ..analysis.passes import PassContext, sentinel_preflight
        sentinel_preflight(
            PassContext("serve", f"serve:B{self.B}", engine=self),
            level="build")

    def _refuse_for_diffusion(self, kv_layout, role):
        """What the block schedule does not compose with yet, said at
        construction."""
        L = self.block_len
        if self.spec_k:
            raise ValueError(
                "speculative decoding drafts one token after another; a "
                f"model that generates by diffusion over blocks of {L} "
                "has no such draft (spec_tokens must be 0)")
        if kv_layout != "paged":
            raise TypeError(
                "a model that generates by diffusion over blocks serves "
                "through the paged pool only: the dense ring buffers' "
                "step programs are not taught the block schedule")
        if role != "unified":
            raise ValueError(
                f"role {role!r}: a hand-off ships a slot at a token "
                "boundary, a block-diffusion slot stands inside a block; "
                "only the unified role serves such a model")
        if self.prefill_chunk % L:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} is not a multiple of "
                f"the model's block length {L}: prefill consumes whole "
                "blocks")

    def _refuse_for_kinds(self, kv_layout, role, prefix_sharing):
        """What a model with sliding-window layers does not compose with
        yet, said at construction: each of these moves or shares the
        pages of a slot's WHOLE depth and would have to carry the rings'
        rows along (ROADMAP R3)."""
        what = "a model with sliding-window layers keeps a ring of " \
            "O(window) rows a slot in those layers"
        if kv_layout != "paged":
            raise TypeError(
                f"{what} of the PAGED pool: the dense ring buffers' step "
                "programs hold every layer to the slot's whole depth "
                "(kv_layout must be 'paged')")
        if self.spec_k:
            raise ValueError(
                f"{what}: a rejected draft's rows would have overwritten "
                "rows still inside the window (spec_tokens must be 0)")
        if role != "unified":
            raise ValueError(
                f"role {role!r}: {what}, and a hand-off ships the pages "
                "of the page table alone; only the unified role serves "
                "such a model")
        if prefix_sharing:
            raise ValueError(
                f"prefix_sharing=True: {what}, so a shared prefix's pages "
                "hold the full layers' rows only and a request mapped "
                "onto them would lack the window layers' (the default "
                "resolves to off for such a model)")

    def preflight(self, *, level: str = "full", manager=None):
        """Full static sentinel over the serve step programs: the
        donation lint proves every donated paged carry (KV pool,
        caches, cursors) is really aliased in both the decode and
        mixed admission programs — an unaliased carry silently doubles
        the KV pool's HBM.  Uses the side-effect-free lower_step probe;
        returns a SentinelReport (None when FLAGS_static_sentinel is
        off).  Error findings raise SentinelError."""
        from ..analysis.passes import PassContext, sentinel_preflight
        return sentinel_preflight(
            PassContext("serve", f"serve:B{self.B}", engine=self),
            level=level, manager=manager)

    # -- pool geometry -----------------------------------------------------
    @staticmethod
    def _paged_geometry(B, max_len, prefill_chunk, page_size=None,
                        num_pages=None):
        """(page_size, pages_per_slot, num_pages) for a paged batcher —
        the ONE place the geometry formulas live (init, and the
        allocation-free byte estimator below).  pages_per_slot covers
        the logical depth PLUS the write window (ceil(C/ps)+1 pages):
        the windowed page write (ops.paged_kv_update) must never clamp
        two window entries onto one page.  num_pages defaults to
        dense-equivalent capacity (every slot fully backed + the null
        page)."""
        from ..framework.flags import get_flag
        ps = int(page_size or get_flag("kv_page_size", 16))
        cache_len = max_len + prefill_chunk - 1
        pages_per_slot = max(
            (max_len - 1) // ps + (-(-prefill_chunk // ps)) + 1,
            -(-cache_len // ps))
        auto = 1 + B * pages_per_slot
        num_pages = int(num_pages or get_flag("kv_pool_pages", 0)
                        or auto)
        return ps, pages_per_slot, num_pages

    @staticmethod
    def _ring_pages(kinds, prefill_chunk, page_size) -> int:
        """Pages of a slot's ring in the window layers' pool (0 for a
        model without kinds): ops.ring_pages of the rows the model says a
        slot needs there under the widest step."""
        if not kinds:
            return 0
        from ..ops import ring_pages
        return ring_pages(kinds["window"]["window"], prefill_chunk,
                          page_size)

    @classmethod
    def paged_kv_bytes(cls, model, max_batch_size, max_len,
                       prefill_chunk: int = 32, page_size=None,
                       num_pages=None, kv_dtype=None) -> int:
        """Device bytes a paged batcher of this geometry would hold
        (pool + scales + page table) — pure shape arithmetic, NO
        allocation (the bench's int8-vs-bf16 sizing comparison must
        not burn two throwaway pools of HBM).  Matches
        kv_cache_bytes() of a real instance (test-pinned)."""
        B = int(max_batch_size)
        prefill_chunk = max(1, min(int(prefill_chunk), int(max_len)))
        ps, p_slot, n_pages = cls._paged_geometry(
            B, int(max_len), prefill_chunk, page_size, num_pages)
        # the row is the MODEL's to state (K and V heads, or one latent)
        spec = model.kv_row_spec(kv_dtype)
        layers = model.config.num_hidden_layers
        table = B * p_slot * 4
        if spec.get("kinds"):
            # a pool a kind: the full layers' behind the page table, the
            # window layers' rings (B slots of ring pages, no table)
            held = cls._kind_pool_bytes(
                spec, n_pages, B * cls._ring_pages(
                    spec["kinds"], prefill_chunk, ps), ps)
            return sum(held.values()) + table
        rows = sum(int(np.prod(row)) for row in spec["pools"].values())
        pool = n_pages * ps * layers * rows \
            * jnp.dtype(spec["dtype"]).itemsize
        scales = len(spec["pools"]) * n_pages * layers * spec["scales"] * 4
        return pool + scales + table

    @staticmethod
    def _kind_pool_bytes(spec, num_pages, window_pages, page_size):
        """{kind: bytes} of the pools of a model with kinds of layer."""
        item = jnp.dtype(spec["dtype"]).itemsize
        out = {}
        for kind, pages in (("full", num_pages), ("window", window_pages)):
            k = spec["kinds"][kind]
            rows = sum(int(np.prod(spec["pools"][n])) for n in k["pools"])
            out[kind] = pages * page_size * len(k["layers"]) * rows * item
        return out

    # -- public API --------------------------------------------------------
    def submit(self, input_ids, max_new_tokens: int = 32,
               slo: str = "batch",
               deadline_ms: Optional[float] = None,
               on_token=None) -> int:
        """Queue one request; returns its id.  Admission happens at the
        next chunk boundary, in SLO-class priority order (FIFO by
        arrival within a class).

        slo: "interactive" | "batch" | "best_effort".
        deadline_ms: latest time (from now) by which the request must
        be ADMITTED; still queued past it = shed as a deadline miss
        (None reads FLAGS_serve_default_deadline_ms; 0/unset = none).
        on_token: streaming callback `on_token(req_id, tokens, done)`
        fired from run()/step() as chunks complete — `tokens` is the
        NEW burst of output-surviving token ids (EOS-trimmed, capped
        at max_new_tokens; speculation delivers whole accepted runs),
        `done=True` exactly once at the terminal delivery (finish,
        drain flush or shed).  Callback exceptions are swallowed and
        counted (`callback_errors`) — a broken consumer must not
        poison the batch.

        Every submitted id appears exactly once in run()'s results —
        a request shed by the bounded queue / a deadline / the drain
        protocol comes back with `shed=True` and an empty (or partial)
        output, never silently dropped (the chaos no-leak contract)."""
        ids = np.asarray(input_ids.value if isinstance(input_ids, Tensor)
                         else input_ids, np.int32).reshape(-1)
        if len(ids) == 0:
            raise ValueError("empty prompt: a request needs at least "
                             "one token to condition on")
        if len(ids) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(ids)}) + {max_new_tokens} new tokens "
                f"exceeds the slot depth max_len={self.max_len}")
        if slo not in SLO_CLASSES:
            raise ValueError(f"unknown SLO class {slo!r}; known: "
                             f"{SLO_CLASSES}")
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, ids, int(max_new_tokens), slo=slo,
                      arrival=self._arrival_seq, on_token=on_token)
        req.t_submit = self._now()
        self._arrival_seq += 1
        if deadline_ms is None:
            deadline_ms = float(get_flag("serve_default_deadline_ms")
                                or 0.0)
        if deadline_ms <= 0:
            deadline_ms = None          # 0/unset = no deadline, same
            #                             convention as the flag
        if deadline_ms is not None:
            req.deadline = self._now() + float(deadline_ms) / 1e3
            self._has_deadlines = True
        self._submitted += 1
        if self._draining:
            # admissions are closed: the request is accounted, shed
            self._shed(req, "drain")
            return rid
        with self._qlock:
            depth = int(get_flag("serve_queue_depth") or 0)
            if depth > 0 and self._queued_count() >= depth:
                victim = self._shed_victim(req)
                if victim is req:
                    self._shed(req, "queue_full")
                    return rid
                self._queues[victim.slo].remove(victim)
                self._shed(victim, "queue_full")
            self._queues[slo].append(req)
        return rid

    def _queued_count(self) -> int:
        with self._qlock:
            return sum(len(q) for q in self._queues.values())

    def queue_snapshot(self) -> Dict[str, int]:
        """Atomic {slo_class: queued count} snapshot — one consistent
        view of every class queue (the lock orders it against a
        concurrent submit/admit), so a router balancing on per-class
        depth (or telemetry_report) can never see a torn count."""
        with self._qlock:
            return {c: len(q) for c, q in self._queues.items()}

    @property
    def queued(self) -> int:
        """Requests waiting for a slot (all SLO classes)."""
        return self._queued_count()

    def _shed_victim(self, incoming: Request) -> Request:
        """Queue-overflow victim: lowest SLO class first, newest
        arrival within it — the incoming request itself when nothing
        queued ranks below it.  Only QUEUED requests are candidates;
        in-flight slots are untouchable."""
        order = {c: i for i, c in enumerate(SLO_CLASSES)}

        def rank(r):
            return (order[r.slo], r.arrival)
        victim = incoming
        for q in self._queues.values():
            for r in q:
                if rank(r) > rank(victim):
                    victim = r
        return victim

    def step(self) -> List[Request]:
        """One scheduling round: evict finished slots, shed queued
        requests past their deadline, admit queued requests into free
        slots (SLO priority, FIFO within class), run one scan chunk
        (admission-mode while any slot is still consuming its prompt,
        pure decode otherwise).  Returns requests finished this round.

        Once `guard.drain_requested()` is set (SIGTERM), admissions
        close: queued requests are shed with reason "drain" and only
        the in-flight slots keep decoding."""
        from .. import telemetry as _tel
        from ..distributed import guard
        self._chunk_no = self._chunk_count + 1
        self._phase_ms = dict.fromkeys(PHASES, 0.0)
        with _tel.span("serve.step", chunk=self._chunk_no):
            if not self._draining and guard.drain_requested():
                self._begin_drain()
            with self._phase("evict"):
                newly = self._evict()
            if not self._draining:
                with self._phase("admit") as admit:
                    before = self._admissions
                    self._shed_deadline_missed()
                    self._admit()
                    admit.set(admitted=self._admissions - before)
            # frozen hand-off slots (prefill role, prompt consumed,
            # waiting for a decode worker) are done=True device-side and
            # need no chunks — a prefill batcher whose live slots are
            # all frozen parks until export_handoff() frees them
            if any(r is not None and r.req_id not in self._handoff_ready
                   for r in self._slots) \
                    and self._run_chunk(mixed=bool(self._mode_host.any())):
                # pre-chunk evictions cleared their slots, so the two
                # harvests are disjoint
                with self._phase("evict"):
                    newly += self._evict()
                self._close_chunk()
        return newly

    @contextlib.contextmanager
    def _phase(self, name: str, **ids):
        """One phase of a step(): the span `serve.<name>`, and its
        duration added to the chunk's record whether or not anything
        listens (two clock reads)."""
        from .. import telemetry as _tel
        t0 = time.perf_counter()
        try:
            with _tel.span("serve." + name, **ids) as sp:
                yield sp
        finally:
            self._phase_ms[name] += (time.perf_counter() - t0) * 1e3

    def _close_chunk(self):
        """The chunk's phase durations into the bounded window (steady
        chunks only, as `_chunk_times`) and onto its `serve.chunk`
        event, which waits for the second eviction to be counted."""
        ph = self._phase_ms
        ph["harvest"] -= ph["deliver"]
        if not self._first_use:
            self._phase_times.append(tuple(ph[k] for k in PHASES))
        fields, self._chunk_event = self._chunk_event, None
        if fields is not None:
            from .. import telemetry as _tel
            fields.update((f"{k}_ms", round(ph[k], 3)) for k in PHASES)
            _tel.emit("serve.chunk", fields)

    def run(self) -> Dict[int, np.ndarray]:
        """Drive until queue and slots drain; returns {req_id: tokens}
        for EVERY submitted request (shed ones included — empty or
        partial outputs, `Request.shed` set).

        Drain contract (mirrors the r9 training drain): when SIGTERM
        sets the drain flag, admissions stop, in-flight decodes finish
        within PADDLE_DRAIN_GRACE seconds, and on grace expiry the
        still-running slots are flushed as PARTIAL results — run()
        then returns normally so the caller can deliver what exists
        and exit ELASTIC_EXIT_CODE."""
        while self._queued_count() or any(r is not None
                                          for r in self._slots):
            if self._draining and self._drain_deadline is not None \
                    and self._now() > self._drain_deadline:
                self._flush_partial()
                break
            if self._handoff_ready:
                # prefill-role batcher driven standalone: once every
                # live slot is frozen awaiting hand-off (and no queued
                # request can fill a free slot) step() can make no
                # progress — park and let the router export
                occ = [r for r in self._slots if r is not None]
                if occ and all(r.req_id in self._handoff_ready
                               for r in occ) \
                        and not (len(occ) < self.B
                                 and self._queued_count()):
                    break
            self.step()
        return {rid: r.output() for rid, r in self._finished.items()}

    @property
    def drained(self) -> bool:
        """True once the SIGTERM drain protocol engaged — the caller's
        cue to exit ELASTIC_EXIT_CODE after delivering run()'s
        results."""
        return self._draining

    # -- streaming delivery (ISSUE 11 satellite) ---------------------------
    def _deliver(self, req: Request, done: bool) -> int:
        """Hand the request's NEW output-surviving tokens to its
        on_token callback: the deliverable prefix is EOS-trimmed and
        capped at max_new_tokens (exactly what output() will return),
        so a streamed consumer never sees a token the final result
        drops.  `done=True` fires exactly once, at the terminal
        delivery.  Host-plane only — the compiled programs cannot
        tell a streaming request from a plain one.  Returns how many
        tokens it handed out."""
        if req.on_token is None:
            return 0
        cap = req.max_new_tokens
        if self.eos is not None and self.eos in req.tokens:
            cap = min(cap, req.tokens.index(self.eos) + 1)
        end = min(len(req.tokens), cap)
        burst = [int(t) for t in req.tokens[req.delivered:end]]
        if not burst and not done:
            return 0
        req.delivered_tokens.extend(burst)
        try:
            req.on_token(req.req_id, burst, done)
        except Exception:
            self._cb_errors += 1
        return len(burst)

    # -- robustness plumbing (ISSUE 9) -------------------------------------
    def _shed(self, req: Request, reason: str):
        """Terminal no-service state: the request is accounted in
        `_finished` (so run() returns it and nothing leaks) but marked
        shed.  Callers remove it from queue/slot structures FIRST; an
        in-flight decode is never shed."""
        req.finished = True
        req.shed = True
        req.shed_reason = reason
        self._finished[req.req_id] = req
        self._deliver(req, done=True)
        self._shed_count += 1
        self._shed_by_class[req.slo] += 1
        self._terminal_window.append(1.0)
        from .. import telemetry as _tel
        _tel.counter("serve.shed").inc()         # sink or not
        if _tel.active():
            _tel.emit("serve.shed", req=req.req_id, slo=req.slo,
                      reason=reason, requeues=req.requeues,
                      tokens=len(req.tokens))

    def _shed_deadline_missed(self):
        """Shed every QUEUED request whose admission deadline passed
        (`serve.deadline_miss`).  Skipped entirely until a deadline
        ever enters the queue — the flags-off path stays one bool."""
        if not self._has_deadlines:
            return
        now = self._now()
        from .. import telemetry as _tel
        with self._qlock:
            for cls in SLO_CLASSES:
                q = self._queues[cls]
                survivors = deque()
                while q:
                    req = q.popleft()
                    if req.deadline is not None and now > req.deadline:
                        self._deadline_misses += 1
                        _tel.counter("serve.deadline_miss").inc()
                        if _tel.active():
                            _tel.emit("serve.deadline_miss",
                                      req=req.req_id, slo=req.slo,
                                      late_ms=round(
                                          (now - req.deadline) * 1e3,
                                          3))
                        self._shed(req, "deadline")
                    else:
                        survivors.append(req)
                self._queues[cls] = survivors

    def _requeue(self, req: Request):
        """Put a faulted-slot request back into its class queue AT ITS
        ARRIVAL POSITION (strict FIFO by arrival survives requeues)."""
        with self._qlock:
            q = self._queues[req.slo]
            idx = 0
            while idx < len(q) and q[idx].arrival < req.arrival:
                idx += 1
            q.insert(idx, req)
        self._requeue_count += 1
        from .. import telemetry as _tel
        _tel.counter("serve.requeue").inc()
        if _tel.active():
            _tel.emit("serve.requeue", req=req.req_id, slo=req.slo,
                      requeues=req.requeues)

    def _stage(self, i: int, **fields):
        """Stage a write of slot i's device-side state: `done`, `mode`,
        `pos`, `plen`, `tok` (the token it feeds next; under the block
        schedule the passes its block has had), `prompt` [max_len],
        `pages` [pages_per_slot].  A later write of a field wins.
        Nothing reads that state but the step programs, so every write
        of a chunk boundary waits for `_flush_staged`."""
        self._staged.setdefault(i, {}).update(fields)

    def _flush_staged(self):
        """Apply the staged slot writes in ONE program of fixed shapes,
        whatever the number of slots written: a [B] mask and a
        slot-shaped value for each field that some slot writes.  (One
        `.at[i].set` a field a slot was 13 ms of dispatch a request
        admitted and 6-9 a request finished, all of it with the device
        idle; requests of one length end, and are replaced, together.)"""
        if not self._staged:
            return
        state = {"done": self._done, "mode": self._mode, "pos": self._pos,
                 "plen": self._plen, "prompt": self._prompts,
                 "tok": self._tok["step"] if self._diffusion
                 else self._tok}
        if self.kv_layout == "paged":
            state["pages"] = self._page_table
        written = {f for fields in self._staged.values() for f in fields}
        state = {f: state[f] for f in sorted(written)}
        masks = {f: np.zeros((self.B,), bool) for f in state}
        values = {f: np.zeros(a.shape, a.dtype) for f, a in state.items()}
        for i, fields in self._staged.items():
            for f, v in fields.items():
                masks[f][i] = True
                values[f][i] = v
        self._staged.clear()
        new = _slot_writes(state, masks, values)
        self._done = new.get("done", self._done)
        self._mode = new.get("mode", self._mode)
        self._pos = new.get("pos", self._pos)
        self._plen = new.get("plen", self._plen)
        self._prompts = new.get("prompt", self._prompts)
        if "pages" in new:
            self._page_table = new["pages"]
        if "tok" in new:
            self._tok = dict(self._tok, step=new["tok"]) \
                if self._diffusion else new["tok"]

    def _clear_slot(self, i: int):
        """Free slot i's device-side state: done/mode flags, and for
        the paged layout the slot's page mapping (prompt pages stay
        resident as cached prefix pages; the freed slot's junk lanes
        write the null page)."""
        if self._slots[i] is not None:
            self._no_freeze.discard(self._slots[i].req_id)
        self._slots[i] = None
        # a free slot rests at depth 0: the paged kernel's walk follows
        # pos, and a stale depth would walk the null page that many times
        self._stage(i, done=True, mode=False, pos=0)
        self._mode_host[i] = False
        self._done_host[i] = True
        self._pos_host[i] = 0
        if self.kv_layout == "paged" and self._plans[i] is not None:
            self._alloc.release_plan(self._plans[i])
            self._plans[i] = None
            self._stage(i, pages=np.zeros((self.pages_per_slot,),
                                          np.int32))

    def _fault_slot(self, i: int, reason: str = "decode_fault"):
        """Slot i's decode came back poisoned: evict the slot (pages
        released, pending trie nodes dropped — nothing the faulted
        chunk wrote is ever shareable), discard every token the
        request produced (satellite: the re-decode re-emits them, so
        keeping them would double-count `tokens_produced`), and
        requeue the request at its arrival position for a from-scratch
        re-decode — or shed it when its deadline passed or its retry
        budget (FLAGS_serve_retry_budget) is spent.  The rest of the
        batch keeps decoding untouched."""
        req = self._slots[i]
        self._clear_slot(i)
        req.requeues += 1
        budget = int(get_flag("serve_retry_budget") or 3)
        shedding = (req.deadline is not None
                    and self._now() > req.deadline) \
            or req.requeues > budget or self._draining
        if shedding and req.delivered_tokens:
            # a streaming consumer already HOLDS the delivered prefix —
            # with no re-decode coming, disowning it would break the
            # "never see a token the final result drops" contract.
            # The final output becomes exactly what was streamed (a
            # partial result); the undelivered tail is dropped.  The
            # authoritative copy matters: an intermediate requeue may
            # have discarded `tokens` and the re-decode may not have
            # caught back up to the delivered frontier
            req.tokens[:] = req.delivered_tokens
            del req.token_passes[len(req.tokens):]
            req.partial = True
        else:
            # the re-decode re-emits every token bit-exactly (greedy),
            # so discarding them keeps tokens_produced honest
            req.tokens.clear()
            req.token_passes.clear()
        # the re-decode re-serves the request from scratch: its spans
        # must describe the decode the user actually received
        req.t_admit = None
        req.t_first = None
        req.admit_chunk = req.first_token_chunk = -1
        if shedding:
            self._shed(req, reason)
        else:
            self._requeue(req)

    def _finish_spans(self, req: Request):
        """Close a DELIVERED request's latency spans: stamp t_done,
        fold queue/TTFT/TPOT/e2e into the bounded stats windows and
        the per-SLO attainment counters, and publish one
        `serve.request` event (sink-gated; the host aggregates always
        accumulate so stats() answers sink-less).  Shed requests never
        come through here — no service, no latency sample."""
        from .. import telemetry as _tel
        now = self._now()
        req.t_done = now
        req.done_chunk = self._chunk_no
        _tel.mark("serve.req.done", req=req.req_id, chunk=self._chunk_no)
        self._terminal_window.append(0.0)
        queue_ms = ((req.t_admit if req.t_admit is not None else now)
                    - req.t_submit) * 1e3
        e2e_ms = (now - req.t_submit) * 1e3
        n = min(len(req.tokens), req.max_new_tokens)
        # TTFT/TPOT only exist once a first token did: a drain-flushed
        # request that never produced one must not shift the TTFT
        # percentiles with a no-token wait
        ttft_ms = None
        tpot_ms = None
        if req.t_first is not None:
            ttft_ms = (req.t_first - req.t_submit) * 1e3
            if n > 1:
                # chunked decode emits tokens in bursts, so per-request
                # TPOT is the honest average over the decode window,
                # not a per-token measurement
                tpot_ms = (now - req.t_first) * 1e3 / (n - 1)
        self._lat["queue_ms"].append(queue_ms)
        self._lat["e2e_ms"].append(e2e_ms)
        if ttft_ms is not None:
            self._lat["ttft_ms"].append(ttft_ms)
        if tpot_ms is not None:
            self._lat["tpot_ms"].append(tpot_ms)
        slo = self._slo_lat[req.slo]
        slo["completed"] += 1
        met = None
        if req.deadline is not None:
            slo["with_deadline"] += 1
            met = (req.t_admit is not None
                   and req.t_admit <= req.deadline)
            if met:
                slo["deadline_met"] += 1
        if _tel.active():
            fields = dict(req=req.req_id, slo=req.slo, tokens=n,
                          queue_ms=round(queue_ms, 3),
                          e2e_ms=round(e2e_ms, 3),
                          requeues=req.requeues, partial=req.partial,
                          admit_chunk=req.admit_chunk,
                          first_token_chunk=req.first_token_chunk,
                          done_chunk=req.done_chunk)
            if ttft_ms is not None:
                fields["ttft_ms"] = round(ttft_ms, 3)
            if tpot_ms is not None:
                fields["tpot_ms"] = round(tpot_ms, 3)
            if met is not None:
                fields["deadline_met"] = met
            _tel.emit("serve.request", fields)
            _tel.histogram("serve.e2e_ms").observe(e2e_ms)
            if ttft_ms is not None:
                _tel.histogram("serve.ttft_ms").observe(ttft_ms)
            if tpot_ms is not None:
                _tel.histogram("serve.tpot_ms").observe(tpot_ms)

    def _begin_drain(self):
        """SIGTERM arrived: close admissions (queued requests shed with
        reason "drain"), start the PADDLE_DRAIN_GRACE window for the
        in-flight decodes."""
        self._draining = True
        grace = float(os.environ.get("PADDLE_DRAIN_GRACE", "60"))
        self._drain_deadline = self._now() + grace
        n_shed = 0
        with self._qlock:
            for q in self._queues.values():
                while q:
                    self._shed(q.popleft(), "drain")
                    n_shed += 1
        from .. import telemetry as _tel
        if _tel.active():
            _tel.emit("serve.drain", phase="begin", shed=n_shed,
                      in_flight=self.active, grace_s=grace)

    def _flush_partial(self):
        """Grace expired: flush every still-running slot as a PARTIAL
        result (tokens so far, `Request.partial` set) — delivered, not
        shed; the chunk that was in flight completed at the last
        boundary, so the tokens are real."""
        flushed = 0
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            self._clear_slot(i)
            req.finished = True
            req.partial = True
            self._finished[req.req_id] = req
            self._completed += 1
            self._finish_spans(req)
            self._deliver(req, done=True)
            flushed += 1
        from .. import telemetry as _tel
        if _tel.active():
            _tel.emit("serve.drain", phase="flush", flushed=flushed)

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def tokens_produced(self) -> int:
        """USEFUL tokens produced so far: per request, only tokens that
        survive to its output() (capped at max_new_tokens; EOS-trimmed
        at eviction).  The junk lanes a slot decodes between finishing
        and the next chunk boundary are NOT counted — they would
        overstate serve throughput on chunk-misaligned workloads."""
        live = sum(min(len(r.tokens), r.max_new_tokens)
                   for r in self._slots if r is not None)
        done = sum(min(len(r.tokens), r.max_new_tokens)
                   for r in self._finished.values())
        return live + done

    @property
    def compiled_programs(self) -> int:
        """Distinct compiled step programs this batcher has used — at
        most 2 (the C=1 decode scan + the admission scan) regardless
        of how many prompt lengths it served (the
        no-recompile-per-length contract, pinned by tests); 1 if every
        chunk it ever ran had an admission in flight."""
        return len(self._programs_used)

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the KV cache (pool + scales + page
        tables for the paged layout; the dense ring buffers
        otherwise) — the serve bench's KV HBM metric."""
        leaves = jax.tree_util.tree_leaves(self._cache)
        if self.kv_layout == "paged":
            leaves = leaves + [self._page_table]
        return int(sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in leaves))

    def _attainment_of(self, cls: str) -> Optional[float]:
        """Per-SLO-class attainment, THE derivation stats() and the
        router's balance view share: deadline-bearing traffic reports
        admitted-in-time / deadlined; deadline-free traffic reports
        the served fraction; None with no signal yet (a fresh replica
        is 'headroom', not 'failing')."""
        rec = self._slo_lat[cls]
        shed = self._shed_by_class[cls]
        if rec["with_deadline"]:
            return rec["deadline_met"] / rec["with_deadline"]
        if rec["completed"] or shed:
            return rec["completed"] / (rec["completed"] + shed)
        return None

    @property
    def shed_rate_window(self) -> float:
        """Shed fraction over the last 256 TERMINAL requests (ISSUE 19
        satellite) — the sliding-window twin of the cumulative
        shed_rate: an old shed burst ages out of this one as later
        requests deliver, so a routing/autoscaling policy reading it
        sees CURRENT pressure.  0.0 with no terminal signal yet."""
        w = self._terminal_window
        return round(sum(w) / len(w), 4) if w else 0.0

    def prefix_match_len(self, input_ids) -> int:
        """Prompt tokens of `input_ids` already resident in THIS
        batcher's prefix cache — the prefill work an admission here
        would skip (ISSUE 15 satellite).  A pure read-only trie probe
        (PageAllocator.prefix_match_len): no page is pinned, no LRU
        order perturbed, nothing admitted.  0 for the dense layout or
        with prefix sharing off."""
        if self.kv_layout != "paged" or not self.prefix_sharing:
            return 0
        ids = np.asarray(input_ids.value
                         if isinstance(input_ids, Tensor)
                         else input_ids, np.int32).reshape(-1)
        return self._alloc.prefix_match_len(ids)

    def router_view(self, prompt=None, digest: bool = False) \
            -> Dict[str, object]:
        """Compact host-plane policy view for the serve-fleet router
        (inference/router.py) — everything pick_replica() weighs, and
        the record a replica-per-rank worker publishes to the KV plane
        (router.ReplicaPublisher, the r14 FleetSink key schema).  Much
        cheaper than stats(): no latency summaries, no device reads.
        With `prompt` the view carries this replica's
        prefix_hit_tokens for it (read-only probe).  With `digest` the
        view also carries the bounded trie digest
        (FLAGS_serve_digest_entries) — only the PUBLISHED view pays
        the trie walk; per-submit probes never do."""
        qbc = self.queue_snapshot()
        view: Dict[str, object] = {
            "queued": sum(qbc.values()),
            "queued_by_class": qbc,
            "active": self.active,
            "slots": self.B,
            "role": self.role,
            "handoff_ready": len(self._handoff_ready),
            "draining": self._draining,
            "shed_rate": round(self._shed_count / self._submitted, 4)
            if self._submitted else 0.0,
            "shed_rate_window": self.shed_rate_window,
            "attainment": {c: self._attainment_of(c)
                           for c in SLO_CLASSES},
        }
        if self.kv_layout == "paged":
            view["kv_pages_free"] = self._alloc.pages_free
            view["kv_pages_cached"] = self._alloc.pages_cached
            if digest and self.prefix_sharing:
                n = int(get_flag("serve_digest_entries", 32) or 0)
                view["trie_digest"] = self._alloc.trie_digest(n)
                view["page_size"] = self.page_size
        if prompt is not None:
            view["prefix_hit_tokens"] = self.prefix_match_len(prompt)
        return view

    def stats(self) -> Dict[str, object]:
        """Scheduler counters for the serve bench: slot occupancy,
        prefill-vs-decode token split, per-chunk wall times (p50 over
        the last 1024 chunks; max/counts lifetime-wide; each program's
        first call is excluded from the time stats — it may include
        the one-time XLA compile), and the KV-pool block (pages
        used/free/cached, prefix-hit tokens, evictions, pool bytes).
        prefill_tokens/decode_tokens count scan-level WORK (every lane
        the programs advanced); tokens_produced counts only tokens that
        survive to request outputs.  For a model that generates by
        diffusion over blocks, decode_tokens are the tokens its commit
        passes EMITTED (junk blocks of a finished slot included, as an
        autoregressive slot's junk steps are), so prefill_token_share
        keeps its meaning; the lanes the decode side processed, L a
        slot-pass, are `decode_lanes`, beside the schedule's other
        counts (DIFFUSION_COUNTERS)."""
        n = self._chunk_count
        occ = (self._occupancy_total / (n * self.B)) if n else 0.0
        times = sorted(self._chunk_times)
        qbc = self.queue_snapshot()     # ONE atomic view: "queued"
        #                                 and the per-class counts can
        #                                 never disagree (ISSUE 15)
        out = {
            "chunks": n,
            "decode_chunks": self._chunk_kind_counts["decode"],
            "admit_chunks": self._chunk_kind_counts["admit"],
            "slots": self.B,
            "avg_occupancy": occ,
            "prefill_tokens": self._prefill_tok_total,
            "decode_tokens": self._decode_tok_total,
            # lanes the step programs computed (slots x width x steps)
            # and those that carried a prompt or decode token
            "lanes": self._lanes,
            "lanes_valid": self._lanes_valid,
            "tokens_produced": self.tokens_produced,
            "chunk_time_p50": times[len(times) // 2] if times else 0.0,
            "chunk_time_max": self._chunk_time_max,
            "phase_ms": self._phase_summary(),
            # pages one paged-attention call covers, summed over every
            # chunk's scan steps: held by occupied slots / walked by
            # the kernel.  Equal while every slot is occupied (the walk
            # is ragged); a free slot walks its one or two pages
            "kv_pages_live": self._kv_pages_live,
            "kv_pages_walked": self._kv_pages_walked,
            **self._kv_pages_by_kind,
            **self._model_counts,
            "compiled_programs": self.compiled_programs,
            "kv_layout": self.kv_layout,
            "kv_bytes": self.kv_cache_bytes(),
            # serve-robustness counters (ISSUE 9).  The no-leak
            # contract chaos_check --serve asserts: once queue and
            # slots drain, requests_submitted == requests_completed +
            # requests_shed, with requeued requests completing exactly
            # once (their discarded pre-fault tokens never reach
            # tokens_produced)
            "requests_submitted": self._submitted,
            "requests_admitted": self._admissions,
            "requests_completed": self._completed,
            "requests_shed": self._shed_count,
            "requests_requeued": self._requeue_count,
            "shed_by_class": dict(self._shed_by_class),
            "shed_rate_window": self.shed_rate_window,
            "deadline_misses": self._deadline_misses,
            "chunk_retries": self._chunk_retries,
            "hung_chunks": self._hung_chunks,
            "callback_errors": self._cb_errors,
            "queued": sum(qbc.values()),
            "queued_by_class": qbc,
            "drained": self._draining,
            # disaggregated serving (ISSUE 20): hand-off terminals.
            # Per-batcher no-leak partition becomes submitted ==
            # completed + shed + handoffs_out (imports count as
            # submissions on the decode side)
            "role": self.role,
            "handoffs_out": self._handoffs_out,
            "handoffs_in": self._handoffs_in,
            "handoff_bytes": self._handoff_bytes,
            "handoff_ready": len(self._handoff_ready),
        }
        wo = getattr(self.model, "_weight_only", None)
        out["weight_only"] = wo["dtype"] if wo else "none"
        if self.spec_k:
            # speculation block (ISSUE 11): accept_rate over drafted
            # tokens, accepted_per_step (= n_emit, drafts + bonus) over
            # a bounded window of active slot-steps
            from ..telemetry import percentiles_of
            window = list(self._spec_emit_window)
            pct = percentiles_of(window)
            out.update(
                spec_tokens=self.spec_k,
                spec_drafted=self._spec_drafted,
                spec_accepted=self._spec_accepted,
                spec_accept_rate=round(
                    self._spec_accepted / self._spec_drafted, 4)
                if self._spec_drafted else 0.0,
                spec_accepted_per_step={
                    "mean": round(sum(window) / len(window), 3)
                    if window else 0.0,
                    "p50": round(pct["p50"], 3),
                    "p99": round(pct["p99"], 3)},
            )
        # per-request latency spans (ISSUE 10): queue->admit->first-
        # token->finish percentiles over the last 1024 delivered
        # requests, and per-SLO-class deadline attainment.  The shared
        # summary derivation (ISSUE 14) adds TRUE window min/max —
        # percentile reservoirs sample away exactly the extreme
        # straggler/TTFT outliers an incident investigation needs
        from ..telemetry import summary_of
        latency = {}
        for k, window in self._lat.items():
            s = summary_of(list(window))
            latency[k] = {"count": s["count"],
                          "min": round(s["min"], 3),
                          "max": round(s["max"], 3),
                          "p50": round(s["p50"], 3),
                          "p90": round(s["p90"], 3),
                          "p99": round(s["p99"], 3)}
        out["latency"] = latency
        attain = {}
        for cls in SLO_CLASSES:
            rec = dict(self._slo_lat[cls])
            rec["shed"] = self._shed_by_class[cls]
            att = self._attainment_of(cls)
            if att is not None:
                rec["attainment"] = round(att, 4)
            attain[cls] = rec
        out["slo_attainment"] = attain
        if self.kv_layout == "paged":
            out.update(
                kv_page_size=self.page_size,
                kv_pages=self.num_pages,
                kv_pages_used=self._alloc.pages_used,
                kv_pages_free=self._alloc.pages_free,
                kv_pages_cached=self._alloc.pages_cached,
                kv_dtype=self._kv_dtype,
                prefix_hit_tokens=self._alloc.prefix_hit_tokens,
                import_hit_tokens=self._alloc.import_hit_tokens,
                grafted_pages=self._alloc.grafted_pages,
                evictions=self._alloc.evictions,
                cow_copies=self._alloc.cow_copies,
            )
            if self._kinds:
                # the bytes each kind of layer really holds
                out["kv_pool_bytes"] = dict(self._kv_pool_bytes)
                out["kv_ring_pages"] = self.ring_pages
        else:
            out.update(prefix_hit_tokens=0, import_hit_tokens=0,
                       grafted_pages=0, evictions=0, cow_copies=0)
        return out

    def _phase_summary(self) -> Dict[str, Dict[str, float]]:
        """{phase: {p50, max}} in ms over the window of chunks: which
        part of step() a slow stretch sat in."""
        from ..telemetry import summary_of
        out = {}
        for k, column in zip(PHASES, zip(*self._phase_times)):
            s = summary_of(column, qs=(50,))
            out[k] = {"p50": round(s["p50"], 3), "max": round(s["max"], 3)}
        return out or {k: {"p50": 0.0, "max": 0.0} for k in PHASES}

    # -- scheduling --------------------------------------------------------
    def _evict(self) -> List[Request]:
        out = []
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            if req.req_id in self._handoff_ready:
                # frozen awaiting hand-off: done=True device-side is
                # the freeze, not a finish — never evict, never treat
                # as capped; export_handoff() clears the slot
                continue
            hit_eos = self.eos is not None and self.eos in req.tokens
            if hit_eos:
                req.tokens = req.tokens[: req.tokens.index(self.eos)
                                        + 1]
                del req.token_passes[len(req.tokens):]
            if self.role == "prefill" and not self._mode_host[i] \
                    and req.tokens and not hit_eos \
                    and not self._done_host[i] \
                    and req.req_id not in self._no_freeze \
                    and len(req.tokens) < req.max_new_tokens:
                # prefill worker finished this slot's prompt (pos >=
                # prompt_len, first token(s) emitted inside the admit
                # scan): FREEZE it — done=True parks the lanes (done
                # lanes advance nothing; their junk writes land past
                # pos, never on valid rows) with pages pinned until a
                # decode worker imports the KV.  Also reached when a
                # role flip strands mid-decode slots: they hand off
                # at pos = prompt_len + k and resume elsewhere.
                self._handoff_ready[req.req_id] = i
                self._stage(i, done=True)
                self._done_host[i] = True
                continue
            # capacity clamp: a slot whose ring buffer filled stops
            # emitting — finish it short rather than spin forever
            # (unreachable while submit() enforces prompt+new<=max_len)
            capped = (self._done_host[i] and not self._mode_host[i]
                      and req.tokens)
            if hit_eos or capped \
                    or len(req.tokens) >= req.max_new_tokens:
                req.finished = True
                self._finished[req.req_id] = req
                self._completed += 1
                self._finish_spans(req)
                self._deliver(req, done=True)
                # _clear_slot unmaps the slot's pages (prompt pages
                # stay resident as cached prefix pages) and points the
                # freed slot at the null page — a free slot's junk
                # lanes keep writing, and its old pages may be someone
                # else's now
                self._clear_slot(i)
                out.append(req)
        return out

    def _admit(self):
        """Stage queued requests into free slots: plan the slot's page
        mapping (prefix-shared pages + fresh privates, CoW copy at a
        mid-page divergence), write the prompt into the device-side
        buffer and flip the slot to prefill mode.  No forward pass
        happens here — the UNSHARED part of the prompt is consumed
        chunk by chunk inside the next admission-mode scan, overlapped
        with every live slot's decode.

        SLO order: classes in priority order, strict FIFO by arrival
        within a class.  Under pool pressure (alloc fails even after
        evicting cached prefix pages) the class HEAD defers to a later
        boundary and blocks its own and lower classes — no head-of-
        line bypass, so later short prompts can never starve a
        deferred long one (satellite regression) — unless nothing is
        running, which means the pool can never serve this request:
        that raises.  Injected faults (`serve.admit` /
        `serve.kv_alloc`) retry FIFO-in-place, bounded by
        FLAGS_serve_retry_budget.

        Runs under the queue lock: admission pops heads while a
        concurrent submit() may be appending — the router's balance
        snapshots must order against both."""
        with self._qlock:
            return self._admit_locked()

    def _admit_locked(self):
        from ..distributed import fault
        from .. import telemetry as _tel
        free = [i for i in range(self.B) if self._slots[i] is None]

        def retry_exhausted(q, req, reason):
            """Injected admission-path fault: bump the per-request
            retry count.  Past FLAGS_serve_retry_budget the request
            is shed (True — caller moves to the next one); otherwise
            it keeps its FIFO position for the next boundary (False —
            caller defers this class and lower)."""
            req.admit_faults += 1
            if req.admit_faults > int(
                    get_flag("serve_retry_budget") or 3):
                q.popleft()
                self._shed(req, reason)
                return True
            return False

        for cls in SLO_CLASSES:
            q = self._queues[cls]
            while q and free:
                req = q[0]
                # injected admission fault: error = transient (retry
                # this head at the next boundary, FIFO kept); skip =
                # admission rejected outright (shed)
                try:
                    f = fault.hit("serve.admit",
                                  key=f"req{req.req_id}:{cls}")
                except fault.FaultError:
                    if retry_exhausted(q, req, "admit_fault"):
                        continue
                    return          # blocked: same+lower classes wait
                if f is not None and f.mode == "skip":
                    q.popleft()
                    self._shed(req, "admit_fault")
                    continue
                plan = None
                if self.kv_layout == "paged":
                    ps = self.page_size
                    covered_rows = min(
                        len(req.prompt) + req.max_new_tokens
                        + self._overshoot, self._cache_len)
                    covered_pages = min(-(-covered_rows // ps),
                                        self.pages_per_slot)
                    try:
                        fk = fault.hit("serve.kv_alloc",
                                       key=f"req{req.req_id}")
                    except fault.FaultError:
                        # transient allocator fault == pool pressure:
                        # FIFO deferral, bounded like admit faults
                        if retry_exhausted(q, req, "kv_alloc_fault"):
                            continue
                        return
                    if fk is not None:
                        # data-mode kv_alloc fault: simulated pool
                        # exhaustion — defer exactly like pressure
                        # (bounded so times=* cannot spin run())
                        if retry_exhausted(q, req, "kv_alloc_fault"):
                            continue
                        return
                    plan = self._alloc.admit(
                        req.prompt if self.prefix_sharing
                        else req.prompt[:0], covered_pages)
                    if plan is None:
                        if self.active == 0:
                            # nothing is running, so no pages will
                            # ever free: deferring would spin forever
                            raise RuntimeError(
                                f"KV pool ({self.num_pages - 1} usable "
                                f"pages of {ps} rows) cannot ever hold "
                                f"this request ({covered_pages} pages); "
                                f"grow num_pages or shrink the request")
                        return      # pressure: defer same+lower classes
                q.popleft()
                i = free.pop(0)
                self._admissions += 1
                self._slots[i] = req
                req.t_admit = self._now()   # re-stamped on re-admission
                req.admit_chunk = self._chunk_no
                _tel.mark("serve.req.admit", req=req.req_id,
                          chunk=self._chunk_no)
                buf = np.zeros((self.max_len,), np.int32)
                buf[: len(req.prompt)] = req.prompt
                # tok 0: an autoregressive slot's first input, or (the
                # block schedule) no pass yet: the scan seeds the block
                # from the prompt's tail when the slot first decodes
                self._stage(i, prompt=buf, plen=len(req.prompt), tok=0,
                            done=False)
                self._block_step_host[i] = 0
                self._done_host[i] = False
                start = 0
                if plan is not None:
                    self._plans[i] = plan
                    row = np.zeros((self.pages_per_slot,), np.int32)
                    row[: len(plan.pages)] = plan.pages
                    self._stage(i, pages=row)
                    if plan.cow is not None:
                        # copy-on-write at the divergence boundary:
                        # clone the partially-matched page into the
                        # slot's first private page, then prefill
                        # resumes mid-page.  admit() pinned the source
                        # so pressure could not reclaim it before this
                        # copy — unpin it now
                        src, dst = plan.cow
                        self._cache = self._page_copy_fn()(
                            self._cache, jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32))
                        self._alloc.release_page(src)
                    start = plan.shared_tokens
                # prefix-shared tokens are already resident: prefill
                # starts at the divergence, or straight to decode when
                # only the final prompt token remains.  Under the block
                # schedule a row's K/V hangs on every token of its
                # block, so shared rows are rounded down to whole blocks
                # (a page ends where a block ends, and a mid-page
                # divergence resumes in the slot's private copy), only
                # the prompt's WHOLE blocks prefill, and its tail goes
                # to the first block
                L = self.block_len
                start = start // L * L
                prefilling = start < len(req.prompt) // L * L
                self._stage(i, pos=start, mode=prefilling)
                self._pos_host[i] = start
                self._mode_host[i] = prefilling

    # -- compiled pieces ---------------------------------------------------
    def _param_vals(self):
        sd = self.model.state_dict()
        return [sd[n]._value for n in self._names]

    def _program_key(self, width: int, length: int):
        base = ("serve_step", self.B, self._cache_len, self.max_len,
                width, length)
        if self.kv_layout == "paged":
            base += ("paged", self.page_size, self.num_pages,
                     self.pages_per_slot, self._kv_dtype)
        if self.spec_k:
            # speculation changes BOTH programs (the draft cache rides
            # the admit carry too) and the compiled body closes over
            # the draft — K and the draft's identity are part of what
            # the program baked in (satellite 2)
            base += ("spec", self.spec_k) + self._draft_key
        if self._diffusion:
            base += ("diffusion",) + tuple(self._diffusion.values())
        if self._kinds:
            base += ("ring", self.ring_pages)
        return base

    def _page_copy_fn(self):
        """One-page device copy (pool rows + scales, all layers) for
        copy-on-write admissions; compiled once per pool shape and
        cached on the model beside the step programs."""
        from .generation import _model_program_cache
        key = ("serve_page_copy", self.num_pages, self.page_size,
               self._kv_dtype)

        def build():
            def serve_page_copy(cache, src, dst):
                out = dict(cache)
                for name in cache:
                    buf = cache[name]
                    out[name] = buf.at[dst].set(buf[src])
                return out
            return jax.jit(serve_page_copy, donate_argnums=(0,))
        return _model_program_cache(self.model, key, build)

    # -- disaggregated hand-off (ISSUE 20) ---------------------------------
    def set_role(self, role: str):
        """Host-plane role flip (the autoscaler's role-repair path).
        Flipping to 'prefill' strands nothing: slots mid-decode freeze
        at the next boundary and hand off their KV; flipping away from
        'prefill' simply reopens normal decode for future admissions
        (already-frozen slots still leave via export_handoff)."""
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role {role!r}: unified|prefill|decode")
        if role != "unified" and self.kv_layout != "paged":
            raise TypeError("disaggregated roles need kv_layout="
                            "'paged' (the hand-off ships pages)")
        if self._diffusion:
            self._refuse_for_diffusion(self.kv_layout, role)
        if self._kinds:
            self._refuse_for_kinds(self.kv_layout, role, False)
        self.role = role

    def _page_export_fn(self):
        """Fixed-shape page gather for hand-off/replication export:
        [pages_per_slot] page ids -> per-buffer [pages_per_slot, ...]
        rows.  Pad entries point at the null page (junk by design), so
        ONE compiled program covers every export regardless of how
        many pages are valid.  Read-only: the pool is not donated."""
        from .generation import _model_program_cache
        key = ("serve_page_export", self.num_pages, self.page_size,
               self.pages_per_slot, self._kv_dtype)

        def build():
            def serve_page_export(cache, idx):
                return {name: cache[name][idx] for name in cache}
            return jax.jit(serve_page_export)
        return _model_program_cache(self.model, key, build)

    def _page_import_fn(self):
        """Fixed-shape page scatter for hand-off/replication import:
        rows land at the given page ids; entries the import does not
        need (already-resident shared chunks, pad rows) point at the
        null page, whose content is junk by contract — so duplicate
        null indices in the scatter are harmless.  The pool is donated
        exactly like the step carries."""
        from .generation import _model_program_cache
        key = ("serve_page_import", self.num_pages, self.page_size,
               self.pages_per_slot, self._kv_dtype)

        def build():
            def serve_page_import(cache, idx, data):
                out = dict(cache)
                for name in cache:
                    out[name] = cache[name].at[idx].set(data[name])
                return out
            return jax.jit(serve_page_import, donate_argnums=(0,))
        return _model_program_cache(self.model, key, build)

    def _handoff_page_bytes(self, data, n_pages: int) -> int:
        total = 0
        for a in data.values():
            total += (a.nbytes // self.pages_per_slot) * n_pages
        return int(total)

    def export_handoff(self, rid: int):
        """Detach a frozen hand-off-ready request: gather its valid KV
        pages (rows [0, pos)) plus everything a decode worker needs to
        resume at pos — prompt, emitted tokens, SLO state — and free
        the slot.  The prompt's full chunks stay RESIDENT here as
        cached prefix pages, so later prompts sharing them still skip
        their prefill chunks on this worker.  Accounting: the request
        leaves as a hand-off, not a completion — per batcher,
        submitted == completed + shed + handoffs_out."""
        i = self._handoff_ready.pop(rid, None)
        if i is None:
            raise KeyError(f"request {rid} is not hand-off ready")
        req = self._slots[i]
        pos = int(self._pos_host[i])
        ps = self.page_size
        n_pages = -(-pos // ps)
        plan = self._plans[i]
        idx = np.zeros((self.pages_per_slot,), np.int32)
        idx[:n_pages] = plan.pages[:n_pages]
        data = self._page_export_fn()(self._cache, jnp.asarray(idx))
        nbytes = self._handoff_page_bytes(data, n_pages)
        meta = {
            "rid": int(req.req_id),
            "prompt": np.asarray(req.prompt, np.int32),
            "pos": pos,
            "plen": int(len(req.prompt)),
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": int(req.max_new_tokens),
            "slo": req.slo,
            "deadline": req.deadline,
            "t_submit": req.t_submit,
            "t_first": req.t_first,
            "n_pages": int(n_pages),
            "page_size": int(ps),
            "kv_dtype": self._kv_dtype,
            "nbytes": int(nbytes),
        }
        self._handoffs_out += 1
        self._handoff_bytes += nbytes
        self._clear_slot(i)
        from .. import telemetry as _tel
        if _tel.active():
            _tel.emit("serve.handoff", dir="export", req=int(rid),
                      pages=int(n_pages), bytes=int(nbytes), pos=pos)
        return meta, data

    def import_handoff(self, meta, data, on_token=None) -> Optional[int]:
        """Admit a handed-off request at ``pos = prompt_len + k``: no
        prefill chunk ever runs for it here (the zero-recompute
        contract — this batcher's prefill_tokens stat stays flat).
        Pages whose chunks are already resident in the local trie are
        NOT rewritten — their rows are bit-identical by the prefix-
        sharing determinism argument — and count as cross-replica
        prefix hits; the rest scatter into freshly allocated pages and
        the prompt chain grafts into the trie, so the fleet-tier cache
        grows where decode traffic lands.  Returns the local req_id,
        or None when no slot (or no pages) is free — the caller
        retries at the next boundary; nothing is allocated on None."""
        if self.role == "prefill":
            raise RuntimeError("prefill-role batcher cannot import a "
                               "hand-off")
        if self._diffusion:
            raise ValueError(
                "a hand-off ships a slot at a token boundary, a "
                "block-diffusion slot stands inside a block; only the "
                "unified role, without hand-offs, serves such a model")
        if self._kinds:
            raise ValueError(
                "a hand-off ships the pages of the page table alone; a "
                "model with sliding-window layers keeps rings beside them "
                "that no hand-off carries")
        if self.kv_layout != "paged":
            raise TypeError("import_handoff needs the paged KV layout")
        if int(meta["page_size"]) != self.page_size \
                or str(meta["kv_dtype"]) != self._kv_dtype:
            raise ValueError(
                "hand-off geometry mismatch: got page_size=%s/%s, "
                "this pool is %d/%s" % (meta["page_size"],
                                        meta["kv_dtype"],
                                        self.page_size, self._kv_dtype))
        with self._qlock:
            free = [i for i in range(self.B)
                    if self._slots[i] is None]
            if not free:
                return None
            prompt = np.asarray(meta["prompt"], np.int32)
            pos = int(meta["pos"])
            ps = self.page_size
            covered_rows = min(
                len(prompt) + int(meta["max_new_tokens"])
                + self._overshoot, self._cache_len)
            covered_pages = min(-(-covered_rows // ps),
                                self.pages_per_slot)
            n_pages = int(meta["n_pages"])
            if n_pages > covered_pages:
                raise ValueError(
                    f"hand-off spans {n_pages} pages but this pool "
                    f"covers {covered_pages} per slot")
            plan = self._alloc.admit(
                prompt if self.prefix_sharing else prompt[:0],
                covered_pages, imported=True)
            if plan is None:
                return None
            if plan.cow is not None:
                # the imported data fully covers the divergence page —
                # skip the device copy, just unpin the CoW source
                self._alloc.release_page(plan.cow[0])
            # scatter only the NON-shared valid pages; shared chunks
            # already hold bit-identical rows (and may be mapped by
            # other live slots) — their data rows land on the null page
            idx = np.zeros((self.pages_per_slot,), np.int32)
            for j in range(plan.n_shared_pages, n_pages):
                idx[j] = plan.pages[j]
            self._cache = self._page_import_fn()(
                self._cache, jnp.asarray(idx), data)
            rid = self._next_id
            self._next_id += 1
            req = Request(rid, prompt, int(meta["max_new_tokens"]),
                          slo=str(meta.get("slo", "batch")),
                          deadline=meta.get("deadline"),
                          arrival=self._arrival_seq,
                          on_token=on_token)
            self._arrival_seq += 1
            if req.deadline is not None:
                self._has_deadlines = True
            req.tokens = [int(t) for t in meta.get("tokens", ())]
            req.t_submit = float(meta.get("t_submit")
                                 or self._now())
            req.t_first = meta.get("t_first")
            req.t_admit = self._now()
            req.admit_chunk = self._chunk_no
            i = free[0]
            self._slots[i] = req
            self._submitted += 1       # arrives as a hand-off, so the
            self._admissions += 1      # no-leak partition still closes
            self._handoffs_in += 1
            nbytes = int(meta.get("nbytes")
                         or self._handoff_page_bytes(data, n_pages))
            self._handoff_bytes += nbytes
            buf = np.zeros((self.max_len,), np.int32)
            buf[: len(prompt)] = prompt
            self._plans[i] = plan
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[: len(plan.pages)] = plan.pages
            self._stage(i, prompt=buf, plen=len(prompt),
                        tok=int(req.tokens[-1]) if req.tokens else 0,
                        done=False, pages=row, pos=pos, mode=False)
            self._done_host[i] = False
            self._pos_host[i] = pos
            self._mode_host[i] = False
            # the prompt's full chunks are valid through pos: complete
            # them now — this is the trie GRAFT that makes the prefix
            # shareable on the decode side
            self._alloc.mark_progress(plan, pos)
            from .. import telemetry as _tel
            if _tel.active():
                _tel.emit("serve.handoff", dir="import", req=int(rid),
                          pages=int(n_pages), bytes=nbytes, pos=pos,
                          dedup_pages=int(plan.n_shared_pages))
            return rid

    def unfreeze_handoff(self, rid: int):
        """Degraded-fleet fallback: no decode-capable replica is left,
        so the frozen slot resumes decoding HERE — the prefill worker
        temporarily breaks its admit-only program diet rather than
        deadlock the request."""
        i = self._handoff_ready.pop(rid)
        # pin the exemption BEFORE clearing done: without it the next
        # _evict sweep would re-freeze this slot instantly (all freeze
        # conditions hold again) and the fleet livelocks on the
        # freeze/unfreeze ping-pong
        self._no_freeze.add(rid)
        self._stage(i, done=False)
        self._done_host[i] = False

    # -- hot-prefix replication (fleet-tier cache placement) ---------------
    def export_prefix(self, tokens):
        """Holder side of cache placement: (n_tokens, data) covering
        the resident complete chain for `tokens`, or None when nothing
        is resident.  Read-only and synchronous — gathered at this
        chunk boundary, before any allocation could evict the chain."""
        if self.kv_layout != "paged" or not self.prefix_sharing:
            return None
        n_tok, pages = self._alloc.export_chain(tokens)
        pages = pages[: self.pages_per_slot]
        if not pages:
            return None
        idx = np.zeros((self.pages_per_slot,), np.int32)
        idx[: len(pages)] = pages
        data = self._page_export_fn()(self._cache, jnp.asarray(idx))
        return len(pages) * self.page_size, data

    def import_prefix(self, tokens, n_tokens: int, data) -> int:
        """Target side of cache placement: graft the chain's chunks
        into the local trie (skipping already-resident ones) and
        scatter the holder's page data.  Returns pages grafted; 0
        under pool pressure — placement is best-effort and must never
        starve serving."""
        if self.kv_layout != "paged" or not self.prefix_sharing:
            return 0
        n_chunks = min(int(n_tokens) // self.page_size,
                       self.pages_per_slot)
        pairs = self._alloc.graft(tokens, n_chunks)
        if not pairs:
            return 0
        idx = np.zeros((self.pages_per_slot,), np.int32)
        for ci, page in pairs:
            idx[ci] = page
        self._cache = self._page_import_fn()(
            self._cache, jnp.asarray(idx), data)
        return len(pairs)

    def _step_fn(self, width: int, length: int, record: bool = True):
        """The unified scan program: `length` steps, each feeding a
        [B, width] token block.  record=False (lower_step) builds or
        fetches the program WITHOUT touching the batcher's
        program/timing bookkeeping — an analysis probe must not
        inflate compiled_programs or defeat the first-use compile
        exclusion.  Per slot b and step:

          prefilling?  consume n=min(width, plen-pos) prompt tokens
                       from prompts[b, pos:pos+width]
          decoding?    feed [tok[b], pad...] (n=1)
          free/done?   n=0 (lanes run but nothing advances)

        Lanes past n write throwaway KV at pos+n..pos+width-1; queries
        only see cache rows j <= pos+lane (per-slot position mask in
        ops.cached_attention / ops.paged_attention) and the next step's
        valid lanes overwrite those rows before its queries can reach
        them, so the garbage is never observable (free slots write
        their junk into the null page).  The logit at lane n-1 is
        argmax-sampled; a slot emits iff it decoded or consumed its
        FINAL prompt chunk (the emitted token then being the prompt's
        greedy first token — bit-identical to what a monolithic
        prefill would sample).  That is the AUTOREGRESSIVE step: one
        token a decoding slot.

        THE BLOCK SCHEDULE (a model whose block_diffusion() gives a
        block length L > 1; `block_core` below; `tok` in the carry is
        then the slots' blocks).  Attention is block-causal (rows j <=
        the end of the block that holds pos+lane), the logit of a lane
        predicts that lane's own token, and per slot and step:

          prefilling?  consume n=min(width, whole blocks left) prompt
                       tokens; no lane needs the head.  When none is
                       left the slot turns to decoding; the prompt's
                       last plen mod L tokens go to its first block
          decoding?    feed its block at lanes 0..L-1 at pos (a multiple
                       of L; n=L): a fresh block holds the prompt's
                       tokens at positions below plen and [MASK] in the
                       rest.  While a lane is masked the pass DENOISES:
                       in every masked lane x0 = argmax and its softmax
                       confidence (fp32); all masked lanes above the
                       threshold are fixed if they number at least the
                       pass's quota, else the quota's most confident;
                       pos stays.  With no lane masked the pass COMMITS:
                       it emits the block (prompt lanes excluded), adds
                       L to pos and leaves a fresh block
          free/done?   n=0

        The pad-lane discipline carries over: every pass of a block
        writes K/V rows pos..pos+L-1 (a denoise pass with [MASK] in
        some lanes); each pass overwrites what the one before left
        there, and the rows are FINAL only after the commit pass, which
        is the last to write them before pos moves on.  No query of
        another block can see them earlier: they lie past every
        committed row.  The scan yields [B, steps * L] tokens with -1
        holes (the speculative program's harvest contract) and beside
        them the pass at which each was fixed.
        """
        key = self._program_key(width, length)
        # first_use consults the MODEL-level store, not this batcher's
        # key set: an LRU-evicted program that recompiles mid-life is
        # excluded from timing again, and a second batcher reusing a
        # warm program keeps its first chunks in the timing window
        from .generation import (_model_program_cache,
                                 _program_cache_contains)
        first_use = not _program_cache_contains(self.model, key)
        if record:
            self._first_use = first_use
        if record and first_use and key in self._programs_used:
            # mid-life re-trace of a program this batcher already ran
            # (LRU eviction / cleared model cache): snapshot stats()
            # into the telemetry plane BEFORE the rebuild — the counters
            # themselves must survive the recompile (regression-pinned),
            # and the snapshot timestamps exactly which chunks predate
            # the new program (its timing stats restart via _first_use)
            from .. import telemetry as _tel
            if _tel.active():
                _tel.emit("serve.recompile",
                          dict(self.stats(), program=str(key)))
        if record:
            self._programs_used.add(key)
        model = self.model
        names = self._names
        C, K = int(width), int(length)
        max_len = self.max_len
        paged = self.kv_layout == "paged"
        spec = self.spec_k > 0
        draft = self._draft
        draft_names = self._draft_names
        counted = bool(self._counter_names)
        n_model_counts = len(self._model_counter_names)
        diffusion = self._diffusion
        from ..jit import _swapped_state

        def build():
            def block_core(carry):
                """One [B, C] step of the block schedule (_step_fn's
                docstring), over the same carry layout."""
                L, S = (diffusion["block_length"],
                        diffusion["denoising_steps"])
                (cache, dcache, page_table, blk, pos, mode, plen,
                 prompts, done) = carry
                prefilling = mode & ~done
                decoding = ~mode & ~done
                lanes = jnp.arange(C, dtype=jnp.int32)
                idx = jnp.clip(pos[:, None] + lanes[None], 0,
                               max_len - 1)
                pref_x = jnp.take_along_axis(prompts, idx, axis=1)
                # a block no pass has had yet: the prompt's tail, then
                # [MASK].  Masked is a FLAG, not `id == mask`: a prompt
                # may hold the mask id
                fresh = (blk["step"] == 0)[:, None]
                given = (pos[:, None] + lanes[None, :L]) < plen[:, None]
                ids = jnp.where(
                    fresh, jnp.where(given, pref_x[:, :L],
                                     diffusion["mask_token_id"]),
                    blk["ids"])
                masked = jnp.where(fresh, ~given, blk["masked"])
                fixed_at = jnp.where(fresh, jnp.where(given, -1, 0),
                                     blk["fixed_at"])
                dec_x = jnp.concatenate(
                    [ids, jnp.zeros((ids.shape[0], C - L), jnp.int32)],
                    axis=1)
                x = jnp.where(prefilling[:, None], pref_x, dec_x)
                whole = plen // L * L
                n_valid = jnp.where(
                    prefilling, jnp.minimum(C, whole - pos),
                    jnp.where(decoding, L, 0)).astype(jnp.int32)
                counts = None
                if n_model_counts:
                    from ..incubate.distributed.models.moe import \
                        StepCounters
                    counts = StepCounters(lanes[None] < n_valid[:, None])
                # the head on the block's lanes only
                lg, cache = model.forward_cached_paged(
                    x, cache, page_table, pos, counts, head_lanes=L)
                with jax.named_scope("diffusion.sample"):
                    lg = lg.astype(jnp.float32)
                    x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    top = jnp.max(lg, axis=-1, keepdims=True)
                    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
                    quota = jnp.asarray(_step_quotas(L, S), jnp.int32)[
                        jnp.clip(blk["step"], 0, S - 1)]
                    fix, by_threshold = _unmask_choice(
                        conf, masked, quota,
                        diffusion["confidence_threshold"])
                with jax.named_scope("diffusion.update"):
                    any_masked = jnp.any(masked, axis=-1)
                    denoise = decoding & any_masked
                    commit = decoding & ~any_masked
                    fix = fix & denoise[:, None]
                    emit = commit[:, None] & (fixed_at >= 0)
                    out_tok = jnp.where(emit, ids, -1)
                    out_pass = jnp.where(emit, fixed_at, -1)
                    spent = jnp.sum(jnp.where(commit, blk["step"] + 1, 0))
                    blk = {"ids": jnp.where(fix, x0, ids),
                           "masked": masked & ~fix,
                           "fixed_at": jnp.where(
                               fix, blk["step"][:, None], fixed_at),
                           "step": jnp.where(denoise, blk["step"] + 1, 0)}
                    finishing = prefilling & (pos + n_valid >= whole)
                    pos = pos + jnp.where(commit, L,
                                          jnp.where(prefilling, n_valid, 0))
                    mode = mode & ~finishing
                    # a slot whose next block would start past its depth
                    done = done | (pos >= max_len)
                    n_pref = jnp.sum(jnp.where(prefilling, n_valid, 0))
                    n_dec = jnp.sum(emit.astype(jnp.int32))
                    schedule = jnp.stack([
                        jnp.sum(denoise), jnp.sum(commit), spent,
                        jnp.sum(fix),
                        jnp.sum(fix & by_threshold[:, None]),
                        jnp.sum(decoding) * L]).astype(jnp.int32)
                if counts is not None:
                    schedule = jnp.concatenate([counts.vector(), schedule])
                carry = (cache, dcache, page_table, blk, pos, mode, plen,
                         prompts, done)
                return carry, (out_tok, n_pref, n_dec, schedule, out_pass)

            def step_core(carry):
                """One [B, C] step over the shared carry layout; the
                draft (speculation on) consumes the SAME x at the same
                pos so its dense cache stays row-for-row in lockstep
                with the target's — prefill fills both, decode rounds
                in the admit program advance both by one."""
                (cache, dcache, page_table, tok, pos, mode, plen,
                 prompts, done) = carry
                prefilling = mode & ~done
                lanes = jnp.arange(C, dtype=jnp.int32)
                idx = jnp.clip(pos[:, None] + lanes[None], 0,
                               max_len - 1)
                pref_x = jnp.take_along_axis(prompts, idx, axis=1)
                dec_x = jnp.concatenate(
                    [tok[:, None],
                     jnp.zeros((tok.shape[0], C - 1),
                               jnp.int32)], axis=1)
                x = jnp.where(prefilling[:, None], pref_x, dec_x)
                n_valid = jnp.where(
                    prefilling,
                    jnp.minimum(C, plen - pos),
                    jnp.where(done, 0, 1)).astype(jnp.int32)
                counts = None
                if counted:
                    # the expert layers route the lanes the step keeps
                    # and count their routing as they are traced
                    from ..incubate.distributed.models.moe import \
                        StepCounters
                    counts = StepCounters(lanes[None] < n_valid[:, None])
                    lg, cache = model.forward_cached_paged(
                        x, cache, page_table, pos, counts)
                elif paged:
                    lg, cache = model.forward_cached_paged(
                        x, cache, page_table, pos)
                else:
                    lg, cache = model.forward_cached(x, cache, pos)
                if spec:
                    # draft prefill rides the admit chunk (logits
                    # discarded — XLA DCEs the draft's lm head here)
                    _, dcache = draft.forward_cached(x, dcache, pos)
                with jax.named_scope("serve.sample"):
                    last = jnp.clip(n_valid - 1, 0, C - 1)
                    lg_last = jnp.take_along_axis(
                        lg, last[:, None, None], axis=1)[:, 0]
                    nxt = jnp.argmax(lg_last.astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)
                finishing = prefilling & (pos + n_valid >= plen)
                emit = finishing | (~prefilling & ~done)
                pos = pos + n_valid
                mode = mode & ~finishing
                tok = jnp.where(emit, nxt, tok)
                # clamp: a slot at capacity stops advancing
                done = done | (pos >= max_len - 1)
                out_tok = jnp.where(emit, nxt,
                                    jnp.full_like(nxt, -1))
                n_pref = jnp.sum(
                    jnp.where(prefilling, n_valid, 0))
                n_dec = jnp.sum(
                    (~prefilling
                     & (n_valid > 0)).astype(jnp.int32))
                carry = (cache, dcache, page_table, tok, pos, mode,
                         plen, prompts, done)
                if counted:
                    return carry, (out_tok, n_pref, n_dec,
                                   counts.vector())
                return carry, (out_tok, n_pref, n_dec)

            def merged(steps):
                """[K, n] counts of a block-schedule scan -> [n]: the
                model's by its own rule, the schedule's summed."""
                out = [jnp.sum(steps[:, n_model_counts:], axis=0)]
                if n_model_counts:
                    from ..incubate.distributed.models.moe import \
                        StepCounters
                    out.insert(0, StepCounters.merge(
                        steps[:, :n_model_counts]))
                return jnp.concatenate(out)

            def run_scan(cache, dcache, page_table, tok, pos, mode,
                         plen, prompts, done):
                def body(carry, _):
                    return block_core(carry) if diffusion \
                        else step_core(carry)
                carry = (cache, dcache, page_table, tok, pos, mode,
                         plen, prompts, done)
                carry, ys = jax.lax.scan(body, carry, None, length=K)
                toks, n_pref, n_dec = ys[:3]
                if diffusion:
                    # [K, B, L] -> [B, K * L]: a slot's row is its
                    # emission stream in position order, -1 = no token
                    # (the speculative program's harvest contract), and
                    # beside it the pass at which each was fixed
                    def stream(a):
                        return a.transpose(1, 0, 2).reshape(a.shape[1], -1)
                    return (carry, stream(toks), jnp.sum(n_pref),
                            jnp.sum(n_dec), merged(ys[3]), stream(ys[4]))
                counts = ()
                if counted:
                    # a counting model's program has one more output
                    from ..incubate.distributed.models.moe import \
                        StepCounters
                    counts = (StepCounters.merge(ys[3]),)
                return (carry, toks.T, jnp.sum(n_pref),
                        jnp.sum(n_dec)) + counts

            if spec:
                def serve_step(param_vals, draft_vals, cache, dcache,
                               page_table, tok, pos, mode, plen,
                               prompts, done):
                    with _swapped_state(model, names,
                                        list(param_vals)):
                        if draft_names:
                            with _swapped_state(draft, draft_names,
                                                list(draft_vals)):
                                carry, toks, n_pref, n_dec = run_scan(
                                    cache, dcache, page_table, tok,
                                    pos, mode, plen, prompts, done)
                        else:
                            carry, toks, n_pref, n_dec = run_scan(
                                cache, dcache, page_table, tok, pos,
                                mode, plen, prompts, done)
                    (cache, dcache, page_table, tok, pos, mode, plen,
                     prompts, done) = carry
                    return (cache, dcache, page_table, tok, pos, mode,
                            plen, prompts, done, toks, n_pref, n_dec)
                return jax.jit(serve_step,
                               donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9,
                                               10))

            def serve_step(param_vals, cache, page_table, tok, pos,
                           mode, plen, prompts, done):
                with _swapped_state(model, names, list(param_vals)):
                    carry, toks, n_pref, n_dec, *counts = run_scan(
                        cache, None, page_table, tok, pos, mode, plen,
                        prompts, done)
                (cache, _, page_table, tok, pos, mode, plen, prompts,
                 done) = carry
                return (cache, page_table, tok, pos, mode, plen,
                        prompts, done, toks, n_pref, n_dec, *counts)
            # donate every carry buffer: the KV pool dominates — a
            # non-donated chunk pays a pool-sized HBM copy per call
            return jax.jit(serve_step,
                           donate_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
        if not record and first_use:
            # probe miss: build a throwaway jit WITHOUT inserting it
            # into the model cache — .lower() never compiles, so a
            # cached probe entry would make the first real chunk look
            # warm (first_use=False) while still paying the XLA
            # compile into the timing stats
            return build()
        return _model_program_cache(model, key, build)

    def _carry_args(self):
        self._flush_staged()
        if self.kv_layout == "paged":
            pt = self._page_table
        else:
            # a [B, 1] placeholder rides the dense carry so both
            # layouts share one program signature (and the donation
            # set); it is never read
            pt = jnp.zeros((self.B, 1), jnp.int32)
        if self.spec_k:
            # the draft cache is one more donated carry, slotted right
            # after the target cache; with K=0 the signature is the
            # pre-speculation one, byte for byte
            return (self._cache, self._dcache, pt, self._tok, self._pos,
                    self._mode, self._plen, self._prompts, self._done)
        return (self._cache, pt, self._tok, self._pos, self._mode,
                self._plen, self._prompts, self._done)

    def _draft_param_vals(self):
        if not self._draft_names:
            return []
        sd = self._draft.state_dict()
        return [sd[n]._value for n in self._draft_names]

    def _spec_step_fn(self, record: bool = True):
        """The speculative DECODE program (ISSUE 11): `chunk` scan
        steps, each drafting K tokens with the draft model (an inner
        K+1-step scan — the extra step exists only for its KV write,
        so an all-accepted round leaves no hole in the draft cache)
        and verifying them in ONE target pass of width K+1 — the
        verify width folded into the chunk axis, so the r6 2-programs
        contract holds.  Per slot and step:

          drafts d_1..d_K  = greedy draft continuations of tok
          verify x         = [tok, d_1..d_K] at pos (writes K+1 KV
                             rows, exactly the prefill-chunk lane
                             discipline)
          targets t_i      = argmax of verify lane i-1 — t_1 is
                             PRECISELY the non-speculative next token,
                             and each accepted d_i == t_i keeps the
                             chain exact
          accept a         = longest prefix with d_i == t_i; emit
                             t_1..t_{a+1} (a drafts + the bonus
                             token), advance pos by a+1

        Rejected rows (pos+a+1..pos+K) are never rolled back on
        device: they sit beyond the new frontier, and the next verify
        window overwrites them before any query can attend them (the
        scan's pad-lane discipline) — the HOST rolls back nothing but
        its own pos view, which arrives already-accepted.  Greedy
        output is therefore bit-exact vs non-speculative decode."""
        Kd = self.spec_k
        W = self._spec_w
        key = self._program_key(W, self.chunk)
        from .generation import (_model_program_cache,
                                 _program_cache_contains)
        first_use = not _program_cache_contains(self.model, key)
        if record:
            self._first_use = first_use
            if first_use and key in self._programs_used:
                # mid-life re-trace (LRU eviction / cleared model
                # cache): same snapshot contract as _step_fn
                from .. import telemetry as _tel
                if _tel.active():
                    _tel.emit("serve.recompile",
                              dict(self.stats(), program=str(key)))
            self._programs_used.add(key)
        model = self.model
        names = self._names
        draft = self._draft
        draft_names = self._draft_names
        K_steps = self.chunk
        max_len = self.max_len
        paged = self.kv_layout == "paged"
        from ..jit import _swapped_state

        def build():
            def spec_core(carry):
                (cache, dcache, page_table, tok, pos, mode, plen,
                 prompts, done) = carry

                # -- draft K (+1 for the cache write) greedy tokens --
                def dbody(dc, _):
                    dcache, dtok, dpos = dc
                    dlg, dcache = draft.forward_cached(
                        dtok[:, None], dcache, dpos)
                    with jax.named_scope("serve.sample"):
                        nxt = jnp.argmax(dlg[:, 0].astype(jnp.float32),
                                         axis=-1).astype(jnp.int32)
                    return (dcache, nxt, dpos + 1), nxt
                (dcache, _, _), drafts = jax.lax.scan(
                    dbody, (dcache, tok, pos), None, length=Kd + 1)
                drafts = drafts.T                       # [B, K+1]

                # -- verify in one width-(K+1) target pass --
                x = jnp.concatenate([tok[:, None], drafts[:, :Kd]],
                                    axis=1)             # [B, K+1]
                if paged:
                    lg, cache = model.forward_cached_paged(
                        x, cache, page_table, pos)
                else:
                    lg, cache = model.forward_cached(x, cache, pos)
                with jax.named_scope("serve.sample"):
                    tgt = jnp.argmax(lg.astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)  # [B, K+1]

                # -- accept the longest matching prefix + bonus ------
                match = (drafts[:, :Kd] == tgt[:, :Kd]).astype(
                    jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                # capacity clamp mirrors the non-speculative one-token
                # steps: never emit past the max_len-1 frontier
                allowed = jnp.maximum(max_len - 1 - pos, 0)
                n_emit = jnp.where(done, 0,
                                   jnp.minimum(acc + 1, allowed)) \
                    .astype(jnp.int32)
                lanes = jnp.arange(W, dtype=jnp.int32)
                emit_mask = lanes[None, :] < n_emit[:, None]
                out_tok = jnp.where(emit_mask, tgt,
                                    jnp.full_like(tgt, -1))
                last = jnp.clip(n_emit - 1, 0, W - 1)
                new_tok = jnp.take_along_axis(
                    tgt, last[:, None], axis=1)[:, 0]
                tok = jnp.where(n_emit > 0, new_tok, tok)
                pos = pos + n_emit
                done = done | (pos >= max_len - 1)
                # true accepted-draft count for the accounting plane:
                # under the capacity clamp n_emit-1 would UNDERCOUNT
                # matches (drafted stays K, so the accepted+rejected==
                # drafted partition needs the unclamped acc)
                n_acc = jnp.where(n_emit > 0, acc, 0)
                carry = (cache, dcache, page_table, tok, pos, mode,
                         plen, prompts, done)
                return carry, (out_tok, n_emit, n_acc)

            def serve_step(param_vals, draft_vals, cache, dcache,
                           page_table, tok, pos, mode, plen, prompts,
                           done):
                def run_scan():
                    def body(carry, _):
                        return spec_core(carry)
                    carry = (cache, dcache, page_table, tok, pos,
                             mode, plen, prompts, done)
                    return jax.lax.scan(body, carry, None,
                                        length=K_steps)
                with _swapped_state(model, names, list(param_vals)):
                    if draft_names:
                        with _swapped_state(draft, draft_names,
                                            list(draft_vals)):
                            carry, (toks, n_emit, n_acc) = run_scan()
                    else:
                        carry, (toks, n_emit, n_acc) = run_scan()
                (cache, dcache, page_table, tok, pos, mode, plen,
                 prompts, done) = carry
                # [K_steps, B, W] -> [B, K_steps*W]: each slot's row is
                # its chunk-ordered emission stream (-1 = no token),
                # the same harvest contract as the plain decode program
                toks = toks.transpose(1, 0, 2).reshape(
                    toks.shape[1], K_steps * W)
                n_dec = jnp.sum(n_emit)
                return (cache, dcache, page_table, tok, pos, mode,
                        plen, prompts, done, toks, n_emit.T, n_acc.T,
                        jnp.asarray(0, jnp.int32), n_dec)
            return jax.jit(serve_step,
                           donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
        if not record and first_use:
            return build()
        return _model_program_cache(model, key, build)

    def lower_step(self, mixed: bool = False):
        """`jax.stages.Lowered` for the (admission if mixed else
        decode) step program with its donation set — the analysis
        suite's entry point for lint_donation over the paged carries.
        Under speculation the decode program is the draft/verify scan
        and both programs carry the (donated) draft cache.  A pure
        probe: it never touches the batcher's program or timing
        bookkeeping (record=False)."""
        if mixed:
            fn = self._step_fn(self.prefill_chunk, self.admit_steps,
                               record=False)
        elif self.spec_k:
            fn = self._spec_step_fn(record=False)
        else:
            fn = self._step_fn(self._decode_width, self.chunk,
                               record=False)
        if self.spec_k:
            return fn.lower(self._param_vals(),
                            self._draft_param_vals(),
                            *self._carry_args())
        return fn.lower(self._param_vals(), *self._carry_args())

    def _kv_page_counts(self, width: int, steps: int):
        """(live, walked, by kind): the pages one paged-attention call
        covers, summed over the `steps` scan steps of the chunk about to be
        dispatched — `live`: up to the frontier of each occupied slot;
        `walked`: what the attention walks of all B (the bound its own
        module states, handed over in the model's kv_row_spec: the K/V
        kernel's and the latent kernel's IS the frontier, the latent XLA
        walk's is the deepest slot's block).  Replayed on the host from `_pos_host` and the slots' prompts as step_core advances
        them, no device read; a speculative decode step counts the one
        token it is sure to advance, and under the block schedule a
        pass fixes the quota's lanes and no more (what the confidence
        threshold fixes beyond it is in the data: a block then commits
        earlier than counted here, and the count is a lower bound).
        (0, 0) for the dense layout.

        The third entry is {} but for a model with KINDS of layer:
        `live` and `walked` are then the FULL layers' call (a slot's
        whole depth), and it gives KIND_PAGE_COUNTS: each kind's walk
        times its layers (the window layers' from the same bound the
        kernel uses, its fifth argument the window) and the pages the
        window layers' valid lanes need."""
        if self.kv_layout != "paged":
            return 0, 0, {}
        from ..ops.pallas.paged_attention import first_page
        from ..ops.pallas.paged_attention import pages_walked as to_frontier
        pages_walked = self._pages_walked
        pos = self._pos_host.astype(np.int64)
        done = self._done_host.copy()
        mode = self._mode_host.copy()
        occupied = np.array([r is not None for r in self._slots])
        plen = np.array([len(r.prompt) if r is not None else 0
                         for r in self._slots], np.int64)
        live = walked = 0
        by_kind = dict.fromkeys(self._kv_pages_by_kind, 0)
        if self._kinds:
            W, ps = self._kinds["window"]["window"], self.page_size
            n_full, n_win = (len(self._kinds[k]["layers"])
                             for k in ("full", "window"))
        if self._diffusion:
            L, S = self.block_len, self._diffusion["denoising_steps"]
            quotas = np.array(_step_quotas(L, S))
            whole = plen // L * L
            step = self._block_step_host.copy()
            n_masked = self._block_masked_host.copy()
        for _ in range(steps):
            n = held = pages_walked(pos, width, self.page_size,
                                    self.pages_per_slot)
            if pages_walked is not to_frontier:
                held = to_frontier(pos, width, self.page_size,
                                   self.pages_per_slot)
            walked += int(n.sum())
            live += int(held[occupied].sum())
            filling = mode & ~done
            if self._kinds:
                valid = np.where(filling, np.minimum(width, plen - pos),
                                 ~done)
                need = (pos + valid - 1) // ps - first_page(pos, ps, W) + 1
                by_kind["kv_pages_walked_full"] += n_full * int(n.sum())
                by_kind["kv_pages_walked_window"] += n_win * int(
                    pages_walked(pos, width, ps, self.ring_pages, W).sum())
                by_kind["kv_pages_window_needed"] += n_win * int(
                    need[occupied & (valid > 0)].sum())
            if self._diffusion:
                decoding = ~mode & ~done
                # a fresh block masks what lies past the prompt
                n_masked = np.where(step == 0,
                                    L - np.clip(plen - pos, 0, L), n_masked)
                commit = decoding & (n_masked == 0)
                n_masked -= np.where(
                    decoding & ~commit,
                    np.minimum(quotas[np.clip(step, 0, S - 1)], n_masked), 0)
                step = np.where(decoding & ~commit, step + 1, 0)
                pos += np.where(filling, np.minimum(width, whole - pos),
                                np.where(commit, L, 0))
                mode &= ~(filling & (pos >= whole))
                done |= pos >= self.max_len
                continue
            pos += np.where(filling, np.minimum(width, plen - pos), ~done)
            mode &= ~(filling & (pos >= plen))
            done |= pos >= self.max_len - 1
        return live, walked, by_kind

    def _run_chunk(self, mixed: bool) -> bool:
        """One scan chunk, in the phases `serve.dispatch`,
        `serve.device_wait` and `serve.harvest` (the `on_token`
        callbacks inside it as ONE `serve.deliver`).  False where an
        injected chunk fault stopped it before the program ran: the
        chunk retries at the next boundary."""
        from ..distributed import fault
        from .. import telemetry as _tel
        t0 = time.perf_counter()
        kind = "admit" if mixed else "decode"
        ck = self._chunk_no
        n_emit = n_acc = passes = None
        counted = []        # a counting model's one more output (and,
        #                     under the block schedule, the tokens' passes)
        # the chunk's program by its width and scan length
        # (_decode_width is 1 for one token a step)
        width, steps = (self.prefill_chunk, self.admit_steps) if mixed \
            else (self._decode_width, self.chunk)
        *pages, by_kind = self._kv_page_counts(width, steps)
        try:
            with self._phase("dispatch", kind=kind, chunk=ck, steps=steps,
                             width=width, kv_pages_live=pages[0],
                             kv_pages_walked=pages[1], **by_kind):
                if self.spec_k and not mixed:
                    fn = self._spec_step_fn()
                else:
                    fn = self._step_fn(width, steps)
                # the chunk dispatch runs under the serve watchdog
                # (FLAGS_stop_check_timeout): a hang dumps thread stacks
                # / aborts per the r9 contract, and a delay-injected
                # chunk that ages past the deadline is counted as hung
                # below.  The serve.chunk fault fires INSIDE the watched
                # window but BEFORE fn touches the donated carries — an
                # injected chunk fault loses nothing; the chunk retries
                # at the next boundary (under speculation that includes
                # a fault mid-verify: no draft token ever leaks from a
                # chunk that never returned)
                with self._watch:
                    fault.hit("serve.chunk", key=kind)
                    if self.spec_k:
                        out = fn(self._param_vals(),
                                 self._draft_param_vals(),
                                 *self._carry_args())
                        if mixed:
                            (self._cache, self._dcache, page_table,
                             self._tok, self._pos, self._mode,
                             self._plen, self._prompts, self._done, toks,
                             n_pref, n_dec) = out
                        else:
                            (self._cache, self._dcache, page_table,
                             self._tok, self._pos, self._mode,
                             self._plen, self._prompts, self._done, toks,
                             n_emit, n_acc, n_pref, n_dec) = out
                    else:
                        (self._cache, page_table, self._tok, self._pos,
                         self._mode, self._plen, self._prompts,
                         self._done, toks, n_pref, n_dec, *counted) = fn(
                            self._param_vals(), *self._carry_args())
                        if self._diffusion:
                            passes = counted.pop()
        except fault.FaultError:
            self._chunk_retries += 1
            self._consecutive_chunk_faults += 1
            if _tel.active():
                _tel.emit("serve.chunk_fault", kind=kind,
                          retries=self._chunk_retries)
            # a PERSISTENT chunk fault (times=*) would otherwise spin
            # run() forever — past the budget, surface it to the
            # caller like StepAnomalyGuard's bad-step budget
            if self._consecutive_chunk_faults > int(
                    get_flag("serve_retry_budget") or 3):
                raise
            return False
        self._consecutive_chunk_faults = 0
        self._kv_pages_live += pages[0]
        self._kv_pages_walked += pages[1]
        for name, v in by_kind.items():
            self._kv_pages_by_kind[name] += v
        if self._watch.last_reported:
            self._hung_chunks += 1
            if _tel.active():
                _tel.emit("serve.hung", kind=kind,
                          wall_ms=round(
                              (time.perf_counter() - t0) * 1e3, 3))
        if self.kv_layout == "paged":
            self._page_table = page_table
        # ONE batched host transfer per chunk — each device_get is a
        # blocking round trip, so fetching tokens/mode/done/pos/counters
        # separately would pay it six times per boundary
        block = (self._tok["step"], self._tok["masked"]) \
            if self._diffusion else None
        with self._phase("device_wait", kind=kind, chunk=ck):
            (toks, mode_h, done_h, pos_h, n_pref, n_dec, n_emit,
             n_acc, counted, passes, block) = jax.device_get(
                (toks, self._mode, self._done, self._pos, n_pref, n_dec,
                 n_emit, n_acc, counted, passes, block))
        if block is not None:
            self._block_step_host = np.array(block[0], np.int64)
            self._block_masked_host = np.sum(block[1], axis=1,
                                             dtype=np.int64)
        counts = dict(zip(self._counter_names,
                          (int(v) for vec in counted for v in vec)))
        # the lanes the chunk's programs computed, and those that carried
        # a prompt token or a decoding slot's token (or block: L lanes)
        lanes = self.B * width * steps
        lanes_valid = int(n_pref) + (counts["decode_lanes"]
                                     if self._diffusion else int(n_dec))
        with self._phase("harvest", chunk=ck, lanes=lanes,
                         lanes_valid=lanes_valid, **counts):
            self._lanes += lanes
            self._lanes_valid += lanes_valid
            self._count_model(counts)
            self._harvest(kind, t0, np.asarray(toks), mode_h, done_h,
                          pos_h, int(n_pref), int(n_dec), n_emit, n_acc,
                          passes)
        return True

    def _count_model(self, counts):
        """A chunk's model counts into stats(): sums, but a `_max`
        count keeps the largest chunk's."""
        for name, v in counts.items():
            old = self._model_counts[name]
            self._model_counts[name] = max(old, v) \
                if name.endswith("_max") else old + v

    def _harvest(self, kind, t0, toks, mode_h, done_h, pos_h, n_pref,
                 n_dec, n_emit, n_acc, passes=None):
        """What the host does with a chunk's outputs (`toks` is [B, K],
        [B, K*(k+1)] under speculation, or [B, K*L] with `passes`
        beside it under the block schedule; -1 = no token): the fault
        sweep, the accounting, the prefix trie's progress, each slot's
        new tokens and their delivery."""
        from ..distributed import fault
        from .. import telemetry as _tel
        self._mode_host = np.array(mode_h)
        self._done_host = np.array(done_h)
        self._pos_host = np.array(pos_h)
        # serve.decode: per-live-slot fault sweep — a poisoned slot is
        # evicted and its request requeued/shed (_fault_slot) BEFORE
        # its pending trie nodes could be marked complete or its
        # chunk tokens harvested, while every other slot proceeds
        # untouched.  Unset, this whole block is one cached string
        # compare (fault.is_active)
        if fault.is_active():
            faulted = []
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                try:
                    f = fault.hit("serve.decode",
                                  key=f"slot{i}:req{req.req_id}")
                except fault.FaultError:
                    faulted.append(i)
                    continue
                if f is not None:   # data modes poison the slot too
                    faulted.append(i)
            for i in faulted:
                self._fault_slot(i)
        dt = time.perf_counter() - t0
        # a program's FIRST call may include its XLA compile — keep it
        # out of the wall-time stats so chunk_time_max/p50 describe
        # steady-state chunks, not a one-time multi-second compile
        if not self._first_use:
            self._chunk_times.append(dt)
            self._chunk_time_max = max(self._chunk_time_max, dt)
        self._chunk_count += 1
        self._chunk_kind_counts[kind] += 1
        self._occupancy_total += self.active
        self._prefill_tok_total += n_pref
        self._decode_tok_total += n_dec
        if n_emit is not None:
            # speculation accounting (ISSUE 11): n_emit [B, K_steps] is
            # tokens emitted per slot per scan step (0 = inactive);
            # n_acc carries the TRUE accepted-draft count per step —
            # n_emit-1 would undercount on a capacity-clamped step —
            # so accepted + rejected == drafted holds exactly
            ne = np.asarray(n_emit)
            active = ne > 0
            n_active = int(active.sum())
            drafted = n_active * self.spec_k
            accepted = int(np.asarray(n_acc)[active].sum())
            self._spec_drafted += drafted
            self._spec_accepted += accepted
            self._spec_steps += n_active
            self._spec_emit_window.extend(int(v) for v in ne[active])
            if _tel.active():
                _tel.emit("serve.spec", drafted=drafted,
                          accepted=accepted, steps=n_active,
                          accept_rate=round(accepted / drafted, 4)
                          if drafted else 0.0)
        if self.kv_layout == "paged":
            # prompt pages that finished filling this chunk become
            # shareable for the NEXT admission
            for i, plan in enumerate(self._plans):
                if plan is not None and plan.nodes:
                    self._alloc.mark_progress(plan,
                                              int(self._pos_host[i]))
        if _tel.active():
            # published by _close_chunk, once the step's last phase is
            # counted
            self._chunk_event = dict(
                kind=kind, chunk=self._chunk_no,
                wall_ms=round(dt * 1e3, 3),
                occupancy=self.active, slots=self.B,
                prefill_tokens=n_pref, decode_tokens=n_dec,
                first_use=self._first_use)
            # cost ledger measured-wall feed (ISSUE 12): the chunk
            # wall lands on the ledger label of the very program that
            # ran it; first_use walls (may include the compile) are
            # excluded like the chunk-time stats above
            _tel.costledger.observe(f"serve_step.{kind}", dt * 1e3,
                                    cold=self._first_use)
            if self.kv_layout == "paged":
                _tel.emit("serve.kv",
                          pages=self.num_pages,
                          pages_used=self._alloc.pages_used,
                          pages_free=self._alloc.pages_free,
                          pages_cached=self._alloc.pages_cached,
                          prefix_hit_tokens=self._alloc
                          .prefix_hit_tokens,
                          evictions=self._alloc.evictions,
                          kv_bytes=self.kv_cache_bytes())
        t_harvest = self._now()
        live = []
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            req.tokens.extend(int(t) for t in toks[i] if t >= 0)
            if passes is not None:
                req.token_passes.extend(
                    int(p) for t, p in zip(toks[i], passes[i]) if t >= 0)
            if req.t_first is None and req.tokens:
                req.t_first = t_harvest
                req.first_token_chunk = self._chunk_no
                _tel.mark("serve.req.first_token", req=req.req_id,
                          chunk=self._chunk_no)
            live.append(req)
        # streaming: hand out this chunk's bursts now — TTFT for an
        # interactive caller is the FIRST chunk boundary, not run()'s
        # return (speculation lands accepted runs here in one burst)
        with self._phase("deliver") as deliver:
            deliver.set(tokens=sum(self._deliver(req, done=False)
                                   for req in live))
