"""Continuous-batching request scheduler over the ragged serving step.

The engine turns the repo's serving ingredients (paged int8 KV pools,
the EP-MoE decode step, the ragged paged-attention kernel) into a
traffic-serving runtime: requests arrive on a trace, are ADMITTED into
slots when the page pool can hold their first chunk, their prompts are
prefilled in CHUNKS interleaved with other requests' decode tokens
(one ragged mixed batch per step — no prefill stall, no rectangle),
and when the pool runs dry mid-decode the lowest-priority request is
EVICTED (pages freed, request re-queued; on re-admission its prompt
*plus everything generated so far* is re-prefilled, so generation
resumes from the exact cursor — the recompute-eviction discipline).

Scheduling model (all host-side, numpy; the device work is ONE jitted
``Transformer.serving_step`` per engine step):

* a step's batch is assembled slot-by-slot under a static
  ``token_budget``: each active slot contributes
  ``min(chunk, remaining_sequence)`` tokens — 1 in steady decode, up
  to ``chunk`` while prefilling — packed at 8-aligned offsets;
* THE PACKED WIDTH FOLLOWS THE BATCH. A step launches at one of TWO
  ``block_q`` rungs: the lowest (8, or the tuned floor) where every
  row fits it — a decode-only step, ``live + 8`` rows wide with
  ``live = min(token_budget, 8 * slots)`` —, else the cap (the chunk's
  block). What a step at the cap needs is ``q_starts[s] + block(s)``
  rows for its batched rows (``Transformer.step_rows_needed``: the
  query block each attention launch moves for the row; a slot outside
  the batch needs nothing, every launch skips it), and its arrays are
  the narrowest width of a short LADDER that covers that
  (:func:`chunk_widths`: a chunk behind every decode row, a midpoint,
  and the widest, ``_t_pad = token_budget + block_q_cap``). The key of
  the step program, ``(block_q, width)``, is thus a function of what
  ``_assemble`` packed; a rung's first launch builds the programs of
  all its widths (:meth:`ServingEngine._unbuilt`), so none is built
  under traffic;
* pages for the new tokens are allocated from one shared free list;
  allocation failure triggers eviction (victims: the latest-arrived
  active request not already in this step's batch — LIFO preemption),
  and a row that still cannot get pages is deferred one step;
* per-slot device ``kv_lens`` are zeroed for slots outside the batch,
  so the kernel never walks a deferred row's pages.

Degradation: the first device failure of the Pallas kernel path flips
the engine onto the XLA twin (``use_pallas=False``) and retries — the
``tools/native``-style graceful-degradation story at engine level, so
a fault-plan replay (bench.py --dryrun --faults) exercises scheduling
under chaos without hardware. Degradation is no longer one-way: every
failure also lands in a :class:`~triton_distributed_tpu.runtime.health
.HealthLedger`, whose probation machinery re-promotes the fused path
after enough clean XLA steps plus seeded probes (and, in the
disaggregated engine, re-promotes the DCN wire and fails a dead slice
over onto the survivor).
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from triton_distributed_tpu.tracing import Span, spanned


#: Priority classes, best-first: admission, eviction-victim selection
#: and the fleet's brownout shedding all order by the index in this
#: tuple (``tier_rank``) — interactive outranks batch outranks
#: background everywhere a scheduling decision is made.
TIERS = ("interactive", "batch", "background")

TIER_RANK = {name: i for i, name in enumerate(TIERS)}


def tier_rank(priority: str | None) -> int:
    """Numeric rank of a priority class (lower = more important).
    Unknown/unset priorities rank as interactive — the single-tenant
    default must behave exactly like the pre-tenancy engine."""
    return TIER_RANK.get(priority, 0)


@dataclass(frozen=True)
class TenantConfig:
    """Per-tenant serving contract (docs/SERVING.md § Multi-tenant).

    ``priority`` — the tenant's tier (``TIERS``), the default for its
    requests' ``Request.priority``. ``slo_ms`` — per-request modeled
    completion SLO; the fleet router's deadline slack term is
    ``slo_ms − modeled completion`` (inf = no deadline, the slack term
    vanishes). ``token_budget`` — cap on the packed tokens the
    tenant's RESIDENT rows may claim per engine step (None =
    unbounded). ``page_share`` — fraction of each engine's page pool
    the tenant's residents may hold; both shares are enforced at
    admission (a request over its tenant's share defers WITHOUT
    head-of-line blocking other tenants)."""

    priority: str = "interactive"
    slo_ms: float = float("inf")
    token_budget: int | None = None
    page_share: float = 1.0

    def __post_init__(self):
        if self.priority not in TIERS:
            raise ValueError(
                f"unknown priority {self.priority!r} (want one of "
                f"{TIERS})")
        if not 0.0 < self.page_share <= 1.0:
            raise ValueError(
                f"page_share must be in (0, 1], got {self.page_share}")
        if self.token_budget is not None and self.token_budget < 8:
            raise ValueError(
                f"token_budget must be >= 8 (one packed row), got "
                f"{self.token_budget}")


#: The tenant every unconfigured request belongs to: interactive tier,
#: no deadline, full shares — byte-identical scheduling to the
#: pre-tenancy engine.
DEFAULT_TENANT = TenantConfig()


def effective_rank(req, now: float, aging_ticks: int) -> int:
    """The rank admission actually orders by: the request's tier rank
    minus one bump per ``aging_ticks`` ticks waited since arrival —
    the anti-starvation aging that lets a background request outrank a
    sustained interactive flood once it has waited long enough.
    Deterministic (pure function of the tick clock), floor 0."""
    rank = tier_rank(getattr(req, "priority", None))
    if rank == 0 or aging_ticks <= 0:
        return rank
    waited = max(float(now) - float(req.arrival), 0.0)
    return max(0, rank - int(waited // aging_ticks))


@dataclass
class Request:
    """One serving request. ``arrival`` is in engine-step units (the
    deterministic clock the tests and the Poisson trace share)."""

    rid: int
    prompt: np.ndarray                 # (L,) int32 token ids
    max_new: int = 8
    arrival: float = 0.0
    # multi-tenancy: the tenant key (looked up in the engine/fleet
    # tenants map) and the priority class. ``priority=None`` defers to
    # the tenant's configured tier; both defaults reproduce the
    # single-tenant engine exactly.
    tenant: str = "default"
    priority: str | None = None

    # runtime (engine-owned)
    generated: list = field(default_factory=list)
    cursor: int = 0                    # tokens of `seq` already in KV
    slot: int | None = None
    evictions: int = 0
    done: bool = False
    completion_step: int | None = None
    # resident-but-not-schedulable: the request holds its slot and pages
    # but must not be batched or evicted — the state of a finished
    # prefill awaiting its KV ship (prefill side) and of a shipped-to
    # slot whose pages are still in flight (decode side). The
    # DisaggregatedEngine owns the flag; the colocated engine never
    # sets it.
    parked: bool = False
    # perf_counter stamps, None until the event: handed to
    # ``ServingEngine.submit``; FIRST given a slot by
    # ``ProtocolOps.admit`` (a re-admission after an eviction is not a
    # second wait; a row that enters by ``reserve_shipped`` has none);
    # end of the advance loop of the first step after which
    # ``generated`` is non-empty. ``rid`` is what they share.
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None

    # ``seq``'s buffer, prompt + ``max_new`` long, and how many of the
    # generated tokens it holds
    _seq_buf: np.ndarray | None = field(default=None, repr=False,
                                        compare=False)
    _seq_n: int = field(default=0, repr=False, compare=False)

    @property
    def seq(self) -> np.ndarray:
        """Every known token of the sequence: prompt + generated. The
        recompute prefix after an eviction IS this — re-prefilling it
        resumes generation from the exact cursor. A view of one buffer
        that takes each generated token once: the scheduler reads it
        several times a row and step, and a sequence can be tens of
        thousands of tokens long."""
        n = len(self.generated)
        if not n:
            return self.prompt
        lp = len(self.prompt)
        buf = self._seq_buf
        if buf is None or len(buf) < lp + n:
            buf = self._seq_buf = np.empty(
                (lp + max(n, self.max_new),), np.int32)
            buf[:lp] = self.prompt
            self._seq_n = 0
        have = min(self._seq_n, n)
        buf[lp + have:lp + n] = self.generated[have:n]
        self._seq_n = n
        return buf[:lp + n]


@dataclass(frozen=True)
class EngineConfig:
    slots: int = 8                     # concurrent requests (R)
    token_budget: int = 64             # static packed tokens per step (T)
    chunk: int = 16                    # max prefill tokens per row-step
    page: int = 16
    npages: int = 64
    max_steps: int = 10_000
    # --- decode sampling (engine-side, over the per-slot logits) ---
    # temperature <= 0 keeps greedy argmax; > 0 samples the softmax of
    # logits/temperature, optionally top_k-truncated. Draws are keyed on
    # (seed, rid, tokens-generated-so-far) — NOT the step count — so a
    # request's tokens are deterministic under `seed` regardless of how
    # scheduling interleaved it (eviction replays and the disaggregated
    # split reproduce the colocated stream exactly).
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # --- roles ---
    # prefill_only: the request "completes" (for this engine) once its
    # prompt is in KV and the FIRST token is generated — the prefill
    # half of a disaggregated deployment. The request is NOT marked
    # done; the on_complete hook decides whether its pages free.
    prefill_only: bool = False
    # prefix_cache: per-page refcounts + chain-hash page reuse
    # (serving/state.PagePool) — shared-prefix requests and re-admitted
    # evicted requests reattach resident pages instead of recomputing
    # the prefix.
    prefix_cache: bool = False
    # prefix_share: in-batch shared-prefix dedup (requires
    # prefix_cache). Batch assembly folds every batched row's frozen
    # pages onto the prefix cache's canonical page for the same chain
    # hash — one physical page run walked by many rows' block tables —
    # and marks the rows SHARED_PREFIX in the kernel's attention-
    # topology operand. Cuts page-walk DMA traffic and pool pressure on
    # motif traffic; token streams are unchanged (frozen pages with
    # equal chain hashes hold byte-identical KV by construction).
    prefix_share: bool = False
    # greedy_on_device: decides nothing since PR 34. Every greedy engine
    # (``temperature <= 0``) whose advance needs no logits on the host
    # takes each row's arg-max on the device and fetches a token id a
    # row (``ServingEngine._greedy``), flag or no flag. The field stays
    # accepted because ``benchmark/configs/minicpmsala9b-d8.json`` names
    # it and no benchmark file may be edited; its removal is a line for
    # the next ``benchmark`` issue.
    greedy_on_device: bool = False


#: what a step whose program is built already opens in
#: ``setup.program``'s place: nothing
_STEADY = contextlib.nullcontext()

#: the phases of one ``ServingEngine.step``, in order; each is the host
#: span ``engine.<phase>`` (``tracing.Span``: on a profiler's trace, and
#: a ``perf_counter`` pair) and the per-step list
#: ``EngineStats.<phase>_times``
PHASES = ("admit", "assemble", "upload", "dispatch", "fetch", "advance")


@dataclass
class EngineStats:
    # Everything here describes RETIRED device steps (fetched and
    # advanced) and nothing of the step in flight: a per-step list gets
    # a step's entry, and a counter its share, in the ``step()`` call
    # that retires it.
    # seconds of host clock per device step: uploads + dispatch + fetch
    # (``upload_times + dispatch_times + fetch_times`` of that step)
    step_times: list = field(default_factory=list)
    # the six phases of ``ServingEngine.step`` (``PHASES``), seconds of
    # host clock, one entry per step that ran the device (an empty step
    # appends to none); a degraded step's re-run is summed into its
    # entry. A step's six entries are ITS OWN phases: launched ahead,
    # its admit, assemble, upload and dispatch ran in one call of
    # ``step()`` and its fetch and advance in the next, so they sum to
    # a call's wall time only for a step retired in the call that
    # launched it.
    admit_times: list = field(default_factory=list)
    assemble_times: list = field(default_factory=list)
    upload_times: list = field(default_factory=list)
    dispatch_times: list = field(default_factory=list)
    fetch_times: list = field(default_factory=list)     # wait + D2H
    advance_times: list = field(default_factory=list)
    # request stamps summed where they are taken (``Request.t_*``):
    # submit -> first slot, and first slot -> first token
    queue_wait_s: float = 0.0
    admissions: int = 0
    first_token_s: float = 0.0
    first_tokens: int = 0
    step_tokens: list = field(default_factory=list)
    step_generated: list = field(default_factory=list)
    completed: int = 0
    generated_tokens: int = 0
    prefill_tokens: int = 0
    evictions: int = 0
    deferrals: int = 0
    # the pool append of the device steps: (slot, page) runs handed to
    # the ``kernels/kv_append`` kernel, and steps whose append went by
    # its XLA twin, the row scatter (head-sharded pools, use_pallas off)
    append_runs: int = 0
    append_scatter_steps: int = 0
    # pages the ragged kernel walks, per attention layer of the kind,
    # summed over the batched rows of the device steps (counted in
    # ``_assemble`` from each row's cursor and take): a GLOBAL layer
    # walks every page up to the row's length, a sliding-WINDOW layer
    # only those from its window's first key on (0 without such layers)
    global_pages_walked: int = 0
    window_pages_walked: int = 0
    # the work of a model with block-sparse attention or lightning
    # layers (0 without them), summed over the batched rows of the
    # device steps, counted in ``_assemble``: pages ONE sparse layer's
    # walk visits (a decode row at most ``sparse_topk``, one a chosen
    # block; a prefill chunk the union of its positions' choices, read
    # as every page it holds: an upper bound), rows whose last position
    # is past ``sparse_dense_len`` (their blocks are chosen by score),
    # and rows that read and write a recurrent (lightning, kda) layer's
    # state
    selected_pages_walked: int = 0
    sparse_rows: int = 0
    state_rows: int = 0
    # rows batched through ONE sparse layer's selected walk, and those
    # of them of ONE token (every decode row): the walk's third tile,
    # one token's query rows against several listed pages an iteration
    # (kernels/ragged_paged_attention.py, the selected walk's notes)
    selected_rows: int = 0
    selected_token_rows: int = 0
    # of ``state_rows``, those a LIGHTNING layer's launch runs in the
    # rank-1 form, a token at a time (every decode row, a prompt's tail
    # of at most SHORT tokens: kernels/lightning_attention.py); the
    # rest take its chunk form (0 for a model without such layers)
    state_token_rows: int = 0
    # the work of a model with gated delta-rule (kda) layers (0 without
    # them), counted beside ``state_rows``: rows batched through ONE
    # layer's ``kda_attention`` launch, and those of them with more
    # than one token (at most SHORT of them the rank-1 form a token at
    # a time, more the chunk form: kernels/kda_attention.py)
    kda_rows: int = 0
    kda_chunk_rows: int = 0
    # the work of a model with a token selection (``index_topk``; 0
    # without one), per layer, summed over the batched rows of the
    # device steps, counted in ``_assemble``: rows through the token
    # walk, those of them whose last position is past ``index_topk``
    # (their tokens are kept by score), the indexer keys those rows'
    # contexts hold (what ONE layer's scan reads), the (query position,
    # key) pairs the walk attends (``min(position + 1, index_topk)`` a
    # query position), and the pairs the selection reads scores of (a
    # row's query positions past ``index_topk`` x its pages' keys)
    dsa_rows: int = 0
    dsa_sparse_rows: int = 0
    index_keys_scanned: int = 0
    dsa_selected_tokens: int = 0
    dsa_select_pairs: int = 0
    # the work of a model with a LATENT pool (``kv_latent``; 0 without
    # one), summed over the batched rows of the device steps, counted
    # in ``_assemble``: pages ONE layer's latent walk fetches (a decode
    # row every page it holds, once; a row of more tokens once per
    # query block of ``LATENT_TQ`` tokens, each block walking up to its
    # own last position), rows batched through the walk, and rows of
    # more than one token attended EXPANDED (keys and values
    # up-projected from the cached latents; 0: every row runs absorbed)
    latent_pages_walked: int = 0
    latent_rows: int = 0
    chunk_rows_expanded: int = 0
    # packed rows of the device steps: the sum of their widths (each
    # step's follows its batch, ``ServingEngine._widths``)
    packed_rows: int = 0
    # the steps that hold a CHUNK (a row longer than the low rung's
    # block: they launch at the cap), the sum of their widths, and
    # those of them narrower than the widest (``_t_pad``)
    chunk_steps: int = 0
    chunk_packed_rows: int = 0
    chunk_narrow_steps: int = 0
    # device steps dispatched while the step before was not yet retired
    # (its token ids still on the device): ``ServingEngine.step``
    # launches ahead whenever ``_launch_ahead`` finds nothing that
    # needs them on the host first. Decided at dispatch.
    lookahead_steps: int = 0
    # packed rows of the device steps that were no token's (the step's
    # own width less its tokens): ``serving_step`` hands their expert
    # assignments to ``ops.ep_moe`` masked, so at least
    # ``masked * topk // block_m`` blocks of an expert layer's grouped
    # GEMM hold no row (0 for a model with no EP expert layer)
    moe_masked_rows: int = 0
    # rows of the expert-sorted buffer ONE EP expert layer of each
    # device step's program allocates, summed over the steps
    # (``Transformer.moe_aligned_rows`` at the step's width: a static
    # of the program — every array of ``ops/moe.py::_grouped_mlp`` is
    # that long whatever the step holds; 0 with no EP expert layer)
    moe_aligned_rows: int = 0
    # device steps whose EP expert layers ran the LOCAL path (ONE rank
    # on the tp axis, ``Transformer.moe_local``: sort once, gather
    # once, the grouped GEMMs — no dispatch, no combine, no
    # workspaces): every step of such an engine; 0 at tp > 1 and with
    # no EP expert layer
    moe_local_steps: int = 0
    # step programs this engine dispatched a FIRST time (one a program
    # key: ``block_q``, packed width, ``use_pallas``, ``n_bufs``), and
    # the seconds of their ``setup.program`` spans: tracing, lowering
    # and the compile or the load from the cache. Set-up is no step's:
    # booked at the dispatch, not at the retirement. A warm-up that has
    # visited every rung leaves both still; ``tracing.startup_log``
    # names each program's key and step
    programs_built: int = 0
    program_build_s: float = 0.0
    prefix_hits: int = 0               # pages reattached from the cache
    # --- in-batch shared-prefix dedup (EngineConfig.prefix_share) ---
    shared_prefix_rows: int = 0        # batched rows marked SHARED_PREFIX
    deduped_pages: int = 0             # duplicate pages folded onto canon
    # --- multi-tenancy (zero on single-tenant engines) ---
    preemptions: int = 0               # evictions forced by a higher tier
    tenant_preemptions: dict = field(default_factory=dict)  # tenant -> n
    fair_share_deferrals: dict = field(default_factory=dict)  # tenant -> n
    # CURRENTLY on the XLA twin (no longer a one-way latch: probation
    # re-promotion clears it — see HealthLedger)
    degraded: bool = False
    repromotions: int = 0              # probe-driven returns to the fused path
    # every device-step failure the degrade path absorbed:
    # {"step", "site", "error"} — what failed and when, so a degraded
    # run can say why it is on the XLA twin
    failures: list = field(default_factory=list)
    # --- speculative decoding (serving/spec.py; zero on plain engines) ---
    spec_rows: int = 0                 # verify rows run (one per spec step)
    draft_tokens: int = 0              # draft tokens proposed into verify rows
    accepted_draft_tokens: int = 0     # drafts that matched the keyed sample
    spec_tokens_out: int = 0           # tokens EMITTED by verify rows
    rolled_back_tokens: int = 0        # rejected draft positions rewound
    # adaptive drafter k (spec.py adaptive_k=True): verify rows planned
    # at each per-request draft budget k — empty on fixed-k engines
    adaptive_k_rows: dict = field(default_factory=dict)
    # per-shape-key step-time ledger: grid-schedule traffic key
    # (slots, t_pad, hkv, g, d, page, chunk) ->
    # [count, total_ms, max_pages].
    # tune.traffic re-searches the hot keys after a run and persists
    # winners the next engine build resolves.
    shape_ledger: dict = field(default_factory=dict)

    def note_shape(self, key, ms: float, pages: int) -> None:
        """Record one step against its grid-schedule shape key."""
        ent = self.shape_ledger.setdefault(tuple(key), [0, 0.0, 0])
        ent[0] += 1
        ent[1] += float(ms)
        ent[2] = max(ent[2], int(pages))

    def hot_shape_keys(self, top: int = 4) -> list:
        """Shape keys ranked by total step time spent in them —
        the keys worth paying a schedule search for."""
        ranked = sorted(
            self.shape_ledger.items(), key=lambda kv: -kv[1][1]
        )
        return [k for k, _ in ranked[:max(0, int(top))]]

    @property
    def accepted_tokens_per_step(self) -> float:
        """Tokens a speculative verify row emits per engine step it
        runs in — the speculation multiplier. Every verify row emits at
        least 1 (the keyed sample that corrects the first rejected
        draft, or the bonus token after a clean sweep), so > 1.0 means
        drafts are genuinely being accepted. 0.0 on a plain engine."""
        if not self.spec_rows:
            return 0.0
        return self.spec_tokens_out / self.spec_rows

    @property
    def draft_acceptance_rate(self) -> float:
        if not self.draft_tokens:
            return 0.0
        return self.accepted_draft_tokens / self.draft_tokens

    @property
    def adaptive_k_histogram(self) -> dict:
        """k -> verify-row count under the adaptive drafter, ascending
        k — shows where the per-request budget actually settled."""
        return dict(sorted(self.adaptive_k_rows.items()))


def poisson_trace(seed: int, n_requests: int, mean_interarrival: float,
                  len_lo: int, len_hi: int, max_new_lo: int,
                  max_new_hi: int, vocab: int) -> list:
    """Seeded Poisson arrival trace: exponential inter-arrival gaps (in
    engine-step units), prompt lengths ~ U[len_lo, len_hi) — the
    ISSUE-6 traffic shape (lengths ~U[S/8, 3S/4]) — and uniform
    max_new. Deterministic under ``seed``."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += float(rng.exponential(mean_interarrival))
        ln = int(rng.integers(len_lo, max(len_hi, len_lo + 1)))
        out.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, (ln,)).astype(np.int32),
            max_new=int(rng.integers(max_new_lo, max(max_new_hi,
                                                     max_new_lo + 1))),
            arrival=t,
        ))
    return out


def _greedy_tokens(logits):
    """(rows, vocab) logits -> (rows,) int32: each row's arg-max (the
    first of equals, as ``np.argmax``), -1 where the row's largest is
    not finite (a NaN or +inf anywhere in it)."""
    import jax.numpy as jnp

    top = jnp.max(logits, axis=-1)
    return jnp.where(jnp.isfinite(top), jnp.argmax(logits, axis=-1),
                     -1).astype(jnp.int32)


def _merge_tokens(tokens, src, ids):
    """A step's packed tokens: the host's ``tokens``, but where ``src``
    names a slot (``>= 0``) that slot's token of the step in flight,
    out of ``ids`` (``_greedy_tokens`` of its logits) — the token the
    host has not seen yet. A ``-1`` there (a non-finite row: the fetch
    of that step raises) is clamped to 0 before it indexes the
    embedding."""
    import jax.numpy as jnp

    return jnp.where(src >= 0, jnp.maximum(ids[jnp.maximum(src, 0)], 0),
                     tokens)


# module-level, so every engine of a process shares one compiled
# program a shape
_greedy_jit = jax.jit(_greedy_tokens)
_merge_jit = jax.jit(_merge_tokens)


@dataclass
class _Flight:
    """One device step from its dispatch to its retirement: what
    ``ServingEngine._retire`` needs to fetch its result and advance its
    rows, and what it adds to ``EngineStats`` then. While it is
    ``ServingEngine._flight`` it is also the launch-side view of its
    rows (``ServingEngine._view``)."""

    step: int                     # ``step_count`` at its launch
    phase_s: dict                 # its own seconds in each of ``PHASES``
    report: dict                  # what ``step()`` returns of it
    takes: dict                   # slot -> packed tokens of its row,
                                  # its batched slots ascending
    q_starts: np.ndarray
    q_lens: np.ndarray
    counts: dict                  # EngineStats counter -> its share
    ahead: bool                   # dispatched before the last was retired
    freezes: bool                 # a row's cursor crosses a page's end
    out: object = None            # device: (slots,) ids or the logits
    host: np.ndarray | None = None  # ``out`` fetched


def _ceil8(x: int) -> int:
    return -(-x // 8) * 8


def live_rows(block_q: int, slots: int, token_budget: int) -> int:
    """Packed rows the batched rows of a step at rung ``block_q`` can
    reach: each of ``slots`` rows holds at most ``block_q`` tokens
    (8-aligned), under the token budget."""
    return min(token_budget, slots * _ceil8(block_q))


def packed_width(block_q: int, slots: int, token_budget: int) -> int:
    """The packed width of a step whose every row fits ``block_q``
    tokens (the low rung; a decode-only step): its live rows and one
    block, which covers ``q_starts[s] + block_q`` of its last row."""
    return live_rows(block_q, slots, token_budget) + block_q


#: rows a width must save against the next wider one of its ladder to
#: be worth a step program of its own: each costs 2-4 s of a warm start
#: (PERF.md section 6, PR 42)
WORTH_A_PROGRAM = 64


def chunk_widths(narrowest: int, widest: int, grain: int) -> tuple:
    """The ladder of packed widths, ascending, of the steps that hold a
    chunk: ``widest``, the multiple of ``grain`` at or under the
    midpoint, and ``narrowest`` — each only where it saves
    ``WORTH_A_PROGRAM`` rows against the next one up."""
    ladder = [widest]
    for w in ((narrowest + widest) // 2 // grain * grain, narrowest):
        if w >= narrowest and ladder[0] - w >= WORTH_A_PROGRAM:
            ladder.insert(0, w)
    return tuple(ladder)


# ---------------------------------------------- kind of state x feature
#
# A model's layers may keep, beside or in place of plain K/V pages, a
# RING pool (sliding-window layers), a RECURRENT state a slot with
# compressed keys and a selection (lightning layers, kda layers with
# their convolution tail, block-sparse attention), or a LATENT pool
# (``kv_latent``: one entry a token for
# every head). What would read, keep, roll back or ship a second copy
# of such state is not built for it; ``REFUSED[kind][feature]`` is the
# reason, raised by name where the feature is asked for (a pair that
# is absent is served). ``{what}`` is the kind's name in the model.

REFUSED = {
    "window": {
        "prefix_cache":
            "{what} with prefix_cache=True (and with it prefix_share / "
            "SHARED_PREFIX rows): a cached prefix page of a window "
            "layer is overwritten by its ring",
        "speculation":
            "{what} under SpeculativeEngine: a rejected draft rolls "
            "the cursor back over ring pages the draft has already "
            "overwritten",
        "prefill_only":
            "{what} with prefill_only (the prefill role of "
            "DisaggregatedEngine): kv_ship ships pages by the global "
            "block table, which does not address a ring",
        "gather_pages":
            "kv_ship / page migration with {what}: pages ship by the "
            "global block table, which does not address a ring pool",
        "disaggregated":
            "DisaggregatedEngine with {what}: kv_ship ships pages by "
            "the global block table, which does not address a ring "
            "pool",
    },
    "recurrent": {
        "prefix_cache":
            "{what} with prefix_cache / prefix_share: a request that "
            "reattaches cached pages skips the positions that built "
            "the recurrent state (it needs state snapshots)",
        "speculation":
            "{what} under SpeculativeEngine: a rejected draft needs a "
            "rollback of the recurrent state",
        "prefill_only":
            "{what} with prefill_only (the prefill role of "
            "DisaggregatedEngine): kv_ship ships pages, not the "
            "recurrent state or the compressed keys",
        "gather_pages":
            "kv_ship / page migration with {what}: pages ship, the "
            "recurrent state and the compressed keys do not",
        "disaggregated":
            "DisaggregatedEngine with {what}: kv_ship ships pages, not "
            "the recurrent state or the compressed keys",
    },
    "index": {
        "prefix_cache":
            "{what} with prefix_cache / prefix_share: the token walk "
            "reads CAUSAL rows only (no SHARED_PREFIX topology) and a "
            "page of indexer keys under a second owner is not tested",
        "speculation":
            "{what} under SpeculativeEngine: the token walk has no "
            "verify-tree topology (a draft token's selection is its "
            "own)",
        "prefill_only":
            "{what} with prefill_only (the prefill role of "
            "DisaggregatedEngine): kv_ship ships K and V pages, not "
            "the indexer's keys",
        "gather_pages":
            "kv_ship / page migration with {what}: K and V pages "
            "ship, the indexer's keys do not",
        "disaggregated":
            "DisaggregatedEngine with {what}: kv_ship ships K and V "
            "pages, not the indexer's keys",
    },
    "latent": {
        "prefix_cache":
            "{what} with prefix_cache / prefix_share: the latent walk "
            "reads CAUSAL rows only (no SHARED_PREFIX topology) and a "
            "latent page under a second owner is not tested",
        "speculation":
            "{what} under SpeculativeEngine: the latent walk has no "
            "verify-tree topology",
        "prefill_only":
            "{what} with prefill_only (the prefill role of "
            "DisaggregatedEngine): kv_ship's wire layout is K and V "
            "pages, a latent pool has one array and no V",
        "gather_pages":
            "kv_ship / page migration with {what}: the wire layout is "
            "K and V pages, a latent pool has one array and no V",
        "disaggregated":
            "DisaggregatedEngine with {what}: kv_ship's wire layout is "
            "K and V pages, a latent pool has one array and no V",
    },
}


def state_kinds(mc) -> dict:
    """``{kind: its name in a message}`` of the kinds of state (the
    keys of ``REFUSED``) that the layers of model config ``mc`` keep."""
    kinds = {}
    if mc.window_layers:
        kinds["window"] = "sliding-window layers"
    stateful = [name for name, on in (
        ("lightning layers (layer_mixer)", bool(mc.lightning_layers)),
        ("kda layers (layer_mixer: a state matrix and a convolution "
         "tail a slot)", bool(mc.kda_layers)),
        ("block-sparse attention (sparse_topk)", mc.sparse_topk > 0),
    ) if on]
    if stateful:
        kinds["recurrent"] = ", ".join(stateful)
    if mc.kv_latent:
        kinds["latent"] = "a latent pool (kv_latent)"
    if mc.index_topk:
        kinds["index"] = ("a token selection (index_topk: a pool of "
                          "indexer keys beside K/V)")
    return kinds


def refuse(kinds: dict, feature: str) -> None:
    """Raise ``REFUSED``'s reason if one of ``kinds`` (``state_kinds``)
    cannot serve ``feature``."""
    for kind, what in kinds.items():
        why = REFUSED[kind].get(feature)
        if why is not None:
            raise ValueError(why.format(what=what))


class ServingEngine:
    """The scheduler. Owns the host mirrors (free list, block table,
    lengths, cursors) and the device :class:`ServingState`; every
    :meth:`step` assembles one ragged batch and runs one jitted
    ``model.serving_step``.

    ONE DEVICE STEP IS KEPT IN FLIGHT where nothing needs its result on
    the host first (:meth:`_launch_ahead`): a call of :meth:`step`
    admits, assembles, uploads and dispatches step k, and only THEN
    fetches step k - 1's token ids and advances its rows, so the device
    finds step k queued when k - 1 ends. A decode row's token of the
    step in flight stays on the device and is merged into step k's
    packed tokens there (``_merge_tokens``). What is public after
    ``step()`` returns (``Request.cursor`` / ``generated`` / ``done``,
    ``EngineStats``) describes RETIRED steps only; the launch-side view
    of a row (:meth:`_view`) is the engine's own."""

    #: True on an engine whose advance reads each row's LOGITS on the
    #: host (the speculative engine's verify loop, a probe that compares
    #: them): they come down every step and no step is launched ahead.
    #: False: a greedy engine (``temperature <= 0``) takes each row's
    #: arg-max on the device and fetches token ids.
    host_logits = False
    # the device step dispatched and not yet retired (a class default:
    # analysis/servlint.py builds its shell without ``__init__``)
    _flight: _Flight | None = None

    @spanned("setup.engine")
    def __init__(self, model, params, cfg: EngineConfig, *,
                 moe_state="auto", use_pallas: bool = True,
                 on_complete=None, health=None,
                 health_peer: str = "site:serving_step",
                 grid_schedule=None, tenants=None,
                 aging_ticks: int = 64, ops=None,
                 propagate_failures: bool = False):
        import jax
        import jax.numpy as jnp

        from triton_distributed_tpu.runtime.health import HealthLedger
        from triton_distributed_tpu.serving.protocol import ProtocolOps
        from triton_distributed_tpu.serving.state import PagePool

        self.model = model
        self.params = params
        self.cfg = cfg
        self.use_pallas = use_pallas
        # True: a failed device step is recorded and RE-RAISED instead
        # of degrading onto the XLA twin — for callers whose result
        # must not silently come from the fallback (chip_smoke.py)
        self.propagate_failures = propagate_failures
        # the protocol seam: every scheduling/pool transition runs
        # through these verbs (serving/protocol.py) — the same objects
        # analysis/servlint.py model-checks
        self.ops = ops if ops is not None else ProtocolOps()
        # every failure signal lands here; probation re-promotes the
        # fused path. A shared ledger (DisaggregatedEngine) makes one
        # role's kernel failure visible to the other.
        self.health = health if health is not None else HealthLedger(
            seed=cfg.seed)
        self.health_peer = health_peer
        # what a kind of state beside plain K/V pages cannot serve yet
        # is refused here, by name (``REFUSED``)
        mc = model.config
        self._window = int(mc.window) if mc.window_layers else 0
        self._kinds = state_kinds(mc)
        for feature, on in (
                ("prefix_cache", cfg.prefix_cache or cfg.prefix_share),
                ("speculation", self._spec_key() != (0, 0)),
                ("prefill_only", cfg.prefill_only)):
            if on:
                refuse(self._kinds, feature)
        with Span("setup.state"):
            self.state = model.init_serving_state(
                cfg.slots, cfg.npages, cfg.page, chunk=cfg.chunk
            )
        if self._window:
            st = self.state
            logging.getLogger(__name__).info(
                "serving state: %d global layer(s) x %d pages, %d window "
                "layer(s) x %d slots x ring %d = %d pages (window %d, "
                "chunk %d, page %d)",
                len(st.layers) - len(st.window_layers), st.npages,
                len(st.window_layers), cfg.slots, st.ring,
                cfg.slots * st.ring, self._window, cfg.chunk, cfg.page)
        self._jnp = jnp
        # a greedy engine's tokens: ``_greedy_tokens`` of each step's
        # logits, taken behind the step program and kept on the device
        # (sampling and ``host_logits`` need the logits on the host)
        self._greedy = None if cfg.temperature > 0.0 or self.host_logits \
            else _greedy_jit
        # the newest step's ids on the device, in flight or retired.
        # Zeros until the first step, placed as a step's are, so that
        # the first step's tokens are merged like every other's: the
        # step program sees ONE kind of ``tokens`` a width (a merged,
        # committed array; the host's upload is uncommitted, and the
        # pair would lower every width's first program twice)
        self._ids = None if self._greedy is None else jax.device_put(
            np.zeros((cfg.slots,), np.int32),
            NamedSharding(model.mesh, PartitionSpec()))
        self._uploads: dict = {}        # name -> (host copy, device array)
        pps = self.state.pages_per_seq
        self.table = np.full((cfg.slots, pps), -1, np.int32)
        # context-parallel decode: a model whose mesh carries a cp axis
        # stacks cp pools of cfg.npages pages each; the host allocator
        # mirrors that as cp per-shard pools behind one global page-id
        # namespace (appends route to the shard owning the logical page
        # index, matching the block-table column split the attention
        # walk shards on). cp == 1 is the plain allocator, unchanged.
        cp = getattr(model, "cp", 1)
        if cp > 1:
            from triton_distributed_tpu.serving.state import CpPagePool

            self.pool = CpPagePool(
                cp, cfg.npages, cfg.page, self.state.pages_per_shard,
                prefix_cache=cfg.prefix_cache,
            )
        else:
            self.pool = PagePool(cfg.npages, cfg.page,
                                 prefix_cache=cfg.prefix_cache)
        # hook: called (req, slot) when a request completes (or, under
        # prefill_only, finishes its prefill + first token). Return True
        # (the default behavior) to free the slot and pages; False to
        # PARK the request — slot and pages stay resident, unbatchable
        # and unevictable, until the caller releases them (the
        # DisaggregatedEngine's ship handshake).
        self.on_complete = on_complete
        self.slot_req: list = [None] * cfg.slots
        self.pending: deque = deque()      # not yet arrived (by time)
        self.waiting: deque = deque()      # arrived, not admitted
        self.stats = EngineStats()
        self.step_count = 0
        self._append_runs = 0           # of the batch last assembled
        # likewise: per packed position the slot whose token of the
        # step in flight belongs there, -1 where the host's own stands
        self._token_src = None
        self._pages_walked = [0, 0]     # likewise: [global, window]
        # likewise: [selected pages, sparse rows, state rows, of them
        # short rows of a lightning launch]
        self._state_work = [0, 0, 0, 0]
        # likewise: [rows through the selected walk, of them one-token]
        self._selected_work = [0, 0]
        self._latent_work = [0, 0]      # likewise: [pages fetched, rows]
        self._kda_work = [0, 0]         # likewise: [rows, chunk rows]
        # [rows, rows past topk, keys scanned, pairs walked, pairs chosen from]
        self._dsa_work = [0, 0, 0, 0, 0]
        # seconds of the running step inside each phase (``_phase``)
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        # --- multi-tenancy (all defaults reproduce the single-tenant
        # engine exactly: one implicit tenant at full shares, rank 0,
        # so preemption never finds a strictly-lower victim) ---
        self.tenants: dict = dict(tenants or {})
        self.aging_ticks = int(aging_ticks)
        # tiers the fleet brownout controller is currently squeezing:
        # their rows chunk at half budget and draft at k=1
        self.throttled_tiers: frozenset = frozenset()
        # hook: called (by_req, victim) when admission preempts a
        # lower-tier resident — the fleet wires its event log here
        self.on_preempt = None
        g = model.config.n_heads // model.config.n_kv_heads
        self._g = g
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            auto_block_q,
        )

        self._block_q_cap = auto_block_q(cfg.chunk, g)
        # ``_t_pad`` is the WIDEST width a step takes: a row that starts
        # at the budget's end and moves the cap's block (``_widths``: a
        # step is as wide as its batch needs, mostly narrower). No row
        # PARKS anywhere: a slot outside the batch (q_len == 0) is
        # skipped by every launch, given ``topologies``
        self._t_pad = cfg.token_budget + self._block_q_cap
        self._ladders: dict = {}           # ``_widths``, by rung
        # grid-schedule resolution (explicit > stored > default): the
        # traffic key this engine's every step lands on. A winner
        # persisted by tune.traffic after an earlier run is picked up
        # here on the next build — no search on the serving path.
        from triton_distributed_tpu.tune.schedule import (
            GRID_DEFAULT,
            resolve_schedule,
        )

        c = model.config
        # traffic key: geometry + the prefill chunk (chunking moves the
        # packed-token histogram the schedule is tuned against, so a
        # re-chunked engine is a DIFFERENT hot shape) + the speculation
        # coordinates (draft-k, spec_tree) so tune.traffic re-searches
        # hot SPECULATIVE shapes separately from plain decode at the
        # same geometry
        self._grid_key = (cfg.slots, self._t_pad, c.n_kv_heads, g,
                          c.head_dim, cfg.page, cfg.chunk) \
            + self._spec_key()
        with Span("setup.workspaces"):
            sched = resolve_schedule(
                "flash_decode.ragged_paged", self._grid_key, (model.tp,),
                "int8" if c.kv_quant is not None else None, grid_schedule,
            )
            if getattr(sched, "kind", "ring") != "grid":
                sched = GRID_DEFAULT      # stale ring entry: ignore
            self.grid_schedule = sched
            self._n_bufs = int(sched.n_bufs)
            # tuned block_q is a FLOOR under the cap: a step launches at
            # the rung ``_rung`` gives
            self._block_q_floor = int(sched.block_q)
            # the LOW rung: the least block a row of one token launches at
            self._block_q_low = min(self._block_q_cap, max(
                auto_block_q(1, g), self._block_q_floor))
            # LL MoE workspaces, sized to the packed step width: one set per
            # DISTINCT width, ``{width: per-layer states}``, built here and
            # never inside a step (``EPMoEState.instance`` is static: a
            # state belongs to the kernels compiled for its width). An
            # entry is None where that width's step carries no
            # workspaces (the XLA transport; ONE rank on the tp axis,
            # which exchanges with nobody: ``Transformer.moe_local``);
            # the whole is None for a model with no EP expert layer
            if moe_state == "auto":
                moe_state = {
                    w: model.init_decode_state(w)
                    for w in sorted({w for b in self._rungs()
                                     for w in self._widths(b)})
                } if c.moe == "ep" and c.moe_layers else None
            self.moe_state = moe_state
        self._aligned_rows: dict = {}      # ``_moe_aligned_rows``
        self._moe_local = int(model.moe_local)
        # program keys (``_run_device``) this engine has dispatched
        self._dispatched: set = set()
        if cfg.token_budget % 8:
            raise ValueError("token_budget must be 8-aligned")
        if cfg.chunk > cfg.token_budget:
            raise ValueError(
                f"chunk={cfg.chunk} exceeds token_budget="
                f"{cfg.token_budget}"
            )
        if cfg.prefix_share and not cfg.prefix_cache:
            raise ValueError(
                "prefix_share requires prefix_cache (the chain-hash "
                "registry IS the dedup index)"
            )
        if cp > 1 and cfg.prefix_share:
            raise ValueError(
                "prefix_share is incompatible with context-parallel "
                "decode: in-batch dedup retargets table columns to a "
                "canonical page, but under cp a logical page index is "
                "pinned to its owning shard — aliasing across rows "
                "would break the shard-ownership invariant"
            )
        if cp > 1 and self._spec_key() != (0, 0):
            raise ValueError(
                "speculative decoding is incompatible with context-"
                "parallel decode: verify-tree rows carry TREE topology "
                "descriptors, and the cp shard loop overwrites the "
                "topology row with its per-shard frontier shift"
            )

    def _rung(self, max_q_len: int) -> int:
        """The ``block_q`` a step whose longest row packs ``max_q_len``
        tokens launches at. TWO rungs: the lowest block (no lower than
        the tuned floor of the grid schedule, never past the cap) where
        it covers that row — a decode-only step —, else the cap. The
        blocks between would each cost a step program a width (2-4 s of
        a warm start) to spare the rows beside a prompt's TAIL the
        cap's query block, which they move beside every full chunk
        anyway."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            auto_block_q,
        )

        low = self._block_q_low
        return low if auto_block_q(max_q_len, self._g) <= low \
            else self._block_q_cap

    def _rungs(self) -> list:
        """Every ``block_q`` a step of this engine can launch at."""
        return sorted({self._block_q_low, self._block_q_cap})

    def _moe_aligned_rows(self, width: int) -> int:
        """``Transformer.moe_aligned_rows`` of this engine's step at
        ``width`` (one context a width, like its step program)."""
        if width not in self._aligned_rows:
            self._aligned_rows[width] = self.model.moe_aligned_rows(
                width, self.params)
        return self._aligned_rows[width]

    def _widths(self, block_q: int) -> tuple:
        """The packed widths, ascending, a step of this engine at rung
        ``block_q`` takes: ``_assemble`` picks the narrowest that covers
        what its batch needs. Under the cap one, :func:`packed_width`.
        At the cap :func:`chunk_widths` from that width and a chunk
        behind it up to ``_t_pad``, no further than the first that
        covers every batch the model's launches can ask for: a row of
        ``t`` tokens packed against the budget's end asks most
        (``Transformer.step_rows_needed``; a latent model's walk moves
        a row's own tokens only, so its one width covers the budget)."""
        if block_q not in self._ladders:
            cfg, cap = self.cfg, self._block_q_cap
            ladder = (packed_width(self._block_q_low, cfg.slots,
                                   cfg.token_budget),)
            if block_q == cap:
                reach = max(
                    self.model.step_rows_needed(
                        [cfg.token_budget - _ceil8(t)], [t], cap)
                    for t in range(1, cfg.chunk + 1))
                ladder = chunk_widths(ladder[0] + cap, self._t_pad,
                                      max(8, cap // 2))
                # the widths some batch outgrows, and the first none does
                ladder = ladder[:1 + sum(w < reach for w in ladder)]
            self._ladders[block_q] = ladder
        return self._ladders[block_q]

    def _unbuilt(self, block_q: int, width: int) -> list:
        """The widths of rung ``block_q`` other than ``width`` whose
        step program this engine has not dispatched on its present
        path: all of them at the rung's first launch, none afterwards.
        ``step`` builds them there, each by an EMPTY batch
        (:meth:`_empty_batch`) through the very frames a step's
        dispatch runs in, so that no later batch — wider than any a
        warm-up sent — builds a program under traffic."""
        return [w for w in self._widths(block_q) if w != width and (
            block_q, w, self.use_pallas, self._n_bufs)
            not in self._dispatched]

    def _empty_batch(self, width: int) -> tuple:
        """The arrays of a step of ``width`` rows that holds no row at
        all: every launch skips every slot, nothing is appended, and
        the ``ServingState`` comes back as it went in."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            causal_topologies,
            topo_width,
        )

        slots = np.zeros((self.cfg.slots,), np.int32)
        rows = np.zeros((width,), np.int32)
        return (rows, rows, np.full((width,), -1, np.int32), slots, slots,
                slots, causal_topologies(self.cfg.slots,
                                         topo_width(self._block_q_cap)))

    def _spec_key(self) -> tuple:
        """Speculation coordinates appended to the grid-schedule traffic
        key: (draft-k, spec_tree width). (0, 0) on plain engines; the
        speculative engine reports its draft budget so hot speculative
        shapes tune separately."""
        return (0, 0)

    # ------------------------------------------------------------ requests

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.pending.append(req)

    def submit_trace(self, trace) -> None:
        for r in sorted(trace, key=lambda r: r.arrival):
            self.submit(r)

    @property
    def idle(self) -> bool:
        return (not self.pending and not self.waiting
                and self._flight is None
                and all(r is None for r in self.slot_req))

    # ------------------------------------------------------------ tenancy

    def _tenant(self, req) -> TenantConfig:
        return self.tenants.get(
            getattr(req, "tenant", "default"), DEFAULT_TENANT)

    def _rank(self, req) -> int:
        """Static tier rank: the request's own priority, else its
        tenant's configured tier."""
        pr = getattr(req, "priority", None)
        if pr is None:
            pr = self._tenant(req).priority
        return tier_rank(pr)

    def _eff_rank(self, req) -> int:
        """Admission-order rank WITH anti-starvation aging."""
        pr = getattr(req, "priority", None)
        if pr is None:
            pr = self._tenant(req).priority
        rank = tier_rank(pr)
        if rank == 0 or self.aging_ticks <= 0:
            return rank
        waited = max(float(self.step_count) - float(req.arrival), 0.0)
        return max(0, rank - int(waited // self.aging_ticks))

    def _chunk_for(self, req) -> int:
        """Per-request prefill chunk: the configured budget, halved
        (floor 1) while the request's tier is under a brownout
        squeeze."""
        c = self.cfg.chunk
        if self.throttled_tiers:
            pr = getattr(req, "priority", None)
            if pr is None:
                pr = self._tenant(req).priority
            if pr in self.throttled_tiers:
                c = max(1, c // 2)
        return c

    # ----------------------------------------------------------- allocator

    def _pages_held(self, cursor: int) -> int:
        return -(-cursor // self.cfg.page)

    def _alloc(self, slot: int, held: int, need: int) -> bool:
        """Grow slot's table from ``held`` to ``need`` pages; all-or-
        nothing — :meth:`ProtocolOps.alloc`."""
        return self.ops.alloc(self, slot, held, need)

    def _free_slot(self, slot: int) -> None:
        """Release the slot's page references (the refcount
        discipline) — :meth:`ProtocolOps.free_slot`."""
        self.ops.free_slot(self, slot)

    def _evict_one(self, batched: set) -> bool:
        """Priority-aware LIFO eviction through the recompute
        discipline — :meth:`ProtocolOps.evict_one`."""
        return self.ops.evict_one(self, batched)

    def _preempt_for(self, by_req) -> bool:
        """Priority preemption of the lowest-tier resident strictly
        below ``by_req``'s effective rank —
        :meth:`ProtocolOps.preempt_for`."""
        return self.ops.preempt_for(self, by_req)

    # ---------------------------------------------------------------- step

    def _view(self, req) -> tuple:
        """``(cursor, length, pending)`` of a resident request as the
        step about to be assembled finds it — the LAUNCH-SIDE view,
        private to the engine: the committed ``req.cursor`` and
        ``len(req.seq)`` with the step in flight applied. ``pending``:
        that step's row reaches the sequence's frontier, so the token at
        ``length - 1`` exists only on the device yet (``_ids[slot]``).
        A request that reaches ``max_new`` with the step in flight has
        nothing left (``length == cursor``): it gets no further row.
        With nothing in flight this is the committed state."""
        cur, n = req.cursor, len(req.seq)
        f = self._flight
        take = f.takes.get(req.slot, 0) if f is not None else 0
        if not take:
            return cur, n, False
        cur += take
        if cur < n:
            return cur, n, False            # a prefill chunk in flight
        target = 1 if self.cfg.prefill_only else req.max_new
        if len(req.generated) + 1 >= target:
            return cur, cur, False          # completes with that step
        return cur, n + 1, True

    def _row_take_bound(self, req) -> int:
        """Upper bound on the tokens this request's next row packs —
        the admission/reservation headroom term. The speculative engine
        widens it by its draft budget."""
        cur, n, _ = self._view(req)
        return min(self._chunk_for(req), n - cur)

    def _committed_pages(self) -> int:
        """Pages the already-admitted slots will claim for their NEXT
        chunk but have not allocated yet — admission must not promise
        them away (allocation happens at batch assembly)."""
        tot = 0
        for req in self.slot_req:
            if req is None or req.parked or req.done:
                continue
            take = self._row_take_bound(req)
            cur = self._view(req)[0]
            tot += max(
                self._pages_held(cur + take) - self._pages_held(cur), 0,
            )
        return tot

    def _fair_share_ok(self, req, first: int) -> bool:
        """Per-tenant fair-share admission gate: would admitting
        ``req`` push its tenant past its configured ``page_share`` of
        the pool, or past its ``token_budget`` of packed tokens per
        step (summed over the tenant's resident rows)? Tenant-local —
        a violation defers THIS request without head-of-line blocking
        other tenants."""
        tc = self._tenant(req)
        if tc.page_share >= 1.0 and tc.token_budget is None:
            return True
        tenant = getattr(req, "tenant", "default")
        resident = [
            r for r in self.slot_req
            if r is not None and not r.done
            and getattr(r, "tenant", "default") == tenant
        ]
        if tc.page_share < 1.0:
            cap = int(tc.page_share * self.pool.npages)
            held = sum(self._pages_held(self._view(r)[0])
                       for r in resident)
            if held + self._pages_held(first) > cap:
                return False
        if tc.token_budget is not None:
            packed = sum(self._row_take_bound(r) for r in resident
                         if not r.parked)
            if packed + first > tc.token_budget:
                return False
        return True

    def _admit(self) -> None:
        """Priority admission (effective tier rank, then FIFO; with one
        tenant every rank is 0 and this is the pre-tenancy FIFO
        exactly) — :meth:`ProtocolOps.admit`."""
        self.ops.admit(self)

    # ------------------------------------------------------ prefix cache

    def _page_hashes(self, req, upto: int) -> list:
        """Chain hashes of ``req.seq``'s first ``upto`` full pages."""
        from triton_distributed_tpu.serving.state import page_chain_hash

        seq, page = req.seq, self.cfg.page
        hashes, h = [], 0
        for p in range(upto):
            h = page_chain_hash(h, seq[p * page:(p + 1) * page])
            hashes.append(h)
        return hashes

    def _attach_prefix(self, req, slot: int) -> None:
        """Reattach the longest run of resident full pages matching this
        request's prefix; the cursor jumps past them — those tokens'
        K/V are already in the pool, byte-identical (frozen pages are a
        pure function of the chained prefix). At least one trailing
        token is always left to recompute so the admission step still
        produces the row's next-token logits."""
        page = self.cfg.page
        limit = min((len(req.seq) - 1) // page, self.state.pages_per_seq)
        matched = 0
        for h in self._page_hashes(req, limit):
            pg = self.pool.lookup(h, matched)
            if pg is None:
                break
            self.pool.retain(pg)
            self.table[slot, matched] = pg
            matched += 1
        if matched:
            req.cursor = matched * page
            self.stats.prefix_hits += matched

    def _register_frozen(self, req, slot: int, old_cursor: int) -> None:
        """Publish pages the cursor just moved past (their content is
        frozen — nothing writes below the cursor) into the prefix
        cache."""
        page = self.cfg.page
        first = old_cursor // page          # first page possibly frozen now
        last = req.cursor // page           # pages [0, last) are full
        if last <= first:
            return
        hashes = self._page_hashes(req, last)
        for p in range(first, last):
            self.pool.register(int(self.table[slot, p]), hashes[p])

    def _plan_row(self, req) -> np.ndarray:
        """The tokens this request's row packs THIS step. Base engine:
        the next ``min(chunk, remaining)`` sequence tokens. The
        speculative engine appends provisional draft tokens to steady
        decode rows (its override records which tail is draft)."""
        cur, n, pending = self._view(req)
        if pending:
            # the row's one token is on the device (``_assemble`` names
            # the slot it comes from); the host packs a 0 in its place
            return np.zeros((1,), np.int32)
        take = min(self._chunk_for(req), n - cur)
        return np.asarray(req.seq[cur:cur + take], np.int32)

    def _row_topology(self, s: int, req, take: int):
        """Per-row attention-topology descriptor (one
        ``(2+2W,)`` int32 row, kernels/ragged_paged_attention.py
        layout) for this step's batch, or None for CAUSAL — the
        default. The speculative engine returns TREE descriptors for
        packed verify trees; batch assembly may still overwrite CAUSAL
        rows with SHARED_PREFIX after the dedup pass."""
        return None

    def _dedup_shared_prefixes(self, batched, topo, width: int) -> None:
        """In-batch shared-prefix dedup (``cfg.prefix_share``): fold
        each batched row's FROZEN pages (fully below its cursor —
        nothing writes them again) onto the prefix cache's canonical
        page for the same chain hash, releasing the duplicate. Rows
        whose leading pages end up multiply-referenced are marked
        SHARED_PREFIX with ``aux = split`` tokens; the kernel masks
        them causally (aliasing is a table-level fact) but the page
        walk now hits one physical run shared across the batch."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            TOPO_CAUSAL,
            shared_prefix_topology_row,
        )

        page = self.cfg.page
        for s in sorted(batched):
            req = self.slot_req[s]
            cursor = self._view(req)[0]
            frozen = min(cursor // page, self.state.pages_per_seq)
            if frozen <= 0:
                continue
            run = 0
            for p, h in enumerate(self._page_hashes(req, frozen)):
                pg = int(self.table[s, p])
                canon = self.pool.lookup(h, p)
                if canon is not None and canon != pg:
                    self.pool.release(pg)
                    self.pool.retain(canon)
                    self.table[s, p] = canon
                    self.stats.deduped_pages += 1
                    pg = canon
                if run == p and self.pool.refs[pg] >= 2:
                    run = p + 1
            if run > 0 and topo[s, 0] == TOPO_CAUSAL:
                topo[s] = shared_prefix_topology_row(
                    min(run * page, cursor), width
                )
                self.stats.shared_prefix_rows += 1

    def _assemble(self):
        from triton_distributed_tpu.kernels.lightning_attention import (
            short_row,
        )
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            LATENT_TQ,
            causal_topologies,
            topo_width,
        )

        cfg = self.cfg
        R, T = cfg.slots, self._t_pad
        tokens = np.zeros((T,), np.int32)
        token_src = np.full((T,), -1, np.int32)
        token_rows = np.zeros((T,), np.int32)
        token_pos = np.full((T,), -1, np.int32)
        q_starts = np.zeros((R,), np.int32)
        q_lens = np.zeros((R,), np.int32)
        kv_dev = np.zeros((R,), np.int32)
        topo_w = topo_width(self._block_q_cap)
        topo = causal_topologies(R, topo_w)
        next_start = 0
        self._append_runs = 0
        self._pages_walked = [0, 0]     # [global, window], one layer each
        self._state_work = [0, 0, 0, 0]
        self._selected_work = [0, 0]    # [rows, rows of one token]
        self._latent_work = [0, 0]      # [pages fetched, rows]
        self._kda_work = [0, 0]         # [rows, rows of several tokens]
        self._dsa_work = [0, 0, 0, 0, 0]
        mc = self.model.config
        batched: set = set()
        takes: dict = {}
        for s in range(R):
            req = self.slot_req[s]
            if req is None or req.parked or req.done:
                continue
            # the launch-side view: with the step in flight applied
            cur, n, pending = self._view(req)
            if n - cur <= 0:
                continue
            row = self._plan_row(req)
            take = len(row)
            if take <= 0:
                continue
            if next_start + _ceil8(take) > cfg.token_budget:
                self.stats.deferrals += 1
                continue                   # token budget spent
            held = self._pages_held(cur)
            need = self._pages_held(cur + take)
            if self.ops.ensure_pages(self, s, held, need, batched):
                # allocation succeeded
                span = slice(next_start, next_start + take)
                tokens[span] = row
                if pending:
                    token_src[next_start] = s
                token_rows[span] = s
                token_pos[span] = np.arange(
                    cur, cur + take, dtype=np.int32
                )
                q_starts[s] = next_start
                q_lens[s] = take
                kv_dev[s] = cur + take
                # the span's pages: the cursor's own up to the last held
                self._append_runs += need - cur // cfg.page
                if not mc.sparse_topk:
                    # (a sparse layer walks a selection, counted below)
                    self._pages_walked[0] += need
                if self._window:
                    self._pages_walked[1] += need - max(
                        cur - self._window + 1, 0) // cfg.page
                if mc.sparse_topk:
                    self._state_work[0] += (
                        min(need, mc.sparse_topk) if take == 1 else need)
                    self._state_work[1] += (
                        cur + take > mc.sparse_dense_len)
                    self._selected_work[0] += 1
                    self._selected_work[1] += take == 1
                if mc.recurrent_layers:
                    self._state_work[2] += 1
                    if mc.lightning_layers:
                        self._state_work[3] += short_row(take)
                if mc.kda_layers:
                    self._kda_work[0] += 1
                    self._kda_work[1] += take > 1
                if mc.index_topk:
                    k, end = mc.index_topk, cur + take
                    self._dsa_work[0] += 1
                    self._dsa_work[1] += end > k
                    self._dsa_work[2] += end if end > k else 0
                    # sum over positions cur .. end - 1 of min(p + 1, k)
                    low = max(min(end, k) - cur, 0)
                    self._dsa_work[3] += (
                        low * (2 * cur + low + 1) // 2 + (take - low) * k)
                    self._dsa_work[4] += (
                        max(end - max(cur, k), 0) * need * cfg.page)
                if mc.kv_latent:
                    self._latent_work[0] += sum(
                        self._pages_held(cur + min(i + LATENT_TQ, take))
                        for i in range(0, take, LATENT_TQ))
                    self._latent_work[1] += 1
                next_start += _ceil8(take)
                batched.add(s)
                takes[s] = take
                desc = self._row_topology(s, req, take)
                if desc is not None:
                    topo[s] = desc
                continue
            # page allocation failed even after eviction: defer the row
            self.stats.deferrals += 1
        if cfg.prefix_share and batched:
            self._dedup_shared_prefixes(batched, topo, topo_w)
        # the step is as wide as THIS batch needs at its rung: the
        # narrowest of the rung's widths that covers every batched
        # row's ``q_starts[s] + block(s)`` (the model says what its
        # launches move for a row; a slot outside the batch stays at 0
        # and is skipped by all of them)
        block_q = self._rung(int(q_lens.max()))
        need = self.model.step_rows_needed(q_starts, q_lens, block_q)
        width = next(w for w in self._widths(block_q) if w >= need)
        self._token_src = token_src[:width]
        return (tokens[:width], token_rows[:width], token_pos[:width],
                q_starts, q_lens, kv_dev, topo, batched, takes)

    def _step_jit(self):
        """The jitted device step this engine launches. The speculative
        engine overrides this with the all-positions-logits twin (same
        batch contract, (T, vocab) logits)."""
        return self.model._serving_jit

    def _step_args(self, arrays, block_q) -> tuple:
        """The jitted step's argument tuple for one assembled batch —
        what :meth:`_run_device` calls it with, and what
        ``_step_jit().lower(...)`` needs to show the module a step
        really launches (chip_smoke.py looks for the ragged kernel's
        custom call there)."""
        jnp = self._jnp
        (tokens, token_rows, token_pos, q_starts, q_lens, kv_dev,
         topo) = arrays
        # a slot outside the batch sits at row 0 of a width that has no
        # room to park it: only a launch told to skip it is safe
        assert topo is not None, "a step's batch needs its topologies"
        state = self.state.replace(
            block_table=jnp.asarray(self.table),
            kv_lens=jnp.asarray(kv_dev),
            # (made int32 on the host: ``jnp.asarray(list, dtype=)``
            # is a conversion program on the device, a step)
            cursors=jnp.asarray(np.asarray(
                [0 if r is None else self._view(r)[0]
                 for r in self.slot_req], np.int32)),
        )
        if self._greedy is None:
            # the logits come down: every token is the host's
            tokens_dev = jnp.asarray(tokens)
        else:
            # a greedy engine merges in front of EVERY step, also one
            # that takes no token from the device: one tiny program a
            # packed width, compiled where the width's step program is
            # (a warm-up that has run a width has run both).
            # ``_token_src`` is of the batch last assembled, as
            # ``arrays`` are
            tokens_dev = _merge_jit(
                self._uploaded("tokens", tokens),
                self._uploaded("token_src", self._token_src), self._ids)
        return (
            self.params, state, tokens_dev,
            self._uploaded("token_rows", token_rows),
            jnp.asarray(token_pos),
            self._uploaded("q_starts", q_starts),
            self._uploaded("q_lens", q_lens),
            self._uploaded("topo", topo),
            # the workspaces of THIS step's width
            None if self.moe_state is None
            else self.moe_state[len(tokens)],
            block_q, self.use_pallas, self._n_bufs,
        )

    def _uploaded(self, name: str, host: np.ndarray):
        """The device copy of one of a step's host arrays — last step's
        while the content is last step's: consecutive decode-only steps
        over the same rows repeat their layout (rows, starts, lengths,
        topology; and their tokens, all of which come from the device:
        zeros and the slots they come from), and an upload costs the
        host ~0.3 ms whatever its size. Only for arguments the step
        does not donate."""
        last = self._uploads.get(name)
        if last is not None and last[0].shape == host.shape \
                and np.array_equal(last[0], host):
            return last[1]
        self._uploads[name] = (host.copy(), self._jnp.asarray(host))
        return self._uploads[name][1]

    def _phase(self, phase: str) -> Span:
        return Span(f"engine.{phase}", self._phase_s, phase,
                    step=self.step_count)

    def _run_device(self, arrays, block_q):
        """Upload one assembled batch and dispatch its step; returns the
        step's result ON THE DEVICE — a greedy engine's ``(slots,)``
        token ids (``_greedy_tokens`` behind the step program), else the
        logits. Nothing here waits for the device: :meth:`_fetch` does."""
        from triton_distributed_tpu.lang.launch import maybe_instrument

        with self._phase("upload"):
            args = self._step_args(arrays, block_q)
        # the program this step runs is built (traced, lowered, compiled
        # or loaded from the cache) inside its FIRST dispatch, which the
        # span ``setup.program`` holds; a build under a key seen before
        # is the log's, as a rebuild. The jitted call stays in THIS
        # frame, first dispatch or not: JAX's tracing and lowering times
        # move by seconds with the shape of the Python stack above them
        # (PERF.md §6, PR 39)
        width = len(arrays[0])
        key = (block_q, width, self.use_pallas, self._n_bufs)
        build = _STEADY if key in self._dispatched else Span(
            "setup.program", step=self.step_count, block_q=block_q,
            width=width)
        with self._phase("dispatch"), build:
            # host-mode heartbeat around the jitted step: an armed
            # watchdog sees a wedged serving step (site "serving_step"),
            # and a fault-plan Stall at that site gates here
            step_fn = maybe_instrument(
                self._step_jit(), axis=None, site="serving_step",
                collective_id=("serving_step", self.health_peer), n=1,
                step=self.step_count,
            )
            out = step_fn(*args)
            if self.moe_state is None or self.moe_state[width] is None:
                logits, self.state = out
            else:
                logits, self.state, states = out
                self.moe_state[width] = states
                # ONE parity sequence for every width: the barrier-free
                # protocol alternates parity from a STEP to the next, so
                # that a peer one step ahead signals the semaphores the
                # step behind does not wait on — whichever widths the
                # two steps have (the sets' kernels may share them)
                for other in self.moe_state.values():
                    if other is not states:
                        for mine, new in zip(other, states):
                            if mine is not None:
                                mine.parity = new.parity
            if self._greedy is not None:
                # the ids stay on the device: the next step's packed
                # tokens are merged from them there (``_merge_tokens``)
                logits = self._ids = self._greedy(logits)
        if build is not _STEADY:
            self._dispatched.add(key)
            self.stats.programs_built += 1
            self.stats.program_build_s += build.seconds
        return logits

    def _fetch(self, flight: _Flight) -> None:
        """Bring a dispatched step's result down (``flight.host``)."""
        with Span("engine.fetch", flight.phase_s, "fetch",
                  step=flight.step):
            # the host fetch is the fence: the wait for the step program,
            # the copy down and the delinearize, deliberately one span (a
            # block_until_ready before it would put a host wake-up on
            # the critical path untraced). Launched ahead, the wait is
            # for the step BEFORE the one the device now runs or holds
            # queued.
            flight.host = np.asarray(flight.out)
            # the device logits are freed here, inside the span, not on
            # return (0.1-0.2 ms of a step's idle gap); ``_ids`` keeps
            # a greedy engine's (slots,) ids
            flight.out = None

    def _launch_ahead(self) -> bool:
        """THE ONE QUESTION ``step()`` asks before it assembles: may
        this step be dispatched before the step in flight is retired —
        or does assembling or advancing it need a value that is still
        on the device, or state that only a retirement commits? Read
        from what the engine can observe, each step anew; where the
        answer is no, the step in flight is retired FIRST and the step
        runs in the synchronous order (launch, fetch, advance in one
        call)."""
        from triton_distributed_tpu.runtime.health import PeerState

        cfg = self.cfg
        if self._greedy is None:
            # the logits come down: a draw keyed on (seed, rid, n), or
            # an advance that reads them (``host_logits``), picks the
            # next token on the host
            return False
        if cfg.prefix_cache and (
                self._flight is not None and self._flight.freezes
                or self.waiting or self.pending
                and self.pending[0].arrival <= self.step_count):
            # the registry is written at a retirement (a page the step
            # in flight freezes is published there, when every token
            # its chain hash covers is down) and read by an admission
            # (``_attach_prefix``, which also takes cached pages out of
            # the pool's headroom) and by the dedup: whatever that step
            # freezes is in it before this one looks anything up
            return False
        if self.stats.degraded or self.health.state(
                self.health_peer) is not PeerState.HEALTHY:
            # a probing or degraded step: the fallback re-runs the
            # batch it just launched, and the ledger hears of a clean
            # step only once its result is down
            return False
        # what admission will find waiting (``pending`` is in arrival
        # order: ``ProtocolOps.admit`` moves its head over likewise)
        due = (*self.waiting, *itertools.takewhile(
            lambda p: p.arrival <= self.step_count, self.pending))
        worst = max((self._eff_rank(r) for r in self.slot_req
                     if r is not None and not r.done), default=0)
        if worst and any(self._eff_rank(w) < worst for w in due):
            # admission may pre-empt a resident for a request that
            # outranks it (``_preempt_for``): the victim's replay needs
            # its committed cursor and every token it generated
            return False
        # ``ensure_pages`` may have to evict, likewise: the pages this
        # step can claim are those the residents' next rows need and the
        # first chunks of the requests it may admit — as many as slots
        # are free, and no more pages than admission promises out of
        # ``pool.available``. That is the SUM over a cp pool's shards,
        # while each page is claimed on the shard that owns its index
        # (a sequence's first pages all on shard 0): the step is safe
        # where the fullest shard could hold all of it
        claimed = self._committed_pages()
        free = sum(r is None for r in self.slot_req)
        firsts = sorted((self._pages_held(min(self._chunk_for(r),
                                              len(r.seq)))
                         for r in due), reverse=True)[:free]
        claimed += min(sum(firsts), max(self.pool.available - claimed, 0))
        return claimed <= self.pool.headroom

    def _prepare(self, arrays, takes, report) -> tuple:
        """The rest of the assemble phase for a batch that launches: the
        step's rung, the health ledger's say on the path it takes, and
        its :class:`_Flight`. Returns ``(flight, block_q, probing)``."""
        from triton_distributed_tpu.runtime.health import PeerState

        tokens, q_starts, q_lens, kv_lens = (
            arrays[0], arrays[3], arrays[4], arrays[5])
        block_q = self._rung(int(q_lens.max()))
        peer = self.health_peer
        if self.use_pallas \
                and self.health.state(peer) is PeerState.UNHEALTHY:
            # the ledger condemned the fused path out-of-band (a
            # shared ledger's other role, a watchdog trip): demote
            # before launching
            self.use_pallas = False
            self.stats.degraded = True
        # PROBATION: on the seeded schedule, try the fused path again
        probing = (not self.use_pallas
                   and self.health.probe_due(peer, self.step_count))
        if probing:
            self.use_pallas = True
        c = self.model.config
        page = self.cfg.page
        # a step that holds a chunk: a row longer than the low rung
        chunk = int(int(q_lens.max()) > self._block_q_low)
        flight = _Flight(
            step=self.step_count, phase_s=self._phase_s, report=report,
            takes=takes, q_starts=q_starts, q_lens=q_lens,
            ahead=self._flight is not None,
            # ``kv_lens`` is each row's cursor past its take
            freezes=bool((kv_lens // page > (kv_lens - q_lens) // page)
                         .any()),
            counts={
                "global_pages_walked": self._pages_walked[0],
                "window_pages_walked": self._pages_walked[1],
                "selected_pages_walked": self._state_work[0],
                "sparse_rows": self._state_work[1],
                "state_rows": self._state_work[2],
                "state_token_rows": self._state_work[3],
                "selected_rows": self._selected_work[0],
                "selected_token_rows": self._selected_work[1],
                "kda_rows": self._kda_work[0],
                "kda_chunk_rows": self._kda_work[1],
                "dsa_rows": self._dsa_work[0],
                "dsa_sparse_rows": self._dsa_work[1],
                "index_keys_scanned": self._dsa_work[2],
                "dsa_selected_tokens": self._dsa_work[3],
                "dsa_select_pairs": self._dsa_work[4],
                "latent_pages_walked": self._latent_work[0],
                "latent_rows": self._latent_work[1],
                "packed_rows": len(tokens),
                "chunk_steps": chunk,
                "chunk_packed_rows": chunk * len(tokens),
                "chunk_narrow_steps": chunk * (len(tokens) < self._t_pad),
                # the step program masks its padding rows' assignments
                "moe_masked_rows": len(tokens) - report["tokens"]
                if c.moe == "ep" and c.moe_layers else 0,
                "moe_aligned_rows": self._moe_aligned_rows(len(tokens)),
                "moe_local_steps": self._moe_local,
            })
        return flight, block_q, probing

    def drain(self) -> dict | None:
        """Retire the step in flight, if there is one: afterwards the
        public state is the whole of what was dispatched, and nothing
        of this engine's is still on the device alone. Returns that
        step's report. ``run()`` ends with it.

        THE CONTRACT for everyone who is not the engine: whoever reads
        or moves this engine's slots, block table or pool between its
        steps — parks, ships, lands or frees a slot, re-queues or
        migrates a resident request, reserves pages, flips a role —
        calls ``drain()`` first (``DisaggregatedEngine.tick`` and
        ``fleet.Replica.step`` do, after every step of theirs).
        ``_launch_ahead`` answers only for what the engine itself
        decides; the hooks (``on_complete``, ``on_preempt``) are called
        from a retirement with the request's committed state."""
        flight, self._flight = self._flight, None
        return None if flight is None else self._retire(flight)

    def step(self) -> dict:
        """One engine step: admit → assemble → upload → dispatch step k,
        THEN fetch and advance step k - 1 (cursors, tokens, completions)
        while the device runs k. Where :meth:`_launch_ahead` says no,
        the same in the synchronous order: k - 1 is retired first, and k
        in this call too. Returns the report of the step this call
        retired (of the one it launched, if it retired none)."""
        ahead = self._launch_ahead()
        retired = None if ahead else self.drain()
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        evictions = self.stats.evictions
        with self._phase("admit"):
            self._admit()
        with self._phase("assemble"):
            (tokens, token_rows, token_pos, q_starts, q_lens, kv_dev,
             topo, batched, takes) = self._assemble()
            if self._flight is not None \
                    and self.stats.evictions != evictions:
                raise RuntimeError(
                    "a request was evicted with a step in flight: its "
                    "replay would miss that step's token")
            report = {"step": self.step_count, "batched": len(batched),
                      "tokens": int(q_lens.sum())}
            if batched:
                arrays = (tokens, token_rows, token_pos, q_starts, q_lens,
                          kv_dev, topo)
                if self._unbuilt(self._rung(int(q_lens.max())),
                                 len(tokens)):
                    # set-up, a rung's first launch: its other widths
                    # are built with nothing in flight
                    retired = self.drain() or retired
                flight, block_q, probing = self._prepare(
                    arrays, takes, report)
        if not batched:
            # nothing to launch: what is in flight is retired
            retired = self.drain() or retired
            self.step_count += 1
            return retired or report
        from triton_distributed_tpu.runtime.health import PeerState

        peer = self.health_peer

        def run_device():
            # a rung's first launch on this path builds the programs of
            # its other widths too, in THIS frame (what lies above the
            # jitted call moves its tracing and the compile cache's
            # key: PERF.md section 6, PR 39); the empty batches leave
            # the state, and the ids a merge reads, as they were
            unbuilt = self._unbuilt(block_q, len(arrays[0]))
            if unbuilt:
                ids, token_src = self._ids, self._token_src
                for w in unbuilt:
                    self._token_src = np.full((w,), -1, np.int32)
                    self._run_device(self._empty_batch(w), block_q)
                self._ids, self._token_src = ids, token_src
            flight.out = self._run_device(arrays, block_q)
            # the pool append of the step as it ran: by the kernel, a
            # (slot, page) run at a time, or by the row scatter
            by_kernel = self.model.kv_append_by_kernel(self.use_pallas)
            flight.counts["append_runs"] = by_kernel * self._append_runs
            flight.counts["append_scatter_steps"] = int(not by_kernel)
            if not ahead:
                # the synchronous order: this step's own result, inside
                # the guarded run as ever
                self._fetch(flight)

        try:
            run_device()
        except Exception as e:
            if not self._failed(self.step_count, e, probing):
                raise
            # scheduling state is untouched: re-run the batch on the
            # XLA twin
            run_device()
        else:
            if probing:
                st = self.health.probe_result(peer, True,
                                              step=self.step_count)
                if st is PeerState.HEALTHY:
                    # enough clean probes: stay on the fused path
                    self.stats.degraded = False
                    self.stats.repromotions += 1
                else:
                    self.use_pallas = False   # keep earning probes
            elif not self.use_pallas and self.stats.degraded:
                st = self.health.observe_clean(peer,
                                               step=self.step_count)
                if st is PeerState.HEALTHY:
                    # SUSPECT cleared (non-fatal signal sources): resume
                    self.use_pallas = True
                    self.stats.degraded = False
                    self.stats.repromotions += 1
        # step k is queued behind k - 1 on the device: now k - 1's ids
        # come down and its rows advance, under the device's step
        before, self._flight = self._flight, flight
        if before is not None:
            retired = self._retire(before)
        if not ahead:
            retired = self.drain()
        self.step_count += 1
        return retired or report

    def _failed(self, step: int, e: Exception, probing: bool = False) -> bool:
        """Book the failure of device step ``step`` and degrade: fall
        back to the XLA twin (the op-level with_fallback story at engine
        level). The failure is a ledger signal: a probe failure drops
        straight back to UNHEALTHY, a first failure is fatal
        (kernel_error), so re-entry to the fused path only ever happens
        through clean probes. False: there is no twin left to fall back
        to, or failures propagate — the caller raises."""
        self.stats.failures.append({
            "step": step, "site": "serving_step",
            "error": f"{type(e).__name__}: {e}",
        })
        if not self.use_pallas or self.propagate_failures:
            return False
        if probing:
            self.health.probe_result(self.health_peer, False, step=step)
        else:
            self.health.record("kernel_error", self.health_peer, step=step)
        self.use_pallas = False
        self.stats.degraded = True
        return True

    def _retire(self, flight: _Flight) -> dict:
        """Fetch a dispatched step's result (if it is not down yet) and
        advance its rows: cursors, generated tokens, ``t_first``,
        completions, freed slots, and the step's entries and counts in
        ``EngineStats`` — everything public about it, in one call."""
        if flight.host is None:
            try:
                self._fetch(flight)
            except Exception as e:
                # a step launched ahead whose failure surfaces only now,
                # as its result comes down: the pools its program
                # donated are gone with it, so there is no batch to
                # re-run; it is booked and told to the ledger (every
                # later step is drained and on the twin), then raised
                self._failed(flight.step, e)
                raise
        stats, phase_s, report = self.stats, flight.phase_s, flight.report
        dt = phase_s["upload"] + phase_s["dispatch"] + phase_s["fetch"]
        with Span("engine.advance", phase_s, "advance", step=flight.step):
            gen_this_step = 0
            prefill_this_step = 0
            for s, take in flight.takes.items():
                req = self.slot_req[s]
                emitted, prefill_toks = self._advance_row(
                    s, req, take, flight.host,
                    flight.q_starts, flight.q_lens)
                gen_this_step += emitted
                prefill_this_step += prefill_toks
                if req.t_first is None and req.generated:
                    req.t_first = time.perf_counter()
                    if req.t_admit is not None:
                        stats.first_token_s += req.t_first - req.t_admit
                        stats.first_tokens += 1
            stats.step_times.append(dt)
            stats.step_tokens.append(report["tokens"])
            for k, v in flight.counts.items():
                setattr(stats, k, getattr(stats, k) + v)
            stats.lookahead_steps += flight.ahead
            stats.step_generated.append(gen_this_step)
            stats.note_shape(
                self._grid_key, dt * 1e3,
                self.pool.npages - self.pool.available,
            )
            stats.prefill_tokens += prefill_this_step
            report.update(
                ms=round(dt * 1e3, 3), generated=gen_this_step,
                free_pages=self.pool.available,
                waiting=len(self.waiting) + len(self.pending),
            )
        for k, v in phase_s.items():
            getattr(stats, k + "_times").append(v)
        return report

    def _advance_row(self, s: int, req, take: int, logits,
                     q_starts, q_lens) -> tuple:
        """Advance one batched row after the device step: move the
        cursor past the packed tokens, publish newly-frozen pages, and
        sample at the sequence frontier. Returns ``(emitted,
        prefill_tokens)`` — tokens this row EMITTED into its stream and
        packed tokens that were prefill (not generation) work. The
        speculative engine overrides this with the verify/accept loop
        (multi-token emission + rejected-draft rollback)."""
        self.ops.advance_cursor(self, s, req, take)
        if req.cursor == len(req.seq):
            # the row's last packed token was its sequence frontier:
            # the logits row is the next-token distribution
            tok = self._sample(logits[s], req)
            req.generated.append(tok)
            self._maybe_complete(req, s)
            return 1, take - 1
        return 0, take

    def _maybe_complete(self, req, s: int) -> None:
        """Completion check after a row emitted into ``req.generated``
        — :meth:`ProtocolOps.complete`."""
        self.ops.complete(self, req, s)

    def _sample(self, row_logits, req) -> int:
        """Next token for one completed row. Greedy argmax at
        ``temperature <= 0``; otherwise softmax sampling of
        ``logits/temperature`` over the ``top_k`` best (0 = full vocab),
        drawn from a generator keyed on (seed, rid, generated-so-far) —
        request-local, so scheduling (chunking, eviction replays, the
        disaggregated prefill/decode split) can never change a
        request's token stream."""
        t = self.cfg.temperature
        if t <= 0.0:
            if self._greedy is not None:
                # the row's arg-max, taken and checked on the device
                # (-1: not finite) one step before it came down
                tok = int(row_logits)
                finite = tok >= 0
            else:
                tok = int(np.argmax(row_logits))
                # argmax lands ON a NaN (or +inf) whenever the row holds
                # one, so this O(1) look catches a poisoned distribution
                # that would otherwise decode as a valid-looking token
                # id (the sampling branch below raises on NaN by itself)
                finite = np.isfinite(row_logits[tok])
            if not finite:
                raise FloatingPointError(
                    f"non-finite logits for request {req.rid} at step "
                    f"{self.step_count}"
                )
            return tok
        z = np.asarray(row_logits, np.float64) / t
        k = self.cfg.top_k
        if 0 < k < z.shape[-1]:
            kth = np.partition(z, -k)[-k]
            z = np.where(z >= kth, z, -np.inf)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        rng = np.random.default_rng(
            (self.cfg.seed, req.rid, len(req.generated))
        )
        return int(rng.choice(p.shape[-1], p=p))

    def run(self, trace=None, max_steps: int | None = None) -> EngineStats:
        """Drive the engine until the trace drains (or ``max_steps``)."""
        if trace is not None:
            self.submit_trace(trace)
        max_steps = max_steps or self.cfg.max_steps
        for _ in range(max_steps):
            if self.idle:
                break
            self.step()
        self.drain()
        return self.stats

    # ------------------------------------------------ shipped admission
    # The decode half of a disaggregated deployment admits requests
    # whose KV was COMPUTED ELSEWHERE: reserve_shipped claims the slot
    # and the block-table-assigned landing pages up front (parked — the
    # in-flight-transfer state eviction must never touch), and
    # commit_shipped flips the row schedulable once the pages have
    # landed. Admission therefore gates on *shipped* pages, not on
    # promises.

    def reserve_shipped(self, req) -> tuple | None:
        """Claim a slot + landing pages for a request whose first
        ``req.cursor`` tokens of KV will arrive by transfer —
        :meth:`ProtocolOps.reserve_shipped`. Returns (slot, page_ids)
        or None (no slot / pool pressure — the caller retries, leaving
        the source pages pinned)."""
        return self.ops.reserve_shipped(self, req)

    def commit_shipped(self, req) -> None:
        """The transfer into this request's reserved pages has landed:
        the row becomes schedulable (and evictable) like any other —
        :meth:`ProtocolOps.commit_shipped`."""
        self.ops.commit_shipped(self, req)

    def release_parked(self, slot: int) -> None:
        """Free a parked slot (source-side handoff after its pages have
        shipped, or an abandoned reservation) —
        :meth:`ProtocolOps.release_parked`."""
        self.ops.release_parked(self, slot)

    # The wire-form page plumbing below is shared by every pool→pool
    # transfer this engine is an endpoint of: the disaggregated
    # prefill→decode ship and the fleet's replica→replica migration
    # (serving/fleet.py) both move the pool's NATIVE quantized bytes,
    # so a page that travels is byte-identical to one that never moved.

    def _kv_wire_jits(self) -> tuple:
        jits = getattr(self, "_kv_wire_cache", None)
        if jits is None:
            import jax

            from triton_distributed_tpu.kernels.kv_ship import (
                gather_kv_pages,
                scatter_kv_pages,
            )

            jits = (jax.jit(gather_kv_pages),
                    jax.jit(scatter_kv_pages, donate_argnums=(0,)))
            self._kv_wire_cache = jits
        return jits

    def gather_pages(self, pids) -> tuple:
        """Pull pool pages ``pids`` into the kv_ship wire layout
        (``(q, s)`` — int8 payload + f32 scale rail under
        ``kv_quant``)."""
        import jax.numpy as jnp

        refuse(self._kinds, "gather_pages")
        gather, _ = self._kv_wire_jits()
        return gather(self.state.layers,
                      jnp.asarray(list(pids), jnp.int32))

    def land_pages(self, pids, q_payload, s_payload) -> None:
        """Scatter an arrived wire payload into this engine's pools at
        page slots ``pids`` (donating scatter + landing fence, the
        ``_commit_ships`` discipline)."""
        import jax
        import jax.numpy as jnp

        _, scatter = self._kv_wire_jits()
        new_layers = scatter(self.state.layers,
                             jnp.asarray(list(pids), jnp.int32),
                             q_payload, s_payload)
        jax.block_until_ready(new_layers)
        self.state = self.state.replace(layers=new_layers)


# ===================================================================
# Disaggregated prefill/decode: two role engines, KV shipped between
# ===================================================================

@dataclass
class ShipRecord:
    """One in-flight KV transfer (prefill pool → decode pool)."""

    req: Request
    pslot: int                   # prefill-side slot (pages pinned)
    dslot: int                   # decode-side reserved slot
    dpids: list                  # decode-side landing page ids
    payload: tuple               # (q, s) device arrays on the decode mesh
    issued_tick: int
    wire_bytes: int
    raw_bytes: int
    launch_ms: float = 0.0


@dataclass
class DisaggStats:
    """Two role engines' stats plus the ship ledger. Wall-time metrics
    model the production deployment — the roles run on DISJOINT slices,
    so the system's wall clock is the slower role, not the host-side
    sum this single-process harness serializes."""

    prefill: EngineStats
    decode: EngineStats
    ships: int = 0
    ship_ms: list = field(default_factory=list)
    shipped_wire_bytes: int = 0
    shipped_raw_bytes: int = 0
    # CURRENTLY on the XLA transfer (probation re-promotion clears it)
    degraded_transport: bool = False
    ship_retries: int = 0              # DCN attempts retried before success/fallback
    # every failed DCN ship attempt: {"tick", "site", "error"}
    transport_failures: list = field(default_factory=list)
    transport_repromotions: int = 0    # probe-driven returns to the DCN wire
    # --- slice-death failover ---
    failover_role: str | None = None   # which role's slice died
    failover_tick: int | None = None
    failover_requeued: int = 0         # requests re-queued onto the survivor
    failover_re_prefill_tokens: int = 0  # KV tokens that must re-prefill
    recovery_tick: int | None = None   # first tick with every re-queued req done

    @property
    def failover(self) -> dict | None:
        """The failover outcome in one dict (None if no slice died)."""
        if self.failover_role is None:
            return None
        return {
            "role": self.failover_role,
            "tick": self.failover_tick,
            "requeued": self.failover_requeued,
            "re_prefill_tokens": self.failover_re_prefill_tokens,
            "recovery_tick": self.recovery_tick,
        }

    @property
    def completed(self) -> int:
        return self.decode.completed

    @property
    def wire_compression(self) -> float:
        """Raw-payload bytes per wire byte actually shipped (> 1 means
        the quantized wire genuinely shrank the DCN transfer)."""
        return (self.shipped_raw_bytes / self.shipped_wire_bytes
                if self.shipped_wire_bytes else 1.0)


class DisaggregatedEngine:
    """Two-role serving topology: a PREFILL engine runs chunked prefill
    (plus the first token) into its local page pool; each finished
    request's KV pages then ship slice→slice — int8 page payloads with
    their per-row f32 scale planes, the pool's native quantized layout
    riding the paired-rail wire — landing in the DECODE engine's pool
    at block-table-assigned slots, overlapped with ongoing decode
    steps. The decode engine admits a request only once its pages have
    LANDED (reserve → transfer → commit), and in-flight transfers pin
    their pages on both sides, so eviction can never free a page
    mid-ship.

    Transport selection (``transport=``):

    * ``"dcn"`` — the quantized DCN wire: paired payload+scale
      ``ppermute`` rails over the hybrid mesh's DCN axis
      (:func:`runtime.multislice.dcn_wire_kv_ship`); requires
      ``hybrid_mesh``.
    * ``"xla"`` — :func:`tools.native.xla_kv_ship`: a plain device_put
      of the payload onto the decode mesh — the degradation target.
    * ``"auto"`` — ``"dcn"`` when a hybrid mesh is given, else
      ``"xla"``. The FIRST failure of the wire path degrades the
      engine onto ``"xla"`` for the rest of the session
      (``stats.degraded_transport``), mirroring the kernel→XLA-twin
      story at engine level.

    ``ship_delay_steps`` holds a transfer "in flight" for that many
    ticks before committing — on hardware the window is the real DCN
    latency; here it deterministically exercises the
    overlap/eviction-pinning machinery.

    ``placement="auto"`` consults the perf model
    (:func:`tune.perf_model.refuse_disaggregation`) with the expected
    ``traffic`` shape and REFUSES to construct the split topology when
    the KV wire would dominate the decode window it must hide under.
    """

    def __init__(self, prefill_model, prefill_params, decode_model,
                 decode_params, cfg: EngineConfig, *, decode_cfg=None,
                 hybrid_mesh=None, dcn_axis: str = "dcn",
                 transport: str = "auto", ship_delay_steps: int = 0,
                 placement: str = "force", traffic: dict | None = None,
                 moe_state="auto", use_pallas: bool = True, health=None,
                 spec_k: int = 0, drafter=None,
                 adaptive_k: bool = False,
                 propagate_failures: bool = False):
        from dataclasses import replace as _rep

        from triton_distributed_tpu.runtime.health import HealthLedger

        if transport not in ("auto", "dcn", "xla"):
            raise ValueError(f"unknown transport {transport!r}")
        for m in (prefill_model, decode_model):
            refuse(state_kinds(m.config), "disaggregated")
        if transport == "auto":
            transport = "dcn" if hybrid_mesh is not None else "xla"
        if transport == "dcn" and hybrid_mesh is None:
            raise ValueError("transport='dcn' needs a hybrid_mesh")
        self.health = health if health is not None else HealthLedger(
            seed=cfg.seed)
        if decode_cfg is None:
            # the decode role's batches are at most one token per slot
            # (8 packed slots each — the row alignment): size its
            # packed width to 8·slots instead of the prefill budget,
            # never wider than it. Part of the point of the split: the
            # decode slice's steps stop paying prefill-sized
            # buffers/blocks (the colocated engine's steps follow their
            # batch, ``ServingEngine._widths`` — its budget must still
            # carry prefill chunks). Evicted requests
            # re-prefilling decode-side chunk at this narrower width.
            dbudget = max(8, min(8 * cfg.slots, cfg.token_budget))
            decode_cfg = _rep(
                cfg, token_budget=dbudget, chunk=min(cfg.chunk, dbudget),
            )
        dcfg = decode_cfg
        if dcfg.page != cfg.page:
            raise ValueError(
                f"page size must match across roles ({cfg.page} vs "
                f"{dcfg.page}) — pages ship verbatim"
            )
        if placement == "auto":
            from triton_distributed_tpu.tune import perf_model

            traffic = dict(traffic or {})
            if spec_k:
                # speculation changes the ship cadence: the decode
                # window the wire must hide under SHRINKS by the
                # accepted-tokens-per-step factor — the perf model
                # prices that (tune/perf_model.spec_step_ms)
                traffic.setdefault("spec_k", spec_k)
            reason = perf_model.refuse_disaggregation(
                decode_model.config, cfg.page, traffic or {},
                ledger=self.health,
            )
            if reason is not None:
                raise ValueError(
                    f"auto placement refuses disaggregation: {reason}"
                )
        self.transport = transport
        self._transport_pref = transport   # what we re-promote back to
        # as on ServingEngine: record AND re-raise instead of degrading
        self.propagate_failures = propagate_failures
        self.hybrid_mesh = hybrid_mesh
        self.dcn_axis = dcn_axis
        self.ship_delay_steps = int(ship_delay_steps)
        self.prefill = ServingEngine(
            prefill_model, prefill_params,
            _rep(cfg, prefill_only=True),
            moe_state=moe_state, use_pallas=use_pallas,
            on_complete=self._on_prefill_complete, health=self.health,
            propagate_failures=propagate_failures,
        )
        self.spec_k = int(spec_k)
        if spec_k:
            # speculation lives on the DECODE role only: the prefill
            # role emits at most one token per request (its frontier
            # draw), so there is nothing to draft there. Local import —
            # spec.py subclasses ServingEngine from this module.
            from triton_distributed_tpu.serving.spec import (
                SpeculativeEngine,
            )

            self.decode = SpeculativeEngine(
                decode_model, decode_params,
                _rep(dcfg, prefill_only=False),
                spec_k=spec_k, drafter=drafter, adaptive_k=adaptive_k,
                moe_state=moe_state, use_pallas=use_pallas,
                health=self.health,
                propagate_failures=propagate_failures,
            )
        else:
            self.decode = ServingEngine(
                decode_model, decode_params,
                _rep(dcfg, prefill_only=False),
                moe_state=moe_state, use_pallas=use_pallas,
                health=self.health,
                propagate_failures=propagate_failures,
            )
        self._ready: deque = deque()       # (req, prefill slot) awaiting ship
        self._inflight: list = []
        self._dead_role: str | None = None  # set by slice-death failover
        self._requeued: list = []           # failover's re-queued requests
        self.ticks = 0
        self.stats = DisaggStats(
            prefill=self.prefill.stats, decode=self.decode.stats
        )
        self._build_jits()

    # ------------------------------------------------------------ plumbing

    def _build_jits(self):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from triton_distributed_tpu.kernels.kv_ship import (
            gather_kv_pages,
            scatter_kv_pages,
        )

        self._gather_jit = jax.jit(gather_kv_pages)
        self._scatter_jit = jax.jit(
            scatter_kv_pages, donate_argnums=(0,)
        )
        mesh_d = self.decode.model.mesh
        tp = self.decode.model.tp_axis
        # payload (L·2, P, Hkv, page[, D]): KV heads stay sharded over
        # the decode slice's tp axis, like the pools they land in
        self._q_sharding = NamedSharding(mesh_d, P(None, None, tp))
        self._s_sharding = NamedSharding(mesh_d, P(None, None, tp))

    def _on_prefill_complete(self, req, slot) -> bool:
        """Prefill-role completion hook: requests already done (max_new
        reached during prefill) finish here; everyone else parks —
        pages pinned — until their KV has shipped."""
        if len(req.generated) >= req.max_new:
            req.done = True
            # account the finished request on the decode ledger (the
            # system's completion ledger), not the prefill engine's
            self.decode.stats.completed += 1
            self.decode.stats.generated_tokens += len(req.generated)
            return True                    # free the prefill slot now
        req.parked = True
        self._ready.append((req, slot))
        return False                       # hold pages for the ship

    # ------------------------------------------------------------ shipping

    def _launch_ships(self) -> None:
        import time as _t

        import jax.numpy as jnp

        # drain the whole ready cohort FIRST (reservations are cheap
        # bookkeeping), then ship it as ONE gather + ONE transport
        # flight: per-tick launch cost stops scaling with the number of
        # simultaneously finishing prefills, and the DCN rail flies one
        # big pair instead of a convoy of small ones
        cohort = []
        while self._ready:
            req, pslot = self._ready[0]
            res = self.decode.reserve_shipped(req)
            if res is None:
                break                      # decode backpressure; retry
            self._ready.popleft()
            dslot, dpids = res
            npg = self.prefill._pages_held(req.cursor)
            cohort.append((req, pslot, dslot, dpids, npg))
        if not cohort:
            return
        t0 = _t.perf_counter()
        pids = jnp.asarray(np.concatenate([
            self.prefill.table[pslot, :npg].astype(np.int32)
            for _, pslot, _, _, npg in cohort
        ]))
        qpay, spay = self._gather_jit(self.prefill.state.layers, pids)
        payload = self._run_transport(qpay, spay)
        dt = _t.perf_counter() - t0
        q_elems = int(np.prod(qpay.shape))
        wire = q_elems * qpay.dtype.itemsize + (
            int(np.prod(spay.shape)) * 4 if spay is not None else 0
        )
        raw = q_elems * max(2, qpay.dtype.itemsize)
        # one ShipRecord per request (the scheduling unit: pins, slots
        # and commit hooks stay per-request); bytes and launch time are
        # attributed by page share, so stats.ships keeps meaning "one
        # request's KV shipped"
        total_pg = sum(npg for *_, npg in cohort)
        for req, pslot, dslot, dpids, npg in cohort:
            frac = npg / total_pg
            self._inflight.append(ShipRecord(
                req=req, pslot=pslot, dslot=dslot, dpids=dpids,
                payload=payload, issued_tick=self.ticks,
                wire_bytes=int(round(wire * frac)),
                raw_bytes=int(round(raw * frac)),
                launch_ms=dt * 1e3 * frac,
            ))

    def _run_transport(self, qpay, spay):
        from triton_distributed_tpu.runtime.health import PeerState

        peer = "site:kv_ship"
        if (self.transport == "dcn"
                and self.health.state(peer) is PeerState.UNHEALTHY):
            # condemned out-of-band (watchdog trip on a prior ship)
            self.transport = "xla"
            self.stats.degraded_transport = True
        probing = (self._transport_pref == "dcn"
                   and self.transport == "xla"
                   and self.health.probe_due(peer, self.ticks))
        if self.transport == "dcn" or probing:
            out = self._dcn_with_retries(qpay, spay)
            if out is not None:
                if probing:
                    st = self.health.probe_result(peer, True,
                                                  step=self.ticks)
                    if st is PeerState.HEALTHY:
                        self.transport = "dcn"
                        self.stats.degraded_transport = False
                        self.stats.transport_repromotions += 1
                elif self.health.state(peer) is PeerState.UNHEALTHY:
                    # the ship completed but only because a watchdog
                    # trip released its stall gate: demote for the next
                    self.transport = "xla"
                    self.stats.degraded_transport = True
                return out
            # retries exhausted: the failure is a ledger signal, then
            # degrade onto the XLA transfer (scheduling state untouched)
            if probing:
                self.health.probe_result(peer, False, step=self.ticks)
            else:
                self.health.record("transport_error", peer,
                                   step=self.ticks)
            self.transport = "xla"
            self.stats.degraded_transport = True
        out = self._transport_xla(qpay, spay)
        if self._transport_pref == "dcn" and self.transport == "xla" \
                and self.stats.degraded_transport:
            # a clean degraded ship: SUSPECT clears straight back,
            # UNHEALTHY earns PROBATION (probes re-promote above)
            st = self.health.observe_clean(peer, step=self.ticks)
            if st is PeerState.HEALTHY:
                self.transport = "dcn"
                self.stats.degraded_transport = False
                self.stats.transport_repromotions += 1
        return out

    def _dcn_with_retries(self, qpay, spay):
        """The DCN wire with capped jittered backoff (the
        ``TDTPU_BOOTSTRAP_*`` pattern at ship scope): up to
        ``TDTPU_SHIP_RETRIES`` attempts (default 3), backing off
        ``TDTPU_SHIP_BACKOFF * 2**attempt`` seconds (default 0.2,
        clamped to ``TDTPU_SHIP_BACKOFF_CAP``, ledger-seeded ±50%
        jitter). Returns the landed payload or None when exhausted —
        the caller degrades. Each attempt runs under the kv_ship
        heartbeat so an armed watchdog can trip on a stalled ship."""
        import os as _os

        from triton_distributed_tpu.lang.launch import maybe_instrument

        retries = max(1, int(_os.environ.get("TDTPU_SHIP_RETRIES", "3")))
        backoff = float(_os.environ.get("TDTPU_SHIP_BACKOFF", "0.2"))
        cap = float(_os.environ.get("TDTPU_SHIP_BACKOFF_CAP", "2.0"))
        send = maybe_instrument(
            self._transport_dcn, axis=None, site="kv_ship",
            collective_id=("kv_ship", self.ticks), n=1, step=self.ticks,
        )
        for attempt in range(retries):
            try:
                return send(qpay, spay)
            except Exception as e:
                self.stats.transport_failures.append({
                    "tick": self.ticks, "site": "kv_ship",
                    "error": f"{type(e).__name__}: {e}",
                })
                if self.propagate_failures:
                    raise
                if attempt == retries - 1:
                    return None
                self.stats.ship_retries += 1
                delay = min(cap, backoff * (2.0 ** attempt))
                delay *= 0.5 + self.health.uniform(
                    "ship_backoff", self.ticks, attempt)
                time.sleep(delay)

    def _transport_xla(self, qpay, spay):
        """The degradation target: a plain device_put of the (already
        wire-shaped) payload onto the decode mesh."""
        from triton_distributed_tpu.tools.native import xla_kv_ship

        return xla_kv_ship(
            (qpay, spay),
            (self._q_sharding, None if spay is None else self._s_sharding),
        )

    def _transport_dcn(self, qpay, spay):
        """The quantized DCN wire: stage the payload+scale pair on the
        hybrid mesh's source role and fly both rails over the DCN axis
        with paired ``ppermute``s. (Single-process staging round-trips
        the host; on a real multislice deployment the role engines
        address one global mesh and the rails ARE the inter-slice
        bytes.)"""
        from triton_distributed_tpu.runtime.multislice import (
            kv_ship_rail,
        )
        from triton_distributed_tpu.tools.native import xla_kv_ship

        rail = kv_ship_rail(
            self.hybrid_mesh, self.dcn_axis, spay is not None
        )
        qh = np.asarray(qpay)
        stk_q = np.stack([qh, np.zeros_like(qh)])
        if spay is not None:
            sh = np.asarray(spay)
            out_q, out_s = rail(stk_q, np.stack([sh, np.zeros_like(sh)]))
            arr_q, arr_s = np.asarray(out_q)[1], np.asarray(out_s)[1]
        else:
            (out_q,) = rail(stk_q)
            arr_q, arr_s = np.asarray(out_q)[1], None
        return xla_kv_ship(
            (arr_q, arr_s),
            (self._q_sharding, None if arr_s is None else self._s_sharding),
        )

    def _commit_ships(self, force: bool = False,
                      release_source: bool = True) -> list:
        """Land ready transfers. ``force`` ignores the in-flight delay
        window and ``release_source=False`` skips freeing the prefill
        pages — the prefill-slice-death path: the payloads already left
        the dead slice, so they commit, but the source pool died with
        its slice. Returns the committed records."""
        import time as _t

        import jax
        import jax.numpy as jnp

        ready = [
            r for r in self._inflight
            if force or self.ticks - r.issued_tick >= self.ship_delay_steps
        ]
        # a launch batch shares one transported payload (same tuple
        # object on every record) and its records share issued_tick, so
        # each group lands with ONE scatter over the concatenated
        # landing pages — the commit-side mirror of the batched gather
        groups: dict = {}
        for r in ready:
            groups.setdefault(id(r.payload), []).append(r)
        for rs in groups.values():
            t0 = _t.perf_counter()
            qd, sd = rs[0].payload
            dpids = jnp.asarray(np.concatenate([
                np.asarray(r.dpids, np.int32) for r in rs
            ]))
            new_layers = self._scatter_jit(
                self.decode.state.layers, dpids, qd, sd,
            )
            jax.block_until_ready(new_layers)          # the landing fence
            self.decode.state = self.decode.state.replace(
                layers=new_layers
            )
            dt = (_t.perf_counter() - t0) * 1e3 / len(rs)
            for r in rs:
                # handoff order matters: the source frees its pinned
                # pages first, THEN the row becomes schedulable
                # (ProtocolOps.ship_commit — the transactional verb
                # servlint model-checks)
                if release_source:
                    self.decode.ops.ship_commit(
                        self.prefill, r.pslot, self.decode, r.req)
                else:
                    self.decode.commit_shipped(r.req)
                self._warm_prefix_cache(r)
                self._inflight.remove(r)
                self.stats.ships += 1
                self.stats.shipped_wire_bytes += r.wire_bytes
                self.stats.shipped_raw_bytes += r.raw_bytes
                self.stats.ship_ms.append(r.launch_ms + dt)
        return ready

    def _warm_prefix_cache(self, r: ShipRecord) -> None:
        """Decode-slice prefix-cache warm-up: the shipped pages' content
        is frozen (nothing on the decode side writes below the shipped
        cursor), so each FULL landed page registers its prefix-chain
        hash in the decode pool the moment it lands. A later request
        sharing the prefix then attaches on the decode slice without
        re-shipping — the pages are already home. Partial trailing
        pages stay private (their content is still growing)."""
        if not self.decode.pool.prefix_cache:
            return
        full = r.req.cursor // self.decode.cfg.page
        full = min(full, len(r.dpids))
        if full <= 0:
            return
        hashes = self.decode._page_hashes(r.req, full)
        for p in range(full):
            self.decode.pool.register(int(r.dpids[p]), hashes[p])

    # ------------------------------------------------------------- driving

    @property
    def idle(self) -> bool:
        return (self.prefill.idle and self.decode.idle
                and not self._ready and not self._inflight)

    def submit_trace(self, trace) -> None:
        self.prefill.submit_trace(trace)

    def tick(self) -> dict:
        """One system tick: a prefill step, ship launches/commits, a
        decode step. On hardware the two roles run concurrently on
        their own slices with the transfer in flight between them;
        the single-process harness serializes them but keeps the same
        ordering semantics (decode never observes a page before its
        commit fence). A fault-plan :class:`SliceDeath` whose step has
        arrived fails the dead role over onto the survivor first."""
        self._check_slice_deaths()
        # the hook's parking, the ships' reservations and commits and a
        # failover move both roles' slots between their steps: neither
        # keeps a step in flight past its own (``ServingEngine.drain``)
        rep_p = (None if self._dead_role == "prefill" or self.prefill.idle
                 else self.prefill.step())
        self.prefill.drain()
        if self._dead_role is None:
            self._launch_ships()
            self._commit_ships()
        rep_d = (None if self._dead_role == "decode" or self.decode.idle
                 else self.decode.step())
        self.decode.drain()
        self.ticks += 1
        if (self.stats.failover_role is not None
                and self.stats.recovery_tick is None
                and all(r.done for r in self._requeued)):
            self.stats.recovery_tick = self.ticks
        return {
            "tick": self.ticks, "prefill": rep_p, "decode": rep_d,
            "inflight": len(self._inflight), "ready": len(self._ready),
        }

    # ------------------------------------------------- slice-death failover

    def _check_slice_deaths(self) -> None:
        """Consume the active plan's :class:`SliceDeath` faults: hybrid
        DCN index 0 is the prefill role, 1 the decode role (the
        ``create_hybrid_mesh`` layout bench builds)."""
        from triton_distributed_tpu.runtime import faults as _faults

        if self._dead_role is not None:
            return
        plan = _faults.active_plan()
        if plan is None:
            return
        dead = plan.dead_slices(self.ticks)
        if not dead:
            return
        roles = {0: "prefill", 1: "decode"}
        dead_roles = sorted({roles[s] for s in dead if s in roles})
        if len(dead_roles) > 1:
            raise RuntimeError(
                f"fault plan killed both serving slices by tick "
                f"{self.ticks} ({dead}) — no survivor to fail over to")
        for s in sorted(dead):
            if s not in roles:
                continue
            role = roles[s]
            self.health.record(
                "slice_death", f"slice:{s}", step=self.ticks,
                detail=f"{role} slice died at tick {self.ticks}")
            self._fail_over(role)
            return

    def _fail_over(self, dead_role: str) -> None:
        """Re-queue everything the dead slice held onto the survivor.
        Zero requests are lost and output stays token-exact: sampling is
        keyed on (seed, rid, generated-so-far), so an exact-cursor
        re-prefill (the eviction recompute discipline — prompt plus
        everything generated) resumes each stream byte-identically."""
        from dataclasses import replace as _rep

        self.stats.failover_role = dead_role
        self.stats.failover_tick = self.ticks
        requeued: list = []
        re_tokens = 0

        def requeue(req, surv):
            nonlocal re_tokens
            if req.done:
                return
            re_tokens += req.cursor
            if req.cursor > 0:
                req.evictions += 1
            req.cursor = 0
            req.slot = None
            req.parked = False
            surv.waiting.append(req)
            requeued.append(req)

        if dead_role == "decode":
            dead, surv = self.decode, self.prefill
            # the survivor becomes a FULL engine: prefill_only off,
            # completions credited to the system (decode) ledger
            surv.cfg = _rep(surv.cfg, prefill_only=False)
            surv.on_complete = self._on_failover_complete
            # requests awaiting/in a ship: their prefilled KV is intact
            # in the SURVIVOR's pool — un-park and decode in place
            kept = set()
            for req, pslot in self._ready:
                req.parked = False
                req.slot = pslot
                kept.add(id(req))
            for r in self._inflight:
                r.req.parked = False
                r.req.slot = r.pslot    # reserve_shipped repointed it
                kept.add(id(r.req))
            self._ready.clear()
            self._inflight.clear()
            # dead-pool residents lost their KV: exact-cursor re-prefill
            for req in dead.slot_req:
                if req is not None and id(req) not in kept:
                    requeue(req, surv)
        else:
            dead, surv = self.prefill, self.decode
            # payloads already transported left the dead slice — land
            # them now (their source pool is gone: no release)
            committed = self._commit_ships(force=True,
                                           release_source=False)
            handled = {id(r.req) for r in committed}
            # never-transported KV is lost: re-prefill from scratch
            for req, pslot in self._ready:
                requeue(req, surv)
                handled.add(id(req))
            self._ready.clear()
            for req in dead.slot_req:
                if req is not None and not req.done \
                        and id(req) not in handled:
                    requeue(req, surv)
        # drain the dead role's queues onto the survivor
        while dead.waiting:
            req = dead.waiting.popleft()
            surv.waiting.append(req)
            requeued.append(req)
        while dead.pending:
            surv.pending.append(dead.pending.popleft())
        # neutralize the dead engine (its device state is gone with the
        # slice; the host mirrors must read as empty so `idle` holds)
        dead.slot_req = [None] * dead.cfg.slots
        dead.table[:] = -1
        self.stats.failover_requeued = len(requeued)
        self.stats.failover_re_prefill_tokens = re_tokens
        self._requeued = requeued
        self._dead_role = dead_role

    def _on_failover_complete(self, req, slot) -> bool:
        """Post-failover completion hook on the surviving prefill-role
        engine: credit the system (decode) ledger, free the slot."""
        self.decode.stats.completed += 1
        self.decode.stats.generated_tokens += len(req.generated)
        return True

    def run(self, trace=None, max_ticks: int | None = None) -> DisaggStats:
        if trace is not None:
            self.submit_trace(trace)
        max_ticks = max_ticks or self.prefill.cfg.max_steps
        for _ in range(max_ticks):
            if self.idle:
                break
            self.tick()
        return self.stats
