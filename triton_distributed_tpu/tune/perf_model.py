"""Analytical performance models for TPU generations.

Reference: python/triton_dist/kernels/nvidia/comm_perf_model.py (NIC /
NVLink / PCIe bandwidth discovery, ``estimate_reduce_scatter_time``
:91) and gemm_perf_model.py (tensor-core TFLOPS tables by device name,
``estimate_gemm_sol_time_ms`` :233) — used to pick SM budgets and
sanity-check measured numbers.

TPU re-design: per-generation datasheet tables (MXU TFLOPS, HBM GB/s,
ICI GB/s per link and links per chip) + speed-of-light estimators for
the collectives this framework ships (ring AG/RS, dense A2A, LL small
messages). The same two consumers: engine auto-selection thresholds and
"is this measurement sane" checks in benches.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class TpuSpec:
    name: str
    bf16_tflops: float       # peak MXU, per chip
    hbm_gbps: float          # HBM bandwidth, per chip
    ici_gbps: float          # ICI bandwidth per link, per direction
    ici_links: int           # torus links per chip
    int8_tops: float = 0.0   # peak s8×s8→s32 MXU rate (0 = no speedup)
    # per-chip share of the inter-slice DCN fabric. Order-of-magnitude
    # deployment numbers (multi-NIC hosts divided by chips per host) —
    # DCN is 4-16× slower than one ICI link, which is exactly why the
    # KV-ship placement model must be able to REFUSE disaggregation.
    dcn_gbps: float = 6.25

    @property
    def s8_tops(self) -> float:
        """Effective int8 MXU rate: the native path where the datasheet
        lists one, else the bf16 rate (int8 then buys bytes, not
        FLOPs)."""
        return self.int8_tops or self.bf16_tflops


# Public datasheet numbers (cloud.google.com/tpu/docs/system-architecture).
# int8 TOPS: the native s8×s8→s32 path — ~2× the bf16 rate on v5e/v5p/
# v6e (the W8A8 grouped GEMM measured 320–350 TOP/s on a v5e against the
# 394 peak, kernels/group_gemm.py); v4 has no separate int8 path.
TPU_SPECS = {
    "v4": TpuSpec("v4", 275.0, 1228.0, 50.0, 6, dcn_gbps=6.25),
    "v5e": TpuSpec("v5e", 197.0, 819.0, 50.0, 4, int8_tops=394.0,
                   dcn_gbps=12.5),
    "v5p": TpuSpec("v5p", 459.0, 2765.0, 100.0, 6, int8_tops=918.0,
                   dcn_gbps=25.0),
    "v6e": TpuSpec("v6e", 918.0, 1640.0, 100.0, 4, int8_tops=1836.0,
                   dcn_gbps=25.0),
}
#: The chip the CPU dev/test mesh stands in for: kernels there run under
#: the interpreter and AOT-compile against an unattached v5e topology,
#: so the model prices v5e. This names a simulation target — it is NOT
#: a fallback for accelerators missing from the table.
CPU_MESH_TARGET = "v5e"


def detect_spec(device=None) -> TpuSpec:
    """Map jax's device_kind onto a spec row (≡ get_device_name-keyed
    tables, gemm_perf_model.py). An accelerator with no row raises: a
    silently borrowed row would price every schedule, wire and
    placement decision for the wrong chip."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return TPU_SPECS[CPU_MESH_TARGET]
    kind = device.device_kind.lower()
    for key, spec in TPU_SPECS.items():
        if key in kind.replace(" ", "").replace("lite", "e"):
            return spec
    if "v5" in kind:
        return TPU_SPECS["v5e" if "lite" in kind else "v5p"]
    raise ValueError(
        f"perf_model: no TpuSpec row for device_kind "
        f"{device.device_kind!r} (known: {sorted(TPU_SPECS)}) — add its "
        "datasheet peaks to TPU_SPECS"
    )


def estimate_gemm_ms(m: int, k: int, n: int, spec: TpuSpec | None = None,
                     efficiency: float = 0.75) -> float:
    """Speed-of-light matmul time (≡ estimate_gemm_sol_time_ms,
    gemm_perf_model.py:233): max of MXU flops time and HBM traffic time."""
    spec = spec or detect_spec()
    flops_ms = (2 * m * k * n) / (spec.bf16_tflops * 1e12 * efficiency) * 1e3
    bytes_moved = 2 * (m * k + k * n + m * n)
    mem_ms = bytes_moved / (spec.hbm_gbps * 1e9) * 1e3
    return max(flops_ms, mem_ms)


def estimate_s8_gemm_ms(m: int, k: int, n: int, spec: TpuSpec | None = None,
                        efficiency: float = 0.75) -> float:
    """Speed-of-light s8×s8→s32 matmul time: the int8-MXU twin of
    :func:`estimate_gemm_ms` — 1-byte operands halve the HBM traffic
    and the native int8 path runs at ``spec.s8_tops``."""
    spec = spec or detect_spec()
    flops_ms = (2 * m * k * n) / (spec.s8_tops * 1e12 * efficiency) * 1e3
    bytes_moved = (m * k + k * n) + 2 * m * n   # s8 in, bf16-ish out
    mem_ms = bytes_moved / (spec.hbm_gbps * 1e9) * 1e3
    return max(flops_ms, mem_ms)


def dequant_pass_ms(rows: int, cols: int, out_itemsize: int = 2,
                    spec: TpuSpec | None = None) -> float:
    """Cost of one per-arrival dequant pass over a wire slab: read the
    1-byte payload (+ scale plane, negligible), write the widened copy —
    pure HBM traffic, the VPU multiply is free under it. This is the
    SKIPPED-PASS term of the int8→MXU model: the epilogue-folded
    consumer never runs this pass (and never re-reads the widened copy
    either, which :func:`estimate_gemm_ms`'s A-term would charge)."""
    spec = spec or detect_spec()
    return rows * cols * (1 + out_itemsize) / (spec.hbm_gbps * 1e9) * 1e3


def int8_mxu_step_ratio(slab_rows: int, k: int, n_cols: int,
                        spec: TpuSpec | None = None) -> float:
    """Projected per-ring-step speedup of the dequant-free int8→MXU
    consumer over dequant-then-matmul on the same int8 wire:
    (dequant pass + bf16 shard matmul) / s8×s8 shard matmul. > 1 means
    the perf model projects the epilogue path as a win."""
    spec = spec or detect_spec()
    legacy = dequant_pass_ms(slab_rows, k, 2, spec) + estimate_gemm_ms(
        slab_rows, k, n_cols, spec
    )
    return legacy / estimate_s8_gemm_ms(slab_rows, k, n_cols, spec)


def estimate_all_gather_ms(shard_bytes: int, n: int,
                           spec: TpuSpec | None = None) -> float:
    """Bidirectional-ring AG over ICI: each chip receives (n-1) shards
    across 2 directions (≡ estimate_allgather in comm_perf_model)."""
    spec = spec or detect_spec()
    wire = shard_bytes * (n - 1) / 2
    return wire / (spec.ici_gbps * 1e9) * 1e3


def estimate_reduce_scatter_ms(shard_bytes: int, n: int,
                               spec: TpuSpec | None = None) -> float:
    """Ring RS moves the same wire bytes as ring AG
    (≡ estimate_reduce_scatter_time, comm_perf_model.py:91)."""
    return estimate_all_gather_ms(shard_bytes, n, spec)


def estimate_all_to_all_ms(local_bytes: int, n: int,
                           spec: TpuSpec | None = None) -> float:
    """Dense A2A: (n-1)/n of the local buffer crosses the bisection;
    on a torus every chip drives ici_links links concurrently."""
    spec = spec or detect_spec()
    wire = local_bytes * (n - 1) / n
    return wire / (spec.ici_gbps * spec.ici_links * 1e9) * 1e3


def overlap_efficiency(compute_ms: float, comm_ms: float) -> float:
    """Fraction of comm hidden if perfectly pipelined under compute —
    the 'overlap %' north-star metric (BASELINE.json)."""
    if comm_ms <= 0:
        return 1.0
    return min(compute_ms, comm_ms) / comm_ms


# ------------------------------------------------------- wire-bytes term
#
# The streaming rings can ship fp8/int8 payloads with per-chunk f32
# scales (lang.wire): the model needs the true wire byte count (payload
# + scale planes) and a comm-bound test so the op entries can pick the
# wire dtype analytically when no measured winner exists.

def ring_wire_bytes(rows: int, cols: int, itemsize: int,
                    wire: str | None = None, chunk_rows: int = 64) -> int:
    """Bytes ONE ring slab puts on the wire: the raw (rows, cols)
    payload at ``itemsize``, or the compressed lang.wire layout — 1-byte
    elements plus one (128·4 B) scale row per ``chunk_rows`` rows."""
    if wire in (None, "bf16"):
        return rows * cols * itemsize
    chunks = -(-rows // max(1, chunk_rows))
    return rows * cols + chunks * 128 * 4


def ring_wire_ms(slab_bytes: int, spec: TpuSpec | None = None) -> float:
    """One unidirectional ring-step transfer over a single ICI link."""
    spec = spec or detect_spec()
    return slab_bytes / (spec.ici_gbps * 1e9) * 1e3


def auto_wire_dtype(slab_rows: int, k: int, n_cols: int, itemsize: int,
                    *, slab_bytes: int | None = None,
                    spec: TpuSpec | None = None,
                    consumer_wq: str | None = None) -> str:
    """'fp8' when the ring is comm-bound at these per-step shapes —
    i.e. the bf16 slab transfer (``slab_bytes``, default the A slab
    rows×k) outlasts the per-step shard matmul the ring hides it under
    — else 'bf16'. Compressing a compute-bound ring buys nothing
    (overlap is already 100%) and costs accuracy, so the selector only
    reaches for the 1-byte wire where it widens the overlap range.

    ``consumer_wq='int8'``: the consumer has declared int8 weight
    numerics, so on comm-bound shapes the selector picks the
    DEQUANT-FREE 'int8-mxu' wire instead of fp8 — same wire bytes, but
    the per-arrival dequant pass disappears and the shard matmul runs
    at the s8×s8 MXU rate (both terms the step-ratio model above
    projects as a win exactly where the wire engages)."""
    spec = spec or detect_spec()
    compute_ms = estimate_gemm_ms(slab_rows, k, n_cols, spec)
    if slab_bytes is None:
        slab_bytes = slab_rows * k * itemsize
    if ring_wire_ms(slab_bytes, spec) <= compute_ms:
        return "bf16"
    return "int8-mxu" if consumer_wq == "int8" else "fp8"


# ------------------------------------------------- ragged serving term
#
# The continuous-batching engine's step cost is dominated by the ragged
# paged-attention page walk (per-row TRUE lengths — the whole point of
# the ragged kernel) plus the packed batch's weight-HBM-bound
# projection reads. The bench (serving_continuous) reports this model
# term next to the measurement so regressions are explainable as
# %-of-speed-of-light, like every other bench row.

#: fixed per-page DMA-issue/loop overhead of the dynamic page walk,
#: MEASURED per backend (the ROADMAP "fold the measured per-page issue
#: cost" follow-on). Keys are coarse backend kinds:
#:
#: * ``"tpu"`` — the round-5 v5e serving-attention measurement
#:   (~0.17 µs/block at 1024-row blocks); refresh on the next
#:   multi-chip run from the serving_disaggregated bench's
#:   ``measured_page_issue_ms`` field.
#: * ``"cpu-interp"`` — the dev-box measurement backing the bench's
#:   model row off-TPU: derived from ``bench.py --dryrun``'s
#:   serving_disaggregated decode-role p50 (the pure-decode steps —
#:   the cleanest per-page signal: ~6 ms over ~6 rows × ~8 walked
#:   pages on the XLA-twin path; the bench re-derives and reports it
#:   as ``measured_page_issue_ms`` every run). Coarse by nature — the
#:   interpreter's cost is partly per-dispatch, not per-page — but 3
#:   orders closer to what the dev box pays than the TPU constant.
RAGGED_PAGE_ISSUE_MS_MEASURED = {
    "tpu": 0.17e-3,
    "cpu-interp": 0.13,
}

RAGGED_PAGE_ISSUE_MS = RAGGED_PAGE_ISSUE_MS_MEASURED["tpu"]


def measured_page_issue_ms(backend: str | None = None) -> float:
    """The measured per-page issue cost for ``backend`` (default: the
    current jax backend — 'tpu' on hardware, the dev-box row
    otherwise)."""
    if backend is None:
        backend = "tpu" if jax.default_backend() == "tpu" else "cpu-interp"
    return RAGGED_PAGE_ISSUE_MS_MEASURED.get(
        backend, RAGGED_PAGE_ISSUE_MS
    )


def ragged_page_walk_ms(kv_lens, page: int, hkv: int, d: int,
                        spec: TpuSpec | None = None,
                        quant: bool = True,
                        issue_ms: float | None = None) -> float:
    """HBM time of one ragged step's KV walk: every row reads
    ``ceil(kv_len/page)`` pages of K AND V (+ the f32 scale planes
    under int8), plus the fixed per-page issue cost — proportional to
    the step's TRUE KV volume, never the slot capacity (the quantity a
    rectangle batch cannot avoid paying). ``issue_ms`` overrides the
    per-page issue constant (pass
    :func:`measured_page_issue_ms` to use the backend's measured row —
    the bench does, so its model term tracks the machine it ran on)."""
    spec = spec or detect_spec()
    if issue_ms is None:
        issue_ms = RAGGED_PAGE_ISSUE_MS
    pages = sum(max(-(-int(l) // page), 1) for l in kv_lens if int(l) > 0)
    per_page = 2 * hkv * page * d * (1 if quant else 2)
    if quant:
        per_page += 2 * hkv * page * 4
    return (pages * per_page / (spec.hbm_gbps * 1e9) * 1e3
            + pages * issue_ms)


def ragged_serving_step_ms(kv_lens, q_lens, *, page: int, hkv: int,
                           g: int, d: int, hidden: int,
                           weight_bytes_per_token_layer: float = 0.0,
                           n_layers: int = 1,
                           spec: TpuSpec | None = None,
                           quant: bool = True,
                           issue_ms: float | None = None) -> float:
    """Analytic one-step model for the continuous engine: the per-layer
    ragged attention walk plus the packed batch's projection/expert
    weight reads (``weight_bytes_per_token_layer`` — serving GEMMs are
    weight-HBM-bound at batch-scale M, so the weight fetch, not the
    FLOPs, is the projection term) and the q/out token traffic."""
    spec = spec or detect_spec()
    t = sum(int(x) for x in q_lens)
    attn = ragged_page_walk_ms(kv_lens, page, hkv, d, spec, quant,
                               issue_ms)
    tok_bytes = 3 * t * hkv * g * d * 2          # q in, out, lse-ish
    w_ms = (weight_bytes_per_token_layer
            / (spec.hbm_gbps * 1e9) * 1e3)
    return n_layers * (
        attn + tok_bytes / (spec.hbm_gbps * 1e9) * 1e3 + w_ms
    )


# ---------------------------------------------------- speculation term
#
# Speculative decoding (serving/spec.py) changes WHAT a decode step is:
# a verify row packs 1 + k tokens and emits 1..k+1 of them, so the
# per-step cost rises a little (wider q traffic, k extra provisional KV
# appends) while the per-TOKEN cost falls by the accepted-tokens-per-
# step factor. Both the fleet router's load term and the
# disaggregation placement gate consume these: speculation SHRINKS the
# decode window a KV ship must hide under, so a split that was priced
# viable at 1 token/step can stop being viable at 2.

#: analytic prior for the per-draft acceptance probability before any
#: verify row has run — deliberately conservative (the n-gram drafter
#: measured ~0.5 on motif-heavy greedy traffic, near zero on
#: incompressible random tokens; the prior sits where under-promising
#: only makes the router/placement err toward the plain engine).
DEFAULT_SPEC_ACCEPTANCE = 0.3


def expected_accepted_per_step(spec_k: int, acceptance_rate: float) -> float:
    """Expected tokens EMITTED by one draft-k verify row under an
    i.i.d. per-draft acceptance probability ``p``:
    ``1 + p + p² + … + p^k`` (truncated geometric — every emitted token
    is an accepted draft or the final correction/bonus draw). Bounded
    in ``[1, k+1]``; the analytic prior where no measured
    ``EngineStats.accepted_tokens_per_step`` exists yet."""
    p = min(max(float(acceptance_rate), 0.0), 1.0)
    if p >= 1.0:
        return float(spec_k + 1)
    return (1.0 - p ** (spec_k + 1)) / (1.0 - p)


def expected_accepted_per_step_tree(spec_tree: int,
                                    acceptance_rate: float,
                                    branches: int = 2) -> float:
    """Expected tokens emitted by one TREE verify row of ``spec_tree``
    nodes hedged ``branches`` ways per level. Where the linear row must
    match ONE proposed token per level, a tree level escapes with any
    of its ``b`` siblings: ``q = 1 - (1-p)^b`` per level, and the node
    budget buys ``spec_tree // b`` levels —
    ``1 + q + q² + … + q^levels``. ``branches=1`` degenerates to
    :func:`expected_accepted_per_step` exactly; wider hedging trades
    depth for per-level escape probability, which wins when the
    traffic's continuations are genuinely ambiguous (branchy motifs)
    and loses on incompressible or single-path streams — the term the
    tune layer prices ``GridSchedule.tree_pack`` against."""
    p = min(max(float(acceptance_rate), 0.0), 1.0)
    b = max(int(branches), 1)
    levels = max(int(spec_tree) // b, 0)
    q = 1.0 - (1.0 - p) ** b
    if q >= 1.0:
        return float(levels + 1)
    return (1.0 - q ** (levels + 1)) / (1.0 - q)


def spec_step_ms(kv_lens, *, spec_k: int, page: int, hkv: int, g: int,
                 d: int, hidden: int, n_layers: int = 1,
                 spec_tree: int = 0,
                 spec: TpuSpec | None = None, quant: bool = True,
                 issue_ms: float | None = None) -> float:
    """Analytic cost of one speculative VERIFY step: the plain ragged
    step with every decode row widened to ``q_len = 1 + spec_k`` (the
    frontier token plus k provisional drafts; ``spec_tree > 0`` widens
    to the tree pack instead — a tree row costs exactly what a linear
    row of the same node count costs, since the ancestor-bitmask mask
    changes which scores survive, not which pages are walked). The
    page walk reads the extra appended pages' worth of KV; the token
    traffic term scales with the widened pack. Divide by
    :func:`expected_accepted_per_step` (or the ``_tree`` variant) for
    the per-emitted-token clock."""
    k = max(int(spec_k), int(spec_tree))
    wide = [int(l) + k for l in kv_lens]
    return ragged_serving_step_ms(
        wide, [1 + k] * len(kv_lens), page=page, hkv=hkv, g=g,
        d=d, hidden=hidden, n_layers=n_layers, spec=spec, quant=quant,
        issue_ms=issue_ms,
    )


def replica_step_ms(engine, *, spec: TpuSpec | None = None) -> float:
    """Analytic time of one engine step at the CURRENT resident
    occupancy (:func:`ragged_serving_step_ms` over the active slots'
    kv/cursor state): a slot still prefilling contributes its next
    chunk of prompt tokens, a decoding slot one token. Duck-typed over
    ``ServingEngine`` and either half of a disaggregated pair:
    anything with ``slot_req``, ``cfg``/``model.config``-shaped knobs.
    Cheap (no kernel runs) and deterministic — this is the modeled
    step clock the fleet accumulates for reproducible goodput, and the
    base of the router's :func:`replica_load_ms` perf term."""
    spec = spec or detect_spec()
    mc = engine.model.config
    # a speculative engine's decode rows are ``1 + spec_k`` wide (the
    # verify pack; tree mode packs its node budget instead) — price
    # the step it actually launches
    k = max(int(getattr(engine, "spec_k", 0)),
            int(getattr(engine, "spec_tree", 0)))
    active = [r for r in engine.slot_req if r is not None]
    kv_lens = [max(r.cursor, 1) + (k if r.cursor >= len(r.prompt) else 0)
               for r in active] or [1]
    q_lens = [
        max(1, min(engine.cfg.chunk, len(r.prompt) - r.cursor))
        if r.cursor < len(r.prompt) else 1 + k
        for r in active
    ] or [1]
    hkv = mc.n_kv_heads
    return ragged_serving_step_ms(
        kv_lens, q_lens, page=engine.cfg.page, hkv=hkv,
        g=mc.n_heads // max(hkv, 1), d=mc.head_dim, hidden=mc.hidden,
        n_layers=mc.n_layers, spec=spec,
        quant=getattr(mc, "kv_quant", None) is not None,
    )


def _spec_accept_factor(engine) -> float:
    """Tokens a speculative engine's verify step EMITS per step run —
    the measured engine rate once verify rows exist, the geometric
    prior before, 1.0 on plain engines. Divides the step clock
    wherever per-token throughput is being priced."""
    k = int(getattr(engine, "spec_k", 0))
    if not k:
        return 1.0
    st = getattr(engine, "stats", None)
    if st is not None and getattr(st, "spec_rows", 0) > 0:
        return max(st.accepted_tokens_per_step, 1.0)
    return expected_accepted_per_step(k, DEFAULT_SPEC_ACCEPTANCE)


def tiered_replica_load_ms(engine, queued_ahead: int, *,
                           spec: TpuSpec | None = None) -> float:
    """:func:`replica_load_ms` with an EXPLICIT queued-ahead count —
    the admission wait a PRIORITIZED arrival pays. Tier-r work
    re-enters admission ahead of every lower tier (the multi-tenant
    priority sort), so a tier-r arrival waits only on the queued
    requests at rank <= r; the caller passes that tier-filtered depth
    and the fleet's per-tenant retry-after prices by the tenant's own
    tier instead of the fleet-blind full queue."""
    step = replica_step_ms(engine, spec=spec) / _spec_accept_factor(engine)
    return step * (1.0 + max(int(queued_ahead), 0))


def replica_load_ms(engine, *, spec: TpuSpec | None = None) -> float:
    """Queue-depth load estimate for one fleet replica: the analytic
    :func:`replica_step_ms` scaled by how many admissions are already
    queued ahead — the router's perf term. A speculative replica's
    step EMITS more than one token, so its effective per-token clock is
    the step divided by accepted-tokens-per-step (the measured engine
    rate once verify rows have run, the geometric prior before) — a
    replica that drains its queue k× faster must price k× cheaper, or
    the router under-routes exactly the replicas speculation sped
    up."""
    queued = len(engine.waiting) + len(engine.pending)
    return tiered_replica_load_ms(engine, queued, spec=spec)


def request_service_ms(engine, req, *,
                       spec: TpuSpec | None = None) -> float:
    """Modeled time to serve ``req`` ITSELF at this engine's current
    occupancy clock: remaining prefill chunks plus remaining decode
    steps (speculation divides the decode part by accepted-tokens-per-
    step), each billed one :func:`replica_step_ms`. The own-work term
    of the router's deadline slack."""
    step = replica_step_ms(engine, spec=spec)
    remaining = max(len(req.seq) - req.cursor, 0)
    chunks = -(-remaining // max(int(engine.cfg.chunk), 1))
    decode = max(int(req.max_new) - len(req.generated), 0)
    return (chunks + decode / _spec_accept_factor(engine)) * step


def request_slack_ms(engine, req, slo_ms: float, *,
                     spec: TpuSpec | None = None) -> float:
    """Deadline slack of routing ``req`` to ``engine``:
    ``slo_ms − modeled completion``, where modeled completion is the
    queue already ahead (:func:`replica_load_ms`) plus the request's
    own remaining work (:func:`request_service_ms`). Negative slack
    means this placement is MODELED to miss the tenant's SLO — the
    fleet router lets that outrank prefix affinity."""
    return (float(slo_ms) - replica_load_ms(engine, spec=spec)
            - request_service_ms(engine, req, spec=spec))


# ------------------------------------------------ hop critical-path term
#
# The dataflow pass (analysis/dataflow.py) counts, per element of every
# contract destination, how many remote DMAs the bytes rode. Feeding
# that histogram back here turns it into a pre-hardware critical-path
# check: a ring of n ranks delivers every chunk in ≤ n-1 hops, so a
# schedule whose max hop count exceeds that has serialized (or detoured)
# its transfers — visible as wall-clock before any chip run (ROADMAP
# PR-4 follow-on, closed round 8: lint rule SL011).

def hop_critical_path_ms(max_hop: int, hop_bytes: int,
                         spec: TpuSpec | None = None) -> float:
    """Wire time of the LONGEST delivery chain: ``max_hop`` sequential
    ring-step transfers of ``hop_bytes`` each (hops on one chain cannot
    overlap each other — each forwards what the previous delivered)."""
    return max_hop * ring_wire_ms(hop_bytes, spec)


def ring_depth_regression(max_hop: int, n: int, hop_bytes: int,
                          spec: TpuSpec | None = None):
    """None when the observed max hop count is within the ring-optimal
    n-1; else (excess_hops, excess_ms) — the critical-path regression a
    serialized/detoured schedule pays per collective."""
    if max_hop <= max(n - 1, 1):
        return None
    excess = max_hop - (n - 1)
    return excess, hop_critical_path_ms(excess, hop_bytes, spec)


# --------------------------------------------------- KV-ship (DCN) term
#
# Disaggregated prefill/decode moves every finished request's KV cache
# slice→slice over DCN — the slowest fabric in the system. The split
# only wins when that transfer hides under the decode work the request
# buys (max_new decode steps); when prompts are long and generations
# short the wire DOMINATES and disaggregation makes latency worse.
# These terms price the ship so `auto` placement can refuse it
# analytically, before any hardware run.

def kv_ship_ms(n_pages: int, page: int, hkv: int, d: int, n_layers: int,
               quant: bool = True, spec: TpuSpec | None = None) -> float:
    """DCN time of ONE request's KV ship: K and V pages for every
    layer in the wire layout (1 B/elem int8 payload + the per-row f32
    scale planes under ``kv_quant``, else raw 2 B/elem pages) across
    the per-chip DCN share. Matches
    ``kernels.kv_ship.ship_wire_bytes`` by construction."""
    from triton_distributed_tpu.kernels.kv_ship import ship_wire_bytes

    spec = spec or detect_spec()
    return (ship_wire_bytes(n_pages, page, hkv, d, n_layers, quant)
            / (spec.dcn_gbps * 1e9) * 1e3)


def migrate_vs_reprefill_ms(n_pages: int, *, page: int, hkv: int, g: int,
                            d: int, hidden: int, n_layers: int = 1,
                            chunk: int = 16, quant: bool = True,
                            spec: TpuSpec | None = None,
                            issue_ms: float | None = None) -> tuple:
    """Price a cross-replica KV-page migration against recomputing the
    same prefix at the new home. Returns ``(migrate_ms, reprefill_ms)``:
    the DCN wire time of shipping ``n_pages`` in native quantized pool
    form (:func:`kv_ship_ms` — the bytes never widen) vs the chunked
    prefill steps that would rebuild the same ``n_pages · page`` tokens
    from scratch (:func:`ragged_serving_step_ms` per chunk, each chunk
    attending everything already rebuilt). The fleet migrates only when
    the wire beats the recompute — long committed prefixes ship, short
    ones re-prefill, and the crossover moves with ``dcn_gbps`` exactly
    like the disaggregation gate's."""
    spec = spec or detect_spec()
    migrate = kv_ship_ms(n_pages, page, hkv, d, n_layers, quant, spec)
    tokens = n_pages * page
    reprefill, done = 0.0, 0
    while done < tokens:
        take = min(chunk, tokens - done)
        done += take
        reprefill += ragged_serving_step_ms(
            [done], [take], page=page, hkv=hkv, g=g, d=d, hidden=hidden,
            n_layers=n_layers, spec=spec, quant=quant, issue_ms=issue_ms)
    return migrate, reprefill


def refuse_disaggregation(model_cfg, page: int, traffic: dict,
                          spec: TpuSpec | None = None, *,
                          ledger=None) -> str | None:
    """The `auto` placement gate: None when the expected per-request KV
    ship hides under the decode window it buys, else a human-readable
    refusal reason. ``traffic``: expected request shape —
    ``prompt_len`` (tokens whose pages ship) and ``max_new`` (decode
    steps the ship can overlap with); optional ``decode_step_ms``
    overrides the analytic steady-step estimate. ``spec_k`` (plus
    optional ``spec_acceptance``) prices speculative decode on the
    decode role: each verify step costs a little more
    (:func:`spec_step_ms`) but emits
    :func:`expected_accepted_per_step` tokens, so the request's decode
    WINDOW shrinks — a ship that hid under ``max_new`` plain steps may
    not hide under ``max_new / accepted`` verify steps, and the gate
    must refuse what speculation made unviable. ``ledger`` (a
    ``runtime.health.HealthLedger``) adds the health gate: a split
    topology is refused while a slice is condemned or the kv_ship wire
    itself is unhealthy — placement consults health, not just perf."""
    if ledger is not None:
        bad_slices = ledger.unhealthy_slices()
        if bad_slices:
            return (
                f"health ledger marks slice(s) {bad_slices} unhealthy — "
                "a split topology cannot place a role on a condemned "
                "slice"
            )
        from triton_distributed_tpu.runtime.health import PeerState

        if ledger.state("site:kv_ship") is PeerState.UNHEALTHY:
            return (
                "health ledger marks the kv_ship wire unhealthy — the "
                "split topology's transport is the thing that is broken"
            )
    spec = spec or detect_spec()
    prompt = int(traffic.get("prompt_len", 1024))
    max_new = int(traffic.get("max_new", 32))
    hkv = model_cfg.n_kv_heads
    d = model_cfg.head_dim
    quant = getattr(model_cfg, "kv_quant", None) is not None
    n_pages = max(-(-prompt // page), 1)
    ship = kv_ship_ms(
        n_pages, page, hkv, d, model_cfg.n_layers, quant, spec
    )
    spec_k = int(traffic.get("spec_k", 0))
    accepted = 1.0
    g = model_cfg.n_heads // max(hkv, 1)
    step_ms = traffic.get("decode_step_ms")
    if step_ms is None:
        if spec_k:
            step_ms = spec_step_ms(
                [prompt], spec_k=spec_k, page=page, hkv=hkv, g=g, d=d,
                hidden=model_cfg.hidden, n_layers=model_cfg.n_layers,
                spec=spec, quant=quant,
            )
        else:
            step_ms = ragged_serving_step_ms(
                [prompt], [1], page=page, hkv=hkv, g=g, d=d,
                hidden=model_cfg.hidden, n_layers=model_cfg.n_layers,
                spec=spec, quant=quant,
            )
    if spec_k:
        # a measured decode_step_ms is taken as the verify-step cost as
        # given (measurements outrank the analytic widening); the
        # window still shrinks by the emission rate
        accepted = expected_accepted_per_step(
            spec_k, float(traffic.get("spec_acceptance",
                                      DEFAULT_SPEC_ACCEPTANCE)))
    n_steps = max_new / accepted
    window = n_steps * float(step_ms)
    if ship <= window:
        return None
    spec_note = (
        f" (speculative decode spec_k={spec_k} emits {accepted:.2f} "
        f"tokens/step — the window shrank to {n_steps:.1f} steps)"
        if spec_k else ""
    )
    return (
        f"kv_ship_ms={ship:.3f} exceeds the decode window "
        f"{window:.3f} ms ({n_steps:.1f} steps x {float(step_ms):.3f} "
        f"ms){spec_note} — "
        f"shipping {n_pages} pages over {spec.dcn_gbps} GB/s DCN "
        "dominates the decode work it buys; keep prefill and decode "
        "colocated for this traffic"
    )


# --------------------------------------- context-parallel decode term
#
# Long-context serving shards one request's page walk across a cp axis
# (kernels/ragged_paged_attention.py TOPO_CP + the cp_decode.lse_combine
# ring): each rank reads only its ~1/cp share of the KV pages, then the
# per-rank (out, lse) partials merge over a cp-1-hop ring. The walk
# term shrinks by cp while the combine term is kv-length-INDEPENDENT,
# so long contexts win and short ones pay a fixed hop tax — these
# terms price that crossover so the fleet router can place long
# requests (and refuse them with numbers) before any hardware run.

def cp_decode_step_ms(kv_len: int, *, cp: int, page: int, hkv: int,
                      g: int, d: int, hidden: int, n_layers: int = 1,
                      spec: TpuSpec | None = None, quant: bool = True,
                      issue_ms: float | None = None) -> float:
    """Per-step decode cost of ONE ``kv_len``-token request on a
    ``cp``-sharded replica: the per-rank ragged walk over
    ``ceil(kv_len/cp)`` tokens (ranks walk their shards concurrently —
    the step pays the slowest, which under an even split is the 1/cp
    share) plus the cross-rank LSE-combine ring — ``cp-1`` sequential
    hops of the f32 ``(out, lse)`` partial slab per layer
    (:func:`hop_critical_path_ms`; hops on one delivery chain cannot
    overlap). ``cp=1`` degenerates to the single-slice walk exactly."""
    spec = spec or detect_spec()
    cp = max(int(cp), 1)
    local = max(-(-int(kv_len) // cp), 1)
    walk = ragged_serving_step_ms(
        [local], [1], page=page, hkv=hkv, g=g, d=d, hidden=hidden,
        n_layers=n_layers, spec=spec, quant=quant, issue_ms=issue_ms)
    if cp == 1:
        return walk
    slab = 4 * hkv * g * (d + 1)       # one row's f32 (out, lse) partial
    combine = n_layers * hop_critical_path_ms(cp - 1, slab, spec)
    return walk + combine


def refuse_long_context(model_cfg, page: int, need_pages: int, *,
                        pool_pages: int, pages_per_seq: int,
                        cp: int = 1,
                        spec: TpuSpec | None = None) -> str | None:
    """The long-context placement gate (the
    :func:`refuse_disaggregation` shape): None when ``need_pages`` —
    the request's END-TO-END KV, prompt plus every token it may
    generate — fits this replica's page pool AND its per-slot table
    width; else the priced refusal reason. Unlike an overload bounce,
    no retry-after can make pool capacity appear, so the reason names
    the missing capability and its price: the cp factor that WOULD
    hold the request and the modeled per-step cost of serving it there
    (:func:`cp_decode_step_ms` — the sharded walk plus the LSE-combine
    ring) against the single-slice HBM walk it replaces."""
    need = int(need_pages)
    cap = min(int(pool_pages), int(pages_per_seq))
    if need <= cap:
        return None
    spec = spec or detect_spec()
    hkv = model_cfg.n_kv_heads
    g = model_cfg.n_heads // max(hkv, 1)
    d = model_cfg.head_dim
    quant = getattr(model_cfg, "kv_quant", None) is not None
    kv = need * page
    # the smallest cp multiple of THIS replica's per-shard capacity
    # that holds the request (its shards are the fleet's pool unit)
    shard_cap = max(cap // max(int(cp), 1), 1)
    want_cp = max(-(-need // shard_cap), 2)
    cp_ms = cp_decode_step_ms(
        kv, cp=want_cp, page=page, hkv=hkv, g=g, d=d,
        hidden=model_cfg.hidden, n_layers=model_cfg.n_layers,
        spec=spec, quant=quant)
    flat_ms = ragged_serving_step_ms(
        [kv], [1], page=page, hkv=hkv, g=g, d=d,
        hidden=model_cfg.hidden, n_layers=model_cfg.n_layers,
        spec=spec, quant=quant)
    return (
        f"request needs {need} KV pages but this replica holds "
        f"{cap} (cp={max(int(cp), 1)}) — a cp={want_cp} replica would "
        f"serve it at ~{cp_ms:.3f} ms/step (sharded walk + "
        f"{want_cp - 1}-hop LSE-combine ring) vs the {flat_ms:.3f} ms "
        "single-slice HBM walk it replaces; route long contexts to a "
        "cp-capable replica"
    )
