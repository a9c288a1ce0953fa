"""shmemlint — static semaphore-protocol and deadlock analysis (L6).

The dynamic correctness evidence for the SHMEM kernel family (chaos
delays + the TPU interpreter's race detector) is probabilistic and
environment-bound: ``tests/test_races.py`` documents a deliberately
removed wait the detector missed under ``dma_execution_mode="on_wait"``,
and on a jax without the TPU-simulation interpreter the dynamic passes
cannot run at all. This package closes that gap *statically* (the
ML-Triton argument — compiler passes over kernel IR instead of runtime
luck, arxiv 2503.14985): each kernel family is symbolically executed
once per rank on an abstract N-rank mesh, every ``lang.shmem`` event
(puts, signal increments, consuming waits, fences, barriers) is
recorded into per-rank traces, and checker passes verify the cross-rank
protocol — credit balance, deadlock freedom, barrier hygiene, RDMA
buffer hazards, VMEM budget.

Layout:

* :mod:`events`    — the event/trace model + the active recorder that
  the ``lang.shmem`` hook layer feeds.
* :mod:`abstract`  — the abstract evaluator: fake refs/semaphores/DMA
  handles and the patched Pallas environment kernels run under.
* :mod:`checks`    — the checker passes (cross-rank replay simulation
  with vector clocks, then the SL-rule checks over the result).
* :mod:`findings`  — finding model, severities, the SL001… rule catalog.
* :mod:`dataflow`  — symbolic payload-provenance replay: delivery
  contracts (SL008), wire-rail consistency (SL009), stale-scale reads
  (SL010) — a schedule can be semaphore-clean and still deliver wrong
  bytes; this pass is what catches that.
* :mod:`contract_infer` — contract inference: run each family's XLA
  twin (``degrades_to``) on rank-tagged inputs and realize the concrete
  delivery contract from the replay's provenance arrays; declared
  contracts become assertions (SL012 on drift, SL013 on a missing
  declaration — inference supplies the contract so SL008 never goes
  blind).
* :mod:`mosaic_compat` — the seconds-fast Mosaic pre-flight (MC001–
  MC010): each family's kernel jaxpr, built for hardware, scanned for
  constructs this toolchain's Mosaic backend rejects.
* :mod:`lint`      — public API (:func:`lint.lint_family`,
  :func:`lint.lint_all`) and the CLI
  (``python -m triton_distributed_tpu.analysis.lint``).
* :mod:`fixtures`  — deliberately broken kernels (missing wait, credit
  imbalance, deadlock, barrier misuse, skipped/dup delivery, mispaired
  wire rails, Mosaic-rejected constructs) pinning each rule forever.

The kernel families under analysis are declared in
:mod:`triton_distributed_tpu.kernels.registry`.
"""

from triton_distributed_tpu.analysis.findings import (
    RULES,
    SCHEMA_VERSION,
    Finding,
    Severity,
)

__all__ = [
    "Finding",
    "Severity",
    "RULES",
    "SCHEMA_VERSION",
    "DeliveryContract",
    "lint_all",
    "lint_family",
    "lint_mesh",
    "preflight_all",
    "infer_family",
    "verify_declared_contracts",
]


def __getattr__(name):
    # lint is imported lazily so `python -m ...analysis.lint` does not
    # re-execute a module already bound by this package import (runpy
    # double-import warning)
    if name in ("lint_all", "lint_family", "lint_mesh"):
        from triton_distributed_tpu.analysis import lint

        return getattr(lint, name)
    if name == "preflight_all":
        from triton_distributed_tpu.analysis import mosaic_compat

        return mosaic_compat.preflight_all
    if name == "DeliveryContract":
        from triton_distributed_tpu.analysis import dataflow

        return dataflow.DeliveryContract
    if name in ("infer_family", "verify_declared_contracts"):
        from triton_distributed_tpu.analysis import contract_infer

        return getattr(contract_infer, name)
    raise AttributeError(name)
