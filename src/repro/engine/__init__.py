"""Batched, device-resident registration engine.

The workload-scale layer over ``repro.core``: scan-compiled optimisation
loops (``engine.loop``) over a pluggable ``optimizer=`` registry — Adam by
default, second-order L-BFGS and Gauss-Newton entries for hard pairs
(``engine.optimizer``) — whole-pipeline batching via ``vmap`` so N volume
pairs register in one jitted program (``engine.batch.register_batch``), a
benchmark-and-cache autotuner that picks the fastest BSI form per
configuration instead of hardcoded defaults (``engine.autotune``),
mesh-sharded data-parallel serving that places the batch axis over a device
pod (``engine.shard``, via ``register_batch(..., mesh=...)``),
convergence-aware early stopping so easy pairs stop paying for BSI work
they no longer need (``engine.convergence``, via ``stop=``), and a
continuous-batching request scheduler that splices queued pairs into lanes
freed by the convergence mask (``engine.serve``).
"""
from repro.engine.autotune import (BsiChoice, autotune_bsi,
                                   default_candidates, default_grad_impls,
                                   resolve_bsi, resolve_options)
from repro.engine.batch import (BatchRegistrationResult, ffd_pipeline,
                                register_batch)
from repro.engine.convergence import (ConvergenceConfig, adam_until,
                                      optimize_until)
from repro.engine.loop import adam_scan, make_adam_runner, optimize_scan
from repro.engine.optimizer import (OPTIMIZERS, AdamOptimizer,
                                    GaussNewtonOptimizer, LbfgsOptimizer,
                                    Objective, adam, available_optimizers,
                                    gauss_newton, lbfgs, make_objective,
                                    optimizer_token, resolve_optimizer)
from repro.engine.serve import (AsyncRegistrationService, QueueFull,
                                RegistrationScheduler, RegistrationTimeout,
                                ServeResult, ServeStats)
from repro.engine.shard import make_registration_mesh

__all__ = [
    "BsiChoice",
    "autotune_bsi",
    "default_candidates",
    "default_grad_impls",
    "resolve_bsi",
    "resolve_options",
    "BatchRegistrationResult",
    "ffd_pipeline",
    "register_batch",
    "ConvergenceConfig",
    "adam_until",
    "optimize_until",
    "adam_scan",
    "make_adam_runner",
    "optimize_scan",
    "OPTIMIZERS",
    "AdamOptimizer",
    "GaussNewtonOptimizer",
    "LbfgsOptimizer",
    "Objective",
    "adam",
    "available_optimizers",
    "gauss_newton",
    "lbfgs",
    "make_objective",
    "optimizer_token",
    "resolve_optimizer",
    "AsyncRegistrationService",
    "QueueFull",
    "RegistrationScheduler",
    "RegistrationTimeout",
    "ServeResult",
    "ServeStats",
    "make_registration_mesh",
]
