"""Unified registration options — one frozen, hashable configuration object.

Every registration entry point used to take the same ~12-keyword sprawl
(``tile, levels, iters, lr, bending_weight, mode, impl, grad_impl,
compute_dtype, similarity, stop``), and each one re-validated and re-keyed
the subset it cared about.  :class:`RegistrationOptions` consolidates that
surface:

* it is the **single place options are validated** (``__post_init__``) and
  canonicalised (:meth:`normalized`);
* because it is frozen and hashable, it is the **single cache key** for
  compiled runners (``core.registration``, ``engine.batch``), the autotuner
  (``engine.autotune.resolve_options``) and the serving buckets
  (``engine.serve``);
* entry points accept ``options=RegistrationOptions(...)``.  The legacy
  keyword arguments still work through :func:`merge_legacy_options`, which
  emits a ``DeprecationWarning`` once per call site and produces the exact
  same options object — so the kwarg path and the options path share one
  compiled program and return bit-identical results.

This module deliberately imports nothing from ``repro`` at module scope
(only lazily, inside methods): it sits at the bottom of the dependency
stack so both ``repro.core`` and ``repro.engine`` can import it freely.
"""

from __future__ import annotations

import dataclasses
import sys
import warnings
from typing import Any

__all__ = [
    "UNSET",
    "RegistrationOptions",
    "merge_legacy_options",
]


class _Unset:
    """Sentinel distinguishing "keyword not passed" from an explicit value."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self):
        return "UNSET"

    def __bool__(self):
        return False


UNSET = _Unset()

_BSI_IMPLS = ("auto", "jnp", "pallas")
_FUSED = ("auto", "on", "off")


def _bsi_modes():
    """``("auto",)`` + the canonical mode set.

    Derived lazily from ``repro.core.interpolate.MODE_NAMES`` — the single
    source every layer validates against — so a new mode registers here
    without a drifting duplicate list (this module keeps repro imports out
    of module scope; see ``__post_init__``'s registry imports).
    """
    from repro.core.interpolate import MODE_NAMES

    return ("auto",) + MODE_NAMES


def _grad_impls():
    """``("auto",)`` + ``repro.core.interpolate.GRAD_IMPLS`` (same rule)."""
    from repro.core.interpolate import GRAD_IMPLS

    return ("auto",) + GRAD_IMPLS


@dataclasses.dataclass(frozen=True)
class RegistrationOptions:
    """The full registration configuration, validated and hashable.

    Defaults match the historical ``ffd_register`` / ``register_batch``
    keyword defaults; ``affine_register`` keeps its own legacy defaults
    (``iters=60, lr=0.02``) through its deprecation shim.

    Fields
    ------
    tile:            control-point spacing ``(dx, dy, dz)``.
    levels:          pyramid levels (coarse-to-fine, 2x downsampling).
    iters:           Adam steps per level (also the early-stop ceiling).
    lr:              Adam learning rate.
    bending_weight:  bending-energy regularisation weight.
    mode, impl:      BSI algorithm form / kernel backend (``"auto"`` =
                     the ``engine.autotune`` winner).
    grad_impl:       BSI adjoint implementation (``"auto"`` | ``"xla"`` |
                     ``"jnp"`` | ``"pallas"`` | ``"matmul"``).
    compute_dtype:   reduced-precision dtype for BSI + warp (e.g.
                     ``"bfloat16"``), or None for fp32 throughout.
    similarity:      registered similarity name or a ``(warped, fixed) ->
                     scalar`` loss callable (lower = better).
    transform:       transform model: registered name (``"displacement"`` |
                     ``"velocity"``) or a frozen spec from
                     ``repro.core.transform`` (e.g.
                     ``velocity(squarings=4)``).  ``"velocity"`` integrates
                     a stationary velocity field by scaling and squaring —
                     invertible, fold-free deformations for the IGS-safety
                     workloads; names normalise to their spec instance.
    regularizer:     registered name (``"none"`` | ``"bending"``) or a
                     frozen spec from ``repro.core.regularizer``.
                     ``"none"`` keeps the historical ``bending_weight``
                     finite-difference proxy; ``"bending"`` replaces it
                     with the analytic uniform-cubic-B-spline bending
                     energy (weight via ``bending(weight=...)``).
    stop:            optional ``engine.convergence.ConvergenceConfig`` —
                     early-stop each level when the loss plateaus.
    fused:           fused level-step kernel (``core.ffd.fused_warp_loss``:
                     BSI + warp + similarity in one VMEM Pallas pass, no
                     dense field in HBM).  ``"auto"`` lets the autotuner
                     race it against the unfused step per backend (custom
                     similarities and over-budget volumes fall back to
                     ``"off"``); ``"on"`` forces it (raising when
                     unsupported); ``"off"`` is the unfused pipeline.
    optimizer:       registered optimiser name (``"adam"`` | ``"lbfgs"`` |
                     ``"gauss_newton"``) or a frozen spec from
                     ``repro.engine.optimizer`` (e.g. ``lbfgs(history=10)``);
                     names normalise to their spec instance.  The default
                     ``"adam"`` is bit-identical to the pre-registry engine;
                     ``"gauss_newton"`` requires ``similarity="ssd"`` (the
                     only built-in with a least-squares residual form) and
                     an unfused level step (the fused megakernel's
                     partial-sum accumulator never materialises the
                     residual volume).
    fused_reason:    why ``fused`` resolved the way it did — set by
                     ``engine.autotune.resolve_options`` on its output
                     (e.g. ``"forced on"``, ``"velocity transform has no
                     fused composition"``, ``"autotune: fused won"``),
                     ``None`` on hand-built unresolved options.  Excluded
                     from equality/hash on purpose: it is introspection
                     metadata, not configuration, so it never fragments a
                     program cache.
    skipped:         the BSI candidates the autotuner left out of its race,
                     as ``(("mode/impl/grad_impl", reason), ...)`` — out of
                     device memory at compile or load, or a compiled step
                     too large to leave room for the rest of the
                     registration.  Set by ``resolve_options``; introspection
                     only, excluded from equality/hash like ``fused_reason``.
    """

    tile: tuple = (5, 5, 5)
    levels: int = 2
    iters: int = 40
    lr: float = 0.5
    bending_weight: float = 5e-3
    mode: str = "auto"
    impl: str = "auto"
    grad_impl: str = "auto"
    compute_dtype: Any = None
    similarity: Any = "ssd"
    transform: Any = "displacement"
    regularizer: Any = "none"
    stop: Any = None
    fused: str = "auto"
    optimizer: Any = "adam"
    fused_reason: Any = dataclasses.field(default=None, compare=False)
    skipped: tuple = dataclasses.field(default=(), compare=False)

    def __post_init__(self):
        tile = tuple(int(t) for t in self.tile)
        if len(tile) != 3 or any(t < 1 for t in tile):
            raise ValueError(f"tile must be 3 positive ints, got {self.tile!r}")
        object.__setattr__(self, "tile", tile)
        for name in ("levels", "iters"):
            v = int(getattr(self, name))
            if v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
            object.__setattr__(self, name, v)
        for name in ("lr", "bending_weight"):
            v = float(getattr(self, name))
            if not v >= 0 or (name == "lr" and v == 0):
                raise ValueError(f"{name} must be positive, got {v}")
            object.__setattr__(self, name, v)
        modes = _bsi_modes()
        if self.mode not in modes:
            raise ValueError(f"mode must be one of {modes}, got {self.mode!r}")
        if self.impl not in _BSI_IMPLS:
            raise ValueError(f"impl must be one of {_BSI_IMPLS}, got {self.impl!r}")
        grad_impls = _grad_impls()
        if self.grad_impl not in grad_impls:
            raise ValueError(
                f"grad_impl must be one of {grad_impls}, got {self.grad_impl!r}"
            )
        if self.fused in (True, False):  # ergonomic bool spelling
            object.__setattr__(self, "fused", "on" if self.fused else "off")
        if self.fused not in _FUSED:
            raise ValueError(
                f"fused must be one of {_FUSED} (or a bool), got {self.fused!r}"
            )
        if self.compute_dtype is not None:
            import jax.numpy as jnp

            object.__setattr__(
                self, "compute_dtype", jnp.dtype(self.compute_dtype).name
            )
        if not (callable(self.similarity) or isinstance(self.similarity, str)):
            raise TypeError(
                "similarity must be a registered name or a loss callable, "
                f"got {self.similarity!r}"
            )
        # Canonicalise transform/regularizer to their frozen spec instances
        # (same discipline as the fused bool -> "on"/"off" normalisation):
        # "velocity" and velocity() hash equal, and the spec instance is the
        # sole program-cache key downstream.
        from repro.core.regularizer import resolve_regularizer
        from repro.core.transform import VelocityTransform, resolve_transform

        object.__setattr__(self, "transform", resolve_transform(self.transform))
        object.__setattr__(
            self, "regularizer", resolve_regularizer(self.regularizer)
        )
        if self.fused == "on" and isinstance(self.transform, VelocityTransform):
            raise ValueError(
                "fused='on' is incompatible with transform='velocity': the "
                "fused level-step kernel evaluates BSI + warp + similarity "
                "in one pass and cannot interleave the scaling-and-squaring "
                "compositions the velocity transform needs; use fused='auto' "
                "or 'off' (velocity always runs the unfused pipeline)"
            )
        # Canonicalise the optimiser to its frozen spec instance (same
        # discipline): "lbfgs" and lbfgs() hash equal, and the spec is the
        # optimiser token in every downstream program-cache key.
        from repro.engine.optimizer import (GaussNewtonOptimizer,
                                            resolve_optimizer)

        object.__setattr__(self, "optimizer", resolve_optimizer(self.optimizer))
        if isinstance(self.optimizer, GaussNewtonOptimizer):
            from repro.core.similarity import resolve_similarity

            sim_key, _ = resolve_similarity(self.similarity)
            if sim_key != "ssd":
                raise ValueError(
                    "optimizer='gauss_newton' needs the least-squares "
                    "residual form only similarity='ssd' provides, got "
                    f"similarity={self.similarity!r}; use optimizer='lbfgs' "
                    "for non-least-squares similarities"
                )
            if self.fused == "on":
                raise ValueError(
                    "fused='on' is incompatible with optimizer="
                    "'gauss_newton': the fused level step accumulates the "
                    "similarity as in-VMEM partial sums and never "
                    "materialises the residual volume Gauss-Newton "
                    "linearises; use fused='auto' or 'off'"
                )
        if self.stop is not None:
            from repro.engine.convergence import ConvergenceConfig

            if not isinstance(self.stop, ConvergenceConfig):
                raise TypeError(
                    f"stop must be a ConvergenceConfig or None, got {self.stop!r}; "
                    "e.g. stop=ConvergenceConfig(tol=1e-4)"
                )

    def replace(self, **changes) -> "RegistrationOptions":
        """A copy with the given fields replaced (re-validated)."""
        return dataclasses.replace(self, **changes)

    def normalized(self) -> "RegistrationOptions":
        """Canonical form: the cache-key-ready copy.

        ``similarity`` collapses to its registry key (so ``"nmi"`` and a
        registered ``nmi()`` callable share caches), and ``stop`` resolves
        its ``max_iters`` against ``iters`` — after this, equal
        configurations compare (and hash) equal.
        """
        from repro.core.similarity import resolve_similarity
        from repro.engine.convergence import check_stop

        sim_key, _ = resolve_similarity(self.similarity)
        return dataclasses.replace(
            self, similarity=sim_key, stop=check_stop(self.stop, self.iters)
        )

    def for_affine(self) -> "RegistrationOptions":
        """Canonical key for the affine path.

        Affine registration only consumes ``iters``, ``lr``, ``similarity``
        and ``stop``; pinning every FFD-only field to its default keeps the
        affine runner cache from fragmenting when callers vary e.g. ``tile``.
        """
        base = RegistrationOptions()
        return self.normalized().replace(
            tile=base.tile,
            levels=base.levels,
            bending_weight=base.bending_weight,
            mode=base.mode,
            impl=base.impl,
            grad_impl=base.grad_impl,
            compute_dtype=base.compute_dtype,
            transform=base.transform,
            regularizer=base.regularizer,
            fused="off",  # affine has no FFD level step to fuse
        )


# DeprecationWarning bookkeeping: one warning per (entry point, call site),
# deterministic regardless of the process's warning filters.  Tests reset it
# via _reset_deprecation_registry().
_WARNED_SITES: set = set()


def _reset_deprecation_registry():
    _WARNED_SITES.clear()


def merge_legacy_options(
    fn_name, options, legacy: dict, *, defaults=None, stacklevel=3
) -> RegistrationOptions:
    """The deprecation shim behind every registration entry point.

    ``legacy`` maps field name -> value-or-:data:`UNSET` for the keyword
    arguments the entry point still accepts.  Exactly one of the two paths
    may be used:

    * ``options=`` given, no legacy kwargs -> ``options`` passes through;
    * legacy kwargs (or nothing) -> they overlay ``defaults`` into a fresh
      :class:`RegistrationOptions`, and — if any legacy kwarg was actually
      passed — a ``DeprecationWarning`` fires, once per call site.

    Mixing both raises ``TypeError`` (silently preferring one would make the
    other a no-op).
    """
    passed = {k: v for k, v in legacy.items() if v is not UNSET}
    if options is not None:
        if not isinstance(options, RegistrationOptions):
            raise TypeError(
                f"{fn_name}: options must be a RegistrationOptions, "
                f"got {type(options).__name__}"
            )
        if passed:
            raise TypeError(
                f"{fn_name}: pass either options= or the legacy keyword "
                f"arguments {sorted(passed)}, not both"
            )
        return options
    if passed:
        frame = sys._getframe(stacklevel - 1)
        site = (fn_name, frame.f_code.co_filename, frame.f_lineno)
        if site not in _WARNED_SITES:
            _WARNED_SITES.add(site)
            spelled = ", ".join(f"{k}=..." for k in sorted(passed))
            warnings.warn(
                f"{fn_name}: the keyword arguments {sorted(passed)} are "
                f"deprecated; pass options=RegistrationOptions({spelled}) "
                "instead (see repro.core.options)",
                DeprecationWarning,
                stacklevel=stacklevel,
            )
    base = RegistrationOptions() if defaults is None else defaults
    return base.replace(**passed) if passed else base
