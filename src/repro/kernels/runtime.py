"""Shared runtime knobs for the Pallas serving kernels.

One module owns the decisions every kernel wrapper used to make for
itself (four copy-pasted ``_default_interpret`` helpers before this file
existed — a backend change could silently drift per kernel):

* ``default_interpret()`` — whether ``pallas_call`` should run in interpret
  mode.  Off-TPU backends (the CPU test suite) must interpret; a TPU always
  compiles through Mosaic — nothing can force interpret mode there, so a
  run on the chip never times the interpreter by accident.
* ``resolve_backend()`` — validation for the engine-facing compute-backend
  switch (``backend="xla" | "pallas"``) threaded from
  ``serving.GeoServingSystem`` through the pooled step factories down to
  the per-kind block functions.  ``"xla"`` is the oracle path (pure jnp,
  runs everywhere); ``"pallas"`` routes supported block computations
  through ``repro.kernels`` and falls back to the oracle per call site via
  the kernels' own ``*_unsupported`` dispatch predicates.
* ``use_kernel()`` — that per-call decision, which counts every fallback
  in ``XLA_FALLBACKS`` so a run can assert that none happened.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax

# The engine-facing compute backends.  "xla" is the default/oracle path;
# "pallas" dispatches supported calls to the kernels in this package.
BACKENDS = ("xla", "pallas")

# "no sliding window" sentinel shared by every masking path (both Pallas
# kernels and the XLA oracle in models/attention.py): int32-safe and larger
# than any position, so `diff < NO_WINDOW` never masks.  One definition —
# per-kernel copies could drift and silently change window semantics.
NO_WINDOW = 1 << 30

# Calls under backend="pallas" that a kernel's ``*_unsupported`` predicate
# sent to the XLA path instead, keyed by "kernel: reason".  Dispatch is
# decided while a step is traced, so a jitted step counts once per trace,
# not once per execution.  Process-wide, like jax's own trace caches: a
# run that must prove every call reached a kernel reads it (chip_smoke.py).
XLA_FALLBACKS: collections.Counter = collections.Counter()


def use_kernel(backend: str, kernel: str, reason: Optional[str]) -> bool:
    """Whether a block call runs ``kernel``: the backend asks for Pallas and
    the kernel's predicate gave no ``reason`` against the call.  A Pallas
    call that falls back to XLA is counted in :data:`XLA_FALLBACKS`."""
    if resolve_backend(backend) != "pallas":
        return False
    if reason is not None:
        XLA_FALLBACKS[f"{kernel}: {reason}"] += 1
        return False
    return True


def resolve_backend(backend: str) -> str:
    """Validate a compute-backend name; ``ValueError`` names the options."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown compute backend {backend!r}; supported backends: "
            + ", ".join(BACKENDS))
    return backend


def default_interpret() -> bool:
    """Interpret-mode default for ``pallas_call``: interpret iff the default
    jax backend is not a TPU (Pallas TPU kernels cannot lower elsewhere)."""
    return jax.default_backend() != "tpu"
