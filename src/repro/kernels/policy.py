"""The ``--kernels`` switch: which implementation backs the model hot spots.

Three modes, each meaning exactly what it says:

  * ``reference`` — the pure-XLA ``ref.py`` paths (chunked-scan attention,
    scanned cross-entropy, SSD block matmuls).  Runs on any backend.
  * ``pallas``    — the Pallas kernels (flash_attention, fused_xent,
    ssd_scan), lowered to Mosaic.  Needs a TPU backend: asking for it
    anywhere else raises instead of quietly running something else.
  * ``interpret`` — the Pallas kernels through the Pallas interpreter on
    any backend.  A correctness harness (~1000x slower than the reference
    paths on CPU, see kernels/README.md), not a training path: the numerics
    gate (``repro.kernels.numerics``) and the kernel leg of
    ``repro.train.zoo_parity`` use it to prove the kernel step body agrees
    with the reference step body on CPU.

``resolve_kernels`` is called once at ``build_model`` time and the mode is
passed down to the kernel wrappers (``interpret=mode == "interpret"``), so
nothing branches on the backend inside a traced step.
"""
from __future__ import annotations

import jax

KERNEL_CHOICES = ("pallas", "reference", "interpret")


def resolve_kernels(kernels: str) -> str:
    """Validate ``kernels`` -> 'pallas' | 'reference' | 'interpret'.

    Raises ``ValueError`` for an unknown mode and ``RuntimeError`` for
    ``pallas`` off a TPU backend."""
    if kernels not in KERNEL_CHOICES:
        raise ValueError(f"kernels must be one of {KERNEL_CHOICES}, "
                         f"got {kernels!r}")
    backend = jax.default_backend()
    if kernels == "pallas" and backend != "tpu":
        raise RuntimeError(
            f"--kernels pallas needs a TPU backend (Mosaic lowering); JAX's "
            f"backend is {backend!r}.  Use --kernels reference, or "
            f"--kernels interpret for the Pallas interpreter")
    return kernels
