"""TPU compute ops (attention, layers, paged attention).

Every "am I on a TPU?" decision (Pallas interpret mode on the CPU, the
kernel-vs-reference choice of the "auto" dispatchers) goes through
:func:`is_tpu_backend`, so there is one place that answers it.
"""

from __future__ import annotations


def is_tpu_backend() -> bool:
    """True when jax's default backend is the TPU. A backend that fails to
    initialize raises here: no caller may mistake a broken TPU for a CPU."""
    import jax

    return jax.default_backend() == "tpu"
