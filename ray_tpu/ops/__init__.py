"""TPU compute ops: `attention` (flash, training), `layers`,
`paged_attention` (K/V and latent pools), and the recurrent layers' kernels
over ragged rows and a slot a sequence: `ssm_scan` (selective scan),
`power_retention` (a gated accumulation), `kda` (a gated delta rule), `ssd`
(Mamba-2: a matrix state with one decay a head and token).

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


def kernel_tag(name: str) -> dict:
    """`pallas_call` keywords that name a kernel to whoever reads a profile.
    `name` reaches the jaxpr, the Mosaic module and, through the location's
    name stack, the HLO instruction's name (`<name>.<n>`), but not where
    JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0 strips the stack (as the
    benchmark and chip_smoke.py do for stable compile-cache keys: the
    instruction is then `tpu_custom_call.<n>`). `metadata` always becomes
    `frontend_attributes={kernel_metadata={"kernel":"<name>"}}` in the text a
    TPU profile shows for the kernel's event (looked at on a v5e, PR 26)."""
    return {"name": name, "metadata": {"kernel": name}}
