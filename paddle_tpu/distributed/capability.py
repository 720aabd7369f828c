"""Backend capability probes for the distributed surface.

What a backend can do differs between the CPU the tests run on and the
TPU (multi-controller collectives, host-pinned memory). Each probe
answers "can THIS backend do it" by looking for the feature itself, and
the tests that need it skip as "capability absent" where it is missing.
The jax API itself is not probed: the repo targets the one installed
jax.
"""

from __future__ import annotations

__all__ = ["has_pinned_host_memory", "has_multiprocess_collectives"]


def has_multiprocess_collectives():
    """True when this runtime's backend can execute multi-controller
    computations (the launch/elastic e2e tests spawn real worker
    processes). XLA's CPU backend rejects them outright
    ("Multiprocess computations aren't implemented on the CPU
    backend") — the capability boundary is the backend kind, not a jax
    version."""
    import jax

    try:
        return jax.default_backend() != "cpu"
    except Exception:  # noqa: BLE001 — no backend at all
        return False


def has_pinned_host_memory():
    """True when the default device can address ``pinned_host`` memory
    (the offload tests' dependency); CPU-only jax builds advertise only
    ``unpinned_host``."""
    import jax

    try:
        dev = jax.devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
        return "pinned_host" in kinds
    except Exception:  # noqa: BLE001 — absent API means absent feature
        return False
