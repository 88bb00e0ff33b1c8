"""Backend-aware kernel runtime policy (shared by all Pallas entry points).

``resolve_interpret`` decides whether ``pl.pallas_call`` runs in interpret
mode: exactly when the active jax backend is the CPU, which has no Mosaic
lowering to run. An explicit bool wins (tests and the described-topology
compiles pass ``False``).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """``None`` means interpret iff the default backend is the CPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
