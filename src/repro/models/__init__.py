"""Model zoo substrate: shared layers + per-family stacks.

All stacks scan over layers (``jax.lax.scan`` with stacked per-layer
params) so compile time and HLO size are O(1) in depth — required for
the 80-program dry-run matrix on a host CPU, and standard
production practice (MaxText-style).
"""
from repro.models.registry import build_model, Model

__all__ = ["build_model", "Model"]
