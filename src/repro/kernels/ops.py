"""Jit'd public wrappers around the Pallas kernels.

Model code calls these; each dispatches to the TPU kernel and hides
padding/layout glue.  ``interpret`` defaults to auto-detection
(:func:`default_interpret`): interpreted on CPU containers, compiled on a
real TPU backend — the kernel body is the TPU program either way.  Oracles
live in ref.py; tests/test_kernels.py sweeps shapes × dtypes asserting
allclose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.cache_lookup import (cache_lookup_all_layers,  # noqa: F401
                                        cache_lookup_all_layers_tiled,
                                        cache_lookup_layer,
                                        default_interpret)
from repro.kernels.cache_merge import cache_merge_round  # noqa: F401
from repro.kernels.decode_attention import (combine_partials,  # noqa: F401
                                            decode_attention)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.held_combine import held_combine  # noqa: F401
from repro.kernels.ssd_scan import ssd_scan  # noqa: F401


def flash_attention_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        interpret: bool | None = None) -> jax.Array:
    """GQA wrapper: q (B,S,H,hd), k/v (B,T,Hkv,hd) -> (B,S,H,hd)."""
    if interpret is None:
        interpret = default_interpret()
    H, Hkv = q.shape[2], k.shape[2]
    if H != Hkv:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _flash(q, k, v, causal=causal, interpret=interpret)


flash_attention = _flash
