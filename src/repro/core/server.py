"""Server-side CoCa: the two-dimensional global cache and its updates.

The server maintains (§IV.D)
  * ``entries``    — (L, I, d) global cache table E, rows L2-normalised,
  * ``phi_global`` — (I,) global class frequency Φ,
  * ``r_est``      — (L,) expected hit-ratio vector R with **CDF semantics**:
                     R[j] = P(first hit at some layer ≤ j | all layers active).
                     This is the reading under which Alg. 1's subtraction step
                     (R[j] -= R[b] for j ≥ b) is a coherent weighted set-cover
                     greedy.  Initialised from shared-dataset profiling,
                     EMA-updated from client observations (§V.A),
  * ``upsilon``    — (L,) saved inference time Υ per layer (model compute
                     only), derived from the cost model.

Eq. (4) merge:  E[i,j] = γ·Φᵢ/(Φᵢ+φᵢᵏ)·E[i,j] + φᵢᵏ/(Φᵢ+φᵢᵏ)·U[i,j]ᵏ, then
L2-normalise.  Eq. (5):  Φᵢ += φᵢᵏ.

At scale the table is sharded over the class axis I
(:func:`repro.distributed.sharding.shard_server_state`): every update here is
elementwise in I (the Eq.-4 weights, the merge, the L2-normalise over d, the
Φ add), so a class-sharded ServerState flows through ``global_update_body``
with no cross-device communication — GSPMD keeps I split end to end, and
the fused Pallas merge runs per class shard under ``shard_map``.  The
round driver (:mod:`repro.core.simulation`) gathers ``entries`` only at
client subtable allocation.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import ClientUpload
from repro.core.semantic_cache import CacheConfig, CacheTable, l2_normalize


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    gamma: float = 0.99       # Eq. (4) decay γ
    r_ema: float = 0.5        # EMA weight for client hit-ratio observations
    # How a round's K uploads merge (:func:`merge_round`): "auto" picks the
    # fused Pallas kernel on TPU backends and the scanned reference
    # elsewhere; "fused" / "ref" pin a path (parity tests, benchmarks).
    merge_impl: str = "auto"


class ServerState(NamedTuple):
    entries: jax.Array        # (L, I, d)
    phi_global: jax.Array     # (I,) float32
    r_est: jax.Array          # (L,) float32
    upsilon: jax.Array        # (L,) float32 (seconds saved on a layer-j hit)


def init_server(cfg: CacheConfig, init_entries: jax.Array,
                init_phi: jax.Array, r0: jax.Array,
                upsilon: jax.Array) -> ServerState:
    """Build the server from shared-dataset profiling (§V.A empirical data)."""
    return ServerState(
        entries=l2_normalize(init_entries),
        phi_global=init_phi.astype(jnp.float32),
        r_est=r0.astype(jnp.float32),
        upsilon=upsilon.astype(jnp.float32),
    )


def global_update_body(server: ServerState, up: ClientUpload,
                       scfg: ServerConfig) -> ServerState:
    """Apply one client's upload: Eq. (4) cache merge + Eq. (5) frequencies.

    Only cells the client actually absorbed into (``u_touched``) are merged —
    an untouched cell carries no new information (and Eq. (4) with φ=0 is a
    no-op after re-normalisation anyway).

    Unjitted body so the round simulator can fold the per-client merges of a
    whole round into one ``lax.scan`` (:mod:`repro.core.simulation`); call
    :func:`global_update` for the standalone jitted version.
    """
    phi_l = up.phi.astype(jnp.float32)                     # (I,)
    phi_g = server.phi_global                              # (I,)
    denom = jnp.maximum(phi_g + phi_l, 1e-6)
    w_g = (scfg.gamma * phi_g / denom)[None, :, None]      # (1, I, 1)
    w_l = (phi_l / denom)[None, :, None]
    merged = l2_normalize(w_g * server.entries + w_l * l2_normalize(up.u))
    entries = jnp.where(up.u_touched[..., None], merged, server.entries)

    phi_global = phi_g + phi_l

    # Hit-ratio estimate (CDF): EMA toward this client's observed cumulative
    # first-hit fractions, at layers the client actually looked up.
    frames = jnp.maximum(up.phi.sum(), 1)
    obs_cdf = jnp.cumsum(up.hit_counts) / frames
    have_obs = up.lookup_counts > 0
    r_est = jnp.where(have_obs,
                      (1 - scfg.r_ema) * server.r_est + scfg.r_ema * obs_cdf,
                      server.r_est)

    return ServerState(entries=entries, phi_global=phi_global,
                       r_est=r_est, upsilon=server.upsilon)


global_update = partial(jax.jit, static_argnames=("scfg",))(global_update_body)


def _fused_merge(server: ServerState, uploads: ClientUpload,
                 include: jax.Array, gamma: float, mesh):
    """The Pallas merge of a round's uploads, per device shard of the class
    axis when ``mesh`` splits it (Eq. 4/5 are elementwise in I, so every
    device merges its own classes and nothing crosses devices).  JAX refuses
    to lower a Mosaic kernel in a multi-device program outside
    ``shard_map``, so a mesh whose axis does not divide I runs the whole
    merge on every device."""
    from repro.kernels.cache_merge import cache_merge_round
    merge = partial(cache_merge_round, gamma=gamma)
    args = (server.entries, server.phi_global, uploads.u, uploads.phi,
            uploads.u_touched, include)
    if mesh is None:
        return merge(*args)
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import class_axis, fit_spec
    ax = fit_spec(P(class_axis(mesh)), server.phi_global.shape, mesh)[0]
    return jax.shard_map(
        merge, mesh=mesh,
        in_specs=(P(None, ax, None), P(ax), P(None, None, ax, None),
                  P(None, ax), P(None, None, ax), P()),
        out_specs=(P(None, ax, None), P(ax)), check_vma=False)(*args)


def merge_round(server: ServerState, uploads: ClientUpload,
                include: jax.Array, scfg: ServerConfig,
                mesh=None) -> ServerState:
    """Merge one round's stacked uploads (leading K axis) in client order.

    ``include`` — (K,) bool; an excluded client's Eq.-4/5 update is a no-op
    (straggler deadline, fault quarantine).  ``mesh`` — the mesh a
    class-sharded ServerState lives on (:func:`repro.distributed.sharding.
    shard_server_state`); the fused merge then runs per class shard.
    Dispatch per ``scfg.merge_impl``:

    * ``"ref"``   — ``lax.scan`` of :func:`global_update_body` with the
      include gate applied tree-wide: the bit-for-bit oracle, and the only
      path that keeps a class-sharded ServerState collective-free.
    * ``"fused"`` — one Pallas launch for the (L, I, d)/(I,) merge
      (:func:`repro.kernels.cache_merge.cache_merge_round`) plus a tiny
      (L,)-shaped ``jnp`` scan for the R-estimate EMA, op-for-op identical
      to the reference (parity-gated in tests/test_merge_kernel.py).
    * ``"auto"``  — fused on a TPU backend, reference otherwise (interpret-
      mode emulation of the kernel is far slower than XLA on CPU).

    Traceable; ``round_step`` calls it inside the round jit.  Standalone
    callers should use :func:`merge_round_jit` — called eagerly, the fresh
    scan closure would retrace every round.
    """
    impl = scfg.merge_impl
    if impl == "auto":
        impl = "fused" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        def merge(srv, inp):
            up, inc = inp
            new = global_update_body(srv, up, scfg)
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(inc, n, o), new, srv), None
        server, _ = jax.lax.scan(merge, server, (uploads, include))
        return server
    if impl != "fused":
        raise ValueError(f"unknown merge impl: {impl!r}")

    entries, phi_global = _fused_merge(server, uploads, include,
                                       scfg.gamma, mesh)

    # R-estimate EMA: same ops in the same (client) order as the reference.
    def rstep(r, inp):
        phi_k, hits_k, looks_k, inc = inp
        frames = jnp.maximum(phi_k.sum(), 1)
        obs_cdf = jnp.cumsum(hits_k) / frames
        new = jnp.where(looks_k > 0,
                        (1 - scfg.r_ema) * r + scfg.r_ema * obs_cdf, r)
        return jnp.where(inc, new, r), None

    r_est, _ = jax.lax.scan(
        rstep, server.r_est,
        (uploads.phi, uploads.hit_counts, uploads.lookup_counts, include))
    return ServerState(entries=entries, phi_global=phi_global,
                       r_est=r_est, upsilon=server.upsilon)


merge_round_jit = partial(jax.jit,
                          static_argnames=("scfg", "mesh"))(merge_round)


# ---------------------------------------------------------------------------
# Upload admission (the hardened Eq.-4/5 merge front door)
# ---------------------------------------------------------------------------

# Post-normalisation every U row is unit length; anything far above that is a
# transport-corrupted tensor, not a legitimate update.
_U_NORM_BOUND = 1e3


def validate_upload(up: ClientUpload, cfg: CacheConfig | None = None) -> str | None:
    """Admission check for one client upload before the Eq.-4/5 merge.

    An edge server cannot assume the transport delivered what the client
    sent — truncated or bit-flipped uploads must be *rejected*, not absorbed
    into the global cache (a single NaN in ``u`` poisons every later merge of
    that cell).  Returns ``None`` when the upload is admissible, else a short
    reason string:

    * any non-finite value in ``u`` / ``phi`` / the counters,
    * negative ``phi`` or counter entries (counts cannot go backwards),
    * ``u`` rows absurdly far from the client-side L2-normalised scale,
    * a touched cell whose row is all-zero (contradiction: the client claims
      it absorbed there but sent nothing),
    * shape mismatch against ``cfg`` when given.

    Host-side and cheap relative to a merge; the chaos harness
    (:mod:`repro.distributed.faults`) routes every post-round merge through
    this plus :func:`upload_digest` duplicate detection.

    Also accepts a :class:`~repro.core.semantic_cache.CacheTable` (the
    download direction of the same transport): table payloads — including
    quantized int8 tables, whose NaN-poisoned *scales* are just as fatal as
    NaN entries — delegate to :func:`validate_table`.
    """
    if isinstance(up, CacheTable):
        return validate_table(up, cfg)
    u = np.asarray(jax.device_get(up.u))
    phi = np.asarray(jax.device_get(up.phi))
    tau = np.asarray(jax.device_get(up.tau))
    touched = np.asarray(jax.device_get(up.u_touched))
    hits = np.asarray(jax.device_get(up.hit_counts))
    looks = np.asarray(jax.device_get(up.lookup_counts))
    if cfg is not None:
        want = (cfg.num_layers, cfg.num_classes, cfg.sem_dim)
        if u.shape != want:
            return f"u shape {u.shape} != expected {want}"
        if phi.shape != (cfg.num_classes,):
            return f"phi shape {phi.shape} != ({cfg.num_classes},)"
    if not np.isfinite(u).all():
        return "non-finite values in u"
    if not (np.isfinite(phi).all() and np.isfinite(tau).all()):
        return "non-finite status vectors"
    if (phi < 0).any() or (hits < 0).any() or (looks < 0).any():
        return "negative counters"
    norms = np.linalg.norm(u, axis=-1)                       # (L, I)
    if (norms > _U_NORM_BOUND).any():
        return "u rows exceed the normalised-scale bound"
    if (touched & (norms <= 0.0)).any():
        return "touched cells with all-zero rows"
    return None


def validate_table(table: CacheTable,
                   cfg: CacheConfig | None = None) -> str | None:
    """Admission check for a transported cache table (downloads, tier cuts).

    The float32 checks mirror :func:`validate_upload`'s (finiteness, the
    normalised-scale row bound).  Quantized tables need their own rules:
    the int8 payload cannot encode a NaN, so transport corruption surfaces
    in the **bf16 scale plane** instead — a single NaN/Inf (or negative)
    scale poisons every lookup score of that row exactly like a NaN entry
    would, and must be rejected at the same door (the chaos-hardening
    guarantee under ``entry_dtype="int8"``; see tests/test_faults.py).
    Returns ``None`` when admissible, else a short reason string.
    """
    entries = np.asarray(jax.device_get(table.entries))
    if cfg is not None:
        want = (cfg.num_layers, cfg.num_classes, cfg.sem_dim)
        if entries.shape != want:
            return f"entries shape {entries.shape} != expected {want}"
    if table.entry_scale is not None:
        scale = np.asarray(jax.device_get(table.entry_scale),
                           dtype=np.float32)           # (L, I)
        if entries.dtype != np.int8:
            return f"quantized table with {entries.dtype} entries"
        if scale.shape != entries.shape[:2]:
            return (f"entry_scale shape {scale.shape} != "
                    f"{entries.shape[:2]}")
        if not np.isfinite(scale).all():
            return "non-finite entry scales"
        if (scale < 0).any():
            return "negative entry scales"
        # Dequantized row norm bound — same transported-scale rule as u.
        norms = np.linalg.norm(entries.astype(np.float32)
                               * scale[..., None], axis=-1)
        if (norms > _U_NORM_BOUND).any():
            return "dequantized rows exceed the normalised-scale bound"
        return None
    if not np.isfinite(entries).all():
        return "non-finite entries"
    if (np.linalg.norm(entries, axis=-1) > _U_NORM_BOUND).any():
        return "entry rows exceed the normalised-scale bound"
    return None


def upload_digest(up: ClientUpload) -> str:
    """Content digest of an upload — the server's duplicate detector.

    A retried/duplicated transmission of the *same* round upload hashes
    identically; merging it twice would double-count ``phi`` (Eq. 5) and
    re-apply the Eq.-4 EMA, skewing the global frequency view.  The harness
    keeps the recent digests per client and drops repeats.
    """
    h = hashlib.sha256()
    for leaf in up:
        arr = np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _profile_initial_cache_impl(sems: jax.Array, labels: jax.Array,
                                num_classes: int):
    onehot = jax.nn.one_hot(labels, num_classes)                  # (N, I)
    counts = onehot.sum(axis=0)                                   # (I,)
    sums = jnp.einsum("nld,ni->lid", sems, onehot)
    centroids = sums / jnp.maximum(counts[None, :, None], 1.0)
    return l2_normalize(centroids), counts


@functools.lru_cache(maxsize=None)
def _profile_initial_cache_jit(num_classes: int, out_shardings):
    # Cached so repeat bootstraps with the same (I, shardings) reuse the
    # compiled program instead of retracing (shardings are hashable).
    return jax.jit(partial(_profile_initial_cache_impl,
                           num_classes=num_classes),
                   out_shardings=out_shardings)


def profile_initial_cache(sems: jax.Array, labels: jax.Array,
                          num_classes: int,
                          mesh=None) -> tuple[jax.Array, jax.Array]:
    """Server-side bootstrap from a globally shared dataset (§III.3).

    ``sems`` — (N, L, d) taps of the shared calibration set, ``labels`` — (N,).
    Returns (entries (L, I, d), phi (I,)): per-class per-layer centroids and
    observed class counts.

    With ``mesh`` the computation is jitted with class-sharded output
    shardings (:func:`repro.distributed.sharding.server_cache_specs`): the
    centroid einsum contracts over the sample axis N, so GSPMD partitions it
    and each device only ever *produces* its I-slice — the full (L, I, d)
    table is never materialised on one device.
    """
    if mesh is None:
        return _profile_initial_cache_impl(sems, labels, num_classes)
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import fit_spec, server_cache_specs
    L, d = sems.shape[1], sems.shape[2]
    specs = server_cache_specs(mesh)
    out_shardings = (
        NamedSharding(mesh, fit_spec(specs["entries"], (L, num_classes, d),
                                     mesh)),
        NamedSharding(mesh, fit_spec(specs["phi_global"], (num_classes,),
                                     mesh)),
    )
    return _profile_initial_cache_jit(num_classes, out_shardings)(sems, labels)
