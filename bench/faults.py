"""Faults planted in the program, to show that the comparison deciding
``correct`` fails a broken timed path.

Each fault is a function ``plant(set_attr)`` that replaces one function of
the program; ``set_attr(obj, name, value)`` is ``setattr`` or pytest's
``monkeypatch.setattr``.  ``bench/tests/test_faults.py`` drives whole runs at
test size on the CPU with each planted; ``bench/control.py --fault <name>``
reads them on the chip at a cell's own size.

``FAULTS`` maps a name to (the driver kind it applies to, the planter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def serve_altered_answer(set_attr) -> None:
    """The tick's lookup answers with the next class and always hits at
    the first layer."""
    from repro.serving import loop
    orig = loop._batched_lookup

    def altered(table, sems, cfg):
        look = orig(table, sems, cfg)
        return look._replace(
            hit=jnp.ones_like(look.hit),
            exit_layer=jnp.zeros_like(look.exit_layer),
            pred=(look.pred + 1) % cfg.num_classes)

    set_attr(loop, "_batched_lookup", altered)


def serve_half_batch(set_attr) -> None:
    """The backbone computes only the even rows of each batch; every odd
    row repeats its even neighbour's result."""
    from repro import models
    orig = models.prefill

    def half(params, batch, cfg, *a, **k):
        keep = jax.tree.map(lambda x: x[0::2], batch)
        logits, caches, taps, cls = orig(params, keep, cfg, *a, **k)
        return (logits, caches, jnp.repeat(taps, 2, axis=0),
                jnp.repeat(cls, 2, axis=0))

    set_attr(models, "prefill", half)


def serve_profile_shifted(set_attr) -> None:
    """The bootstrap's profiling replay records every first hit one layer
    later than it happened, so R, and through it the layers ACA caches,
    is wrong."""
    from repro.core import engine
    boot, look_fn = engine.bootstrap_server_from_taps, engine.lookup_all_layers

    def shifted(table, sems, cfg, *a, **k):
        look = look_fn(table, sems, cfg, *a, **k)
        return look._replace(exit_layer=jnp.minimum(look.exit_layer + 1,
                                                    cfg.num_layers))

    def bootstrap(*a, **k):
        engine.lookup_all_layers = shifted
        try:
            return boot(*a, **k)
        finally:
            engine.lookup_all_layers = look_fn

    set_attr(engine, "bootstrap_server_from_taps", bootstrap)


def _patch_round(set_attr, edit) -> None:
    from repro.core import engine
    orig = engine.round_step

    def patched(states, tables, sems, logits, server, **kw):
        return edit(orig, states, tables, sems, logits, server, **kw)

    set_attr(engine, "round_step", patched)


def rounds_altered_answer(set_attr) -> None:
    """Every served prediction of the round is the next class."""
    def edit(orig, *args, **kw):
        states, server, m = orig(*args, **kw)
        m = dict(m, pred=(m["pred"] + 1) % kw["cfg"].num_classes)
        return states, server, m

    _patch_round(set_attr, edit)


def rounds_state_unchanged(set_attr) -> None:
    """The round returns the server it was given."""
    def edit(orig, states, tables, sems, logits, server, **kw):
        new_states, _, m = orig(states, tables, sems, logits, server, **kw)
        return new_states, server, m

    _patch_round(set_attr, edit)


def rounds_half_batch(set_attr) -> None:
    """Only the first half of the clients' uploads reach the merge."""
    def edit(orig, *args, **kw):
        K = args[2].shape[0]
        mask = jnp.arange(K) < K // 2
        return orig(*args, **dict(kw, upload_mask=mask))

    _patch_round(set_attr, edit)


FAULTS = {
    "serve_altered_answer": ("serve", serve_altered_answer),
    "serve_half_batch": ("serve", serve_half_batch),
    "serve_profile_shifted": ("serve", serve_profile_shifted),
    "rounds_altered_answer": ("rounds", rounds_altered_answer),
    "rounds_state_unchanged": ("rounds", rounds_state_unchanged),
    "rounds_half_batch": ("rounds", rounds_half_batch),
}
