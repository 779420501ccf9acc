"""Host spans at the layer boundaries of the serving tick and the round.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace runs,
it lands in that trace on the clock of the device events, with its integer
counters attached as the event's stats (``ProfileData`` hands them back
under the span's own, unchanged name); with no trace running it costs one
native call.  The profiler is the one sink: nothing is stored here.

Counters are integers computed from values already on the host, so a span
never waits on the device.

Spans and their counters:

``ServingSession.tick`` (:mod:`repro.serving.loop`)

* ``coca.tick`` — the whole tick; ``tick``: the scheduler's block-tick.
* ``coca.tick.admit`` — EDF admission and shedding (``sched.admit()``).
* ``coca.tick.classify`` — the admitted batch's classification, on an
  admitting tick only; ``rows``: requests in the batch; ``wait_us_sum``,
  ``wait_us_max``: host µs from each admitted request's ``submit`` to its
  admission.
* ``coca.tick.backbone`` — the ``tap_fn`` call (the backbone's dispatch).
* ``coca.tick.lookup`` — padding the batch and dispatching the lookup.
* ``coca.tick.sync`` — the tick's one ``jax.device_get`` (cache and
  no-cache paths alike).
* ``coca.tick.retire`` — resolving slots, recency, ``sched.advance()`` and
  the retirements' bookkeeping.

``CocaCluster.step`` (:mod:`repro.core.engine`)

* ``coca.round`` — the whole round; ``round``: the round index.
* ``coca.round.aca`` — one client's allocation by the policy (numpy ACA);
  ``client``: its slot.
* ``coca.round.cut`` — the one compiled cut of every active client's table
  from the stacked allocations; ``clients``: their count K.
* ``coca.round.stack`` — the one compiled stack of the clients' taps and
  logits, and of their tables when the caller cut them.
* ``coca.round.dispatch`` — the fused ``round_step`` call.
* ``coca.round.sync`` — the round's one bundled ``jax.device_get``.

``coca.round.aca`` and ``.cut`` come from the allocation, ``step``'s own
or ``allocate_tables``: they sit inside ``coca.round`` when ``step``
allocates, and just before it, outside any round, when the caller cuts
the tables itself and passes them in (``step(tables=...)``, as the fault
and topology layers do).
``coca.round.stack``, ``.dispatch`` and ``.sync`` belong to the vectorised
round; the per-client reference path and the client-engine baselines sync
once per client and carry none of them.

Self time of a tick or a round is its span less its ``*.sync`` child.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **counters: int) -> TraceAnnotation:
    """A host span named ``name`` (``coca.`` and the layer) carrying the
    integer ``counters``; use as a context manager."""
    return TraceAnnotation(name, **counters)
