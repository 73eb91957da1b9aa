"""Named host spans at the layer boundaries of the served round.

Each span is a ``jax.profiler.TraceAnnotation``: with the profiler off it
costs about a microsecond and records nothing; under
``jax.profiler.trace`` it lands in the same trace as the device's
operations, on the same clock, with its counts as numeric event stats.
Counts known when the span opens go to :func:`span`; counts known only at
its end go to the returned object's ``set_metadata(**counts)``.

Spans go on host code only, never inside a jitted function (a trace-time
span would time tracing, not the call).  Nesting, outermost first::

    serve.tick > serve.admit | fleet.round | serve.finalize
    fleet.round > fleet.lb | fleet.gather | fleet.evaluate | fleet.resume
    fleet.evaluate > dispatch.pack | dispatch.pad | dispatch.launch
                     | dispatch.fetch | dispatch.unpack
"""

from __future__ import annotations

import jax

#: ``ServeEngine.tick``, the whole beat (``admitted``, ``inflight``)
SERVE_TICK = "serve.tick"
#: one request's admission: plan priming, shard groups, LB hook (``rid``,
#: ``snapshot_builds``: the plan snapshots it built, 0 once cached)
SERVE_ADMIT = "serve.admit"
#: one finished request: gid mapping and its hit set (``rid``, ``hits``)
SERVE_FINALIZE = "serve.finalize"
#: ``FleetBatchEngine.step``, one merged round (``parts``, ``rows``)
FLEET_ROUND = "fleet.round"
#: the envelope screen of the round's VERDICT parts (``lb_rows``,
#: ``lb_pruned``)
FLEET_LB = "fleet.lb"
#: repeat, gather and concatenate the round's surviving rows (``rows``)
FLEET_GATHER = "fleet.gather"
#: the round's one evaluator call
FLEET_EVALUATE = "fleet.evaluate"
#: results sent into the plans, finished batches retired (``finished``)
FLEET_RESUME = "fleet.resume"
#: ``packed_batch``'s lengths, bucket sort and reorder (``rows``,
#: ``buckets``)
DISPATCH_PACK = "dispatch.pack"
#: ``KernelSpec.batch``'s trim, power-of-two row padding and choice of
#: execution mode and tile (``rows``, ``padded_rows``, ``cells``: the sum
#: of each requested row's len_x * len_y, ``padded_cells``)
DISPATCH_PAD = "dispatch.pad"
#: the jit-cache lookup and the jitted call: host-to-device copies and the
#: launch (``h2d_bytes``)
DISPATCH_LAUNCH = "dispatch.launch"
#: waiting for the outputs and copying them back (``d2h_bytes``)
DISPATCH_FETCH = "dispatch.fetch"
#: ``packed_batch``'s inverse permutation back to the caller's order
DISPATCH_UNPACK = "dispatch.unpack"

NAMES = (SERVE_TICK, SERVE_ADMIT, SERVE_FINALIZE, FLEET_ROUND, FLEET_LB,
         FLEET_GATHER, FLEET_EVALUATE, FLEET_RESUME, DISPATCH_PACK,
         DISPATCH_PAD, DISPATCH_LAUNCH, DISPATCH_FETCH, DISPATCH_UNPACK)


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` carrying ``counts`` (numbers)."""
    return jax.profiler.TraceAnnotation(name, **counts)
