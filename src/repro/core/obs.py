"""The program's names for its work, as a profiler sees them (DESIGN.md,
"Observability").

Two kinds of name, both fixed strings kept here and nowhere else:

  * device scopes, entered inside ``jit`` with :func:`scope`:
    ``jax.named_scope`` writes the name into the ``op_name`` metadata of
    every op the phase lowers to, which the device trace carries. It exists
    only while tracing, so it costs nothing at run time and changes no op.
  * host spans, entered on the calling thread with :func:`span`:
    ``jax.profiler.TraceAnnotation`` writes an event on the host planes of
    the open profiler session, on the device trace's clock, so an idle gap
    on the device can be put against what the host was doing. With no
    session open it costs one check.

There is no switch: a profiler session is what turns them on. A new phase
gets a constant here, never an ad-hoc string at the call site.
"""
from __future__ import annotations

import jax

# -- device scopes (inside jit) --------------------------------------------
VARIATION = "popt.variation"   # a policy's proposal: trials, moves, kicks
EVALUATE = "popt.evaluate"     # the evaluator's first pass over a batch
RETRY = "popt.retry"           # the evaluator's retry pass and its wheres
SELECT = "popt.select"         # replacement and incumbent tracking
FUSED = "popt.fused"           # one whole-generation Pallas kernel call
ROUND = "popt.round"           # one sync round: the generation scan + merge
MIGRATE = "popt.migrate"       # ring / starvation / mailbox exchange
SYNC = "popt.sync"             # cross-chip incumbent merge: pmin, all-gathers
POLISH = "popt.polish"         # the memetic local-descent pass

SCOPES = (VARIATION, EVALUATE, RETRY, SELECT, FUSED, ROUND, MIGRATE, SYNC,
          POLISH)

# -- host spans (calling thread) -------------------------------------------
ENGINE_INIT = "popt.engine.init"          # init state, warm start, round keys
ENGINE_DISPATCH = "popt.engine.dispatch"  # enqueue the whole-run program
ENGINE_FETCH = "popt.engine.fetch"        # wait for and copy back its result
ENGINE_STEP = "popt.engine.step"          # one host-stepped bucket round
SCHED_PROGRESS = "popt.sched.progress"    # a round's incumbents to the host
SCHED_RUN = "popt.sched.run"              # one bucket's run on a worker

SPANS = (ENGINE_INIT, ENGINE_DISPATCH, ENGINE_FETCH, ENGINE_STEP,
         SCHED_PROGRESS, SCHED_RUN)


def scope(name: str):
    """Name the ops traced inside the ``with`` block (one of ``SCOPES``)."""
    return jax.named_scope(name)


def span(name: str):
    """Name what the calling thread does inside the ``with`` block (one of
    ``SPANS``)."""
    return jax.profiler.TraceAnnotation(name)
