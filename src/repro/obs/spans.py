"""Names of the serving engine's program spans.

:class:`~repro.serving.engine.ContinuousEngine` opens a
``jax.profiler.TraceAnnotation`` with one of these names at each layer
boundary of a tick.  They are recorded only while a ``jax.profiler`` trace
runs (``serve --profile-dir``), into the same ``.xplane.pb`` as the
device's operations and on the same clock, so a device-idle gap can be put
down to the host phase that covers it.  The parent of a span is the span
that caused it::

    repro.tick          one ContinuousEngine.step()
      repro.admit       deadline expiry, the first release flush, admission
                        and the sampling-lane writes
      repro.refreeze    a fold: the full-slot decision, page ids and the
                        refreeze dispatch (and the rare pipeline drain)
      repro.prefill     one prefill chunk's dispatch; the final chunk's
                        first-token sync too
      repro.decode      the decode or draft-verify dispatch
      repro.sync        the designated sync and the commit of a token window
      repro.release     a batched release flush

``repro.sync`` and ``repro.release`` nest under whichever span caused them
(the drain of a fold, the first flush of admission), and stand alone when
``quiesce`` runs between ticks.  This module only names them: ``repro.obs``
imports neither jax nor numpy.
"""
from __future__ import annotations

PREFIX = "repro."
TICK = "repro.tick"
ADMIT = "repro.admit"
REFREEZE = "repro.refreeze"
PREFILL = "repro.prefill"
DECODE = "repro.decode"
SYNC = "repro.sync"
RELEASE = "repro.release"

ALL = (TICK, ADMIT, REFREEZE, PREFILL, DECODE, SYNC, RELEASE)
