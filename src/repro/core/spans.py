"""The program's spans, on the profiler's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: it records nothing
unless a profiler trace is running (``jax.profiler.start_trace``), and
costs about a microsecond and a half when none is.  So the spans are
always in place, with no switch.  A trace reader finds them on the host
planes by name, and their arguments among the event's stats.

* ``repro.submit`` — ``MultiModelEngine.submit``; ``rid``, ``tenant``.
* ``repro.step`` — ``MultiModelEngine.step``, round composition
  included; ``active`` (the tenants with queued work).
* ``repro.wave`` — each wave of a step (``_dispatch_wave``); ``rids``
  (the requests it served), ``occupancy``, ``analytic_us``.
* ``repro.plan`` — the wave's plan lookup (``_resolve_plan``); ``hit``.
* ``repro.execute`` — the executor call of a wave (one per
  ``execute_plan`` on a floor round); ``requests``.
* ``repro.kernel`` — each kernel node run by ``execute_plan`` /
  ``execute_multi_plan``; ``tenant``, ``supernode``, ``resource`` (the
  SoC unit the plan put it on), ``analytic_cycles``.

They nest ``step ⊃ wave ⊃ {plan, execute ⊃ kernel}`` on the thread that
steps the engine.  A list of ids (``rids``, ``active``) is joined with
:data:`SEP`: the profiler's argument encoding takes ``,``, ``=`` and
``#`` for its own.  ``analytic_us`` is the wave's cost at the SoC clock
and ``analytic_cycles`` the kernel node's planned duration: the schedule
model's prediction beside the measured span.  ``tenant`` of a kernel is
its model's graph name, the same in every plan the model runs in.
"""

from __future__ import annotations

from typing import Iterable

from jax.profiler import TraceAnnotation

SUBMIT = "repro.submit"
STEP = "repro.step"
WAVE = "repro.wave"
PLAN = "repro.plan"
EXECUTE = "repro.execute"
KERNEL = "repro.kernel"

SEP = ";"

# ``span(name, **args)`` as a context manager; ``set_metadata(**more)`` on
# it adds arguments known only once its work is done.
span = TraceAnnotation


def joined(values: Iterable[int]) -> str:
    """``values`` joined into one span argument."""
    return SEP.join(str(v) for v in values)
